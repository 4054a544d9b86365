"""Sequence/context parallelism: ring attention + distributed scan.

NEW capability relative to the reference (SURVEY.md §5.7: the 2015 codebase
predates attention; its only sequence-length device is truncated BPTT).
Mandated first-class here: shard the TIME axis of long sequences over the
mesh's ``sp`` axis and exchange only boundary state over ICI.

Two primitives:

- :func:`ring_attention` — blockwise causal attention with the K/V block
  rotating around the ring via ``lax.ppermute`` (one neighbor hop per
  step, riding ICI), with online-softmax accumulation so no device ever
  materializes the full [T, T] score matrix: O(T/P) memory per device,
  compute overlapped with the rotation by XLA's async collective
  scheduling. This is the Liu et al. ring-attention schedule expressed as
  pure shard_map code.

- :func:`sp_scan` — chunked recurrent scan: each device scans its local
  time chunk, then the carry hops to the next device via ppermute; P
  devices process a T-step sequence with O(T/P) activation memory (the
  tBPTT memory story, but distributed and exact).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _online_softmax_block(q, k, v, m_prev, l_prev, o_prev, mask):
    """One blockwise-attention accumulation step (flash-attention style).

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; mask: additive (0 / -inf),
    broadcastable to [B, H, Tq, Tk]; m/l/o are the running max,
    normalizer, and output.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = scores + mask
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
    # Guard fully-masked rows (max = -inf) against NaNs.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    scale = jnp.where(
        jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
    )
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    o_new = o_prev * scale[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v
    )
    return m_new, l_new, o_new


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    axis_name: str = "sp",
    causal: bool = True,
    key_mask: Optional[Array] = None,
    block_size: Optional[int] = None,
) -> Array:
    """Blockwise ring attention INSIDE shard_map.

    q/k/v: the LOCAL time shard [B, H, T_local, D] on each device of the
    ``axis_name`` ring. Returns the local output shard [B, H, T_local, D].
    Device i owns query block i; K/V blocks rotate around the ring so each
    device sees every K/V block once, accumulating via online softmax.

    ``key_mask`` [B, T_local] (1 = valid) marks padded timesteps of the
    LOCAL key block; it rotates around the ring with its K/V block so
    padded keys are excluded from every device's softmax.

    ``block_size``: sub-chunk the VISITING K/V block through the same
    online softmax (the Liu et al. blockwise computation), bounding the
    score buffer at [B, H, T_local, block_size] instead of
    [B, H, T_local, T_local] — the memory lever that lets a device hold
    a long T_local shard without materializing its full block-pair
    score matrix. None = whole block at once (exact same math either
    way; tests assert equality).
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t, d = q.shape
    bs = t if block_size is None else min(block_size, t)
    if bs < 1:
        raise ValueError(f"block_size must be positive, got {block_size}")
    if t % bs:
        raise ValueError(
            f"block_size {bs} must divide the local shard length {t}")
    n_sub = t // bs

    m0 = jnp.full((b, h, t), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, t), q.dtype)
    o0 = jnp.zeros_like(q)

    q_pos = idx * t + jnp.arange(t)  # global positions of local queries
    km = (
        jnp.ones((b, t), q.dtype) if key_mask is None
        else key_mask.astype(q.dtype)
    )

    def body(step, carry):
        kv, m, l, o = carry
        k_blk, v_blk, km_blk = kv
        # Which global block is visiting this device at this step?
        src_block = (idx + step) % n

        def sub_body(s, mlo):
            m, l, o = mlo
            k_sub = lax.dynamic_slice_in_dim(k_blk, s * bs, bs, 2)
            v_sub = lax.dynamic_slice_in_dim(v_blk, s * bs, bs, 2)
            km_sub = lax.dynamic_slice_in_dim(km_blk, s * bs, bs, 1)
            k_pos = src_block * t + s * bs + jnp.arange(bs)
            if causal:
                mask = jnp.where(
                    q_pos[:, None] >= k_pos[None, :], 0.0, -jnp.inf
                ).astype(q.dtype)
            else:
                mask = jnp.zeros((t, bs), q.dtype)
            # Padded keys of the visiting sub-block: -inf everywhere.
            mask = mask[None, None] + jnp.where(
                km_sub > 0, 0.0, -jnp.inf
            ).astype(q.dtype)[:, None, None, :]
            return _online_softmax_block(
                q, k_sub, v_sub, m, l, o, mask)

        if n_sub == 1:
            m, l, o = sub_body(0, (m, l, o))
        else:
            # Rematerialize each sub-block in the backward pass: without
            # this, the scan-lowered loop SAVES every sub-block's
            # [B, H, T_local, bs] probability matrix as a VJP residual,
            # stacking right back to the full [T_local, T_local] the
            # chunking exists to avoid. With remat, the backward
            # recomputes each sub-block's scores from the (small) q/k/v
            # slices — bounded memory in training too, at ~1 extra
            # forward of compute (the flash-attention trade).
            m, l, o = lax.fori_loop(
                0, n_sub, jax.checkpoint(sub_body), (m, l, o))
        # Rotate K/V (+ their mask) to the next device (neighbor hop
        # over ICI).
        perm = [(i, (i - 1) % n) for i in range(n)]
        kv = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm),
            (k_blk, v_blk, km_blk),
        )
        return kv, m, l, o

    _, m, l, o = lax.fori_loop(
        0, n, body, ((k, v, km), m0, l0, o0)
    )
    return o / jnp.maximum(l[..., None], 1e-20)


def ulysses_attention(
    q: Array,
    k: Array,
    v: Array,
    axis_name: str = "sp",
    causal: bool = True,
    key_mask: Optional[Array] = None,
) -> Array:
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism INSIDE
    shard_map — the OTHER standard SP scheme next to :func:`ring_attention`.

    q/k/v: the LOCAL time shard [B, H, T_local, D]. Two ``all_to_all``
    collectives swap the sharded axis: heads scatter over the ring while
    the time axis gathers, so each device runs ordinary FULL-sequence
    attention on H/P of the heads, then the output swaps back to
    time-sharded. Communication is two all-to-alls of activations —
    q/k/v stacked into ONE scatter collective plus one return swap
    (vs P-1 K/V ppermute hops for the ring); the full [T, T] score
    matrix of the local heads IS materialized, so Ulysses trades ring's
    O(T_local) score memory for fewer, larger collectives — the right
    choice when T fits on-device and the head count divides the ring.

    ``key_mask`` [B, T_local]: all-gathered over the ring so padded
    keys are excluded from the full-sequence softmax.
    """
    n = axis_size(axis_name)
    b, h, t, d = q.shape
    if h % n:
        raise ValueError(
            f"ulysses needs n_heads ({h} local) divisible by the "
            f"{axis_name} axis size {n}; use ring attention otherwise")

    # ONE scatter collective for all three: [3, B, H, T_local, D] ->
    # [3, B, H/P, T_global, D]
    qkv = lax.all_to_all(
        jnp.stack([q, k, v]), axis_name,
        split_axis=2, concat_axis=3, tiled=True)
    qg, kg, vg = qkv[0], qkv[1], qkv[2]
    mask_full = (
        None if key_mask is None
        else lax.all_gather(
            key_mask, axis_name, axis=1, tiled=True)  # [B, T_global]
    )
    tg = qg.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", qg, kg) / jnp.sqrt(
        jnp.asarray(d, qg.dtype))
    neg = jnp.asarray(-jnp.inf, qg.dtype)
    if causal:
        cm = jnp.tril(jnp.ones((tg, tg), bool))
        scores = jnp.where(cm[None, None], scores, neg)
    if mask_full is not None:
        scores = jnp.where(
            mask_full[:, None, None, :] > 0, scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    # Guard fully-masked query rows (softmax of all -inf) against NaN.
    if mask_full is not None:
        w = jnp.where(jnp.isfinite(scores).any(-1, keepdims=True), w, 0.0)
    og = jnp.einsum("bhqk,bhkd->bhqd", w, vg)
    # [B, H/P, T_global, D] -> [B, H, T_local, D]
    return lax.all_to_all(
        og, axis_name, split_axis=2, concat_axis=1, tiled=True)


def make_ring_attention(
    mesh: Mesh, axis_name: str = "sp", causal: bool = True,
    masked: bool = False, block_size: Optional[int] = None,
):
    """shard_map-wrapped ring attention over global [B, H, T, D] arrays
    time-sharded on ``axis_name``. With ``masked=True`` the returned fn
    takes a fourth [B, T] key-validity mask (also time-sharded)."""
    spec = P(None, None, axis_name, None)
    if masked:
        fn = lambda q, k, v, m: ring_attention(  # noqa: E731
            q, k, v, axis_name, causal=causal, key_mask=m,
            block_size=block_size,
        )
        in_specs = (spec, spec, spec, P(None, axis_name))
    else:
        fn = functools.partial(
            ring_attention, axis_name=axis_name, causal=causal,
            block_size=block_size,
        )
        in_specs = (spec, spec, spec)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )


def sp_scan(
    step_fn: Callable,
    carry_init,
    xs_local: Array,
    axis_name: str = "sp",
):
    """Distributed sequential scan over a time-sharded sequence.

    Each device holds xs_local [T_local, ...]. Device 0 scans its chunk
    from ``carry_init``, hands its final carry to device 1 via ppermute,
    and so on. The ring is inherently sequential — wall-clock is the
    serial scan plus n carry hops — the win is O(T/P) activation memory
    per device, the SP analogue of tBPTT windows (reference
    doTruncatedBPTT :1262) without gradient truncation.

    Returns (final_carry_on_every_device, ys_local).
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)

    def body(dev, state):
        carry, ys = state
        # Only the active device runs its chunk's scan this round: the
        # lax.cond lowers to an XLA conditional, so inactive devices sit
        # at the ppermute instead of redundantly recomputing the same
        # scan n times (round-1 review weak #4).
        active = idx == dev

        def do_scan(c):
            return lax.scan(step_fn, c, xs_local)

        def skip(c):
            return c, ys

        carry_out, ys = lax.cond(active, do_scan, skip, carry)
        # Hand the carry to the next device in the ring.
        perm = [(i, (i + 1) % n) for i in range(n)]
        carry_next = jax.tree.map(
            lambda c: lax.ppermute(c, axis_name, perm), carry_out
        )
        # Devices beyond the active one adopt the received carry; the
        # final iteration leaves every device with the global carry.
        carry = jax.tree.map(
            lambda recv, cur: jnp.where(idx == dev + 1, recv, cur),
            carry_next,
            carry_out,
        )
        return carry, ys

    ys0 = jax.eval_shape(
        lambda: lax.scan(step_fn, carry_init, xs_local)[1]
    )
    ys_init = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), ys0
    )
    carry, ys = lax.fori_loop(0, n, body, (carry_init, ys_init))
    # After the loop the LAST device holds the global final carry;
    # broadcast it to the whole ring.
    carry = jax.tree.map(
        lambda c: lax.psum(
            jnp.where(idx == n - 1, c, jnp.zeros_like(c)), axis_name
        ),
        carry,
    )
    return carry, ys
