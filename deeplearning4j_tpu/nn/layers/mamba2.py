"""Mamba-2 mixer: causal depthwise convolution, selective state-space
recurrence, gated RMSNorm (Dao & Gu 2024; the form HF's
``GraniteMoeHybridMambaLayer`` / ``BambaMixer`` computes).

For one row, with ``H`` heads of ``P`` channels, ``G`` groups and a state
of ``N`` per channel::

    [z | xBC | dt] = h W_in                      (d_inner | d_inner + 2 G N | H)
    xBC = silu(causal_depthwise_conv_K(xBC) + b)
    [x | B | C] = xBC                            (d_inner | G N | G N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (per head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               (S: P x N a head)
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z)) * w  W_out                    (norm over a group)

Two programs compute the recurrence (the tests keep a third, one
position a ``lax.scan`` step, as their oracle), both accumulating in
float32 and carrying ``S`` in float32:

- :func:`ssm_chunk_scan` (prefill): chunks of ``chunk`` positions,
  products within a chunk and the state carried between chunks.
- :func:`ssm_step` (decode): one position for a batch of rows, on the
  TPU a Pallas kernel that updates the state in place and visits live
  rows only.

**The carried state** of a row is the last ``K - 1`` convolution inputs
(``conv`` ``[B, K - 1, C]``) and ``S``. ``S`` is carried *packed*
(``ssm`` ``[B, H, N / r, r P]`` float32, ``r = 128 / P`` where that
divides): a head's ``[P, N]`` state with ``r`` of its ``N`` columns side
by side in one row of ``r P`` lanes, so that a 64-channel head fills
128-lane registers and the per-channel factors of the update broadcast
along sublanes. :func:`pack_state` / :func:`unpack_state` convert.

**A masked position leaves the state as it was**: ``dt`` is forced to 0
there (``exp(0 A) = 1`` and ``0 x B = 0``) and the convolution's tail is
taken from the last *valid* inputs by each row's length. That is what
lets a right-padded, bucketed prompt prefill to exactly the state of
the unpadded prompt (serving/engine.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.profiler.scopes import scope

_HI = jax.lax.Precision.HIGHEST
#: heads one grid step of the decode kernel updates
_STEP_HEAD_BLOCK = 64
_STEP_VMEM_LIMIT = 48 * 1024 * 1024


# ---------------------------------------------------------------------
# the packed state layout
# ---------------------------------------------------------------------
def pack_factor(d_head: int, d_state: int) -> int:
    """How many of a head's ``N`` state columns share one row of lanes."""
    r = max(1, 128 // d_head)
    return r if d_state % r == 0 else 1


def pack_state(s):
    """``[B, H, P, N]`` -> ``[B, H, N / r, r P]``: entry ``[i, j P + p]``
    is ``S[p, i r + j]``."""
    b, h, p, n = s.shape
    r = pack_factor(p, n)
    s = s.reshape(b, h, p, n // r, r)
    return jnp.transpose(s, (0, 1, 3, 4, 2)).reshape(b, h, n // r, r * p)


def unpack_state(sp, d_head: int):
    """The inverse of :func:`pack_state`."""
    b, h, rows, lanes = sp.shape
    r = lanes // d_head
    s = sp.reshape(b, h, rows, r, d_head)
    return jnp.transpose(s, (0, 1, 4, 2, 3)).reshape(
        b, h, d_head, rows * r)


def _pack_columns(v, d_head: int, r: int):
    """A per-state-column vector ``[..., N]`` laid out as one packed
    tile ``[..., N / r, r P]`` (each entry repeated over its ``P``
    lanes)."""
    n = v.shape[-1]
    v = v.reshape(v.shape[:-1] + (n // r, r, 1))
    v = jnp.broadcast_to(v, v.shape[:-1] + (d_head,))
    return v.reshape(v.shape[:-3] + (n // r, r * d_head))


# ---------------------------------------------------------------------
# the convolution
# ---------------------------------------------------------------------
def conv_taps(x, tail, w, lengths=None):
    """Depthwise causal convolution of ``x`` ``[B, T, C]`` continuing
    ``tail`` ``[B, K - 1, C]`` (the inputs before it; None = zeros).
    ``w`` is ``[K, C]`` with ``w[K - 1]`` on the current input. Returns
    ``(the taps' sum [B, T, C] float32, new tail)``; the new tail holds
    the last ``K - 1`` inputs before each row's ``lengths`` (None =
    ``T``), so right-padding never enters it."""
    bsz, t, c = x.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((bsz, k - 1, c), x.dtype)
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    acc = sum(seq[:, j:j + t, :].astype(jnp.float32)
              * w[j].astype(jnp.float32) for j in range(k))
    if lengths is None:
        new_tail = seq[:, t:, :]
    else:
        at = lengths[:, None] + jnp.arange(k - 1)[None, :]
        new_tail = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    return acc, new_tail


def causal_conv(xbc, tail, w, b, lengths=None):
    """``silu(conv_taps(xbc) + b)`` at ``xbc``'s dtype (``b`` ``[C]``)
    and the new tail: Mamba-2's convolution."""
    acc, new_tail = conv_taps(xbc, tail, w, lengths)
    out = jax.nn.silu(acc + b.astype(jnp.float32)).astype(xbc.dtype)
    return out, new_tail


# ---------------------------------------------------------------------
# the recurrence: chunked (prefill)
# ---------------------------------------------------------------------
def _grouped(x, dt, bm, cm, s0):
    """Split the head axis into (group, head of the group)."""
    bsz, t, h, p = x.shape
    g = bm.shape[2]
    return (x.reshape(bsz, t, g, h // g, p), dt.reshape(bsz, t, g, h // g),
            s0.reshape(bsz, g, h // g, p, s0.shape[-1]))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssm_chunk_scan(x, dt, a, bm, cm, d_skip, s0, *, chunk: int):
    """The chunked form, jitted under its own name so that the program
    that holds it shows one ``_ssm_chunk_scan`` scope."""
    bsz, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    span = min(chunk, t)
    pad = -t % span
    if pad:   # dt = 0 at the padding: the state passes through
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (t + pad) // span
    xg, dtg, sg = _grouped(x.astype(jnp.float32), dt.astype(jnp.float32),
                           bm, cm, s0.astype(jnp.float32))
    ag = a.astype(jnp.float32).reshape(g, h // g)

    def chunks(v):     # [B, nc * L, ...] -> [nc, B, L, ...]
        return jnp.moveaxis(
            v.reshape((bsz, nc, span) + v.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((span, span), bool))

    def one_chunk(s, inp):
        xc, dtc, bc, cc = inp      # [B,L,G,Hg,P] [B,L,G,Hg] [B,L,G,N] x2
        cs = jnp.cumsum(dtc * ag, axis=1)               # [B,L,G,Hg], <= 0
        dtx = dtc[..., None] * xc
        gram = jnp.einsum("blgn,bsgn->bgls", cc, bc, precision=_HI)
        seg = (jnp.moveaxis(cs, 1, -1)[..., :, None]
               - jnp.moveaxis(cs, 1, -1)[..., None, :])  # [B,G,Hg,L,S]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        y = jnp.einsum("bghls,bsghp->blghp",
                       gram[:, :, None] * decay, dtx, precision=_HI)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "blgn,bghpn->blghp", cc, s, precision=_HI)
        to_end = jnp.exp(cs[:, -1:] - cs)               # [B,L,G,Hg]
        s = (jnp.exp(cs[:, -1])[..., None, None] * s
             + jnp.einsum("bsghp,bsgn->bghpn", dtx * to_end[..., None],
                          bc, precision=_HI))
        return s, y

    s_t, ys = jax.lax.scan(
        one_chunk, sg,
        (chunks(xg), chunks(dtg), chunks(bm.astype(jnp.float32)),
         chunks(cm.astype(jnp.float32))))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, t + pad, h, p)[:, :t]
    y = y + d_skip.astype(jnp.float32)[:, None] * x[:, :t].astype(
        jnp.float32)
    return y, s_t.reshape(bsz, h, p, n)


def ssm_chunk_scan(x, dt, a, bm, cm, d_skip, s0, chunk: int):
    """The recurrence ``chunk`` positions at a time. ``x`` ``[B, T, H,
    P]``, ``dt`` ``[B, T, H]`` (after softplus, 0 at masked positions),
    ``a`` ``[H]`` (negative), ``bm``/``cm`` ``[B, T, G, N]``, ``d_skip``
    ``[H]``, ``s0`` ``[B, H, P, N]``; any ``T`` (the tail is padded with
    ``dt = 0``). Returns ``(y [B, T, H, P] float32, S_T float32)``."""
    return _ssm_chunk_scan(x, dt, a, bm, cm, d_skip, s0, chunk=int(chunk))


# ---------------------------------------------------------------------
# the recurrence: one step (decode)
# ---------------------------------------------------------------------
def _step_operands(x, dt, a, bm, cm, d_head: int, r: int):
    """The update's factors in the packed layout: the decay and the
    input term a head ``[B, H, r P]``, ``B`` and ``C`` a group
    ``[B, G, N / r, r P]``."""
    decay = jnp.exp(dt * a.astype(jnp.float32))                # [B, H]
    a2 = jnp.broadcast_to(decay[..., None], decay.shape + (r * d_head,))
    u2 = jnp.tile(dt[..., None] * x, (1, 1, r))                # [B,H,rP]
    return (a2, u2, _pack_columns(bm, d_head, r),
            _pack_columns(cm, d_head, r))


def _ssm_step_plain(sp, a2, u2, bmat, cmat):
    bsz, h, rows, lanes = sp.shape
    g = bmat.shape[1]
    s = sp.reshape(bsz, g, h // g, rows, lanes)
    s = (a2.reshape(bsz, g, h // g, 1, lanes) * s
         + u2.reshape(bsz, g, h // g, 1, lanes) * bmat[:, :, None])
    y2 = jnp.sum(s * cmat[:, :, None], axis=3)
    return s.reshape(sp.shape), y2.reshape(bsz, h, lanes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_update(sp, a2, u2, bmat, cmat, live, *,
                     interpret: bool = False):
    """The one-step state update as a Pallas kernel (jitted on its own:
    one trace for every layer, and the label ``_ssm_step_update`` in the
    device trace). One grid step is one (row, block of heads): the
    packed state block comes in, ``S = decay S + u B`` is written back
    in place (the state operand is aliased to the output), and the
    column sums of ``S C`` go out. Rows are visited live ones first;
    a dead row's steps all point at the last live block, so nothing of
    a dead row is copied either way and its state stays as it was."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, h, rows, lanes = sp.shape
    g = bmat.shape[1]
    hb = min(_STEP_HEAD_BLOCK, h // g)
    while (h // g) % hb:
        hb -= 1
    nj = h // hb
    alive = live.astype(jnp.int32) > 0
    # live rows first (in slot order), then the last live row repeated
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(
        jnp.int32)
    n_live = jnp.maximum(jnp.sum(alive.astype(jnp.int32)), 1)
    last = order[n_live - 1]
    order = jnp.where(jnp.arange(bsz) < n_live, order, last)

    def at(i, j, order_ref, n_ref):
        on = i < n_ref[0]
        return order_ref[i], jnp.where(on, j, nj - 1)

    def head_map(i, j, order_ref, n_ref):
        row, jj = at(i, j, order_ref, n_ref)
        return row, jj, 0

    def group_map(i, j, order_ref, n_ref):
        row, jj = at(i, j, order_ref, n_ref)
        return row, (jj * hb) // (h // g), 0, 0

    def state_map(i, j, order_ref, n_ref):
        row, jj = at(i, j, order_ref, n_ref)
        return row, jj, 0, 0

    def kernel(order_ref, n_ref, a_ref, u_ref, b_ref, c_ref, s_ref,
               so_ref, y_ref):
        @pl.when(pl.program_id(0) < n_ref[0])
        def _update():
            bt, ct = b_ref[0, 0], c_ref[0, 0]

            def head(k, carry):
                s = (a_ref[0, pl.ds(k, 1), :] * s_ref[0, k]
                     + u_ref[0, pl.ds(k, 1), :] * bt)
                so_ref[0, k] = s
                y_ref[0, pl.ds(k, 1), :] = jnp.sum(
                    s * ct, axis=0, keepdims=True)
                return carry

            jax.lax.fori_loop(0, hb, head, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bsz, nj),
        in_specs=[pl.BlockSpec((1, hb, lanes), head_map),
                  pl.BlockSpec((1, hb, lanes), head_map),
                  pl.BlockSpec((1, 1, rows, lanes), group_map),
                  pl.BlockSpec((1, 1, rows, lanes), group_map),
                  pl.BlockSpec((1, hb, rows, lanes), state_map)],
        out_specs=[pl.BlockSpec((1, hb, rows, lanes), state_map),
                   pl.BlockSpec((1, hb, lanes), head_map)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(sp.shape, sp.dtype),
                   jax.ShapeDtypeStruct((bsz, h, lanes), jnp.float32)],
        # operand 6 counts the two scalar-prefetch operands
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_LIMIT),
        interpret=interpret,
    )(order, n_live[None], a2, u2, bmat, cmat, sp)


def use_step_kernel(toggle) -> bool:
    """The block's ``use_kernels``: None = the kernel on a TPU and the
    plain program elsewhere, True / ``"interpret"`` force it."""
    if toggle is None:
        return jax.default_backend() == "tpu"
    return bool(toggle)


def ssm_step(sp, x, dt, a, bm, cm, d_skip, live=None, kernel=None):
    """One position for every row. ``sp`` is the packed state
    ``[B, H, N / r, r P]`` float32; ``x`` ``[B, H, P]``, ``dt``
    ``[B, H]``, ``bm``/``cm`` ``[B, G, N]``; ``live`` ``[B]`` marks the
    rows whose state may change (None = all). Returns ``(y [B, H, P]
    float32, new packed state)``; a dead row's ``y`` is 0."""
    bsz, h, p = x.shape
    r = sp.shape[-1] // p
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    if live is None:
        live = jnp.ones((bsz,), jnp.int32)
    alive = live.astype(jnp.int32) > 0
    dtf = jnp.where(alive[:, None], dtf, 0.0)
    ops = _step_operands(xf, dtf, a, bm.astype(jnp.float32),
                         cm.astype(jnp.float32), p, r)
    if use_step_kernel(kernel):
        sp_new, y2 = _ssm_step_update(sp, *ops, live,
                                      interpret=(kernel == "interpret"))
    else:
        sp_new, y2 = _ssm_step_plain(sp, *ops)
    y = jnp.sum(y2.reshape(bsz, h, r, p), axis=2)
    y = y + d_skip.astype(jnp.float32)[:, None] * xf
    return jnp.where(alive[:, None, None], y, 0.0), sp_new


# ---------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------
def mixer_shapes(width: int, n_heads: int, d_head: int, d_state: int,
                 n_groups: int, d_conv: int) -> dict:
    d_inner = n_heads * d_head
    conv_dim = d_inner + 2 * n_groups * d_state
    return {"W_in": (width, 2 * d_inner + 2 * n_groups * d_state
                     + n_heads),
            "conv_w": (d_conv, conv_dim), "conv_b": (conv_dim,),
            "dt_bias": (n_heads,), "A_log": (n_heads,), "D": (n_heads,),
            "norm_w": (d_inner,), "W_out": (d_inner, width)}


@scope("norm")
def gated_rms_norm(y, z, w, n_groups: int, eps: float):
    """``RMSNorm(y * silu(z)) * w``, the mean square taken over each of
    ``n_groups`` equal parts of the last axis, in float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = v.reshape(v.shape[:-1] + (n_groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(v.shape) * w.astype(jnp.float32)


def mamba2_mixer(params, hn, state, mask, *, n_heads: int, d_head: int,
                 d_state: int, n_groups: int, chunk: int, eps: float,
                 live=None, kernel=None):
    """The mixer on ``hn`` ``[B, T, D]`` (already normed). ``state`` is
    ``{"conv", "ssm"}`` or None (a fresh row); ``mask`` ``[B, T]`` marks
    each row's valid prefix (None = all). Returns ``(out [B, T, D],
    new state)``."""
    bsz, t, _ = hn.shape
    d_inner = n_heads * d_head
    gn = n_groups * d_state
    with scope("mixer/proj"):
        proj = hn @ params["W_in"]
        z = proj[..., :d_inner]
        xbc = proj[..., d_inner:2 * d_inner + 2 * gn]
        dt = proj[..., 2 * d_inner + 2 * gn:]
    with scope("mixer/conv"):
        lengths = (None if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        xbc, conv = causal_conv(
            xbc, None if state is None else state["conv"],
            params["conv_w"], params["conv_b"], lengths)
    with scope("mixer/ssm"):
        x = xbc[..., :d_inner].reshape(bsz, t, n_heads, d_head)
        bm = xbc[..., d_inner:d_inner + gn].reshape(
            bsz, t, n_groups, d_state)
        cm = xbc[..., d_inner + gn:].reshape(bsz, t, n_groups, d_state)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))
        if mask is not None:
            dt = dt * mask.astype(jnp.float32)[:, :, None]
        a = -jnp.exp(params["A_log"].astype(jnp.float32))
        if state is not None and t == 1:
            y, ssm = ssm_step(state["ssm"], x[:, 0], dt[:, 0], a,
                              bm[:, 0], cm[:, 0], params["D"], live,
                              kernel)
            y = y[:, None]
        else:
            s0 = (jnp.zeros((bsz, n_heads, d_head, d_state), jnp.float32)
                  if state is None
                  else unpack_state(state["ssm"], d_head))
            y, s_t = ssm_chunk_scan(x, dt, a, bm, cm, params["D"], s0,
                                    chunk)
            ssm = pack_state(s_t)
    y = gated_rms_norm(y.reshape(bsz, t, d_inner), z, params["norm_w"],
                       n_groups, eps).astype(hn.dtype)
    with scope("mixer/proj"):
        return y @ params["W_out"], {"conv": conv, "ssm": ssm}
