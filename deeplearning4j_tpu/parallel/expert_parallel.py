"""Expert parallelism: capacity-factored mixture-of-experts.

NEW capability relative to the reference (SURVEY.md §2.7 "NOT present"
list). Two dispatch paths, both with per-expert capacity buffers so
FLOPs are independent of the expert count (the defining property of
expert parallelism — a dense one-hot dispatch multiplies every token by
every expert, scaling compute ×E):

- ``moe_apply``: GSPMD path. Routing builds dispatch/combine tensors
  [B, E, C] with C = ceil(capacity_factor · B · k / E); the dispatch
  einsum gathers tokens into per-expert buffers [E, C, D] and the expert
  FFN runs batched over the expert axis. With ``W_up/W_down`` sharded on
  the mesh ``ep`` axis (``ep_param_shardings``) XLA lowers the gather /
  return einsums to all-to-all over ICI.
- ``make_ep_moe``: explicit shard_map path. Tokens live sharded over the
  ``ep`` axis; after local routing, ``lax.all_to_all`` exchanges the
  per-expert buffers so each device computes only its local experts, and
  a second all-to-all returns results — the canonical two-all-to-all MoE
  schedule (GShard/Switch), with FLOPs per device constant as experts
  scale with the mesh.

Routing is top-k (switch-style k=1 default, GShard k=2 with gate
renormalization) with a load-balancing auxiliary loss (Shazeer et al.:
E · Σ_e f_e·p_e over first-choice assignment fractions f and mean router
probabilities p). Tokens beyond an expert's capacity are dropped (their
combine weight is zero — the residual path of a surrounding block passes
them through unchanged).

``moe_apply_dense`` retains the dense one-hot dispatch as the semantic
reference for parity tests.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def init_moe_params(
    key, n_experts: int, d_in: int, d_hidden: int, dtype=jnp.float32
):
    k1, k2, k3 = jax.random.split(key, 3)
    scale = 1.0 / jnp.sqrt(d_in)
    return {
        "router": scale * jax.random.normal(k1, (d_in, n_experts), dtype),
        "W_up": scale * jax.random.normal(
            k2, (n_experts, d_in, d_hidden), dtype
        ),
        "W_down": (1.0 / jnp.sqrt(d_hidden)) * jax.random.normal(
            k3, (n_experts, d_hidden, d_in), dtype
        ),
    }


def expert_capacity(
    n_tokens: int, n_experts: int, capacity_factor: float, top_k: int = 1
) -> int:
    """Per-expert buffer length C: tokens each expert can accept."""
    c = int(math.ceil(capacity_factor * n_tokens * top_k / n_experts))
    return max(1, min(c, n_tokens))


def route_top_k(
    logits: Array,
    capacity: int,
    top_k: int = 1,
    normalize_gates: bool = True,
) -> Tuple[Array, Array, Array]:
    """Top-k capacity routing: logits [B, E] -> (dispatch [B, E, C],
    combine [B, E, C], aux scalar).

    ``dispatch`` is a {0,1} token→slot assignment (each token occupies at
    most k slots, each expert at most C tokens, first-come in batch
    order); ``combine`` is dispatch weighted by the (optionally
    renormalized) router gate. Routing runs at AT LEAST f32 — cumsum-
    based slot positions are exact integers that bf16 cannot represent
    past 256 — and follows the input up to f64 (gradient checks run the
    whole net in double precision; a hard f32 cast here would inject
    rounding noise larger than the centered difference).
    """
    f32 = jnp.promote_types(logits.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(f32), axis=-1)  # [B, E]
    B, E = probs.shape
    remaining = probs
    counts = jnp.zeros((E,), f32)          # tokens already seated per expert
    dispatch = jnp.zeros((B, E, capacity), f32)
    combine = jnp.zeros((B, E, capacity), f32)
    gate_total = jnp.zeros((B,), f32)
    aux = jnp.zeros((), f32)
    for k in range(top_k):
        expert = jnp.argmax(remaining, axis=-1)                  # [B]
        oh = jax.nn.one_hot(expert, E, dtype=f32)                # [B, E]
        gate = jnp.sum(probs * oh, axis=-1)                      # [B]
        # Slot index within the chosen expert, offset by seats taken in
        # earlier choice rounds; rows where oh == 0 produce positions that
        # may collide with real slots, so every slot write is masked by
        # ``keep``.
        pos = jnp.cumsum(oh, axis=0) - 1.0 + counts[None, :]     # [B, E]
        keep = oh * (pos < capacity).astype(f32)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=f32)
        slot = slot * keep[..., None]                            # [B, E, C]
        dispatch = dispatch + slot
        combine = combine + slot * gate[:, None, None]
        gate_total = gate_total + gate
        counts = counts + jnp.sum(keep, axis=0)
        if k == 0:
            # Load-balancing aux loss over FIRST-choice assignment
            # fractions (Switch Transformer eq. 4).
            f = jnp.mean(oh, axis=0)
            p = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(f * p)
        remaining = remaining * (1.0 - oh)
    if normalize_gates and top_k > 1:
        combine = combine / jnp.maximum(
            gate_total[:, None, None], jnp.asarray(1e-9, f32))
    return dispatch, combine, aux


def _expert_ffn(xe: Array, w_up: Array, w_down: Array) -> Array:
    """Batched per-expert FFN on capacity buffers [E, C, D]."""
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe, w_up))
    return jnp.einsum("ech,ehd->ecd", h, w_down)


def moe_apply(
    params,
    x: Array,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    ep_axis: Optional[str] = None,
) -> Tuple[Array, Array]:
    """Capacity-dispatched MoE: x [B, D] -> (y [B, D], aux scalar).

    FLOPs: dispatch/combine cost B·(E·C)·D = capacity_factor·k·B²·D and
    the expert FFN costs (E·C)·D·H = capacity_factor·k·B·D·H — both
    independent of E. Under pjit with ``ep_param_shardings`` XLA inserts
    the expert all-to-all.

    With ``ep_axis`` (only valid inside shard_map binding that axis; x
    and the router are per-shard, W_up/W_down hold the LOCAL expert
    slice) the dispatch buffers are exchanged with two explicit
    ``lax.all_to_all``s — see ``make_ep_moe``.
    """
    B = x.shape[0]
    E = params["router"].shape[1]
    capacity = expert_capacity(B, E, capacity_factor, top_k)
    rdt = jnp.promote_types(x.dtype, jnp.float32)
    logits = x.astype(rdt) @ params["router"].astype(rdt)
    dispatch, combine, aux = route_top_k(logits, capacity, top_k)
    xe = jnp.einsum("bec,bd->ecd", dispatch.astype(x.dtype), x)
    if ep_axis is not None:
        # [E, C, D] -> [E/n_ep, n_ep·C, D]: device j receives expert
        # block j's tokens from every peer.
        xe = lax.all_to_all(
            xe, ep_axis, split_axis=0, concat_axis=1, tiled=True)
    ye = _expert_ffn(xe, params["W_up"], params["W_down"])
    if ep_axis is not None:
        # [E/n_ep, n_ep·C, D] -> [E, C, D]: results return to the
        # tokens' home devices, expert blocks back in expert order.
        ye = lax.all_to_all(
            ye, ep_axis, split_axis=1, concat_axis=0, tiled=True)
    y = jnp.einsum("bec,ecd->bd", combine.astype(ye.dtype), ye)
    return y, aux


def moe_apply_dense(params, x: Array) -> Tuple[Array, Array]:
    """Dense one-hot top-1 dispatch (every token × every expert, masked
    after): the semantic reference for moe_apply parity tests; FLOPs ×E."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, probs.shape[-1], dtype=x.dtype)
    h = jnp.einsum("be,bd,edf->bef", onehot, x, params["W_up"])
    h = jax.nn.relu(h)
    y = jnp.einsum("bef,efd->bd", h, params["W_down"])
    y = y * gate[:, None]
    f = jnp.mean(onehot, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = probs.shape[-1] * jnp.sum(f * p)
    return y, aux


def make_ep_moe(
    mesh: Mesh,
    ep_axis: str = "ep",
    token_axes: Optional[Sequence[str]] = None,
    capacity_factor: float = 1.25,
    top_k: int = 1,
):
    """Explicit expert-parallel MoE: returns ``fn(params, x) -> (y, aux)``
    to be called OUTSIDE jit (it is itself jit-able).

    Tokens are sharded over ``token_axes`` (default: just ``ep_axis``;
    pass ``("dp", "ep")`` for a dp×ep mesh), experts over ``ep_axis``.
    Per shard: local routing against the full router, dispatch into
    [E, C_loc, D] buffers, ``lax.all_to_all`` (split experts, concat
    capacity) so each device holds [E/n_ep, n_ep·C_loc, D] for its local
    experts, local FFN, all-to-all back, weighted combine. The aux loss
    is pmean-ed over the token axes.
    """
    if ep_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {ep_axis!r}")
    axes = tuple(token_axes) if token_axes else (ep_axis,)
    n_ep = mesh.shape[ep_axis]

    def local_fn(params, x):
        E = params["router"].shape[1]
        if E % n_ep:
            raise ValueError(f"n_experts {E} not divisible by ep={n_ep}")
        y, aux = moe_apply(
            params, x, capacity_factor, top_k, ep_axis=ep_axis)
        for ax in axes:
            aux = lax.pmean(aux, ax)
        return y, aux

    param_specs = {
        "router": P(),
        "W_up": P(ep_axis, None, None),
        "W_down": P(ep_axis, None, None),
    }
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P(axes, None)),
        out_specs=(P(axes, None), P()),
        check_vma=False,
    )


def ep_param_shardings(mesh: Mesh, ep_axis: str = "ep"):
    """NamedShardings placing the expert axis on ``ep``."""
    return {
        "router": NamedSharding(mesh, P()),
        "W_up": NamedSharding(mesh, P(ep_axis, None, None)),
        "W_down": NamedSharding(mesh, P(ep_axis, None, None)),
    }
