"""Mixture-of-experts dense layer (conf bean + impl).

NEW capability relative to the reference (SURVEY.md §2.7 expert-
parallelism mandate): a capacity-factored top-k MoE FFN block that slots
into a MultiLayerNetwork/ComputationGraph stack next to attention layers
(models/zoo.py ``moe_transformer_lm``). Dispatch math lives in
parallel/expert_parallel.py; this layer adapts it to the framework's
layer contract:

- accepts [N, C] feed-forward or [N, C, T] recurrent activations
  (tokens = N·T);
- the load-balancing auxiliary loss is returned through the layer-state
  channel (``{"aux_loss": ...}``) and added to the training score by
  MultiLayerNetwork._loss_fn weighted by ``aux_weight`` — the same
  functional-state route BatchNormalization uses for running stats;
- ``ep_axis`` names a mesh axis for explicit all-to-all expert
  parallelism when the surrounding train step runs under shard_map
  (same convention as MultiHeadSelfAttention.ring_axis), with
  ``W_up/W_down`` holding the local expert slice.

Beside it, :func:`dropless_moe` is the served form of today's sparse
models (nn/layers/hybrid.py holds it in a block): top-k routing over
ALL of the router's outputs with no capacity and no dropped token,
gated (SwiGLU) experts, a shared expert every token passes, and
``experts_held``, the range of the router's outputs whose experts this
chip holds. The gates follow one of two rules (:func:`route`): the
softmax over the picked logits, or sigmoid scores picked with a
per-expert selection bias, normalised and scaled. The chip computes
``sum over held picked experts of gate x expert(h)``; a pick that falls
on an expert held elsewhere adds nothing here. The (token, pick) pairs
are sorted by expert and ONE grouped product a matrix runs over the
held experts (:func:`grouped_product`:
the Pallas grouped matmul ``megablox.gmm`` on a TPU, which reads the
weights of an expert once for every tile of rows that reaches it and
never for an expert no row picked; ``jax.lax.ragged_dot`` elsewhere).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.serde import register_bean
from deeplearning4j_tpu.nn.layers.base import LayerImplBase
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.parallel.expert_parallel import moe_apply
from deeplearning4j_tpu.profiler.scopes import scope


@register_bean("MoeDense")
@dataclasses.dataclass
class MoeDense(FeedForwardLayer):
    """Conf bean: n_in must equal n_out (the block is residual-shaped:
    route -> expert FFN (n_in -> n_hidden -> n_out) -> combine [+ x])."""

    n_experts: int = 8
    n_hidden: int = 0           # 0 => 4 * n_in
    capacity_factor: float = 1.25
    top_k: int = 1
    aux_weight: float = 0.01    # weight of the load-balancing loss
    residual: bool = True
    ep_axis: Optional[str] = None  # expert-parallel mesh axis

    scope_group = "moe"


class MoeDenseImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        if lc.n_out and lc.n_out != lc.n_in:
            raise ValueError(
                f"MoeDense needs n_in == n_out, got {lc.n_in}/{lc.n_out}")
        d, e = lc.n_in, lc.n_experts
        h = lc.n_hidden or 4 * d
        kr, ku, kd = jax.random.split(key, 3)
        scheme = conf.resolved("weight_init")
        dist = conf.resolved("dist")
        return {
            "router": init_weights(kr, (d, e), scheme, dist, dtype),
            "W_up": init_weights(ku, (e, d, h), scheme, dist, dtype),
            "W_down": init_weights(kd, (e, h, d), scheme, dist, dtype),
        }

    @classmethod
    def init_state(cls, conf, dtype=jnp.float32):
        # Registers the layer in the state pytree so _forward_fn threads
        # the per-batch aux loss out to _loss_fn.
        return {"aux_loss": jnp.zeros((), dtype)}

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None):
        lc = conf.layer
        x = cls.maybe_dropout(conf, x, train, rng)
        recurrent = x.ndim == 3  # [N, C, T]
        if recurrent:
            n, c, t = x.shape
            tokens = jnp.transpose(x, (0, 2, 1)).reshape(n * t, c)
        else:
            tokens = x
        y, aux = moe_apply(
            params, tokens,
            capacity_factor=lc.capacity_factor,
            top_k=lc.top_k,
            ep_axis=lc.ep_axis,
        )
        if lc.residual:
            y = y + tokens
        y = cls.activation_of(conf)(y)
        if recurrent:
            y = jnp.transpose(y.reshape(n, t, c), (0, 2, 1))
            if mask is not None:
                y = y * mask[:, None, :]
        return y, {"aux_loss": aux}


# ---------------------------------------------------------------------
# dropless top-k routing over a share of gated experts
# ---------------------------------------------------------------------
#: rows of the sorted (token, pick) pairs one grid step of the grouped
#: product takes: few rows (decode) want small tiles, so that a tile
#: reaches few experts; many rows (prefill) want large ones, so that an
#: expert's weights are read for few tiles
_GROUP_TILE_ROWS = (128, 512)
_GROUP_TILE_ROWS_FROM = 4096


def _tile(size: int, want: int) -> int:
    """The largest divisor of ``size`` that is at most ``want`` and a
    multiple of 128, else ``size`` whole."""
    for t in range(min(want, size) // 128 * 128, 0, -128):
        if size % t == 0:
            return t
    return size


def use_grouped_kernel(toggle) -> bool:
    """The block's ``use_kernels``: None = the kernel on a TPU, ``ragged_dot``
    elsewhere; True / ``"interpret"`` force the kernel."""
    if toggle is None:
        return jax.default_backend() == "tpu"
    return bool(toggle)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_grouped_product(xs, w, group_sizes, *, interpret: bool = False):
    """``xs[rows of group e] @ w[e]`` for every held expert ``e``, as
    ONE Pallas call (jitted on its own: one trace for all layers; in
    the device trace the call carries the name of the function that
    makes it, ``gmm``). ``xs`` ``[M, K]`` sorted by expert, rows
    past ``sum(group_sizes)`` belonging to no held expert; ``w``
    ``[E, K, N]``. The result's rows past the groups are not written."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = xs.shape
    n = w.shape[2]
    tm = _GROUP_TILE_ROWS[m >= _GROUP_TILE_ROWS_FROM]
    if interpret:
        tm = 8
    pad = -m % tm
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    # the weights' tile: about 3 MB of bf16, whole rows of K first
    tk = _tile(k, 2048)
    tn = _tile(n, max(128, (3 << 20) // (2 * tk) // 128 * 128))
    out = gmm(xs, w, group_sizes, preferred_element_type=xs.dtype,
              tiling=(tm, tk, tn), interpret=interpret)
    return out[:m]


def grouped_product(xs, w, group_sizes, kernel=None):
    if use_grouped_kernel(kernel):
        return _moe_grouped_product(xs, w, group_sizes,
                                    interpret=(kernel == "interpret"))
    return jax.lax.ragged_dot(xs, w, group_sizes)


def gated_ffn(x, w_in, w_out):
    """``W_out (silu(g) * u)`` with ``[g | u] = x W_in``."""
    gu = x @ w_in
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w_out


def moe_shapes(width: int, n_router: int, n_held: int, d_expert: int,
               d_shared: int) -> dict:
    shapes = {"router": (width, n_router),
              "We_in": (n_held, width, 2 * d_expert),
              "We_out": (n_held, d_expert, width)}
    if d_shared:
        shapes.update(Ws_in=(width, 2 * d_shared),
                      Ws_out=(d_shared, width))
    return shapes


#: the gate rules of :func:`dropless_moe`, by name
GATE_RULES = ("softmax_topk", "sigmoid_bias")


def route(logits, top_k: int, rule: str = "softmax_topk", bias=None,
          scale: float = 1.0, eps: float = 1e-20):
    """``(gates [M, k] float32, idx [M, k])`` from the router's float32
    ``logits`` ``[M, E]`` under a gate rule:

    - ``"softmax_topk"``: pick the ``top_k`` largest logits, the gates
      are the softmax over the picked logits (granitemoehybrid);
    - ``"sigmoid_bias"``: score ``s = sigmoid(logits)``, pick the
      ``top_k`` largest ``s + bias`` (``bias`` ``[E]``, a selection
      term only: it moves picks, never gates, and takes no gradient),
      the gates are the picked SCORES over their sum plus ``eps`` (the
      normaliser's epsilon: afmoe and DeepSeek-V3's router 1e-20,
      lfm2_moe 1e-6), times ``scale``.

    Under a gradient the gates are differentiable in ``logits`` (so the
    router learns through them) and the picks are not: ``idx`` is
    whole numbers."""
    if rule == "softmax_topk":
        top, idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top, axis=-1), idx
    if rule != "sigmoid_bias":
        raise ValueError(f"gate rule {rule!r}: expected one of "
                         f"{GATE_RULES}")
    score = jax.nn.sigmoid(logits)
    pick = score if bias is None else score + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(pick), top_k)
    g = jnp.take_along_axis(score, idx, axis=-1)
    return g / (jnp.sum(g, axis=-1, keepdims=True) + eps) * scale, idx


#: the most rows of the sorted pairs one trip of a training pass takes
#: (:func:`_held_slabs`): few enough that the passes stop within a
#: sixteenth of a 65,536-pair layer of the last held pair, enough that
#: a trip's two dozen megabytes hide what starting it costs
_SLAB_ROWS = 4096


def _slab_rows(rows: int) -> int:
    """The rows a slab: the largest divisor of ``rows`` that is at most
    ``_SLAB_ROWS`` (whole slabs, so that no row is worked twice), or
    all the ``rows`` as one slab where the count has no divisor within
    a quarter of that."""
    most = min(_SLAB_ROWS, rows)
    size = next(s for s in range(most, 0, -1) if rows % s == 0)
    return size if 4 * size >= most else rows


def _slab(x, start):
    return jax.lax.dynamic_slice_in_dim(x, start, _slab_rows(x.shape[0]))


def _slabs_to(n_in, rows: int):
    """The slabs of ``rows`` rows that reach row ``n_in``."""
    size = _slab_rows(rows)
    return (n_in + size - 1) // size


def _held_slabs(n_in, out, slab_of):
    """``out`` with its rows below ``n_in`` (the held pairs: the sorted
    pairs' rows within the groups) made ``slab_of``'s, a slab of rows at
    a time in place, by a loop whose trip count is read from ``n_in``:
    the work follows the held pairs, whatever ``out.shape[0]``.
    ``slab_of(start, out)`` gives the blocks of columns, side by side,
    of rows ``start`` to ``start + _slab_rows(out.shape[0])`` (``out``
    as the slabs before left it: a pass that overwrites its own input
    reads it there). Rows past the last slab are left as they were:
    where ``out`` starts uninitialised (:func:`_fresh_slabs`) they may
    hold anything, like the rows a grouped product never writes, and
    whoever reads the result reads rows below ``n_in`` only, or
    selects."""
    size = _slab_rows(out.shape[0])

    def step(i, out):
        col = 0
        # (a slab's values are final before a row of ``out`` changes:
        # else the compiler may read a pass's own input again between
        # two blocks' writes, and copy all of ``out`` to do so)
        for block in jax.lax.optimization_barrier(slab_of(i * size, out)):
            out = jax.lax.dynamic_update_slice(out, block, (i * size, col))
            col += block.shape[1]
        return out

    return jax.lax.fori_loop(0, _slabs_to(n_in, out.shape[0]), step, out)


def _fresh_slabs(n_in, shape, dtype, slab_of):
    """:func:`_held_slabs` into a buffer that starts uninitialised
    (nothing zeroes it). The buffer is made inside a conditional on
    ``n_in``: the compiler allocates such a buffer at the top of the
    computation that holds it, which for a backward pass's would else
    be the top of the whole step, a forward pass before its first
    write."""
    def empty():
        return jax.lax.empty(shape, dtype)

    return jax.lax.cond(
        n_in > 0, lambda: _held_slabs(n_in, empty(), slab_of), empty)


def _held_pick(rows, picks, held, i):
    """Pick ``i``'s rows of the sorted pairs' ``rows``, one ``[M, D]``
    gather by column ``i`` of the inverse permutation ``picks``, in
    float32; 0, selected, where the pick is not held."""
    return jnp.where(held[:, i:i + 1], rows[picks[:, i]], 0).astype(
        jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_pairs(top_k, n_held, tokens, expert, held):
    """The (token, pick) pairs sorted by held expert, ``expert``
    ``[M k]`` being a pair's held expert or ``n_held`` where it is not
    held, so that the pairs not held sort past the groups. Returns
    ``(order, sizes, inside, xs, worked)``: the sort's permutation, the
    held experts' group sizes, ``inside`` ``[M k, 1]`` (the sorted rows
    within the groups), ``xs = tokens[order // top_k]`` (a token's row
    for each of its pairs, in the sorted order) and the rows of ``xs``
    that were made.

    With no gradient asked this is plain indexing, a served program's
    text: ``bincount`` for the sizes, one gather of all ``M k`` rows.
    Under a gradient the sizes are counted by comparison (no
    scatter-add of 65,536 ones), and ``xs`` is made for the held pairs
    only (:func:`_fresh_slabs`: the first product reads no other row).
    Going back nothing is scattered, ``order`` being a permutation: a
    token's cotangent is the sum of its HELD pairs' rows, gathered by
    the inverse permutation a pick at a time (``held`` ``[M, top_k]``;
    a pair not held sorts past the groups, where the grouped product's
    transpose writes nothing, and is selected away after its gather).
    The rule's own forward makes the inverse, the ``argsort`` the
    combine makes too (one operation once compiled). The gathers going
    back are traced under the scope of the call."""
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    inside = (jnp.arange(expert.shape[0]) < jnp.sum(sizes))[:, None]
    return (order, sizes, inside, tokens[order // top_k],
            jnp.int32(expert.shape[0]))


def _sorted_pairs_fwd(top_k, n_held, tokens, expert, held):
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.sum(expert == jnp.arange(n_held)[:, None], axis=1,
                    dtype=jnp.int32)
    n_in = jnp.sum(sizes)
    inside = (jnp.arange(expert.shape[0]) < n_in)[:, None]
    xs = _fresh_slabs(
        n_in, (expert.shape[0], tokens.shape[1]), tokens.dtype,
        lambda start, _: [tokens[_slab(order, start) // top_k]])
    worked = _slabs_to(n_in, expert.shape[0]) * _slab_rows(expert.shape[0])
    return (order, sizes, inside, xs, worked), (jnp.argsort(order), held)


def _sorted_pairs_bwd(top_k, n_held, res, g):
    # one gather a pick, selected, summed in float32 and cast once: a
    # gather of all the pairs would be re-laid out as [M, k, D] before
    # its sum, and a select before it is a pass over the pairs' rows
    back, held = res
    d_xs = g[3]
    picks = back.reshape(-1, top_k)
    total = functools.reduce(jnp.add, [
        _held_pick(d_xs, picks, held, i) for i in range(top_k)])
    return total.astype(d_xs.dtype), None, None


_sorted_pairs.defvjp(_sorted_pairs_fwd, _sorted_pairs_bwd)


def _gated(g, u):
    return jax.nn.silu(g) * u


@jax.custom_vjp
def _gated_rows(gu, sizes):
    """``silu(g) * u`` with ``[g | u] = gu``, the first product's
    ``[M k, 2 F]`` rows, of which those within the groups (``sizes``:
    the first ``n_in = sum(sizes)``) were written. With no gradient
    asked: the expression over all the rows. Under a gradient, forward
    and back, the same expression and ITS transpose over the held
    pairs' rows only (:func:`_fresh_slabs`, and :func:`_held_slabs` over
    the dead ``gu``): the cotangent's two halves are written side by
    side over ``gu``'s own rows, ONE ``[M k, 2 F]`` array whose rows
    past the last slab still hold ``gu``: like the rows past the groups
    of every array the products' transposes read, they may hold
    anything (the transposes read none of them)."""
    f = gu.shape[1] // 2
    # (written out, not ``_gated`` of two slices: a served program's
    # text slices the second half after it has activated the first)
    return jax.nn.silu(gu[:, :f]) * gu[:, f:]


def _gated_rows_fwd(gu, sizes):
    f = gu.shape[1] // 2
    n_in = jnp.sum(sizes)

    def slab_of(start, _):
        rows = _slab(gu, start)
        return [_gated(rows[:, :f], rows[:, f:])]

    act = _fresh_slabs(n_in, (gu.shape[0], f), gu.dtype, slab_of)
    return act, (gu, n_in)


def _gated_rows_bwd(res, d_act):
    gu, n_in = res
    f = gu.shape[1] // 2

    def slab_of(start, gu):
        rows = _slab(gu, start)
        _, back = jax.vjp(_gated, rows[:, :f], rows[:, f:])
        return back(_slab(d_act, start))

    # over ``gu`` itself, which nothing reads after this
    return _held_slabs(n_in, gu, slab_of), None


_gated_rows.defvjp(_gated_rows_fwd, _gated_rows_bwd)


@jax.custom_vjp
def _combine_picks(ys, gates, held, inside, order, sizes):
    """``sum over a token's held picks of gate x ys[the pick's sorted
    row]``, float32 ``[M, D]``. ``ys`` ``[M k, D]`` are the sorted
    pairs' rows, those past the held groups never written: they may
    hold anything and are selected away, not scaled. ``gates`` and
    ``held`` are ``[M, k]``; ``order`` is the sort's permutation, the
    pairs not held last, so that ``inside`` ``[M k, 1]``, the rows
    within the groups (``sizes``), is ``held`` in the sorted order.

    With no gradient asked this is plain indexing: the select over the
    sorted rows, ONE gather of all the pairs by the inverse
    permutation, their float32 ``[M, k, D]`` and its weighted sum (a
    served program is what it was). Under a gradient the rule works a
    pick at a time on gathered ``[M, D]`` rows. Forward: ``top_k``
    gathers, each selected by its column of ``held``, scaled and added
    in float32. Back: a sorted pair's cotangent is its gate times its
    token's row of ``dy``, one gather from ``[M, D]``, the product in
    float32 rounded once to ``ys``' dtype, made for the held pairs only
    (:func:`_fresh_slabs`: the second product's transposes read no other
    row; ``dy`` is read at ``ys``' dtype, which is exact where the
    caller casts this sum to that dtype, as the layer does); a gate's
    is its pick's row against ``dy``. No float32 ``[M, k, D]`` either
    way, no select over ``[M k, D]``, and no pass over the pairs' rows
    but the one that writes the held pairs' cotangent."""
    m, top_k = gates.shape
    ys = jnp.where(inside, ys, 0)
    back = jnp.argsort(order)
    picked = ys[back].reshape(m, top_k, -1).astype(jnp.float32)
    return jnp.sum(picked * jnp.where(held, gates, 0.0)[..., None], axis=1)


def _combine_picks_fwd(ys, gates, held, inside, order, sizes):
    picks = jnp.argsort(order).reshape(gates.shape)
    y = functools.reduce(jnp.add, [
        _held_pick(ys, picks, held, i) * gates[:, i:i + 1]
        for i in range(gates.shape[1])])
    return y, (ys, gates, held, order, picks, jnp.sum(sizes))


def _combine_picks_bwd(res, dy):
    ys, gates, held, order, picks, n_in = res
    top_k = gates.shape[1]
    # the layer casts the sum to ys' dtype, so dy is such a value lifted
    # to float32: its rows are gathered at that dtype, nothing rounded
    rows = dy.astype(ys.dtype)
    gate = gates.reshape(-1)

    def slab_of(start, _):
        pairs = _slab(order, start)
        return [(gate[pairs][:, None] * rows[pairs // top_k]).astype(
            ys.dtype)]

    d_ys = _fresh_slabs(n_in, ys.shape, ys.dtype, slab_of)
    d_gates = jnp.stack(
        [jnp.sum(dy * _held_pick(ys, picks, held, i), axis=-1)
         for i in range(top_k)], axis=1)
    return d_ys, d_gates.astype(gates.dtype), None, None, None, None


_combine_picks.defvjp(_combine_picks_fwd, _combine_picks_bwd)


def dropless_moe(params, tokens, valid=None, *, top_k: int,
                 experts_held: Tuple[int, int], kernel=None,
                 gate_rule: str = "softmax_topk",
                 route_scale: float = 1.0, route_eps: float = 1e-20,
                 detach_scores: bool = False):
    """Routed plus shared experts on ``tokens`` ``[M, D]``.

    ``params``: ``router`` ``[D, E]`` (all ``E`` outputs, whatever is
    held), ``We_in`` ``[E_held, D, 2 F]``, ``We_out`` ``[E_held, F, D]``
    for the experts ``experts_held = (lo, hi)`` of the router's outputs,
    and, where the layer has a shared expert, ``Ws_in`` / ``Ws_out``.
    ``valid`` ``[M]`` marks the tokens that exist (padding and idle
    rows route nowhere and count nowhere).

    Returns ``(y [M, D], counts)``: the gates follow ``gate_rule``
    (:func:`route`; ``"sigmoid_bias"`` reads ``params["expert_bias"]``
    ``[E]``, ``route_scale`` and ``route_eps``), in float32; no token is
    dropped, whatever the load. It trains: under ``jax.grad`` the
    grouped products transpose (the library's rule for its kernel:
    the same kernel on the transposed weights for the rows, ``tgmm``
    for the weights; ``ragged_dot``'s own elsewhere), the router gets
    its gradient through the gates, ``expert_bias`` none, and a pick on
    an expert held elsewhere adds nothing to the value or to any
    gradient: the rows past the held groups, which the kernel never
    writes going either way, are SELECTED away from the value and from
    the tokens' cotangent, and nothing else reads them. The sort with
    the row movement by its permutation, the gated activation between
    the two products and the gate-weighted combine state their own
    rules (:func:`_sorted_pairs`, :func:`_gated_rows`,
    :func:`_combine_picks`). With no gradient asked all three are plain
    indexing over all ``M k`` pairs' rows (a served program's text).
    Under a gradient what runs over which rows:

    - over the HELD pairs' rows only, ``n_in = sum(sizes)`` of the
      ``M k``, a slab of ``_SLAB_ROWS`` at a time by a loop whose trip
      count is read from ``n_in`` (:func:`_held_slabs`; all ``M k`` may
      be held, and then every slab runs): the gather of ``xs``, the
      activation forward and its transpose (the cotangent written over
      ``gu``), and the pass that writes ``ys``' cotangent; rows past the
      last slab are never written, zeroed or read;
    - over the groups' rows, by its own grid: the grouped kernel and
      its transposes;
    - a pick at a time on gathered ``[M, D]`` rows, by the inverse
      permutation where autodiff would scatter-add, each gather
      selected by its column of ``held`` inside the float32 sum that
      follows it: the combine's value, the gates' cotangent and the
      tokens' (no ``[M, k, D]`` array, no select over ``[M k, D]``);
    - numbers only: the sort of the ``M k`` keys and its inverse; the
      sizes are counted by comparison (``bincount``'s scatter-add is
      the served program's).

    Under ``detach_scores`` the gates are constants to the gradient:
    neither the router nor ``tokens`` takes one through them (the
    block's ``freeze_router``). ``counts`` are int32 scalars:
    ``moe_picks`` (token x pick pairs routed), ``moe_picks_held`` (those
    on held experts), ``moe_experts_touched`` (held experts with at
    least one row), ``moe_load_max`` (the fullest held expert's rows),
    ``moe_pair_rows_worked`` (the sorted pairs' rows the passes around
    the products covered: slabs x slab rows under a gradient, within a
    slab of ``moe_picks_held``; ``M k`` with none asked)."""
    m = tokens.shape[0]
    lo, hi = experts_held
    n_held = hi - lo
    if params["We_in"].shape[0] != n_held:
        raise ValueError(
            f"experts_held {experts_held} names {n_held} experts, the "
            f"layer holds {params['We_in'].shape[0]}")
    with scope("moe/route"):
        logits = jnp.dot(tokens, params["router"],
                         preferred_element_type=jnp.float32)
        if detach_scores:
            logits = jax.lax.stop_gradient(logits)
        gates, idx = route(logits, top_k, gate_rule,          # [M, k]
                           params.get("expert_bias"), route_scale,
                           route_eps)
        routed = (jnp.ones((m, 1), bool) if valid is None
                  else valid.astype(bool)[:, None])
        held = (idx >= lo) & (idx < hi) & routed
    with scope("moe/sort"):
        # sort the pairs by held expert; what is not held sorts last.
        # Rows past the groups are never written by the kernel, forward
        # or transposed, nor by a training pass around it, and may hold
        # anything: select them away (do not scale) from the value (as
        # ``inside`` over the sorted rows, or as ``held`` over a pick's
        # gathered rows: the same pairs); going back nothing reads them
        expert = jnp.where(held, idx - lo, n_held).reshape(-1)
        order, sizes, inside, xs, worked = _sorted_pairs(
            top_k, n_held, tokens, expert, held)
    with scope("moe/experts"):
        gu = grouped_product(xs, params["We_in"], sizes, kernel)
        act = _gated_rows(gu, sizes).astype(tokens.dtype)
        ys = grouped_product(act, params["We_out"], sizes, kernel)
    with scope("moe/combine"):
        y = _combine_picks(ys, gates, held, inside, order, sizes)
    if "Ws_in" in params:
        with scope("moe/shared"):
            y = y + gated_ffn(tokens, params["Ws_in"],
                              params["Ws_out"]).astype(jnp.float32)
    with scope("moe/route"):
        counts = {
            "moe_picks": jnp.sum(routed.astype(jnp.int32)) * top_k,
            "moe_picks_held": jnp.sum(held.astype(jnp.int32)),
            "moe_experts_touched": jnp.sum(
                (sizes > 0).astype(jnp.int32)),
            "moe_load_max": jnp.max(sizes),
            "moe_pair_rows_worked": worked}
    with scope("moe/combine"):
        return y.astype(tokens.dtype), counts
