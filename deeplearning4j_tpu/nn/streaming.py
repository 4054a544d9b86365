"""Slot-aware streaming-state utilities.

Shared by the network-level rnn-state APIs
(``MultiLayerNetwork.rnn_clear_previous_state`` /
``ComputationGraph.rnn_clear_previous_state``) and, for the length
buckets, the serving decode engine (``serving/engine.py``).

CONTRACT — streaming state is batch-major: every leaf of an rnn-state
pytree (attention ``k``/``v``/``filled``, GravesLSTM/GRU carried
``(h, c)``) has the batch dimension on axis 0, one row per batch
element (a *slot*);
``clear_state_rows`` relies on the contract to reset individual slots
without touching their neighbours. A zeroed attention row is exactly
the empty-cache state (``filled == 0`` masks every cached position in
``AttentionImpl._stream_attend``), and zeroed LSTM/GRU rows equal the
initial carry, so a cleared slot streams as if freshly created.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp


def scan_length_bucket(n: int, minimum: int = 8) -> int:
    """Next power of two >= max(n, minimum) — the jit-cache key for
    length-dependent decode scans and prefills.

    Keying compiled executables on the raw length grows the jit cache
    unboundedly under varied request lengths (every distinct
    ``n_tokens`` used to cost a full XLA compile of the generate scan);
    bucketing bounds compilations at O(log max_len) while wasting at
    most 2x scan steps for ``n >= minimum`` (below it, up to
    ``minimum`` steps run — the floor trades those cheap frozen-carry
    steps for not compiling a separate tiny-scan executable per
    sub-``minimum`` length), and the actual length rides alongside as
    a traced operand so masking stays exact."""
    n = max(int(n), int(minimum))
    return 1 << (n - 1).bit_length()


def make_bucketed_generate(step: Callable, vocab: int, dtype,
                           bucket: int):
    """Build the jitted freeze-carry greedy decode scan shared by
    ``MultiLayerNetwork.generate`` and ``ComputationGraph.generate``.

    ``step(params, state, x, rnn) -> (out [B, V, T], new_rnn)`` is the
    network's streaming forward for one one-hot token. The returned
    jitted callable ``(params, state, rnn_state, tok0, n_rem) ->
    (toks [B, bucket], rnn)`` scans ``bucket`` steps with the true
    remaining length traced: steps at ``i >= n_rem`` freeze the carry,
    so one executable serves every ``n_tokens`` in the bucket and the
    rnn state still lands exactly at the post-generation position."""
    def gen_fn(params, state, rnn_state, tok0, n_rem):
        def body(carry, i):
            rnn, tok = carry
            x = jax.nn.one_hot(tok, vocab, dtype=dtype)[:, :, None]
            out, new_rnn = step(params, state, x, rnn)
            nxt = jnp.argmax(out[:, :, -1], axis=1).astype(jnp.int32)
            live = i < n_rem  # bucket-pad steps freeze the carry
            keep = functools.partial(jnp.where, live)
            return (jax.tree_util.tree_map(keep, new_rnn, rnn),
                    jnp.where(live, nxt, tok)), nxt

        (rnn, _), toks = jax.lax.scan(body, (rnn_state, tok0),
                                      jnp.arange(bucket))
        return jnp.swapaxes(toks, 0, 1), rnn

    return jax.jit(gen_fn)


def reset_streaming_state(rnn_state: Any, slots) -> Any:
    """Shared body of ``rnn_clear_previous_state`` for both
    ``MultiLayerNetwork`` and ``ComputationGraph``: ``slots=None``
    wipes everything (fresh empty container), ``slots=[...]`` zeroes
    only those batch rows via ``clear_state_rows``. Returns the new
    state container."""
    if slots is None:
        return {}
    if not rnn_state:
        raise ValueError(
            "no streaming state to clear slots from — run "
            "rnn_time_step first (or call without slots)")
    return clear_state_rows(rnn_state, slots)


def clear_state_rows(rnn_state: Any, slots: Iterable[int]) -> Any:
    """Zero the given batch rows of every leaf in a streaming-state
    pytree, leaving all other rows untouched.

    This is the per-slot counterpart of the whole-batch state wipe: a
    caller streaming a batch of sequences restarts one of them while
    the others keep going. Slot indices are
    validated against the state's batch size; a scalar leaf violates
    the batch-major contract and raises."""
    idx = sorted({int(s) for s in slots})
    if not idx:
        return rnn_state
    leaves = jax.tree_util.tree_leaves(rnn_state)
    if not leaves:
        return rnn_state
    for leaf in leaves:
        if getattr(leaf, "ndim", 0) < 1:
            raise ValueError(
                "streaming-state leaf is scalar — per-slot clearing "
                "requires batch-major state (axis 0 = slot); re-run "
                "the prefill with this version's per-row cache")
    n = min(leaf.shape[0] for leaf in leaves)
    bad = [s for s in idx if s < 0 or s >= n]
    if bad:
        raise ValueError(
            f"slots {bad} out of range for streaming batch size {n}")
    iarr = jnp.asarray(idx, jnp.int32)

    def zero_rows(a):
        return a.at[iarr].set(jnp.zeros((), a.dtype))

    return jax.tree_util.tree_map(zero_rows, rnn_state)
