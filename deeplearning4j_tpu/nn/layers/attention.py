"""Multi-head self-attention layer.

NEW capability relative to the reference (2015 — predates attention;
SURVEY.md §5.7 mandates long-context support as first-class in this
framework). Follows the framework's [N, C, T] recurrent layout so it
composes with GravesLSTM/RnnOutputLayer in a MultiLayerNetwork stack.

When ``ring_axis`` names a mesh axis present at trace time (sequence
parallelism), the core attention runs as ring attention over that axis
(parallel/sequence_parallel.py); otherwise it is a fused dense
flash-style attention that XLA maps onto the MXU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.layers import BaseRecurrentLayer, PagedCache
from deeplearning4j_tpu.nn.conf.serde import register_bean
from deeplearning4j_tpu.nn.layers.base import LayerImplBase
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.profiler.scopes import scope

# -- tensor-parallel head sharding (serving TP, ISSUE 12) --------------
#
# Trace-time marker stack: when the enclosing program is the body of a
# fully-manual ``shard_map`` over a TP mesh axis with attention weights
# head-sharded (Wq/Wk/Wv column-sliced so each shard owns n_heads/TP
# whole heads, Wo row-sliced), the attention layers must (a) reshape
# onto the LOCAL head count and (b) all-reduce the partial output
# projection — the Megatron self-attention block. The serving decode
# engine (serving/tp.py) enters this context inside its shard_map
# bodies; training TP needs none of it (the trainers shard via GSPMD
# param specs, parallel/data_parallel.py:tp_param_specs, and XLA
# derives the same collective). Thread-local: engines in one process
# may trace concurrently (the in-process replica pattern), and a tp>1
# scope must not leak into a sibling engine's plain-jit trace.
_TP_SCOPES = threading.local()


def _tp_stack() -> List[Tuple[str, int]]:
    stack = getattr(_TP_SCOPES, "stack", None)
    if stack is None:
        stack = _TP_SCOPES.stack = []
    return stack


@contextlib.contextmanager
def tp_head_shards(axis_name: str, size: int):
    """Declare that attention params (and KV caches) within this trace
    are head-sharded ``size``-ways over mesh axis ``axis_name``."""
    stack = _tp_stack()
    stack.append((str(axis_name), int(size)))
    try:
        yield
    finally:
        stack.pop()


def _tp_scope() -> Optional[Tuple[str, int]]:
    stack = _tp_stack()
    return stack[-1] if stack else None


def _tp_local_heads(n_heads: int, tp: Tuple[str, int]) -> int:
    axis, size = tp
    if n_heads % size:
        raise ValueError(
            f"tensor parallelism over {axis!r} needs tp ({size}) to "
            f"divide n_heads ({n_heads}): head sharding slices whole "
            "heads")
    return n_heads // size


@register_bean("MultiHeadSelfAttention")
@dataclasses.dataclass
class MultiHeadSelfAttention(BaseRecurrentLayer):
    """Conf bean: n_in = model width C, n_out = model width out; heads
    must divide n_out."""

    n_heads: int = 4
    causal: bool = True
    ring_axis: Optional[str] = None  # sequence-parallel mesh axis
    # sub-chunk the visiting K/V block inside the ring (blockwise online
    # softmax): bounds the per-device score buffer at
    # [B, H, T_local, ring_block_size] instead of [.., T_local, T_local]
    # — the memory lever for LONG local shards; None = whole block
    ring_block_size: Optional[int] = None
    # which SP schedule runs over ring_axis: "ring" (K/V ppermute hops,
    # O(T_local) score memory) or "ulysses" (two all-to-alls swap
    # heads<->time, full-T attention on H/P heads per device — fewer,
    # larger collectives; needs n_heads % sp == 0)
    sp_mode: str = "ring"
    # pallas flash-attention path: True forces it (TPU, no mask, T
    # multiple of 128 and >= 256), False forces dense, None = auto —
    # engages at T >= 2048 when T % 512 == 0 (healthy kernel blocks),
    # and at T >= 8192 unconditionally (dense OOMs long before 32k)
    use_flash: Optional[bool] = None
    # pallas PAGED-attention decode kernel (the serving engine's pool;
    # ISSUE 12): True forces it (TPU), False forces the XLA
    # gather-by-block-table program, "interpret" runs the kernel in
    # pallas interpret mode (the CPU parity-testing hook), None = auto
    # — kernel on TPU, XLA gather everywhere else (see
    # _should_use_flash_paged)
    use_flash_paged: Optional[object] = None
    # KV-cache length for rnn_time_step streaming (reference
    # rnnTimeStep contract, BaseRecurrentLayer stateMap): a FIXED-size
    # right-aligned sliding cache so the decode step compiles once
    # (static shapes — no per-step recompilation as context grows);
    # tokens older than this many steps fall out of the window
    stream_max_t: int = 512

    #: the impl names its own parts (``attn/qkv`` ...)
    scope_group = None

    def serving_caches(self):
        return (attention_cache(self),)


class AttentionImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        kq, kk, kv, ko = jax.random.split(key, 4)
        scheme = conf.resolved("weight_init")
        dist = conf.resolved("dist")
        d_in, d = lc.n_in, lc.n_out
        return {
            "Wq": init_weights(kq, (d_in, d), scheme, dist, dtype),
            "Wk": init_weights(kk, (d_in, d), scheme, dist, dtype),
            "Wv": init_weights(kv, (d_in, d), scheme, dist, dtype),
            "Wo": init_weights(ko, (d, d), scheme, dist, dtype),
            "b": jnp.zeros((d,), dtype),
        }

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None):
        lc = conf.layer
        h = lc.n_heads
        d = lc.n_out
        if d % h:
            raise ValueError(f"n_out {d} not divisible by n_heads {h}")
        dh = d // h
        tp = _tp_scope()
        if tp is not None:
            h = _tp_local_heads(h, tp)
        with scope("attn/qkv"):
            x = cls.maybe_dropout(conf, x, train, rng)
            xt = jnp.transpose(x, (0, 2, 1))  # [N, T, C]

            def split_heads(m):
                y = xt @ m  # [N, T, D] (local D/TP under tp head sharding)
                return jnp.transpose(
                    y.reshape(y.shape[0], y.shape[1], h, dh), (0, 2, 1, 3)
                )  # [N, H, T, dh]

            q = split_heads(params["Wq"])
            k = split_heads(params["Wk"])
            v = split_heads(params["Wv"])
        # (from outside every scope: ``_attend_core`` places its own)
        o, state = cls._attend_core(lc, q, k, v, state, train, mask)
        with scope("attn/out"):
            o = jnp.transpose(o, (0, 2, 1, 3)).reshape(
                o.shape[0], o.shape[2], h * dh
            )  # [N, T, D] (local heads under tp)
            if tp is not None:
                # row-parallel output projection: each shard's o covers
                # its own heads, the matmul yields a partial [N, T, D]
                # sum — ONE all-reduce completes it (bias added once,
                # after). Partials accumulate AND all-reduce in f32,
                # rounding to the compute dtype once: bf16 partials
                # rounded per shard then summed double-round, and the
                # extra noise flips argmaxes vs the single-chip engine
                # (the bench id-match gate caught it at tp=2/bf16)
                out = jax.lax.dot_general(
                    o, params["Wo"], (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out = jax.lax.psum(out, tp[0]).astype(o.dtype)
            else:
                out = o @ params["Wo"]
            out = out + params["b"]
            out = cls.activation_of(conf)(out)
            out = jnp.transpose(out, (0, 2, 1))  # [N, D, T]
            if mask is not None:
                out = out * mask[:, None, :]
        return out, state

    @classmethod
    def _attend_core(cls, lc, q, k, v, state, train, mask):
        """Attention-core dispatch on [N, H, T, dh] q/k/v, shared with
        TransformerBlockImpl: streaming continuation, ring/Ulysses
        sequence parallelism, pallas flash, or dense — plus the serving
        KV-cache prefill. It places its own scopes (``attn/core``,
        ``attn/cache``, ``tables``) and is called from outside every
        scope, because the flash program must stay bare
        (profiler/scopes.py)."""
        if state is not None:
            # Streaming continuation (rnn_time_step): attend over the
            # carried KV cache + this chunk — the attention analogue of
            # the LSTM carried (h, c) (reference BaseRecurrentLayer
            # stateMap). Always causal (the future is unwritten when
            # decoding). An optional right-padded chunk mask lets a
            # bucket-padded suffix chunk resume a partially-filled
            # cache (serving chunked prefill); unmasked streaming (the
            # reference contract, and the decode hot path) is the
            # mask=None fast path.
            return cls._stream_attend(lc, q, k, v, state, mask)
        if lc.ring_axis:
            from deeplearning4j_tpu.parallel.sequence_parallel import (
                ring_attention,
                ulysses_attention,
            )

            with scope("attn/core"):
                if lc.sp_mode == "ulysses":
                    if lc.ring_block_size:
                        raise ValueError(
                            "ring_block_size bounds the RING schedule's "
                            "score memory; ulysses materializes the "
                            "full [T, T] scores of its local heads — "
                            "unset ring_block_size or use "
                            "sp_mode='ring'")
                    o = ulysses_attention(
                        q, k, v, lc.ring_axis, causal=lc.causal,
                        key_mask=mask,
                    )
                elif lc.sp_mode == "ring":
                    o = ring_attention(
                        q, k, v, lc.ring_axis, causal=lc.causal,
                        key_mask=mask, block_size=lc.ring_block_size,
                    )
                else:
                    raise ValueError(
                        f"sp_mode {lc.sp_mode!r}: expected 'ring' or "
                        "'ulysses'")
            return o, None
        # a SLIDING layer (its bean says so) bands every program by its
        # window; the flash program takes no band, so a sequence past
        # the window stays on the plain one
        band = (lc.stream_max_t if getattr(lc, "sliding", False)
                and q.shape[2] > lc.stream_max_t else None)
        if band is None and _should_use_flash(lc.use_flash, q, mask):
            # bare: the library's program is charged to ``attn/core`` by
            # its own entry (``LIBRARY_SCOPES``). Grouped KV heads go in
            # as they are: the kernel runs a KV head over its group
            o = _flash_attention(q, k, v, lc.causal)
        else:
            # the dense program takes one key head a query head, so a
            # group's keys are repeated for it; the cache below keeps
            # the KV heads only
            with scope("attn/core"):
                ke, ve = _repeat_kv_heads(q, k, v)
                o = _dense_attention(q, ke, ve, lc.causal, mask, band)
        new_state = None
        if not train:
            # Prefill: expose the (right-aligned, fixed-size) KV
            # cache so a later rnn_time_step call continues this
            # context. Under output()/evaluate the returned rnn
            # state is discarded, so XLA dead-code-eliminates the
            # cache build; training (train=True) never creates it —
            # tBPTT windows stay independent, as without a cache.
            # (Built for non-causal layers too so that a SECOND
            # streaming call reaches _stream_attend's explicit
            # cannot-stream error instead of silently attending
            # chunk-locally.)
            with scope("attn/cache"):
                new_state = cls._prefill_cache(lc, k, v, mask)
        return o, new_state

    # -- rnn_time_step streaming (fixed-size sliding KV cache) ---------
    @staticmethod
    def _right_align(shift, *arrays):
        """Right-rotate each batch row of ``[N, H, T, dh]`` arrays by
        its per-row ``shift`` along the time axis — the
        pad-out-of-view trick shared by bucket-padded prefill and
        masked chunk continuation: after rotation a ``[:, :, -tm:, :]``
        window slice keeps real tokens contiguous at the right edge,
        and the wrapped pad lands in the left region the per-row
        ``filled`` mask invalidates (it must never receive attention
        weight — both call sites rely on exactly this invariant)."""
        roll = jax.vmap(lambda a, s: jnp.roll(a, s, axis=1))
        return tuple(roll(a, shift) for a in arrays)

    @classmethod
    def _prefill_cache(cls, lc, k, v, mask=None):
        """Right-align the last ``stream_max_t`` K/V positions into the
        fixed-size cache (zeros pad the left when underfilled).

        ``filled`` is a PER-ROW int32 vector [N]: each batch row is an
        independent streaming slot with its own valid-length, so ragged
        requests can share one batched cache (serving/engine.py slots).
        With ``mask`` (right-padded prompts, [N, T] 1/0 over the valid
        prefix) each row's real K/V are rotated to the right edge of
        the window and ``filled`` counts only real tokens — the padded
        tail wraps into the left region that the per-row window mask
        already invalidates, so a bucket-padded prefill streams
        identically to an unpadded prefill of the same prompt (the
        masked left region may hold wrapped pad instead of zeros; it
        never receives attention weight). Works for any T, including
        T > stream_max_t (ordinary masked inference on long padded
        batches): the window then keeps each row's last
        ``min(length, stream_max_t)`` valid positions."""
        tm = lc.stream_max_t
        n, h, t, dh = k.shape
        if mask is None:
            filled = jnp.full((n,), min(t, tm), jnp.int32)
        else:
            # rotate each row's pad out of view BEFORE windowing:
            # valid K/V land contiguous at the right edge for any T
            # (window-sized or longer) — see _right_align
            lengths = jnp.sum(mask.astype(jnp.int32), axis=1)  # [N]
            k, v = cls._right_align(t - lengths, k, v)
            filled = jnp.minimum(lengths, tm)
        zk = jnp.zeros((n, h, tm, dh), k.dtype)
        ck = jnp.concatenate([zk, k], axis=2)[:, :, -tm:, :]
        cv = jnp.concatenate([zk, v], axis=2)[:, :, -tm:, :]
        return {"k": ck, "v": cv, "filled": filled}

    @classmethod
    def _paged_attend(cls, lc, q, k, v, cache, mask=None):
        """Gather-by-block-table attention over the shared KV block
        pool (the serving engine's KV layout — vLLM's
        PagedAttention memory model on the XLA level: the pallas
        kernel :func:`_paged_flash_attention`, which walks the same
        ``ntab`` table entries a compute block of several pool blocks
        per trip of its loop with its own double-buffered copies, is the TPU
        hot path; this program is its semantics, its off-TPU path and
        its parity oracle).

        The cache dict is NOT a per-slot row but a view into one pool
        shared by every slot and the radix prefix trie:

        - ``pk``/``pv`` [n_blocks, block_tokens, H, dh] — the device
          pool; a block holds ``block_tokens`` consecutive tokens of
          exactly one logical sequence (possibly shared by several
          slots/trie entries via host-side refcounts).
        - ``table`` [B, S] int32 — each row's ring-addressed block
          table: logical block ``g`` (covering absolute token
          positions ``[g*bt, (g+1)*bt)``) lives at ring slot
          ``g % S``; -1 = unmapped.
        - ``base`` [B, S] int32 — ``g*bt`` for the block each ring
          slot currently holds (validates ring-slot occupancy: a slot
          whose base disagrees with the probed logical block is stale
          and masked).
        - ``floor`` [B] int32 — minimum valid absolute position (a
          prefix-trie splice of a window-slid entry exposes only the
          positions the entry actually stored).
        - ``filled`` [B] int32 — absolute length = the next write
          position (NOT capped at the window, unlike the dense cache).

        Per call: the chunk's K/V scatter into the pool at their
        absolute positions THROUGH the table (one flat scatter; pad
        positions and unmapped rows drop), then every query gathers
        the ``<= window + t`` tokens its sliding window can reach and
        attends under exactly the dense path's validity rule — causal,
        last-``stream_max_t`` window, per-row floor. Writes precede
        the gather inside one program, so position ``p``'s content is
        committed before any query with ``qpos >= p`` reads it; stale
        garbage past ``filled`` is causally masked and overwritten by
        the next append (which is what lets a speculative round rewind
        a rejected tail by moving ``filled`` back). The host guarantees
        every block written here has refcount 1 (copy-on-write happens
        before dispatch), so shared prefix blocks are never mutated."""
        tm = lc.stream_max_t
        b, hq, t, dh = q.shape
        h = k.shape[1]          # the pool holds the KV heads
        if not lc.causal:
            raise ValueError(
                "non-causal (bidirectional) attention cannot stream: "
                "rnn_time_step continuation would need future tokens; "
                "use causal=True or run output() on full sequences")
        pk, pv = cache["pk"], cache["pv"]
        table, base = cache["table"], cache["base"]
        floor, filled = cache["floor"], cache["filled"]
        nb, bt = pk.shape[0], pk.shape[1]
        n_tok = nb * bt
        s_ring = table.shape[1]
        pkf = pk.reshape(n_tok, h, dh)
        pvf = pv.reshape(n_tok, h, dh)
        # -- scatter the chunk's K/V to their absolute positions ------
        with scope("tables"):
            if mask is None:
                lengths = jnp.full((b,), t, jnp.int32)
            else:
                lengths = jnp.sum(mask.astype(jnp.int32), axis=1)
            pos = filled[:, None] + jnp.arange(t)[None, :]    # [B, t]
            blk = jnp.take_along_axis(table, (pos // bt) % s_ring,
                                      axis=1)
            writable = (jnp.arange(t)[None, :] < lengths[:, None]) & (
                blk >= 0)
            widx = jnp.where(writable, blk * bt + pos % bt, n_tok)
        with scope("attn/cache"):
            kt = jnp.swapaxes(k, 1, 2).reshape(b * t, h, dh)
            vt = jnp.swapaxes(v, 1, 2).reshape(b * t, h, dh)
            pkf = pkf.at[widx.reshape(-1)].set(kt.astype(pkf.dtype),
                                               mode="drop")
            pvf = pvf.at[widx.reshape(-1)].set(vt.astype(pvf.dtype),
                                               mode="drop")
        # -- gather each row's reachable window -----------------------
        # consecutive logical blocks from the earliest any query needs
        # (bounded per-executable: ~window + chunk tokens, NOT the
        # whole ring — the decode step reads ~window keys like dense)
        ntab = _paged_table_entries(s_ring, tm, bt, t)
        with scope("tables"):
            lo = jnp.maximum(floor, jnp.maximum(filled - tm + 1, 0))
            lo_blk = lo // bt
            g = lo_blk[:, None] + jnp.arange(ntab)[None, :]  # [B, ntab]
            tb = jnp.take_along_axis(table, g % s_ring, axis=1)
            bb = jnp.take_along_axis(base, g % s_ring, axis=1)
            bval = (tb >= 0) & (bb == g * bt)      # ring slot holds g
        toggle = getattr(lc, "use_flash_paged", None)
        if _should_use_flash_paged(toggle, bt, dh, t):
            # fused pallas kernel (ISSUE 12; ISSUE 25: a compute
            # block is several table entries; ISSUE 30: a grid step is
            # a row and query tile, the walk a loop inside it; ISSUE 31:
            # a KV head's group of query heads rides its tile): each row
            # walks its block list INSIDE the kernel, copying only
            # mapped and reachable pool blocks — no [B, ntab*bt, ...]
            # gather ever materializes in HBM. Same validity rule,
            # same value-level NaN masking, online softmax; parity vs
            # the gather program is argmax-level (different float
            # reduction shape — the PR 6 paged-parity convention).
            with scope("attn/core"):
                pools = (pkf.reshape(nb, bt, h, dh),
                         pvf.reshape(nb, bt, h, dh))
            with scope("tables"):     # the kernel's scalar operands
                scalars = (
                    jnp.where(bval, tb, 0).astype(jnp.int32),
                    bval.astype(jnp.int32), lo_blk.astype(jnp.int32),
                    floor.astype(jnp.int32), filled.astype(jnp.int32),
                    lengths.astype(jnp.int32))
            with scope("attn/core"):
                o = _paged_flash_attention(
                    q, *pools, *scalars, tm=tm,
                    interpret=(toggle == "interpret"))
            with scope("tables"):
                return o, {"pk": pkf.reshape(nb, bt, h, dh),
                           "pv": pvf.reshape(nb, bt, h, dh),
                           "table": table, "base": base, "floor": floor,
                           "filled": filled + lengths}
        with scope("tables"):
            off = jnp.arange(bt)
            gidx = (jnp.where(bval, tb, 0)[:, :, None] * bt
                    + off[None, None, :]).reshape(b, ntab * bt)
            kpos = (g[:, :, None] * bt
                    + off[None, None, :]).reshape(b, ntab * bt)
            kval = jnp.repeat(bval, bt, axis=1)        # [B, ntab*bt]
        with scope("attn/core"):
            ek = jnp.swapaxes(pkf[gidx], 1, 2)         # [B, H, K, dh]
            ev = jnp.swapaxes(pvf[gidx], 1, 2)
            # gather lanes outside each row's WRITTEN span carry foreign
            # data: invalid-block lanes read a placeholder block, and a
            # freshly (re)allocated tail block holds whatever its previous
            # owner left there — possibly NaN under fault injection, since
            # eviction releases blocks by reference without scrubbing. A
            # NaN value survives a zero softmax weight (0 * NaN = NaN), so
            # values must be zeroed at the VALUE level over the full
            # validity rule — block mapped AND position inside
            # [floor, filled + written) — or a recycled dirty block
            # silently corrupts its next owner through masked lanes
            # (caught by the chaos gate and the paranoid-off regression).
            # The pallas kernel above enforces the SAME rule on its DMA'd
            # V blocks (`vlive` in _paged_flash_attention) — the two paths
            # share the contract, and the kernel parity tests poison a
            # freed block to prove it holds there too
            vlive = (kval
                     & (kpos < (filled + lengths)[:, None])
                     & (kpos >= floor[:, None]))
            ev = jnp.where(vlive[:, None, :, None], ev, 0)
            qpos = filled[:, None] + jnp.arange(t)[None, :]
            scores = _grouped_scores(q, ek) / jnp.sqrt(
                jnp.asarray(dh, q.dtype))
            ok = (kval[:, None, :]
                  & (kpos[:, None, :] <= qpos[:, :, None])      # causal
                  & (kpos[:, None, :] > qpos[:, :, None] - tm)  # window
                  & (kpos[:, None, :] >= floor[:, None, None]))
            neg = jnp.asarray(-1e30, q.dtype)
            scores = jnp.where(ok[:, None], scores, neg)
            w = jax.nn.softmax(scores, axis=-1)
            o = _grouped_values(w, ev)
        with scope("tables"):
            return o, {"pk": pkf.reshape(nb, bt, h, dh),
                       "pv": pvf.reshape(nb, bt, h, dh),
                       "table": table, "base": base, "floor": floor,
                       "filled": filled + lengths}

    @classmethod
    def _stream_attend(cls, lc, q, k, v, cache, mask=None):
        """Dense attention of the current chunk's queries over
        cache + chunk. The cache stays ``stream_max_t`` long (static
        shapes — one compiled decode step regardless of how much
        context has streamed); the oldest tokens slide out when the
        window is exceeded.

        ``mask`` (``[N, T]`` 1/0, right-padded) marks the chunk's valid
        prefix per row: this is the resume-from-a-partially-filled-cache
        path, shared by TWO serving callers — chunked prefill (a
        pow2/fixed-width padded suffix chunk continues a prefix-cache
        hit) and the speculative verify attend (every slot's
        [current token | draft] chunk scores in one batched pass, each
        row masked to its own draft length — B rows at B different
        lengths AND different ``filled`` levels share one executable).
        Pad keys never receive weight, pad positions never enter the
        cache (the same roll-the-pad-out-of-view trick as
        ``_prefill_cache``), and ``filled`` advances by each row's true
        chunk length — so a padded chunked continuation streams
        identically to an unpadded one-shot prefill of the same
        tokens, and output position ``i`` of a verify chunk holds
        exactly the logits sequential decode would have produced after
        its first ``i`` chunk tokens (the property speculative
        acceptance rests on — serving/engine.py rewinds rejected
        tails afterwards by moving ``filled`` back).
        ``mask=None`` (the decode hot path) keeps the original,
        roll-free program."""
        if isinstance(cache, dict) and "pk" in cache:
            # block-pool layout (the serving engine's): same
            # streaming contract, storage indirected through per-row
            # block tables — the dense row path below is the net's own
            # streaming cache (``generate``, a cold admission's row)
            return cls._paged_attend(lc, q, k, v, cache, mask)
        tm = lc.stream_max_t
        t = q.shape[2]
        if not lc.causal:
            raise ValueError(
                "non-causal (bidirectional) attention cannot stream: "
                "rnn_time_step continuation would need future tokens; "
                "use causal=True or run output() on full sequences")
        if t > tm:
            raise ValueError(
                f"rnn_time_step continuation chunk of {t} steps exceeds "
                f"stream_max_t={tm}: raise stream_max_t or stream "
                "smaller chunks")
        # Attend over the FULL [cache | chunk] extension (length tm+t)
        # and slice only the returned cache: slicing BEFORE attending
        # would drop cached keys still inside the sliding window of the
        # chunk's EARLY queries (chunked streaming would diverge from
        # one-token-at-a-time streaming once the window saturates).
        with scope("attn/cache"):
            ek = jnp.concatenate([cache["k"], k], axis=2)   # [N,H,tm+t,dh]
            ev = jnp.concatenate([cache["v"], v], axis=2)
        with scope("attn/core"):
            prev = cache["filled"]                    # [N] per-slot lengths
            if mask is None:
                lengths = jnp.full(q.shape[:1], t, jnp.int32)
            else:
                lengths = jnp.sum(mask.astype(jnp.int32), axis=1)  # [N]
            filled = jnp.minimum(prev + lengths, tm)
            scores = _grouped_scores(q, ek) / jnp.sqrt(
                jnp.asarray(q.shape[-1], q.dtype)
            )
            j = jnp.arange(tm + t)                    # extension positions
            i = jnp.arange(t)                         # query i at ext tm+i
            ok = (
                (j[None, :] <= tm + i[:, None])       # causal
                & (j[None, :] >= i[:, None] + 1)      # its last-tm window
            )                                         # [t, tm+t]
            # per-slot validity: cache zeros (or an idle/evicted slot's
            # stale rows — filled == 0 invalidates the whole window) never
            # receive weight, so slots at different fill levels share one
            # batched step without contaminating each other
            ok = ok[None] & (j[None, None, :] >= tm - prev[:, None, None])
            if mask is not None:
                # chunk pad (positions past each row's true chunk length)
                # is invalid too — a padded chunk attends exactly like its
                # unpadded counterpart
                ok = ok & ((j[None, None, :] < tm)
                           | (j[None, None, :] - tm
                              < lengths[:, None, None]))
            neg = jnp.asarray(-1e30, q.dtype)
            scores = jnp.where(ok[:, None], scores, neg)
            w = jax.nn.softmax(scores, axis=-1)
            o = _grouped_values(w, ev)
        with scope("attn/cache"):
            if mask is None:
                ck, cv = ek[:, :, -tm:, :], ev[:, :, -tm:, :]
            else:
                # rotate each row's chunk pad out of view before windowing
                # (see _right_align — shared with _prefill_cache)
                ek, ev = cls._right_align(t - lengths, ek, ev)
                ck, cv = ek[:, :, -tm:, :], ev[:, :, -tm:, :]
        return o, {"k": ck, "v": cv, "filled": filled}


@register_bean("TransformerBlock")
@dataclasses.dataclass
class TransformerBlock(BaseRecurrentLayer):
    """Conf bean: a full pre-LN transformer block — LayerNorm →
    multi-head self-attention → residual, then LayerNorm → FFN
    (``ffn_mult``× inner width, gelu) → residual.

    This is the convergence-grade building unit the bare
    ``MultiHeadSelfAttention`` stack lacks: without the residual path
    and pre-LN, width ≥ 1024 stacks diverge at any useful lr (measured,
    an earlier round's BENCHMARKS.md flagship section), which is the standard
    transformer-training result. NEW capability vs the 2015 reference
    (predates attention; SURVEY.md §5.7 mandates first-class
    long-context), layered on the framework's [N, C, T] recurrent
    layout so it composes with RnnOutputLayer and the sp/pp/tp
    parallel trainers.

    When ``n_in != n_out`` the block first applies a learned input
    projection (no residual across it — the standard embed step);
    homogeneous interior blocks (n_in == n_out) are pure residual and
    therefore stackable under the pipeline trainer's homogeneous-stage
    mode."""

    n_heads: int = 4
    causal: bool = True
    ffn_mult: int = 4
    ffn_activation: str = "gelu"
    ring_axis: Optional[str] = None
    ring_block_size: Optional[int] = None
    sp_mode: str = "ring"
    use_flash: Optional[bool] = None
    use_flash_paged: Optional[object] = None
    stream_max_t: int = 512

    scope_group = None

    def serving_caches(self):
        return (attention_cache(self),)


def attention_cache(bean) -> PagedCache:
    """The one cache an attention layer holds in a served engine: keys
    and values a token, ``stream_max_t`` tokens back."""
    kv_heads = getattr(bean, "n_kv_heads", bean.n_heads)
    return PagedCache(
        bean.stream_max_t, group=bean.n_heads // kv_heads,
        token_width=kv_heads * (getattr(bean, "head_dim", 0)
                                or bean.n_out // bean.n_heads))


def _layer_norm(x, g, b, eps=1e-5):
    from deeplearning4j_tpu.nn.layers.normalization import layer_norm

    return layer_norm(x, g, b, axis=-1, eps=eps)


class TransformerBlockImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        d_in, d = lc.n_in, lc.n_out
        dff = lc.ffn_mult * d
        kq, kk, kv, ko, k1, k2, ki = jax.random.split(key, 7)
        scheme = conf.resolved("weight_init")
        dist = conf.resolved("dist")
        p = {
            "ln1_g": jnp.ones((d,), dtype),
            "ln1_b": jnp.zeros((d,), dtype),
            "Wq": init_weights(kq, (d, d), scheme, dist, dtype),
            "Wk": init_weights(kk, (d, d), scheme, dist, dtype),
            "Wv": init_weights(kv, (d, d), scheme, dist, dtype),
            "Wo": init_weights(ko, (d, d), scheme, dist, dtype),
            "bo": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype),
            "ln2_b": jnp.zeros((d,), dtype),
            "W1": init_weights(k1, (d, dff), scheme, dist, dtype),
            "b1": jnp.zeros((dff,), dtype),
            "W2": init_weights(k2, (dff, d), scheme, dist, dtype),
            "b2": jnp.zeros((d,), dtype),
        }
        if d_in != d:
            p["Wi"] = init_weights(ki, (d_in, d), scheme, dist, dtype)
        return p

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.activations import activation

        lc = conf.layer
        h, d = lc.n_heads, lc.n_out
        if d % h:
            raise ValueError(f"n_out {d} not divisible by n_heads {h}")
        dh = d // h
        tp = _tp_scope()
        if tp is not None:
            h = _tp_local_heads(h, tp)
        # an interior block's entry re-layout goes with the norm that
        # reads it
        with scope("embed" if "Wi" in params else "norm"):
            x = cls.maybe_dropout(conf, x, train, rng)
            xt = jnp.transpose(x, (0, 2, 1))  # [N, T, C]
            if "Wi" in params:
                xt = xt @ params["Wi"]

        hn = _layer_norm(xt, params["ln1_g"], params["ln1_b"])

        def split_heads(m):
            y = hn @ m  # [N, T, D] (local D/TP under tp head sharding)
            return jnp.transpose(
                y.reshape(y.shape[0], y.shape[1], h, dh), (0, 2, 1, 3)
            )  # [N, H, T, dh]

        with scope("attn/qkv"):
            q = split_heads(params["Wq"])
            k = split_heads(params["Wk"])
            v = split_heads(params["Wv"])
        # (from outside every scope: ``_attend_core`` places its own)
        o, state = AttentionImpl._attend_core(
            lc, q, k, v, state, train, mask)
        with scope("attn/out"):
            o = jnp.transpose(o, (0, 2, 1, 3)).reshape(
                o.shape[0], o.shape[2], h * dh)  # [N, T, D] (local heads)
            if tp is not None:
                # row-parallel Wo: one all-reduce per block completes the
                # partial sum; LN params, biases, and the (replicated) FFN
                # see the full-width activation — the Megatron block with
                # only the attention heads sharded (the KV cache is the
                # memory that matters in serving; serving/tp.py). f32
                # accumulate + f32 psum + one rounding, as in
                # AttentionImpl.apply — per-shard bf16 rounding before the
                # sum flips argmaxes vs single-chip
                attn = jax.lax.dot_general(
                    o, params["Wo"], (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                attn = jax.lax.psum(attn, tp[0]).astype(o.dtype)
            else:
                attn = o @ params["Wo"]
            xt = xt + (attn + params["bo"])

        h2 = _layer_norm(xt, params["ln2_g"], params["ln2_b"])
        with scope("ffn"):
            ffn = activation(lc.ffn_activation)(
                h2 @ params["W1"] + params["b1"])
            xt = xt + (ffn @ params["W2"] + params["b2"])

            out = jnp.transpose(xt, (0, 2, 1))  # [N, D, T]
            if mask is not None:
                out = out * mask[:, None, :]
        return out, state


# Beans carrying the shared attention-core options (n_heads, causal,
# ring_axis/sp_mode, use_flash, stream_max_t). Parallel trainers
# dispatch on this tuple, not the concrete classes, so both stay
# covered by tp head-sharding, sp ring validation, etc.
ATTENTION_BEANS = (MultiHeadSelfAttention, TransformerBlock)


def guard_streamable(named_layer_beans) -> None:
    """Raise if any layer bean carries ring_axis: rnn_time_step streams
    on a single device, and sequence-parallel attention cannot (shared
    by MultiLayerNetwork.rnn_time_step and
    ComputationGraph.rnn_time_step)."""
    for name, lc in named_layer_beans:
        if getattr(lc, "ring_axis", None):
            raise ValueError(
                f"rnn_time_step streams on a single device; layer "
                f"{name} is configured with ring_axis="
                f"{lc.ring_axis!r} (sequence parallelism) and cannot "
                "stream — rebuild the conf with ring_axis=None for "
                "serving")


def _should_use_flash(use_flash, q, mask) -> bool:
    """Whether an unmasked whole sequence takes the kernel
    (:func:`_flash_attention`): ``use_flash`` None = this rule, False =
    the dense program always, True = the kernel or an error. Static a
    program, so a trace's kernel names say which ran. (The paged decode
    analogue is :func:`_should_use_flash_paged`: it gates on the backend
    and tile health, not on length.)"""
    if use_flash is False:
        return False
    t, dh = q.shape[2], q.shape[3]
    kernel_ok = (jax.default_backend() == "tpu" and mask is None
                 and t >= 256 and t % 128 == 0
                 and (dh <= 128 or dh % 128 == 0))
    if use_flash and not kernel_ok:
        raise ValueError(
            "use_flash=True requires the TPU backend, no mask, a "
            "sequence length >= 256 divisible by 128, and head dim "
            "<= 128 or divisible by 128")
    if use_flash is None:
        # Auto mode: from the published training contexts up, where
        # the kernel keeps the [T, T] scores out of HBM (what this chip
        # measured there is in ``_flash_kernel``; below 2048 the two
        # were never timed against each other on it). t % 512 == 0
        # keeps the tiles at 512 or more: tiles of 512 already lose
        # 4-16% to 1024, and a T like 2176 (= 128 * 17) would run tiles
        # of 128. From 8192 up the dense program's float32 scores are
        # 4.3 GB a row of 16 heads, so memory overrides tile health.
        return kernel_ok and t >= 2048 and (t % 512 == 0 or t >= 8192)
    return bool(use_flash)


@functools.lru_cache(maxsize=None)
def _flash_kernel(heads: int, t: int, causal: bool, grouped: bool,
                  interpret: bool):
    """The library's block-sparse kernel over one sequence of ``t``, its
    backward fused: for ``heads`` query heads each with a KV head of its
    own or, ``grouped``, for the ``heads`` of ONE KV head's group (the
    library's MQA form). The mask information is host-side numpy, made
    once a geometry and kept (0.5 s at 32 heads of 8,192; identical
    heads share one table): concrete arrays, whatever trace asks first.

    Tiles are the largest of (1024, 512, 256, 128) dividing ``t`` for
    queries and keys alike, the forward scoring 512 keys at a time, the
    backward the whole tile. Swept on one v5e at both training cells'
    geometries, forward + backward of one layer (``PERF.md`` section 6,
    PR 43): 8 x 16 heads of 128 at T = 2,048 5.55 ms, 2 x 32 heads of 64
    over 8 KV heads at T = 8,192 31.3 ms, where the kernel this replaced
    (``pallas.ops.tpu.flash_attention`` at 1,024-tiles, keys repeated a
    group) took 9.80 and 47.4. Tiles of 512 score fewer masked pairs and
    are slower (5.77, 36.4): a grid step costs more than the pairs it
    saves; of the 2,048-tiles most do not fit VMEM and the rest are
    slower; the two-kernel backward reads 7.11 and 37.1; key tiles of
    2,048 in the backward halve the fused form's partial dQ at T = 8,192
    for the same time (31.5) and lose at 2,048 (6.19), so one rule
    serves both."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    n = next(b for b in (1024, 512, 256, 128) if t % b == 0)
    blocks = splash.BlockSizes(
        block_q=n, block_kv=n, block_kv_compute=min(n, 512),
        block_q_dkv=n, block_kv_dkv=n, block_kv_dkv_compute=n,
        use_fused_bwd_kernel=True)
    one = (masks.CausalMask if causal else masks.FullMask)((t, t))
    make = (splash.make_splash_mqa_single_device if grouped
            else splash.make_splash_mha_single_device)
    with jax.ensure_compile_time_eval():
        return make(masks.MultiHeadMask([one] * heads),
                    block_sizes=blocks, interpret=interpret)


def _flash_attention(q, k, v, causal, *, interpret: bool = False):
    """Whole-sequence attention without the O(T²) score matrix: the
    library's block-sparse ("splash") Pallas kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``) a batch row.
    A tile no query of which sees a key is neither fetched nor scored
    and only the diagonal tiles apply the mask; the backward forms dQ,
    dK and dV in ONE pass over the scores (five products and one
    ``exp`` where a dq and a dkv kernel take seven and two) and reads
    the forward's log-sum-exp one row a head. bf16 operands, float32
    scores, sums and accumulators. ``interpret`` runs the kernel through
    the Pallas interpreter (the CPU parity tests).

    ``k`` / ``v`` may hold fewer heads than ``q``: the keys are never
    repeated, the kernel runs a KV head at a time over its group's
    query heads and dK, dV come back a KV head. (The library's MHA form
    takes such ``k`` as it is and its kernels read the same time alone,
    31.0 ms against 31.3; in LFM2's step the grouped call is 1.9%
    faster end to end, 62.8 k tokens/s against 61.6 k: the rotary and
    projection fusions XLA builds around the call's operands come out
    cheaper, ``PERF.md`` section 6, PR 43.)"""
    b, h, t, dh = q.shape
    hk = k.shape[1]
    with scope("attn/core"):
        q = q * dh ** -0.5
    if hk == h:
        kernel = _flash_kernel(h, t, causal, False, interpret)
        return jax.vmap(kernel)(q, k, v)
    kernel = _flash_kernel(h // hk, t, causal, True, interpret)
    o = jax.vmap(jax.vmap(kernel))(
        q.reshape(b, hk, h // hk, t, dh), k, v)
    return o.reshape(b, h, t, dh)


#: query-tile rows of the paged kernel: a chunk that is a multiple of
#: this walks the block list once per tile, so VMEM holds one tile's
#: accumulators whatever the chunk length
_PAGED_Q_TILE = 128

#: query tiles of at most this many rows (decode, a verify chunk) score
#: a compute block on the vector unit, all heads at once; longer tiles
#: keep one MXU product per head
_PAGED_SHORT_TILE = 8

#: VMEM the kernel's pool buffers (K and V, two slots each) may take,
#: and the most table entries one compute block holds
_PAGED_POOL_VMEM = 4 << 20
_PAGED_MAX_BLOCKS = 16
_PAGED_VMEM_LIMIT = 32 << 20

#: first position of a table entry the walk skipped: past every query,
#: so the copy that was never made is masked like a future key
_PAGED_FAR = 1 << 30


def _paged_q_tile(t: int) -> int:
    """Query rows one grid step holds: whole ``_PAGED_Q_TILE`` tiles of
    a prefill chunk, else the chunk itself (decode, verify)."""
    return _PAGED_Q_TILE if t % _PAGED_Q_TILE == 0 else t


def _paged_grid(n_rows: int, t: int) -> Tuple[int, int]:
    """The paged kernel's grid: one step a (row, query tile)."""
    return n_rows, t // _paged_q_tile(t)


def _paged_table_entries(ring_slots: int, window: int,
                         block_tokens: int, t: int) -> int:
    """ntab, the consecutive logical blocks a row's ``t`` queries can
    reach through a ``window``-token sliding window (never the whole
    ring): what the gather program gathers and the kernel walks."""
    return min(ring_slots, (window + t - 2) // block_tokens + 2)


def _paged_blocks_per_step(block_tokens: int, n_heads: int,
                           head_dim: int, pool_dtype, ntab: int,
                           grp: int = 1, t: int = 1) -> int:
    """P, the table entries (pool blocks) one compute block of the paged
    kernel holds, for ``n_heads`` KV heads each serving ``grp`` query
    heads and a chunk of ``t`` queries: as many as keep the
    double-buffered K and V scratch inside ``_PAGED_POOL_VMEM`` at the
    pool's tiled size (the head axis pads to the dtype's sublane tile,
    the head dim to 128 lanes), at most ``_PAGED_MAX_BLOCKS`` and never
    more than the walk is long. The short form lifts a compute block to
    float32 before any arithmetic, so there the block is reckoned at 4
    bytes a number whatever the pool holds: its time is the vector
    unit's work a key, and on a bf16 pool a block of 8 entries beat one
    of 16 at every occupancy but a full batch of full windows (my chip
    run, PR 35: ``PERF.md`` section 6)."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    if _paged_tile_form(grp, _paged_q_tile(t), n_heads) == "short":
        itemsize = max(itemsize, 4)
    sublanes = 8 * max(1, 4 // itemsize)
    entry = (block_tokens * -(-n_heads // sublanes) * sublanes
             * -(-head_dim // 128) * 128 * itemsize)
    return max(1, min(_PAGED_POOL_VMEM // (4 * entry),
                      _PAGED_MAX_BLOCKS, ntab))


def _paged_tile_form(grp: int, tq: int, n_kv_heads: int) -> str:
    """How the paged kernel scores a compute block (its docstring has
    the three forms): ``"short"``, a short tile of ungrouped heads on
    the vector unit in float32; ``"flat"``, a short tile in ONE MXU
    product over the block's (key, head) rows, for grouped heads; and
    ``"tile"``, a product a KV head, for everything else."""
    if tq > _PAGED_SHORT_TILE:
        return "tile"
    if grp == 1:
        return "short"
    return "flat" if n_kv_heads & (n_kv_heads - 1) == 0 else "tile"


def _paged_walk_tiles(table, base, floor, filled, bt, tm, p_blk, t):
    """Per query tile of a dispatch's host tables (numpy; the
    arithmetic of :meth:`AttentionImpl._paged_attend` and the kernel's
    ``span``/``reach``): ``hit`` [B, whole compute blocks], the entries
    mapped and reachable by some query of the tile, and ``trips`` [B],
    the compute blocks the kernel's loop walks for them, of the rows
    that map anything."""
    table = np.asarray(table)
    # a row that maps nothing (an idle slot) has no hit and no trip
    rows = np.flatnonzero((table >= 0).any(axis=1))
    table, base, floor, filled = (np.asarray(a)[rows] for a in (
        table, base, floor, filled))
    s_ring = table.shape[1]
    ntab = _paged_table_entries(s_ring, tm, bt, t)
    lo_blk = np.maximum(floor, np.maximum(filled - tm + 1, 0)) // bt
    e = np.arange(-(-ntab // p_blk) * p_blk, dtype=np.int32)[None, :]
    g = lo_blk.astype(np.int32)[:, None] + e
    at = np.arange(len(filled))[:, None], g % s_ring
    mapped = (table[at] >= 0) & (base[at] == g * bt) & (e < ntab)
    e_lo = np.where(mapped, e, ntab).min(axis=1)
    e_hi = np.where(mapped, e, -1).max(axis=1)
    tq = _paged_q_tile(t)
    for i in range(t // tq):
        q0 = filled + i * tq
        lo = np.maximum(np.maximum(q0 - tm + 1, 0) // bt - lo_blk, e_lo)
        hi = np.minimum((q0 + tq - 1) // bt - lo_blk, e_hi)
        yield (mapped & (e >= lo[:, None]) & (e <= hi[:, None]),
               np.where(hi >= lo, hi // p_blk - lo // p_blk + 1, 0))


def paged_walk_stats(table, base, floor, filled, *, block_tokens: int,
                     window: int, blocks_per_step: int,
                     chunk: int = 1) -> Tuple[int, int, int]:
    """What one call of the paged kernel does with a dispatch's host
    tables, in one pass over them: ``live``, the pool blocks it copies
    — entries mapped and reachable by some query of a tile, summed
    over the query tiles; ``walked``, the pool blocks' worth of keys
    it scores, ``blocks_per_step`` for every compute block that holds
    a live entry (``live / walked`` is the share of the kernel's
    arithmetic spent on keys that exist); and ``steps``, the steps it
    pays: its grid (:func:`_paged_grid`, a step a row and query tile,
    idle rows too) plus the trips of the loop inside a step, one a
    compute block between the first and the last entry the tile
    reaches (those that score keys are ``walked / blocks_per_step``)."""
    rows, tiles = _paged_grid(len(np.asarray(filled)), chunk)
    live = walked = 0
    steps = rows * tiles
    for hit, trips in _paged_walk_tiles(table, base, floor, filled,
                                        block_tokens, window,
                                        blocks_per_step, chunk):
        live += int(hit.sum())
        walked += blocks_per_step * int(hit.reshape(
            len(hit), hit.shape[1] // blocks_per_step, blocks_per_step
        ).any(axis=2).sum())
        steps += int(trips.sum())
    return live, walked, steps


def paged_walk_counts(table, base, floor, filled, **geometry
                      ) -> Tuple[int, int]:
    """``(live, walked)`` of :func:`paged_walk_stats`."""
    return paged_walk_stats(table, base, floor, filled, **geometry)[:2]


def paged_steps_paid(table, base, floor, filled, **geometry) -> int:
    """``steps`` of :func:`paged_walk_stats`."""
    return paged_walk_stats(table, base, floor, filled, **geometry)[2]


def _should_use_flash_paged(toggle, block_tokens: int,
                            head_dim: int, t: int = 1) -> bool:
    """Dispatch rule for the pallas paged-attention kernel
    (:func:`_paged_flash_attention`, a loop over a row's compute blocks
    of several pool blocks each) vs the XLA gather-by-block-table program in
    :meth:`AttentionImpl._paged_attend`:

    - ``None`` (auto): the kernel on the TPU backend when the block
      shape tiles healthily — ``block_tokens`` a multiple of 8
      (sublane), ``head_dim`` a multiple of 128 (lane), and a query
      chunk ``t`` that is one tile (``t <= _PAGED_Q_TILE``: decode,
      verify) or whole tiles (a pow2 prefill chunk); toy/test
      geometries below the native tile and odd long chunks stay on
      the XLA gather. Off-TPU always falls back to the gather program
      (the kernel's DMA scheduling is TPU-specific; interpret mode
      exists for parity testing, not serving).
    - ``True``: force the kernel — raises off-TPU or on unhealthy
      tiles instead of silently degrading.
    - ``False``: the XLA gather program always.
    - ``"interpret"``: the kernel through the pallas interpreter on
      any backend — the CPU bit-parity testing hook (tier-1 gates the
      kernel's semantics against the gather program with it).

    How many pool blocks a compute block holds and which unit scores them
    is the kernel's own business (``_paged_blocks_per_step``,
    ``_PAGED_SHORT_TILE``): every shape this rule admits lowers either
    way (tests/test_attention_tpu_lowering.py).

    Both paths enforce the SAME value-level masking rule: gathered /
    DMA'd V lanes outside ``[floor, filled + written)`` are zeroed at
    the VALUE level, not just score-masked, because a recycled dirty
    block's NaN survives a zero softmax weight (0 x NaN = NaN — the
    PR 6 poisoned-neighbour fix; the kernel parity tests poison a
    freed block to prove the kernel preserves it)."""
    if toggle is False or (toggle is None
                           and jax.default_backend() != "tpu"):
        return False
    if toggle == "interpret":
        return True
    tiles_ok = (block_tokens % 8 == 0 and head_dim % 128 == 0
                and (t <= _PAGED_Q_TILE or t % _PAGED_Q_TILE == 0))
    if toggle is None:
        return tiles_ok
    if jax.default_backend() != "tpu" or not tiles_ok:
        raise ValueError(
            "use_flash_paged=True requires the TPU backend, "
            "block_tokens % 8 == 0, head dim % 128 == 0 and a query "
            f"chunk of at most {_PAGED_Q_TILE} or a multiple of it "
            f"(got block_tokens={block_tokens}, head_dim={head_dim}, "
            f"chunk={t} on {jax.default_backend()!r}); use "
            "'interpret' for off-TPU parity testing or None for auto "
            "fallback")
    return True


# jitted on its own so that a program of many layers traces and lowers
# the kernel once (same shapes, same ``tm``), not once a layer: the
# body's per-entry branches make a trace cost about a second
@functools.partial(jax.jit, static_argnames=("tm", "interpret", "causal",
                                             "stats"))
def _paged_flash_attention(q, pk, pv, bid, bval, lo_blk, floor,
                           filled, lengths, *, tm: int,
                           interpret: bool = False, causal: bool = True,
                           stats: bool = False):
    """Fused pallas paged-attention kernel (ISSUE 12, regridded in
    ISSUE 25, the walk moved into the body in ISSUE 30; pallas_guide.md,
    boom_attention_tricks.md §8-12 — the in-repo flash kernel's decode
    successor). One grid step = one (row, query tile):
    ``grid = _paged_grid(B, t)``. Inside it a loop walks the row's
    COMPUTE BLOCKS, a compute block being
    ``P = _paged_blocks_per_step(...)`` consecutive table entries
    (``P x bt`` keys, all heads), in ascending order, from the first to
    the last that holds an entry some query of the tile can reach
    (causal above, the window's lower edge below) between the row's
    first and last mapped entry. The bounds are read from the row's own
    tables, so the trips follow the data: an idle slot, or a tile below
    its row's floor, runs none — it initialises, emits 0 and costs its
    grid step — and a row of 400 tokens walks 4 compute blocks of the
    17 its table has room for (:func:`paged_steps_paid` counts both).

    - the BLOCK TABLE rides as scalar-prefetch operands and the pools
      stay in HBM (``memory_space=ANY``). The kernel fetches a compute
      block itself: one async copy per *mapped and reachable* entry —
      a pool block ``[bt, H, dh]`` is contiguous — into a two-slot
      VMEM scratch ``[2, P*bt, H, dh]`` each for K and V. A trip starts
      the NEXT trip's copies into the other slot before it waits for
      its own; a row's last trip looks ahead over the grid for the next
      (row, tile) that walks anything and starts ITS first compute
      block, and the grid's first step does the same for the first, so
      no row exposes a copy's latency at its start whatever idle slots
      lie between. The slot parity is a loop carry inside a row and an
      SMEM scalar across grid steps; both grid axes stay
      ``"arbitrary"``, the prefetch depends on the order. No
      ``[B, ntab*bt, H, dh]`` gather ever materializes.
    - an entry the walk skipped leaves stale scratch behind; its keys
      take a position past every query (``_PAGED_FAR``), so they are
      masked at the score AND the value level like any future key.
    - scoring follows the query tile. A short tile
      (``<= _PAGED_SHORT_TILE`` rows: decode, verify) works on the
      vector unit in the pool's own ``[key, H, dh]`` layout — multiply
      by the query row, reduce over lanes — all heads at once, float32;
      a longer tile (prefill) keeps one MXU product per head against
      the compute block's ``P x bt`` keys. Either way ONE online-softmax
      rescale per compute block (running max / sum / accumulator in
      VMEM scratch) under the SAME validity rule as the XLA gather
      program: entry mapped, causal, last-``tm`` window, per-row floor.
    - value-level masking: V lanes outside ``[floor, filled + len)``
      are zeroed BEFORE the weighted sum — a zero softmax weight does
      not kill a NaN (0 x NaN = NaN), so a recycled dirty block would
      otherwise poison its next owner through masked lanes (the PR 6
      fix, preserved here; `_should_use_flash_paged` documents the
      shared contract). Fully-masked blocks contribute exactly zero
      mass (``p`` is zeroed where invalid, so ``l`` never counts
      them — rows with NO valid key anywhere, idle slots, emit 0 like
      the gather path's uniform-softmax-over-zeroed-values).

    - GROUPED KV HEADS (ISSUE 31): the pool holds ``Hkv`` heads and
      ``q`` ``Hq = grp x Hkv``; query head ``h`` reads KV head
      ``h // grp``. A compute block is copied once for all of them. A
      short tile of grouped heads takes the FLAT form: the compute
      block lands in VMEM as one 2-D array of (key, KV head) rows, ONE
      MXU product scores all ``t x Hq`` query rows against all of them
      and a row keeps its own KV head's columns (``Hkv`` times the
      products needed, no slice and no re-layout; the vector-unit form
      at ``grp`` rows a key head read 12% of the HBM peak at group 6, a
      head-by-head MXU form 10%: my chip run, PR 31). A longer tile is
      handed over with a KV head's ``grp x tq`` query rows together, so
      one MXU product a KV head scores the whole group against its
      keys. ``grp`` 1 is the kernel of ISSUE 30, trace for trace.

    - TWO MORE FORMS (ISSUE 41), both off by default and both decided
      while tracing, so the call without them is the program it was.
      ``causal=False``: a table read WHOLE up to ``filled`` by every
      query of the chunk (entry ``e`` is seen iff ``floor <= e <
      filled``; ``lengths`` 0): a cache that is not a sequence of the
      queries' own positions, EVA's summaries (nn/layers/eva.py).
      ``stats=True``: the call also hands back each query row's
      ``log(sum exp)`` of its scores (``-1e30`` where it saw nothing)
      and its output in float32, so that two walks (two tables, two
      pools) merge into ONE softmax outside; ungrouped heads only.

    Shapes: q [B, Hq, t, dh]; pk/pv [nb, bt, Hkv, dh] (post-scatter);
    bid/bval [B, ntab] int32 (pool block per logical block, validity;
    padded here to whole compute blocks, and reduced here to each
    row's first and last mapped entry); lo_blk/floor/filled/lengths
    [B] int32. Returns o [B, H, t, dh]. Queries tile by
    ``_PAGED_Q_TILE`` when ``t`` is a multiple of it (a prefill
    chunk); shorter chunks (decode, verify) are one tile. Parity vs
    the gather program is argmax-level (one float reduction runs
    blockwise, the other over the flat gather — the PR 6 paged-parity
    convention), gated per tier-1 workload in tests/test_serving_tp.py
    via interpret mode."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b_sz, hq_sz, t, dh = q.shape
    bt, h_sz = pk.shape[1], pk.shape[2]
    # grouped KV heads: query head ``h`` reads KV head ``h // grp``
    grp = hq_sz // h_sz
    ntab = bid.shape[1]
    tq = _paged_q_tile(t)
    grid = _paged_grid(b_sz, t)
    nq = grid[1]
    total = b_sz * nq
    # a KV head's rows of a tile: its group's query heads
    n_rows = grp * tq
    # the three forms of a compute block's scoring (the docstring):
    # short, one key head a query head, on the vector unit; flat, a
    # short tile of GROUPED heads, all heads in one MXU product; tile
    form = _paged_tile_form(grp, tq, h_sz)
    short, flat = form == "short", form == "flat"
    if stats and grp > 1:
        raise NotImplementedError(
            "stats=True hands back ungrouped heads' sums only")
    p_blk = _paged_blocks_per_step(bt, h_sz, dh, pk.dtype, ntab, grp, t)
    n_keys = p_blk * bt
    scale = dh ** -0.5
    # a row's first and last mapped entry bound its walk (none mapped,
    # an idle slot: ntab and -1, an empty walk)
    entry = jnp.arange(ntab, dtype=jnp.int32)
    e_lo = jnp.min(jnp.where(bval > 0, entry, ntab), axis=1)
    e_hi = jnp.max(jnp.where(bval > 0, entry, -1), axis=1)
    pad = -ntab % p_blk
    if pad:
        bid = jnp.pad(bid, ((0, 0), (0, pad)))
        bval = jnp.pad(bval, ((0, 0), (0, pad)))
    # a short tile is handed over (and returned) as [B, t, H, dh], the
    # pool's own minor layout, so a query row is one [H, dh] read.
    # A flat tile as [B, 1, t x Hq, dh], row ``(i x grp + g) x Hkv + h``
    # being position ``i`` of query head ``h x grp + g``: its KV head is
    # the row number's low bits; the pools as [nb, bt x Hkv, dh], the
    # same bytes, so that a compute block lands in VMEM as ONE 2-D
    # array of (key, head) rows with nothing to re-lay.
    # A longer tile as [B x tiles, Hkv, grp x tq, dh]: a KV head's rows
    # are its group's heads, tile by tile, so ONE MXU product a KV head
    # scores them all
    if short:
        q = jnp.swapaxes(q, 1, 2)
    elif flat:
        q = jnp.transpose(q.reshape(b_sz, h_sz, grp, t, dh),
                          (0, 3, 2, 1, 4)).reshape(b_sz, 1, t * hq_sz, dh)
        pk = pk.reshape(pk.shape[0], bt * h_sz, dh)
        pv = pv.reshape(pv.shape[0], bt * h_sz, dh)
    elif grp > 1:
        q = jnp.transpose(q.reshape(b_sz, h_sz, grp, nq, tq, dh),
                          (0, 3, 1, 2, 4, 5)).reshape(
                              b_sz * nq, h_sz, n_rows, dh)
    rows = ((n_rows, h_sz) if short else (1, t * hq_sz) if flat
            else (h_sz, n_rows))
    # a pool block's rows in the K/V scratch
    blk_rows = bt * h_sz if flat else bt

    def kernel(bid_ref, bval_ref, lo_ref, floor_ref, filled_ref,
               len_ref, elo_ref, ehi_ref, q_ref, pk_ref, pv_ref, o_ref,
               *rest):
        # (``stats``: the sums' output rides before the scratch)
        lse_ref = rest[0] if stats else None
        kbuf, vbuf, sem, m_ref, l_ref, acc_ref, slot_ref = rest[-7:]
        b = pl.program_id(0)
        i = pl.program_id(1)
        cur = b * nq + i

        def tile_start(bb, ii):
            """The first query position of tile ``ii`` of row ``bb``;
            without a causal edge every query stands at the table's
            last entry."""
            if causal:
                return filled_ref[bb] + ii * tq
            return filled_ref[bb] - 1

        def span(bb, ii):
            """Entries ``[lo, hi]`` of row ``bb`` that some query of
            tile ``ii`` can reach (causal above, last-``tm`` window
            below; the floor is in ``lo_blk`` already) between the
            row's first and last mapped entry, and the compute blocks
            ``[j_lo, j_end)`` that hold them: none (``j_end == j_lo``)
            for an idle slot or a tile below its row's floor."""
            q0 = tile_start(bb, ii)
            lo = jnp.maximum(jnp.maximum(q0 - tm + 1, 0) // bt
                             - lo_ref[bb], elo_ref[bb])
            if causal:
                hi = jnp.minimum((q0 + tq - 1) // bt - lo_ref[bb],
                                 ehi_ref[bb])
            else:       # (an empty table: ``q0`` -1, no entry to reach)
                hi = jnp.where(q0 < 0, -1, jnp.minimum(
                    q0 // bt - lo_ref[bb], ehi_ref[bb]))
            j_lo = lo // p_blk
            return lo, hi, j_lo, jnp.where(hi >= lo, hi // p_blk + 1,
                                           j_lo)

        def reach(bb, jj, lo, hi):
            """Per entry of the compute block: mapped and reachable —
            the one predicate that decides copy, wait and mask."""
            e0 = jj * p_blk
            return [(bval_ref[bb, e0 + p] > 0) & (e0 + p >= lo)
                    & (e0 + p <= hi) for p in range(p_blk)]

        def copies(bb, jj, sl, live, go):
            for p in range(p_blk):
                @pl.when(live[p])
                def _entry(p=p):
                    blk = bid_ref[bb, jj * p_blk + p]
                    rows = pl.ds(p * blk_rows, blk_rows)
                    go(pltpu.make_async_copy(
                        pk_ref.at[blk], kbuf.at[sl, rows], sem.at[0, sl]))
                    go(pltpu.make_async_copy(
                        pv_ref.at[blk], vbuf.at[sl, rows], sem.at[1, sl]))

        def fetch_from(c0, sl):
            """Start, into slot ``sl``, the copies of the first compute
            block of the first (row, tile) at or after grid step ``c0``
            that walks one; nothing when no later step does."""
            def idle(c):
                _, _, j_lo, j_end = span(
                    jnp.minimum(c, total - 1) // nq, c % nq)
                return (c < total) & (j_end == j_lo)

            c = jax.lax.while_loop(idle, lambda c: c + 1, c0)

            @pl.when(c < total)
            def _start():
                bb = c // nq
                lo, hi, j_lo, _ = span(bb, c % nq)
                copies(bb, j_lo, sl, reach(bb, j_lo, lo, hi),
                       lambda dma: dma.start())

        @pl.when(cur == 0)
        def _first():
            slot_ref[0] = 0
            fetch_from(cur, 0)

        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        q0 = tile_start(b, i)                  # tile's first position
        written = filled_ref[b] + len_ref[b]

        def short_block(k0, slot):
            # [keys, H, dh] as the pool holds it; every per-key and
            # per-(key, head) number is [keys, H, 1]
            kpos = jnp.concatenate([
                k0[p] + jax.lax.broadcasted_iota(
                    jnp.int32, (bt, h_sz, 1), 0)
                for p in range(p_blk)], axis=0)
            vlive = (kpos < written) & (kpos >= floor_ref[b])
            kb = kbuf[slot].astype(jnp.float32)
            vb = jnp.where(vlive, vbuf[slot], 0).astype(jnp.float32)
            reachable = kpos >= floor_ref[b]

            def row(r, carry):
                qpos = q0 + r if causal else q0
                ok = reachable & (kpos <= qpos) & (kpos > qpos - tm)
                qv = q_ref[0, r].astype(jnp.float32) * scale  # [H, dh]
                s = jnp.sum(kb * qv[None], axis=2, keepdims=True)
                s = jnp.where(ok, s, -1e30)                # [keys, H, 1]
                m_prev = m_ref[r]                          # [H, 128]
                m_next = jnp.maximum(m_prev, jnp.max(s, axis=0))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.where(ok, jnp.exp(s - m_next[:, :1][None]), 0.0)
                l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=0)
                acc_ref[r] = (alpha[:, :1] * acc_ref[r]
                              + jnp.sum(p * vb, axis=0))
                m_ref[r] = m_next
                return carry

            if tq == 1:
                row(0, None)
            else:
                jax.lax.fori_loop(0, tq, row, None)

        def positions(k0, idx):
            """The position of key ``idx`` of the compute block, whose
            entries start at ``k0``."""
            pos = k0[0] + idx
            for p in range(1, p_blk):
                pos = jnp.where(idx >= p * bt, k0[p] - p * bt + idx, pos)
            return pos

        def flat_block(k0, slot):
            # [key x Hkv + head, dh] as the copies left it: ONE product
            # scores every query row against every (key, head) row, and
            # a row keeps the columns of its own KV head. Eight times
            # the products a head-by-head form would make, on a unit
            # that has them to spare, and no slice or re-layout at all
            n_q, n_col = t * hq_sz, n_keys * h_sz
            shift = h_sz.bit_length() - 1
            col = jax.lax.broadcasted_iota(jnp.int32, (1, n_col), 1)
            kpos = positions(k0, col >> shift)
            row = jax.lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)
            qpos = q0 + functools.reduce(
                jnp.add, [(row >= i * hq_sz).astype(jnp.int32)
                          for i in range(1, t)], 0) if causal else q0
            ok = (((col & (h_sz - 1)) == (row & (h_sz - 1)))
                  & (kpos <= qpos) & (kpos > qpos - tm)
                  & (kpos >= floor_ref[b]))             # [rows, cols]
            vrow = jax.lax.broadcasted_iota(jnp.int32, (n_col, 1), 0)
            vpos = positions(k0, vrow >> shift)
            vlive = (vpos < written) & (vpos >= floor_ref[b])
            vb = vbuf[slot]
            vb = jnp.where(vlive, vb, jnp.zeros_like(vb))
            s = jax.lax.dot_general(
                q_ref[0, 0], kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, -1e30)
            m_prev = m_ref[0]                          # [rows, 128]
            m_next = jnp.maximum(
                m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(ok, jnp.exp(s - m_next[:, :1]), 0.0)
            l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=1,
                                                  keepdims=True)
            acc_ref[0] = (alpha[:, :1] * acc_ref[0]
                          + jax.lax.dot_general(
                              p.astype(vb.dtype), vb,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
            m_ref[0] = m_next

        def tile_block(k0, slot):

            kpos = positions(k0, jax.lax.broadcasted_iota(
                jnp.int32, (1, n_keys), 1))
            qrow = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
            qpos = q0 + (qrow % tq if grp > 1 else qrow) if causal else q0
            ok = ((kpos <= qpos) & (kpos > qpos - tm)
                  & (kpos >= floor_ref[b]))              # [rows, keys]
            # value-level masking (see docstring): one [keys, 1]
            # column — the written-span rule is q-position-independent
            vpos = positions(k0, jax.lax.broadcasted_iota(
                jnp.int32, (n_keys, 1), 0))
            vlive = (vpos < written) & (vpos >= floor_ref[b])
            for h in range(h_sz):
                kb = kbuf[slot, :, h, :]                   # [keys, dh]
                vb = vbuf[slot, :, h, :]
                vb = jnp.where(vlive, vb, jnp.zeros_like(vb))
                s = jax.lax.dot_general(
                    q_ref[0, h], kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(ok, s, -1e30)
                m_prev = m_ref[h]                        # [rows, 128]
                m_next = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.where(ok, jnp.exp(s - m_next[:, :1]), 0.0)
                l_ref[h] = (alpha * l_ref[h]
                            + jnp.sum(p, axis=1, keepdims=True))
                acc_ref[h] = (alpha[:, :1] * acc_ref[h]
                              + jax.lax.dot_general(
                                  p.astype(vb.dtype), vb,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32))
                m_ref[h] = m_next

        lo, hi, j_lo, j_end = span(b, i)

        def trip(j, slot):
            # the walk is in order, so what comes next is this row's
            # next compute block or, after its last, the first one of
            # the next (row, tile) that has any: started before this
            # trip waits for its own, in the slot the last trip left
            last = j + 1 == j_end

            @pl.when(jnp.logical_not(last))
            def _next():
                copies(b, j + 1, 1 - slot, reach(b, j + 1, lo, hi),
                       lambda dma: dma.start())

            pl.when(last)(lambda: fetch_from(cur + 1, 1 - slot))
            live = reach(b, j, lo, hi)
            copies(b, j, slot, live, lambda dma: dma.wait())
            # an entry's first key position; a skipped entry's keys
            # sit past every query
            k0 = [jnp.where(live[p], (lo_ref[b] + j * p_blk + p) * bt,
                            _PAGED_FAR) for p in range(p_blk)]
            pl.when(functools.reduce(jnp.logical_or, live))(
                lambda: (short_block if short else flat_block if flat
                         else tile_block)(k0, slot))
            return 1 - slot

        # the slot parity outlives the grid step: the next (row, tile)
        # that walks finds its first compute block where the last trip
        # before it put it
        slot_ref[0] = jax.lax.fori_loop(j_lo, j_end, trip, slot_ref[0])

        l = l_ref[...][..., :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)
                    ).astype(o_ref.dtype)
        if stats:
            lse_ref[0] = jnp.where(
                l_ref[...] == 0, -1e30,
                m_ref[...] + jnp.log(jnp.where(l_ref[...] == 0, 1.0,
                                               l_ref[...])))

    def q_map(b, i, *refs):
        if short or flat:
            return (b, 0, 0, 0)
        return (b * nq + i, 0, 0, 0) if grp > 1 else (b, 0, i, 0)

    pool_rows = (n_keys * h_sz,) if flat else (n_keys, h_sz)
    o_spec = pl.BlockSpec((1, *rows, dh), q_map)
    o_shape = jax.ShapeDtypeStruct(
        q.shape, jnp.float32 if stats else q.dtype)
    if stats:
        o_spec = [o_spec, pl.BlockSpec((1, *rows, 128), q_map)]
        o_shape = [o_shape, jax.ShapeDtypeStruct(
            (*q.shape[:-1], 128), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, *rows, dh), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((2, *pool_rows, dh), pk.dtype),
            pltpu.VMEM((2, *pool_rows, dh), pv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((*rows, 128), jnp.float32),      # running max
            pltpu.VMEM((*rows, 128), jnp.float32),      # running sum
            pltpu.VMEM((*rows, dh), jnp.float32),       # accumulator
            pltpu.SMEM((1,), jnp.int32),                # K/V slot parity
        ],
    )
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=o_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_PAGED_VMEM_LIMIT),
        interpret=interpret,
    )(bid, bval, lo_blk, floor, filled, lengths, e_lo, e_hi, q, pk, pv)
    if stats:
        # ``[B, H, t, dh]`` float32 and ``[B, H, t]``, whatever the form
        o, lse = o[0], o[1][..., 0]
        if short:
            return jnp.swapaxes(o, 1, 2), jnp.swapaxes(lse, 1, 2)
        return o, lse
    if short:
        return jnp.swapaxes(o, 1, 2)
    if flat:
        return jnp.transpose(o.reshape(b_sz, t, grp, h_sz, dh),
                             (0, 3, 2, 1, 4)).reshape(b_sz, hq_sz, t, dh)
    if grp > 1:
        return jnp.transpose(o.reshape(b_sz, nq, h_sz, grp, tq, dh),
                             (0, 2, 3, 1, 4, 5)).reshape(
                                 b_sz, hq_sz, t, dh)
    return o


def _repeat_kv_heads(q, k, v):
    """``k``/``v`` with each KV head repeated for the query heads it
    serves; the arrays themselves where the counts agree."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _grouped_scores(q, k):
    """``q k^T`` ``[B, Hq, Q, K]`` for ``q`` ``[B, Hq, Q, dh]`` against
    ``k`` ``[B, Hkv, K, dh]``: query head ``h`` reads KV head
    ``h // (Hq / Hkv)``. Equal counts keep the plain product."""
    b, hq, t, dh = q.shape
    hk = k.shape[1]
    if hq == hk:
        return jnp.einsum("bhqd,bhkd->bhqk", q, k)
    s = jnp.einsum("bhgqd,bhkd->bhgqk",
                   q.reshape(b, hk, hq // hk, t, dh), k)
    return s.reshape(b, hq, t, k.shape[2])


def _grouped_values(w, v):
    """``w v`` for weights ``[B, Hq, Q, K]`` and ``v`` ``[B, Hkv, K,
    dh]``, the counterpart of :func:`_grouped_scores`."""
    b, hq, t, nk = w.shape
    hk = v.shape[1]
    if hq == hk:
        return jnp.einsum("bhqk,bhkd->bhqd", w, v)
    o = jnp.einsum("bhgqk,bhkd->bhgqd",
                   w.reshape(b, hk, hq // hk, t, nk), v)
    return o.reshape(b, hq, t, v.shape[3])


def _dense_attention(q, k, v, causal, mask, window=None):
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    neg = jnp.asarray(-1e30, q.dtype)
    if causal:
        cm = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            # key j seen by query i iff i - window < j <= i
            cm = cm & ~jnp.tril(jnp.ones((t, t), bool), -window)
        scores = jnp.where(cm, scores, neg)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :] > 0, scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)
