"""Continuous-batching decode engine over a paged KV block pool.

The serving counterpart of ``MultiLayerNetwork.generate``: instead of
one request owning the whole batch (and the chip), ``n_slots`` decode
slots are multiplexed across many concurrent requests — the
continuous-batching pattern of modern inference stacks, grown out of
the reference's streaming ``rnnTimeStep`` contract (SURVEY §1 L1).
Keys and values live in ONE layout: a device pool of fixed-size blocks
per attention layer (``[kv_blocks, block_tokens, H, dh]``,
serving/block_pool.py); a slot owns a host-side block table, and the
radix prefix trie (serving/prefix_cache.py) leases the same blocks.

Dataflow per scheduling round (one ``step()``):

0. **Failure handling** (ISSUE 3; every knob defaults off = the
   bit-identical PR 2 engine) — requeue fault victims whose backoff
   elapsed, apply the round's scheduled :class:`FaultPlan` events,
   sweep deadlines/queue-timeouts (expired requests terminate wherever
   they are: queued, mid-admission, or mid-decode — eviction only
   releases block references, so neighbours never stall).
1. **Admit** — while a slot is free and requests are queued, prefill
   the next prompt at batch 1 (right-padded to a pow2 length bucket,
   masked — streams identically to an unpadded prefill, see
   ``AttentionImpl._prefill_cache``) through the net's own streaming
   cache, then scatter that dense B=1 row into freshly allocated pool
   blocks (``paged_scatter``; the block table is a traced operand, so
   admission never retraces) and the first sampled token into the
   slot. With the radix prefix cache enabled (``prefix_cache_rows``,
   serving/prefix_cache.py), the longest cached prefix of the prompt
   is SPLICED — its blocks referenced from the new slot's table, no
   copy — and only the *suffix* prefills, straight into the table;
   every completed admission leaves its blocks leased to the trie, so
   shared system prompts/templates prefill once.
2. **Chunked prefill** (``prefill_chunk > 0``) — suffix prefill splits
   into fixed-width masked chunks that resume the carried cache
   (``AttentionImpl._stream_attend`` with a chunk mask), scheduled
   BETWEEN decode rounds under the scheduler's per-round token budget
   (``Scheduler.plan_chunks``; policy knob ``decode``- vs
   ``ttft``-priority), so a long prompt never stalls running slots
   longer than the budget — one chunk, under decode priority. With
   ``adaptive_prefill=True`` the budget steps down/up with queue
   pressure (``Scheduler.adapt_budget``) so decode latency degrades
   smoothly under overload instead of cliffing.
3. **Decode** — ONE jitted ``lax.scan`` advances ALL slots
   ``decode_chunk`` tokens with the pool cache in the scan carry and
   sampling on device (serving/sampler.py). Idle slots ride along
   harmlessly: their ``filled == 0`` row masks every cached position
   (nn/layers/attention.py), so live slots are never contaminated.
   With **speculative decoding** on (``spec_draft_len=K``, ISSUE 4) a
   round whose n-gram tables propose anything PREPENDS one batched
   verify pass to the decode scan: each greedy slot's host-side draft
   table (serving/spec.py) proposes up to K next tokens, a single
   masked chunk-continuation forward (the same
   ``AttentionImpl._stream_attend`` path chunked prefill uses) scores
   all B slots' drafts at once, per-slot accepted-prefix lengths are
   computed on device (serving/sampler.py ``greedy_acceptance``),
   rejected tails are rolled back by moving each row's ``filled``
   back (the tokens stay where they lie in their blocks, masked), the
   model's own token at the first divergence commits as the bonus
   token, and the decode scan resumes
   from the verified state — both dispatches land in ONE host
   round-trip, so a speculative round commits
   ``decode_chunk + accepted + 1`` tokens per slot where a plain round
   commits ``decode_chunk``: the accepted drafts ride free on the
   round's weight reads, and the round COUNT never exceeds the
   spec-off engine's (the win degrades to zero under hostile
   workloads instead of inverting). Greedy output is bit-identical to
   plain decode (accepted tokens ARE the greedy tokens, by
   construction). Rounds with no drafts anywhere run the plain decode
   executable alone; acceptance rates feed
   ``Scheduler.record_acceptance``, which steps the live K down
   (never below 1) when acceptance is poor and back up when it
   recovers, and verify width bills against the same per-round budget
   prefill chunks do (``Scheduler.plan_chunks``).
4. **Detect & quarantine** (``paranoid=True``) — ONE extra jitted
   finiteness check over the pool + sampled ids (the single new
   executable of the failure-handling layer; its verdict is per
   BLOCK, mapped to slots through the host tables). A non-finite slot
   is quarantined: its block references released (a poisoned block is
   scrubbed when its last reference drops), poisoned prefix-cache
   entries invalidated, the victim re-queued with capped retry +
   exponential backoff (terminal ``finish_reason="fault"`` past the
   cap). Healthy
   slots are bit-unaffected — the same row-independence that lets
   idle slots ride along.
5. **Evict** — requests that hit ``max_new_tokens`` (or ``eos_id``)
   free their slot without stalling the batch; the slot's blocks
   return to the free list (those the trie or another slot still
   references stay resident).

**Incremental delivery** (ISSUE 5; default off = bit-identical): with
``on_delta=callback`` (or ``emit_deltas=True`` + ``drain_deltas()``),
every COMMITTED token surfaces the round it commits — the admission's
first token, decode-chunk tokens, and verify-accepted speculative
tokens, but never a rejected draft tail (emission happens after the
rewind and after the paranoid sweep) and never a duplicate across
fault retries (per-request high-water mark, snapshotted as
``delta_sent``; greedy retries reproduce the streamed prefix
bit-identically, so suppression is exact — a SAMPLING victim that
already streamed terminates ``"fault"`` instead of retrying, since a
redrawn sequence could not be spliced onto the streamed prefix). This
is what the serving gateway (serving/gateway.py) fans out to
streaming HTTP clients.

``snapshot()`` captures everything host-side (queue, per-slot request
metadata + generated ids, RNG key, prefix-trie prefixes, retry state)
as a plain dict; ``DecodeEngine.restore`` rebuilds the device-side KV
state by re-prefilling the recorded tokens through the SAME chunked
prefill path, so a restarted process finishes the same ids (greedy:
bit-identical — asserted by the chaos gate in
tests/test_serving_faults.py).

Compile-count guarantees (asserted in tests/test_serving_engine.py,
tests/test_serving_prefix_cache.py, tests/test_serving_faults.py and
tests/test_serving_spec.py): ONE decode-step executable, ONE
``paged_scatter`` and ONE ``paged_tok`` executable (a cold admission's
row and first token), ONE
health-check executable (paranoid mode only — the only addition of the
failure layer), ONE verify executable per pow2 draft-width bucket
(speculative mode only — O(log spec_draft_len) total), ONE
chunk-continuation executable per distinct suffix width (exactly one
in chunked mode — every chunk is ``prefill_chunk`` wide; one per pow2
suffix bucket otherwise), and one cold-prefill executable per pow2
prompt bucket — admission order, slot index, request length, cache
hits, sampling config, faults, deadlines, retries, and draft content
never retrace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.layers.attention import guard_streamable
from deeplearning4j_tpu.nn.streaming import scan_length_bucket
from deeplearning4j_tpu.profiler.scopes import scope
from deeplearning4j_tpu.serving.block_pool import KindTables
from deeplearning4j_tpu.serving.faults import FaultEvent, FaultPlan, poison_rows
from deeplearning4j_tpu.serving.kv_memory import KvMemory
from deeplearning4j_tpu.serving.prefix_cache import RadixPrefixCache
from deeplearning4j_tpu.serving.sampler import (
    residual_sample,
    sample_tokens,
    stochastic_acceptance,
)
from deeplearning4j_tpu.serving.scheduler import (
    GenerationResult,
    Request,
    Scheduler,
)
from deeplearning4j_tpu.serving.spec import NgramDraftTable
from deeplearning4j_tpu.serving.tenancy import (
    TenantRegistry,
    WeightedFairScheduler,
)
from deeplearning4j_tpu.serving.tp import TPContext

#: restore() kwarg sentinel — ``None`` is a meaningful toggle value
#: (auto mode) for ``use_flash_paged``
_UNSET = object()


#: VMEM of a TPU core (v5e: 128 MiB): the compiler may stage a whole
#: buffer that fits it through it
_TPU_VMEM_BYTES = 128 << 20


def _compiler_options(leaf_bytes: int) -> Optional[Dict[str, str]]:
    """What the engine tells the TPU's compiler about its programs,
    given the bytes of its smallest pool leaf: where a leaf FITS the
    chip's VMEM (the block's serving cell's at bf16: 92 MB), that a
    buffer is not to be staged through VMEM for a use that touches
    under nine tenths of it. Every program takes the pool, donated, and
    writes a few rows of it; such a leaf the compiler otherwise copies
    in for the append's scatter and out again, a whole leaf each way a
    step, on the HBM the weights' products are bound by (my chip runs,
    PR 35: a decode round 43.7 -> 41.6 ms; a leaf of 137 MB never
    moved). None for a pool of larger leaves, whose programs compile as
    they always did, and off the TPU, where the option does not
    exist."""
    if jax.default_backend() != "tpu" or leaf_bytes > _TPU_VMEM_BYTES:
        return None
    return {"xla_tpu_msa_inefficient_use_to_copy_ratio": "0.9"}


@dataclasses.dataclass
class _Slot:
    request: Request
    tokens: List[int]
    prefix_reused: int = 0
    ttft_s: Optional[float] = None
    #: prefix-cache entry this admission spliced from (quarantine
    #: scrubs it if the slot turns out poisoned), or None on a cold
    #: admission
    hit_row: Optional[int] = None
    #: speculative-decoding counters: tokens drafted for / accepted by
    #: this request (surface on its GenerationResult)
    spec_drafted: int = 0
    spec_accepted: int = 0


@dataclasses.dataclass
class _Pending:
    """An admission in flight: the slot is reserved, the suffix is
    part-way through (chunked) prefill, and ``rnn`` carries a COLD
    admission's dense B=1 streaming state accumulated so far (None
    before the first cold chunk, and throughout a warm admission,
    whose chunks append through ``tab``). ``seq`` is the
    token sequence being prefilled — the request's prompt for a live
    admission, prompt + generated ids for a snapshot-restore rebuild."""

    request: Request
    slot: int
    rnn: Any
    tok: Any                      # last chunk's sampled token, [1]
    done: int                     # suffix tokens already prefilled
    matched: int                  # prompt tokens reused from the cache
    hit: Any                      # PrefixHit lease to release, or None
    seq: List[int] = dataclasses.field(default_factory=list)
    #: the slot's block table: spliced trie blocks on a warm hit
    #: (suffix chunks then append THROUGH it, zero-copy), or None
    #: until a cold admission's dense prefill completes and scatters
    #: into freshly allocated blocks
    tab: Optional[KindTables] = None
    #: what each prefill program counted (device scalars), added to
    #: ``stats`` when the first token is fetched
    counts: List[Any] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if not self.seq:
            self.seq = [int(t) for t in self.request.prompt]

    @property
    def remaining(self) -> int:
        return len(self.seq) - self.matched - self.done


@dataclasses.dataclass
class _InflightRound:
    """One dispatched-but-unlanded decode round (``async_rounds=True``,
    ISSUE 14): the device arrays whose fetch was deferred to the next
    ``step()``, plus everything the landing needs to commit them. The
    ``rids`` map guards against slots whose request was cancelled or
    deadline-evicted between dispatch and landing — their rows are
    discarded, neighbours are untouched (the same per-row independence
    idle slots ride on)."""

    active: List[int]
    rids: Dict[int, int]              # slot -> request id at dispatch
    drafts: Optional[Dict[int, List[int]]]
    verify_out: Optional[Tuple]       # (lens, emitted, acc) or None
    seq: Any                          # device [B, T], unfetched
    t0: float                         # perf_counter at dispatch start
    td0: float                        # phase clock at decode dispatch
    dispatch_end: float               # phase clock after dispatch
    ver_dt: float                     # verify dispatch wall
    #: fused multi-round scan (ISSUE 16): rounds fused into this
    #: dispatch (1 = a plain stepped round), the decode tokens the
    #: DEVICE wrote per slot (n_rounds * decode_chunk — the paged
    #: table advance), and the device [B] committed-prefix lengths
    #: (None on the stepped path: the whole chunk is the prefix)
    n_rounds: int = 1
    decode_tokens: int = 0
    n_valid: Any = None
    #: what the decode program counted (device scalars by name)
    counts: Any = None


class _PhaseClock:
    """Host-side per-request phase clock (ISSUE 7 tentpole): every
    request accumulates a monotone, DISJOINT-interval phase breakdown
    — queue wait, admission (split cold-prefill / chunked-suffix /
    prefix-splice), per-round decode / verify / stall —
    plus an ordered event timeline, one entry per phase transition
    (capped: a pathological million-round request cannot grow the
    recorder without bound). Because every attributed interval is a
    sub-interval of [submit, terminal] and no two overlap, the phase
    sums can never exceed the request's end-to-end wall time — the
    invariant the gateway soak gates over HTTP.

    Fault retries and paged preemptions open a NEW attempt (the
    timeline keeps absolute ``t_s`` offsets from submit, so attempts
    read as consecutive chapters of one request), and ``enqueue_t``
    resets so each attempt's queue wait is its own."""

    #: ordered-event cap PER ATTEMPT; past it, events are counted
    #: (``events_dropped``) instead of stored — phase totals stay exact
    MAX_EVENTS = 512

    __slots__ = ("submit_t", "enqueue_t", "attempts", "ttft_s",
                 "first_delta_s", "last_commit_t", "rounds")

    def __init__(self, submit_t: float):
        self.submit_t = submit_t
        self.enqueue_t = submit_t
        self.attempts: List[Dict[str, Any]] = [self._attempt()]
        self.ttft_s: Optional[float] = None
        #: submit -> the request's first delta handed to ``on_delta``
        #: / the delta buffer: when a streaming consumer can first see
        #: a token, which is later than ``ttft_s`` (when admission
        #: fetched it) by however long the engine held it
        self.first_delta_s: Optional[float] = None
        self.last_commit_t: Optional[float] = None
        self.rounds = 0

    @staticmethod
    def _attempt() -> Dict[str, Any]:
        return {"phases": {}, "events": [], "events_dropped": 0}

    def add(self, now: float, phase: str, dur_s: float,
            **detail: Any) -> None:
        """Accumulate ``dur_s`` into ``phase`` and append a timeline
        event at ``now`` (offsets are relative to submit)."""
        att = self.attempts[-1]
        phases = att["phases"]
        phases[phase] = phases.get(phase, 0.0) + dur_s
        if len(att["events"]) < self.MAX_EVENTS:
            event = {"t_s": now - self.submit_t, "phase": phase,
                     "dur_s": dur_s}
            if detail:
                event.update(detail)
            att["events"].append(event)
        else:
            att["events_dropped"] += 1

    def event(self, now: float, phase: str, **detail: Any) -> None:
        self.add(now, phase, 0.0, **detail)

    def new_attempt(self, now: float, reason: str) -> None:
        """A retry/preemption/defer requeued the request: close the
        current attempt and start the next (distinct attempts in the
        timeline — the soak's retried-request gate)."""
        self.event(now, "requeue", reason=reason)
        self.attempts.append(self._attempt())
        self.enqueue_t = now

    def phase_totals(self) -> Dict[str, float]:
        totals: Dict[str, float] = {}
        for att in self.attempts:
            for phase, dur in att["phases"].items():
                totals[phase] = totals.get(phase, 0.0) + dur
        return totals

    def summary(self, now: float, tokens: int) -> Dict[str, Any]:
        """The terminal timing breakdown (``GenerationResult.timing``
        + the flight-recorder record)."""
        p = self.phase_totals()
        admission = (p.get("admit_cold", 0.0)
                     + p.get("admit_chunk", 0.0)
                     + p.get("admit_splice", 0.0))
        return {
            "queue_wait_s": p.get("queue_wait", 0.0),
            "admission_s": admission,
            "admission_cold_s": p.get("admit_cold", 0.0),
            "admission_chunked_s": p.get("admit_chunk", 0.0),
            "admission_splice_s": p.get("admit_splice", 0.0),
            "decode_s": p.get("decode", 0.0),
            "verify_s": p.get("verify", 0.0),
            "stall_s": p.get("stall", 0.0),
            "ttft_s": self.ttft_s,
            "first_delta_s": self.first_delta_s,
            "e2e_s": now - self.submit_t,
            "attempts": len(self.attempts),
            "rounds": self.rounds,
            "tokens": int(tokens),
        }


#: one-line HELP text per serving track, emitted on /v1/metrics via
#: ``Tracer.describe`` (registered by ``DecodeEngine`` at init)
SERVING_TRACK_HELP = {
    "serving_ttft_s": "submit-to-first-token latency distribution",
    "serving_itl_s": "inter-token latency distribution (per-round "
                     "commit gap / tokens committed)",
    "serving_queue_wait_s": "queue-entry-to-admission-start wait "
                            "distribution (per attempt)",
    "serving_round_s": "scheduling-round wall-time distribution",
    "serving_e2e_s": "submit-to-terminal latency distribution",
    "serving_tokens_generated": "tokens committed across all requests",
    "serving_admitted": "requests admitted into a slot",
    "serving_evicted": "slots freed (finish, cancel, quarantine)",
    "serving_prefill_tokens": "prompt tokens prefilled",
    "serving_prefill_tokens_skipped": "prompt tokens served from the "
                                      "prefix cache instead",
    "serving_deadline_expired": "requests past their end-to-end "
                                "deadline",
    "serving_shed": "requests shed by backpressure",
    "serving_cancelled": "requests cancelled by the caller",
    "serving_quarantined": "slots quarantined by the paranoid sweep",
    "serving_retries": "fault-retry re-admissions",
    "serving_retry_failures": "requests that exhausted the retry cap",
    "serving_tp_dispatch_s": "sharded (tensor-parallel) device "
                             "dispatch wall-time distribution "
                             "(decode/verify dispatches; tp > 1 "
                             "engines only)",
    "serving_tp_shards": "tensor-parallel shard count (1 = "
                         "single-chip engine)",
    "serving_tp_kv_bytes": "per-shard device KV bytes "
                           "({shard=...}-labeled; total/TP under "
                           "head sharding)",
    "serving_blocks_free": "free KV pool blocks (per-shard "
                           "{shard=...} copies under tp > 1 — block "
                           "ids are shard-invariant, so every shard "
                           "reports the same count over its own "
                           "head-sliced bytes)",
    "serving_blocks_used": "used KV pool blocks (per-shard copies "
                           "under tp > 1, as serving_blocks_free)",
    "serving_frag_tokens": "allocated-but-masked pool tokens "
                           "(per-shard copies under tp > 1)",
    "serving_qos_preempted": "slots recompute-preempted by the "
                             "weighted-fair scheduler (over-quota "
                             "tenant evicted for a waiting "
                             "same-or-higher-priority arrival; "
                             "tenancy-enabled engines only)",
    "serving_kv_import_s": "cross-replica KV import wall time "
                           "(device scatter + trie seed per shipped "
                           "prefix; ISSUE 14)",
    "serving_admission_warm_s": "admission device-work wall for "
                                "requests that reused a cached "
                                "prefix (splice + suffix "
                                "prefill) — the warm half of the "
                                "warm-vs-recompute comparison",
    "serving_admission_cold_s": "admission device-work wall for "
                                "requests prefilled from scratch — "
                                "the recompute half of the "
                                "warm-vs-recompute comparison",
    "serving_kv_exports": "warmed prefixes exported to peers "
                          "(ISSUE 14 KV transfer plane)",
    "serving_kv_imports": "warmed prefixes imported from peers "
                          "(ISSUE 14 KV transfer plane)",
    "serving_host_step_s": "inter-dispatch host wall (previous "
                           "round's token sync to the next decode "
                           "dispatch) — the per-round host-loop cost "
                           "fused decode amortizes over K rounds "
                           "(ISSUE 16)",
    "serving_fused_rounds": "rounds fused per decode scan dispatch "
                            "(the pow2 K-bucket actually run; "
                            "fused_rounds > 0 engines only, "
                            "ISSUE 16)",
    "serving_kv_spill_s": "trie-victim spill wall (host copy + pack "
                          "of the staged device gather, off the "
                          "decode hot path; ISSUE 17 KV tier)",
    "serving_kv_reload_s": "tier-reload wall (host/disk payload "
                           "re-imported via the jitted kv_import "
                           "scatter + trie re-seed; ISSUE 17)",
    "serving_kv_tier_hits": "prefix lookups answered per tier "
                            "({tier=hbm|host|disk} labeled; hbm = "
                            "trie hits, host/disk = tier reload "
                            "matches; ISSUE 17)",
    "serving_kv_tier_spills": "trie victims admitted to the spill "
                              "tier (ISSUE 17)",
    "serving_kv_tier_reloads": "spilled prefixes reloaded into the "
                               "trie (ISSUE 17)",
    "serving_kv_tier_drops": "spilled prefixes lost (budget "
                             "overflow, reload fault, clear; "
                             "ISSUE 17)",
    "serving_kv_tier_host_bytes": "payload bytes resident in the "
                                  "host-DRAM tier (gauge; ISSUE 17)",
    "serving_kv_tier_disk_bytes": "payload bytes resident in the "
                                  "disk ring (gauge; ISSUE 17)",
}


def _request_dict(req: Request) -> Dict[str, Any]:
    """Plain-dict form of a Request (snapshot wire format)."""
    return {
        "prompt": [int(t) for t in req.prompt],
        "max_new_tokens": int(req.max_new_tokens),
        "temperature": float(req.temperature),
        "top_k": None if req.top_k is None else int(req.top_k),
        "eos_id": None if req.eos_id is None else int(req.eos_id),
        "id": req.id,
        "deadline_s": req.deadline_s,
        "queue_timeout_s": req.queue_timeout_s,
        "trace": req.trace,
        # tenancy identity (ISSUE 13): restore must bill the same
        # tenant the drained process did, or the snapshot would
        # launder a flooder's work onto the default quota
        "tenant": req.tenant,
        "priority": req.priority,
    }


def _targs(req: Request) -> Dict[str, Any]:
    """Span-args fragment carrying the request's fleet trace context
    (ISSUE 10) — empty for untraced requests, so local-only traffic
    adds zero bytes per span."""
    return {"trace": req.trace} if req.trace else {}


def _request_from(d: Dict[str, Any]) -> Request:
    return Request(**d)


def _lm_shape_of(net):
    """(forward, vocab, named layer beans) for a MultiLayerNetwork or
    an LM-shaped single-input/single-output ComputationGraph. The
    forward signature is ``(params, state, x, mask, rnn) ->
    (out [B, V, T], new_rnn, counts)``, ``counts`` being what the
    layers counted in the pass (int32 scalars by name, {} for most
    nets); a MultiLayerNetwork's also takes ``head_at`` ``[B]`` (the
    head at one position a row, ``T`` = 1 out) and ``live`` ``[B]``
    (which rows hold a request, for layers that ask)."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    if isinstance(net, ComputationGraph):
        in_name, out_name, vocab = net.lm_shape()

        def forward(params, state, x, mask, rnn, live=None):
            # (no graph layer asks which rows are live)
            acts, _, new_rnn = net._forward_fn(
                params, state, {in_name: x}, None, False,
                masks=None if mask is None else {in_name: mask},
                rnn_state=rnn)
            return acts[out_name], new_rnn, {}

        beans = [(name, lv.conf.layer)
                 for name, lv in net._layer_vertices.items()]
        return forward, vocab, beans

    vocab = net.conf.confs[0].layer.n_in
    out_bean = net.conf.confs[-1].layer
    if vocab != getattr(out_bean, "n_out", None):
        raise ValueError(
            "DecodeEngine requires an LM-shaped net (first-layer n_in "
            f"== output n_out; got {vocab} vs "
            f"{getattr(out_bean, 'n_out', None)})")

    def forward(params, state, x, mask, rnn, head_at=None, live=None):
        counts = {}
        out, _, new_rnn = net._forward_fn(
            params, state, x, None, False, feature_mask=mask,
            rnn_state=rnn, head_at=head_at, live=live, counters=counts)
        return out, new_rnn, counts

    beans = [(str(i), c.layer) for i, c in enumerate(net.conf.confs)]
    return forward, vocab, beans


class DecodeEngine:
    """Slot-multiplexed batched decoding for one LM-shaped network.

    Submit requests (``submit``), then ``run()`` drains queue + slots
    and returns ``{request_id: GenerationResult}`` — or drive one
    scheduling round at a time with ``step()`` to interleave
    ``cancel()``/``snapshot()`` with progress. Greedy requests
    (temperature 0, the default) produce ids bit-identical to a
    sequential ``net.generate(prompt, n)`` call per request.

    ``decode_chunk`` is the continuous-batching granularity: the batch
    advances that many tokens per dispatch (amortizing host round
    trips) and admissions/evictions happen at chunk boundaries.

    The engine ADOPTS its net. Under mixed precision (float32 masters,
    a bf16 ``compute_dtype``) construction casts the weights to the
    compute dtype once (``net.compute_params``: every layer but the
    output layer, which keeps its master dtype) and rebinds
    ``net.params`` to that tree, releasing the masters: every program
    computes with the values it computed with before, rounded once and
    not once a dispatch, and the served model holds 2 bytes a weight.
    A served net is therefore no longer a training net (``fit`` on it
    would train the bf16 parameters); whoever serves a model owns the
    net it hands over, as ``dl4j-tpu serve`` owns the one it restores.
    A net already resident at its compute dtype is left as it is.
    ``stats["param_bytes"]`` / ``["param_bytes_cast"]`` say what is
    resident and how much of it construction cast.

    Keys and values live in a pool of ``kv_blocks`` blocks of
    ``block_tokens`` tokens per attention layer (the engine's only KV
    layout); a slot's block table grows a block at a time and frees
    blocks that slide out of every layer's window. A cell of the pool
    has the dtype the layers compute keys and values in (the net's
    compute dtype where it has one, else its master dtype), so a token
    costs ``2 x KV heads x head dim x that width`` bytes a layer:
    ``stats["kv_bytes_per_token"]`` over all layers and
    ``["kv_dtype_bytes"]`` say what the pool holds (a float32-master /
    bf16-compute net: 2 bytes a number, the numbers the layers made).
    ``prefix_cache_rows > 0`` enables the radix prefix cache (a trie
    of at most that many entries, each leasing pool blocks;
    serving/prefix_cache.py):
    admissions splice the longest cached prefix of their prompt and
    prefill only the suffix. ``prefill_chunk > 0`` enables chunked
    (non-blocking) admission: suffix prefill runs in fixed-width chunks
    between decode rounds, paced by ``admission_policy`` ("ttft" or
    "decode") and ``prefill_budget`` (tokens per round; see
    ``Scheduler.plan_chunks``).

    ``spec_draft_len=K`` (default 0 = off, the bit-identical PR 3
    engine) enables self-speculative decoding (ISSUE 4): per-slot
    n-gram draft tables (``draft_source="ngram"``, serving/spec.py)
    propose up to K next tokens per greedy slot per round, ONE batched
    verify pass scores every slot's draft (masked chunk continuation —
    one weight read for up to K+1 tokens per slot), accepted prefixes
    commit, rejected tails rewind out of the KV cache, the model's
    own token at the divergence point rides along as the bonus token,
    and the round's decode chunk resumes from the verified state in
    the same host round-trip (accepted tokens are pure profit per
    round; a hostile workload degrades to plain-decode throughput
    instead of below it). Greedy output is bit-identical to the
    spec-off engine (acceptance IS greedy-match); rounds with no
    drafts run plain decode alone; the live K adapts to measured
    acceptance between 1 and the configured ceiling
    (``Scheduler.record_acceptance``). Per-request acceptance counters
    surface on ``GenerationResult.spec_drafted`` / ``spec_accepted``.

    Failure-handling knobs (ISSUE 3; ALL default off — the engine is
    then bit-identical to the PR 2 engine):

    - ``Request.deadline_s`` / ``Request.queue_timeout_s`` — per-
      request end-to-end and queue-wait budgets; expiry terminates the
      request wherever it is with partial tokens and
      ``finish_reason="deadline"`` (or ``"shed"`` for queue timeout).
    - ``cancel(rid)`` — terminate a queued, retrying, admitting, or
      running request (``finish_reason="cancelled"``, partial tokens).
    - ``max_queue`` + ``shed_policy`` ("reject-new" | "shed-oldest") —
      bounded admission queue; the shed victim's result is
      ``finish_reason="shed"``.
    - ``adaptive_prefill`` — queue pressure (depth x estimated
      suffix-prefill tokens) steps the per-round prefill budget
      down/up (``Scheduler.adapt_budget``) so decode latency degrades
      smoothly under overload.
    - ``paranoid`` — per-round jitted finiteness check over the slot
      pool (the failure layer's ONE new executable); non-finite slots
      are quarantined and retried (``max_retries``, exponential
      ``retry_backoff_rounds``), with poisoned prefix-cache entries
      invalidated before the retry.
    - ``fault_plan`` — a seeded :class:`FaultPlan` injecting NaN
      slots, admission failures, stalls, and prefix-cache corruption
      at chosen rounds (serving/faults.py), for chaos testing.
    - ``stall_threshold_s`` — rounds slower than this count as
      ``slow_steps`` (mirrored to the tracer).
    - ``clock`` — injectable time source (``faults.ManualClock`` makes
      deadline/stall tests deterministic); defaults to
      ``time.perf_counter``.

    ``tenants=TenantRegistry(...)`` (ISSUE 13; default None = the
    seed FIFO scheduler, zero per-tenant bookkeeping) swaps in the
    weighted-fair :class:`~deeplearning4j_tpu.serving.tenancy.
    WeightedFairScheduler`: admission ordered priority-then-
    most-underserved with per-tenant token accounting (prompt +
    decode tokens, deficit carry-over), per-tenant slot/queue
    quotas, and recompute-preemption of over-quota or lower-class
    slots when a higher-priority arrival would otherwise wait
    (``_qos_round``; greedy victims requeue and regenerate
    bit-identical ids). Per-request latency histograms and the
    shed/preempted counters gain ``{tenant=...}`` labeled twins, and
    ``GenerationResult.tenant`` echoes the billed tenant.

    ``async_rounds=True`` (ISSUE 14; default off = the synchronous
    engine) double-buffers ``step()``: a dispatched decode round's
    token fetch defers to the START of the next ``step()`` — landed
    before any scheduling decision, so ids (greedy AND sampling) are
    bit-identical and the executable set is unchanged, while the
    inter-round host gap (lock yields, submit handling) overlaps
    device compute instead of inflating decode ITL under admission
    storms. ``export_kv``/``import_kv``
    ship warmed prefixes across replicas (serving/kv_transfer.py).

    ``snapshot()``/``DecodeEngine.restore()`` round-trip the full
    host-side state through a plain dict and rebuild device KV state
    by re-prefilling recorded tokens — crash recovery that finishes
    the same ids. The tenant registry rides the snapshot, so a
    drained engine restores its quotas. An async engine lands its
    in-flight round before snapshotting.

    An optional ``profiler.tracer.Tracer`` receives prefill/admit/
    decode/prefix-splice spans plus per-round counters (admitted,
    evicted, prefix hits/misses, chunks scheduled, tokens decoded,
    occupancy, tokens/sec) and cumulative failure-event tracks
    (``serving_deadline_expired``, ``serving_shed``,
    ``serving_cancelled``, ``serving_faults_injected``,
    ``serving_faults_detected``, ``serving_quarantined``,
    ``serving_retries``, ``serving_retry_failures``,
    ``serving_slow_steps``) so a serving run — and its failures — are
    observable without print-debugging.

    Request-scoped observability (ISSUE 7; pure host bookkeeping —
    greedy ids, RNG consumption, and compile counts are bit-identical
    with it on or off):

    - ``record_timing=True`` (default) stamps a monotone phase clock
      onto every request (:class:`_PhaseClock`): queue wait, admission
      split cold/chunked/splice, per-round decode/verify/stall, and
      per-round commit timestamps. The breakdown surfaces on
      ``GenerationResult.timing`` and feeds five engine-OWNED
      latency histograms (``self.histograms``: ``serving_ttft_s``,
      ``serving_itl_s``, ``serving_queue_wait_s``,
      ``serving_round_s``, ``serving_e2e_s`` —
      :class:`profiler.tracer.Histogram`, registered into the tracer
      when one is attached so ``/v1/metrics`` exports them).
    - ``flight_recorder=256`` keeps the last N TERMINAL requests'
      full traces (ordered phase-event timelines, one chapter per
      retry attempt) in a bounded ring; ``request_trace(rid)`` reads
      one back — the gateway's ``GET /v1/requests/<id>/trace``.
    - every serving span carries the request id(s) in its args
      (``serving.admit``/``prefill``/``prefill_chunk``/
      ``decode_chunk``/``spec_verify``/``prefix_splice``/
      ``cow_copy``), so a Chrome trace is
      filterable by request."""

    #: valid shed policies for a full admission queue: reject the new
    #: arrival, or shed the oldest queued request in its favour
    SHED_POLICIES = ("reject-new", "shed-oldest")

    #: valid speculative draft sources. "ngram" = host-side per-slot
    #: prompt-lookup tables (serving/spec.py) — free drafts, no second
    #: model; the knob exists so a draft-model source can slot in later
    DRAFT_SOURCES = ("ngram",)

    #: idle rounds before a retired tenant's LABELED HISTOGRAM tracks
    #: drop from the scrape (ISSUE 14 satellite): long enough that
    #: any real scrape cadence sees the tenant's final distributions,
    #: short enough that a churning population stays bounded
    TENANT_HIST_RETIRE_ROUNDS = 4096

    #: stats keys that count failure events (each mirrors into a
    #: cumulative tracer track named ``serving_<key>``)
    FAILURE_KEYS = ("deadline_expired", "queue_timeouts", "cancelled",
                    "shed", "faults_injected", "faults_detected",
                    "quarantined", "retries", "retry_failures",
                    "slow_steps")

    def __init__(self, net, n_slots: int = 8, decode_chunk: int = 8,
                 min_prompt_bucket: int = 8, tracer=None, seed: int = 0,
                 prefix_cache_rows: int = 0, prefill_chunk: int = 0,
                 admission_policy: str = "ttft",
                 prefill_budget: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 adaptive_prefill: bool = False,
                 pressure_high: Optional[int] = None,
                 pressure_low: Optional[int] = None,
                 paranoid: bool = False,
                 fault_plan: Optional[FaultPlan] = None,
                 max_retries: int = 2,
                 retry_backoff_rounds: int = 1,
                 stall_threshold_s: Optional[float] = None,
                 clock=None,
                 spec_draft_len: int = 0,
                 draft_source: str = "ngram",
                 on_delta=None,
                 emit_deltas: bool = False,
                 # accepted only because the benchmark's configuration
                 # files pass it: the block pool is the one KV layout
                 paged_kv: bool = True,
                 block_tokens: int = 16,
                 kv_blocks: Optional[int] = None,
                 record_timing: bool = True,
                 flight_recorder: int = 256,
                 tp: int = 1,
                 use_flash_paged=None,
                 tenants: Optional[TenantRegistry] = None,
                 async_rounds: bool = False,
                 fused_rounds: int = 0,
                 kv_host_tier_bytes: int = 0,
                 kv_disk_tier_path: Optional[str] = None,
                 kv_disk_tier_bytes: Optional[int] = None):
        if not paged_kv:
            raise ValueError(
                "paged_kv=False: the dense KV layout was removed in "
                "PR 29; the block pool is the engine's only layout")
        if n_slots < 1:
            raise ValueError(f"n_slots {n_slots} < 1")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk {decode_chunk} < 1")
        if fused_rounds < 0:
            raise ValueError(f"fused_rounds {fused_rounds} < 0")
        if shed_policy not in self.SHED_POLICIES:
            raise ValueError(
                f"shed_policy {shed_policy!r}: expected one of "
                f"{self.SHED_POLICIES}")
        if spec_draft_len < 0:
            raise ValueError(f"spec_draft_len {spec_draft_len} < 0")
        if draft_source not in self.DRAFT_SOURCES:
            raise ValueError(
                f"draft_source {draft_source!r}: expected one of "
                f"{self.DRAFT_SOURCES}")
        if max_retries < 0:
            raise ValueError(f"max_retries {max_retries} < 0")
        if retry_backoff_rounds < 0:
            raise ValueError(
                f"retry_backoff_rounds {retry_backoff_rounds} < 0")
        net.init()
        self.net = net
        self.n_slots = int(n_slots)
        self.decode_chunk = int(decode_chunk)
        #: fused multi-round decode (ISSUE 16): 0 = off (the
        #: bit-identical stepped engine); K > 0 = decision-free rounds
        #: may dispatch as ONE on-device scan of up to K rounds
        #: (pow2-bucketed), amortizing the host step loop over
        #: K * decode_chunk tokens
        self.fused_rounds = int(fused_rounds)
        self.tracer = tracer
        self._forward, self.vocab, beans = _lm_shape_of(net)
        guard_streamable(iter(beans))
        from deeplearning4j_tpu.nn.conf.layers import BaseRecurrentLayer

        #: layers whose streaming state is one row a SLOT (a recurrent
        #: mixer's convolution tail and state), kept slot-major beside
        #: the paged KV leaves; only paged layers get block tables.
        #: Their prefill is masked (nn/layers/hybrid.py), which is what
        #: a right-padded bucket asks of a carried state
        self._state_layers: List[str] = []
        #: some layer is told which slots hold a request (a block whose
        #: experts route live rows only): the decode program takes the
        #: ``live`` operand
        self._wants_live = any(getattr(bean, "wants_live", False)
                               for _, bean in beans)
        #: the layers that page caches, by what each declares
        paged = []
        for name, bean in beans:
            # carried-state recurrents only: RnnOutputLayer is
            # recurrent-typed but stateless, so it streams fine
            if not isinstance(bean, BaseRecurrentLayer):
                continue
            # the engine reads what a bean says of itself, never its
            # class: the caches it pages, or ``()``, one row a slot
            caches = bean.serving_caches()
            if caches is None:
                raise ValueError(
                    f"DecodeEngine streams through the attention KV "
                    f"cache; layer {name} "
                    f"({type(bean).__name__}) carries a recurrent "
                    "state this engine's masked slot prefill does not "
                    "support")
            if caches:
                paged.append((name, bean, caches))
            else:
                self._state_layers.append(name)
        if not paged:
            raise ValueError(
                "DecodeEngine requires at least one attention layer")
        #: the KV memory: the paged layers' caches by KIND, a slot's
        #: block tables, the pools (serving/kv_memory.py)
        self.kv = KvMemory([(name, caches) for name, _, caches in paged],
                           block_tokens)
        # the longest prompt: the widest kind's window (a cold admission
        # of a one-kind net prefills a dense row, which takes no band;
        # several kinds make every admission a paged one, whose programs
        # band each layer by its own window)
        self.window = self.kv.wmax
        #: token ids in through a gather (the first layer embeds them)
        #: or, for a net whose first layer takes ``n_in == vocab``
        #: columns, one-hot
        self._ids_in = bool(getattr(net, "takes_token_ids", False))
        # what a state-carrying or expert net cannot have yet is
        # refused here by the option's name, not served wrongly
        refused = []
        if self._state_layers:
            refused += [
                ("prefix_cache_rows", prefix_cache_rows,
                 "a cached prefix would need the recurrent state at "
                 "its end, which the trie does not keep"),
                ("kv_host_tier_bytes", kv_host_tier_bytes,
                 "the spill tier moves KV blocks only"),
                ("kv_disk_tier_path", kv_disk_tier_path,
                 "the spill tier moves KV blocks only"),
                ("spec_draft_len", spec_draft_len,
                 "a rejected draft cannot be rewound out of a "
                 "recurrent state"),
                ("fused_rounds", fused_rounds,
                 "the fused scan has no counters or live-row operand")]
        if len(self.kv.kinds) > 1:
            two = "over two kinds of KV block"
            refused += [
                ("prefix_cache_rows", prefix_cache_rows,
                 f"the prefix trie leases one kind of block, not a "
                 f"prefix {two}"),
                ("kv_host_tier_bytes", kv_host_tier_bytes,
                 f"the spill tier has no payload {two}"),
                ("kv_disk_tier_path", kv_disk_tier_path,
                 f"the spill tier has no payload {two}"),
                ("spec_draft_len", spec_draft_len,
                 f"a rejected draft's rewind {two} is untested"),
                ("fused_rounds", fused_rounds,
                 f"blocks expire between a fused scan's rounds {two}"),
                ("paranoid", paranoid,
                 f"the health sweep reads one pool, not one {two}"),
                ("fault_plan", fault_plan,
                 f"fault injection poisons one pool, not one {two}")]
            if self._state_layers:
                raise ValueError(
                    f"layers {self._state_layers} carry a slot state "
                    f"and the attention layers have windows "
                    f"{[k.window for k in self.kv.kinds]}: a paged "
                    "admission does not carry slot state yet")
        unsharded = [name for name, bean in beans
                     if not getattr(bean, "shards_over_tp", True)]
        if unsharded:
            refused.append(
                ("tp", tp if tp > 1 else 0,
                 "experts and grouped KV heads are not sharded over tp"))
        layers = (self._state_layers or unsharded
                  or [k.layers for k in self.kv.kinds])
        bad = list(self.kv.misfits(prefill_chunk)) + [
            (option, value, layers, why)
            for option, value, why in refused if value]
        if bad:
            option, value, layers, why = bad[0]
            raise ValueError(
                f"{option}={value!r} is not supported for this net "
                f"(layers {layers}): {why}")
        # -- tensor-parallel head sharding (ISSUE 12; default tp=1 =
        # the bit-identical single-chip engine) -----------------------
        if tp < 1:
            raise ValueError(f"tp {tp} < 1")
        self.tp = int(tp)
        self.tp_ctx: Optional[TPContext] = None
        if self.tp > 1:
            for name, bean, _ in paged:
                if bean.n_heads % self.tp:
                    raise ValueError(
                        f"tp {self.tp} does not divide layer {name}'s "
                        f"n_heads ({bean.n_heads}): head sharding "
                        "slices whole heads")
            self.tp_ctx = TPContext(self.tp,
                                    [name for name, _, _ in paged])
        #: pallas paged-attention kernel toggle (ISSUE 12 satellite):
        #: None = auto (TPU only; the XLA gather path is the off-TPU
        #: fallback), True = force (TPU), False = gather always,
        #: "interpret" = run the kernel in pallas interpret mode (the
        #: CPU parity-testing hook). Stamped onto the net's paging
        #: beans — the engine owns its net in serving deployments.
        self.use_flash_paged = use_flash_paged
        if use_flash_paged is not None:
            for _, bean, _ in paged:
                bean.use_flash_paged = use_flash_paged
        cast_bytes = self._adopt_weights()
        #: the weights every dispatch reads: the net's, resident at
        #: its compute dtype (``_adopt_weights``), sharded where
        #: tp > 1, so that no program casts or re-shards them per call
        self._params = (self.tp_ctx.place(net.params)
                        if self.tp_ctx else net.params)
        self._state = (self.tp_ctx.place(net.state)
                       if self.tp_ctx and net.state else net.state)
        self.spec_draft_len = int(spec_draft_len)
        self.draft_source = draft_source
        if self.spec_draft_len >= self.window:
            raise ValueError(
                f"spec_draft_len {spec_draft_len} must stay below the "
                f"cache window ({self.window}): a verify chunk carries "
                "the draft plus the current token, and a rejected tail "
                "can only be rewound while nothing slid out of the "
                "window")
        self.prefill_chunk = int(prefill_chunk)
        # -- multi-tenant QoS (ISSUE 13; default off = the seed FIFO
        # scheduler, zero per-tenant bookkeeping — tenancy must be
        # free when unused) ------------------------------------------
        self.tenants = tenants
        sched_kwargs = dict(min_bucket=min_prompt_bucket,
                            prefill_chunk=self.prefill_chunk,
                            prefill_budget=prefill_budget,
                            policy=admission_policy,
                            max_queue=max_queue,
                            pressure_high=pressure_high,
                            pressure_low=pressure_low,
                            spec_draft_len=self.spec_draft_len)
        if tenants is not None:
            self.scheduler = WeightedFairScheduler(
                self.window, tenants=tenants, **sched_kwargs)
        else:
            self.scheduler = Scheduler(self.window, **sched_kwargs)
        #: per-tenant latency histograms (``family{tenant="..."}``
        #: tracks, lazily created per tenant seen) and cumulative
        #: per-tenant stats mirrored as labeled tracer samples —
        #: riding the PR 12 labeled-sample scheme so a fleet scrape
        #: shows ``{replica=...,tenant=...}``
        self._tenant_hists: Dict[str, Any] = {}
        self.tenant_stats: Dict[str, Dict[str, int]] = {}
        # -- the KV block pool (ISSUE 6) ------------------------------
        # what one round writes of a slot's cache: a fused scan writes
        # K rounds of decode tokens before the host sees any of them
        round_write = max(
            self.decode_chunk + self.spec_draft_len + 1,
            self.fused_rounds * self.decode_chunk)
        #: the compiler options of the programs built from here on
        #: (``_jit``): decided once the kinds' pools are sized
        self._jit_options = None
        self.block_tokens = bt = self.kv.block_tokens
        #: the engine's counters, and those the KV memory keeps in it
        self.stats: Dict[str, Any] = {}
        self.kv.size(
            kv_blocks=kv_blocks, n_slots=self.n_slots,
            # (with several kinds every admission is paged, and a
            # chunked one's widest dispatch is its chunk)
            dispatch=(self.window if len(self.kv.kinds) == 1
                      else self.prefill_chunk or self.window),
            round_write=round_write, trie_rows=prefix_cache_rows,
            decode_steps=self.decode_chunk, stats=self.stats,
            jit_wrap=self._jit, tp_ctx=self.tp_ctx,
            relieve=self._paged_reserve, span=self._span)
        self.kv_blocks = self.kv.kv_blocks
        #: one entry a slot (``kv.tabs`` itself): its block tables
        self._kv_tabs = self.kv.tabs
        cell = jnp.dtype(net._compute_dtype or net._dtype).itemsize
        self._jit_options = _compiler_options(min(
            k.pool.n_blocks * bt * k.token_width * cell // self.tp
            for k in self.kv.kinds))
        #: the widest kind's allocator (a one-kind net's only one)
        self.block_pool = self.kv.kinds[0].pool
        #: the prefix trie: entries lease pool BLOCKS (zero-copy); the
        #: row count caps entries, the block pool caps bytes
        self.prefix_cache = (
            RadixPrefixCache(prefix_cache_rows, bt,
                             ref_block=self.block_pool.ref,
                             release_block=self.kv.release)
            if prefix_cache_rows else None)
        # -- tiered KV spill store (ISSUE 17; default off = the
        # evict-to-recompute engine). Trie victims export via the
        # jitted kv_gather into packed DKV1 payloads held in a
        # host-DRAM LRU (then a disk ring, then dropped); a trie miss
        # at admission checks the tier BEFORE recomputing and reloads
        # through the jitted kv_import scatter — same pow2-bucketed
        # executables as the cross-replica transfer plane, zero new
        # retraces. ------------------------------------------------
        self.kv_host_tier_bytes = int(kv_host_tier_bytes or 0)
        self.kv_disk_tier_path = kv_disk_tier_path
        self.kv_disk_tier_bytes = kv_disk_tier_bytes
        self.kv_tier = None
        #: spills staged this round: the eviction hook dispatches ONLY
        #: the device gather (async); the host copy + pack drains at
        #: the END of step() so spilling never blocks the decode round
        self._pending_spills: List[Tuple] = []
        if (self.kv_host_tier_bytes or kv_disk_tier_path):
            if self.prefix_cache is None:
                raise ValueError(
                    "the KV spill tier needs prefix_cache_rows > 0 "
                    "(it spills trie victims)")
            from deeplearning4j_tpu.serving.kv_tier import KVTierStore

            self.kv_tier = KVTierStore(
                host_budget_bytes=self.kv_host_tier_bytes,
                disk_path=kv_disk_tier_path,
                disk_budget_bytes=kv_disk_tier_bytes)
            self.prefix_cache.on_evict = self._stage_spill
        #: host-side per-slot n-gram draft tables (None = spec off —
        #: the engine is then the bit-identical PR 3 engine)
        self.spec = (NgramDraftTable() if self.spec_draft_len
                     else None)
        self.shed_policy = shed_policy
        self.adaptive_prefill = bool(adaptive_prefill)
        self.paranoid = bool(paranoid)
        self.fault_plan = fault_plan
        self.max_retries = int(max_retries)
        self.retry_backoff_rounds = int(retry_backoff_rounds)
        self.stall_threshold_s = stall_threshold_s
        self._clock = clock if clock is not None else time.perf_counter
        #: incremental-delivery hook (ISSUE 5): when ``on_delta`` is a
        #: callable (or ``emit_deltas`` is True), every COMMITTED token
        #: is surfaced the round it commits — the first token at
        #: admission, each decode-chunk token, and accepted speculative
        #: tokens (never a rejected draft tail: ``rows`` only ever
        #: carries the accepted prefix + bonus token, and the paranoid
        #: sweep runs before any append). ``on_delta(rid, tokens)``
        #: fires inside ``step()``; with no callback, deltas accumulate
        #: for ``drain_deltas()``. Both default off, and the tracking
        #: is pure host bookkeeping — a delta-less engine is
        #: bit-identical to the PR 4 engine.
        self.on_delta = on_delta
        self.emit_deltas = bool(emit_deltas)
        #: per-request high-water mark of delivered tokens: a fault
        #: retry restarts a request's token list from scratch, but its
        #: already-streamed prefix must not be re-delivered (greedy
        #: retries reproduce the prefix bit-identically, so suppressing
        #: duplicates is exact)
        self._delta_sent: Dict[int, int] = {}
        self._delta_buf: Dict[int, List[int]] = {}
        # -- request-scoped observability (ISSUE 7; pure host
        # bookkeeping — ids, compile counts, and RNG consumption are
        # bit-identical with it on or off) --------------------------
        if flight_recorder < 0:
            raise ValueError(f"flight_recorder {flight_recorder} < 0")
        self.record_timing = bool(record_timing)
        self.flight_recorder = int(flight_recorder)
        #: per-live-request phase clocks (popped at terminal)
        self._clocks: Dict[int, _PhaseClock] = {}
        #: ring of the last ``flight_recorder`` TERMINAL requests'
        #: traces, keyed by id (insertion-ordered: oldest evicted)
        self._flight: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()
        #: engine-OWNED latency histograms (work with tracer=None;
        #: registered into the tracer for /v1/metrics exposition)
        self.histograms: Dict[str, Any] = {}
        if self.record_timing:
            from deeplearning4j_tpu.profiler.tracer import Histogram

            self.histograms = {
                name: Histogram()
                for name in ("serving_ttft_s", "serving_itl_s",
                             "serving_queue_wait_s", "serving_round_s",
                             "serving_e2e_s",
                             "serving_tp_dispatch_s",
                             "serving_kv_import_s",
                             "serving_admission_warm_s",
                             "serving_admission_cold_s",
                             "serving_host_step_s",
                             "serving_fused_rounds",
                             "serving_kv_spill_s",
                             "serving_kv_reload_s")}
        self.describe_metrics()
        # -- async double-buffered rounds (ISSUE 14; default off =
        # the bit-identical synchronous engine): round N's token
        # fetch defers to the START of the next step(), so the
        # inter-round host gap (gateway lock yields, submit handling,
        # scheduler work) overlaps device compute instead of adding
        # to decode ITL. Every scheduling decision still sees exactly
        # the state the synchronous engine would — landing happens
        # before admission/eviction each round — so greedy AND
        # sampling ids are bit-identical (tested) and the executable
        # set is unchanged.
        self.async_rounds = bool(async_rounds)
        self._inflight: Optional[_InflightRound] = None
        #: host-loop observability (ISSUE 16): wall stamp of the last
        #: token sync — the next dispatch's gap to it is the
        #: serving_host_step_s observation
        self._last_sync_end: Optional[float] = None

        self._key = jax.random.key(seed)
        self._slots: List[Optional[_Slot]] = [None] * self.n_slots
        self._pending: List[_Pending] = []
        self._reserved: set = set()       # slots held by _pending
        self._submit_t: Dict[int, float] = {}
        #: {attention layer: {"pk", "pv"}}, each
        #: ``[kv_blocks, block_tokens, H, dh]``; made by the first
        #: admission (``_ensure_paged_pool``)
        self._pool = None
        #: the slot-major state of ``_state_layers``
        #: ({layer: {"conv", "ssm"}}, [n_slots, ...]); it rides every
        #: decode dispatch beside the pool's KV leaves
        self._slot_state: Dict[str, Any] = {}
        self._toks = None                 # [B] int32 current tokens
        #: ``_uploaded``'s (host copy, device array) by operand
        self._sent: Dict[str, Tuple[np.ndarray, Any]] = {}
        self._temps = np.zeros(self.n_slots, np.float32)
        self._top_ks = np.full(self.n_slots, self.vocab, np.int32)
        self._round = 0
        self._terminal: Dict[int, GenerationResult] = {}
        self._retries: Dict[int, int] = {}
        self._requeue: List[Tuple[int, Request]] = []  # (ready_round, req)
        self._admit_fail_pending = 0
        self._has_deadlines = False
        #: ids whose admission has started at least once —
        #: queue_timeout_s bounds time-to-FIRST-admission only, so a
        #: fault-retried request waiting in the queue again is exempt
        self._started: set = set()
        self.stats.update({
            "tokens_generated": 0, "requests_finished": 0,
            "decode_time_s": 0.0, "chunks": 0, "occupancy_sum": 0.0,
            "admitted": 0, "evicted": 0, "prefill_tokens": 0,
            "prefill_tokens_skipped": 0, "chunks_scheduled": 0,
            "spec_rounds": 0, "spec_fallback_rounds": 0,
            "spec_drafted": 0, "spec_accepted": 0,
            "preempted": 0, "paged_admit_deferred": 0,
            "qos_preempted": 0,
            # the resident weights: bytes of ``_params``, and bytes of
            # the masters that construction cast to the compute dtype
            # (0 for a net that was already resident there)
            "param_bytes": sum(
                leaf.size * leaf.dtype.itemsize      # (shapes count too)
                for leaf in jax.tree.leaves(self._params)),
            "param_bytes_cast": cast_bytes,
            # what the jitted programs count themselves and return
            # with the tokens (nn/layers/hybrid.py ``counters``):
            # routed (row, pick) pairs, those on held experts, held
            # experts with a row and the fullest one's rows (both
            # summed over layers and steps), layers x steps, and live
            # rows x state layers x steps; ``prefill_<name>`` is the
            # part of each that prefill programs counted
            **{prefix + name: 0 for prefix in ("", "prefill_")
               for name in ("moe_picks", "moe_picks_held",
                            "moe_experts_touched", "moe_layer_steps",
                            "moe_load_max", "ssm_state_rows")},
            # KV transfer plane (ISSUE 14): cross-replica prefix
            # shipping counters (nonzero only when export/import run)
            "kv_exports": 0, "kv_exported_tokens": 0,
            "kv_imports": 0, "kv_imported_tokens": 0,
            "kv_imported_blocks": 0, "kv_import_declined": 0,
            # tiered KV spill store (ISSUE 17): mirrored from the
            # KVTierStore each refresh (nonzero only with a tier)
            "kv_tier_spills": 0, "kv_tier_reloads": 0,
            "kv_tier_drops": 0, "kv_tier_demotions": 0,
            "kv_tier_hits_host": 0, "kv_tier_hits_disk": 0,
            "kv_tier_host_bytes": 0, "kv_tier_disk_bytes": 0,
            "kv_tier_spill_skipped": 0, "kv_tier_reload_declined": 0,
            "kv_tier_reload_faults": 0, "kv_tier_exports": 0,
        })
        for key in self.FAILURE_KEYS:
            self.stats[key] = 0
        self._build_jits()

    def _adopt_weights(self) -> int:
        """Make the net's weights resident at its compute dtype, once:
        ``net.compute_params`` is the rule every forward pass applies
        to what it is handed, and applied here it leaves the programs
        nothing to cast (a net of float32 masters otherwise pays the
        whole cast in every dispatch). ``net.params`` is rebound to the
        result a layer at a time, so a master is released as soon as
        its copy exists and the two trees never stand side by side; a
        net already resident at its compute dtype keeps every array it
        had. Returns the bytes of the masters that were cast."""
        net, cast_bytes = self.net, 0
        with self._span("serving.weights_cast"):
            for key in list(net.params):
                sub = net.params[key]
                done = net.compute_params({key: sub})[key]
                cast_bytes += sum(
                    old.nbytes for old, new in zip(
                        jax.tree.leaves(sub), jax.tree.leaves(done))
                    if new is not old)
                net.params[key] = done
            if cast_bytes:
                # the span ends when the copies exist, not when their
                # casts were enqueued
                jax.block_until_ready(net.params)
        return cast_bytes

    # -- jitted computations (fixed executables; see module docstring) -
    def _jit(self, fn, donate_argnums=()):
        """The engine's one compilation entry point: plain ``jax.jit``
        at ``tp == 1`` (the bit-identical single-chip engine), or the
        TP context's ``shard_map`` wrapper at ``tp > 1`` — the SAME
        step functions become fully-manual sharded programs over the
        ``tp`` mesh axis with per-leaf specs derived from key paths
        (serving/tp.py). Every jitted computation the engine (or its
        block pool) owns is built through here, so
        the compile-count discipline reads through unchanged."""
        if self.tp_ctx is not None:
            return self.tp_ctx.wrap(fn, donate_argnums=donate_argnums)
        return jax.jit(fn, donate_argnums=donate_argnums,
                       compiler_options=self._jit_options)

    def _place(self, tree):
        """Commit a fresh device pytree onto the TP mesh under its
        derived sharding (no-op at ``tp == 1``). Persistent state the
        engine creates EAGERLY (the block pool, the current-token
        vector) must be placed at creation: an
        uncommitted array entering a sharded executable would compile
        a second specialization the round its committed successor
        returns (the retrace the spike caught)."""
        if self.tp_ctx is not None:
            return self.tp_ctx.place(tree)
        return tree

    def _build_jits(self):
        forward, chunk = self._forward, self.decode_chunk
        ids_in = self._ids_in
        kv = self.kv

        def encode(tok):
            # one position a row for the net's first layer: the ids
            # themselves where it embeds them, else one-hot columns
            if ids_in:
                return tok[:, None]
            with scope("embed"):
                return jax.nn.one_hot(
                    tok, self.vocab, dtype=self.net._dtype)[:, :, None]

        def seen(pool, tabs, filled=None):
            # the per-layer state the forward pass sees: every paged
            # layer's pool leaves beside its KIND's block tables, ONE
            # set a kind and dispatch (``tabs``: ``KvMemory.pack``'s
            # operand, unpacked here). ``filled`` is a scan's carried
            # copy of the only table operand a step advances
            shared = kv.operands(tabs, filled)
            return {name: dict(st, **shared[name]) if name in shared
                    else st for name, st in pool.items()}

        def kept(rnn):
            # a pass's new state parted into what the engine carries
            # between dispatches (pool leaves, slot-state rows) and
            # the advanced ``filled``: every paged layer added the
            # same lengths, so the first one's stands for all and no
            # program hands a layer's tables back
            filled = next(st["filled"] for name, st in rnn.items()
                          if name in kv.layers)
            return {name: ({leaf: st[leaf] for leaf in kv.leaves
                            if leaf in st}
                           if name in kv.layers else st)
                    for name, st in rnn.items()}, filled

        # (a phase is the first scope of a program's body: what the
        # device's time is for, whatever the program is called)
        @scope("admit")
        def chunk_prefill(params, state, x, mask, rnn, tabs, temp,
                          top_k, key):
            # masked prefill resuming a carried cache: a warm
            # admission's pool leaves under its block table, or
            # (``tabs`` None) a COLD admission's dense B=1 row, the
            # net's own streaming cache, from the chunk before (None
            # at its first). Forward, then sample at each row's last
            # VALID position
            with scope("tables"):
                length = jnp.sum(mask.astype(jnp.int32), axis=1)
            cold = tabs is None
            rows = {}
            if not cold:
                rnn = seen(rnn, tabs)
                if self._wants_live:
                    # an admission's row holds a request, whatever its
                    # tables say (a paged one's first chunk starts at
                    # ``filled`` 0, which reads as an idle slot)
                    with scope("tables"):
                        rows["live"] = jnp.ones(x.shape[:1], jnp.int32)
            if ids_in:
                # the head at the sampled position only: a vocabulary
                # this wide is not worth a column per prompt position
                out, new_rnn, counts = forward(
                    params, state, x, mask, rnn, head_at=length - 1,
                    **rows)
                probs = out[:, :, 0]
            else:
                out, new_rnn, counts = forward(params, state, x, mask,
                                               rnn, **rows)
                with scope("head/logits"):
                    probs = jnp.take_along_axis(
                        out, (length - 1)[:, None, None],
                        axis=2)[:, :, 0]
            with scope("head/sample"):
                tok = sample_tokens(probs, temp, top_k, key)
            return tok, new_rnn if cold else kept(new_rnn)[0], counts

        def prefill(params, state, x, mask, temp, top_k, key):
            # cold prefill = the continuation body with no carried
            # cache (separate jit wrapper keeps its own executable
            # cache, so compile_counts stays per-path)
            return chunk_prefill(params, state, x, mask, None, None,
                                 temp, top_k, key)

        @scope("decode")
        def decode(params, state, pool, tabs, toks, temps, top_ks,
                   key, live=None):
            # ``tabs``: the dispatch's block tables; ``live`` [B]:
            # which slots hold a
            # request, for the layers that ask (one operand each a
            # dispatch). Of the tables only ``filled`` is carried:
            # the rest is the same at every step
            with scope("head/sample"):
                keys = jax.random.split(key, chunk)

            def body(carry, k):
                pool, filled, tok = carry
                out, new_rnn, counts = forward(
                    params, state, encode(tok), None,
                    seen(pool, tabs, filled), live=live)
                with scope("head/sample"):
                    nxt = sample_tokens(out[:, :, -1], temps, top_ks, k)
                return (*kept(new_rnn), nxt), (nxt, counts)

            (pool, _, tok), (seq, counts) = jax.lax.scan(
                body, (pool, tabs[:, -1], toks), keys)
            # what the layers counted, summed over the chunk's steps
            counts = {name: jnp.sum(v) for name, v in counts.items()}
            with scope("head/sample"):
                return pool, tok, jnp.swapaxes(seq, 0, 1), counts

        @scope("decode")
        def fused_decode(params, state, pool, tabs, toks, temps,
                         top_ks, eos_ids, remaining, keys):
            # fused multi-round decode (ISSUE 16): K stepped rounds
            # as ONE scan over K * chunk positions. ``keys`` carries
            # the K per-round host keys (the exact keys K stepped
            # dispatches would have consumed, in order), each
            # vmap-split into its chunk keys — so the flattened key
            # stream, and with it every sampled id, is bit-identical
            # to K sequential decode dispatches. eos/stop detection
            # runs on device: ``eos_ids[B]`` (-1 = none) and
            # ``remaining[B]`` (max_new_tokens headroom at dispatch)
            # yield ``n_valid[B]`` — the per-slot committed prefix of
            # the K * chunk emitted tokens. Finished slots ride the
            # rest of the scan as dead rows (per-row independence:
            # neighbours' ids are untouched, the same invariant idle
            # slots rest on) and their overshoot is dropped at
            # landing, exactly like a chunk overshooting eos today.
            k_rounds = keys.shape[0]
            with scope("head/sample"):
                flat = jax.vmap(
                    lambda kk: jax.random.split(kk, chunk))(keys)
                flat = flat.reshape(k_rounds * chunk)

            def body(carry, k):
                pool, filled, tok = carry
                out, new_rnn, _ = forward(params, state, encode(tok),
                                          None, seen(pool, tabs, filled))
                with scope("head/sample"):
                    nxt = sample_tokens(out[:, :, -1], temps, top_ks, k)
                return (*kept(new_rnn), nxt), nxt

            (pool, _, tok), seq = jax.lax.scan(
                body, (pool, tabs[:, -1], toks), flat)
            with scope("head/sample"):
                seq = jnp.swapaxes(seq, 0, 1)       # [B, K * chunk]
                t = k_rounds * chunk
                pos = jnp.arange(t)
                is_eos = seq == eos_ids[:, None]
                eos_pos = jnp.min(
                    jnp.where(is_eos, pos[None, :], t), axis=1)
                n_valid = jnp.minimum(
                    jnp.minimum(eos_pos + 1, t),
                    jnp.clip(remaining, 0, t)).astype(jnp.int32)
            return pool, tok, seq, n_valid

        self._prefill_jit = self._jit(prefill)
        # donate the carried cache: the block pool rides EVERY
        # dispatch as an operand, and without input-output
        # aliasing each call would copy the whole pool just to
        # write one round's blocks (measured 1.8x warm-TTFT
        # regression on the CPU proxy). What is donated is what a
        # dispatch writes and the engine keeps: the pool leaves and
        # the slot-state rows. The block tables are NOT: they are the
        # argument beside it, made anew on the host each dispatch
        # and read by every paged layer (XLA rejects one buffer
        # donated through two pytree leaves, so what the layers
        # share cannot ride inside the donated pytree)
        self._chunk_jit = self._jit(chunk_prefill, donate_argnums=(4,))
        self._decode_jit = self._jit(decode, donate_argnums=(2,))
        self._fused_jit = (
            self._jit(fused_decode, donate_argnums=(2,))
            if self.fused_rounds else None)
        self._state_admit_jit = None
        if self._state_layers:
            @scope("admit")
            @scope("mixer")
            def state_admit(slots, row, slot):
                # a prefilled row's recurrent state into its slot
                def put(p, o):
                    return jax.lax.dynamic_update_slice_in_dim(
                        p, o.astype(p.dtype), slot, axis=0)

                return jax.tree_util.tree_map(put, slots, row)

            self._state_admit_jit = self._jit(state_admit,
                                              donate_argnums=(0,))
        self._verify_jit = None
        if self.spec_draft_len:
            vocab, dtype = self.vocab, self.net._dtype

            def verify(params, state, pool, tabs, toks, draft, lens,
                       temps, top_ks, key):
                # ONE forward scores every slot's draft: the chunk fed
                # per row is [current token | draft], right-padded to
                # the round's pow2 width bucket; the mask keeps each
                # row's pad out of attention AND out of the cache (the
                # _stream_attend ragged-chunk contract), so B slots
                # with different draft lengths share this executable.
                # Output position i holds the logits AFTER
                # context + draft[:i] — exactly what sequential decode
                # would have seen — so greedy-matching drafts against
                # argmax targets accepts precisely the tokens plain
                # greedy decode would emit.
                seq = jnp.concatenate([toks[:, None], draft], axis=1)
                x = (seq if ids_in else jnp.swapaxes(
                    jax.nn.one_hot(seq, vocab, dtype=dtype), 1, 2))
                pos = jnp.arange(seq.shape[1])
                mask = (pos[None, :]
                        <= lens[:, None]).astype(jnp.float32)
                out, new_rnn, _ = forward(params, state, x, mask,
                                          seen(pool, tabs))
                new_pool, filled = kept(new_rnn)
                # acceptance (ISSUE 16): greedy rows keep the equality
                # rule (bit-parity with plain greedy decode); sampling
                # rows accept each draft token with probability
                # p_tau(draft) — the Leviathan p/q rejection rule with
                # the n-gram drafter's point-mass q — so sampling
                # traffic rides the verify pass with target-model
                # marginals preserved exactly
                k_acc, k_bonus = jax.random.split(key)
                acc = stochastic_acceptance(
                    jnp.swapaxes(out, 1, 2)[:, :-1], draft, lens,
                    temps, top_ks, k_acc)
                # bonus token AFTER the accepted prefix: on a greedy
                # row argmax == target (the correction token at the
                # first divergence, or the free extra token on full
                # acceptance); on a rejected sampling row the draw is
                # from the RESIDUAL distribution (rejected token
                # banned, renormalized) — the second half of the
                # rejection-sampling identity
                probs = jnp.take_along_axis(
                    out, acc[:, None, None], axis=2)[:, :, 0]
                w = draft.shape[1]
                rejected = acc < lens
                rej_tok = jnp.take_along_axis(
                    draft, jnp.minimum(acc, w - 1)[:, None],
                    axis=1)[:, 0]
                bonus = residual_sample(probs, rej_tok, rejected,
                                        temps, top_ks, k_bonus)
                # roll each row's rejected tail back out of the cache;
                # the committed cache then holds exactly
                # context + accepted prefix, with the bonus token as
                # the slot's new current (not-yet-cached) token:
                # tokens stay where they lie in their blocks and the
                # rewind is the ONE ``filled`` moving back (a key past
                # it is masked, and the next write lands on it); the
                # tables go out with it for the decode dispatch to
                # chain on, as device arrays, no second upload
                tabs = tabs.at[:, -1].set(filled - (lens - acc))
                dpad = jnp.concatenate(
                    [draft, jnp.zeros_like(draft[:, :1])], axis=1)
                emitted = jnp.where(
                    pos[None, :] < acc[:, None], dpad,
                    jnp.where(pos[None, :] == acc[:, None],
                              bonus[:, None], 0))
                return new_pool, tabs, bonus, emitted, acc

            self._verify_jit = self._jit(verify, donate_argnums=(2,))
        bt, s_ring = self.block_tokens, kv.kinds[0].ring

        @scope("admit")
        @scope("attn/cache")
        def scatter_row(pool, rnn1, table_row, length):
            # a cold admission's one whole-row write: a dense B=1
            # post-prefill row's valid window tokens to their
            # ABSOLUTE positions in the slot's freshly-allocated
            # blocks (warm admissions skip this entirely via the
            # zero-copy splice)
            out = {}
            for name, st in pool.items():   # the KV layers
                k1, v1 = rnn1[name]["k"], rnn1[name]["v"]
                fd = rnn1[name]["filled"][0]
                w = k1.shape[2]
                nbk = st["pk"].shape[0]
                n_tok = nbk * bt
                absp = length - w + jnp.arange(w)
                safe = jnp.clip(absp, 0)
                blk = table_row[(safe // bt) % s_ring]
                idx = jnp.where((absp >= length - fd) & (blk >= 0),
                                blk * bt + safe % bt, n_tok)
                kt = jnp.transpose(k1[0], (1, 0, 2))   # [W, H, dh]
                vt = jnp.transpose(v1[0], (1, 0, 2))
                h, dh = kt.shape[1], kt.shape[2]
                pkf = st["pk"].reshape(n_tok, h, dh).at[idx].set(
                    kt.astype(st["pk"].dtype), mode="drop")
                pvf = st["pv"].reshape(n_tok, h, dh).at[idx].set(
                    vt.astype(st["pv"].dtype), mode="drop")
                out[name] = {"pk": pkf.reshape(nbk, bt, h, dh),
                             "pv": pvf.reshape(nbk, bt, h, dh)}
            return out

        @scope("admit")
        @scope("head/sample")
        def put_tok(toks, tok1, slot):
            return jax.lax.dynamic_update_slice(
                toks, tok1.astype(toks.dtype), (slot,))

        def kv_import(pool, new, ids):
            # KV transfer import (ISSUE 14): scatter shipped
            # block stacks [n, bt, H, dh] into the pool at the
            # freshly-allocated ids; pad lanes carry an
            # out-of-range id and drop. One executable per pow2
            # block-count bucket (serving/kv_transfer.py pads),
            # the engine's standing compile discipline. Under tp
            # the shipped leaves shard on their head axis exactly
            # like the pool (same pk/pv key paths).
            out = {}
            for name, st in pool.items():
                npk = new[name]["pk"].astype(st["pk"].dtype)
                npv = new[name]["pv"].astype(st["pv"].dtype)
                out[name] = {
                    "pk": st["pk"].at[ids].set(npk, mode="drop"),
                    "pv": st["pv"].at[ids].set(npv, mode="drop"),
                }
            return out

        def kv_gather(pool, ids):
            # KV transfer export (ISSUE 14): pull the selected
            # blocks [W, bt, H, dh] out of the pool so only the
            # exported slice crosses to host (a whole-pool host
            # copy would scale with pool size, not export size,
            # under the engine lock). Pad ids are out of range
            # and fill zero; one executable per pow2 bucket,
            # like the import twin.
            out = {}
            for name, st in pool.items():
                out[name] = {
                    "pk": jnp.take(st["pk"], ids, axis=0,
                                   mode="fill", fill_value=0),
                    "pv": jnp.take(st["pv"], ids, axis=0,
                                   mode="fill", fill_value=0),
                }
            return out

        self._scatter_jit = self._jit(scatter_row,
                                      donate_argnums=(0,))
        self._tok_jit = self._jit(put_tok)
        self._kv_import_jit = self._jit(kv_import,
                                        donate_argnums=(0,))
        self._kv_gather_jit = self._jit(kv_gather)
        self._health_jit = None
        if self.paranoid:
            vocab = self.vocab

            def paged_health(pool, toks):
                # per-BLOCK finiteness (ISSUE 6 satellite) + sampled-id
                # range check, the failure layer's only compile-count
                # addition: the pool axis is blocks, not slots, so the
                # sweep's verdict is per block and the HOST maps
                # blocks -> victims via the block tables —
                # quarantining a victim then releases references
                # without scrubbing blocks shared with innocent slots
                oks = []
                for st in pool.values():
                    for leaf in (st["pk"], st["pv"]):
                        fin = jnp.isfinite(leaf.astype(jnp.float32))
                        oks.append(jnp.all(
                            fin.reshape(leaf.shape[0], -1), axis=1))
                blocks_ok = functools.reduce(jnp.logical_and, oks)
                return blocks_ok, (toks >= 0) & (toks < vocab)

            self._health_jit = self._jit(paged_health)

    def compile_counts(self) -> Dict[str, int]:
        """Executable counts per jitted computation (the no-retrace
        guarantee: decode, paged_scatter, paged_tok, and the
        paranoid health_check stay at 1; prefill equals the number of
        distinct cold prompt-length buckets seen; chunk_prefill equals
        the number of distinct suffix widths — exactly 1 in chunked
        mode; verify, in speculative mode, equals the number of
        distinct pow2 draft-width buckets seen — at most
        O(log spec_draft_len))."""
        def n(f):
            return int(getattr(f, "_cache_size", lambda: -1)())

        counts = {"prefill": n(self._prefill_jit),
                  "chunk_prefill": n(self._chunk_jit),
                  "decode": n(self._decode_jit)}
        if self._fused_jit is not None:
            # one executable per pow2 K-bucket actually dispatched —
            # at most log2(fused_rounds) + 1
            counts["fused_decode"] = n(self._fused_jit)
        if self._verify_jit is not None:
            counts["verify"] = n(self._verify_jit)
        if self._health_jit is not None:
            counts["health_check"] = n(self._health_jit)
        if self._state_admit_jit is not None:
            counts["state_admit"] = n(self._state_admit_jit)
        counts["paged_scatter"] = n(self._scatter_jit)
        counts["paged_tok"] = n(self._tok_jit)
        counts["kv_import"] = n(self._kv_import_jit)
        counts["kv_gather"] = n(self._kv_gather_jit)
        counts.update(self.block_pool.compile_counts())
        return counts

    # -- request lifecycle ---------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its id (``run()`` drains). With a
        bounded queue (``max_queue``), a full queue sheds per
        ``shed_policy``: the result for a shed request (this one under
        "reject-new", the oldest queued one under "shed-oldest") is
        delivered with ``finish_reason="shed"`` at the next
        ``run()``/``step()`` drain."""
        bad = [t for t in request.prompt
               if not 0 <= int(t) < self.vocab]
        if bad:
            raise ValueError(
                f"prompt ids {bad[:4]} outside vocab [0, {self.vocab})")
        self.scheduler.validate(request)
        if (self.tenants is not None
                and self.scheduler.tenant_full(request.tenant)):
            # per-tenant queue bound (ISSUE 13): the tenant's OWN
            # backlog is full — always reject-new, whatever the
            # global shed policy: shedding ANOTHER tenant's oldest
            # to admit a flooder would invert the QoS contract
            rid = self.scheduler.assign_id(request)
            self._mint_clock(rid)
            self._shed(request)
            return rid
        if self.scheduler.full:
            if self.shed_policy == "reject-new":
                rid = self.scheduler.assign_id(request)
                self._mint_clock(rid)
                self._shed(request)
                return rid
            self._shed(self.scheduler.shed_victim())
        rid = self.scheduler.submit(request)
        self._submit_t[rid] = self._clock()
        self._mint_clock(rid, self._submit_t[rid])
        if (request.deadline_s is not None
                or request.queue_timeout_s is not None):
            self._has_deadlines = True
        return rid

    def cancel(self, request_id: int) -> bool:
        """Terminate a request wherever it is — queued, waiting out a
        retry backoff, mid-admission, or decoding in a slot. Running
        requests return their partial tokens; the result
        (``finish_reason="cancelled"``) is delivered at the next
        ``run()``/``step()`` drain. Returns False when the id is
        unknown or already terminal."""
        req = self.scheduler.remove(request_id)
        if req is not None:
            self._record_terminal(req, [], "cancelled")
            self._failure_event("cancelled")
            return True
        for i, (_, queued) in enumerate(self._requeue):
            if queued.id == request_id:
                del self._requeue[i]
                self._record_terminal(queued, [], "cancelled")
                self._failure_event("cancelled")
                return True
        for pending in list(self._pending):
            if pending.request.id == request_id:
                self._abort_pending(pending)
                self._record_terminal(pending.request, [], "cancelled")
                self._failure_event("cancelled")
                return True
        for slot, state in enumerate(self._slots):
            if state is not None and state.request.id == request_id:
                self._record_terminal(
                    state.request, state.tokens, "cancelled",
                    state.prefix_reused, state.ttft_s,
                    state.spec_drafted, state.spec_accepted)
                self._failure_event("cancelled")
                self._evict_slot(slot)
                return True
        return False

    def _span(self, name, **args):
        if self.tracer is None:
            return contextlib.nullcontext(args)
        return self.tracer.span(name, **args)

    def _admit_span(self, request: Request, slot: int):
        """``serving.admit``: one request's admission work of this
        round. Its children are ``serving.prompt_encode``,
        ``serving.prefill`` / ``serving.prefill_chunk`` and
        ``serving.first_token_sync``; what is left is the scheduler's
        part (prefix lookup, block allocation, slot bookkeeping)."""
        return self._span("serving.admit", rid=request.id, slot=slot,
                          **_targs(request))

    def _traces_of(self, slots) -> Dict[str, Any]:
        """Span-args fragment mapping request id -> fleet trace
        context for a batched span covering several slots (ISSUE 10
        — decode_chunk / spec_verify carry ``rids`` lists; this is
        the parallel trace map). Empty when no covered request is
        traced."""
        traces = {
            str(self._slots[s].request.id): self._slots[s].request.trace
            for s in slots
            if self._slots[s] is not None
            and self._slots[s].request.trace}
        return {"traces": traces} if traces else {}

    # -- request-scoped observability (ISSUE 7) ------------------------
    def describe_metrics(self) -> None:
        """Register the engine's histogram tracks + HELP text with the
        attached tracer (no-op without one). Idempotent; the gateway
        calls it again after attaching its own tracer."""
        if self.tracer is None:
            return
        if hasattr(self.tracer, "register_histogram"):
            for name, hist in self.histograms.items():
                self.tracer.register_histogram(name, hist)
            for name, hist in self._tenant_hists.items():
                self.tracer.register_histogram(name, hist)
        if hasattr(self.tracer, "describe"):
            for name, help_text in SERVING_TRACK_HELP.items():
                self.tracer.describe(name, help_text)

    def _mint_clock(self, rid: int,
                    submit_t: Optional[float] = None) -> None:
        if self.record_timing:
            self._clocks[rid] = _PhaseClock(
                self._clock() if submit_t is None else submit_t)

    def _clock_of(self, rid) -> Optional[_PhaseClock]:
        return self._clocks.get(rid) if self.record_timing else None

    def _observe(self, name: str, value, n: int = 1) -> None:
        hist = self.histograms.get(name)
        if hist is not None and value is not None:
            hist.observe(value, n)

    def _observe_tenant(self, family: str, tenant: str, value,
                        n: int = 1) -> None:
        """Per-tenant labeled twin of :meth:`_observe` (ISSUE 13):
        records into the ``family{tenant="..."}`` histogram track,
        created and tracer-registered on the tenant's first sample.
        No-op (zero cost) on engines without a TenantRegistry."""
        if (self.tenants is None or not self.record_timing
                or value is None):
            return
        name = f'{family}{{tenant="{tenant}"}}'
        hist = self._tenant_hists.get(name)
        if hist is None:
            from deeplearning4j_tpu.profiler.tracer import Histogram

            hist = self._tenant_hists[name] = Histogram()
            if (self.tracer is not None
                    and hasattr(self.tracer, "register_histogram")):
                self.tracer.register_histogram(name, hist)
        hist.observe(value, n)

    def _tenant_count(self, tenant: str, key: str,
                      n: int = 1) -> None:
        """Bump a per-tenant cumulative stat (mirrored as
        ``serving_<key>{tenant=...}`` labeled samples by
        ``_emit_counters``). No-op without tenancy."""
        if self.tenants is None:
            return
        stats = self.tenant_stats.setdefault(
            tenant, {"tokens_generated": 0, "admitted": 0,
                     "shed": 0, "preempted": 0})
        stats[key] = stats.get(key, 0) + n

    def request_trace(self, rid: int) -> Optional[Dict[str, Any]]:
        """Flight-recorder record for one TERMINAL request: the timing
        breakdown plus the ordered per-attempt phase timeline. None
        once evicted from the ring (or for unknown/live ids, or with
        ``record_timing=False``) — the gateway maps that to 404/202."""
        return self._flight.get(rid)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _failure_event(self, kind: str,
                       tenant: Optional[str] = None) -> None:
        self.stats[kind] += 1
        if self.tracer is not None:
            self.tracer.incr(f"serving_{kind}")
            if tenant is not None and self.tenants is not None:
                # labeled twin (ISSUE 13): same family, same counter
                # type — merge_prometheus sums it per label set, so
                # the fleet scrape answers "who got shed"
                self.tracer.incr(
                    f'serving_{kind}{{tenant="{tenant}"}}')

    def _note_progress(self, state: _Slot) -> None:
        """Surface a slot's newly committed tokens as a delta (see
        ``on_delta``). Called only where tokens are COMMITTED — after
        admission's first token and after the round's appends (which
        post-date the paranoid quarantine sweep and contain only
        verify-accepted speculative tokens) — so a streaming consumer
        can never observe a token the engine later disowns."""
        self._emit_delta(state.request.id, state.tokens)

    def _emit_delta(self, rid: int, tokens: List[int]) -> None:
        cb = self.on_delta
        if cb is None and not self.emit_deltas:
            return
        sent = self._delta_sent.get(rid, 0)
        fresh = tokens[sent:]
        if not fresh:
            return
        self._delta_sent[rid] = len(tokens)
        clock = self._clocks.get(rid) if sent == 0 else None
        if clock is not None and clock.first_delta_s is None:
            now = self._clock()
            clock.first_delta_s = now - clock.submit_t
            clock.event(now, "first_delta", n=len(fresh))
        if cb is not None:
            cb(rid, [int(t) for t in fresh])
        else:
            self._delta_buf.setdefault(rid, []).extend(
                int(t) for t in fresh)

    def drain_deltas(self) -> Dict[int, List[int]]:
        """Return (and clear) the per-request committed-token deltas
        accumulated since the last drain (``emit_deltas=True`` engines
        without an ``on_delta`` callback). Keys are request ids; values
        are the tokens committed since the previous drain, in order."""
        buf = self._delta_buf
        self._delta_buf = {}
        return buf

    def _record_terminal(self, request: Request, tokens, reason: str,
                         prefix_reused: int = 0,
                         ttft: Optional[float] = None,
                         spec_drafted: int = 0,
                         spec_accepted: int = 0) -> None:
        """Write a request's terminal result (drained into the caller's
        dict by the next ``step()``), and drop every piece of host
        bookkeeping keyed by its id. Any committed-but-unstreamed tail
        (a request cancelled between its admission round's first token
        and the decode that would have streamed it) flushes as a final
        delta first, so concatenated deltas equal the terminal's token
        list — with ONE exception: a capped-retry ``"fault"`` terminal
        delivers no tokens (the PR 3 contract; its earlier streamed
        attempts were disowned by quarantine)."""
        self._emit_delta(request.id, list(tokens))
        timing = None
        clock = self._clocks.pop(request.id, None)
        if clock is not None:
            now = self._clock()
            clock.ttft_s = ttft  # the EXACT value the result carries
            clock.event(now, "terminal", reason=reason)
            timing = clock.summary(now, len(tokens))
            self._observe("serving_e2e_s", timing["e2e_s"])
            self._observe_tenant("serving_e2e_s", request.tenant,
                                 timing["e2e_s"])
            # tenancy-enabled engines stamp the tenant onto the
            # flight record and the request_done instant so the
            # saved-trace half of latency_report --tenant can group
            # by it; tenant-blind engines stay byte-identical
            tenancy = ({"tenant": request.tenant}
                       if self.tenants is not None else {})
            if self.flight_recorder:
                self._flight[request.id] = {
                    "id": request.id, "finish_reason": reason,
                    "timing": timing, "attempts": clock.attempts,
                    **tenancy, **_targs(request),
                }
                while len(self._flight) > self.flight_recorder:
                    self._flight.popitem(last=False)
            if self.tracer is not None:
                # a self-describing trace: latency_report.py reads
                # these instants back out of a saved Chrome trace
                self.tracer.instant("serving.request_done",
                                    rid=request.id, reason=reason,
                                    timing=timing, **tenancy,
                                    **_targs(request))
        self._terminal[request.id] = GenerationResult(
            id=request.id, tokens=list(tokens), finish_reason=reason,
            prompt_len=len(request.prompt),
            prefix_tokens_reused=prefix_reused, ttft_s=ttft,
            retries=self._retries.pop(request.id, 0),
            spec_drafted=spec_drafted, spec_accepted=spec_accepted,
            timing=timing, trace=request.trace,
            tenant=(request.tenant if self.tenants is not None
                    else None))
        self.stats["requests_finished"] += 1
        self._submit_t.pop(request.id, None)
        self._started.discard(request.id)
        self._delta_sent.pop(request.id, None)
        self.scheduler.release(request.id)

    def _shed(self, request: Request) -> None:
        self._record_terminal(request, [], "shed")
        self._failure_event("shed", tenant=request.tenant)
        self._tenant_count(request.tenant, "shed")

    def _abort_pending(self, pending: _Pending) -> None:
        """Drop an in-flight admission (cancel/deadline): release the
        prefix-cache lease and free the reserved slot."""
        if pending.hit is not None and self.prefix_cache is not None:
            self.prefix_cache.release(pending.hit)
        self.kv.free(pending.tab)
        pending.tab = None
        self._reserved.discard(pending.slot)
        self._pending.remove(pending)

    def _evict_slot(self, slot: int) -> None:
        """Free the slot. Eviction releases REFERENCES:
        exclusively-owned blocks return to the free list (scrubbed
        there if the paranoid sweep poisoned them, so a poisoned slot
        stops existing), blocks shared with the trie or other slots
        stay resident and untouched — the per-block quarantine
        contract (ISSUE 6 satellite). A slot-state layer's row is left
        as it is: the next admission overwrites it whole. The slot's
        speculative draft state dies with it (a quarantined or
        cancelled slot must never donate drafts to its successor)."""
        self._release_slot(slot)
        self.stats["evicted"] += 1

    def _release_slot(self, slot: int) -> None:
        """What eviction and preemption share."""
        self.kv.free(self._kv_tabs[slot])
        self._kv_tabs[slot] = None
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._top_ks[slot] = self.vocab
        if self.spec is not None:
            self.spec.drop(slot)

    # -- the KV memory's device pool and its relief (ISSUE 6) ----------
    @property
    def _pool(self):    # (``kv.pool``: every program's donated operand)
        return self.kv.pool

    @_pool.setter
    def _pool(self, tree) -> None:
        self.kv.pool = tree

    def _paged_reserve(self, n: int, protect=(), kind=None) -> bool:
        """Make ``n`` blocks of ``kind``'s pool allocatable: first
        evict LRU prefix-trie
        entries (references only — shared blocks stay resident), then
        preempt the youngest unprotected slot(s), requeueing their
        requests (greedy re-admissions regenerate identical ids, so
        preemption is invisible to results — the continuous-batching
        analogue of vLLM's recompute preemption)."""
        pool = (kind or self.kv.kinds[0]).pool
        while pool.free_blocks < n and self.prefix_cache is not None:
            if not self.prefix_cache.evict_one():
                break
        while pool.free_blocks < n:
            victim = None
            for slot in range(self.n_slots - 1, -1, -1):
                if (self._slots[slot] is not None
                        and slot not in protect):
                    victim = slot
                    break
            if victim is None:
                return pool.free_blocks >= n
            self._preempt_slot(victim)
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Release a running slot's blocks under pool pressure and
        requeue its request (no retry charge — nothing failed). The
        re-admission prefills the prompt from scratch; a greedy
        request regenerates bit-identical tokens, and the delta
        high-water mark suppresses re-streaming. A SAMPLING request
        that already streamed cannot be preempted honestly (the RNG
        redraw would splice two sequences) — it terminates ``fault``,
        the same contract quarantine applies."""
        state = self._slots[slot]
        self.stats["preempted"] += 1
        if self.tracer is not None:
            self.tracer.incr("serving_preempted")
            if self.tenants is not None:
                self.tracer.incr(
                    f'serving_preempted{{tenant='
                    f'"{state.request.tenant}"}}')
        self._tenant_count(state.request.tenant, "preempted")
        self._release_slot(slot)
        if ((self.on_delta is not None or self.emit_deltas)
                and state.request.temperature > 0
                and self._delta_sent.get(state.request.id, 0) > 0):
            self._record_terminal(state.request, state.tokens, "fault",
                                  state.prefix_reused, state.ttft_s,
                                  state.spec_drafted,
                                  state.spec_accepted)
            return
        clock = self._clock_of(state.request.id)
        if clock is not None:
            clock.new_attempt(self._clock(), "preempted")
        self._requeue.append((self._round + 1, state.request))

    def _split_row(self, rnn1):
        """A dense B=1 prefill state split into (its attention layers'
        caches, its slot-state layers' rows)."""
        kv = {n: st for n, st in rnn1.items()
              if n not in self._state_layers}
        return kv, {n: rnn1[n] for n in self._state_layers}

    def _write_row(self, rnn1, length: int,
                   slot: Optional[int] = None) -> Optional[KindTables]:
        """A cold admission's (or a restore's) one whole-row write:
        fresh tables over what a dense B=1 prefill row holds
        (``KvMemory.cover``), the row's keys and values scattered into
        their blocks, its recurrent state into the slot's row (a trie
        entry being re-primed has no slot, and no such state). None
        when the pool cannot be relieved."""
        self._one_kind_only("writing a dense prefill row into block "
                            "tables (restore, a trie entry's re-priming)")
        self._ensure_paged_pool(rnn1)
        tab = self.kv.cover(length)
        if tab is None:
            return None
        kv, row = self._split_row(rnn1)
        table_row, _ = tab.kinds[0].arrays(self.kv.kinds[0].ring)
        self._pool = self._scatter_jit(
            self._pool, kv, jnp.asarray(table_row),
            jnp.asarray(tab.length, jnp.int32))
        if row:
            with self._span("serving.state_admit", slot=slot):
                self._slot_state = self._state_admit_jit(
                    self._slot_state, row, jnp.asarray(slot, jnp.int32))
        return tab

    def _live_operand(self):
        """``(live,)`` for the decode program of a net some layer of
        which asks which slots hold a request (an idle slot's row
        routes to no expert and its recurrent state is left alone,
        nn/layers/hybrid.py); ``()`` for every other net, whose program
        has no such operand."""
        if not self._wants_live:
            return ()
        return (self._uploaded("live", np.asarray(
            [s is not None for s in self._slots], np.int32)),)

    def _uploaded(self, name: str, host: np.ndarray):
        """A round's small per-slot operand on the device: uploaded
        anew only when it differs from what the last dispatch sent
        (temperatures, top-k and the live mask change with an admission
        or an eviction, not with a round; no program donates them)."""
        sent = self._sent.get(name)
        if sent is None or not np.array_equal(sent[0], host):
            sent = self._sent[name] = (host.copy(), jnp.asarray(host))
        return sent[1]

    def _add_counts(self, counts, prefill: bool = False) -> None:
        """What a program counted (device scalars, ready with its
        tokens) onto ``stats``; a prefill program's also under
        ``prefill_<name>``, so that a reader can take the decode
        program's part of a round."""
        for name, v in jax.device_get(counts).items():  # one fetch
            self.stats[name] += int(v)
            if prefill:
                self.stats["prefill_" + name] += int(v)

    def _strip_pool(self, rnn):
        """What a program hands back (pool leaves and, for a net
        with slot-state layers, the state rows that rode the same
        donated operand) parted into ``_slot_state`` and the returned
        pool. No program returns table operands."""
        if not self._state_layers:
            return rnn
        self._slot_state = {name: rnn[name]
                            for name in self._state_layers}
        return {name: st for name, st in rnn.items()
                if name not in self._state_layers}

    def _paged_stats_refresh(self) -> None:
        self.kv.refresh_stats(
            admitting=[p.tab for p in self._pending],
            leased=(self.prefix_cache._payloads.values()
                    if self.prefix_cache is not None else ()))
        if self.kv_tier is not None:
            t = self.kv_tier.stats
            self.stats["kv_tier_spills"] = t["spills"]
            self.stats["kv_tier_reloads"] = t["reloads"]
            self.stats["kv_tier_drops"] = t["drops"]
            self.stats["kv_tier_demotions"] = t["demotions"]
            self.stats["kv_tier_hits_host"] = t["hits_host"]
            self.stats["kv_tier_hits_disk"] = t["hits_disk"]
            self.stats["kv_tier_host_bytes"] = self.kv_tier.host_bytes
            self.stats["kv_tier_disk_bytes"] = self.kv_tier.disk_bytes

    def _one_kind_only(self, what: str) -> None:
        """Refuse, by its name, what holds one kind of KV block to a
        net that has several."""
        if len(self.kv.kinds) > 1:
            raise NotImplementedError(
                f"{what} is not supported for this net: its attention "
                f"layers have windows {[k.window for k in self.kv.kinds]}"
                ", and the transfer, tier, snapshot and dense-row "
                "formats hold one kind of KV block")

    # -- cross-replica KV transfer (ISSUE 14) --------------------------
    def export_kv(self, prompt,
                  cap_bytes: Optional[int] = None) -> Optional[bytes]:
        """Serialize the longest cached prefix of ``prompt`` as a
        framed binary payload any peer replica can import
        (serving/kv_transfer.py). None when nothing reusable is
        cached or the engine has no trie; ``cap_bytes`` raises
        :class:`~deeplearning4j_tpu.serving.kv_transfer
        .KVTransferTooLarge` from size arithmetic BEFORE any device
        gather. Layout-invariant: a TP=N engine exports full logical
        blocks (host reassembly), so the receiver's width need not
        match."""
        self._one_kind_only("export_kv")
        from deeplearning4j_tpu.serving.kv_transfer import (
            KVTransferTooLarge,
            export_prefix,
        )

        payload = export_prefix(self, prompt, cap_bytes=cap_bytes)
        if payload is None and self.kv_tier is not None:
            # tier fallback (ISSUE 17): a trie-cold replica whose
            # host/disk tier still holds the prefix is a working
            # donor — serve the stored DKV1 payload directly, zero
            # device work (the payload stays resident: an export is
            # read-only)
            self.drain_spills()  # a just-evicted prefix may be staged
            ent = self.kv_tier.match(prompt)
            if ent is not None:
                _key, payload, _tier = ent
                if cap_bytes is not None and len(payload) > cap_bytes:
                    raise KVTransferTooLarge(
                        f"tier export is {len(payload)} bytes, over "
                        f"the {cap_bytes}-byte cap")
                self.stats["kv_tier_exports"] += 1
        return payload

    def import_kv(self, payload: bytes):
        """Splice a peer's exported prefix into this engine's pool
        and radix trie; the next admission of that prompt splices it
        exactly like a locally-computed entry (greedy bit-parity
        gated in tests/test_kv_transfer.py). Declines softly
        (``imported: False``) under pool/trie pressure; raises
        :class:`~deeplearning4j_tpu.serving.kv_transfer
        .KVTransferError` on a malformed frame or geometry mismatch —
        either way the caller's recompute path still covers
        correctness."""
        self._one_kind_only("import_kv")
        from deeplearning4j_tpu.serving.kv_transfer import import_prefix

        return import_prefix(self, payload)

    # -- tiered KV spill store (ISSUE 17) ------------------------------
    #: staged-spill cap: each staged spill pins one gathered block
    #: stack on device until the end-of-round drain — under a
    #: pathological eviction storm the cap bounds that transient
    #: footprint, and overflow victims fall back to the seed behavior
    #: (dropped, recompute later)
    MAX_PENDING_SPILLS = 8

    def _stage_spill(self, tokens, tab) -> None:
        """Pressure-eviction hook (installed as
        ``prefix_cache.on_evict``): stage the victim's blocks for the
        host tier. ONLY the jitted ``kv_gather`` dispatches here —
        an async device op whose result is computed from the current
        (immutable) pool value, so the victim's blocks can be freed
        and recycled immediately. The device-to-host copy and the
        DKV1 pack are deferred to :meth:`drain_spills` at the end of
        the round, keeping the export off the decode hot path."""
        tier = self.kv_tier
        if tier is None or self._pool is None:
            return
        matched, floor, bt = tab.length, tab.floor, self.block_tokens
        if matched - floor <= 0:
            return
        want = list(range(floor // bt, (matched - 1) // bt + 1))
        if any(g not in tab.blocks for g in want):
            return  # window slide broke contiguity: nothing to spill
        bids = [tab.blocks[g] for g in want]
        if any(b in self.block_pool.poisoned for b in bids):
            return  # quarantined state must never be spilled
        key = tuple(int(t) for t in tokens)
        if len(self._pending_spills) >= self.MAX_PENDING_SPILLS:
            self.stats["kv_tier_spill_skipped"] += 1
            return
        from deeplearning4j_tpu.serving.kv_transfer import _pow2_bucket

        width = _pow2_bucket(len(bids))
        ids = np.full(width, self.kv_blocks, np.int32)
        ids[:len(bids)] = bids
        gathered = self._kv_gather_jit(self._pool, jnp.asarray(ids))
        self._pending_spills.append(
            (key, want, floor, len(bids), gathered))

    def drain_spills(self) -> int:
        """Pack every staged spill into the tier (device-to-host copy
        + DKV1 frame). Runs at the end of ``step()`` — after the next
        round has already dispatched — and before any tier read that
        must see just-evicted entries (export fallback, snapshot).
        Returns the number of payloads drained."""
        if not self._pending_spills:
            return 0
        from deeplearning4j_tpu.serving.kv_transfer import pack_prefix

        staged, self._pending_spills = self._pending_spills, []
        for key, want, floor, n, gathered in staged:
            t0 = self._clock()
            layers = []
            for name in sorted(gathered):
                st = gathered[name]
                layers.append((name, np.asarray(st["pk"])[:n],
                               np.asarray(st["pv"])[:n]))
            payload = pack_prefix(list(key), want, floor,
                                  self.block_tokens, layers)
            tier = self.kv_tier.put(key, payload)
            self._observe("serving_kv_spill_s", self._clock() - t0)
            with self._span("serving.kv_spill", tokens=len(key),
                            blocks=n, tier=tier,
                            bytes=len(payload)):
                pass
        return len(staged)

    def _tier_reload(self, prompt) -> bool:
        """Admission-side tier check (the ladder's upward half): on a
        trie miss, the longest tier payload sharing a usable prefix
        with ``prompt`` re-imports through the jitted ``kv_import``
        scatter (``import_prefix`` — same pow2 buckets as the
        cross-replica plane, zero new executables) and re-seeds the
        trie. True = the caller should re-run its trie lookup. Every
        fault falls through to recompute: a malformed payload is
        dropped from the tier, a soft decline (pool/trie pressure)
        leaves it resident for a later retry."""
        ent = self.kv_tier.match(prompt)
        if ent is None:
            return False
        key, payload, tier_name = ent
        from deeplearning4j_tpu.serving.kv_transfer import (
            KVTransferError,
            import_prefix,
        )

        t0 = self._clock()
        try:
            out = import_prefix(self, payload)
        except KVTransferError:
            self.kv_tier.drop(key)
            self.stats["kv_tier_reload_faults"] += 1
            return False
        if not out.get("imported"):
            self.stats["kv_tier_reload_declined"] += 1
            return False
        self.kv_tier.take(key)
        dt = self._clock() - t0
        self._observe("serving_kv_reload_s", dt)
        with self._span("serving.kv_reload", tier=tier_name,
                        tokens=out.get("tokens"),
                        blocks=out.get("blocks"),
                        bytes=len(payload)):
            pass
        return True

    def _encode_prompt(self, prompt, bucket):
        """A prompt segment right-padded to ``bucket`` and its mask:
        ids ``[1, bucket]`` for a net that embeds them, one-hot columns
        ``[1, V, bucket]`` for one whose first layer takes ``n_in ==
        vocab``."""
        mask = np.zeros((1, bucket), np.float32)
        mask[0, :len(prompt)] = 1.0
        if self._ids_in:
            x = np.zeros((1, bucket), np.int32)
            x[0, :len(prompt)] = prompt
        else:
            x = np.zeros((1, self.vocab, bucket), np.float32)
            x[0, list(prompt), np.arange(len(prompt))] = 1.0
        return jnp.asarray(x), jnp.asarray(mask)

    def _start_admission(self, request: Request, slot: int):
        """Begin admitting ``request`` into ``slot``: look up the radix
        prefix cache, splice the matched prefix's blocks into the
        slot's table, and either
        prefill the whole suffix now (blocking mode) or enqueue a
        pending admission for chunk-by-chunk progress between decode
        rounds (chunked mode)."""
        self._started.add(request.id)
        clock = self._clock_of(request.id)
        if clock is not None:
            now = self._clock()
            self._observe("serving_queue_wait_s",
                          now - clock.enqueue_t)
            self._observe_tenant("serving_queue_wait_s",
                                 request.tenant,
                                 now - clock.enqueue_t)
            clock.add(now, "queue_wait", now - clock.enqueue_t,
                      slot=slot)
        matched, hit, tab = 0, None, None
        if self.prefix_cache is not None:
            hit = self.prefix_cache.lookup(request.prompt)
            if (self.kv_tier is not None
                    and (hit is None
                         or hit.matched <= self.prefix_cache.payload(
                             hit.row).floor)):
                # tier ladder, upward half (ISSUE 17): a trie miss
                # (or an unusable sub-floor hit) checks host DRAM,
                # then disk, BEFORE recomputing — a hit re-imports
                # through the jitted kv_import scatter and re-seeds
                # the trie, so the re-run lookup splices it exactly
                # like a never-evicted entry
                if hit is not None:
                    self.prefix_cache.release(hit)
                    hit = None
                if self._tier_reload(request.prompt):
                    hit = self.prefix_cache.lookup(request.prompt)
            if hit is not None:
                payload = self.prefix_cache.payload(hit.row)
                if hit.matched > payload.floor:
                    # ZERO-COPY warm hit: reference the entry's blocks
                    # up to the matched length (``KvMemory.splice``)
                    matched = hit.matched
                    tab, spliced = self.kv.splice(payload, matched)
                    self.stats["prefill_tokens_skipped"] += matched
                    with self._span("serving.prefix_splice",
                                    rid=request.id, row=hit.row,
                                    matched=matched, blocks=spliced,
                                    **_targs(request)):
                        pass
                    if clock is not None:
                        clock.event(self._clock(), "admit_splice",
                                    matched=matched, blocks=spliced)
                else:
                    self.prefix_cache.release(hit)
                    hit = None
        if tab is None and len(self.kv.kinds) > 1:
            # several kinds: a cold admission streams through the
            # block tables from its first token (a dense row would hold
            # every layer's keys for the whole prompt, and take no
            # band), each chunk's programs banding a layer by its window
            self._ensure_paged_pool()
            tab = self.kv.new_table()
        pending = _Pending(request, slot, None, None, 0, matched, hit,
                           tab=tab)
        if self.prefill_chunk:
            self._reserved.add(slot)
            self._pending.append(pending)
            return
        # blocking mode: the whole suffix in ONE pow2-bucketed prefill
        # (cold: the original admission path, bit for bit; warm: one
        # continuation chunk at the suffix's bucket)
        if not self._advance_prefill(pending, pending.remaining):
            self._defer_admission(pending)
            return
        self._complete_admission(pending)

    def _defer_admission(self, pending: _Pending) -> None:
        """Back out an admission the block pool cannot currently
        hold: release the trie lease and any spliced or
        written blocks, free the reserved slot, and requeue the
        request for the next round — decode drains slots and frees
        blocks, so capacity recovers without shedding."""
        if pending.hit is not None and self.prefix_cache is not None:
            self.prefix_cache.release(pending.hit)
            pending.hit = None
        self.kv.free(pending.tab)
        pending.tab = None
        self._reserved.discard(pending.slot)
        if pending in self._pending:
            self._pending.remove(pending)
        self.stats["paged_admit_deferred"] += 1
        clock = self._clock_of(pending.request.id)
        if clock is not None:
            clock.new_attempt(self._clock(), "admit_deferred")
        self._requeue.append((self._round + 1, pending.request))

    def _advance_prefill(self, pending: _Pending, max_tokens: int):
        """Prefill the next ``<= max_tokens`` tokens of a pending
        admission's sequence, padded+masked to a fixed width so repeat
        widths never retrace: ``prefill_chunk`` in chunked mode, the
        pow2 bucket of the segment in blocking mode."""
        req = pending.request
        lo = pending.matched + pending.done
        seg = list(pending.seq[lo:lo + max_tokens])
        width = (self.prefill_chunk
                 or self.scheduler.bucket_of(len(seg)))
        with self._span("serving.prompt_encode", rid=req.id,
                        width=width, tokens=len(seg)):
            x, mask = self._encode_prompt(seg, width)
            temp = jnp.asarray([req.temperature], jnp.float32)
            top_k = jnp.asarray([req.top_k or self.vocab], jnp.int32)
        clock = self._clock_of(req.id)
        warm = pending.tab is not None
        if warm:
            # WARM admission: the suffix chunk streams straight
            # into the slot's block table (spliced trie blocks +
            # freshly allocated ones) — no dense scratch row ever
            # materializes, which is what makes the warm path
            # zero-whole-row-copy
            if not self.kv.ensure(pending.tab, len(seg),
                                    rid=req.id):
                return False
            carried = self._pool
            tables = self.kv.pack([pending.tab], chunk=width,
                                        tokens=len(seg))
        else:
            carried, tables = pending.rnn, None
        t0 = self._clock()
        if carried is None:
            # first cold segment: no carried state yet — the bucketed
            # cold-prefill executable establishes it
            phase = "admit_cold"
            with self._span("serving.prefill", rid=req.id,
                            bucket=width, tokens=len(seg),
                            **_targs(req)):
                tok, rnn, counts = self._prefill_jit(
                    self._params, self._state, x, mask, temp,
                    top_k, self._next_key())
        else:
            phase = "admit_chunk"
            with self._span("serving.prefill_chunk", rid=req.id,
                            width=width, tokens=len(seg),
                            done=pending.done, **_targs(req)):
                tok, rnn, counts = self._chunk_jit(
                    self._params, self._state, x, mask, carried,
                    tables, temp, top_k, self._next_key())
        if clock is not None:
            now = self._clock()
            clock.add(now, phase, now - t0, tokens=len(seg))
        if warm:
            self._pool = self._strip_pool(rnn)
            pending.tab.length += len(seg)
            # (a prompt longer than a kind's window leaves blocks
            # behind it chunk by chunk)
            self.kv.expire(pending.tab)
        else:
            pending.rnn = rnn
        pending.tok = tok
        pending.counts.append(counts)
        pending.done += len(seg)
        self.stats["prefill_tokens"] += len(seg)
        self.stats["chunks_scheduled"] += 1
        return True

    def _ensure_paged_pool(self, rnn1=None) -> None:
        """Create the device block pool (``KvMemory.make_pool``) lazily
        from the first dense B=1 streaming state, which says each
        layer's heads; where no dense row is ever made (several kinds:
        every admission is paged), from the shapes a smallest cold
        prefill WOULD give, traced and not run. Every program that takes
        the pool hands it back at the dtype it came in with
        (``_forward_fn``). The slot-state rows (a recurrent state is
        accumulated into, not copied) stay at the dense row's dtype."""
        if self._pool is not None:
            return
        if rnn1 is None:
            x, mask = self._encode_prompt([0], self.scheduler.bucket_of(1))
            one = jnp.ones((1,), jnp.float32)
            _, rnn1, _ = jax.eval_shape(
                self._prefill_jit, self._params, self._state, x, mask,
                one, one.astype(jnp.int32), self._key)
        kv, row = self._split_row(rnn1)
        self.kv.make_pool(kv, self.net._compute_dtype)
        self._slot_state = jax.tree_util.tree_map(
            lambda a: jnp.zeros((self.n_slots,) + a.shape[1:], a.dtype),
            row)
        self._toks = self._place(jnp.zeros((self.n_slots,), jnp.int32))

    def _complete_admission(self, pending: _Pending):
        """Suffix fully prefilled: the first token into the slot and,
        for a cold admission, the one scatter of its dense B=1 row
        into freshly allocated blocks; then lease the prompt's blocks
        to the prefix cache and release the hit lease. Nothing is
        stored twice: the slot's blocks ARE the cache entry (zero-copy
        insert via refcount bumps)."""
        request, slot = pending.request, pending.slot
        if pending.tab is None:
            tab = self._write_row(pending.rnn, len(pending.seq), slot)
            if tab is None:
                self._defer_admission(pending)
                return
        else:
            tab = pending.tab
            pending.tab = None
        self._toks = self._tok_jit(self._toks, pending.tok,
                                   jnp.asarray(slot, jnp.int32))
        hit_row = None
        if self.prefix_cache is not None:
            if pending.hit is not None:
                hit_row = pending.hit.row
                self.prefix_cache.release(pending.hit)
            # zero-copy insert: the trie references the slot's own
            # blocks; the slot's next append CoWs the shared
            # boundary block instead of corrupting the entry
            self.prefix_cache.insert_blocks(request.prompt,
                                            tab.kinds[0])
        self._kv_tabs[slot] = tab
        self._reserved.discard(slot)
        # fetch the first token BEFORE stamping TTFT: the value fetch
        # is the sync point that forces the in-flight prefill/admit
        # dispatches to completion (async dispatch would otherwise
        # report host-side dispatch time as time-to-first-token)
        with self._span("serving.first_token_sync", rid=request.id):
            first = int(np.asarray(pending.tok)[0])
            for counts in pending.counts:
                self._add_counts(counts, prefill=True)
        submit_t = self._submit_t.get(request.id)
        ttft = (self._clock() - submit_t
                if submit_t is not None else None)
        clock = self._clock_of(request.id)
        if clock is not None:
            now = self._clock()
            clock.event(now, "first_token", ttft_s=ttft,
                        prefix_reused=pending.matched)
            clock.last_commit_t = now  # ITL starts after this token
            self._observe("serving_ttft_s", ttft)
            self._observe_tenant("serving_ttft_s", request.tenant,
                                 ttft)
            # warm-vs-recompute admission comparison (ISSUE 14): the
            # attempt's accumulated admission device work, split by
            # whether a cached prefix (local OR imported) was reused
            phases = clock.attempts[-1]["phases"]
            adm = (phases.get("admit_cold", 0.0)
                   + phases.get("admit_chunk", 0.0))
            self._observe("serving_admission_warm_s" if pending.matched
                          else "serving_admission_cold_s", adm)
        state = _Slot(request, [first], prefix_reused=pending.matched,
                      ttft_s=ttft, hit_row=hit_row)
        self.stats["tokens_generated"] += 1
        self.stats["admitted"] += 1
        self._tenant_count(request.tenant, "admitted")
        self._tenant_count(request.tenant, "tokens_generated")
        if self._finished(state):
            # PR 3 blind spot (ISSUE 4 satellite): a request finishing
            # AT admission never reaches the post-decode health sweep,
            # so a fault injected the same round (a poisoned prefix
            # block riding the splice in) would be delivered as a healthy
            # terminal. Check the admitted row BEFORE draining its
            # terminal — same health executable, same shapes, so
            # compile counts are untouched.
            if (self._health_jit is not None
                    and not self._row_healthy(slot)):
                self._quarantine_victim(slot, state)
                return
            self._finish(state, slot, evict=False)
        else:
            self._slots[slot] = state
            self._temps[slot] = request.temperature
            self._top_ks[slot] = request.top_k or self.vocab
            if self.spec is not None:
                self.spec.seed(slot, [int(t) for t in request.prompt]
                               + state.tokens)

    @staticmethod
    def _hit_eos(slot_state: _Slot) -> bool:
        req = slot_state.request
        return bool(req.eos_id is not None
                    and slot_state.tokens
                    and slot_state.tokens[-1] == req.eos_id)

    def _finished(self, slot_state: _Slot) -> bool:
        if len(slot_state.tokens) >= slot_state.request.max_new_tokens:
            return True
        return self._hit_eos(slot_state)

    def _finish(self, slot_state: _Slot, slot: int,
                evict: bool = True):
        # eos wins even when it lands exactly on the max_new_tokens-th
        # token: the response terminated cleanly, not by truncation
        reason = "eos" if self._hit_eos(slot_state) else "length"
        self._record_terminal(slot_state.request, slot_state.tokens,
                              reason, slot_state.prefix_reused,
                              slot_state.ttft_s,
                              slot_state.spec_drafted,
                              slot_state.spec_accepted)
        if evict:
            self._evict_slot(slot)

    # -- failure handling ----------------------------------------------
    def _elapsed(self, request_id: int, now: float) -> Optional[float]:
        t0 = self._submit_t.get(request_id)
        return None if t0 is None else now - t0

    def _sweep_deadlines(self) -> None:
        """Expire deadlines/queue-timeouts wherever the request is.
        Queued: removed before any device work. Mid-admission: the
        reserved slot is freed and the lease released. Running: the
        slot evicts via the normal path (neighbours keep
        decoding), partial tokens are returned. No-op (and zero cost)
        unless some submitted request carried a deadline."""
        if not self._has_deadlines:
            return
        now = self._clock()
        for req in self.scheduler.queued_requests():
            el = self._elapsed(req.id, now)
            if el is None:
                continue
            if req.deadline_s is not None and el > req.deadline_s:
                self.scheduler.remove(req.id)
                self._record_terminal(req, [], "deadline")
                self._failure_event("deadline_expired")
            elif (req.queue_timeout_s is not None
                  and req.id not in self._started
                  and el > req.queue_timeout_s):
                # first-admission wait only: a fault-retried request
                # back in the queue already started once — shedding it
                # here would break the retry the quarantine promised
                self.scheduler.remove(req.id)
                self._shed(req)
                self._failure_event("queue_timeouts")
        for ready, req in list(self._requeue):
            el = self._elapsed(req.id, now)
            if (el is not None and req.deadline_s is not None
                    and el > req.deadline_s):
                self._requeue.remove((ready, req))
                self._record_terminal(req, [], "deadline")
                self._failure_event("deadline_expired")
        for pending in list(self._pending):
            el = self._elapsed(pending.request.id, now)
            if (el is not None and pending.request.deadline_s is not None
                    and el > pending.request.deadline_s):
                self._abort_pending(pending)
                self._record_terminal(pending.request, [], "deadline")
                self._failure_event("deadline_expired")
        for slot, state in enumerate(self._slots):
            if state is None:
                continue
            el = self._elapsed(state.request.id, now)
            if (el is not None and state.request.deadline_s is not None
                    and el > state.request.deadline_s):
                self._record_terminal(
                    state.request, state.tokens, "deadline",
                    state.prefix_reused, state.ttft_s,
                    state.spec_drafted, state.spec_accepted)
                self._failure_event("deadline_expired")
                self._evict_slot(slot)
        # drop the flag once no live request carries a time budget —
        # the sweep stays zero-cost afterwards and, since the flag
        # also gates fused dispatch (``_plan_fused``), one
        # deadline-carrying request must not disable fusing for the
        # rest of the engine's life
        def _timed(req: Request) -> bool:
            return (req.deadline_s is not None
                    or req.queue_timeout_s is not None)

        self._has_deadlines = (
            any(_timed(r) for r in self.scheduler.queued_requests())
            or any(_timed(r) for _, r in self._requeue)
            or any(_timed(p.request) for p in self._pending)
            or any(s is not None and _timed(s.request)
                   for s in self._slots))

    def _inject_faults(self) -> None:
        if self.fault_plan is None:
            return
        for event in self.fault_plan.events_at(self._round):
            self._inject(event)

    def _inject(self, event: FaultEvent) -> None:
        """Apply one scheduled fault. All injection is host-side (see
        serving/faults.py) — compile counts cannot change. Events whose
        target does not exist this round (no active slot to NaN, no
        stored cache entry to corrupt) are skipped and NOT recorded."""
        if event.kind == "stall":
            if hasattr(self._clock, "advance"):
                self._clock.advance(event.seconds)
            else:
                time.sleep(event.seconds)
        elif event.kind == "admit_fail":
            self._admit_fail_pending += 1
        elif event.kind == "nan":
            slot = event.slot
            if slot is None:
                active = [i for i, s in enumerate(self._slots)
                          if s is not None]
                slot = active[0] if active else None
            if (slot is None or slot >= self.n_slots
                    or self._slots[slot] is None or self._pool is None):
                return
            # poison the slot's EXCLUSIVELY-owned blocks (the ones
            # its own decode writes touch — a sampler NaN lands
            # there); shared prefix blocks model a different fault
            # (cache_corrupt) and are immutable to this slot
            tab = self._kv_tabs[slot]
            excl = [b for b in (tab.blocks.values() if tab else [])
                    if self.block_pool.refcount(b) == 1]
            if not excl:
                return
            self._pool = poison_rows(self._pool, excl)
        elif event.kind == "cache_corrupt":
            if self.prefix_cache is None or self._pool is None:
                return
            rows = self.prefix_cache.stored_rows()
            row = event.row if event.row is not None else (
                rows[0] if rows else None)
            if row is None or row not in rows:
                return
            # bit-rot one block of the stored entry; the paranoid
            # per-block sweep (or the splice victim's probe)
            # catches it and invalidates the entry
            blocks = self.prefix_cache.payload(row).blocks
            if not blocks:
                return
            bid = blocks[min(blocks)]
            self._pool = poison_rows(self._pool, [bid])
        self.fault_plan.record(event)
        self._failure_event("faults_injected")

    def _requeue_victim(self, request: Request) -> None:
        """Schedule a fault victim's re-admission: capped retries with
        exponential backoff (in rounds); past the cap the request
        terminates with ``finish_reason="fault"``."""
        attempts = self._retries.get(request.id, 0) + 1
        if attempts > self.max_retries:
            self._retries[request.id] = attempts - 1
            self._record_terminal(request, [], "fault")
            self._failure_event("retry_failures")
            return
        self._retries[request.id] = attempts
        self._failure_event("retries")
        clock = self._clock_of(request.id)
        if clock is not None:
            clock.new_attempt(self._clock(), "fault_retry")
        ready = self._round + max(
            1, self.retry_backoff_rounds * (2 ** (attempts - 1)))
        self._requeue.append((ready, request))

    def _drain_requeue(self) -> None:
        if not self._requeue:
            return
        ready = [(r, q) for r, q in self._requeue if r <= self._round]
        if not ready:
            return
        self._requeue = [(r, q) for r, q in self._requeue
                         if r > self._round]
        for _, req in ready:
            self.scheduler.requeue(req)

    def _paged_health(self):
        """Run the per-block health executable and fold the verdict
        back through the host block tables: returns
        ``(bad_blocks: set, toks_ok: np.ndarray[B])``. Bad blocks are
        remembered in the pool's poisoned set so they are scrubbed the
        moment their last reference drops — never while an innocent
        sharer still reads them."""
        blocks_ok, toks_ok = self._health_jit(self._pool, self._toks)
        blocks_ok = np.asarray(blocks_ok)
        bad = {b for b in np.nonzero(~blocks_ok)[0].tolist()
               if self.block_pool.refcount(b) > 0}
        self.block_pool.poisoned.update(bad)
        return bad, np.asarray(toks_ok)

    def _slot_blocks_bad(self, slot: int, bad: set) -> bool:
        tab = self._kv_tabs[slot]
        return bool(tab and (set(tab.blocks.values()) & bad))

    def _row_healthy(self, slot: int) -> bool:
        """One slot's verdict from the (single) jitted health check —
        the at-admission probe for requests that finish before any
        decode round could sweep them."""
        bad, toks_ok = self._paged_health()
        return bool(toks_ok[slot]) and not self._slot_blocks_bad(
            slot, bad)

    def _quarantine_victim(self, slot: int, state: _Slot) -> None:
        """Quarantine one poisoned slot: its blocks released (a
        poisoned one is scrubbed as its last reference drops, so the
        pool is finite again), its prefix-cache footprint invalidated
        (both the entry the admission spliced from and the entry it
        inserted,
        since either end may carry the corruption), draft state
        dropped, and the victim re-queued with backoff. Shared by the
        post-decode sweep and the finish-at-admission probe."""
        self._failure_event("faults_detected")
        self._failure_event("quarantined")
        if self.prefix_cache is not None:
            if state.hit_row is not None:
                # only scrub the spliced entry if it still shares
                # the matched prefix with this prompt (the stored
                # entry may extend past it — rewind semantics) —
                # LRU may have recycled the id for an unrelated
                # healthy entry since the admission spliced it
                held = self.prefix_cache.row_prefix(state.hit_row)
                prompt = tuple(int(t)
                               for t in state.request.prompt)
                m = state.prefix_reused
                if (held is not None and len(held) >= m
                        and held[:m] == prompt[:m]):
                    self.prefix_cache.invalidate_row(state.hit_row)
            self.prefix_cache.invalidate(state.request.prompt)
        self._evict_slot(slot)
        if ((self.on_delta is not None or self.emit_deltas)
                and state.request.temperature > 0
                and self._delta_sent.get(state.request.id, 0) > 0):
            # a SAMPLING victim that already streamed tokens cannot be
            # retried under incremental delivery: the retry redraws
            # RNG, so its tokens diverge from the streamed prefix and
            # the high-water dedup would splice two different
            # sequences into one stream. Greedy retries reproduce the
            # prefix bit-identically (they requeue below); a sampled
            # stream fails honestly instead of lying token-by-token —
            # and its terminal carries the already-streamed tokens
            # (state.tokens == exactly what was delivered: the
            # poisoned round's output never appended), keeping the
            # concat(deltas)==terminal invariant even on this path
            self._record_terminal(state.request, state.tokens, "fault",
                                  state.prefix_reused, state.ttft_s,
                                  state.spec_drafted,
                                  state.spec_accepted)
            self._failure_event("retry_failures")
            return
        self._requeue_victim(state.request)

    def _quarantine(self, active: List[int]) -> List[int]:
        """Paranoid sweep after decode/verify: one jitted finiteness
        check over the pool + sampled ids. Poisoned slots are handed to
        ``_quarantine_victim``. Returns the healthy subset of
        ``active`` — the poisoned round's tokens never reach a
        result."""
        bad, toks_ok = self._paged_health()
        healthy, victims = [], []
        for slot in active:
            if bool(toks_ok[slot]) and not self._slot_blocks_bad(
                    slot, bad):
                healthy.append(slot)
            else:
                victims.append(slot)
        for slot in victims:
            self._quarantine_victim(slot, self._slots[slot])
        if bad and self.prefix_cache is not None:
            # entries still holding poisoned blocks (cache bit-rot
            # caught BEFORE any splice — the shared pool makes
            # corruption visible immediately)
            for row in list(self.prefix_cache.stored_rows()):
                payload = self.prefix_cache.payload(row)
                if set(payload.blocks.values()) & bad:
                    self.prefix_cache.invalidate_row(row)
                    self._failure_event("faults_detected")
        return healthy

    # -- speculative draft & verify (ISSUE 4) --------------------------
    def _plan_drafts(self, active: List[int]) -> Dict[int, List[int]]:
        """Per-slot draft proposals for this round from the n-gram
        tables. Sampling slots draft too (ISSUE 16): the stochastic
        acceptance rule gives a drafted sampling slot exactly the
        target model's sampling marginals, so temperature traffic
        rides the same verify pass greedy traffic does. Each draft is
        capped at the live K (``Scheduler.draft_len`` —
        acceptance-adapted), the tokens the round's decode chunk won't
        already deliver (a request the chunk alone finishes gains
        nothing from drafting — its verify lanes would be pure waste),
        and the slot's window headroom: a rejected tail can only be
        rewound while no token slid out of the sliding window, so a
        slot within K+1 tokens of saturation drafts less (down to
        zero at the brim — the chunk still advances it exactly like
        plain decode)."""
        k = self.scheduler.draft_len
        drafts: Dict[int, List[int]] = {}
        for slot in active:
            state = self._slots[slot]
            req = state.request
            filled = min(len(req.prompt) + len(state.tokens) - 1,
                         self.window)
            cap = min(k,
                      req.max_new_tokens - len(state.tokens)
                      - self.decode_chunk,
                      self.window - filled - 1)
            drafts[slot] = (self.spec.draft(slot, cap) if cap > 0
                            else [])
        return drafts

    def _dispatch_verify(self, drafts: Dict[int, List[int]], pool_op,
                         tables):
        """Dispatch one batched draft-verify pass over the whole slot
        pool: pad every slot's draft to the round's pow2 width bucket
        (compile counts stay O(log K)) and run the single verify
        executable (forward + greedy acceptance + per-slot rewind +
        bonus token in one program). The pool/current-token state is
        updated in place with the (still in-flight) device outputs so
        the round's decode chunk chains onto the committed state (and
        onto the tables the program hands back with each row's
        rejected tail rewound out of ``filled``) —
        NOTHING syncs here; ``_land_verify`` fetches the results after
        the decode dispatch so a speculative round still costs ONE
        host round-trip."""
        max_len = max(len(d) for d in drafts.values())
        width = min(scan_length_bucket(max_len, minimum=1),
                    self.window - 1)
        draft = np.zeros((self.n_slots, width), np.int32)
        lens = np.zeros(self.n_slots, np.int32)
        for slot, toks in drafts.items():
            toks = list(toks)[:width]
            if toks:
                draft[slot, :len(toks)] = toks
            lens[slot] = len(toks)
        with self._span("serving.spec_verify", width=width,
                        drafted=int(lens.sum()),
                        rids=[self._slots[s].request.id
                              for s, d in drafts.items() if d],
                        **self._traces_of(
                            s for s, d in drafts.items() if d)):
            (pool_op, tables, self._toks, emitted,
             acc) = self._verify_jit(
                self._params, self._state, pool_op, tables,
                self._toks, jnp.asarray(draft), jnp.asarray(lens),
                jnp.asarray(self._temps), jnp.asarray(self._top_ks),
                self._next_key())
        return pool_op, tables, (lens, emitted, acc)

    def _land_verify(self, drafts: Dict[int, List[int]], lens,
                     emitted, acc):
        """Fetch a dispatched verify pass's results (the decode sync
        already forced them) and do the host-side accounting: per-slot
        and cumulative acceptance counters, and the K-adaptation
        feedback. Returns ``(rows, n_emit)``: ``rows[slot][:n_emit]``
        are the slot's speculative tokens this round — its accepted
        draft prefix plus the model's own token at the first
        divergence (or the free extra token on full acceptance)."""
        emitted = np.asarray(emitted)  # [B, W+1]
        acc = np.asarray(acc)
        drafted = int(lens.sum())
        accepted = int(acc.sum())  # undrafted rows contribute 0
        self.stats["spec_rounds"] += 1
        self.stats["spec_drafted"] += drafted
        self.stats["spec_accepted"] += accepted
        for slot in drafts:
            state = self._slots[slot]
            state.spec_drafted += int(lens[slot])
            state.spec_accepted += int(acc[slot])
        self.scheduler.record_acceptance(drafted, accepted)
        if self.tracer is not None:
            self.tracer.counter("serving_spec_accept_rate",
                                accepted / max(drafted, 1))
            self.tracer.counter("serving_spec_draft_len",
                                self.scheduler.draft_len)
        return emitted, acc + 1

    # -- multi-tenant QoS round hook (ISSUE 13) ------------------------
    def _qos_round(self) -> None:
        """Once per scheduling round, before admission: feed the
        weighted-fair scheduler the per-tenant slot occupancy
        (deficit refill + quota accounting), then recompute-preempt
        the over-quota slots it names — through the PR 6 preemption
        path, so a high-priority arrival admits THIS round instead
        of waiting out a flooder's decode rounds. Greedy victims
        requeue and regenerate bit-identical ids; a sampling victim
        that already streamed terminates ``fault`` (the preemption
        contract, unchanged)."""
        running: Dict[str, int] = {}
        view: List[Tuple[int, str, int]] = []
        for slot, state in enumerate(self._slots):
            if state is None:
                continue
            tenant = state.request.tenant
            running[tenant] = running.get(tenant, 0) + 1
            view.append((slot, tenant,
                         self.tenants.effective_priority(
                             state.request)))
        for pending in self._pending:
            tenant = pending.request.tenant
            running[tenant] = running.get(tenant, 0) + 1
        self.scheduler.begin_round(running)
        if not self.scheduler.pending or not view:
            return
        free = sum(1 for slot in range(self.n_slots)
                   if self._slots[slot] is None
                   and slot not in self._reserved)
        for slot in self.scheduler.plan_preemptions(view, free):
            if self._slots[slot] is not None:
                self.stats["qos_preempted"] = (
                    self.stats.get("qos_preempted", 0) + 1)
                if self.tracer is not None:
                    self.tracer.incr("serving_qos_preempted")
                self._preempt_slot(slot)

    # -- fused multi-round decode (ISSUE 16) ---------------------------
    def _plan_fused(self, active: List[int], spec_round: bool) -> int:
        """Rounds to fuse into this dispatch: 0 = step (the plain
        decode executable), K >= 1 = one K-round scan. A scan is
        dispatched only when NOTHING needs a per-round host decision:
        no queued arrivals (``Scheduler.decision_pending`` — also the
        gate on QoS preemption planning, which only fires for queued
        arrivals), no admission mid-prefill, no requeued victims
        waiting out a backoff, no fault plan (injections are
        round-indexed), no live deadlines (a deadline must be able to
        expire between ROUNDS, not between windows), and no draft this
        round (a verify pass needs its per-round host lookup). Cancels
        need no carve-out: a cancel mid-window lands through the
        ``rids`` guard exactly like the async-rounds engine, and the
        NEXT round sees the freed slot. K is the pow2 bucket covering
        the widest live request's remaining rounds, capped at
        ``fused_rounds`` — the executable set is bounded at
        log2(fused_rounds) + 1 and a near-finished batch never pays
        for rounds it cannot use."""
        if (not self.fused_rounds or self._fused_jit is None
                or spec_round or self._pending or self._requeue
                or self.fault_plan is not None or self._has_deadlines
                or self.scheduler.decision_pending()):
            return 0
        max_rem = max(self._slots[s].request.max_new_tokens
                      - len(self._slots[s].tokens) for s in active)
        need = -(-max_rem // self.decode_chunk)
        k = 1
        while k < need and k * 2 <= self.fused_rounds:
            k *= 2
        return k

    def _reserve_round(self, active: List[int], drafts, spec_round,
                       fuse_k: int):
        """Before a decode dispatch (the
        ``serving.reserve`` span): the round's view of ``(active,
        drafts, spec_round, fuse_k)`` after every block its writes
        will cross into is reserved."""
        # allocation on demand: reserve every block this round's
        # writes will cross into (verify width + the decode chunk),
        # CoW-ing tail blocks still shared with the trie — under pool
        # pressure the youngest slot is preempted (requeued, ids
        # regenerate identically)
        ensured: set = set()
        for slot in list(active):
            if self._slots[slot] is None:
                continue   # preempted by an earlier reserve
            n_tok = max(fuse_k, 1) * self.decode_chunk
            if spec_round:
                n_tok += len(drafts.get(slot, ())) + 1
            if self.kv.ensure(
                    self._kv_tabs[slot], n_tok,
                    protect=ensured | {slot},
                    rid=self._slots[slot].request.id):
                ensured.add(slot)
            else:
                self._preempt_slot(slot)
        # preemption (by the reserve or explicit) may have emptied
        # slots mid-list — rebuild the round's view
        active = [s for s in active if self._slots[s] is not None]
        if drafts is not None:
            drafts = {s: d for s, d in drafts.items() if s in active}
            spec_round = any(drafts.values())
        if fuse_k and self._requeue:
            # a pool-pressure preemption during reservation is a
            # scheduling decision: fall back to stepped (the extra
            # reserved blocks stay table-owned for the following
            # rounds — nothing leaks)
            fuse_k = 0
        return active, drafts, spec_round, fuse_k

    # -- the serving loop ----------------------------------------------
    def has_work(self) -> bool:
        """True while anything is queued, admitting, decoding,
        waiting out a retry backoff, or dispatched-but-unlanded
        (async rounds)."""
        return bool(self.scheduler.pending or self._pending
                    or self._requeue or self._inflight is not None
                    or any(s is not None for s in self._slots))

    def _drain_terminal(self, results: Dict[int, GenerationResult]):
        if self._terminal:
            results.update(self._terminal)
            self._terminal.clear()

    def _land_round(self, inf: _InflightRound) -> None:
        """Commit one dispatched decode round: fetch the tokens (the
        sync point), mirror table advances, run the paranoid
        sweep, append/stream committed tokens, finish/evict, and do
        the round's accounting. Synchronous engines call this inline
        right after dispatch (behavior identical to the pre-ISSUE-14
        engine); ``async_rounds`` engines call it at the START of the
        next ``step()``, before any scheduling decision, which is what
        keeps ids bit-identical while the fetch overlaps the
        inter-step host gap.

        Slots whose request was cancelled or deadline-evicted between
        dispatch and landing (async mode only — handler threads share
        the engine lock between steps) are skipped via the ``rids``
        guard: their rows are discarded, and the blocks their
        in-flight writes touched were either still table-mapped
        (harmless overwrite of live positions' successors, masked by
        ``filled``) or freed-but-unreallocated (nothing allocates
        between dispatch and landing)."""
        t_sync0 = self._clock() if self.record_timing else 0.0
        # a synchronous round fetched inside ``serving.decode_chunk``;
        # only a round left in flight still has the device to wait for
        with (self._span("serving.token_sync") if self.async_rounds
              else contextlib.nullcontext()):
            seq, n_valid, counts = jax.device_get(
                (inf.seq, inf.n_valid, inf.counts or {}))  # one fetch
            self._add_counts(counts)
        with self._span("serving.commit", active=len(inf.active)):
            self._commit_round(inf, seq, n_valid, t_sync0)

    def _commit_round(self, inf: _InflightRound, seq, n_valid,
                      t_sync0: float) -> None:
        """The host's half of landing a round, after the fetch: the
        ``serving.commit`` span."""
        v_n = None
        v_rows = None
        if inf.verify_out is not None:
            live_drafts = {
                s: d for s, d in inf.drafts.items()
                if (self._slots[s] is not None
                    and self._slots[s].request.id == inf.rids.get(s))}
            v_rows, v_n = self._land_verify(live_drafts,
                                            *inf.verify_out)
        ver_dt = inf.ver_dt
        # decode attribution: dispatch wall + sync wall — in sync
        # mode the fetch already happened inside the dispatch window
        # so the second term is ~0 and this equals the pre-ISSUE-14
        # measurement; in async mode the inter-step gap is EXCLUDED
        # (it belongs to no phase — the device was working, the host
        # was elsewhere), keeping phase sums <= e2e.
        dec_dt = ((inf.dispatch_end - inf.td0)
                  + (self._clock() - t_sync0)
                  if self.record_timing else 0.0)
        if self.tp > 1 and self.record_timing:
            # sharded-dispatch wall (ISSUE 12): the decode (and
            # chained verify) round-trips through the shard_map
            # executables — per-dispatch, not per-token, so the
            # histogram reads as "what does one TP round cost"
            self._observe("serving_tp_dispatch_s", dec_dt)
            if ver_dt:
                self._observe("serving_tp_dispatch_s", ver_dt)
        active = [s for s in inf.active
                  if self._slots[s] is not None
                  and self._slots[s].request.id == inf.rids.get(s)]
        if v_rows is not None:
            rows = [list(v_rows[s][:int(v_n[s])]) + list(seq[s])
                    for s in range(self.n_slots)]
        elif n_valid is not None:
            # fused scan: the device already found each slot's
            # committed prefix (eos / max_new_tokens cut); the
            # overshoot rows past it are dead-row ride-along, dropped
            # here (the _finished break below stays as backstop)
            rows = [list(seq[s][:int(n_valid[s])])
                    for s in range(self.n_slots)]
        else:
            rows = seq
        # host-loop observability (ISSUE 16): the token sync is done —
        # everything until the next decode dispatch is host-loop wall
        if self.record_timing:
            self._last_sync_end = self._clock()
        dt = time.perf_counter() - inf.t0
        # mirror the device-side filled advance (decode writes —
        # n_rounds * decode_chunk under a fused scan — + verify's
        # accepted+bonus) into the host tables, and release blocks
        # that slid out of every window
        with self._span("serving.kv_release"):
            for slot in active:
                tab = self._kv_tabs[slot]
                tab.length += inf.decode_tokens + (
                    int(v_n[slot]) if v_n is not None else 0)
                self.kv.expire(tab)
            self.kv.count_held(active)
        if self.paranoid:
            active = self._quarantine(active)
        emitted = 0
        round_usage: Dict[str, int] = {}
        for slot in active:
            state = self._slots[slot]
            appended = []
            for tok in rows[slot]:
                state.tokens.append(int(tok))
                appended.append(int(tok))
                emitted += 1
                if self._finished(state):
                    break
            if self.tenants is not None and appended:
                tenant = state.request.tenant
                round_usage[tenant] = (
                    round_usage.get(tenant, 0) + len(appended))
                self._tenant_count(tenant, "tokens_generated",
                                   len(appended))
            # deltas flow AFTER the paranoid sweep filtered
            # ``active`` (a quarantined slot's round never streams)
            # and cover the admission's first token too — the
            # diff-based high-water mark picks it up here, where
            # this round's health verdict is already in
            self._note_progress(state)
            if self.record_timing and appended:
                clock = self._clocks.get(state.request.id)
                if clock is not None:
                    now_c = self._clock()
                    if ver_dt:
                        clock.add(now_c, "verify", ver_dt)
                    clock.add(now_c, "decode", dec_dt)
                    if clock.last_commit_t is not None:
                        gap = ((now_c - clock.last_commit_t)
                               / len(appended))
                        self._observe("serving_itl_s", gap,
                                      n=len(appended))
                        self._observe_tenant(
                            "serving_itl_s",
                            state.request.tenant, gap,
                            n=len(appended))
                    clock.last_commit_t = now_c
                    clock.rounds += inf.n_rounds
                    clock.event(now_c, "commit", n=len(appended))
            if self._finished(state):
                self._finish(state, slot)
            elif self.spec is not None:
                # committed ids extend the slot's n-gram context;
                # finished slots dropped theirs in _evict_slot
                self.spec.extend(slot, appended)
        self.stats["tokens_generated"] += emitted
        self.stats["decode_time_s"] += dt
        self.stats["chunks"] += 1
        if self.tenants is not None and round_usage:
            # committed decode tokens charge each tenant's
            # deficit: the fair share is tokens, not admissions
            self.scheduler.note_usage(round_usage)
        occ = len(active) / self.n_slots
        self.stats["occupancy_sum"] += occ
        if self.tracer is not None:
            self._emit_counters()

    def step(self, results: Optional[Dict[int, GenerationResult]] = None
             ) -> Dict[int, GenerationResult]:
        """One scheduling round: requeue/faults/deadline sweeps, admit
        into free slots (advancing chunked prefills under the
        scheduler's round budget), one decode chunk, paranoid
        quarantine, evictions. Public so a caller can interleave
        ``cancel()`` / ``snapshot()`` / fault assertions with progress;
        ``run()`` is exactly a ``step()`` loop. Terminal results
        accumulate into (and are returned via) ``results``.

        The call is one ``serving.round`` span; inside it every
        stretch of host work is one leaf span (``serving.sweeps``,
        ``serving.admit`` and its children, ``serving.reserve``,
        ``serving.tables``, ``serving.decode_dispatch``,
        ``serving.token_sync``, ``serving.commit``,
        ``serving.round_end``), so that a device idle gap in a
        ``jax.profiler`` trace is named by what the host was doing."""
        if results is None:
            results = {}
        with self._span("serving.round", round=self._round):
            self._run_round(results)
        return results

    def _run_round(self, results: Dict[int, GenerationResult]) -> None:
        if self._inflight is not None:
            # async double-buffered rounds (ISSUE 14): land the round
            # the PREVIOUS step dispatched before any of this round's
            # scheduling. Everything below — admission, eviction, QoS,
            # draft planning — then sees exactly the state the
            # synchronous engine would at the same point, so ids are
            # bit-identical; only the host's observation of the round
            # moved, letting the inter-step gap (gateway lock yields,
            # submit handling) overlap device compute instead of
            # inflating decode ITL under admission storms.
            inf, self._inflight = self._inflight, None
            self._land_round(inf)
        # phase-clock round anchors (ISSUE 7): the pre-decode gap —
        # sweeps, fault handling, OTHER requests' admission chunks —
        # is the "stall" phase of every slot that was already running
        # when the round began (captured as (slot, rid) pairs so a
        # same-round evict+readmit cannot misattribute)
        rt0 = self._clock() if self.record_timing else None
        running_at_start = (
            [(i, s.request.id) for i, s in enumerate(self._slots)
             if s is not None] if self.record_timing else ())
        t_start = (self._clock()
                   if self.stall_threshold_s is not None else None)
        # an admit_fail is scoped to ITS round ("the next admission
        # this round fails"): one left unconsumed — no admission ran —
        # expires rather than ambushing an unrelated later workload
        self._admit_fail_pending = 0
        with self._span("serving.sweeps"):
            self._drain_requeue()
            self._inject_faults()
            self._sweep_deadlines()
            if self.tenants is not None:
                self._qos_round()
        for slot in range(self.n_slots):
            if (self._slots[slot] is None
                    and slot not in self._reserved
                    and self.scheduler.pending):
                if self._admit_fail_pending > 0:
                    # injected admission-time allocation failure: the
                    # victim re-queues with backoff, no device work
                    # ran. It still counts as STARTED — service was
                    # attempted, so queue_timeout_s (a bound on
                    # time-to-first-service) no longer sheds its retry
                    self._admit_fail_pending -= 1
                    victim = self.scheduler.pop()
                    self._started.add(victim.id)
                    self._failure_event("faults_detected")
                    self._requeue_victim(victim)
                    continue
                # the scheduler chooses WHOM to admit (FIFO without
                # tenancy; priority-then-deficit with it); None =
                # every queued tenant is over its slot quota, so the
                # round admits nobody rather than admitting unfairly
                nxt = self.scheduler.pop_admissible()
                if nxt is None:
                    break
                with self._admit_span(nxt, slot):
                    self._start_admission(nxt, slot)
        if self._pending:
            if self.adaptive_prefill:
                budget = self.scheduler.adapt_budget()
                if self.tracer is not None:
                    self.tracer.counter("serving_prefill_budget",
                                        budget)
                    self.tracer.counter("serving_pressure",
                                        self.scheduler.pressure())
            # a verify pass occupies the same between-decode gap that
            # prefill chunks do: bill its width (current K + the
            # current token) against the round's prefill budget so the
            # admission policies' decode-gap promises still hold
            verify_reserve = 0
            if (self.spec is not None
                    and any(s is not None for s in self._slots)):
                verify_reserve = self.scheduler.draft_len + 1
            grants = self.scheduler.plan_chunks(
                [p.remaining for p in self._pending],
                verify_tokens=verify_reserve)
            targets = [self._pending[i] for i in grants]
            deferred: set = set()
            for p in targets:
                if id(p) in deferred:
                    continue
                with self._admit_span(p.request, p.slot):
                    advanced = self._advance_prefill(
                        p, self.prefill_chunk)
                if not advanced:
                    # paged pool pressure: back the admission out and
                    # retry next round (decode keeps its cadence)
                    self._defer_admission(p)
                    deferred.add(id(p))
            if self.tracer is not None:
                self.tracer.counter("serving_round_prefill_chunks",
                                    len(grants))
            finished = [p for p in self._pending
                        if p.remaining == 0]
            for p in finished:
                with self._admit_span(p.request, p.slot):
                    self._complete_admission(p)
                if p in self._pending:
                    self._pending.remove(p)
        active = [i for i, s in enumerate(self._slots)
                  if s is not None]
        if active:
            drafts = (self._plan_drafts(active)
                      if self.spec is not None else None)
            spec_round = drafts is not None and any(drafts.values())
            fuse_k = self._plan_fused(active, spec_round)
            with self._span("serving.reserve", active=len(active)):
                (active, drafts, spec_round,
                 fuse_k) = self._reserve_round(
                    active, drafts, spec_round, fuse_k)
        if active:
            # (with every slot preempted for blocks the round ends with
            # no decode; requeues drain next round)
            t0 = time.perf_counter()
            verify_out = None
            ver_dt = 0.0
            if self.record_timing:
                # stall phase: round start → decode dispatch, for
                # slots that were running the whole time (disjoint
                # from their own decode/verify attribution below)
                t_pre = self._clock()
                if t_pre > rt0:
                    for slot, rid0 in running_at_start:
                        state = self._slots[slot]
                        if state is None or state.request.id != rid0:
                            continue
                        clock = self._clocks.get(rid0)
                        if clock is not None:
                            clock.add(t_pre, "stall", t_pre - rt0)
            with self._span("serving.tables", active=len(active)):
                # the round's ONE upload of the block tables, shared
                # by every layer and by the verify and decode
                # dispatches
                tables = self.kv.pack(self._kv_tabs)
                pool_op = self._pool
                if self._slot_state:
                    # the slot-state layers' rows ride the dispatch
                    # beside the KV leaves (``_strip_pool`` parts them)
                    pool_op = dict(pool_op, **self._slot_state)
                live = self._live_operand()
                temps = self._uploaded("temps", self._temps)
                top_ks = self._uploaded("top_ks", self._top_ks)
            if spec_round:
                # verify dispatch chains into the decode dispatch
                # below (the scan resumes from the verified state), so
                # a speculative round commits accepted drafts + bonus
                # + a full decode chunk in ONE host round-trip — the
                # round count can never exceed the spec-off engine's
                # (the rewind travels inside the executable as
                # a filled decrement, and the post-verify tables chain
                # into the decode scan as the verify program's output)
                tv0 = self._clock() if self.record_timing else 0.0
                pool_op, tables, verify_out = self._dispatch_verify(
                    drafts, pool_op, tables)
                if self.record_timing:
                    ver_dt = self._clock() - tv0
            elif self.spec is not None:
                # no slot drafted anything (no n-gram match, or every
                # slot samples): plain decode — speculation is an
                # accelerator, never a requirement
                self.stats["spec_fallback_rounds"] += 1
            td0 = self._clock() if self.record_timing else 0.0
            if self.record_timing and self._last_sync_end is not None:
                # host-loop wall: previous round's token sync to this
                # dispatch — the per-round cost a fused scan amortizes
                self._observe("serving_host_step_s",
                              td0 - self._last_sync_end)
            n_valid, counts = None, {}
            with self._span("serving.decode_chunk",
                            active=len(active), fused=fuse_k,
                            rids=[self._slots[s].request.id
                                  for s in active],
                            **self._traces_of(active)):
                with self._span("serving.decode_dispatch"):
                    if fuse_k:
                        # fused K-round scan: draw the SAME K host keys K
                        # stepped rounds would (RNG-stream parity), hand
                        # eos ids + max_new headroom to the device for
                        # on-device stop detection
                        keys = jnp.stack([self._next_key()
                                          for _ in range(fuse_k)])
                        eos_ids = np.full(self.n_slots, -1, np.int32)
                        remaining = np.zeros(self.n_slots, np.int32)
                        for s in active:
                            st = self._slots[s]
                            if st.request.eos_id is not None:
                                eos_ids[s] = int(st.request.eos_id)
                            remaining[s] = (st.request.max_new_tokens
                                            - len(st.tokens))
                        (pool_op, self._toks, seq,
                         n_valid) = self._fused_jit(
                            self._params, self._state, pool_op,
                            tables, self._toks, temps, top_ks,
                            jnp.asarray(eos_ids),
                            jnp.asarray(remaining), keys)
                        self._observe("serving_fused_rounds", fuse_k)
                    else:
                        (pool_op, self._toks, seq,
                         counts) = self._decode_jit(
                            self._params, self._state, pool_op,
                            tables, self._toks, temps, top_ks,
                            self._next_key(), *live)
                if not self.async_rounds:
                    with self._span("serving.token_sync"):
                        # [B, T]; forces the whole round (verify
                        # included) done, and brings what the program
                        # counted in the same fetch
                        seq, counts = jax.device_get((seq, counts))
            self._pool = self._strip_pool(pool_op)
            inf = _InflightRound(
                active=list(active),
                rids={s: self._slots[s].request.id for s in active},
                drafts=drafts, verify_out=verify_out, seq=seq,
                t0=t0, td0=td0,
                dispatch_end=(self._clock() if self.record_timing
                              else 0.0),
                ver_dt=ver_dt,
                n_rounds=max(fuse_k, 1),
                decode_tokens=max(fuse_k, 1) * self.decode_chunk,
                n_valid=n_valid, counts=counts)
            if self.async_rounds:
                # round N's fetch waits for the NEXT step: stash the
                # dispatched round and return. The round-time
                # histogram observes the DISPATCH wall here (the
                # landing belongs to the next round's timeline — the
                # phase clock's disjoint-interval invariant holds
                # because decode attribution at landing covers only
                # dispatch + sync walls, never the inter-step gap).
                self._inflight = inf
                if self.record_timing:
                    self._observe("serving_round_s",
                                  inf.dispatch_end - rt0)
            else:
                self._land_round(inf)
                if self.record_timing:
                    self._observe("serving_round_s",
                                  self._clock() - rt0)
        with self._span("serving.round_end"):
            if self._pending_spills:
                # end-of-round spill drain (ISSUE 17): the gathers were
                # dispatched at eviction time and the next round's
                # device work is already in flight — the host copy +
                # pack lands here, off the decode hot path
                self.drain_spills()
            self._paged_stats_refresh()
            self._round += 1
            if t_start is not None:
                if self._clock() - t_start > self.stall_threshold_s:
                    self._failure_event("slow_steps")
            self._drain_terminal(results)

    def run(self) -> Dict[int, GenerationResult]:
        """Drain the queue: admit into free slots (advancing chunked
        prefills under the scheduler's round budget), decode in chunks,
        evict finished requests — until no work remains. Terminal
        results produced outside a run (sheds at submit, cancels while
        idle) are delivered here too."""
        results: Dict[int, GenerationResult] = {}
        self._drain_terminal(results)
        while self.has_work():
            self.step(results)
        return results

    def _emit_counters(self) -> None:
        """Mirror the engine's cumulative counters into the tracer
        (one Chrome-trace counter track each) so a serving run is
        observable from the trace alone. Failure events mirror at
        event time instead (``Tracer.incr`` in ``_failure_event``) —
        they must be visible even in rounds that never decode."""
        for key in ("admitted", "evicted", "chunks_scheduled",
                    "tokens_generated", "prefill_tokens",
                    "prefill_tokens_skipped", "spec_rounds",
                    "spec_fallback_rounds", "spec_drafted",
                    "spec_accepted"):
            self.tracer.counter(f"serving_{key}", self.stats[key])
        # block-pool gauges (ISSUE 6 satellite): the gateway's
        # /v1/metrics exports these tracks verbatim, so pool
        # health is visible from the HTTP front door
        self._paged_stats_refresh()
        for key in ("blocks_free", "blocks_used", "cow_copies",
                    "prefix_blocks_spliced", "frag_tokens",
                    "preempted", "paged_admit_deferred",
                    "paged_blocks_live", "paged_blocks_walked",
                    "paged_blocks_per_step", "paged_steps_per_row",
                    "paged_steps_paid", "table_uploads",
                    "param_bytes", "param_bytes_cast",
                    "kv_bytes_per_token", "kv_dtype_bytes"):
            self.tracer.counter(f"serving_{key}", self.stats[key])
        if self.prefix_cache is not None:
            for key in ("hits", "misses", "evictions"):
                self.tracer.counter(f"serving_prefix_{key}",
                                    self.prefix_cache.stats[key])
        if self.kv_tier is not None:
            # per-tier ladder counters (ISSUE 17): hbm = trie hits,
            # host/disk = tier reload matches — one labeled track
            # each so the federation prices the ladder per rung
            t = self.kv_tier.stats
            for tier, value in (("hbm", self.prefix_cache.stats["hits"]),
                                ("host", t["hits_host"]),
                                ("disk", t["hits_disk"])):
                self.tracer.counter(
                    f'serving_kv_tier_hits{{tier="{tier}"}}', value)
            for key in ("spills", "reloads", "drops"):
                self.tracer.counter(f"serving_kv_tier_{key}", t[key])
            self.tracer.counter("serving_kv_tier_host_bytes",
                                self.kv_tier.host_bytes)
            self.tracer.counter("serving_kv_tier_disk_bytes",
                                self.kv_tier.disk_bytes)
        self._emit_tp_gauges()
        self._emit_tenant_gauges()

    def _open_tenants(self) -> set:
        """Tenants with at least one OPEN request anywhere in the
        engine (queued, retrying, admitting, or in a slot) — the
        liveness test the per-tenant gauge retirement keys on."""
        open_t = {s.request.tenant for s in self._slots
                  if s is not None}
        open_t.update(p.request.tenant for p in self._pending)
        open_t.update(req.tenant for _, req in self._requeue)
        open_t.update(req.tenant
                      for req in self.scheduler.queued_requests())
        return open_t

    def _emit_tenant_gauges(self) -> None:
        """Per-tenant labeled copies of the per-round serving
        counters (ISSUE 13): ``serving_tokens_generated{tenant=...}``
        / ``serving_admitted{...}`` ride the same family names as
        their unlabeled twins, via ``Tracer.gauge`` (last-value
        table only — no event-log growth per round). The sparse
        failure counters (shed/preempted) get labeled ``incr`` twins
        at event time instead.

        RETIREMENT (ISSUE 14 satellite, the PR 13 known fact fixed):
        a tenant whose open-request count drops to zero gets one
        final emission round — so a scrape between its last commit
        and its retirement still sees the closing totals — and is
        then retired: its ``tenant_stats`` entry and gauge tracks
        are dropped, instead of freezing at the last sample forever
        on a server whose tenant population churns."""
        if self.tenants is None or self.tracer is None:
            return
        gauge = getattr(self.tracer, "gauge", self.tracer.counter)
        drop = getattr(self.tracer, "drop_gauge", None)
        open_now = self._open_tenants()
        was_open = getattr(self, "_tenant_open_last", set())
        for tenant in list(self.tenant_stats):
            stats = self.tenant_stats[tenant]
            if tenant not in open_now and tenant not in was_open:
                # idle for a full emission round: the closing totals
                # already went out last round — retire the tracks
                del self.tenant_stats[tenant]
                if drop is not None:
                    for key in stats:
                        if key in ("shed", "preempted"):
                            continue
                        drop(f'serving_{key}{{tenant="{tenant}"}}')
                continue
            for key, value in stats.items():
                if key in ("shed", "preempted"):
                    continue  # incr'd (counter-typed) at event time
                gauge(f'serving_{key}{{tenant="{tenant}"}}', value)
        self._tenant_open_last = open_now
        # the labeled HISTOGRAM twins retire too — a churning tenant
        # population must not grow the scrape without bound — but on
        # a much LONGER idle horizon than the gauges: latency
        # distributions are what an operator scrapes minutes later,
        # so they outlive the tenant by TENANT_HIST_RETIRE_ROUNDS
        # rounds instead of evaporating two rounds after its last
        # request (which would beat any real scrape cadence)
        drop_hist = getattr(self.tracer, "drop_histogram", None)
        idle = getattr(self, "_tenant_hist_idle", None)
        if idle is None:
            idle = self._tenant_hist_idle = {}
        hist_tenants = {name.rsplit('{tenant="', 1)[-1][:-2]
                        for name in self._tenant_hists}
        for tenant in hist_tenants:
            if tenant in open_now:
                idle.pop(tenant, None)
                continue
            idle[tenant] = idle.get(tenant, 0) + 1
            if idle[tenant] > self.TENANT_HIST_RETIRE_ROUNDS:
                idle.pop(tenant)
                suffix = f'{{tenant="{tenant}"}}'
                for name in [n for n in self._tenant_hists
                             if n.endswith(suffix)]:
                    del self._tenant_hists[name]
                    if drop_hist is not None:
                        drop_hist(name)

    def _emit_tp_gauges(self) -> None:
        """Per-shard observability (ISSUE 12 satellite): under tp > 1
        the pool/frag gauges gain ``{shard=...}``-labeled per-shard
        copies (block IDS are shard-invariant — the host BlockTable is
        the same on every shard — so the per-shard count equals the
        fleet count while the BYTES behind each count are the shard's
        head slice), plus ``serving_tp_kv_bytes{shard=...}`` measured
        from the actual addressable shards. Labeled names ride the
        PR 10 ``merge_prometheus`` labeling scheme, so a fleet scrape
        shows ``{replica=...,shard=...}``."""
        if self.tracer is None:
            return
        self.tracer.gauge("serving_tp_shards", self.tp)
        if self.tp_ctx is None:
            return
        per_shard = self.kv_shard_bytes()
        for shard, nbytes in per_shard.items():
            self.tracer.gauge(
                f'serving_tp_kv_bytes{{shard="{shard}"}}', nbytes)
            for key in ("blocks_free", "blocks_used", "frag_tokens"):
                self.tracer.gauge(
                    f'serving_{key}{{shard="{shard}"}}',
                    self.stats[key])

    def kv_shard_bytes(self) -> Dict[int, int]:
        """Per-shard addressable KV-cache bytes (the block pool): the
        ``total/TP`` acceptance arithmetic and the per-shard gauges
        read this. At ``tp == 1`` shard 0 holds everything."""
        if self._pool is None:
            return {i: 0 for i in range(self.tp)}
        if self.tp_ctx is not None:
            return self.tp_ctx.shard_bytes(self._pool)
        total = sum(
            int(np.prod(leaf.shape) * leaf.dtype.itemsize)
            for leaf in jax.tree_util.tree_leaves(self._pool))
        return {0: total}

    @property
    def mean_occupancy(self) -> float:
        chunks = self.stats["chunks"]
        return self.stats["occupancy_sum"] / chunks if chunks else 0.0

    # -- crash-safe snapshot / resume ----------------------------------
    def _prefill_sequence(self, seq: List[int], temperature: float = 0.0,
                          top_k: Optional[int] = None):
        """Prefill an arbitrary token sequence to a B=1 streaming state
        through the regular (chunked) prefill path — the rebuild
        primitive for ``restore``. Segments are capped at the cache
        window, so sequences longer than the window roll exactly the
        way live decoding rolled them. Returns ``(rnn, tok)``."""
        probe = Request(list(seq), 1, temperature=temperature,
                        top_k=top_k)
        pending = _Pending(probe, -1, None, None, 0, 0, None,
                           seq=[int(t) for t in seq])
        step_max = min(self.prefill_chunk or self.window, self.window)
        while pending.remaining:
            self._advance_prefill(pending,
                                  min(step_max, pending.remaining))
        return pending.rnn, pending.tok

    def _prime_prefix(self, prefix) -> None:
        """Recompute one snapshotted prefix-cache entry: prefill is
        deterministic, so the re-primed blocks are bit-identical to
        the stored state the crash destroyed."""
        if self.prefix_cache is None or not len(prefix):
            return
        rnn, _ = self._prefill_sequence([int(t) for t in prefix])
        # re-prime into fresh blocks, hand ownership to the trie
        # (the restore-path twin of the zero-copy live insert)
        tab = self._write_row(rnn, len(prefix))
        if tab is None:
            return    # pool too small for this entry: skip —
            #           the cache is a cache, not state
        self.prefix_cache.insert_blocks(prefix, tab.kinds[0])
        self.kv.free(tab)

    def _rebuild_slot(self, slot: int, request: Request,
                      tokens: List[int], prefix_reused: int,
                      spec_drafted: int = 0,
                      spec_accepted: int = 0,
                      delta_sent: Optional[int] = None) -> None:
        """Rebuild a snapshotted in-flight slot: re-prefill
        prompt + generated ids minus the last (exactly the cache a
        mid-decode slot holds — the newest id is the slot's current
        token, not yet in cache), scatter it in, and resume decoding
        where the crash happened. The n-gram draft table is pure
        derived state, so it rebuilds deterministically from the same
        recorded ids (no device arrays, nothing extra in the wire
        format)."""
        seq = [int(t) for t in request.prompt] + [int(t)
                                                 for t in tokens[:-1]]
        rnn, _ = self._prefill_sequence(seq, request.temperature,
                                        request.top_k)
        tok = jnp.asarray([int(tokens[-1])], jnp.int32)
        with self._span("serving.admit", rid=request.id,
                        slot=slot, **_targs(request)):
            tab = self._write_row(rnn, len(seq), slot)
        if tab is None:
            raise RuntimeError(
                "restore could not allocate blocks for a "
                "snapshotted slot — kv_blocks is smaller than the "
                "snapshot's working set")
        self._toks = self._tok_jit(self._toks, tok,
                                   jnp.asarray(slot, jnp.int32))
        self._kv_tabs[slot] = tab
        self._slots[slot] = _Slot(request, [int(t) for t in tokens],
                                  prefix_reused=prefix_reused,
                                  ttft_s=None,
                                  spec_drafted=spec_drafted,
                                  spec_accepted=spec_accepted)
        self._delta_sent[request.id] = (len(tokens) if delta_sent is None
                                        else int(delta_sent))
        self._started.add(request.id)
        self._temps[slot] = request.temperature
        self._top_ks[slot] = request.top_k or self.vocab
        if self.spec is not None:
            self.spec.seed(slot, [int(t) for t in request.prompt]
                           + [int(t) for t in tokens])

    def snapshot(self) -> Dict[str, Any]:
        """Everything needed to finish this engine's work in a fresh
        process, as a plain (JSON-serializable) dict: config, RNG key,
        scheduler queue, per-slot request metadata + generated ids,
        in-flight admissions (restored as queued — their partial
        device state is recomputed), retry/backoff state, prefix-trie
        prefixes, and undelivered terminal results. Device arrays are
        deliberately NOT captured: ``restore`` rebuilds KV state by
        re-prefilling recorded tokens, which is smaller, portable, and
        exactly reproducible."""
        self._one_kind_only("snapshot")
        if self._inflight is not None:
            # an async engine snapshots LANDED state: commit the
            # dispatched round first so the wire format carries every
            # token the device already produced (dropping it would
            # still restore correctly — greedy recompute — but why
            # recompute a round that is already done)
            inf, self._inflight = self._inflight, None
            self._land_round(inf)
        if self._pending_spills:
            # land staged spills too: the payloads are droppable, but
            # the staged gathers reference THIS process's pool
            self.drain_spills()
        now = self._clock()

        def entry(req: Request) -> Dict[str, Any]:
            return {"request": _request_dict(req),
                    "elapsed_s": self._elapsed(req.id, now),
                    "started": req.id in self._started}

        slots: List[Optional[Dict[str, Any]]] = []
        for state in self._slots:
            if state is None:
                slots.append(None)
            else:
                slots.append({
                    "request": _request_dict(state.request),
                    "tokens": list(state.tokens),
                    "prefix_reused": state.prefix_reused,
                    "elapsed_s": self._elapsed(state.request.id, now),
                    "spec_drafted": state.spec_drafted,
                    "spec_accepted": state.spec_accepted,
                    # tokens the pre-crash process already STREAMED to
                    # a consumer (undrained buffered deltas count as
                    # un-streamed): the restored engine re-emits only
                    # what never left the building
                    "delta_sent": (
                        self._delta_sent.get(state.request.id,
                                             len(state.tokens))
                        - len(self._delta_buf.get(state.request.id,
                                                  []))),
                })
        return {
            "version": 1,
            "config": {
                "n_slots": self.n_slots,
                "decode_chunk": self.decode_chunk,
                "min_prompt_bucket": self.scheduler.min_bucket,
                "prefix_cache_rows": (self.prefix_cache.rows
                                      if self.prefix_cache else 0),
                "prefill_chunk": self.prefill_chunk,
                "admission_policy": self.scheduler.policy,
                "prefill_budget": self.scheduler._budget_ceiling,
                "max_queue": self.scheduler.max_queue,
                "shed_policy": self.shed_policy,
                "adaptive_prefill": self.adaptive_prefill,
                "paranoid": self.paranoid,
                "max_retries": self.max_retries,
                "retry_backoff_rounds": self.retry_backoff_rounds,
                "stall_threshold_s": self.stall_threshold_s,
                "spec_draft_len": self.spec_draft_len,
                "draft_source": self.draft_source,
                "paged_kv": True,
                "block_tokens": self.block_tokens,
                "kv_blocks": self.kv_blocks,
                "record_timing": self.record_timing,
                "flight_recorder": self.flight_recorder,
                # provenance, not payload: the snapshot wire format is
                # LAYOUT-INVARIANT (host tables + token ids, no device
                # arrays), so a snapshot taken at one tp width
                # restores at any other — restore(tp=...) overrides
                "tp": self.tp,
                "use_flash_paged": self.use_flash_paged,
                "async_rounds": self.async_rounds,
                "fused_rounds": self.fused_rounds,
                # tier contents are droppable cache (ISSUE 17):
                # record the knobs, never the payloads — a restored
                # engine re-tiers under its own pressure
                "kv_host_tier_bytes": self.kv_host_tier_bytes,
                "kv_disk_tier_path": self.kv_disk_tier_path,
                "kv_disk_tier_bytes": self.kv_disk_tier_bytes,
            },
            # block bookkeeping rides the snapshot for inspection and
            # exact-capacity restores (restore REBUILDS device blocks
            # by re-prefilling recorded tokens, so tables here are
            # provenance, not payload)
            "paged": {
                "block_tokens": self.block_tokens,
                "kv_blocks": self.kv_blocks,
                "tables": {
                    str(slot): {"length": tab.length,
                                "floor": tab.floor,
                                "blocks": {
                                    str(g): int(b) for g, b
                                    in tab.kinds[0].blocks.items()}}
                    for slot, tab in enumerate(self._kv_tabs)
                    if tab is not None},
                "refcounts": {
                    str(b): self.block_pool.refcount(b)
                    for b in range(self.kv_blocks)
                    if self.block_pool.refcount(b) > 0},
            },
            # tenant registry (ISSUE 13): quotas/priorities survive a
            # drain/restore without the booting host re-plumbing them
            # (restore(tenants=) still overrides)
            "tenants": (self.tenants.to_dict()
                        if self.tenants is not None else None),
            # draft TABLES are derived state (rebuilt from recorded
            # ids); only the adaptation point needs the wire format
            "spec": ({"draft_len": self.scheduler.draft_len,
                      "drafted": self.scheduler._spec_drafted,
                      "accepted": self.scheduler._spec_accepted,
                      "rounds": self.scheduler._spec_rounds}
                     if self.spec is not None else None),
            "rng_key": np.asarray(
                jax.random.key_data(self._key)).tolist(),
            "round": self._round,
            "slots": slots,
            "pending": [entry(p.request) for p in self._pending],
            "queue": [entry(r)
                      for r in self.scheduler.queued_requests()],
            "requeue": [dict(entry(req),
                             delay_rounds=max(0, ready - self._round))
                        for ready, req in self._requeue],
            "retries": {str(k): v for k, v in self._retries.items()},
            "prefix_prompts": (
                [list(p) for p in self.prefix_cache.cached_prefixes()]
                if self.prefix_cache is not None else []),
            "terminal": [dataclasses.asdict(r)
                         for r in self._terminal.values()],
        }

    @classmethod
    def restore(cls, net, snapshot: Dict[str, Any], tracer=None,
                fault_plan: Optional[FaultPlan] = None, clock=None,
                seed: int = 0, tp: Optional[int] = None,
                use_flash_paged=_UNSET,
                tenants: Optional[TenantRegistry] = None
                ) -> "DecodeEngine":
        """Rebuild an engine from ``snapshot()`` output in a fresh
        process: same config, prefix cache re-primed (deterministic
        prefill reproduces each stored entry), every in-flight slot's KV
        state re-prefilled from its recorded ids, queue/retry state and
        RNG key restored — ``run()`` then finishes the same ids a
        crash-free engine would have (greedy: bit-identical). In-flight
        chunked admissions restart from the queue front (their partial
        prefill is recomputed); deadlines keep their already-elapsed
        time.

        ``tp`` overrides the snapshot's tensor-parallel width (ISSUE
        12): the wire format is layout-invariant — host block tables,
        token ids, NO device arrays — so a snapshot taken at TP=2
        restores at TP=1 (or 4) bit-identically; device KV is rebuilt
        by re-prefill under the restoring engine's own sharding.
        ``use_flash_paged`` likewise overrides the kernel toggle (a
        TPU-taken snapshot restores on a CPU host with the gather
        fallback)."""
        cfg = snapshot["config"]
        if not cfg.get("paged_kv", False):
            raise ValueError(
                "this snapshot was taken by an engine with the dense "
                "KV layout (config.paged_kv false), which was removed "
                "in PR 29; it cannot be restored")
        if tp is None:
            tp = int(cfg.get("tp", 1))
        if use_flash_paged is _UNSET:
            use_flash_paged = cfg.get("use_flash_paged")
        if tenants is None and snapshot.get("tenants"):
            # the drained engine's quotas/priorities ride the wire
            # format — the restoring host keeps them unless it
            # explicitly passes a registry of its own
            tenants = TenantRegistry.from_dict(snapshot["tenants"])
        eng = cls(
            net, n_slots=cfg["n_slots"],
            decode_chunk=cfg["decode_chunk"],
            min_prompt_bucket=cfg["min_prompt_bucket"], tracer=tracer,
            seed=seed, prefix_cache_rows=cfg["prefix_cache_rows"],
            prefill_chunk=cfg["prefill_chunk"],
            admission_policy=cfg["admission_policy"],
            prefill_budget=cfg["prefill_budget"],
            max_queue=cfg["max_queue"], shed_policy=cfg["shed_policy"],
            adaptive_prefill=cfg["adaptive_prefill"],
            paranoid=cfg["paranoid"], fault_plan=fault_plan,
            max_retries=cfg["max_retries"],
            retry_backoff_rounds=cfg["retry_backoff_rounds"],
            stall_threshold_s=cfg["stall_threshold_s"], clock=clock,
            spec_draft_len=cfg.get("spec_draft_len", 0),
            draft_source=cfg.get("draft_source", "ngram"),
            block_tokens=cfg.get("block_tokens", 16),
            kv_blocks=cfg.get("kv_blocks") or None,
            record_timing=cfg.get("record_timing", True),
            flight_recorder=cfg.get("flight_recorder", 256),
            tp=tp, use_flash_paged=use_flash_paged,
            tenants=tenants,
            async_rounds=cfg.get("async_rounds", False),
            fused_rounds=cfg.get("fused_rounds", 0),
            kv_host_tier_bytes=cfg.get("kv_host_tier_bytes", 0),
            kv_disk_tier_path=cfg.get("kv_disk_tier_path"),
            kv_disk_tier_bytes=cfg.get("kv_disk_tier_bytes"))
        spec_state = snapshot.get("spec")
        if spec_state and eng.spec is not None:
            # resume K-adaptation where the crash left it (final ids
            # are K-independent under greedy; this preserves cadence)
            eng.scheduler.draft_len = int(spec_state["draft_len"])
            eng.scheduler._spec_drafted = int(
                spec_state.get("drafted", 0))
            eng.scheduler._spec_accepted = int(
                spec_state.get("accepted", 0))
            eng.scheduler._spec_rounds = int(
                spec_state.get("rounds", 0))
        now = eng._clock()
        max_id = -1

        def arm(req: Request, elapsed) -> None:
            nonlocal max_id
            eng._submit_t[req.id] = now - (elapsed or 0.0)
            # restored phase clock: e2e keeps the pre-crash elapsed
            # time (submit_t back-dated), the timeline marks the
            # restore boundary, and queue wait restarts here — the
            # pre-crash breakdown died with the old process
            eng._mint_clock(req.id, eng._submit_t[req.id])
            clock = eng._clock_of(req.id)
            if clock is not None:
                clock.event(now, "restored",
                            elapsed_s=float(elapsed or 0.0))
                clock.enqueue_t = now
            if (req.deadline_s is not None
                    or req.queue_timeout_s is not None):
                eng._has_deadlines = True
            max_id = max(max_id, req.id)

        for prefix in snapshot.get("prefix_prompts", []):
            eng._prime_prefix(prefix)
        for slot, sd in enumerate(snapshot["slots"]):
            if sd is None:
                continue
            req = _request_from(sd["request"])
            eng._rebuild_slot(slot, req, list(sd["tokens"]),
                              int(sd.get("prefix_reused", 0)),
                              int(sd.get("spec_drafted", 0)),
                              int(sd.get("spec_accepted", 0)),
                              delta_sent=sd.get("delta_sent"))
            # in-flight ids stay issued: the duplicate-id guard must
            # survive the restart exactly like the queue's ids do
            eng.scheduler._issued.add(req.id)
            arm(req, sd.get("elapsed_s"))
        # in-flight admissions were the oldest waiters: they re-enter
        # at the queue front, ahead of the queued requests
        for entry in list(snapshot.get("pending", [])) + list(
                snapshot["queue"]):
            req = _request_from(entry["request"])
            eng.scheduler.requeue(req)
            arm(req, entry.get("elapsed_s"))
            if entry.get("started"):
                eng._started.add(req.id)
        for entry in snapshot.get("requeue", []):
            req = _request_from(entry["request"])
            eng._requeue.append(
                (eng._round + int(entry.get("delay_rounds", 0)), req))
            eng.scheduler._issued.add(req.id)
            arm(req, entry.get("elapsed_s"))
            if entry.get("started"):
                eng._started.add(req.id)
        eng._retries = {int(k): int(v)
                        for k, v in snapshot.get("retries", {}).items()}
        for rd in snapshot.get("terminal", []):
            eng._terminal[rd["id"]] = GenerationResult(**rd)
            max_id = max(max_id, rd["id"])
        if max_id >= 0:
            eng.scheduler.reserve_ids_through(max_id)
        key_data = np.asarray(snapshot["rng_key"], np.uint32)
        eng._key = jax.random.wrap_key_data(jnp.asarray(key_data))
        return eng
