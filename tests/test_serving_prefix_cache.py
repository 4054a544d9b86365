"""Radix prefix cache + chunked-prefill admission (ISSUE 2 tentpole).

The contract under test: admissions that reuse a cached prefix (and/or
prefill their suffix in chunks between decode rounds) produce greedy
ids EXACTLY equal to the cache-disabled blocking engine — which PR 1
already pins to sequential ``generate()`` — while compile counts stay
bounded and no admission stalls the pool longer than the scheduler's
round budget."""

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    BlockPool,
    BlockTable,
    DecodeEngine,
    RadixPrefixCache,
    Request,
    Scheduler,
)

V = 12
SHARED = [1, 4, 7, 2, 9, 3, 5, 2]  # the "system prompt" of the tests


def _net(seed=7, stream_max_t=64):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _one_hot_seq(ids):
    x = np.zeros((1, V, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


def _solo_generate(prompt, n, seed=7, stream_max_t=64):
    net = _net(seed, stream_max_t)
    net.rnn_clear_previous_state()
    return np.asarray(net.generate(_one_hot_seq(prompt), n))[0].tolist()


BT = 4  # block size of the trie unit tests


def _trie(rows, kv_blocks=64):
    """A trie over a real block pool, wired as the engine wires it."""
    pool = BlockPool(kv_blocks, BT)
    cache = RadixPrefixCache(rows, BT, ref_block=pool.ref,
                             release_block=pool.deref)
    return cache, pool


def _put(cache, pool, prompt):
    """Insert ``prompt`` the way a finished admission does: the slot's
    freshly allocated blocks are leased to the trie, then the slot
    lets go of its own references."""
    tab = BlockTable(BT, length=len(prompt))
    for g in range(-(-len(prompt) // BT)):
        tab.blocks[g] = pool.alloc()
    ok = cache.insert_blocks(prompt, tab)
    for bid in tab.blocks.values():
        pool.deref(bid)
    return ok


class TestRadixTrie:
    def test_miss_then_hit_after_insert(self):
        cache, pool = _trie(rows=2)
        assert cache.lookup([1, 2, 3, 4]) is None
        assert _put(cache, pool, [1, 2, 3, 4])
        hit = cache.lookup([1, 2, 3, 4, 5, 6])
        assert hit is not None
        assert hit.matched == 4
        assert cache.payload(hit.row).length == 4
        cache.release(hit)

    def test_exact_match_rewinds_one_token(self):
        """A full-prefix hit never consumes the whole prompt: the last
        token re-streams to produce first-token logits (zero-length
        suffixes cannot exist by construction)."""
        cache, pool = _trie(rows=2)
        _put(cache, pool, [1, 2, 3, 4])
        hit = cache.lookup([1, 2, 3, 4])
        assert hit.matched == 3
        assert cache.payload(hit.row).length == 4   # the entry, whole
        cache.release(hit)

    def test_divergent_tail_is_rewound(self):
        """RadixAttention-style sharing: a prompt diverging m tokens
        into a cached entry reuses those m tokens via rewind — stored
        prompts need not be prefixes of the query."""
        cache, pool = _trie(rows=2)
        _put(cache, pool, SHARED + [0, 0])
        hit = cache.lookup(SHARED + [3])
        assert hit.matched == len(SHARED)
        assert cache.payload(hit.row).length == len(SHARED) + 2
        cache.release(hit)
        # query that is a proper prefix of the stored prompt
        hit = cache.lookup(SHARED)
        assert hit.matched == len(SHARED) - 1
        cache.release(hit)

    def test_one_token_prompt_never_hits(self):
        cache, pool = _trie(rows=2)
        _put(cache, pool, [5])
        assert cache.lookup([5]) is None

    def test_edge_split_preserves_both_prompts(self):
        cache, pool = _trie(rows=4)
        _put(cache, pool, SHARED + [0])
        _put(cache, pool, SHARED + [1])
        assert cache.cached_prefixes() == sorted(
            [tuple(SHARED + [0]), tuple(SHARED + [1])])
        for tail, m in [([0], 9), ([1], 9), ([2], 8)]:
            hit = cache.lookup(SHARED + tail + [7])
            assert hit is not None and hit.matched == m, (tail, hit)
            cache.release(hit)

    def test_duplicate_insert_refreshes_not_duplicates(self):
        cache, pool = _trie(rows=2)
        assert _put(cache, pool, [1, 2, 3])
        assert not _put(cache, pool, [1, 2, 3])
        assert cache.stats["inserts"] == 1
        assert len(cache.cached_prefixes()) == 1
        assert pool.used_blocks == 1    # the duplicate leased nothing

    def test_lru_eviction_order(self):
        cache, pool = _trie(rows=2)
        _put(cache, pool, [1, 1, 1])
        _put(cache, pool, [2, 2, 2])
        hit = cache.lookup([1, 1, 1, 9])   # refreshes [1,1,1]
        cache.release(hit)
        _put(cache, pool, [3, 3, 3])       # evicts LRU [2,2,2]
        assert cache.stats["evictions"] == 1
        assert tuple([2, 2, 2]) not in cache.cached_prefixes()
        assert tuple([1, 1, 1]) in cache.cached_prefixes()

    def test_leased_row_survives_eviction_pressure(self):
        """Satellite edge case: evicting a ref-counted prefix while a
        slot still reads it must be refused — the insert declines
        instead when no unleased entry exists."""
        cache, pool = _trie(rows=1)
        _put(cache, pool, [1, 2, 3])
        hit = cache.lookup([1, 2, 3, 4])   # lease entry 0
        assert hit is not None
        assert not _put(cache, pool, [7, 8, 9])
        assert not cache.evict_one()       # nor for block pressure
        assert cache.stats["declined"] == 1
        assert cache.stats["evictions"] == 0
        assert tuple([1, 2, 3]) in cache.cached_prefixes()
        cache.release(hit)                 # lease dropped: evictable
        assert _put(cache, pool, [7, 8, 9])
        assert cache.stats["evictions"] == 1

    def test_insert_survives_eviction_pruning_walk_path(self):
        """Regression: on a full cache, insert's LRU eviction may prune
        the very node its pre-allocation walk returned; grafting must
        re-walk the live trie or the new entry lands detached
        (unreachable, and a later eviction KeyErrors in the prune
        loop). Multi-turn prompts each extending the last hit exactly
        this on a 1-entry cache."""
        cache, pool = _trie(rows=1)
        turns = [SHARED, SHARED + [0, 1], SHARED + [0, 1, 2, 3]]
        for i, t in enumerate(turns):
            hit = cache.lookup(t)
            if hit is not None:
                cache.release(hit)
            assert _put(cache, pool, t)
            assert cache.cached_prefixes() == [tuple(t)], (
                f"turn {i}: entry detached from the trie")

    def test_engine_multiturn_tight_cache_stays_consistent(self):
        """Same regression through the public engine API: conversation
        turns over a tight cache keep exact parity and never corrupt
        the trie."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           prefix_cache_rows=1)
        turns = [SHARED, SHARED + [0, 1], SHARED + [0, 1, 2, 3]]
        for t in turns:
            rid = eng.submit(Request(list(t), 4))
            res = eng.run()
            assert res[rid].tokens == _solo_generate(t, 4)
        assert eng.prefix_cache.stats["hits"] >= 2

    def test_entries_lease_blocks_and_eviction_returns_them(self):
        """An entry is a lease on pool blocks, never a copy: insert
        takes one reference a block, a block the admitted slot still
        holds survives the entry's eviction, and an evicted entry's
        exclusively held blocks go back to the free list."""
        cache, pool = _trie(rows=2, kv_blocks=8)
        tab = BlockTable(BT, length=6)
        tab.blocks = {0: pool.alloc(), 1: pool.alloc()}
        assert cache.insert_blocks([1, 2, 3, 4, 5, 6], tab)
        assert [pool.refcount(b) for b in tab.blocks.values()] == [2, 2]
        assert sorted(cache.block_ids()) == sorted(tab.blocks.values())
        # the payload is a frozen copy: the slot's table moving on
        # does not move the entry
        tab.length += 3
        tab.blocks[2] = pool.alloc()
        hit = cache.lookup([1, 2, 3, 4, 5, 6, 7])
        assert cache.payload(hit.row).length == 6
        assert sorted(cache.payload(hit.row).blocks) == [0, 1]
        cache.release(hit)
        assert cache.evict_one()
        assert cache.cached_prefixes() == []
        assert [pool.refcount(b) for b in tab.blocks.values()] == [1, 1, 1]
        assert pool.used_blocks == 3       # the slot's, still resident
        _put(cache, pool, [9, 9, 9, 9, 9])
        assert pool.used_blocks == 5
        assert cache.clear() == 1
        assert pool.used_blocks == 3

    def test_invalidate_scrubs_entry(self):
        """Fault quarantine: invalidate drops exactly the named entry
        (exact prompt or id) and frees its id and blocks for reuse."""
        cache, pool = _trie(rows=2)
        _put(cache, pool, [1, 2, 3])
        _put(cache, pool, [1, 2, 3, 4, 5])
        assert cache.invalidate([1, 2, 3])
        assert not cache.invalidate([1, 2, 3])   # already gone
        assert cache.cached_prefixes() == [(1, 2, 3, 4, 5)]
        assert cache.stats["invalidations"] == 1
        (row,) = cache.stored_rows()
        assert cache.row_prefix(row) == (1, 2, 3, 4, 5)
        assert cache.invalidate_row(row)
        assert cache.cached_prefixes() == []
        assert pool.used_blocks == 0
        # both ids free again: two fresh inserts succeed, no eviction
        assert _put(cache, pool, [7, 7])
        assert _put(cache, pool, [8, 8])
        assert cache.stats["evictions"] == 0

    def test_invalidate_leased_row_defers_free(self):
        """Invalidating an entry another in-flight admission still
        leases must NOT hand its id to the free list: a concurrent
        insert reusing it would corrupt the old lease's bookkeeping.
        The entry is unmapped immediately (no new lookups hit it) and
        its id freed by the LAST release."""
        cache, pool = _trie(rows=2)
        _put(cache, pool, [1, 2, 3, 4])
        hit = cache.lookup([1, 2, 3, 4, 9])      # leases the entry
        assert cache.invalidate([1, 2, 3, 4])
        assert cache.lookup([1, 2, 3, 4, 9]) is None  # unmapped now
        assert hit.row not in cache._free        # ...but NOT freed
        # an insert while the lease is live must take the OTHER id
        assert _put(cache, pool, [5, 5, 5])
        assert cache.stored_rows() != [hit.row]
        cache.release(hit)                       # last lease frees it
        assert hit.row in cache._free
        assert _put(cache, pool, [6, 6])
        assert sorted(cache.stored_rows()) == [0, 1]


class TestSchedulerChunkPlanning:
    def test_decode_priority_grants_one_chunk_per_round(self):
        s = Scheduler(64, prefill_chunk=8, policy="decode")
        assert s.plan_chunks([30, 20, 10]) == [0]
        assert s.plan_chunks([3]) == [0]

    def test_ttft_priority_frontloads_oldest(self):
        s = Scheduler(64, prefill_chunk=8, policy="ttft")
        # budget defaults to 4 chunks: oldest finishes first
        assert s.plan_chunks([16, 40]) == [0, 0, 1, 1]
        assert s.plan_chunks([40]) == [0, 0, 0, 0]

    def test_explicit_budget_and_floor(self):
        s = Scheduler(64, prefill_chunk=8, prefill_budget=16,
                      policy="ttft")
        assert s.plan_chunks([40, 40]) == [0, 0]
        # budget below one chunk floors at one chunk (progress)
        s = Scheduler(64, prefill_chunk=8, prefill_budget=1)
        assert s.plan_chunks([40]) == [0]

    def test_no_chunking_means_no_plan(self):
        s = Scheduler(64)
        assert s.plan_chunks([40]) == []

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            Scheduler(64, policy="fifo")


def _shared_prefix_cases(n_tails=5):
    cases = [(SHARED + [t], 4 + t % 3) for t in range(n_tails)]
    cases += [(SHARED, 5), ([5, 2], 3)]
    return cases


class TestEnginePrefixParity:
    """Greedy ids must be bit-identical with the prefix cache on vs
    off, in every admission mode (the tentpole's correctness gate)."""

    @pytest.mark.parametrize("kwargs", [
        {"prefix_cache_rows": 4},
        {"prefix_cache_rows": 4, "prefill_chunk": 4},
        {"prefix_cache_rows": 4, "prefill_chunk": 4,
         "admission_policy": "decode"},
        {"prefill_chunk": 4},  # chunked cold prefill, no cache
    ])
    def test_greedy_ids_identical_to_cache_off(self, kwargs):
        cases = _shared_prefix_cases()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           **kwargs)
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = eng.run()
        for rid, (p, n) in zip(ids, cases):
            assert res[rid].tokens == _solo_generate(p, n), (
                f"request {rid} diverged with {kwargs}")

    def test_full_prefix_hit_decodes_identically(self):
        """Zero-length-suffix edge case: a prompt exactly equal to a
        cached prefix re-streams only its final token."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           prefix_cache_rows=2)
        a = eng.submit(Request(SHARED, 6))
        res_a = eng.run()
        b = eng.submit(Request(list(SHARED), 6))  # identical prompt
        res_b = eng.run()
        want = _solo_generate(SHARED, 6)
        assert res_a[a].tokens == want
        assert res_b[b].tokens == want
        assert res_b[b].prefix_tokens_reused == len(SHARED) - 1
        assert eng.prefix_cache.stats["hits"] == 1

    def test_prompt_exactly_at_stream_max_t(self):
        """Satellite edge case: a window-filling prompt admits, caches,
        and re-admits warm without corruption."""
        window = 32
        prompt = [(i * 5 + 1) % V for i in range(window)]
        eng = DecodeEngine(_net(stream_max_t=window), n_slots=2,
                           decode_chunk=2, seed=0, prefix_cache_rows=2,
                           prefill_chunk=8)
        a = eng.submit(Request(prompt, 4))
        b = eng.submit(Request(list(prompt), 4))
        res = eng.run()
        want = _solo_generate(prompt, 4, stream_max_t=window)
        assert res[a].tokens == want
        assert res[b].tokens == want

    def test_duplicate_submit_after_release_hits_cache(self):
        """Satellite edge case: a finished id resubmitted (allowed once
        released) takes the warm path and still matches solo."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           prefix_cache_rows=2)
        req = Request(SHARED + [0], 4)
        eng.submit(req)
        with pytest.raises(ValueError, match="already submitted"):
            eng.submit(req)
        eng.run()
        eng.submit(req)
        res = eng.run()
        assert res[req.id].tokens == _solo_generate(SHARED + [0], 4)
        assert res[req.id].prefix_tokens_reused == len(SHARED)

    def test_graph_network_warm_parity(self):
        """ComputationGraph nets (vertex-named rnn state, masks-dict
        plumbing) take the same warm chunked path bit-identically."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadSelfAttention,
        )
        from deeplearning4j_tpu.ops.losses import LossFunction

        def gnet():
            conf = (
                NeuralNetConfiguration.Builder()
                .seed(6).learning_rate(0.01)
                .graph_builder().add_inputs("in")
                .add_layer("attn", MultiHeadSelfAttention(
                    n_in=V, n_out=16, n_heads=2, causal=True,
                    stream_max_t=32), "in")
                .add_layer("out", L.RnnOutputLayer(
                    n_in=16, n_out=V, activation="softmax",
                    loss_function=LossFunction.MCXENT), "attn")
                .set_outputs("out").build())
            return ComputationGraph(conf).init()

        solo = gnet()
        want = {}
        for tail in (0, 1, 2):
            solo.rnn_clear_previous_state()
            want[tail] = np.asarray(solo.generate(
                _one_hot_seq(SHARED + [tail]), 6))[0].tolist()
        eng = DecodeEngine(gnet(), n_slots=2, decode_chunk=3,
                           prefix_cache_rows=2, prefill_chunk=4)
        ids = {eng.submit(Request(SHARED + [t], 6)): t
               for t in (0, 1, 2)}
        res = eng.run()
        for rid, tail in ids.items():
            assert res[rid].tokens == want[tail]
        assert eng.prefix_cache.stats["hits"] >= 1

    def test_sampled_requests_run_warm_without_error(self):
        """Non-greedy requests share the warm path (parity is a greedy
        guarantee; sampling just has to stay well-formed)."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=3,
                           prefix_cache_rows=2, prefill_chunk=4)
        ids = [eng.submit(Request(SHARED + [t], 6, temperature=0.8,
                                  top_k=4)) for t in range(3)]
        res = eng.run()
        assert all(len(res[r].tokens) == 6 for r in ids)
        assert all(0 <= t < V for r in ids for t in res[r].tokens)


class TestHitRateAndCounters:
    def test_hit_rate_on_shared_prefix_workload(self):
        """The tentpole's cache-quality gate: >= 0.7 hit rate on the
        80%-shared synthetic workload, most prefill tokens skipped."""
        tails = [[t] for t in range(10)]
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           prefix_cache_rows=8)
        ids = [eng.submit(Request(SHARED + t, 3)) for t in tails]
        eng.run()
        assert eng.prefix_cache.hit_rate >= 0.7
        total_prompt = sum(len(SHARED) + 1 for _ in tails)
        skipped = eng.stats["prefill_tokens_skipped"]
        assert skipped / total_prompt >= 0.7
        assert (eng.stats["prefill_tokens"] + skipped == total_prompt)

    def test_counters_flow_through_tracer(self):
        """Satellite: a serving run is observable from the trace alone
        — admitted/evicted/hits/misses/chunks/tokens counters land in
        the tracer."""
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           prefix_cache_rows=4, prefill_chunk=4,
                           tracer=tracer)
        for t in range(4):
            eng.submit(Request(SHARED + [t], 4))
        eng.run()
        last = tracer.latest_counters()
        assert last["serving_admitted"] == 4
        assert last["serving_evicted"] == eng.stats["evicted"]
        assert last["serving_chunks_scheduled"] == \
            eng.stats["chunks_scheduled"]
        assert last["serving_tokens_generated"] == \
            eng.stats["tokens_generated"]
        assert last["serving_prefix_hits"] == \
            eng.prefix_cache.stats["hits"]
        assert last["serving_prefix_misses"] == \
            eng.prefix_cache.stats["misses"]
        assert tracer.spans("serving.prefix_splice")
        assert tracer.spans("serving.prefill_chunk")

    def test_ttft_recorded_and_warm_reuse_reported(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           prefix_cache_rows=2)
        a = eng.submit(Request(SHARED + [0], 3))
        b = eng.submit(Request(SHARED + [1], 3))
        res = eng.run()
        assert res[a].ttft_s is not None and res[a].ttft_s > 0
        assert res[a].prefix_tokens_reused == 0
        assert res[b].prefix_tokens_reused == len(SHARED)


class TestNonBlockingAdmission:
    def test_decode_priority_stall_bounded_by_one_chunk(self):
        """Acceptance criterion: with chunked prefill under decode
        priority, no decode round waits on more than ONE prefill chunk
        (measured in-process via the tracer counter)."""
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefill_chunk=4, admission_policy="decode",
                           tracer=tracer)
        eng.submit(Request([3, 1, 4], 24))        # long-running decoder
        for t in range(3):                        # long prompts churn in
            eng.submit(Request(SHARED * 4 + [t], 4))
        eng.run()
        per_round = tracer.counter_values("serving_round_prefill_chunks")
        assert per_round, "chunked admissions must emit round counters"
        assert max(per_round) <= 1

    def test_ttft_priority_may_batch_chunks_per_round(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefill_chunk=4, admission_policy="ttft",
                           tracer=tracer)
        eng.submit(Request([3, 1, 4], 24))
        eng.submit(Request(SHARED * 4 + [0], 4))  # 33-token prompt
        eng.run()
        per_round = tracer.counter_values("serving_round_prefill_chunks")
        assert max(per_round) > 1  # budget (4 chunks) front-loads

    def test_neighbours_unperturbed_by_chunked_admission(self):
        """A decoding slot's ids must be exactly its solo ids even when
        a long prompt prefills chunk-by-chunk alongside."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefill_chunk=4, admission_policy="decode")
        a = eng.submit(Request([3, 1, 4, 1, 5], 20))
        b = eng.submit(Request(SHARED * 4, 5))
        res = eng.run()
        assert res[a].tokens == _solo_generate([3, 1, 4, 1, 5], 20)
        assert res[b].tokens == _solo_generate(SHARED * 4, 5)


class TestBoundedCompiles:
    def test_warm_engine_never_retraces(self, assert_no_retrace):
        """decode=1, a cold row's scatter and the first token's put 1
        each, no row movers (a hit is a splice, an insert a lease),
        TWO chunk executables (a cold row's continuation, a warm
        table's), one cold prefill per bucket — then arbitrary
        admissions (hit, miss, full hit, new slots, sampling configs)
        reuse them all."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefix_cache_rows=4, prefill_chunk=4)
        for p, n in _shared_prefix_cases(3):
            eng.submit(Request(p, n))
        eng.run()
        counts = eng.compile_counts()
        assert counts["decode"] == 1
        assert counts["paged_scatter"] == 1
        assert counts["paged_tok"] == 1
        assert not {"admit", "prefix_fetch", "prefix_store"} & set(counts)
        assert counts["chunk_prefill"] == 2   # every chunk same width
        assert counts["prefill"] == 1         # cold first-chunk shape
        with assert_no_retrace(eng):
            eng.submit(Request(SHARED + [9, 9], 7))
            eng.submit(Request(SHARED, 2, temperature=1.2, top_k=3))
            eng.submit(Request([9, 9, 8, 8, 7, 7, 6, 6, 5, 5], 4))
            eng.run()

    def test_blocking_mode_buckets_suffix_prefills(self,
                                                   assert_no_retrace):
        """Without chunking, warm suffixes compile one continuation
        executable per pow2 suffix bucket, cold prompts one prefill per
        bucket — and seen buckets never retrace."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefix_cache_rows=4)
        eng.submit(Request(SHARED + [0], 3))          # cold, bucket 16
        eng.submit(Request(SHARED + [1], 3))          # warm, suffix -> 8
        eng.submit(Request(SHARED + [1, 2, 3], 3))    # warm, suffix -> 8
        eng.run()
        counts = eng.compile_counts()
        assert counts["prefill"] == 1
        assert counts["chunk_prefill"] == 1
        with assert_no_retrace(eng):
            eng.submit(Request(SHARED + [4], 3))      # warm, seen bucket
            eng.run()


@pytest.mark.slow
class TestPrefixSoak:
    def test_churn_soak_with_cache_and_chunks(self):
        rng = np.random.default_rng(0)
        cases = []
        for i in range(30):
            if rng.random() < 0.8:
                p = SHARED + rng.integers(0, V, 1 + i % 4).tolist()
            else:
                p = rng.integers(0, V, rng.integers(1, 20)).tolist()
            cases.append((p, int(rng.integers(1, 25))))
        eng = DecodeEngine(_net(seed=13), n_slots=4, decode_chunk=4,
                           seed=1, prefix_cache_rows=8,
                           prefill_chunk=8)
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = eng.run()
        for rid, (p, n) in zip(ids, cases):
            assert res[rid].tokens == _solo_generate(p, n, seed=13)
        assert eng.prefix_cache.hit_rate >= 0.5
        counts = eng.compile_counts()
        assert counts["decode"] == 1
        assert counts["paged_scatter"] == 1 and counts["paged_tok"] == 1
        assert 1 <= counts["chunk_prefill"] <= 2
