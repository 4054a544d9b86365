"""Paged KV memory: one block-pool cache shared by decode slots and
the prefix trie (ISSUE 6 tentpole).

The contract under test: ``DecodeEngine`` keeps keys and values in
ONE block-granular device pool (fixed-size token blocks, per-slot
block tables, zero-copy prefix splices with refcounts, copy-on-write
on divergence) — and every greedy request's ids stay BIT-IDENTICAL to
sequential B=1 ``generate()`` across all four admission
modes x prefix cache on/off x speculation on/off, with compile counts
bounded at one decode executable plus one verify per pow2
draft bucket."""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    BlockPool,
    BlockTable,
    DecodeEngine,
    FaultEvent,
    FaultPlan,
    RadixPrefixCache,
    Request,
)

V = 12


def _net(seed=7, stream_max_t=64, n_layers=2):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=n_layers, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _one_hot_seq(ids):
    x = np.zeros((1, V, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


_SOLO_CACHE = {}


def _solo_generate(prompt, n, seed=7, stream_max_t=64):
    key = (tuple(prompt), n, seed, stream_max_t)
    if key not in _SOLO_CACHE:
        net = _net(seed, stream_max_t)
        net.rnn_clear_previous_state()
        _SOLO_CACHE[key] = np.asarray(
            net.generate(_one_hot_seq(prompt), n))[0].tolist()
    return _SOLO_CACHE[key]


# shared-prefix workload: exercises splice + CoW + cold admissions
SHARED = [1, 4, 7, 2, 5, 9, 3, 3]
CASES = [(SHARED + [1, 6], 8), (SHARED + [2, 0], 5),
         ([9, 3, 3], 11), (SHARED + [4, 8], 7), ([2, 2], 9)]


class TestPagedParityMatrix:
    """ISSUE 6 acceptance gate: greedy id bit-parity with sequential
    generate across all 4 admission modes x prefix on/off x spec
    on/off."""

    @pytest.mark.parametrize("prefill_chunk,policy", [
        (0, "ttft"), (0, "decode"), (4, "ttft"), (4, "decode")])
    @pytest.mark.parametrize("prefix_rows", [0, 4])
    @pytest.mark.parametrize("spec", [0, 3])
    def test_greedy_bit_parity(self, prefill_chunk, policy,
                               prefix_rows, spec):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=prefix_rows,
                           prefill_chunk=prefill_chunk,
                           admission_policy=policy,
                           spec_draft_len=spec)
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = eng.run()
        for rid, (p, n) in zip(ids, CASES):
            assert res[rid].tokens == _solo_generate(p, n), (
                f"paged engine diverged from sequential generate at "
                f"chunk={prefill_chunk} policy={policy} "
                f"prefix={prefix_rows} spec={spec}")
        counts = eng.compile_counts()
        assert counts["decode"] == 1, counts
        assert "admit" not in counts
        assert counts["paged_scatter"] == 1
        assert counts["paged_tok"] == 1
        if spec:
            # one verify executable per pow2 draft-width bucket
            assert 1 <= counts["verify"] <= spec.bit_length() + 1
        if prefix_rows:
            # the trie owns NO jitted movers: a warm hit is a
            # host-side block-table splice
            assert "prefix_fetch" not in counts
            assert "prefix_store" not in counts

    def test_no_retrace_once_warm(self, assert_no_retrace):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=3,
                           block_tokens=8,
                           prefix_cache_rows=4, prefill_chunk=4,
                           spec_draft_len=3)
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = eng.run()
        with assert_no_retrace(eng):
            more = [eng.submit(Request(p, n)) for p, n in CASES[:3]]
            res.update(eng.run())
        for rid, (p, n) in zip(ids + more, CASES + CASES[:3]):
            assert res[rid].tokens == _solo_generate(p, n)

    def test_graph_network_paged_parity(self):
        """ComputationGraph nets thread the paged cache dicts through
        their own rnn-state plumbing unchanged."""
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

        prompt, n = [2, 5, 9], 8
        solo = gnet()
        solo.rnn_clear_previous_state()
        want = np.asarray(solo.generate(_one_hot_seq(prompt), n))
        eng = DecodeEngine(gnet(), n_slots=2, decode_chunk=4,
                           block_tokens=4)
        rid = eng.submit(Request(prompt, n))
        assert eng.run()[rid].tokens == want[0].tolist()

    def test_window_slide_over_block_ring(self):
        """Totals past the window exercise ring reuse + slid-out block
        frees; ids must still match the dense sliding-window decode."""
        prompt = [1, 4, 7, 2, 5, 9, 3, 3, 8, 6, 0, 2] * 2  # 24 tokens
        n = 24                            # 48 total > window 32
        eng = DecodeEngine(_net(stream_max_t=32), n_slots=2,
                           decode_chunk=3, seed=0, block_tokens=4)
        rid = eng.submit(Request(prompt, n))
        res = eng.run()
        assert res[rid].tokens == _solo_generate(prompt, n,
                                                 stream_max_t=32)
        # the ring recycled: a 48-token history at block_tokens=4
        # touches 12 logical blocks, but live residency never exceeds
        # window + one round of writes
        assert eng.block_pool.used_blocks == 0   # all freed after run


class TestZeroCopySharing:
    def test_warm_hit_splices_blocks_without_row_copy(self):
        """A warm admission reuses the entry's blocks by reference:
        splice counters move, no prefix_fetch executable exists, and
        the only device copy is the CoW of the boundary block."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4)
        r1 = eng.submit(Request(SHARED + [1, 6], 6))
        eng.run()
        assert eng.stats["prefix_blocks_spliced"] == 0   # cold
        r2 = eng.submit(Request(SHARED + [2, 0], 6))
        res = eng.run()
        assert res[r2].tokens == _solo_generate(SHARED + [2, 0], 6)
        assert res[r2].prefix_tokens_reused == len(SHARED)
        assert eng.stats["prefix_blocks_spliced"] >= 1
        assert eng.stats["prefill_tokens_skipped"] >= len(SHARED)
        counts = eng.compile_counts()
        assert "prefix_fetch" not in counts
        # CoW happened at most once per admission (boundary block
        # only — never a whole row)
        assert 1 <= eng.stats["cow_copies"] <= 4

    def test_block_aligned_prefix_needs_no_cow(self):
        """A match ending exactly on a block boundary splices with
        ZERO device work: appends start a fresh block."""
        prompt_a = SHARED[:]              # 8 tokens == 1 full block
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4)
        eng.submit(Request(prompt_a + [5, 2], 4))
        eng.run()
        cow_before = eng.stats["cow_copies"]
        rid = eng.submit(Request(prompt_a + [9, 9], 6))
        res = eng.run()
        assert res[rid].tokens == _solo_generate(prompt_a + [9, 9], 6)
        assert res[rid].prefix_tokens_reused == len(prompt_a)
        # the 8-token match covers exactly the shared full block; the
        # divergent suffix lands in fresh blocks — no boundary CoW for
        # THIS hit (the engine may CoW its own insert's tail later)
        assert eng.stats["prefix_blocks_spliced"] >= 1
        assert eng.stats["cow_copies"] <= cow_before + 1

    def test_shared_block_immutable_across_sharers(self):
        """Two requests diverging after a shared prefix must not see
        each other's tokens through the shared block (CoW isolation),
        and a third request re-hitting the prefix still gets exact
        ids — the entry's block was never mutated."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4)
        tails = ([1, 6], [2, 0], [4, 8])
        ids = [eng.submit(Request(SHARED + t, 7)) for t in tails]
        res = eng.run()
        for rid, t in zip(ids, tails):
            assert res[rid].tokens == _solo_generate(SHARED + t, 7)

    def test_pool_fully_free_when_idle_without_cache(self):
        eng = DecodeEngine(_net(), n_slots=3, decode_chunk=2, seed=0,
                           block_tokens=8)
        for p, n in CASES:
            eng.submit(Request(p, n))
        eng.run()
        assert eng.block_pool.used_blocks == 0
        assert eng.block_pool.free_blocks == eng.kv_blocks

    def test_idle_pool_holds_only_trie_blocks(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4)
        for p, n in CASES:
            eng.submit(Request(p, n))
        eng.run()
        trie_blocks = set(eng.prefix_cache.block_ids())
        assert eng.block_pool.used_blocks == len(trie_blocks)
        eng.prefix_cache.clear()
        assert eng.block_pool.used_blocks == 0


class TestOversubscription:
    def test_more_slots_than_dense_rows_at_equal_bytes(self):
        """The memory headline: at the DENSE engine's byte budget
        (n_dense window rows), the paged engine runs strictly more
        concurrent slots — short requests hold short tables."""
        window, bt = 64, 8
        n_dense = 2
        kv_blocks = n_dense * (window // bt)       # equal device bytes
        n_paged = 5
        eng = DecodeEngine(_net(), n_slots=n_paged, decode_chunk=2,
                           seed=0, block_tokens=bt,
                           kv_blocks=kv_blocks)
        cases = [([1 + i, 4, 7 + (i % 3), 2], 6) for i in range(n_paged)]
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = eng.run()
        for rid, (p, n) in zip(ids, cases):
            assert res[rid].tokens == _solo_generate(p, n)
        # every slot held a live request at once in at least one round
        assert eng.mean_occupancy > n_dense / n_paged
        assert eng.stats["preempted"] == 0   # they genuinely all fit

    def test_preemption_under_pool_pressure_keeps_ids_exact(self):
        """When the pool truly cannot hold every active slot, the
        youngest is preempted and requeued — its re-admission
        regenerates bit-identical greedy ids (vLLM-style recompute
        preemption, invisible in results)."""
        window, bt = 32, 4
        eng = DecodeEngine(_net(stream_max_t=window), n_slots=4,
                           decode_chunk=2, seed=0, block_tokens=bt, kv_blocks=26)
        cases = [([1, 4, 7, 2, 5, 9, 3, 3, 8, 6][: 6 + (i % 4)], 18)
                 for i in range(6)]
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = eng.run()
        for rid, (p, n) in zip(ids, cases):
            assert res[rid].tokens == _solo_generate(
                p, n, stream_max_t=window), (
                f"preempted request {rid} diverged on re-admission")
        assert eng.stats["preempted"] >= 1
        assert eng.block_pool.used_blocks == 0


class TestPagedQuarantine:
    def test_victim_releases_blocks_without_scrubbing_shared(self):
        """ISSUE 6 satellite regression: poison a victim whose table
        SHARES prefix blocks with an innocent slot. The innocent must
        finish bit-identical (the shared block is released by
        reference, never zeroed under it) while the victim retries."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4, paranoid=True,
                           fault_plan=FaultPlan(
                               [FaultEvent(4, "nan", slot=0)]),
                           max_retries=3)
        # seed the shared prefix, then run two sharers side by side
        seed_rid = eng.submit(Request(SHARED + [1, 6], 2))
        eng.run()
        a = eng.submit(Request(SHARED + [2, 0], 10))   # slot 0: victim
        b = eng.submit(Request(SHARED + [4, 8], 10))   # slot 1: innocent
        res = eng.run()
        assert res[b].retries == 0
        assert res[b].tokens == _solo_generate(SHARED + [4, 8], 10), (
            "innocent slot's ids corrupted by its neighbour's "
            "quarantine — a shared block was scrubbed while live")
        assert res[a].retries >= 1
        assert res[a].tokens == _solo_generate(SHARED + [2, 0], 10)
        assert eng.stats["quarantined"] >= 1
        # every poisoned block was scrubbed once its last ref dropped
        assert eng.block_pool.poisoned == set()
        assert eng.block_pool.stats["scrubbed"] >= 1
        del res, seed_rid

    def test_corrupted_entry_block_detected_and_invalidated(self):
        """cache_corrupt bit-rots a stored entry's block inside the
        SHARED pool; the per-block sweep invalidates the entry and the
        workload still finishes exact."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4, paranoid=True,
                           fault_plan=FaultPlan(
                               [FaultEvent(3, "cache_corrupt")]),
                           max_retries=3)
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = eng.run()
        for rid, (p, n) in zip(ids, CASES):
            if res[rid].finish_reason != "fault":
                assert res[rid].tokens == _solo_generate(p, n)
        assert eng.prefix_cache.stats["invalidations"] >= 1
        assert eng.block_pool.poisoned == set()

    def test_undetected_without_paranoid(self):
        """No paranoid sweep, no detection: the knob buys the
        checks."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           block_tokens=8,
                           fault_plan=FaultPlan(
                               [FaultEvent(1, "nan", slot=0)]))
        rid = eng.submit(Request([1, 4, 7, 2], 8))
        res = eng.run()
        assert res[rid].finish_reason in ("length", "eos")
        assert eng.stats["faults_detected"] == 0

    def test_recycled_dirty_block_cannot_corrupt_next_owner(self):
        """Review regression: with paranoid OFF, eviction releases a
        NaN-poisoned victim's blocks UNSCRUBBED (nothing marked them
        poisoned). The
        engine value-masks every lane outside a row's written
        span — so a later request reallocating the dirty block must
        still produce exact ids (0 x NaN = NaN would otherwise leak
        through its unwritten tail)."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           block_tokens=8,
                           fault_plan=FaultPlan(
                               [FaultEvent(1, "nan", slot=0)]))
        victim = eng.submit(Request([1, 4, 7, 2], 8))
        res = eng.run()
        assert res[victim].finish_reason in ("length", "eos")
        assert eng.block_pool.used_blocks == 0   # dirty blocks freed
        after = eng.submit(Request([9, 3, 3], 11))
        res = eng.run()
        assert res[after].tokens == _solo_generate([9, 3, 3], 11), (
            "a recycled dirty block leaked the previous victim's NaN "
            "into the next owner's attention output")


class TestPagedSnapshotRestore:
    def test_snapshot_carries_block_tables_and_refcounts(self):
        """ISSUE 6 satellite: the snapshot is still plain JSON and
        records the paged bookkeeping (tables + refcounts) alongside
        the recorded tokens that rebuild them."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4, prefill_chunk=4)
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = {}
        for _ in range(4):
            eng.step(res)
        snap = eng.snapshot()
        json.dumps(snap)                      # plain JSON
        assert snap["config"]["paged_kv"] is True
        assert snap["config"]["block_tokens"] == 8
        paged = snap["paged"]
        assert paged["kv_blocks"] == eng.kv_blocks
        assert paged["tables"], "no live slot tables snapshotted"
        for tab in paged["tables"].values():
            assert tab["length"] >= 1
            assert tab["blocks"]
        assert paged["refcounts"]
        eng2 = DecodeEngine.restore(_net(), snap)
        assert eng2.kv_blocks == eng.kv_blocks
        res.update(eng2.run())
        for rid, (p, n) in zip(ids, CASES):
            assert res[rid].tokens == _solo_generate(p, n), (
                f"restored paged engine diverged on request {rid}")

    @pytest.mark.parametrize("says", [False, None])
    def test_dense_snapshot_is_refused_by_name(self, says):
        """A snapshot an engine with the dense layout took (its config
        says ``paged_kv: false``, or, older, says nothing) names what
        became of that layout instead of restoring into another."""
        snap = DecodeEngine(_net(), n_slots=2, decode_chunk=2).snapshot()
        assert snap["config"]["paged_kv"] is True
        assert snap["paged"] is not None
        if says is None:
            del snap["config"]["paged_kv"]
        else:
            snap["config"]["paged_kv"] = says
        with pytest.raises(ValueError, match="removed in PR 29"):
            DecodeEngine.restore(_net(), snap)


class TestPagedObservability:
    def test_engine_stats_and_tracer_gauges(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4, tracer=tracer)
        for p, n in CASES:
            eng.submit(Request(p, n))
        eng.run()
        for key in ("blocks_free", "blocks_used", "cow_copies",
                    "prefix_blocks_spliced", "frag_tokens",
                    "preempted"):
            assert key in eng.stats
        latest = tracer.latest_counters()
        assert "serving_blocks_used" in latest
        assert "serving_cow_copies" in latest
        assert "serving_prefix_blocks_spliced" in latest
        text = tracer.prometheus_text()
        assert "serving_blocks_free" in text
        assert "serving_frag_tokens" in text

    def test_gateway_metrics_expose_block_gauges(self):
        """End-to-end: the HTTP front door's /v1/metrics carries the
        block-pool gauges of a paged engine (ISSUE 6 satellite)."""
        from deeplearning4j_tpu.serving import (
            GatewayClient,
            ServingGateway,
        )

        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4)
        gw = ServingGateway(eng).start()
        try:
            client = GatewayClient(gw.address)
            out = client.generate([1, 4, 7, 2], max_new_tokens=6)
            assert out["tokens"] == _solo_generate([1, 4, 7, 2], 6)
            metrics = client.metrics()
            assert "serving_blocks_used" in metrics
            assert "serving_blocks_free" in metrics
            assert "serving_prefix_blocks_spliced" in metrics
        finally:
            gw.close()

    def test_fragmentation_counts_masked_tail_tokens(self):
        """A lone 9-token sequence on 8-token blocks holds 2 blocks =
        16 allocated tokens, 7 of them pad — the frag gauge must see
        exactly the allocated-but-masked tail."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           block_tokens=8)
        rid = eng.submit(Request([1, 4, 7, 2, 5, 9, 3], 40))
        res = {}
        eng.step(res)                  # admission + one decode chunk
        eng._paged_stats_refresh()
        tab = eng._kv_tabs[0]
        allocated = len(tab.blocks) * 8
        live = tab.length - tab.floor
        assert eng.stats["frag_tokens"] == allocated - live
        eng.run()
        del res, rid


    def test_one_kind_of_layer_is_a_list_of_one_kind(self):
        """A net whose attention layers agree on their window holds a
        slot's blocks as ``KindTables`` of ONE table and counts by kind
        as any other net does: the kind's live blocks are all the live
        blocks, and within the window every spanned block is held."""
        from deeplearning4j_tpu.serving.block_pool import KindTables

        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8)
        eng.submit(Request([1, 4, 7, 2, 5, 9, 3], 12))
        eng.step({})
        tab = eng._kv_tabs[0]
        assert isinstance(tab, KindTables) and len(tab.kinds) == 1
        assert set(tab.blocks) == {(0, g) for g in tab.kinds[0].blocks}
        eng.run()
        (kind,) = eng.kv.kinds
        w = kind.window
        assert eng.stats[f"paged_blocks_live_w{w}"] == eng.stats[
            "paged_blocks_live"] > 0
        assert eng.stats[f"kv_blocks_held_w{w}"] == eng.stats[
            f"kv_blocks_spanned_w{w}"] > 0


class TestPagedUnits:
    def test_block_pool_validation(self):
        with pytest.raises(ValueError, match="power of two"):
            BlockPool(8, 6)
        with pytest.raises(ValueError, match="kv_blocks"):
            BlockPool(0, 8)
        with pytest.raises(ValueError, match="power of two"):
            DecodeEngine(_net(), n_slots=1, block_tokens=12)
        with pytest.raises(ValueError, match="kv_blocks"):
            DecodeEngine(_net(), n_slots=1, block_tokens=8, kv_blocks=2)
        with pytest.raises(ValueError, match="block_tokens"):
            DecodeEngine(_net(stream_max_t=16), n_slots=1,
                         block_tokens=32)
        with pytest.raises(ValueError, match="removed in PR 29"):
            DecodeEngine(_net(), n_slots=1, paged_kv=False)
        assert DecodeEngine(_net(), n_slots=1).block_pool is not None

    def test_block_pool_refcounts_and_scrub_marking(self):
        pool = BlockPool(4, 8)
        a = pool.alloc()
        pool.ref(a)
        assert pool.refcount(a) == 2
        assert not pool.deref(a)
        assert pool.deref(a)                # last ref frees
        assert pool.free_blocks == 4
        with pytest.raises(AssertionError):
            pool.deref(a)

    def test_block_table_ring_and_coverage(self):
        tab = BlockTable(8)
        tab.blocks = {0: 5, 1: 2}
        tab.length = 12
        table, base = tab.arrays(4)
        assert table[0] == 5 and base[0] == 0
        assert table[1] == 2 and base[1] == 8
        assert table[2] == -1
        assert tab.coverage(0) == 8 and tab.coverage(1) == 4
        assert tab.tail_block() == (1, 2)
        assert tab.new_logical_blocks(4) == []      # fits in tail
        assert tab.new_logical_blocks(5) == [2]

    def test_trie_entry_references_pool_blocks(self):
        pool = BlockPool(8, 8)
        trie = RadixPrefixCache(4, 8, pool.ref, pool.deref)
        tab = BlockTable(8)
        tab.blocks = {0: pool.alloc()}
        tab.length = 3
        assert trie.insert_blocks([1, 2, 3], tab)
        assert pool.refcount(tab.blocks[0]) == 2
        hit = trie.lookup([1, 2, 3, 4])
        assert hit is not None and hit.matched == 3
        assert trie.payload(hit.row).blocks == tab.blocks
        trie.release(hit)
        assert trie.invalidate([1, 2, 3])
        assert pool.refcount(tab.blocks[0]) == 1

    def test_deltas_concat_equals_terminal_paged(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           emit_deltas=True)
        ids = [eng.submit(Request(p, n)) for p, n in CASES[:3]]
        streamed = {r: [] for r in ids}
        res = {}
        while eng.has_work():
            eng.step(res)
            for rid, toks in eng.drain_deltas().items():
                streamed[rid].extend(toks)
        for rid in ids:
            assert streamed[rid] == res[rid].tokens


def _count_paged_calls(eng, name, pos=3):
    """Wrap one of the engine's jitted programs, whose argument
    ``pos`` is the table operand; the returned list grows by one a
    call that was handed one."""
    inner, calls = getattr(eng, name), []

    def counted(*args):
        if args[pos] is not None:
            calls.append(args[pos])
        return inner(*args)

    setattr(eng, name, counted)
    return calls


class TestSharedTables:
    """ISSUE 28: the block tables of a paged dispatch are uploaded
    ONCE and enter the program beside the donated pool; every layer
    reads the same arrays and no program hands them back a layer."""

    @pytest.mark.parametrize("n_layers", [2, 6])
    def test_one_upload_a_dispatch_at_any_depth(self, n_layers):
        import jax
        import jax.numpy as jnp

        tracer = Tracer()
        eng = DecodeEngine(_net(n_layers=n_layers), n_slots=2,
                           decode_chunk=2, seed=0, block_tokens=8, tracer=tracer)
        for p, n in CASES:
            eng.submit(Request(p, n))
        eng.run()
        assert len(eng._pool) == n_layers
        # cold blocking admissions take no table: the decode rounds
        # are the paged dispatches, one upload each
        assert eng.stats["chunks"] > 0
        assert eng.stats["table_uploads"] == eng.stats["chunks"]
        assert (tracer.latest_counters()["serving_table_uploads"]
                == eng.stats["table_uploads"])
        # what the decode program hands back: pool leaves, tokens,
        # counters — no layer's table operands
        out = jax.eval_shape(
            eng._decode_jit, eng._params, eng._state, eng._pool,
            eng.kv.pack(eng._kv_tabs), eng._toks,
            jnp.asarray(eng._temps), jnp.asarray(eng._top_ks),
            jax.random.key(0))
        names = {str(getattr(k, "key", k))
                 for path, _ in jax.tree_util.tree_flatten_with_path(
                     out)[0] for k in path}
        assert not names & {"table", "base", "floor", "filled"}, names
        assert {"pk", "pv"} <= names
        assert set(out[0]) == set(eng._pool)

    def test_spec_round_rewinds_the_one_filled(self):
        """A verify dispatch chained into the decode dispatch, with a
        rejected draft: the rewind reaches the decode scan through the
        tables the verify program hands back, and the round still
        makes one upload."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           spec_draft_len=3)
        uploaded = []
        upload = eng.kv.pack

        def uploading(tabs, chunk=1):
            uploaded.append(upload(tabs, chunk))
            return uploaded[-1]

        eng.kv.pack = uploading
        verified = _count_paged_calls(eng, "_verify_jit")
        decoded = _count_paged_calls(eng, "_decode_jit")
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = eng.run()
        for rid, (p, n) in zip(ids, CASES):
            assert res[rid].tokens == _solo_generate(p, n)
        spec_rounds = eng.stats["spec_rounds"]
        assert spec_rounds > 0
        assert eng.stats["spec_drafted"] > eng.stats["spec_accepted"]
        # one upload a round, spec or plain
        assert len(decoded) == eng.stats["chunks"] == len(uploaded)
        assert eng.stats["table_uploads"] == len(uploaded)
        # a spec round's upload goes to the verify program, and what
        # its decode dispatch takes is that program's output
        assert len(verified) == spec_rounds
        assert all(any(t is u for u in uploaded) for t in verified)
        fresh = sum(any(t is u for u in uploaded) for t in decoded)
        assert fresh == len(decoded) - spec_rounds
        assert eng.stats["spec_fallback_rounds"] == fresh > 0

    def test_warm_chunked_admission_through_the_shared_operand(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefix_cache_rows=4, prefill_chunk=4)
        warm = _count_paged_calls(eng, "_chunk_jit", pos=5)
        ids = [eng.submit(Request(p, n)) for p, n in CASES]
        res = eng.run()
        for rid, (p, n) in zip(ids, CASES):
            assert res[rid].tokens == _solo_generate(p, n)
        assert eng.stats["prefix_blocks_spliced"] > 0
        assert warm, "no admission streamed through a block table"
        assert all(t.shape == (1, 2 * eng.kv.kinds[0].ring + 2)
                   for t in warm)
        assert (eng.stats["table_uploads"]
                == eng.stats["chunks"] + len(warm))
