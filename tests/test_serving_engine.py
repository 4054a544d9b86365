"""Continuous-batching decode engine (ISSUE 1 tentpole).

The contract under test: the engine multiplexes many requests onto ONE
compiled batched decode step over a slot pool, and each greedy request's
ids are EXACTLY what a sequential B=1 ``generate()`` would have produced
— admission order, slot index, neighbours, and padding must all be
invisible to a request's own tokens."""

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import DecodeEngine, Request

V = 12


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


def _solo_generate(prompt, n, seed=7):
    net = _net(seed)
    net.rnn_clear_previous_state()
    return np.asarray(net.generate(_one_hot_seq(prompt), n))[0].tolist()


class TestEngineParity:
    def test_greedy_matches_sequential_generate(self):
        """Exact ids per request vs B=1 generate, with more requests
        than slots (forces queueing, eviction, re-admission)."""
        prompts = [[1, 4, 7, 2], [9, 3, 3], [5, 2, 8, 1, 6, 0, 4],
                   [2, 2], [11, 0, 6]]
        lens = [6, 11, 4, 9, 17]
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0)
        ids = [eng.submit(Request(p, n))
               for p, n in zip(prompts, lens)]
        res = eng.run()
        for rid, p, n in zip(ids, prompts, lens):
            assert res[rid].tokens == _solo_generate(p, n)
            assert res[rid].finish_reason == "length"
            assert res[rid].prompt_len == len(p)

    def test_single_token_request(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2)
        rid = eng.submit(Request([3, 1], 1))
        res = eng.run()
        assert res[rid].tokens == _solo_generate([3, 1], 1)

    def test_graph_network_parity(self):
        """ComputationGraph nets serve through the same engine."""
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
        eng = DecodeEngine(gnet(), n_slots=2, decode_chunk=4)
        rid = eng.submit(Request(prompt, n))
        res = eng.run()
        assert res[rid].tokens == want[0].tolist()


class TestRaggedAdmissionEviction:
    def test_requests_join_and_leave_mid_flight(self,
                                                assert_no_retrace):
        """Ragged prompt AND decode lengths on a small pool: short
        requests finish and free their slot while long ones keep
        decoding; late admissions join a half-decoded batch. Every
        request must still match its solo run exactly — with zero
        retraces once the first wave warmed all buckets."""
        cases = [([1, 2, 3], 3), ([4, 5, 6, 7, 8, 9, 10, 11, 1], 21),
                 ([7], 5), ([2, 9, 4, 6], 13), ([10, 10], 2),
                 ([0, 1, 2, 3, 4, 5], 8), ([8, 6, 4], 17)]
        eng = DecodeEngine(_net(seed=11), n_slots=3, decode_chunk=2,
                           seed=5)
        warm_ids = [eng.submit(Request(p, n)) for p, n in cases[:2]]
        res = eng.run()  # warms decode/admit + both buckets
        with assert_no_retrace(eng):
            ids = [eng.submit(Request(p, n)) for p, n in cases[2:]]
            res.update(eng.run())
        for rid, (p, n) in zip(warm_ids + ids, cases):
            assert res[rid].tokens == _solo_generate(p, n, seed=11), (
                f"request {rid} diverged from its solo decode")
        assert eng.stats["requests_finished"] == len(cases)

    def test_eviction_does_not_disturb_neighbours(self):
        """A long request spanning many admission waves decodes the
        same ids as alone on an idle engine."""
        long_prompt, long_n = [3, 1, 4, 1, 5], 24
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=2)
        rid = eng.submit(Request(long_prompt, long_n))
        churn = [eng.submit(Request([i % V], 2)) for i in range(6)]
        res = eng.run()
        assert res[rid].tokens == _solo_generate(long_prompt, long_n)
        assert all(len(res[c].tokens) == 2 for c in churn)

    def test_eos_frees_slot_early(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=4)
        base = _solo_generate([1, 2, 3], 8)
        eos = base[2]  # may occur earlier: truncate at FIRST hit
        rid = eng.submit(Request([1, 2, 3], 50, eos_id=eos))
        res = eng.run()
        assert res[rid].tokens == base[:base.index(eos) + 1]
        assert res[rid].finish_reason == "eos"

    def test_eos_on_final_token_reports_eos(self):
        """eos landing exactly on the max_new_tokens-th token is a
        clean termination, not a length truncation."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=4)
        base = _solo_generate([1, 2, 3], 8)
        stop = base.index(base[2]) + 1  # first hit of the eos token
        rid = eng.submit(Request([1, 2, 3], stop, eos_id=base[2]))
        res = eng.run()
        assert res[rid].tokens == base[:stop]
        assert res[rid].finish_reason == "eos"

    def test_finished_request_id_is_released(self):
        """Scheduler forgets finished ids (bounded memory under churn)
        while still rejecting concurrent duplicates."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        req = Request([1, 2], 3)
        eng.submit(req)
        with pytest.raises(ValueError, match="already submitted"):
            eng.submit(req)
        eng.run()
        assert not eng.scheduler._issued
        eng.submit(req)  # finished id may be reused
        assert eng.run()[req.id].tokens == _solo_generate([1, 2], 3)


class TestCompileCounts:
    def test_no_retrace_after_warmup_across_admissions(
            self, assert_no_retrace):
        """The tentpole's compile guarantee: one decode executable,
        one scatter of an admitted row and one put of its first token,
        one prefill executable per prompt-length
        bucket — further admissions (any slot, any order, any length
        in a seen bucket, any sampling config) never retrace."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        # warmup: buckets 8 (len<=8) and 16 (len 9..16)
        eng.submit(Request([1, 2, 3], 4))
        eng.submit(Request(list(range(10)), 4))
        eng.run()
        warm = eng.compile_counts()
        assert warm["decode"] == 1
        assert warm["paged_scatter"] == 1
        assert warm["paged_tok"] == 1
        assert "admit" not in warm
        assert warm["prefill"] == 2
        # same buckets, new lengths/slots/configs: no new executables
        with assert_no_retrace(eng):
            eng.submit(Request([5] * 7, 9, temperature=0.7, top_k=4))
            eng.submit(Request([2] * 13, 3))
            eng.submit(Request([8], 5))
            eng.run()

    def test_generate_scan_is_bucketed(self):
        """Satellite: generate() keys its jit cache on the pow2 bucket
        of the scan length, not on n_tokens — varied request lengths
        stay within O(log max) compiles."""
        net = _net()
        net.rnn_clear_previous_state()
        net.generate(_one_hot_seq([1, 2, 3]), 6)   # n_rem 5 -> bucket 8
        assert set(net._generate_fns) == {8}
        net.rnn_clear_previous_state()
        net.generate(_one_hot_seq([1, 2, 3]), 9)   # n_rem 8 -> bucket 8
        assert set(net._generate_fns) == {8}
        net.rnn_clear_previous_state()
        net.generate(_one_hot_seq([1, 2, 3]), 12)  # n_rem 11 -> bucket 16
        assert set(net._generate_fns) == {8, 16}


class TestSampling:
    def test_top_k_one_is_greedy_at_any_temperature(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=9)
        a = eng.submit(Request([1, 2, 3], 6, temperature=2.0, top_k=1))
        b = eng.submit(Request([1, 2, 3], 6))
        res = eng.run()
        assert res[a].tokens == res[b].tokens

    def test_sampling_is_seed_deterministic(self):
        def run(seed):
            eng = DecodeEngine(_net(), n_slots=1, decode_chunk=4,
                               seed=seed)
            rid = eng.submit(Request([1, 2, 3], 10, temperature=1.0))
            return eng.run()[rid].tokens

        assert run(3) == run(3)

    def test_request_validation(self):
        eng = DecodeEngine(_net(), n_slots=1)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit(Request([V + 3], 4))
        with pytest.raises(ValueError, match="window"):
            eng.submit(Request([1] * 100, 4))  # window is 64
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request([1], 0)
        with pytest.raises(ValueError, match="empty"):
            Request([], 4)

    def test_rejects_non_lm_shaped_net(self):
        from deeplearning4j_tpu.models.zoo import mlp

        with pytest.raises(ValueError, match="attention|LM-shaped"):
            DecodeEngine(MultiLayerNetwork(mlp()).init(), n_slots=1)


class TestPerSlotStateReset:
    def test_clearing_one_slot_leaves_neighbours_intact(self):
        """Satellite: rnn_clear_previous_state(slots=[0]) must reset
        row 0 to the fresh-state decode and leave row 1's continuation
        untouched."""
        import jax.numpy as jnp

        net = _net()
        x = np.concatenate([_one_hot_seq([1, 2, 3]),
                            _one_hot_seq([9, 8, 7])])
        net.rnn_clear_previous_state()
        net.rnn_time_step(jnp.asarray(x))
        net.rnn_clear_previous_state(slots=[0])
        step = np.concatenate([_one_hot_seq([4]), _one_hot_seq([4])])
        out = np.asarray(net.rnn_time_step(jnp.asarray(step)))

        ctrl = _net()  # row 1's uncleaned continuation
        ctrl.rnn_clear_previous_state()
        ctrl.rnn_time_step(jnp.asarray(x))
        out_ctrl = np.asarray(ctrl.rnn_time_step(jnp.asarray(step)))
        np.testing.assert_array_equal(out[1], out_ctrl[1])

        fresh = _net()  # row 0 must decode as if freshly created
        fresh.rnn_clear_previous_state()
        out_fresh = np.asarray(fresh.rnn_time_step(_one_hot_seq([4])))
        # allclose, not bit-equal: the cleared slot streams through the
        # cache path (every position masked) while a fresh net takes
        # the dense prefill path — same math, different XLA program
        np.testing.assert_allclose(out[0], out_fresh[0], rtol=1e-5,
                                   atol=1e-7)

    def test_graph_per_slot_reset(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadSelfAttention,
        )
        from deeplearning4j_tpu.ops.losses import LossFunction

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
        net = ComputationGraph(conf).init()
        x = np.concatenate([_one_hot_seq([1, 2, 3]),
                            _one_hot_seq([9, 8, 7])])
        net.rnn_time_step(x)
        net.rnn_clear_previous_state(slots=[1])
        st = net._rnn_state["attn"]
        assert int(np.asarray(st["filled"])[0]) == 3
        assert int(np.asarray(st["filled"])[1]) == 0
        assert np.all(np.asarray(st["k"])[1] == 0)
        assert np.any(np.asarray(st["k"])[0] != 0)

    def test_out_of_range_slot_raises(self):
        net = _net()
        net.rnn_clear_previous_state()
        net.rnn_time_step(_one_hot_seq([1, 2]))
        with pytest.raises(ValueError, match="out of range"):
            net.rnn_clear_previous_state(slots=[5])


@pytest.mark.slow
class TestSoak:
    def test_many_ragged_requests_soak(self, assert_no_retrace):
        """Long-running churn: 40 requests with varied prompt/decode
        lengths over 4 slots, every one parity-checked."""
        rng = np.random.default_rng(0)
        cases = [(rng.integers(0, V, rng.integers(1, 30)).tolist(),
                  int(rng.integers(1, 40))) for _ in range(40)]
        eng = DecodeEngine(_net(seed=13), n_slots=4, decode_chunk=4,
                           seed=1)
        warm = [([i % V for i in range(n)], 2) for n in (8, 9, 17)]
        for p, n in warm:  # one admission per bucket (8, 16, 32)
            eng.submit(Request(p, n))
        eng.run()
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        with assert_no_retrace(eng):
            res = eng.run()
        for rid, (p, n) in zip(ids, cases):
            assert res[rid].tokens == _solo_generate(p, n, seed=13)
        counts = eng.compile_counts()
        assert counts["decode"] == 1 and counts["admit"] == 1
        assert counts["prefill"] <= 3  # buckets 8, 16, 32
