"""Fused multi-round decode scan (ISSUE 16 tentpole).

The contract under test: ``DecodeEngine(fused_rounds=K)`` dispatches
ONE jitted K-round scan (sampler + paged scatter + on-device eos/max
detection) whenever no per-round host decision is pending, returning
up to K*decode_chunk tokens per live slot in one host round-trip —
and the emitted ids are BIT-IDENTICAL to the stepped engine at every
K, across paged KV, speculative drafting, tensor parallelism, and
async double-buffered rounds. K is bucketed at pow2 sizes (one fused
executable per bucket, zero retrace on repeat traffic), any pending
decision (queued arrivals, deadlines, faults, spec drafts) falls back
to per-round stepping within one window, and snapshot/restore carries
the knob."""

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


SHARED = [1, 4, 7, 2, 5, 9, 3, 3]
PROMPT = SHARED + [1, 6, 2, 0]
CASES = [(SHARED + [1, 6], 8), (SHARED + [2, 0], 5),
         ([9, 3, 3], 11), (SHARED + [4, 8], 7), ([2, 2], 9)]

#: the matrix dimensions (block size x spec x tp x async); each config is
#: ONE stepped reference engine + ONE fused engine, module-cached —
#: the K sweep reuses the fused engine by lowering ``fused_rounds``
#: (a host-side knob: ring and executables were sized for the max)
CONFIGS = {
    "plain": dict(),
    "paged_spec": dict(block_tokens=8, prefix_cache_rows=4,
                       prefill_chunk=4, spec_draft_len=3),
    "paged_tp2": dict(block_tokens=8, tp=2),
    "paged_async": dict(block_tokens=8, async_rounds=True),
}

_STEPPED = {}
_FUSED = {}
_REF = {}


def _reference(prompt, n):
    # greedy ids are engine-config-invariant (PR 1 pins them to
    # sequential ``generate()``), so one stepped engine references all
    key = (tuple(prompt), n)
    if key not in _REF:
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        rid = eng.submit(Request(list(prompt), n))
        _REF[key] = eng.run()[rid].tokens
    return _REF[key]


def _stepped_results(cfg):
    if cfg not in _STEPPED:
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           **CONFIGS[cfg])
        ids = [eng.submit(Request(list(p), n)) for p, n in CASES]
        res = eng.run()
        _STEPPED[cfg] = [(res[i].tokens, res[i].finish_reason)
                         for i in ids]
    return _STEPPED[cfg]


def _fused_engine(cfg):
    if cfg not in _FUSED:
        _FUSED[cfg] = DecodeEngine(
            _net(), n_slots=2, decode_chunk=2, seed=0,
            fused_rounds=8, **CONFIGS[cfg])
    return _FUSED[cfg]


class TestFusedParity:
    @pytest.mark.parametrize("cfg", list(CONFIGS))
    @pytest.mark.parametrize("k", [8, 4, 2, 1])
    def test_greedy_bit_parity(self, cfg, k):
        eng = _fused_engine(cfg)
        eng.fused_rounds = k
        ids = [eng.submit(Request(list(p), n)) for p, n in CASES]
        res = eng.run()
        got = [(res[i].tokens, res[i].finish_reason) for i in ids]
        assert got == _stepped_results(cfg)
        # one fused executable per pow2 bucket, never more
        assert eng.compile_counts()["fused_decode"] <= 4

    def test_fused_path_actually_dispatches(self):
        eng = _fused_engine("plain")
        eng.fused_rounds = 8
        for p, n in CASES:
            eng.submit(Request(list(p), n))
        eng.run()
        assert eng.compile_counts()["fused_decode"] >= 1
        assert eng.histograms["serving_fused_rounds"].count > 0
        assert eng.histograms["serving_host_step_s"].count > 0

    def test_zero_retrace_on_repeat_traffic(self):
        eng = _fused_engine("plain")
        eng.fused_rounds = 8
        for p, n in CASES:
            eng.submit(Request(list(p), n))
        eng.run()
        counts = eng.compile_counts()
        for p, n in CASES:
            eng.submit(Request(list(p), n))
        eng.run()
        assert eng.compile_counts() == counts

    def test_sampling_parity(self):
        # the fused dispatch draws the EXACT host keys K stepped
        # rounds would consume, so sampling ids match bit-for-bit too
        stepped = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               seed=3)
        fused = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                             seed=3, fused_rounds=4)
        req = dict(temperature=0.9, top_k=4)
        i_s = stepped.submit(Request(list(PROMPT), 12, **req))
        i_f = fused.submit(Request(list(PROMPT), 12, **req))
        assert stepped.run()[i_s].tokens == fused.run()[i_f].tokens

    def test_eos_inside_window(self):
        # eos detection is ON DEVICE: a slot whose eos lands mid-scan
        # must truncate at the eos token exactly like stepped mode
        stepped = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               seed=0)
        fused = _fused_engine("plain")
        fused.fused_rounds = 8
        kw = dict(max_new_tokens=16, eos_id=3)
        i_s = stepped.submit(Request(list(CASES[2][0]), **kw))
        i_f = fused.submit(Request(list(CASES[2][0]), **kw))
        rs, rf = stepped.run()[i_s], fused.run()[i_f]
        assert rf.tokens == rs.tokens
        assert rf.finish_reason == rs.finish_reason

    def test_constructor_validation(self):
        with pytest.raises(ValueError):
            DecodeEngine(_net(), n_slots=2, fused_rounds=-1)


class TestFusedFallback:
    def test_cancel_mid_window_async(self):
        # async + fused: cancel lands between dispatch and landing —
        # the window's rows for the cancelled id are discarded via the
        # rids guard and the neighbour is untouched
        eng = _fused_engine("paged_async")
        eng.fused_rounds = 8
        rid = eng.submit(Request(list(PROMPT), 40))
        # long enough to span several K=8 windows — still mid-flight
        # when the cancel lands between dispatch and landing
        other = eng.submit(Request(list(CASES[2][0]), 35))
        res = {}
        eng.step(res)
        eng.step(res)
        assert eng._inflight is not None
        assert eng.cancel(rid)
        res.update(eng.run())
        assert res[rid].finish_reason == "cancelled"
        assert res[other].tokens == _reference(CASES[2][0], 35)

    def test_deadline_traffic_falls_back_and_recovers(self):
        # a live deadline forbids fusing (expiry must be able to land
        # between ROUNDS) — and once the timed request drains, fusing
        # resumes: one deadline must not disable the fast path forever
        eng = _fused_engine("plain")
        eng.fused_rounds = 8
        before = eng.histograms["serving_fused_rounds"].count
        rid = eng.submit(Request(list(CASES[0][0]), CASES[0][1],
                                 deadline_s=600.0))
        res = eng.run()
        assert (res[rid].tokens, res[rid].finish_reason) \
            == _stepped_results("plain")[0]
        assert eng.histograms["serving_fused_rounds"].count == before
        rid2 = eng.submit(Request(list(CASES[0][0]), CASES[0][1]))
        eng.run()
        assert eng.histograms["serving_fused_rounds"].count > before

    def test_snapshot_between_windows(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           fused_rounds=2)
        ids = [eng.submit(Request(list(CASES[0][0]), 21)),
               eng.submit(Request(list(CASES[2][0]), 17))]
        res = {}
        eng.step(res)
        eng.step(res)
        assert eng.has_work()    # genuinely mid-flight
        snap = eng.snapshot()
        assert snap["config"]["fused_rounds"] == 2
        eng2 = DecodeEngine.restore(_net(), snap)
        assert eng2.fused_rounds == 2
        res.update(eng2.run())
        # restore reassigns request ids: compare the token MULTISET
        # against stepped references of the same two workloads
        ref = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        rids = [ref.submit(Request(list(CASES[0][0]), 21)),
                ref.submit(Request(list(CASES[2][0]), 17))]
        rres = ref.run()
        assert (sorted(tuple(r.tokens) for r in res.values())
                == sorted(tuple(rres[i].tokens) for i in rids))


class TestCliKnob:
    def test_serve_parse(self):
        from deeplearning4j_tpu.cli.driver import build_parser

        args = build_parser().parse_args(
            ["serve", "--model", "m.zip", "--fused-rounds", "8"])
        assert args.fused_rounds == 8
        args = build_parser().parse_args(["serve", "--model", "m.zip"])
        assert args.fused_rounds == 0

    def test_fleet_child_argv_carries_fused_rounds(self):
        from deeplearning4j_tpu.cli.driver import (
            _serve_child_argv,
            build_parser,
        )

        args = build_parser().parse_args(
            ["fleet", "--model", "m.zip", "--fused-rounds", "4"])
        argv = _serve_child_argv(args, 9999, "child-0")
        i = argv.index("--fused-rounds")
        assert argv[i + 1] == "4"
        args = build_parser().parse_args(["fleet", "--model", "m.zip"])
        assert "--fused-rounds" not in _serve_child_argv(
            args, 9999, "child-0")
