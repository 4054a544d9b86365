"""The serving round's host phases as spans (ISSUE 24): every
``Tracer.span`` is also one ``jax.profiler`` annotation, the stepper
thread's wall is covered by leaf spans that nest and do not overlap,
and a request's ``timing`` says how long the handler waited for the
stepper's lock (``gateway_wait_s``) and when its first delta left the
engine (``first_delta_s``).

Spans and stamps are host bookkeeping: greedy ids are the same with a
profile being taken and without."""

import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import tracer as tracer_mod
from deeplearning4j_tpu.profiler.tracer import Tracer, annotate
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    GatewayClient,
    Request,
    ServingGateway,
)

V = 12
PROMPTS = [[1, 4, 7, 2], [9, 3, 3], [5, 2, 8, 1, 6, 0, 4], [2, 2],
           [3, 1, 4, 1, 5, 9, 2, 6]]
LENS = [7, 12, 5, 10, 9]

#: the leaves of one paged round, as ``DecodeEngine.step`` names them
ROUND_LEAVES = {"serving.sweeps", "serving.prompt_encode",
                "serving.prefill", "serving.first_token_sync",
                "serving.reserve", "serving.tables",
                "serving.decode_dispatch", "serving.token_sync",
                "serving.commit", "serving.round_end"}


def _net(seed=7):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 64
    return net


def _phase_sum(timing):
    return (timing["queue_wait_s"] + timing["admission_s"]
            + timing["decode_s"] + timing["verify_s"]
            + timing["stall_s"])


class _Stub:
    """Stands in for ``jax.profiler.TraceAnnotation``: notes every
    enter and exit with the arguments it was built from."""

    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _Stub.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        _Stub.log.append(("exit", self.name, self.kwargs))
        return False


@pytest.fixture
def stub(monkeypatch):
    _Stub.log = []
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _Stub)
    return _Stub.log


def _run_gateway(requests=5, **engine_kwargs):
    """A few streamed requests through a gateway; returns (results by
    id, the tracer's events, the stepper thread's tid)."""
    kwargs = dict(n_slots=3, decode_chunk=3, seed=0, block_tokens=8)
    kwargs.update(engine_kwargs)
    engine = DecodeEngine(_net(), **kwargs)
    gw = ServingGateway(engine, keepalive_s=0.1)
    out = {}

    def one(i):
        client = GatewayClient(gw.address, timeout_s=60.0)
        stream = client.stream(PROMPTS[i], LENS[i])
        tokens = [t for delta in stream for t in delta]
        out[i] = (tokens, stream.result)

    with gw:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(requests)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120.0)
        assert not any(th.is_alive() for th in threads)
        tid = gw._stepper.ident % 2 ** 31
    return out, engine.tracer.events(), tid


def _leaves(spans):
    """The spans that hold no other span (same thread; nested spans
    lie inside their parent)."""
    spans = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
    leaves = []
    for i, e in enumerate(spans):
        end = e["ts"] + e["dur"]
        nxt = spans[i + 1] if i + 1 < len(spans) else None
        if nxt is None or nxt["ts"] >= end:
            leaves.append(e)
    return leaves


class TestAnnotation:
    def test_span_enters_and_leaves_one_annotation(self, stub):
        tracer = Tracer()
        with tracer.span("serving.decode_chunk", active=2, fused=0,
                         rids=[3, 4], traces={"3": "r1/a0"},
                         tenant="a", share=0.5, warm=True):
            assert [e[0] for e in stub] == ["enter"]
        assert [(e[0], e[1]) for e in stub] == [
            ("enter", "serving.decode_chunk"),
            ("exit", "serving.decode_chunk")]
        sent = stub[0][2]
        assert sent == {"active": 2, "fused": 0, "tenant": "a",
                        "share": 0.5, "warm": True}
        assert not any(isinstance(v, (list, dict, tuple))
                       for v in sent.values())
        # the Chrome event keeps every argument
        (event,) = tracer.spans("serving.decode_chunk")
        assert event["args"]["rids"] == [3, 4]
        assert event["args"]["traces"] == {"3": "r1/a0"}

    def test_span_yields_its_args_and_leaves_on_error(self, stub):
        tracer = Tracer()
        with pytest.raises(RuntimeError):
            with tracer.span("gateway.submit") as args:
                args["rid"] = 9
                raise RuntimeError("boom")
        assert [e[0] for e in stub] == ["enter", "exit"]
        assert tracer.spans("gateway.submit")[0]["args"] == {"rid": 9}

    def test_annotate_is_a_real_annotation_with_no_profile(self):
        # no stub: the profiler's own class, entered with none taken
        with annotate("train.dispatch", step=3, rids=[1]) as a:
            assert isinstance(a, jax.profiler.TraceAnnotation)

    def test_fit_scan_enters_train_dispatch(self, monkeypatch):
        from deeplearning4j_tpu.nn import multilayer

        seen = []

        def fake(name, **kw):
            seen.append((name, kw))
            return annotate(name, **kw)

        monkeypatch.setattr(multilayer, "annotate", fake)
        net = _net()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (2, 2, 6))
        x = np.eye(V, dtype=np.float32)[ids].transpose(0, 1, 3, 2)
        net.fit_scan(x, x)
        net.fit_scan(x, x)
        assert seen == [("train.dispatch", {"step": 0}),
                        ("train.dispatch", {"step": 2})]


class TestRoundSpans:
    def test_engine_round_has_every_leaf_under_one_parent(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           block_tokens=8,
                           tracer=tracer)
        ids = [eng.submit(Request(p, n))
               for p, n in zip(PROMPTS[:3], LENS[:3])]
        eng.run()
        spans = tracer.spans()
        rounds = tracer.spans("serving.round")
        assert [r["args"]["round"] for r in rounds] == list(
            range(len(rounds)))
        names = {s["name"] for s in spans}
        assert ROUND_LEAVES <= names
        # construction's one span comes before the first round
        (cast,) = tracer.spans("serving.weights_cast")
        assert cast["ts"] + cast["dur"] <= rounds[0]["ts"]
        inside = [s for s in spans if s["name"] not in (
            "serving.round", "serving.weights_cast")]
        for s in inside:   # every other span lies inside one round
            assert any(r["ts"] <= s["ts"] and s["ts"] + s["dur"]
                       <= r["ts"] + r["dur"] for r in rounds), s
        for s in tracer.spans("serving.admit"):
            assert s["args"]["rid"] in ids
        # decode_chunk is the parent of dispatch and sync
        chunk = tracer.spans("serving.decode_chunk")[0]
        kids = [s for s in spans if s["name"] in (
            "serving.decode_dispatch", "serving.token_sync")
            and chunk["ts"] <= s["ts"]
            and s["ts"] + s["dur"] <= chunk["ts"] + chunk["dur"]]
        assert [k["name"] for k in kids] == [
            "serving.decode_dispatch", "serving.token_sync"]

    def test_async_round_syncs_in_the_next_round(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           async_rounds=True, tracer=tracer)
        eng.submit(Request(PROMPTS[0], 8))
        eng.run()
        chunks = tracer.spans("serving.decode_chunk")
        syncs = tracer.spans("serving.token_sync")
        assert chunks and len(syncs) == len(chunks)
        for c, s in zip(chunks, syncs):   # not inside decode_chunk
            assert s["ts"] >= c["ts"] + c["dur"]

    def test_chunked_admission_spans_carry_the_request(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           prefix_cache_rows=4, prefill_chunk=4,
                           tracer=tracer)
        ids = [eng.submit(Request(PROMPTS[2], 5)),
               eng.submit(Request(PROMPTS[4], 5))]
        eng.run()
        admits = tracer.spans("serving.admit")
        assert {s["args"]["rid"] for s in admits} == set(ids)
        assert len(admits) > len(ids)   # a span per round of work
        assert tracer.spans("serving.prompt_encode")

    def test_stepper_leaves_are_disjoint_and_cover_its_wall(self):
        _, events, tid = _run_gateway()
        spans = [e for e in events if e["ph"] == "X"
                 and e["tid"] == tid]
        rounds = [s for s in spans if s["name"] == "serving.round"]
        assert len(rounds) >= 4
        t_lo = rounds[0]["ts"]
        t_hi = rounds[-1]["ts"] + rounds[-1]["dur"]
        spans = [s for s in spans
                 if s["ts"] >= t_lo and s["ts"] + s["dur"] <= t_hi]
        # nesting: a span either holds the next one or ends before it
        ordered = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for s in ordered:
            while stack and stack[-1] <= s["ts"]:
                stack.pop()
            end = s["ts"] + s["dur"]
            assert not stack or end <= stack[-1], s
            stack.append(end)
        # what is not under a round is one of the gateway's leaves
        for s in ordered:
            if s["name"].startswith("gateway."):
                assert not any(r["ts"] <= s["ts"] < r["ts"] + r["dur"]
                               for r in rounds), s
            else:
                assert s["name"].startswith("serving.")
        leaves = _leaves(spans)
        # (``serving.commit`` holds ``serving.kv_release`` since PR 31:
        # the release of blocks that slid out of a window is its child)
        assert {s["name"] for s in leaves} >= {
            "gateway.lock_yield", "serving.kv_release",
            "serving.round_end", "serving.decode_dispatch",
            "serving.token_sync", "serving.tables"}
        assert "serving.commit" in {s["name"] for s in spans}
        for a, b in zip(leaves, leaves[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        # every instant is in some span below ``serving.round``
        # (``serving.admit``'s own time is the scheduler's)
        covered, reach = 0.0, t_lo
        for s in ordered:
            if s["name"] == "serving.round":
                continue
            end = s["ts"] + s["dur"]
            covered += max(0.0, end - max(reach, s["ts"]))
            reach = max(reach, end)
        assert covered >= 0.99 * (t_hi - t_lo), (
            covered, t_hi - t_lo)

    def test_submit_span_holds_the_lock_wait(self):
        out, events, tid = _run_gateway(requests=2)
        submits = [e for e in events if e["name"] == "gateway.submit"]
        waits = [e for e in events if e["name"] == "gateway.lock_wait"]
        assert len(submits) == len(waits) == 2
        assert sorted(s["args"]["rid"] for s in submits) == sorted(
            res["id"] for _, res in out.values())
        for s in submits:
            assert s["tid"] != tid   # a handler's thread
            (w,) = [w for w in waits if w["tid"] == s["tid"]
                    and s["ts"] <= w["ts"]
                    and w["ts"] + w["dur"] <= s["ts"] + s["dur"]]


class TestRequestStamps:
    def test_gateway_timing_orders_the_stamps(self):
        out, _, _ = _run_gateway()
        assert len(out) == 5
        for tokens, res in out.values():
            t = res["timing"]
            assert res["tokens"] == tokens
            assert t["gateway_wait_s"] >= 0.0
            assert t["ttft_s"] <= t["first_delta_s"] <= t["e2e_s"]
            assert _phase_sum(t) <= t["e2e_s"]

    def test_first_delta_waits_for_the_round_after_admission(self):
        """What ``first_delta_s - ttft_s`` is: the admission's first
        token leaves with the next round's, not when it is fetched."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           emit_deltas=True)
        rid = eng.submit(Request(PROMPTS[0], 7))
        res = eng.run()[rid]
        events = eng.request_trace(rid)["attempts"][0]["events"]
        phases = [e["phase"] for e in events]
        assert phases.index("first_token") < phases.index(
            "first_delta") < phases.index("commit")
        first = next(e for e in events if e["phase"] == "first_delta")
        assert first["n"] == 1 + eng.decode_chunk
        assert first["t_s"] == res.timing["first_delta_s"]
        assert phases.count("first_delta") == 1

    def test_no_delta_consumer_no_stamp(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0)
        rid = eng.submit(Request(PROMPTS[0], 7))
        timing = eng.run()[rid].timing
        assert timing["first_delta_s"] is None
        assert "gateway_wait_s" not in timing

    def test_greedy_ids_same_with_a_profile_being_taken(self, tmp_path):
        def ids():
            eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3,
                               seed=0, block_tokens=8,
                               tracer=Tracer())
            rids = [eng.submit(Request(p, n))
                    for p, n in zip(PROMPTS, LENS)]
            res = eng.run()
            return [res[r].tokens for r in rids]

        plain = ids()
        jax.profiler.start_trace(str(tmp_path / "profile"))
        try:
            profiled = ids()
        finally:
            jax.profiler.stop_trace()
        assert profiled == plain
