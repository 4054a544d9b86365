"""Request-scoped observability (ISSUE 7): the streaming Histogram
track type, the tracer's gauge/incr/describe surface, the engine's
per-request phase clock + flight recorder, the gateway's trace
endpoints, and the latency-report tool.

The contract under test: observability is pure host bookkeeping —
greedy ids, RNG consumption, and compile counts are bit-identical with
every knob on or off — and every per-request phase breakdown is a
disjoint-interval decomposition of the request's life, so phase sums
can never exceed end-to-end wall time."""

import json
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Histogram, Tracer
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    FaultEvent,
    FaultPlan,
    GatewayClient,
    GatewayError,
    Request,
    ServingGateway,
)
from scripts.latency_report import (
    histogram_quantile,
    parse_prometheus_histograms,
    report_from_events,
    report_from_metrics_text,
    run_report,
)

V = 12


def _net(seed=7, stream_max_t=64):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


PROMPTS = [[1, 4, 7, 2], [9, 3, 3], [5, 2, 8, 1, 6, 0, 4], [2, 2]]
LENS = [6, 11, 4, 9]


def _phase_sum(timing):
    return (timing["queue_wait_s"] + timing["admission_s"]
            + timing["decode_s"] + timing["verify_s"]
            + timing["stall_s"])


class TestHistogram:
    """Satellite: histogram math — boundaries, quantiles, threads,
    exposition."""

    def test_boundary_value_lands_in_its_bound_bucket(self):
        # Prometheus `le` semantics: a value exactly on a bound counts
        # in that bound's bucket, not the next one up
        h = Histogram(bounds=(1.0, 2.0, 4.0))
        for v in (1.0, 2.0, 4.0, 0.5, 3.0, 5.0):
            h.observe(v)
        counts, total_sum, total = h.snapshot()
        assert counts == [2, 1, 2, 1]  # (<=1): {1.0, 0.5}; (<=2): {2};
        #                                (<=4): {4, 3}; +Inf: {5}
        assert total == 6 and total_sum == pytest.approx(15.5)

    def test_quantile_within_one_bucket_width_of_exact(self):
        # known distribution: 1000 log-uniform latencies
        rng = np.random.default_rng(3)
        values = np.exp(rng.uniform(np.log(1e-3), np.log(1.0), 1000))
        h = Histogram()
        for v in values:
            h.observe(float(v))
        for q in (0.1, 0.5, 0.9, 0.99):
            exact = float(np.quantile(values, q))
            est = h.quantile(q)
            # the winning bucket's width bounds the estimation error
            import bisect

            i = bisect.bisect_left(h.bounds, exact)
            lo = h.bounds[i - 1] if i > 0 else 0.0
            hi = (h.bounds[i] if i < len(h.bounds)
                  else h.bounds[-1])
            assert abs(est - exact) <= (hi - lo) + 1e-12, (
                f"q={q}: est {est} vs exact {exact} "
                f"(bucket [{lo}, {hi}])")

    def test_quantile_edges_and_empty(self):
        h = Histogram(bounds=(1.0, 2.0))
        assert np.isnan(h.quantile(0.5))
        h.observe(1.5)
        assert 1.0 <= h.quantile(0.0) <= h.quantile(1.0) <= 2.0
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_observe_n_weights_like_repeats(self):
        a, b = Histogram(), Histogram()
        for _ in range(5):
            a.observe(0.02)
        b.observe(0.02, n=5)
        assert a.snapshot() == b.snapshot()

    def test_thread_safety_under_concurrent_observe(self):
        h = Histogram()
        n_threads, per = 8, 5000

        def work():
            for _ in range(per):
                h.observe(0.01)

        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == n_threads * per
        assert h.sum == pytest.approx(0.01 * n_threads * per)

    def test_prometheus_exposition_parses_and_is_monotone(self):
        h = Histogram()
        rng = np.random.default_rng(0)
        for v in rng.exponential(0.05, 500):
            h.observe(float(v))
        text = "\n".join(h.prometheus_lines("serving_ttft_s")) + "\n"
        parsed = parse_prometheus_histograms(text)
        fam = parsed["serving_ttft_s"]
        cums = [c for _, c in fam["buckets"]]
        assert cums == sorted(cums), "cumulative buckets not monotone"
        assert fam["buckets"][-1][0] == float("inf")
        assert fam["buckets"][-1][1] == fam["count"] == 500
        # the parsed buckets answer quantiles close to the histogram's
        assert histogram_quantile(fam["buckets"], 0.5) == \
            pytest.approx(h.quantile(0.5), rel=1e-6)

    def test_invalid_bounds_rejected(self):
        for bad in ((), (1.0, 1.0), (2.0, 1.0)):
            with pytest.raises(ValueError):
                Histogram(bounds=bad)


class TestTracerTracks:
    def test_incr_returns_running_total(self):
        t = Tracer()
        assert t.incr("serving_shed") == 1.0
        assert t.incr("serving_shed", 2.0) == 3.0

    def test_gauge_updates_without_pushing_events(self):
        t = Tracer(max_events=8)
        with t.span("real_work"):
            pass
        for _ in range(10_000):
            t.gauge("scrape_gauge", 1.0)
        assert len(t.spans("real_work")) == 1
        assert t.latest_counters()["scrape_gauge"] == 1.0
        assert t.prometheus_text().count("scrape_gauge") == 2  # TYPE+sample

    def test_describe_emits_help_line(self):
        t = Tracer()
        t.counter("serving_admitted", 3)
        t.describe("serving_admitted", "requests admitted\ninto slots")
        text = t.prometheus_text()
        # newlines collapse: HELP is a single line
        assert ("# HELP serving_admitted requests admitted into slots"
                in text)

    def test_observe_creates_and_exports_histogram_track(self):
        t = Tracer()
        t.observe("serving_e2e_s", 0.25)
        t.counter("other_gauge", 1.0)
        assert t.histogram("serving_e2e_s").count == 1
        text = t.prometheus_text(prefix="serving_")
        assert 'serving_e2e_s_bucket{le="+Inf"} 1' in text
        assert "other_gauge" not in text
        # observe pushes NO events: the histogram is the aggregate
        n_events = len(t.events())  # just the counter's one event
        for _ in range(100):
            t.observe("serving_e2e_s", 0.25)
        assert len(t.events()) == n_events

    def test_clear_drops_histograms_keeps_descriptions(self):
        t = Tracer()
        t.describe("serving_e2e_s", "end to end")
        t.observe("serving_e2e_s", 0.1)
        t.clear()
        assert t.histogram("serving_e2e_s") is None
        t.observe("serving_e2e_s", 0.1)
        assert "# HELP serving_e2e_s" in t.prometheus_text()


class TestEnginePhaseClock:
    def test_timing_breakdown_sums_under_e2e_and_ttft_matches(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           prefix_cache_rows=4, prefill_chunk=4,
                           tracer=tracer)
        ids = [eng.submit(Request(list(p), n))
               for p, n in zip(PROMPTS, LENS)]
        res = eng.run()
        for rid in ids:
            timing = res[rid].timing
            assert timing is not None
            assert _phase_sum(timing) <= timing["e2e_s"]
            assert timing["ttft_s"] == res[rid].ttft_s
            assert timing["tokens"] == len(res[rid].tokens)
            assert timing["attempts"] == 1
            trace = eng.request_trace(rid)
            assert trace["timing"] == timing
            phases = [e["phase"]
                      for e in trace["attempts"][0]["events"]]
            assert phases[0] == "queue_wait"
            assert "first_token" in phases and "terminal" in phases

    def test_histograms_populated_and_registered_with_tracer(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           tracer=tracer)
        rid = eng.submit(Request([1, 4, 7, 2], 7))
        res = eng.run()
        for name in ("serving_ttft_s", "serving_queue_wait_s",
                     "serving_round_s", "serving_e2e_s"):
            assert eng.histograms[name].count >= 1, name
            # registered BY REFERENCE: the tracer exports the very
            # same object /v1/metrics will read
            assert tracer.histogram(name) is eng.histograms[name]
        # ITL: every token after the first measures one gap
        assert eng.histograms["serving_itl_s"].count == \
            len(res[rid].tokens) - 1

    def test_record_timing_off_is_invisible_and_bit_identical(self):
        on = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0)
        off = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           record_timing=False, flight_recorder=0)
        ids_on = [on.submit(Request(list(p), n))
                  for p, n in zip(PROMPTS, LENS)]
        ids_off = [off.submit(Request(list(p), n))
                   for p, n in zip(PROMPTS, LENS)]
        res_on, res_off = on.run(), off.run()
        for a, b in zip(ids_on, ids_off):
            assert res_on[a].tokens == res_off[b].tokens
        assert res_off[ids_off[0]].timing is None
        assert off.request_trace(ids_off[0]) is None
        assert off._clocks == {} and off.histograms == {}
        assert on.compile_counts() == off.compile_counts()

    def test_flight_recorder_ring_evicts_oldest(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           flight_recorder=3)
        ids = [eng.submit(Request([1 + i % 4, 4, 7], 4))
               for i in range(6)]
        eng.run()
        assert [rid for rid in ids if eng.request_trace(rid)] == \
            ids[-3:]

    def test_fault_retries_appear_as_distinct_attempts(self):
        plan = FaultPlan([FaultEvent(0, "admit_fail")])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=3, seed=0,
                           paranoid=True, fault_plan=plan,
                           max_retries=2)
        rid = eng.submit(Request([1, 4, 7, 2], 5))
        res = eng.run()
        assert res[rid].retries == 1
        trace = eng.request_trace(rid)
        assert len(trace["attempts"]) == 2
        assert trace["timing"]["attempts"] == 2
        assert any(e["phase"] == "requeue"
                   for e in trace["attempts"][0]["events"])
        assert _phase_sum(trace["timing"]) <= \
            trace["timing"]["e2e_s"]

    def test_snapshot_restore_marks_restored_attempt(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        ids = [eng.submit(Request(list(p), 9)) for p in PROMPTS]
        eng.step()  # some slots mid-flight, some queued
        snap = json.loads(json.dumps(eng.snapshot()))
        eng2 = DecodeEngine.restore(_net(), snap)
        res = eng2.run()
        for rid in ids:
            timing = res[rid].timing
            assert timing is not None
            assert _phase_sum(timing) <= timing["e2e_s"]
            trace = eng2.request_trace(rid)
            assert trace["attempts"][0]["events"][0]["phase"] == \
                "restored"

    def test_spans_carry_request_ids(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           prefix_cache_rows=4, tracer=tracer)
        ids = [eng.submit(Request([1, 4, 7, 2], 5)),
               eng.submit(Request([1, 4, 7, 2, 9], 5))]
        eng.run()
        for span in tracer.spans("serving.admit"):
            assert span["args"]["rid"] in ids
        for span in tracer.spans("serving.prefill"):
            assert span["args"]["rid"] in ids
        for span in tracer.spans("serving.decode_chunk"):
            assert set(span["args"]["rids"]) <= set(ids)
        assert any(s["args"]["rid"] in ids
                   for s in tracer.spans("serving.prefix_splice"))
        done = [e for e in tracer.events()
                if e["name"] == "serving.request_done"]
        assert sorted(e["args"]["rid"] for e in done) == sorted(ids)

    def test_no_retrace_with_observability_on(self, assert_no_retrace):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           tracer=Tracer())
        eng.submit(Request([1, 4, 7, 2], 5))
        eng.run()
        with assert_no_retrace(eng):
            eng.submit(Request([2, 5, 8, 1], 5))
            eng.run()


class _Gateway:
    """Context helper: engine + gateway + client for one test."""

    def __init__(self, **engine_kwargs):
        self.engine = DecodeEngine(_net(), **engine_kwargs)
        self.gw = ServingGateway(self.engine, keepalive_s=0.1)

    def __enter__(self):
        self.gw.start()
        self.client = GatewayClient(self.gw.address, timeout_s=60.0)
        return self

    def __exit__(self, *exc):
        self.gw.close()


class TestGatewayObservability:
    def test_request_trace_endpoint_lifecycle(self):
        with _Gateway(n_slots=2, decode_chunk=3, seed=0) as g:
            out = g.client.generate([1, 2, 3, 4, 5], 6)
            trace = g.client.trace(out["id"])
            assert trace["finish_reason"] == out["finish_reason"]
            assert trace["timing"]["ttft_s"] == out["ttft_s"]
            assert _phase_sum(trace["timing"]) <= \
                trace["timing"]["e2e_s"]
            assert out["timing"] == trace["timing"]
            with pytest.raises(GatewayError) as err:
                g.client.trace(99_999)
            assert err.value.status == 404
            with pytest.raises(GatewayError) as err:
                g.client._call("GET", "/v1/requests/nope/trace")
            assert err.value.status == 400

    def test_trace_endpoint_202_while_running(self):
        with _Gateway(n_slots=1, decode_chunk=2, seed=0) as g:
            s = g.client.stream([1, 4, 7, 2], 10_000)
            next(iter(s))  # at least one delta: the request is live
            assert g.client.trace(s.id).get("running") is True
            g.client.cancel(s.id)
            list(s)
            trace = g.client.trace(s.id)
            assert trace["finish_reason"] == "cancelled"

    def test_trace_export_is_chrome_trace_json(self):
        with _Gateway(n_slots=2, decode_chunk=3, seed=0) as g:
            g.client.generate([1, 2, 3], 5)
            doc = g.client.trace_events()
            events = doc["traceEvents"]
            assert events and all("ph" in e for e in events)
            decode = [e for e in events
                      if e["name"] == "serving.decode_chunk"]
            assert decode and all("rids" in e["args"]
                                  for e in decode)
            # the export round-trips as a loadable Chrome trace
            assert json.loads(json.dumps(doc)) == doc

    def test_metrics_scrape_never_evicts_span_history(self):
        """Satellite regression: 10k scrapes leave span events
        intact (the old per-scrape ``tracer.counter`` calls would
        have rolled the capped log over many times)."""
        with _Gateway(n_slots=2, decode_chunk=3, seed=0) as g:
            g.client.generate([1, 2, 3, 4], 5)
            spans_before = len(g.engine.tracer.spans())
            assert spans_before >= 1
            for _ in range(10_000):
                g.gw._metrics_text()
            assert len(g.engine.tracer.spans()) == spans_before
            # the gauges still export
            text = g.client.metrics()
            assert "serving_gateway_queue_depth" in text

    def test_metrics_exports_latency_histograms(self):
        with _Gateway(n_slots=2, decode_chunk=3, seed=0) as g:
            g.client.generate([1, 2, 3, 4], 6)
            text = g.client.metrics()
            hists = parse_prometheus_histograms(text)
            for name in ("serving_ttft_s", "serving_itl_s",
                         "serving_e2e_s"):
                fam = hists[name]
                cums = [c for _, c in fam["buckets"]]
                assert cums == sorted(cums)
                assert fam["buckets"][-1][1] == fam["count"] >= 1
            assert "# HELP serving_ttft_s" in text


class TestLatencyReport:
    def test_report_from_saved_chrome_trace(self, tmp_path, capsys):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           tracer=tracer)
        for p, n in zip(PROMPTS, LENS):
            eng.submit(Request(list(p), n))
        eng.run()
        path = str(tmp_path / "trace.json")
        tracer.save(path)
        rows = run_report(path)
        phases = {r["phase"] for r in rows}
        assert {"ttft", "e2e", "round", "queue_wait"} <= phases
        for row in rows:
            assert row["count"] >= 1
            assert row["p50_ms"] <= row["p99_ms"]
        from scripts.latency_report import main

        assert main([path]) == 0
        out = capsys.readouterr().out
        assert "ttft" in out and "p99" in out

    def test_report_from_live_gateway(self):
        with _Gateway(n_slots=2, decode_chunk=3, seed=0) as g:
            g.client.generate([1, 2, 3, 4], 6)
            rows = run_report(g.gw.address)
            by_phase = {r["phase"]: r for r in rows}
            assert by_phase["ttft"]["count"] >= 1
            assert by_phase["e2e"]["p50_ms"] > 0

    def test_report_events_mode_matches_timing(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=3, seed=0,
                           tracer=tracer)
        rid = eng.submit(Request([1, 4, 7, 2], 6))
        res = eng.run()
        rows = report_from_events(tracer.events())
        ttft = next(r for r in rows if r["phase"] == "ttft")
        assert ttft["p50_ms"] == pytest.approx(
            res[rid].ttft_s * 1e3)

    def test_report_from_metrics_text_plain_tracer(self):
        t = Tracer()
        for v in (0.01, 0.02, 0.04):
            t.observe("serving_ttft_s", v)
        rows = report_from_metrics_text(t.prometheus_text())
        assert rows and rows[0]["phase"] == "ttft"
        assert rows[0]["count"] == 3
