"""Training-telemetry invariants (ISSUE 8): the tracing listener must
be exact (bit-identical params/scores, equal compile counts, zero
retrace), structurally honest (phase sums <= wall), and actually
populated (histograms, spans, JSONL, endpoints) across the per-step,
fused-scan, tBPTT, solver, and parallel-trainer paths."""

import json
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener,
    IterationListener,
    TracingIterationListener,
    fire_crossed,
)
from deeplearning4j_tpu.optimize.telemetry import (
    TRAIN_HISTOGRAMS,
    MetricsLog,
    TrainTelemetry,
    window_counts,
)
from deeplearning4j_tpu.profiler.tracer import Tracer


def _mlp(seed=42, algo=None):
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(0.1)
        .updater(Updater.SGD)
    )
    if algo is not None:
        b = b.optimization_algo(algo)
    conf = (
        b.list()
        .layer(0, L.DenseLayer(n_in=4, n_out=16, activation="relu"))
        .layer(1, L.OutputLayer(
            n_in=16, n_out=3, activation="softmax",
            loss_function=LossFunction.MCXENT))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _batch(n=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


class _CountingListener(IterationListener):
    def __init__(self, every=1):
        self.invoked_every = every
        self.calls = []

    def iteration_done(self, model, iteration):
        self.calls.append(iteration)


# ----------------------------------------------------------------------
# Satellite: fire_crossed cadence edge cases
# ----------------------------------------------------------------------
class TestFireCrossedCadence:
    def test_invoked_every_zero_means_every_call(self):
        lst = _CountingListener(every=0)
        fire_crossed([lst], None, 0, 1)
        fire_crossed([lst], None, 1, 5)
        assert lst.calls == [1, 5]

    def test_negative_invoked_every_means_every_call(self):
        lst = _CountingListener(every=-3)
        fire_crossed([lst], None, 2, 3)
        assert lst.calls == [3]

    def test_empty_window_never_fires(self):
        lst = _CountingListener(every=1)
        fire_crossed([lst], None, 7, 7)
        lst0 = _CountingListener(every=0)
        fire_crossed([lst0], None, 0, 0)
        assert lst.calls == [] and lst0.calls == []

    def test_window_crossing_multiple_multiples_fires_once(self):
        lst = _CountingListener(every=3)
        fire_crossed([lst], None, 0, 10)  # crosses 3, 6, 9
        assert lst.calls == [10]

    def test_window_not_crossing_does_not_fire(self):
        lst = _CountingListener(every=10)
        fire_crossed([lst], None, 11, 19)
        assert lst.calls == []
        fire_crossed([lst], None, 19, 20)  # crosses 20
        assert lst.calls == [20]

    def test_boundary_exact_multiple(self):
        # end landing exactly ON a multiple fires; start ON a multiple
        # does not re-fire for the same multiple.
        lst = _CountingListener(every=4)
        fire_crossed([lst], None, 0, 4)
        fire_crossed([lst], None, 4, 7)
        assert lst.calls == [4]

    def test_matches_per_step_cadence_over_many_windows(self):
        # Windows of ragged sizes produce the same number of fires a
        # per-step loop at the same cadence would coalesce to.
        lst = _CountingListener(every=5)
        edges = [0, 3, 5, 9, 15, 16, 25]
        for a, b in zip(edges, edges[1:]):
            fire_crossed([lst], None, a, b)
        # crossings of 5/10+15/20+25 coalesce per call: windows
        # (3,5], (9,15], (16,25] each fire once
        assert lst.calls == [5, 15, 25]


# ----------------------------------------------------------------------
# Tentpole: exactness invariants
# ----------------------------------------------------------------------
class TestTelemetryExactness:
    def test_bit_identical_params_and_scores_with_listener(self,
                                                           tmp_path):
        ds = _batch()
        dark = _mlp()
        observed = _mlp()
        log = MetricsLog(str(tmp_path / "m.jsonl"))
        collect = CollectScoresIterationListener()
        observed.set_listeners(
            TracingIterationListener(tracer=Tracer(), metrics_log=log),
            collect)
        dark_collect = CollectScoresIterationListener()
        dark.set_listeners(dark_collect)
        for _ in range(4):
            dark.fit(ds)
            observed.fit(ds)
        log.close()
        # per-step loss trajectory identical
        assert [s for _, s in dark_collect.scores] == \
            [s for _, s in collect.scores]
        for a, b in zip(jax.tree.leaves(dark.params),
                        jax.tree.leaves(observed.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_equal_compile_counts_on_off(self):
        ds = _batch()
        dark = _mlp()
        observed = _mlp()
        observed.set_listeners(TracingIterationListener(Tracer()))
        dark.fit(ds)
        observed.fit(ds)
        assert (dark._train_step._cache_size()
                == observed._train_step._cache_size() == 1)

    def test_expert_counters_ride_the_same_step_on_and_off(self, tmp_path):
        """An expert net's step returns what its blocks counted
        (``moe_*``) beside the health scalars: with the listener on
        they reach the record, the ``train.dispatch`` span and the
        ``train_<name>`` counters; on or off, one executable and the
        same parameters (the invariant ``_step_body`` states)."""
        from deeplearning4j_tpu.models.zoo import lfm2_moe_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        def net_of():
            return MultiLayerNetwork(lfm2_moe_lm(
                vocab_size=32, hidden_size=32, layers=[1, 2, 3],
                experts_held=(2, 6), warmup_steps=1, remat=True)).init()

        toks = np.random.default_rng(0).integers(0, 32, (3, 2, 13))
        dark, observed = net_of(), net_of()
        tracer = Tracer()
        path = str(tmp_path / "moe.jsonl")
        with MetricsLog(path) as log:
            observed.set_listeners(TracingIterationListener(
                tracer=tracer, metrics_log=log))
            for net in (dark, observed):
                for _ in range(2):
                    net.fit_scan(toks[:, :, :-1], toks[:, :, 1:])
        assert (dark._train_steps_scan._cache_size()
                == observed._train_steps_scan._cache_size() == 1)
        for a, b in zip(jax.tree.leaves(dark.params),
                        jax.tree.leaves(observed.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        names = ("moe_picks", "moe_picks_held", "moe_experts_touched",
                 "moe_load_max", "moe_layer_steps", "moe_pair_rows_worked")
        records = MetricsLog.read(path)
        assert len(records) == 2
        for rec in records:
            # 3 steps x 3 expert layers; 2 x 12 tokens x top 2 a layer
            assert rec["moe_layer_steps"] == 9
            assert rec["moe_picks"] == 9 * 2 * 12 * 2
            assert 0 < rec["moe_picks_held"] < rec["moe_picks"]
            assert 0 < rec["moe_experts_touched"] <= 9 * 4
            # the training passes' slabs reach as far as the held pairs
            # (one slab a layer at this size: all its 48 pairs' rows)
            assert (rec["moe_picks_held"] <= rec["moe_pair_rows_worked"]
                    <= rec["moe_picks"])
        spans = [e for e in tracer.events()
                 if e["ph"] == "X" and e["name"] == "train.dispatch"]
        assert len(spans) == 2
        assert all(set(names) <= set(e["args"]) for e in spans)
        assert tracer.latest_counters()["train_moe_picks"] == 2 * 432
        assert (tracer.latest_counters()["train_moe_pair_rows_worked"]
                == sum(rec["moe_pair_rows_worked"] for rec in records))
        # the dark net carries the same counts, unfetched
        assert set(names) <= set(dark.train_telemetry.health)

    def test_no_retrace_with_telemetry_on(self, assert_no_retrace):
        ds = _batch()
        net = _mlp()
        net.set_listeners(TracingIterationListener(Tracer()))
        net.fit(ds)  # warm
        k_feats = np.stack([np.asarray(ds.features)] * 4)
        k_labels = np.stack([np.asarray(ds.labels)] * 4)
        net.fit_scan(k_feats, k_labels)  # warm the scan executable
        with assert_no_retrace(net._train_step,
                               net._train_steps_scan):
            net.fit(ds)
            net.fit_scan(k_feats, k_labels)

    def test_phase_sums_le_wall(self, tmp_path):
        path = str(tmp_path / "phases.jsonl")
        net = _mlp()
        with MetricsLog(path) as log:
            net.set_listeners(
                TracingIterationListener(metrics_log=log))
            for i in range(3):
                net.fit(_batch(seed=i))
        records = MetricsLog.read(path)
        assert len(records) == 3
        for rec in records:
            assert (rec["data_wait_s"] + rec["dispatch_s"]
                    + rec["sync_s"]) <= rec["wall_s"] + 1e-9


# ----------------------------------------------------------------------
# Histograms, spans, JSONL
# ----------------------------------------------------------------------
class TestInstruments:
    def test_histograms_populated_on_three_step_fit(self):
        net = _mlp()
        lst = TracingIterationListener(Tracer())
        net.set_listeners(lst)
        for i in range(3):
            net.fit(_batch(seed=i))
        for name in TRAIN_HISTOGRAMS:
            assert lst.hists[name].count == 3, name
        assert lst.hists["train_sync_s"].count == 3
        assert np.isfinite(lst.quantile("train_step_s", 0.5))

    def test_scan_window_observes_k_per_step_samples(self):
        net = _mlp()
        tracer = Tracer()
        lst = TracingIterationListener(tracer)
        net.set_listeners(lst)
        K = 5
        ds = _batch(seed=3)
        net.fit_scan(np.stack([np.asarray(ds.features)] * K),
                     np.stack([np.asarray(ds.labels)] * K))
        # one fire, K per-step samples in the step + health histograms
        assert lst.hists["train_step_s"].count == K
        assert lst.hists["train_grad_norm"].count == K
        assert lst.hists["train_sync_s"].count == 1
        spans = {e["name"] for e in tracer.events() if e["ph"] == "X"}
        assert {"train.step", "train.data_wait", "train.dispatch",
                "train.sync"} <= spans
        step = tracer.spans("train.step")[0]
        assert step["args"]["steps"] == K
        assert step["args"]["data_wait_s"] + \
            step["args"]["dispatch_s"] + step["args"]["sync_s"] \
            <= step["dur"] * 1e-6 + 1e-9

    def test_iterator_fit_records_data_wait(self):
        net = _mlp()
        lst = TracingIterationListener(frequency=100)  # never fires
        net.set_listeners(lst)
        net.fit(ListDataSetIterator([_batch(seed=i)
                                     for i in range(4)]))
        # the window holds 4 steps and a measured iterator wait
        snap = net.train_telemetry.consume()
        assert snap["steps"] == 4
        assert snap["data_wait_s"] > 0.0
        assert snap["examples"] == 32

    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "log.jsonl")
        with MetricsLog(path) as log:
            log.write({"iteration": 1, "score": 0.5})
            log.write({"iteration": 2, "score": 0.25,
                       "grad_norm": 1.25})
        records = MetricsLog.read(path)
        assert records == [
            {"iteration": 1, "score": 0.5},
            {"iteration": 2, "score": 0.25, "grad_norm": 1.25}]
        with pytest.raises(ValueError):  # closed sink rejects writes
            log.write({"iteration": 3})

    def test_tracer_counters_and_prometheus(self):
        net = _mlp()
        tracer = Tracer()
        net.set_listeners(TracingIterationListener(tracer))
        for i in range(2):
            net.fit(_batch(seed=i))
        latest = tracer.latest_counters()
        assert latest["train_steps_total"] == 2
        assert latest["train_examples_per_sec"] > 0
        text = tracer.prometheus_text(prefix="train_")
        assert "# TYPE train_step_s histogram" in text
        assert "train_step_s_bucket" in text
        assert "# TYPE train_steps_total counter" in text
        assert "# HELP train_grad_norm" in text


# ----------------------------------------------------------------------
# Other fit paths: tBPTT, solver, ComputationGraph
# ----------------------------------------------------------------------
class TestOtherPaths:
    def test_tbptt_health(self):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(1)
            .learning_rate(0.05)
            .list()
            .layer(0, L.GravesLSTM(n_in=3, n_out=8))
            .layer(1, L.RnnOutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(4)
            .t_bptt_backward_length(4)
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        lst = TracingIterationListener(Tracer())
        net.set_listeners(lst)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 3, 8)).astype(np.float32)
        y = np.abs(rng.normal(size=(2, 3, 8))).astype(np.float32)
        y = y / y.sum(axis=1, keepdims=True)
        net.fit(DataSet(x, y))
        assert lst.hists["train_grad_norm"].count == 2  # 2 windows
        assert lst.hists["train_step_s"].count == 2

    def test_solver_path_telemetry(self):
        from deeplearning4j_tpu.nn.conf.enums import (
            OptimizationAlgorithm,
        )

        net = _mlp(algo=OptimizationAlgorithm.LBFGS)
        lst = TracingIterationListener(Tracer())
        net.set_listeners(lst)
        net.fit(_batch())
        assert lst.hists["train_step_s"].count >= 1
        assert lst.hists["train_grad_norm"].count >= 1
        assert lst.hists["train_update_ratio"].count >= 1

    def test_graph_fit_and_scan_health(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(5)
            .learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", L.DenseLayer(n_in=4, n_out=8,
                                         activation="relu"), "in")
            .add_layer("out", L.OutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT), "d")
            .set_outputs("out")
            .build()
        )
        net = ComputationGraph(conf).init()
        lst = TracingIterationListener(Tracer())
        net.set_listeners(lst)
        ds = _batch()
        net.fit(ds)
        assert lst.hists["train_grad_norm"].count == 1
        K = 3
        net.fit_scan(np.stack([np.asarray(ds.features)] * K),
                     np.stack([np.asarray(ds.labels)] * K))
        assert lst.hists["train_grad_norm"].count == 1 + K


# ----------------------------------------------------------------------
# Parallel trainers: spans + mesh annotations
# ----------------------------------------------------------------------
class TestParallelSpans:
    def test_parallel_trainer_step_spans_carry_mesh(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        tracer = Tracer()
        net = _mlp()
        trainer = ParallelTrainer(net, mesh, tracer=tracer)
        ds = _batch(n=16)
        trainer.fit(ds)
        spans = tracer.spans("train.parallel_step")
        assert len(spans) == 1
        args = spans[0]["args"]
        assert args["trainer"] == "data"
        assert args["mesh"] == {"dp": len(jax.devices())}
        assert args["dp"] == "dp"
        assert args["devices"] == len(jax.devices())
        # health landed in the net's telemetry too
        snap = net.train_telemetry.consume()
        assert snap["steps"] == 1 and snap["health"] is not None

    def test_parallel_trainer_fit_scan_span(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        tracer = Tracer()
        net = _mlp()
        trainer = ParallelTrainer(net, mesh, tracer=tracer)
        ds = _batch(n=16)
        K = 3
        trainer.fit_scan(np.stack([np.asarray(ds.features)] * K),
                         np.stack([np.asarray(ds.labels)] * K))
        spans = tracer.spans("train.parallel_step")
        assert len(spans) == 1
        assert spans[0]["args"]["steps"] == K
        assert spans[0]["args"]["fused"] == "scan"

    def test_pipeline_trainer_step_spans(self):
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
        from deeplearning4j_tpu.parallel.pipeline_parallel import (
            PipelineTrainer,
        )

        mesh = make_mesh(MeshSpec({"pp": 2}),
                         devices=jax.devices()[:2])
        tracer = Tracer()
        net = _mlp()
        trainer = PipelineTrainer(net, mesh, n_microbatches=2,
                                  tracer=tracer)
        trainer.fit(_batch(n=8))
        spans = tracer.spans("train.parallel_step")
        assert len(spans) == 1
        args = spans[0]["args"]
        assert args["trainer"] == "pipeline"
        assert args["mesh"] == {"pp": 2}
        assert args["n_microbatches"] == 2


# ----------------------------------------------------------------------
# UiServer endpoints + latency report
# ----------------------------------------------------------------------
class TestEndpointsAndReport:
    def _trained_tracer(self, steps=3):
        tracer = Tracer()
        net = _mlp()
        net.set_listeners(TracingIterationListener(tracer))
        for i in range(steps):
            net.fit(_batch(seed=i))
        return tracer

    def test_ui_server_train_metrics_and_trace(self):
        from deeplearning4j_tpu.ui.server import UiClient, UiServer

        tracer = self._trained_tracer()
        server = UiServer(tracer=tracer).start()
        try:
            client = UiClient(server.address)
            text = client.get_train_metrics()
            assert "train_step_s_bucket" in text
            assert "# TYPE train_steps_total counter" in text
            doc = client.get_train_trace()
            names = {e["name"] for e in doc["traceEvents"]}
            assert "train.step" in names
        finally:
            server.stop()

    def test_ui_server_404_without_tracer(self):
        from deeplearning4j_tpu.ui.server import UiServer

        server = UiServer().start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    server.address + "/train/metrics")
            assert exc.value.code == 404
        finally:
            server.stop()

    def test_latency_report_from_saved_training_trace(self, tmp_path):
        from scripts.latency_report import main, run_report

        tracer = self._trained_tracer()
        path = str(tmp_path / "train_trace.json")
        tracer.save(path)
        rows = run_report(path)
        phases = {r["phase"] for r in rows}
        assert {"step", "data_wait", "sync"} <= phases
        step_row = next(r for r in rows if r["phase"] == "step")
        assert step_row["count"] == 3
        assert step_row["p50_ms"] >= 0
        # --json mode parses
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert main([path, "--json"]) == 0
        parsed = json.loads(buf.getvalue())
        assert {r["phase"] for r in parsed} == phases

    def test_latency_report_live_train_metrics_url(self):
        from deeplearning4j_tpu.ui.server import UiServer
        from scripts.latency_report import run_report

        tracer = self._trained_tracer()
        server = UiServer(tracer=tracer).start()
        try:
            # full endpoint URL: scraped as-is
            rows = run_report(server.address + "/train/metrics")
            assert {"step", "data_wait", "sync"} <= {
                r["phase"] for r in rows}
            # base URL: probed (/v1/metrics 404s, /train/metrics wins)
            rows2 = run_report(server.address)
            assert {r["phase"] for r in rows2} == {
                r["phase"] for r in rows}
        finally:
            server.stop()


# ----------------------------------------------------------------------
# Early stopping through the tracer
# ----------------------------------------------------------------------
class TestEarlyStoppingTrace:
    def test_termination_lands_in_trace(self):
        from deeplearning4j_tpu.earlystopping import (
            EarlyStoppingConfiguration,
            EarlyStoppingTrainer,
            InMemoryModelSaver,
            MaxEpochsTerminationCondition,
        )

        tracer = Tracer()
        conf = (
            EarlyStoppingConfiguration.Builder()
            .epoch_termination_conditions(
                MaxEpochsTerminationCondition(3))
            .model_saver(InMemoryModelSaver())
            .build()
        )
        it = ListDataSetIterator([_batch(seed=i) for i in range(2)])
        result = EarlyStoppingTrainer(conf, _mlp(), it,
                                      tracer=tracer).fit()
        assert result.total_epochs == 3
        assert tracer.latest_counters()["train_early_stop"] == 1
        epochs = tracer.spans("train.epoch")
        assert len(epochs) == 3
        assert [e["args"]["epoch"] for e in epochs] == [0, 1, 2]
        assert epochs[-1]["args"]["terminated"] is True
        instants = [e for e in tracer.events()
                    if e["ph"] == "i"
                    and e["name"] == "train.early_stop"]
        assert len(instants) == 1
        assert "MaxEpochsTerminationCondition" in \
            instants[0]["args"]["details"]


# ----------------------------------------------------------------------
# telemetry unit behavior
# ----------------------------------------------------------------------
class TestTelemetryUnits:
    def test_consume_empty_window_returns_none(self):
        tel = TrainTelemetry()
        tel.add_data_wait(0.5)
        assert tel.consume() is None  # no steps -> no sample
        tel.record_step(dispatch_s=0.1, examples=4)
        snap = tel.consume()
        assert snap["steps"] == 1 and snap["examples"] == 4
        # the empty drain left the window untouched: the accrued wait
        # belongs to the window that finally carried a step
        assert snap["data_wait_s"] == 0.5
        assert tel.consume() is None

    def test_window_counts(self):
        assert window_counts((4, 8, 3, 10)) == (4, 32, 320)
        assert window_counts((2, 16, 784)) == (2, 32, 32)
        # stacked conv images are NOT token streams
        assert window_counts((2, 16, 1, 28, 28)) == (2, 32, 32)

    def test_batch_counts_conv_images_are_not_tokens(self):
        from deeplearning4j_tpu.optimize.telemetry import batch_counts

        class Shaped:
            def __init__(self, shape):
                self.shape = shape

        assert batch_counts(Shaped((128, 784))) == (128, 128)
        assert batch_counts(Shaped((8, 3, 20))) == (8, 160)  # [B,C,T]
        assert batch_counts(Shaped((128, 1, 28, 28))) == (128, 128)

    def test_first_window_wall_anchors_at_first_event(self):
        import time as _time

        tel = TrainTelemetry()
        _time.sleep(0.15)  # idle between construction and training
        tel.record_step(dispatch_s=0.01)
        snap = tel.consume()
        # wall spans the first measured event, not the idle gap
        assert snap["wall_s"] < 0.1
        assert snap["dispatch_s"] <= snap["wall_s"] + 1e-9
