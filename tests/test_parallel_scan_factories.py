"""Pod-scale fused training (ParallelTrainer.fit_scan over the dp mesh)
and conf-driven iterator factory SPIs."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.scaleout.api import (
    CollectionJobIteratorFactory,
    DataSetIteratorFactory,
    DataSetJobIterator,
)


def _net(compute_dtype=None):
    b = NeuralNetConfiguration.Builder().seed(5).learning_rate(0.1)
    if compute_dtype:
        b = b.compute_dtype(compute_dtype)
    conf = (b.list()
            .layer(0, L.DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                    loss_function=LossFunction.MCXENT))
            .build())
    return MultiLayerNetwork(conf)


def _stacked(k=6, batch=32, seed=0):
    rng = np.random.default_rng(seed)
    cls = rng.integers(0, 3, k * batch)
    x = rng.normal(loc=cls[:, None] * 0.7,
                   size=(k * batch, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[cls]
    return (x.reshape(k, batch, 8), y.reshape(k, batch, 3), x, cls)


class TestParallelFitScan:
    def test_scanned_global_steps_converge(self):
        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        trainer = ParallelTrainer(_net("bfloat16"), mesh=mesh)
        feats, labels, x, cls = _stacked()
        first = None
        for _ in range(20):
            scores = trainer.fit_scan(feats, labels)
            if first is None:
                first = float(np.asarray(scores[0]))
        last = float(np.asarray(scores[-1]))
        assert last < first
        acc = (trainer.net.predict(x) == cls).mean()
        assert acc > 0.8
        assert trainer.net.iteration == 20 * feats.shape[0]

    def test_rejects_local_steps_mode(self):
        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        trainer = ParallelTrainer(_net(), mesh=mesh,
                                  average_each_iteration=False,
                                  local_steps=2)
        feats, labels, _, _ = _stacked(k=2)
        with pytest.raises(ValueError, match="local_steps"):
            trainer.fit_scan(feats, labels)


class _IrisLikeFactory(DataSetIteratorFactory):
    def create(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(12, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 12)]
        return ListDataSetIterator(
            [DataSet(x[i:i + 4], y[i:i + 4]) for i in range(0, 12, 4)])


class TestIteratorFactories:
    def test_collection_job_iterator_factory(self):
        it = CollectionJobIteratorFactory([1, 2, 3]).create()
        jobs = []
        while it.has_next():
            jobs.append(it.next("w0"))
        assert [j.work for j in jobs] == [1, 2, 3]
        it.reset()
        assert it.has_next()

    def test_dataset_job_iterator(self):
        ds_iter = _IrisLikeFactory().create()
        jobs = DataSetJobIterator(ds_iter)
        seen = 0
        while jobs.has_next():
            job = jobs.next("w1")
            assert job.work.features.shape == (4, 4)
            assert job.job_id == seen
            seen += 1
        assert seen == 3
        jobs.reset()
        assert jobs.has_next()
        assert jobs.next().job_id == 0

    def test_factory_from_conf(self):
        conf = {DataSetIteratorFactory.KEY:
                f"{__name__}._IrisLikeFactory"}
        factory = DataSetIteratorFactory.from_conf(conf)
        assert isinstance(factory, _IrisLikeFactory)
        it = factory.create()
        assert it.next().num_examples() == 4

    def test_factory_from_conf_rejects_wrong_type(self):
        conf = {DataSetIteratorFactory.KEY: "builtins.dict"}
        with pytest.raises(TypeError):
            DataSetIteratorFactory.from_conf(conf)


class TestGraphFitScan:
    def test_graph_scanned_steps(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.optimize.listeners import (
            BestScoreIterationListener,
        )

        conf = (
            NeuralNetConfiguration.Builder().seed(6).learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("h", L.DenseLayer(n_in=8, n_out=16,
                                         activation="tanh"), "in")
            .add_layer("out", L.OutputLayer(
                n_in=16, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT), "h")
            .set_outputs("out")
            .build()
        )
        graph = ComputationGraph(conf).init()
        best = BestScoreIterationListener()
        graph.listeners = [best]
        feats, labels, x, cls = _stacked(k=4, batch=32)
        first = None
        for _ in range(30):
            scores = graph.fit_scan(feats, labels)
            if first is None:
                first = float(np.asarray(scores[0]))
        arr = np.asarray(scores)
        assert arr.shape == (4,)
        assert graph.iteration == 120
        assert arr[-1] < first  # loss went down across the run
        pred = np.asarray(graph.output(x)[0]).argmax(1)
        assert (pred == cls).mean() > 0.8
        assert np.isfinite(best.best_score)
        assert best.best_iteration > 0

    def test_rejects_wrong_label_count(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (
            NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("o1", L.OutputLayer(
                n_in=8, n_out=2, activation="softmax",
                loss_function=LossFunction.MCXENT), "in")
            .add_layer("o2", L.OutputLayer(
                n_in=8, n_out=2, activation="softmax",
                loss_function=LossFunction.MCXENT), "in")
            .set_outputs("o1", "o2")
            .build()
        )
        graph = ComputationGraph(conf).init()
        feats = np.zeros((2, 4, 8), np.float32)
        one_label = np.zeros((2, 4, 2), np.float32)
        with pytest.raises(ValueError, match="label arrays"):
            graph.fit_scan(feats, one_label)


class TestAccumulateGradients:
    def _data(self):
        rng = np.random.default_rng(2)
        cls = rng.integers(0, 3, 64)
        x = rng.normal(loc=cls[:, None], size=(64, 8)).astype(np.float32)
        return DataSet(x, np.eye(3, dtype=np.float32)[cls])

    def test_accum_with_divide_equals_sync_mean(self):
        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        ds = self._data()
        t1 = ParallelTrainer(_net(), mesh=mesh)
        t2 = ParallelTrainer(_net(), mesh=mesh,
                             accumulate_gradients=True,
                             divide_gradient=True)
        t1.fit(ds)
        t2.fit(ds)
        np.testing.assert_allclose(
            np.asarray(t1.net.params_flat()),
            np.asarray(t2.net.params_flat()), rtol=1e-6)

    def test_accum_without_divide_takes_bigger_steps(self):
        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        n = mesh.shape["dp"]
        if n == 1:
            pytest.skip("needs >1 device to distinguish sum from mean")
        ds = self._data()
        mean_t = ParallelTrainer(_net(), mesh=mesh)
        sum_t = ParallelTrainer(_net(), mesh=mesh,
                                accumulate_gradients=True,
                                divide_gradient=False)
        p0 = np.asarray(mean_t.net.params_flat()).copy()
        mean_t.fit(ds)
        sum_t.fit(ds)
        d_mean = np.asarray(mean_t.net.params_flat()) - p0
        d_sum = np.asarray(sum_t.net.params_flat()) - p0
        # summed gradients move n times as far on the first (SGD) step
        np.testing.assert_allclose(d_sum, n * d_mean, rtol=1e-4, atol=1e-6)


class TestMultiHost:
    def test_single_process_noop_and_helpers(self):
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel.multihost import (
            global_to_host_local,
            host_local_to_global,
            initialize_multihost,
            sync_hosts,
        )

        assert initialize_multihost() == 0  # no pod env: no-op
        sync_hosts()  # no-op barrier
        mesh = make_mesh(MeshSpec({"dp": len(jax.devices())}))
        x = np.arange(len(jax.devices()) * 4, dtype=np.float32).reshape(
            len(jax.devices()), 4)
        g = host_local_to_global(x, mesh, P("dp"))
        assert g.shape == x.shape
        back = global_to_host_local(g, mesh, P("dp"))
        np.testing.assert_allclose(np.asarray(back), x)

    def test_context_with_control_plane(self):
        from deeplearning4j_tpu.parallel.multihost import MultiHostContext
        from deeplearning4j_tpu.scaleout.coordinator import (
            CoordinatorServer,
        )

        server = CoordinatorServer()
        server.start()
        try:
            ctx = MultiHostContext(
                coordinator_url=server.address, heartbeat_interval=0.05)
            assert ctx.is_chief()
            assert ctx.num_processes == 1
            import time

            with server.state.lock:
                t0 = server.state.workers["host-0"]
            time.sleep(0.2)  # a few heartbeats
            with server.state.lock:
                assert server.state.workers["host-0"] > t0  # beat advanced
            ctx.close()
            time.sleep(0.05)
            with server.state.lock:
                assert "host-0" not in server.state.workers  # deregistered
        finally:
            server.stop()

    def test_bootstrap_failure_propagates(self, monkeypatch):
        """A genuine jax.distributed failure (bad coordinator, timeout)
        must raise, not silently degrade into N single-process runs that
        all think they are chief."""
        import deeplearning4j_tpu.parallel.multihost as mh

        monkeypatch.setattr(mh, "_initialized", False)

        def boom(**kw):
            raise RuntimeError("barrier timed out connecting to coordinator")

        monkeypatch.setattr(mh.jax.distributed, "initialize", boom)
        with pytest.raises(RuntimeError, match="barrier timed out"):
            mh.initialize_multihost(coordinator_address="10.0.0.1:1234",
                                    num_processes=2, process_id=0)
        assert mh._initialized is False

    def test_bootstrap_already_initialized_is_benign(self, monkeypatch):
        import deeplearning4j_tpu.parallel.multihost as mh

        monkeypatch.setattr(mh, "_initialized", False)

        def already(**kw):
            # the message current JAX actually raises on double-init
            # (jax/_src/distributed.py)
            raise RuntimeError(
                "distributed.initialize should only be called once.")

        monkeypatch.setattr(mh.jax.distributed, "initialize", already)
        assert mh.initialize_multihost(
            coordinator_address="10.0.0.1:1234",
            num_processes=1, process_id=0) == 0
        assert mh._initialized is True
        monkeypatch.setattr(mh, "_initialized", False)


class TestGraphParallelTrainer:
    """ParallelTrainer over a ComputationGraph: dp-sharded synchronous
    steps must match single-device graph training exactly."""

    def _graph_conf(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.graph_conf import MergeVertex
        from deeplearning4j_tpu.ops.losses import LossFunction

        return (
            NeuralNetConfiguration.Builder()
            .seed(42)
            .learning_rate(0.1)
            .graph_builder()
            .add_inputs("a", "b")
            .add_layer("da", L.DenseLayer(n_in=4, n_out=6,
                                          activation="relu"), "a")
            .add_layer("db", L.DenseLayer(n_in=3, n_out=6,
                                          activation="relu"), "b")
            .add_vertex("m", MergeVertex(), "da", "db")
            .add_layer(
                "out",
                L.OutputLayer(n_in=12, n_out=3, activation="softmax",
                              loss_function=LossFunction.MCXENT),
                "m",
            )
            .set_outputs("out")
            .build()
        )

    def test_multi_input_graph_matches_single_device(self):
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        rng = np.random.default_rng(0)
        xa = rng.normal(size=(16, 4)).astype(np.float32)
        xb = rng.normal(size=(16, 3)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        mds = MultiDataSet([xa, xb], [y])

        g_ref = ComputationGraph(self._graph_conf()).init()
        g_dp = ComputationGraph(self._graph_conf()).init()
        mesh = make_mesh(MeshSpec({"dp": 4}))
        trainer = ParallelTrainer(g_dp, mesh)
        for _ in range(4):
            g_ref.fit(mds)
            trainer.fit(mds)
        np.testing.assert_allclose(
            float(g_dp.score_value), float(g_ref.score_value), rtol=1e-5)
        for name in g_ref.params:
            for k in g_ref.params[name]:
                np.testing.assert_allclose(
                    np.asarray(g_dp.params[name][k]),
                    np.asarray(g_ref.params[name][k]),
                    rtol=1e-4, atol=1e-6,
                )

    def test_graph_fit_scan_sharded(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        rng = np.random.default_rng(1)
        K, B = 6, 16
        xa = rng.normal(size=(K, B, 4)).astype(np.float32)
        xb = rng.normal(size=(K, B, 3)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (K, B))]

        g_dp = ComputationGraph(self._graph_conf()).init()
        mesh = make_mesh(MeshSpec({"dp": 4}))
        trainer = ParallelTrainer(g_dp, mesh)
        scores = trainer.fit_scan({"a": xa, "b": xb}, [y])
        s = np.asarray(scores)
        assert s.shape == (K,) and np.all(np.isfinite(s))
        assert s[-1] < s[0]

    def test_graph_rejects_tp_but_supports_local_steps(self):
        import pytest

        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        # tp needs the sequential Megatron alternation — still MLN-only.
        mesh = make_mesh(MeshSpec({"dp": 2, "tp": 2}))
        g = ComputationGraph(self._graph_conf())
        with pytest.raises(ValueError, match="sequential layer chain"):
            ParallelTrainer(g, mesh, tp_axis="tp")
        # K-local-steps-then-average works for graphs now (round-2
        # review item 2); trajectory parity is asserted in
        # test_pipeline_expert.py::TestGraphLocalSteps.
        g2 = ComputationGraph(self._graph_conf())
        mesh2 = make_mesh(MeshSpec({"dp": 4}))
        ParallelTrainer(g2, mesh2, average_each_iteration=False,
                        local_steps=2)


class TestMaskedParallelFitScan:
    def test_masked_batches_over_dp_mesh(self):
        from deeplearning4j_tpu.models.zoo import lstm_classifier
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        rng = np.random.default_rng(0)
        k, b, t = 3, 8, 6
        feats = rng.normal(size=(k, b, 5, t)).astype(np.float32)
        labels = np.zeros((k, b, 3, t), np.float32)
        idx = rng.integers(0, 3, (k, b, t))
        for i in range(k):
            for j in range(b):
                labels[i, j, idx[i, j], np.arange(t)] = 1.0
        lens = rng.integers(2, t + 1, (k, b))
        fm = (np.arange(t)[None, None, :] < lens[:, :, None]).astype(
            np.float32)

        net = MultiLayerNetwork(lstm_classifier(
            n_in=5, n_hidden=8, n_classes=3, lr=0.05))
        trainer = ParallelTrainer(net, make_mesh(MeshSpec({"dp": 4})))
        scores = trainer.fit_scan(feats, labels,
                                  features_mask_stacked=fm,
                                  labels_mask_stacked=fm)
        s = np.asarray(scores)
        assert s.shape == (k,) and np.all(np.isfinite(s))


class TestMaskedGraphFitScan:
    """Masked time-series ComputationGraph batches through the fused
    scan path: parity with per-step masked graph fit()."""

    def _graph(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (
            NeuralNetConfiguration.Builder().seed(11).learning_rate(0.05)
            .graph_builder()
            .add_inputs("in")
            .add_layer("lstm", L.GravesLSTM(n_in=5, n_out=8,
                                            activation="tanh"), "in")
            .add_layer("out", L.RnnOutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT), "lstm")
            .set_outputs("out")
            .build()
        )
        return ComputationGraph(conf).init()

    def test_matches_per_step_masked_fit(self):
        rng = np.random.default_rng(4)
        k, b, t = 3, 6, 7
        feats = rng.normal(size=(k, b, 5, t)).astype(np.float32)
        labels = np.zeros((k, b, 3, t), np.float32)
        idx = rng.integers(0, 3, (k, b, t))
        for i in range(k):
            for j in range(b):
                labels[i, j, idx[i, j], np.arange(t)] = 1.0
        lens = rng.integers(3, t + 1, (k, b))
        fm = (np.arange(t)[None, None, :] < lens[:, :, None]).astype(
            np.float32)

        g_step, g_scan = self._graph(), self._graph()
        for i in range(k):
            g_step.fit(DataSet(feats[i], labels[i],
                               features_mask=fm[i], labels_mask=fm[i]))
        scores = g_scan.fit_scan(
            feats, labels, masks_stacked=fm, label_masks_stacked=fm)
        assert np.all(np.isfinite(np.asarray(scores)))
        for name in g_step.params:
            for p in g_step.params[name]:
                np.testing.assert_allclose(
                    np.asarray(g_scan.params[name][p]),
                    np.asarray(g_step.params[name][p]),
                    rtol=1e-5, atol=1e-6,
                )

    def test_masked_graph_scan_over_dp_mesh(self):
        from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer

        rng = np.random.default_rng(5)
        k, b, t = 2, 8, 5
        feats = rng.normal(size=(k, b, 5, t)).astype(np.float32)
        labels = np.zeros((k, b, 3, t), np.float32)
        idx = rng.integers(0, 3, (k, b, t))
        for i in range(k):
            for j in range(b):
                labels[i, j, idx[i, j], np.arange(t)] = 1.0
        fm = np.ones((k, b, t), np.float32)

        g = self._graph()
        trainer = ParallelTrainer(g, make_mesh(MeshSpec({"dp": 4})))
        scores = trainer.fit_scan(
            {"in": feats}, [labels],
            features_mask_stacked={"in": fm},
            labels_mask_stacked={"out": fm})
        s = np.asarray(scores)
        assert s.shape == (k,) and np.all(np.isfinite(s))

    def test_single_mask_presence_and_bad_keys(self):
        import pytest as _pytest

        rng = np.random.default_rng(6)
        k, b, t = 2, 4, 5
        feats = rng.normal(size=(k, b, 5, t)).astype(np.float32)
        labels = np.zeros((k, b, 3, t), np.float32)
        idx = rng.integers(0, 3, (k, b, t))
        for i in range(k):
            for j in range(b):
                labels[i, j, idx[i, j], np.arange(t)] = 1.0
        fm = np.ones((k, b, t), np.float32)

        g = self._graph()
        s1 = g.fit_scan(feats, labels, label_masks_stacked={"out": fm})
        assert np.all(np.isfinite(np.asarray(s1)))
        s2 = g.fit_scan(feats, labels, masks_stacked={"in": fm})
        assert np.all(np.isfinite(np.asarray(s2)))
        # mistyped keys must raise, not silently train unmasked
        with _pytest.raises(ValueError, match="not network inputs"):
            g.fit_scan(feats, labels, masks_stacked={"input": fm})
        with _pytest.raises(ValueError, match="not network outputs"):
            g.fit_scan(feats, labels, label_masks_stacked={"o": fm})


class TestAttentionTensorParallel:
    """Megatron head-sharded attention: tp_param_specs lays Wq/Wk/Wv out
    column-parallel (whole heads per device) and Wo row-parallel; GSPMD
    inserts the post-projection all-reduce. Numerics must match the
    replicated net."""

    def _net(self, seed=5):
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        return MultiLayerNetwork(transformer_lm(
            n_in=8, width=16, n_layers=2, n_heads=4, n_classes=8,
            lr=1e-2, seed=seed)).init()

    def _batch(self, seed=0, n=4, c=8, t=12, k=8):
        from tests.helpers import lm_batch

        return lm_batch(np.random.default_rng(seed), n, c, t, k)

    def test_dp_tp_transformer_matches_single_device(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        x, y = self._batch()
        ref = self._net()
        tp_net = self._net()
        mesh = make_mesh(MeshSpec({"dp": 2, "tp": 4}))
        trainer = ParallelTrainer(tp_net, mesh, tp_axis="tp")

        # attention QKV actually sharded over heads, Wo over rows
        wq = tp_net.params["0"]["Wq"]
        assert "tp" in tuple(wq.sharding.spec), "Wq not head-sharded"
        assert tuple(tp_net.params["0"]["Wo"].sharding.spec)[0] == "tp"

        for _ in range(3):
            ref.fit(DataSet(x, y))
            s_tp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(
            s_tp, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(tp_net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"param {si}/{name} diverged under dp x tp",
                )

    def test_tp_rejects_indivisible_heads_and_ring(self):
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec({"tp": 8}))
        bad = MultiLayerNetwork(transformer_lm(
            n_in=8, width=24, n_layers=1, n_heads=3, n_classes=8))
        with pytest.raises(ValueError, match="n_heads"):
            ParallelTrainer(bad, mesh, tp_axis="tp")
        ringy = MultiLayerNetwork(transformer_lm(
            n_in=8, width=16, n_layers=1, n_heads=8, n_classes=8,
            ring_axis="tp"))
        with pytest.raises(ValueError, match="sp_axis"):
            ParallelTrainer(ringy, mesh, tp_axis="tp")

    def test_dp_tp_fsdp_three_axis_composition(self):
        """dp x tp x fsdp on one mesh: attention heads shard over tp,
        fsdp overlays ZeRO-3 sharding on the leaves tp left replicated
        (biases, output W), the batch shards over dp x fsdp — exact
        single-device trajectory."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        x, y = self._batch()
        ref = self._net()
        net3 = self._net()
        mesh = make_mesh(MeshSpec({"dp": 2, "tp": 2, "fsdp": 2}))
        trainer = ParallelTrainer(
            net3, mesh, tp_axis="tp", fsdp_axis="fsdp")
        assert "tp" in tuple(net3.params["0"]["Wq"].sharding.spec)
        assert "fsdp" in tuple(net3.params["2"]["W"].sharding.spec)
        for _ in range(3):
            ref.fit(DataSet(x, y))
            s3 = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s3, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(net3.params[si][name]), np.asarray(p),
                    atol=3e-4,
                    err_msg=f"param {si}/{name} diverged under 3-axis",
                )
