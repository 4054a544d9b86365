"""ComputationGraph tests: DAG config, vertices, multi-output training.

Pattern from reference nn/graph/{TestComputationGraphNetwork,
TestCompGraphMulti}.java and ComputationGraphConfigurationTest
(SURVEY.md §4).
"""

import numpy as np
import pytest
from jax import enable_x64

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iris import iris_dataset
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    ElementWiseOp,
    ElementWiseVertex,
    MergeVertex,
    SubsetVertex,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.losses import LossFunction


def _simple_graph_conf():
    return (
        NeuralNetConfiguration.Builder()
        .seed(42)
        .learning_rate(0.1)
        .graph_builder()
        .add_inputs("in")
        .add_layer("dense", L.DenseLayer(n_in=4, n_out=8, activation="relu"), "in")
        .add_layer(
            "out",
            L.OutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT,
            ),
            "dense",
        )
        .set_outputs("out")
        .build()
    )


class TestGraphConfig:
    def test_topological_order(self):
        conf = _simple_graph_conf()
        order = conf.topological_order()
        assert order.index("dense") < order.index("out")

    def test_json_round_trip(self):
        conf = _simple_graph_conf()
        back = ComputationGraphConfiguration.from_json(conf.to_json())
        assert back.to_json() == conf.to_json()
        assert isinstance(back.vertices["dense"].conf.layer, L.DenseLayer)

    def test_cycle_detection(self):
        conf = _simple_graph_conf()
        conf.vertex_inputs["dense"] = ["out"]
        with pytest.raises(ValueError, match="cycle"):
            conf.topological_order()

    def test_unknown_input_rejected(self):
        builder = (
            NeuralNetConfiguration.Builder()
            .graph_builder()
            .add_inputs("in")
            .add_layer("out", L.OutputLayer(n_in=4, n_out=2), "nope")
            .set_outputs("out")
        )
        with pytest.raises(ValueError):
            builder.build()


class TestGraphTraining:
    def test_equivalent_to_mlp_on_iris(self):
        graph = ComputationGraph(_simple_graph_conf()).init()
        ds = iris_dataset()
        ds.normalize_zero_mean_unit_variance()
        first = graph.score(ds)
        for _ in range(40):
            graph.fit(ds)
        assert graph.score(ds) < first * 0.7
        out = graph.output(ds.features)[0]
        assert out.shape == (150, 3)

    def test_merge_vertex_multi_input(self):
        conf = (
            NeuralNetConfiguration.Builder()
            .seed(1)
            .graph_builder()
            .add_inputs("in1", "in2")
            .add_layer("d1", L.DenseLayer(n_in=3, n_out=4, activation="tanh"), "in1")
            .add_layer("d2", L.DenseLayer(n_in=2, n_out=4, activation="tanh"), "in2")
            .add_vertex("merge", MergeVertex(), "d1", "d2")
            .add_layer(
                "out",
                L.OutputLayer(n_in=8, n_out=2, activation="softmax"),
                "merge",
            )
            .set_outputs("out")
            .build()
        )
        graph = ComputationGraph(conf).init()
        x1 = np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32)
        x2 = np.random.default_rng(1).normal(size=(5, 2)).astype(np.float32)
        out = graph.output(x1, x2)[0]
        assert out.shape == (5, 2)
        y = np.zeros((5, 2), np.float32)
        y[:, 0] = 1.0
        graph.fit(([x1, x2], [y]))
        assert np.isfinite(graph.score_value)

    def test_elementwise_and_subset_vertices(self):
        conf = (
            NeuralNetConfiguration.Builder()
            .seed(1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("a", L.DenseLayer(n_in=4, n_out=6, activation="tanh"), "in")
            .add_layer("b", L.DenseLayer(n_in=4, n_out=6, activation="tanh"), "in")
            .add_vertex(
                "sum", ElementWiseVertex(op=ElementWiseOp.ADD), "a", "b"
            )
            .add_vertex("subset", SubsetVertex(from_index=0, to_index=3), "sum")
            .add_layer(
                "out",
                L.OutputLayer(n_in=4, n_out=2, activation="softmax"),
                "subset",
            )
            .set_outputs("out")
            .build()
        )
        graph = ComputationGraph(conf).init()
        x = np.zeros((3, 4), np.float32)
        out = graph.output(x)[0]
        assert out.shape == (3, 2)

    def test_multi_output_training(self):
        conf = (
            NeuralNetConfiguration.Builder()
            .seed(1)
            .learning_rate(0.05)
            .graph_builder()
            .add_inputs("in")
            .add_layer("trunk", L.DenseLayer(n_in=4, n_out=8, activation="relu"), "in")
            .add_layer(
                "out1",
                L.OutputLayer(n_in=8, n_out=3, activation="softmax"),
                "trunk",
            )
            .add_layer(
                "out2",
                L.OutputLayer(
                    n_in=8, n_out=1, activation="identity",
                    loss_function=LossFunction.MSE,
                ),
                "trunk",
            )
            .set_outputs("out1", "out2")
            .build()
        )
        graph = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(10, 4)).astype(np.float32)
        y1 = np.zeros((10, 3), np.float32)
        y1[np.arange(10), rng.integers(0, 3, 10)] = 1.0
        y2 = rng.normal(size=(10, 1)).astype(np.float32)
        for _ in range(5):
            graph.fit(([x], [y1, y2]))
        assert np.isfinite(graph.score_value)
        outs = graph.output(x)
        assert outs[0].shape == (10, 3)
        assert outs[1].shape == (10, 1)

    def test_save_load(self, tmp_path):
        graph = ComputationGraph(_simple_graph_conf()).init()
        ds = iris_dataset()
        graph.fit(ds)
        path = str(tmp_path / "graph")
        graph.save(path)
        loaded = ComputationGraph.load(path)
        x = ds.features[:5]
        np.testing.assert_allclose(
            np.asarray(graph.output(x)[0]),
            np.asarray(loaded.output(x)[0]),
            atol=1e-6,
        )


class TestGraphGradients:
    def test_gradient_check_simple_graph(self):
        from jax.flatten_util import ravel_pytree
        import jax
        import jax.numpy as jnp

        graph = ComputationGraph(_simple_graph_conf()).init()
        rng = np.random.default_rng(3)
        x = rng.normal(size=(6, 4)).astype(np.float64)
        y = np.zeros((6, 3), np.float64)
        y[np.arange(6), rng.integers(0, 3, 6)] = 1.0

        with enable_x64(True):
            params64 = jax.tree.map(
                lambda p: jnp.asarray(np.asarray(p), jnp.float64), graph.params
            )
            flat0, unravel = ravel_pytree(params64)
            inputs = {"in": jnp.asarray(x)}
            labels = [jnp.asarray(y)]

            def loss_flat(flat):
                score, _ = graph._loss_fn(
                    unravel(flat), {}, None, inputs, labels, None, None
                )
                return score

            analytic = np.asarray(jax.grad(loss_flat)(flat0))
            flat0 = np.asarray(flat0)
            eps = 1e-6
            idxs = np.random.default_rng(0).choice(
                len(flat0), size=25, replace=False
            )
            for i in idxs:
                e = np.zeros_like(flat0)
                e[i] = eps
                num = (
                    float(loss_flat(jnp.asarray(flat0 + e)))
                    - float(loss_flat(jnp.asarray(flat0 - e)))
                ) / (2 * eps)
                denom = abs(analytic[i]) + abs(num)
                if denom > 1e-8:
                    assert abs(analytic[i] - num) / denom < 1e-3


class TestGraphFitScanGuards:
    """fit_scan is the plain-SGD full-BPTT fast path; mis-configured
    graphs must raise instead of silently training wrong (ADVICE r1)."""

    def test_rejects_tbptt(self):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        conf = _simple_graph_conf()
        conf.backprop_type = BackpropType.TRUNCATED_BPTT
        graph = ComputationGraph(conf)
        x = np.zeros((2, 4, 4), np.float32)
        y = np.zeros((2, 4, 3), np.float32)
        with pytest.raises(ValueError, match="truncated-BPTT"):
            graph.fit_scan(x, y)

    def test_rejects_non_sgd(self):
        from deeplearning4j_tpu.nn.conf.enums import OptimizationAlgorithm

        conf = _simple_graph_conf()
        for v in conf.vertices.values():
            v.conf.optimization_algo = OptimizationAlgorithm.LBFGS
        graph = ComputationGraph(conf)
        x = np.zeros((2, 4, 4), np.float32)
        y = np.zeros((2, 4, 3), np.float32)
        with pytest.raises(ValueError, match="only supports SGD"):
            graph.fit_scan(x, y)
