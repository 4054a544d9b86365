"""Mixed-precision training: bf16 compute with f32 master params.

NEW TPU-native capability (no reference counterpart — the reference is
f32-only BLAS): forward/backward run in ``compute_dtype`` while params,
updater state, and the loss stay at the master dtype. Convergence must
track the f32 run closely, params must never leave f32, and the conf knob
must survive the JSON wire format."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction


def _conf(compute_dtype=None, with_bn=False):
    b = NeuralNetConfiguration.Builder().seed(7).learning_rate(0.1)
    if compute_dtype:
        b = b.compute_dtype(compute_dtype)
    lb = b.list()
    idx = 0
    lb.layer(idx, L.DenseLayer(n_in=8, n_out=16, activation="relu"))
    idx += 1
    if with_bn:
        lb.layer(idx, L.BatchNormalization(n_in=16, n_out=16))
        idx += 1
    lb.layer(idx, L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                loss_function=LossFunction.MCXENT))
    return lb.build()


def _graph_conf(compute_dtype="bfloat16"):
    b = NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
    if compute_dtype:
        b = b.compute_dtype(compute_dtype)
    return (
        b.graph_builder()
        .add_inputs("in")
        .add_layer("h", L.DenseLayer(n_in=8, n_out=16,
                                     activation="relu"), "in")
        .add_layer("out", L.OutputLayer(
            n_in=16, n_out=3, activation="softmax",
            loss_function=LossFunction.MCXENT), "h")
        .set_outputs("out")
        .build()
    )


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    cls = rng.integers(0, 3, n)
    x = rng.normal(loc=cls[:, None] * 0.5, size=(n, 8)).astype(np.float32)
    return x, np.eye(3, dtype=np.float32)[cls]


class TestMixedPrecision:
    def test_converges_like_f32(self):
        x, y = _data()
        n32 = MultiLayerNetwork(_conf()).init()
        nbf = MultiLayerNetwork(_conf("bfloat16")).init()
        for _ in range(30):
            n32.fit(x, y)
            nbf.fit(x, y)
        assert abs(float(n32.score_value) - float(nbf.score_value)) < 0.05
        assert np.isfinite(float(nbf.score_value))

    def test_master_params_stay_f32(self):
        x, y = _data()
        net = MultiLayerNetwork(_conf("bfloat16")).init()
        net.fit(x, y)
        for lp in net.params.values():
            for p in lp.values():
                assert p.dtype == jnp.float32

    def test_state_layers_keep_master_dtype(self):
        x, y = _data()
        net = MultiLayerNetwork(_conf("bfloat16", with_bn=True)).init()
        for _ in range(3):
            net.fit(x, y)
        for st in net.state.values():
            for leaf in st.values():
                if hasattr(leaf, "dtype") and jnp.issubdtype(
                        leaf.dtype, jnp.floating):
                    assert leaf.dtype == jnp.float32

    def test_json_round_trip(self):
        conf = _conf("bfloat16")
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.compute_dtype == "bfloat16"
        net = MultiLayerNetwork(back).init()
        x, y = _data(16)
        net.fit(x, y)
        assert np.isfinite(float(net.score_value))

    def test_inference_output_finite(self):
        x, _ = _data()
        net = MultiLayerNetwork(_conf("bfloat16")).init()
        out = np.asarray(net.output(x))
        assert out.shape == (64, 3)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=2e-2)


class TestMixedPrecisionGraph:
    def test_graph_bf16_compute(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        net = ComputationGraph(_graph_conf()).init()
        x, y = _data(32)
        for _ in range(5):
            net.fit(x, y)
        assert np.isfinite(float(net.score_value))
        for lp in net.params.values():
            for p in lp.values():
                assert p.dtype == jnp.float32

    def test_invalid_compute_dtype_message(self):
        import pytest

        with pytest.raises(ValueError, match="bf16"):
            MultiLayerNetwork(_conf("bf16"))


class TestMixedPrecisionTbptt:
    def test_tbptt_bf16(self):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        lb = (NeuralNetConfiguration.Builder().seed(9).learning_rate(0.05)
              .compute_dtype("bfloat16").list())
        lb.layer(0, L.GravesLSTM(n_in=4, n_out=8, activation="tanh"))
        lb.layer(1, L.RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                     loss_function=LossFunction.MCXENT))
        conf = (lb.backprop_type(BackpropType.TRUNCATED_BPTT)
                .t_bptt_forward_length(4).t_bptt_backward_length(4).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 4, 12)).astype(np.float32)
        y = np.zeros((8, 3, 12), np.float32)
        y[np.arange(8)[:, None], rng.integers(0, 3, (8, 12)),
          np.arange(12)[None, :]] = 1.0
        for _ in range(3):
            net.fit(x, y)
        assert np.isfinite(float(net.score_value))
        for lp in net.params.values():
            for p in lp.values():
                assert p.dtype == jnp.float32


class TestFitScan:
    """Scanned multi-step training (K steps = one XLA computation): the
    dispatch-latency fast path the training cell runs."""

    def test_trains_and_matches_sequential_shape(self):
        x, y = _data(n=128)
        feats = np.stack([x[i * 32:(i + 1) * 32] for i in range(4)] * 4)
        labels = np.stack([y[i * 32:(i + 1) * 32] for i in range(4)] * 4)

        net = MultiLayerNetwork(_conf()).init()
        before = float(net.score(
            __import__("deeplearning4j_tpu.datasets.dataset",
                       fromlist=["DataSet"]).DataSet(x, y)))
        scores = np.asarray(net.fit_scan(feats, labels))
        assert scores.shape == (16,)
        assert net.iteration == 16
        assert np.all(np.isfinite(scores))
        # loss decreased across the scanned steps
        assert scores[-1] < before
        assert scores[-1] < scores[0]

    def test_rejects_tbptt_and_second_order(self):
        import pytest

        from deeplearning4j_tpu.nn.conf.enums import (
            BackpropType,
            OptimizationAlgorithm,
        )

        x, y = _data(32)
        feats, labels = np.stack([x]), np.stack([y])

        lb = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
              .list())
        lb.layer(0, L.DenseLayer(n_in=8, n_out=8, activation="tanh"))
        lb.layer(1, L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss_function=LossFunction.MCXENT))
        tb = lb.backprop_type(BackpropType.TRUNCATED_BPTT).build()
        with pytest.raises(ValueError, match="truncated"):
            MultiLayerNetwork(tb).init().fit_scan(feats, labels)

        lb2 = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
               .optimization_algo(OptimizationAlgorithm.LBFGS).list())
        lb2.layer(0, L.DenseLayer(n_in=8, n_out=8, activation="tanh"))
        lb2.layer(1, L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss_function=LossFunction.MCXENT))
        with pytest.raises(ValueError, match="SGD"):
            MultiLayerNetwork(lb2.build()).init().fit_scan(feats, labels)

    def test_listener_cadence_matches_fit(self):
        from deeplearning4j_tpu.optimize.listeners import (
            ScoreIterationListener,
        )

        x, y = _data(64)
        feats = np.stack([x[:32], x[32:]] * 8)  # K=16 steps per call
        labels = np.stack([y[:32], y[32:]] * 8)
        net = MultiLayerNetwork(_conf()).init()
        fired = []

        listener = ScoreIterationListener(10)
        listener.iteration_done = lambda model, it: fired.append(it)
        net.listeners = [listener]
        net.fit_scan(feats, labels)  # iterations 0 -> 16: crosses 10
        net.fit_scan(feats, labels)  # 16 -> 32: crosses 20 and 30
        assert fired == [16, 32]

    def test_chained_calls_stay_lazy_and_finite(self):
        x, y = _data(n=64)
        feats = np.stack([x[:32], x[32:]])
        labels = np.stack([y[:32], y[32:]])
        net = MultiLayerNetwork(_conf("bfloat16")).init()
        for _ in range(5):
            scores = net.fit_scan(feats, labels)
        # score_value stays a lazy device scalar until the caller forces it
        assert np.isfinite(float(net.score_value))
        assert np.isfinite(np.asarray(scores)).all()
        assert net.iteration == 10


class TestF32OutputHead:
    """Under mixed precision the OUTPUT layer runs at the master dtype:
    a bf16 softmax quantizes probabilities coarsely enough to stall
    training at a calibration plateau (measured on LeNet/MNIST in
    an earlier round)."""

    def test_mln_output_layer_runs_f32(self):
        import jax.numpy as jnp

        net = MultiLayerNetwork(_conf("bfloat16")).init()
        x, _ = _data()
        acts, _, _ = net._forward_fn(
            net.params, {}, jnp.asarray(x), None, False, None,
            collect=True)
        assert acts[0].dtype == jnp.bfloat16   # body: compute dtype
        assert acts[-1].dtype == jnp.float32   # head: master dtype

    def test_graph_output_vertex_runs_f32(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (
            NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
            .compute_dtype("bfloat16")
            .graph_builder()
            .add_inputs("in")
            .add_layer("h", L.DenseLayer(n_in=8, n_out=16,
                                         activation="relu"), "in")
            .add_layer("out", L.OutputLayer(
                n_in=16, n_out=3, activation="softmax",
                loss_function="mcxent"), "h")
            .set_outputs("out")
            .build()
        )
        g = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        x = {"in": jnp.asarray(
            rng.normal(size=(8, 8)).astype(np.float32))}
        acts, _, _ = g._forward_fn(g.params, {}, x, None, False, None)
        assert acts["h"].dtype == jnp.bfloat16
        assert acts["out"].dtype == jnp.float32


def _mln(compute_dtype="bfloat16"):
    net = MultiLayerNetwork(_conf(compute_dtype)).init()
    x, _ = _data()
    return (net, str(net.n_layers - 1),
            lambda p: net._forward_fn(p, {}, jnp.asarray(x), None,
                                      False)[0])


def _graph(compute_dtype="bfloat16"):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(_graph_conf(compute_dtype)).init()
    x, _ = _data()
    return (net, "out",
            lambda p: net._forward_fn(p, {}, {"in": jnp.asarray(x)},
                                      None, False)[0]["out"])


class TestComputeParams:
    """The cast rule of mixed precision is one method of a net
    (``compute_params``): ``_forward_fn`` applies it to what it is
    handed, a holder of resident weights (the serving engine) applies
    it once, and applied twice it is the identity on ARRAYS."""

    @pytest.mark.parametrize("build", [_mln, _graph],
                             ids=["multilayer", "graph"])
    def test_forward_agrees_bit_for_bit_on_cast_params(self, build):
        net, head, forward = build()
        cast = net.compute_params(net.params)
        for key, sub in cast.items():
            for name, leaf in sub.items():
                if key == head:     # the output layer: master, untouched
                    assert leaf is net.params[key][name]
                else:
                    assert leaf.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(forward(net.params)),
                                      np.asarray(forward(cast)))
        again = net.compute_params(cast)
        assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                          jax.tree.leaves(cast)))

    @pytest.mark.parametrize("build", [_mln, _graph],
                             ids=["multilayer", "graph"])
    def test_a_net_without_a_compute_dtype_hands_back_its_tree(
            self, build):
        net, _, _ = build(None)
        assert net.compute_params(net.params) is net.params

    def test_the_scanned_step_lowers_as_with_the_rule_inlined(self):
        """``fit_scan``'s step, lowered with the rule factored out (the
        code as it is) and with the rule written into the forward pass
        (as it was before ``compute_params``): the same text."""
        x, y = _data(16)
        feats, labels = jnp.asarray(x[None]), jnp.asarray(y[None])

        def lowered(net):
            return net._train_steps_scan.lower(
                net.params, net.state, net.updater_state, 0,
                jax.random.key(0), feats, labels, 1.0).as_text()

        factored = lowered(MultiLayerNetwork(
            _conf("bfloat16", with_bn=True)).init())
        net = MultiLayerNetwork(_conf("bfloat16", with_bn=True)).init()
        forward, last = net._forward_fn, str(net.n_layers - 1)

        def inlined(params, *args, **kwargs):
            params = {
                si: (sub if si == last else jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.bfloat16), sub))
                for si, sub in params.items()}
            return forward(params, *args, **kwargs)

        net._forward_fn = inlined
        net.compute_params = lambda params: params
        text = lowered(net)
        assert "bf16" in text and text == factored
