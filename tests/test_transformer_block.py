"""TransformerBlock + LayerNormalization + warmup_cosine lr policy
(round-4 review item 1: the convergence-grade flagship unit).

Correctness backbone per SURVEY §4: finite-difference gradient check
(reference GradientCheckUtil.java:48 pattern), conf serde round-trip,
streaming-vs-full-forward parity (reference rnnTimeStep contract), and
a convergence smoke on the analytic Markov task (datasets/markov.py).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.markov import (
    make_chain,
    markov_lm_batches,
)
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.attention import TransformerBlock
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction


def _block_conf(n_in=6, width=8, n_layers=2, n_heads=2, vocab=6,
                lr=1e-3, **conf_kw):
    b = (
        NeuralNetConfiguration.Builder()
        .seed(7).learning_rate(lr).updater("adam")
        .activation("identity")
        .list()
    )
    for i in range(n_layers):
        b.layer(i, TransformerBlock(
            n_in=n_in if i == 0 else width, n_out=width,
            n_heads=n_heads, causal=True))
    b.layer(n_layers, L.LayerNormalization(n_in=width, n_out=width))
    b.layer(n_layers + 1, L.RnnOutputLayer(
        n_in=width, n_out=vocab, activation="softmax",
        loss_function=LossFunction.MCXENT))
    conf = b.build()
    for k, v in conf_kw.items():
        setattr(conf, k, v)
    return conf


def _lm_ds(n=4, c=6, t=5, vocab=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, c, t)).astype(np.float32)
    y = np.zeros((n, vocab, t), np.float32)
    idx = rng.integers(0, vocab, (n, t))
    for i in range(n):
        y[i, idx[i], np.arange(t)] = 1.0
    return DataSet(x, y)


class TestTransformerBlockGradients:
    def test_gradient_check(self):
        net = MultiLayerNetwork(_block_conf()).init()
        assert check_gradients(
            net, _lm_ds(), max_params_to_check=80, print_results=True)

    def test_gradient_check_projected_input(self):
        # n_in != n_out exercises the Wi input-projection branch
        net = MultiLayerNetwork(_block_conf(n_in=5, width=8)).init()
        assert check_gradients(
            net, _lm_ds(c=5), max_params_to_check=60,
            print_results=True)


class TestSerde:
    def test_round_trip(self):
        conf = _block_conf()
        conf.confs[0].lr_policy = "warmup_cosine"
        conf.confs[0].lr_warmup_steps = 10
        conf.confs[0].lr_total_steps = 100
        js = conf.to_json()
        c2 = MultiLayerConfiguration.from_json(js)
        lc = c2.confs[0].layer
        assert isinstance(lc, TransformerBlock)
        assert lc.ffn_mult == 4 and lc.n_heads == 2
        assert isinstance(c2.confs[2].layer, L.LayerNormalization)
        assert c2.confs[0].lr_policy == "warmup_cosine"
        assert c2.confs[0].lr_total_steps == 100


class TestStreaming:
    def test_stream_matches_full_forward(self):
        """Prefill + chunked rnn_time_step must equal the full forward
        on the streamed suffix (reference rnnTimeStep parity; mirrors
        the MultiHeadSelfAttention streaming tests)."""
        conf = _block_conf(n_in=6, width=8)
        for c in conf.confs:
            if isinstance(c.layer, TransformerBlock):
                c.layer.stream_max_t = 32
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(3)
        x = rng.normal(size=(2, 6, 12)).astype(np.float32)
        full = np.asarray(net.output(x))
        net.rnn_clear_previous_state()
        outs = []
        for t0 in range(0, 12, 3):
            outs.append(np.asarray(net.rnn_time_step(x[:, :, t0:t0 + 3])))
        stream = np.concatenate(outs, axis=2)
        np.testing.assert_allclose(stream, full, rtol=2e-4, atol=2e-4)


class TestLrPolicy:
    def test_warmup_cosine_shape(self):
        from deeplearning4j_tpu.nn.updater.updaters import resolve_lr

        conf = NeuralNetConfiguration(
            learning_rate=1.0, lr_policy="warmup_cosine",
            lr_warmup_steps=10, lr_total_steps=110, lr_min_fraction=0.1)
        lr0 = float(resolve_lr(conf, 0))
        lr_half_warm = float(resolve_lr(conf, 5))
        lr_peak = float(resolve_lr(conf, 10))
        lr_mid = float(resolve_lr(conf, 60))
        lr_end = float(resolve_lr(conf, 110))
        assert lr0 == 0.0
        assert abs(lr_half_warm - 0.5) < 1e-6
        assert abs(lr_peak - 1.0) < 1e-6
        # cosine midpoint: frac + (1-frac)/2 = 0.55
        assert abs(lr_mid - 0.55) < 1e-6
        assert abs(lr_end - 0.1) < 1e-6
        # past the horizon it stays at the floor
        assert abs(float(resolve_lr(conf, 500)) - 0.1) < 1e-6

    def test_policy_excludes_schedule(self):
        from deeplearning4j_tpu.nn.updater.updaters import resolve_lr

        conf = NeuralNetConfiguration(
            learning_rate=1.0, lr_policy="warmup_cosine",
            learning_rate_schedule={10: 0.5},
            lr_warmup_steps=5, lr_total_steps=50)
        with pytest.raises(ValueError, match="mutually exclusive"):
            resolve_lr(conf, 0)


class TestParallelComposition:
    """TransformerBlock under the mesh trainers (round-4 code-review
    items: tp head/FFN sharding and sp ring validation must dispatch on
    the shared attention-bean capability, not the concrete class)."""

    def _nets(self, ring_axis=None, seed=5):
        conf = _block_conf(n_in=8, width=8, n_layers=2, n_heads=4,
                           vocab=8, lr=1e-2)
        conf.confs[0].seed = seed
        for c in conf.confs:
            if isinstance(c.layer, TransformerBlock):
                c.layer.ring_axis = ring_axis
        return MultiLayerNetwork(conf).init()

    def _batch(self, n=4, c=8, t=16, seed=2):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, c, t)).astype(np.float32)
        y = np.zeros((n, c, t), np.float32)
        idx = rng.integers(0, c, (n, t))
        for i in range(n):
            y[i, idx[i], np.arange(t)] = 1.0
        return x, y

    def test_dp_tp_matches_single_device(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        x, y = self._batch()
        ref = self._nets()
        tp_net = self._nets()
        mesh = make_mesh(MeshSpec({"dp": 2, "tp": 4}))
        trainer = ParallelTrainer(tp_net, mesh, tp_axis="tp")
        # Megatron block shardings actually applied
        assert "tp" in tuple(tp_net.params["0"]["Wq"].sharding.spec)
        assert tuple(tp_net.params["0"]["W1"].sharding.spec)[1] == "tp"
        assert tuple(tp_net.params["0"]["W2"].sharding.spec)[0] == "tp"
        for _ in range(3):
            ref.fit(DataSet(x, y))
            s_tp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s_tp, float(ref.score_value),
                                   rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(tp_net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"param {si}/{name} diverged under dp x tp")

    def test_sp_ring_matches_single_device(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        x, y = self._batch(t=16)
        ref = self._nets(ring_axis=None)
        sp_net = self._nets(ring_axis="sp")
        mesh = make_mesh(MeshSpec({"sp": 4}))
        trainer = ParallelTrainer(sp_net, mesh, sp_axis="sp")
        scores_ref, scores_sp = [], []
        for _ in range(3):
            ref.fit(DataSet(x, y))
            scores_ref.append(float(ref.score_value))
            scores_sp.append(trainer.fit(DataSet(x, y)))
        np.testing.assert_allclose(scores_sp, scores_ref, rtol=2e-4)

    def test_set_input_type_no_preprocessors_around_layernorm(self):
        """LayerNormalization is shape-preserving: set_input_type must
        not wrap it in RnnToFF/FFToRnn (which would fold batch into
        time)."""
        from deeplearning4j_tpu.nn.conf.inputs import InputType

        b = (
            NeuralNetConfiguration.Builder()
            .seed(1).learning_rate(1e-2).updater("adam")
            .activation("identity")
            .list()
            .layer(0, TransformerBlock(n_in=6, n_out=8, n_heads=2))
            .layer(1, L.LayerNormalization(n_in=8, n_out=8))
            .layer(2, L.RnnOutputLayer(
                n_in=8, n_out=6, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .set_input_type(InputType.recurrent(6))
        )
        conf = b.build()
        assert not conf.input_preprocessors, (
            f"unexpected preprocessors {conf.input_preprocessors}")
        net = MultiLayerNetwork(conf).init()
        out = net.output(np.random.default_rng(0).normal(
            size=(3, 6, 5)).astype(np.float32))
        assert np.asarray(out).shape == (3, 6, 5)


class TestComputationGraph:
    def test_transformer_block_in_graph(self):
        """The block works as a ComputationGraph vertex (shared
        get_impl registry — reference ComputationGraph.java DAG)."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(4).learning_rate(1e-2).updater("adam")
            .activation("identity")
            .graph_builder()
            .add_inputs("in")
            .add_layer("blk", TransformerBlock(
                n_in=6, n_out=8, n_heads=2), "in")
            .add_layer("norm", L.LayerNormalization(n_in=8, n_out=8),
                       "blk")
            .add_layer("out", L.RnnOutputLayer(
                n_in=8, n_out=6, activation="softmax",
                loss_function=LossFunction.MCXENT), "norm")
            .set_outputs("out")
            .build()
        )
        g = ComputationGraph(conf).init()
        ds = _lm_ds()
        s0 = None
        for _ in range(15):
            g.fit(ds)
            if s0 is None:
                s0 = float(g.score_value)
        assert np.isfinite(float(g.score_value))
        assert float(g.score_value) < s0  # learning, not just running


class TestMarkovTask:
    def test_entropy_floor_below_uniform(self):
        _, pi, floor = make_chain(32, seed=0, concentration=1.5)
        assert 0.5 < floor < np.log(32)
        assert abs(float(np.sum(pi)) - 1.0) < 1e-8

    def test_flagship_converges_toward_floor(self):
        """Tiny flagship on the Markov task: held-out loss must move
        from ~log V toward the analytic floor — the convergence
        gate of the flagship block, in miniature."""
        V, T = 16, 32
        feats, labels, floor = markov_lm_batches(
            V, n_seq=128, seq_len=T, seed=0, sample_seed=1)
        hf, hl, _ = markov_lm_batches(
            V, n_seq=64, seq_len=T, seed=0, sample_seed=9)
        conf = _block_conf(n_in=V, width=16, n_layers=2, n_heads=2,
                           vocab=V, lr=3e-3)
        conf.confs[0].lr_policy = "warmup_cosine"
        conf.confs[0].lr_warmup_steps = 16
        conf.confs[0].lr_total_steps = 160
        net = MultiLayerNetwork(conf).init()
        K, B = 8, 16
        f = feats.reshape(K, B, V, T)
        la = labels.reshape(K, B, V, T)
        held = DataSet(hf, hl)
        start = net.score(held)
        for _ in range(20):
            net.fit_scan(f, la)
        end = net.score(held)
        assert start > floor + 0.3  # starts well above the floor
        # converged most of the way from log V toward the floor
        assert end - floor < 0.5 * (start - floor)
