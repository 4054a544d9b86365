"""Finite-difference gradient checks for the round-2 layer families
(review r2 item 7): ImageLSTM, RecursiveAutoEncoder pretrain,
MultiHeadSelfAttention, and MoeDense with routing held away from
decision boundaries.

Same correctness backbone as the reference's GradientCheckUtil.java:48
driving every layer family (SURVEY §4), extending the existing suites
(tests/test_rnn.py:63, tests/test_cnn.py:114-196).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction


def _rnn_ds(n=4, c_in=3, c_out=4, t_in=6, t_out=None, seed=0):
    """Sequence DataSet: features [N, c_in, t_in], labels
    [N, c_out, t_out or t_in]."""
    t_out = t_in if t_out is None else t_out
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, c_in, t_in)).astype(np.float32)
    y = np.zeros((n, c_out, t_out), np.float32)
    idx = rng.integers(0, c_out, (n, t_out))
    for i in range(n):
        y[i, idx[i], np.arange(t_out)] = 1.0
    return DataSet(x, y)


class TestImageLstmGradients:
    """ImageLSTM (Karpathy captioning math, ImageLSTM.java:176-251):
    T+1 input steps (image + words), T output steps."""

    def test_gradient_check(self):
        t = 5  # words; input carries t+1 steps
        conf = (
            NeuralNetConfiguration.Builder()
            .seed(3).learning_rate(0.05)
            .list()
            .layer(0, L.ImageLSTM(n_in=3, n_out=4, n_hidden=5,
                                  activation="tanh"))
            .layer(1, L.RnnOutputLayer(
                n_in=4, n_out=4, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        ds = _rnn_ds(c_in=3, c_out=4, t_in=t + 1, t_out=t)
        assert check_gradients(
            net, ds, max_params_to_check=60, print_results=True)


class TestAttentionGradients:
    """MultiHeadSelfAttention bean (nn/layers/attention.py) under the
    standard harness, causal and bidirectional."""

    @pytest.mark.parametrize("causal", [True, False],
                            ids=["causal", "bidirectional"])
    def test_gradient_check(self, causal):
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadSelfAttention,
        )

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(5).learning_rate(0.05)
            .list()
            .layer(0, MultiHeadSelfAttention(
                n_in=6, n_out=8, n_heads=2, causal=causal))
            .layer(1, L.RnnOutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        ds = _rnn_ds(c_in=6, c_out=3, t_in=4)
        assert check_gradients(
            net, ds, max_params_to_check=60, print_results=True)


class TestRecursiveAutoEncoderGradients:
    """Pretrain-score gradient of RecursiveAutoEncoderImpl (the
    closed-form tail-harmonic folding score) vs centered finite
    differences in f64 — the pretrain path sits outside net._loss_fn,
    so the standard harness does not reach it."""

    def test_pretrain_gradient_check(self):
        from deeplearning4j_tpu.nn.layers.pretrain import (
            RecursiveAutoEncoderImpl,
        )

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(7).learning_rate(0.05)
            .list()
            .layer(0, L.RecursiveAutoEncoder(n_in=5, n_out=3,
                                             activation="tanh"))
            .layer(1, L.OutputLayer(
                n_in=3, n_out=2, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        impl = RecursiveAutoEncoderImpl
        c = net.conf.confs[0]
        rng = np.random.default_rng(1)
        x64 = jnp.asarray(rng.normal(size=(6, 5)), jnp.float64)

        with enable_x64(True):
            params = jax.tree.map(
                lambda p: jnp.asarray(np.asarray(p), jnp.float64),
                net.params["0"])
            _, grads = impl.pretrain_value_and_grad(c, params, x64, None)
            eps = 1e-6
            checked = 0
            for name, p in params.items():
                flat = np.asarray(p).ravel()
                g = np.asarray(grads[name]).ravel()
                for j in range(min(flat.size, 20)):
                    bump = np.zeros_like(flat)
                    bump[j] = eps
                    pp = dict(params)
                    pp[name] = jnp.asarray(
                        (flat + bump).reshape(p.shape))
                    lp = float(impl.pretrain_loss(c, pp, x64, None))
                    pp[name] = jnp.asarray(
                        (flat - bump).reshape(p.shape))
                    lm = float(impl.pretrain_loss(c, pp, x64, None))
                    num = (lp - lm) / (2 * eps)
                    denom = abs(num) + abs(g[j])
                    if denom < 1e-8:
                        continue
                    rel = abs(num - g[j]) / denom
                    assert rel < 1e-6, (name, j, num, g[j])
                    checked += 1
            assert checked > 30


class TestMoeGradients:
    """MoeDense with routing FROZEN by construction: capacity_factor =
    n_experts keeps every token undropped, and the check perturbs
    params by 1e-6 — far below the gate-logit margins of the seeded
    init — so top-k decisions (the only discontinuity) cannot flip
    between the two sides of the centered difference."""

    def test_gradient_check_away_from_routing_boundaries(self):
        from deeplearning4j_tpu.nn.layers.moe import MoeDense

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(11).learning_rate(0.05)
            .list()
            .layer(0, L.DenseLayer(n_in=5, n_out=6, activation="tanh"))
            .layer(1, MoeDense(n_in=6, n_out=6, n_experts=2,
                               n_hidden=8, capacity_factor=2.0,
                               aux_weight=0.01))
            .layer(2, L.OutputLayer(
                n_in=6, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(2)
        x = rng.normal(size=(8, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
        assert check_gradients(
            net, DataSet(x, y), max_params_to_check=80,
            print_results=True)
