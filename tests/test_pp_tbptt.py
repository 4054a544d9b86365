"""PP + tBPTT (round-4 review item 9): truncated BPTT through the
packed-row PipelineTrainer — deep LSTM stacks (the reference's core
workload, MultiLayerNetwork.java doTruncatedBPTT :1262) get 1/S stage
memory. Each time window runs the full microbatched GPipe schedule and
one optimizer step; per-(stage, replica, microbatch) RNN carries cross
windows stage-sharded under stop-gradient.

Trajectory-parity pattern mirrors test_pipeline_expert.py:680."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import BackpropType, Updater
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.pipeline_parallel import PipelineTrainer
from deeplearning4j_tpu.ops.losses import LossFunction


def _deep_lstm(window: int, n_in=6, hidden=(8, 8, 8), n_classes=3,
               lr=0.05, seed=5):
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed).learning_rate(lr).updater(Updater.SGD)
        .activation("tanh")
        .list()
    )
    prev = n_in
    for i, h in enumerate(hidden):
        b.layer(i, L.GravesLSTM(n_in=prev, n_out=h))
        prev = h
    b.layer(len(hidden), L.RnnOutputLayer(
        n_in=prev, n_out=n_classes, activation="softmax",
        loss_function=LossFunction.MCXENT))
    conf = (b.backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(window)
            .t_bptt_backward_length(window)
            .build())
    return MultiLayerNetwork(conf).init()


def _seq_batch(b=8, c=6, t=12, n_classes=3, seed=0, masked=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, c, t)).astype(np.float32)
    y = np.zeros((b, n_classes, t), np.float32)
    idx = rng.integers(0, n_classes, (b, t))
    for i in range(b):
        y[i, idx[i], np.arange(t)] = 1.0
    if not masked:
        return DataSet(x, y)
    fm = np.ones((b, t), np.float32)
    fm[b // 2:, t - 3:] = 0.0  # uneven tails across microbatches
    return DataSet(x, y, features_mask=fm, labels_mask=fm.copy())


class TestPpTbpttParity:
    def _parity(self, mesh_axes, window=4, t=12, steps=3, masked=False,
                n_microbatches=2):
        net_pp = _deep_lstm(window)
        net_sd = _deep_lstm(window)
        mesh = make_mesh(MeshSpec(mesh_axes))
        trainer = PipelineTrainer(
            net_pp, mesh, n_microbatches=n_microbatches)
        assert trainer.tbptt
        for step in range(steps):
            ds = _seq_batch(t=t, seed=step, masked=masked)
            s_pp = trainer.fit(ds)
            net_sd.fit(ds)
            assert abs(s_pp - float(net_sd.score_value)) < 1e-4, step
        # iteration advanced once per WINDOW (reference cadence)
        assert net_pp.iteration == net_sd.iteration
        for k in net_sd.params:
            for name in net_sd.params[k]:
                np.testing.assert_allclose(
                    np.asarray(net_pp.params[k][name]),
                    np.asarray(net_sd.params[k][name]),
                    rtol=1e-4, atol=1e-5,
                    err_msg=f"{k}/{name} diverged")

    def test_pp_tbptt_matches_single_device(self):
        self._parity({"pp": 2})

    def test_pp4_tbptt_uneven_last_window(self):
        # t=10 with window 4 -> windows of 4, 4, 2 (ragged tail)
        self._parity({"pp": 4}, window=4, t=10)

    def test_dp_pp_tbptt_matches_single_device(self):
        self._parity({"dp": 2, "pp": 2})

    def test_pp_tbptt_masked(self):
        self._parity({"pp": 2}, masked=True)

    def test_window_carry_matters(self):
        """The carried state must actually flow: training with tBPTT
        windows differs from training each window independently (a
        zero-carry bug would make these identical)."""
        net_a = _deep_lstm(window=4)
        mesh = make_mesh(MeshSpec({"pp": 2}))
        tr_a = PipelineTrainer(net_a, mesh, n_microbatches=2)
        ds = _seq_batch(t=8, seed=0)
        tr_a.fit(ds)
        # independent windows: same model trained on the two window
        # slices as separate full-BPTT batches
        net_b = _deep_lstm(window=4)
        conf_b = net_b.conf
        conf_b.backprop_type = BackpropType.STANDARD
        tr_b = PipelineTrainer(net_b, mesh, n_microbatches=2)
        x = np.asarray(ds.features)
        y = np.asarray(ds.labels)
        tr_b.fit(DataSet(x[:, :, :4], y[:, :, :4]))
        tr_b.fit(DataSet(x[:, :, 4:], y[:, :, 4:]))
        diffs = [
            float(np.abs(np.asarray(net_a.params[k][n])
                         - np.asarray(net_b.params[k][n])).max())
            for k in net_a.params for n in net_a.params[k]]
        assert max(diffs) > 1e-6, "window carry had no effect"

    def test_stage_sharding_holds_under_tbptt(self):
        net = _deep_lstm(window=4)
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = PipelineTrainer(net, mesh, n_microbatches=2)
        trainer.fit(_seq_batch())
        assert (max(trainer.per_device_state_bytes().values())
                < trainer.total_state_bytes())

    def test_attention_tbptt_no_bogus_carry(self):
        """Attention layers (BaseRecurrentLayer subclasses) carry NO
        state across tBPTT windows in training — the serving KV cache
        must not be collected as a window carry (train=True probe)."""
        from deeplearning4j_tpu.nn.layers.attention import (
            TransformerBlock,
        )

        def build():
            b = (
                NeuralNetConfiguration.Builder()
                .seed(3).learning_rate(0.01).updater(Updater.SGD)
                .activation("identity")
                .list()
                .layer(0, TransformerBlock(n_in=6, n_out=8, n_heads=2))
                .layer(1, L.GravesLSTM(n_in=8, n_out=8,
                                       activation="tanh"))
                .layer(2, L.RnnOutputLayer(
                    n_in=8, n_out=3, activation="softmax",
                    loss_function=LossFunction.MCXENT))
                .backprop_type(BackpropType.TRUNCATED_BPTT)
                .t_bptt_forward_length(4).t_bptt_backward_length(4)
            )
            return MultiLayerNetwork(b.build()).init()

        net_pp, net_sd = build(), build()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = PipelineTrainer(
            net_pp, mesh, n_microbatches=2,
            stage_ranges=[(0, 1), (1, 3)])
        for step in range(2):
            ds = _seq_batch(t=8, seed=step)
            s_pp = trainer.fit(ds)
            net_sd.fit(ds)
            assert abs(s_pp - float(net_sd.score_value)) < 1e-4, step
        for k in net_sd.params:
            for name in net_sd.params[k]:
                np.testing.assert_allclose(
                    np.asarray(net_pp.params[k][name]),
                    np.asarray(net_sd.params[k][name]),
                    rtol=1e-4, atol=1e-5, err_msg=f"{k}/{name}")

    def test_listener_fires_per_window(self):
        net = _deep_lstm(window=4)
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = PipelineTrainer(net, mesh, n_microbatches=2)
        seen = []

        class Rec:
            invoked_every = 1

            def iteration_done(self, model, it):
                seen.append(it)

        net.set_listeners(Rec())
        trainer.fit(_seq_batch(t=12))  # 3 windows of 4
        assert seen == [1, 2, 3]

    def test_fit_scan_rejects_tbptt(self):
        net = _deep_lstm(window=4)
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = PipelineTrainer(net, mesh, n_microbatches=2)
        with pytest.raises(ValueError, match="truncated-BPTT"):
            trainer.fit_scan(np.zeros((2, 8, 6, 12), np.float32),
                             np.zeros((2, 8, 3, 12), np.float32))
