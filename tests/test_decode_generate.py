"""Fused on-device generation (round-5 review next #5 support):
``generate`` must reproduce the per-token ``rnn_time_step`` loop
exactly — same ids, same final cache position."""

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

V = 12


def _net(seed=7):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 64
    return net


def _one_hot_seq(ids):
    x = np.zeros((1, V, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


class TestGenerate:
    def test_matches_per_token_loop(self):
        prompt = [1, 4, 7, 2]
        n = 12

        loop_net = _net()
        loop_net.rnn_clear_previous_state()
        out = loop_net.rnn_time_step(_one_hot_seq(prompt))
        tok = int(np.asarray(out)[0, :, -1].argmax())
        loop_ids = [tok]
        for _ in range(n - 1):
            out = loop_net.rnn_time_step(_one_hot_seq([tok]))
            tok = int(np.asarray(out)[0, :, 0].argmax())
            loop_ids.append(tok)

        gen_net = _net()
        gen_net.rnn_clear_previous_state()
        ids = np.asarray(gen_net.generate(_one_hot_seq(prompt), n))
        assert ids.shape == (1, n)
        assert ids[0].tolist() == loop_ids

    def test_single_token(self):
        net = _net()
        net.rnn_clear_previous_state()
        ids = np.asarray(net.generate(_one_hot_seq([3, 1]), 1))
        assert ids.shape == (1, 1)

    def test_state_continues_after_generate(self):
        """generate leaves the cache positioned so further streaming
        continues the same sequence."""
        a = _net()
        a.rnn_clear_previous_state()
        ids = np.asarray(a.generate(_one_hot_seq([5, 2]), 4))
        cont = a.rnn_time_step(_one_hot_seq([int(ids[0, -1])]))
        nxt_a = int(np.asarray(cont)[0, :, 0].argmax())

        b = _net()
        b.rnn_clear_previous_state()
        ids_b = np.asarray(b.generate(_one_hot_seq([5, 2]), 5))
        assert int(ids_b[0, -1]) == nxt_a

    def test_graph_generate_matches_per_token_loop(self):
        """ComputationGraph.generate == its rnn_time_step loop (the
        graph counterpart of the MLN contract)."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadSelfAttention,
        )
        from deeplearning4j_tpu.ops.losses import LossFunction

        def gnet():
            conf = (
                NeuralNetConfiguration.Builder()
                .seed(6).learning_rate(0.01)
                .graph_builder().add_inputs("in")
                .add_layer("attn", MultiHeadSelfAttention(
                    n_in=V, n_out=16, n_heads=2, causal=True,
                    stream_max_t=32), "in")
                .add_layer("out", L.RnnOutputLayer(
                    n_in=16, n_out=V, activation="softmax",
                    loss_function=LossFunction.MCXENT), "attn")
                .set_outputs("out").build())
            return ComputationGraph(conf).init()

        prompt = [2, 5, 9]
        n = 8
        loop_net = gnet()
        loop_net.rnn_clear_previous_state()
        out = loop_net.rnn_time_step(_one_hot_seq(prompt))[0]
        tok = int(np.asarray(out)[0, :, -1].argmax())
        loop_ids = [tok]
        for _ in range(n - 1):
            out = loop_net.rnn_time_step(_one_hot_seq([tok]))[0]
            tok = int(np.asarray(out)[0, :, -1].argmax())
            loop_ids.append(tok)

        gen_net = gnet()
        gen_net.rnn_clear_previous_state()
        ids = np.asarray(gen_net.generate(_one_hot_seq(prompt), n))
        assert ids.shape == (1, n)
        assert ids[0].tolist() == loop_ids

    def test_graph_generate_rejects_multi_io(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.ops.losses import LossFunction

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(1).learning_rate(0.01)
            .graph_builder().add_inputs("a", "b")
            .add_layer("da", L.DenseLayer(n_in=2, n_out=3), "a")
            .add_layer("db", L.DenseLayer(n_in=2, n_out=3), "b")
            .add_layer("out", L.OutputLayer(
                n_in=3, n_out=2, activation="softmax",
                loss_function=LossFunction.MCXENT), "da")
            .set_outputs("out").build())
        net = ComputationGraph(conf).init()
        with pytest.raises(ValueError, match="single-input"):
            net.generate(np.zeros((1, 2, 3), np.float32), 4)

    def test_batched_prompts(self):
        net = _net()
        net.rnn_clear_previous_state()
        x = np.concatenate([_one_hot_seq([1, 2, 3]),
                            _one_hot_seq([9, 8, 7])])
        ids = np.asarray(net.generate(x, 6))
        assert ids.shape == (2, 6)
        # each row must match its own single-prompt generation
        for row, prompt in zip(ids, ([1, 2, 3], [9, 8, 7])):
            solo = _net()
            solo.rnn_clear_previous_state()
            want = np.asarray(solo.generate(_one_hot_seq(prompt), 6))
            assert row.tolist() == want[0].tolist()
