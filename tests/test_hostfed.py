"""Host-fed training path (round-5 review next #1): disk-streaming
iterators -> C++ prefetch ring -> fit_stream window fusion.

Numerics contract: fit_stream over an async disk iterator must produce
EXACTLY the trajectory of sequential fit() on the same batches (window
fusion and device-side ingest change scheduling, not math).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.streaming import (
    CifarBinStreamIterator,
    TokenSequenceFileIterator,
    read_token_file_header,
    write_token_file,
)
from deeplearning4j_tpu.native_rt import NativeAsyncDataSetIterator


def _write_cifar_file(path, rows_data, rows_labels):
    rows = np.concatenate(
        [np.concatenate([[l], d.ravel()])[None]
         for d, l in zip(rows_data, rows_labels)]).astype(np.uint8)
    rows.tofile(path)


class TestCifarBinStream:
    def test_streams_rows_across_files(self, tmp_path):
        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 255, (50, 3, 32, 32), np.uint8)
        labels = rng.integers(0, 10, 50).astype(np.uint8)
        p1, p2 = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
        _write_cifar_file(p1, imgs[:30], labels[:30])
        _write_cifar_file(p2, imgs[30:], labels[30:])
        it = CifarBinStreamIterator([p1, p2], batch_size=16)
        assert it.total_examples() == 50
        got_f, got_l = [], []
        while True:
            ds = it.next()
            if ds is None:
                break
            got_f.append(np.asarray(ds.features))
            got_l.append(np.asarray(ds.labels).argmax(1))
        # batches never span files: 30 -> 16+14, 20 -> 16+4
        assert [len(f) for f in got_f] == [16, 14, 16, 4]
        np.testing.assert_array_equal(np.concatenate(got_f), imgs)
        np.testing.assert_array_equal(np.concatenate(got_l), labels)

    def test_state_dict_resume(self, tmp_path):
        rng = np.random.default_rng(1)
        imgs = rng.integers(0, 255, (20, 3, 32, 32), np.uint8)
        labels = rng.integers(0, 10, 20).astype(np.uint8)
        p = str(tmp_path / "a.bin")
        _write_cifar_file(p, imgs, labels)
        it = CifarBinStreamIterator([p], batch_size=8)
        it.next()
        state = it.state_dict()
        want = np.asarray(it.next().features)
        it2 = CifarBinStreamIterator([p], batch_size=8)
        it2.load_state_dict(state)
        np.testing.assert_array_equal(np.asarray(it2.next().features),
                                      want)

    def test_rejects_bad_file(self, tmp_path):
        p = tmp_path / "bad.bin"
        p.write_bytes(b"\x01" * 100)
        with pytest.raises(ValueError, match="not a CIFAR-10"):
            CifarBinStreamIterator([str(p)], batch_size=4)


class TestTokenFile:
    def test_round_trip(self, tmp_path):
        rng = np.random.default_rng(2)
        toks = rng.integers(0, 64, (10, 17), np.int32)
        p = str(tmp_path / "toks.bin")
        write_token_file(p, toks, vocab=64)
        assert read_token_file_header(p) == (10, 17, 64, 1)
        it = TokenSequenceFileIterator(p, batch_size=4)
        assert it.total_examples() == 10
        assert it.input_columns() == 16
        feats, labels = [], []
        while True:
            ds = it.next()
            if ds is None:
                break
            feats.append(np.asarray(ds.features))
            labels.append(np.asarray(ds.labels))
        np.testing.assert_array_equal(np.concatenate(feats),
                                      toks[:, :-1])
        np.testing.assert_array_equal(np.concatenate(labels),
                                      toks[:, 1:])

    def test_u16_vocab(self, tmp_path):
        toks = np.arange(2 * 5).reshape(2, 5) + 300
        p = str(tmp_path / "toks16.bin")
        write_token_file(p, toks, vocab=1000)
        assert read_token_file_header(p)[3] == 2
        it = TokenSequenceFileIterator(p, batch_size=2)
        np.testing.assert_array_equal(
            np.asarray(it.next().features), toks[:, :-1])

    def test_rejects_out_of_range(self, tmp_path):
        with pytest.raises(ValueError, match="outside"):
            write_token_file(str(tmp_path / "x.bin"),
                             np.array([[0, 99]]), vocab=64)


def _mlp_cifar_net(seed=5):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.losses import LossFunction

    conf = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(0.05)
        .list()
        .layer(0, L.ConvolutionLayer(
            n_in=3, n_out=8, kernel_size=(3, 3), stride=(2, 2),
            activation="relu"))
        .layer(1, L.OutputLayer(
            n_out=10, activation="softmax",
            loss_function=LossFunction.MCXENT))
        .set_input_type(InputType.convolutional(32, 32, 3))
        .build()
    )
    return MultiLayerNetwork(conf).init()


class TestFitStream:
    def _data(self, tmp_path, n=48):
        rng = np.random.default_rng(3)
        imgs = rng.integers(0, 255, (n, 3, 32, 32), np.uint8)
        labels = rng.integers(0, 10, n).astype(np.uint8)
        p = str(tmp_path / "train.bin")
        _write_cifar_file(p, imgs, labels)
        return p, imgs, labels

    def test_matches_sequential_fit_exactly(self, tmp_path):
        import jax
        import jax.numpy as jnp

        p, imgs, labels = self._data(tmp_path, n=48)
        B, K = 8, 3
        ingest = jax.jit(lambda a: a.astype(jnp.float32) / 255.0)

        stream_net = _mlp_cifar_net()
        it = NativeAsyncDataSetIterator(
            CifarBinStreamIterator([p], batch_size=B), queue_size=4)
        scores = stream_net.fit_stream(it, scan_steps=K, ingest=ingest)
        assert scores is not None and np.isfinite(np.asarray(scores)).all()
        assert stream_net.iteration == 48 // B

        seq_net = _mlp_cifar_net()
        onehot = np.eye(10, dtype=np.float32)[labels]
        for lo in range(0, 48, B):
            seq_net.fit(DataSet(imgs[lo:lo + B].astype(np.float32) / 255.0,
                                onehot[lo:lo + B]))
        assert seq_net.iteration == stream_net.iteration
        for a, b in zip(jax.tree.leaves(stream_net.params),
                        jax.tree.leaves(seq_net.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_ragged_tail_trains_all_batches(self, tmp_path):
        import jax
        import jax.numpy as jnp

        p, imgs, labels = self._data(tmp_path, n=44)  # 5.5 batches of 8
        ingest = jax.jit(lambda a: a.astype(jnp.float32) / 255.0)
        net = _mlp_cifar_net()
        it = NativeAsyncDataSetIterator(
            CifarBinStreamIterator([p], batch_size=8), queue_size=4)
        net.fit_stream(it, scan_steps=2, ingest=ingest)
        # 44 examples -> batches of 8,8,8,8,8,4: windows (2,2) + tail (2)
        assert net.iteration == 6

    def test_masked_batches_flow_through(self):
        """Masked variable-length batches: fit_stream must forward the
        masks to fit_scan (fused) and fit (ragged), matching sequential
        masked fit exactly."""
        import jax

        from deeplearning4j_tpu.datasets.iterator import (
            ListDataSetIterator,
        )
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ops.losses import LossFunction

        def net():
            conf = (
                NeuralNetConfiguration.Builder()
                .seed(11)
                .learning_rate(0.05)
                .list()
                .layer(0, L.GravesLSTM(n_in=3, n_out=4))
                .layer(1, L.RnnOutputLayer(
                    n_in=4, n_out=2, activation="softmax",
                    loss_function=LossFunction.MCXENT))
                .build()
            )
            return MultiLayerNetwork(conf).init()

        rng = np.random.default_rng(4)
        b, t, k = 4, 6, 4
        batches = []
        for _ in range(k):
            x = rng.normal(size=(b, 3, t)).astype(np.float32)
            idx = rng.integers(0, 2, (b, t))
            y = np.zeros((b, 2, t), np.float32)
            for i in range(b):
                y[i, idx[i], np.arange(t)] = 1.0
            lens = rng.integers(3, t + 1, b)
            fm = (np.arange(t)[None, :] < lens[:, None]).astype(
                np.float32)
            batches.append(DataSet(x, y, fm, fm.copy()))

        stream_net = net()
        stream_net.fit_stream(
            ListDataSetIterator(batches), scan_steps=2)
        seq_net = net()
        for ds in batches:
            seq_net.fit(ds)
        for a, c in zip(jax.tree.leaves(stream_net.params),
                        jax.tree.leaves(seq_net.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-6)

    def test_graph_fit_stream_matches_sequential(self):
        """ComputationGraph.fit_stream == sequential graph fit on the
        same batches — including a multi-input graph and a ragged
        tail."""
        import jax

        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.datasets.iterator import (
            ListDataSetIterator,
        )
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.graph_conf import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.ops.losses import LossFunction

        def net():
            conf = (
                NeuralNetConfiguration.Builder()
                .seed(9).learning_rate(0.05)
                .graph_builder()
                .add_inputs("a", "b")
                .add_layer("da", L.DenseLayer(n_in=4, n_out=5,
                                              activation="relu"), "a")
                .add_layer("db", L.DenseLayer(n_in=3, n_out=5,
                                              activation="tanh"), "b")
                .add_vertex("m", MergeVertex(), "da", "db")
                .add_layer("out", L.OutputLayer(
                    n_in=10, n_out=2, activation="softmax",
                    loss_function=LossFunction.MCXENT), "m")
                .set_outputs("out").build())
            return ComputationGraph(conf).init()

        rng = np.random.default_rng(8)
        batches = []
        for n in [6, 6, 6, 6, 6, 4]:  # ragged final batch
            xa = rng.normal(size=(n, 4)).astype(np.float32)
            xb = rng.normal(size=(n, 3)).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
            batches.append(MultiDataSet([xa, xb], [y]))

        stream_net = net()
        scores = stream_net.fit_stream(
            ListDataSetIterator(batches), scan_steps=2)
        assert np.isfinite(np.asarray(scores)).all()
        seq_net = net()
        for b in batches:
            seq_net.fit(b)
        assert stream_net.iteration == seq_net.iteration
        for x, y2 in zip(jax.tree.leaves(stream_net.params),
                         jax.tree.leaves(seq_net.params)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y2), rtol=1e-5, atol=1e-6)

    def test_graph_ragged_tail_applies_ingest(self):
        """Ragged tails must go through the SAME ingest transforms as
        fused windows — otherwise a u8/ids stream trains its tail on
        raw wire data."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.iterator import (
            ListDataSetIterator,
        )
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.ops.losses import LossFunction

        def net():
            conf = (
                NeuralNetConfiguration.Builder()
                .seed(4).learning_rate(0.05)
                .graph_builder().add_inputs("x")
                .add_layer("d", L.DenseLayer(
                    n_in=6, n_out=8, activation="relu"), "x")
                .add_layer("out", L.OutputLayer(
                    n_in=8, n_out=2, activation="softmax",
                    loss_function=LossFunction.MCXENT), "d")
                .set_outputs("out").build())
            return ComputationGraph(conf).init()

        rng = np.random.default_rng(1)
        u8_batches, f32_batches = [], []
        for n in [8, 8, 8, 4]:  # 1 fused window of 2 + ragged (8, 4)
            xu8 = rng.integers(0, 255, (n, 6), np.uint8)
            y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
            u8_batches.append(DataSet(xu8, y))
            f32_batches.append(
                DataSet(xu8.astype(np.float32) / 255.0, y))

        ingest = jax.jit(lambda d: {
            k: v.astype(jnp.float32) / 255.0 for k, v in d.items()})
        stream_net = net()
        stream_net.fit_stream(ListDataSetIterator(u8_batches),
                              scan_steps=2, ingest=ingest)
        seq_net = net()
        for b in f32_batches:
            seq_net.fit(b)
        assert stream_net.iteration == seq_net.iteration == 4
        for a, c in zip(jax.tree.leaves(stream_net.params),
                        jax.tree.leaves(seq_net.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-6)

    def test_token_stream_lm_learns(self, tmp_path):
        """End-to-end LM host-fed path: token ids on disk, one-hot on
        device, loss decreases on a learnable Markov language."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.markov import (
            make_chain,
            sample_tokens,
        )
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        V, T = 16, 12
        chain, _, floor = make_chain(V, seed=0)
        toks = sample_tokens(chain, 64, T, seed=1)
        path = str(tmp_path / "lm.bin")
        write_token_file(path, toks, vocab=V)

        net = MultiLayerNetwork(transformer_lm(
            n_in=V, width=32, n_layers=1, n_heads=2, n_classes=V,
            lr=1e-2, seed=3)).init()
        one_hot = jax.jit(lambda ids: jax.nn.one_hot(
            ids, V, dtype=jnp.float32).transpose(0, 1, 3, 2))
        first = last = None
        for _ in range(12):
            it = NativeAsyncDataSetIterator(
                TokenSequenceFileIterator(path, batch_size=16),
                queue_size=4)
            scores = net.fit_stream(it, scan_steps=4, ingest=one_hot,
                                    ingest_labels=one_hot)
            vals = np.asarray(scores)
            if first is None:
                first = float(vals[0])
            last = float(vals[-1])
        assert last < first - 0.3, (first, last)
