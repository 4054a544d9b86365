"""Bundled real-data fixtures + the W2V batched-update stability fix
(round-4 review item 8 / missing #1: honest gates need real data).

The reference ships 13 MB of real fixtures (dl4j-test-resources);
datasets/fixtures mirrors the two that matter for gates: 200 real MNIST
digits (mnist_first_200.txt -> IDX) and the 97k-sentence raw_sentences
corpus the reference's Word2VecTests train on. sklearn's bundled
digits (1,797 real images) complete the set.
"""

import numpy as np

from deeplearning4j_tpu.datasets.fixtures import (
    digits_dataset,
    mnist200_datasets,
    raw_sentences,
)


class TestFixtureLoaders:
    def test_mnist200_shapes_and_split(self):
        tr, te = mnist200_datasets(n_test=40, seed=0)
        assert tr.features.shape == (160, 784)
        assert te.features.shape == (40, 784)
        assert tr.labels.shape == (160, 10)
        f = np.asarray(tr.features)
        assert 0.0 <= f.min() and f.max() <= 1.0
        # real data: pixel histogram is bimodal (ink vs paper), unlike
        # the synthetic fallback's smooth jitter
        assert (f == 0).mean() > 0.5
        # deterministic split
        tr2, _ = mnist200_datasets(n_test=40, seed=0)
        np.testing.assert_array_equal(
            np.asarray(tr.features), np.asarray(tr2.features))

    def test_digits_dataset(self):
        tr, te = digits_dataset()
        assert tr.features.shape[1] == 64
        assert tr.features.shape[0] + te.features.shape[0] == 1797

    def test_raw_sentences_corpus(self):
        s = raw_sentences(limit=1000)
        assert len(s) == 1000
        assert any("day" in ln.lower() for ln in s)
        assert all(isinstance(ln, str) and ln for ln in s)


class TestRealDataTraining:
    def test_mlp_learns_real_digits(self):
        """Held-out accuracy on REAL images — the gate an earlier
        round's bench.py used."""
        from deeplearning4j_tpu.models.zoo import mlp
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        tr, te = digits_dataset()
        net = MultiLayerNetwork(mlp(sizes=(64, 128, 10), lr=0.3)).init()
        for _ in range(40):
            net.fit(tr)
        acc = float(net.evaluate([te]).accuracy())
        assert acc >= 0.9, f"real-digits held-out accuracy {acc}"


class TestW2VBatchedStability:
    """The MAX_EXP clamp (sequence_vectors.py _hs_inner/_ns_inner):
    without it, batched scatter-add training on REAL text frequency
    distributions diverges to NaN (hot Huffman roots / hot negatives
    accumulate thousands of same-sign stale-value updates per batch).
    The zipf-synthetic benches never developed it; the bundled real
    corpus does, within a few thousand sentences."""

    def _train(self, **kw):
        from deeplearning4j_tpu.nlp.word2vec import Word2Vec

        sents = raw_sentences(limit=6000)
        w2v = Word2Vec(layer_size=32, window=5, min_word_frequency=5,
                       batch_size=2048, seed=3, subsampling=1e-3, **kw)
        w2v.build_vocab_from(sents)
        w2v.fit(sents)
        return w2v

    def test_hs_stays_finite_on_real_text(self):
        w2v = self._train(use_hierarchic_softmax=True, negative=0)
        syn0 = np.asarray(w2v.syn0)
        assert np.isfinite(syn0).all()
        assert float(np.abs(syn0).max()) < 50.0
        assert np.isfinite(w2v.similarity("day", "night"))

    def test_ns_stays_finite_on_real_text(self):
        w2v = self._train(use_hierarchic_softmax=False, negative=5)
        syn0 = np.asarray(w2v.syn0)
        assert np.isfinite(syn0).all()
        assert float(np.abs(syn0).max()) < 50.0
