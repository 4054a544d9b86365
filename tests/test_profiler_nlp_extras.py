"""Tests: Hessian-free optimizer, tracer/profiler, inverted index,
document iterators/windows, plot renderers."""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.nlp.document_iterator import (
    PAD,
    CollectionDocumentIterator,
    FileDocumentIterator,
    LabelAwareDocumentIterator,
    windows,
)
from deeplearning4j_tpu.nlp.inverted_index import InvertedIndex
from deeplearning4j_tpu.profiler import Tracer


def _net(algo=None, iterations=5):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.enums import OptimizationAlgorithm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.losses import LossFunction

    b = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
         .iterations(iterations))
    if algo is not None:
        b = b.optimization_algo(algo)
    conf = (b.list()
            .layer(0, L.DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                    loss_function=LossFunction.MCXENT))
            .build())
    return MultiLayerNetwork(conf).init()


class TestHessianFree:
    def test_reduces_loss_on_iris(self):
        from deeplearning4j_tpu.datasets.iris import iris_dataset
        from deeplearning4j_tpu.nn.conf.enums import OptimizationAlgorithm

        ds = iris_dataset()
        net = _net(OptimizationAlgorithm.HESSIAN_FREE, iterations=15)
        before = net.score(ds)
        net.fit(ds)
        after = net.score(ds)
        assert after < before * 0.7

    def test_direction_is_descent(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.iris import iris_dataset
        from deeplearning4j_tpu.optimize.solver import (
            FlatProblem,
            StochasticHessianFree,
        )

        net = _net()
        opt = StochasticHessianFree(net, max_iterations=1)
        problem = FlatProblem(net, iris_dataset())
        opt._problem = problem
        score, grad = problem.value_and_grad(problem.x0)
        d = opt.direction(problem.x0, grad, 0)
        assert float(jnp.vdot(grad, d)) < 0  # descent direction

    def test_hvp_matches_finite_difference(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.iris import iris_dataset
        from deeplearning4j_tpu.optimize.solver import FlatProblem

        net = _net()
        problem = FlatProblem(net, iris_dataset())
        x = problem.x0
        rng = np.random.default_rng(0)
        v = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
        v = v / jnp.linalg.norm(v)
        eps = 1e-3
        _, gp = problem.value_and_grad(x + eps * v)
        _, gm = problem.value_and_grad(x - eps * v)
        fd = (gp - gm) / (2 * eps)
        hv = problem.hessian_vector_product(x, v)
        # loose tolerance: float32 finite differences
        assert float(jnp.linalg.norm(hv - fd)) < 0.05 * (
            1.0 + float(jnp.linalg.norm(fd)))


class TestTracer:
    def test_spans_and_save(self, tmp_path):
        tracer = Tracer()
        with tracer.span("work", kind="test"):
            pass
        tracer.counter("score", 1.5)
        tracer.instant("marker")
        spans = tracer.spans("work")
        assert len(spans) == 1 and spans[0]["dur"] >= 0
        out = tmp_path / "trace.json"
        tracer.save(str(out))
        data = json.loads(out.read_text())
        names = {e["name"] for e in data["traceEvents"]}
        assert names == {"work", "score", "marker"}


class TestInvertedIndex:
    def _index(self):
        idx = InvertedIndex()
        idx.add_doc("the cat sat on the mat".split(), label="a")
        idx.add_doc("the dog sat".split(), label="b")
        idx.add_doc("cats and dogs".split())
        return idx

    def test_postings_and_df(self):
        idx = self._index()
        assert idx.num_documents() == 3
        assert idx.documents_containing("sat") == [0, 1]
        assert idx.document_frequency("the") == 2
        assert idx.documents_containing("ghost") == []
        assert idx.label(1) == "b" and idx.label(2) is None

    def test_tfidf_and_search(self):
        idx = self._index()
        # 'cat' appears only in doc 0
        assert idx.tfidf("cat", 0) > 0
        assert idx.tfidf("cat", 1) == 0.0
        ranked = idx.search(["cat", "mat"])
        assert ranked[0][0] == 0
        assert idx.search(["ghost"]) == []

    def test_sample_batch(self):
        idx = self._index()
        batch = idx.sample_batch(2, np.random.default_rng(0))
        assert len(batch) == 2
        assert all(isinstance(d, list) for d in batch)


class TestDocumentIterators:
    def test_collection_iterator(self):
        it = CollectionDocumentIterator(["a", "b"])
        assert list(it) == ["a", "b"]
        assert list(it) == ["a", "b"]  # reset on iter

    def test_file_iterator(self, tmp_path):
        (tmp_path / "1.txt").write_text("first doc")
        (tmp_path / "sub").mkdir()
        (tmp_path / "sub" / "2.txt").write_text("second doc")
        (tmp_path / "skip.bin").write_text("nope")
        it = FileDocumentIterator(str(tmp_path))
        assert list(it) == ["first doc", "second doc"]

    def test_label_aware(self):
        it = LabelAwareDocumentIterator(["x", "y"], ["pos", "neg"])
        it.reset()
        it.next_document()
        assert it.current_label() == "pos"
        it.next_document()
        assert it.current_label() == "neg"
        with pytest.raises(ValueError):
            LabelAwareDocumentIterator(["x"], ["a", "b"])

    def test_windows_padding_and_focus(self):
        ws = windows("a b c".split(), window_size=3)
        assert len(ws) == 3
        assert ws[0].tokens == [PAD, "a", "b"]
        assert ws[0].focus_word() == "a"
        assert ws[2].tokens == ["b", "c", PAD]
        with pytest.raises(ValueError):
            windows(["a"], window_size=2)  # even size


class TestRenderers:
    def test_render_scatter(self, tmp_path):
        from deeplearning4j_tpu.plot.renderers import render_scatter

        rng = np.random.default_rng(0)
        coords = rng.normal(size=(50, 2))
        labels = rng.integers(0, 3, 50)
        path = render_scatter(coords, labels,
                              str(tmp_path / "scatter.png"))
        assert os.path.getsize(path) > 500

    def test_plot_filters_grid(self, tmp_path):
        from PIL import Image

        from deeplearning4j_tpu.plot.renderers import PlotFilters

        rng = np.random.default_rng(1)
        weights = rng.normal(size=(9, 16))
        path = PlotFilters((4, 4)).render(weights,
                                          str(tmp_path / "filters.png"))
        img = Image.open(path)
        assert img.size == (3 * 5 + 1, 3 * 5 + 1)

    def test_plot_filters_shape_check(self, tmp_path):
        from deeplearning4j_tpu.plot.renderers import PlotFilters

        with pytest.raises(ValueError):
            PlotFilters((4, 4)).render(np.zeros((2, 10)), "x.png")
