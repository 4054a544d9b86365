"""UI observability tests: storage, server endpoints, listeners.

Reference pattern: deeplearning4j-ui is exercised via listener POSTs into
the REST resources; here a live localhost server + in-process storage."""

import numpy as np

from deeplearning4j_tpu.ui import (
    ActivationIterationListener,
    FlowIterationListener,
    HistogramIterationListener,
    HistoryStorage,
    UiClient,
    UiServer,
)
from deeplearning4j_tpu.ui.storage import histogram


def _tiny_net():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.losses import LossFunction

    conf = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
            .list()
            .layer(0, L.DenseLayer(n_in=5, n_out=8, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=8, n_out=2, activation="softmax",
                                    loss_function=LossFunction.MCXENT))
            .build())
    return MultiLayerNetwork(conf).init()


class TestHistoryStorage:
    def test_put_get_since(self):
        st = HistoryStorage()
        for i in range(5):
            st.put("score", i, float(i))
        assert st.get("score") == [(i, float(i)) for i in range(5)]
        assert st.get("score", since=2) == [(3, 3.0), (4, 4.0)]
        assert st.latest("score") == (4, 4.0)
        assert st.keys() == ["score"]

    def test_retention_bound(self):
        st = HistoryStorage(max_points=3)
        for i in range(10):
            st.put("k", i, i)
        assert [i for i, _ in st.get("k")] == [7, 8, 9]

    def test_histogram_shape(self):
        h = histogram(np.random.default_rng(0).normal(size=100), bins=10)
        assert len(h["counts"]) == 10
        assert len(h["edges"]) == 11
        assert sum(h["counts"]) == 100


class TestListeners:
    def test_histogram_listener_records_score_and_params(self):
        st = HistoryStorage()
        net = _tiny_net()
        net.set_listeners(HistogramIterationListener(st))
        X = np.random.default_rng(1).normal(size=(16, 5)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[np.arange(16) % 2]
        net.fit(X, y)
        assert len(st.get("score")) >= 1
        hist_keys = [k for k in st.keys() if k.startswith("histogram/")]
        assert hist_keys  # one per param tensor
        _, h = st.latest(hist_keys[0])
        assert sum(h["counts"]) > 0

    def test_flow_and_activation_listeners(self):
        st = HistoryStorage()
        net = _tiny_net()
        X = np.random.default_rng(2).normal(size=(8, 5)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[np.arange(8) % 2]
        net.set_listeners(FlowIterationListener(st),
                          ActivationIterationListener(st, X))
        net.fit(X, y)
        _, flow = st.latest("flow")
        assert [l["type"] for l in flow["layers"]] == [
            "DenseLayer", "OutputLayer"]
        assert flow["num_params"] == 5 * 8 + 8 + 8 * 2 + 2
        _, acts = st.latest("activations")
        assert len(acts) >= 2 and all(a >= 0 for a in acts)

    def test_flow_listener_probe_adds_act_stats(self):
        st = HistoryStorage()
        net = _tiny_net()
        X = np.random.default_rng(3).normal(size=(8, 5)).astype(
            np.float32)
        y = np.eye(2, dtype=np.float32)[np.arange(8) % 2]
        net.set_listeners(FlowIterationListener(st, probe_features=X))
        net.fit(X, y)
        _, flow = st.latest("flow")
        for layer in flow["layers"]:
            assert layer["activation_mean"] >= 0
            assert "activation_std" in layer

    def test_flow_listener_graph_dag(self):
        """ComputationGraph DAG: vertices ship in topological order
        with their input edges and per-vertex activation stats
        (round-5 review next #7)."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.graph_conf import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.ops.losses import LossFunction

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(5)
            .learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d1", L.DenseLayer(n_in=4, n_out=6,
                                          activation="relu"), "in")
            .add_layer("d2", L.DenseLayer(n_in=4, n_out=6,
                                          activation="tanh"), "in")
            .add_vertex("merge", MergeVertex(), "d1", "d2")
            .add_layer("out", L.OutputLayer(
                n_in=12, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT), "merge")
            .set_outputs("out")
            .build()
        )
        net = ComputationGraph(conf).init()
        X = np.random.default_rng(4).normal(size=(8, 4)).astype(
            np.float32)
        y = np.eye(3, dtype=np.float32)[np.arange(8) % 3]
        st = HistoryStorage()
        net.set_listeners(FlowIterationListener(st, probe_features=X))
        net.fit(X, y)
        _, flow = st.latest("flow")
        assert flow["inputs"] == ["in"] and flow["outputs"] == ["out"]
        names = [v["name"] for v in flow["vertices"]]
        assert set(names) == {"d1", "d2", "merge", "out"}
        assert names.index("merge") > names.index("d1")
        assert names.index("out") > names.index("merge")
        by_name = {v["name"]: v for v in flow["vertices"]}
        assert sorted(by_name["merge"]["inputs"]) == ["d1", "d2"]
        assert by_name["d1"]["inputs"] == ["in"]
        assert by_name["d1"]["n_params"] == 4 * 6 + 6
        for v in flow["vertices"]:
            assert v["activation_mean"] >= 0, v
        assert flow["num_params"] == 2 * (4 * 6 + 6) + 12 * 3 + 3


class TestUiServer:
    def setup_method(self):
        self.server = UiServer().start()
        self.client = UiClient(self.server.address)

    def teardown_method(self):
        self.server.stop()

    def test_update_and_series_roundtrip(self):
        self.client.put("score", 1, 0.5)
        self.client.put("score", 2, 0.25)
        assert self.client.get_series("score") == [(1, 0.5), (2, 0.25)]
        assert self.client.get_series("score", since=1) == [(2, 0.25)]

    def test_remote_listener_feeds_server(self):
        net = _tiny_net()
        net.set_listeners(HistogramIterationListener(self.client))
        X = np.random.default_rng(4).normal(size=(8, 5)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[np.arange(8) % 2]
        net.fit(X, y)
        assert len(self.client.get_series("score")) >= 1

    def test_nearest_neighbors_endpoint(self):
        rng = np.random.default_rng(5)
        base = rng.normal(size=16)
        vecs = [base + rng.normal(scale=0.01, size=16) for _ in range(3)]
        vecs.append(-base)  # the odd one out
        labels = ["king", "queen", "prince", "banana"]
        self.client.set_vectors(labels, np.stack(vecs))
        near = self.client.nearest("king", k=2)
        assert "banana" not in near
        assert set(near) <= {"queen", "prince"}

    def test_dashboard_served(self):
        import urllib.request

        with urllib.request.urlopen(self.server.address + "/") as resp:
            html = resp.read().decode()
        assert "dashboard" in html
        # the view renderers ship in the page: scatter (t-SNE), chain
        # flow, and the ComputationGraph DAG flow
        for fn in ("function scatter", "function flow",
                   "function dagflow", "v.vertices"):
            assert fn in html

    def test_graph_flow_roundtrip(self):
        """A DAG flow payload POSTed by a remote listener comes back
        intact through /series (endpoint-tested per review #7)."""
        payload = {
            "vertices": [
                {"name": "d1", "type": "DenseLayer", "inputs": ["in"],
                 "activation_mean": 0.5},
                {"name": "out", "type": "OutputLayer",
                 "inputs": ["d1"]},
            ],
            "inputs": ["in"], "outputs": ["out"], "num_params": 7,
        }
        self.client.put("flow", 3, payload)
        pts = self.client.get_series("flow")
        assert pts[-1][0] == 3
        got = pts[-1][1]
        assert [v["name"] for v in got["vertices"]] == ["d1", "out"]
        assert got["outputs"] == ["out"]


class TestIncrementalPolling:
    def test_offset_and_counts(self):
        from deeplearning4j_tpu.ui.storage import HistoryStorage

        st = HistoryStorage(max_points=5)
        for i in range(8):
            st.put("score", i, float(i))
        # 3 oldest trimmed; global offsets still line up
        assert st.counts()["score"] == 8
        assert [i for i, _ in st.get_from("score", 0)] == [3, 4, 5, 6, 7]
        assert [i for i, _ in st.get_from("score", 6)] == [6, 7]
        assert st.get_from("score", 8) == []
        # duplicate iteration numbers are preserved (count-based, not
        # iteration-based)
        st.put("score", 7, 99.0)
        assert [p for _, p in st.get_from("score", 8)] == [99.0]

    def test_server_endpoints(self):
        import json
        import urllib.request

        from deeplearning4j_tpu.ui.server import UiServer

        server = UiServer()
        server.start()
        try:
            for i in range(4):
                server.storage.put("s", i, float(i))
            ks = json.loads(urllib.request.urlopen(
                server.address + "/keys").read())
            assert ks["counts"]["s"] == 4
            got = json.loads(urllib.request.urlopen(
                server.address + "/series?key=s&offset=2").read())
            assert [i for i, _ in got["points"]] == [2, 3]
        finally:
            server.stop()


class TestRenderPayloads:
    """The three round-1-missing view types (review missing #5):
    activation/filter image grids, t-SNE scatter, network flow."""

    def test_image_grid_normalizes_per_map(self):
        from deeplearning4j_tpu.ui.render import image_grid_payload

        maps = np.stack([
            np.linspace(0.0, 1.0, 16).reshape(4, 4),
            np.full((4, 4), 3.0),                    # constant map -> 0s
        ])
        p = image_grid_payload(maps)
        assert p["type"] == "image_grid" and (p["h"], p["w"]) == (4, 4)
        assert p["images"][0][0] == 0 and p["images"][0][-1] == 255
        assert set(p["images"][1]) == {0}

    def test_image_grid_takes_first_example_and_caps(self):
        from deeplearning4j_tpu.ui.render import image_grid_payload

        batch = np.random.default_rng(0).normal(size=(3, 40, 5, 6))
        p = image_grid_payload(batch, max_images=8)
        assert len(p["images"]) == 8 and (p["h"], p["w"]) == (5, 6)

    def test_filter_grid_shape(self):
        from deeplearning4j_tpu.ui.render import filter_grid_payload

        w = np.random.default_rng(1).normal(size=(12, 3, 5, 5))
        p = filter_grid_payload(w, max_images=16)
        assert len(p["images"]) == 12 and (p["h"], p["w"]) == (5, 5)

    def test_scatter_payload_with_labels(self):
        from deeplearning4j_tpu.ui.render import scatter_payload

        import pytest as _pytest

        p = scatter_payload([[0.0, 1.0], [2.5, -1.0]], ["a", "b"])
        assert p["type"] == "scatter"
        assert p["points"] == [[0.0, 1.0], [2.5, -1.0]]
        assert p["labels"] == ["a", "b"]
        with _pytest.raises(ValueError):
            scatter_payload([[1.0, 2.0, 3.0]])

    def test_activation_image_listener_on_conv_net(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ops.losses import LossFunction
        from deeplearning4j_tpu.ui.listeners import ActivationImageListener
        from deeplearning4j_tpu.ui.storage import HistoryStorage

        conf = (
            NeuralNetConfiguration.Builder()
            .seed(2)
            .list()
            .layer(0, L.ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                         activation="relu"))
            .layer(1, L.OutputLayer(n_out=3, activation="softmax",
                                    loss_function=LossFunction.MCXENT))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        store = HistoryStorage()
        probe = np.random.default_rng(3).normal(
            size=(2, 1, 8, 8)).astype(np.float32)
        ActivationImageListener(store, probe).iteration_done(net, 1)
        keys = set(store.keys())
        assert "activation_images/layer0" in keys
        assert any(k.startswith("filters/") for k in keys)
        grid = store.get("activation_images/layer0")[-1][1]
        assert grid["type"] == "image_grid"
        assert len(grid["images"]) == 4 and (grid["h"], grid["w"]) == (6, 6)
        fkey = next(k for k in keys if k.startswith("filters/"))
        fgrid = store.get(fkey)[-1][1]
        assert fgrid["type"] == "image_grid"
        assert (fgrid["h"], fgrid["w"]) == (3, 3)

    def test_tsne_scatter_roundtrip_through_server(self):
        from deeplearning4j_tpu.ui.render import publish_tsne
        from deeplearning4j_tpu.ui.server import UiClient, UiServer

        server = UiServer()
        server.start()
        try:
            client = UiClient(server.address)
            coords = np.asarray([[0.0, 0.0], [1.0, 2.0], [-1.0, 0.5]])
            publish_tsne(client, coords, ["x", "y", "z"], iteration=3)
            pts = client.get_series("tsne")
            payload = pts[-1][1]
            assert payload["type"] == "scatter"
            assert payload["labels"] == ["x", "y", "z"]
            assert len(payload["points"]) == 3
        finally:
            server.stop()

    def test_dashboard_has_all_three_renderers(self):
        import urllib.request

        from deeplearning4j_tpu.ui.server import UiServer

        server = UiServer()
        server.start()
        try:
            html = urllib.request.urlopen(
                server.address + "/", timeout=5).read().decode()
        finally:
            server.stop()
        # renderer functions + their dispatch tags all present
        for needle in ("function imageGrid", "function scatter",
                       "function flow", "image_grid", "v.layers",
                       "putImageData"):
            assert needle in html, needle


class TestDashboardInteractivity:
    """The dashboard's interactive pieces (flow hover/click detail,
    t-SNE iteration scrubber) — structural checks; no JS engine ships
    in this image, so balance and presence are the testable surface."""

    def test_dashboard_script_balanced_and_interactive(self):
        from deeplearning4j_tpu.ui.server import _DASHBOARD

        for piece in ("wireScrub", "_flowPin", "_flowHover",
                      "addEventListener('mousemove'",
                      "addEventListener('click'",
                      "input[type=range]"):
            assert piece in _DASHBOARD, piece
        script = _DASHBOARD.split("<script>")[1].split("</script>")[0]
        for op, cl in (("{", "}"), ("(", ")"), ("[", "]")):
            assert script.count(op) == script.count(cl), (op, cl)

    def test_flow_payload_carries_per_layer_detail(self):
        import numpy as np

        from deeplearning4j_tpu.models.zoo import mlp
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ui.listeners import FlowIterationListener

        class Sink:
            def put(self, key, it, payload):
                self.payload = payload

        sink = Sink()
        net = MultiLayerNetwork(mlp((20, 16, 4))).init()
        FlowIterationListener(sink).iteration_done(net, 0)
        layers = sink.payload["layers"]
        assert layers[0]["n_params"] == 20 * 16 + 16
        assert layers[0]["param_shapes"]["W"] == [20, 16]
        assert layers[0]["updater"]
        total = sum(l["n_params"] for l in layers)
        assert total == sink.payload["num_params"]
