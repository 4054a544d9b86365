"""The serving engine holds its weights at the compute dtype (ISSUE 32).

The contract under test: ``DecodeEngine`` applies the net's own cast
rule (``net.compute_params``) ONCE, at construction, adopts the net
(``net.params`` IS the engine's tree, the float32 masters are
released), and serves the tokens the net's stepping reference produced
from the masters; its programs hold no cast of a weight any more; a net
that is already resident at its compute dtype keeps every array it
had; and ``dl4j-tpu serve`` restores no optimizer moments for a net it
will only serve."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import DecodeEngine, Request

V = 12
CASES = [([1, 4, 7, 2], 9), ([9, 3, 3], 6), ([5, 2, 8, 1, 6, 0, 4], 11)]


def _net(compute_dtype="bfloat16", seed=7):
    conf = transformer_lm(n_in=V, width=32, n_layers=2, n_heads=4,
                          n_classes=V, seed=seed)
    for c in conf.confs:
        c.compute_dtype = compute_dtype
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 64
    return MultiLayerNetwork(conf).init()


def _stepped(net, prompt, n):
    """Greedy ids by the net's own stepping reference: the prompt, then
    a token a call, through ``rnn_time_step``."""
    eye = np.eye(V, dtype=np.float32)
    net.rnn_clear_previous_state()
    out = net.rnn_time_step(eye[prompt].T[None])
    toks = [int(jnp.argmax(out[0, :, -1]))]
    while len(toks) < n:
        out = net.rnn_time_step(eye[toks[-1]][None, :, None])
        toks.append(int(jnp.argmax(out[0, :, -1])))
    net.rnn_clear_previous_state()
    return toks


def _cell_net(cell: str, seed=5):
    """(net, engine options, configuration) of a served cell at its
    rehearsal sizes, as ``benchmark/serve_cell.py`` builds them."""
    _, cfg, _, model = common.find_cell(common.load_benchmark(), cell,
                                        True)
    dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
    return model.build_net(cfg, seed), dep, cfg


def test_masters_are_cast_once_and_the_net_is_adopted():
    net = _net()
    last = str(net.n_layers - 1)
    want = [_stepped(net, p, n) for p, n in CASES]   # from the masters
    masters = sum(leaf.nbytes for key, sub in net.params.items()
                  if key != last for leaf in jax.tree.leaves(sub))
    head = dict(net.params[last])
    tracer = Tracer()
    eng = DecodeEngine(net, n_slots=2, decode_chunk=3, tracer=tracer)

    assert eng._params is net.params
    for key, sub in eng._params.items():
        for name, leaf in sub.items():
            assert leaf.dtype == (jnp.float32 if key == last
                                  else jnp.bfloat16), (key, name)
    # the head keeps its master dtype AND its arrays
    assert all(eng._params[last][k] is v for k, v in head.items())
    assert eng.stats["param_bytes_cast"] == masters
    assert eng.stats["param_bytes"] == masters // 2 + sum(
        v.nbytes for v in head.values())
    assert len(tracer.spans("serving.weights_cast")) == 1

    ids = [eng.submit(Request(list(p), n)) for p, n in CASES]
    res = eng.run()
    assert [res[i].tokens for i in ids] == want
    # the adopted net steps as it did from the masters
    assert [_stepped(net, p, n) for p, n in CASES] == want
    # a second engine over the adopted net finds nothing to cast
    again = DecodeEngine(net, n_slots=2, decode_chunk=3)
    assert again.stats["param_bytes_cast"] == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(again._params),
                                      jax.tree.leaves(eng._params)))


class _Lowered:
    """Stands in for one of the engine's jitted programs and keeps the
    text its first call lowers to."""

    def __init__(self, fn):
        self.fn, self.text = fn, None

    def __call__(self, *args):
        if self.text is None:
            self.text = self.fn.lower(*args).as_text()
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def test_the_blocks_programs_cast_no_weight():
    """The lowered ``decode`` and cold ``prefill`` of the block's
    rehearsal configuration: no ``convert`` from a float32 operand of a
    weight's shape is left in either."""
    net, dep, cfg = _cell_net("cgpt1p3b-serve.chat-steady")
    shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(net.params)
              if leaf.dtype == jnp.float32 and leaf.ndim}
    flash = dep.pop("use_flash_paged", None)
    eng = DecodeEngine(net, seed=5, use_flash_paged=flash, **dep)
    assert eng.stats["param_bytes_cast"] > 0
    eng._prefill_jit = _Lowered(eng._prefill_jit)
    eng._decode_jit = _Lowered(eng._decode_jit)
    rng = np.random.default_rng(1)
    ids = [eng.submit(Request(
        rng.integers(0, cfg["vocab_size"], n).tolist(), 6))
        for n in (9, 40)]
    res = eng.run()
    assert all(len(res[i].tokens) == 6 for i in ids)
    convert = re.compile(
        r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)xf32>\) -> "
        r"tensor<[0-9x]+xbf16>")
    for program in (eng._prefill_jit, eng._decode_jit):
        assert "stablehlo.dot_general" in program.text
        cast = {tuple(int(d) for d in m.split("x"))
                for m in convert.findall(program.text)}
        assert not cast & shapes, sorted(cast & shapes)


def _f32_net():
    return _net(compute_dtype=None), dict(n_slots=2, decode_chunk=3)


def _fixture_net(cell):
    net, dep, _ = _cell_net(cell)
    dep["use_flash_paged"] = False     # the plain programs: identity
    return net, dep                    # is a property of the weights


@pytest.mark.parametrize("build", [
    pytest.param(_f32_net, id="compute_dtype_none"),
    pytest.param(lambda: _fixture_net("granite4hs-serve.chat-steady-g4hs"), id="granite"),
    pytest.param(lambda: _fixture_net("trinity-large-serve.docs-mixed-tlp"), id="afmoe"),
])
def test_a_net_resident_at_its_compute_dtype_is_left_as_it_is(build):
    net, kw = build()
    assert net._compute_dtype is None
    before = {key: dict(sub) for key, sub in net.params.items()}
    eng = DecodeEngine(net, **kw)
    assert eng._params is net.params
    assert eng.stats["param_bytes_cast"] == 0
    assert eng.stats["param_bytes"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(before))
    for key, sub in before.items():
        assert eng._params[key].keys() == sub.keys()
        for name, leaf in sub.items():
            assert eng._params[key][name] is leaf, (key, name)


def test_serve_restores_no_moments_and_training_restores_them(tmp_path):
    from deeplearning4j_tpu.cli.driver import (
        build_parser,
        gateway_from_args,
    )
    from deeplearning4j_tpu.util.model_serializer import (
        restore_model,
        write_model,
    )

    net = _net()
    path = str(tmp_path / "model.zip")
    write_model(net, path)
    trained = restore_model(path)
    assert (jax.tree.structure(trained.updater_state)
            == jax.tree.structure(net.updater_state))
    assert jax.tree.leaves(trained.updater_state)
    assert restore_model(path, updater_state=False).updater_state == {}

    args = build_parser().parse_args(
        ["serve", "--model", path, "--port", "0", "--slots", "2"])
    gw = gateway_from_args(args).start()
    try:
        served = gw.engine.net
        assert served.updater_state == {}
        assert gw.engine.stats["param_bytes_cast"] > 0
        assert gw.engine._params is served.params
    finally:
        gw.close()
