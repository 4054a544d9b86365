"""The program names its parts in the device trace
(``deeplearning4j_tpu/profiler/scopes.py``): for the tiny net of each
block kind (the pre-LN block; the hybrid block with Mamba-2, with the
short convolution, with experts, with window and full attention), trained
and through the engine's programs,

(a) every product, convolution, custom call, sort, gather and scatter of
    the program carries a group of the vocabulary in its ``op_name``, as
    ``benchmark/opscopes.py`` cuts one, and an engine program's paths
    start with its phase;
(b) the lowered text without locations is byte for byte what it is with
    every scope a null context: a scope is metadata only.

Nothing runs and nothing is compiled: the nets hold shapes in place of
weights, an engine's programs are stood in for by recorders that keep
what each is first called with and hand back zeros, and the paths are
read from the unoptimized HLO (an inner ``jit`` is a ``call`` there,
whose ``op_name`` XLA's inliner prefixes to the callee's).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, opscopes
from deeplearning4j_tpu.profiler import scopes
from deeplearning4j_tpu.profiler.scopes import scope
from deeplearning4j_tpu.serving import DecodeEngine, Request

TRAINED = ["cgpt1p3b-train.train-step", "lfm2-8b-a1b-train.moe-step-8k"]
SERVED = ["cgpt1p3b-serve.chat-steady",
          "granite4hs-serve.chat-steady-g4hs",
          "trinity-large-serve.docs-mixed-tlp"]
PROGRAMS = ("_prefill_jit", "_chunk_jit", "_decode_jit", "_scatter_jit",
            "_tok_jit", "_state_admit_jit")
PHASE = {"_decode_jit": "decode"}          # every other: admit
#: the groups whose work a cell's programs must show
GROUPS_OF = {
    TRAINED[0]: {"attn", "ffn", "head", "embed"},
    TRAINED[1]: {"attn", "ffn", "moe", "mixer", "head", "embed"},
    SERVED[0]: {"attn", "ffn", "head", "embed"},
    SERVED[1]: {"attn", "moe", "mixer", "head", "embed"},
    SERVED[2]: {"attn", "ffn", "moe", "head", "embed"}}
#: the operations that do a layer's work: all of them must be named
WORK = ("dot", "convolution", "custom-call", "sort", "gather", "scatter")


def test_a_name_outside_the_vocabulary_is_refused_where_it_is_written():
    with pytest.raises(ValueError, match="atn"):
        scope("atn")
    with pytest.raises(ValueError):
        scope("attn/experts")           # a child of another group
    with scope("attn/qkv"), scope("decode"), scope("update/step"):
        pass
    for group, children in scopes.GROUPS.items():
        assert not set(children) & set(scopes.GROUPS), group
    assert not set(scopes.PHASES) & set(scopes.GROUPS)


# ---------------------------------------------------------------------
# nets of shapes, programs that are lowered and never run
# ---------------------------------------------------------------------
def _struct(a, dtype=None):
    return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)


def _net_of_shapes(cell, optimizer=False):
    """The cell's rehearsal net on the benchmark's normal path, its
    weights' shapes in place of its weights (held at the compute dtype
    where an engine would cast them), the plain programs in place of
    the Pallas kernels (interpreted, those lower for seconds)."""
    _, cfg, mix, model = common.find_cell(common.load_benchmark(), cell,
                                          True)
    cfg = dict(cfg, kernels=None)
    make = model.weights.make_params
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model.weights, "make_params",
                      lambda *a: jax.eval_shape(lambda: make(*a)))
        net = model.build_net(cfg, 5)
    if not optimizer:
        cd, head = net._compute_dtype, str(net.n_layers - 1)
        net.params = {
            k: {n: _struct(leaf, None if k == head or not jnp.issubdtype(
                leaf.dtype, jnp.floating) else cd)
                for n, leaf in sub.items()}
            for k, sub in net.params.items()}
    return net, cfg, mix


class _Recorder:
    """Stands in for one of the engine's jitted programs: keeps what its
    first call lowers to and the shapes it was called with, and hands
    back zeros of the program's output shapes."""

    def __init__(self, fn):
        self.fn, self.lowered, self.args = fn, None, None

    def __call__(self, *args):
        if self.lowered is None:
            self.args = jax.tree.map(_struct, args)
            self.lowered = self.fn.lower(*args)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(self.fn, *args))

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _engine(cell):
    net, cfg, _ = _net_of_shapes(cell)
    dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
    dep["use_flash_paged"] = False
    eng = DecodeEngine(net, seed=5, **dep)
    for name in PROGRAMS:
        if getattr(eng, name) is not None:
            setattr(eng, name, _Recorder(getattr(eng, name)))
    rng = np.random.default_rng(1)
    eng.submit(Request(rng.integers(0, cfg["vocab_size"], 9).tolist(), 3))
    eng.run()
    return eng


def _train_step(cell):
    """(the jitted scanned step, the shapes one call takes)."""
    net, cfg, mix = _net_of_shapes(cell, optimizer=True)
    moments = {si: ({"m": sub, "v": sub} if sub else {})
               for si, sub in net.params.items()}
    b, t, v = 2, 16, cfg["vocab_size"]
    x = (jax.ShapeDtypeStruct((1, b, t), jnp.int32) if net.takes_token_ids
         else jax.ShapeDtypeStruct((1, b, v, t), jnp.uint8))
    key = jax.eval_shape(lambda: jax.random.key(0))
    return net._train_steps_scan, (net.params, net.state, moments, 0, key,
                                   x, x, 1.0)


@pytest.fixture(scope="module")
def lowered():
    """``{(cell, program): (jitted function, argument shapes, what it
    lowered to)}`` of every program of the five rehearsal nets."""
    out = {}
    for cell in TRAINED:
        step, args = _train_step(cell)
        out[cell, "steps"] = (step, args, step.lower(*args))
    for cell in SERVED:
        eng = _engine(cell)
        for name in PROGRAMS:
            rec = getattr(eng, name)
            if rec is not None and rec.lowered is not None:
                out[cell, name] = (rec.fn, rec.args, rec.lowered)
    return out


# ---------------------------------------------------------------------
# (a) every operation that does a layer's work is named
# ---------------------------------------------------------------------
_INSTRUCTION = re.compile(          # (a tuple's type holds spaces)
    r"^\s*(?:ROOT )?[\w.\-]+ = .*? ([a-z][a-z\-]*)\(")
_CALLED = re.compile(
    r"(?:to_apply|body|condition|calls)=([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def _paths(lowered_program):
    """``[(opcode, op_name)]`` of the program's unoptimized HLO, a
    called computation's names behind its call's (as XLA's inliner
    puts them)."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    text = lowered_program.compiler_ir("hlo").as_hlo_module().to_string(
        opts)
    computations, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?([\w.\-]+) (?:\(.*\) -> .* )?\{$",
                        line)
        if head:
            name = head.group(1)
            computations[name] = []
            entry = name if line.startswith("ENTRY") else None
            if entry:
                root = entry
            continue
        m = _INSTRUCTION.match(line)
        if m and name:
            op = re.search(r'op_name="([^"]*)"', line)
            called = [c for a, b in _CALLED.findall(line)
                      for c in ([a] if a else re.split(r",\s*", b))]
            # (an argument's sharding annotation is no work)
            opcode = ("sharding" if 'custom_call_target="Sharding"' in line
                      else m.group(1))
            computations[name].append(
                (opcode, op.group(1) if op else "", called))
    out, seen, todo = [], set(), [(root, "")]
    while todo:
        comp, prefix = todo.pop()
        if (comp, prefix) in seen:
            continue
        seen.add((comp, prefix))
        for opcode, op_name, called in computations[comp]:
            full = "/".join(p for p in (prefix, op_name) if p)
            out.append((opcode, full))
            for c in called:
                todo.append((c, full if opcode == "call" else prefix))
    return out


@pytest.mark.parametrize("cell", TRAINED + SERVED)
def test_every_working_operation_carries_a_group(lowered, cell):
    programs = {k[1]: v for k, v in lowered.items() if k[0] == cell}
    assert programs
    assert cell in TRAINED or {"_decode_jit", "_tok_jit"} <= set(programs)
    groups = set()
    for name, (_, _, low) in programs.items():
        # (the two programs that only write a row into a slot)
        kinds = (("dynamic-update-slice",) if name in (
            "_tok_jit", "_state_admit_jit") else WORK)
        work = [(op, path) for op, path in _paths(low) if op in kinds]
        assert work, name
        for op, path in work:
            phase, group, child, _ = opscopes.cut(path)
            assert group is not None, (name, op, path)
            groups.add(group)
            if cell in SERVED:
                assert phase == PHASE.get(name, "admit"), (name, path)
    assert GROUPS_OF[cell] <= groups


def test_a_gradient_wraps_a_scope_and_the_reader_takes_it_off(lowered):
    _, _, low = lowered["lfm2-8b-a1b-train.moe-step-8k", "steps"]
    paths = [p for _, p in _paths(low)]
    back = [p for p in paths if "transpose(jvp(moe))/experts" in p]
    assert back and all(opscopes.cut(p) == (None, "moe", "experts", True)
                        for p in back)
    assert any(opscopes.cut(p)[1:3] == ("update", "step") for p in paths)
    assert any(opscopes.cut(p)[1:3] == ("head", "loss") for p in paths)


# ---------------------------------------------------------------------
# the library's attention program, which no scope of ours wraps
# ---------------------------------------------------------------------
@pytest.mark.parametrize("entry", ["jit(_splash_attention)",
                                   "jit(flash_attention)"])
def test_a_library_entry_stands_for_the_attention_core(entry):
    assert scopes.LIBRARY_SCOPES[entry] == "attn/core"
    forward = f"jit(steps)/while/body/jvp(vmap({entry}))/pallas_call"
    assert opscopes.cut(forward) == (None, "attn", "core", False)
    backward = (f"jit(steps)/while/body/transpose(jvp(vmap({entry})))/"
                "splash_mha_dkv_no_residuals/pallas_call")
    assert opscopes.cut(backward) == (None, "attn", "core", True)


@pytest.mark.parametrize("kv_heads", [4, 2])   # MHA; a group a KV head
def test_the_flash_programs_kernels_fall_under_the_attention_core(
        kv_heads):
    """Read from the program, not written down: the kernels
    ``_flash_attention`` lowers to for the TPU, under a gradient inside
    a loop as a training step holds them, are charged to ``attn/core``
    through ``LIBRARY_SCOPES``, the backward one as backward."""
    from deeplearning4j_tpu.nn.layers.attention import _flash_attention

    q = jax.ShapeDtypeStruct((1, 4, 512, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, kv_heads, 512, 64), jnp.bfloat16)

    def steps(q, k, v):
        def body(c, _):
            g = jax.grad(lambda q: jnp.sum(_flash_attention(
                q, k, v, True).astype(jnp.float32)))(q + c)
            return g[0, 0, 0, 0], None
        return jax.lax.scan(body, jnp.zeros((), q.dtype), None, length=2)

    low = jax.jit(steps).trace(q, k, k).lower(lowering_platforms=("tpu",))
    kernels = [opscopes.cut(path) for op, path in _paths(low)
               if op == "custom-call"]
    assert sorted(kernels) == [(None, "attn", "core", False),
                               (None, "attn", "core", True)]


# ---------------------------------------------------------------------
# (b) a scope is metadata only
# ---------------------------------------------------------------------
@pytest.mark.parametrize("cell", TRAINED + SERVED)
def test_the_lowered_text_is_the_same_without_the_scopes(
        lowered, cell, monkeypatch):
    programs = {k[1]: v for k, v in lowered.items() if k[0] == cell}
    with_scopes = {name: low.as_text()
                   for name, (_, _, low) in programs.items()}
    assert any("stablehlo.dot_general" in t for t in with_scopes.values())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()      # the traced programs hold their name stacks
    try:
        for name, (fn, args, _) in programs.items():
            bare = fn.lower(*args)
            assert not any(opscopes.cut(p)[1] for _, p in _paths(bare))
            assert bare.as_text() == with_scopes[name], name
    finally:
        jax.clear_caches()
