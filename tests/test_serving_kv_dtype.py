"""The paged KV pool is held at the dtype its keys and values were
computed in (ISSUE 35).

The contract under test: a state leaf a layer was HANDED leaves a
mixed-precision forward pass at the dtype it came in with, and a leaf
the pass created at the master dtype (``_carried_state``, the one rule
of ``MultiLayerNetwork._forward_fn`` and ``ComputationGraph``'s twin);
``DecodeEngine`` makes its pool at the net's compute dtype where it has
one. So a float32-master / bf16-compute net serves from a bf16 pool
that holds, bit for bit, the numbers the float32 pool of the old rule
held; a net that is resident at its compute dtype, and every caller
that hands in master-dtype state, lowers to the program it lowered to
before; and the transfer plane and the spill tier follow the pool's
dtype from the arrays themselves."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn import multilayer
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import DecodeEngine, Request
from tests.test_serving_weights import _cell_net, _Lowered

V = 12
CASES = [([1, 4, 7, 2], 9), ([9, 3, 3], 6), ([5, 2, 8, 1, 6, 0, 4], 11)]


def _set(confs, dtype, compute_dtype):
    for c in confs:
        c.dtype = dtype
        c.compute_dtype = compute_dtype
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 64


def _net(dtype="float32", compute_dtype="bfloat16", seed=7):
    conf = transformer_lm(n_in=V, width=32, n_layers=2, n_heads=4,
                          n_classes=V, seed=seed)
    _set(conf.confs, dtype, compute_dtype)
    return MultiLayerNetwork(conf).init()


def _graph(dtype="float32", compute_dtype="bfloat16", seed=7):
    """An LM-shaped ComputationGraph: two attention layers and a head."""
    from deeplearning4j_tpu.nn.layers.attention import (
        MultiHeadSelfAttention,
    )

    def attn(n_in):
        return MultiHeadSelfAttention(n_in=n_in, n_out=32, n_heads=4,
                                      causal=True, stream_max_t=64)

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .learning_rate(0.01).graph_builder().add_inputs("in")
            .add_layer("a0", attn(V), "in")
            .add_layer("a1", attn(32), "a0")
            .add_layer("out", L.RnnOutputLayer(
                n_in=32, n_out=V, activation="softmax",
                loss_function="mcxent"), "a1")
            .set_outputs("out").build())
    _set([v.conf for v in conf.vertices.values()], dtype, compute_dtype)
    return ComputationGraph(conf).init()


def _pool_dtypes(eng):
    return {leaf.dtype for leaf in jax.tree.leaves(eng._pool)}


def _old_rule(new, handed, master_dtype):
    """The rule as it was written before ISSUE 35: every carried leaf
    to the master dtype, whatever it came in with."""
    return jax.tree_util.tree_map(
        lambda a: multilayer._cast_floating(a, master_dtype), new)


# -- (a) the bf16 pool holds the float32 pool's numbers ---------------

def test_a_mixed_precision_pool_is_bf16_and_holds_the_f32_pools_numbers():
    """One admission and two decode rounds through a bf16 pool, and
    through a float32 pool handed to a second engine over the same
    weights (the parent's layout: the rule keeps what it is handed, so
    both are reachable without a switch). Through the kernel
    (``interpret``), which hands a layer its attention at the queries'
    dtype whatever the pool's (the gather program, the off-TPU path,
    scores a float32 pool in float32 and hands every later layer
    float32 activations: there the old layout computed OTHER keys).

    Every position a decode step wrote, and the whole first layer,
    agree bit for bit: those keys and values were computed in bf16 and
    the float32 cell held sixteen zero bits behind them. The PROMPT's
    rows of a later layer do not: a bucketed prefill multiplies each
    block's output by its float32 mask, so every layer after the first
    computes the prompt's keys from float32 activations, and the bf16
    pool holds those numbers rounded once (``scatter_row``'s cast)."""
    prompt, n = [1, 4, 7, 2, 9, 3, 3, 5, 2, 8, 1, 6, 0, 4, 11, 10, 7], 7
    bf16 = jnp.dtype(jnp.bfloat16)

    def served(widen):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=3,
                           tracer=Tracer(), use_flash_paged="interpret")
        eng._ensure_paged_pool()
        assert _pool_dtypes(eng) == {bf16}
        if widen:
            eng._pool = jax.tree.map(
                lambda a: a.astype(jnp.float32), eng._pool)
        rid = eng.submit(Request(list(prompt), n))
        results = {}
        eng.step(results)                       # admission + round 1
        blocks = dict(eng._kv_tabs[0].kinds[0].blocks)
        eng.step(results)                       # round 2
        assert eng.stats["chunks"] == 2
        pool = jax.tree.map(np.asarray, eng._pool)
        while eng.has_work():
            eng.step(results)
        return eng, pool, blocks, results[rid].tokens

    narrow, pool16, blocks, toks16 = served(False)
    wide, pool32, blocks32, toks32 = served(True)
    assert toks16 == toks32 and len(toks16) == n
    assert blocks == blocks32
    assert _pool_dtypes(narrow) == {bf16}
    assert _pool_dtypes(wide) == {jnp.dtype(jnp.float32)}
    bt = narrow.block_tokens
    # the flat token rows of the prompt and of the six decoded tokens
    # whose keys two rounds wrote
    rows = np.asarray([blocks[g // bt] * bt + g % bt
                       for g in range(len(prompt) + 6)])
    decoded = rows[len(prompt):]
    for name in pool16:
        for leaf in ("pk", "pv"):
            a = pool16[name][leaf].astype(np.float32)
            b = pool32[name][leaf]
            a, b = (x.reshape((-1,) + x.shape[2:]) for x in (a, b))
            assert np.count_nonzero(b[rows]) > 0
            np.testing.assert_array_equal(a[decoded], b[decoded])
            # what the float32 cells held, rounded once
            np.testing.assert_array_equal(
                a, b.astype(jnp.bfloat16).astype(np.float32))
            if name == "0":
                np.testing.assert_array_equal(a, b)
    # every program compiled once in either engine
    for eng in (narrow, wide):
        counts = eng.compile_counts()
        assert counts["decode"] == 1 and counts["prefill"] == 1
    # the gauges say which pool a trace ran: bytes a token, cell width
    per_tok = 2 * 2 * 32                  # k and v, 2 layers, width 32
    assert narrow.stats["kv_bytes_per_token"] == per_tok * 2
    assert narrow.stats["kv_dtype_bytes"] == 2
    counters = narrow.tracer.latest_counters()
    assert counters["serving_kv_bytes_per_token"] == per_tok * 2
    assert counters["serving_kv_dtype_bytes"] == 2


def test_a_pool_follows_the_dense_row_where_no_compute_dtype_is_set():
    """No compute dtype: the pool is made at the dtype the dense
    prefill row has, which is the dtype the layers computed its keys
    in (one-hot float32 columns into bf16 masters promote)."""
    for dtype in ("float32", "bfloat16"):
        net = _net(dtype=dtype, compute_dtype=None)
        assert net._compute_dtype is None
        eng = DecodeEngine(net, n_slots=2, decode_chunk=3)
        rnn, _ = eng._prefill_sequence([1, 2, 3])
        row = {st[leaf].dtype for st in rnn.values() for leaf in "kv"}
        eng._ensure_paged_pool(rnn)
        assert _pool_dtypes(eng) == row and len(row) == 1
        assert eng.stats["kv_dtype_bytes"] == row.pop().itemsize


# -- (b) the paged engine serves the dense path's tokens --------------

def test_the_rehearsal_cell_serves_the_dense_paths_greedy_ids():
    """The block's rehearsal configuration, kernel in ``interpret``:
    the engine's greedy ids from its bf16 pool equal the ids of the
    net's own dense streaming path (``rnn_time_step``, float32 state)
    request for request."""
    net, dep, cfg = _cell_net("cgpt1p3b-serve.chat-steady")
    assert dep["use_flash_paged"] == "interpret"
    vocab = cfg["vocab_size"]
    eye = np.eye(vocab, dtype=np.float32)
    rng = np.random.default_rng(4)
    cases = [(rng.integers(0, vocab, n).tolist(), m)
             for n, m in ((9, 6), (21, 9), (40, 5))]

    def stepped(prompt, n):
        net.rnn_clear_previous_state()
        out = net.rnn_time_step(eye[prompt].T[None])
        toks = [int(jnp.argmax(out[0, :, -1]))]
        while len(toks) < n:
            out = net.rnn_time_step(eye[toks[-1]][None, :, None])
            toks.append(int(jnp.argmax(out[0, :, -1])))
        net.rnn_clear_previous_state()
        return toks

    want = [stepped(p, n) for p, n in cases]
    eng = DecodeEngine(net, seed=5, **dep)
    ids = [eng.submit(Request(list(p), n)) for p, n in cases]
    res = eng.run()
    assert _pool_dtypes(eng) == {jnp.dtype(jnp.bfloat16)}
    assert [res[i].tokens for i in ids] == want


# -- (c) nets that hand in master-dtype state lower as they did -------

def _decode_text(build, monkeypatch, old: bool, widen: bool = False):
    """The lowered text and jaxpr of the engine's decode program after
    a short run, with the rule as it is or (``old``) written the old
    way; ``widen``: the pool handed over at float32 (the parent's
    layout of a mixed-precision net)."""
    if old:
        monkeypatch.setattr(multilayer, "_carried_state", _old_rule)
        import deeplearning4j_tpu.nn.graph as graph
        monkeypatch.setattr(graph, "_carried_state", _old_rule)
    eng = DecodeEngine(build(), n_slots=2, decode_chunk=3, seed=3)
    if widen:
        eng._ensure_paged_pool()
        eng._pool = jax.tree.map(lambda a: a.astype(jnp.float32),
                                 eng._pool)
    seen = {}
    decode = eng._decode_jit

    class Spy(_Lowered):
        def __call__(self, *args):
            if self.text is None:
                seen["jaxpr"] = jax.make_jaxpr(
                    decode.__wrapped__)(*args)
            return super().__call__(*args)

    eng._decode_jit = Spy(decode)
    ids = [eng.submit(Request(list(p), n)) for p, n in CASES]
    res = eng.run()
    monkeypatch.undo()
    return eng._decode_jit.text, seen["jaxpr"], [res[i].tokens for i in ids]


def _pool_converts(jaxpr, pool_shapes):
    """``convert_element_type`` equations, anywhere in ``jaxpr``, whose
    operand has a pool leaf's shape."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if (eqn.primitive.name == "convert_element_type"
                    and tuple(eqn.invars[0].aval.shape) in pool_shapes):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("build,widen", [
    pytest.param(lambda: _net("bfloat16", None), False,
                 id="mln-bf16-masters"),
    pytest.param(lambda: _net("float32", None), False, id="mln-f32"),
    pytest.param(lambda: _graph("bfloat16", None), False,
                 id="graph-bf16-masters"),
    pytest.param(lambda: _graph("float32", None), False, id="graph-f32"),
    # mixed precision handed master-dtype state, as every caller did
    # before ISSUE 35: the one case in which the rule is evaluated
    pytest.param(_net, True, id="mln-mixed-f32-pool"),
    pytest.param(_graph, True, id="graph-mixed-f32-pool"),
])
def test_master_dtype_state_decodes_through_the_same_program(
        build, widen, monkeypatch):
    """The decode program of a net that hands in state at its master
    dtype: the lowered text hashes to what it hashed to with the rule
    written the old way, no pool leaf is converted, the tokens are the
    same."""
    text, jaxpr, toks = _decode_text(build, monkeypatch, False, widen)
    was, _, toks_old = _decode_text(build, monkeypatch, True, widen)
    digest = [hashlib.sha256(t.encode()).hexdigest() for t in (text, was)]
    assert digest[0] == digest[1]
    assert toks == toks_old
    eng = DecodeEngine(build(), n_slots=2, decode_chunk=3)
    eng._ensure_paged_pool()
    shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(eng._pool)}
    assert not _pool_converts(jaxpr, shapes)


@pytest.mark.parametrize("build", [
    pytest.param(_net, id="mln"), pytest.param(_graph, id="graph")])
def test_a_mixed_precision_decode_converts_no_pool_leaf(build,
                                                        monkeypatch):
    """float32 masters under bf16, the pool as the engine makes it: the
    decode program carries the bf16 leaves through its scan with no
    ``convert_element_type`` on one (the old rule would have widened
    them on the way out of every step)."""
    _, jaxpr, _ = _decode_text(build, monkeypatch, old=False)
    eng = DecodeEngine(build(), n_slots=2, decode_chunk=3)
    eng._ensure_paged_pool()
    shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(eng._pool)}
    assert _pool_dtypes(eng) == {jnp.dtype(jnp.bfloat16)}
    assert not _pool_converts(jaxpr, shapes)


def _other_cell(cell: str, old: bool, monkeypatch):
    """The lowered text of every program a cell of another
    configuration runs at its rehearsal sizes (the served cells AT
    THEIR STATED bf16, which the rehearsal group swaps for float32),
    with the rule as it is or written the old way."""
    from benchmark import common, traffic
    from benchmark.train_cell import Feed

    if old:
        import deeplearning4j_tpu.nn.graph as graph
        monkeypatch.setattr(multilayer, "_carried_state", _old_rule)
        monkeypatch.setattr(graph, "_carried_state", _old_rule)
    bench = common.load_benchmark()
    _, cfg, mix, model = common.find_cell(bench, cell, True)
    with open(common.os.path.join(common.ROOT, next(
            c["file"] for c in bench["configs"]
            if c["name"] == cell.split(".")[0]))) as f:
        stated = common.json.load(f)
    texts = {}

    class Kept(_Lowered):
        def __call__(self, *args, **kw):
            if self.text is None:
                self.text = self.fn.lower(*args, **kw).as_text()
            return self.fn(*args, **kw)

    if "optimizer" in cfg:                      # a trained cell
        net = model.build_net(cfg, 5, optimizer=cfg["optimizer"])
        net.__dict__["_train_steps_scan"] = Kept(net._train_steps_scan)
        pool = traffic.train_pool(mix, 5, cfg["vocab_size"])
        feats, labels = Feed(pool, model, cfg, 1).next()
        np.asarray(net.fit_scan(feats, labels))
        texts["steps"] = net._train_steps_scan.text
        made = {leaf.dtype for leaf in jax.tree.leaves(net.params)}
    else:
        cfg = dict(cfg, dtype=stated["dtype"],
                   compute_dtype=stated["compute_dtype"])
        net = model.build_net(cfg, 5)
        dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
        dep["use_flash_paged"] = False
        eng = DecodeEngine(net, seed=5, **dep)
        names = ["_prefill_jit", "_chunk_jit", "_decode_jit",
                 "_scatter_jit", "_state_admit_jit"]
        names = [n for n in names if getattr(eng, n) is not None]
        for name in names:
            setattr(eng, name, Kept(getattr(eng, name)))
        rng = np.random.default_rng(1)
        ids = [eng.submit(Request(
            rng.integers(0, cfg["vocab_size"], n).tolist(), 5))
            for n in (9, 40)]
        res = eng.run()
        assert all(len(res[i].tokens) == 5 for i in ids)
        texts = {n: getattr(eng, n).text for n in names
                 if getattr(eng, n).text is not None}
        made = _pool_dtypes(eng)
        if eng._state_layers:
            # a recurrent slot state keeps the dtype its layer makes it
            # at (Mamba-2's SSM state is float32 in a bf16 net)
            assert {st["ssm"].dtype for st in eng._slot_state.values()
                    } == {jnp.dtype(jnp.float32)}
    monkeypatch.undo()
    return texts, made, net


@pytest.mark.parametrize("cell,programs,made", [
    ("granite4hs-serve.chat-steady-g4hs",
     {"_prefill_jit", "_decode_jit", "_scatter_jit", "_state_admit_jit"},
     "bfloat16"),
    # (several kinds: the cold prefill is traced for the pool's shapes
    # and never run)
    ("trinity-large-serve.docs-mixed-tlp",
     {"_prefill_jit", "_chunk_jit", "_decode_jit"}, "bfloat16"),
    ("cgpt1p3b-train.train-step", {"steps"}, "float32"),
    ("lfm2-8b-a1b-train.moe-step-8k", {"steps"}, "float32"),
])
def test_the_other_cells_programs_are_the_parents_text(
        cell, programs, made, monkeypatch):
    """The four cells the mechanism bypasses: every program each runs
    lowers, letter for letter, to what it lowered to with the rule
    written the old way; the served ones' pools are bf16 as they were."""
    now, dtypes, net = _other_cell(cell, False, monkeypatch)
    was, dtypes_old, _ = _other_cell(cell, True, monkeypatch)
    assert set(now) == programs == set(was)
    for name in programs:
        assert (hashlib.sha256(now[name].encode()).hexdigest()
                == hashlib.sha256(was[name].encode()).hexdigest()), name
    assert dtypes == dtypes_old == {jnp.dtype(made)}


# -- (d) the transfer plane and the tier follow the pool --------------

def _prefix_engine(**kw):
    return DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=3,
                        block_tokens=4, kv_blocks=48,
                        prefix_cache_rows=8, **kw)


PROMPT = [1, 4, 7, 2, 9, 3, 3, 5, 2, 8, 1, 6, 0, 4]


def _serve(eng, prompt=PROMPT, n=6):
    rid = eng.submit(Request(list(prompt), n))
    return eng.run()[rid]


def test_export_and_import_round_trip_a_bf16_pool():
    from deeplearning4j_tpu.serving.kv_transfer import unpack_prefix

    donor = _prefix_engine()
    want = _serve(donor).tokens
    payload = donor.export_kv(PROMPT)
    shipped = unpack_prefix(payload)["layers"]
    assert {pk.dtype for pk, _ in shipped.values()} == {
        jnp.dtype(jnp.bfloat16)}
    taker = _prefix_engine()
    out = taker.import_kv(payload)
    assert out["imported"], out
    assert _pool_dtypes(taker) == {jnp.dtype(jnp.bfloat16)}
    got = _serve(taker)
    assert got.tokens == want and got.prefix_tokens_reused > 0


def test_import_casts_a_float32_payload_into_a_bf16_pool():
    """A payload a float32-pool engine of the same weights wrote (the
    parent's): its cells hold bf16 numbers, so the cast on import is
    exact and the taker serves the donor's tokens from the prefix."""
    from deeplearning4j_tpu.serving.kv_transfer import (
        KVTransferError,
        pack_prefix,
        unpack_prefix,
    )

    donor = _prefix_engine()
    want = _serve(donor).tokens
    parsed = unpack_prefix(donor.export_kv(PROMPT))
    head = parsed["header"]

    def repack(dtype):
        return pack_prefix(
            head["tokens"], head["blocks"], head["floor"],
            head["block_tokens"],
            [(name, pk.astype(dtype), pv.astype(dtype))
             for name, (pk, pv) in sorted(parsed["layers"].items())])

    taker = _prefix_engine()
    out = taker.import_kv(repack(np.float32))
    assert out["imported"], out
    assert _pool_dtypes(taker) == {jnp.dtype(jnp.bfloat16)}
    got = _serve(taker)
    assert got.tokens == want and got.prefix_tokens_reused > 0
    # a payload that is not floating is still refused by name
    with pytest.raises(KVTransferError, match="dtype"):
        _prefix_engine().import_kv(repack(np.int32))


def test_spill_and_reload_round_trip_a_bf16_pool():
    """A prefix evicted from the trie spills to the host tier as bf16
    blocks and reloads into the bf16 pool: the re-admission splices it
    and serves the first admission's tokens."""
    from deeplearning4j_tpu.serving.kv_transfer import unpack_prefix

    eng = _prefix_engine(kv_host_tier_bytes=1 << 20)
    want = _serve(eng).tokens
    while eng.prefix_cache.evict_one():
        pass
    eng.drain_spills()
    assert eng.kv_tier.stats["spills"] >= 1
    ent = eng.kv_tier.match(PROMPT)
    assert ent is not None
    stored = unpack_prefix(ent[1])["layers"]
    assert {pk.dtype for pk, _ in stored.values()} == {
        jnp.dtype(jnp.bfloat16)}
    got = _serve(eng)
    assert eng.kv_tier.stats["reloads"] >= 1
    assert got.tokens == want and got.prefix_tokens_reused > 0
    assert _pool_dtypes(eng) == {jnp.dtype(jnp.bfloat16)}


# -- (e) rnn_time_step keeps master-dtype state, one compile ----------

def test_an_lstm_under_mixed_precision_carries_float32_state_one_compile():
    lb = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
          .compute_dtype("bfloat16").list())
    lb.layer(0, L.GravesLSTM(n_in=5, n_out=8, activation="tanh"))
    lb.layer(1, L.RnnOutputLayer(n_in=8, n_out=5, activation="softmax",
                                 loss_function="mcxent"))
    conf = lb.build()
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 1)).astype(np.float32)
    for _ in range(4):
        out = net.rnn_time_step(x)
        assert out.dtype == jnp.float32
        leaves = jax.tree.leaves(net._rnn_state)
        assert leaves and all(
            leaf.dtype == jnp.float32 for leaf in leaves
            if jnp.issubdtype(leaf.dtype, jnp.floating))
    # the first call CREATES the state (one program), every later call
    # is handed float32 state and gives float32 state back (one more)
    assert net._rnn_step_jit._cache_size() == 2
    for _ in range(3):
        net.rnn_time_step(x)
    assert net._rnn_step_jit._cache_size() == 2


def test_carried_state_keeps_what_it_is_handed_and_creates_at_master():
    bf, f32 = jnp.bfloat16, jnp.float32
    new = {"pk": jnp.ones((2, 3), bf), "h": jnp.ones((2,), bf),
           "filled": jnp.ones((2,), jnp.int32)}
    handed = {"pk": jnp.zeros((2, 3), bf), "h": jnp.zeros((2,), f32),
              "filled": jnp.zeros((2,), jnp.int32)}
    out = multilayer._carried_state(new, handed, f32)
    assert out["pk"] is new["pk"]                # handed bf16: as it is
    assert out["h"].dtype == f32                 # handed float32
    assert out["filled"] is new["filled"]        # an integer: alone
    made = multilayer._carried_state(new, None, f32)
    assert made["pk"].dtype == f32 and made["h"].dtype == f32
    # a leaf the layer was not handed is created: the master dtype
    part = multilayer._carried_state(new, {"h": handed["h"]}, f32)
    assert part["pk"].dtype == f32
    # a tuple carry (an LSTM's (h, c)) is matched by position
    pair = multilayer._carried_state(
        (new["h"], new["h"]), (handed["h"], handed["pk"][0, :2]), f32)
    assert [a.dtype for a in pair] == [f32, bf]


# -- what the kernel and the compiler are told about a bf16 pool ------

@pytest.mark.parametrize("dtype,grp,t,want", [
    # the block's serving cell: 16 KV heads of 128, ungrouped, decode
    # and a verify chunk run the short form, which lifts to float32
    ("float32", 1, 1, 8), ("bfloat16", 1, 1, 8), ("bfloat16", 1, 5, 8),
    # an ungrouped prefill tile and grouped heads never lift: what the
    # scratch holds at the pool's own width (the other served cells')
    ("bfloat16", 1, 128, 16), ("bfloat16", 4, 1, 16),
    ("bfloat16", 6, 1024, 16), ("float32", 1, 128, 8),
])
def test_a_compute_block_is_sized_at_the_width_its_form_computes_in(
        dtype, grp, t, want):
    from deeplearning4j_tpu.nn.layers.attention import (
        _paged_blocks_per_step,
    )

    heads = 16 if grp == 1 else 8
    assert _paged_blocks_per_step(16, heads, 128, jnp.dtype(dtype), 129,
                                  grp, t) == want


def test_the_engine_counts_the_walk_with_the_kernels_compute_block():
    """``paged_blocks_per_step`` (what the benchmark's readers divide
    by) is the compute block the kernel of that kind and chunk takes."""
    from deeplearning4j_tpu.nn.layers import attention as att

    net, dep, cfg = _cell_net("cgpt1p3b-serve.chat-steady")
    eng = DecodeEngine(net, seed=5, **dep)
    rid = eng.submit(Request([1, 2, 3, 4, 5, 6, 7], 4))
    assert len(eng.run()[rid].tokens) == 4
    kind = eng.kv.kinds[0]
    assert kind.group == 1
    pk = eng._pool[kind.layers[0]]["pk"]
    ntab = att._paged_table_entries(kind.ring, kind.window,
                                    eng.block_tokens, 1)
    assert eng.stats["paged_blocks_per_step"] == (
        att._paged_blocks_per_step(eng.block_tokens, pk.shape[2],
                                   pk.shape[3], pk.dtype, ntab, 1, 1))


def test_the_compiler_is_told_only_of_a_leaf_that_fits_vmem(monkeypatch):
    """The option is decided from the pool's own leaf: on the TPU, for
    a leaf under the chip's 128 MiB of VMEM; the other served cells'
    leaves are larger (268 MB, 339 MB) and their programs compile as
    they did; off the TPU the option does not exist."""
    from deeplearning4j_tpu.serving import engine as engine_mod

    small, large = 92 << 20, 137 << 20
    assert engine_mod._compiler_options(small) is None        # the CPU
    net, dep, _ = _cell_net("cgpt1p3b-serve.chat-steady")
    assert DecodeEngine(net, seed=5, **dep)._jit_options is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine_mod._compiler_options(small) == {
        "xla_tpu_msa_inefficient_use_to_copy_ratio": "0.9"}
    assert engine_mod._compiler_options(large) is None
    # the engine reckons the leaf it will make: blocks x tokens x KV
    # heads x head dim at the pool's width
    net, dep, cfg = _cell_net("cgpt1p3b-serve.chat-steady")
    dep["use_flash_paged"] = False
    seen = []
    monkeypatch.setattr(engine_mod, "_compiler_options",
                        lambda n: seen.append(n))
    eng = DecodeEngine(net, seed=5, **dep)
    eng._ensure_paged_pool()
    assert seen == [jax.tree.leaves(eng._pool)[0].nbytes]
