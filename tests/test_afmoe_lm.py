"""The ``afmoe`` LM (nn/layers/hybrid.py ``HybridMoeBlock`` with its
afmoe options, moe.py ``route``, attention.py's grouped paged kernel,
models/zoo.py ``afmoe_lm``, the engine's block table a layer kind)
against its plain reference (benchmark/models/afmoe_reference.py) at the
benchmark's rehearsal sizes: window 32, context 128, one dense and three
expert layers (three sliding, one full), seeded random weights, float32,
on the CPU.

Tolerances, each with its reason:

- logits, program against reference: 5e-5 on log-probabilities. Both are
  float32; the program sums the experts' gated sum over sorted rows, the
  attention a block of keys at a time (the kernel) or over a gathered
  window, and the norms in another order than the reference does. The
  same net computed in bfloat16 misses by over 1e-2 (asserted), so the
  tolerance tells float32 from the precision below it.
- served gaps: 5e-5, the same quantity read through the serving check:
  at every served position the served token's reference logit lies
  within it of the reference's best.
- the paged kernel against the gather program: 2e-5 on outputs of order
  1 (float32, one sums a compute block at a time); group 1 is asserted
  BIT FOR BIT against the kernel's own output with the queries' group
  unrolled by hand, which is what the parent's kernel computes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.models import afmoe_reference as reference
from deeplearning4j_tpu.nn.layers import attention as att
from deeplearning4j_tpu.nn.layers import hybrid, moe
from deeplearning4j_tpu.serving import DecodeEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 31


def rehearsal_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-large-serve.json")) as f:
        cfg = json.load(f)
    cfg = common.overlay(cfg, cfg["rehearsal"])
    cfg["kernels"] = None      # the plain programs unless a test says
    return common.overlay(cfg, over)


CFG = rehearsal_cfg()
MODEL = common.load_model(CFG, "this test's CFG")
WINDOW, BT = CFG["sliding_window"], 8


@pytest.fixture(scope="module")
def net():
    return MODEL.build_net(CFG, SEED)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).tolist()
            for n in lengths]


def engine(net, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("decode_chunk", 4)
    return DecodeEngine(net, block_tokens=BT, **kw)


def serve(eng, reqs, n_new=12, watch=None):
    ids = [eng.submit(Request(list(p), n_new)) for p in reqs]
    res = {}
    while eng.has_work():
        eng.step(res)
        if watch is not None:
            watch(eng)
    return [list(res[i].tokens) for i in ids]


# -- (a) the full forward pass, contexts past the window ---------------
@pytest.mark.parametrize("gain", [1.0, 0.1])
def test_full_forward_matches_the_plain_reference(gain):
    """At the rehearsal's ``attn_post_gain`` and at the cell's (the
    attention's post-norm weights drawn a tenth as large)."""
    cfg = rehearsal_cfg(attn_post_gain=gain)
    net = MODEL.build_net(cfg, SEED)
    post = np.asarray(net.params["2"]["post1_w"])
    assert abs(post.mean() - gain) < 0.02 * gain + 1e-3
    toks = np.asarray(prompts([100, 100], seed=3))
    want = jax.nn.log_softmax(
        reference.forward_logits(SEED, cfg, toks), axis=-1)
    got = jnp.transpose(jnp.log(net.output(toks)), (0, 2, 1))
    assert got.shape == want.shape == (2, 100, CFG["vocab_size"])
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the precision below misses by far more than the tolerance
    low = MODEL.build_net(rehearsal_cfg(dtype="bfloat16",
                                        compute_dtype="bfloat16",
                                        attn_post_gain=gain), SEED)
    bf16 = jnp.transpose(jnp.log(low.output(toks).astype(jnp.float32)),
                         (0, 2, 1))
    assert float(jnp.max(jnp.abs(bf16 - want))) > 1e-2


# -- (a) through the engine: paged admission, then decode --------------
@pytest.mark.parametrize("how", ["blocking", "chunked", "kernels"])
def test_engine_serves_the_reference_at_every_position(net, how):
    reqs = prompts([5, 40, 70, 90, 33])        # three cross the window
    if how == "kernels":    # the paged and the grouped kernel, interpreted
        net = MODEL.build_net(rehearsal_cfg(kernels="interpret"), SEED)
    eng = engine(net, prefill_chunk=0 if how == "blocking" else 16,
                 use_flash_paged="interpret" if how == "kernels"
                 else False)
    served = serve(eng, reqs)
    gaps, _ = MODEL.served_gaps(SEED, CFG, list(zip(reqs, served)))
    assert gaps.size == 5 * 12 and gaps.max() <= 5e-5
    stats = eng.stats
    assert 0.3 < stats["moe_picks_held"] / stats["moe_picks"] < 0.8
    # three expert layers count, the dense one does not
    assert stats["moe_layer_steps"] % 3 == 0
    counts = eng.compile_counts()
    assert counts["decode"] == 1 and counts["prefill"] == 0
    assert counts["paged_scatter"] == 0     # no dense row was ever made
    if how != "blocking":
        assert counts["chunk_prefill"] == 1


# -- (b) the share ------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    cfg = rehearsal_cfg(router_outputs=16, num_experts=2,
                        experts_held=[0, 2])
    key = jax.random.key(5)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    full = {"router": 0.3 * jax.random.normal(key, (d, 16)),
            "expert_bias": 0.05 * jax.random.normal(
                jax.random.fold_in(key, 1), (16,)),
            "We_in": 0.2 * jax.random.normal(
                jax.random.fold_in(key, 2), (16, d, 2 * f)),
            "We_out": 0.2 * jax.random.normal(
                jax.random.fold_in(key, 3), (16, f, d)),
            "Ws_in": 0.2 * jax.random.normal(
                jax.random.fold_in(key, 4), (d, 2 * f)),
            "Ws_out": 0.2 * jax.random.normal(
                jax.random.fold_in(key, 5), (f, d))}
    x = jax.random.normal(jax.random.fold_in(key, 6), (23, d))
    whole = reference.feed_forward(
        full, x, dict(cfg, experts_held=(0, 16)), "experts", "highest")
    shared = moe.gated_ffn(x, full["Ws_in"], full["Ws_out"])
    routed = 0.0
    for lo in range(0, 16, 2):
        part = dict(full, We_in=full["We_in"][lo:lo + 2],
                    We_out=full["We_out"][lo:lo + 2])
        y, counts = moe.dropless_moe(
            part, x, top_k=cfg["num_experts_per_tok"],
            experts_held=(lo, lo + 2), gate_rule="sigmoid_bias",
            route_scale=cfg["route_scale"])
        routed = routed + (y - shared)       # the shared expert ONCE
        assert int(counts["moe_picks"]) == 23 * 2
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)


def test_the_gate_rules():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    g, idx = moe.route(logits, 2)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(g, jax.nn.softmax(logits[:, :2]), rtol=1e-6)
    # the bias moves the pick, never the gate
    bias = jnp.asarray([0.0, -1.0, 0.0, 0.9])
    g, idx = moe.route(logits, 2, "sigmoid_bias", bias, scale=2.448)
    s = jax.nn.sigmoid(logits[0])
    assert idx.tolist() == [[3, 0]]          # 0.27 + 0.9 beats 0.88
    np.testing.assert_allclose(
        g[0], 2.448 * jnp.asarray([s[3], s[0]]) / (s[3] + s[0]), rtol=1e-6)
    with pytest.raises(ValueError, match="gate rule"):
        moe.route(logits, 2, "argmax")


# -- (c) a block table a layer kind -------------------------------------
def test_a_window_kind_holds_its_window_and_serves_what_holding_all_does(
        net):
    reqs = prompts([90, 70, 12], seed=4)
    eng = engine(net, prefill_chunk=16)
    wide, narrow = eng.kv.kinds
    assert (wide.window, narrow.window) == (128, WINDOW)
    assert wide.layers == ["3"] and narrow.layers == ["1", "2", "4"]
    most = {0: 0, 1: 0}

    def watch(e):
        for tab in list(e._kv_tabs) + [p.tab for p in e._pending]:
            for k, t in enumerate(tab.kinds if tab is not None else ()):
                most[k] = max(most[k], len(t.blocks))

    served = serve(eng, reqs, watch=watch)
    # never more than the window, one dispatch's writes and slack
    assert most[1] <= -(-WINDOW // BT) + 16 // BT + 2
    assert most[0] == -(-(90 + 12 - 1) // BT)        # the whole context
    assert eng.stats["kv_blocks_held_w32"] < eng.stats[
        "kv_blocks_spanned_w32"]
    assert eng.stats["kv_blocks_held_w128"] == eng.stats[
        "kv_blocks_spanned_w128"]
    assert all(p.free_blocks == p.n_blocks
               for p in (wide.pool, narrow.pool))    # all given back
    # a run that releases nothing serves the same logits
    keep = engine(net, prefill_chunk=16)
    keep.kv.expire = lambda tab: None
    keep.kv.kinds[1].ring = keep.kv.kinds[0].ring    # room for every block
    keep._build_jits()
    assert serve(keep, reqs) == served
    gaps, _ = MODEL.served_gaps(SEED, CFG, list(zip(reqs, served)))
    assert gaps.max() <= 5e-5


def test_one_upload_a_dispatch_whatever_the_kinds(net):
    eng = engine(net, prefill_chunk=16)
    serve(eng, prompts([20, 50]), n_new=5)
    dispatches = (eng.stats["chunks"] + eng.stats["chunks_scheduled"])
    assert eng.stats["table_uploads"] == dispatches
    rings = [k.ring for k in eng.kv.kinds]
    tabs = eng.kv.pack([None] * 3)
    assert tabs.shape == (3, 2 * sum(rings) + len(rings) + 1)


@pytest.mark.parametrize("option,kw", [
    ("prefix_cache_rows", {"prefix_cache_rows": 4}),
    ("kv_host_tier_bytes", {"kv_host_tier_bytes": 1 << 20}),
    ("kv_disk_tier_path", {"kv_disk_tier_path": "/tmp/x"}),
    ("spec_draft_len", {"spec_draft_len": 2}),
    ("paranoid", {"paranoid": True}),
    ("tp", {"tp": 2}),
])
def test_each_refused_option_raises_with_its_name(net, option, kw):
    with pytest.raises(ValueError, match=option):
        engine(net, **kw)


@pytest.mark.parametrize("method,args", [
    ("snapshot", ()), ("export_kv", ([1, 2, 3],)), ("import_kv", (b"",))])
def test_each_refused_method_raises_with_its_name(net, method, args):
    with pytest.raises(NotImplementedError, match=method):
        getattr(engine(net), method)(*args)


def test_a_one_kind_nets_tables_are_one_kinds():
    """The packing of one kind is the parent's ``[B, 2 S + 2]``: table,
    base, floor, filled."""
    from deeplearning4j_tpu.serving.kv_memory import unpack_tables

    packed = np.arange(3 * 12).reshape(3, 12)
    (one,) = unpack_tables(packed)
    assert (one["table"] == packed[:, :5]).all()
    assert (one["base"] == packed[:, 5:10]).all()
    assert (one["floor"] == packed[:, 10]).all()
    assert (one["filled"] == packed[:, 11]).all()
    two = unpack_tables(np.arange(3 * 17).reshape(3, 17), (4, 3))
    assert [t["table"].shape[1] for t in two] == [4, 3]
    assert (two[0]["filled"] == two[1]["filled"]).all()
    assert (two[1]["floor"] == np.arange(3 * 17).reshape(3, 17)[:, 15]).all()


# -- (d) the kernel, grouped and windowed --------------------------------
def ragged_case(rng, b, hq, hk, dh, t, tm, nb=40):
    s_ring = 2 * -(-tm // BT) + 4
    filled = rng.integers(0, 3 * tm, b).astype(np.int32)
    filled[rng.integers(0, b)] = 0                       # an idle row
    table = np.full((b, s_ring), -1, np.int32)
    base = np.full((b, s_ring), -1, np.int32)
    free = list(rng.permutation(nb))
    for r in range(b):
        if not filled[r]:
            continue
        for g in range(max(0, filled[r] - tm + 1) // BT,
                       (filled[r] + t - 1) // BT + 1):
            table[r, g % s_ring] = free.pop() if free else 0
            base[r, g % s_ring] = g * BT

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    cache = {"pk": draw(nb, BT, hk, dh), "pv": draw(nb, BT, hk, dh),
             "table": jnp.asarray(table), "base": jnp.asarray(base),
             "floor": jnp.zeros(b, jnp.int32),
             "filled": jnp.asarray(filled)}
    return draw(b, hq, t, dh), draw(b, hk, t, dh), draw(b, hk, t, dh), cache


@pytest.mark.parametrize("tm", [16, 40])
@pytest.mark.parametrize("group,t", [(1, 1), (4, 1), (6, 1), (6, 16),
                                     (4, 3)])
def test_the_grouped_kernel_equals_the_gather_program(group, t, tm):
    rng = np.random.default_rng([group, t, tm])
    hk, dh, b = 2, 16, 5
    q, k, v, cache = ragged_case(rng, b, hk * group, hk, dh, t, tm, nb=64)
    bean = att.MultiHeadSelfAttention(n_in=8, n_out=8, n_heads=hk * group,
                                      stream_max_t=tm)
    out = {}
    for flag in ("interpret", False):
        bean.use_flash_paged = flag
        out[flag], _ = att.AttentionImpl._paged_attend(
            bean, q, k, v, dict(cache))
    idle = np.asarray(cache["filled"]) == 0
    np.testing.assert_allclose(out["interpret"][~idle], out[False][~idle],
                               atol=2e-5)
    if group > 1 and t == 1:
        # a group's heads as extra rows compute what the same kernel
        # computes a query head at a time over its KV head repeated
        bean.use_flash_paged = "interpret"
        rep = dict(cache, pk=jnp.repeat(cache["pk"], group, axis=2),
                   pv=jnp.repeat(cache["pv"], group, axis=2))
        one, _ = att.AttentionImpl._paged_attend(
            bean, q, jnp.repeat(k, group, axis=1),
            jnp.repeat(v, group, axis=1), rep)
        np.testing.assert_allclose(one, out["interpret"], atol=1e-6)


@pytest.mark.parametrize("t,tm", [(1, 16), (1, 40), (16, 16), (3, 40)])
def test_group_one_is_the_parents_kernel_bit_for_bit(t, tm):
    """The kernel's output at one key head a query head, through the
    interpreter, against what the tree before the grouped form (PR 30's)
    gave for the same seeded case: ``tests/fixtures/
    paged_kernel_pr30.npz``, written by running this file's
    ``ragged_case`` on that tree."""
    rng = np.random.default_rng([1, t, tm])
    q, k, v, cache = ragged_case(rng, 5, 2, 2, 16, t, tm, nb=64)
    bean = att.MultiHeadSelfAttention(n_in=8, n_out=8, n_heads=2,
                                      stream_max_t=tm,
                                      use_flash_paged="interpret")
    got, _ = att.AttentionImpl._paged_attend(bean, q, k, v, dict(cache))
    with np.load(os.path.join(ROOT, "tests", "fixtures",
                              "paged_kernel_pr30.npz")) as want:
        assert (np.asarray(got) == want[f"t{t}_tm{tm}"]).all()


# -- (e) rotary positions through the cache -----------------------------
def test_rotary_positions_through_the_cache_are_absolute(net):
    """A chunk that resumes a cache rotates at the cache's length, the
    dense row's ``pos`` and the paged tables' ``filled`` alike; a full
    layer carries no position (its keys go into the cache as they come
    out of the norm)."""
    q = jax.random.normal(jax.random.key(1), (2, 4, 6, 16))
    k = jax.random.normal(jax.random.key(2), (2, 2, 6, 16))
    whole_q, whole_k = hybrid.rope(q, k, jnp.zeros(2, jnp.int32), 1e4)
    tail_q, tail_k = hybrid.rope(q[:, :, 4:], k[:, :, 4:],
                                 jnp.asarray([4, 4]), 1e4)
    np.testing.assert_allclose(tail_q, whole_q[:, :, 4:], atol=1e-6)
    np.testing.assert_allclose(tail_k, whole_k[:, :, 4:], atol=1e-6)
    # the reference rotates the same way, at absolute positions
    ref = reference.rotate(jnp.transpose(k[0], (1, 0, 2)), 1e4)
    np.testing.assert_allclose(jnp.transpose(whole_k[0], (1, 0, 2)), ref,
                               atol=1e-6)
    # the score of (i, j) depends on i - j alone
    a, b = hybrid.rope(q[:, :, :1], k[:, :, :1], jnp.asarray([3, 50]), 1e4)
    c, d = hybrid.rope(q[:, :, :1], k[:, :, :1], jnp.asarray([3, 50]) + 7,
                       1e4)
    np.testing.assert_allclose(jnp.sum(a[:, :2] * b, -1),
                               jnp.sum(c[:, :2] * d, -1), atol=1e-4)
    # stepping the net: past the window the dense row still knows where
    # it is, and only sliding layers carry ``pos``
    toks = np.asarray(prompts([60], seed=9))
    want = np.asarray(net.output(toks))
    net.rnn_clear_previous_state()
    net.rnn_time_step(toks[:, :50])
    got = np.asarray(net.rnn_time_step(toks[:, 50:]))
    np.testing.assert_allclose(got, want[:, :, 50:], atol=2e-6)
    state = net.rnn_get_previous_state() if hasattr(
        net, "rnn_get_previous_state") else net._rnn_state
    assert "pos" in state["1"] and "pos" not in state["3"]
    assert int(state["1"]["pos"][0]) == 60
    assert int(state["1"]["filled"][0]) == WINDOW
    net.rnn_clear_previous_state()
