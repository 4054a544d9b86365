"""The ``evabyte`` LM (nn/layers/eva.py, nn/layers/hybrid.py
``HybridMoeBlock(mixer="eva")``, attention.py's paged kernel in its two
new forms, models/zoo.py ``evabyte_lm``, the engine's two kinds of
table for one layer) against its plain reference
(benchmark/models/evabyte_reference.py) at a small size: hidden 64, 4
heads of 16, window 32, chunk = block 4, 3 layers, seeded random
weights, on the CPU.

Tolerances, each with its reason:

- logits, program against reference, float32: 5e-5 on every head's
  logits (of order 1). Both are float32; the program pools a chunk and
  sums a window's scores in another order than the reference does. Each
  of the layer's three nearest mistakes (uniform pooling, a sliding
  floor, summaries visible one window early) misses by over 0.1
  (asserted), two thousand times the tolerance.
- served gaps: 5e-5, the same quantity read through the serving check:
  at every served position the served token's reference logit lies
  within it of the reference's best.
- the residual sum in bfloat16 where float32 is stated
  (``fp32_skip_add``): with float32 weights and products and ONLY the
  stream between the blocks rounded to bfloat16 after every branch, the
  logits miss by over a hundred times the tolerance (asserted); with the
  stream as stated the same hand-run blocks pass it.
- the paged kernel's two new forms against the gather program: 2e-5 on
  outputs of order 1 (float32, one sums a compute block at a time).
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.models import evabyte_reference as reference
from deeplearning4j_tpu.nn.layers import attention as att
from deeplearning4j_tpu.nn.layers import eva
from deeplearning4j_tpu.nn.layers.hybrid import (
    HybridMoeBlockImpl,
    TiedLMHeadImpl,
)
from deeplearning4j_tpu.serving import DecodeEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 41
TOL = 5e-5


def small_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-serve.json")) as f:
        cfg = json.load(f)
    cfg = common.overlay(cfg, cfg["rehearsal"])
    cfg = common.overlay(cfg, {"num_hidden_layers": 3,
                               "layers_held": [0, 1, 2],
                               "served_context": 160})
    return common.overlay(cfg, over)


CFG = small_cfg()
MODEL = common.load_model(CFG, "this test's CFG")
W, C, LAYERS = CFG["window_size"], CFG["chunk_size"], 3


@pytest.fixture(scope="module")
def net():
    return MODEL.build_net(CFG, SEED)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).tolist()
            for n in lengths]


def engine(net, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("use_flash_paged", False)
    return DecodeEngine(net, decode_chunk=8, block_tokens=C, **kw)


def serve(eng, reqs, n_new=24, watch=None):
    ids = [eng.submit(Request(list(p), n_new)) for p in reqs]
    res = {}
    while eng.has_work():
        eng.step(res)
        if watch is not None:
            watch(eng)
    return [list(res[i].tokens) for i in ids]


def every_heads_logits(net, toks):
    """``[N, T, 8 x V]`` from the net's own forward pass and head."""
    acts, _, _ = net._forward_fn(net.params, net.state, jnp.asarray(toks),
                                 None, False, collect=True)
    last = len(net.conf.confs) - 1
    z = TiedLMHeadImpl.all_logits(net.conf.confs[last],
                                  net.params[str(last)], acts[-2])
    return np.asarray(z.reshape(*z.shape[:2], -1))


# -- the full forward pass, 3.5 windows --------------------------------
def test_full_forward_matches_the_reference_on_every_head(net):
    toks = np.asarray(prompts([112, 112], seed=3))
    want = reference.forward_logits(SEED, CFG, toks, every_head=True)
    got = every_heads_logits(net, toks)
    assert got.shape == want.shape == (2, 112, 8 * CFG["vocab_size"])
    np.testing.assert_allclose(got, want, atol=TOL)
    # head 0 is what ``output`` serves
    np.testing.assert_allclose(
        jnp.transpose(net.output(toks), (0, 2, 1)),
        jax.nn.softmax(want[..., :CFG["vocab_size"]], axis=-1), atol=TOL)


def test_streaming_matches_the_reference_across_boundaries(net):
    """``rnn_time_step``: chunks that end on, start on and straddle
    chunk and window boundaries."""
    toks = np.asarray(prompts([112, 112], seed=4))
    want = jax.nn.softmax(reference.forward_logits(SEED, CFG, toks),
                          axis=-1)
    net.rnn_clear_previous_state()
    out, at = [], 0
    for n in (5, 1, 1, 30, 7, 1, 32, 20, 15):
        out.append(net.rnn_time_step(toks[:, at:at + n]))
        at += n
    net.rnn_clear_previous_state()
    np.testing.assert_allclose(
        jnp.transpose(jnp.concatenate(out, axis=2), (0, 2, 1)), want,
        atol=TOL)


# -- the three nearest mistakes, and the residual's precision ----------
@pytest.mark.parametrize("broken", reference.BROKEN[1:])
def test_a_broken_layer_fails_the_tolerance(net, broken):
    toks = np.asarray(prompts([112], seed=5))
    got = every_heads_logits(net, toks)
    wrong = reference.forward_logits(SEED, CFG, toks, every_head=True,
                                     broken=broken)
    assert float(np.max(np.abs(got - wrong))) > 0.1 >= 2000 * TOL
    if broken != "early_summaries":
        # ... and only past what the mistake touches: inside the first
        # window no summary is visible and both floors are 0
        np.testing.assert_allclose(got[:, :W], wrong[:, :W], atol=TOL)


def test_bfloat16_residual_sums_fail_the_tolerance(net):
    """The blocks by hand, float32 weights and products, the stream
    between them as ``fp32_skip_add`` states it and then rounded to
    bfloat16 after every branch: nothing else differs."""
    toks = np.asarray(prompts([112], seed=5))
    want = reference.forward_logits(SEED, CFG, toks, every_head=True)
    confs, last = net.conf.confs, len(net.conf.confs) - 1
    miss = {}
    for keep in (True, False):
        x = jnp.transpose(net.params["0"]["W"][toks], (0, 2, 1))
        for i in range(1, last):
            conf = copy.deepcopy(confs[i])
            conf.layer.fp32_residual = keep
            x, _ = HybridMoeBlockImpl.apply(
                conf, net.params[str(i)],
                x if keep else x.astype(jnp.bfloat16))
            assert x.dtype == (jnp.float32 if keep else jnp.bfloat16)
        z = TiedLMHeadImpl.all_logits(confs[last], net.params[str(last)],
                                      x.astype(jnp.float32))
        miss[keep] = float(np.max(np.abs(
            np.asarray(z.reshape(*z.shape[:2], -1)) - want)))
    assert miss[True] <= TOL and miss[False] > 100 * TOL, miss


# -- through the engine -------------------------------------------------
@pytest.fixture
def short_blocks(monkeypatch):
    """Two table entries a compute block: the interpreted kernel's body
    unrolls an entry at a time, and at sixteen its trace alone takes
    half a minute. (Any number is the same arithmetic in more trips.)"""
    monkeypatch.setattr(att, "_PAGED_MAX_BLOCKS", 2)


@pytest.mark.parametrize("how", ["gather", "kernels"])
def test_engine_serves_the_reference_at_every_position(net, how,
                                                       short_blocks):
    """Chunked admission through both pools, then decode: prompts whose
    rounds of 8 cross a chunk's end every round and a window's end
    inside a round (27 -> 32 in its first, 45 -> 64 in its third), two
    rows at different phases in every round, one short row."""
    reqs, cfg, layers = prompts([27, 45, 100, 3]), CFG, LAYERS
    if how == "kernels":    # the paged kernel's two forms, interpreted
        reqs, layers = reqs[:2], 1
        cfg = small_cfg(num_hidden_layers=1, layers_held=[0])
        net = MODEL.build_net(cfg, SEED)
    eng = engine(net, use_flash_paged="interpret" if how == "kernels"
                 else False)
    served = serve(eng, reqs)
    gaps, _ = MODEL.served_gaps(SEED, cfg, list(zip(reqs, served)))
    assert gaps.size == len(reqs) * 24 and gaps.max() <= TOL
    counts = eng.compile_counts()
    assert counts["decode"] == 1 and counts["chunk_prefill"] == 1
    assert counts["prefill"] == 0 and counts["paged_scatter"] == 0
    # every completed chunk of every row was pooled once a layer: the
    # rounds' last tokens (one more is sampled than is ever cached)
    done = sum((len(p) + 24 - 1) // C for p in reqs)
    assert eng.stats["eva_summaries_written"] >= layers * done
    assert eng.stats["eva_summary_entries_read"] > 0
    assert all(k.pool.used_blocks == 0 for k in eng.kv.kinds)


def test_pool_accounting_at_boundaries_cancel_and_preemption(net):
    eng = engine(net, n_slots=2)
    summary, window = eng.kv.kinds
    assert (summary.span, window.span) == (C * C, C)
    assert (summary.leaves, window.aligned) == (("sk", "sv"), True)
    seen = []

    def watch(eng):
        for tab in eng._kv_tabs:
            if tab is None:
                continue
            s, w = tab.kinds
            floor = tab.length // W * W
            # the window's kind holds nothing below the aligned floor
            # once a round has ended, and everything from it up
            assert min(w.blocks, default=floor // C) * C >= floor
            assert all(g in w.blocks
                       for g in range(floor // C, -(-tab.length // C)))
            # a summary block a ``C`` chunks, none ever released
            assert all(g in s.blocks
                       for g in range(-(-tab.length // (C * C))))
            seen.append((tab.length, eva.visible(tab.length, W, C)))

    reqs = prompts([70, 90], seed=7)
    ids = [eng.submit(Request(list(p), 60)) for p in reqs]
    res = {}
    for _ in range(6):
        eng.step(res)
        watch(eng)
    assert any(n >= 2 * W for n, _ in seen)
    assert all(v == n // W * W // C for n, v in seen)
    released = eng.stats["eva_window_blocks_released"]
    assert released >= 2 * (2 * W // C)    # two windows a row, gone
    assert eng.cancel(ids[0])
    eng.step(res)
    assert eng._kv_tabs.count(None) == 1
    used = [k.pool.used_blocks for k in eng.kv.kinds]
    # a preempted row gives back every block of both kinds
    slot = next(i for i, t in enumerate(eng._kv_tabs) if t is not None)
    eng._preempt_slot(slot)
    assert [k.pool.used_blocks for k in eng.kv.kinds] == [0, 0] != used
    while eng.has_work():
        eng.step(res)
    assert res[ids[0]].finish_reason == "cancelled"
    gaps, _ = MODEL.served_gaps(SEED, CFG,
                                [(reqs[1], list(res[ids[1]].tokens))])
    assert gaps.size == 60 and gaps.max() <= TOL
    assert eng.stats["preempted"] == 1
    assert all(k.pool.used_blocks == 0 for k in eng.kv.kinds)
    assert (eng.stats["eva_window_blocks_released"]
            < eng.stats["eva_window_blocks_allocated"])


REFUSED = {
    "prefix_cache_rows": 4, "kv_host_tier_bytes": 1 << 20,
    "kv_disk_tier_path": "/tmp/never", "spec_draft_len": 2,
    "fused_rounds": 2, "tp": 2, "paranoid": True,
    "block_tokens": 6, "prefill_chunk": 24}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_an_option_this_net_cannot_have_is_refused_by_name(net, option):
    kw = dict(n_slots=2, decode_chunk=8, block_tokens=C,
              prefill_chunk=16)
    kw[option] = REFUSED[option]
    with pytest.raises(ValueError, match=option):
        DecodeEngine(net, **kw)


@pytest.mark.parametrize("call", ["snapshot", "export_kv", "import_kv"])
def test_what_holds_one_kind_of_block_is_refused_by_name(net, call):
    eng = engine(net)
    with pytest.raises(NotImplementedError, match="one kind of KV block"):
        getattr(eng, call)(*(() if call == "snapshot" else ([1, 2, 3],)
                             if call == "export_kv" else (b"",)))


# -- the paged kernel's two new forms ----------------------------------
@pytest.mark.parametrize("t", [1, 256])
def test_kernel_forms_match_the_gather_program(t, short_blocks):
    """One token a row (the short form) and two tiles of an admission
    chunk (the engine's test above runs a chunk of one tile); rows in
    different windows, an idle row, a row inside its first window (no
    summary to read)."""
    win, c, bt, h, d = (64 if t == 1 else 256), 8, 8, 2, 16
    filled = np.asarray([3 * win, 0, win, 0], np.int32)
    if t == 1:
        filled += np.asarray([37, 0, win - 1, 17], np.int32)
    rows, cap = len(filled), 4 * win + t
    key = jax.random.key(t)
    nb, nbs = rows * (win + t) // bt + 8, rows * cap // (bt * c) + 8
    ring, sring = (win + t) // bt + 4, cap // (bt * c) + 2
    table = np.full((rows, ring), -1, np.int32)
    base = np.full((rows, ring), -1, np.int32)
    stable = np.full((rows, sring), -1, np.int32)
    sbase = np.full((rows, sring), -1, np.int32)
    free, sfree = iter(range(nb)), iter(range(nbs))
    for r, n in enumerate(filled):
        if r == 1:
            continue        # an idle row maps nothing
        for g in range(n // win * win // bt, -(-(n + t) // bt)):
            table[r, g % ring], base[r, g % ring] = next(free), g * bt
        for g in range(-(-(n + t) // (bt * c))):
            stable[r, g], sbase[r, g] = next(sfree), g * bt * c
    ks = jax.random.split(key, 9)
    cache = {
        "pk": jax.random.normal(ks[0], (nb, bt, h, d)),
        "pv": jax.random.normal(ks[1], (nb, bt, h, d)),
        "sk": jax.random.normal(ks[2], (nbs, bt, h, d)),
        "sv": jax.random.normal(ks[3], (nbs, bt, h, d)),
        "table": jnp.asarray(table), "base": jnp.asarray(base),
        "stable": jnp.asarray(stable), "sbase": jnp.asarray(sbase),
        "floor": jnp.zeros((rows,), jnp.int32),
        "filled": jnp.asarray(filled)}
    q, k, v = (jax.random.normal(ks[4 + i], (rows, h, t, d))
               for i in range(3))
    mu, phi = (jax.random.normal(ks[7 + i], (h, d)) for i in range(2))
    out = {}
    for toggle in (False, "interpret"):
        out[toggle] = eva.paged(q, k, v, dict(cache), mu, phi,
                                window=win, chunk=c, toggle=toggle)
    live = np.asarray([0, 2, 3])
    np.testing.assert_allclose(out["interpret"][0][live],
                               out[False][0][live], atol=2e-5)
    assert float(jnp.max(jnp.abs(out["interpret"][0][1]))) == 0.0
    for leaf in ("pk", "pv", "sk", "sv"):
        np.testing.assert_array_equal(out["interpret"][1][leaf],
                                      out[False][1][leaf])
    done = sum((n + t) // c - n // c for r, n in enumerate(filled)
               if r != 1)
    assert int(out[False][2]) == int(out["interpret"][2]) == done
