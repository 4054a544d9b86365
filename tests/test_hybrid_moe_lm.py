"""The hybrid Mamba-2 / grouped-KV attention expert LM (nn/layers/
hybrid.py, mamba2.py, moe.py ``dropless_moe``, models/zoo.py
``granite_moe_hybrid_lm``) against its plain reference
(benchmark/models/granite_hybrid_reference.py) at the benchmark's
rehearsal sizes: seeded random weights, float32, on the CPU.

Tolerances, each with its reason:

- logits, program against reference: 2e-5. Both are float32; the
  program sums the recurrence a chunk at a time, the experts' gated sum
  over sorted rows and the softmaxes in another order than the
  reference does, and the logits are ~0.01 in size.
- served gaps: 2e-5, the same quantity read through the serving
  check: at every served position the served token's reference logit
  lies within it of the reference's best.
- chunked scan against the sequential recurrence: 1e-4 on outputs of
  order 1-10 (float32 products of up to 37 positions' decays).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.models import granite_hybrid_reference as reference
from deeplearning4j_tpu.nn.layers import mamba2, moe
from deeplearning4j_tpu.nn.layers.attention import (
    _grouped_scores,
    _grouped_values,
)
from deeplearning4j_tpu.serving import DecodeEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 7


def rehearsal_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite4hs-serve.json")) as f:
        cfg = json.load(f)
    cfg = common.overlay(cfg, cfg["rehearsal"])
    cfg["kernels"] = None      # the plain programs unless a test says
    return common.overlay(cfg, over)


CFG = rehearsal_cfg()
MODEL = common.load_model(CFG, "this test's CFG")


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
    return DecodeEngine(net, block_tokens=16,
                        kv_blocks=64, **kw)


def serve(eng, reqs, n_new=9):
    ids = [eng.submit(Request(list(p), n_new)) for p in reqs]
    res = eng.run()
    return [list(res[i].tokens) for i in ids]


# -- (a) the full forward pass ----------------------------------------
def test_full_forward_matches_the_plain_reference(net):
    toks = np.asarray(prompts([41, 41], seed=3))
    want = reference.forward_logits(SEED, CFG, toks)
    got = jnp.transpose(jnp.log(net.output(toks)), (0, 2, 1))
    want = jax.nn.log_softmax(want, axis=-1)
    assert got.shape == want.shape == (2, 41, CFG["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- (b) through the engine: bucketed masked prefill, then decode ------
@pytest.mark.parametrize("how", ["blocking", "chunked", "kernels"])
def test_engine_serves_the_reference_at_every_position(net, how):
    reqs = prompts([5, 19, 33, 12, 40])
    if how == "kernels":    # the three Pallas kernels, interpreted
        net = MODEL.build_net(rehearsal_cfg(kernels="interpret"), SEED)
    eng = engine(net, prefill_chunk=16 if how == "chunked" else 0,
                 use_flash_paged="interpret" if how == "kernels"
                 else False)
    served = serve(eng, reqs)
    gaps, _ = MODEL.served_gaps(SEED, CFG, list(zip(reqs, served)))
    assert gaps.size == 5 * 9 and gaps.max() <= 2e-5
    stats = eng.stats
    # every live row's every pick was routed, half of them held
    assert stats["moe_picks"] == 2 * (stats["moe_picks_held"]
                                      + stats["moe_picks"] // 2
                                      - stats["moe_picks_held"])
    assert 0.3 < stats["moe_picks_held"] / stats["moe_picks"] < 0.7
    assert stats["moe_experts_touched"] <= 4 * stats["moe_layer_steps"]
    assert stats["ssm_state_rows"] > 0
    assert eng.compile_counts()["decode"] == 1


# -- (c) the chunked scan ---------------------------------------------
def ssm_sequential(x, dt, a, bm, cm, d_skip, s0):
    """The recurrence as written, one position a ``lax.scan`` step: the
    oracle of ``mamba2.ssm_chunk_scan``, same arguments, same result."""
    bsz, t, h, p = x.shape
    g = bm.shape[2]
    xg, dtg, sg = mamba2._grouped(
        x.astype(jnp.float32), dt.astype(jnp.float32), bm, cm,
        s0.astype(jnp.float32))
    ag = a.astype(jnp.float32).reshape(g, h // g)

    def step(s, inp):
        xt, dtt, bt, ct = inp            # [B,G,Hg,P] [B,G,Hg] [B,G,N]
        decay = jnp.exp(dtt * ag)[..., None, None]
        s = decay * s + (dtt[..., None] * xt)[..., None] * bt[
            :, :, None, None, :]
        y = jnp.einsum("bghpn,bgn->bghp", s, ct,
                       precision=jax.lax.Precision.HIGHEST)
        return s, y

    seq = (jnp.moveaxis(xg, 1, 0), jnp.moveaxis(dtg, 1, 0),
           jnp.moveaxis(bm.astype(jnp.float32), 1, 0),
           jnp.moveaxis(cm.astype(jnp.float32), 1, 0))
    s_t, ys = jax.lax.scan(step, sg, seq)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, t, h, p)
    y = y + d_skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y, s_t.reshape(bsz, h, p, -1)


@pytest.mark.parametrize("t,chunk", [(37, 8), (37, 16), (5, 256)])
def test_chunked_scan_matches_the_sequential_recurrence(t, chunk):
    ks = jax.random.split(jax.random.key(t), 7)
    b, h, p, n = 2, 4, 16, 16
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, t, 1, n))
    cm = jax.random.normal(ks[4], (b, t, 1, n))
    d = jax.random.normal(ks[5], (h,))
    s0 = jax.random.normal(ks[6], (b, h, p, n))
    y1, s1 = ssm_sequential(x, dt, a, bm, cm, d, s0)
    y2, s2 = mamba2.ssm_chunk_scan(x, dt, a, bm, cm, d, s0, chunk)
    np.testing.assert_allclose(y2, y1, atol=1e-4)
    np.testing.assert_allclose(s2, s1, atol=1e-4)


def test_a_padded_rows_state_is_the_unpadded_rows(net):
    conf = net.conf.confs[1]          # the first Mamba-2 block
    lc, params = conf.layer, net.params["1"]
    kw = dict(n_heads=lc.ssm_heads, d_head=lc.ssm_d_head,
              d_state=lc.ssm_d_state, n_groups=lc.ssm_groups,
              chunk=lc.ssm_chunk, eps=lc.rms_eps)
    hn = jax.random.normal(jax.random.key(1), (1, 32, lc.n_out))
    mask = (jnp.arange(32) < 21)[None].astype(jnp.float32)
    out_p, st_p = mamba2.mamba2_mixer(params, hn, None, mask, **kw)
    out_u, st_u = mamba2.mamba2_mixer(params, hn[:, :21], None, None,
                                      **kw)
    np.testing.assert_array_equal(st_p["conv"], st_u["conv"])
    np.testing.assert_allclose(st_p["ssm"], st_u["ssm"], atol=1e-6)
    np.testing.assert_allclose(out_p[:, :21], out_u, atol=1e-5)


def test_the_step_kernel_leaves_a_dead_rows_state_alone():
    ks = jax.random.split(jax.random.key(2), 6)
    b, h, p, n = 3, 4, 16, 16
    sp = mamba2.pack_state(jax.random.normal(ks[0], (b, h, p, n)))
    x = jax.random.normal(ks[1], (b, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, h)))
    a = -jnp.exp(jax.random.normal(ks[3], (h,)))
    bm = jax.random.normal(ks[4], (b, 1, n))
    cm = jax.random.normal(ks[5], (b, 1, n))
    live = jnp.asarray([1, 0, 1])
    y_k, s_k = mamba2.ssm_step(sp, x, dt, a, bm, cm, jnp.ones(h), live,
                               "interpret")
    y_p, s_p = mamba2.ssm_step(sp, x, dt, a, bm, cm, jnp.ones(h), live,
                               False)
    np.testing.assert_allclose(s_k, s_p, atol=1e-6)
    np.testing.assert_allclose(y_k, y_p, atol=1e-5)
    np.testing.assert_array_equal(s_k[1], sp[1])
    assert float(jnp.abs(y_k[1]).max()) == 0.0


# -- (d) grouped KV heads ---------------------------------------------
def test_grouped_heads_read_their_kv_head_and_equal_counts_are_plain():
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 4, 5, 8))
    k = jax.random.normal(ks[1], (2, 2, 7, 8))
    v = jax.random.normal(ks[2], (2, 2, 7, 8))
    s = _grouped_scores(q, k)
    np.testing.assert_allclose(
        s, jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)),
        atol=1e-6)
    w = jax.nn.softmax(s, axis=-1)
    np.testing.assert_allclose(
        _grouped_values(w, v),
        jnp.einsum("bhqk,bhkd->bhqd", w, jnp.repeat(v, 2, axis=1)),
        atol=1e-6)
    # the block's own shapes (as many KV heads as query heads) take the
    # product they took before this layer existed, bit for bit
    k4 = jnp.repeat(k, 2, axis=1)
    np.testing.assert_array_equal(
        _grouped_scores(q, k4), jnp.einsum("bhqd,bhkd->bhqk", q, k4))


def test_paged_and_dense_attention_agree_with_grouped_heads(net):
    reqs = prompts([23, 40, 9], seed=5)
    paged = serve(engine(net, use_flash_paged="interpret"), reqs)
    dense = serve(DecodeEngine(net, n_slots=3, decode_chunk=4), reqs)
    assert paged == dense
    pool = engine(net)
    serve(pool, reqs[:1])
    (leaves,) = pool._pool.values()      # one attention layer's KV
    assert leaves["pk"].shape == (64, 16, CFG["num_key_value_heads"],
                                  CFG["hidden_size"]
                                  // CFG["num_attention_heads"])
    assert sorted(pool._slot_state) == ["1", "3"]


# -- (e) the share -----------------------------------------------------
def test_the_two_halves_add_up_to_the_uncut_reference_layer():
    whole = rehearsal_cfg(num_local_experts=8, experts_held=[0, 8])
    from benchmark.models import granite_hybrid_weights as weights

    p = reference._f32(weights.make_layer(
        weights.layer_key(weights.root_key(SEED), 0), whole, "mamba"))
    h = jax.random.normal(jax.random.key(4), (2, 13, whole["hidden_size"]))
    want = reference.experts(p, h, whole, "highest")
    x = h.reshape(26, -1)
    halves = []
    for lo, hi, shared in ((0, 4, True), (4, 8, False)):
        part = {"router": p["router"], "We_in": p["We_in"][lo:hi],
                "We_out": p["We_out"][lo:hi]}
        if shared:       # what both chips compute alike counts once
            part.update(Ws_in=p["Ws_in"], Ws_out=p["Ws_out"])
        y, counts = moe.dropless_moe(part, x, top_k=2,
                                     experts_held=(lo, hi))
        halves.append((y, int(counts["moe_picks_held"])))
    np.testing.assert_allclose(halves[0][0] + halves[1][0],
                               want.reshape(26, -1), atol=1e-5)
    assert halves[0][1] + halves[1][1] == 26 * 2


# -- (f) dropless ------------------------------------------------------
@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_a_batch_routed_wholly_to_one_expert_loses_no_token(kernel):
    ks = jax.random.split(jax.random.key(5), 4)
    d, e, f, m = 16, 4, 8, 40
    params = {"router": jnp.zeros((d, e)).at[:, 2].set(1.0),
              "We_in": jax.random.normal(ks[0], (e, d, 2 * f)) * 0.3,
              "We_out": jax.random.normal(ks[1], (e, f, d)) * 0.3}
    x = jnp.abs(jax.random.normal(ks[2], (m, d)))   # logit 2 is largest
    y, counts = moe.dropless_moe(params, x, top_k=1, experts_held=(0, e),
                                 kernel=kernel)
    want = moe.gated_ffn(x, params["We_in"][2], params["We_out"][2])
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert int(counts["moe_load_max"]) == m
    assert int(counts["moe_experts_touched"]) == 1
    assert int(counts["moe_picks_held"]) == int(counts["moe_picks"]) == m


# -- (g) what a state-carrying net cannot have yet ---------------------
@pytest.mark.parametrize("option,kw", [
    ("prefix_cache_rows", dict(prefix_cache_rows=4)),
    ("kv_host_tier_bytes", dict(kv_host_tier_bytes=1 << 20)),
    ("kv_disk_tier_path", dict(kv_disk_tier_path="/tmp/none")),
    ("spec_draft_len", dict(spec_draft_len=2)),
    ("fused_rounds", dict(fused_rounds=2)),
    ("tp", dict(tp=2)),
])
def test_each_refused_option_raises_with_its_name(net, option, kw):
    with pytest.raises(ValueError, match=option):
        engine(net, **kw)


def test_generate_refuses_an_embedding_first_net_by_name(net):
    with pytest.raises(ValueError, match="EmbeddingLayer, sequence"):
        net.generate(np.zeros((1, 4), np.int32), 2)


def test_the_embedding_layer_takes_a_sequence_of_ids(net):
    conf = net.conf.confs[0]
    assert conf.layer.sequence and conf.layer.takes_token_ids
    assert sorted(net.params["0"]) == ["W"]               # no bias
    ids = np.asarray(prompts([7, 7], seed=1))
    from deeplearning4j_tpu.nn.layers import get_impl

    out, _ = get_impl(conf.layer).apply(conf, net.params["0"], ids)
    want = np.asarray(net.params["0"]["W"])[ids] * conf.layer.multiplier
    np.testing.assert_allclose(out, np.transpose(want, (0, 2, 1)),
                               rtol=1e-6)
    # the bean's new fields survive the serializer; without them the
    # layer is the index column -> row it always was
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.confs[0].layer == conf.layer


def test_a_stepping_user_carries_the_mixers_state_and_nothing_else(net):
    net.rnn_clear_previous_state()
    net.rnn_time_step(np.asarray(prompts([6], seed=2)))
    state = net._rnn_state
    assert sorted(state["1"]) == ["conv", "ssm"]          # a Mamba block
    assert "counters" not in state["2"] and "live" not in state["2"]
    net.rnn_clear_previous_state()


# -- (h) a slot released and reused carries nothing over ---------------
def test_a_reused_slot_carries_nothing_over(net):
    first, second = prompts([37, 11], seed=9)
    eng = engine(net, n_slots=1)
    one_after_the_other = serve(eng, [first, second])
    alone = serve(engine(net, n_slots=1), [second])
    assert one_after_the_other[1] == alone[0]
    assert eng.stats["evicted"] == 2
    # nothing zeroes a released row: the next admission overwrites it
    # whole, and a row no request holds is neither read nor written
    assert "state_clear" not in eng.compile_counts()


# -- the command line ---------------------------------------------------
def test_cli_serves_the_zoo_model_from_token_ids(tmp_path):
    """``dl4j-tpu serve --model hybrid.zip``: the zoo builder's net
    through the serializer, the CLI's engine and gateway, token ids
    over the wire."""
    from deeplearning4j_tpu.cli.driver import (
        build_parser,
        gateway_from_args,
    )
    from deeplearning4j_tpu.models.zoo import granite_moe_hybrid_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.client import GatewayClient
    from deeplearning4j_tpu.util.model_serializer import write_model

    tiny = MultiLayerNetwork(granite_moe_hybrid_lm(
        experts_held=(0, 4), max_position_embeddings=64)).init()
    path = str(tmp_path / "hybrid.zip")
    write_model(tiny, path)
    args = build_parser().parse_args(
        ["serve", "--model", path, "--port", "0", "--slots", "2",
         "--kv-blocks", "32", "--use-flash-paged", "off"])
    gw = gateway_from_args(args).start()
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        out = GatewayClient(gw.address).generate(prompt, 6)
        want, seq = [], list(prompt)
        for _ in range(6):
            want.append(int(np.asarray(
                tiny.output(np.asarray([seq])))[0, :, -1].argmax()))
            seq.append(want[-1])
        assert out["tokens"] == want
    finally:
        gw.close()
