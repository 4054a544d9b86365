"""LFM2-8B-A1B's stack (``lfm2_moe``), trained, at a small size on the
CPU with seeded random weights: the gated short-convolution mixer
against a literal sum and through the streaming state; ``jax.grad``
through the dropless expert layer with the grouped kernel in
``interpret`` against ``ragged_dot``; the chip's share tied to the
model (four shares' outputs and gradients add up to the uncut
reference's); the layer's rules under a gradient against its full-width
form (the held pairs' slabs, ``nan`` in what they leave unwritten, a
served program's text); integer labels against the one-hot loss, and no one-hot
of the vocabulary in the step's program; the program's logits, loss,
every leaf's gradient and three Adam steps against the plain reference
(``benchmark/models/lfm2_moe_reference.py``); ``expert_bias`` a leaf no
updater moves."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, reference
from benchmark.models import lfm2_moe_reference as ref
from benchmark.models import lfm2_moe_weights as weights
from deeplearning4j_tpu.models.zoo import lfm2_moe_lm
from deeplearning4j_tpu.nn.layers import hybrid, moe
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2-8b-a1b-train.json")) as f:
    FILE = json.load(f)
#: the configuration's rehearsal sizes, float32 throughout
CFG = common.overlay(FILE, FILE["rehearsal"])
MODEL = common.load_model(CFG, "lfm2-8b-a1b-train.json")
HYPER = dict(FILE["optimizer"], lr_warmup_steps=2)
SEED = 2147483659      # past 2**31, as the driver's


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], shape).astype(np.int32)


# ---------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------
def test_short_conv_is_the_literal_per_position_sum():
    d, t, k = 8, 11, 3
    rng = np.random.default_rng(1)
    p = {"W_in": rng.normal(size=(d, 3 * d)), "conv_w": rng.normal(
        size=(k, d)), "W_out": rng.normal(size=(d, d))}
    h = rng.normal(size=(2, t, d))
    proj = h @ p["W_in"]
    b, c, u = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    bu = b * u
    want = np.zeros_like(h)
    for n in range(2):
        for pos in range(t):
            y = np.zeros(d)
            for j in range(k):
                src = pos - (k - 1) + j
                if src >= 0:
                    y += p["conv_w"][j] * bu[n, src]
            want[n, pos] = (c[n, pos] * y) @ p["W_out"]
    with jax.enable_x64(True):
        got, state = hybrid.short_conv_mixer(
            {n: jnp.asarray(v) for n, v in p.items()}, jnp.asarray(h),
            None, None)
        # (the taps are summed and gated in float32 whatever comes in)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)
        # the carried state is the last K - 1 gated inputs
        np.testing.assert_allclose(np.asarray(state["conv"]),
                                   bu[:, -(k - 1):], rtol=1e-12)
        # and the plain reference's mixer is the same sum
        row = ref.short_conv({n: jnp.asarray(v, jnp.float32)
                              for n, v in p.items()},
                             jnp.asarray(h[0], jnp.float32), "highest")
    np.testing.assert_allclose(np.asarray(row), want[0], rtol=2e-4,
                               atol=2e-4)


def _small_net(**kw):
    conf = lfm2_moe_lm(vocab_size=64, layers=[0, 1, 2, 3],
                       experts_held=(0, 4), **kw)
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("prefix", [1, 2, 9])
def test_streaming_after_a_prefix_agrees_with_the_full_pass(prefix):
    net = _small_net()
    x = _ids((2, 16))
    full = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    outs = [np.asarray(net.rnn_time_step(x[:, :prefix]))]
    outs += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))
             for t in range(prefix, 16)]
    np.testing.assert_allclose(np.concatenate(outs, axis=2), full,
                               atol=2e-6)
    tail = net._rnn_state["1"]["conv"]       # layer 1 is a conv layer
    assert tail.shape == (2, 2, 64)


def test_a_right_padded_prefix_leaves_the_tail_of_its_valid_part():
    rng = np.random.default_rng(3)
    p = {"W_in": jnp.asarray(rng.normal(size=(8, 24)), jnp.float32),
         "conv_w": jnp.asarray(rng.normal(size=(3, 8)), jnp.float32),
         "W_out": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(1, 9, 8)), jnp.float32)
    mask = jnp.asarray([[1] * 5 + [0] * 4], jnp.float32)
    _, padded = hybrid.short_conv_mixer(p, h, None, mask)
    _, exact = hybrid.short_conv_mixer(p, h[:, :5], None, None)
    np.testing.assert_allclose(np.asarray(padded["conv"]),
                               np.asarray(exact["conv"]), rtol=1e-6)


# ---------------------------------------------------------------------
# the expert layer under a gradient
# ---------------------------------------------------------------------
def _expert_layer(seed=0, d=16, f=8, e=8, m=24):
    rng = np.random.default_rng(seed)
    full = {"router": rng.normal(size=(d, e)) * 0.5,
            "We_in": rng.normal(size=(e, d, 2 * f)) * 0.3,
            "We_out": rng.normal(size=(e, f, d)) * 0.3,
            "expert_bias": rng.normal(size=(e,)) * 0.05}
    full = {n: jnp.asarray(v, jnp.float32) for n, v in full.items()}
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    return full, x, probe


def _share(full, lo, hi):
    return dict(full, We_in=full["We_in"][lo:hi],
                We_out=full["We_out"][lo:hi])


def _share_value(p, x, probe, held, kernel):
    y, _ = moe.dropless_moe(p, x, top_k=3, experts_held=held,
                            kernel=kernel, gate_rule="sigmoid_bias",
                            route_eps=1e-6)
    return jnp.sum(y * probe), y


@pytest.mark.parametrize("held", [(0, 8), (2, 6), (5, 8)])
def test_grad_through_the_grouped_kernel_is_ragged_dots(held):
    """``interpret`` runs the library's Pallas kernel and ITS transpose
    rule (the kernel on the transposed weights, ``tgmm``); rows past
    the held groups are never written either way and must reach
    neither the value nor a gradient."""
    full, x, probe = _expert_layer()
    p = _share(full, *held)

    def grads(kernel):
        (_, y), g = jax.value_and_grad(
            lambda p, x: _share_value(p, x, probe, held, kernel),
            argnums=(0, 1), has_aux=True)(p, x)
        return y, g

    y_k, (gp_k, gx_k) = grads("interpret")
    y_r, (gp_r, gx_r) = grads(False)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(gx_k, gx_r, rtol=2e-5, atol=2e-6)
    for name in ("router", "We_in", "We_out"):
        np.testing.assert_allclose(gp_k[name], gp_r[name], rtol=2e-5,
                                   atol=2e-6, err_msg=name)
    # the selection bias reaches the loss through the picks alone
    assert not np.any(np.asarray(gp_k["expert_bias"]))
    assert np.all(np.isfinite(np.asarray(gx_k)))


def test_four_shares_add_up_to_the_uncut_reference():
    """The share tied to the model: what the four chips' expert layers
    give, each for its own experts, adds up to the uncut layer of the
    plain reference, in the value and in the gradients with respect to
    the layer's input and the router."""
    full, x, probe = _expert_layer(seed=4)
    cfg = {"num_experts_per_tok": 3, "routed_scaling_factor": 1,
           "experts_held": (0, 8), "freeze_router": False}

    def uncut(p, x):
        y = ref.experts(p, x, cfg, "highest")
        return jnp.sum(y * probe), y

    (_, y_ref), (gp_ref, gx_ref) = jax.value_and_grad(
        uncut, argnums=(0, 1), has_aux=True)(full, x)
    y_sum, gx_sum, gr_sum = 0.0, 0.0, 0.0
    for lo in range(0, 8, 2):
        held = (lo, lo + 2)
        (_, y), (gp, gx) = jax.value_and_grad(
            lambda p, x: _share_value(p, x, probe, held, False),
            argnums=(0, 1), has_aux=True)(_share(full, *held), x)
        y_sum, gx_sum, gr_sum = y_sum + y, gx_sum + gx, gr_sum + gp[
            "router"]
        # a share's own experts get the uncut layer's gradient
        np.testing.assert_allclose(gp["We_in"],
                                   gp_ref["We_in"][lo:lo + 2],
                                   rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(y_sum, y_ref, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(gx_sum, gx_ref, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(gr_sum, gp_ref["router"], rtol=2e-4,
                               atol=2e-6)


# ---------------------------------------------------------------------
# the row movements' transpose rule: gathers by the inverse permutation
# ---------------------------------------------------------------------
D_ROWS = 20            # the layer's width here; no other size is 20


@jax.custom_vjp
def _cotangent_inside(sizes, x):
    """``x`` itself going forward; going back, its cotangent's rows
    within the groups (the first ``sum(sizes)``) and 0 past them."""
    return x


_cotangent_inside.defvjp(
    lambda sizes, x: (x, sizes),
    lambda sizes, g: (None, jnp.where(
        (jnp.arange(g.shape[0]) < jnp.sum(sizes))[:, None], g, 0)))


def _plain_sorted_pairs(top_k, n_held, tokens, expert, held):
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    inside = (jnp.arange(expert.shape[0]) < jnp.sum(sizes))[:, None]
    return (order, sizes, inside,
            _cotangent_inside(sizes, tokens[order // top_k]),
            jnp.int32(expert.shape[0]))


def _plain_gated_rows(gu, sizes):
    gu = _cotangent_inside(sizes, gu)
    f = gu.shape[1] // 2
    return jax.nn.silu(gu[:, :f]) * gu[:, f:]


def _plain_combine(ys, gates, held, inside, order, sizes):
    m, top_k = gates.shape
    picked = jnp.where(inside, ys, 0)[jnp.argsort(order)].reshape(
        m, top_k, -1).astype(jnp.float32)
    return jnp.sum(picked * jnp.where(held, gates, 0.0)[..., None], axis=1)


def _plain_indexing(patch):
    """The layer in its full-width form, as plain indexing writes it:
    every pass over all ``M k`` pairs' rows, a select over the sorted
    pairs' rows on either side and on the first product's cotangent,
    one gather of all the pairs each way (autodiff transposes each into
    a scatter-add) and the float32 ``[M, k, D]`` under the weighted
    sum."""
    patch.setattr(moe, "_sorted_pairs", _plain_sorted_pairs)
    patch.setattr(moe, "_gated_rows", _plain_gated_rows)
    patch.setattr(moe, "_combine_picks", _plain_combine)


def _loss_and_counts(p, x, probe, valid, held, top_k,
                     rule="sigmoid_bias", frozen=False, kernel=False):
    y, counts = moe.dropless_moe(
        p, x, valid, top_k=top_k, experts_held=held, kernel=kernel,
        gate_rule=rule, route_eps=1e-6, detach_scores=frozen)
    return jnp.sum(y * probe), counts


def _layer_loss(*args):
    return _loss_and_counts(*args)[0]


@pytest.mark.parametrize("frozen", [True, False],
                         ids=["frozen", "learning"])
@pytest.mark.parametrize("rule", moe.GATE_RULES)
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("top_k", [1, 3])
@pytest.mark.parametrize("held", [(0, 8), (2, 6), (5, 8)])
def test_row_movements_transpose_as_plain_indexing_does(
        monkeypatch, held, top_k, masked, rule, frozen):
    """The combine's rule and the row movement's against plain
    indexing: the value, the input's gradient and every leaf's. The
    router's gradient is the rule's ``d_gates`` through :func:`route`;
    with the routing frozen it is zero on both sides."""
    full, x, probe = _expert_layer(seed=7, d=D_ROWS)
    p = _share(full, *held)
    valid = (jnp.asarray(np.random.default_rng(8).random(x.shape[0]) < 0.7)
             if masked else None)

    def grads():
        return jax.value_and_grad(_layer_loss, argnums=(0, 1))(
            p, x, probe, valid, held, top_k, rule, frozen)

    value, (gp, gx) = grads()
    with monkeypatch.context() as patch:
        _plain_indexing(patch)
        value_plain, (gp_plain, gx_plain) = grads()
    np.testing.assert_allclose(value, value_plain, rtol=2e-5)
    np.testing.assert_allclose(gx, gx_plain, rtol=2e-5, atol=2e-6)
    for name in ("router", "We_in", "We_out"):
        np.testing.assert_allclose(gp[name], gp_plain[name], rtol=2e-5,
                                   atol=2e-6, err_msg=name)
    assert np.any(np.asarray(gx)) and np.any(np.asarray(gp["We_out"]))
    if frozen or top_k > 1:     # (one softmax pick's gate is 1, whatever)
        assert np.any(np.asarray(gp["router"])) != frozen
        assert np.any(np.asarray(gp_plain["router"])) != frozen
    if masked:      # a row that does not exist takes no gradient
        assert not np.any(np.asarray(gx)[~np.asarray(valid)])


# ---------------------------------------------------------------------
# the training passes around the products: the held pairs' rows only
# ---------------------------------------------------------------------
SLAB = 8               # ``moe._SLAB_ROWS`` here, so that 24 pairs are 3


#: the held range by the share of the picks it holds; ``none`` holds
#: experts no token picks (their selection bias is -100)
SHARES = {"none": (2, 6), "quarter": (0, 2), "mid_slab": (3, 8),
          "every": (0, 8)}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("top_k", [1, 3])
@pytest.mark.parametrize("share", list(SHARES))
def test_training_passes_cover_the_held_pairs_rows_and_no_others(
        monkeypatch, share, top_k, masked):
    """Under a gradient the gather of ``xs``, the activation forward and
    back and the combine's ``d_ys`` run a slab of rows at a time as far
    as the held pairs reach: the layer's value and EVERY gradient
    (tokens, ``We_in``, ``We_out``, the router's through the learning
    gates) are the full-width form's, whether no pick is held, a
    quarter, a share that ends inside a slab, or every pick; and
    ``moe_pair_rows_worked`` reads the slabs' rows, within one slab of
    ``moe_picks_held`` (the full-width form works all ``M k``)."""
    monkeypatch.setattr(moe, "_SLAB_ROWS", SLAB)
    full, x, probe = _expert_layer(seed=7, d=D_ROWS)
    held = SHARES[share]
    if share == "none":
        full["expert_bias"] = full["expert_bias"].at[
            held[0]:held[1]].set(-100.0)
    p = _share(full, *held)
    valid = (jnp.asarray(np.random.default_rng(8).random(x.shape[0]) < 0.7)
             if masked else None)
    pairs = x.shape[0] * top_k

    def grads():
        return jax.value_and_grad(_loss_and_counts, argnums=(0, 1),
                                  has_aux=True)(
            p, x, probe, valid, held, top_k)

    (value, counts), (gp, gx) = grads()
    with monkeypatch.context() as patch:
        _plain_indexing(patch)
        (value_plain, counts_plain), (gp_plain, gx_plain) = grads()
    np.testing.assert_allclose(value, value_plain, rtol=2e-5)
    np.testing.assert_allclose(gx, gx_plain, rtol=2e-5, atol=2e-6)
    for name in ("router", "We_in", "We_out"):
        np.testing.assert_allclose(gp[name], gp_plain[name], rtol=2e-5,
                                   atol=2e-6, err_msg=name)
    n_in, worked = (int(counts[name]) for name in (
        "moe_picks_held", "moe_pair_rows_worked"))
    assert worked == -(-n_in // SLAB) * SLAB <= pairs
    assert int(counts_plain["moe_pair_rows_worked"]) == pairs
    assert int(counts_plain["moe_picks_held"]) == n_in
    if share == "none":
        assert n_in == worked == 0 and not np.any(np.asarray(gx))
    else:
        assert np.any(np.asarray(gx)) and np.any(np.asarray(gp["We_in"]))
        if top_k > 1:
            assert np.any(np.asarray(gp["router"]))
    if share == "quarter":
        assert 0 < n_in < pairs // 2 and worked < pairs
    if share == "mid_slab":
        assert n_in % SLAB and worked < pairs
    if share == "every" and not masked:
        assert n_in == worked == pairs


def _poisoned(rows, n_inside):
    return rows.at[n_inside:].set(jnp.nan)


def _unwritten_is_nan(patch):
    """What a training pass never writes holds ``nan`` (a buffer the
    program starts uninitialised: here, made of ``nan``)."""
    patch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))


@pytest.mark.parametrize("top_k", [1, 3])
def test_rows_never_written_reach_neither_value_nor_gradient(
        monkeypatch, top_k):
    """Select, do not scale, and read no row past the groups: the
    sorted pairs' rows past the held groups are never written by the
    grouped kernel, going either way, nor by the training passes
    around it. With ``nan`` in every such row of ``ys`` the combine's
    value and its gradients (the held rows', the gates') are finite and
    the clean input's; so are the activation's value and cotangent
    below the groups' end from ``nan`` past it in ``gu`` and in the
    cotangent of ``act``, and the tokens' cotangent from a poisoned
    cotangent of ``xs``, for a share with picks not held and a
    ``valid`` mask; and what the passes leave unwritten is left alone,
    not zeroed."""
    monkeypatch.setattr(moe, "_SLAB_ROWS", SLAB)
    _unwritten_is_nan(monkeypatch)
    rng = np.random.default_rng(12)
    m, d, e, lo, hi = 24, D_ROWS, 8, 2, 6
    idx = jnp.asarray(rng.integers(0, e, (m, top_k)))
    valid = jnp.asarray(rng.random(m) < 0.7)
    held = (idx >= lo) & (idx < hi) & valid[:, None]
    n_inside = int(jnp.sum(held))
    assert 0 < n_inside < m * top_k
    expert = jnp.where(held, idx - lo, hi - lo).reshape(-1)
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=hi - lo + 1)[:hi - lo]
    inside = (jnp.arange(m * top_k) < n_inside)[:, None]
    #: where the last slab ends: rows past it are never written
    n_worked = min(-(-n_inside // SLAB) * SLAB, m * top_k)
    gates = jnp.asarray(rng.random((m, top_k)) + 0.1, jnp.float32)
    ys = jnp.asarray(rng.normal(size=(m * top_k, d)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)

    def combined(ys, gates):
        y = moe._combine_picks(ys, gates, held, inside, order, sizes)
        return jnp.sum(y * probe), y

    (_, y), (g_ys, g_gates) = jax.value_and_grad(
        combined, argnums=(0, 1), has_aux=True)(ys, gates)
    (_, y_bad), (g_ys_bad, g_gates_bad) = jax.value_and_grad(
        combined, argnums=(0, 1), has_aux=True)(
            _poisoned(ys, n_inside), gates)
    # (no gradient asked: the rule's primal selects too)
    y_primal = moe._combine_picks(_poisoned(ys, n_inside), gates, held,
                                  inside, order, sizes)
    for got, want in ((y_bad, y), (g_ys_bad[:n_inside], g_ys[:n_inside]),
                      (g_gates_bad, g_gates), (y_primal, y)):
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert np.any(np.asarray(g_gates)) and np.any(np.asarray(g_ys))
    # a held pair's cotangent is its gate times its token's row of the
    # value's; past the last slab nothing is written, and a pair not
    # held gives its gate nothing
    np.testing.assert_allclose(
        g_ys[:n_inside], (gates.reshape(-1)[:, None] * jnp.repeat(
            probe, top_k, axis=0))[order][:n_inside], rtol=1e-6)
    assert np.all(np.isnan(np.asarray(g_ys)[n_worked:]))
    assert not np.any(np.asarray(g_gates)[~np.asarray(held)])

    # the gated activation between the products
    gu = jnp.asarray(rng.normal(size=(m * top_k, 2 * d)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(m * top_k, d)), jnp.float32)
    act_plain, back_plain = jax.vjp(
        lambda gu: jax.nn.silu(gu[:, :d]) * gu[:, d:], gu)
    act, back = jax.vjp(lambda gu: moe._gated_rows(gu, sizes),
                        _poisoned(gu, n_inside))
    (d_gu,), (d_gu_plain,) = back(_poisoned(cot, n_inside)), back_plain(cot)
    for got, want in ((act, act_plain), (d_gu, d_gu_plain)):
        assert np.all(np.isfinite(np.asarray(got)[:n_inside]))
        np.testing.assert_allclose(got[:n_inside], want[:n_inside],
                                   rtol=1e-6, atol=1e-7)
        # (``act`` starts uninitialised; the cotangent is written over
        # ``gu``, poisoned here)
        assert np.all(np.isnan(np.asarray(got)[n_worked:]))
    np.testing.assert_array_equal(     # no gradient asked: every row
        moe._gated_rows(gu, sizes), act_plain)

    tokens = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(m * top_k, d)), jnp.float32)
    (order_r, sizes_r, inside_r, xs, worked), back = jax.vjp(
        lambda t: moe._sorted_pairs(top_k, hi - lo, t, expert, held),
        tokens)
    np.testing.assert_array_equal(order_r, order)
    np.testing.assert_array_equal(sizes_r, sizes)
    np.testing.assert_array_equal(inside_r, inside)
    assert int(worked) == -(-n_inside // SLAB) * SLAB
    np.testing.assert_array_equal(xs[:n_inside],
                                  tokens[order // top_k][:n_inside])
    assert np.all(np.isnan(np.asarray(xs)[n_worked:]))
    no = [np.zeros(a.shape, jax.dtypes.float0)
          for a in (order_r, sizes_r, inside_r)]

    def tokens_cotangent(d_xs):
        return back((*no, d_xs, np.zeros((), jax.dtypes.float0)))[0]

    g_tokens = tokens_cotangent(jnp.where(inside, cot, 0))
    g_tokens_bad = tokens_cotangent(_poisoned(cot, n_inside))
    assert np.all(np.isfinite(np.asarray(g_tokens_bad)))
    np.testing.assert_allclose(g_tokens_bad, g_tokens, rtol=1e-6)
    assert not np.any(np.asarray(g_tokens)[~np.asarray(valid)])


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_the_layer_reads_nothing_its_passes_left_unwritten(
        monkeypatch, kernel):
    """The whole layer under a gradient, ``nan`` wherever a training
    pass wrote nothing (``xs``, ``act``, the cotangents of ``gu`` and
    of ``ys``, past the held pairs' last slab): the value and every
    gradient are finite and are those of buffers that start as zeros,
    through ``ragged_dot`` and through the library's kernel and its
    transposes."""
    monkeypatch.setattr(moe, "_SLAB_ROWS", SLAB)
    full, x, probe = _expert_layer()
    held, top_k = (2, 6), 3
    valid = jnp.asarray(np.random.default_rng(8).random(x.shape[0]) < 0.7)

    def grads():
        return jax.value_and_grad(_loss_and_counts, argnums=(0, 1),
                                  has_aux=True)(
            _share(full, *held), x, probe, valid, held, top_k,
            kernel=kernel)

    (value, counts), (gp, gx) = grads()
    assert int(counts["moe_pair_rows_worked"]) < x.shape[0] * top_k
    with monkeypatch.context() as patch:
        _unwritten_is_nan(patch)
        (value_bad, _), (gp_bad, gx_bad) = grads()
    for got, want in ((value_bad, value), (gx_bad, gx), *(
            (gp_bad[name], gp[name]) for name in gp)):
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert np.any(np.asarray(gx)) and np.any(np.asarray(gp["router"]))


def _row_scatters(text):
    """The operand types of a lowered program's ``scatter`` operations
    over floating rows of the layer's width."""
    operands = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)', text,
        re.DOTALL)
    return [t for t in operands
            if re.fullmatch(rf"tensor<\d+x{D_ROWS}x(f|bf)\d+>", t)]


def _row_results(text, op):
    """How many rows each ``op`` of a lowered program gives (a line's
    last type is its result's), for those whose result is floating
    rows of the layer's width."""
    results = re.findall(
        rf'stablehlo\.{op}\b[^\n]*tensor<(\d+)x{D_ROWS}x(?:f|bf)\d+>\n',
        text)
    return sorted(int(n) for n in results)


@pytest.mark.parametrize("top_k", [1, 3])
def test_the_layers_gradient_scatters_no_row(monkeypatch, top_k):
    """The rules are engaged: ``jax.grad`` of the layer lowers to
    gathers alone over the pairs' rows (the gates' ``[M, E]`` scatter
    stays, the ONE scatter left: the sizes are counted by comparison),
    a pick at a time, and to the four loops over the held pairs' slabs,
    where plain indexing's holds the two scatter-adds, ``bincount``'s,
    the float32 ``[M, k, D]``, selects over the sorted pairs' rows and
    no loop; forward, the layer's program is plain indexing's."""
    full, x, probe = _expert_layer(seed=7, d=D_ROWS)
    held = (2, 6)
    args = (_share(full, *held), x, probe, None, held, top_k,
            "sigmoid_bias")
    m = x.shape[0]

    def lowered():
        # (a jit of its own each time: nothing traced before the swap)
        # (the value too, as a training step asks: the probe's loss is
        # linear in the layer's value, whose forward would else be dead)
        grad = jax.jit(jax.value_and_grad(_layer_loss, argnums=(0, 1)),
                       static_argnums=(4, 5, 6)).lower(*args).as_text()
        fwd = jax.jit(_layer_loss,
                      static_argnums=(4, 5, 6)).lower(*args).as_text()
        return grad, fwd

    grad, fwd = lowered()
    with monkeypatch.context() as patch:
        _plain_indexing(patch)
        grad_plain, fwd_plain = lowered()
    assert _row_scatters(grad) == []
    scatter = '"stablehlo.scatter"('
    assert grad.count(scatter) == 1          # the gates'
    assert grad_plain.count(scatter) == 4    # ... bincount's, the rows'
    assert len(_row_scatters(grad_plain)) == 2
    # the gather of xs, the activation forward and back, d_ys; each
    # but the one over ``gu`` in a conditional that scopes its buffer
    assert grad.count("stablehlo.while") == 4
    assert grad.count("stablehlo.case") == 3
    assert "stablehlo.while" not in grad_plain + fwd
    assert "stablehlo.case" not in grad_plain + fwd
    # plain indexing: all the pairs' rows at once, forward, both ways
    assert _row_results(grad_plain, "gather") == [m * top_k] * 2
    # the rules: the tokens' rows for the pairs and, going back, the
    # pairs' rows of the [M, D] cotangent; [M, D] rows a pick for the
    # value, for the gates' cotangent and for the tokens'
    assert _row_results(grad, "gather") == sorted(
        [m * top_k] * 2 + [m] * (3 * top_k))
    gather = '"stablehlo.gather"('
    # (and the held pairs' gates in the sorted order, numbers)
    assert grad.count(gather) == grad_plain.count(gather) + 3 * top_k + 1
    # no float32 [M, k, D], and no select over the sorted pairs' rows
    # (the one on ``gu`` is as wide as the experts, not as the layer)
    picked = f"tensor<{m}x{top_k}x{D_ROWS}xf32>"
    assert picked in grad_plain and picked not in grad
    if top_k > 1:
        assert m * top_k in _row_results(grad_plain, "select")
        assert m * top_k not in _row_results(grad, "select")
        assert m in _row_results(grad, "select")
    # forward only (a served program): the same gathers, nothing more
    assert fwd.count(gather) == fwd_plain.count(gather) >= 2
    assert fwd == fwd_plain


@pytest.mark.parametrize("rule", moe.GATE_RULES)
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("top_k", [1, 3])
def test_with_no_gradient_asked_the_layer_is_plain_indexings_program(
        monkeypatch, top_k, masked, rule):
    """A served program is what it was: with no gradient asked the
    layer, its value and the counts a served block returns, lowers to
    the text of plain indexing character for character, whatever rules
    stand by for a gradient: no loop, no uninitialised buffer, the
    sizes by ``bincount``. (``scripts/compile_cell.py --hash`` says the
    same of the served cells' programs at their real sizes.)"""
    full, x, _ = _expert_layer(seed=7, d=D_ROWS)
    held = (2, 6)
    valid = (jnp.asarray(np.random.default_rng(8).random(x.shape[0]) < 0.7)
             if masked else None)

    def served(p, x, valid):
        y, counts = moe.dropless_moe(
            p, x, valid, top_k=top_k, experts_held=held, kernel=False,
            gate_rule=rule, route_eps=1e-6)
        del counts["moe_pair_rows_worked"]      # as the block does
        return y, counts

    def lowered():
        return jax.jit(served).lower(_share(full, *held), x,
                                     valid).as_text()

    text = lowered()
    with monkeypatch.context() as patch:
        _plain_indexing(patch)
        assert text == lowered()
    assert "stablehlo.while" not in text and "empty" not in text.lower()
    assert text.count('"stablehlo.scatter"(') == 1      # bincount's


def test_route_names_its_epsilon():
    logits = jnp.asarray([[4.0, -6.0, -6.0, 3.0]])
    g_small, _ = moe.route(logits, 2, "sigmoid_bias", eps=1e-20)
    g_large, _ = moe.route(logits, 2, "sigmoid_bias", eps=0.5)
    assert abs(float(jnp.sum(g_small)) - 1.0) < 1e-6
    s = jax.nn.sigmoid(logits[0, jnp.asarray([0, 3])])
    np.testing.assert_allclose(g_large[0], s / (jnp.sum(s) + 0.5),
                               rtol=1e-6)


# ---------------------------------------------------------------------
# ids in, ids as labels
# ---------------------------------------------------------------------
def test_integer_labels_give_the_one_hot_loss_and_gradient():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(2, 7, 33)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 33, (2, 7)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (2, 7)), jnp.float32)
    mcxent = losses.loss_fn("mcxent")

    def one_hot(z, m):
        probs = jnp.transpose(jax.nn.softmax(z, axis=-1), (0, 2, 1))
        hot = jnp.transpose(jax.nn.one_hot(labels, 33), (0, 2, 1))
        return mcxent(probs, hot, m)

    for m in (None, mask):
        a, ga = jax.value_and_grad(
            lambda z: losses.label_cross_entropy(z, labels, m))(logits)
        b, gb = jax.value_and_grad(lambda z: one_hot(z, m))(logits)
        np.testing.assert_allclose(a, b, rtol=1e-5)
        np.testing.assert_allclose(ga, gb, rtol=1e-4, atol=1e-7)


def _eqn_outputs(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqn_outputs(sub)


def test_the_step_makes_no_one_hot_of_the_vocabulary():
    """The step's program takes int32 ids and int32 labels, and nothing
    of [tokens, vocab] numbers in it is a comparison turned into
    numbers (a one-hot). At 512 tokens over 4,096 rows, where such an
    array is 8 MiB and everything else of the net is small, the
    compiled step's scratch is the logits, their gradient and under
    one more copy for all the rest (the CPU compiler's memory analysis:
    2.9 copies; probabilities and a one-hot label would each add
    one)."""
    def net_of(vocab, **kw):
        return MultiLayerNetwork(lfm2_moe_lm(
            vocab_size=vocab, layers=[1, 2], experts_held=(0, 4),
            **kw)).init()

    net = net_of(512)
    ids = jnp.zeros((1, 2, 24), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, s, u, f, y: net._train_steps_scan.__wrapped__(
            p, s, u, 0, jax.random.key(0), f, y))(
        net.params, net.state, net.updater_state, ids, ids)
    ins = [v.aval for v in closed.jaxpr.invars[-2:]]
    assert [(a.shape, a.dtype) for a in ins] == [((1, 2, 24), jnp.int32)] * 2
    big = 2 * 24 * 512
    seen, hot = 0, []
    for eqn in _eqn_outputs(closed.jaxpr):
        for out in eqn.outvars:
            aval = out.aval
            # [.., tokens, vocab]-shaped: all the tokens by all the rows
            if (not hasattr(aval, "shape") or 512 not in aval.shape
                    or np.prod(aval.shape) % big):
                continue
            seen += 1
            if (eqn.primitive.name == "convert_element_type"
                    and eqn.invars[0].aval.dtype == jnp.bool_):
                hot.append(eqn)
    assert seen and not hot, (seen, hot)

    net = net_of(4096, hidden_size=32, num_attention_heads=2,
                 num_key_value_heads=1, intermediate_size=32,
                 moe_intermediate_size=16)
    ids = jnp.zeros((1, 2, 256), jnp.int32)
    analysis = net._train_steps_scan.lower(
        net.params, net.state, net.updater_state, 0, jax.random.key(0),
        ids, ids).compile().memory_analysis()
    assert analysis.temp_size_in_bytes < 3.5 * (512 * 4096 * 4)


def test_fit_scan_trains_on_ids_and_the_loss_falls():
    net = _small_net(lr=3e-3, warmup_steps=1)
    assert net.takes_token_ids and net.takes_label_ids
    toks = _ids((6, 4, 17), seed=7)
    first = float(np.asarray(net.fit_scan(toks[:1, :, :-1],
                                          toks[:1, :, 1:]))[0])
    for _ in range(12):
        scores = net.fit_scan(toks[:, :, :-1], toks[:, :, 1:])
    assert abs(first - np.log(64)) < 0.1
    assert float(np.asarray(scores)[-1]) < first - 0.5
    # fit() takes the same ids, and score() reads the same loss
    from deeplearning4j_tpu.datasets.dataset import DataSet

    ds = DataSet(toks[0, :, :-1], toks[0, :, 1:])
    before = net.score(ds)
    net.fit(ds)
    assert np.isfinite(before) and net.iteration == 74


def test_every_entry_point_keeps_ids_whole():
    """``compute_gradient_and_score`` and ``feed_forward`` read ids and
    id labels as ``fit`` and ``score`` do: a float cast would round an
    id over 256 under bfloat16 and hand the head float labels."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = _small_net(compute_dtype="bfloat16")
    toks = _ids((4, 17), seed=8)
    ds = DataSet(toks[:, :-1], toks[:, 1:])
    score, grad = net.compute_gradient_and_score(ds)
    np.testing.assert_allclose(score, net.score(ds), rtol=1e-6)
    assert np.isfinite(score) and grad is not None
    acts = net.feed_forward(toks[:, :-1])
    assert acts[-1].shape == (4, CFG["vocab_size"], 16)


# ---------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------
@pytest.fixture(scope="module", params=[False, True],
                ids=["router_learns", "router_frozen"])
def pair(request):
    """The adapter's net (the program on its normal path, grouped
    kernel in ``interpret``, a layer recomputed), the reference's
    parameters from the same seed and the configuration both read:
    the router learning through the gates, and, as the cell runs it,
    ``freeze_router``."""
    cfg = dict(CFG, freeze_router=request.param)
    net = MODEL.build_net(cfg, SEED, optimizer=HYPER)
    return net, weights.make_params(SEED, cfg), cfg


def test_logits_and_loss_are_the_references(pair):
    net, params, cfg = pair
    toks = _ids((2, 25), seed=9)
    z, _, _ = net._forward_fn(net.params, net.state,
                              jnp.asarray(toks[:, :-1]), None, False,
                              logits=True)
    row_logits = jax.jit(lambda p, r: ref.row_logits(p, r, cfg,
                                                     "highest"))
    want = jnp.stack([row_logits(params, jnp.asarray(r[:-1]))
                      for r in toks])
    np.testing.assert_allclose(z, want, rtol=2e-4, atol=2e-5)
    loss = net._loss_fn(net.params, net.state, None,
                        jnp.asarray(toks[:, :-1]),
                        jnp.asarray(toks[:, 1:]), None, None)[0]
    row_loss = jax.jit(lambda p, r: ref.row_loss(p, r, cfg, "highest"))
    want = np.mean([float(row_loss(params, jnp.asarray(r)))
                    for r in toks])
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)


def test_every_leafs_gradient_is_the_references(pair):
    net, params, cfg = pair
    toks = _ids((2, 25), seed=10)
    _, grads, _ = net._grad_and_score(
        net.params, net.state, None, jnp.asarray(toks[:, :-1]),
        jnp.asarray(toks[:, 1:]), None, None)
    want = jax.jit(jax.grad(lambda p: sum(
        ref.row_loss(p, jnp.asarray(r), cfg, "highest")
        for r in toks) / len(toks)))(params)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for si, leaves in want.items():
        for name, g in leaves.items():
            scale = float(jnp.max(jnp.abs(g))) or 1.0
            np.testing.assert_allclose(
                np.asarray(grads[si][name]) / scale,
                np.asarray(g) / scale, atol=2e-4,
                err_msg=f"{si}.{name}")
    routers = [np.asarray(leaves["router"]) for leaves in grads.values()
               if "expert_bias" in leaves]
    for si, leaves in grads.items():
        if "expert_bias" in leaves:
            assert not np.any(np.asarray(leaves["expert_bias"]))
    # the gates teach the router, unless the routing is frozen
    assert routers and all(
        np.any(g) != cfg["freeze_router"] for g in routers)


@pytest.mark.parametrize("frozen", [False, True],
                         ids=["router_learns", "router_frozen"])
def test_three_adam_steps_are_the_references(frozen):
    cfg = dict(CFG, freeze_router=frozen)
    net = MODEL.build_net(cfg, SEED + 1, optimizer=HYPER)
    routers = {si: np.asarray(p["router"])
               for si, p in net.params.items() if "expert_bias" in p}
    bias = {si: np.asarray(p["expert_bias"])
            for si, p in net.params.items() if "expert_bias" in p}
    assert bias and all(np.any(b) for b in bias.values())
    pool = _ids((3, 2, 25), seed=11)
    scores = [float(np.asarray(net.fit_scan(*(
        a[None] for a in MODEL.encode_batch(b, cfg))))[0]) for b in pool]
    out, params = reference.follow_steps(
        weights.make_params(SEED + 1, cfg),
        lambda p, row: ref.row_loss(p, row, cfg, "highest"),
        HYPER, pool)
    np.testing.assert_allclose(scores, out["losses"], rtol=1e-5)
    for si, leaves in params.items():
        for name, want in leaves.items():
            np.testing.assert_allclose(
                net.params[si][name], want, rtol=1e-3, atol=2e-6,
                err_msg=f"{si}.{name}")
    # no updater moved the selection bias, and its moments stayed 0
    for si, b in bias.items():
        np.testing.assert_array_equal(net.params[si]["expert_bias"], b)
        assert not np.any(np.asarray(
            net.updater_state[si]["m"]["expert_bias"]))
    # a frozen router is the array it was; a learning one has moved
    for si, r in routers.items():
        assert np.array_equal(net.params[si]["router"], r) == frozen


def test_zeroing_the_selection_bias_changes_the_picks():
    full, x, _ = _expert_layer(seed=6, m=256)
    logits = x @ full["router"]
    _, with_bias = moe.route(logits, 3, "sigmoid_bias",
                             bias=full["expert_bias"], eps=1e-6)
    _, without = moe.route(logits, 3, "sigmoid_bias",
                           bias=jnp.zeros_like(full["expert_bias"]),
                           eps=1e-6)
    assert np.any(np.sort(with_bias, axis=1) != np.sort(without, axis=1))


# ---------------------------------------------------------------------
# served: the slot-state path carries the convolution's tail
# ---------------------------------------------------------------------
def test_the_serving_engine_serves_it_through_the_slot_state_path():
    """No served cell is asked for; the engine takes the net as it
    takes a Mamba-2 one: the attention layer's keys paged, each conv
    layer's 2-column tail one row a slot. Three prompts over two
    slots (a slot is reused) decode to the tokens a full forward pass
    over prompt and served tokens puts first."""
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    net = MultiLayerNetwork(lfm2_moe_lm(
        vocab_size=64, layers=[0, 1, 2, 3], experts_held=(0, 6),
        max_position_embeddings=128, initializer_range=0.3)).init()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).tolist() for n in (5, 20, 33)]
    eng = DecodeEngine(net, n_slots=2, decode_chunk=4, block_tokens=8)
    assert eng._state_layers == ["1", "2", "4"]
    ids = [eng.submit(Request(p, 6)) for p in prompts]
    res = eng.run()
    served = [list(res[i].tokens) for i in ids]
    assert any(len(set(s)) > 2 for s in served)   # not a one-token loop
    for prompt, toks in zip(prompts, served):
        # teacher-forced: one full pass over prompt + served tokens
        out = np.asarray(net.output(np.asarray([prompt + toks[:-1]])))
        want = out[0, :, len(prompt) - 1:].argmax(axis=0).tolist()
        assert toks == want
    assert eng.stats["moe_layer_steps"] > 0
