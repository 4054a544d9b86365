"""The plain reference of the ``lfm2_moe`` stack, TRAINED: the forward
pass, the loss and its gradients in float32 ``jax.numpy`` (the
gradients by ``jax.grad`` of this file's forward pass), Adam by
``benchmark/reference.py``. No kernels, no sorting, no grouped product,
no flash program.

The equations, from the catalog's ``config`` and ``described_as`` and
from HF's ``modeling_lfm2_moe.py`` as ISSUE 33's author recalls it (no
network here; the configuration lists them under ``assumed``). RMSNorm
everywhere (eps ``norm_eps``), no biases::

    x0 = E[ids]                                         E [vocab, d], the head tied to it
    layer l:  x = x + mixer_l(N1 x);   x = x + ffn_l(N2 x)
    conv mixer:   [B | C | u] = h W_in                  W_in [d, 3 d]
        y_t = C_t * sum_{j < K} w[j] (B u)_{t - (K - 1) + j}     depthwise, causal, no bias; w [K, d]
        out = y W_out
    attention mixer: 32 query / 8 KV heads of 64, causal, scale 1 / sqrt(64)
        q, k = RMSNorm_64(heads(h Wq)), RMSNorm_64(heads(h Wk));  rotate-half RoPE on both (theta 1e6);  v = heads(h Wv)
        out = concat(softmax(q k^T / 8) v) Wo
    ffn, published layer < num_dense_layers:  W_out(silu(g) * u),  [g | u] = h W_in,  width 7168
    ffn, else:  s = sigmoid(h Wr) (float32);  pick = top-4 of (s + expert_bias)
        g = s[pick] / (sum s[pick] + 1e-6) * routed_scaling_factor
        out = sum over picked AND held e of g_e expert_e(h),  width 1792
    logits = N_out(x_L) E^T;   loss = mean over tokens of -log softmax(logits)[label]

``expert_bias`` moves picks only and takes no gradient; under the
configuration's ``freeze_router`` the scores are constants to the
gradient, so ``Wr`` takes none and ``h`` none through them. Every HELD
expert is applied to every token, weighted by the token's gate for it
(0 where the token did not pick it); picks on experts held elsewhere
add nothing, as in the program. One sequence at a time
(``reference.follow_steps`` accumulates the gradient row by row), a
layer recomputed on the way back (``jax.checkpoint``), the attention a
block of queries at a time and the experts one at a time, so that three
steps at the published widths fit one chip beside nothing else.

It imports nothing of the program and takes nothing the program made.
Matrix products go through ``benchmark.reference.mm``, so that ``prec``
(and with it the ``fp8`` control) reaches every one of them; the norms,
the rotation, the softmax, the sigmoid and the convolution's taps are
float32 elementwise work in every precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import norms, reference
from benchmark.models import lfm2_moe_weights as weights
from benchmark.reference import _round_to, mm

#: queries a block of attention
QUERY_BLOCK = 512
#: the normaliser's epsilon of ``norm_topk_prob``
ROUTE_EPS = 1e-6


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta: float):
    """Rotary positions (rotate-half) on ``[T, H, dh]`` at positions
    ``0..T-1``."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * cos + half * sin


def short_conv(p: dict, h, prec: str):
    """One row ``h`` ``[T, D]`` through the gated short convolution,
    the taps as shifted copies."""
    t, d = h.shape
    proj = mm(h, p["W_in"], prec, "td,de->te")
    b, c, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    k = p["conv_w"].shape[0]
    seq = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), b * u])
    y = sum(p["conv_w"][j] * seq[j:j + t] for j in range(k))
    return mm(c * y, p["W_out"], prec, "td,de->te")


def attention(p: dict, h, cfg: dict, prec: str):
    """One row ``h`` ``[T, D]``: grouped KV heads, QK-norm, the
    rotation, causal, a block of queries at a time."""
    t, d = h.shape
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    eps = cfg["norm_eps"]
    q = mm(h, p["Wq"], prec, "td,de->te").reshape(t, hq, dh)
    k = mm(h, p["Wk"], prec, "td,de->te").reshape(t, hk, dh)
    v = mm(h, p["Wv"], prec, "td,de->te").reshape(t, hk, dh)
    q = rotate(rms_norm(q, p["q_norm_w"], eps), cfg["rope_theta"])
    k = rotate(rms_norm(k, p["k_norm_w"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, hq // hk, axis=1)
    v = jnp.repeat(v, hq // hk, axis=1)
    nq = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def block(qb, i0):
        i = i0 + jnp.arange(nq)
        sc = mm(qb, k, prec, "qhc,khc->hqk") / math.sqrt(dh)
        seen = jnp.arange(t)[None, :] <= i[:, None]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), v, prec, "hqk,khc->qhc")

    o = jax.lax.map(lambda a: block(*a),
                    (q.reshape(t // nq, nq, hq, dh),
                     jnp.arange(0, t, nq)))
    return mm(o.reshape(t, d), p["Wo"], prec, "te,ed->td")


def gated(x, w_in, w_out, prec: str):
    gu = mm(x, w_in, prec, "nd,df->nf")
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out, prec,
              "nf,fd->nd")


def gates_of(p: dict, x, cfg: dict, prec: str):
    """``[N, router_outputs]``: the normalised, scaled score where a
    token picked the expert, 0 elsewhere. The picks (whole numbers)
    carry no gradient, the bias none; under ``freeze_router`` the
    scores none either."""
    k = cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(mm(x, p["router"], prec, "nd,de->ne"))
    if cfg["freeze_router"]:
        score = jax.lax.stop_gradient(score)
    _, idx = jax.lax.top_k(
        jax.lax.stop_gradient(score + p["expert_bias"]), k)
    g = jnp.take_along_axis(score, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + ROUTE_EPS) * cfg[
        "routed_scaling_factor"]
    return jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], idx].set(g)


def experts(p: dict, x, cfg: dict, prec: str):
    """The held experts' part of the routed sum for ``x`` ``[N, D]``:
    each held expert on every token, times the token's gate for it."""
    lo, hi = cfg["experts_held"]
    gates = gates_of(p, x, cfg, prec)[:, lo:hi]

    @jax.checkpoint
    def one(y, e):
        w_in, w_out, gate = e
        return y + gate[:, None] * gated(x, w_in, w_out, prec), None

    return jax.lax.scan(one, jnp.zeros_like(x),
                        (p["We_in"], p["We_out"], gates.T))[0]


def layer(p: dict, x, cfg: dict, kind, prec: str):
    """One row ``x`` ``[T, D]`` through one layer."""
    mixer, ffn = kind
    eps = cfg["norm_eps"]
    h = rms_norm(x, p["norm1_w"], eps)
    x = x + (short_conv(p, h, prec) if mixer == "conv"
             else attention(p, h, cfg, prec))
    h = rms_norm(x, p["norm2_w"], eps)
    return x + (gated(h, p["Ws_in"], p["Ws_out"], prec) if ffn == "dense"
                else experts(p, h, cfg, prec))


def row_logits(params: dict, tokens, cfg: dict, prec: str):
    """Logits ``[T, V]`` (over the held slice of the vocabulary) of one
    row of token ids ``[T]``, from ``params`` in the program's layout.
    A layer is recomputed on the way back."""
    kinds = weights.layer_kinds(cfg)
    e = params["0"]["W"]
    x = _round_to(e, prec).astype(jnp.float32)[tokens]
    for i, kind in enumerate(kinds):
        x = jax.checkpoint(
            functools.partial(layer, cfg=cfg, kind=kind, prec=prec))(
                params[str(i + 1)], x)
    hn = rms_norm(x, params[str(len(kinds) + 1)]["norm_w"],
                  cfg["norm_eps"])
    return mm(hn, e, prec, "td,vd->tv")


def row_loss(params: dict, row, cfg: dict, prec: str):
    """Mean next-token cross-entropy (nats) of one row of ``T + 1``
    token ids."""
    logp = jax.nn.log_softmax(row_logits(params, row[:-1], cfg, prec),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def train_reference(seed: int, cfg: dict, hyper: dict, batches,
                    prec: str = "highest") -> dict:
    """Follow the first ``len(batches)`` steps of the job
    (``reference.follow_steps``): each batch is ``[B, T + 1]`` token
    ids. Returns each step's loss, the first gradient's norm by leaf
    (every leaf of the program, ``expert_bias`` among them at 0) and
    the norm of each leaf's change after the last step."""
    out, params = reference.follow_steps(
        weights.make_params(seed, cfg),
        functools.partial(row_loss, cfg=cfg, prec=prec),
        hyper, batches)
    out["delta_norms"] = norms.flat_norms(norms.delta_norms(
        params, weights.make_params(seed, cfg)))
    return out

