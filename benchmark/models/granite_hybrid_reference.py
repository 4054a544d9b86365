"""The plain reference of the hybrid stack: float32 ``jax.numpy``, no
kernels, no cache, no chunking, no sorting.

It follows HF's ``GraniteMoeHybridForCausalLM`` (the equations are in
``deeplearning4j_tpu/nn/layers/hybrid.py``'s and ``mamba2.py``'s
docstrings, written there from the same source), with the departures
the configuration file lists under ``assumed``. The recurrence is the
sequential one, a ``lax.scan`` over time; every HELD expert is applied
to every token and weighted by its gate (0 where the token did not pick
it), and picks that fall on experts held elsewhere add nothing, as in
the program. It is a full forward pass over prompt + served tokens,
made layer by layer from the seed so that one float32 layer is
resident at a time.

It imports nothing of the program and takes nothing the program made.
Matrix products go through ``benchmark.reference.mm``, so that ``prec``
(and with it the ``fp8`` control) reaches every one of them; the
convolution, the recurrence, the norms and the softmaxes are float32
elementwise work in every precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.models import granite_hybrid_weights as weights
from benchmark.reference import _round_to, mm


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attention_mixer(p: dict, h, cfg: dict, prec: str):
    """Causal softmax attention with grouped KV heads, no positional
    term, scores times ``attention_multiplier``."""
    s, t, d = h.shape
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    q = mm(h, p["Wq"], prec, "std,de->ste").reshape(s, t, hq, dh)
    k = mm(h, p["Wk"], prec, "std,de->ste").reshape(s, t, hk, dh)
    v = mm(h, p["Wv"], prec, "std,de->ste").reshape(s, t, hk, dh)
    k = jnp.repeat(k, hq // hk, axis=2)
    v = jnp.repeat(v, hq // hk, axis=2)
    sc = mm(q, k, prec, "sqhc,skhc->shqk") * cfg["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -jnp.inf)
    o = mm(jax.nn.softmax(sc, axis=-1), v, prec, "shqk,skhc->sqhc")
    return mm(o.reshape(s, t, d), p["Wo"], prec, "std,de->ste")


def mamba_mixer(p: dict, h, cfg: dict, prec: str):
    """Mamba-2, the recurrence one position a step."""
    s, t, _ = h.shape
    nh, dp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, kc = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                cfg["mamba_d_conv"])
    di, gn = nh * dp, g * n
    proj = mm(h, p["W_in"], prec, "std,de->ste")
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * gn],
                  proj[..., 2 * di + 2 * gn:])
    seq = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(seq[:, j:j + t] * p["conv_w"][j]
                          for j in range(kc)) + p["conv_b"])
    x = xbc[..., :di].reshape(s, t, g, nh // g, dp)
    bm = xbc[..., di:di + gn].reshape(s, t, g, n)
    cm = xbc[..., di + gn:].reshape(s, t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(s, t, g, nh // g)
    a = -jnp.exp(p["A_log"]).reshape(g, nh // g)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None]
                 * bt[:, :, None, None, :])
        return state, jnp.sum(state * ct[:, :, None, None, :], axis=-1)

    _, ys = jax.lax.scan(
        step, jnp.zeros((s, g, nh // g, dp, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    y = jnp.moveaxis(ys, 0, 1) + p["D"].reshape(g, nh // g)[..., None] * x
    y = y.reshape(s, t, g, di // g) * jax.nn.silu(z).reshape(
        s, t, g, di // g)
    y = rms_norm(y, 1.0, cfg["rms_norm_eps"]).reshape(s, t, di)
    return mm(y * p["norm_w"], p["W_out"], prec, "std,de->ste")


def gated(x, w_in, w_out, prec: str):
    gu = mm(x, w_in, prec, "nd,df->nf")
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out, prec,
              "nf,fd->nd")


def experts(p: dict, h, cfg: dict, prec: str):
    """Shared expert plus the held routed experts, each applied to
    every token and weighted by its gate."""
    s, t, d = h.shape
    x = h.reshape(s * t, d)
    logits = mm(x, p["router"], prec, "nd,de->ne")
    top, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    gates = jnp.zeros_like(logits).at[
        jnp.arange(s * t)[:, None], idx].set(jax.nn.softmax(top, axis=-1))
    lo, hi = cfg["experts_held"]

    def one(y, e):
        w_in, w_out, gate = e
        return y + gate[:, None] * gated(x, w_in, w_out, prec), None

    y, _ = jax.lax.scan(one, gated(x, p["Ws_in"], p["Ws_out"], prec),
                        (p["We_in"], p["We_out"], gates[:, lo:hi].T))
    return y.reshape(s, t, d)


def layer(p: dict, x, cfg: dict, kind: str, prec: str):
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    mixer = attention_mixer if kind == "attention" else mamba_mixer
    x = x + r * mixer(p, rms_norm(x, p["norm1_w"], eps), cfg, prec)
    return x + r * experts(p, rms_norm(x, p["norm2_w"], eps), cfg, prec)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_step(key, x, frozen, kind, prec):
    cfg = dict(frozen)
    return layer(_f32(weights._make_layer(key, frozen, kind)), x, cfg,
                 kind, prec)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed_step(key, tokens, frozen, prec):
    e = _f32(weights._make_ends(key, frozen))["E"]
    return _round_to(e, prec).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _head_step(key, x, frozen, eps, scaling, prec):
    ends = _f32(weights._make_ends(key, frozen))
    hn = rms_norm(x, ends["norm_w"], eps)
    return mm(hn, ends["E"], prec, "std,vd->stv") / scaling


def _static(cfg: dict):
    """``weights._frozen`` plus what only the forward pass reads."""
    extra = ("attention_multiplier", "residual_multiplier",
             "rms_norm_eps", "num_experts_per_tok")
    return weights._frozen(cfg) + tuple((k, cfg[k]) for k in extra)


def forward_logits(seed: int, cfg: dict, tokens: np.ndarray,
                   prec: str = "highest") -> np.ndarray:
    """Logits ``[S, T, V]`` (over the held slice of the vocabulary) of
    the stack over ``tokens`` ``[S, T]``. Causal, so padding at the end
    of a row changes nothing before it."""
    weights.n_held(cfg)
    key = weights.root_key(seed)
    frozen = _static(cfg)
    x = _embed_step(key, jnp.asarray(tokens, jnp.int32),
                    weights._frozen(cfg), prec)
    x = x * cfg["embedding_multiplier"]
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        x = _layer_step(weights.layer_key(key, i), x, frozen, kind, prec)
    return np.asarray(_head_step(key, x, weights._frozen(cfg),
                                 cfg["rms_norm_eps"],
                                 cfg["logits_scaling"], prec))


def served_gaps(seed: int, cfg: dict, samples, control: str = None):
    """``reference.served_gaps`` over this stack's forward pass."""
    return reference.served_gaps(
        functools.partial(forward_logits, seed, cfg), samples, control)
