"""The plain reference of the ``afmoe`` stack: float32 ``jax.numpy``,
no kernels, no cache, no sorting.

It follows HF's ``AfmoeForCausalLM`` as the configuration's ``assumed``
states it (the equations are in ISSUE 31 and in
``deeplearning4j_tpu/nn/layers/hybrid.py``'s docstring, written there
from the same description)::

    x0 = E[ids] * sqrt(hidden)                                (mup_enabled)
    a  = N1(x);  q, k = Nq(Wq a), Nk(Wk a);  v = Wv a        (RMSNorm over head_dim)
    sliding layer: q, k = RoPE(q, k);  key j seen by query i iff i - window < j <= i
    full layer:    no positional term, causal
    o  = softmax(q k^T / sqrt(head_dim)) v * sigmoid(Wg a);   x = x + N2(Wo o)
    h  = N3(x);  dense: f = Wd(silu(Wgate h) * Wup h)
    experts: s = sigmoid(h Wr);  pick = top_k(s + b);  g = s[pick] / (sum + 1e-20) * route_scale
             f = shared(h) + sum over picked AND held e of g_e expert_e(h)
    x  = x + N4(f);   logits = Whead Nf(x)

Every HELD expert is applied to the tokens that picked it, weighted by
their gates (gathered where they are at most a quarter of a block of
tokens, else to every token with gate 0 where it was not picked: the
same sum either way; a float32 product runs at ~2 TFLOP/s on the chip
and 32 experts over every token of a 16k row took two minutes); picks
that fall on experts held elsewhere add nothing, as in the program. It
is a full forward pass over prompt + served tokens, made layer by layer
from the seed so that one float32 layer is resident at a time;
attention runs a block of queries at a time against the stretch of keys
its band reaches, the feed-forwards a block of tokens at a time, so that
a 16k-token row fits the chip; the head is applied at the served
positions only.

It imports nothing of the program and takes nothing the program made.
Matrix products go through ``benchmark.reference.mm``, so that ``prec``
(and with it the ``fp8`` control) reaches every one of them; the norms,
the rotation, the softmax and the sigmoids are float32 elementwise work
in every precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import afmoe_weights as weights
from benchmark.reference import _round_to, mm

#: queries a block of attention, tokens a block of a feed-forward
QUERY_BLOCK = 256
TOKEN_BLOCK = 4096


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


def _blocks(t: int, size: int) -> int:
    return size if t % size == 0 else t


def attention(p: dict, a, cfg: dict, kind: str, prec: str):
    """One row ``a`` ``[T, D]``: grouped KV heads, QK-norm, the
    rotation and the window band on a sliding layer, the output gate."""
    t, _ = a.shape
    hq, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = mm(a, p["Wq"], prec, "td,de->te").reshape(t, hq, dh)
    k = mm(a, p["Wk"], prec, "td,de->te").reshape(t, hk, dh)
    v = mm(a, p["Wv"], prec, "td,de->te").reshape(t, hk, dh)
    q, k = rms_norm(q, p["q_norm_w"], eps), rms_norm(k, p["k_norm_w"], eps)
    if kind == "sliding":
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hk, axis=1)
    v = jnp.repeat(v, hq // hk, axis=1)
    nq = _blocks(t, QUERY_BLOCK)
    # the keys a block of queries is scored against: all of them, or
    # (a sliding layer) the stretch its band can reach
    nk = t if kind == "full" else min(t, cfg["sliding_window"] + nq)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, nq, axis=0)
        i = i0 + jnp.arange(nq)
        j0 = jnp.clip(i0 + nq - nk, 0, t - nk)
        kb = jax.lax.dynamic_slice_in_dim(k, j0, nk, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, j0, nk, axis=0)
        j = j0 + jnp.arange(nk)
        sc = mm(qb, kb, prec, "qhc,khc->hqk") / math.sqrt(dh)
        seen = j[None, :] <= i[:, None]
        if kind == "sliding":
            seen &= j[None, :] > i[:, None] - cfg["sliding_window"]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vb, prec, "hqk,khc->qhc")

    o = jax.lax.map(block, jnp.arange(0, t, nq)).reshape(t, hq * dh)
    o = o * jax.nn.sigmoid(mm(a, p["Wg"], prec, "td,de->te"))
    return mm(o, p["Wo"], prec, "te,ed->td")


def gated(x, w_in, w_out, prec: str):
    gu = mm(x, w_in, prec, "nd,df->nf")
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out, prec,
              "nf,fd->nd")


def gates_of(p: dict, x, cfg: dict, prec: str):
    """``[N, router_outputs]``: the normalised, scaled score where a
    token picked the expert, 0 elsewhere; and ``[N]``, the token's
    routing MARGIN: the least by which a HELD expert's biased score
    would have to move, against the line between the last pick and the
    first expert left out, for the held experts the token picks to
    change (a picked one's score less the first left out's; the last
    pick's less an unpicked one's). A program whose rounding moves a
    score by more than the margin may pick otherwise than this
    reference and still be sound."""
    k = cfg["num_experts_per_tok"]
    lo, hi = cfg["experts_held"]
    score = jax.nn.sigmoid(mm(x, p["router"], prec, "nd,de->ne"))
    biased = score + p["expert_bias"]
    top, idx = jax.lax.top_k(biased, k + 1)
    idx = idx[:, :k]
    g = jnp.take_along_axis(score, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * cfg[
        "route_scale"]
    gates = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], idx].set(g)
    last, out = top[:, k - 1:k], top[:, k:]
    held = biased[:, lo:hi]
    margin = jnp.where(held >= last, held - out, last - held).min(axis=-1)
    return gates, margin


def feed_forward(p: dict, x, cfg: dict, ffn: str, prec: str):
    """``x`` ``[N, D]``, a block of tokens at a time."""
    return _feed_forward(p, x, cfg, ffn, prec)[0]


def _feed_forward(p: dict, x, cfg: dict, ffn: str, prec: str):
    """:func:`feed_forward` and each token's routing margin
    (:func:`gates_of`; infinite in a dense layer)."""
    lo, hi = cfg["experts_held"]

    def block(xb):
        y = gated(xb, p["Ws_in"], p["Ws_out"], prec)
        if ffn == "dense":
            return y, jnp.full(xb.shape[:1], jnp.inf, jnp.float32)
        gates, margin = gates_of(p, xb, cfg, prec)
        gates = gates[:, lo:hi]
        most = max(xb.shape[0] // 4, 1)

        def one(y, e):
            w_in, w_out, gate = e

            def picked(y):
                # the ``most`` tokens with the largest gates hold every
                # token that picked the expert; the others' gate is 0
                g, rows = jax.lax.top_k(gate, most)
                return y.at[rows].add(
                    g[:, None] * gated(xb[rows], w_in, w_out, prec))

            def every(y):
                return y + gate[:, None] * gated(xb, w_in, w_out, prec)

            return jax.lax.cond(jnp.sum(gate > 0) <= most, picked, every,
                                y), None

        return jax.lax.scan(
            one, y, (p["We_in"], p["We_out"], gates.T))[0], margin

    n, d = x.shape
    nb = _blocks(n, TOKEN_BLOCK)
    y, margin = jax.lax.map(block, x.reshape(n // nb, nb, d))
    return y.reshape(n, d), margin.reshape(n)


def layer(p: dict, x, cfg: dict, kind, prec: str):
    """One row ``x`` ``[T, D]`` through one layer: its output and each
    token's routing margin in it."""
    attn, ffn = kind
    eps = cfg["rms_norm_eps"]
    mixed = attention(p, rms_norm(x, p["norm1_w"], eps), cfg, attn, prec)
    x = x + rms_norm(mixed, p["post1_w"], eps)
    f, margin = _feed_forward(p, rms_norm(x, p["norm2_w"], eps), cfg,
                              ffn, prec)
    return x + rms_norm(f, p["post2_w"], eps), margin


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_step(key, x, frozen, kind, prec):
    cfg = dict(frozen)
    p = _f32(weights._make_layer(key, weights._frozen(cfg), kind[1]))
    return jax.lax.map(lambda row: layer(p, row, cfg, kind, prec), x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed_step(key, tokens, frozen, prec):
    e = weights._make_end(key, frozen, "E").astype(jnp.float32)
    hidden = dict(frozen)["hidden_size"]
    return _round_to(e, prec).astype(jnp.float32)[tokens] * math.sqrt(
        hidden)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _head_step(key, x, frozen, eps, prec):
    """``x`` ``[S, P, D]``, the positions asked for: ``[S, P, V]``."""
    norm = weights._make_end(key, frozen, "norm_w").astype(jnp.float32)
    head = weights._make_end(key, frozen, "head").astype(jnp.float32)
    return mm(rms_norm(x, norm, eps), head, prec, "spd,vd->spv")


def _static(cfg: dict):
    """``weights._frozen`` plus what only the forward pass reads."""
    extra = ("rms_norm_eps", "num_experts_per_tok", "route_scale",
             "rope_theta", "sliding_window")
    return weights._frozen(cfg) + tuple((k, cfg[k]) for k in extra)


def hidden_states(seed: int, cfg: dict, tokens: np.ndarray,
                  prec: str = "highest"):
    """The stack's output before the final norm, ``[S, T, D]``, over
    ``tokens`` ``[S, T]``, and each position's least routing margin
    over the layers, ``[S, T]``. Causal, so padding at the end of a row
    changes nothing before it."""
    if not cfg["mup_enabled"]:
        raise ValueError("the reference scales the embedding (mup_enabled)")
    weights.n_held(cfg)
    key = weights.root_key(seed)
    frozen = _static(cfg)
    x = _embed_step(key, jnp.asarray(tokens, jnp.int32),
                    weights._frozen(cfg), prec)
    margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    for i, kind in enumerate(weights.layer_kinds(cfg)):
        x, m = _layer_step(weights.layer_key(key, i), x, frozen, kind,
                           prec)
        margin = jnp.minimum(margin, m)
    return x, margin


def logits_at(seed: int, cfg: dict, tokens: np.ndarray, at: np.ndarray,
              prec: str = "highest"):
    """Logits ``[S, P, V]`` (over the held slice of the vocabulary) at
    the positions ``at`` ``[S, P]`` of ``tokens`` ``[S, T]``, and the
    routing margins there, ``[S, P]``."""
    x, margin = hidden_states(seed, cfg, tokens, prec)
    x = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
    logits = _head_step(weights.root_key(seed), x, weights._frozen(cfg),
                        cfg["rms_norm_eps"], prec)
    return (np.asarray(logits),
            np.asarray(jnp.take_along_axis(margin, jnp.asarray(at),
                                           axis=1)))


def forward_logits(seed: int, cfg: dict, tokens: np.ndarray,
                   prec: str = "highest") -> np.ndarray:
    """Logits ``[S, T, V]`` at every position (tests; a served check
    asks for the served positions only)."""
    tokens = np.asarray(tokens)
    at = np.broadcast_to(np.arange(tokens.shape[1]), tokens.shape)
    return logits_at(seed, cfg, tokens, at, prec)[0]


def served_rows(seed: int, cfg: dict, samples, control: str = None):
    """``benchmark.reference.served_gaps``' gaps, a request a call and
    the head at the served positions only (a 16k-token row's logits at
    every position would be 1.6 GB): for each ``(prompt, served)``
    sample, at every served position, how far the served token's
    reference logit lies below the reference's best; with ``control``,
    the same gap for the token that precision puts first there (else
    None); and the reference's routing margin at each of the
    positions. Flat arrays over all served positions."""
    prog, ctrl, margins = [], [], []
    for prompt, served in samples:
        seq = list(prompt) + list(served[:-1])
        pad = 1 << max(len(seq) - 1, 7).bit_length()
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(seq)] = seq
        at = np.arange(len(prompt) - 1, len(seq))[None, :]
        ref, margin = logits_at(seed, cfg, toks, at)
        best = ref[0].max(axis=-1)
        rows = np.arange(len(served))
        prog.append(best - ref[0][rows, np.asarray(served)])
        margins.append(margin[0])
        if control:
            low = logits_at(seed, cfg, toks, at, control)[0][0]
            ctrl.append(best - ref[0][rows, low.argmax(axis=-1)])
    return (np.concatenate(prog),
            np.concatenate(ctrl) if ctrl else None,
            np.concatenate(margins))


def served_gaps(seed: int, cfg: dict, samples, control: str = None):
    """The gaps of :func:`served_rows` at the served positions where
    the reference's routing is DECISIVE: its margin is over the
    configuration's ``check.decisive_margin``. At the others a held
    expert lies so near the line between picked and left out that
    bfloat16 rounding of the scores decides the pick; a sound program
    may then add or drop a whole expert's output against this
    reference, which moves a logit as far as the control's errors do
    and says nothing of its arithmetic. What is compared is therefore
    the program where its routing is not in doubt: there its gaps are
    those of rounding alone, and the control (whose errors cross
    margins many times wider) still picks otherwise."""
    prog, ctrl, margin = served_rows(seed, cfg, samples, control)
    keep = margin > float(cfg["check"]["decisive_margin"])
    if not keep.any():
        raise ValueError(
            f"none of {keep.size} served positions has a routing margin "
            f"over check.decisive_margin {cfg['check']['decisive_margin']}")
    return prog[keep], None if ctrl is None else ctrl[keep]
