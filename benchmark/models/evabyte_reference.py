"""The plain reference of the ``evabyte`` stack: the whole sequence at
once, float32, ``jax.numpy`` only, explicit masks, no cache, no kernel,
no batching of requests. Matrix products are ``benchmark.reference.mm``
(float32 accumulation at ``Precision.HIGHEST``, operands rounded as
``prec`` says), everything else plain float32.

**The layer** (EVA, *Efficient Attention via Control Variates*,
arXiv:2302.04542, as ``attention_class: eva`` runs it). Per head ``h``
of width ``d`` (128), ``s = d^-1/2``, ``q_i k_j v_j`` after the rotation
(rotate-half, ``rope_theta`` 1e5, no scaling), window ``W``
(``window_size`` 2048), chunk ``C`` (``chunk_size`` 16), learned
``mu_h, phi_h`` in ``R^d``. With ``k'_j = k_j d^-1/4``, for every
COMPLETE chunk ``c = [cC, cC + C)``::

    a_cj = softmax_{j in c}(mu_h . k'_j)                  kt_c = sum_j a_cj k_j
    b_cj = softmax_{j in c}(phi_h . k'_j - |k'_j|^2 / 2)  vt_c = sum_j b_cj v_j

(the paper's control-variate estimate with the random feature's mean
learned and its noise dropped). Query ``i`` of window ``w = i // W``
reads ``E(i) = {j : wW <= j <= i}`` exactly and the summaries
``S(i) = {c : c < wW / C}`` of every earlier window (a chunk of the
current window is never read as a summary)::

    o_i = sum_{E(i)} p_ij v_j + sum_{S(i)} pt_ic vt_c,
    (p_i, pt_i) = softmax over the joined logits [s q_i.k_j ; s q_i.kt_c]

**The block**: ``x + Wo eva(N1 x)``, ``x + W2 (silu(W1 N2 x) * W3 N2
x)``, ``N`` an RMSNorm (eps 1e-5) whose gain is ``1 + w``
(``norm_add_unit_offset``); the head ``N x @ head^T`` over
``num_pred_heads x vocab_size`` rows, head 0 (the next byte) the first
``vocab_size``. All of it float32 here; the program rounds the
products' operands to bfloat16 and keeps the softmax, the pooling, the
residual sums and the logits in float32 (``mixedp_attn``,
``fp32_skip_add``, ``fp32_logits``).

**Departures from the published model**, all stated in the
configuration's ``assumed``: 16 of the 32 layers; random weights; the
``d^-1/4`` scaling of the two pooling logits and the ``-|k'|^2 / 2``
term, the head as ONE matrix with head 0 first, and the equations'
provenance (the paper and the released ``eva_pt_ref.py`` as ISSUE 41's
author recalls them: no network here); ``mu`` and ``phi`` drawn
N(0, 1); the seven further prediction heads are held and computed
(``every_head``) and never served: multi-byte self-speculative decoding
is not built.

``broken`` (tests only) computes the layer WRONG in one named way, so
that a test can show its tolerance tells the layer from its nearest
mistakes: ``"uniform_pooling"`` (``a`` and ``b`` uniform),
``"sliding_floor"`` (``E(i) = {j : i - W < j <= i}``),
``"early_summaries"`` (summaries visible one window early: ``S(i)``
also holds the complete chunks of the query's OWN window that lie behind
it, ``c < (w + 1) W / C``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import evabyte_weights as weights
from benchmark.reference import _round_to, mm

#: queries a block of the attention is computed for
QUERY_BLOCK = 512
BROKEN = (None, "uniform_pooling", "sliding_floor", "early_summaries")


def rms_norm(x, w, eps: float):
    """The unit-offset RMSNorm: gain ``1 + w``."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


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


def summaries(p: dict, k, v, chunk: int, broken=None):
    """``kt``, ``vt`` ``[T / C, H, dh]`` of ``k``, ``v`` ``[T, H, dh]``
    (``C | T``)."""
    t, h, dh = k.shape
    kc = k.reshape(t // chunk, chunk, h, dh)
    vc = v.reshape(t // chunk, chunk, h, dh)
    kp = kc * dh ** -0.25
    a = jnp.sum(kp * p["eva_mu"], axis=-1)                  # [T/C, C, H]
    b = jnp.sum(kp * p["eva_phi"], axis=-1) - 0.5 * jnp.sum(kp * kp,
                                                            axis=-1)
    if broken == "uniform_pooling":
        a, b = jnp.zeros_like(a), jnp.zeros_like(b)
    a = jax.nn.softmax(a, axis=1)[..., None]
    b = jax.nn.softmax(b, axis=1)[..., None]
    return jnp.sum(a * kc, axis=1), jnp.sum(b * vc, axis=1)


def attention(p: dict, a, cfg: dict, prec: str, broken=None):
    """One row ``a`` ``[T, D]`` (``T`` a multiple of the window, or at
    most one window and a multiple of the chunk)."""
    t, _ = a.shape
    h, dh = cfg["num_attention_heads"], weights.head_dim(cfg)
    win, chunk = cfg["window_size"], cfg["chunk_size"]
    q = mm(a, p["Wq"], prec, "td,de->te").reshape(t, h, dh)
    k = mm(a, p["Wk"], prec, "td,de->te").reshape(t, h, dh)
    v = mm(a, p["Wv"], prec, "td,de->te").reshape(t, h, dh)
    q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    kt, vt = summaries(p, k, v, chunk, broken)
    # a block of queries lies inside one window (or is the whole row)
    nq = math.gcd(QUERY_BLOCK, win) if t > win else t
    # the keys a block of queries reads exactly: its own window's (a
    # sliding floor, the mistake, reaches one window further back)
    back = 2 if broken == "sliding_floor" else 1
    nk = min(t, back * win)
    c = jnp.arange(t // chunk)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, nq, axis=0)
        i = i0 + jnp.arange(nq)
        j0 = jnp.clip((i0 // win - back + 1) * win, 0, t - nk)
        kb = jax.lax.dynamic_slice_in_dim(k, j0, nk, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, j0, nk, axis=0)
        j = j0 + jnp.arange(nk)
        w = i // win
        exact = j[None, :] <= i[:, None]                         # E(i)
        if broken == "sliding_floor":
            exact &= j[None, :] > i[:, None] - win
        else:
            exact &= j[None, :] >= (w * win)[:, None]
        upto = (w + (broken == "early_summaries")) * (win // chunk)
        pooled = ((c[None, :] < upto[:, None])                   # S(i)
                  & ((c[None, :] + 1) * chunk <= i[:, None]))
        s1 = mm(qb, kb, prec, "qhc,khc->hqk") / math.sqrt(dh)
        s2 = mm(qb, kt, prec, "qhc,khc->hqk") / math.sqrt(dh)
        s = jnp.concatenate([jnp.where(exact[None], s1, -jnp.inf),
                             jnp.where(pooled[None], s2, -jnp.inf)],
                            axis=-1)
        pr = jax.nn.softmax(s, axis=-1)
        return (mm(pr[..., :nk], vb, prec, "hqk,khc->qhc")
                + mm(pr[..., nk:], vt, prec, "hqk,khc->qhc"))

    o = jax.lax.map(block, jnp.arange(0, t, nq)).reshape(t, h * dh)
    return mm(o, p["Wo"], prec, "te,ed->td")


def gated(x, w_in, w_out, prec: str):
    gu = mm(x, w_in, prec, "nd,df->nf")
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out, prec,
              "nf,fd->nd")


def layer(p: dict, x, cfg: dict, prec: str, broken=None):
    """One row ``x`` ``[T, D]`` through one layer."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["norm1_w"], eps), cfg, prec,
                      broken)
    return x + gated(rms_norm(x, p["norm2_w"], eps), p["Ws_in"],
                     p["Ws_out"], prec)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _static(cfg: dict):
    """``weights._frozen`` plus what only the forward pass reads."""
    extra = ("rms_norm_eps", "rope_theta", "window_size", "chunk_size")
    return weights._frozen(cfg) + tuple((k, cfg[k]) for k in extra)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_step(key, x, frozen, prec, broken):
    cfg = dict(frozen)
    p = _f32(weights._make_layer(key, weights._frozen(cfg)))
    return jax.lax.map(lambda row: layer(p, row, cfg, prec, broken), x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed_step(key, tokens, frozen, prec):
    e = weights._make_end(key, frozen, "E").astype(jnp.float32)
    return _round_to(e, prec).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _head_step(key, x, frozen, eps, prec, every_head):
    """``x`` ``[S, P, D]``, the positions asked for: ``[S, P, V]``
    (head 0) or ``[S, P, num_pred_heads x V]``."""
    cfg = dict(frozen)
    norm = weights._make_end(key, frozen, "norm_w").astype(jnp.float32)
    head = weights._make_end(key, frozen, "head").astype(jnp.float32)
    if not every_head:
        head = head[:cfg["vocab_size"]]
    return mm(rms_norm(x, norm, eps), head, prec, "spd,vd->spv")


def padded(n: int, cfg: dict) -> int:
    """The length a row of ``n`` positions is computed at: whole
    windows past one, else whole chunks (causal, so the padding at a
    row's end changes nothing before it)."""
    unit = cfg["window_size"] if n > cfg["window_size"] else cfg[
        "chunk_size"]
    return -(-n // unit) * unit


def hidden_states(seed: int, cfg: dict, tokens: np.ndarray,
                  prec: str = "highest", broken=None):
    """The stack's output before the final norm, ``[S, T, D]``, over
    ``tokens`` ``[S, T]`` (``T`` as :func:`padded` gives it)."""
    if broken not in BROKEN:
        raise ValueError(f"broken {broken!r}: expected one of {BROKEN}")
    if cfg["window_size"] % cfg["chunk_size"]:
        raise ValueError("chunk_size does not divide window_size")
    key = weights.root_key(seed)
    x = _embed_step(key, jnp.asarray(tokens, jnp.int32),
                    weights._frozen(cfg), prec)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_step(weights.layer_key(key, i), x, _static(cfg), prec,
                        broken)
    return x


def logits_at(seed: int, cfg: dict, tokens: np.ndarray, at: np.ndarray,
              prec: str = "highest", every_head: bool = False,
              broken=None) -> np.ndarray:
    """Logits ``[S, P, V]`` (head 0; ``every_head``: all of them,
    ``[S, P, num_pred_heads x V]``) at the positions ``at`` ``[S, P]``
    of ``tokens`` ``[S, T]``."""
    x = hidden_states(seed, cfg, tokens, prec, broken)
    x = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
    return np.asarray(_head_step(
        weights.root_key(seed), x, weights._frozen(cfg),
        cfg["rms_norm_eps"], prec, every_head))


def forward_logits(seed: int, cfg: dict, tokens: np.ndarray,
                   prec: str = "highest", every_head: bool = False,
                   broken=None) -> np.ndarray:
    """Logits at every position of ``tokens`` ``[S, T]`` (tests; a
    served check asks for the served positions only)."""
    tokens = np.asarray(tokens)
    s, t = tokens.shape
    toks = np.zeros((s, padded(t, cfg)), np.int32)
    toks[:, :t] = tokens
    at = np.broadcast_to(np.arange(t), (s, t))
    return logits_at(seed, cfg, toks, at, prec, every_head, broken)


def served_gaps(seed: int, cfg: dict, samples, control: str = None):
    """``benchmark.reference.served_gaps``' gaps, a request a call and
    head 0 at the served positions only: for each ``(prompt, served)``
    sample, at every served position, how far the served token's
    reference logit lies below the reference's best; with ``control``,
    the same gap for the token that precision puts first there (else
    None). Flat arrays over all served positions."""
    prog, ctrl = [], []
    for prompt, served in samples:
        seq = list(prompt) + list(served[:-1])
        toks = np.zeros((1, padded(len(seq), cfg)), np.int32)
        toks[0, :len(seq)] = seq
        at = np.arange(len(prompt) - 1, len(seq))[None, :]
        ref = logits_at(seed, cfg, toks, at)[0]
        best = ref.max(axis=-1)
        rows = np.arange(len(served))
        prog.append(best - ref[rows, np.asarray(served)])
        if control:
            low = logits_at(seed, cfg, toks, at, control)[0]
            ctrl.append(best - ref[rows, low.argmax(axis=-1)])
    return (np.concatenate(prog),
            np.concatenate(ctrl) if ctrl else None)
