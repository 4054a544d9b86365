"""The plain reference of the block stack: float32 ``jax.numpy``, no
kernels, no cache, no batching tricks.

It follows Cerebras-GPT's published block (GPT-2 layout: pre-LN,
multi-head causal attention, GELU feed-forward of four times the width,
residuals) with the departures the configuration files list under
``assumed``: no learned positional table (the program has none), the
tanh form of GELU (the program's ``jax.nn.gelu``; the published model
uses the erf form), and a one-hot input projection without bias in
place of the token embedding.

It imports nothing of the program and takes nothing the program made:
weights come from ``cgpt_block_weights.py`` and the seed. It works
layer by layer (serving) or row by row (training), so it fits beside
nothing else on one chip, and the harness runs it after the program's
state is freed. The arithmetic of a product (``mm``, with the ``fp8``
control), Adam and the two loops are ``benchmark/reference.py``'s,
shared with every other model's reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import norms, reference
from benchmark.models import cgpt_block_weights as weights
from benchmark.reference import _round_to, mm


def layer_norm(x, g, b, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p: dict, x, n_heads: int, prec: str):
    """One pre-LN block on ``x`` [N, T, d]."""
    n, t, d = x.shape
    dh = d // n_heads
    h = layer_norm(x, p["ln1_g"], p["ln1_b"])

    def heads(w):
        return mm(h, w, prec, "ntd,de->nte").reshape(n, t, n_heads, dh)

    q, k, v = heads(p["Wq"]), heads(p["Wk"]), heads(p["Wv"])
    s = mm(q, k, prec, "nqhc,nkhc->nhqk") / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm(a, v, prec, "nhqk,nkhc->nqhc").reshape(n, t, d)
    x = x + mm(o, p["Wo"], prec, "ntd,de->nte") + p["bo"]
    h2 = layer_norm(x, p["ln2_g"], p["ln2_b"])
    f = gelu_tanh(mm(h2, p["W1"], prec, "ntd,df->ntf") + p["b1"])
    return x + mm(f, p["W2"], prec, "ntf,fd->ntd") + p["b2"]


def embed(wi, tokens, prec: str):
    """One-hot times ``Wi``: a row of ``Wi``, rounded as a product's
    operand would be."""
    return _round_to(wi, prec).astype(jnp.float32)[tokens]


def head(ends: dict, x, prec: str):
    hn = layer_norm(x, ends["g"], ends["b"])
    return mm(hn, ends["W"], prec, "ntd,dv->ntv") + ends["b_out"]


# ---------------------------------------------------------------------
# serving: the full forward over prompt + served tokens, layer by layer
# ---------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _block_step(layer_key, x, width, ffn, n_layers, n_heads, prec):
    p = weights.make_block(layer_key, width, ffn, n_layers)
    return block(p, x, n_heads, prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _embed_step(key, tokens, vocab, width, n_layers, prec):
    return embed(weights.make_ends(key, vocab, width, n_layers)["Wi"],
                 tokens, prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _head_step(key, x, vocab, width, n_layers, prec):
    return head(weights.make_ends(key, vocab, width, n_layers), x, prec)


def forward_logits(seed: int, cfg: dict, tokens: np.ndarray,
                   prec: str = "highest") -> np.ndarray:
    """Logits [S, T, V] of the stack over ``tokens`` [S, T]. Causal, so
    padding at the end of a row changes nothing before it."""
    vocab, width = cfg["vocab_size"], cfg["n_embd"]
    ffn, n_layers, n_heads = cfg["n_inner"], cfg["n_layer"], cfg["n_head"]
    key = weights.root_key(seed)
    x = _embed_step(key, jnp.asarray(tokens, jnp.int32), vocab, width,
                    n_layers, prec)
    for i in range(n_layers):
        x = _block_step(weights.layer_key(key, i), x, width, ffn,
                        n_layers, n_heads, prec)
    return np.asarray(_head_step(key, x, vocab, width, n_layers, prec))


def served_gaps(seed: int, cfg: dict, samples, control: str = None):
    """``reference.served_gaps`` over this stack's forward pass."""
    return reference.served_gaps(
        functools.partial(forward_logits, seed, cfg), samples, control)


# ---------------------------------------------------------------------
# training: the loss of one row; the loop is reference.follow_steps
# ---------------------------------------------------------------------
def row_loss(params: dict, row, n_layers: int, n_heads: int, prec: str):
    """Mean next-token cross-entropy (nats) of one row of ``T + 1``
    token ids: the program's MCXENT on softmax outputs, mean over
    positions."""
    x = embed(params["0"]["Wi"], row[None, :-1], prec)
    step = jax.checkpoint(
        lambda p, xin: block(p, xin, n_heads, prec))
    for i in range(n_layers):
        x = step({k: v for k, v in params[str(i)].items() if k != "Wi"},
                 x)
    ends = {"g": params[str(n_layers)]["g"],
            "b": params[str(n_layers)]["b"],
            "W": params[str(n_layers + 1)]["W"],
            "b_out": params[str(n_layers + 1)]["b"]}
    logp = jax.nn.log_softmax(head(ends, x, prec)[0], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def train_reference(seed: int, cfg: dict, hyper: dict, batches,
                    prec: str = "highest") -> dict:
    """Follow the first ``len(batches)`` steps of the job
    (``reference.follow_steps``): each batch is ``[B, T + 1]`` token
    ids. Returns each step's loss, the first gradient's norm by leaf
    and the norm of each leaf's change after the last step."""
    sizes = (cfg["vocab_size"], cfg["n_embd"], cfg["n_inner"],
             cfg["n_layer"])
    out, params = reference.follow_steps(
        weights.make_params(seed, *sizes),
        functools.partial(row_loss, n_layers=cfg["n_layer"],
                          n_heads=cfg["n_head"], prec=prec),
        hyper, batches)
    start = weights.make_params(seed, *sizes)
    out["delta_norms"] = norms.flat_norms(
        norms.delta_norms(params, start))
    return out
