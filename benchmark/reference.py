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
weights come from ``weights.py`` and the seed. It works layer by layer
(serving) or row by row (training), so it fits beside nothing else on
one chip, and the harness runs it after the program's state is freed.

``prec`` chooses the arithmetic of every matrix product: ``highest``
(float32, six bf16 passes on a TPU) is the reference; ``bf16`` is what
the configurations state; ``fp8`` (operands rounded to float8 e4m3) is
the control, the nearest precision below the stated one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

PRECISIONS = ("highest", "bf16", "fp8")


def _round_to(a, prec: str):
    if prec == "highest":
        return a
    if prec == "bf16":
        return a.astype(jnp.bfloat16)
    if prec == "fp8":
        # what a tempting fp8 path would do: a power-of-two scale per
        # tensor so that its largest entry sits at e4m3's top (448),
        # rounding straight through (the cotangent is not rounded)
        top = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        scale = jnp.exp2(jnp.ceil(jnp.log2(top / 448.0)))
        q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a + jax.lax.stop_gradient(q * scale - a)
    raise ValueError(f"precision {prec!r}: expected one of {PRECISIONS}")


def mm(a, b, prec: str, spec: str):
    """``einsum(spec, a, b)`` in float32 accumulation, operands rounded
    as ``prec`` says."""
    return jnp.einsum(spec, _round_to(a, prec), _round_to(b, prec),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


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


def served_gaps(seed: int, cfg: dict, samples, control: str = None,
                rows_per_call: int = 2):
    """For each ``(prompt, served)`` sample: at every served position,
    how far the served token's reference logit lies below the
    reference's best. With ``control`` set, also the same gap for the
    token that precision puts first at the same positions.

    Returns ``(program_gaps, control_gaps)``: flat float arrays over all
    served positions of all samples (``control_gaps`` is None without a
    control)."""
    longest = max(len(p) + len(s) - 1 for p, s in samples)
    pad = 1 << max(longest - 1, 7).bit_length()
    toks = np.zeros((len(samples), pad), np.int32)
    for r, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served[:-1])
        toks[r, :len(seq)] = seq
    prog, ctrl = [], []
    for lo in range(0, len(samples), rows_per_call):
        rows = toks[lo:lo + rows_per_call]
        ref = forward_logits(seed, cfg, rows, "highest")
        low = (forward_logits(seed, cfg, rows, control)
               if control else None)
        for r, (prompt, served) in enumerate(samples[lo:lo + len(rows)]):
            at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
            best = ref[r, at].max(axis=-1)
            prog.append(best - ref[r, at, np.asarray(served)])
            if low is not None:
                ctrl.append(best - ref[r, at, low[r, at].argmax(axis=-1)])
    return (np.concatenate(prog),
            np.concatenate(ctrl) if ctrl else None)


# ---------------------------------------------------------------------
# training: loss, gradient and Adam, row by row
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


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(1,))
def _accumulate(params, acc, row, n_layers, n_heads, prec):
    loss, g = jax.value_and_grad(row_loss)(params, row, n_layers,
                                           n_heads, prec)
    return loss, jax.tree.map(jnp.add, acc, g)


def lr_at(hyper: dict, iteration: int) -> float:
    """Linear warm-up then cosine decay, as the configuration states."""
    warm, total = hyper["lr_warmup_steps"], hyper["lr_total_steps"]
    ramp = min(iteration / warm, 1.0) if warm > 0 else 1.0
    prog = min(max((iteration - warm) / (total - warm), 0.0), 1.0)
    frac = hyper["lr_min_fraction"]
    cos = frac + (1.0 - frac) * 0.5 * (1.0 + math.cos(math.pi * prog))
    return hyper["learning_rate"] * ramp * cos


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, m, v, grads, lr, bias, b1, b2, eps):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, c: p - lr * bias * a / (jnp.sqrt(c) + eps),
        params, m, v)
    return params, m, v


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(
        a.astype(jnp.float32) ** 2)), tree)


@jax.jit
def delta_norms(tree, start):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(
        (a.astype(jnp.float32) - b) ** 2)), tree, start)


def flat_norms(tree) -> dict:
    """``{"<layer>.<leaf>": norm}`` as Python floats."""
    return {f"{layer}.{name}": float(val)
            for layer, leaves in tree.items()
            for name, val in leaves.items()}


def train_reference(seed: int, cfg: dict, hyper: dict, batches,
                    prec: str = "highest") -> dict:
    """Follow the first ``len(batches)`` steps of the job: each batch is
    ``[B, T + 1]`` token ids. Returns each step's loss, the first
    gradient's norm by leaf and the norm of each leaf's change after
    the last step."""
    vocab, width = cfg["vocab_size"], cfg["n_embd"]
    ffn, n_layers, n_heads = cfg["n_inner"], cfg["n_layer"], cfg["n_head"]
    params = weights.make_params(seed, vocab, width, ffn, n_layers)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    b1, b2, eps = hyper["adam_mean_decay"], hyper["adam_var_decay"], \
        hyper["epsilon"]
    out = {"losses": [], "grad_norms": None}
    for it, batch in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, params)
        total = 0.0
        for row in np.asarray(batch, np.int32):
            loss, acc = _accumulate(params, acc, jnp.asarray(row),
                                    n_layers, n_heads, prec)
            total += float(loss)
        grads = jax.tree.map(lambda a: a / len(batch), acc)
        out["losses"].append(total / len(batch))
        if it == 0:
            out["grad_norms"] = flat_norms(leaf_norms(grads))
        t = it + 1
        bias = math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        params, m, v = _adam(params, m, v, grads, lr_at(hyper, it), bias,
                             b1, b2, eps)
        del grads, acc
    start = weights.make_params(seed, vocab, width, ffn, n_layers)
    out["delta_norms"] = flat_norms(delta_norms(params, start))
    return out
