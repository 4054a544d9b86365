"""What every model's plain reference shares: the arithmetic of a
matrix product at a chosen precision (and with it the control of
``correct``), Adam and its schedule, and the two loops
that are the same for every model: following a job's first steps row
by row, and reading the gaps of served tokens block by block.

A model's own reference (``models/<model>.py`` and what it keeps beside
it) writes its layers with ``mm`` and hands these loops its weights and
its forward pass; it then has the ``fp8`` control for nothing. Nothing
here imports the program or takes what the program made.

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

from benchmark.norms import flat_norms, leaf_norms

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


# ---------------------------------------------------------------------
# serving: the gaps of served tokens, a few rows a call
# ---------------------------------------------------------------------
def served_gaps(forward, samples, control: str = None,
                rows_per_call: int = 2):
    """For each ``(prompt, served)`` sample: at every served position,
    how far the served token's reference logit lies below the
    reference's best. With ``control`` set, also the same gap for the
    token that precision puts first at the same positions.

    ``forward(tokens [S, T], prec)`` is the model's full forward pass,
    logits ``[S, T, V]``, causal, so that padding at the end of a row
    changes nothing before it. Returns ``(program_gaps, control_gaps)``:
    flat float arrays over all served positions of all samples
    (``control_gaps`` is None without a control)."""
    longest = max(len(p) + len(s) - 1 for p, s in samples)
    pad = 1 << max(longest - 1, 7).bit_length()
    toks = np.zeros((len(samples), pad), np.int32)
    for r, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served[:-1])
        toks[r, :len(seq)] = seq
    prog, ctrl = [], []
    for lo in range(0, len(samples), rows_per_call):
        rows = toks[lo:lo + rows_per_call]
        ref = forward(rows, "highest")
        low = forward(rows, control) if control else None
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


def follow_steps(params, row_loss, hyper: dict, batches):
    """Follow the first ``len(batches)`` steps of a job from ``params``
    (the seeded start, in the program's layout; consumed). Each batch
    is ``[B, T + 1]`` token ids and ``row_loss(params, row)`` the mean
    next-token loss of one row; the gradient is accumulated row by
    row, so no more than one row's activations are ever held.

    Returns ``(out, params)``: each step's loss and the first
    gradient's norm by leaf in ``out``, and the parameters after the
    last step, from which the caller takes the norm of each leaf's
    change against a start it makes again."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(params, acc, row):
        loss, g = jax.value_and_grad(row_loss)(params, row)
        return loss, jax.tree.map(jnp.add, acc, g)

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    b1, b2, eps = hyper["adam_mean_decay"], hyper["adam_var_decay"], \
        hyper["epsilon"]
    out = {"losses": [], "grad_norms": None}
    for it, batch in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, params)
        total = 0.0
        for row in np.asarray(batch, np.int32):
            loss, acc = accumulate(params, acc, jnp.asarray(row))
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
    return out, params
