"""Loss functions.

Capability-parity set for the reference's ``LossFunctions.LossFunction`` enum
(external ND4J dependency, consumed by output-layer confs at reference
nn/conf/layers/BaseOutputLayer — values MSE, EXPLL, XENT, MCXENT, RMSE_XENT,
SQUARED_LOSS, RECONSTRUCTION_CROSSENTROPY, NEGATIVELOGLIKELIHOOD).

Convention (matches the reference's scoring): each loss returns the *mean
per-example* loss where the per-example loss sums over output units. Time
series inputs of shape [N, C, T] are scored per (example, timestep) with an
optional ``mask`` of shape [N, T] (reference: masked scoring in
BaseOutputLayer + Evaluation.evalTimeSeries, eval/Evaluation.java:171-226).

All functions are pure and jit-safe: ``loss_fn(name)(activations, labels,
mask)`` returns a scalar.
"""

from __future__ import annotations

import enum
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-8


class LossFunction(str, enum.Enum):
    MSE = "mse"
    EXPLL = "expll"
    XENT = "xent"
    MCXENT = "mcxent"
    RMSE_XENT = "rmse_xent"
    SQUARED_LOSS = "squared_loss"
    RECONSTRUCTION_CROSSENTROPY = "reconstruction_crossentropy"
    NEGATIVELOGLIKELIHOOD = "negativeloglikelihood"
    COSINE_PROXIMITY = "cosine_proximity"
    L1 = "l1"
    HINGE = "hinge"


def _flatten_time(a: Array) -> Array:
    """[N, C, T] -> [N*T, C] so losses see a 2-d (example, unit) matrix."""
    if a.ndim == 3:
        return jnp.transpose(a, (0, 2, 1)).reshape(-1, a.shape[1])
    return a


def _flatten_mask(mask: Optional[Array], n_rows: int) -> Optional[Array]:
    if mask is None:
        return None
    return mask.reshape(-1)[:n_rows]


def _reduce(per_example: Array, mask: Optional[Array]) -> Array:
    """Mean over (possibly masked) examples of a per-example loss vector."""
    if mask is None:
        return jnp.mean(per_example)
    mask = mask.astype(per_example.dtype)
    return jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _make(per_example_fn: Callable[[Array, Array], Array]):
    def loss(activations: Array, labels: Array, mask: Optional[Array] = None) -> Array:
        a = _flatten_time(activations)
        y = _flatten_time(labels)
        m = _flatten_mask(mask, a.shape[0])
        return _reduce(per_example_fn(a, y), m)

    return loss


def _mse(a, y):
    return jnp.sum((y - a) ** 2, axis=-1) / a.shape[-1]


def _squared(a, y):
    return jnp.sum((y - a) ** 2, axis=-1)


def _xent(a, y):
    a = jnp.clip(a, _EPS, 1.0 - _EPS)
    return -jnp.sum(y * jnp.log(a) + (1.0 - y) * jnp.log(1.0 - a), axis=-1)


def _mcxent(a, y):
    return -jnp.sum(y * jnp.log(jnp.clip(a, _EPS, None)), axis=-1)


def _expll(a, y):
    # Poisson-style exponential log likelihood.
    return jnp.sum(a - y * jnp.log(jnp.clip(a, _EPS, None)), axis=-1)


def _rmse_xent(a, y):
    return jnp.sqrt(_mse(a, y))


def _cosine(a, y):
    an = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + _EPS)
    yn = y / (jnp.linalg.norm(y, axis=-1, keepdims=True) + _EPS)
    return -jnp.sum(an * yn, axis=-1)


def _l1(a, y):
    return jnp.sum(jnp.abs(y - a), axis=-1)


def _hinge(a, y):
    # labels in {0,1} one-hot -> {-1,+1}
    return jnp.sum(jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * a), axis=-1)


_LOSSES: dict[LossFunction, Callable] = {
    LossFunction.MSE: _make(_mse),
    LossFunction.SQUARED_LOSS: _make(_squared),
    LossFunction.XENT: _make(_xent),
    LossFunction.MCXENT: _make(_mcxent),
    LossFunction.NEGATIVELOGLIKELIHOOD: _make(_mcxent),
    LossFunction.RECONSTRUCTION_CROSSENTROPY: _make(_xent),
    LossFunction.EXPLL: _make(_expll),
    LossFunction.RMSE_XENT: _make(_rmse_xent),
    LossFunction.COSINE_PROXIMITY: _make(_cosine),
    LossFunction.L1: _make(_l1),
    LossFunction.HINGE: _make(_hinge),
}


@jax.custom_vjp
def _label_nll_sum(logits: Array, labels: Array, weights: Array) -> Array:
    """``sum_i weights_i (logsumexp(logits_i) - logits_i[labels_i])``
    over ``logits`` ``[M, V]`` float32, ``labels`` ``[M]`` whole
    numbers, ``weights`` ``[M]``. Its own transpose rule, so that what
    is kept for the way back is the logits and one number a row, and
    what comes back is ONE ``[M, V]`` array, ``(softmax - hit) x
    weight``: the hit is a comparison fused into that pass, never a
    one-hot array."""
    return _label_nll_fwd(logits, labels, weights)[0]


def _label_nll_fwd(logits, labels, weights):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * weights), (logits, lse, labels,
                                               weights)


def _label_nll_bwd(res, g):
    logits, lse, labels, weights = res
    p = jnp.exp(logits - lse[:, None])
    hit = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                   1) == labels[:, None]
    return (jnp.where(hit, p - 1.0, p) * (g * weights)[:, None], None,
            None)


_label_nll_sum.defvjp(_label_nll_fwd, _label_nll_bwd)


def label_cross_entropy(logits: Array, labels: Array,
                        mask: Optional[Array] = None) -> Array:
    """MCXENT for a head that hands over its LOGITS ``[..., V]``
    (float32) and labels that are class ids ``[...]``: the mean over
    (masked) positions of ``-log softmax(logits)[label]``, the same
    number ``mcxent`` gives for softmax outputs and one-hot labels,
    with nothing of the logits' shape made but their gradient."""
    v = logits.shape[-1]
    flat = logits.reshape(-1, v).astype(jnp.float32)
    ids = labels.reshape(-1).astype(jnp.int32)
    if mask is None:
        weights = jnp.full(ids.shape, 1.0 / ids.shape[0], jnp.float32)
    else:
        m = mask.reshape(-1).astype(jnp.float32)
        weights = m / jnp.maximum(jnp.sum(m), 1.0)
    return _label_nll_sum(flat, ids, weights)


def loss_fn(which: LossFunction | str) -> Callable[..., Array]:
    """Look up ``(activations, labels, mask=None) -> scalar`` by name."""
    if isinstance(which, str):
        which = LossFunction(which.lower())
    return _LOSSES[which]
