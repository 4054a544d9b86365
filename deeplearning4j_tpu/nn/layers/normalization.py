"""Normalization layers: batch norm + local response normalization.

Reference: nn/layers/normalization/BatchNormalization.java (402 LoC) and
LocalResponseNormalization.java. Batch-norm running statistics are carried
in the functional state pytree (no mutation), the TPU-idiomatic equivalent
of the reference's in-place moving averages.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.layers.base import LayerImplBase
from deeplearning4j_tpu.profiler.scopes import scope


class BatchNormImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        n = lc.n_out or lc.n_in
        return {
            "gamma": jnp.full((n,), lc.gamma, dtype),
            "beta": jnp.full((n,), lc.beta, dtype),
        }

    @classmethod
    def init_state(cls, conf, dtype=jnp.float32):
        lc = conf.layer
        n = lc.n_out or lc.n_in
        return {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None, mask=None):
        lc = conf.layer
        # Normalize over all axes except the channel axis (axis 1 for 4-d
        # CNN activations, axis 1 for [N, C]).
        axes = (0,) if x.ndim == 2 else (0, 2, 3)
        shape = (1, -1) if x.ndim == 2 else (1, -1, 1, 1)
        if train:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            decay = lc.decay
            new_state = {
                "mean": decay * state["mean"] + (1 - decay) * mean,
                "var": decay * state["var"] + (1 - decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        xhat = (x - mean.reshape(shape)) * lax.rsqrt(
            var.reshape(shape) + lc.eps
        )
        if lc.lock_gamma_beta:
            out = xhat
        else:
            out = params["gamma"].reshape(shape) * xhat + params["beta"].reshape(
                shape
            )
        return out, new_state


@scope("norm")
def layer_norm(x, g, b, axis: int = -1, eps: float = 1e-5):
    """LayerNorm over ``axis``; moments at >= f32 so the bf16 compute
    path keeps a stable normalizer (promote, don't hard-cast — the f64
    gradient-check path must stay f64). Shared by LayerNormImpl (axis 1
    on [N, C, T]) and TransformerBlockImpl (trailing axis on [N, T, C]).
    """
    ct = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(ct)
    mu = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + jnp.asarray(eps, ct))
    shape = [1] * x.ndim
    shape[axis] = -1
    return (y * g.astype(ct).reshape(shape)
            + b.astype(ct).reshape(shape)).astype(x.dtype)


class LayerNormImpl(LayerImplBase):
    """Per-example LayerNorm over the channel axis (conf bean
    LayerNormalization); works on [N, C] and [N, C, T]."""

    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        n = lc.n_out or lc.n_in
        return {"g": jnp.ones((n,), dtype), "b": jnp.zeros((n,), dtype)}

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None):
        lc = conf.layer
        y = layer_norm(x, params["g"], params["b"], axis=1, eps=lc.eps)
        return y, None


class LRNImpl(LayerImplBase):
    """Across-channel local response normalization (reference
    LocalResponseNormalization.java):
    y = x / (k + alpha * sum_{j in window} x_j^2)^beta.
    """

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None, mask=None):
        lc = conf.layer
        half = int(lc.n) // 2
        sq = x * x
        # Sliding window sum over the channel axis via reduce_window.
        s = lax.reduce_window(
            sq,
            0.0,
            lax.add,
            window_dimensions=(1, 2 * half + 1, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (half, half), (0, 0), (0, 0)),
        )
        return x / jnp.power(lc.k + lc.alpha * s, lc.beta), state
