"""Embedding layer.

Reference: nn/layers/feedforward/embedding/EmbeddingLayer.java — input is a
column of integer indices [N, 1]; output is W[idx] + b. On TPU the lookup is
``jnp.take`` which XLA lowers to a gather; backprop produces a scatter-add.
With the bean's ``sequence`` the input is ``[N, T]`` token ids and the output
``[N, n_out, T]``, the recurrent layout: a language model's first layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import LayerImplBase
from deeplearning4j_tpu.nn.weights import init_weights


class EmbeddingImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        w = init_weights(
            key,
            (lc.n_in, lc.n_out),
            conf.resolved("weight_init"),
            conf.resolved("dist"),
            dtype,
        )
        if lc.sequence:
            return {"W": w}
        b = jnp.full((lc.n_out,), conf.resolved("bias_init"), dtype)
        return {"W": w, "b": b}

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None, mask=None):
        lc = conf.layer
        idx = x.astype(jnp.int32)
        if lc.sequence:
            # [N, T] token ids -> [N, n_out, T]
            z = jnp.take(params["W"], idx, axis=0)
            z = z * jnp.asarray(lc.multiplier, z.dtype)
            return jnp.transpose(z, (0, 2, 1)), state
        if idx.ndim == 2:
            idx = idx[:, 0]
        z = jnp.take(params["W"], idx, axis=0) + params["b"]
        return cls.activation_of(conf)(z), state
