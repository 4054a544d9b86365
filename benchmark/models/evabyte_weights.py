"""Seeded weights of the ``evabyte`` stack (EVA attention over an
aligned window and learned chunk summaries, SwiGLU, unit-offset
RMSNorm, a head of ``num_pred_heads x vocab_size`` rows), made on the
device a layer at a time.

The benchmark owns the weights: the program under test is handed them
(in its own layout, ``evabyte.build_net``), and the plain reference
(``evabyte_reference.py``) makes the same values again from the same
seed, one layer at a time. Nothing here imports the program; the shapes
are written out from the configuration's keys.

Values: matrices N(0, ``init_std``) (the published 0.01275), the
norms' ``w`` 0.05 z (the gain is ``1 + w``: not exactly 1, so that a
path which drops a norm or its unit offset shows), the pooling vectors
``eva_mu`` and ``eva_phi`` N(0, ``pool_std``) (1: the published
initialisation, a clamped N(0, 1) times ``init_std``, would pool every
chunk all but uniformly and a program that pooled uniformly would pass).
Every leaf is drawn in float32 and ROUNDED to the configuration's
``dtype`` (bfloat16): those rounded values are the model, for the
program and the reference alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORMS = ("norm1_w", "norm2_w", "norm_w")
POOLS = ("eva_mu", "eva_phi")
ENDS = ("E", "head", "norm_w")


def root_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31): the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_key(key, i: int):
    return jax.random.fold_in(key, i)


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of one layer, in the order the leaves are
    drawn (the position is the fold-in index). ``Ws_in`` is
    ``[gate | up]``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, dh = cfg["num_attention_heads"], head_dim(cfg)
    if cfg["num_key_value_heads"] != h:
        raise ValueError("evabyte's heads are not grouped")
    return {"norm1_w": (d,), "Wq": (d, h * dh), "Wk": (d, h * dh),
            "Wv": (d, h * dh), "Wo": (h * dh, d), "eva_mu": (h, dh),
            "eva_phi": (h, dh), "norm2_w": (d,), "Ws_in": (d, 2 * f),
            "Ws_out": (f, d)}


def _leaf(key, name: str, shape, dtype, cfg: dict):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        return (0.05 * z).astype(dtype)
    std = cfg["pool_std"] if name in POOLS else cfg["init_std"]
    return (std * z).astype(dtype)


def _frozen(cfg: dict):
    """The configuration's shape keys as a hashable, for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "num_pred_heads", "dtype",
            "init_std", "pool_std")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, frozen) -> dict:
    cfg = dict(frozen)
    return {name: _leaf(jax.random.fold_in(key, j), name, shape,
                        jnp.dtype(cfg["dtype"]), cfg)
            for j, (name, shape) in enumerate(layer_shapes(cfg).items())}


def make_layer(key, cfg: dict) -> dict:
    """One layer's leaves at the configuration's ``dtype``, from its
    layer key."""
    return _make_layer(key, _frozen(cfg))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_end(key, frozen, name: str):
    cfg = dict(frozen)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shape = {"E": (v, d), "head": (cfg["num_pred_heads"] * v, d),
             "norm_w": (d,)}[name]
    return _leaf(jax.random.fold_in(key, 1_000_000 + ENDS.index(name)),
                 name, shape, jnp.dtype(cfg["dtype"]), cfg)


def make_end(key, cfg: dict, name: str):
    """What sits outside the layers, one leaf a call: the embedding
    ``E``, the untied ``head`` (``num_pred_heads x vocab_size`` rows,
    head 0 first) and the final norm ``norm_w``."""
    return _make_end(key, _frozen(cfg), name)


def make_params(seed: int, cfg: dict) -> dict:
    """The whole stack in the program's layout: ``{"0": embedding,
    "1".."L": blocks, str(L + 1): head}``; nothing passes the host."""
    key = root_key(seed)
    n = cfg["num_hidden_layers"]
    params = {"0": {"W": make_end(key, cfg, "E")},
              str(n + 1): {"norm_w": make_end(key, cfg, "norm_w"),
                           "E": make_end(key, cfg, "head")}}
    for i in range(n):
        params[str(i + 1)] = dict(make_layer(layer_key(key, i), cfg))
    return params
