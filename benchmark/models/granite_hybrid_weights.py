"""Seeded weights of the hybrid Mamba-2 / attention expert stack, made
on the device a layer at a time.

The benchmark owns the weights: the program under test is handed them
(in its own layout, ``granite_hybrid.build_net``), and the plain
reference (``granite_hybrid_reference.py``) makes the same values again
from the same seed, one layer at a time. Nothing here imports the
program; the shapes are written out from the configuration's keys.

Values: matrices N(0, ``initializer_range``) (the family's 0.02), norm
weights 1 + 0.05 z (not exactly 1, so that a path which drops one
shows), the convolution's bias likewise small. The tied embedding's
rows are N(0, ``embedding_std``), SMALLER than the matrices: at 0.02 a
random tied head scores the input token itself (``12 |E_tok|^2``) above
every other, every precision then serves the same one-token loop, and
the comparison that decides ``correct`` would be blind. What Mamba-2 initialises by its
own rule and the config does not carry: ``A`` uniform in [1, 16]
(``A_log`` its logarithm), ``dt`` log-uniform in [1e-3, 1e-1]
(``dt_bias`` its inverse softplus), ``D`` = 1. Every leaf is drawn in
float32 and ROUNDED to the configuration's ``dtype`` (the published
checkpoint is bfloat16): those rounded values are the model, for the
program and the reference alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


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


def layer_kinds(cfg: dict) -> list:
    """The kinds of the layers held: the first ``num_hidden_layers`` of
    the published ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_held(cfg: dict) -> int:
    """``num_local_experts`` is the experts HELD (the configuration
    lists it under ``reduced``); the router keeps ``router_outputs``."""
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_local_experts"]:
        raise ValueError(
            f"experts_held {cfg['experts_held']} does not name "
            f"num_local_experts = {cfg['num_local_experts']} experts")
    return hi - lo


def layer_shapes(cfg: dict, kind: str) -> dict:
    """Leaf name -> shape of one layer, in the order the leaves are
    drawn (the position is the fold-in index)."""
    d = cfg["hidden_size"]
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    if kind == "attention":
        dh = d // cfg["num_attention_heads"]
        kv = cfg["num_key_value_heads"] * dh
        mix = {"Wq": (d, d), "Wk": (d, kv), "Wv": (d, kv), "Wo": (d, d)}
    else:
        h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        conv = h * p + 2 * gn
        mix = {"W_in": (d, 2 * h * p + 2 * gn + h),
               "conv_w": (cfg["mamba_d_conv"], conv), "conv_b": (conv,),
               "dt_bias": (h,), "A_log": (h,), "D": (h,),
               "norm_w": (h * p,), "W_out": (h * p, d)}
    return {"norm1_w": (d,), **mix, "norm2_w": (d,),
            "router": (d, cfg["router_outputs"]),
            "We_in": (n_held(cfg), d, 2 * f),
            "We_out": (n_held(cfg), f, d),
            "Ws_in": (d, 2 * fs), "Ws_out": (fs, d)}


def _leaf(key, name: str, shape, dtype, std: float):
    if name == "D":
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("norm"):
        return (1.0 + 0.05 * z).astype(dtype)
    return (std * z).astype(dtype)


def _frozen(cfg: dict):
    """The configuration's shape keys as a hashable, for ``jit``."""
    keys = ("hidden_size", "intermediate_size", "shared_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_n_groups", "mamba_d_state",
            "mamba_d_conv", "num_local_experts", "router_outputs",
            "vocab_size", "dtype", "initializer_range", "embedding_std")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", tuple(cfg["experts_held"])),)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_layer(key, frozen, kind: str) -> dict:
    cfg = dict(frozen)
    return {name: _leaf(jax.random.fold_in(key, j), name, shape,
                        jnp.dtype(cfg["dtype"]), cfg["initializer_range"])
            for j, (name, shape) in enumerate(
                layer_shapes(cfg, kind).items())}


def make_layer(key, cfg: dict, kind: str) -> dict:
    """One layer's leaves at the configuration's ``dtype``, from its
    layer key."""
    return _make_layer(key, _frozen(cfg), kind)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_ends(key, frozen) -> dict:
    cfg = dict(frozen)
    dtype = jnp.dtype(cfg["dtype"])
    return {"E": _leaf(jax.random.fold_in(key, 1_000_000), "E",
                       (cfg["vocab_size"], cfg["hidden_size"]), dtype,
                       cfg["embedding_std"]),
            "norm_w": _leaf(jax.random.fold_in(key, 1_000_001), "norm_w",
                            (cfg["hidden_size"],), dtype, 0.0)}


def make_ends(key, cfg: dict) -> dict:
    """What sits outside the layers: the tied embedding / head matrix
    ``E`` (the held slice of the vocabulary) and the final norm."""
    return _make_ends(key, _frozen(cfg))


def make_params(seed: int, cfg: dict) -> dict:
    """The whole stack in the program's layout: ``{"0": embedding,
    "1".."L": blocks, str(L + 1): head}``, one compiled program a kind
    of layer, called once a layer; nothing passes the host."""
    key = root_key(seed)
    ends = make_ends(key, cfg)
    kinds = layer_kinds(cfg)
    params = {"0": {"W": ends["E"]},
              str(len(kinds) + 1): {"norm_w": ends["norm_w"]}}
    for i, kind in enumerate(kinds):
        params[str(i + 1)] = dict(make_layer(layer_key(key, i), cfg, kind))
    return params
