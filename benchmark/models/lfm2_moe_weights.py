"""Seeded weights of the ``lfm2_moe`` stack (gated short-convolution
and grouped-KV attention layers, a leading dense feed-forward, then
sigmoid top-k routing over a chip's share of experts, a tied head),
made on the device a layer at a time.

The benchmark owns the weights: the program under test is handed them
(in its own layout, ``lfm2_moe.build_net``), and the plain reference
(``lfm2_moe_reference.py``) makes the same values again from the same
seed. Nothing here imports the program; the shapes are written out from
the configuration's keys.

Values: matrices N(0, ``initializer_range``) (0.02), norm weights
1 + 0.05 z (not exactly 1, so that a path which drops a norm, or a
QK-norm, shows), the router's selection bias ``expert_bias``
N(0, ``expert_bias_std``): not 0, so that a router which leaves it out
picks other experts, and small (0.002), so that the share of a step's
picks that falls on the held experts hardly moves with the seed
(PERF.md section 6, PR 31 and PR 33). Every leaf is drawn in float32,
ROUNDED to the configuration's ``compute_dtype`` (bfloat16) and held as
float32: the job's float32 masters start on values its bfloat16 compute
copy holds exactly, for the program and the reference alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORMS = ("norm1_w", "norm2_w", "q_norm_w", "k_norm_w", "norm_w")
MIXERS = {"conv": "conv", "full_attention": "attention"}


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


def layers_held(cfg: dict) -> list:
    """The published indices of the layers held; ``layer_types`` is
    kept whole and indexed by them."""
    held = list(cfg["layers_held"])
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held {held} does not name num_hidden_layers = "
            f"{cfg['num_hidden_layers']} layers")
    return held


def layer_kinds(cfg: dict) -> list:
    """``(mixer, feed-forward)`` of each layer held: ``"conv"`` or
    ``"attention"``, ``"dense"`` (a published index below
    ``num_dense_layers``) or ``"experts"``."""
    return [(MIXERS[cfg["layer_types"][i]],
             "dense" if i < cfg["num_dense_layers"] else "experts")
            for i in layers_held(cfg)]


def n_held(cfg: dict) -> int:
    """``num_experts`` is the experts HELD (the configuration lists it
    under ``reduced``); the router keeps ``router_outputs``."""
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError(
            f"experts_held {cfg['experts_held']} does not name "
            f"num_experts = {cfg['num_experts']} experts")
    return hi - lo


def layer_shapes(cfg: dict, kind) -> dict:
    """Leaf name -> shape of one layer, in the order the leaves are
    drawn (the position is the fold-in index), under the program's
    names: ``W_in`` ``[d, 3 d]`` is ``[B | C | u]``, ``conv_w``
    ``[K, d]`` has its last tap on the current input, ``Ws_in`` /
    ``We_in`` are ``[gate | up]``."""
    mixer, ffn = kind
    d = cfg["hidden_size"]
    if mixer == "conv":
        mix = {"W_in": (d, 3 * d), "conv_w": (cfg["conv_L_cache"], d),
               "W_out": (d, d)}
    else:
        dh = d // cfg["num_attention_heads"]
        kv = cfg["num_key_value_heads"] * dh
        mix = {"Wq": (d, d), "Wk": (d, kv), "Wv": (d, kv), "Wo": (d, d),
               "q_norm_w": (dh,), "k_norm_w": (dh,)}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        feed = {"Ws_in": (d, 2 * f), "Ws_out": (f, d)}
    else:
        f = cfg["moe_intermediate_size"]
        feed = {"router": (d, cfg["router_outputs"]),
                "We_in": (n_held(cfg), d, 2 * f),
                "We_out": (n_held(cfg), f, d),
                "expert_bias": (cfg["router_outputs"],)}
    return {"norm1_w": (d,), **mix, "norm2_w": (d,), **feed}


def _leaf(key, name: str, shape, cfg: dict):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        a = 1.0 + 0.05 * z
    else:
        a = (cfg["expert_bias_std"] if name == "expert_bias"
             else cfg["initializer_range"]) * z
    return a.astype(jnp.dtype(cfg["compute_dtype"])).astype(jnp.float32)


def _frozen(cfg: dict):
    """The configuration's shape keys as a hashable, for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "moe_intermediate_size", "conv_L_cache",
            "num_experts", "router_outputs", "vocab_size",
            "compute_dtype", "initializer_range", "expert_bias_std")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", tuple(cfg["experts_held"])),)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_layer(key, frozen, kind) -> dict:
    cfg = dict(frozen)
    return {name: _leaf(jax.random.fold_in(key, j), name, shape, cfg)
            for j, (name, shape) in enumerate(
                layer_shapes(cfg, kind).items())}


def make_layer(key, cfg: dict, kind) -> dict:
    """One layer's leaves, float32 on bfloat16's grid, from its key."""
    return _make_layer(key, _frozen(cfg), tuple(kind))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_end(key, frozen, name: str):
    cfg = dict(frozen)
    shape = ((cfg["hidden_size"],) if name == "norm_w"
             else (cfg["vocab_size"], cfg["hidden_size"]))
    at = ("E", "norm_w").index(name)
    return _leaf(jax.random.fold_in(key, 1_000_000 + at), name, shape,
                 cfg)


def make_end(key, cfg: dict, name: str):
    """What sits outside the layers: the embedding ``E`` (the held
    slice of the vocabulary; the head is tied to it) and the final
    norm ``norm_w``."""
    return _make_end(key, _frozen(cfg), name)


def make_params(seed: int, cfg: dict) -> dict:
    """The whole stack in the program's layout: ``{"0": embedding,
    "1".."L": blocks, str(L + 1): the head's norm}``."""
    key = root_key(seed)
    kinds = layer_kinds(cfg)
    params = {"0": {"W": make_end(key, cfg, "E")},
              str(len(kinds) + 1): {"norm_w": make_end(key, cfg,
                                                       "norm_w")}}
    for i, kind in enumerate(kinds):
        params[str(i + 1)] = dict(make_layer(layer_key(key, i), cfg,
                                             kind))
    return params
