"""Seeded weights of the ``afmoe`` stack (sliding-window and full
attention layers, gated attention, QK-norm, sandwich norms, sigmoid
top-k routing over a chip's share of experts), made on the device a
layer at a time.

The benchmark owns the weights: the program under test is handed them
(in its own layout, ``afmoe.build_net``), and the plain reference
(``afmoe_reference.py``) makes the same values again from the same
seed, one layer at a time. Nothing here imports the program; the shapes
are written out from the configuration's keys.

Values: matrices N(0, ``initializer_range``) (0.02), norm weights
1 + 0.05 z (not exactly 1, so that a path which drops one of the four
sandwich norms, or a QK-norm, shows), the attention's post-norm
``attn_post_gain`` (0.1) times that (under random weights a row's
attention output is nearly one vector, the mean of its values, for
every token of the row; a post-norm of gain 1 blows it up to the size
of the embedding, the router then sees the ROW and not the token, every
token of a row picks the same experts and a chip's share of a row's
picks lies anywhere from 8% to 21%; at 0.1 the token decides, as in a
trained model, and the share is 1 / 8: PERF.md section 6), the router's
selection bias
``expert_bias`` N(0, ``expert_bias_std``) (not 0, so that a router
which leaves it out picks other experts and fails the check). Every
leaf is drawn in float32 and ROUNDED to the configuration's ``dtype``
(bfloat16): those rounded values are the model, for the program and the
reference alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORMS = ("norm1_w", "norm2_w", "post1_w", "post2_w", "q_norm_w",
         "k_norm_w", "norm_w")


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
    """``(attention, feed-forward)`` of each layer held: ``"sliding"``
    or ``"full"``, ``"dense"`` (a published index below
    ``num_dense_layers``) or ``"experts"``."""
    return [(cfg["layer_types"][i].split("_")[0],
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


def layer_shapes(cfg: dict, ffn: str) -> dict:
    """Leaf name -> shape of one layer, in the order the leaves are
    drawn (the position is the fold-in index). Every layer's attention
    has the same shapes, sliding or full."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    mix = {"Wq": (d, q), "Wk": (d, kv), "Wv": (d, kv), "Wo": (q, d),
           "q_norm_w": (dh,), "k_norm_w": (dh,), "Wg": (d, q)}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        feed = {"Ws_in": (d, 2 * f), "Ws_out": (f, d)}
    else:
        f = cfg["moe_intermediate_size"]
        fs = cfg["num_shared_experts"] * f
        feed = {"router": (d, cfg["router_outputs"]),
                "We_in": (n_held(cfg), d, 2 * f),
                "We_out": (n_held(cfg), f, d),
                "Ws_in": (d, 2 * fs), "Ws_out": (fs, d),
                "expert_bias": (cfg["router_outputs"],)}
    return {"norm1_w": (d,), **mix, "norm2_w": (d,), **feed,
            "post1_w": (d,), "post2_w": (d,)}


def _leaf(key, name: str, shape, dtype, cfg: dict):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        gain = cfg["attn_post_gain"] if name == "post1_w" else 1.0
        return (gain * (1.0 + 0.05 * z)).astype(dtype)
    std = (cfg["expert_bias_std"] if name == "expert_bias"
           else cfg["initializer_range"])
    return (std * z).astype(dtype)


def _frozen(cfg: dict):
    """The configuration's shape keys as a hashable, for ``jit``."""
    keys = ("hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "intermediate_size",
            "moe_intermediate_size", "num_shared_experts", "num_experts",
            "router_outputs", "vocab_size", "dtype", "initializer_range",
            "expert_bias_std", "attn_post_gain")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", tuple(cfg["experts_held"])),)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_layer(key, frozen, ffn: str) -> dict:
    cfg = dict(frozen)
    return {name: _leaf(jax.random.fold_in(key, j), name, shape,
                        jnp.dtype(cfg["dtype"]), cfg)
            for j, (name, shape) in enumerate(
                layer_shapes(cfg, ffn).items())}


def make_layer(key, cfg: dict, ffn: str) -> dict:
    """One layer's leaves at the configuration's ``dtype``, from its
    layer key."""
    return _make_layer(key, _frozen(cfg), ffn)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_end(key, frozen, name: str):
    cfg = dict(frozen)
    shape = ((cfg["hidden_size"],) if name == "norm_w"
             else (cfg["vocab_size"], cfg["hidden_size"]))
    at = ("E", "head", "norm_w").index(name)
    return _leaf(jax.random.fold_in(key, 1_000_000 + at), name, shape,
                 jnp.dtype(cfg["dtype"]), cfg)


def make_end(key, cfg: dict, name: str):
    """What sits outside the layers, one leaf a call: the embedding
    ``E`` and the untied ``head`` (each the held slice of the
    vocabulary) and the final norm ``norm_w``."""
    return _make_end(key, _frozen(cfg), name)


def make_params(seed: int, cfg: dict) -> dict:
    """The whole stack in the program's layout: ``{"0": embedding,
    "1".."L": blocks, str(L + 1): head}``, one compiled program a kind
    of layer, called once a layer; nothing passes the host."""
    key = root_key(seed)
    kinds = layer_kinds(cfg)
    params = {"0": {"W": make_end(key, cfg, "E")},
              str(len(kinds) + 1): {"norm_w": make_end(key, cfg, "norm_w"),
                                    "E": make_end(key, cfg, "head")}}
    for i, (_, ffn) in enumerate(kinds):
        params[str(i + 1)] = dict(make_layer(layer_key(key, i), cfg, ffn))
    return params
