"""Seeded weights of the block stack, made on the device.

The benchmark owns the weights: the program under test is handed them,
and the plain reference (``cgpt_block_reference.py``) makes the same
values again from the same seed, layer by layer, so that it never holds
more than one block and takes nothing the program has made.

Values follow the GPT-2 convention the Cerebras-GPT paper trains from:
matrices N(0, 0.02), the two matrices that write into the residual
stream N(0, 0.02 / sqrt(2 L)). Biases and LayerNorm gains and offsets
get a small seeded spread as well (not 0 and 1), so that a path which
drops one of them shows in the comparison.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: leaf order inside one block: the position is the fold-in index, so
#: adding a leaf at the end never changes the others' values
BLOCK_LEAVES = ("ln1_g", "ln1_b", "Wq", "Wk", "Wv", "Wo", "bo",
                "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")
_STD = 0.02


def root_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31): the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def block_shapes(width: int, ffn: int) -> dict:
    d, f = width, ffn
    return {"ln1_g": (d,), "ln1_b": (d,), "Wq": (d, d), "Wk": (d, d),
            "Wv": (d, d), "Wo": (d, d), "bo": (d,), "ln2_g": (d,),
            "ln2_b": (d,), "W1": (d, f), "b1": (f,), "W2": (f, d),
            "b2": (d,)}


def _leaf(key, name: str, shape, n_layers: int):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g") or name == "g":
        return 1.0 + 0.05 * z
    if name in ("Wo", "W2"):
        return (_STD / math.sqrt(2.0 * n_layers)) * z
    return _STD * z


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def make_block(layer_key, width: int, ffn: int, n_layers: int) -> dict:
    """One block's 13 leaves, float32, from its layer key."""
    shapes = block_shapes(width, ffn)
    return {name: _leaf(jax.random.fold_in(layer_key, j), name,
                        shapes[name], n_layers)
            for j, name in enumerate(BLOCK_LEAVES)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def make_ends(key, vocab: int, width: int, n_layers: int) -> dict:
    """What sits outside the blocks: the input projection ``Wi`` (the
    one-hot embedding), the final LayerNorm and the output head."""
    k = [jax.random.fold_in(key, 1_000_000 + j) for j in range(5)]
    return {"Wi": _leaf(k[0], "Wi", (vocab, width), n_layers),
            "g": _leaf(k[1], "g", (width,), n_layers),
            "b": _leaf(k[2], "b", (width,), n_layers),
            "W": _leaf(k[3], "W", (width, vocab), n_layers),
            "b_out": _leaf(k[4], "b_out", (vocab,), n_layers)}


def layer_key(key, i: int):
    return jax.random.fold_in(key, i)


def make_params(seed: int, vocab: int, width: int, ffn: int,
                n_layers: int) -> dict:
    """The whole stack in the program's layout: ``{"0": block (with
    Wi), ..., str(L): final LayerNorm, str(L+1): head}``. One compiled
    program per kind, called once per layer; nothing passes the host."""
    key = root_key(seed)
    ends = make_ends(key, vocab, width, n_layers)
    params = {}
    for i in range(n_layers):
        params[str(i)] = dict(make_block(layer_key(key, i), width, ffn,
                                         n_layers))
    params["0"]["Wi"] = ends["Wi"]
    params[str(n_layers)] = {"g": ends["g"], "b": ends["b"]}
    params[str(n_layers + 1)] = {"W": ends["W"], "b": ends["b_out"]}
    return params
