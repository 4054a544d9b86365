"""Norms by leaf of a tree of parameters, gradients or moments: what
``correct`` compares of a training job, read the same way from the
program's state (``train_cell.py``) and from the plain reference
(``reference.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
