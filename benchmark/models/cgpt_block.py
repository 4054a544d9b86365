"""The adapter of ``"model": "cgpt_block"``: Cerebras-GPT's pre-LN block
stack, the program's ``transformer_lm_flagship``.

This file and what it keeps beside it (``cgpt_block_weights.py``,
``cgpt_block_reference.py``, ``cgpt_block_flops.py``) are the only
places that read the configuration's model keys: ``n_embd``, ``n_head``,
``n_inner``, ``n_layer``, ``n_positions``. ``benchmark/models/__init__.py``
states the contract.
"""

from __future__ import annotations

import numpy as np

from benchmark.models import cgpt_block_flops as flops  # noqa: F401
from benchmark.models import cgpt_block_reference as block_reference
from benchmark.models import cgpt_block_weights as weights

train_reference = block_reference.train_reference
served_gaps = block_reference.served_gaps


def describe(cfg: dict) -> str:
    return (f"{cfg['n_layer']} pre-LN blocks of width {cfg['n_embd']} "
            f"({cfg['n_head']} heads, feed-forward {cfg['n_inner']}, "
            f"context {cfg['n_positions']})")


def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's flagship net at the configuration's sizes, with the
    weights ``cgpt_block_weights.py`` makes from the seed. With
    ``optimizer`` the net gets its updater state (a training job);
    without, none (a served model carries no moments)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("the program's block has a feed-forward of four "
                         "times the width; the configuration says "
                         f"{cfg['n_inner']} for {cfg['n_embd']}")
    opt = optimizer or {}
    conf = transformer_lm_flagship(
        vocab=cfg["vocab_size"], width=cfg["n_embd"],
        n_layers=cfg["n_layer"], n_heads=cfg["n_head"],
        lr=opt.get("learning_rate", 3e-4),
        warmup_steps=opt.get("lr_warmup_steps", 100),
        total_steps=opt.get("lr_total_steps", 1000),
        seed=seed & 0x7FFFFFFF)
    for c in conf.confs:
        c.compute_dtype = cfg["compute_dtype"]
        for key in ("lr_min_fraction", "adam_mean_decay",
                    "adam_var_decay", "epsilon"):
            if key in opt:
                setattr(c, key, opt[key])
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = cfg["n_positions"]
    net = MultiLayerNetwork(conf)
    # adopt the seeded weights in place of init(): init() would draw its
    # own leaf by leaf and allocate Adam's moments for a served model
    net.params = weights.make_params(
        seed, cfg["vocab_size"], cfg["n_embd"], cfg["n_inner"],
        cfg["n_layer"])
    net.state = {}
    net.updater_state = {
        str(i): (upd.init(net.params[str(i)]) if optimizer else {})
        for i, upd in enumerate(net._updaters)}
    net._initialized = True
    if net._compute_dtype != jnp.dtype(cfg["compute_dtype"]):
        raise ValueError(f"the net computes in {net._compute_dtype}, the "
                         f"configuration states {cfg['compute_dtype']}")
    return net


def encode_batch(tokens: np.ndarray, cfg: dict):
    """``[B, T + 1]`` ids to the program's ``[B, V, T]`` uint8 features
    (tokens 0..T-1) and labels (tokens 1..T): this net takes one-hot
    columns."""
    eye = np.eye(cfg["vocab_size"], dtype=np.uint8)
    return (np.ascontiguousarray(eye[tokens[:, :-1]].transpose(0, 2, 1)),
            np.ascontiguousarray(eye[tokens[:, 1:]].transpose(0, 2, 1)))


def start_params(seed: int, cfg: dict):
    """The seeded start in the program's layout, a block at a time and
    then what sits outside the blocks, so that the caller never holds a
    second copy of the stack."""
    n_layers = cfg["n_layer"]
    key = weights.root_key(seed)
    ends = weights.make_ends(key, cfg["vocab_size"], cfg["n_embd"],
                             n_layers)
    for i in range(n_layers):
        start = dict(weights.make_block(
            weights.layer_key(key, i), cfg["n_embd"], cfg["n_inner"],
            n_layers))
        if i == 0:
            start["Wi"] = ends["Wi"]
        yield {str(i): start}
    yield {str(n_layers): {"g": ends["g"], "b": ends["b"]},
           str(n_layers + 1): {"W": ends["W"], "b": ends["b_out"]}}
