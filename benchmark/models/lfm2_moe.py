"""The adapter of ``"model": "lfm2_moe"``: Liquid's ``lfm2_moe`` stack
(LFM2-8B-A1B: gated short-convolution layers beside grouped-KV
attention layers with QK-norm and rotary positions, leading dense
feed-forwards, then sigmoid top-k routing with a selection bias over a
chip's share of experts, no shared expert, a tied head), the program's
``lfm2_moe_lm``. TRAINED: it has the six training calls and no
``served_gaps`` (``common.need`` refuses a served cell of it).

This file and what it keeps beside it (``lfm2_moe_weights.py``,
``lfm2_moe_reference.py``, ``lfm2_moe_flops.py``) are the only places
that read the configuration's model keys. ``benchmark/models/
__init__.py`` states the contract.
"""

from __future__ import annotations

import numpy as np

from benchmark.models import lfm2_moe_flops as flops  # noqa: F401
from benchmark.models import lfm2_moe_reference
from benchmark.models import lfm2_moe_weights as weights

train_reference = lfm2_moe_reference.train_reference


def describe(cfg: dict) -> str:
    kinds = weights.layer_kinds(cfg)
    mixers = [m for m, _ in kinds]
    return (f"{len(kinds)} lfm2_moe blocks (published layers "
            f"{weights.layers_held(cfg)}) of width {cfg['hidden_size']}: "
            f"{mixers.count('conv')} gated short convolutions of "
            f"{cfg['conv_L_cache']} taps and {mixers.count('attention')} "
            f"attention of {cfg['num_attention_heads']}/"
            f"{cfg['num_key_value_heads']} heads of "
            f"{cfg['hidden_size'] // cfg['num_attention_heads']}, "
            f"{[f for _, f in kinds].count('dense')} dense feed-forward of "
            f"{cfg['intermediate_size']}, experts {cfg['experts_held']} of "
            f"{cfg['router_outputs']} held (width "
            f"{cfg['moe_intermediate_size']}), top "
            f"{cfg['num_experts_per_tok']} by sigmoid score, vocabulary "
            f"{cfg['vocab_size']} (tied), float32 masters, "
            f"{cfg['compute_dtype']} compute, "
            f"{'a layer recomputed on the way back' if cfg['remat'] else 'no recomputation'}")


def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's zoo net at the configuration's sizes, holding the
    weights ``lfm2_moe_weights.py`` makes from the seed as float32
    masters, computing in ``compute_dtype``. With ``optimizer`` the net
    gets its updater state (the training job)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import lfm2_moe_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if not (cfg["norm_topk_prob"] and cfg["use_expert_bias"]):
        raise ValueError("the program's lfm2_moe router normalises the "
                         "picked scores and adds a selection bias; the "
                         "configuration says otherwise")
    weights.n_held(cfg)
    opt = optimizer or {}
    conf = lfm2_moe_lm(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"], layers=weights.layers_held(cfg),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"], rope_theta=cfg["rope_theta"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=cfg["experts_held"],
        freeze_router=cfg["freeze_router"], norm_eps=cfg["norm_eps"],
        max_position_embeddings=cfg["trained_context"],
        initializer_range=cfg["initializer_range"],
        dtype="float32", compute_dtype=cfg["compute_dtype"],
        lr=opt.get("learning_rate", 3e-4),
        warmup_steps=opt.get("lr_warmup_steps", 100),
        total_steps=opt.get("lr_total_steps", 1000),
        remat=cfg["remat"], seed=seed & 0x7FFFFFFF)
    kernels = cfg.get("kernels")    # the rehearsal: "interpret"
    for c in conf.confs:
        for key in ("lr_min_fraction", "adam_mean_decay",
                    "adam_var_decay", "epsilon"):
            if key in opt:
                setattr(c, key, opt[key])
        if kernels is not None and hasattr(c.layer, "use_kernels"):
            c.layer.use_kernels = kernels
    net = MultiLayerNetwork(conf)
    # adopt the seeded weights in place of init()
    net.params = weights.make_params(seed, cfg)
    net.state = {}
    net.updater_state = {
        str(i): (upd.init(net.params[str(i)]) if optimizer else {})
        for i, upd in enumerate(net._updaters)}
    net._initialized = True
    want = jnp.dtype(cfg["compute_dtype"])
    if (net._compute_dtype or net._dtype) != want:
        raise ValueError(
            f"the net computes in {net._compute_dtype or net._dtype}, "
            f"the configuration states {cfg['compute_dtype']}")
    return net


def encode_batch(tokens: np.ndarray, cfg: dict):
    """``[B, T + 1]`` ids to the program's features, the ids
    ``[B, T]`` of tokens 0..T-1, and labels, the ids of tokens 1..T:
    this net embeds ids and its head is scored on ids."""
    tokens = np.asarray(tokens, np.int32)
    return (np.ascontiguousarray(tokens[:, :-1]),
            np.ascontiguousarray(tokens[:, 1:]))


def start_params(seed: int, cfg: dict):
    """The seeded start in the program's layout, a layer at a time and
    then what sits outside the layers."""
    key = weights.root_key(seed)
    kinds = weights.layer_kinds(cfg)
    for i, kind in enumerate(kinds):
        yield {str(i + 1): dict(weights.make_layer(
            weights.layer_key(key, i), cfg, kind))}
    yield {"0": {"W": weights.make_end(key, cfg, "E")},
           str(len(kinds) + 1): {
               "norm_w": weights.make_end(key, cfg, "norm_w")}}
