"""The adapter of ``"model": "granite_hybrid"``: IBM's
``granitemoehybrid`` stack (Mamba-2 layers beside grouped-KV attention,
each followed by dropless top-k routing over a chip's share of gated
experts plus a shared expert), the program's ``granite_moe_hybrid_lm``.
Served only: it has no training calls (``common.need`` refuses a
``train_job`` cell of it).

This file and what it keeps beside it (``granite_hybrid_weights.py``,
``granite_hybrid_reference.py``, ``granite_hybrid_flops.py``) are the
only places that read the configuration's model keys.
``benchmark/models/__init__.py`` states the contract.
"""

from __future__ import annotations

from benchmark.models import granite_hybrid_flops as flops  # noqa: F401
from benchmark.models import granite_hybrid_reference as hybrid_reference
from benchmark.models import granite_hybrid_weights as weights

served_gaps = hybrid_reference.served_gaps


def describe(cfg: dict) -> str:
    kinds = weights.layer_kinds(cfg)
    return (f"{len(kinds)} hybrid blocks of width {cfg['hidden_size']} "
            f"({kinds.count('mamba')} Mamba-2 of "
            f"{cfg['mamba_n_heads']}x{cfg['mamba_d_head']}, state "
            f"{cfg['mamba_d_state']}; {kinds.count('attention')} "
            f"attention of {cfg['num_attention_heads']}/"
            f"{cfg['num_key_value_heads']} heads), experts "
            f"{cfg['experts_held']} of {cfg['router_outputs']} held, top "
            f"{cfg['num_experts_per_tok']}, vocabulary "
            f"{cfg['vocab_size']}, {cfg['dtype']}")


def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's zoo net at the configuration's sizes, holding the
    weights ``granite_hybrid_weights.py`` makes from the seed. Resident
    and compute dtype are the configuration's ``dtype`` (no float32
    masters: a served model has none)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import granite_moe_hybrid_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if optimizer is not None:
        raise ValueError("granite_hybrid is served only")
    if cfg["compute_dtype"] != cfg["dtype"]:
        raise ValueError(
            f"the configuration states dtype {cfg['dtype']} and "
            f"compute_dtype {cfg['compute_dtype']}: a served model "
            "holds its weights at the dtype it computes in")
    weights.n_held(cfg)
    conf = granite_moe_hybrid_lm(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=weights.layer_kinds(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        attention_multiplier=cfg["attention_multiplier"],
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        num_local_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        experts_held=cfg["experts_held"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["served_context"],
        dtype=cfg["dtype"], seed=seed & 0x7FFFFFFF)
    kernels = cfg.get("kernels")    # the rehearsal: "interpret"
    if kernels is not None:
        for c in conf.confs:
            if hasattr(c.layer, "use_kernels"):
                c.layer.use_kernels = kernels
    net = MultiLayerNetwork(conf)
    # adopt the seeded weights in place of init()
    net.params = weights.make_params(seed, cfg)
    net.state = {}
    net.updater_state = {str(i): {} for i in range(len(conf.confs))}
    net._initialized = True
    if (net._dtype != jnp.dtype(cfg["dtype"])
            or net._compute_dtype is not None):
        raise ValueError(
            f"the net holds {net._dtype} and computes in "
            f"{net._compute_dtype or net._dtype}, the configuration "
            f"states {cfg['dtype']}")
    return net
