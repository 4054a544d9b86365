"""The adapter of ``"model": "afmoe"``: Arcee's ``afmoe`` stack
(Trinity: sliding-window attention layers with rotary positions beside
full-attention layers without, QK-norm, gated attention, four sandwich
norms a layer, leading dense layers, then sigmoid top-k routing over a
chip's share of experts plus a shared expert, an untied head), the
program's ``afmoe_lm``. Served only: it has no training calls
(``common.need`` refuses a ``train_job`` cell of it).

This file and what it keeps beside it (``afmoe_weights.py``,
``afmoe_reference.py``, ``afmoe_flops.py``) are the only places that
read the configuration's model keys. ``benchmark/models/__init__.py``
states the contract.
"""

from __future__ import annotations

from benchmark.models import afmoe_flops as flops  # noqa: F401
from benchmark.models import afmoe_reference
from benchmark.models import afmoe_weights as weights

served_gaps = afmoe_reference.served_gaps


def describe(cfg: dict) -> str:
    kinds = weights.layer_kinds(cfg)
    attn = [a for a, _ in kinds]
    return (f"{len(kinds)} afmoe blocks (published layers "
            f"{weights.layers_held(cfg)}) of width {cfg['hidden_size']}: "
            f"{attn.count('sliding')} sliding (window "
            f"{cfg['sliding_window']}, rotary) and {attn.count('full')} "
            f"full attention of {cfg['num_attention_heads']}/"
            f"{cfg['num_key_value_heads']} heads of {cfg['head_dim']}, "
            f"{[f for _, f in kinds].count('dense')} dense feed-forward of "
            f"{cfg['intermediate_size']}, experts {cfg['experts_held']} of "
            f"{cfg['router_outputs']} held, top "
            f"{cfg['num_experts_per_tok']} by sigmoid score, vocabulary "
            f"{cfg['vocab_size']}, context {cfg['served_context']}, "
            f"{cfg['dtype']}")


def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's zoo net at the configuration's sizes, holding the
    weights ``afmoe_weights.py`` makes from the seed. Resident and
    compute dtype are the configuration's ``dtype`` (no float32
    masters: a served model has none)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import afmoe_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if optimizer is not None:
        raise ValueError("afmoe is served only")
    if cfg["compute_dtype"] != cfg["dtype"]:
        raise ValueError(
            f"the configuration states dtype {cfg['dtype']} and "
            f"compute_dtype {cfg['compute_dtype']}: a served model "
            "holds its weights at the dtype it computes in")
    weights.n_held(cfg)
    conf = afmoe_lm(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"], layers=weights.layers_held(cfg),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_theta=cfg["rope_theta"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], experts_held=cfg["experts_held"],
        mup_enabled=cfg["mup_enabled"], rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["served_context"],
        initializer_range=cfg["initializer_range"],
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
