"""The adapter of ``"model": "evabyte"``: the ``evabyte`` stack (EVA
attention: exact keys inside an aligned window, one learned summary a
chunk of every earlier window, one softmax over both; SwiGLU;
unit-offset RMSNorm; the residual sum in float32; a head of
``num_pred_heads x vocab_size`` rows of which head 0 is served), the
program's ``evabyte_lm``. Served only: it has no training calls
(``common.need`` refuses a ``train_job`` cell of it).

This file and what it keeps beside it (``evabyte_weights.py``,
``evabyte_reference.py``, ``evabyte_flops.py``) are the only places
that read the configuration's model keys.
``benchmark/models/__init__.py`` states the contract.
"""

from __future__ import annotations

from benchmark.models import evabyte_flops as flops  # noqa: F401
from benchmark.models import evabyte_reference
from benchmark.models import evabyte_weights as weights

served_gaps = evabyte_reference.served_gaps


def describe(cfg: dict) -> str:
    return (f"{cfg['num_hidden_layers']} evabyte blocks (published "
            f"layers {cfg['layers_held']}) of width {cfg['hidden_size']}: "
            f"EVA attention of {cfg['num_attention_heads']} heads of "
            f"{weights.head_dim(cfg)}, window {cfg['window_size']}, "
            f"chunk {cfg['chunk_size']}, SwiGLU of "
            f"{cfg['intermediate_size']}, {cfg['num_pred_heads']} heads "
            f"over {cfg['vocab_size']} bytes (head 0 served), context "
            f"{cfg['served_context']}, {cfg['dtype']}")


def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's zoo net at the configuration's sizes, holding the
    weights ``evabyte_weights.py`` makes from the seed. Resident and
    compute dtype are the configuration's ``dtype`` (no float32
    masters: a served model has none); the residual stream between the
    blocks is float32 whatever that is (``fp32_skip_add``)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import evabyte_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if optimizer is not None:
        raise ValueError("evabyte is served only")
    if cfg["compute_dtype"] != cfg["dtype"]:
        raise ValueError(
            f"the configuration states dtype {cfg['dtype']} and "
            f"compute_dtype {cfg['compute_dtype']}: a served model "
            "holds its weights at the dtype it computes in")
    if len(cfg["layers_held"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layers_held {cfg['layers_held']} does not name "
            f"num_hidden_layers = {cfg['num_hidden_layers']} layers")
    conf = evabyte_lm(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        head_dim=weights.head_dim(cfg),
        intermediate_size=cfg["intermediate_size"],
        window_size=cfg["window_size"], chunk_size=cfg["chunk_size"],
        num_pred_heads=cfg["num_pred_heads"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["served_context"],
        norm_add_unit_offset=cfg["norm_add_unit_offset"],
        fp32_skip_add=cfg["fp32_skip_add"],
        initializer_range=cfg["init_std"], dtype=cfg["dtype"],
        seed=seed & 0x7FFFFFFF)
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
