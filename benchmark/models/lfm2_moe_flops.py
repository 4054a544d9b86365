"""Operations and bytes the ``lfm2_moe`` stack needs to TRAIN, from its
shapes.

A step's operations are three times the forward pass's (the backward
pass is two products a forward product); no recomputed operation is
counted, whatever the program recomputes. The forward pass of a token:
two operations a matrix weight it multiplies with (the mixers, the
dense feed-forward, the routers, the tied head over the held slice of
the vocabulary), the attention layers' scores and weighted values at
the causal mean context, and the routed experts AT THEIR EXPECTATION: a
token makes ``num_experts_per_tok`` picks over ``router_outputs``
experts of which ``num_experts`` are held here, so ``top_k x held /
outputs`` picks a token fall on a held expert (1 at the published
sizes; the program's own ``moe_picks_held`` says how far a step lies
from it, and a training cell's readers are handed no program counters).
The convolution's taps, the norms, the rotation and the embedding's
gather are left out (under 0.1%).

The grouped product's own counts are at the configuration's STATED
compute dtype (2 bytes a number).
"""

from __future__ import annotations

# readers reach both as ``obs["flops"].<name>``; they are every model's
from benchmark.peaks import BYTES_AT, roofline_seconds  # noqa: F401

#: the grouped product in the device trace, by the label of
#: ``benchmark/xplane.py`` (HLO name without its number, then the
#: custom-call target): the library's forward kernel, which also
#: computes the rows' cotangents on the transposed weights, and its
#: transposed kernel, which computes the weights' cotangents
GROUPED_CALLS = ("gmm_tpu_custom_call", "tgmm_tpu_custom_call")


def grouped_call_seconds(ops: dict) -> float:
    """Device seconds of the grouped-product calls among a trace's
    operations (``{label: seconds}``); 0 where it holds none."""
    return sum(ops.get(name, 0.0) for name in GROUPED_CALLS)


def kinds(cfg: dict) -> list:
    """``(mixer, feed-forward)`` of each layer held (as
    ``lfm2_moe_weights.layer_kinds``: this file reads shapes only)."""
    return [("conv" if cfg["layer_types"][i] == "conv" else "attention",
             "dense" if i < cfg["num_dense_layers"] else "experts")
            for i in cfg["layers_held"]]


def expert_params(cfg: dict) -> int:
    """One routed expert: ``W_in`` (d x 2f) and ``W_out`` (f x d)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_params(cfg: dict, mixer: str) -> int:
    d = cfg["hidden_size"]
    if mixer == "conv":
        return 4 * d * d                      # W_in (d x 3d), W_out
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return 2 * d * d + 2 * d * kv             # Wq, Wo; Wk, Wv


def held_picks_per_token(cfg: dict) -> float:
    """The expected picks of a token that fall on a held expert."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_outputs"])


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    d = cfg["hidden_size"]
    total = 2.0 * cfg["vocab_size"] * d                    # the head
    for mixer, ffn in kinds(cfg):
        total += 2.0 * mixer_params(cfg, mixer)
        if mixer == "attention":
            # position t sees t + 1 keys: 2 d for q.k and 2 d for a.v
            # a pair, all heads together
            total += 4.0 * d * (seq + 1) / 2.0
        if ffn == "dense":
            total += 2.0 * 3 * d * cfg["intermediate_size"]
        else:
            total += 2.0 * d * cfg["router_outputs"]
            total += 2.0 * expert_params(cfg) * held_picks_per_token(cfg)
    return total


def train_flops_per_step(cfg: dict, mix: dict) -> float:
    """Forward plus backward of one step of the traffic's batch."""
    tokens = mix["batch"] * mix["seq_len"]
    return 3.0 * tokens * forward_flops_per_token(cfg, mix["seq_len"])


def expert_layers(cfg: dict) -> int:
    return [f for _, f in kinds(cfg)].count("experts")


def held_pairs_per_step(cfg: dict, mix: dict) -> float:
    """(token, pick) pairs on held experts in one expert layer of one
    step, at their expectation."""
    return mix["batch"] * mix["seq_len"] * held_picks_per_token(cfg)


def grouped_train_flops(cfg: dict, pairs: float) -> float:
    """One expert layer, one step: the pairs' three products forward
    (``W_in`` is two of them side by side) and the two transposes of
    each: the rows' cotangents and the weights'."""
    return 3.0 * 2.0 * pairs * expert_params(cfg)


def grouped_train_bytes(cfg: dict, pairs: float) -> float:
    """What those six calls have to move at the stated dtype: each held
    expert's matrices read forward and read again for the rows'
    cotangents, their cotangents written, and each call's rows in and
    out (a pair's row is ``d`` wide at the layer's edge, ``2 f`` after
    the first product, ``f`` into the second)."""
    b = BYTES_AT[cfg["compute_dtype"]]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * cfg["num_experts"] * expert_params(cfg)
    rows = pairs * (
        (d + 2 * f) + (f + d)            # forward: in and out, twice
        + (2 * f + d) + (d + f)          # the rows' cotangents
        + (d + 2 * f) + (f + d))         # the weights': both operands
    return (weights + rows) * b
