"""Operations and bytes the hybrid stack needs, from its shapes.

Counted at the configuration's STATED dtype (2 bytes a weight, a cached
key or value; the recurrent state at the 4 bytes the configuration
states under ``assumed``), for what one decode step has to touch: the
weights outside the experts once, the weights of each held expert that
some live row picked once, each live row's recurrent state read and
written, the live keys and values, the held slice of the tied head.
The embedding's gather (one row a token) is left out.
"""

from __future__ import annotations

# readers reach both as ``obs["flops"].<name>``; they are every model's
from benchmark.peaks import BYTES_AT, roofline_seconds  # noqa: F401

#: the recurrent state is carried in float32 (the configuration's
#: ``assumed.ssm_state``)
STATE_BYTES = 4


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def expert_params(cfg: dict) -> int:
    """One routed expert: ``W_in`` (d x 2f) and ``W_out`` (f x d)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "attention":
        kv = (cfg["num_key_value_heads"] * d
              // cfg["num_attention_heads"])
        return 2 * d * d + 2 * d * kv
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d * (2 * di + 2 * gn + cfg["mamba_n_heads"]) + di * d


def shared_params(cfg: dict) -> int:
    """What every token multiplies with after the mixer, outside the
    routed experts: the shared expert and the router."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_intermediate_size"] + d * cfg[
        "router_outputs"]


def nonexpert_params(cfg: dict) -> int:
    """Matrix weights outside the routed experts, all layers."""
    return sum(mixer_params(cfg, k) + shared_params(cfg)
               for k in kinds(cfg))


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def state_numbers(cfg: dict) -> int:
    """One row's SSM state in one Mamba-2 layer."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"])


def conv_tail_numbers(cfg: dict) -> int:
    conv = (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])
    return (cfg["mamba_d_conv"] - 1) * conv


def kv_numbers_per_token(cfg: dict) -> int:
    """Keys and values one cached position holds in one attention
    layer."""
    return (2 * cfg["num_key_value_heads"] * cfg["hidden_size"]
            // cfg["num_attention_heads"])


# ---------------------------------------------------------------------
# the three kernels' own counts
# ---------------------------------------------------------------------
def grouped_bytes(cfg: dict, touched: float) -> float:
    """The grouped products of ``touched`` (expert, layer, step)
    visits: each touched expert's two matrices once."""
    return touched * expert_params(cfg) * BYTES_AT[cfg["dtype"]]


def grouped_flops(cfg: dict, picks_held: float) -> float:
    """Two operations a weight for every (row, pick) pair that fell on
    a held expert."""
    return 2.0 * picks_held * expert_params(cfg)


def ssm_step_bytes(cfg: dict, state_rows: float) -> float:
    """``state_rows`` (live row, Mamba layer, step) updates: the state
    read and written."""
    return 2.0 * state_rows * state_numbers(cfg) * STATE_BYTES


def ssm_step_flops(cfg: dict, state_rows: float) -> float:
    """A state entry's update and its part of the output: decay
    multiply, input multiply-add, output multiply-add."""
    return 5.0 * state_rows * state_numbers(cfg)


# ---------------------------------------------------------------------
# one decode dispatch
# ---------------------------------------------------------------------
def decode_round(cfg: dict, steps: int, live_rows: float,
                 context_tokens: float, touched: float,
                 picks_held: float, state_rows: float):
    """(operations, bytes) of ``steps`` decode steps: ``live_rows``
    rows summed over the steps, ``context_tokens`` cached positions
    read summed over rows and steps, and the program's own counts of
    touched experts, held picks and state rows over the dispatch."""
    b = BYTES_AT[cfg["dtype"]]
    ks = kinds(cfg)
    n_attn = ks.count("attention")
    once = nonexpert_params(cfg) + head_params(cfg)
    nbytes = (steps * once * b + grouped_bytes(cfg, touched)
              + ssm_step_bytes(cfg, state_rows)
              + 2.0 * state_rows * conv_tail_numbers(cfg) * b
              + n_attn * context_tokens * kv_numbers_per_token(cfg) * b)
    nflops = (2.0 * live_rows * once + grouped_flops(cfg, picks_held)
              + ssm_step_flops(cfg, state_rows)
              + n_attn * 2.0 * context_tokens
              * kv_numbers_per_token(cfg)
              * (cfg["num_attention_heads"]
                 // cfg["num_key_value_heads"]))
    return nflops, nbytes
