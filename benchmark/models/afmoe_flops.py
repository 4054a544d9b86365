"""Operations and bytes the ``afmoe`` stack needs, from its shapes.

Counted at the configuration's STATED dtype (2 bytes a weight, a cached
key or value), for what one decode step has to touch: the weights
outside the routed experts once (attention with its gate, the dense
feed-forwards, shared experts, routers), the weights of each held
expert that some live row picked once, the live keys and values of each
layer KIND (a sliding layer reads at most its window of a context, a
full layer all of it), the held slice of the untied head. The
embedding's gather (one row a token) is left out.
"""

from __future__ import annotations

# readers reach both as ``obs["flops"].<name>``; they are every model's
from benchmark.peaks import BYTES_AT, roofline_seconds  # noqa: F401


def kinds(cfg: dict) -> list:
    """``(attention, feed-forward)`` of each layer held (as
    ``afmoe_weights.layer_kinds``: this file reads shapes only)."""
    return [(cfg["layer_types"][i].split("_")[0],
             "dense" if i < cfg["num_dense_layers"] else "experts")
            for i in cfg["layers_held"]]


def layers_of(cfg: dict, attention: str) -> int:
    return [a for a, _ in kinds(cfg)].count(attention)


def kind_windows(cfg: dict) -> dict:
    """A layer kind's window: what the engine names its counters by
    (``paged_blocks_live_w<window>``)."""
    return {"sliding": cfg["sliding_window"],
            "full": cfg["served_context"]}


def expert_params(cfg: dict) -> int:
    """One routed expert: ``W_in`` (d x 2f) and ``W_out`` (f x d)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_params(cfg: dict) -> int:
    """``Wq``, ``Wo`` and the output gate's ``Wg`` (d x heads x
    head_dim each), ``Wk`` and ``Wv`` (d x KV heads x head_dim)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return d * dh * (3 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])


def feed_params(cfg: dict, ffn: str) -> int:
    """What every token multiplies with after attention, outside the
    routed experts: a dense layer's feed-forward, or the shared experts
    and the router."""
    d = cfg["hidden_size"]
    if ffn == "dense":
        return 3 * d * cfg["intermediate_size"]
    return (cfg["num_shared_experts"] * expert_params(cfg)
            + d * cfg["router_outputs"])


def nonexpert_params(cfg: dict) -> int:
    """Matrix weights outside the routed experts, all layers."""
    return sum(attention_params(cfg) + feed_params(cfg, ffn)
               for _, ffn in kinds(cfg))


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def kv_numbers_per_token(cfg: dict) -> int:
    """Keys and values one cached position holds in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


# ---------------------------------------------------------------------
# the two kernels' own counts
# ---------------------------------------------------------------------
def grouped_bytes(cfg: dict, touched: float) -> float:
    """The grouped products of ``touched`` (expert, layer, step)
    visits: each touched expert's two matrices once."""
    return touched * expert_params(cfg) * BYTES_AT[cfg["dtype"]]


def grouped_flops(cfg: dict, picks_held: float) -> float:
    """Two operations a weight for every (row, pick) pair that fell on
    a held expert."""
    return 2.0 * picks_held * expert_params(cfg)


def paged_live_bytes(cfg: dict, block_tokens: int, live: dict) -> float:
    """Keys and values of the live pool blocks, whole blocks:
    ``live[kind]`` pool blocks one layer's kernel call of the kind
    copies, times the kind's layers."""
    return sum(layers_of(cfg, kind) * blocks * block_tokens
               * kv_numbers_per_token(cfg) * BYTES_AT[cfg["dtype"]]
               for kind, blocks in live.items())


def paged_flops(cfg: dict, block_tokens: int, live: dict) -> float:
    """Scores and weighted values: two operations each a key or value
    number and query head of its KV head's group."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return sum(2.0 * group * layers_of(cfg, kind) * blocks * block_tokens
               * kv_numbers_per_token(cfg)
               for kind, blocks in live.items())


# ---------------------------------------------------------------------
# one decode dispatch
# ---------------------------------------------------------------------
def decode_round(cfg: dict, steps: int, live_rows: float,
                 context_tokens: dict, touched: float,
                 picks_held: float):
    """(operations, bytes) of ``steps`` decode steps: ``live_rows``
    rows summed over the steps, ``context_tokens[kind]`` the cached
    positions one layer of the kind reads summed over rows and steps (a
    sliding layer at most its window of each), and the program's own
    counts of touched experts and held picks over the dispatch."""
    b = BYTES_AT[cfg["dtype"]]
    once = nonexpert_params(cfg) + head_params(cfg)
    keys = sum(layers_of(cfg, kind) * n * kv_numbers_per_token(cfg)
               for kind, n in context_tokens.items())
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    nbytes = steps * once * b + grouped_bytes(cfg, touched) + keys * b
    nflops = (2.0 * live_rows * once + grouped_flops(cfg, picks_held)
              + 2.0 * group * keys)
    return nflops, nbytes
