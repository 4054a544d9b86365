"""Operations and bytes the block stack needs, from its shapes.

Counted at the configuration's STATED compute dtype (2 bytes a weight,
2 bytes a cached key or value), each read once per decode step, whatever
the program under test happens to stream today. A program that keeps
float32 copies reads twice these bytes and shows a low share; when it
stops, the share rises towards 100 and cannot pass it.

The embedding and the head are outside the count (the vocabulary is
reduced, see the configuration files), as is recomputation.
"""

from __future__ import annotations

# readers reach both as ``obs["flops"].<name>``; they are every model's
from benchmark.peaks import BYTES_AT, roofline_seconds  # noqa: F401


def block_matmul_params(width: int, ffn: int) -> int:
    """Weights of one block that a token multiplies with: Wq, Wk, Wv,
    Wo (d x d each) and W1, W2 (d x ffn each)."""
    return 4 * width * width + 2 * width * ffn


def block_forward_flops_per_token(width: int, ffn: int) -> int:
    """Matrix products of one block for one token, forward: two
    operations per weight."""
    return 2 * block_matmul_params(width, ffn)


def attention_forward_flops(width: int, n_query: int, n_context: int) -> int:
    """Scores and weighted sum of one block: ``n_query`` queries that
    each see ``n_context`` keys (all heads together: 2*d for q.k and
    2*d for a.v per pair)."""
    return 4 * width * n_query * n_context


def causal_attention_forward_flops(width: int, seq: int) -> int:
    """A whole causal sequence in one block: position t sees t + 1
    keys, so seq*(seq+1)/2 pairs."""
    return 4 * width * (seq * (seq + 1) // 2)


def train_flops_per_token(width: int, ffn: int, n_layers: int,
                          seq: int) -> float:
    """Forward plus backward (twice the forward) per trained token:
    6 * N_block + attention, no recomputation."""
    per_seq = n_layers * (
        seq * block_forward_flops_per_token(width, ffn)
        + causal_attention_forward_flops(width, seq))
    return 3.0 * per_seq / seq


def decode_step_flops(width: int, ffn: int, n_layers: int,
                      contexts) -> int:
    """One decode step of a batch: one new token per live row, row r
    attending over ``contexts[r]`` cached positions."""
    rows = len(contexts)
    return n_layers * (
        rows * block_forward_flops_per_token(width, ffn)
        + attention_forward_flops(width, 1, 1) * sum(contexts))


def decode_step_bytes(width: int, ffn: int, n_layers: int, contexts,
                      compute_dtype: str) -> int:
    """Bytes one decode step has to read: every block weight once, and
    each live row's cached keys and values once."""
    b = BYTES_AT[compute_dtype]
    weights = n_layers * block_matmul_params(width, ffn) * b
    kv = n_layers * 2 * width * b * sum(contexts)
    return weights + kv


def paged_live_bytes(width: int, n_layers: int, block_tokens: int,
                     live_blocks: float, compute_dtype: str) -> float:
    """Bytes the paged-attention kernel has to read in one decode step:
    the keys and values of every live pool block (``block_tokens``
    positions of ``width`` each), once per layer. ``live_blocks`` is
    what one layer's call copies, the engine's ``paged_blocks_live``
    for one dispatch."""
    return (n_layers * live_blocks * block_tokens * 2 * width
            * BYTES_AT[compute_dtype])
