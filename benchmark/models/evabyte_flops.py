"""Operations and bytes the ``evabyte`` stack needs, from its shapes.

Counted at the configuration's STATED dtype (2 bytes a weight, a cached
key, value or summary), for what a dispatch has to touch: every layer's
weights and head 0's rows once a step (or once an admission chunk), the
exact keys and values of each live row's ALIGNED window and the summary
entries of every window before it once a layer, and the summary
writer's ``chunk_size`` keys and values in and one entry out for every
chunk completed. The embedding's gather (one row a token) and the seven
prediction heads that are held and never served are left out.
"""

from __future__ import annotations

# readers reach both as ``obs["flops"].<name>``; they are every model's
from benchmark.peaks import BYTES_AT, roofline_seconds  # noqa: F401


def kind_windows(cfg: dict) -> dict:
    """A kind of cache's window: what the engine names its counters by
    (``kv_blocks_held_w<window>``). The summaries are never released
    (their window is the served context); the exact keys are, a whole
    aligned window at a time."""
    return {"summary": cfg["served_context"],
            "window": cfg["window_size"]}


def layers_of(cfg: dict, kind: str) -> int:
    """Every layer holds both kinds."""
    return cfg["num_hidden_layers"]


def layer_params(cfg: dict) -> int:
    """``Wq Wk Wv Wo`` and the SwiGLU's three matrices."""
    d = cfg["hidden_size"]
    return 4 * d * d + 3 * d * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    """Head 0, the next byte's: the rows a step reads."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def step_params(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg)


def kv_numbers_per_entry(cfg: dict) -> int:
    """Numbers one cached position, or one summary entry, holds in one
    layer: a key's and a value's."""
    return 2 * cfg["hidden_size"]


# ---------------------------------------------------------------------
# the attention's own counts (the paged kernel's two calls a layer)
# ---------------------------------------------------------------------
def paged_bytes(cfg: dict, entries: float) -> float:
    """``entries`` cache entries (exact keys or summaries) one layer's
    attention reads, over all layers."""
    return (cfg["num_hidden_layers"] * entries * kv_numbers_per_entry(cfg)
            * BYTES_AT[cfg["dtype"]])


def paged_flops(cfg: dict, pairs: float) -> float:
    """Scores and weighted values of ``pairs`` (query, entry) pairs one
    layer scores: two operations each a key and a value number."""
    return (2.0 * cfg["num_hidden_layers"] * pairs
            * kv_numbers_per_entry(cfg))


def writer_bytes(cfg: dict, written: float) -> float:
    """The summary writer: ``written`` entries over all layers, each
    ``chunk_size`` keys and values in and one entry out."""
    return (written * (cfg["chunk_size"] + 1) * kv_numbers_per_entry(cfg)
            * BYTES_AT[cfg["dtype"]])


# ---------------------------------------------------------------------
# one dispatch
# ---------------------------------------------------------------------
def decode_round(cfg: dict, steps: int, live_rows: float, entries: float,
                 written: float):
    """(operations, bytes) of ``steps`` decode steps: ``live_rows`` rows
    summed over the steps, ``entries`` the cache entries (exact keys and
    summaries) one layer reads summed over rows and steps, ``written``
    the summary entries written over all layers."""
    b = BYTES_AT[cfg["dtype"]]
    nbytes = (steps * step_params(cfg) * b + paged_bytes(cfg, entries)
              + writer_bytes(cfg, written))
    nflops = 2.0 * live_rows * step_params(cfg) + paged_flops(cfg, entries)
    return nflops, nbytes


def admit_chunk(cfg: dict, tokens: float, entries: float, pairs: float,
                written: float):
    """(operations, bytes) of one admission chunk of ``tokens`` prompt
    positions: the layers' weights once and head 0 at one position,
    ``entries`` cache entries read a layer, ``pairs`` (query, entry)
    pairs scored a layer, ``written`` summary entries over all
    layers."""
    b = BYTES_AT[cfg["dtype"]]
    body = cfg["num_hidden_layers"] * layer_params(cfg)
    nbytes = ((body + head_params(cfg)) * b + paged_bytes(cfg, entries)
              + writer_bytes(cfg, written))
    nflops = (2.0 * tokens * body + 2.0 * head_params(cfg)
              + paged_flops(cfg, pairs))
    return nflops, nbytes
