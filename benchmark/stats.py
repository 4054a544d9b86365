"""Arithmetic from request timelines to the serving metrics.

A request record is what ``loadgen.py`` writes: ``due``, ``sent``,
``in_window``, ``ok`` and ``token_times`` (the client's clock at which
each streamed token arrived, seconds on the shared monotonic clock).
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; ``inf`` values sort last and are returned as such
    when the percentile falls on them."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or math.isinf(vals[hi]):
        return vals[hi] if pos > lo else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def window_requests(records):
    return [r for r in records if r["in_window"]]


def ttft_ms(record) -> float:
    """Due time to first streamed token; a failed request counts as
    beyond any limit."""
    if not record["ok"] or not record["token_times"]:
        return math.inf
    return 1000.0 * (record["token_times"][0] - record["due"])


def ttft_percentile_ms(records, q: float) -> float:
    return percentile([ttft_ms(r) for r in window_requests(records)], q)


def tpot_mean_ms(records) -> float:
    """Token-weighted mean time per output token after the first, over
    the completed requests due in the window: sum of (last - first
    token time) over sum of (tokens - 1)."""
    span = 0.0
    steps = 0
    for r in window_requests(records):
        if r["ok"] and len(r["token_times"]) > 1:
            span += r["token_times"][-1] - r["token_times"][0]
            steps += len(r["token_times"]) - 1
    if steps == 0:
        raise ValueError("no completed request with two tokens or more")
    return 1000.0 * span / steps


def counts(records) -> dict:
    win = window_requests(records)
    return {"attempted": len(win),
            "failed": sum(1 for r in win if not r["ok"])}


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver takes it (``statistics.quantiles``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
