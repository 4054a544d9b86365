"""Per-phase latency report for the serving AND training stacks
(ISSUE 7 satellite; training tracks added by ISSUE 8).

Reads either a SAVED Chrome trace (``Tracer.save`` output, a
``GET /v1/trace`` download, or a ``/train/trace`` download) or a LIVE
metrics URL, auto-detects which track families are present, and prints
one latency table:

- **serving rows** — p50/p90/p99 for TTFT, inter-token latency, queue
  wait, round time, and end-to-end (``serving_*`` histogram families /
  ``serving.request_done`` instants).
- **training rows** — p50/p90/p99 for per-step wall (``step``),
  iterator wait (``data_wait``), and host-sync wall (``sync``)
  (``train_*`` histogram families / ``train.step`` span args).

Two sources, same table:

- **Live URL**: a full metrics endpoint
  (``http://host:port/v1/metrics`` or ``http://host:port/train/
  metrics``) is scraped as-is; a BASE url tries the serving gateway's
  ``/v1/metrics`` and the UiServer's ``/train/metrics`` and merges
  whatever answers. Quantiles are bucket-interpolated from the
  Prometheus ``histogram`` families — exactly what a PromQL
  ``histogram_quantile`` would answer.
- **Saved trace** (``trace.json``): exact quantiles from the
  ``serving.request_done`` instants / ``serving.decode_chunk`` spans
  (serving) and from the per-window ``train.step`` spans, whose args
  carry the phase breakdown; a fused K-step window contributes K
  per-step samples (window value / steps, K times).

**Fleet mode** (``--fleet``, ISSUE 10): point it at a
:class:`~deeplearning4j_tpu.serving.ServingRouter` base URL (or a
saved ``/v1/fleet/metrics`` text file) and it reads the FEDERATED
exposition — fleet-wide histogram families (replica families merged
bucket-wise by the router) AND the per-replica
``{replica="<id>"}``-labeled copies — reporting p50/p90/p99
TTFT/ITL/e2e both fleet-wide and per replica, plus the
``replay_gap`` row (``router_replay_gap_s``: stream-break to first
post-replay token — the latency a failover actually added).

Usage::

    python scripts/latency_report.py trace.json
    python scripts/latency_report.py http://127.0.0.1:8000
    python scripts/latency_report.py http://127.0.0.1:9000/train/metrics
    python scripts/latency_report.py --fleet http://127.0.0.1:8800
    python scripts/latency_report.py --fleet --json fleet_metrics.txt
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import urllib.request
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

QUANTILES = (0.5, 0.9, 0.99)

#: histogram-track → table-row label, in print order
LIVE_ROWS = (
    ("serving_ttft_s", "ttft"),
    ("serving_itl_s", "itl"),
    ("serving_queue_wait_s", "queue_wait"),
    ("serving_round_s", "round"),
    ("serving_e2e_s", "e2e"),
)

#: training histogram-track → table-row label (ISSUE 8): auto-detected
#: beside the serving families — a scrape carrying both prints both.
TRAIN_LIVE_ROWS = (
    ("train_step_s", "step"),
    ("train_data_wait_s", "data_wait"),
    ("train_sync_s", "sync"),
)

_BUCKET_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_bucket\{le="([^"]+)"\}\s+(\d+)\s*$')
_SCALAR_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)_(sum|count)\s+(\S+)\s*$")
#: the federated exposition's per-replica samples (ISSUE 10): same
#: families, ``replica`` label first, ``le`` last — exactly as
#: ``Tracer.merge_prometheus`` emits them.
_FLEET_BUCKET_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_bucket\{replica="([^"]*)",'
    r'le="([^"]+)"\}\s+(\d+)\s*$')
_FLEET_SCALAR_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_(sum|count)\{replica="([^"]*)"\}'
    r"\s+(\S+)\s*$")


def parse_prometheus_histograms(
        text: str) -> Dict[str, Dict[str, object]]:
    """Prometheus text → ``{name: {"buckets": [(le, cum)],
    "sum": float, "count": int}}``. Only ``histogram`` families are
    collected; the ``le`` bounds keep text order (the exposition is
    monotone by contract — the histogram-math tests assert it)."""
    hists: Dict[str, Dict[str, object]] = {}

    def entry(name: str) -> Dict[str, object]:
        return hists.setdefault(
            name, {"buckets": [], "sum": 0.0, "count": 0})

    for line in text.splitlines():
        m = _BUCKET_RE.match(line)
        if m:
            name, le, cum = m.group(1), m.group(2), int(m.group(3))
            bound = math.inf if le == "+Inf" else float(le)
            entry(name)["buckets"].append((bound, cum))
            continue
        m = _SCALAR_RE.match(line)
        if m:
            name, kind, value = m.group(1), m.group(2), m.group(3)
            if name in hists:
                entry(name)[kind] = (float(value) if kind == "sum"
                                     else int(value))
    return {n: h for n, h in hists.items() if h["buckets"]}


def histogram_quantile(buckets: List[Tuple[float, int]],
                       q: float) -> float:
    """PromQL-style ``histogram_quantile`` over cumulative
    ``(le, count)`` buckets: linear interpolation inside the winning
    bucket, +Inf clamped to the highest finite bound."""
    total = buckets[-1][1]
    if total == 0:
        return math.nan
    rank = q * total
    prev_bound, prev_cum = 0.0, 0
    for bound, cum in buckets:
        if cum >= rank and cum > prev_cum:
            hi = bound
            if math.isinf(hi):
                hi = prev_bound if prev_bound > 0 else 1.0
            return (prev_bound
                    + (hi - prev_bound)
                    * max(rank - prev_cum, 0.0) / (cum - prev_cum))
        prev_bound, prev_cum = bound, cum
    return prev_bound


def _exact_quantile(values: List[float], q: float) -> float:
    if not values:
        return math.nan
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, round(q * (len(ordered) - 1))))
    return ordered[idx]


def parse_fleet_histograms(
        text: str) -> Dict[str, Dict[str, Dict[str, object]]]:
    """The per-replica half of a federated scrape:
    ``{replica_id: {family: {"buckets": [(le, cum)], "sum", "count"}}}``
    from the ``{replica="<id>", le="..."}``-labeled samples
    ``Tracer.merge_prometheus`` emits next to each merged fleet
    family."""
    out: Dict[str, Dict[str, Dict[str, object]]] = {}

    def entry(rid: str, name: str) -> Dict[str, object]:
        return out.setdefault(rid, {}).setdefault(
            name, {"buckets": [], "sum": 0.0, "count": 0})

    for line in text.splitlines():
        m = _FLEET_BUCKET_RE.match(line)
        if m:
            name, rid, le, cum = m.groups()
            bound = math.inf if le == "+Inf" else float(le)
            entry(rid, name)["buckets"].append((bound, int(cum)))
            continue
        m = _FLEET_SCALAR_RE.match(line)
        if m:
            name, kind, rid, value = m.groups()
            if name in out.get(rid, {}):
                entry(rid, name)[kind] = (
                    float(value) if kind == "sum" else
                    int(float(value)))
    return {rid: {n: h for n, h in fams.items() if h["buckets"]}
            for rid, fams in out.items()}


#: fleet-scope rows: the serving families plus the router's
#: replay-added-latency histogram (ISSUE 10) and the KV transfer
#: plane's rows (ISSUE 14): cross-replica transfer wall, plus the
#: warm-vs-recompute admission split the transfer exists to win
FLEET_ROWS = LIVE_ROWS + (
    ("router_replay_gap_s", "replay_gap"),
    ("serving_kv_transfer_s", "kv_transfer"),
    ("serving_kv_import_s", "kv_import"),
    ("serving_admission_warm_s", "admission_warm"),
    ("serving_admission_cold_s", "admission_cold"),
    # host-loop rows (ISSUE 16): inter-dispatch host wall (the cost
    # fused decode amortizes) + rounds fused per scan dispatch
    ("serving_host_step_s", "host_step"),
    ("serving_fused_rounds", "fused_rounds"),
    # spill-tier rows (ISSUE 17): spill pack wall + tier reload wall
    # — read kv_reload against admission_cold above to price
    # reload-vs-recompute, exactly as admission_warm prices the
    # trie-warm half
    ("serving_kv_spill_s", "kv_spill"),
    ("serving_kv_reload_s", "kv_reload"),
)

#: per-tenant rows (ISSUE 13): the per-request families that carry
#: ``{tenant=...}`` labeled copies on tenancy-enabled engines
#: (round time is per-round, not per-request — no tenant copy)
TENANT_ROWS = (
    ("serving_ttft_s", "ttft"),
    ("serving_itl_s", "itl"),
    ("serving_queue_wait_s", "queue_wait"),
    ("serving_e2e_s", "e2e"),
)

#: ``{tenant="...",le="..."}``-labeled samples: a tenancy-enabled
#: replica's own exposition AND the fleet-level per-tenant merge
#: ``Tracer.merge_prometheus`` emits (the ``{replica=...,tenant=...}``
#: per-replica copies deliberately do NOT match — one tenant table,
#: not one per replica pair)
_TENANT_BUCKET_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_bucket\{tenant="([^"]*)",'
    r'le="([^"]+)"\}\s+(\d+)\s*$')
_TENANT_SCALAR_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_(sum|count)\{tenant="([^"]*)"\}'
    r"\s+(\S+)\s*$")


def parse_tenant_histograms(
        text: str) -> Dict[str, Dict[str, Dict[str, object]]]:
    """The per-tenant half of a scrape: ``{tenant: {family:
    {"buckets": [(le, cum)], "sum", "count"}}}`` from the
    ``{tenant="...", le="..."}``-labeled samples (ISSUE 13)."""
    out: Dict[str, Dict[str, Dict[str, object]]] = {}

    def entry(tid: str, name: str) -> Dict[str, object]:
        return out.setdefault(tid, {}).setdefault(
            name, {"buckets": [], "sum": 0.0, "count": 0})

    for line in text.splitlines():
        m = _TENANT_BUCKET_RE.match(line)
        if m:
            name, tid, le, cum = m.groups()
            bound = math.inf if le == "+Inf" else float(le)
            entry(tid, name)["buckets"].append((bound, int(cum)))
            continue
        m = _TENANT_SCALAR_RE.match(line)
        if m:
            name, kind, tid, value = m.groups()
            if name in out.get(tid, {}):
                entry(tid, name)[kind] = (
                    float(value) if kind == "sum" else
                    int(float(value)))
    return {tid: {n: h for n, h in fams.items() if h["buckets"]}
            for tid, fams in out.items()}


def tenant_report(text: str) -> Dict[str, object]:
    """``--tenant`` rows from one metrics scrape (a replica's
    ``/v1/metrics`` or a router's federated ``/v1/fleet/metrics``):
    one p50/p90/p99 table per tenant."""
    return {"tenants": {
        tid: rows for tid, rows in sorted(
            (tid, _rows_of(fams, TENANT_ROWS))
            for tid, fams in parse_tenant_histograms(text).items())
        if rows}}


def tenant_report_from_events(events) -> Dict[str, object]:
    """``--tenant`` rows from a saved Chrome trace: exact quantiles
    over the ``serving.request_done`` instants, grouped by the
    ``tenant`` arg tenancy-enabled engines stamp (ISSUE 13)."""
    series: Dict[str, Dict[str, List[float]]] = {}
    for event in events:
        if (event.get("ph") != "i"
                or event.get("name") != "serving.request_done"):
            continue
        args = event.get("args") or {}
        tid = args.get("tenant")
        if tid is None:
            continue
        timing = args.get("timing") or {}
        rows = series.setdefault(
            tid, {"ttft": [], "itl": [], "queue_wait": [],
                  "e2e": []})
        if timing.get("ttft_s") is not None:
            rows["ttft"].append(timing["ttft_s"])
        rows["queue_wait"].append(timing.get("queue_wait_s", 0.0))
        if timing.get("e2e_s") is not None:
            rows["e2e"].append(timing["e2e_s"])
        tokens = timing.get("tokens") or 0
        if (tokens > 1 and timing.get("ttft_s") is not None
                and timing.get("e2e_s") is not None):
            rows["itl"].append(
                (timing["e2e_s"] - timing["ttft_s"]) / (tokens - 1))
    out: Dict[str, List[Dict[str, object]]] = {}
    for tid in sorted(series):
        rows = [{
            "phase": label,
            "count": len(series[tid][label]),
            **{f"p{int(q * 100)}_ms":
               1e3 * _exact_quantile(series[tid][label], q)
               for q in QUANTILES},
        } for label in ("ttft", "itl", "queue_wait", "e2e")
            if series[tid][label]]
        if rows:
            out[tid] = rows
    return {"tenants": out}


def _rows_of(hists: Dict[str, Dict[str, object]],
             row_spec) -> List[Dict[str, object]]:
    rows = []
    for track, label in row_spec:
        h = hists.get(track)
        if h is None:
            continue
        rows.append({
            "phase": label,
            "count": h["count"],
            **{f"p{int(q * 100)}_ms":
               1e3 * histogram_quantile(h["buckets"], q)
               for q in QUANTILES},
        })
    return rows


def _admission_comparison(
        hists: Dict[str, Dict[str, object]]
        ) -> Optional[Dict[str, object]]:
    """Warm-import vs recompute admission comparison (ISSUE 14): the
    device-work wall of admissions that reused a cached/imported
    prefix vs those that prefilled from scratch, as p50s plus the
    recompute-over-warm ratio — the number the KV transfer plane
    exists to raise."""
    warm = hists.get("serving_admission_warm_s")
    cold = hists.get("serving_admission_cold_s")
    if not warm or not cold or not warm["count"] or not cold["count"]:
        return None
    warm_p50 = histogram_quantile(warm["buckets"], 0.5)
    cold_p50 = histogram_quantile(cold["buckets"], 0.5)
    return {
        "warm_count": warm["count"],
        "cold_count": cold["count"],
        "warm_admission_p50_ms": 1e3 * warm_p50,
        "recompute_admission_p50_ms": 1e3 * cold_p50,
        "recompute_over_warm_p50": (cold_p50 / warm_p50
                                    if warm_p50 > 0 else math.inf),
    }


def fleet_report(text: str) -> Dict[str, object]:
    """``--fleet`` rows from one federated exposition: the merged
    (unlabeled) families become the ``"fleet"`` table, the
    ``{replica=...}``-labeled copies one table per replica, plus the
    ISSUE 14 warm-vs-recompute admission comparison when both halves
    carry samples."""
    hists = parse_prometheus_histograms(text)
    fleet_rows = _rows_of(hists, FLEET_ROWS)
    replicas = {
        rid: _rows_of(fams, LIVE_ROWS)
        for rid, fams in sorted(parse_fleet_histograms(text).items())}
    return {"fleet": fleet_rows,
            "replicas": {rid: rows for rid, rows in replicas.items()
                         if rows},
            "admission_comparison": _admission_comparison(hists)}


def report_from_metrics_text(text: str) -> List[Dict[str, object]]:
    """Table rows from a metrics scrape (live mode): serving and/or
    training histogram families, whichever the text carries."""
    return _rows_of(parse_prometheus_histograms(text),
                    LIVE_ROWS + TRAIN_LIVE_ROWS)


def report_from_events(events) -> List[Dict[str, object]]:
    """Table rows from a Chrome trace's event list (saved-trace
    mode): exact quantiles over the per-request
    ``serving.request_done`` timing instants + decode-span round
    times (serving), and over the ``train.step`` span args (training —
    a K-step fused window contributes K per-step samples)."""
    series: Dict[str, List[float]] = {
        "ttft": [], "first_delta": [], "gateway_wait": [], "itl": [],
        "queue_wait": [], "round": [], "e2e": []}
    train: Dict[str, List[float]] = {
        "step": [], "data_wait": [], "sync": []}
    for event in events:
        args = event.get("args") or {}
        if (event.get("ph") == "X"
                and event.get("name") == "train.step"):
            steps = max(1, int(args.get("steps") or 1))
            dur_s = float(event.get("dur", 0.0)) * 1e-6
            train["step"].extend([dur_s / steps] * steps)
            train["data_wait"].extend(
                [float(args.get("data_wait_s", 0.0)) / steps] * steps)
            if args.get("sync_s") is not None:
                train["sync"].append(float(args["sync_s"]))
        elif (event.get("ph") == "i"
                and event.get("name") == "serving.request_done"):
            timing = args.get("timing") or {}
            if timing.get("ttft_s") is not None:
                series["ttft"].append(timing["ttft_s"])
            # beside ttft: when the first delta left the engine, and
            # (behind a gateway) the handler's wait for the stepper
            for key in ("first_delta", "gateway_wait"):
                if timing.get(f"{key}_s") is not None:
                    series[key].append(timing[f"{key}_s"])
            series["queue_wait"].append(
                timing.get("queue_wait_s", 0.0))
            if timing.get("e2e_s") is not None:
                series["e2e"].append(timing["e2e_s"])
            tokens = timing.get("tokens") or 0
            if (tokens > 1 and timing.get("ttft_s") is not None
                    and timing.get("e2e_s") is not None):
                series["itl"].append(
                    (timing["e2e_s"] - timing["ttft_s"])
                    / (tokens - 1))
        elif (event.get("ph") == "X"
                and event.get("name") == "serving.decode_chunk"):
            series["round"].append(event.get("dur", 0.0) * 1e-6)
    rows = [{
        "phase": label,
        "count": len(series[label]),
        **{f"p{int(q * 100)}_ms":
           1e3 * _exact_quantile(series[label], q)
           for q in QUANTILES},
    } for label in ("ttft", "first_delta", "gateway_wait", "itl",
                    "queue_wait", "round", "e2e")
        if series[label]]
    rows.extend({
        "phase": label,
        "count": len(train[label]),
        **{f"p{int(q * 100)}_ms":
           1e3 * _exact_quantile(train[label], q)
           for q in QUANTILES},
    } for label in ("step", "data_wait", "sync") if train[label])
    return rows


def render(rows: List[Dict[str, object]], source: str) -> str:
    lines = [f"latency report — {source}",
             f"{'phase':<12} {'count':>7} "
             + " ".join(f"{'p%d' % int(q * 100) + ' (ms)':>12}"
                        for q in QUANTILES)]
    for row in rows:
        cells = " ".join(
            f"{row[f'p{int(q * 100)}_ms']:>12.3f}"
            for q in QUANTILES)
        lines.append(f"{row['phase']:<12} {row['count']:>7} {cells}")
    return "\n".join(lines)


def _scrape(url: str) -> str:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode("utf-8", "replace")


def run_report(source: str) -> List[Dict[str, object]]:
    """Rows for one source: a live URL (a full metrics endpoint, or a
    base URL probed for the serving gateway's ``/v1/metrics`` and the
    UiServer's ``/train/metrics``) or a trace-file path."""
    if source.startswith(("http://", "https://")):
        base = source.rstrip("/")
        if base.endswith("/metrics"):
            return report_from_metrics_text(_scrape(base))
        texts, errors = [], []
        for path in ("/v1/metrics", "/train/metrics"):
            try:
                texts.append(_scrape(base + path))
            except Exception as e:  # probe: either endpoint may 404
                errors.append(f"{path}: {e}")
        if not texts:
            raise RuntimeError(
                f"no metrics endpoint answered at {base} "
                f"({'; '.join(errors)})")
        return report_from_metrics_text("\n".join(texts))
    with open(source) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) \
        else doc
    return report_from_events(events)


def run_tenant_report(source: str) -> Dict[str, object]:
    """``--tenant`` rows for one source: a router/replica base URL
    (the federated ``/v1/fleet/metrics`` is probed first, then the
    gateway's ``/v1/metrics``), a full metrics URL, a saved metrics
    text, or a saved Chrome trace (grouped ``serving.request_done``
    instants)."""
    if source.startswith(("http://", "https://")):
        base = source.rstrip("/")
        if base.endswith("/metrics"):
            return tenant_report(_scrape(base))
        errors = []
        for path in ("/v1/fleet/metrics", "/v1/metrics"):
            try:
                return tenant_report(_scrape(base + path))
            except Exception as e:  # probe: either may 404
                errors.append(f"{path}: {e}")
        raise RuntimeError(
            f"no metrics endpoint answered at {base} "
            f"({'; '.join(errors)})")
    with open(source) as f:
        raw = f.read()
    try:
        doc = json.loads(raw)
    except ValueError:
        return tenant_report(raw)  # saved metrics text
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) \
        else doc
    return tenant_report_from_events(events)


def run_fleet_report(source: str) -> Dict[str, object]:
    """``--fleet`` rows for one source: a router base URL (scraped at
    ``/v1/fleet/metrics``), a full federated-metrics URL, or a saved
    federated exposition text file."""
    if source.startswith(("http://", "https://")):
        base = source.rstrip("/")
        if not base.endswith("/metrics"):
            base = base + "/v1/fleet/metrics"
        return fleet_report(_scrape(base))
    with open(source) as f:
        return fleet_report(f.read())


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("source",
                    help="saved Chrome trace path, or gateway base "
                         "URL (http://host:port); with --fleet, a "
                         "router base URL or saved federated-metrics "
                         "text")
    ap.add_argument("--json", action="store_true",
                    help="emit the rows as JSON instead of a table")
    ap.add_argument("--fleet", action="store_true",
                    help="federated mode (ISSUE 10): read a router's "
                         "/v1/fleet/metrics and report fleet-wide "
                         "AND per-replica quantiles, plus the "
                         "replay-gap row")
    ap.add_argument("--tenant", action="store_true",
                    help="per-tenant mode (ISSUE 13): one "
                         "TTFT/ITL/queue-wait/e2e table per tenant "
                         "from the {tenant=...}-labeled families "
                         "(live scrape, saved federated text, or a "
                         "saved trace's request_done instants); "
                         "--json emits {\"tenants\": {tid: rows}}")
    args = ap.parse_args(argv)
    if args.tenant:
        report = run_tenant_report(args.source)
        if not report["tenants"]:
            print(f"no per-tenant latency data found in "
                  f"{args.source}", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(report))
        else:
            first = True
            for tid, rows in report["tenants"].items():
                if not first:
                    print()
                first = False
                print(render(rows,
                             f"{args.source} (tenant {tid})"))
        return 0
    if args.fleet:
        report = run_fleet_report(args.source)
        if not report["fleet"] and not report["replicas"]:
            print(f"no fleet latency data found in {args.source}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(report))
        else:
            print(render(report["fleet"],
                         f"{args.source} (fleet-wide)"))
            comp = report.get("admission_comparison")
            if comp:
                print()
                print(f"admission: warm p50 "
                      f"{comp['warm_admission_p50_ms']:.1f}ms "
                      f"({comp['warm_count']}) vs recompute p50 "
                      f"{comp['recompute_admission_p50_ms']:.1f}ms "
                      f"({comp['cold_count']}) — recompute/warm "
                      f"{comp['recompute_over_warm_p50']:.2f}x")
            for rid, rows in report["replicas"].items():
                print()
                print(render(rows, f"replica {rid}"))
        return 0
    rows = run_report(args.source)
    if not rows:
        print("no serving or training latency data found in "
              f"{args.source}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(rows))
    else:
        print(render(rows, args.source))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
