#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the same mix
offered at several rates against one engine, a short window each.

    python3 benchmark/tools/sweep.py --workload <cell> \
        --rates 1,1.5,2,2.5,3 --seconds 25 --seed 5

A rate is sustained when the backlog does not grow through the window:
time to first token in the window's last third stays near the first
third's, and the output tokens completed follow the tokens offered.
One JSON line per rate goes to ``chiprun_out/sweep_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, stats, traffic  # noqa: E402
from benchmark.common import log  # noqa: E402


def summarize(records, probe, before, after, seconds, cfg):
    win = stats.window_requests(records)
    ok = [r for r in win if r["ok"]]
    t0 = min(r["due"] for r in win)
    thirds = [[], [], []]
    for r in win:
        thirds[min(int(3 * (r["due"] - t0) / seconds), 2)].append(
            stats.ttft_ms(r))
    rounds = [r for r in probe.rounds
              if probe.window_from <= r["t"] <= probe.window_to]
    done_in = sum(sum(1 for t in r["token_times"]
                      if probe.window_from <= t <= probe.window_to)
                  for r in records)
    n_rounds = max(after["chunks"] - before["chunks"], 1)
    return {
        "attempted": len(win), "failed": len(win) - len(ok),
        "tokens_per_s_streamed": done_in / seconds,
        "tpot_mean_ms": stats.tpot_mean_ms(records) if ok else None,
        "ttft_p50_ms": stats.ttft_percentile_ms(records, 50),
        "ttft_p90_ms": stats.ttft_percentile_ms(records, 90),
        "ttft_p50_by_third_ms": [stats.percentile(t, 50) if t else None
                                 for t in thirds],
        "queue_wait_p90_ms": stats.percentile(
            [1000 * r["timing"]["queue_wait_s"] for r in ok], 90)
        if ok else None,
        "occupancy": 100.0 * (after["occupancy_sum"]
                              - before["occupancy_sum"]) / n_rounds,
        "active_max": max((r["active"] for r in rounds), default=0),
        "kv_live_share_max": 100.0 * max(
            (r["live_blocks"] for r in rounds), default=0)
        / cfg["deployment"]["kv_blocks"],
        "preempted": after["preempted"] - before["preempted"],
        "admit_deferred": after["paged_admit_deferred"]
        - before["paged_admit_deferred"],
        "late_max_ms": max(1000 * (r["sent"] - r["due"]) for r in records),
    }


def main(argv=None) -> int:
    from benchmark import serve_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.t0, args.trace = time.perf_counter(), 0
    bench = common.load_benchmark()
    cell, cfg, mix, model = common.find_cell(bench, args.workload,
                                             args.rehearse)
    common.setup_jax(cell, args.rehearse)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"sweep_{cell['name']}.jsonl")
    gw = serve_cell.build_gateway(model, cfg, args.seed)
    with common.stopped_at_exit(gw.close):
        serve_cell.warm_up(gw, cfg, mix, args.seed)
        probe = serve_cell.RoundProbe(gw.engine)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            schedule = traffic.serving_schedule(
                dict(mix, rate_per_s=rate), args.seed + i, args.seconds,
                cfg["vocab_size"])
            probe.rounds.clear()
            records, before, after, _ = serve_cell.measure(
                gw, schedule, args, mix, probe, None)
            row = dict(rate_per_s=rate,
                       offered=traffic.offered(schedule),
                       **summarize(records, probe, before, after,
                                   args.seconds, cfg))
            log(json.dumps(row))
            with open(path, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
