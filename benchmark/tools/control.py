#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers every
limit of ``correct`` is set from: the largest that sound runs of the
program give over the seeds, and the smallest that the control gives.

The control is the plain reference put in the program's place and
computed in float8 (e4m3) operands, the nearest precision below the
bfloat16 the configurations state. For a served model it does not
decode: at each position of the same prompts and served tokens it reads
the gap of the token the lower precision puts first.

    python3 benchmark/tools/control.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 11,12,13 [--seconds 12]

One JSON line per seed goes to ``chiprun_out/control_<cell>.jsonl``.
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

from benchmark import common, traffic  # noqa: E402
from benchmark.common import log  # noqa: E402


def train_seed(model, seed, cfg, mix, with_control, prec):
    from benchmark import train_cell

    opt = cfg["optimizer"]
    n = int(mix["checked_steps"])
    pool = traffic.train_pool(mix, seed, cfg["vocab_size"])
    net = model.build_net(cfg, seed, optimizer=opt)
    feed = train_cell.Feed(pool, model, cfg, 1)
    program = train_cell.first_steps(net, model, feed, seed, cfg, n,
                                     opt["adam_mean_decay"])
    del net, feed
    common.free_device_memory()
    ref = model.train_reference(seed, cfg, opt, pool[:n], "highest")
    limits = cfg["check"]["limits"]
    out = {"seed": seed, "program": {
        k: v["value"] for k, v in
        train_cell.compare(program, ref, limits).items()}}
    if with_control:
        low = model.train_reference(seed, cfg, opt, pool[:n], prec)
        out["control"] = {k: v["value"] for k, v in
                          train_cell.compare(low, ref, limits).items()}
    return out


def serve_seed(model, seed, cfg, mix, with_control, prec, seconds, t0):
    from benchmark import serve_cell, stats

    args = argparse.Namespace(t0=t0, seed=seed, seconds=seconds, trace=0)
    schedule = traffic.serving_schedule(mix, seed, seconds,
                                        cfg["vocab_size"])
    gw = serve_cell.build_gateway(model, cfg, seed)
    with common.stopped_at_exit(gw.close):
        serve_cell.warm_up(gw, cfg, mix, seed)
        records, _, _, _ = serve_cell.measure(gw, schedule, args, mix,
                                              None, None)
    samples = serve_cell.pick_sample(
        records, schedule, seed, int(cfg["check"]["sample_requests"]))
    n = stats.counts(records)
    del gw
    common.free_device_memory()
    prog, low = model.served_gaps(
        seed, cfg, samples, control=prec if with_control else None)
    out = {"seed": seed, "counts": n, "served_tokens": int(prog.size),
           "program": serve_cell.gap_numbers(prog)}
    if low is not None:
        out["control"] = serve_cell.gap_numbers(low)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell, cfg, mix, model = common.find_cell(bench, args.workload,
                                             args.rehearse)
    common.setup_jax(cell, args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    prec = cfg["check"]["control"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"control_{cell['name']}.jsonl")
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        if mix["kind"] == "train_job":
            row = train_seed(model, seed, cfg, mix, seed in ctl, prec)
        else:
            row = serve_seed(model, seed, cfg, mix, seed in ctl, prec,
                             args.seconds, t0)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        log(json.dumps(row))
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
    for name in rows[0]["program"]:
        sound = [r["program"][name] for r in rows]
        low = [r["control"][name] for r in rows if "control" in r]
        log(f"{name}: program largest {max(sound):.6g} over "
            f"{len(sound)} seeds; control smallest "
            f"{min(low) if low else float('nan'):.6g} over {len(low)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
