#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer readers are found by
the names in ``BENCHMARK.json``, the model's adapter by the name in the
configuration file. The last line of standard output is the
result, one JSON object; everything else goes on earlier lines. Without
an accelerator (or with fewer chips than the cell asks for) the run
exits 2 and prints no result. ``--rehearse`` is the tiny rehearsal for
debugging the harness on any backend: it swaps in each file's
``rehearsal`` sizes and prints counts only, never a device metric.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUNNERS = {"open_loop": "serve_cell", "closed_loop": "serve_cell",
           "train_job": "train_cell"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints counts only")
    args = ap.parse_args(argv)
    args.t0 = T0

    import importlib

    from benchmark import common

    try:
        bench = common.load_benchmark()
        cell, cfg, mix, model = common.find_cell(bench, args.workload,
                                                 args.rehearse)
        runner = importlib.import_module(
            f"benchmark.{RUNNERS[mix['kind']]}")
        return runner.run(args, bench, cell, cfg, mix, model)
    except common.Refused as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
