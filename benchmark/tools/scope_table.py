#!/usr/bin/env python3
"""The device's busy time by the program's own layers, from the trace a
traced run of a cell left in this checkout:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 51 --trace 1
    python3 benchmark/tools/scope_table.py --workload <cell>

logs ``benchmark/opscopes.py``'s table (``[bench] device scopes: ...``)
and prints one JSON line with the cell's scope metrics, read by the
readers ``benchmark/metrics/scope_*.py`` / ``train_scope_*.py`` that
``benchmark/scope_metrics.json`` lists for it. Those fourteen entries
are not in ``BENCHMARK.json`` yet (``PERF.md`` section 7 says which test
pins that list's tail), so ``run.py`` itself does not report them: this
tool is how they are read until a ``benchmark`` PR lists them. It needs
no chip, only the trace file (and ``--device-kind`` for the peaks the
table prints beside the achieved rates)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import common, peaks, traffic, xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--device-kind", default="TPU v5 lite")
    args = ap.parse_args()
    bench = common.load_benchmark()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    path = xplane.find_trace(common.trace_dir(cell["name"]))
    if path is None:
        raise SystemExit(f"no trace under {common.trace_dir(cell['name'])}: "
                         "make a traced run of the cell first")
    with open(os.path.join(common.HERE, "scope_metrics.json")) as f:
        listed = [m for m in json.load(f)["per_layer"]
                  if cell["name"] in m["workloads"]]
    # what a reader looks at of a run: the cell's kind and its trace
    obs = {"kind": traffic.load(cell["traffic"])["kind"],
           "cell": cell["name"], "trace": {"path": path},
           "peaks": peaks.peaks_of(args.device_kind)}
    metrics = {}
    for m in listed:
        value = common.load_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    print(json.dumps({"workload": cell["name"], "trace": path,
                      "metrics": metrics}))
    return 0 if metrics else 1


if __name__ == "__main__":
    raise SystemExit(main())
