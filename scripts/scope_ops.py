#!/usr/bin/env python3
"""The operations of ONE scope group in a traced run, one line each:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 51 --trace 1
    python3 scripts/scope_ops.py --workload <cell> --group moe [--min-ms 0.25]

``benchmark/tools/scope_table.py`` says how much of the busy chip a group
and its children take; this says WHICH compiled instructions that time is
(ms a run, runs, seconds of the stretch, ``hlo_category``, the instruction
with its shape, the end of its path), forward and backward apart, so that
a change to a layer can be judged operation by operation (``PERF.md``
section 5 gives the expert layer's row movements this way). It needs the
trace file only, no chip; ``--trace <file>`` reads any trace, another
tree's or a pruned one (``benchmark/tools/record_scope_fixture.py
--prune``)."""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import common, opscopes, xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    where = ap.add_mutually_exclusive_group(required=True)
    where.add_argument("--workload")
    where.add_argument("--trace")
    ap.add_argument("--group", required=True)
    ap.add_argument("--min-ms", type=float, default=0.25)
    args = ap.parse_args()
    path = args.trace or xplane.find_trace(common.trace_dir(args.workload))
    if path is None:
        raise SystemExit("no trace: make a traced run of the cell first")
    with open(path, "rb") as f:
        space = opscopes.messages()["XSpace"].FromString(f.read())
    programs = opscopes.compiled_programs(space)
    for plane in space.planes:
        line = next((ln for ln in plane.lines
                     if ln.name == xplane.OPS_LINE), None)
        if not plane.name.startswith("/device:TPU:") or line is None:
            continue
        events = sorted(
            ((e.offset_ps * 1e-3, (e.offset_ps + e.duration_ps) * 1e-3,
              e.metadata_id) for e in line.events),
            key=lambda t: (t[0], -t[1]))
        merged, own = xplane.union_and_self_times(events)
        runs, _ = opscopes._runs_and_parents(events)
        busy = sum(hi - lo for lo, hi in merged) * 1e-9
        names, rows, parts = opscopes.stat_names(plane), [], {}
        for entry in plane.event_metadata:
            if entry.key not in own:
                continue
            name = opscopes._text(entry.value.name)
            rec = opscopes.statistics(names, entry.value, opscopes.STATS)
            tf_op = rec.get("tf_op") or ""
            if opscopes.cut(tf_op)[1] is None:      # a compiler-made copy
                instruction = name.split(" = ", 1)[0].strip().lstrip("%")
                tf_op = programs.get(rec.get("program_id"), {}).get(
                    instruction) or tf_op
            _, group, child, backward = opscopes.cut(tf_op)
            if group != args.group:
                continue
            n, s = runs[entry.key], own[entry.key]
            part = (f"{group}/{child}" if child else group) + (
                " backward" if backward else " forward")
            parts[part] = parts.get(part, 0.0) + s
            rows.append((s / n * 1e3, n, s, rec.get("hlo_category"),
                         " ".join(name.split())[:100],
                         tf_op.rsplit(":", 1)[0][-60:]))
        print(f"{plane.name}: busy {busy:.4f} s; {args.group} "
              f"{sum(parts.values()):.4f} s")
        for part, s in sorted(parts.items(), key=lambda kv: -kv[1]):
            print(f"  {part:28s} {s:.4f} s  {100 * s / busy:5.2f}% of busy")
        print("  ms/run runs seconds category | instruction | path")
        for ms, n, s, category, name, where in sorted(rows, reverse=True):
            if ms >= args.min_ms:
                print(f"  {ms:.3f} {n} {s:.4f} {category} | {name} | {where}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
