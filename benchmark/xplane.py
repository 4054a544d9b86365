"""From a profiler trace (``.xplane.pb``) to device busy time, time per
operation and named idle gaps.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with a line of
operations (``XLA Ops``) and a line of whole programs (``XLA Modules``).
Operations nest (a ``while`` spans its body), so busy time is the union
of the intervals and an operation's own time is its span less its
children's. An idle gap is named by the program that ran next
(``before_jit_decode``); a gap inside one program's span is
``within_<program>``.

A trace with no device plane (a CPU run) reduces to ``None``: there is
no device number to report.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def program_name(event_name: str) -> str:
    """``jit_decode(1234567)`` -> ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def op_label(text: str) -> str:
    """A short, stable label for an operation's trace name, which on a
    TPU is the whole HLO instruction: ``%fusion.8 = bf16[..] fusion(..)``
    becomes ``fusion_fusion`` (the instruction's name without its
    number, then its opcode, or a custom call's target), so that the
    copies of one operation in unrolled layers add up."""
    text = text.strip()
    if " = " not in text:
        return text[:64]
    lhs, rhs = text.split(" = ", 1)
    name = re.sub(r"\.\d+$", "", lhs.lstrip("%"))
    if rhs.startswith("("):      # a tuple type: skip to its closing ")"
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else ""
    opcode = rhs.strip().split("(", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name}_{target.group(1) if target else opcode}"[:64]


def _events(line, label=lambda name: name):
    return sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns),
                    label(e.name)) for e in line.events),
                  key=lambda t: (t[0], -t[1]))


def union_and_self_times(events):
    """``events``: (start, end, name) sorted by start, longest first on
    ties. Returns (merged busy intervals, {name: own seconds})."""
    merged, own, stack = [], {}, []
    for start, end, name in events:
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:   # a child: its span is not its parent's own time
            parent = stack[-1][2]
            own[parent] = own.get(parent, 0.0) - (
                min(end, stack[-1][1]) - start)
        own[name] = own.get(name, 0.0) + (end - start)
        stack.append((start, end, name))
    return merged, {k: v * 1e-9 for k, v in own.items()}


def name_gaps(merged, modules, t_lo: float, t_hi: float) -> dict:
    """Idle seconds between busy intervals inside ``[t_lo, t_hi]``, by
    the program that followed."""
    gaps = {}
    starts = [m[0] for m in modules]
    edges = [t_lo] + [t for iv in merged for t in iv] + [t_hi]
    for lo, hi in zip(edges[0::2], edges[1::2]):
        if hi <= lo:
            continue
        # the program running when the gap ends, else the next one
        i = bisect.bisect_right(starts, hi + 1.0) - 1
        if i >= 0 and modules[i][1] > hi:
            kind = "within" if modules[i][0] < lo else "before"
            label = f"{kind}_{program_name(modules[i][2])}"
        elif i + 1 < len(modules):
            label = f"before_{program_name(modules[i + 1][2])}"
        else:
            label = "after_last_program"
        gaps[label] = gaps.get(label, 0.0) + (hi - lo) * 1e-9
    return gaps


def reduce_trace(path: str):
    """The reduction of one trace file: per chip busy seconds, own time
    by operation, time and count by program, idle gaps by the program
    that followed. ``None`` when the trace holds no device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = _events(lines[OPS_LINE], op_label)
        if not ops:
            continue
        modules = (_events(lines[MODULES_LINE])
                   if MODULES_LINE in lines else [])
        merged, own = union_and_self_times(ops)
        t_lo = min(ops[0][0], modules[0][0] if modules else ops[0][0])
        t_hi = max(max(e[1] for e in ops),
                   max((m[1] for m in modules), default=0.0))
        programs = {}
        for start, end, name in modules:
            rec = programs.setdefault(program_name(name),
                                      {"seconds": 0.0, "count": 0})
            rec["seconds"] += (end - start) * 1e-9
            rec["count"] += 1
        chips.append({
            "plane": plane.name,
            "busy_s": sum(hi - lo for lo, hi in merged) * 1e-9,
            "span_s": (t_hi - t_lo) * 1e-9,
            "ops": own, "programs": programs,
            "gaps": name_gaps(merged, modules, t_lo, t_hi)})
    if not chips:
        return None
    n = len(chips)

    def mean_by_key(field):
        keys = set().union(*(c[field] for c in chips))
        return {k: sum(c[field].get(k, 0.0) for c in chips) / n
                for k in keys}

    programs = {}
    for c in chips:
        for k, rec in c["programs"].items():
            tot = programs.setdefault(k, {"seconds": 0.0, "count": 0})
            tot["seconds"] += rec["seconds"] / n
            tot["count"] += rec["count"] / n
    return {"chips": n,
            "busy_s": sum(c["busy_s"] for c in chips) / n,
            "span_s": sum(c["span_s"] for c in chips) / n,
            "ops": mean_by_key("ops"), "programs": programs,
            "gaps": mean_by_key("gaps")}


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
