"""From a profiler trace to the device's idle time by what the host was
doing: each idle interval between two device programs is given to the
innermost span of the program (``Tracer.span`` / ``annotate``, which
the profiler keeps on its own clock) that covers it on the stepper
thread's line of the host plane.

``xplane.py`` names a gap by the program that ran next; this names it
by the host phase that caused it. The groups are the benchmark's layers:
``gateway.*`` spans are the gateway's; ``serving.sweeps``,
``serving.admit`` (less its children) and ``serving.reserve`` the
scheduler's; every other ``serving.*`` span the engine step's. A gap
inside one device program (``within_<program>``, by ``xplane``'s rule)
is no host phase's and is kept apart; idle under no program span is
``unattributed``: the number that says the spans have rotted.

A trace with no device plane, or none of the program's spans (a program
from before they existed), reduces to ``None``: no reader reports.
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import common, xplane

HOST_PLANE = "/host:CPU"
ROUND = "serving.round"
PREFIXES = ("gateway.", "serving.", "train.")
SCHEDULER = ("serving.sweeps", "serving.admit", "serving.reserve")
GROUPS = ("gateway", "scheduler", "engine step")
UNATTRIBUTED = "unattributed"
#: the device program whose start is checked against the spans around it
DECODE_PROGRAM = "jit_decode"
DISPATCH, SYNC = "serving.decode_dispatch", "serving.token_sync"
#: a decode program further than this outside its spans means the two
#: planes are not on one clock: nothing is reported then
CLOCK_LIMIT_NS = 1e6


def group_of(leaf: str) -> str:
    """The layer a leaf span's idle time is charged to."""
    if leaf.startswith("gateway."):
        return "gateway"
    return "scheduler" if leaf in SCHEDULER else "engine step"


def innermost(spans):
    """``spans``: (start, end, name) of one thread, sorted by start,
    longest first on ties, properly nested. Returns the thread's time
    cut into disjoint (start, end, name) pieces, each named by the
    innermost span that covers it."""
    pieces, stack = [], []   # stack of [end, name], cursor = piece start
    cursor = None

    def cut(upto):
        nonlocal cursor
        if stack and upto > cursor:
            pieces.append((cursor, upto, stack[-1][1]))
        cursor = max(cursor, upto)

    for start, end, name in spans:
        while stack and stack[-1][0] <= start:
            cut(stack[-1][0])
            stack.pop()
        if cursor is not None:
            cut(start)
        cursor = start
        stack.append([end, name])
    while stack:
        cut(stack[-1][0])
        stack.pop()
    return pieces


def idle_intervals(merged, modules, t_lo: float, t_hi: float):
    """The device's idle intervals inside ``[t_lo, t_hi]`` as
    (start, end, label): ``within_<program>`` for a gap inside one
    program's span (``xplane.name_gaps``' rule), else ``None``."""
    out = []
    starts = [m[0] for m in modules]
    edges = [t_lo] + [t for iv in merged for t in iv] + [t_hi]
    for lo, hi in zip(edges[0::2], edges[1::2]):
        if hi <= lo:
            continue
        i = bisect.bisect_right(starts, hi + 1.0) - 1
        inside = i >= 0 and modules[i][1] > hi and modules[i][0] < lo
        out.append((lo, hi, "within_" + xplane.program_name(
            modules[i][2]) if inside else None))
    return out


def attribute(idle, pieces) -> dict:
    """Seconds of the unlabelled intervals of ``idle`` by the piece
    (``innermost``) that covers them, ``unattributed`` under none;
    labelled intervals keep their label."""
    out = {}

    def add(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns * 1e-9

    starts = [p[0] for p in pieces]
    for lo, hi, label in idle:
        if label is not None:
            add(label, hi - lo)
            continue
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][0] < hi:
            part = min(hi, pieces[i][1]) - max(lo, pieces[i][0])
            if part > 0:
                add(pieces[i][2], part)
                covered += part
            i += 1
        add(UNATTRIBUTED, (hi - lo) - covered)
    return out


def check_clock(modules, spans) -> dict:
    """Every ``jit_decode`` program on the device must start after the
    start of the ``serving.decode_dispatch`` that launched it and before
    the end of the ``serving.token_sync`` that followed. Returns how
    many were checked, the median lag behind the dispatch's start and
    the farthest any lay outside its spans (ns)."""
    dispatches = [s for s in spans if s[2] == DISPATCH]
    syncs = [s for s in spans if s[2] == SYNC]
    d_starts = [s[0] for s in dispatches]
    s_starts = [s[0] for s in syncs]
    lags, outside = [], []
    for start, _, name in modules:
        if xplane.program_name(name) != DECODE_PROGRAM:
            continue
        i = bisect.bisect_right(d_starts, start + CLOCK_LIMIT_NS) - 1
        if i < 0:
            continue            # launched before the trace began
        j = bisect.bisect_left(s_starts, dispatches[i][0])
        if j >= len(syncs) or (i + 1 == len(dispatches)
                               and start > syncs[j][1]):
            continue            # its spans ended after the trace did
        lags.append(start - dispatches[i][0])
        outside.append(max(dispatches[i][0] - start,
                           start - syncs[j][1], 0.0))
    return {"checked": len(lags),
            "median_lag_ns": statistics.median(lags) if lags else None,
            "worst_outside_ns": max(outside) if outside else None}


def stepper_spans(data):
    """The program's spans on the line of the host plane that holds the
    most ``serving.round`` spans (the gateway's stepper thread; the
    profiler names a Python thread's line after the process), or
    ``None`` where no line holds one."""
    best, most = None, 0
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [e for e in xplane._events(line)
                     if e[2].startswith(PREFIXES)]
            rounds = sum(1 for e in spans if e[2] == ROUND)
            if rounds > most:
                best, most = spans, rounds
    return best


def reduce_trace(path: str):
    """The reduction of one trace file: idle seconds of the device by
    leaf span, by ``within_<program>`` and ``unattributed``, the three
    groups, and the clock check. ``None`` with no device plane, no
    stepper line, no decode program to check the clock by, or one
    outside its spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = stepper_spans(data)
    if spans is None:
        return None
    pieces = innermost(spans)
    chips, clock = [], None
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = (xplane._events(lines[xplane.OPS_LINE])
               if xplane.OPS_LINE in lines else [])
        if not ops:
            continue
        modules = (xplane._events(lines[xplane.MODULES_LINE])
                   if xplane.MODULES_LINE in lines else [])
        merged, _ = xplane.union_and_self_times(ops)
        t_lo = min(ops[0][0], modules[0][0] if modules else ops[0][0])
        t_hi = max(max(e[1] for e in ops),
                   max((m[1] for m in modules), default=0.0))
        chips.append(attribute(
            idle_intervals(merged, modules, t_lo, t_hi), pieces))
        clock = clock or check_clock(modules, spans)
    if not chips:
        return None
    if not clock["checked"] or clock["worst_outside_ns"] > CLOCK_LIMIT_NS:
        common.log(f"host spans: clock check FAILED, {clock}: no "
                   f"{DECODE_PROGRAM} program between its spans, or one "
                   "outside them; nothing reported")
        return None
    n = len(chips)
    by_name = {k: sum(c.get(k, 0.0) for c in chips) / n
               for k in set().union(*chips)}
    within = {k: v for k, v in by_name.items()
              if k.startswith("within_")}
    leaves = {k: v for k, v in by_name.items()
              if k not in within and k != UNATTRIBUTED}
    groups = dict.fromkeys(GROUPS, 0.0)
    for leaf, s in leaves.items():
        groups[group_of(leaf)] += s
    return {"idle_s": sum(by_name.values()), "leaves": leaves,
            "within": within,
            "unattributed_s": by_name.get(UNATTRIBUTED, 0.0),
            "groups": groups, "clock": clock}


def of(obs):
    """The run's reduction, made once and kept on ``obs`` for the
    readers that share it, from the trace ``SubTrace`` left for the
    cell (``obs["cell"]``). ``None`` where the run's own reduction
    (``obs["trace"]``) found no device."""
    if "hostspans" not in obs:
        red = None
        path = (xplane.find_trace(common.trace_dir(obs["cell"]))
                if obs.get("trace") is not None else None)
        if path is not None:
            red = reduce_trace(path)
        if red is not None:
            log_table(red, obs.get("trace_window_s"))
        obs["hostspans"] = red
    return obs["hostspans"]


def share(obs, key: str):
    """One group's (or ``unattributed``'s) idle seconds as a percentage
    of the traced stretch, the denominator of ``device_idle_share``."""
    red = of(obs) if obs["kind"] != "train_job" else None
    if red is None or not obs.get("trace_window_s"):
        return None
    seconds = (red["unattributed_s"] if key == UNATTRIBUTED
               else red["groups"][key])
    return 100.0 * seconds / obs["trace_window_s"]


def log_table(red: dict, window_s) -> None:
    rows = sorted(red["leaves"].items(), key=lambda kv: -kv[1])
    rows += sorted(red["within"].items(), key=lambda kv: -kv[1])
    rows.append((UNATTRIBUTED, red["unattributed_s"]))
    c = red["clock"]
    common.log(
        f"host spans: device idle {red['idle_s']:.4f} s"
        + (f" of {window_s:.3f} s traced" if window_s else "")
        + "; by leaf span: "
        + ", ".join(f"{k} {v:.4f}" for k, v in rows)
        + "; by layer: "
        + ", ".join(f"{k} {v:.4f}" for k, v in red["groups"].items())
        + f"; clock check over {c['checked']} {DECODE_PROGRAM} "
        f"programs: median lag behind the dispatch "
        f"{(c['median_lag_ns'] or 0.0) / 1e3:.1f} us, farthest outside "
        f"its spans {(c['worst_outside_ns'] or 0.0) / 1e3:.1f} us")
