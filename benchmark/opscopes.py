"""From a profiler trace to the device's BUSY time by the program's own
layers: each operation's own time on a chip's ``XLA Ops`` line
(``xplane.union_and_self_times``: a ``while`` is not its body) is given
to the scope the program traced it in, read from the operation's
metadata in the trace (``tf_op``, the JAX name stack that
``deeplearning4j_tpu/profiler/scopes.py``'s ``scope`` writes into).

``xplane.py`` names device time by HLO instruction (``fusion_fusion``);
this names it by layer. A path is cut into a PHASE (``admit`` /
``decode``, the first scope of an engine program's body), a GROUP (the
first group of the vocabulary after it), a CHILD (the next entry, if it
is one of the group's) and ``backward`` (a ``transpose(`` anywhere: the
gradient's way back). The wrappers a gradient puts around an entry
(``transpose(jvp(attn))``) are taken off; library code that no scope may
wrap is charged by ``scopes.LIBRARY_SCOPES``. An operation whose path
holds no group is ``unscoped``: the number that says the scopes have
rotted.

**A fused operation is charged whole to the instruction whose metadata
XLA kept for it** (for a ``convolution fusion``, the product): casts,
biases and activations fused onto a product are the product's group's,
so ``cast`` reads low where the casts are fused into the products.

**An operation the compiler put in itself carries no path** (the
``slice-start`` / ``slice-done`` / ``copy-start`` / ``copy-done`` of a
weight it prefetches into fast memory, the ``copy`` of a re-layout): it
INHERITS the path of the nearest operation that reads its result (or,
failing that, that made its operand) in the compiled program, which the
trace carries whole (the ``Hlo Proto`` of the ``/host:metadata`` plane).
What was inherited is kept apart (``inherited``), by group and
``hlo_category``: that table says what a cell's copies serve.

The metadata statistics are not in ``jax.profiler.ProfileData`` (it
hands out an event's own statistics only), so the file is read with
``google.protobuf`` through the few fields of the trace's schema
declared here (tensorflow's copy of the schema takes ~15 s to import).
Beside the seconds it sums the compiler's own ``flops`` and
``bytes_accessed`` over each operation's runs, a group at a time.

A trace with no device plane, a program without the vocabulary, or a
trace in which no operation carries a group (executables loaded from a
compile-cache entry that a tree without scopes compiled: the cache's key
leaves metadata out) reduces to ``None``: never a false 0 or 100.
"""

from __future__ import annotations

import functools
import re
import time

from benchmark import common, xplane

try:
    from deeplearning4j_tpu.profiler import scopes
except ImportError:         # a program from before it named its parts
    scopes = None

UNSCOPED = "unscoped"
#: what a gradient or a batching transform wraps a name-stack entry in
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
#: the event metadata's statistics the reduction reads
STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed", "source",
          "program_id")
#: the plane that holds each compiled program, and the statistic
PROGRAMS_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"
#: how far from an operation without a path its heir is looked for,
#: and the instructions the search does not pass: beyond a loop's tuple
#: or a parameter lies another part of the program, not this
#: operation's reader
_INHERIT_DEPTH = 10
_BARRIERS = ("tuple", "while", "conditional", "call", "parameter")


# ---------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------
def _schema():
    """Message classes for the fields of the trace's schema that are
    read here (``tsl/profiler/protobuf/xplane.proto``; a map is read as
    its repeated key/value entries), in a pool of their own."""
    from google.protobuf import (
        descriptor_pb2,
        descriptor_pool,
        message_factory,
    )

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_opscopes.proto", package="benchmark_opscopes",
        syntax="proto3")

    def message(name, *fields, oneof=None):
        msg = fd.message_type.add(name=name)
        if oneof:
            msg.oneof_decl.add(name=oneof)
        for fname, number, ftype, repeated in fields:
            f = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type = F.TYPE_MESSAGE
                f.type_name = f".benchmark_opscopes.{ftype}"
            else:
                f.type = ftype
            if oneof and fname.endswith("_value"):
                f.oneof_index = 0

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("double_value", 2, F.TYPE_DOUBLE, False),
            ("uint64_value", 3, F.TYPE_UINT64, False),
            ("int64_value", 4, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_BYTES, False),
            ("bytes_value", 6, F.TYPE_BYTES, False),
            ("ref_value", 7, F.TYPE_UINT64, False), oneof="value")
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
            ("offset_ps", 2, F.TYPE_INT64, False),
            ("duration_ps", 3, F.TYPE_INT64, False))
    message("XLine", ("name", 2, F.TYPE_STRING, False),
            ("timestamp_ns", 3, F.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, F.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    # the compiled program (``xla/service/hlo.proto``): what is read
    message("OpMetadata", ("op_name", 2, F.TYPE_BYTES, False))
    message("HloInstruction", ("name", 1, F.TYPE_STRING, False),
            ("opcode", 2, F.TYPE_STRING, False),
            ("metadata", 7, "OpMetadata", False),
            ("tuple_index", 13, F.TYPE_INT64, False),
            ("id", 35, F.TYPE_INT64, False),
            ("operand_ids", 36, F.TYPE_INT64, True),
            ("called_computation_ids", 38, F.TYPE_INT64, True))
    message("HloComputation", ("instructions", 2, "HloInstruction", True),
            ("id", 5, F.TYPE_INT64, False))
    message("HloModule", ("computations", 3, "HloComputation", True))
    message("HloProto", ("hlo_module", 1, "HloModule", False))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {m.name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"benchmark_opscopes.{m.name}"))
        for m in fd.message_type}


@functools.cache
def messages() -> dict:
    """The schema's message classes by name, made once (a test builds
    its stand-in trace from them)."""
    return _schema()


def _text(raw) -> str:
    return raw.decode("utf-8", "replace") if isinstance(raw, bytes) else raw


def stat_names(plane) -> dict:
    """``{id: name}`` of a plane's statistics, made once a plane."""
    return {e.key: e.value.name for e in plane.stat_metadata}


def statistics(names, metadata, wanted) -> dict:
    """``{statistic's name: value}`` of one event's metadata, for the
    statistics named in ``wanted`` (``names``: the plane's
    ``stat_names``); one the event lacks is absent."""
    out = {}
    for stat in metadata.stats:
        name = names.get(stat.metadata_id)
        which = stat.WhichOneof("value")
        if name not in wanted or which is None:
            continue
        value = getattr(stat, which)
        if which == "ref_value":
            value = names.get(value, "")
        elif which == "str_value":
            value = _text(value)
        out[name] = value
    return out


# ---------------------------------------------------------------------
# a path
# ---------------------------------------------------------------------
def cut(tf_op: str):
    """``(phase, group, child, backward)`` of an operation's ``tf_op``
    (``jit(decode)/decode/while/body/attn/qkv/dot_general:``); each
    None where the path holds none."""
    path = tf_op.rsplit(":", 1)[0]
    backward = "transpose(" in path
    entries = []
    for entry in path.split("/"):
        while (m := _WRAPPED.match(entry)):
            entry = m.group(1)
        entries.extend(scopes.LIBRARY_SCOPES.get(entry, entry).split("/"))
    phase = group = child = None
    for i, entry in enumerate(entries):
        if entry in scopes.GROUPS:
            group = entry
            nxt = entries[i + 1] if i + 1 < len(entries) else None
            child = nxt if nxt in scopes.GROUPS[group] else None
            break
        if phase is None and entry in scopes.PHASES:
            phase = entry
    return phase, group, child, backward


# ---------------------------------------------------------------------
# the compiled programs: whose a compiler-made operation is
# ---------------------------------------------------------------------
def _nearest(start, edges, grouped, opcode):
    """The nearest instruction along ``edges`` (breadth first,
    ``_INHERIT_DEPTH`` deep, not past ``_BARRIERS``) that is one of
    ``grouped``, those whose own path has a group."""
    seen, level = {start}, [start]
    for _ in range(_INHERIT_DEPTH):
        level = [n for i in level for n in edges.get(i, ())
                 if n not in seen and not seen.add(n)
                 and opcode.get(n) not in _BARRIERS]
        for n in level:
            if n in grouped:
                return n
    return None


def _into_loops(module, by_id, readers, makers) -> None:
    """Edges from what a ``while`` is handed to what reads it inside:
    a loop's operand is a tuple, and entry ``k`` of it is read in the
    body through ``get-tuple-element(parameter), index=k`` (a weight the
    compiler lays out anew once a dispatch, before the loop whose
    products read it)."""
    bodies = {c.id: c for c in module.computations}
    for loop in by_id.values():
        if loop.opcode != "while" or not loop.called_computation_ids:
            continue
        handed = by_id.get(loop.operand_ids[0])
        body = bodies.get(loop.called_computation_ids[0])
        if handed is None or handed.opcode != "tuple" or body is None:
            continue
        params = {i.id for i in body.instructions
                  if i.opcode == "parameter"}
        for inside in body.instructions:
            if (inside.opcode == "get-tuple-element"
                    and inside.operand_ids[0] in params
                    and inside.tuple_index < len(handed.operand_ids)):
                outside = handed.operand_ids[inside.tuple_index]
                readers.setdefault(outside, []).append(inside.id)
                makers.setdefault(inside.id, []).append(outside)


def heirs_of(program) -> dict:
    """``{instruction name: inherited path}`` for every instruction of
    a compiled program (an ``HloProto``) that has no group of its own
    and an instruction near it that has: readers of its result first,
    then makers of its operands."""
    module = program.hlo_module
    by_id = {i.id: i for c in module.computations for i in c.instructions}
    path = {i: _text(inst.metadata.op_name) for i, inst in by_id.items()}
    opcode = {i: inst.opcode for i, inst in by_id.items()}
    makers = {i: list(inst.operand_ids) for i, inst in by_id.items()}
    readers = {}
    for inst, operands in makers.items():
        for operand in operands:
            readers.setdefault(operand, []).append(inst)
    _into_loops(module, by_id, readers, makers)
    grouped = {i for i, p in path.items() if cut(p)[1] is not None}
    heirs = {}
    for i, inst in by_id.items():
        if i in grouped:
            continue
        heir = (_nearest(i, readers, grouped, opcode)
                or _nearest(i, makers, grouped, opcode))
        if heir is not None:
            heirs[inst.name] = path[heir]
    return heirs


def compiled_programs(space) -> dict:
    """``{program id: heirs_of(program)}`` of the compiled programs the
    trace carries."""
    out = {}
    for plane in space.planes:
        if plane.name != PROGRAMS_PLANE:
            continue
        names = stat_names(plane)
        for entry in plane.event_metadata:
            found = re.search(r"\((\d+)\)$", _text(entry.value.name))
            raw = statistics(names, entry.value, (HLO_STAT,)).get(HLO_STAT)
            if found and raw:
                out[int(found.group(1))] = heirs_of(
                    messages()["HloProto"].FromString(raw))
    return out


# ---------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------
def _runs_and_parents(events):
    """How often each key ran, and the keys that ever held another
    operation inside their span (a ``while``: the compiler's flops of
    it are its body's, which the body's operations carry too)."""
    runs, parents, stack = {}, set(), []
    for start, end, key in events:
        runs[key] = runs.get(key, 0) + 1
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parents.add(stack[-1][1])
        stack.append((end, key))
    return runs, parents


def _reduce_plane(plane, programs):
    """One chip: busy seconds and, for each operation that ran, its own
    seconds, its work and where it belongs."""
    ops_line = next((ln for ln in plane.lines
                     if ln.name == xplane.OPS_LINE), None)
    if ops_line is None or not ops_line.events:
        return None
    events = sorted(
        ((e.offset_ps * 1e-3, (e.offset_ps + e.duration_ps) * 1e-3,
          e.metadata_id) for e in ops_line.events),
        key=lambda t: (t[0], -t[1]))
    merged, own = xplane.union_and_self_times(events)
    runs, parents = _runs_and_parents(events)
    names, rows = stat_names(plane), []
    for entry in plane.event_metadata:
        if entry.key not in own:
            continue
        key, name = entry.key, _text(entry.value.name)
        rec = statistics(names, entry.value, STATS)
        tf_op, inherited = rec.get("tf_op") or "", False
        if cut(tf_op)[1] is None:
            # no path of its own: the compiled program may know whose
            # result it makes
            instruction = name.split(" = ", 1)[0].strip().lstrip("%")
            heir = programs.get(rec.get("program_id"), {}).get(instruction)
            if heir is not None:
                tf_op, inherited = heir, True
        rows.append({
            "label": xplane.op_label(name), "seconds": own[key],
            "tf_op": tf_op, "inherited": inherited,
            "category": rec.get("hlo_category") or "?",
            "source": rec.get("source") or "",
            # a parent's flops are its children's: counted there
            "flops": 0.0 if key in parents
            else float(rec.get("flops") or 0) * runs[key],
            "bytes": 0.0 if key in parents
            else float(rec.get("bytes_accessed") or 0) * runs[key]})
    return {"busy_s": sum(hi - lo for lo, hi in merged) * 1e-9,
            "rows": rows}


def _add(table, key, value):
    table[key] = table.get(key, 0.0) + value


def reduce_trace(path: str):
    """The reduction of one trace file, a mean over its chips:
    ``busy_s``; own seconds by ``groups`` (``unscoped`` among them: they
    add up to busy), by ``children`` (``attn/qkv``), by ``categories``
    (``attn | data formatting``), by ``labels`` (``fusion_fusion |
    moe/combine``: what ``xplane``'s name for an operation holds), by
    ``phases`` and of the ``backward`` operations; ``admit_children``,
    the admission programs' seconds by child; ``inherited``, the seconds of compiler-made operations by the
    group and category they were given to; ``work``, a group's flops,
    bytes and seconds; the largest ``unscoped`` operations. ``None`` as
    the module docstring says."""
    if scopes is None:
        return None
    with open(path, "rb") as f:
        space = messages()["XSpace"].FromString(f.read())
    devices = [p for p in space.planes
               if p.name.startswith("/device:TPU:")]
    programs = compiled_programs(space) if devices else {}
    chips = [c for c in (_reduce_plane(p, programs) for p in devices)
             if c is not None]
    if not chips:
        return None
    n = len(chips)
    red = {"busy_s": sum(c["busy_s"] for c in chips) / n, "groups": {},
           "children": {}, "categories": {}, "labels": {}, "phases": {},
           "admit_children": {}, "inherited": {}, "work": {},
           "backward_s": 0.0}
    unscoped = {}
    for chip in chips:
        for row in chip["rows"]:
            phase, group, child, backward = cut(row["tf_op"])
            group = group or UNSCOPED
            s = row["seconds"] / n
            part = f"{group}/{child}" if child else group
            _add(red["groups"], group, s)
            _add(red["children"], part, s)
            _add(red["categories"], f"{group} | {row['category']}", s)
            _add(red["labels"], f"{row['label']} | {part}", s)
            if row["inherited"]:
                _add(red["inherited"], f"{part} | {row['category']}", s)
            if phase:
                _add(red["phases"], phase, s)
            if phase == "admit":
                _add(red["admit_children"], part, s)
            if backward:
                red["backward_s"] += s
            work = red["work"].setdefault(
                group, {"flops": 0.0, "bytes": 0.0, "seconds": 0.0})
            work["flops"] += row["flops"] / n
            work["bytes"] += row["bytes"] / n
            work["seconds"] += s
            if group == UNSCOPED:
                key = (row["label"], row["category"],
                       row["source"] or row["tf_op"])
                _add(unscoped, key, s)
    red["unscoped_top"] = [
        [*key, s] for key, s in sorted(unscoped.items(),
                                       key=lambda kv: -kv[1])[:10]]
    if not any(s > 0 for g, s in red["groups"].items() if g != UNSCOPED):
        common.log("device scopes: no operation of the trace carries a "
                   "scope of the vocabulary (executables from a compile "
                   "cache that a tree without scopes filled? clear it): "
                   "nothing reported")
        return None
    return red


def of(obs):
    """The run's reduction, made once and kept on ``obs`` for the
    readers that share it, from the trace ``SubTrace`` left for the cell
    (``obs["cell"]``). ``None`` where the run's own reduction
    (``obs["trace"]``) found no device."""
    if "opscopes" not in obs:
        red = None
        path = (xplane.find_trace(common.trace_dir(obs["cell"]))
                if obs.get("trace") is not None else None)
        if path is not None:
            t0 = time.perf_counter()
            red = reduce_trace(path)
            took = time.perf_counter() - t0
            if red is not None:
                log_table(red, obs.get("peaks"), took)
        obs["opscopes"] = red
    return obs["opscopes"]


def share(obs, trained: bool, groups=None, phase=None):
    """Own device seconds of ``groups`` (or of every operation of
    ``phase``) as a percentage of the device's busy time in the traced
    stretch, ``pallas_share``'s denominator as this reduction measured
    it (so the groups and ``unscoped`` add up to 100). ``trained``: the
    kind of cell the metric belongs to; None in the other kind."""
    if (obs["kind"] == "train_job") != trained:
        return None
    red = of(obs)
    if red is None or not red["busy_s"]:
        return None
    seconds = (red["phases"].get(phase, 0.0) if phase
               else sum(red["groups"].get(g, 0.0) for g in groups))
    return 100.0 * seconds / red["busy_s"]


def _ranked(table: dict, most=None) -> str:
    return ", ".join(f"{k} {v:.4f}" for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:most] if v >= 5e-5)


def log_table(red: dict, peaks, took_s: float) -> None:
    """One line a run: busy seconds by group and child, by group and
    ``hlo_category``, the largest of ``xplane``'s labels by child, a
    group's achieved TFLOP/s and GB/s from the compiler's own counts,
    the admission programs by child, the way back, and the largest
    unscoped operations with their source."""
    busy = red["busy_s"]
    rates = []
    for group, w in sorted(red["work"].items(),
                           key=lambda kv: -kv[1]["seconds"]):
        if w["seconds"] >= 5e-5:
            rates.append(
                f"{group} {w['flops'] / w['seconds'] / 1e12:.2f} TFLOP/s "
                f"{w['bytes'] / w['seconds'] / 1e9:.1f} GB/s")
    peak = (f" (peaks {peaks['bf16_flops'] / 1e12:.0f} TFLOP/s, "
            f"{peaks['hbm_bytes_per_s'] / 1e9:.0f} GB/s)" if peaks else "")
    common.log(
        f"device scopes: busy {busy:.4f} s; by group: "
        + ", ".join(f"{k} {v:.4f} ({100 * v / busy:.1f}%)"
                    for k, v in sorted(red["groups"].items(),
                                       key=lambda kv: -kv[1]))
        + "; by child: " + _ranked(red["children"])
        + "; by group and category: " + _ranked(red["categories"])
        + "; by label and child: " + _ranked(red["labels"], 24)
        + "; achieved, by the compiler's flops and bytes_accessed"
        + peak + ": " + ", ".join(rates)
        + "; by phase: " + (_ranked(red["phases"]) or "none")
        + "; admission by child: "
        + (_ranked(red["admit_children"]) or "none")
        + "; compiler-made operations, by the group that reads them: "
        + (_ranked(red["inherited"]) or "none")
        + f"; backward {red['backward_s']:.4f} of {busy:.4f}"
        + "; largest unscoped: "
        + ("; ".join(f"{label} [{cat}] {s:.4f} at {src}"
                     for label, cat, src, s in red["unscoped_top"])
           or "none")
        + f"; reduced in {took_s:.1f}s")
