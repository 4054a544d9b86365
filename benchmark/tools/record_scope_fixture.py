#!/usr/bin/env python3
"""Record the small trace kept as
``tests/cells/fixtures/tiny_scopes.xplane.pb``: a jitted training step
of two scopes of the program's vocabulary (``attn/qkv`` around a loop of
products, ``ffn`` around one more), with its gradient and an update, run
four times on the chip and profiled as ``common.SubTrace`` profiles a
cell. Prints
what the chip's own ``tf_op`` says of each operation and what
``benchmark/opscopes.py`` makes of the trace, then cuts the file to what
that reduction reads (of each chip's plane the ``XLA Ops`` and ``XLA
Modules`` lines, every event's name, start and duration, the five
statistics and the program id of an operation's metadata; of the
``/host:metadata`` plane the compiled programs that ran) and writes
``chiprun_out/tiny_scopes.xplane.pb``. Run on the chip.

    python3 benchmark/tools/record_scope_fixture.py --prune SRC DST

cuts a file recorded earlier, anywhere."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def prune(src: str, dst: str) -> None:
    from benchmark import opscopes, xplane

    msgs = opscopes.messages()
    with open(src, "rb") as f:
        space = msgs["XSpace"].FromString(f.read())
    out = msgs["XSpace"]()
    ran = set()     # the programs whose operations the device planes hold
    # (the device planes come first in a trace, the programs after)
    for plane in sorted(space.planes,
                        key=lambda p: p.name == opscopes.PROGRAMS_PLANE):
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != opscopes.PROGRAMS_PLANE:
            continue
        new = out.planes.add(name=plane.name)
        keep = (opscopes.STATS if device else (opscopes.HLO_STAT,))
        stat_ids = {e.key for e in plane.stat_metadata
                    if e.value.name in keep}
        used = set()
        for ln in plane.lines:
            if ln.name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                continue
            line = new.lines.add(name=ln.name, timestamp_ns=ln.timestamp_ns)
            for e in ln.events:
                line.events.add(metadata_id=e.metadata_id,
                                offset_ps=e.offset_ps,
                                duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        refs, names = set(), opscopes.stat_names(plane)
        for entry in plane.event_metadata:
            name = opscopes._text(entry.value.name)
            if (entry.key not in used if device else not any(
                    name.endswith(f"({i})") for i in ran)):
                continue
            ran.add(opscopes.statistics(names, entry.value, (
                "program_id",)).get("program_id"))
            kept = new.event_metadata.add(key=entry.key)
            kept.value.id, kept.value.name = entry.value.id, entry.value.name
            for stat in entry.value.stats:
                if stat.metadata_id in stat_ids:
                    kept.value.stats.add().CopyFrom(stat)
                    if stat.WhichOneof("value") == "ref_value":
                        refs.add(stat.ref_value)
        for e in plane.stat_metadata:
            if e.key in stat_ids or e.key in refs:
                new.stat_metadata.add().CopyFrom(e)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"pruned {src} ({os.path.getsize(src)} bytes) to {dst} "
          f"({os.path.getsize(dst)} bytes)")
    print("XPLANE", xplane.reduce_trace(dst))
    print("OPSCOPES", opscopes.reduce_trace(dst))


def main() -> int:
    if sys.argv[1:2] == ["--prune"]:
        prune(*sys.argv[2:4])
        return 0
    import jax
    import jax.numpy as jnp

    from benchmark import common, opscopes, peaks, xplane
    from deeplearning4j_tpu.profiler import scope

    def tiny_step(w1, w2, x):
        def loss(w1, w2):
            # the scopes directly under the gradient, as a layer's are
            # under a training step's (``transpose(jvp(attn))/qkv``)
            with scope("attn/qkv"):
                h = jax.lax.fori_loop(
                    0, 3, lambda _, h: jnp.tanh(h @ w1), x)
            with scope("ffn"):
                h = jnp.tanh(h @ w2) * 0.5
            return jnp.sum(h.astype(jnp.float32))

        value, (g1, g2) = jax.value_and_grad(loss, argnums=(0, 1))(w1, w2)
        with scope("update/step"):
            return value, w1 - 0.01 * g1, w2 - 0.01 * g2

    step = jax.jit(tiny_step)
    x = jnp.ones((256, 256), jnp.bfloat16)
    w1 = jnp.full((256, 256), 0.01, jnp.bfloat16)
    w2 = jnp.full((256, 256), 0.02, jnp.bfloat16)
    jax.block_until_ready(step(w1, w2, x))
    trace = common.SubTrace("scope_fixture")
    trace.dir = os.path.join(ROOT, "chiprun_out", "scope_fixture")
    trace.start()
    for _ in range(4):
        jax.block_until_ready(step(w1, w2, x))
        time.sleep(0.002)
    trace.stop()
    path = xplane.find_trace(trace.dir)
    kind = jax.devices()[0].device_kind
    print("trace", path, os.path.getsize(path), "bytes; device", kind)
    with open(path, "rb") as f:
        space = opscopes.messages()["XSpace"].FromString(f.read())
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            names = opscopes.stat_names(plane)
            for entry in plane.event_metadata:
                rec = opscopes.statistics(names, entry.value,
                                           opscopes.STATS)
                if rec:
                    print("OP", opscopes._text(entry.value.name)[:60],
                          "|", rec.get("hlo_category"), "|",
                          rec.get("tf_op"), "|", rec.get("source"))
    red = opscopes.reduce_trace(path)
    print("OPSCOPES", red)
    if red is not None:
        opscopes.log_table(red, peaks.peaks_of(kind), 0.0)
    prune(path, os.path.join(ROOT, "chiprun_out", "tiny_scopes.xplane.pb"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
