#!/usr/bin/env python3
"""Record the small trace kept as ``tests/cells/fixtures/tiny.xplane.pb``
and print its layout: a jitted program with a loop run four times with
host sleeps between, so the trace has nested operations, program spans
and idle gaps. Run on the chip; writes under ``chiprun_out/fixture``."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import xplane

    def tiny_step(x):
        def body(_, a):
            return jnp.tanh(a @ a) * 0.5
        return jax.lax.fori_loop(0, 3, body, x).sum()

    step = jax.jit(tiny_step)
    x = jnp.ones((256, 256), jnp.bfloat16)
    step(x).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(4):
        step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = xplane.find_trace(out)
    print("trace", path, os.path.getsize(path), "bytes")
    shutil.copy(path, os.path.join(ROOT, "chiprun_out",
                                   "tiny.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, str(v)[:40]) for k, v in list(e.stats)[:6]])
    print("REDUCED", xplane.reduce_trace(path))
    for p in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        print(p, os.path.getsize(p) if os.path.isfile(p) else "")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
