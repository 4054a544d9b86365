#!/usr/bin/env python3
"""Record the small trace kept as
``tests/cells/fixtures/tiny_spans.xplane.pb``: a two-layer engine with a
paged pool behind the gateway, a few streamed requests over localhost,
profiled as ``common.SubTrace`` profiles a cell (host tracer level 1,
Python tracer off), so that the program's spans (``serving.round`` and
its leaves, the gateway's) lie beside the device's programs in one file.
Prints the file's layout and what ``benchmark/hostspans.py`` makes of
it. Run on the chip; writes ``chiprun_out/tiny_spans.xplane.pb``.

    python3 benchmark/tools/record_span_fixture.py --prune SRC DST

cuts a recorded file to what the two reductions read, for the tree
(here, off the chip: it needs TensorFlow's copy of the trace's schema):
of each chip's plane the ``XLA Ops`` and ``XLA Modules`` lines, of the
host's plane the program's spans on the lines that hold any, every
event's name, start and duration, and nothing else (no compiled
programs, no statistics, no runtime threads)."""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

VOCAB = 32
#: (prompt length, tokens asked for), sent a few milliseconds apart
TRACED = [(9, 10), (14, 6), (5, 12), (20, 8)]


def prune(src: str, dst: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark import hostspans, xplane

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != hostspans.HOST_PLANE:
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}

        def kept(event):
            return device or names[event.metadata_id].startswith(
                hostspans.PREFIXES)

        lines = [ln for ln in plane.lines
                 if (ln.name in (xplane.OPS_LINE, xplane.MODULES_LINE)
                     if device else any(kept(e) for e in ln.events))]
        new = out.planes.add(id=plane.id, name=plane.name)
        for ln in lines:
            line = new.lines.add(id=ln.id, name=ln.name,
                                 timestamp_ns=ln.timestamp_ns)
            for e in ln.events:
                if kept(e):
                    line.events.add(metadata_id=e.metadata_id,
                                    offset_ps=e.offset_ps,
                                    duration_ps=e.duration_ps)
                    new.event_metadata[e.metadata_id].id = e.metadata_id
                    new.event_metadata[e.metadata_id].name = names[
                        e.metadata_id]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"pruned {src} ({os.path.getsize(src)} bytes) to {dst} "
          f"({os.path.getsize(dst)} bytes)")
    print("XPLANE", xplane.reduce_trace(dst))
    print("HOSTSPANS", hostspans.reduce_trace(dst))


def main() -> int:
    if sys.argv[1:2] == ["--prune"]:
        prune(*sys.argv[2:4])
        return 0
    import jax
    import numpy as np

    from benchmark import common, hostspans, xplane
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        GatewayClient,
        ServingGateway,
    )

    net = MultiLayerNetwork(transformer_lm(
        n_in=VOCAB, width=64, n_layers=2, n_heads=2, n_classes=VOCAB,
        seed=3)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 128
    engine = DecodeEngine(net, n_slots=4, decode_chunk=4, seed=3,
                          paged_kv=True, block_tokens=16, kv_blocks=64,
                          use_flash_paged=False)
    rng = np.random.default_rng(3)

    def send(gw, jobs, gap_s):
        def one(n_prompt, n_new):
            client = GatewayClient(gw.address, timeout_s=300.0)
            for _ in client.stream(
                    rng.integers(0, VOCAB, n_prompt).tolist(), n_new):
                pass

        threads = [threading.Thread(target=one, args=job)
                   for job in jobs]
        for th in threads:
            th.start()
            time.sleep(gap_s)
        for th in threads:
            th.join()

    out = os.path.join(ROOT, "chiprun_out", "span_fixture")
    with ServingGateway(engine, keepalive_s=0.2) as gw:
        send(gw, [(8, 6), (16, 6), (32, 6)], 0.0)   # compiles
        send(gw, TRACED, 0.0)
        trace = common.SubTrace("span_fixture")
        trace.dir = out
        trace.start()
        send(gw, TRACED, 0.004)
        trace.stop()
    path = xplane.find_trace(out)
    print("trace", path, os.path.getsize(path), "bytes; device",
          jax.devices()[0].device_kind)
    shutil.copy(path, os.path.join(ROOT, "chiprun_out",
                                   "tiny_spans.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:4]:
                print("     ", repr(e.name)[:70], e.start_ns,
                      e.duration_ns)
    print("XPLANE", xplane.reduce_trace(path))
    red = hostspans.reduce_trace(path)
    print("HOSTSPANS", red)
    if red is not None:
        hostspans.log_table(red, trace.window_s)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
