"""Native-vs-Python transformer decode latency (round-4 review item 7).

Exports the KV-cache decode step of the width-256 transformer through
the C++ PJRT client (compile once, cache device-resident) and measures
per-token decode latency against the jax rnn_time_step path on the same
chip. Three processes ONE AFTER ANOTHER, mirroring
tests/test_pjrt_native_decode.py: export (jax on CPU), native run (no
jax backend), jax run. This parent never touches jax, so each child in
turn is the only process holding the chip — which is why the row is a
command of its own and not a child of bench.py.

Run: python scripts/native_decode_bench.py [--steps 64]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPORT = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.native_rt.pjrt import (
        export_decode_step_for_native)

    net = MultiLayerNetwork(transformer_lm(
        n_in=64, width=256, n_layers=4, n_heads=8, n_classes=64,
        seed=7)).init()
    # serving window matched to the bench_decode row (2048 tokens);
    # width stays 256: the export bakes the weights into the program
    # as constants (~400 MB of f32 at width 1024 — ROADMAP D12)
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 2048
    code, copts, template, _ = export_decode_step_for_native(net)
    d = sys.argv[1]
    open(d + "/dec.vhlo", "wb").write(code)
    open(d + "/dec_copts.pb", "wb").write(copts)
    np.savez(d + "/cache0.npz", *template)
    net.save(d + "/net.zip")
    print("EXPORTED", len(code))
""") % (REPO,)

NATIVE = textwrap.dedent("""
    import sys, time, json
    sys.path.insert(0, %r)
    import numpy as np
    from deeplearning4j_tpu.native_rt.pjrt import (
        CompiledProgram, PjrtClient, buffer_from_host, tpu_plugin_path)

    d, steps = sys.argv[1], int(sys.argv[2])
    code = open(d + "/dec.vhlo", "rb").read()
    copts = open(d + "/dec_copts.pb", "rb").read()
    z = np.load(d + "/cache0.npz")
    cache0 = [z[k] for k in z.files]
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(steps, 1, 64, 1)).astype(np.float32)

    plugin = tpu_plugin_path()
    if plugin is None:
        raise SystemExit("no TPU chip on this host")
    with PjrtClient(plugin, "") as client:
        t0 = time.perf_counter()
        prog = CompiledProgram(client, code, copts)
        t_compile = time.perf_counter() - t0
        cache = [buffer_from_host(client, c) for c in cache0]
        # warm
        inp = buffer_from_host(client, xs[0])
        res = prog.execute([inp] + cache)
        inp.destroy()
        res[0].to_host()
        res[0].destroy()
        for b in cache:
            b.destroy()
        cache = res[1:]
        ts = []
        for x in xs:
            t0 = time.perf_counter()
            inp = buffer_from_host(client, x)
            res = prog.execute([inp] + cache)
            _ = res[0].to_host()  # the served logits
            ts.append(time.perf_counter() - t0)
            inp.destroy()
            res[0].destroy()
            for b in cache:
                b.destroy()
            cache = res[1:]
        prog.destroy()
    ts = np.asarray(ts) * 1e3
    print("NATIVE_RESULT " + json.dumps({
        "compile_s": round(t_compile, 2),
        "median_ms": round(float(np.median(ts)), 2),
        "p90_ms": round(float(np.percentile(ts, 90)), 2),
        "tokens_per_sec": round(1000.0 / float(np.median(ts)), 1)}))
""") % (REPO,)

JAXRUN = textwrap.dedent("""
    import sys, time, json
    sys.path.insert(0, %r)
    import numpy as np
    import jax
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        raise SystemExit("jax found no TPU: %%s" %% jax.default_backend())
    d, steps = sys.argv[1], int(sys.argv[2])
    net = MultiLayerNetwork.load(d + "/net.zip")
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(steps, 1, 64, 1)).astype(np.float32)
    net.rnn_clear_previous_state()
    np.asarray(net.rnn_time_step(xs[0]))  # compile + warm
    ts = []
    for x in xs:
        t0 = time.perf_counter()
        _ = np.asarray(net.rnn_time_step(x))
        ts.append(time.perf_counter() - t0)
    ts = np.asarray(ts) * 1e3
    print("JAX_RESULT " + json.dumps({
        "median_ms": round(float(np.median(ts)), 2),
        "p90_ms": round(float(np.percentile(ts, 90)), 2),
        "tokens_per_sec": round(1000.0 / float(np.median(ts)), 1)}))
""") % (REPO,)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    with tempfile.TemporaryDirectory() as d:
        r = subprocess.run([sys.executable, "-c", EXPORT, d], env=env,
                           capture_output=True, timeout=300, text=True)
        assert r.returncode == 0, r.stderr[-1500:]
        print(r.stdout.strip())
        r = subprocess.run(
            [sys.executable, "-c", NATIVE, d, str(args.steps)],
            env=env, capture_output=True, timeout=600, text=True)
        assert r.returncode == 0, (r.stdout[-300:], r.stderr[-1500:])
        print(r.stdout.strip())
        r = subprocess.run(
            [sys.executable, "-c", JAXRUN, d, str(args.steps)],
            env=env, capture_output=True, timeout=600, text=True)
        assert r.returncode == 0, (r.stdout[-300:], r.stderr[-1500:])
        print([ln for ln in r.stdout.splitlines()
               if "JAX_RESULT" in ln][0])


if __name__ == "__main__":
    main()
