"""An ``open_loop`` or ``closed_loop`` cell: the model served from the
paged KV pool behind the gateway, driven over localhost by the load
generator in a child process.

The objects are the ones ``cli/driver.py:gateway_from_args`` builds
(``DecodeEngine`` -> ``ServingGateway``), built directly because the
weights are the benchmark's and a saved model would carry Adam's
moments through a zip. ``correct`` is decided after the window has
closed, the pool is freed and the plain reference has run over a seeded
sample of the requests the window finished, the longest among them.
"""

from __future__ import annotations

import json
import numbers
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import common, loadgen, peaks, stats, traffic
from benchmark.common import log


def counters(engine) -> dict:
    """Every numeric entry of ``engine.stats``, whole: a counter the
    program gains reaches its reader with no edit here."""
    # int and float first: that check is the cheap one, and a traced
    # run makes it for every entry twice a round
    return {k: v for k, v in engine.stats.items()
            if isinstance(v, (int, float, numbers.Real))
            and not isinstance(v, bool)}


def moved(before: dict, after: dict) -> dict:
    """What was added to each counter that changed."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


class RoundProbe:
    """Wraps ``engine.step`` (traced runs only) and notes, after every
    round, when it ended, how many slots were live, their cached
    lengths, the pool blocks that slot and pending tables hold, and
    what the round added to each of the engine's counters
    (``counted``, the counters that moved)."""

    def __init__(self, engine):
        self.engine = engine
        self.rounds = []
        self._step = engine.step
        engine.step = self._wrapped

    def _wrapped(self, results=None):
        eng = self.engine
        before = counters(eng)
        out = self._step(results)
        counted = moved(before, counters(eng))
        tabs = [t for t in eng._kv_tabs if t is not None]
        live = sum(len(t.blocks) for t in tabs) + sum(
            len(p.tab.blocks) for p in eng._pending if p.tab is not None)
        self.rounds.append({
            "t": time.monotonic(), "decoded": "chunks" in counted,
            "counted": counted,
            "active": sum(1 for s in eng._slots if s is not None),
            "contexts": [t.length for t in tabs], "live_blocks": live})
        return out


def build_gateway(model, cfg: dict, seed: int):
    from deeplearning4j_tpu.serving import DecodeEngine, ServingGateway

    net = model.build_net(cfg, seed)
    log(f"net built: {model.describe(cfg)}; "
        f"{common.bytes_in_use() / 2**30:.2f} GiB in use")
    dep = dict(cfg["deployment"])
    dep.pop("why", None)
    flash = dep.pop("use_flash_paged", None)
    engine = DecodeEngine(net, seed=seed & 0x7FFFFFFF,
                          use_flash_paged=flash, **dep)
    return ServingGateway(engine, host="127.0.0.1", port=0).start()


def warm_up(gw, cfg: dict, mix: dict, seed: int) -> None:
    """One request through every prompt bucket the mix's clipped lengths
    can reach, long enough to run a decode round, all at once so that a
    full-width round runs too."""
    eng = gw.engine
    lo, hi = mix["prompt"].get("min", 1), mix["prompt"].get("max")
    if mix["prompt"]["dist"] == "fixed":
        lo = hi = mix["prompt"]["value"]
    lengths = sorted({min(hi, eng.scheduler.bucket_of(n))
                      for n in (lo, hi)}
                     | {b for b in (1 << e for e in range(3, 20))
                        if eng.scheduler.bucket_of(lo) <= b <= hi})
    rng = np.random.default_rng([seed, 99])
    host, port = gw.address.split("://", 1)[-1].rsplit(":", 1)
    deadline = time.monotonic() + 1500.0
    recs, threads = [], []
    for n in lengths:
        req = {"id": -1, "prompt": rng.integers(
            0, cfg["vocab_size"], n).tolist(),
            "max_new": eng.decode_chunk + 2}
        rec = loadgen.new_record(req, time.monotonic(), False)
        th = threading.Thread(
            target=loadgen.stream_one, daemon=True,
            args=(host, int(port), req["prompt"], req["max_new"],
                  deadline, rec, 1500.0))   # a cold run compiles here
        th.start()
        recs.append(rec)
        threads.append(th)
    for th in threads:
        th.join()
    bad = [r["error"] for r in recs if not r["ok"]]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")
    log(f"warmed prompt lengths {lengths}; compile counts "
        f"{eng.compile_counts()}")


def snapshot(engine) -> dict:
    snap = counters(engine)
    snap["compiles"] = sum(engine.compile_counts().values())
    return snap


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def pick_sample(records, schedule: dict, seed: int, n: int):
    """A seeded sample of the window's finished requests, the longest
    (prompt + served tokens) among them."""
    done = [r for r in stats.window_requests(records) if r["ok"]]
    if not done:
        return []
    by_id = {r["id"]: r for r in schedule["requests"]}
    done.sort(key=lambda r: r["id"])
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 77])
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :max(n - 1, 0)]]
    return [(by_id[r["id"]]["prompt"], r["tokens"]) for r in picks]


def measure(gw, schedule: dict, args, mix: dict, probe, trace):
    """Run the load generator against the gateway; returns (records,
    stats before, stats after, set-up seconds)."""
    eng = gw.engine
    child = subprocess.Popen(
        [sys.executable, os.path.join(common.HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        t_window = time.monotonic() + schedule["lead_in_s"] + 0.5
        setup_s = (time.perf_counter() - args.t0) + (
            t_window - time.monotonic())
        job = dict(schedule, address=gw.address, t0=t_window)
        child.stdin.write(json.dumps(job).encode())
        child.stdin.close()
        beat = loadgen.Heartbeat().start()
        sleep_until(t_window)
        before = snapshot(eng)
        if probe is not None:
            probe.window_from = time.monotonic()
        if trace is not None:
            sleep_until(t_window + float(mix.get("trace_after_s", 2.0)))
            trace.start()
            sleep_until(trace.t_start + float(mix.get("trace_seconds",
                                                      3.0)))
            trace.stop()
        sleep_until(t_window + schedule["window_s"])
        after = snapshot(eng)
        if probe is not None:
            probe.window_to = time.monotonic()
        raw = child.stdout.read()
        child.wait(timeout=schedule["drain_limit_s"] + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    out = json.loads(raw)
    mine, theirs = beat.stop(), out["heartbeat"]
    for who, hb in (("server", mine), ("generator", theirs)):
        at = (hb["stall_at"] - t_window) if hb["stall_at"] else None
        log(f"{who} process kept off the processor for at most "
            f"{hb['stall_max_ms']:.1f} ms (at {at} s of the window)")
    worst = sorted((r for r in out["records"] if r["sent"] is not None),
                   key=lambda r: r["due"] - r["sent"])[:3]
    log("latest sends: " + ", ".join(
        f"{1000 * (r['sent'] - r['due']):.1f} ms late at "
        f"{r['due'] - t_window:.2f} s" for r in worst))
    return out["records"], before, after, setup_s


def end_to_end(records) -> dict:
    return {"tpot_mean_ms": stats.tpot_mean_ms(records),
            "ttft_p90_ms": stats.ttft_percentile_ms(records, 90.0),
            "ttft_p50_ms": stats.ttft_percentile_ms(records, 50.0)}


def gap_numbers(gaps) -> dict:
    """The two numbers compared: the widest gap by which a served
    token's reference logit lies below the reference's best, and the
    mean gap over all served tokens of the sample (0 wherever the served
    token is the reference's own first choice)."""
    return {"served_logit_gap": float(gaps.max()),
            "served_gap_mean": float(gaps.mean())}


def check_outputs(model, samples, seed: int, cfg: dict) -> dict:
    """Each number compared, beside its limit."""
    limits = cfg["check"]["limits"]
    if not samples:
        return {k: {"value": float("inf"), "limit": v, "ok": False,
                    "tokens": 0} for k, v in limits.items()}
    gaps, _ = model.served_gaps(seed, cfg, samples)
    return {k: {"value": v, "limit": limits[k],
                "ok": bool(v <= limits[k]), "tokens": int(gaps.size)}
            for k, v in gap_numbers(gaps).items()}


def run(args, bench: dict, cell: dict, cfg: dict, mix: dict,
        model) -> int:
    common.need(model, common.SERVE_API)
    device = common.setup_jax(cell, args.rehearse)
    seed = args.seed
    schedule = traffic.serving_schedule(mix, seed, float(args.seconds),
                                        cfg["vocab_size"])
    log(f"offered in the window: {traffic.offered(schedule)}")
    gw = build_gateway(model, cfg, seed)
    trace = common.SubTrace(cell["name"]) if args.trace else None
    with common.stopped_at_exit(gw.close):
        eng = gw.engine
        t0 = time.perf_counter()
        warm_up(gw, cfg, mix, seed)
        log(f"warm-up {time.perf_counter() - t0:.1f}s; kv_blocks "
            f"{eng.kv_blocks}; {common.bytes_in_use() / 2**30:.2f} GiB "
            f"in use of {common.bytes_limit() / 2**30:.2f} "
            f"({time.perf_counter() - args.t0:.1f}s)")
        probe = RoundProbe(eng) if args.trace else None
        records, before, after, setup_s = measure(
            gw, schedule, args, mix, probe, trace)
        peak = common.memory_peak_bytes()
        health_ok = gw.failure is None
    n = stats.counts(records)
    late = [1000.0 * (r["sent"] - r["due"]) for r in records
            if r["sent"] is not None and r["due"] is not None]
    e2e = end_to_end(records) if n["attempted"] > n["failed"] else {}
    errors = sorted({r["error"] for r in records if r["error"]})
    log(f"window: {n}, e2e {e2e}, setup {setup_s:.2f}s, generator late "
        f"max {max(late):.2f} ms, peak {peak / 2**30:.2f} GiB, engine "
        f"delta {moved(before, after)}"
        + (f", errors {errors[:3]}" if errors else ""))

    # ---- correct: free the pool and the weights, then the reference --
    samples = pick_sample(records, schedule, seed,
                          int(cfg["check"]["sample_requests"]))
    if probe is not None:
        probe.engine = probe._step = None
    del eng, gw
    common.free_device_memory()
    log(f"program freed: {common.bytes_in_use() / 2**30:.2f} GiB in use")
    t0 = time.perf_counter()
    rows = check_outputs(model, samples, seed, cfg)
    for name, row in rows.items():
        log(f"compared {name}: {row['value']:.6g} (limit {row['limit']}) "
            f"over {row['tokens']} served tokens of {len(samples)} "
            f"requests, {'ok' if row['ok'] else 'NOT OK'}")
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    correct = bool(all(r["ok"] for r in rows.values()) and health_ok
                   and n["failed"] == 0 and n["attempted"] > 0)

    if args.trace:
        obs = {"kind": mix["kind"], "cell": cell["name"], "model": model,
               "cfg": cfg, "mix": mix,
               "records": records, "window_s": float(args.seconds),
               "before": before, "after": after,
               "rounds": [r for r in probe.rounds
                          if probe.window_from <= r["t"]
                          <= probe.window_to],
               "traced_rounds": [r for r in probe.rounds if r["decoded"]
                                 and trace.t_start <= r["t"]
                                 <= trace.t_stop],
               "late_ms": late, "stats": stats, "flops": model.flops,
               "trace": trace.reduce() if not args.rehearse else None,
               "trace_window_s": trace.window_s,
               "peaks": (peaks.peaks_of(device["kind"])
                         if not args.rehearse else None)}
        metrics = common.read_per_layer(bench, cell, obs, args.rehearse)
    elif args.rehearse:
        metrics = {}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in common.metrics_for(bench, cell, "end_to_end")
                   if m["name"] in values}
    device["memory_peak_bytes"] = peak
    print(common.result_line(correct, n["attempted"], n["failed"],
                             metrics, device, rows,
                             trace if not args.rehearse else None),
          flush=True)
    return 0
