"""A ``train_job`` cell: one training job, steps back to back.

Set-up builds ONE object, the program's net with its compiled step and
optimizer state, drives it through its first steps by the window's own
call (``fit_scan``) and feed, reading what ``correct`` needs, and hands
the same object to the window. After the window the program's state is
freed and the plain reference follows the same first steps.
"""

from __future__ import annotations

import collections
import statistics
import time

import numpy as np

from benchmark import common, norms, peaks, traffic
from benchmark.common import log
from benchmark.loadgen import Heartbeat


class Feed:
    """The job's input pipeline: cycles through the seeded token pool,
    encodes a batch on the host as the model takes it (the adapter's
    ``encode_batch``) and puts it on the device. Counts the time the
    trainer waited for it."""

    def __init__(self, pool: np.ndarray, model, cfg: dict,
                 scan_steps: int):
        self.pool, self.k = pool, scan_steps
        self.model, self.cfg = model, cfg
        self.at = 0
        self.wait_s = 0.0

    def next(self):
        import jax

        t0 = time.perf_counter()
        feats, labels = [], []
        for _ in range(self.k):
            f, y = self.model.encode_batch(
                self.pool[self.at % len(self.pool)], self.cfg)
            feats.append(f)
            labels.append(y)
            self.at += 1
        out = (jax.device_put(np.stack(feats)),
               jax.device_put(np.stack(labels)))
        self.wait_s += time.perf_counter() - t0
        return out


def program_norms(net, model, seed: int, cfg: dict, what: str,
                  b1: float = 0.0):
    """Norms by leaf read from the program's state. ``grad``: the first
    gradient as Adam got it, ``m / (1 - b1)`` after one step. ``delta``:
    each leaf's distance from its seeded start, the start made again a
    layer at a time (the adapter's ``start_params``)."""
    if what == "grad":
        tree = {si: st["m"] for si, st in net.updater_state.items()}
        return {k: v / (1.0 - b1) for k, v in
                norms.flat_norms(norms.leaf_norms(tree)).items()}
    out = {}
    for start in model.start_params(seed, cfg):
        out.update(norms.flat_norms(norms.delta_norms(
            {k: net.params[k] for k in start}, start)))
    return out


def worst_leaf_gap(program: dict, ref: dict) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    return max(abs(program[k] - ref[k]) / max(ref[k], floor) for k in ref)


def compare(program: dict, ref: dict, limits: dict) -> dict:
    """Each number compared, beside its limit."""
    rows = {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(program["losses"], ref["losses"])),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"],
                                        ref["grad_norms"]),
        "delta_norm_gap": worst_leaf_gap(program["delta_norms"],
                                         ref["delta_norms"]),
    }
    return {k: {"value": v, "limit": limits[k], "ok": bool(v <= limits[k])}
            for k, v in rows.items()}


def first_steps(net, model, feed: Feed, seed: int, cfg: dict,
                n_steps: int, b1: float) -> dict:
    """Drive the program's object through its first steps by the
    window's call and feed; read each loss, the first gradient's norms
    and the parameters' change."""
    out = {"losses": [], "grad_norms": None}
    for s in range(n_steps):
        feats, labels = feed.next()
        scores = np.asarray(net.fit_scan(feats, labels), np.float64)
        out["losses"].extend(float(v) for v in scores)
        if s == 0:
            out["grad_norms"] = program_norms(net, model, seed, cfg,
                                              "grad", b1)
    out["losses"] = out["losses"][:n_steps]
    out["delta_norms"] = program_norms(net, model, seed, cfg, "delta")
    return out


def run(args, bench: dict, cell: dict, cfg: dict, mix: dict,
        model) -> int:
    common.need(model, common.TRAIN_API)
    device = common.setup_jax(cell, args.rehearse)
    import jax

    seed, seconds = args.seed, float(args.seconds)
    opt = cfg["optimizer"]
    k = int(mix["scan_steps"])
    if k != 1:
        raise ValueError("the gradient of the first step is read from the "
                         "optimizer's state after one step: scan_steps "
                         "has to be 1")
    log(f"device ready ({time.perf_counter() - args.t0:.1f}s)")
    pool = traffic.train_pool(mix, seed, cfg["vocab_size"])
    log(f"token pool drawn ({time.perf_counter() - args.t0:.1f}s)")
    net = model.build_net(cfg, seed, optimizer=opt)
    log(f"net built: {model.describe(cfg)}; "
        f"{common.bytes_in_use() / 2**30:.2f} GiB in use "
        f"({time.perf_counter() - args.t0:.1f}s)")
    feed = Feed(pool, model, cfg, k)
    program = first_steps(net, model, feed, seed, cfg,
                          int(mix["checked_steps"]),
                          opt["adam_mean_decay"])
    log(f"first steps done, losses {program['losses']} "
        f"({time.perf_counter() - args.t0:.1f}s); "
        f"{common.bytes_in_use() / 2**30:.2f} GiB in use")
    step_fn = net._train_steps_scan
    compiles_before = int(step_fn._cache_size())
    trace = common.SubTrace(cell["name"]) if args.trace else None
    trace_from = float(mix.get("trace_after_s", 1.0))
    trace_to = trace_from + float(mix.get("trace_seconds", 3.0))
    tokens_per_call = k * mix["batch"] * mix["seq_len"]
    ahead = int(mix.get("run_ahead_steps", 1))

    # ---- the window: steps back to back, the host ``ahead`` steps ----
    # ---- before the device, so that a stalled host idles no chip  ----
    feed.wait_s = 0.0
    calls = 0
    pending = collections.deque()
    beat = Heartbeat().start()
    log("window opens")
    setup_s = time.perf_counter() - args.t0
    t_start, t_start_mono = time.perf_counter(), time.monotonic()
    while True:
        now = time.perf_counter() - t_start
        if now >= seconds:
            break
        if trace is not None:
            if trace.t_start is None and now >= trace_from:
                trace.start()
            elif (trace.t_start is not None and trace.t_stop is None
                  and now >= trace_to):
                jax.block_until_ready(list(pending))
                trace.stop()
        feats, labels = feed.next()
        scores = net.fit_scan(feats, labels)
        pending.append(scores)
        if len(pending) > ahead:             # bound the run-ahead
            jax.block_until_ready(pending.popleft())
        calls += 1
    jax.block_until_ready(list(pending))
    window_s = time.perf_counter() - t_start
    stall = beat.stop()
    if trace is not None and trace.t_start is not None \
            and trace.t_stop is None:
        trace.stop()
    last_loss = float(np.asarray(scores)[-1])
    tok_per_s = calls * tokens_per_call / window_s
    compiles_after = int(step_fn._cache_size())
    peak = common.memory_peak_bytes()
    log(f"window {window_s:.3f}s, {calls} steps, {tok_per_s:.1f} tokens/s,"
        f" data wait {feed.wait_s:.3f}s, last loss {last_loss:.4f}, "
        f"peak {peak / 2**30:.2f} GiB")
    at = stall["stall_at"] - t_start_mono if stall["stall_at"] else 0.0
    log(f"process kept off the processor for at most "
        f"{stall['stall_max_ms']:.1f} ms (at {at:.2f} s of the window), "
        f"{ahead} steps of run-ahead")

    # ---- correct: free the program, then follow it with the reference
    del net, step_fn, pending, scores, feats, labels
    common.free_device_memory()
    t0 = time.perf_counter()
    ref = model.train_reference(
        seed, cfg, opt, pool[:int(mix["checked_steps"])], "highest")
    rows = compare(program, ref, cfg["check"]["limits"])
    finite = bool(np.isfinite(last_loss))
    for name, row in rows.items():
        log(f"compared {name}: {row['value']:.6g} (limit {row['limit']}) "
            f"{'ok' if row['ok'] else 'NOT OK'}")
    log(f"last loss finite: {finite}; reference took "
        f"{time.perf_counter() - t0:.1f}s")
    correct = finite and all(r["ok"] for r in rows.values())

    if args.trace:
        obs = {"kind": "train_job", "cell": cell["name"], "model": model,
               "cfg": cfg, "mix": mix,
               "window_s": window_s, "steps": calls * k,
               "tokens_per_s": tok_per_s, "data_wait_s": feed.wait_s,
               "compiles_before": compiles_before,
               "compiles_after": compiles_after,
               "trace": trace.reduce() if not args.rehearse else None,
               "trace_window_s": trace.window_s,
               "peaks": (peaks.peaks_of(device["kind"])
                         if not args.rehearse else None),
               "flops": model.flops}
        metrics = common.read_per_layer(bench, cell, obs, args.rehearse)
    elif args.rehearse:
        metrics = {}
    else:
        values = {"train_tok_per_s": tok_per_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in common.metrics_for(bench, cell, "end_to_end")}
    device["memory_peak_bytes"] = peak
    print(common.result_line(correct, calls, 0, metrics, device, rows,
                             trace if not args.rehearse else None),
          flush=True)
    return 0
