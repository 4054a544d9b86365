"""What every cell's runner shares: finding a cell's files by the names
in ``BENCHMARK.json``, the device gate, the program's net with the
benchmark's weights, the traced sub-window, the per-layer readers and
the one result line."""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what a run leaves behind (traces) lives here, inside the checkout
OUT_DIR = os.path.join(ROOT, ".bench_out")


class Refused(Exception):
    """The run may not start (no accelerator, too few chips, unknown
    cell): exit code 2, no result line."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------
def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys, nested groups merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlay(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def find_cell(bench: dict, name: str, rehearse: bool):
    """(cell, configuration, traffic mix) for a workload's name. Under
    ``rehearse`` each file's ``rehearsal`` group overrides its sizes."""
    from benchmark import traffic

    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: "
                      f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load(cell["traffic"])
    if rehearse:
        cfg = overlay(cfg, cfg.get("rehearsal", {}))
        mix = overlay(mix, mix.get("rehearsal", {}))
    return cell, cfg, mix


def metrics_for(bench: dict, cell: dict, group: str) -> list:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) this
    cell reports: those that list it, and those that list no cells and
    move (or are) a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def load_reader(name: str):
    """The reader ``metrics/<name>.py``: a module with ``read(obs)``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('-', '_').replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(bench: dict, cell: dict, obs: dict,
                   counts_only: bool = False) -> dict:
    """Every per-layer metric of the cell whose reader found something
    to read. ``counts_only`` (the rehearsal) keeps what the program
    counts and drops every time, rate and share."""
    out = {}
    for m in metrics_for(bench, cell, "per_layer"):
        if counts_only and m["source"] != "program_counter":
            continue
        value = load_reader(m["name"])(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------
def setup_jax(cell: dict, rehearse: bool) -> dict:
    """Place the compile cache, then refuse unless JAX offers the chips
    the cell asks for. Returns the device as JAX reports it."""
    import jax

    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    # small programs too: every run finds all of them after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    backend = jax.default_backend()
    devices = jax.devices()
    if not rehearse:
        if backend != "tpu":
            raise Refused(f"jax's default backend is {backend!r}, not "
                          "'tpu': no accelerator, nothing to measure "
                          "(--rehearse runs the tiny rehearsal)")
        if len(devices) < cell["chips"]:
            raise Refused(f"{len(devices)} chips, the cell asks for "
                          f"{cell['chips']}")
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"device {info}; compile cache {cache_dir}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def bytes_in_use() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices())


def bytes_limit() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get(
        "bytes_limit", 0))


def free_device_memory() -> None:
    """Free everything the process holds on the device. Called once the
    window has closed and its numbers are on the host: every live array
    is deleted outright, because a reference kept by a thread that is
    still winding down (an HTTP handler of a dropped lead-out request)
    would otherwise keep the whole pool alive under the reference."""
    import jax

    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------
# the program's net, holding the benchmark's weights
# ---------------------------------------------------------------------
def build_net(cfg: dict, seed: int, optimizer: dict = None):
    """The program's flagship net at the configuration's sizes, with the
    weights ``weights.py`` makes from the seed. With ``optimizer`` the
    net gets its updater state (a training job); without, none (a
    served model carries no moments)."""
    import jax.numpy as jnp

    from benchmark import weights
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("the program's block has a feed-forward of four "
                         "times the width; the configuration says "
                         f"{cfg['n_inner']} for {cfg['n_embd']}")
    opt = optimizer or {}
    conf = transformer_lm_flagship(
        vocab=cfg["vocab_size"], width=cfg["n_embd"],
        n_layers=cfg["n_layer"], n_heads=cfg["n_head"],
        lr=opt.get("learning_rate", 3e-4),
        warmup_steps=opt.get("lr_warmup_steps", 100),
        total_steps=opt.get("lr_total_steps", 1000),
        seed=seed & 0x7FFFFFFF)
    for c in conf.confs:
        c.compute_dtype = cfg["compute_dtype"]
        for key in ("lr_min_fraction", "adam_mean_decay",
                    "adam_var_decay", "epsilon"):
            if key in opt:
                setattr(c, key, opt[key])
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = cfg["n_positions"]
    net = MultiLayerNetwork(conf)
    # adopt the seeded weights in place of init(): init() would draw its
    # own leaf by leaf and allocate Adam's moments for a served model
    net.params = weights.make_params(
        seed, cfg["vocab_size"], cfg["n_embd"], cfg["n_inner"],
        cfg["n_layer"])
    net.state = {}
    net.updater_state = {
        str(i): (upd.init(net.params[str(i)]) if optimizer else {})
        for i, upd in enumerate(net._updaters)}
    net._initialized = True
    if net._compute_dtype != jnp.dtype(cfg["compute_dtype"]):
        raise ValueError(f"the net computes in {net._compute_dtype}, the "
                         f"configuration states {cfg['compute_dtype']}")
    return net


# ---------------------------------------------------------------------
# the traced sub-window
# ---------------------------------------------------------------------
class SubTrace:
    """Profile a stretch of the window and reduce it. ``start`` and
    ``stop`` are called from the thread that drives the window."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.t_start = self.t_stop = None
        self.reduction = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.monotonic()

    def stop(self) -> None:
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    @property
    def window_s(self):
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start

    def reduce(self):
        from benchmark import xplane

        path = xplane.find_trace(self.dir)
        if path is None:
            return None
        t0 = time.perf_counter()
        self.reduction = xplane.reduce_trace(path)
        log(f"trace {path} ({os.path.getsize(path) / 2**20:.1f} MiB) "
            f"reduced in {time.perf_counter() - t0:.1f}s")
        return self.reduction


# ---------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------
def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, trace: SubTrace = None) -> str:
    from benchmark import xplane

    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics,
           "device": dict(device)}
    red = trace.reduction if trace is not None else None
    if red is not None:
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": xplane.top(red["ops"]),
                            "idle_gaps": xplane.top(red["gaps"])}
    return json.dumps(out)


@contextlib.contextmanager
def stopped_at_exit(*closers):
    """Run the body, then every closer, whatever happened."""
    try:
        yield
    finally:
        for close in closers:
            try:
                close()
            except Exception as e:  # boundary: report, keep closing
                print(f"[bench] close failed: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
