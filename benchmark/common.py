"""What every cell's runner shares: finding a cell's files by the names
in ``BENCHMARK.json``, the model's adapter, the device gate, the traced
sub-window, the per-layer readers and the one result line."""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what a run leaves behind (traces) lives here, inside the checkout
OUT_DIR = os.path.join(ROOT, ".bench_out")


#: a name as ``BENCHMARK.json`` allows it: it is joined into a path
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
#: what a runner calls of a model's adapter (``models/__init__.py``)
TRAIN_API = ("build_net", "describe", "encode_batch", "start_params",
             "train_reference", "flops")
SERVE_API = ("build_net", "describe", "served_gaps", "flops")


class Refused(Exception):
    """The run may not start (no accelerator, too few chips, unknown
    cell, a configuration that names no model): exit code 2, no result
    line."""


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


def load_by_path(kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded by its path: a later PR
    brings one as a new file and edits none."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not NAME.match(name) or not os.path.isfile(path):
        raise Refused(f"no file {kind}/{name}.py under {HERE}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(cfg: dict, path: str):
    """The adapter of the model a configuration names. A configuration
    without ``model`` is refused: there is no default model."""
    name = cfg.get("model")
    if not isinstance(name, str):
        raise Refused(f"{path} states no \"model\": the adapter under "
                      "benchmark/models/ that builds and checks it")
    return load_by_path("models", name)


def need(model, api) -> None:
    """Refuse a model whose adapter lacks what this kind of cell calls."""
    missing = [name for name in api if not hasattr(model, name)]
    if missing:
        raise Refused(f"{model.__file__} lacks {missing}, which this "
                      "kind of cell calls (benchmark/models/__init__.py)")


def find_cell(bench: dict, name: str, rehearse: bool):
    """(cell, configuration, traffic mix, model adapter) for a
    workload's name. Under ``rehearse`` each file's ``rehearsal`` group
    overrides its sizes."""
    from benchmark import traffic

    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: "
                      f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    model = load_model(cfg, conf["file"])
    mix = traffic.load(cell["traffic"])
    if rehearse:
        cfg = overlay(cfg, cfg.get("rehearsal", {}))
        mix = overlay(mix, mix.get("rehearsal", {}))
    return cell, cfg, mix, model


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
    return load_by_path("metrics", name).read


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
# the traced sub-window
# ---------------------------------------------------------------------
def trace_dir(cell_name: str) -> str:
    """Where a cell's traced run leaves its trace."""
    return os.path.join(OUT_DIR, "trace", cell_name)


class SubTrace:
    """Profile a stretch of the window and reduce it. ``start`` and
    ``stop`` are called from the thread that drives the window."""

    def __init__(self, cell_name: str):
        self.dir = trace_dir(cell_name)
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
                device: dict, compared: dict,
                trace: SubTrace = None) -> str:
    """The run's one result line. ``compared`` (each number that decided
    ``correct``, with its limit) comes last in it and, before it, as the
    last lines of standard error: what is kept of a run that was not
    correct."""
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
    for name, row in compared.items():
        print(f"compared {name}: {row['value']:.6g} (limit "
              f"{row['limit']})", file=sys.stderr, flush=True)
    # a number that is not finite (nothing to compare) has no JSON form
    out["compared"] = {
        k: {"value": row["value"] if math.isfinite(row["value"]) else None,
            "limit": row["limit"]} for k, row in compared.items()}
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
