"""Training telemetry: per-step phase clock, gradient-health outputs,
and the headless JSONL metrics sink (ISSUE 8).

The serving stack's flight recorder (serving/engine.py, ISSUE 7) made
every request's latency breakdown legible; this module is the TRAINING
half of the same discipline. Three pieces, deliberately tiny:

- :func:`grad_health` — global grad norm, update/param ratio, param
  norm, and nonfinite-grad count computed as EXTRA OUTPUTS inside the
  networks' existing jitted train steps. Because the health scalars are
  always traced into the step (attached listener or not), the
  telemetry-on and telemetry-off executables are the SAME executable:
  zero new compiles, zero retraces, bit-identical params by
  construction. The scalars ride back as lazy device arrays and are
  only fetched at the step's one existing host sync (the listener's
  score fetch).
- :class:`TrainTelemetry` — a host-side phase accumulator every network
  owns (``net.train_telemetry``): data-wait (iterator fetch), dispatch
  wall, step/example/token counts, and the latest health pytree. The
  fit loops stamp it with ~two ``perf_counter`` calls per step; nobody
  reads it unless a :class:`TracingIterationListener
  <deeplearning4j_tpu.optimize.listeners.TracingIterationListener>`
  (or other consumer) drains a window. Phases are disjoint
  sub-intervals of the window wall, so phase sums <= wall holds
  STRUCTURALLY, mirroring the serving _PhaseClock contract.
- :class:`MetricsLog` — a line-per-record JSONL sink for headless runs
  (no UiServer, no tracer): one ``json.dumps`` per listener fire,
  trivially greppable/pandas-loadable.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from deeplearning4j_tpu.profiler.scopes import scope

#: The five training histogram tracks (ISSUE 8 tentpole): latency-style
#: phases in seconds plus gradient-health value distributions.
TRAIN_HISTOGRAMS = (
    "train_step_s",
    "train_data_wait_s",
    "train_grad_norm",
    "train_update_ratio",
    "train_param_norm",
)

#: Host-sync wall also keeps a histogram so the latency report's live
#: mode can answer sync quantiles; it rides beside the five core tracks.
TRAIN_SYNC_HISTOGRAM = "train_sync_s"

#: ``# HELP`` text per training track (the serving SERVING_TRACK_HELP
#: counterpart), applied via ``Tracer.describe``.
TRAIN_TRACK_HELP: Dict[str, str] = {
    "train_step_s": "per-step wall time (window wall / steps)",
    "train_data_wait_s": "per-step host wait on the data iterator",
    "train_sync_s": "host-sync wall at the listener's score fetch",
    "train_grad_norm": "global L2 norm of the step gradient",
    "train_update_ratio":
        "L2 norm of the applied parameter delta / new param norm",
    "train_param_norm": "global L2 norm of the post-step parameters",
    "train_examples_per_sec": "training throughput over the last window",
    "train_tokens_per_sec":
        "token throughput over the last window (time-series batches)",
    "train_score": "latest training score (loss)",
    "train_steps_total": "cumulative training steps observed",
    "train_nonfinite_grads":
        "cumulative count of non-finite gradient elements seen",
    "train_early_stop": "early-stopping terminations fired",
}

#: Gradient-health leaf names, in the order every producer emits them.
HEALTH_KEYS = ("grad_norm", "update_ratio", "param_norm",
               "nonfinite_grads")

#: Norm-valued histograms span 1e-8 .. 1e4 (4 log buckets/decade): grad
#: and param norms roam far outside the latency default of 100us..100s.
VALUE_BOUNDS = tuple(10.0 ** (e / 4.0) for e in range(-32, 17))


@scope("update/health")
def grad_health(grads, params, new_params):
    """Gradient-health scalars, traced INSIDE the jitted train step.

    Returns ``{grad_norm, update_ratio, param_norm, nonfinite_grads}``
    as f32 device scalars. ``update_ratio`` uses the actually-applied
    delta (old minus new params), so it reflects the post-normalization
    post-LR update the step really took, not the raw gradient. All
    reductions accumulate in f32 so bf16 training reports stable norms.
    """
    import jax
    import jax.numpy as jnp

    def sumsq(tree):
        total = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(tree):
            total = total + jnp.sum(
                jnp.square(leaf.astype(jnp.float32)))
        return total

    g_leaves = jax.tree.leaves(grads)
    nonfinite = jnp.zeros((), jnp.float32)
    for leaf in g_leaves:
        nonfinite = nonfinite + jnp.sum(
            (~jnp.isfinite(leaf)).astype(jnp.float32))
    param_sq = sumsq(new_params)
    delta_sq = jnp.zeros((), jnp.float32)
    for old, new in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_params)):
        delta_sq = delta_sq + jnp.sum(jnp.square(
            old.astype(jnp.float32) - new.astype(jnp.float32)))
    param_norm = jnp.sqrt(param_sq)
    return {
        "grad_norm": jnp.sqrt(sumsq(grads)),
        "update_ratio": jnp.sqrt(delta_sq)
        / jnp.maximum(param_norm, 1e-12),
        "param_norm": param_norm,
        "nonfinite_grads": nonfinite,
    }


def host_grad_health(grad, x_old, x_new):
    """Host-side (numpy) variant for the line-search solver loop
    (optimize/solver.py): the solver is host-composed — it already
    fetches the score every iteration — so health there is plain numpy
    on the flat vectors, adding zero executables."""
    import numpy as np

    g = np.asarray(grad)
    new = np.asarray(x_new)
    param_norm = float(np.linalg.norm(new))
    return {
        "grad_norm": float(np.linalg.norm(g)),
        "update_ratio": float(
            np.linalg.norm(new - np.asarray(x_old))
            / max(param_norm, 1e-12)),
        "param_norm": param_norm,
        "nonfinite_grads": float(np.count_nonzero(~np.isfinite(g))),
    }


def fetch_health(health) -> Optional[Dict[str, List[float]]]:
    """Normalize a recorded health payload to ``{key: [floats]}``:
    accepts a dict of device/host scalars, a dict of [K] per-step
    arrays (the fit_scan window shape), a zero-arg callable producing
    either, or None. Flattening happens HERE, at the consumer's sync
    point — producers never pay a fetch."""
    import numpy as np

    if health is None:
        return None
    if callable(health):
        health = health()
    if health is None:
        return None
    out: Dict[str, List[float]] = {}
    for key, value in health.items():
        arr = np.asarray(value, dtype=np.float64).ravel()
        out[key] = [float(v) for v in arr]
    return out


class TrainTelemetry:
    """Host-side phase accumulator for one training loop.

    Every network owns one (``net.train_telemetry``). The fit loops add
    disjoint measured intervals — data-wait around the iterator fetch,
    dispatch wall around the jitted call — plus step/example/token
    counts and the step's health outputs. A consumer (the tracing
    listener) drains the window with :meth:`consume`; the window wall
    is measured at drain time, AFTER the consumer's score sync, so
    ``data_wait + dispatch + sync <= wall`` is guaranteed by interval
    containment rather than by luck.
    """

    __slots__ = ("wall_start", "data_wait_s", "dispatch_s", "steps",
                 "examples", "tokens", "health", "_active")

    def __init__(self) -> None:
        self._reset(time.perf_counter())

    def _reset(self, now: float) -> None:
        self.wall_start = now
        self.data_wait_s = 0.0
        self.dispatch_s = 0.0
        self.steps = 0
        self.examples = 0
        self.tokens = 0
        self.health: Any = None
        self._active = False

    def _anchor(self, elapsed: float) -> None:
        """Re-anchor the wall origin at the START of a window's first
        measured event (``elapsed`` seconds ago). Without this, the
        first window's wall would stretch back to network CONSTRUCTION
        — dataset downloads and conf building between init and the
        first fit would read as step time."""
        if not self._active:
            self.wall_start = time.perf_counter() - elapsed
            self._active = True

    def add_data_wait(self, seconds: float) -> None:
        self._anchor(seconds)
        self.data_wait_s += seconds

    def record_step(self, dispatch_s: float = 0.0, steps: int = 1,
                    examples: int = 0, tokens: int = 0,
                    health=None) -> None:
        """Stamp one dispatch: ``steps`` optimizer iterations covered
        (K for a fused fit_scan window), batch sizes, and the step's
        health outputs (device pytree, [K]-leaf pytree, or a lazy
        callable — kept un-fetched until a consumer drains)."""
        self._anchor(dispatch_s)
        self.dispatch_s += dispatch_s
        self.steps += steps
        self.examples += examples
        self.tokens += tokens
        if health is not None:
            self.health = health

    def consume(self) -> Optional[Dict[str, Any]]:
        """Drain the window: returns ``{wall_s, data_wait_s,
        dispatch_s, steps, examples, tokens, health}`` and starts a new
        window. None when no step landed since the last drain (a
        listener firing twice at one iteration must not emit an empty
        sample) — an empty drain leaves the window UNTOUCHED, so
        accrued data-wait and the wall origin survive into the window
        that finally carries a step (phase sums <= wall stays an
        interval-containment fact)."""
        now = time.perf_counter()
        if self.steps == 0:
            return None
        snap = {
            "wall_s": now - self.wall_start,
            "data_wait_s": self.data_wait_s,
            "dispatch_s": self.dispatch_s,
            "steps": self.steps,
            "examples": self.examples,
            "tokens": self.tokens,
            "health": self.health,
        }
        self._reset(now)
        return snap


def batch_counts(features, ids: bool = False) -> tuple:
    """(examples, tokens) of one batch: tokens is B*T for EXACTLY
    rank-3 ([B, C, T]) time-series features, or for ``[B, T]`` token
    ids (``ids``: the net's ``takes_token_ids``); any other rank (2-D
    dense, 4-D conv images) counts tokens == examples — a [B, C, H, W]
    image batch must not report B*H as a token rate."""
    shape = getattr(features, "shape", None)
    if not shape:
        return 0, 0
    examples = int(shape[0])
    if ids and len(shape) == 2:
        return examples, examples * int(shape[1])
    tokens = examples * int(shape[2]) if len(shape) == 3 else examples
    return examples, tokens


def window_counts(shape, ids: bool = False) -> tuple:
    """(steps, examples, tokens) of one stacked fit_scan window
    ([K, B, ...]; tokens = K*B*T only for exactly [K, B, C, T] time
    series or [K, B, T] token ids, mirroring :func:`batch_counts`).
    Shape-only — never slices a device array (a host-side ``feats[0]``
    would dispatch a gather executable just to read a shape)."""
    k = int(shape[0])
    examples = k * int(shape[1])
    if ids and len(shape) == 3:
        return k, examples, examples * int(shape[2])
    tokens = (examples * int(shape[3]) if len(shape) == 4
              else examples)
    return k, examples, tokens


def emit_step_span(tracer, dispatch_s: float,
                   args: Dict[str, Any]) -> None:
    """One ``train.parallel_step`` complete span ending now, carrying
    the trainer's mesh-config ``args`` — the shared emitter behind
    every parallel trainer's per-step Perfetto track."""
    if tracer is None:
        return
    dur_us = dispatch_s * 1e6
    tracer.complete("train.parallel_step", tracer.now_us() - dur_us,
                    dur_us, **args)


def mesh_args(mesh, trainer: str, **extra) -> Dict[str, Any]:
    """JSON-safe span annotation for a parallel trainer's step spans:
    mesh shape by axis name plus the trainer kind and any active-axis
    assignments — what makes a MULTICHIP sweep's per-combo Chrome
    traces comparable side by side in Perfetto."""
    args: Dict[str, Any] = {
        "trainer": trainer,
        "mesh": {str(name): int(size)
                 for name, size in dict(mesh.shape).items()},
        "devices": int(mesh.devices.size),
    }
    for key, value in extra.items():
        if value is not None:
            args[key] = value
    return args


class MetricsLog:
    """Append-only JSONL metrics sink for headless training runs.

    One JSON object per line; ``write`` is thread-safe and flushes so a
    crashed run keeps every completed record. Reader side:
    ``MetricsLog.read(path)`` returns the parsed records (skipping a
    torn final line, which only an OS-level crash can leave).
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._f = open(path, "a", encoding="utf-8")

    def write(self, record: Dict[str, Any]) -> None:
        line = json.dumps(record, sort_keys=True)
        with self._lock:
            if self._f.closed:
                raise ValueError(f"MetricsLog {self.path} is closed")
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    def __enter__(self) -> "MetricsLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        records = []
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    break  # torn tail from a hard crash
        return records
