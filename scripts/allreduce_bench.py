"""Distributed step-time decomposition (BASELINE row 5).

The reference's Spark loop moves params through the DRIVER each round
(SparkDl4jMultiLayer.java:301-383: broadcast :307/:314, per-partition
fit :349, accumulator sum :355-359 — an O(N)-through-one-process
reduction). The TPU-native replacement is one fused XLA program:
shard_map(compute grads) + psum over the mesh, with no host round trip.
This script measures both the DECOMPOSED phases (fan-out / compute /
reduce, each as its own dispatch, analogous to the reference's phase
structure) and the fused ParallelTrainer step that replaces them,
emitting one JSON line bench.py re-emits as a bench row.

Runs on the 8-virtual-device CPU mesh — a CPU proxy for the call
pattern, not a device number (the mesh/collective code is the same on
real chips). Invoked by bench.py as a subprocess; it forces the CPU
platform and so never asks for the chip its parent holds.
"""

from __future__ import annotations

import json
import time

import numpy as np


def main():
    import os

    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.mnist import mnist_dataset
    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    batch = 2048
    mesh = make_mesh(MeshSpec({"dp": 8}))
    ds = mnist_dataset(train=True, num_examples=batch)
    feats = np.asarray(ds.features, np.float32)
    labels = np.asarray(ds.labels, np.float32)

    net = MultiLayerNetwork(mlp()).init()
    trainer = ParallelTrainer(net, mesh, dp_axis="dp")

    # --- phase kernels (each its own dispatch, like the reference's
    # broadcast / executor-fit / accumulator phases) ---
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("dp"))

    def fan_out():
        p = jax.device_put(
            jax.tree.map(np.asarray, net.params), rep)
        f = jax.device_put(feats, row)
        y = jax.device_put(labels, row)
        jax.block_until_ready((p, f, y))
        return p, f, y

    params_r, feats_s, labels_s = fan_out()

    # Per-shard UNREDUCED gradients (shard_map, no psum): each device
    # computes grads on its batch shard only, stacked on a leading dp
    # axis — the executor-local fit of the reference's phase structure.
    # A plain jitted grad would let GSPMD fuse the all-reduce INTO the
    # compute phase and the decomposition would time a no-op reduce.
    from jax import shard_map
    from jax.sharding import PartitionSpec

    def _local_grads(p, f, y):
        g = jax.grad(
            lambda pp: net._loss_fn(pp, {}, None, f, y, None, None)[0]
        )(p)
        return jax.tree.map(lambda a: a[None], g)

    grad_fn = jax.jit(shard_map(
        _local_grads, mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec("dp"),
                  PartitionSpec("dp")),
        out_specs=PartitionSpec("dp"),
        check_vma=False))

    @jax.jit
    def reduce_mean(g):
        # the actual cross-device reduction (the accumulator-sum +
        # divide of the reference loop, as one XLA all-reduce)
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                jnp.mean(a, axis=0), rep), g)

    def timed(fn, n=9):
        # 9 trials, inner-quartile trimmed median: CPU-host scheduling
        # jitter put r4's min-max spread at 1.7x (review weak #2)
        fn()  # warm/compile
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append((time.perf_counter() - t0) * 1e3)
        core = sorted(ts)[2:-2]
        return float(np.median(core)), [round(min(core), 3),
                                        round(max(core), 3)]

    t_fan, s_fan = timed(lambda: fan_out())
    t_comp, s_comp = timed(lambda: grad_fn(params_r, feats_s, labels_s))
    grads = grad_fn(params_r, feats_s, labels_s)
    t_red, s_red = timed(lambda: reduce_mean(grads))

    dsd = DataSet(feats, labels)
    trainer.fit(dsd)  # warm/compile the fused step

    def fused():
        trainer.fit(dsd)
        jax.block_until_ready(net.params)

    t_fused, s_fused = timed(fused)

    print(json.dumps({
        "metric": "dp8_allreduce_step_time",
        "value": round(t_fused, 3),
        "unit": "ms/step (VIRTUAL 8-CPU-device mesh: collective-decomposition correctness artifact, NOT a chip perf figure; trimmed spread)",
        "vs_baseline": None,
        "spread": s_fused,
        "trials": 9,
        "decomposition_ms": {
            "fan_out": round(t_fan, 3),
            "compute": round(t_comp, 3),
            "reduce": round(t_red, 3),
            "phased_total": round(t_fan + t_comp + t_red, 3),
        },
    }))


if __name__ == "__main__":
    main()
