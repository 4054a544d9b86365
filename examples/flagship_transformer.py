"""The converging flagship: a width-1024 pre-LN transformer trained to
the analytic entropy floor of a Markov language — the configuration
an earlier round's bench.py gates at >= 40% MFU (measures 55-69% on a v5e chip depending
on width).

Demonstrates the round-4 pieces working together:
- ``zoo.transformer_lm_flagship``: TransformerBlock stack (attention +
  gelu FFN + residuals), final LayerNormalization, Adam with
  linear-warmup + cosine lr (``lr_policy="warmup_cosine"``).
- bf16 compute with f32 master params and f32 output head.
- ``datasets.markov``: a synthetic language whose OPTIMAL loss is
  known in closed form, so "converged" is a theorem, not a vibe.
- Optional dp x pp x tp mesh training via
  ``HomogeneousPipelineTrainer`` (run with --mesh on >= 8 devices,
  e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU).

Run: python examples/flagship_transformer.py [--width 512] [--mesh]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

if os.environ.get("DL4J_EXAMPLES_PLATFORM", "cpu") == "cpu":
    # --xla_force_host_platform_device_count only multiplies CPU
    # devices; force the CPU backend so the simulated mesh exists even
    # where an accelerator plugin is registered.
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=512)
    # 5 layers: block 0 carries the vocab->width projection (its own
    # pre group under --mesh), leaving 4 identical blocks — divisible
    # by the pp=2 stage axis
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--mesh", action="store_true",
                    help="train dp x pp x tp on an 8-device mesh")
    args = ap.parse_args()

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.markov import markov_lm_batches
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    V, T, B, pool = 64, 256, 16, 512
    K = pool // B
    conf = transformer_lm_flagship(
        vocab=V, width=args.width, n_layers=args.layers, n_heads=8,
        lr=3e-4, warmup_steps=min(K, max(1, args.epochs * K // 4)),
        total_steps=args.epochs * K)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()

    feats, labels, floor = markov_lm_batches(
        V, n_seq=pool, seq_len=T, seed=0, sample_seed=1)
    hf, hl, _ = markov_lm_batches(
        V, n_seq=128, seq_len=T, seed=0, sample_seed=777)
    held = DataSet(hf, hl)
    print(f"entropy floor {floor:.4f} nats (uniform = {np.log(V):.4f})")

    if args.mesh:
        from deeplearning4j_tpu.parallel.homogeneous_pipeline import (
            HomogeneousPipelineTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec({"dp": 2, "pp": 2, "tp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, tp_axis="tp", n_microbatches=2)
        print(f"mesh: {dict(mesh.shape)}; stages hold "
              f"{max(trainer.per_device_state_bytes().values()) / 1e6:.1f}"
              f" MB/device of a "
              f"{trainer.total_stack_bytes() / 1e6:.1f} MB stack")
        for ep in range(args.epochs):
            for s in range(K):
                sl = slice(s * B, (s + 1) * B)
                trainer.fit(DataSet(feats[sl], labels[sl]))
            print(f"epoch {ep}: train {float(net.score_value):.4f}")
    else:
        f = jax.device_put(
            feats.reshape(K, B, V, T).astype(np.uint8))
        lab = jax.device_put(
            labels.reshape(K, B, V, T).astype(np.uint8))
        for ep in range(args.epochs):
            t0 = time.perf_counter()
            scores = net.fit_scan(f, lab)
            last = float(np.asarray(scores[-1]))
            print(f"epoch {ep}: train {last:.4f} "
                  f"({K * B * T / (time.perf_counter() - t0):,.0f} "
                  f"tok/s)")

    serving = net.unsharded_clone() if args.mesh else net
    hs = serving.score(held)
    print(f"held-out {hs:.4f} vs floor {floor:.4f} "
          f"(gap {hs - floor:.4f}) "
          f"{'CONVERGED' if hs - floor < 0.25 else 'still training'}")


if __name__ == "__main__":
    main()
