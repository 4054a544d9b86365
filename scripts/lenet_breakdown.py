"""Per-op LeNet-5 train-step breakdown on the real TPU chip.

Attributes the LeNet step time (an earlier round measured ~13-14% MFU)
to its constituent blocks, to tell "the 1998 architecture" from "the
conv machinery" (a wide CNN on the same machinery measured ~47%).

Method: ablation over conf-built subnets timed on the IDENTICAL
fit_scan path (K fused steps per dispatch, value-fetch
sync, bf16 compute + f32 head). Subtracting a minimal head-only net's
time isolates each block, so scan plumbing/updater/dispatch overheads
cancel instead of being mis-attributed (a naive per-op microbench pays
a fixed ~1.5 ms/step serialization cost on this transport and sums to
3x the real step). Run:

    python scripts/lenet_breakdown.py [--batch 2048] [--k 64]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(layers, input_type, lr=0.002):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    b = (NeuralNetConfiguration.Builder()
         .seed(12345).learning_rate(lr)
         .updater(Updater.NESTEROVS).momentum(0.9)
         .list())
    for i, layer in enumerate(layers):
        b.layer(i, layer)
    conf = b.set_input_type(input_type).build()
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    return MultiLayerNetwork(conf).init()


def _time_net(net, feats, labels, k, reps=3, calls=20):
    """ms/step over `calls` BACK-TO-BACK fit_scan dispatches with one
    value-fetch sync at the end: a per-call sync
    pays a host round trip per call and would swamp sub-ms steps."""

    def run():
        for _ in range(calls):
            out = net.fit_scan(feats, labels)[-1]
        return out

    float(np.asarray(run()))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run()
        float(np.asarray(out))  # waits for the device
        best = min(best, time.perf_counter() - t0)
    return best / (k * calls) * 1e3  # ms/step


def kernel_compare(B=2048, K=64, calls=10, reps=3):
    """Hand-kernel-vs-XLA on the LeNet conv1 shape (round-5 review
    next #3): [B,1,28,28] (*) [20,1,5,5], bf16.

    Measures, under one scan-fused estimator (K steps per dispatch,
    ``calls`` back-to-back dispatches, ONE value-fetch sync):
    - XLA's conv_general_dilated (the production path),
    - a pallas VPU tap-accumulation kernel in its IDEAL layout
      (batch-on-lanes [28,28,B], granted the transpose for free),
    - an im2col+GEMM formulation ([B*576, 25] @ [25, 20]),
    each as fwd + a B*20*24*24 bf16 accumulator update (47 MB at the
    default batch 2048) that forces full output materialization
    without a (slow) global reduce; the accumulator-only floor is
    printed so the conv share is readable.

    Round-5 measurement: XLA 0.292 ms vs
    pallas 1.244 ms vs floor 0.120 ms — conv-only ~0.17 vs ~1.12 ms,
    XLA's packed-MXU conv beats the VPU hand kernel ~6.5x on the real
    MACs; C_in 1->8 zero-packing and NHWC layouts measured as no-ops
    (XLA normalizes layout itself).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    TILE = 256
    if B % TILE:
        raise SystemExit(
            f"--batch {B} must be a multiple of {TILE} for the pallas "
            "grid")
    key = jax.random.key(0)

    def _sync(out):
        return float(np.asarray(jax.tree.leaves(out)[0].reshape(-1)[0]))

    def timeit_scan(step, carry0):
        @jax.jit
        def run(c):
            return lax.scan(lambda c, _: (step(c), None), c, None,
                            length=K)[0]
        _sync(run(carry0))
        _sync(run(carry0))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = carry0
            for _ in range(calls):
                out = run(out)
            _sync(out)
            best = min(best, (time.perf_counter() - t0) / (K * calls))
        return best * 1e3  # ms/step

    w0 = (jax.random.normal(key, (20, 5, 5)) * 0.05).astype(jnp.bfloat16)
    x_nchw = jax.random.normal(key, (B, 1, 28, 28), jnp.bfloat16)
    x_hwb = jnp.transpose(x_nchw[:, 0], (1, 2, 0))
    eff = 2 * B * 20 * 25 * 24 * 24
    acc0_nchw = jnp.zeros((B, 20, 24, 24), jnp.bfloat16)
    acc0_hwb = jnp.zeros((20, 24, 24, B), jnp.bfloat16)

    def acc_step(conv_fn):
        def step(c):
            w, acc = c
            acc = acc + conv_fn(w)
            w = w + (1e-12 * acc[0, 0, 0, 0].astype(jnp.float32)
                     ).astype(w.dtype)
            return (w, acc)
        return step

    rows = []

    def xla_fwd(w):
        return lax.conv_general_dilated(
            x_nchw, w[:, None], (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    rows.append(("XLA conv_general_dilated (NCHW)",
                 timeit_scan(acc_step(xla_fwd), (w0, acc0_nchw))))

    def pal_kernel(w_ref, x_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)
        for o in range(20):
            acc = jnp.zeros((24, 24, TILE), jnp.float32)
            for dy in range(5):
                for dx in range(5):
                    acc += w_ref[o, dy, dx] * xb[dy:dy + 24,
                                                 dx:dx + 24, :]
            o_ref[o] = acc.astype(o_ref.dtype)

    def pallas_fwd(w):
        return pl.pallas_call(
            pal_kernel,
            grid=(B // TILE,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((28, 28, TILE),
                                   lambda i: (0, 0, i))],
            out_specs=pl.BlockSpec((20, 24, 24, TILE),
                                   lambda i: (0, 0, 0, i)),
            out_shape=jax.ShapeDtypeStruct((20, 24, 24, B),
                                           jnp.bfloat16),
        )(w.astype(jnp.float32), x_hwb)

    # correctness vs XLA before timing
    ref = np.asarray(xla_fwd(w0)).transpose(1, 2, 3, 0)
    got = np.asarray(pallas_fwd(w0))
    err = float(np.abs(ref.astype(np.float32)
                       - got.astype(np.float32)).max())
    if err >= 0.05:  # not assert: must survive python -O
        raise SystemExit(f"pallas kernel wrong: max err {err}")
    rows.append(("pallas VPU tap kernel (ideal [28,28,B] layout)",
                 timeit_scan(acc_step(pallas_fwd),
                             (w0, acc0_hwb))))

    def im2col_fwd(w):
        p = lax.conv_general_dilated_patches(
            x_nchw, (5, 5), (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        p = p.transpose(0, 2, 3, 1).reshape(-1, 25)
        z = p @ w.reshape(20, 25).T
        return z.reshape(B, 24, 24, 20).transpose(0, 3, 1, 2)

    rows.append(("im2col + GEMM formulation",
                 timeit_scan(acc_step(im2col_fwd),
                             (w0, acc0_nchw))))

    def floor_step(c):
        w, acc = c
        acc = acc + jnp.bfloat16(1e-6)
        w = w + (1e-12 * acc[0, 0, 0, 0].astype(jnp.float32)).astype(
            w.dtype)
        return (w, acc)

    rows.append(("accumulator-only harness floor",
                 timeit_scan(floor_step, (w0, acc0_nchw))))

    acc_mb = B * 20 * 24 * 24 * 2 / 1e6
    print(f"\nconv1 kernel comparison  batch={B}  (fwd + "
          f"{acc_mb:.0f} MB accumulator; ms/step, best of "
          f"{reps}; pallas max err {err:.4f})")
    floor = rows[-1][1]
    for name, ms in rows:
        conv_ms = ms - floor if name != rows[-1][0] else ms
        tf = eff / (conv_ms / 1e3) / 1e12 if conv_ms > 0 else float("inf")
        extra = ("" if name == rows[-1][0]
                 else f"  conv-only ~{conv_ms:.3f} ms ({tf:.1f} Tf/s on"
                      " the real MACs)")
        print(f"{name:48s} {ms:8.3f}{extra}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--kernel-compare", action="store_true",
                    help="run the conv1 hand-kernel-vs-XLA comparison "
                         "instead of the ablation")
    args = ap.parse_args()
    B, K = args.batch, args.k
    if args.kernel_compare:
        kernel_compare(B=B, K=K)
        return

    import jax

    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.ops.losses import LossFunction

    rng = np.random.default_rng(0)

    def data(shape, n_out=10):
        feats = jax.device_put(
            rng.normal(size=(K, B) + shape).astype(np.float32))
        labels = jax.device_put(np.eye(n_out, dtype=np.float32)[
            rng.integers(0, n_out, (K, B))])
        return feats, labels

    def out_layer(n_out=10):
        return L.OutputLayer(n_out=n_out, activation="softmax",
                             loss_function=LossFunction.MCXENT)

    results = {}

    # head-only baseline: flatten 784 -> out (scan plumbing + updater +
    # softmax head; every ablation net pays this too)
    net = _build([out_layer()], InputType.convolutional(28, 28, 1))
    f, lab = data((1, 28, 28))
    results["head784"] = _time_net(net, f, lab, K)

    # + conv1 block (conv1 + pool1)
    net = _build([
        L.ConvolutionLayer(n_out=20, kernel_size=(5, 5), stride=(1, 1),
                           activation="identity"),
        L.SubsamplingLayer(pooling_type=L.PoolingType.MAX,
                           kernel_size=(2, 2), stride=(2, 2)),
        out_layer(),
    ], InputType.convolutional(28, 28, 1))
    results["conv1_block"] = _time_net(net, f, lab, K)

    # conv1 alone (no pool) to split conv from pool
    net = _build([
        L.ConvolutionLayer(n_out=20, kernel_size=(5, 5), stride=(1, 1),
                           activation="identity"),
        out_layer(),
    ], InputType.convolutional(28, 28, 1))
    results["conv1_nopool"] = _time_net(net, f, lab, K)

    # conv2 block on its natural input [20,12,12]
    net = _build([
        L.ConvolutionLayer(n_out=50, kernel_size=(5, 5), stride=(1, 1),
                           activation="identity"),
        L.SubsamplingLayer(pooling_type=L.PoolingType.MAX,
                           kernel_size=(2, 2), stride=(2, 2)),
        out_layer(),
    ], InputType.convolutional(12, 12, 20))
    f2, lab2 = data((20, 12, 12))
    results["conv2_block"] = _time_net(net, f2, lab2, K)

    # head-only at the conv2 input shape (its own flatten cost)
    net = _build([out_layer()], InputType.convolutional(12, 12, 20))
    results["head2880"] = _time_net(net, f2, lab2, K)

    # dense tail 800 -> 500 -> 10
    net = _build([
        L.DenseLayer(n_out=500, activation="relu"),
        out_layer(),
    ], InputType.feed_forward(800))
    f3, lab3 = data((800,))
    results["dense_tail"] = _time_net(net, f3, lab3, K)

    net = _build([out_layer()], InputType.feed_forward(800))
    results["head800"] = _time_net(net, f3, lab3, K)

    # the real thing
    from deeplearning4j_tpu.datasets.mnist import mnist_dataset
    from deeplearning4j_tpu.models.zoo import lenet5
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = lenet5(lr=0.002)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()
    ds = mnist_dataset(train=True, num_examples=B * 8)
    batches = ds.batch_by(B)
    reps = (K + len(batches) - 1) // len(batches)
    feats = np.stack([b.features for b in batches] * reps)[:K]
    feats = jax.device_put(feats.reshape(K, B, 1, 28, 28))
    labels = jax.device_put(
        np.stack([b.labels for b in batches] * reps)[:K])
    full = _time_net(net, feats, labels, K)

    conv1 = results["conv1_nopool"] - results["head784"]
    pool1 = results["conv1_block"] - results["conv1_nopool"]
    conv2_blk = results["conv2_block"] - results["head2880"]
    dense = results["dense_tail"] - results["head800"]
    head = results["head784"]
    attributed = conv1 + pool1 + conv2_blk + dense + head

    print(f"\nLeNet-5 ablation breakdown  batch={B}  K={K} "
          f"(fit_scan path, ms/step, best of 3)")
    print(f"{'component':<36}{'ms/step':>9}{'% of full':>11}")
    for name, ms in [
        ("conv1 1->20 5x5 (fwd+bwd)", conv1),
        ("pool1 2x2 (fwd+bwd)", pool1),
        ("conv2 block 20->50 +pool (fwd+bwd)", conv2_blk),
        ("dense 800->500 (fwd+bwd)", dense),
        ("head: flatten+out+loss+updater+scan", head),
        ("sum of attributed", attributed),
        ("full LeNet step", full),
        ("residual (interactions)", full - attributed),
    ]:
        print(f"{name:<36}{ms:>9.4f}{ms / full * 100:>10.1f}%")
    print("\nraw ablation nets (ms/step):",
          {k: round(v, 4) for k, v in results.items()})


if __name__ == "__main__":
    main()
