"""16k-context per-component breakdown (round-4 review item 2).

The round-3 16k row ran at 2.9% MFU. This script decomposes the step
the way scripts/lenet_breakdown.py did for LeNet: flash kernel fwd and
fwd+bwd in isolation, non-attention matmul share, remat on/off, batch
scaling, and — the hypothesis under test — HEAD DIMENSION: at width 256
/ 8 heads, dh = 32, so every attention matmul contracts over 32
elements and fills at most a quarter of a 128-wide MXU tile; a
width-1024 / 8-head model (dh = 128) fills full tiles.

Run on the real chip: python scripts/longcontext_breakdown.py
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _sync(x):
    return float(np.asarray(jax_sum(x)))


def jax_sum(x):
    import jax.numpy as jnp

    if isinstance(x, (list, tuple)):
        return sum(jnp.sum(v) for v in x)
    return __import__("jax").numpy.sum(x)


def timed(fn, n=5, warm=1):
    for _ in range(warm):
        _sync(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        _sync(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3  # ms


def flash_kernel_times(B, H, T, dh):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import _flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, dh)),
                           jnp.bfloat16) for _ in range(3))

    fwd = jax.jit(lambda a, b, c: _flash_attention(a, b, c, True))

    def loss(a, b, c):
        return jnp.sum(_flash_attention(a, b, c, True)
                       .astype(jnp.float32))

    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    t_f = timed(lambda: fwd(q, k, v))
    t_fb = timed(lambda: bwd(q, k, v))
    # executed causal MACs: 2 matmuls * T*T/2 * dh per head
    flops = 2 * 2 * B * H * (T * T / 2) * dh
    mfu_f = flops / (t_f / 1e3) / 197e12
    mfu_fb = 3 * flops / (t_fb / 1e3) / 197e12  # bwd ~2x fwd flops
    return t_f, t_fb, mfu_f, mfu_fb


def step_time(width, n_layers, n_heads, B, T, remat, flagship):
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo import (
        transformer_lm,
        transformer_lm_flagship,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if flagship:
        conf = transformer_lm_flagship(
            vocab=64, width=width, n_layers=n_layers, n_heads=n_heads,
            lr=3e-4, warmup_steps=10, total_steps=1000, remat=remat)
    else:
        conf = transformer_lm(n_in=64, width=width, n_layers=n_layers,
                              n_heads=n_heads, n_classes=64,
                              remat=remat)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, 64, T)).astype(np.float32)
    idx = rng.integers(0, 64, (B, T))
    y = np.eye(64, dtype=np.float32)[idx].transpose(0, 2, 1)
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    net.fit(ds)
    float(np.asarray(net.score_value))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        net.fit(ds)
        float(np.asarray(net.score_value))
        ts.append(time.perf_counter() - t0)
    t = float(np.median(ts)) * 1e3

    if flagship:
        per_layer = 12 * width * width + T * width  # causal flash attn
        fpt = 3 * 2 * (n_layers * per_layer + 2 * 64 * width)
    else:
        attn = T * width
        layer0 = 3 * 64 * width + width * width + attn
        layer = 4 * width * width + attn
        fpt = 3 * 2 * (layer0 + (n_layers - 1) * layer + 64 * width)
    mfu = fpt * B * T / (t / 1e3) / 197e12
    return t, mfu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    args = ap.parse_args()
    T = args.seq

    print(f"== flash kernel in isolation (T={T}, causal, blocks "
          f"pinned) ==")
    for B, H, dh, tag in ((1, 8, 32, "w256/h8  (r03 config)"),
                          (1, 8, 128, "w1024/h8 (full MXU tile)"),
                          (4, 8, 32, "w256/h8 B4"),
                          (4, 8, 128, "w1024/h8 B4")):
        t_f, t_fb, mfu_f, mfu_fb = flash_kernel_times(B, H, T, dh)
        print(f"  dh={dh:4d} B={B}: fwd {t_f:7.1f} ms (mfu {mfu_f:.3f})"
              f"  fwd+bwd {t_fb:7.1f} ms (mfu {mfu_fb:.3f})  [{tag}]")

    print("== full train step ==")
    for width, layers, heads, B, remat, flag, tag in (
            (256, 4, 8, 1, True, False, "r03 row"),
            (256, 4, 8, 1, False, False, "no remat"),
            (256, 4, 8, 4, False, False, "B=4, no remat"),
            (1024, 8, 8, 1, True, True, "flagship-wide, remat"),
            (1024, 8, 8, 2, True, True, "flagship-wide B2, remat"),
            (1024, 8, 8, 4, True, True, "flagship-wide B4, remat"),
    ):
        try:
            t, mfu = step_time(width, layers, heads, B, T, remat, flag)
            tok_s = B * T / (t / 1e3)
            print(f"  w={width} L={layers} B={B} remat={int(remat)}: "
                  f"{t:7.0f} ms  {tok_s:9,.0f} tok/s  mfu={mfu:.3f}"
                  f"  [{tag}]")
        except Exception as e:
            print(f"  w={width} L={layers} B={B}: FAILED {e!r} [{tag}]")


if __name__ == "__main__":
    main()
