"""Flagship transformer tuning probe (round-4 review item 1).

Trains transformer_lm_flagship on the Markov-chain task on the real
chip, reporting per-epoch wall clock, tokens/sec, MFU, and held-out
loss vs the analytic entropy floor — the tuning loop for the flagship
block. Run: python scripts/flagship_probe.py [--width 1024 ...]
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def flops_per_token(width, n_layers, seq, vocab):
    per_layer = 12 * width * width + 2 * seq * width
    return 3 * 2 * (n_layers * per_layer + 2 * vocab * width)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--pool-seqs", type=int, default=512)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup-epochs", type=int, default=2)
    args = ap.parse_args()

    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.markov import markov_lm_batches
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    V, T, B = args.vocab, args.seq, args.batch
    if args.pool_seqs % B or args.epochs < 2:
        raise SystemExit("--pool-seqs must be divisible by --batch and "
                         "--epochs >= 2 (epoch 0 is the compile epoch)")
    K = args.pool_seqs // B
    steps_per_epoch = K
    total = args.epochs * steps_per_epoch

    conf = transformer_lm_flagship(
        vocab=V, width=args.width, n_layers=args.layers,
        n_heads=args.heads, lr=args.lr,
        warmup_steps=args.warmup_epochs * steps_per_epoch,
        total_steps=total)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()

    t0 = time.perf_counter()
    feats, labels, floor = markov_lm_batches(
        V, n_seq=args.pool_seqs, seq_len=T, seed=0, sample_seed=1)
    hf, hl, _ = markov_lm_batches(
        V, n_seq=128, seq_len=T, seed=0, sample_seed=777)
    print(f"datagen {time.perf_counter() - t0:.1f}s floor={floor:.4f}")

    f = jax.device_put(
        feats.reshape(K, B, V, T).astype(np.uint8))
    lab = jax.device_put(
        labels.reshape(K, B, V, T).astype(np.uint8))
    held = DataSet(hf, hl)

    fpt = flops_per_token(args.width, args.layers, T, V)
    tok_per_epoch = K * B * T
    t0 = time.perf_counter()
    scores = net.fit_scan(f, lab)
    first_loss = float(np.asarray(scores[0]))
    print(f"compile+first epoch {time.perf_counter() - t0:.1f}s "
          f"first-step loss {first_loss:.3f}")

    rates = []
    for ep in range(1, args.epochs):
        t0 = time.perf_counter()
        scores = net.fit_scan(f, lab)
        last = float(np.asarray(scores[-1]))  # sync
        dt = time.perf_counter() - t0
        tok_s = tok_per_epoch / dt
        rates.append(tok_s)
        mfu = tok_s * fpt / 197e12
        print(f"epoch {ep}: {dt*1000:.0f} ms  {tok_s:,.0f} tok/s "
              f"mfu={mfu:.3f} train={last:.4f}")
    hs = net.score(held)
    med = float(np.median(rates))
    print(f"held-out={hs:.4f} floor={floor:.4f} gap={hs - floor:.4f}")
    print(f"median {med:,.0f} tok/s mfu={med * fpt / 197e12:.4f} "
          f"spread=[{min(rates):,.0f}, {max(rates):,.0f}]")


if __name__ == "__main__":
    main()
