"""Word2Vec end-to-end words/sec on the real TPU chip: HS and NS rows.

Protocol identical to the round-2 BENCHMARKS.md measurement (an earlier round's file) (zipf 1M
words, vocab 10k, d=128, window 5, single chip, warm) so rounds stay
comparable; adds the negative-sampling row the review flagged as
unmeasured, and a host-tokenization timing isolating the native
dl4j_tokenize gain. Run: python scripts/w2v_bench.py [--words 1000000]
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def make_corpus(n_words: int, vocab: int = 10_000, sent_len: int = 20,
                seed: int = 7):
    rng = np.random.default_rng(seed)
    # zipf over the vocab, tokens as strings "w<i>"
    ranks = np.arange(1, vocab + 1)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    ids = rng.choice(vocab, size=n_words, p=probs)
    words = np.array([f"w{i}" for i in range(vocab)])
    toks = words[ids]
    return [
        " ".join(toks[i:i + sent_len])
        for i in range(0, n_words, sent_len)
    ]


def run(mode: str, corpus, n_words: int, batch_size: int = 8192,
        subsampling: float = 0.0) -> dict:
    import jax

    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    kw = dict(layer_size=128, window=5, min_word_frequency=1,
              batch_size=batch_size, seed=3, subsampling=subsampling)
    if mode == "hs":
        w2v = Word2Vec(use_hierarchic_softmax=True, negative=0, **kw)
    else:
        w2v = Word2Vec(use_hierarchic_softmax=False, negative=5, **kw)
    w2v.build_vocab_from(corpus)

    # tokenization-only timing (the round-2 host bottleneck)
    t0 = time.perf_counter()
    flat, _ = w2v._tokenize_corpus(corpus)
    tok_s = time.perf_counter() - t0

    # warm compile on a small slice
    w2v.fit(corpus[:200])
    w2v._reset_weights()

    t0 = time.perf_counter()
    w2v.fit(corpus)
    _ = np.asarray(w2v.syn0)[0, 0]  # force device completion
    dt = time.perf_counter() - t0

    # [V, D] table transfer behavior at this vocab (the round-4
    # large-vocab question: does the embedding-table hop dominate?)
    t0 = time.perf_counter()
    host = np.asarray(w2v.syn0)
    t_d2h = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = jax.device_put(host)
    dev.block_until_ready()
    t_h2d = time.perf_counter() - t0
    return {
        "mode": mode,
        "vocab": int(host.shape[0]),
        "words_per_sec": round(n_words / dt, 1),
        "fit_seconds": round(dt, 3),
        "tokenize_seconds": round(tok_s, 3),
        "tokens_kept": int(len(flat)),
        "pairs_trained": int(w2v._pairs_trained),
        "syn0_mb": round(host.nbytes / 1e6, 1),
        "syn0_device_to_host_s": round(t_d2h, 3),
        "syn0_host_to_device_s": round(t_h2d, 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--words", type=int, default=1_000_000)
    ap.add_argument("--vocab", type=int, default=10_000)
    ap.add_argument("--batch-size", type=int, default=8192)
    ap.add_argument("--subsampling", type=float, default=0.0)
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    t0 = time.perf_counter()
    corpus = make_corpus(args.words, vocab=args.vocab)
    print(f"corpus: {args.words:,} words, vocab {args.vocab:,} "
          f"({time.perf_counter() - t0:.1f}s)")
    for mode in ("hs", "ns"):
        for t in range(args.trials):
            print(mode, t, run(mode, corpus, args.words,
                               batch_size=args.batch_size,
                               subsampling=args.subsampling))


if __name__ == "__main__":
    main()
