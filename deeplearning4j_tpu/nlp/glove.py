"""GloVe: co-occurrence counting + weighted least-squares factorization.

Mirror of reference nlp models/glove/{Glove.java:31, AbstractCoOccurrences,
GloveWeightLookupTable}. The reference counts co-occurrences with an actor
pipeline spilling to binary files and trains with per-element AdaGrad
(Hogwild); here counting is a host-side dict pass (1/distance weighting,
symmetric window) for in-RAM corpora, or the disk-spill counter
(nlp/cooccurrence.py DiskBackedCoOccurrences, the AbstractCoOccurrences
bounded-memory design) when ``max_pairs_in_memory`` is set; training is
a jitted batched AdaGrad scatter update either way.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.sequence_vectors import SequenceVectors
from deeplearning4j_tpu.nlp.vocab import build_vocab


class Glove(SequenceVectors):
    def __init__(
        self,
        layer_size: int = 100,
        window: int = 15,
        learning_rate: float = 0.05,
        min_word_frequency: int = 5,
        epochs: int = 25,
        x_max: float = 100.0,
        alpha: float = 0.75,
        batch_size: int = 65536,
        symmetric: bool = True,
        seed: int = 12345,
    ):
        super().__init__(
            layer_size=layer_size,
            window=window,
            learning_rate=learning_rate,
            min_word_frequency=min_word_frequency,
            epochs=epochs,
            batch_size=batch_size,
            seed=seed,
            use_hierarchic_softmax=False,
        )
        self.x_max = x_max
        self.alpha = alpha
        self.symmetric = symmetric

    # ------------------------------------------------------------------
    def _count_cooccurrences(
        self, sequences: Iterable[Sequence[str]]
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        counts: Dict[Tuple[int, int], float] = {}
        for tokens in sequences:
            idxs = [
                self.vocab.index_of(t)
                for t in tokens
                if self.vocab.contains_word(t)
            ]
            for pos, center in enumerate(idxs):
                for off in range(1, self.window + 1):
                    j = pos + off
                    if j >= len(idxs):
                        break
                    w = 1.0 / off
                    a, b = center, idxs[j]
                    counts[(a, b)] = counts.get((a, b), 0.0) + w
                    if self.symmetric:
                        counts[(b, a)] = counts.get((b, a), 0.0) + w
        if not counts:
            raise ValueError("Empty co-occurrence matrix")
        ij = np.asarray(list(counts.keys()), np.int32)
        x = np.asarray(list(counts.values()), np.float32)
        return ij[:, 0], ij[:, 1], x

    # ------------------------------------------------------------------
    @functools.cached_property
    def _glove_step(self):
        x_max, alpha = self.x_max, self.alpha

        @jax.jit
        def step(w, wt, b, bt, gw, gwt, gb, gbt, rows, cols, xij, lr):
            wi = w[rows]
            wj = wt[cols]
            diff = (
                jnp.sum(wi * wj, axis=-1) + b[rows] + bt[cols] - jnp.log(xij)
            )
            fx = jnp.minimum(1.0, (xij / x_max) ** alpha)
            g = fx * diff  # [B]
            loss = 0.5 * jnp.mean(fx * diff * diff)
            dwi = g[:, None] * wj
            dwj = g[:, None] * wi
            # AdaGrad accumulators (reference GloveWeightLookupTable's
            # per-element historical gradient).
            gw = gw.at[rows].add(dwi * dwi)
            gwt = gwt.at[cols].add(dwj * dwj)
            gb = gb.at[rows].add(g * g)
            gbt = gbt.at[cols].add(g * g)
            w = w.at[rows].add(-lr * dwi / jnp.sqrt(gw[rows] + 1e-8))
            wt = wt.at[cols].add(-lr * dwj / jnp.sqrt(gwt[cols] + 1e-8))
            b = b.at[rows].add(-lr * g / jnp.sqrt(gb[rows] + 1e-8))
            bt = bt.at[cols].add(-lr * g / jnp.sqrt(gbt[cols] + 1e-8))
            return w, wt, b, bt, gw, gwt, gb, gbt, loss

        return step

    # ------------------------------------------------------------------
    TABLE_NAMES = ("w", "wt", "b", "bt", "gw", "gwt", "gb", "gbt")

    def init_tables(self) -> None:
        """Allocate factorization tables + AdaGrad accumulators on the
        model so training can proceed incrementally (the distributed
        performer trains co-occurrence shards between table averages)."""
        v, d = self.vocab.num_words(), self.layer_size
        key = jax.random.key(self.seed)
        k1, k2 = jax.random.split(key)
        self.w = (jax.random.uniform(k1, (v, d)) - 0.5) / d
        self.wt = (jax.random.uniform(k2, (v, d)) - 0.5) / d
        self.b = jnp.zeros((v,))
        self.bt = jnp.zeros((v,))
        self.gw = jnp.zeros((v, d))
        self.gwt = jnp.zeros((v, d))
        self.gb = jnp.zeros((v,))
        self.gbt = jnp.zeros((v,))
        self.losses: List[float] = []
        # fresh shuffle stream: repeated fit() runs stay seed-reproducible
        self._glove_rng = np.random.default_rng(self.seed)

    def train_cooccurrences(self, rows, cols, xij,
                            learning_rate=None) -> float:
        """One shuffled pass over the given co-occurrence triples at a
        fixed lr; returns the pair-weighted mean batch loss over the
        pass — the incremental granularity the distributed
        GlovePerformer dispatches at
        (reference scaleout/perform/models/glove/GlovePerformer.java)."""
        if not hasattr(self, "w"):
            raise ValueError("init_tables() (or fit) must run first")
        lr = float(learning_rate if learning_rate is not None
                   else self.learning_rate)
        rows = np.asarray(rows, np.int32)
        cols = np.asarray(cols, np.int32)
        xij = np.asarray(xij, np.float32)
        if len(rows) == 0:
            return 0.0  # empty shard: no work, a real (non-NaN) loss
        if not hasattr(self, "_glove_rng"):
            self._glove_rng = np.random.default_rng(self.seed)
        order = self._glove_rng.permutation(len(rows))
        # Device-scalar accumulation: one host sync per PASS, not per
        # batch (a per-batch float() would wait for the device after
        # every dispatch).
        loss_sum = jnp.zeros((), jnp.float32)
        for start in range(0, len(rows), self.batch_size):
            sel = order[start : start + self.batch_size]
            (self.w, self.wt, self.b, self.bt, self.gw, self.gwt,
             self.gb, self.gbt, loss) = self._glove_step(
                self.w, self.wt, self.b, self.bt,
                self.gw, self.gwt, self.gb, self.gbt,
                jnp.asarray(rows[sel]), jnp.asarray(cols[sel]),
                jnp.asarray(xij[sel]), lr,
            )
            loss_sum = loss_sum + loss * len(sel)
        # Final embedding = w + wt (standard GloVe practice).
        self.syn0 = self.w + self.wt
        return float(loss_sum) / len(rows)

    def train_cooccurrence_batches(self, batches, learning_rate=None,
                                   shuffle_window: int = 8) -> float:
        """One pass over an iterable of (rows, cols, xij) batches at a
        fixed lr — the disk-streaming counterpart of
        ``train_cooccurrences``. The merged spill stream arrives in
        sorted key order, so ``shuffle_window`` consecutive batches are
        buffered and shuffled TOGETHER (train_cooccurrences permutes the
        concatenation) before their scatter steps — bounded-memory SGD
        mixing, vs the in-memory path's full-pair-set permutation (a
        global shuffle would need O(pairs) memory, the thing this path
        exists to avoid). Peak memory: shuffle_window batches + tables."""
        if not hasattr(self, "w"):
            raise ValueError("init_tables() (or fit) must run first")
        # Pair-count-weighted mean across flushes so the returned epoch
        # loss is comparable to the in-memory path's full-pass loss (a
        # bare last-flush loss would reflect only the final window).
        loss_weighted_sum = 0.0
        total_pairs = 0
        window: list = []

        def flush():
            nonlocal loss_weighted_sum, total_pairs
            if not window:
                return
            rows = np.concatenate([b[0] for b in window])
            cols = np.concatenate([b[1] for b in window])
            xij = np.concatenate([b[2] for b in window])
            flush_loss = self.train_cooccurrences(
                rows, cols, xij, learning_rate)
            loss_weighted_sum += flush_loss * len(rows)
            total_pairs += len(rows)
            window.clear()

        for batch in batches:
            window.append(batch)
            if len(window) >= shuffle_window:
                flush()
        flush()
        self.syn0 = self.w + self.wt
        return loss_weighted_sum / total_pairs if total_pairs else 0.0

    def fit(
        self,
        sequences_factory,
        max_pairs_in_memory: int | None = None,
        spill_dir: str | None = None,
    ) -> None:
        """``max_pairs_in_memory`` bounds counting memory: co-occurrence
        counts spill to sorted disk shards past that many distinct pairs
        and training streams the k-way merge per epoch (reference
        AbstractCoOccurrences maxMemory knob)."""
        from deeplearning4j_tpu.nlp.cooccurrence import (
            DiskBackedCoOccurrences,
        )

        seqs = (
            sequences_factory()
            if callable(sequences_factory)
            else sequences_factory
        )
        seqs = list(seqs)
        if self.vocab is None:
            self.vocab = build_vocab(seqs, self.min_word_frequency)
        self.init_tables()
        if max_pairs_in_memory is None:
            rows, cols, xij = self._count_cooccurrences(seqs)
            for _ in range(self.epochs):
                self.losses.append(
                    self.train_cooccurrences(rows, cols, xij))
            return
        counter = DiskBackedCoOccurrences(
            self.vocab, window=self.window, symmetric=self.symmetric,
            max_pairs_in_memory=max_pairs_in_memory, spill_dir=spill_dir,
        )
        try:
            counter.count_sequences(seqs)
            if counter.n_shards() == 0:
                raise ValueError("Empty co-occurrence matrix")
            for _ in range(self.epochs):
                self.losses.append(self.train_cooccurrence_batches(
                    counter.iter_batches(self.batch_size)))
        finally:
            counter.cleanup()
