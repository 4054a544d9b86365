"""SequenceVectors: the generic embedding training engine.

Mirror of reference nlp models/sequencevectors/SequenceVectors.java (866
LoC; fit :100-176) + the learning-algorithm SPI (ElementsLearningAlgorithm
-> SkipGram, learning/impl/elements/SkipGram.java 234 LoC) and the
InMemoryLookupTable hot loop (iterateSample).

TPU inversion of the Hogwild design (SURVEY.md §7 "Hogwild -> synchronous"
hard part): instead of N threads racing on shared syn0/syn1, each epoch
mines (center, context) index pairs host-side, then a jitted step performs
the skip-gram update for a whole batch via gather -> dense HS/NS loss ->
scatter-add, with the learning rate annealed per batch exactly like the
reference's per-word anneal. Deterministic, reproducible, and batched onto
the VPU/MXU. Subsampling of frequent words matches word2vec semantics.
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.vocab import (
    VocabCache,
    assign_huffman_codes,
    build_vocab,
    huffman_arrays,
    unigram_table_probs,
)

Array = jax.Array


def _sigmoid(x):
    return jax.nn.sigmoid(x)


class SequenceVectors:
    """Trains element embeddings over an iterable of token sequences."""

    def __init__(
        self,
        layer_size: int = 100,
        window: int = 5,
        learning_rate: float = 0.025,
        min_learning_rate: float = 1e-4,
        negative: int = 0,
        use_hierarchic_softmax: bool = True,
        min_word_frequency: int = 5,
        subsampling: float = 0.0,  # reference default: disabled (SequenceVectors.java:206)
        epochs: int = 1,
        batch_size: int = 4096,
        seed: int = 12345,
    ):
        self.layer_size = layer_size
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.min_word_frequency = min_word_frequency
        self.subsampling = subsampling
        self.epochs = epochs
        self.batch_size = batch_size
        self.seed = seed

        self.vocab: Optional[VocabCache] = None
        self.syn0: Optional[Array] = None  # [V, D] word vectors
        self.syn1: Optional[Array] = None  # [V, D] HS inner-node weights
        self.syn1neg: Optional[Array] = None  # [V, D] NS context weights
        self._native_vocab = None  # C++ tokenizer hash (lazy, ABI v3)
        self._native_vocab_tried = False

    # ------------------------------------------------------------------
    # Vocab + weights
    # ------------------------------------------------------------------
    def build_vocab_from(self, sequences: Iterable[Sequence[str]]) -> None:
        self.vocab = build_vocab(
            (s.split() if isinstance(s, str) else s for s in sequences),
            self.min_word_frequency)
        if self.use_hs:
            assign_huffman_codes(self.vocab)
        self._native_vocab = None  # rebuilt lazily for the new vocab
        self._native_vocab_tried = False
        self._reset_weights()

    def _reset_weights(self) -> None:
        v = self.vocab.num_words()
        d = self.layer_size
        # Drop compiled-step caches: their closures captured the OLD
        # vocab's Huffman tables / unigram logits, and a re-built vocab
        # would otherwise train against stale (wrong-vocab) indices.
        self.__dict__.pop("_hs_step_cache", None)
        self.__dict__.pop("_ns_step", None)
        self.__dict__.pop("_ns_inner", None)
        key = jax.random.key(self.seed)
        # syn0 ~ U(-0.5, 0.5)/D (reference InMemoryLookupTable.resetWeights)
        self.syn0 = (
            jax.random.uniform(key, (v, d), jnp.float32) - 0.5
        ) / d
        self.syn1 = jnp.zeros((v, d), jnp.float32)
        self.syn1neg = jnp.zeros((v, d), jnp.float32)
        if self.use_hs:
            codes, points, mask = huffman_arrays(self.vocab)
            self._codes = jnp.asarray(codes)
            self._points = jnp.asarray(points)
            self._code_mask = jnp.asarray(mask)
            # host-side copies for the mining path (reading the device
            # arrays there would wait for the queued compute)
            self._code_len_np = mask.sum(axis=1)
            self._code_lmax = int(codes.shape[1])
        # Negative sampling draws from a PRECOMPUTED unigram table
        # (reference InMemoryLookupTable's table, sized 1e8 there):
        # table[uniform_int] is O(1) per draw, where categorical over
        # [V] logits materializes (B, K, V) gumbel noise — 4e9 floats
        # per batch at V=100k (measured ~130 ms/batch, the large-vocab
        # NS wall; an earlier round's BENCHMARKS.md W2V section). Table quantization of
        # p^0.75 matches the reference's sampling semantics exactly.
        probs = np.asarray(unigram_table_probs(self.vocab), np.float64)
        tsize = int(min(2 ** 24, max(2 ** 20, 16 * v)))
        # Cumulative fill (reference table construction): slot i holds
        # the word whose cumulative p^0.75 mass covers fraction i/tsize
        # — every word gets >= 0 slots with NO truncation bias against
        # the tail (a per-word min-1-then-truncate scheme would cut the
        # rarest words' slots whenever rounding overshoots).
        cum = np.cumsum(probs / probs.sum())
        self._neg_table = jnp.asarray(np.searchsorted(
            cum, (np.arange(tsize) + 0.5) / tsize).astype(np.int32))

    # ------------------------------------------------------------------
    # Pair mining (host side)
    # ------------------------------------------------------------------
    def _keep_probs(self) -> np.ndarray:
        """Frequent-word subsampling keep-probability per vocab index
        (word2vec formula, reference iterateSample's sampling branch)."""
        total = max(1, self.vocab.total_word_occurrences())
        counts = np.array(
            [w.count for w in self.vocab.vocab_words()], np.float64
        )
        if self.subsampling <= 0:
            return np.ones_like(counts)
        f = counts / total
        keep = (np.sqrt(f / self.subsampling) + 1) * self.subsampling / f
        return np.minimum(1.0, keep)

    def _tokenize_corpus(self, sequences: Iterable[Sequence[str]]):
        """Corpus -> (flat vocab-index array, sequence-id array).

        Fast path: the C++ vocab-hash tokenizer (ABI v3,
        native/dl4j_native.cpp dl4j_tokenize) — the corpus is joined
        into one newline-separated buffer with C-speed str.join and
        scanned natively, removing the per-token Python dict lookup
        that dominated round-2 host time (~0.55 s/1M words). Sequences
        may be token lists (tokens must be whitespace-free — true of
        any tokenizer output; the native and fallback paths otherwise
        disagree on how to split them) OR raw whitespace-separated
        strings (the reference's SentenceIterator contract; interior
        newlines are treated as plain spaces, matching str.split)."""
        from deeplearning4j_tpu.native_rt.lib import NativeVocab

        if self._native_vocab is None and self._native_vocab_tried is False:
            self._native_vocab_tried = True
            words = self.vocab.vocab_words()
            self._native_vocab = NativeVocab.create(
                [w.word for w in words],
                np.asarray([w.index for w in words], np.int32))
        if self._native_vocab is not None:
            # Materialize one-shot iterators first: the join consumes
            # them, and a native failure must still be able to fall
            # back (list of refs — cheap).
            if not isinstance(sequences, (list, tuple)):
                sequences = list(sequences)
            text = "\n".join(
                s.replace("\n", " ") if isinstance(s, str)
                else " ".join(s)
                for s in sequences)
            out = self._native_vocab.tokenize(text.encode("utf-8"))
            if out is not None:
                return out
        word_to_idx = {
            w.word: w.index for w in self.vocab.vocab_words()
        }
        flat_parts: List[np.ndarray] = []
        seq_parts: List[np.ndarray] = []
        for sid, tokens in enumerate(sequences):
            if isinstance(tokens, str):
                tokens = tokens.split()
            idxs = [word_to_idx[t] for t in tokens if t in word_to_idx]
            if idxs:
                arr = np.asarray(idxs, np.int32)
                flat_parts.append(arr)
                seq_parts.append(np.full(len(arr), sid, np.int32))
        if not flat_parts:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        return np.concatenate(flat_parts), np.concatenate(seq_parts)

    def _mine_pairs(
        self, sequences: Iterable[Sequence[str]], rng: np.random.Generator
    ):
        flat, seq_id = self._tokenize_corpus(sequences)
        yield from self._mine_pairs_from_ids(flat, seq_id, rng)

    def _mine_pairs_from_ids(
        self, flat: np.ndarray, seq_id: np.ndarray,
        rng: np.random.Generator,
    ):
        """Yield (center_idx, context_idx) int32 arrays in batches, applying
        frequent-word subsampling and the word2vec per-center random window
        shrink. Fully vectorized: the corpus is flattened into one index
        array with sequence ids, and every window offset is one numpy
        slice-compare — no per-token Python loop (this mining is the
        words/sec hot path feeding the jitted update)."""
        if len(flat) == 0:
            return
        keep_prob = self._keep_probs()
        # Native C++ fast path: subsample + window walk + shuffle in one
        # call (native/dl4j_native.cpp dl4j_mine_pairs); numpy below is
        # the portable fallback with identical semantics.
        from deeplearning4j_tpu.native_rt.lib import (
            mine_pairs as _native,
            native_available,
        )

        kp_tok = keep_prob[flat]  # one O(corpus) gather, shared below
        if native_available():
            native = _native(
                flat, seq_id, self.window,
                kp_tok.astype(np.float32) if self.subsampling > 0 else None,
                int(rng.integers(2 ** 63)))
            if native is not None:
                centers, contexts = native
                if len(centers) == 0:
                    return
                yield from self._pad_and_batch(centers, contexts, rng)
                return
        # Subsample frequent words (removal shortens the effective window
        # distance, as in word2vec).
        keep = rng.random(len(flat)) < kp_tok
        flat, seq_id = flat[keep], seq_id[keep]
        if len(flat) == 0:
            return
        # Per-center random window size b in [1, window].
        b = rng.integers(1, self.window + 1, size=len(flat))
        cen_parts: List[np.ndarray] = []
        ctx_parts: List[np.ndarray] = []
        for d in range(1, self.window + 1):
            if d >= len(flat):
                break
            same = seq_id[:-d] == seq_id[d:]
            # (center=i, context=i+d) if d <= b[i]; and the mirror pair.
            m1 = same & (d <= b[:-d])
            m2 = same & (d <= b[d:])
            cen_parts.append(flat[:-d][m1])
            ctx_parts.append(flat[d:][m1])
            cen_parts.append(flat[d:][m2])
            ctx_parts.append(flat[:-d][m2])
        if not cen_parts:
            return  # corpus degenerated to (at most) one surviving token
        centers = np.concatenate(cen_parts)
        contexts = np.concatenate(ctx_parts)
        if len(centers) == 0:
            return
        # Shuffle so batches mix offsets/sequences (SGD quality).
        order = rng.permutation(len(centers))
        centers, contexts = centers[order], contexts[order]
        yield from self._pad_and_batch(centers, contexts, rng)

    # Short-path class bound: centers whose Huffman code fits in this
    # many levels run through a kernel sliced to [:, :L] — under a zipf
    # corpus most pairs take this class, nearly halving the [B, L, D]
    # gather/scatter volume of the padded-to-max path.
    _HS_SHORT_LEN = 8

    def _pad_and_batch(self, centers, contexts, rng):
        """Pad the tail to a full batch by resampling existing pairs, so
        every jitted step sees one static shape (no tail recompiles).
        Yields (centers, contexts, l_max, pair_offset): l_max is the
        Huffman-path slice the HS kernel needs (0 when HS is off — the
        NS kernel ignores it) and pair_offset is the batch's position in
        the PRE-SPLIT shuffled pair order, which the lr anneal is
        computed from — so splitting by code-length class changes
        execution order (each class runs contiguously, avoiding
        per-chunk executable alternation) without skewing rare-word
        pairs onto the low-lr tail of the schedule."""
        total = len(centers)
        if self.use_hs:
            short = self._code_len_np[centers] <= self._HS_SHORT_LEN
            splits = [
                (centers[short], contexts[short],
                 min(self._HS_SHORT_LEN, self._code_lmax)),
                (centers[~short], contexts[~short], self._code_lmax),
            ]
        else:
            splits = [(centers, contexts, 0)]
        for cen, ctx, lmax in splits:
            n = len(cen)
            if n == 0:
                continue
            rem = n % self.batch_size
            if rem and n > self.batch_size:
                extra = rng.integers(0, n, size=self.batch_size - rem)
                cen = np.concatenate([cen, cen[extra]])
                ctx = np.concatenate([ctx, ctx[extra]])
            n_batches = max(1, len(cen) // self.batch_size)
            for j, s in enumerate(range(0, len(cen), self.batch_size)):
                # pre-split position: batch j of this class sits at
                # fraction (j+0.5)/n_batches of the full shuffled pass
                offset = int((j + 0.5) / n_batches * total)
                yield (
                    cen[s:s + self.batch_size],
                    ctx[s:s + self.batch_size],
                    lmax,
                    offset,
                )

    # ------------------------------------------------------------------
    # Jitted batched skip-gram updates
    # ------------------------------------------------------------------
    def _hs_step(self, l_max: Optional[int] = None):
        """Scanned multi-batch HS update: one dispatch trains S batches
        (centers/contexts [S, B], lrs [S]) via lax.scan — amortizes the
        host->device dispatch latency that would otherwise dominate
        words/sec. ``l_max`` slices the Huffman path tables to the
        batch's code-length class (see _pad_and_batch) — the compiled
        step is cached per class."""
        cache = self.__dict__.setdefault("_hs_step_cache", {})
        if l_max not in cache:
            inner = self._hs_inner(l_max)

            # donate: the embedding tables are dead after each dispatch;
            # without donation every chunk copies [V, D] x2 out.
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def steps(syn0, syn1, centers, contexts, lrs):
                def body(carry, inp):
                    s0, s1 = carry
                    c, x, lr = inp
                    s0, s1, loss = inner(s0, s1, c, x, lr)
                    return (s0, s1), loss

                (syn0, syn1), losses = jax.lax.scan(
                    body, (syn0, syn1), (centers, contexts, lrs)
                )
                return syn0, syn1, jnp.mean(losses)

            cache[l_max] = steps
        return cache[l_max]

    def _hs_inner(self, l_max: Optional[int] = None):
        codes, points, cmask = self._codes, self._points, self._code_mask
        if l_max is not None and l_max < codes.shape[1]:
            codes = codes[:, :l_max]
            points = points[:, :l_max]
            cmask = cmask[:, :l_max]

        def step(syn0, syn1, centers, contexts, lr):
            # Skip-gram HS: input vector = context word (word2vec trains
            # the *context* against the center's Huffman path).
            h = syn0[contexts]  # [B, D]
            pts = points[centers]  # [B, L]
            cds = codes[centers].astype(jnp.float32)  # [B, L]
            msk = cmask[centers]  # [B, L]
            w = syn1[pts]  # [B, L, D]
            dot = jnp.einsum("bld,bd->bl", w, h)
            # p(code) via sigmoid; gradient of -log-likelihood. The
            # MAX_EXP=6 clamp mirrors the reference's exp-table range
            # (InMemoryLookupTable.iterateSample skips HS updates whose
            # logit falls outside the table): besides fidelity it is
            # the stability brake for BATCHED scatter-adds — without
            # it, hot Huffman roots accumulate thousands of same-sign
            # stale-value updates per batch on real-text frequency
            # distributions and the tables diverge to NaN (measured on
            # the bundled raw_sentences corpus; zipf-synthetic runs
            # were too short to develop it).
            g = (1.0 - cds - _sigmoid(dot)) * msk  # [B, L]
            g = g * (jnp.abs(dot) < 6.0)
            dh = jnp.einsum("bl,bld->bd", g, w)  # accumulate into syn0
            dw = jnp.einsum("bl,bd->bld", g, h)  # into syn1 rows
            syn0 = syn0.at[contexts].add(lr * dh)
            syn1 = syn1.at[pts.reshape(-1)].add(
                lr * dw.reshape(-1, dw.shape[-1])
            )
            loss = -jnp.sum(
                jnp.log(
                    _sigmoid(jnp.where(cds > 0, -dot, dot)) + 1e-10
                )
                * msk
            ) / jnp.maximum(1, centers.shape[0])
            return syn0, syn1, loss

        return step

    @functools.cached_property
    def _ns_step(self):
        """Scanned multi-batch negative-sampling update (see _hs_step)."""
        inner = self._ns_inner

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def steps(syn0, syn1neg, centers, contexts, lrs, rng):
            def body(carry, inp):
                s0, s1, key = carry
                c, x, lr = inp
                key, sub = jax.random.split(key)
                s0, s1, loss = inner(s0, s1, c, x, lr, sub)
                return (s0, s1, key), loss

            (syn0, syn1neg, _), losses = jax.lax.scan(
                body, (syn0, syn1neg, rng), (centers, contexts, lrs)
            )
            return syn0, syn1neg, jnp.mean(losses)

        return steps

    @functools.cached_property
    def _ns_inner(self):
        neg_table = self._neg_table
        k = self.negative

        def step(syn0, syn1neg, centers, contexts, lr, rng):
            h = syn0[contexts]  # [B, D]
            pos = syn1neg[centers]  # [B, D]
            draws = jax.random.randint(
                rng, (centers.shape[0], k), 0, neg_table.shape[0])
            negs = neg_table[draws]  # [B, K]
            wneg = syn1neg[negs]  # [B, K, D]
            dot_pos = jnp.sum(pos * h, axis=-1)  # [B]
            dot_neg = jnp.einsum("bkd,bd->bk", wneg, h)
            # The reference saturates NS gradients outside the
            # exp-table range (iterateSample: g = (label-1)*alpha /
            # (label-0)*alpha at |f| > MAX_EXP) rather than skipping.
            # Under BATCHED scatter-adds saturation is not a brake —
            # sustained +/-1 gradients on hot rows (high-frequency
            # negatives) accumulate stale-value updates until the
            # tables overflow (measured NaN on the bundled
            # raw_sentences corpus). We therefore zero updates outside
            # the table range for NS as well — a documented deviation
            # with the same fixed-range rationale as the table itself.
            in_rng_pos = jnp.abs(dot_pos) < 6.0
            in_rng_neg = jnp.abs(dot_neg) < 6.0
            g_pos = (1.0 - _sigmoid(dot_pos)) * in_rng_pos  # label 1
            g_neg = -_sigmoid(dot_neg) * in_rng_neg  # label 0
            # Exclude accidental positives: the reference's iterateSample
            # skips sampled negatives equal to the target word.
            g_neg = g_neg * (negs != centers[:, None]).astype(g_neg.dtype)
            dh = g_pos[:, None] * pos + jnp.einsum("bk,bkd->bd", g_neg, wneg)
            syn0 = syn0.at[contexts].add(lr * dh)
            syn1neg = syn1neg.at[centers].add(lr * g_pos[:, None] * h)
            syn1neg = syn1neg.at[negs.reshape(-1)].add(
                lr * (g_neg[..., None] * h[:, None, :]).reshape(-1, h.shape[-1])
            )
            loss = -(
                jnp.sum(jnp.log(_sigmoid(dot_pos) + 1e-10))
                + jnp.sum(jnp.log(_sigmoid(-dot_neg) + 1e-10))
            ) / jnp.maximum(1, centers.shape[0])
            return syn0, syn1neg, loss

        return step

    # ------------------------------------------------------------------
    def fit(self, sequences_factory) -> None:
        """Train. ``sequences_factory`` is a zero-arg callable returning a
        fresh iterable of token sequences (one pass per epoch), or a list.
        """
        if not self.use_hs and self.negative <= 0:
            raise ValueError(
                "No training objective: enable hierarchical softmax "
                "(use_hierarchic_softmax=True) and/or negative sampling "
                "(negative > 0)"
            )
        if self.vocab is None:
            seqs = (
                sequences_factory()
                if callable(sequences_factory)
                else sequences_factory
            )
            self.build_vocab_from(seqs)
        total_pairs_est = None
        rng = np.random.default_rng(self.seed)
        key = jax.random.key(self.seed + 1)
        pairs_done = 0
        # Rough anneal denominator: total occurrences * window * epochs.
        denom = max(
            1,
            self.vocab.total_word_occurrences() * self.window * self.epochs,
        )
        def annealed_lrs(pair_offsets):
            fracs = np.asarray(pair_offsets, np.float64) / denom
            return np.maximum(
                self.min_learning_rate,
                self.learning_rate * (1.0 - np.minimum(1.0, fracs)),
            ).astype(np.float32)

        key_box = [key]
        # Fast path: tokenize ONCE and reuse the id-corpus across
        # epochs — the ids (8 B/token) are far smaller than the token
        # strings, and epochs differ only in subsampling/window draws,
        # which happen in the miner. Only taken when BOTH hold:
        # - the corpus is a materialized iterable (a CALLABLE factory
        #   may stream fresh/augmented sequences per epoch — the
        #   documented contract — so it is re-invoked and re-tokenized
        #   each epoch), and
        # - _mine_pairs is not overridden (ParagraphVectors mines
        #   label-word pairs from the sequences themselves and must see
        #   them, not the id arrays).
        plain_miner = type(self)._mine_pairs is SequenceVectors._mine_pairs
        id_corpus = None
        for epoch in range(self.epochs):
            if id_corpus is not None:
                batches = self._mine_pairs_from_ids(*id_corpus, rng)
            else:
                seqs = (
                    sequences_factory()
                    if callable(sequences_factory)
                    else sequences_factory
                )
                if plain_miner and not callable(sequences_factory):
                    id_corpus = self._tokenize_corpus(seqs)
                    batches = self._mine_pairs_from_ids(*id_corpus, rng)
                else:
                    batches = self._mine_pairs(seqs, rng)
            pairs_done = self._dispatch_chunks(
                batches, annealed_lrs, key_box, pairs_done)
        self._pairs_trained = pairs_done

    # batches per device dispatch (see _hs_step docstring)
    _DISPATCH_CHUNK = 64
    # chunks staged on device before their compute is dispatched: a
    # whole window uploads back-to-back, then the window's compute
    # dispatches with no host->device copy in between. Whether
    # interleaving upload and compute per chunk would do as well on a
    # local chip is not measured.
    # 128 chunks x 64 batches x 8192 pairs x 8 B = ~0.5 GB ceiling.
    _STAGE_WINDOW = 128

    def _dispatch_chunks(self, batches, lr_fn, key_box, pairs_done=0) -> int:
        """Stack mined (centers, contexts) batches into scan chunks,
        upload them window-at-a-time, then run the scanned jitted
        updates per window (see _STAGE_WINDOW for why staging is
        windowed rather than interleaved per chunk — review round-1
        weak #5). ``lr_fn(pair_offsets)`` maps each batch's global pair
        offset (pre-split epoch position + prior passes) to its
        learning rate; ``key_box`` is a 1-element list holding the RNG
        key (advanced in place). Returns the updated pair count. Shared
        by fit() and train_sequences(). Chunk order is deterministic
        (mining order), so same-seed runs stay reproducible.
        """
        CHUNK = self._DISPATCH_CHUNK
        # lrs are computed at STAGE time from each batch's PRE-SPLIT
        # pair offset (pairs_done at entry = the base of this pass), so
        # every device input — indices AND learning rates — uploads in
        # the idle window and the compute phase dispatches back-to-back
        # with no host->device copy in between to drain the pipeline.
        pass_base = pairs_done
        # The scan dispatches DONATE the embedding tables; an exception
        # mid-dispatch (device error, Ctrl-C) would otherwise leave
        # self.syn0/... bound to deleted buffers. Snapshot to host once
        # per pass (~15 MB, device idle here) and restore on failure so
        # the model stays readable at its pass-entry state.
        backup = (np.asarray(self.syn0), np.asarray(self.syn1),
                  np.asarray(self.syn1neg))
        try:
            return self._dispatch_chunks_inner(
                batches, lr_fn, key_box, pairs_done)
        except BaseException:
            self.syn0 = jnp.asarray(backup[0])
            self.syn1 = jnp.asarray(backup[1])
            self.syn1neg = jnp.asarray(backup[2])
            raise

    def _dispatch_chunks_inner(self, batches, lr_fn, key_box,
                               pairs_done=0) -> int:
        CHUNK = self._DISPATCH_CHUNK
        pass_base = pairs_done

        def stage(group, lmax):
            s, bsize = len(group), len(group[0][0])
            offsets = pass_base + np.asarray(
                [off for _, _, off in group], np.float64)
            entry = (jnp.asarray(np.stack([c for c, _, _ in group])),
                     jnp.asarray(np.stack([x for _, x, _ in group])),
                     jnp.asarray(lr_fn(offsets)),
                     s, bsize, lmax)
            return entry

        def run(staged, pairs_done):
            for cen_d, ctx_d, lrs_d, s, bsize, lmax in staged:
                if self.use_hs:
                    self.syn0, self.syn1, _ = self._hs_step(lmax)(
                        self.syn0, self.syn1, cen_d, ctx_d, lrs_d
                    )
                if self.negative > 0:
                    key_box[0], sub = jax.random.split(key_box[0])
                    self.syn0, self.syn1neg, _ = self._ns_step(
                        self.syn0, self.syn1neg, cen_d, ctx_d, lrs_d, sub
                    )
                pairs_done += s * bsize
            return pairs_done

        staged = []
        pending: dict = {}
        for c, x, lmax, offset in batches:
            buf = pending.setdefault((len(c), lmax), [])
            buf.append((c, x, offset))
            if len(buf) >= CHUNK:
                staged.append(stage(buf, lmax))
                pending[(len(c), lmax)] = []
                if len(staged) >= self._STAGE_WINDOW:
                    pairs_done = run(staged, pairs_done)
                    staged = []
        for (_, lmax), buf in pending.items():
            if buf:
                staged.append(stage(buf, lmax))
        return run(staged, pairs_done)

    def train_sequences(self, sequences, learning_rate=None) -> int:
        """One incremental pass over the given token sequences at a fixed
        learning rate — the ``trainSentence`` granularity the param-server
        performers dispatch at (reference scaleout/perform/.../
        Word2VecPerformer.java:232), vs ``fit``'s full annealed epochs.
        Returns the number of (center, context) pairs trained."""
        if self.vocab is None:
            raise ValueError("build_vocab_from must run before training")
        lr = float(learning_rate if learning_rate is not None
                   else self.learning_rate)
        if not hasattr(self, "_stream_rng"):
            self._stream_rng = np.random.default_rng(self.seed + 7)
            self._stream_key = jax.random.key(self.seed + 11)
        key_box = [self._stream_key]
        done = self._dispatch_chunks(
            self._mine_pairs(sequences, self._stream_rng),
            lambda offsets: np.full((len(offsets),), lr, np.float32),
            key_box,
        )
        self._stream_key = key_box[0]
        return done

    # ------------------------------------------------------------------
    # WordVectors API (reference wordvectors/WordVectors.java)
    # ------------------------------------------------------------------
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        if i < 0:
            return None
        return np.asarray(self.syn0[i])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = np.linalg.norm(va) * np.linalg.norm(vb)
        if denom == 0:
            return 0.0
        return float(np.dot(va, vb) / denom)

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            v = self.get_word_vector(word_or_vec)
            exclude = {word_or_vec}
            if v is None:
                return []
        else:
            v = np.asarray(word_or_vec)
            exclude = set()
        m = np.asarray(self.syn0)
        norms = np.linalg.norm(m, axis=1) * (np.linalg.norm(v) + 1e-12)
        sims = m @ v / np.maximum(norms, 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            if w in exclude:
                continue
            out.append(w)
            if len(out) >= top_n:
                break
        return out
