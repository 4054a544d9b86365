"""Benchmark driver: prints one JSON line per config; the final line is
the headline row the round harness parses.

Round-4 protocol (review items 1, 4, 5, 8):

- **Interleaved median-of-N trials.** Every throughput row runs N >= 3
  timed trials; the fit_scan family is interleaved round-robin across
  configs so slow drift of the machine hits all configs alike instead
  of whichever ran last. Rows emit ``{"value": median, "spread":
  [min, max], "trials": N}`` — round-over-round deltas can be told
  apart from run-to-run noise.
- **Converging flagship.** ``transformer_lm_flagship`` (width 1024 x 8
  pre-LN blocks) trains on the Markov-chain task (datasets/markov.py)
  whose optimal loss is the analytic conditional entropy; the row
  carries BOTH mfu >= 0.40 and a held-out convergence gate — the same
  run utilizes and converges (round-3 review's top ask).
- **All five BASELINE configs.** MLP, LeNet (+wide-CNN control with a
  real accuracy gate), Word2Vec words/sec with a semantic-quality gate
  on the bundled REAL corpus, DBN pretrain+finetune, and the dp
  allreduce step-time decomposition (subprocess on the 8-virtual-device
  CPU mesh: a CPU proxy for the collective's call pattern, not a device
  number).
- **Real-data accuracy.** When the bundled fixtures exist (they ship
  in-package), MLP accuracy is also measured on 200 REAL MNIST digits
  and on sklearn's 1,797 real digit images; the synthetic-MNIST gate
  remains for throughput-path parity with earlier rounds.

``vs_baseline`` compares against ESTIMATED reference figures (the
reference publishes no numbers — BASELINE.md): 3000 ex/s for the MLP,
500 ex/s for conv nets, 2015-era nd4j-native CPU stand-ins.

The run refuses to start unless jax's default backend is ``tpu``, and
every utilization divides by the peak of the ``device_kind`` jax reports
(``PEAK_BF16_FLOPS``; an unknown kind raises).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

REFERENCE_CPU_EXAMPLES_PER_SEC = 3000.0  # estimated; none published
REFERENCE_CPU_LENET_EXAMPLES_PER_SEC = 500.0  # estimated; none published
# Hogwild 2015 CPU Word2Vec: ~100k words/s on many cores (estimated).
REFERENCE_CPU_W2V_WORDS_PER_SEC = 100_000.0
#: peak dense bf16 FLOP/s of one chip, keyed by the ``device_kind`` jax
#: reports (Google Cloud documentation, "TPU v5e": 197 TFLOP/s)
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
ACCURACY_GATE = 0.97
_GATE_FAILED = False


def _fail_gate(msg: str) -> None:
    global _GATE_FAILED
    print(f"GATE FAILED: {msg}", file=sys.stderr)
    _GATE_FAILED = True


def peak_bf16_flops() -> float:
    """Peak of the device this process runs on; a kind missing from
    the table is an error, never a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device_kind {kind!r}; add it to "
            "bench.PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[kind]


def _require_tpu() -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            "bench.py measures the accelerator and jax's default "
            f"backend here is {jax.default_backend()!r}; run it on the "
            "chip (a CPU timing is not a device number)")


def _sync(x) -> float:
    # fetching the value waits for the device AND hands back the float
    # every caller asserts on
    return float(np.asarray(x))


# Train-step FLOPs/example ~= 3x forward (fwd + bwd-activations +
# bwd-weights), matmul/conv MACs only.
MLP_FLOPS_PER_EXAMPLE = 3 * 2 * (784 * 500 + 500 * 10)
LENET_FLOPS_PER_EXAMPLE = 3 * 2 * (
    20 * 5 * 5 * 1 * 24 * 24
    + 50 * 5 * 5 * 20 * 8 * 8
    + 800 * 500
    + 500 * 10
)
WIDE_CNN_FLOPS_PER_EXAMPLE = 3 * 2 * (
    9 * 3 * 64 * 32 * 32
    + 9 * 64 * 64 * 32 * 32
    + 9 * 64 * 128 * 16 * 16
    + 9 * 128 * 128 * 16 * 16
    + 128 * 8 * 8 * 256
    + 256 * 10
)


def transformer_flops_per_token(seq: int, n_in=64, width=256,
                                n_layers=4, n_classes=64,
                                causal_flash=False) -> int:
    """Analytic train FLOPs/token for zoo.transformer_lm (bare-attention
    stack). EXECUTED MACs: dense attention computes the full TxT scores
    (~2*T*d per token); the causal pallas flash kernel skips future
    blocks (~half) — causal_flash=True accounts for that, keeping mfu
    comparable as hardware utilization across rows."""
    attn = (seq * width) if causal_flash else (2 * seq * width)
    layer0 = 3 * n_in * width + width * width + attn
    layer = 3 * width * width + width * width + attn
    return 3 * 2 * (layer0 + (n_layers - 1) * layer + width * n_classes)


def flagship_flops_per_token(width, n_layers, seq, vocab,
                             causal_flash=False) -> int:
    """zoo.transformer_lm_flagship (pre-LN TransformerBlock): per layer
    qkv 3w^2 + attn-proj w^2 + FFN 8w^2 = 12w^2 MACs/token + causal
    attention (2*T*w dense; T*w when the flash kernel skips future
    blocks); embed + head 2*V*w."""
    attn = (seq * width) if causal_flash else (2 * seq * width)
    per_layer = 12 * width * width + attn
    return 3 * 2 * (n_layers * per_layer + 2 * vocab * width)


def _mnist_accuracy(net, as_image=False, n=4096):
    from deeplearning4j_tpu.datasets.mnist import mnist_dataset

    test = mnist_dataset(train=False, num_examples=n, as_image=as_image)
    ev = net.evaluate([b for b in test.batch_by(1024)])
    return round(float(ev.accuracy()), 4)


# ----------------------------------------------------------------------
# fit_scan family: setup() compiles + converges + gates; trial() is one
# timed window. Trials interleave round-robin across all five configs.
# ----------------------------------------------------------------------
class ScanBench:
    name = "?"
    calls_per_trial = 4
    rate_scale = 1.0  # tokens-per-example for sequence benches

    def setup(self):
        raise NotImplementedError

    def trial(self):
        # calls_per_trial is sized per config so the one end-of-trial
        # fetch stays a small fraction of the window (fit_scan calls
        # chain lazily — the whole window is device-bound until the
        # final sync).
        t0 = time.perf_counter()
        for _ in range(self.calls_per_trial):
            scores = self.net.fit_scan(self.feats, self.labels)
        final = _sync(scores[-1])
        dt = time.perf_counter() - t0
        assert np.isfinite(final), f"{self.name}: non-finite loss"
        self.rates.append(
            self.calls_per_trial * self.scan_steps * self.batch
            * self.rate_scale / dt)

    def finish(self, rates):
        raise NotImplementedError

    def _stack(self, feats_list, labels_list, scan_steps,
               feats_shape=None):
        """Stack + (optionally reshape) on HOST, then one device_put."""
        import jax

        reps = (scan_steps + len(feats_list) - 1) // len(feats_list)
        f = np.stack(list(feats_list) * reps)[:scan_steps]
        y = np.stack(list(labels_list) * reps)[:scan_steps]
        if feats_shape is not None:
            f = f.reshape(feats_shape)
        return jax.device_put(f), jax.device_put(y)


class MlpBench(ScanBench):
    # round 5: scan depth 64 -> 256 (same examples/trial via 24 calls).
    # The row is dispatch-bound at 64 steps/call (compute windows of a
    # few ms); at 256 fused steps the per-dispatch share shrinks 4x.
    name = "mnist_mlp_784_500_10_train_throughput"
    batch, scan_steps, calls_per_trial = 2048, 256, 24

    def setup(self):
        from deeplearning4j_tpu.datasets.mnist import mnist_dataset
        from deeplearning4j_tpu.models.zoo import mlp
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = mlp()
        for c in conf.confs:
            c.compute_dtype = "bfloat16"
        self.net = MultiLayerNetwork(conf).init()
        ds = mnist_dataset(train=True, num_examples=self.batch * 8)
        bs = ds.batch_by(self.batch)
        self.feats, self.labels = self._stack(
            [b.features for b in bs], [b.labels for b in bs],
            self.scan_steps)
        self.rates = []
        # compile + converge (a few hundred steps), gate BEFORE the
        # timed window (sustained full-lr overtraining in bf16
        # saturates the softmax eventually — BENCHMARKS.md)
        _sync(self.net.fit_scan(self.feats, self.labels)[-1])
        for _ in range(6):
            scores = self.net.fit_scan(self.feats, self.labels)
        assert np.isfinite(_sync(scores[-1]))
        self.accuracy = _mnist_accuracy(self.net)
        if self.accuracy < ACCURACY_GATE:
            _fail_gate(f"mlp synthetic accuracy {self.accuracy}")
        self.real = _real_data_accuracies()

    def finish(self, rates):
        med = float(np.median(rates))
        row = {
            "metric": self.name,
            "value": round(med, 1),
            "unit": "examples/sec/chip",
            "vs_baseline": round(med / REFERENCE_CPU_EXAMPLES_PER_SEC, 2),
            "mfu": round(
                med * MLP_FLOPS_PER_EXAMPLE / peak_bf16_flops(), 4),
            "accuracy": self.accuracy,
        }
        row.update(self.real)
        return row


def _real_data_accuracies() -> dict:
    """Accuracy on REAL data (round-4 review item 8): 200 bundled real
    MNIST digits + sklearn's 1,797 real digit images. Trains small
    dedicated nets (seconds); gates are sized to the train-set sizes
    (160 real MNIST examples -> 0.75; 1,437 digits -> 0.93)."""
    try:
        from deeplearning4j_tpu.datasets.fixtures import (
            digits_dataset,
            mnist200_datasets,
        )
    except Exception as e:  # fixtures absent: synthetic-only fallback
        print(f"real-data fixtures unavailable ({e})", file=sys.stderr)
        return {}
    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    out = {}
    tr, te = mnist200_datasets()
    net = MultiLayerNetwork(mlp(sizes=(784, 128, 10), lr=0.3)).init()
    for _ in range(80):
        net.fit(tr)
    out["accuracy_real_mnist200"] = round(
        float(net.evaluate([te]).accuracy()), 4)
    if out["accuracy_real_mnist200"] < 0.75:
        _fail_gate(f"real mnist200 {out['accuracy_real_mnist200']}")

    tr, te = digits_dataset()
    net = MultiLayerNetwork(mlp(sizes=(64, 128, 10), lr=0.3)).init()
    for _ in range(60):
        net.fit(tr)
    out["accuracy_real_digits"] = round(
        float(net.evaluate([te]).accuracy()), 4)
    if out["accuracy_real_digits"] < 0.93:
        _fail_gate(f"real digits {out['accuracy_real_digits']}")
    return out


class LenetBench(ScanBench):
    name = "mnist_lenet5_train_throughput"
    batch, scan_steps, calls_per_trial = 2048, 64, 10

    def setup(self):
        import jax

        from deeplearning4j_tpu.datasets.mnist import mnist_dataset
        from deeplearning4j_tpu.models.zoo import lenet5
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        # bf16 conv stack converges at 0.002 (f32 at 0.01; both diverge
        # at 0.05 with batch 2048 — BENCHMARKS.md)
        conf = lenet5(lr=0.002)
        for c in conf.confs:
            c.compute_dtype = "bfloat16"
        self.net = MultiLayerNetwork(conf).init()
        ds = mnist_dataset(train=True, num_examples=self.batch * 8)
        bs = ds.batch_by(self.batch)
        self.feats, self.labels = self._stack(
            [b.features for b in bs], [b.labels for b in bs],
            self.scan_steps,
            feats_shape=(self.scan_steps, self.batch, 1, 28, 28))
        self.rates = []
        _sync(self.net.fit_scan(self.feats, self.labels)[-1])
        for _ in range(6):
            scores = self.net.fit_scan(self.feats, self.labels)
        assert np.isfinite(_sync(scores[-1]))
        self.accuracy = _mnist_accuracy(self.net, as_image=True)
        if self.accuracy < ACCURACY_GATE:
            _fail_gate(f"lenet synthetic accuracy {self.accuracy}")

    def finish(self, rates):
        med = float(np.median(rates))
        return {
            "metric": self.name,
            "value": round(med, 1),
            "unit": "examples/sec/chip",
            "vs_baseline": round(
                med / REFERENCE_CPU_LENET_EXAMPLES_PER_SEC, 2),
            "mfu": round(
                med * LENET_FLOPS_PER_EXAMPLE / peak_bf16_flops(), 4),
            "accuracy": self.accuracy,
        }


class WideCnnBench(ScanBench):
    """Conv-MFU control at MXU-filling widths — now with a real
    convergence gate: class = template + unit noise (a task with CNN
    inductive bias; a linear-pixel teacher defeats pooled conv nets,
    measured 15% — the template task reaches 1.00)."""

    name = "wide_cnn_cifar_scale_train_throughput"
    batch, scan_steps, calls_per_trial = 1024, 16, 6

    def setup(self):
        import jax

        from deeplearning4j_tpu.models.zoo import wide_cnn
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = wide_cnn(lr=0.005)
        for c in conf.confs:
            c.compute_dtype = "bfloat16"
        self.net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        self.templates = rng.normal(size=(10, 3, 32, 32)).astype(
            np.float32)
        x, y, _ = self._make(self.scan_steps * self.batch, 1)
        self.feats = jax.device_put(
            x.reshape(self.scan_steps, self.batch, 3, 32, 32))
        self.labels = jax.device_put(
            y.reshape(self.scan_steps, self.batch, 10))
        self.rates = []
        _sync(self.net.fit_scan(self.feats, self.labels)[-1])
        for _ in range(12):
            scores = self.net.fit_scan(self.feats, self.labels)
        assert np.isfinite(_sync(scores[-1]))
        hx, _, hc = self._make(2048, 99)
        out = np.asarray(self.net.output(hx))
        self.accuracy = round(float((out.argmax(1) == hc).mean()), 4)
        if self.accuracy < ACCURACY_GATE:
            _fail_gate(f"wide_cnn accuracy {self.accuracy}")
        # REAL pixels through the REAL on-disk format: the same conv
        # architecture trained on the bundled CIFAR-binary fixture of
        # real photograph patches (datasets/fixtures/README.md) —
        # native C++ decode -> fit -> held-out accuracy.
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.fixtures import (
            real_patches_cifar,
        )

        rtr, rte = real_patches_cifar(n_test=40, seed=0)
        pad = lambda y: np.pad(np.asarray(y), ((0, 0), (0, 8)))  # noqa
        rnet = MultiLayerNetwork(wide_cnn(lr=0.01)).init()
        rds = DataSet(rtr.features, pad(rtr.labels))
        for _ in range(120):
            rnet.fit(rds)
        rout = np.asarray(rnet.output(rte.features))
        self.accuracy_real_patches = round(float(
            (rout.argmax(1) == np.asarray(rte.labels).argmax(1)).mean()),
            4)
        if self.accuracy_real_patches < 0.9:
            _fail_gate(
                f"wide_cnn real patches {self.accuracy_real_patches}")

    def _make(self, n, seed):
        r = np.random.default_rng(seed)
        cls = r.integers(0, 10, n)
        x = (0.5 * self.templates[cls]
             + r.normal(size=(n, 3, 32, 32))).astype(np.float32)
        return x, np.eye(10, dtype=np.float32)[cls], cls

    def finish(self, rates):
        med = float(np.median(rates))
        return {
            "metric": self.name,
            "value": round(med, 1),
            "unit": "examples/sec/chip",
            "vs_baseline": round(
                med / REFERENCE_CPU_LENET_EXAMPLES_PER_SEC, 2),
            "mfu": round(
                med * WIDE_CNN_FLOPS_PER_EXAMPLE / peak_bf16_flops(),
                4),
            "accuracy": self.accuracy,
            "accuracy_real_patches": self.accuracy_real_patches,
        }


class TransformerBench(ScanBench):
    name = "transformer_lm_train_throughput"
    batch, seq, scan_steps, calls_per_trial = 64, 512, 8, 10
    rate_scale = seq  # tokens per example

    def setup(self):
        import jax

        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = transformer_lm(n_in=64, width=256, n_layers=4,
                              n_heads=8, n_classes=64)
        for c in conf.confs:
            c.compute_dtype = "bfloat16"
        self.net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        self.feats = jax.device_put(
            rng.normal(size=(self.scan_steps, self.batch, 64, self.seq))
            .astype(np.float32))
        idx = rng.integers(0, 64, (self.scan_steps, self.batch, self.seq))
        self.labels = jax.device_put(
            np.eye(64, dtype=np.float32)[idx].transpose(0, 1, 3, 2))
        self.rates = []
        _sync(self.net.fit_scan(self.feats, self.labels)[-1])

    def finish(self, rates):
        med = float(np.median(rates))  # already tokens/s (rate_scale)
        return {
            "metric": self.name,
            "value": round(med, 1),
            "unit": ("tokens/sec/chip (width-256 DISPATCH-BOUND toy "
                     "control kept for round-over-round comparability "
                     "— too narrow to fill the MXU; the flagship and "
                     "long-context rows are the utilization statements)"),
            "vs_baseline": None,  # reference has no attention model
            "mfu": round(
                med * transformer_flops_per_token(self.seq)
                / peak_bf16_flops(), 4),
        }


# ----------------------------------------------------------------------
def run_interleaved(benches, n_trials=3):
    for b in benches:
        t0 = time.perf_counter()
        b.setup()
        print(f"setup {b.name}: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    for _ in range(n_trials):
        for b in benches:
            b.trial()
    rows = []
    for b in benches:
        row = b.finish(b.rates)
        row["spread"] = [round(min(b.rates), 1), round(max(b.rates), 1)]
        row["trials"] = len(b.rates)
        rows.append(row)
    return rows


# ----------------------------------------------------------------------
def bench_flagship():
    """The converging high-MFU flagship (review r3 item 1): width-2048
    x 8 TransformerBlock LM on the analytic Markov task. ONE run both
    converges (held-out CE within 0.25 nats of the entropy floor) and
    utilizes (mfu >= 0.40; measures ~0.71 at B=16 — B=8 measured ~0.69,
    width 1024 ~0.55; B=16 still converges: held-out gap 0.094 nats).
    Per-epoch wall times double as the trials."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.markov import markov_lm_batches
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # pool 1024 (524k tokens): a 512-seq pool overfits the ~403M-param
    # width-2048 model by epoch 8 (held-out worsens past ~epoch 5)
    V, T, B, pool, epochs = 64, 512, 16, 1024, 7
    K = pool // B  # scan steps per epoch
    width, n_layers = 2048, 8

    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=16,
        lr=2e-4, warmup_steps=K, total_steps=epochs * K)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()

    feats, labels, floor = markov_lm_batches(
        V, n_seq=pool, seq_len=T, seed=0, sample_seed=1)
    hf, hl, _ = markov_lm_batches(
        V, n_seq=128, seq_len=T, seed=0, sample_seed=777)
    f = jax.device_put(feats.reshape(K, B, V, T).astype(np.uint8))
    lab = jax.device_put(labels.reshape(K, B, V, T).astype(np.uint8))
    held = DataSet(hf, hl)

    start_loss = _sync(net.fit_scan(f, lab)[0])  # compile + epoch 0
    rates = []
    for _ in range(1, epochs):
        t0 = time.perf_counter()
        scores = net.fit_scan(f, lab)
        assert np.isfinite(_sync(scores[-1]))
        rates.append(K * B * T / (time.perf_counter() - t0))

    held_loss = net.score(held)
    fpt = flagship_flops_per_token(width, n_layers, T, V)
    med = float(np.median(rates))
    mfu = med * fpt / peak_bf16_flops()
    converged = bool(held_loss - floor <= 0.25)
    if not converged:
        _fail_gate(
            f"flagship held-out {held_loss:.4f} vs floor {floor:.4f}")
    if mfu < 0.40:
        _fail_gate(f"flagship mfu {mfu:.4f} < 0.40")
    device_row = {
        "metric": "transformer_flagship_2048x8_train_throughput",
        "value": round(med, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # no reference counterpart exists
        "mfu": round(mfu, 4),
        "spread": [round(min(rates), 1), round(max(rates), 1)],
        "trials": len(rates),
        "converged": converged,
        "held_out_loss_nats": round(float(held_loss), 4),
        "entropy_floor_nats": round(float(floor), 4),
        "initial_loss_nats": round(float(start_loss), 4),
    }

    # HOST-FED epochs on the same model (round-5 review next #1): the
    # SAME token pool streams from an on-disk DL4JTOK1 binary through
    # the C++ prefetch ring (native_rt ring buffer) into fit_stream —
    # ids on the wire, one-hot on device. Gate: within 10% of the
    # device-resident epochs above.
    import tempfile

    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.markov import (
        make_chain,
        sample_tokens,
    )
    from deeplearning4j_tpu.datasets.streaming import (
        TokenSequenceFileIterator,
        write_token_file,
    )
    from deeplearning4j_tpu.native_rt import NativeAsyncDataSetIterator

    chain, _, _ = make_chain(V, seed=0)
    toks = sample_tokens(chain, pool, T, seed=1)  # == the trained pool
    tmpd = tempfile.mkdtemp(prefix="dl4j_hostfed_")
    try:
        tok_path = os.path.join(tmpd, "flagship_tokens.bin")
        write_token_file(tok_path, toks, vocab=V)
        one_hot = jax.jit(lambda ids: jax.nn.one_hot(
            ids, V, dtype=jnp.bfloat16).transpose(0, 1, 3, 2))
        hrates = []
        for i in range(4):
            it = NativeAsyncDataSetIterator(
                TokenSequenceFileIterator(tok_path, batch_size=B),
                queue_size=8)
            t0 = time.perf_counter()
            scores = net.fit_stream(it, scan_steps=K, ingest=one_hot,
                                    ingest_labels=one_hot)
            assert np.isfinite(_sync(scores[-1]))
            if i > 0:  # epoch 0 compiles the one-hot ingest
                hrates.append(K * B * T / (time.perf_counter() - t0))
    finally:
        import shutil

        shutil.rmtree(tmpd, ignore_errors=True)
    hmed = float(np.median(hrates))
    ratio = hmed / med
    if ratio < 0.9:
        _fail_gate(f"hostfed flagship at {ratio:.3f}x device-resident")
    hostfed_row = {
        "metric": "transformer_flagship_hostfed_train_throughput",
        "value": round(hmed, 1),
        "unit": ("tokens/sec/chip (token ids streamed from on-disk "
                 "binary via C++ prefetch ring; one-hot on device)"),
        "vs_baseline": None,
        "vs_device_resident": round(ratio, 4),
        "mfu": round(hmed * fpt / peak_bf16_flops(), 4),
        "spread": [round(min(hrates), 1), round(max(hrates), 1)],
        "trials": len(hrates),
    }
    return [device_row, hostfed_row]


def bench_hostfed_cnn():
    """Wide-CNN host-fed stress row: 200 MB of u8 pixels stream from
    CIFAR-binary files on disk through the C++ prefetch ring into
    fit_stream windows (one fused 64-batch dispatch per window).

    Windows upload serialized via sync_each_window, so the achievable
    ceiling is compute/(compute + upload + sync); whether uploads
    overlap compute without it on a local chip is not measured
    (ROADMAP A6). The row reports the measured hostfed/device-resident
    ratio; the flagship hostfed row is the small-wire-format (token
    ids) counterpart."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.streaming import (
        CifarBinStreamIterator,
    )
    from deeplearning4j_tpu.models.zoo import wide_cnn
    from deeplearning4j_tpu.native_rt import NativeAsyncDataSetIterator
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    B, K = 1024, 64  # one window = one on-disk file pass
    conf = wide_cnn(lr=0.005)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()

    # The WideCnnBench template task, quantized to real u8 pixels:
    # x_f32 in ~[-4, 4] -> u8; ingest restores the float statistics.
    rng = np.random.default_rng(0)
    templates = rng.normal(size=(10, 3, 32, 32)).astype(np.float32)
    cls = rng.integers(0, 10, K * B)
    x = 0.5 * templates[cls] + rng.normal(size=(K * B, 3, 32, 32))
    xu8 = np.clip((x + 4.0) * (255.0 / 8.0), 0, 255).astype(np.uint8)
    tmpd = tempfile.mkdtemp(prefix="dl4j_hostfed_cnn_")
    path = os.path.join(tmpd, "train_batch.bin")
    rows = np.concatenate(
        [cls.astype(np.uint8)[:, None], xu8.reshape(K * B, -1)], axis=1)
    rows.tofile(path)
    del rows
    ingest = jax.jit(
        lambda a: a.astype(jnp.bfloat16) * (8.0 / 255.0) - 4.0)

    # device-resident control: the same u8 window resident on device
    feats_dev = jax.device_put(xu8.reshape(K, B, 3, 32, 32))
    y = np.eye(10, dtype=np.float32)[cls].reshape(K, B, 10)
    labels_dev = jax.device_put(y)
    _sync(net.fit_scan(ingest(feats_dev), labels_dev)[-1])  # compile
    drates = []
    for _ in range(3):
        t0 = time.perf_counter()
        scores = net.fit_scan(ingest(feats_dev), labels_dev)
        assert np.isfinite(_sync(scores[-1]))
        drates.append(K * B / (time.perf_counter() - t0))
    dmed = float(np.median(drates))

    hrates = []
    try:
        for _ in range(3):
            it = NativeAsyncDataSetIterator(
                CifarBinStreamIterator([path], batch_size=B),
                queue_size=8)
            t0 = time.perf_counter()
            scores = net.fit_stream(it, scan_steps=K, ingest=ingest,
                                    sync_each_window=True)
            assert np.isfinite(_sync(scores[-1]))
            hrates.append(K * B / (time.perf_counter() - t0))
    finally:
        import shutil

        shutil.rmtree(tmpd, ignore_errors=True)
    hmed = float(np.median(hrates))
    ratio = hmed / dmed
    # 200 MB of pixels per window is upload-bound; the floor is a smoke
    # gate for total breakage only, not a perf target (ROADMAP A6)
    if ratio < 0.008:
        _fail_gate(f"hostfed wide-CNN at {ratio:.3f}x device-resident")
    return {
        "metric": "wide_cnn_hostfed_train_throughput",
        "value": round(hmed, 1),
        "unit": ("examples/sec/chip (u8 pixels streamed from on-disk "
                 "CIFAR binaries via C++ prefetch ring; serialized "
                 "H2D)"),
        "vs_baseline": round(
            hmed / REFERENCE_CPU_LENET_EXAMPLES_PER_SEC, 2),
        "vs_device_resident": round(ratio, 4),
        "device_resident_examples_per_sec": round(dmed, 1),
        "spread": [round(min(hrates), 1), round(max(hrates), 1)],
        "trials": len(hrates),
    }


def bench_decode():
    """Serving row (round-5 review next #5): KV-cache decode on the
    width-1024 flagship with a 2048-token window, B=1.

    Two paths:
    - python per-token: ``rnn_time_step`` loop, one jitted dispatch +
      value fetch per token (one host round trip per token).
    - fused on-device: ``generate`` — ONE dispatch scans N tokens with
      the cache in the scan carry.

    The C++ PJRT client's row is ``python
    scripts/native_decode_bench.py``, a command of its own: this
    process holds the chip through jax, and a chip serves one process
    at a time.

    Gates: fused/python id parity >= 0.9 over the compared window, and
    a fused-throughput floor."""
    import jax

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    V, width, n_layers, window = 64, 1024, 8, 2048
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompt_ids = rng.integers(0, V, 128)
    prompt = np.zeros((1, V, len(prompt_ids)), np.float32)
    prompt[0, prompt_ids, np.arange(len(prompt_ids))] = 1.0

    def one_hot1(tok):
        x = np.zeros((1, V, 1), np.float32)
        x[0, tok, 0] = 1.0
        return x

    # --- python per-token path (32 timed tokens) ----------------------
    net.rnn_clear_previous_state()
    out = net.rnn_time_step(prompt)
    tok = int(np.asarray(out)[0, :, -1].argmax())
    loop_ids = [tok]
    lat = []
    for _ in range(32):
        t0 = time.perf_counter()
        out = net.rnn_time_step(one_hot1(tok))
        tok = int(np.asarray(out)[0, :, 0].argmax())
        lat.append(time.perf_counter() - t0)
        loop_ids.append(tok)
    py_p50 = float(np.median(lat))

    # --- fused generate path ------------------------------------------
    n_gen = 128
    net.rnn_clear_previous_state()
    ids = np.asarray(net.generate(prompt, n_gen))  # compile + run
    match = float(np.mean(ids[0, :len(loop_ids)] == loop_ids))
    if match < 0.9:
        _fail_gate(f"decode fused/per-token id match {match:.2f}")
    grates = []
    for _ in range(3):
        net.rnn_clear_previous_state()
        t0 = time.perf_counter()
        ids = np.asarray(net.generate(prompt, n_gen))
        grates.append(n_gen / (time.perf_counter() - t0))
    gmed = float(np.median(grates))
    if gmed < 300.0:
        _fail_gate(f"fused decode {gmed:.0f} tok/s < 300")

    row = {
        "metric": "decode_tokens_per_sec",
        "value": round(gmed, 1),
        "unit": ("tokens/sec (width-1024 flagship, 2048-token KV "
                 "window, B=1, fused on-device scan)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "spread": [round(min(grates), 1), round(max(grates), 1)],
        "trials": len(grates),
        "fused_per_token_id_match": round(match, 4),
        "python_per_token_p50_ms": round(py_p50 * 1e3, 2),
        "python_per_token_tokens_per_sec": round(1.0 / py_p50, 1),
    }
    return row


def bench_decode_batched():
    """Serving row (ISSUE 1 tentpole): continuous-batching decode on
    the SAME width-1024 flagship / 2048-window config as the B=1 row,
    but with the slot-based engine (serving/engine.py) multiplexing 8
    concurrent requests through ONE jitted batched decode step.

    Gates:
    - smoke: the 8-slot aggregate tokens/sec must EXCEED the B=1 fused
      rate measured in the same process (batching that loses to B=1
      means the slot masking broke the batched step);
    - parity: each request's greedy ids match its sequential B=1
      ``generate()`` ids (>= 0.9 over the decoded window, same bar as
      the fused/per-token gate — ties under bf16 may argmax-flip);
    - compile count: after warmup, admissions and chunks reuse ONE
      decode executable, ONE admit executable, and one prefill per
      prompt-length bucket (a retrace would silently serialize)."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_gen, prompt_len = 8, 128, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_slots)]

    def one_hot(ids):
        x = np.zeros((1, V, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        return x

    # --- B=1 fused reference: rate for the gate, ids for parity ------
    solo_ids = []
    b1_rates = []
    for i, p in enumerate(prompts):
        net.rnn_clear_previous_state()
        ids = np.asarray(net.generate(one_hot(p), n_gen))  # warm
        if i < 3:  # timed trials on the warmed executable
            net.rnn_clear_previous_state()
            t0 = time.perf_counter()
            ids = np.asarray(net.generate(one_hot(p), n_gen))
            b1_rates.append(n_gen / (time.perf_counter() - t0))
        solo_ids.append(ids[0].tolist())
    b1 = float(np.median(b1_rates))

    # --- engine: warm (compiles prefill/admit/decode), then timed ----
    # chunk 32 = 4 decode dispatches per 128-token round: each dispatch
    # is a host round trip, and 4 chunk boundaries still exercise
    # admission/eviction
    engine = DecodeEngine(net, n_slots=n_slots, decode_chunk=32)

    def one_round():
        for p in prompts:
            engine.submit(Request(prompt=p, max_new_tokens=n_gen))
        t0 = time.perf_counter()
        results = engine.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in results.values())
        return toks / dt, results

    _, results = one_round()  # warmup: compiles + parity ids
    matches = []
    by_order = sorted(results.values(), key=lambda r: r.id)
    for r, solo in zip(by_order, solo_ids):
        matches.append(float(np.mean(
            np.asarray(r.tokens) == np.asarray(solo))))
    match = float(np.mean(matches))
    if match < 0.9:
        _fail_gate(f"batched/sequential id match {match:.2f}")

    counts0 = engine.compile_counts()
    rates = []
    for _ in range(3):
        rate, _ = one_round()
        rates.append(rate)
    counts1 = engine.compile_counts()
    if counts1 != counts0 or counts1.get("decode") not in (1, -1):
        _fail_gate(f"engine retraced after warmup: {counts0} "
                   f"-> {counts1}")

    agg = float(np.median(rates))
    if agg <= b1:
        _fail_gate(
            f"batched decode {agg:.0f} tok/s <= B=1 fused {b1:.0f}")
    return {
        "metric": "decode_batched_tokens_per_sec",
        "value": round(agg, 1),
        "unit": (f"aggregate tokens/sec (width-1024 flagship, "
                 f"2048-token KV window, {n_slots} slots x {n_gen} "
                 "tokens, continuous-batching engine)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "spread": [round(min(rates), 1), round(max(rates), 1)],
        "trials": len(rates),
        "vs_b1_fused": round(agg / b1, 2),
        "b1_fused_tokens_per_sec": round(b1, 1),
        "batched_sequential_id_match": round(match, 4),
        "mean_slot_occupancy": round(engine.mean_occupancy, 3),
        "compile_counts": counts1,
    }


def bench_prefix_cache():
    """Serving rows (ISSUE 2 tentpole): radix prefix cache + chunked
    prefill on the SAME width-1024 flagship / 2048-window / 8-slot
    config as the continuous-batching row.

    Workload: 16 requests whose prompts share an 80% prefix (1024
    shared "system prompt" tokens + 256 distinct tail tokens), run
    twice on one engine — round 1 populates the radix cache (its first
    admission wave is the COLD sample: every prompt misses and chunk-
    prefills from token 0), round 2 is the WARM sample (every prompt
    hits; only the 256-token suffix prefills). TTFT is compared between
    the matched first-``n_slots`` admission waves of each round so
    queue position cancels out.

    Gates:
    - parity: round-2 (warm-path) greedy ids match the sequential B=1
      ``generate()`` ids (>= 0.9 over the decoded window — the same
      bf16 argmax-tie bar as the batched row; the cache-off engine is
      pinned to generate() by that row's gate, so this is on-vs-off
      parity by transitivity);
    - TTFT: median warm TTFT < median cold TTFT;
    - reuse: >= 0.7 of round-2 prompt tokens served from the cache,
      round-2 hit rate >= 0.7;
    - throughput under churn: the warm round's aggregate tokens/sec
      must EXCEED the B=1 fused rate (PR 1's batched-decode gate);
    - compile counts: decode/admit/prefix_fetch/prefix_store/
      chunk_prefill all 1 after round 1, unchanged by round 2."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_gen = 8, 64
    shared_len, tail_len, n_reqs = 1024, 256, 16
    prompt_len = shared_len + tail_len
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    shared = rng.integers(0, V, shared_len).tolist()
    prompts = [shared + rng.integers(0, V, tail_len).tolist()
               for _ in range(n_reqs)]

    def one_hot(ids):
        x = np.zeros((1, V, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        return x

    # --- B=1 fused reference: throughput gate + parity ids -----------
    solo_ids = []
    b1_rates = []
    for i, p in enumerate(prompts[:n_slots]):
        net.rnn_clear_previous_state()
        ids = np.asarray(net.generate(one_hot(p), n_gen))  # warm
        if i < 3:
            net.rnn_clear_previous_state()
            t0 = time.perf_counter()
            ids = np.asarray(net.generate(one_hot(p), n_gen))
            b1_rates.append(n_gen / (time.perf_counter() - t0))
        solo_ids.append(ids[0].tolist())
    b1 = float(np.median(b1_rates))

    engine = DecodeEngine(net, n_slots=n_slots, decode_chunk=32,
                          prefix_cache_rows=4, prefill_chunk=256,
                          admission_policy="ttft")

    def one_round():
        ids = [engine.submit(Request(prompt=p, max_new_tokens=n_gen))
               for p in prompts]
        t0 = time.perf_counter()
        results = engine.run()
        dt = time.perf_counter() - t0
        ordered = [results[i] for i in ids]
        toks = sum(len(r.tokens) for r in ordered)
        return ordered, toks / dt

    # warmup on a DIFFERENT shared prefix (first token forced distinct,
    # so the measured cold round still misses): compiles every
    # executable — incl. prefix_fetch via the second request's hit —
    # leaving the cold round to measure admission, not XLA compiles.
    # The two requests run in SEPARATE run() calls: submitted together
    # they would both start admission before either inserts, and the
    # second would miss instead of compiling the fetch path
    other = rng.integers(0, V, shared_len).tolist()
    other[0] = (shared[0] + 1) % V
    for _ in range(2):
        engine.submit(Request(
            prompt=other + rng.integers(0, V, tail_len).tolist(),
            max_new_tokens=n_gen))
        engine.run()

    cold_res, _ = one_round()       # round 1: populates the cache
    counts_warm = engine.compile_counts()
    skipped_r1 = engine.stats["prefill_tokens_skipped"]
    hits_r1 = engine.prefix_cache.stats["hits"]
    warm_res, warm_rate = one_round()   # round 2: every prompt hits
    counts_after = engine.compile_counts()

    for key in ("decode", "admit", "prefix_fetch", "prefix_store",
                "chunk_prefill"):
        if counts_after.get(key) not in (1, -1):
            _fail_gate(f"prefix-cache engine {key} executable count "
                       f"{counts_after.get(key)} != 1")
    if counts_after != counts_warm:
        _fail_gate(f"prefix-cache engine retraced between rounds: "
                   f"{counts_warm} -> {counts_after}")

    matches = [float(np.mean(np.asarray(r.tokens)
                             == np.asarray(solo)))
               for r, solo in zip(warm_res[:n_slots], solo_ids)]
    match = float(np.mean(matches))
    if match < 0.9:
        _fail_gate(f"warm-path/sequential id match {match:.2f}")

    cold_wave = [r.ttft_s for r in cold_res[:n_slots]
                 if r.prefix_tokens_reused == 0]
    warm_wave = [r.ttft_s for r in warm_res[:n_slots]]
    cold_ttft = float(np.median(cold_wave))
    warm_ttft = float(np.median(warm_wave))
    if not warm_ttft < cold_ttft:
        _fail_gate(f"warm TTFT {warm_ttft * 1e3:.1f} ms not below "
                   f"cold {cold_ttft * 1e3:.1f} ms")

    skipped_r2 = engine.stats["prefill_tokens_skipped"] - skipped_r1
    skip_ratio = skipped_r2 / float(n_reqs * prompt_len)
    hit_rate_r2 = (engine.prefix_cache.stats["hits"] - hits_r1) / float(
        n_reqs)
    if skip_ratio < 0.7:
        _fail_gate(f"prefill-tokens-skipped ratio {skip_ratio:.2f} "
                   "< 0.7 on the 80%-shared workload")
    if hit_rate_r2 < 0.7:
        _fail_gate(f"warm-round hit rate {hit_rate_r2:.2f} < 0.7")
    if warm_rate <= b1:
        _fail_gate(f"warm churn decode {warm_rate:.0f} tok/s <= B=1 "
                   f"fused {b1:.0f}")

    return [{
        "metric": "decode_prefix_ttft_ms",
        "value": round(warm_ttft * 1e3, 1),
        "unit": ("ms median submit-to-first-token, warm admission "
                 f"wave ({shared_len}-token shared prefix cached, "
                 f"{tail_len}-token suffix chunk-prefilled; width-1024 "
                 "flagship, 2048-token window)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "cold_ttft_ms": round(cold_ttft * 1e3, 1),
        "warm_vs_cold": round(warm_ttft / cold_ttft, 3),
        "trials": len(warm_wave),
        "spread": [round(min(warm_wave) * 1e3, 1),
                   round(max(warm_wave) * 1e3, 1)],
    }, {
        "metric": "decode_prefix_cached_tokens_per_sec",
        "value": round(warm_rate, 1),
        "unit": (f"aggregate tokens/sec under churn ({n_reqs} reqs x "
                 f"{n_gen} tokens over {n_slots} slots, radix prefix "
                 "cache + 256-token chunked prefill, width-1024 "
                 "flagship)"),
        "vs_baseline": None,
        "trials": 1,
        "vs_b1_fused": round(warm_rate / b1, 2),
        "b1_fused_tokens_per_sec": round(b1, 1),
        "prefill_tokens_skipped_ratio": round(skip_ratio, 4),
        "warm_hit_rate": round(hit_rate_r2, 4),
        "warm_sequential_id_match": round(match, 4),
        "compile_counts": counts_after,
    }]


def bench_decode_paged():
    """Paged KV block pool rows (ISSUE 6 tentpole): at EQUAL window
    and EQUAL device bytes, the block-granular layout (a) runs
    strictly more concurrent decode slots than the dense row layout,
    and (b) serves warm prefix hits by zero-copy block-table splice at
    a TTFT no worse than the PR 2 copy-based warm path.

    Config: width-512 / 4-block transformer, 1024-token window,
    16-token blocks, bf16 — sized so the row validates end-to-end on
    the CPU proxy; both gates are layout properties (byte arithmetic +
    id parity), not throughput races, so they transfer to the chip
    unchanged.

    Gates:
    - capacity: with ``kv_blocks`` = exactly the bytes of the dense
      engine's ``n_dense`` window rows, the paged engine decodes
      ``4 x n_dense`` requests CONCURRENTLY (peak live slots ==
      submitted requests; the dense layout physically caps at
      ``n_dense``) with zero preemptions and ids matching B=1
      ``generate()`` (>= 0.9 bf16 argmax bar);
    - zero-copy warm TTFT: median TTFT over the whole warm round on
      the paged engine <= 1.05x the dense prefix-cache engine's (same
      workload, same rounds); the warm path does ZERO whole-row
      copies —
      counter-asserted: no ``prefix_fetch`` executable exists, splice
      counters moved, and CoW copies stay below one block per
      admission;
    - compile counts: ONE paged decode executable, one scatter, one
      token put — unchanged between rounds."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window, bt = 64, 512, 4, 1024, 16
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    def one_hot(ids):
        x = np.zeros((1, V, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        return x

    rng = np.random.default_rng(0)

    # --- row 1: max concurrent slots at equal device bytes ----------
    n_dense = 4
    n_paged = 4 * n_dense
    kv_blocks = n_dense * (window // bt)   # == n_dense dense rows
    prompt_len, n_gen = 96, 48
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_paged)]
    solo_ids = []
    for p in prompts[:n_dense]:
        net.rnn_clear_previous_state()
        solo_ids.append(
            np.asarray(net.generate(one_hot(p), n_gen))[0].tolist())

    eng = DecodeEngine(net, n_slots=n_paged, decode_chunk=16,
                       paged_kv=True, block_tokens=bt,
                       kv_blocks=kv_blocks)
    ids = [eng.submit(Request(prompt=p, max_new_tokens=n_gen))
           for p in prompts]
    t0 = time.perf_counter()
    results = {}
    peak = blocks_peak = 0
    while eng.has_work():
        eng.step(results)
        peak = max(peak, sum(s is not None for s in eng._slots))
        blocks_peak = max(blocks_peak, eng.block_pool.used_blocks)
    dt = time.perf_counter() - t0
    toks = sum(len(results[i].tokens) for i in ids)
    if set(results) != set(ids):
        _fail_gate("paged capacity run lost requests")
    if any(results[i].finish_reason not in ("length", "eos")
           for i in ids):
        _fail_gate("paged capacity run had unhealthy terminals")
    if peak <= n_dense:
        _fail_gate(
            f"paged peak concurrency {peak} not above the dense "
            f"layout's {n_dense} rows at equal bytes")
    if eng.stats["preempted"]:
        _fail_gate("paged capacity run preempted — budget arithmetic "
                   "is off")
    match = float(np.mean([
        np.mean(np.asarray(results[i].tokens) == np.asarray(s))
        for i, s in zip(ids[:n_dense], solo_ids)]))
    if match < 0.9:
        _fail_gate(f"paged/sequential id match {match:.2f} < 0.9")
    counts = eng.compile_counts()
    for key in ("decode", "paged_scatter", "paged_tok"):
        if counts.get(key) != 1:
            _fail_gate(f"paged {key} executable count "
                       f"{counts.get(key)} != 1")
    row_slots = {
        "metric": "decode_paged_max_slots",
        "value": peak,
        "unit": (f"peak concurrent decode slots at the dense "
                 f"layout's byte budget ({n_dense} x {window}-token "
                 f"rows = {kv_blocks} x {bt}-token blocks; "
                 f"{prompt_len}-token prompts + {n_gen} generated; "
                 f"width-{width} {n_layers}-block transformer, bf16)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "trials": 1,
        "dense_max_slots": n_dense,
        "vs_dense": round(peak / n_dense, 2),
        "aggregate_tokens_per_sec": round(toks / dt, 1),
        "sequential_id_match": round(match, 4),
        "blocks_used_peak": int(blocks_peak),
        "compile_counts": counts,
    }

    # --- row 2: zero-copy warm prefix TTFT vs the PR 2 copy path ----
    shared_len, tail_len, n_reqs, n_slots, n_gen2 = 512, 128, 8, 4, 32
    shared = rng.integers(0, V, shared_len).tolist()
    wprompts = [shared + rng.integers(0, V, tail_len).tolist()
                for _ in range(n_reqs)]

    def ttft_rounds(engine):
        # round 1 populates the cache (cold), round 2 is the warm
        # sample; TTFT is compared over the WHOLE warm round (all
        # n_reqs admissions): the paged engine syncs a wave's
        # admissions together where dense syncs each one eagerly, so
        # a first-wave-only median would reward eager syncing while
        # the paged round finishes every admission sooner
        waves = []
        for _ in range(2):
            rids = [engine.submit(Request(prompt=p,
                                          max_new_tokens=n_gen2))
                    for p in wprompts]
            res = engine.run()
            waves.append([res[r].ttft_s for r in rids])
        return waves

    def build(paged):
        return DecodeEngine(
            net, n_slots=n_slots, decode_chunk=16,
            prefix_cache_rows=4, prefill_chunk=128,
            admission_policy="ttft", paged_kv=paged, block_tokens=bt)

    warm_meds = {}
    warm_waves = {}
    paged_eng = None
    for paged in (False, True):
        engine = build(paged)
        # warmup on a DIFFERENT prefix compiles every executable
        # (incl. the warm-hit path via the second run), so the
        # measured rounds time admissions, not XLA
        other = rng.integers(0, V, shared_len).tolist()
        other[0] = (shared[0] + 1) % V
        for _ in range(2):
            engine.submit(Request(
                prompt=other + rng.integers(0, V, tail_len).tolist(),
                max_new_tokens=n_gen2))
            engine.run()
        _, warm = ttft_rounds(engine)
        warm_meds[paged] = float(np.median(warm))
        warm_waves[paged] = float(np.median(warm[:n_slots]))
        if paged:
            paged_eng = engine
    if not warm_meds[True] <= warm_meds[False] * 1.05:
        _fail_gate(
            f"paged zero-copy warm TTFT {warm_meds[True] * 1e3:.1f} "
            f"ms above the dense copy-based "
            f"{warm_meds[False] * 1e3:.1f} ms")
    pcounts = paged_eng.compile_counts()
    if "prefix_fetch" in pcounts or "prefix_store" in pcounts:
        _fail_gate("paged warm path compiled a row mover — not "
                   "zero-copy")
    if paged_eng.stats["prefix_blocks_spliced"] < n_reqs:
        _fail_gate("paged warm round spliced fewer blocks than "
                   "admissions — hits missed")
    admissions = paged_eng.stats["admitted"]
    if paged_eng.stats["cow_copies"] > 2 * admissions:
        _fail_gate(
            f"paged CoW copies {paged_eng.stats['cow_copies']} "
            f"exceed one boundary block per admission wave "
            f"({admissions} admissions) — whole-row copying snuck "
            "back in")
    row_ttft = {
        "metric": "decode_paged_prefix_ttft_ms",
        "value": round(warm_meds[True] * 1e3, 1),
        "unit": (f"ms median submit-to-first-token, warm admission "
                 f"wave via ZERO-COPY block splice "
                 f"({shared_len}-token shared prefix, {tail_len}-token "
                 f"suffix chunk-prefilled; width-{width} "
                 f"{n_layers}-block transformer, {window}-token "
                 "window, bf16)"),
        "vs_baseline": None,
        "trials": n_reqs,
        "dense_copy_warm_ttft_ms": round(warm_meds[False] * 1e3, 1),
        "vs_dense_copy": round(warm_meds[True] / warm_meds[False], 3),
        "first_wave_ttft_ms": round(warm_waves[True] * 1e3, 1),
        "dense_first_wave_ttft_ms": round(warm_waves[False] * 1e3, 1),
        "prefix_blocks_spliced": int(
            paged_eng.stats["prefix_blocks_spliced"]),
        "cow_copies": int(paged_eng.stats["cow_copies"]),
        "whole_row_copies": 0,
        "compile_counts": pcounts,
    }
    return [row_slots, row_ttft]


def bench_decode_spec():
    """Serving row (ISSUE 4 tentpole): self-speculative decoding —
    n-gram drafting + single-pass K-token verification — on the SAME
    width-1024 flagship / 2048-window / 8-slot config as the
    continuous-batching row, under churn (24 requests over 8 slots, so
    slots freed early by accepted drafts admit new work sooner).

    Workload ("repetitive wave"): each prompt is a 64-token random
    head followed by the model's OWN 128-token greedy continuation —
    the prompt-lookup regime, where the output re-treads material
    present in the prompt (for this random-weight LM, its repetition
    cycles). Candidates whose continuation drifts chaotically are
    filtered out up front by simulating the n-gram table against the
    known true stream (the row advertises the favourable-workload
    ceiling; the acceptance-rate annotation reports what speculation
    actually contributed on it). A speculative round PREPENDS one
    batched verify pass to the decode chunk in the same host
    round-trip: accepted draft tokens + the bonus token are extra
    committed tokens on top of the chunk, so a speculative round never
    commits fewer tokens (nor costs more host round-trips) than a
    plain round — the win degrades toward zero on hostile workloads
    instead of inverting.

    Gates:
    - throughput: the speculative engine's aggregate tokens/sec must
      EXCEED the non-speculative engine measured in the same process
      on the same workload (trials interleaved so slow drift of the
      machine cannot favour either side);
    - parity: spec-on greedy ids match the spec-off engine's ids
      (>= 0.9 over the decoded window — the same bf16 argmax-tie bar
      as the batched row; exact-id equality is asserted at f32 in
      tests/test_serving_spec.py);
    - compile counts: verify executables stay within the pow2
      draft-width buckets (<= log2(K)+1) and NOTHING retraces between
      the warmed timed runs of either engine."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    from deeplearning4j_tpu.serving.spec import NgramDraftTable

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_reqs, n_gen, draft_k = 8, 24, 128, 32
    head_len, cont_len, n_cands = 64, 128, 32
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    def one_hot(ids):
        x = np.zeros((1, V, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        return x

    # candidate prompts = head + the model's own continuation; score
    # each candidate's TAIL predictability by replaying the n-gram
    # table against the known true stream, keep the best n_reqs (the
    # same greedy stream the engines will decode — filtering is pure
    # workload construction, not measurement)
    rng = np.random.default_rng(0)
    cands = []
    for _ in range(n_cands):
        head = rng.integers(0, V, head_len).tolist()
        net.rnn_clear_previous_state()
        stream = np.asarray(net.generate(
            one_hot(head), cont_len + n_gen))[0].tolist()
        prompt = head + stream[:cont_len]
        table = NgramDraftTable()
        table.seed(0, prompt)
        hits = 0
        for tok in stream[cont_len:]:
            d = table.draft(0, 1)
            hits += bool(d and d[0] == tok)
            table.extend(0, [tok])
        cands.append((hits, prompt))
    cands.sort(key=lambda c: -c[0])
    prompts = [p for _, p in cands[:n_reqs]]
    net.rnn_clear_previous_state()

    base = DecodeEngine(net, n_slots=n_slots, decode_chunk=32)
    spec = DecodeEngine(net, n_slots=n_slots, decode_chunk=32,
                        spec_draft_len=draft_k)

    def one_round(engine):
        ids = [engine.submit(Request(prompt=list(p),
                                     max_new_tokens=n_gen))
               for p in prompts]
        t0 = time.perf_counter()
        results = engine.run()
        dt = time.perf_counter() - t0
        ordered = [results[i] for i in ids]
        toks = sum(len(r.tokens) for r in ordered)
        return ordered, toks / dt

    base_res, _ = one_round(base)       # warm: compiles + parity ids
    spec_res, _ = one_round(spec)
    matches = [float(np.mean(np.asarray(s.tokens)
                             == np.asarray(b.tokens)))
               for s, b in zip(spec_res, base_res)]
    match = float(np.mean(matches))
    if match < 0.9:
        _fail_gate(f"spec/non-spec greedy id match {match:.2f}")

    counts0 = {"base": base.compile_counts(),
               "spec": spec.compile_counts()}
    max_buckets = int(np.log2(draft_k)) + 1
    if not 1 <= counts0["spec"]["verify"] <= max_buckets:
        _fail_gate(f"verify executables {counts0['spec']['verify']} "
                   f"outside [1, {max_buckets}] pow2 buckets")

    drafted0 = spec.stats["spec_drafted"]
    accepted0 = spec.stats["spec_accepted"]
    base_rates, spec_rates = [], []
    for _ in range(3):
        _, r = one_round(base)
        base_rates.append(r)
        _, r = one_round(spec)
        spec_rates.append(r)
    counts1 = {"base": base.compile_counts(),
               "spec": spec.compile_counts()}
    if counts1 != counts0:
        _fail_gate(f"speculative bench retraced after warmup: "
                   f"{counts0} -> {counts1}")

    drafted = spec.stats["spec_drafted"] - drafted0
    accepted = spec.stats["spec_accepted"] - accepted0
    acceptance = accepted / max(drafted, 1)
    base_rate = float(np.median(base_rates))
    spec_rate = float(np.median(spec_rates))
    if spec_rate <= base_rate:
        _fail_gate(f"speculative decode {spec_rate:.0f} tok/s <= "
                   f"non-speculative {base_rate:.0f} on the "
                   "repetitive workload")
    rounds = (spec.stats["spec_rounds"]
              + spec.stats["spec_fallback_rounds"])
    return {
        "metric": "decode_spec_tokens_per_sec",
        "value": round(spec_rate, 1),
        "unit": (f"aggregate tokens/sec (width-1024 flagship, "
                 f"2048-token KV window, {n_reqs} reqs x {n_gen} "
                 f"tokens over {n_slots} slots, n-gram drafting "
                 f"K={draft_k} + single-pass verification riding the "
                 "decode round, predictability-filtered "
                 "self-continuation workload)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "spread": [round(min(spec_rates), 1),
                   round(max(spec_rates), 1)],
        "trials": len(spec_rates),
        "vs_nonspec_engine": round(spec_rate / base_rate, 2),
        "nonspec_tokens_per_sec": round(base_rate, 1),
        "acceptance_rate": round(acceptance, 4),
        "workload_tail_predictability": round(
            float(np.mean([h for h, _ in cands[:n_reqs]])) / n_gen,
            4),
        "tokens_per_round": round(
            spec.stats["tokens_generated"] / max(rounds, 1), 2),
        "spec_round_share": round(
            spec.stats["spec_rounds"] / max(rounds, 1), 4),
        "spec_nonspec_id_match": round(match, 4),
        "compile_counts": counts1["spec"],
    }


def bench_fused_decode():
    """Fused multi-round decode rows (ISSUE 16 tentpole).

    Row 1 — ``fused_decode_tokens_per_sec``: B=1 decode on the
    width-1024 flagship / 2048-window config at ``decode_chunk=1``
    (the latency-oriented stream where EVERY token pays the host step
    loop: dispatch, token fetch, bookkeeping). The fused engine
    (``fused_rounds=8``) dispatches ONE on-device scan per 8 rounds —
    the host loop is amortized 8x — and must beat the stepped engine
    by >= 1.15x on the CPU proxy (the host loop is the cost being
    deleted; on a real chip the dispatch share is larger still).
    Gates: ids BIT-IDENTICAL to the stepped engine (same per-round op
    sequence, just scanned), exactly ONE fused executable (the
    workload's remaining-token count walks down in whole K=8 windows,
    so only the K=8 pow2 bucket compiles), zero retrace between the
    warmed timed runs, interleaved median-of-3.

    Row 2 — ``fused_itl_storm_ratio``: the PR 14 admission-storm soak
    re-run with fused rounds ON (``async_rounds=True`` +
    ``fused_rounds=8``): the victim stream's mean ITL under a
    continuous chunked-admission storm must stay within the existing
    <= 1.1x + 3ms-CPU-slack gate over the STEPPED idle-admission ITL
    (``fused_rounds`` lowered to 0 for the idle runs — the PR 14
    denominator; idle ITL with fusing ON is reported separately, it
    is the ~1.4x FASTER number and would make the ratio measure the
    idle speedup instead of storm damage). The storm keeps the queue
    non-empty, so the engine falls back to per-round stepping and
    admission keeps its cadence; a fused engine that held the device
    for K rounds while arrivals waited would blow this gate.

    Annotation — stochastic acceptance (the second tentpole half):
    a sampling-temperature request over a repetitive prompt on a
    spec engine must actually draft (sampling traffic rides the
    verify pass now); its acceptance rate is reported."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window = 64, 1024, 8, 2048
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, V, 16).tolist()
    # 1 admission token + 128 decode tokens = sixteen whole K=8
    # windows at decode_chunk=1: only the K=8 bucket ever compiles
    n_gen, fuse_k = 129, 8

    stepped = DecodeEngine(net, n_slots=1, decode_chunk=1, seed=0)
    fused = DecodeEngine(net, n_slots=1, decode_chunk=1, seed=0,
                         fused_rounds=fuse_k)

    def one_round(engine):
        rid = engine.submit(Request(list(prompt), n_gen))
        t0 = time.perf_counter()
        res = engine.run()[rid]
        dt = time.perf_counter() - t0
        return res.tokens, len(res.tokens) / dt

    step_ids, _ = one_round(stepped)    # warm: compiles + parity ids
    fused_ids, _ = one_round(fused)
    if fused_ids != step_ids:
        _fail_gate("fused decode ids diverged from the stepped "
                   "engine's — the scan is not the same computation")
    counts0 = fused.compile_counts()
    if counts0.get("fused_decode") != 1:
        _fail_gate(f"fused executables {counts0.get('fused_decode')} "
                   "!= 1 (whole-window workload must stay in the "
                   "K=8 pow2 bucket)")
    step_rates, fused_rates = [], []
    for _ in range(3):
        _, r = one_round(stepped)
        step_rates.append(r)
        _, r = one_round(fused)
        fused_rates.append(r)
    counts1 = fused.compile_counts()
    if counts1 != counts0:
        _fail_gate(f"fused bench retraced after warmup: "
                   f"{counts0} -> {counts1}")
    step_rate = float(np.median(step_rates))
    fused_rate = float(np.median(fused_rates))
    if fused_rate < 1.15 * step_rate:
        _fail_gate(
            f"fused decode {fused_rate:.0f} tok/s < 1.15x stepped "
            f"{step_rate:.0f} — the scan is not deleting the host "
            "loop")

    # --- stochastic-acceptance annotation: sampling rides spec ------
    spec = DecodeEngine(net, n_slots=1, decode_chunk=4,
                        spec_draft_len=8, seed=0)
    rep = ([7, 3, 11, 5] * 12)[:48]
    rid = spec.submit(Request(rep, 64, temperature=0.8, top_k=8))
    spec.run()
    drafted = spec.stats["spec_drafted"]
    accepted = spec.stats["spec_accepted"]
    if drafted == 0:
        _fail_gate("sampling-temperature traffic did not ride the "
                   "spec verify pass (stochastic acceptance is not "
                   "drafting)")
    row_fused = {
        "metric": "fused_decode_tokens_per_sec",
        "value": round(fused_rate, 1),
        "unit": (f"tokens/sec (width-1024 flagship, 2048-token KV "
                 f"window, B=1, decode_chunk=1, fused_rounds="
                 f"{fuse_k} scan vs per-round stepping, interleaved "
                 "median of 3; gate >= 1.15x stepped, ids "
                 "bit-identical)"),
        "vs_baseline": None,  # reference rnnTimeStep has no LM serving
        "spread": [round(min(fused_rates), 1),
                   round(max(fused_rates), 1)],
        "trials": len(fused_rates),
        "vs_stepped_engine": round(fused_rate / step_rate, 2),
        "stepped_tokens_per_sec": round(step_rate, 1),
        "id_match": 1.0,
        "sampling_spec_acceptance_rate": round(
            accepted / max(drafted, 1), 4),
        "sampling_spec_drafted": int(drafted),
        "compile_counts": counts1,
    }

    # --- row 2: admission storm with fused rounds on ----------------
    V2, width2, n_layers2, window2, bt = 64, 512, 4, 1024, 16
    conf2 = transformer_lm_flagship(
        vocab=V2, width=width2, n_layers=n_layers2, n_heads=8,
        seed=11)
    for c in conf2.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window2
    net2 = MultiLayerNetwork(conf2).init()

    def victim_itl(eng, storm_rng, storm):
        rid = eng.submit(Request(
            storm_rng.integers(0, V2, 24).tolist(), 256))
        res = {}
        fed = 0
        while eng.has_work():
            if storm and fed < 24 and eng.scheduler.pending < 2:
                eng.submit(Request(
                    storm_rng.integers(0, V2, 8).tolist(), 2))
                fed += 1
            eng.step(res)
        r = res[rid]
        return ((r.timing["e2e_s"] - r.timing["ttft_s"])
                / (len(r.tokens) - 1))

    storm_rng = np.random.default_rng(1)
    eng = DecodeEngine(net2, n_slots=8, decode_chunk=32,
                       paged_kv=True, block_tokens=bt,
                       prefill_chunk=8, admission_policy="decode",
                       seed=0, async_rounds=True,
                       fused_rounds=fuse_k)
    # warm every pow2 K-bucket the storm's mixed remaining-token
    # counts can reach, so no fused compile lands inside a timed run
    for warm_gen in (257, 97, 65, 33, 2):
        eng.submit(Request(
            storm_rng.integers(0, V2, 8).tolist(), warm_gen))
        eng.run()
    idles, fused_idles, storms = [], [], []
    for _ in range(3):
        # stepped idle (the PR 14 denominator): fusing off — a
        # host-side knob, the executables and ring stay warm
        eng.fused_rounds = 0
        idles.append(victim_itl(eng, storm_rng, storm=False))
        eng.fused_rounds = fuse_k
        fused_idles.append(victim_itl(eng, storm_rng, storm=False))
        storms.append(victim_itl(eng, storm_rng, storm=True))
    idle_med = sorted(idles)[1]
    fused_idle_med = sorted(fused_idles)[1]
    storm_med = sorted(storms)[1]
    if storm_med > 1.1 * idle_med + 3e-3:
        _fail_gate(
            f"fused-rounds decode ITL under the admission storm is "
            f"{storm_med * 1e3:.2f}ms vs stepped idle "
            f"{idle_med * 1e3:.2f}ms (> 1.1x + 3ms slack): the "
            "fused scan is starving admission")
    row_storm = {
        "metric": "fused_itl_storm_ratio",
        "value": round(storm_med / idle_med, 3),
        "unit": ("victim-stream mean ITL under a continuous "
                 "chunked-admission storm over STEPPED idle-admission "
                 "ITL (async_rounds=True + fused_rounds=8 under the "
                 "storm, fused_rounds=0 for the idle baseline, "
                 "decode-priority, median of 3 interleaved triples; "
                 "gate <= 1.1x + 3ms CPU slack — the PR 14 storm "
                 "soak with the fused engine)"),
        "vs_baseline": None,
        "trials": 3,
        "idle_itl_ms": round(idle_med * 1e3, 2),
        "fused_idle_itl_ms": round(fused_idle_med * 1e3, 2),
        "fused_idle_speedup": round(idle_med / fused_idle_med, 2),
        "storm_itl_ms": round(storm_med * 1e3, 2),
    }
    return [row_fused, row_storm]


def bench_gateway_streaming():
    """Serving row (ISSUE 5 tentpole): aggregate throughput through
    the HTTP serving gateway — 8 concurrent SSE streaming clients over
    localhost against the SAME width-1024 flagship / 2048-window /
    8-slot engine config as the in-process batched row. The gateway
    adds a stepping thread, per-delta fan-out queues, SSE framing, and
    socket writes on top of the engine; this row prices that stack.

    Gates:
    - overhead: the HTTP-path aggregate tokens/sec must stay >= 0.9x
      the in-process ``run()`` aggregate measured in the same process
      with interleaved trials (the gateway is a translation layer —
      10% is the allowance for framing + loopback, not for stalling
      the engine);
    - parity: every streamed request's ids are bit-identical to the
      in-process engine's for the same seeded workload (same config,
      same greedy computation — HTTP must change nothing);
    - compile counts: identical before/after the timed HTTP rounds —
      the network layer never retraces an executable."""
    import threading

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        GatewayClient,
        Request,
        ServingGateway,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_gen, prompt_len = 8, 128, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_slots)]

    inproc = DecodeEngine(net, n_slots=n_slots, decode_chunk=32)

    def inproc_round():
        ids = [inproc.submit(Request(prompt=list(p),
                                     max_new_tokens=n_gen))
               for p in prompts]
        t0 = time.perf_counter()
        results = inproc.run()
        dt = time.perf_counter() - t0
        toks = sum(len(results[i].tokens) for i in ids)
        return toks / dt, [results[i].tokens for i in ids]

    _, ref_tokens = inproc_round()  # warm: compiles + reference ids

    # admission_grace_s: the 8 clients submit over ~ms of thread
    # scheduling jitter; the batch-formation window keeps round 1 from
    # running at 1/8 occupancy because one submit won the lock first
    # (in-process run() gets the same full slate by construction)
    gw_engine = DecodeEngine(net, n_slots=n_slots, decode_chunk=32)
    gateway = ServingGateway(gw_engine, keepalive_s=1.0,
                             admission_grace_s=0.25).start()
    client = GatewayClient(gateway.address, timeout_s=600.0)

    def http_round():
        outs = [None] * n_slots
        ttfts = [None] * n_slots
        errors = [None] * n_slots

        def one(i):
            try:
                t_sub = time.perf_counter()
                s = client.stream(prompts[i], n_gen)
                toks, t_first = [], None
                for delta in s:
                    if t_first is None:
                        t_first = time.perf_counter() - t_sub
                    toks.extend(delta)
                outs[i] = toks
                ttfts[i] = t_first
            except Exception as e:  # surface WHICH client died & why
                errors[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_slots)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        failed = {i: repr(e) for i, e in enumerate(errors) if e}
        if failed:
            raise RuntimeError(f"gateway stream clients failed: "
                               f"{failed}")
        toks = sum(len(o) for o in outs)
        return toks / dt, outs, ttfts, dt / max(toks, 1)

    # try/finally: a gate failure must not leave the gateway's stepper
    # thread + HTTP server alive to tax every later bench row
    try:
        _, outs, _, _ = http_round()  # warm the gateway engine
        id_match = float(np.mean([outs[i] == ref_tokens[i]
                                  for i in range(n_slots)]))
        if id_match < 1.0:
            _fail_gate(f"gateway stream ids diverged from the "
                       f"in-process engine (match {id_match:.2f})")

        counts0 = gw_engine.compile_counts()
        in_rates, http_rates, per_tok, ttft_all = [], [], [], []
        for _ in range(3):  # interleaved: drift hits both alike
            r, _ = inproc_round()
            in_rates.append(r)
            r, _, ttfts, tok_s = http_round()
            http_rates.append(r)
            per_tok.append(tok_s)
            ttft_all.extend(t for t in ttfts if t is not None)
        counts1 = gw_engine.compile_counts()
        if counts1 != counts0:
            _fail_gate(f"gateway engine retraced under HTTP traffic: "
                       f"{counts0} -> {counts1}")
    finally:
        gateway.close()
    inproc_rate = float(np.median(in_rates))
    http_rate = float(np.median(http_rates))
    ratio = http_rate / inproc_rate
    if ratio < 0.9:
        _fail_gate(
            f"gateway streaming {http_rate:.0f} tok/s < 0.9x "
            f"in-process {inproc_rate:.0f} (ratio {ratio:.2f})")
    return {
        "metric": "gateway_streaming_tokens_per_sec",
        "value": round(http_rate, 1),
        "unit": (f"aggregate tokens/sec through the HTTP gateway "
                 f"(width-1024 flagship, 2048-token KV window, "
                 f"{n_slots} concurrent SSE streams x {n_gen} tokens, "
                 "localhost)"),
        "vs_baseline": None,  # reference has no serving frontend
        "spread": [round(min(http_rates), 1),
                   round(max(http_rates), 1)],
        "trials": len(http_rates),
        "vs_in_process": round(ratio, 3),
        "in_process_tokens_per_sec": round(inproc_rate, 1),
        "per_token_latency_ms": round(
            1e3 * float(np.median(per_tok)), 3),
        "mean_ttft_ms": round(1e3 * float(np.mean(ttft_all)), 1),
        "gateway_http_id_match": round(id_match, 4),
        "compile_counts": counts1,
    }


def bench_router_overhead():
    """Router-tier row (ISSUE 9): the multi-replica router must be a
    near-free translation layer. 8 concurrent SSE streams over TWO
    gateway replicas (width-1024 flagship, 2048-token window, 4 slots
    each), once DIRECT to the gateways (4 streams each — the same
    engines, no router) and once THROUGH the router, interleaved
    trials. The delta is exactly the router's relay cost: journaling,
    high-water bookkeeping, a second SSE hop per delta.

    Gates:
    - overhead: router-path aggregate tokens/sec >= 0.9x the
      direct-to-gateway aggregate on the same replicas;
    - parity: every routed stream's ids bit-identical to the
      in-process single-engine reference (id match 1.0) — the router
      changes nothing about the computation;
    - compile counts: identical before/after routed traffic on both
      replica engines.

    Annotation: affinity hit rate on an 80%-shared-prefix workload —
    the fraction of warm-eligible requests that landed on the replica
    holding their prefix warm (measured by per-request
    ``prefix_tokens_reused`` through the router)."""
    import threading

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        GatewayClient,
        Request,
        RouterClient,
        ServingGateway,
        ServingRouter,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_streams, n_gen, prompt_len = 8, 64, 128
    per_replica_slots = 4
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_streams)]

    # in-process single-engine reference: the ids every routed stream
    # must match bit for bit (greedy parity across batch topologies
    # is an engine guarantee the serving suite gates)
    ref_eng = DecodeEngine(net, n_slots=n_streams, decode_chunk=32)
    ref_ids = [ref_eng.submit(Request(prompt=list(p),
                                      max_new_tokens=n_gen))
               for p in prompts]
    ref_res = ref_eng.run()
    ref_tokens = [ref_res[i].tokens for i in ref_ids]

    engines = [DecodeEngine(net, n_slots=per_replica_slots,
                            decode_chunk=32, prefix_cache_rows=8)
               for _ in range(2)]
    gateways = [ServingGateway(e, keepalive_s=1.0,
                               admission_grace_s=0.25,
                               replica_id=f"bench-rep-{i}").start()
                for i, e in enumerate(engines)]
    router = ServingRouter([g.address for g in gateways],
                           health_interval_s=0.25,
                           affinity_block_tokens=16).start()
    direct_clients = [GatewayClient(g.address, timeout_s=600.0)
                      for g in gateways]
    routed_client = RouterClient(router.address, timeout_s=600.0)

    def stream_round(client_of):
        """8 concurrent streams; client_of(i) picks the connection
        target per stream index."""
        outs = [None] * n_streams
        errors = [None] * n_streams

        def one(i):
            try:
                s = client_of(i).stream(prompts[i], n_gen)
                toks = []
                for delta in s:
                    toks.extend(delta)
                outs[i] = toks
            except Exception as e:
                errors[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        failed = {i: repr(e) for i, e in enumerate(errors) if e}
        if failed:
            raise RuntimeError(f"stream clients failed: {failed}")
        toks = sum(len(o) for o in outs)
        return toks / dt, outs

    # direct mode pins stream i to replica i%2 — the same 4/4 split
    # the router's rendezvous would have to beat
    def direct_of(i):
        return direct_clients[i % 2]

    def routed_of(i):
        return routed_client

    try:
        _, outs = stream_round(routed_of)  # warm both replicas + ref
        id_match = float(np.mean([outs[i] == ref_tokens[i]
                                  for i in range(n_streams)]))
        if id_match < 1.0:
            _fail_gate(f"routed stream ids diverged from the "
                       f"in-process reference (match "
                       f"{id_match:.2f})")
        stream_round(direct_of)  # warm the direct path alike
        counts0 = [e.compile_counts() for e in engines]
        direct_rates, routed_rates = [], []
        for _ in range(3):  # interleaved: drift hits both alike
            r, _ = stream_round(direct_of)
            direct_rates.append(r)
            r, _ = stream_round(routed_of)
            routed_rates.append(r)
        counts1 = [e.compile_counts() for e in engines]
        if counts1 != counts0:
            _fail_gate(f"replica engines retraced under routed "
                       f"traffic: {counts0} -> {counts1}")

        # affinity annotation: 80%-shared-prefix workload — 8 of 10
        # prompts share a 64-token system prefix (4 affinity blocks)
        shared = rng.integers(0, V, 64).tolist()
        aff_prompts = [shared + rng.integers(0, V, 8).tolist()
                       for _ in range(8)]
        aff_prompts += [rng.integers(0, V, 72).tolist()
                        for _ in range(2)]
        aff_outs = []
        for p in aff_prompts:
            aff_outs.append(routed_client.generate(p, 8))
        warm_eligible = aff_outs[1:8]  # shared cohort minus cold fill
        aff_hits = sum(1 for o in warm_eligible
                       if o["prefix_tokens_reused"] > 0)
        affinity_hit_rate = aff_hits / len(warm_eligible)
        if affinity_hit_rate < 0.7:
            _fail_gate(f"affinity hit rate {affinity_hit_rate:.2f} "
                       "< 0.7 on the 80%-shared-prefix workload")
    finally:
        router.close()
        for g in gateways:
            g.close()
    direct_rate = float(np.median(direct_rates))
    routed_rate = float(np.median(routed_rates))
    ratio = routed_rate / direct_rate
    if ratio < 0.9:
        _fail_gate(
            f"router streaming {routed_rate:.0f} tok/s < 0.9x "
            f"direct-to-gateway {direct_rate:.0f} "
            f"(ratio {ratio:.2f})")
    return {
        "metric": "router_streaming_tokens_per_sec",
        "value": round(routed_rate, 1),
        "unit": (f"aggregate tokens/sec through the multi-replica "
                 f"router (width-1024 flagship, 2048-token KV "
                 f"window, 2 replicas x {per_replica_slots} slots, "
                 f"{n_streams} concurrent SSE streams x {n_gen} "
                 "tokens, localhost)"),
        "vs_baseline": None,  # reference has no serving frontend
        "spread": [round(min(routed_rates), 1),
                   round(max(routed_rates), 1)],
        "trials": len(routed_rates),
        "vs_direct_gateway": round(ratio, 3),
        "direct_tokens_per_sec": round(direct_rate, 1),
        "router_http_id_match": round(id_match, 4),
        "affinity_hit_rate": round(affinity_hit_rate, 3),
        "compile_counts": counts1,
    }


def bench_fleet_trace_overhead():
    """Fleet-observability row (ISSUE 10 acceptance): trace-context
    propagation + the router's fleet tracing (route/queue_wait spans,
    per-replica trace-cache scraping, clock-offset estimation) must be
    cheap enough to leave ON. 8 concurrent SSE streams over TWO
    gateway replicas (the bench_router_overhead topology), through a
    fleet-TRACED router vs a ``fleet_trace=False`` twin over the SAME
    replicas, interleaved trials.

    Gates:
    - overhead: traced-path aggregate tokens/sec >= 0.97x the
      untraced path (the context is one header + one span-args
      string per hop; the scrape rides the existing health loop);
    - parity: ids bit-identical traced vs untraced vs the in-process
      single-engine reference — a trace id must never touch the
      computation;
    - zero retrace: compile counts identical before/after on both
      replica engines (span args are host metadata, not jit inputs);
    - the instruments actually recorded: every traced result carries
      its fleet trace id, the stitched ``/v1/trace`` shows both
      replica lanes skew-corrected, and the replicas' flight records
      carry the router-minted context."""
    import threading

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        Request,
        RouterClient,
        ServingGateway,
        ServingRouter,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_streams, n_gen, prompt_len = 8, 64, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_streams)]
    ref_eng = DecodeEngine(net, n_slots=n_streams, decode_chunk=32)
    ref_ids = [ref_eng.submit(Request(prompt=list(p),
                                      max_new_tokens=n_gen))
               for p in prompts]
    ref_res = ref_eng.run()
    ref_tokens = [ref_res[i].tokens for i in ref_ids]

    engines = [DecodeEngine(net, n_slots=4, decode_chunk=32,
                            prefix_cache_rows=8)
               for _ in range(2)]
    gateways = [ServingGateway(e, keepalive_s=1.0,
                               admission_grace_s=0.25,
                               replica_id=f"fleet-rep-{i}").start()
                for i, e in enumerate(engines)]
    addresses = [g.address for g in gateways]
    traced_router = ServingRouter(addresses, health_interval_s=0.25,
                                  affinity_block_tokens=16,
                                  fleet_trace=True).start()
    dark_router = ServingRouter(addresses, health_interval_s=0.25,
                                affinity_block_tokens=16,
                                fleet_trace=False).start()
    traced_client = RouterClient(traced_router.address,
                                 timeout_s=600.0)
    dark_client = RouterClient(dark_router.address, timeout_s=600.0)

    def stream_round(client):
        outs = [None] * n_streams
        finals = [None] * n_streams
        errors = [None] * n_streams

        def one(i):
            try:
                s = client.stream(prompts[i], n_gen)
                toks = []
                for delta in s:
                    toks.extend(delta)
                outs[i] = toks
                finals[i] = s.result
            except Exception as e:
                errors[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        failed = {i: repr(e) for i, e in enumerate(errors) if e}
        if failed:
            raise RuntimeError(f"stream clients failed: {failed}")
        return sum(len(o) for o in outs) / dt, outs, finals

    try:
        _, outs, finals = stream_round(traced_client)  # warm + check
        id_match = float(np.mean([outs[i] == ref_tokens[i]
                                  for i in range(n_streams)]))
        if id_match < 1.0:
            _fail_gate(f"traced stream ids diverged from the "
                       f"in-process reference (match {id_match:.2f})")
        if not all(f and f.get("trace") for f in finals):
            _fail_gate("traced results missing fleet trace ids")
        _, dark_outs, dark_finals = stream_round(dark_client)
        if dark_outs != outs:
            _fail_gate("untraced stream ids differ from traced — "
                       "the trace context leaked into computation")
        if any(f and f.get("trace") for f in dark_finals):
            _fail_gate("fleet_trace=False results carry trace ids")
        counts0 = [e.compile_counts() for e in engines]
        traced_rates, dark_rates = [], []
        for _ in range(3):  # interleaved: drift hits both alike
            r, _, _ = stream_round(dark_client)
            dark_rates.append(r)
            r, _, _ = stream_round(traced_client)
            traced_rates.append(r)
        counts1 = [e.compile_counts() for e in engines]
        if counts1 != counts0:
            _fail_gate(f"replica engines retraced under traced "
                       f"traffic: {counts0} -> {counts1}")
        # the stitch is real: both replica lanes, skew-corrected
        doc = traced_client.trace_events()
        stitch = next(e for e in doc["traceEvents"]
                      if e.get("name") == "fleet.stitch")
        lanes = stitch["args"]["replicas"]
        if (len(lanes) != 2
                or not all(r["skew_corrected"] for r in lanes)):
            _fail_gate(f"stitched trace lanes wrong: {lanes}")
        # a replica flight record carries the router-minted context
        probe = traced_client.trace(finals[0]["id"])
        if not str(probe.get("trace", "")).startswith(
                str(finals[0]["trace"])):
            _fail_gate(f"replica flight record lost the fleet trace "
                       f"context: {probe.get('trace')!r}")
    finally:
        traced_router.close()
        dark_router.close()
        for g in gateways:
            g.close()
    traced_rate = float(np.median(traced_rates))
    dark_rate = float(np.median(dark_rates))
    ratio = traced_rate / dark_rate
    if ratio < 0.97:
        _fail_gate(
            f"fleet tracing costs too much: {traced_rate:.0f} tok/s "
            f"traced < 0.97x {dark_rate:.0f} untraced "
            f"(ratio {ratio:.3f})")
    return {
        "metric": "fleet_observability_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("traced-router / untraced-router aggregate "
                 "streaming tokens/sec (width-1024 flagship, "
                 "2048-token KV window, 2 replicas x 4 slots, "
                 f"{n_streams} concurrent SSE streams x {n_gen} "
                 "tokens, localhost; fleet tracing = trace-context "
                 "propagation + router spans + trace-cache scrape + "
                 "clock-offset estimation)"),
        "vs_baseline": None,  # reference has no fleet tier at all
        "spread": [round(min(traced_rates) / max(dark_rates), 4),
                   round(max(traced_rates) / min(dark_rates), 4)],
        "trials": len(traced_rates),
        "traced_tokens_per_sec": round(traced_rate, 1),
        "untraced_tokens_per_sec": round(dark_rate, 1),
        "router_http_id_match": round(id_match, 4),
        "compile_counts": counts1,
    }


def bench_fleet_controller_overhead():
    """Fleet-controller row (ISSUE 11 acceptance): the control loop
    must be a free rider on the serving path. 8 concurrent SSE
    streams over TWO gateway replicas (the bench_router_overhead
    topology), through a router whose :class:`FleetController` loop
    is LIVE — scraping replica status and the federated TTFT window
    every ``eval_interval_s``, evaluating SLOs, never triggering a
    scale event (min == max == fleet size; thresholds unreachable) —
    vs a controller-free router over the SAME replicas, interleaved
    trials.

    Gates:
    - overhead: controller-path aggregate tokens/sec >= 0.97x the
      controller-off path (the loop is a sidecar thread reading
      host-side state; its federated scrape rides a separate
      connection);
    - parity: ids bit-identical both paths vs the in-process
      single-engine reference;
    - zero retrace: compile counts identical before/after on both
      replica engines;
    - the loop actually ran (evaluations counted, zero errors) and
      actually held (zero scale events)."""
    import threading

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        FleetController,
        Request,
        RouterClient,
        ServingGateway,
        ServingRouter,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_streams, n_gen, prompt_len = 8, 64, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_streams)]
    ref_eng = DecodeEngine(net, n_slots=n_streams, decode_chunk=32)
    ref_ids = [ref_eng.submit(Request(prompt=list(p),
                                      max_new_tokens=n_gen))
               for p in prompts]
    ref_res = ref_eng.run()
    ref_tokens = [ref_res[i].tokens for i in ref_ids]

    engines = [DecodeEngine(net, n_slots=4, decode_chunk=32,
                            prefix_cache_rows=8)
               for _ in range(2)]
    gateways = [ServingGateway(e, keepalive_s=1.0,
                               admission_grace_s=0.25,
                               replica_id=f"ctl-rep-{i}").start()
                for i, e in enumerate(engines)]
    addresses = [g.address for g in gateways]
    ctl_router = ServingRouter(addresses, health_interval_s=0.25,
                               affinity_block_tokens=16).start()
    plain_router = ServingRouter(addresses, health_interval_s=0.25,
                                 affinity_block_tokens=16).start()
    # a LIVE loop that must never act: fleet already at min == max,
    # thresholds unreachable — pure observation cost
    controller = FleetController(
        ctl_router, replica_factory=None,
        min_replicas=2, max_replicas=2,
        eval_interval_s=0.25, ttft_p99_slo_s=1000.0,
        pressure_high=1e9, pressure_low=0.0).start()
    ctl_client = RouterClient(ctl_router.address, timeout_s=600.0)
    plain_client = RouterClient(plain_router.address,
                                timeout_s=600.0)

    def stream_round(client):
        outs = [None] * n_streams
        errors = [None] * n_streams

        def one(i):
            try:
                s = client.stream(prompts[i], n_gen)
                toks = []
                for delta in s:
                    toks.extend(delta)
                outs[i] = toks
            except Exception as e:
                errors[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        failed = {i: repr(e) for i, e in enumerate(errors) if e}
        if failed:
            raise RuntimeError(f"stream clients failed: {failed}")
        return sum(len(o) for o in outs) / dt, outs

    try:
        _, outs = stream_round(ctl_client)  # warm + parity check
        id_match = float(np.mean([outs[i] == ref_tokens[i]
                                  for i in range(n_streams)]))
        if id_match < 1.0:
            _fail_gate(f"controller-path stream ids diverged from "
                       f"the in-process reference (match "
                       f"{id_match:.2f})")
        _, plain_outs = stream_round(plain_client)
        if plain_outs != outs:
            _fail_gate("controller-off stream ids differ — the "
                       "control loop leaked into computation")
        counts0 = [e.compile_counts() for e in engines]
        ctl_rates, plain_rates = [], []
        for _ in range(3):  # interleaved: drift hits both alike
            r, _ = stream_round(plain_client)
            plain_rates.append(r)
            r, _ = stream_round(ctl_client)
            ctl_rates.append(r)
        counts1 = [e.compile_counts() for e in engines]
        if counts1 != counts0:
            _fail_gate(f"replica engines retraced under controller "
                       f"traffic: {counts0} -> {counts1}")
        if controller.stats["evals"] < 3:
            _fail_gate(f"control loop barely ran "
                       f"({controller.stats['evals']} evals) — the "
                       "row would price nothing")
        if controller.stats["errors"]:
            _fail_gate(f"control loop errored "
                       f"{controller.stats['errors']}x during the "
                       "bench")
        if controller.events:
            _fail_gate(f"controller scaled during the overhead row "
                       f"(events {controller.events}) — the "
                       "comparison is no longer same-fleet")
    finally:
        controller.close()
        ctl_router.close()
        plain_router.close()
        for g in gateways:
            g.close()
    ctl_rate = float(np.median(ctl_rates))
    plain_rate = float(np.median(plain_rates))
    ratio = ctl_rate / plain_rate
    if ratio < 0.97:
        _fail_gate(
            f"fleet controller costs too much: {ctl_rate:.0f} tok/s "
            f"with the loop live < 0.97x {plain_rate:.0f} without "
            f"(ratio {ratio:.3f})")
    return {
        "metric": "fleet_controller_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("controller-on / controller-off router aggregate "
                 "streaming tokens/sec (width-1024 flagship, "
                 "2048-token KV window, 2 replicas x 4 slots, "
                 f"{n_streams} concurrent SSE streams x {n_gen} "
                 "tokens, localhost; loop live at 4 Hz scraping "
                 "replica status + the federated TTFT window, no "
                 "scale events triggered)"),
        "vs_baseline": None,  # reference has no fleet tier at all
        "spread": [round(min(ctl_rates) / max(plain_rates), 4),
                   round(max(ctl_rates) / min(plain_rates), 4)],
        "trials": len(ctl_rates),
        "controller_tokens_per_sec": round(ctl_rate, 1),
        "plain_tokens_per_sec": round(plain_rate, 1),
        "controller_evals": controller.stats["evals"],
        "router_http_id_match": round(id_match, 4),
        "compile_counts": counts1,
    }


def bench_router_wal_overhead():
    """Durable-router row (ISSUE 15 acceptance): the write-ahead
    journal must be a free rider on the serving path. 8 concurrent
    SSE streams over TWO gateway replicas (the standard flagship
    router topology), through a router journaling every
    open/route/progress/done transition to an on-disk WAL with the
    default BATCHED fsync, vs an identically-configured WAL-off
    router over the SAME replicas, interleaved trials.

    Gates:
    - overhead: WAL-on aggregate tokens/sec >= 0.97x WAL-off (the
      journal is framed appends + coalesced fsync on the relay
      threads' path);
    - parity: ids bit-identical both paths vs the in-process
      single-engine reference;
    - zero retrace: compile counts identical before/after on both
      replica engines;
    - the WAL actually recorded the traffic (every stream's open +
      done framed on disk, recoverable by a fresh fold)."""
    import tempfile
    import threading

    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        Request,
        RouterClient,
        ServingGateway,
        ServingRouter,
        read_records,
        recover_state,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_streams, n_gen, prompt_len = 8, 64, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_streams)]
    ref_eng = DecodeEngine(net, n_slots=n_streams, decode_chunk=32)
    ref_ids = [ref_eng.submit(Request(prompt=list(p),
                                      max_new_tokens=n_gen))
               for p in prompts]
    ref_res = ref_eng.run()
    ref_tokens = [ref_res[i].tokens for i in ref_ids]

    engines = [DecodeEngine(net, n_slots=4, decode_chunk=32,
                            prefix_cache_rows=8)
               for _ in range(2)]
    gateways = [ServingGateway(e, keepalive_s=1.0,
                               admission_grace_s=0.25,
                               replica_id=f"wal-rep-{i}").start()
                for i, e in enumerate(engines)]
    addresses = [g.address for g in gateways]
    tmp = tempfile.mkdtemp(prefix="bench-router-wal-")
    wal_path = os.path.join(tmp, "router.wal")
    wal_router = ServingRouter(addresses, health_interval_s=0.25,
                               affinity_block_tokens=16,
                               journal_path=wal_path,
                               fsync="batched").start()
    plain_router = ServingRouter(addresses, health_interval_s=0.25,
                                 affinity_block_tokens=16).start()
    wal_client = RouterClient(wal_router.address, timeout_s=600.0)
    plain_client = RouterClient(plain_router.address,
                                timeout_s=600.0)

    def stream_round(client):
        outs = [None] * n_streams
        errors = [None] * n_streams

        def one(i):
            try:
                s = client.stream(prompts[i], n_gen)
                toks = []
                for delta in s:
                    toks.extend(delta)
                outs[i] = toks
            except Exception as e:
                errors[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        failed = {i: repr(e) for i, e in enumerate(errors) if e}
        if failed:
            raise RuntimeError(f"stream clients failed: {failed}")
        return sum(len(o) for o in outs) / dt, outs

    try:
        _, outs = stream_round(wal_client)  # warm + parity check
        id_match = float(np.mean([outs[i] == ref_tokens[i]
                                  for i in range(n_streams)]))
        if id_match < 1.0:
            _fail_gate(f"WAL-path stream ids diverged from the "
                       f"in-process reference (match "
                       f"{id_match:.2f})")
        _, plain_outs = stream_round(plain_client)
        if plain_outs != outs:
            _fail_gate("WAL-off stream ids differ — the journal "
                       "leaked into computation")
        counts0 = [e.compile_counts() for e in engines]
        wal_rates, plain_rates = [], []
        for _ in range(3):  # interleaved: drift hits both alike
            r, _ = stream_round(plain_client)
            plain_rates.append(r)
            r, _ = stream_round(wal_client)
            wal_rates.append(r)
        counts1 = [e.compile_counts() for e in engines]
        if counts1 != counts0:
            _fail_gate(f"replica engines retraced under WAL "
                       f"traffic: {counts0} -> {counts1}")
        # the journal recorded every stream and folds back clean
        records, torn = read_records(wal_path)
        if torn:
            _fail_gate(f"WAL has a torn tail ({torn} bytes) on a "
                       "healthy run")
        state = recover_state(records)
        done_n = sum(1 for e in state["entries"].values()
                     if e["done"])
        expected = 4 * n_streams  # warm round + 3 timed rounds
        if done_n < expected:
            _fail_gate(f"WAL recovered only {done_n} terminal "
                       f"entries of {expected} journaled streams")
        wal_bytes = os.path.getsize(wal_path)
    finally:
        import shutil

        wal_router.close()
        plain_router.close()
        for g in gateways:
            g.close()
        shutil.rmtree(tmp, ignore_errors=True)
    wal_rate = float(np.median(wal_rates))
    plain_rate = float(np.median(plain_rates))
    ratio = wal_rate / plain_rate
    if ratio < 0.97:
        _fail_gate(
            f"WAL costs too much: {wal_rate:.0f} tok/s journaled "
            f"< 0.97x {plain_rate:.0f} without (ratio {ratio:.3f})")
    return {
        "metric": "router_wal_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("WAL-on (batched fsync) / WAL-off router aggregate "
                 "streaming tokens/sec (width-1024 flagship, "
                 "2048-token KV window, 2 replicas x 4 slots, "
                 f"{n_streams} concurrent SSE streams x {n_gen} "
                 "tokens, localhost; every open/route/progress/done "
                 "transition framed + CRC'd to disk)"),
        "vs_baseline": None,  # reference has no router tier at all
        "spread": [round(min(wal_rates) / max(plain_rates), 4),
                   round(max(wal_rates) / min(plain_rates), 4)],
        "trials": len(wal_rates),
        "wal_tokens_per_sec": round(wal_rate, 1),
        "plain_tokens_per_sec": round(plain_rate, 1),
        "wal_bytes": wal_bytes,
        "wal_recovered_terminals": done_n,
        "router_http_id_match": round(id_match, 4),
        "compile_counts": counts1,
    }


def bench_kv_transfer():
    """KV transfer plane rows (ISSUE 14 tentpole).

    Row 1 — ``kv_transfer_warm_admission_speedup``: cross-replica
    warm admission beats local recompute on a LONG (512-token)
    prompt. A donor engine warms three distinct 512-token prompts and
    exports each as a framed binary payload; a cold receiver pays the
    full-prefill recompute (the control), a second receiver imports
    the payload first and admits warm. Gates: median warm admission
    (import wall + TTFT) < median recompute TTFT, ids BIT-IDENTICAL
    to the donor's (zero retrace asserted on the warm receiver across
    trials, >= 511 prompt tokens spliced per warm admission).

    Row 2 — ``kv_async_itl_storm_ratio``: decode ITL under an
    admission storm stays <= ~1.1x idle-admission ITL on the
    ``async_rounds=True`` engine (the in-engine half of ROADMAP item
    2: double-buffered dispatch hides the inter-round host gap the
    storm inflates). Measured as the VICTIM stream's mean ITL
    ((e2e - ttft)/(tokens-1) — exact, per request; the
    ``serving_itl_s`` histogram pools every stream's per-round gaps,
    including the storm's own short requests, and its log buckets
    quantize p50s at 1.78x steps, so the per-victim mean is the
    resolvable form of the same measurement), median of 3
    interleaved idle/storm pairs; the synchronous twin's ratio is
    annotated as the counterfactual."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window, bt = 64, 512, 4, 1024, 16
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    prompt_len, n_gen, n_trials = 512, 16, 3
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_trials)]
    eng_kw = dict(n_slots=2, decode_chunk=8, paged_kv=True,
                  block_tokens=bt, prefix_cache_rows=4,
                  prefill_chunk=64, seed=0)

    # --- row 1: warm-import admission vs full-prefill recompute -----
    donor = DecodeEngine(net, **eng_kw)
    refs, payloads = [], []
    for p in prompts:
        rid = donor.submit(Request(p, n_gen))
        refs.append(donor.run()[rid].tokens)
        payloads.append(donor.export_kv(p))
    if any(pay is None for pay in payloads):
        _fail_gate("kv donor failed to export a warmed prompt")
        return []
    cold = DecodeEngine(net, **eng_kw)
    warm = DecodeEngine(net, **eng_kw)
    cold_ttfts, warm_costs = [], []
    warm_counts = None
    for i, p in enumerate(prompts):
        rid = cold.submit(Request(p, n_gen))
        res = cold.run()[rid]
        if res.tokens != refs[i]:
            _fail_gate(f"kv recompute control diverged on prompt {i}")
        cold_ttfts.append(res.ttft_s)
        t0 = time.perf_counter()
        out = warm.import_kv(payloads[i])
        t_import = time.perf_counter() - t0
        if not out.get("imported"):
            _fail_gate(f"kv import declined on prompt {i}: {out}")
            continue
        rid = warm.submit(Request(p, n_gen))
        res = warm.run()[rid]
        if res.tokens != refs[i]:
            _fail_gate(f"kv warm-import admission diverged on "
                       f"prompt {i} — the transfer corrupted ids")
        if res.prefix_tokens_reused < prompt_len - 1:
            _fail_gate(
                f"warm admission reused only "
                f"{res.prefix_tokens_reused}/{prompt_len - 1} prompt "
                "tokens — the import did not actually serve it")
        warm_costs.append(t_import + res.ttft_s)
        counts = warm.compile_counts()
        if warm_counts is None:
            warm_counts = counts  # trial-1 executables
        elif counts != warm_counts:
            _fail_gate(f"warm receiver retraced between trials: "
                       f"{warm_counts} -> {counts}")
    cold_med = sorted(cold_ttfts)[len(cold_ttfts) // 2]
    warm_med = sorted(warm_costs)[len(warm_costs) // 2]
    if warm_med >= cold_med:
        _fail_gate(
            f"warm-import admission {warm_med:.3f}s did not beat "
            f"full-prefill recompute {cold_med:.3f}s on a "
            f"{prompt_len}-token prompt")
    row_warm = {
        "metric": "kv_transfer_warm_admission_speedup",
        "value": round(cold_med / max(warm_med, 1e-9), 2),
        "unit": (f"recompute-TTFT over (import + warm-TTFT), median "
                 f"of {n_trials} distinct {prompt_len}-token "
                 f"prompts; width-{width} {n_layers}-block "
                 f"transformer, {window}-window, {bt}-token blocks, "
                 "bf16"),
        "vs_baseline": None,  # reference rnnTimeStep has no KV plane
        "trials": n_trials,
        "recompute_ttft_ms": round(1e3 * cold_med, 1),
        "warm_admission_ms": round(1e3 * warm_med, 1),
        "payload_mb": round(len(payloads[0]) / 2**20, 2),
        "prefix_tokens_reused": prompt_len - 1,
        "id_match": 1.0,
        "compile_counts": warm_counts,
    }

    # --- row 2: decode ITL under an admission storm (async rounds) --
    def victim_itl(eng, storm_rng, storm):
        rid = eng.submit(Request(
            storm_rng.integers(0, V, 24).tolist(), 256))
        res = {}
        fed = 0
        while eng.has_work():
            if storm and fed < 24 and eng.scheduler.pending < 2:
                eng.submit(Request(
                    storm_rng.integers(0, V, 8).tolist(), 2))
                fed += 1
            eng.step(res)
        r = res[rid]
        return ((r.timing["e2e_s"] - r.timing["ttft_s"])
                / (len(r.tokens) - 1))

    storm_kw = dict(n_slots=8, decode_chunk=32, paged_kv=True,
                    block_tokens=bt, prefill_chunk=8,
                    admission_policy="decode", seed=0)
    meds = {}
    for mode in (True, False):
        storm_rng = np.random.default_rng(1)
        eng = DecodeEngine(net, async_rounds=mode, **storm_kw)
        eng.submit(Request(storm_rng.integers(0, V, 8).tolist(), 34))
        eng.run()  # compile warm-up, excluded
        idles, storms = [], []
        for _ in range(3):
            idles.append(victim_itl(eng, storm_rng, storm=False))
            storms.append(victim_itl(eng, storm_rng, storm=True))
        meds[mode] = (sorted(idles)[1], sorted(storms)[1])
    idle_med, storm_med = meds[True]
    # 3 ms absolute slack on top of the 1.1x ratio: CPU-proxy ITLs
    # sit at ~30 ms where host-scheduler noise alone swings several
    # percent between identical runs (same spirit as the tenant
    # soak's fast-mode slack); on a real chip ITLs are ms-scale and
    # the ratio term dominates
    if storm_med > 1.1 * idle_med + 3e-3:
        _fail_gate(
            f"async-rounds decode ITL under the admission storm is "
            f"{storm_med * 1e3:.2f}ms vs idle "
            f"{idle_med * 1e3:.2f}ms (> 1.1x + 3ms slack): "
            "double-buffered dispatch is not hiding the admission "
            "gap")
    row_itl = {
        "metric": "kv_async_itl_storm_ratio",
        "value": round(storm_med / idle_med, 3),
        "unit": ("victim-stream mean ITL under a continuous "
                 "chunked-admission storm over idle-admission ITL "
                 "(async_rounds=True, decode-priority, median of 3 "
                 "interleaved pairs; gate <= 1.1x + 3ms CPU slack)"),
        "vs_baseline": None,
        "trials": 3,
        "idle_itl_ms": round(idle_med * 1e3, 2),
        "storm_itl_ms": round(storm_med * 1e3, 2),
        "sync_engine_ratio": round(meds[False][1] / meds[False][0],
                                   3),
    }
    return [row_warm, row_itl]


def bench_kv_tier():
    """Tiered KV cache rows (ISSUE 17 tentpole).

    Row 1 — ``kv_tier_thrash_speedup``: a cache-thrashing
    long-prompt workload whose working set is ~4x the HBM block pool
    (6 distinct 512-token prompts x 32 blocks each = 192 blocks over
    a 48-block pool) cycled round-robin, so every revisit finds its
    prefix EVICTED from the trie. The no-tier engine recomputes the
    full 512-token prefill per revisit (the seed behavior); the
    tiered engine reloads the spilled payload from host DRAM through
    the jitted ``kv_import`` scatter. Gates: >= 2x tokens/s (the
    host-DRAM sibling of PR 14's 5.8x warm-vs-recompute gap), ids
    BIT-IDENTICAL between the two engines on every request, zero
    retrace across the timed passes, and every timed tiered
    admission actually reloaded (no silent recomputes inflating the
    denominator's twin).

    Row 2 — ``kv_tier_spill_itl_storm_ratio``: the PR 14/16 storm
    gate with SPILL CHURN active — the admission storm's unique
    prompts overflow an 8-row trie, so every storm round evicts and
    spills (staged gather at eviction, host pack drained at
    round end). The victim stream's ITL must stay within the same
    <= 1.1x + 3 ms envelope as the tier-off engine, proving the
    spill path stays off the decode hot path."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    # window 544 (not the transfer bench's 1024): the pool floor is
    # one slot's window + a round of writes, and the thrash row needs
    # a pool SMALL enough that 6 resident prompts are 4x over it
    V, width, n_layers, window, bt = 64, 512, 4, 544, 16
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    prompt_len, n_gen, n_prompts = 512, 8, 6
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_prompts)]
    kv_blocks = 48  # 6 x 32-block prefixes = 192 wanted: 4x pool
    eng_kw = dict(n_slots=1, decode_chunk=8, paged_kv=True,
                  block_tokens=bt, kv_blocks=kv_blocks,
                  prefix_cache_rows=8, prefill_chunk=64, seed=0)

    # --- row 1: thrash throughput, tier vs no-tier ------------------
    def one_pass(eng, ids_out=None):
        toks = 0
        for p in prompts:
            rid = eng.submit(Request(list(p), n_gen))
            res = eng.run()[rid]
            toks += len(res.tokens)
            if ids_out is not None:
                ids_out.append(res.tokens)
        return toks

    walls, all_ids = {}, {}
    tier_counts = None
    for tiered in (False, True):
        eng = DecodeEngine(net, **dict(
            eng_kw, kv_host_tier_bytes=(64 << 20) if tiered else 0))
        one_pass(eng)        # pass 1: cold compute (tier: spills)
        one_pass(eng)        # pass 2: warm-up the revisit path
        #                      (tier: first reload compiles its
        #                      kv_import bucket — excluded, like
        #                      every bench's compile warm-up)
        if tiered:
            tier_counts = eng.compile_counts()
            reloads0 = eng.kv_tier.stats["reloads"]
        ids = []
        t0 = time.perf_counter()
        toks = one_pass(eng, ids) + one_pass(eng, ids)
        walls[tiered] = (toks, time.perf_counter() - t0)
        all_ids[tiered] = ids
        if tiered:
            if eng.compile_counts() != tier_counts:
                _fail_gate(
                    f"tiered engine retraced during the timed "
                    f"passes: {tier_counts} -> "
                    f"{eng.compile_counts()}")
            reloaded = eng.kv_tier.stats["reloads"] - reloads0
            if reloaded < 2 * n_prompts:
                _fail_gate(
                    f"only {reloaded}/{2 * n_prompts} timed "
                    "admissions reloaded from the tier — the rest "
                    "recomputed, so the speedup is mislabeled")
            s = eng.kv_tier.stats
            if s["spills"] != (s["reloads"] + s["drops"]
                               + len(eng.kv_tier)):
                _fail_gate(f"tier books don't reconcile: {s} vs "
                           f"{len(eng.kv_tier)} resident")
    if all_ids[True] != all_ids[False]:
        _fail_gate("tiered engine ids diverged from the no-tier "
                   "engine under thrash — spill/reload corrupted "
                   "state")
    (toks_off, wall_off), (toks_on, wall_on) = walls[False], walls[True]
    tps_off = toks_off / max(wall_off, 1e-9)
    tps_on = toks_on / max(wall_on, 1e-9)
    if tps_on < 2.0 * tps_off:
        _fail_gate(
            f"tiered thrash throughput {tps_on:.1f} tok/s is under "
            f"2x the no-tier engine's {tps_off:.1f} tok/s — the "
            "host reload is not beating recompute")
    row_thrash = {
        "metric": "kv_tier_thrash_speedup",
        "value": round(tps_on / max(tps_off, 1e-9), 2),
        "unit": (f"tokens/s over a round-robin of {n_prompts} "
                 f"distinct {prompt_len}-token prompts whose "
                 f"{n_prompts * prompt_len // bt} prefix blocks are "
                 f"~4x the {kv_blocks}-block pool (2 timed passes; "
                 f"width-{width} {n_layers}-layer transformer, "
                 "bf16); no-tier engine recomputes every revisit, "
                 "tiered engine reloads from host DRAM"),
        "vs_baseline": None,  # the seed engine HAS no spill tier
        "tier_tokens_per_s": round(tps_on, 1),
        "no_tier_tokens_per_s": round(tps_off, 1),
        "id_match": 1.0,
        "compile_counts": tier_counts,
    }

    # --- row 2: victim ITL with spill churn active ------------------
    def victim_itl(eng, storm_rng, storm):
        rid = eng.submit(Request(
            storm_rng.integers(0, V, 24).tolist(), 256))
        res = {}
        fed = 0
        while eng.has_work():
            # storm prompts span >= 2 complete blocks so every trie
            # eviction they force is SPILLABLE (a sub-block victim
            # has nothing packed to spill)
            if storm and fed < 24 and eng.scheduler.pending < 2:
                eng.submit(Request(
                    storm_rng.integers(0, V, 40).tolist(), 2))
                fed += 1
            eng.step(res)
        r = res[rid]
        return ((r.timing["e2e_s"] - r.timing["ttft_s"])
                / (len(r.tokens) - 1))

    # unique storm prompts overflow the 8-row trie: every storm
    # admission evicts an earlier row -> spill churn DURING the
    # victim's decode (the exact hot-path hazard under test)
    storm_kw = dict(n_slots=8, decode_chunk=32, paged_kv=True,
                    block_tokens=bt, prefill_chunk=8,
                    prefix_cache_rows=8, admission_policy="decode",
                    async_rounds=True, seed=0,
                    kv_host_tier_bytes=64 << 20)
    storm_rng = np.random.default_rng(1)
    eng = DecodeEngine(net, **storm_kw)
    eng.submit(Request(storm_rng.integers(0, V, 40).tolist(), 34))
    eng.run()  # compile warm-up, excluded
    # one untimed interleaved pair: the storm overflows the trie and
    # compiles BOTH kv_gather spill buckets (the storm rows' small
    # bucket and the evicted victim row's 32-block bucket) before
    # anything is measured
    victim_itl(eng, storm_rng, storm=False)
    victim_itl(eng, storm_rng, storm=True)
    idles, storms = [], []
    spills0 = eng.kv_tier.stats["spills"]
    for _ in range(3):
        idles.append(victim_itl(eng, storm_rng, storm=False))
        storms.append(victim_itl(eng, storm_rng, storm=True))
    idle_med, storm_med = sorted(idles)[1], sorted(storms)[1]
    churn = eng.kv_tier.stats["spills"] - spills0
    if churn < 10:
        _fail_gate(
            f"the storm only drove {churn} spills — the ITL gate "
            "is not measuring spill churn")
    # same envelope as bench_kv_transfer row 2 (PR 14/16): 1.1x
    # ratio + 3 ms absolute slack for CPU-proxy scheduler noise
    if storm_med > 1.1 * idle_med + 3e-3:
        _fail_gate(
            f"victim ITL with spill churn is "
            f"{storm_med * 1e3:.2f}ms vs idle "
            f"{idle_med * 1e3:.2f}ms (> 1.1x + 3ms slack): the "
            "spill path is leaking onto the decode hot path")
    row_itl = {
        "metric": "kv_tier_spill_itl_storm_ratio",
        "value": round(storm_med / idle_med, 3),
        "unit": ("victim-stream mean ITL under a trie-overflowing "
                 "admission storm with the host tier spilling every "
                 "eviction, over idle-admission ITL (async_rounds, "
                 "decode-priority, median of 3 interleaved pairs; "
                 "gate <= 1.1x + 3ms CPU slack)"),
        "vs_baseline": None,
        "trials": 3,
        "idle_itl_ms": round(idle_med * 1e3, 2),
        "storm_itl_ms": round(storm_med * 1e3, 2),
        "storm_spills": churn,
    }
    return [row_thrash, row_itl]


def bench_tenant_qos_overhead():
    """Multi-tenant QoS row (ISSUE 13 acceptance): tenancy must be
    FREE when unused. Single-tenant traffic (every request on the
    implicit ``default`` tenant) through the weighted-fair scheduler
    vs the SAME workload on the seed FIFO scheduler — same net, same
    width-1024 flagship / 2048-window / 8-slot config, interleaved
    median-of-3.

    Gates:
    - overhead: weighted-fair aggregate tokens/sec >= 0.97x the seed
      scheduler's (the per-round begin_round/pop_admissible hooks and
      the per-tenant histograms are host-side bookkeeping — they may
      not tax the decode hot path);
    - parity: ids bit-identical across the two engines (one
      backlogged tenant's fair order IS arrival order);
    - zero retrace on both engines, and the QoS layer must not have
      acted (zero preemptions, zero sheds): tenancy-on with one
      tenant is OBSERVATION only."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        Request,
        TenantRegistry,
    )

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_gen, prompt_len = 8, 128, 128
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_slots)]

    seed_eng = DecodeEngine(net, n_slots=n_slots, decode_chunk=32)
    fair_eng = DecodeEngine(net, n_slots=n_slots, decode_chunk=32,
                            tenants=TenantRegistry())

    def one_round(engine):
        ids = [engine.submit(Request(prompt=list(p),
                                     max_new_tokens=n_gen))
               for p in prompts]
        t0 = time.perf_counter()
        results = engine.run()
        dt = time.perf_counter() - t0
        toks = sum(len(results[i].tokens) for i in ids)
        return toks / dt, [results[i].tokens for i in ids]

    _, seed_tokens = one_round(seed_eng)   # warm + reference ids
    _, fair_tokens = one_round(fair_eng)
    id_match = float(np.mean([fair_tokens[i] == seed_tokens[i]
                              for i in range(n_slots)]))
    if id_match < 1.0:
        _fail_gate(f"weighted-fair ids diverged from the seed "
                   f"scheduler (match {id_match:.2f})")

    counts0 = {"seed": seed_eng.compile_counts(),
               "fair": fair_eng.compile_counts()}
    seed_rates, fair_rates = [], []
    for _ in range(3):  # interleaved: drift hits both alike
        r, _ = one_round(seed_eng)
        seed_rates.append(r)
        r, _ = one_round(fair_eng)
        fair_rates.append(r)
    counts1 = {"seed": seed_eng.compile_counts(),
               "fair": fair_eng.compile_counts()}
    if counts1 != counts0:
        _fail_gate(f"tenancy bench retraced: {counts0} -> {counts1}")
    if (fair_eng.stats["qos_preempted"] or fair_eng.stats["shed"]
            or fair_eng.stats["preempted"]):
        _fail_gate(
            "the QoS layer ACTED on single-tenant traffic "
            f"(qos_preempted {fair_eng.stats['qos_preempted']}, "
            f"shed {fair_eng.stats['shed']}) — tenancy-on with one "
            "tenant must be observation only")

    seed_rate = float(np.median(seed_rates))
    fair_rate = float(np.median(fair_rates))
    ratio = fair_rate / seed_rate
    if ratio < 0.97:
        _fail_gate(
            f"weighted-fair scheduler {fair_rate:.0f} tok/s < 0.97x "
            f"seed scheduler {seed_rate:.0f} (ratio {ratio:.3f}) — "
            "tenancy is supposed to be free when unused")
    return {
        "metric": "tenant_qos_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("aggregate tokens/sec, weighted-fair scheduler "
                 "(default tenant only) / seed FIFO scheduler "
                 f"(width-1024 flagship, 2048-token window, "
                 f"{n_slots} slots x {n_gen} tokens, interleaved "
                 "median-of-3)"),
        "vs_baseline": None,  # reference has no tenancy tier
        "spread": [round(min(fair_rates) / max(seed_rates), 4),
                   round(max(fair_rates) / min(seed_rates), 4)],
        "trials": len(fair_rates),
        "fair_tokens_per_sec": round(fair_rate, 1),
        "seed_tokens_per_sec": round(seed_rate, 1),
        "tenant_id_match": round(id_match, 4),
        "compile_counts": counts1["fair"],
    }


def bench_observability_overhead():
    """Observability row (ISSUE 7 acceptance): the request-scoped
    flight recorder must be cheap enough to leave ON. Same width-1024
    flagship / 2048-window / 8-slot engine config as the serving rows,
    16-request churn; the observed engine runs with EVERYTHING on —
    capped tracer (request-id'd spans + request_done instants),
    latency histograms, phase clocks, 256-deep flight recorder —
    against a ``tracer=None, record_timing=False`` twin.

    Gates:
    - overhead: observed throughput >= 0.97x the dark engine's
      (interleaved median-of-3 — observability is host bookkeeping,
      ~60 ns clock stamps per dispatch, and must price like it);
    - parity: greedy ids bit-identical observed-vs-dark (the phase
      clock touches no RNG, no device work);
    - zero retrace: compile counts identical before/after the timed
      trials, and equal across the two engines;
    - the instruments actually recorded: every histogram populated,
      every request's trace in the flight recorder with phase sums
      <= e2e."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.profiler.tracer import Tracer
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    V, width, n_layers, window = 64, 1024, 8, 2048
    n_slots, n_req, n_gen, prompt_len = 8, 16, 48, 96
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=8, seed=11)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, prompt_len).tolist()
               for _ in range(n_req)]

    dark = DecodeEngine(net, n_slots=n_slots, decode_chunk=32,
                        tracer=None, record_timing=False,
                        flight_recorder=0)
    observed = DecodeEngine(net, n_slots=n_slots, decode_chunk=32,
                            tracer=Tracer(max_events=65536),
                            record_timing=True, flight_recorder=256)

    def churn(eng):
        ids = [eng.submit(Request(prompt=list(p),
                                  max_new_tokens=n_gen))
               for p in prompts]
        t0 = time.perf_counter()
        results = eng.run()
        dt = time.perf_counter() - t0
        toks = sum(len(results[i].tokens) for i in ids)
        return toks / dt, [results[i].tokens for i in ids], ids

    _, ref_ids, _ = churn(dark)      # warm: compiles
    _, obs_ids, rids = churn(observed)
    id_match = float(np.mean([a == b
                              for a, b in zip(ref_ids, obs_ids)]))
    if id_match < 1.0:
        _fail_gate(f"observability changed greedy ids "
                   f"(match {id_match:.3f})")
    for rid in rids:
        trace = observed.request_trace(rid)
        if trace is None:
            _fail_gate(f"request {rid} missing from the flight "
                       "recorder")
            continue
        t = trace["timing"]
        phase_sum = (t["queue_wait_s"] + t["admission_s"]
                     + t["decode_s"] + t["verify_s"] + t["stall_s"])
        if phase_sum > t["e2e_s"]:
            _fail_gate(f"request {rid} phase sum {phase_sum} > e2e "
                       f"{t['e2e_s']}")
    empty = [k for k, h in observed.histograms.items()
             if h.count == 0]
    if empty:
        _fail_gate(f"histograms never observed: {empty}")

    counts0 = (dark.compile_counts(), observed.compile_counts())
    dark_rates, obs_rates = [], []
    for _ in range(3):  # interleaved: drift hits both alike
        r, _, _ = churn(dark)
        dark_rates.append(r)
        r, _, _ = churn(observed)
        obs_rates.append(r)
    counts1 = (dark.compile_counts(), observed.compile_counts())
    if counts1 != counts0 or counts0[0] != counts0[1]:
        _fail_gate(f"observability retraced: {counts0} -> {counts1}")
    dark_rate = float(np.median(dark_rates))
    obs_rate = float(np.median(obs_rates))
    ratio = obs_rate / dark_rate
    if ratio < 0.97:
        _fail_gate(
            f"observability overhead: {obs_rate:.0f} tok/s < 0.97x "
            f"dark {dark_rate:.0f} (ratio {ratio:.3f})")
    ttft_hist = observed.histograms["serving_ttft_s"]
    itl_hist = observed.histograms["serving_itl_s"]
    return {
        "metric": "observability_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("tokens/sec with tracer + histograms + flight "
                 "recorder ON / tokens/sec dark (width-1024 "
                 f"flagship, 2048-token KV window, {n_slots} slots, "
                 f"{n_req}-request churn x {n_gen} tokens)"),
        "vs_baseline": None,  # reference has no serving stack at all
        "spread": [round(min(o / d for o, d
                             in zip(obs_rates, dark_rates)), 4),
                   round(max(o / d for o, d
                             in zip(obs_rates, dark_rates)), 4)],
        "trials": len(obs_rates),
        "observed_tokens_per_sec": round(obs_rate, 1),
        "dark_tokens_per_sec": round(dark_rate, 1),
        "id_match": round(id_match, 4),
        "ttft_p50_ms": round(1e3 * ttft_hist.quantile(0.5), 2),
        "ttft_p99_ms": round(1e3 * ttft_hist.quantile(0.99), 2),
        "itl_p50_ms": round(1e3 * itl_hist.quantile(0.5), 3),
        "itl_p99_ms": round(1e3 * itl_hist.quantile(0.99), 3),
        "compile_counts": counts1[1],
    }


def bench_train_observability_overhead():
    """Training-observability row (ISSUE 8 acceptance): the tracing
    listener + phase clock + gradient-health outputs must be cheap
    enough to leave ON. MLP 784-500-10 (the BASELINE headline config)
    trained via fused 16-step fit_scan windows; the observed net runs a
    ``TracingIterationListener`` with a capped tracer, all six
    histograms, and a JSONL metrics log firing every window, against a
    listener-free twin.

    Gates:
    - overhead: observed examples/sec >= 0.97x the dark net's
      (interleaved median-of-3 — the health scalars ride the SAME
      executable, so the only cost is host bookkeeping + the per-window
      score sync the listener performs);
    - parity: final params BIT-IDENTICAL dark-vs-observed (same seed,
      same batches, same executable — telemetry touches no RNG and no
      device math);
    - zero retrace: the fit_scan executable count is identical
      before/after the timed trials and equal across the two nets
      (the health outputs exist in both: no listener-conditional
      tracing);
    - the instruments recorded: every histogram populated, every JSONL
      record's phase sums <= window wall."""
    import tempfile

    from deeplearning4j_tpu.models.zoo import mlp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.listeners import (
        TracingIterationListener,
    )
    from deeplearning4j_tpu.optimize.telemetry import MetricsLog
    from deeplearning4j_tpu.profiler.tracer import Tracer

    K, B, windows = 16, 128, 4
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(K, B, 784)).astype(np.float32)
    labels = np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, (K, B))]

    dark = MultiLayerNetwork(mlp()).init()
    observed = MultiLayerNetwork(mlp()).init()
    tracer = Tracer(max_events=65536)
    log_path = tempfile.mktemp(suffix=".jsonl")
    metrics_log = MetricsLog(log_path)
    listener = TracingIterationListener(tracer=tracer,
                                        metrics_log=metrics_log)
    observed.set_listeners(listener)

    def run_windows(net, n):
        for _ in range(n):
            net.fit_scan(feats, labels)
        return _sync(net.score_value)

    run_windows(dark, 1)      # warm: compiles
    run_windows(observed, 1)
    counts0 = (dark._train_steps_scan._cache_size(),
               observed._train_steps_scan._cache_size())

    dark_rates, obs_rates = [], []
    for _ in range(3):  # interleaved: drift hits both alike
        t0 = time.perf_counter()
        run_windows(dark, windows)
        dark_rates.append(
            windows * K * B / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        run_windows(observed, windows)
        obs_rates.append(
            windows * K * B / (time.perf_counter() - t0))
    counts1 = (dark._train_steps_scan._cache_size(),
               observed._train_steps_scan._cache_size())
    metrics_log.close()

    if counts1 != counts0 or counts0[0] != counts0[1]:
        _fail_gate(
            f"training observability retraced: {counts0} -> {counts1}")
    import jax

    p_dark = jax.tree.leaves(dark.params)
    p_obs = jax.tree.leaves(observed.params)
    params_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(p_dark, p_obs))
    if not params_equal:
        _fail_gate("training observability changed the param "
                   "trajectory (final params differ)")
    empty = [name for name, h in listener.hists.items()
             if h.count == 0]
    if empty:
        _fail_gate(f"training histograms never observed: {empty}")
    bad_sums = 0
    for rec in MetricsLog.read(log_path):
        if "wall_s" not in rec:
            continue
        phase_sum = (rec.get("data_wait_s", 0.0)
                     + rec.get("dispatch_s", 0.0)
                     + rec.get("sync_s", 0.0))
        if phase_sum > rec["wall_s"] + 1e-9:
            bad_sums += 1
    if bad_sums:
        _fail_gate(f"{bad_sums} JSONL records with phase sums > wall")
    os.unlink(log_path)

    dark_rate = float(np.median(dark_rates))
    obs_rate = float(np.median(obs_rates))
    ratio = obs_rate / dark_rate
    if ratio < 0.97:
        _fail_gate(
            f"training observability overhead: {obs_rate:.0f} ex/s < "
            f"0.97x dark {dark_rate:.0f} (ratio {ratio:.3f})")
    step_hist = listener.hists["train_step_s"]
    grad_hist = listener.hists["train_grad_norm"]
    return {
        "metric": "train_observability_overhead_ratio",
        "value": round(ratio, 4),
        "unit": ("examples/sec with tracing listener + histograms + "
                 "JSONL log ON / examples/sec dark (MLP 784-500-10, "
                 f"{windows}x fused {K}-step fit_scan windows, "
                 f"batch {B})"),
        "vs_baseline": None,  # reference listeners carry no timing
        "spread": [round(min(o / d for o, d
                             in zip(obs_rates, dark_rates)), 4),
                   round(max(o / d for o, d
                             in zip(obs_rates, dark_rates)), 4)],
        "trials": len(obs_rates),
        "observed_examples_per_sec": round(obs_rate, 1),
        "dark_examples_per_sec": round(dark_rate, 1),
        "params_bit_identical": params_equal,
        "step_p50_ms": round(1e3 * step_hist.quantile(0.5), 3),
        "step_p99_ms": round(1e3 * step_hist.quantile(0.99), 3),
        "grad_norm_p50": round(grad_hist.quantile(0.5), 4),
        "compile_counts": {"fit_scan": counts1[1]},
    }


def bench_w2v():
    """BASELINE row 3: Word2Vec skip-gram words/sec with a semantic
    quality gate on the bundled REAL corpus (the reference's
    Word2VecTests corpus; SequenceVectors.java:100). NS mode — the
    configuration that reproduces real semantics (BENCHMARKS.md)."""
    from deeplearning4j_tpu.datasets.fixtures import raw_sentences
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    sents = raw_sentences() * 10  # 10x the bundled corpus (review #9)
    n_words = sum(len(s.split()) for s in sents)
    w2v = Word2Vec(layer_size=100, window=5, min_word_frequency=5,
                   batch_size=2048, seed=3, subsampling=1e-3,
                   use_hierarchic_softmax=False, negative=5)
    w2v.build_vocab_from(sents)
    w2v.fit(sents)  # warm: compiles every code-length class shape
    w2v._reset_weights()
    rates = []
    for _ in range(7):  # 7 epochs = 7 trials; vectors keep training
        t0 = time.perf_counter()
        w2v.fit(sents)
        _ = np.asarray(w2v.syn0)[0, 0]  # force device completion
        rates.append(n_words / (time.perf_counter() - t0))
    sim_close = float(w2v.similarity("day", "night"))
    sim_far = float(w2v.similarity("day", "money"))
    quality = bool(sim_close > 0.4 and sim_close - sim_far > 0.2)
    if not quality:
        _fail_gate(
            f"w2v quality sim(day,night)={sim_close:.3f} "
            f"sim(day,money)={sim_far:.3f}")
    med = float(np.median(rates))
    return {
        "metric": "w2v_skipgram_ns_words_per_sec",
        "value": round(med, 1),
        "unit": "words/sec/chip (real corpus x10: 971,620 sentences / ~7.57M words, negative=5)",
        "vs_baseline": round(med / REFERENCE_CPU_W2V_WORDS_PER_SEC, 2),
        "spread": [round(min(rates), 1), round(max(rates), 1)],
        "trials": len(rates),
        "quality_gate": quality,
        "sim_day_night": round(sim_close, 3),
        "sim_day_money": round(sim_far, 3),
    }


def bench_dbn():
    """BASELINE row 4: DBN pretrain epochs/sec + finetune accuracy
    (reference MultiLayerNetwork.pretrain :150 + RBM CD-k :110)."""
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.datasets.mnist import mnist_dataset
    from deeplearning4j_tpu.models.zoo import dbn
    from deeplearning4j_tpu.nn.conf.enums import Updater
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    n = 8192
    ds = mnist_dataset(train=True, num_examples=n)
    batches = ds.batch_by(1024)
    net = MultiLayerNetwork(
        dbn(lr=0.05, updater=Updater.NESTEROVS)).init()
    for _ in range(2):  # compile + steady-state warm
        net.pretrain(ListDataSetIterator(batches))
    rates = []
    # 3-epoch windows x 7 trials, min/max trimmed: single-epoch
    # windows (~1 s) were dispatch-latency lottery — r4 spread hit
    # 2.4x (review weak #2)
    for _ in range(9):
        t0 = time.perf_counter()
        for _ in range(3):
            net.pretrain(ListDataSetIterator(batches))
        rates.append(3.0 / (time.perf_counter() - t0))
    rates = sorted(rates)[2:-2]
    for _ in range(40):  # finetune (reference finetune() :1140)
        for b in batches:
            net.fit(b)
    acc = _mnist_accuracy(net, n=2048)
    if acc < ACCURACY_GATE:
        _fail_gate(f"dbn finetune accuracy {acc}")
    med = float(np.median(rates))
    return {
        "metric": "dbn_pretrain_epochs_per_sec",
        "value": round(med, 3),
        "unit": "pretrain epochs/sec (8192 ex, 784-500-250-10 CD-1, 3-epoch windows)",
        "vs_baseline": None,  # reference publishes no DBN numbers
        "spread": [round(min(rates), 3), round(max(rates), 3)],
        "trials": len(rates),
        "finetune_accuracy": acc,
    }


def bench_decode_tp():
    """Tensor-parallel sharded decode row (ISSUE 12 acceptance):
    flagship-family decode at TP in {1, 2, 4} on the 8-virtual-device
    mesh, in a subprocess (the TPU process cannot re-init its backend
    as CPU). scripts/tp_decode_bench.py runs the widths interleaved
    and gates greedy ids bit-identical to single-chip (match 1.0),
    zero retrace + one decode executable per width, per-shard KV
    bytes == total/TP, and TP=4 throughput >= 0.9x TP=1 on CPU
    (communication-bound on the virtual mesh; real chips split the
    matmuls so per-token latency drops with width — annotated
    per-width)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "scripts", "tp_decode_bench.py")],
        capture_output=True, text=True, timeout=900, env=env)
    if proc.returncode != 0:
        _fail_gate(f"tp decode bench gates failed: "
                   f"{proc.stderr[-400:]}")
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    _fail_gate(f"tp decode bench produced no row: "
               f"{proc.stderr[-400:]}")
    return None


def bench_allreduce():
    """BASELINE row 5: dp step-time decomposition on the 8-virtual-
    device mesh, in a subprocess (the TPU process cannot re-init its
    backend as CPU). scripts/allreduce_bench.py prints the row."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "scripts", "allreduce_bench.py")],
        capture_output=True, text=True, timeout=600, env=env)
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    _fail_gate(f"allreduce bench produced no row: {proc.stderr[-400:]}")
    return None


def _long_context_row(metric, width, n_heads, batch, seq, mfu_gate,
                      timed_steps=4):
    """Shared long-context measurement (rounds 4-5; review r5 #4).

    Round-5 config sweep (BENCHMARKS.md long-context section): at 16k
    the width-2048 stack reaches 48.0% MFU (width-1024 measured 37.5%
    — attention's share of executed FLOPs falls from 53% to 40% and
    the wider matmuls run nearer peak); at 32k width-1024 reaches
    42.1% (the r4 anecdote said 17.7%). B-sweeps, remat, and flash
    block-size sweeps measured: B=4 gains ~1pt at w1024 (38.8% vs
    37.5%) and nothing at the shipped configs, B=8 needs remat and
    loses, and uniform 1024-token blocks remain the kernel optimum —
    the stock pallas flash kernel's B=2 efficiency (25-36% of peak on
    its executed MACs) is the remaining wall below the 50% mark.
    """
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    n_layers = 8
    conf = transformer_lm_flagship(
        vocab=64, width=width, n_layers=n_layers, n_heads=n_heads,
        lr=3e-4, warmup_steps=10, total_steps=1000, remat=False)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 64, seq)).astype(np.float32)
    idx = rng.integers(0, 64, (batch, seq))
    y = np.eye(64, dtype=np.float32)[idx].transpose(0, 2, 1)
    ds = DataSet(jax.device_put(x), jax.device_put(y))

    net.fit(ds)  # compile + warm
    _sync(net.score_value)

    def measure():
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                net.fit(ds)
            final = _sync(net.score_value)
            rates.append(timed_steps * batch * seq
                         / (time.perf_counter() - t0))
        if not np.isfinite(final):  # not assert: survives python -O
            _fail_gate(f"{metric} non-finite loss {final}")
        return rates

    fpt = flagship_flops_per_token(
        width, n_layers, seq, 64, causal_flash=True)
    rates = measure()
    med = float(np.median(rates))
    mfu = med * fpt / peak_bf16_flops()
    if mfu < mfu_gate:
        _fail_gate(f"{metric} mfu {mfu:.4f} < {mfu_gate}")
    return {
        "metric": metric,
        "value": round(med, 1),
        "unit": (f"tokens/sec/chip (width-{width} flagship blocks, "
                 f"B={batch}, flash attention)"),
        "vs_baseline": None,  # reference cannot run this config at all
        "mfu": round(mfu, 4),
        "mfu_gate": mfu_gate,
        "spread": [round(min(rates), 1), round(max(rates), 1)],
        "trials": len(rates),
    }


def bench_transformer_long_context():
    """16k row: width-2048 (round-5 config — see _long_context_row)."""
    return _long_context_row(
        "transformer_lm_16k_context_train_throughput",
        width=2048, n_heads=16, batch=2, seq=16384, mfu_gate=0.40)


def bench_transformer_32k_context():
    """32k gated row (round-5 review #4: target >= 0.30 — measured
    0.42)."""
    return _long_context_row(
        "transformer_lm_32k_context_train_throughput",
        width=1024, n_heads=8, batch=2, seq=32768, mfu_gate=0.30)


def _release_device_memory(benches=None) -> None:
    """Free finished rows' device state before the next heavy row: the
    16 GB chip must hold the width-2048 16k-context row (~14 GB), so
    dead nets/windows/executables from earlier rows cannot linger (the
    round-5 full-run OOM: the interleaved family's ~3 GB of resident
    windows starved every later row)."""
    import gc

    import jax

    if benches is not None:
        for b in benches:
            b.__dict__.clear()
    gc.collect()
    jax.clear_caches()


def main() -> None:
    _require_tpu()
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    benches = [LenetBench(), WideCnnBench(), TransformerBench(),
               MlpBench()]
    rows = run_interleaved(benches, n_trials=3)
    mlp_row = rows.pop()  # headline printed LAST
    for r in rows:
        print(json.dumps(r))
    _release_device_memory(benches)
    for fn in (bench_transformer_long_context,
               bench_transformer_32k_context, bench_flagship,
               bench_hostfed_cnn, bench_decode, bench_decode_batched,
               bench_prefix_cache, bench_decode_paged,
               bench_decode_spec, bench_fused_decode,
               bench_decode_tp,
               bench_gateway_streaming, bench_router_overhead,
               bench_fleet_trace_overhead,
               bench_fleet_controller_overhead,
               bench_router_wal_overhead,
               bench_tenant_qos_overhead,
               bench_kv_transfer,
               bench_kv_tier,
               bench_observability_overhead,
               bench_train_observability_overhead,
               bench_w2v, bench_dbn, bench_allreduce):
        try:
            out = fn()
        except Exception as e:  # a broken row must not hide the rest
            _fail_gate(f"{fn.__name__} raised: {e!r}")
            out = None
        for row in ([out] if isinstance(out, dict) else (out or [])):
            print(json.dumps(row))
        _release_device_memory()
    print(json.dumps(mlp_row))
    if _GATE_FAILED:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
