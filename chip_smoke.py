#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls, at the full width of the two flagship models (random
weights from a seed, data from ``datasets/markov.py``):

0. **kernel** (TPU only) — the compiled paged-attention kernel against
   the XLA gather program on the same random pool, at the serving
   geometry and the engine's three query lengths.
1. **serve** — the width-1024 x 8 flagship LM (bf16, 2048-token window)
   is written with ``write_model`` and booted by the CLI's own path
   (``cli/driver.py:gateway_from_args`` -> ``restore_model`` ->
   ``DecodeEngine`` -> ``ServingGateway``) with the paged KV pool, then
   answers blocking and SSE-streamed requests over localhost from
   ``GatewayClient``: prompt lengths in several pow2 buckets, two prompts
   sharing a long prefix. Checked: every request ends ``length``/``eos``;
   ids agree with the same net's teacher-forced forward at >= 0.9, cold
   and warm; free-running agreement with ``net.generate`` is printed
   exactly; on a TPU the decode
   executable's lowered program contains the pallas call;
   ``/v1/healthz`` is ok; a repeat of the warm round compiles nothing.
2. **train** — the width-2048 x 8 flagship at B=16, T=512: one
   ``fit_scan`` window and a few ``fit`` steps on the Markov task, loss
   finite and lower at the end; then one ``fit`` step at T=2048, which
   the attention layer routes to the library's block-sparse (splash)
   attention kernel, forward and fused backward, its lowered program
   checked for the pallas calls.

Without flags it refuses to run unless jax's default backend is ``tpu``:
there is no CPU fallback. ``--tiny`` is the rehearsal switch — small
sizes, any backend, pallas checks reported as skipped off-TPU — for
debugging the script itself under ``JAX_PLATFORMS=cpu``.

Every line names the device jax reports. The last line of stdout is the
result, ``{"ok": true, "device": {...}}``; any failed check or phase
raises, exits non-zero and prints no result. Compiled programs go to the
directory ``util/compile_cache.py`` chooses, so a second run against the
same directory reports warm times.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

#: full = the flagship models at a width the chip is needed for; tiny = the
#: same program at sizes a CPU finishes in a minute
SIZES = {
    "full": dict(
        vocab=64, n_new=64, block_tokens=16, slots=8,
        serve=dict(width=1024, n_layers=8, n_heads=8, window=2048),
        prompts=(24, 200, 1500), shared_prefix=600, shared_tail=40,
        train=dict(width=2048, n_layers=8, n_heads=16),
        lr=2e-4, warmup=64,   # bench_flagship's schedule
        batch=16, seq=512, scan_steps=8, fit_steps=4,
        flash_batch=2, flash_seq=2048),
    "tiny": dict(
        vocab=64, n_new=8, block_tokens=16, slots=8,
        serve=dict(width=64, n_layers=2, n_heads=2, window=128),
        prompts=(6, 20, 100), shared_prefix=40, shared_tail=8,
        train=dict(width=64, n_layers=2, n_heads=2),
        lr=1e-2, warmup=2,
        batch=16, seq=64, scan_steps=8, fit_steps=4,
        flash_batch=2, flash_seq=256),
}
MATCH_GATE = 0.9   # the id-agreement bar bench_decode gates at
#: a token whose reference probability is within this factor of the
#: reference's top choice counts as agreeing: with random weights the
#: top two of 64 logits tie to within bf16 rounding every ~15 tokens
NEAR_TIE = 0.9


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def flagship(vocab, width, n_layers, n_heads, window=None, **conf_kw):
    """The bf16 flagship net at the given size, freshly initialised."""
    from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = transformer_lm_flagship(
        vocab=vocab, width=width, n_layers=n_layers, n_heads=n_heads,
        **conf_kw)
    for c in conf.confs:
        c.compute_dtype = "bfloat16"
        if window is not None and hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = window
    return MultiLayerNetwork(conf).init()


def release_device_memory() -> None:
    """Drop dead nets and executables before the next model is
    built."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()


def match_rate(a, b) -> float:
    n = min(len(a), len(b))
    return float(np.mean(np.asarray(a[:n]) == np.asarray(b[:n])))


def decode_pallas_calls(eng) -> int:
    """How many pallas calls the engine's paged decode program holds,
    counted in its lowered text: a quiet route to the gather program
    would pass every functional check."""
    import jax
    import jax.numpy as jnp

    lowered = eng._decode_jit.lower(
        eng._params, eng._state, eng._pool,
        eng.kv.pack(eng._kv_tabs), eng._toks,
        jnp.asarray(eng._temps), jnp.asarray(eng._top_ks),
        jax.random.key(0)).as_text()
    return lowered.count("tpu_custom_call")


def one_hot(ids, vocab):
    x = np.zeros((1, vocab, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


def forced_agreement(net, prompt, tokens):
    """Teacher-forced check of ``tokens`` generated after ``prompt``:
    one full-sequence forward of ``net`` gives its next-token
    distribution at every generated position under exactly the context
    the decoder had. Returns (share of tokens that ARE the reference's
    argmax, share within NEAR_TIE of it, worst ratio to the reference's
    top choice)."""
    vocab = net.conf.confs[0].layer.n_in
    probs = np.asarray(
        net.output(one_hot(list(prompt) + list(tokens[:-1]), vocab)),
        np.float32)[0][:, len(prompt) - 1:]
    ratio = probs[tokens, np.arange(len(tokens))] / probs.max(axis=0)
    return (float(np.mean(ratio == 1.0)),
            float(np.mean(ratio >= NEAR_TIE)), float(ratio.min()))


# ---------------------------------------------------------------------
# phase 0 (TPU only): the paged kernel against the program it replaces
# ---------------------------------------------------------------------
def kernel_phase(size, log) -> None:
    """``_paged_attend`` twice on the same random pool, block tables and
    chunk — once through the XLA gather program, once through the
    compiled pallas kernel — at the smoke's serving geometry and at the
    benchmark cell's (48 rows, 16 heads), at the three query lengths the
    engine dispatches (decode, a verify chunk, one prefill tile). Rows
    sit at different fill levels (empty, mid-window, slid past the
    window, raised floor, idle), chunks are ragged, and one free
    block is poisoned with NaN: the value-level masking must hold in
    the compiled kernel as it does in the interpreter."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import (
        AttentionImpl,
        MultiHeadSelfAttention,
    )

    h0 = size["serve"]["n_heads"]
    dh = size["serve"]["width"] // h0
    tm, bt = size["serve"]["window"], size["block_tokens"]
    s_ring = 2 * (tm // bt) + 8
    # the smoke's own serving geometry at 4 rows, then the benchmark's
    # serving cell: 48 rows of 16 heads (twice the heads at toy size),
    # the same four fill levels, then every third row at a chat-length
    # context with idle slots between
    for (h, b), t in itertools.product(((h0, 4), (2 * h0, 48)),
                                       (1, 5, 128)):
        lc = MultiHeadSelfAttention(n_in=h * dh, n_out=h * dh, n_heads=h,
                                    stream_max_t=tm)
        rng = np.random.default_rng([t, b])
        nb = 4 * (tm // bt) + 64 + 18 * b
        filled = np.zeros(b, np.int32)
        filled[::3] = rng.integers(2, tm // 3, len(filled[::3]))
        filled[:4] = [0, tm + tm // 4, tm // 3, tm // 2]
        floor = np.zeros(b, np.int32)
        floor[3] = 2 * bt
        table = np.full((b, s_ring), -1, np.int32)
        base = np.full((b, s_ring), -1, np.int32)
        free = list(rng.permutation(nb))
        for r in range(b):
            if r and not filled[r]:
                continue            # an idle slot maps nothing
            lo = max(0, int(filled[r]) - tm) // bt
            for g in range(lo, (int(filled[r]) + t - 1) // bt + 1):
                table[r, g % s_ring] = free.pop()
                base[r, g % s_ring] = g * bt

        def draw(dtype, *shape):
            return jnp.asarray(rng.normal(size=shape), dtype)

        # the engine's dtypes: a bf16 chunk against the bf16 pool (the
        # pool is made at the compute dtype, serving/engine.py)
        q, k, v = (draw(jnp.bfloat16, b, h, t, dh) for _ in range(3))
        pk = draw(jnp.bfloat16, nb, bt, h, dh).at[free[0]].set(jnp.nan)
        pv = draw(jnp.bfloat16, nb, bt, h, dh).at[free[0]].set(jnp.nan)
        lens = rng.integers(1, t + 1, b)
        lens[0] = t
        mask = (None if t == 1 else jnp.asarray(
            np.arange(t)[None] < lens[:, None], jnp.float32))
        outs = {}
        for toggle in (False, True):
            lc.use_flash_paged = toggle
            o, _ = AttentionImpl._paged_attend(
                lc, q, k, v,
                {"pk": pk, "pv": pv, "table": jnp.asarray(table),
                 "base": jnp.asarray(base), "floor": jnp.asarray(floor),
                 "filled": jnp.asarray(filled)}, mask)
            o = np.asarray(o.astype(jnp.float32))
            if mask is not None:  # pad queries are never read
                o = o * np.asarray(mask)[:, None, :, None]
            outs[toggle] = o
        what = f"{b} rows x {h} heads, t={t}"
        check(bool(np.isfinite(outs[True]).all()),
              f"{what}: NaN leaked through the kernel's masked lanes")
        diff = float(np.abs(outs[True] - outs[False]).max())
        log(f"kernel: {what}: paged kernel vs gather program max|diff| "
            f"{diff:.4f} (mean|out| "
            f"{float(np.abs(outs[False]).mean()):.4f}; bf16 chunk, "
            "bf16 pool)")
        # a few bf16 ulps at |out| ~ 1 (the gather program rounds its
        # scores to bf16, the kernel lifts to float32)
        check(diff <= 0.0625, f"{what}: kernel differs from the gather "
              f"program by {diff}")


# ---------------------------------------------------------------------
# phase 1: the server answers a few requests
# ---------------------------------------------------------------------
def serve_phase(size, log, on_tpu: bool, workdir: str) -> None:
    from deeplearning4j_tpu.cli.driver import (
        build_parser,
        gateway_from_args,
    )
    from deeplearning4j_tpu.serving import GatewayClient
    from deeplearning4j_tpu.util.model_serializer import write_model

    vocab, n_new = size["vocab"], size["n_new"]
    net = flagship(vocab, seed=11, **size["serve"])
    model_path = os.path.join(workdir, "flagship_lm.zip")
    t0 = time.perf_counter()
    write_model(net, model_path)
    log(f"serve: wrote {model_path} "
        f"({os.path.getsize(model_path) / 2**20:.0f} MiB, "
        f"{time.perf_counter() - t0:.1f}s)")

    rng = np.random.default_rng(0)
    prompts = {f"p{n}": rng.integers(0, vocab, n).tolist()
               for n in size["prompts"]}
    stem = rng.integers(0, vocab, size["shared_prefix"]).tolist()
    for name in ("shared_a", "shared_b"):
        prompts[name] = stem + rng.integers(
            0, vocab, size["shared_tail"]).tolist()

    # the exact CLI path: argv -> gateway_from_args -> restore_model ->
    # DecodeEngine -> ServingGateway; kernel choice left on auto
    args = build_parser().parse_args([
        "serve", "--model", model_path, "--port", "0",
        "--block-tokens", str(size["block_tokens"]),
        "--slots", str(size["slots"]), "--prefix-cache-rows", "8"])
    gw = gateway_from_args(args).start()
    try:
        client = GatewayClient(gw.address, timeout_s=900.0)
        eng = gw.engine

        def ask(name, stream, out):
            if stream:
                s = client.stream(prompts[name], n_new)
                toks = [t for delta in s for t in delta]
                res = s.result
                check(res is not None and toks == res["tokens"],
                      f"{name}: streamed deltas != terminal tokens")
            else:
                res = client.generate(prompts[name], n_new)
            out[name] = res

        def one_round():
            out = {}
            # the shared-prefix pair goes one after the other, so the
            # second finds the first's blocks in the trie
            plan = [[(f"p{n}", i % 2 == 1)
                     for i, n in enumerate(size["prompts"])],
                    [("shared_a", False)], [("shared_b", True)]]
            for wave in plan:
                threads = [threading.Thread(target=ask,
                                            args=(name, stream, out))
                           for name, stream in wave]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=900.0)
                    check(not t.is_alive(), "a request never returned")
            check(set(out) == set(prompts),
                  f"requests failed: {sorted(set(prompts) - set(out))}")
            return out

        t0 = time.perf_counter()
        first = one_round()
        t_first = time.perf_counter() - t0
        for name, res in first.items():
            check(res["finish_reason"] in ("length", "eos"),
                  f"{name} ended {res['finish_reason']!r}")
            check(len(res["tokens"]) == n_new,
                  f"{name}: {len(res['tokens'])} tokens, want {n_new}")
        reused = first["shared_b"]["prefix_tokens_reused"]
        check(reused > 0, "shared_b reused no prefix blocks")
        log(f"serve: round 1 (cold) {t_first:.1f}s; shared_b reused "
            f"{reused} prompt tokens from the trie")

        # round 2 admits everything through the now-warm trie (its own
        # chunk widths); round 3 repeats round 2 and must compile nothing
        t0 = time.perf_counter()
        second = one_round()
        t_second = time.perf_counter() - t0
        counts = eng.compile_counts()
        t0 = time.perf_counter()
        third = one_round()
        t_third = time.perf_counter() - t0
        check(eng.compile_counts() == counts,
              f"a repeated round compiled: {counts} -> "
              f"{eng.compile_counts()}")
        for name in prompts:
            check(second[name]["tokens"] == third[name]["tokens"],
                  f"{name}: identical warm rounds gave different ids")
        log(f"serve: round 2 (warm trie) {t_second:.1f}s, round 3 "
            f"(repeat) {t_third:.1f}s, compile counts frozen: {counts}")

        health = client.healthz()
        check(health["ok"] and health["state"] == "live",
              f"/v1/healthz: {health}")

        with gw._engine_access():
            n_pallas = decode_pallas_calls(eng)
        if on_tpu:
            check(n_pallas >= 1, "the decode program holds no pallas "
                  "call: the auto rule fell to the gather program")
            log(f"serve: decode program holds {n_pallas} "
                "tpu_custom_call (paged kernel: compiled and ran)")
        else:
            log("serve: pallas check skipped off-TPU (the auto rule "
                f"picks the gather program; {n_pallas} custom calls)")
    finally:
        gw.close()

    # -- is what came out right? ---------------------------------------
    # Teacher-forced: ONE full-sequence forward of the same net over
    # prompt + answer yields the reference's next-token distribution at
    # every generated position, under exactly the context the engine
    # had. Unlike a free-running comparison this cannot drift: one bf16
    # near-tie flips one position, not the rest of the sequence. Gated
    # at the bench_decode bar (agreement up to NEAR_TIE; the strict
    # argmax share is printed beside it), for the cold path (dense
    # prefill -> scatter -> paged decode) and the warm one (trie splice
    # -> paged chunk prefill -> paged decode); and no emitted token may
    # fall below half the reference's top choice.
    rates = {}
    for name, prompt in prompts.items():
        cold = forced_agreement(net, prompt, first[name]["tokens"])
        warm = forced_agreement(net, prompt, second[name]["tokens"])
        rates[name] = {
            "forced_cold": cold[1], "forced_warm": warm[1],
            "argmax_cold": cold[0], "argmax_warm": warm[0],
            "worst_tie": min(cold[2], warm[2])}

    # Free-running, against the repo's other decoder on the same
    # weights, net.generate. After the first bf16 near-tie the
    # sequences part for good, so the rate is the position of that
    # first flip — printed exactly, not gated.
    for name, prompt in prompts.items():
        net.rnn_clear_previous_state()
        ref = np.asarray(net.generate(one_hot(prompt, vocab),
                                      n_new))[0].tolist()
        got = first[name]["tokens"]
        rates[name].update(
            generate=match_rate(got, ref),
            warm_vs_cold=match_rate(got, second[name]["tokens"]))
        log(f"serve: id agreement {name}: " + ", ".join(
            f"{k} {v:.4f}" for k, v in rates[name].items()))
    for name, r in rates.items():
        check(min(r["forced_cold"], r["forced_warm"]) >= MATCH_GATE,
              f"{name}: teacher-forced agreement below {MATCH_GATE}: "
              f"{r}")
        check(r["worst_tie"] >= 0.5,
              f"{name}: emitted a token the reference rates at "
              f"{r['worst_tie']:.3f} of its top choice")


# ---------------------------------------------------------------------
# phase 2: the trainer takes a few steps
# ---------------------------------------------------------------------
def train_phase(size, log, on_tpu: bool) -> None:
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.markov import markov_lm_batches

    vocab, b, t = size["vocab"], size["batch"], size["seq"]
    k, n_fit = size["scan_steps"], size["fit_steps"]
    net = flagship(vocab, lr=size["lr"], warmup_steps=size["warmup"],
                   total_steps=1000, **size["train"])
    feats, labels, floor = markov_lm_batches(
        vocab, n_seq=(k + n_fit) * b, seq_len=t, seed=0,
        sample_seed=1)
    feats = feats.reshape(k + n_fit, b, vocab, t)
    labels = labels.reshape(k + n_fit, b, vocab, t)

    t0 = time.perf_counter()
    scores = np.asarray(net.fit_scan(
        jax.device_put(feats[:k].astype(np.uint8)),
        jax.device_put(labels[:k].astype(np.uint8))), np.float32)
    t_scan = time.perf_counter() - t0
    losses = [float(s) for s in scores]
    t0 = time.perf_counter()
    for i in range(k, k + n_fit):
        net.fit(DataSet(feats[i], labels[i]))
        losses.append(float(net.score_value))
    t_fit = time.perf_counter() - t0
    log(f"train: fit_scan x{k} {t_scan:.1f}s, fit x{n_fit} "
        f"{t_fit:.1f}s; losses "
        + " ".join(f"{v:.4f}" for v in losses)
        + f" (entropy floor {floor:.4f} nats)")
    check(all(np.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    # one step at a length the attention layer routes to the library's
    # block-sparse (splash) kernel on a TPU (T >= 2048): forward + fused
    # backward at head dim 128. Lower the step first to count its
    # pallas calls.
    fb, ft = size["flash_batch"], size["flash_seq"]
    lf, ll, _ = markov_lm_batches(vocab, n_seq=fb, seq_len=ft,
                                  seed=0, sample_seed=2)
    lowered = net._train_step.lower(
        net.params, net.state, net.updater_state, net.iteration,
        jax.random.key(0), jax.numpy.asarray(lf, net._dtype),
        jax.numpy.asarray(ll, net._dtype), None, None).as_text()
    n_pallas = lowered.count("tpu_custom_call")
    t0 = time.perf_counter()
    net.fit(DataSet(lf, ll))
    flash_loss = float(net.score_value)
    t_flash = time.perf_counter() - t0
    check(np.isfinite(flash_loss), f"T={ft} step loss {flash_loss}")
    if on_tpu:
        # two distinct kernels: forward, and the backward that forms
        # dq, dk and dv in one pass (the layers share one lowered
        # function for each)
        check(n_pallas >= 2,
              f"T={ft} step holds {n_pallas} pallas calls, want >= 2")
        log(f"train: T={ft} step {t_flash:.1f}s, loss "
            f"{flash_loss:.4f}, {n_pallas} tpu_custom_call (flash "
            "kernel forward + backward: compiled and ran)")
    else:
        log(f"train: T={ft} step {t_flash:.1f}s, loss "
            f"{flash_loss:.4f}; pallas check skipped off-TPU")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--tiny", action="store_true",
        help="rehearsal: tiny sizes, any backend (the only way to run "
             "off-TPU; pallas checks are skipped there)")
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.native_rt import native_available
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu" and not args.tiny:
        print(f"chip_smoke: jax's default backend is {backend!r}, not "
              "'tpu' — no accelerator, nothing to prove (there is no "
              "CPU fallback; --tiny rehearses the script off-chip)",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    tag = (f"[{device['platform']} {device['kind']} x{device['count']} "
           f"jax {jax.__version__}]")

    def log(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    # cache traffic, counted by jax itself
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    at_start = (len(os.listdir(cache_dir))
                if os.path.isdir(cache_dir) else 0)
    size = SIZES["tiny" if args.tiny else "full"]
    log(f"chip_smoke {'tiny rehearsal' if args.tiny else 'full size'}; "
        f"compile cache {cache_dir} holds {at_start} entries "
        f"({'warm' if at_start else 'cold'} run); native runtime "
        f"available: {native_available()}")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    walls = {}
    try:
        if backend == "tpu":
            t0 = time.perf_counter()
            kernel_phase(size, log)
            walls["kernel_s"] = round(time.perf_counter() - t0, 1)
        t0 = time.perf_counter()
        serve_phase(size, log, backend == "tpu", workdir)
        walls["serve_s"] = round(time.perf_counter() - t0, 1)
        log(f"serve phase passed in {walls['serve_s']}s")
        release_device_memory()
        t0 = time.perf_counter()
        train_phase(size, log, backend == "tpu")
        walls["train_s"] = round(time.perf_counter() - t0, 1)
        log(f"train phase passed in {walls['train_s']}s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    log(f"{'warm' if at_start else 'cold'} wall: {json.dumps(walls)}; "
        f"compile cache hits {cache['hits']}, misses "
        f"{cache['misses']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
