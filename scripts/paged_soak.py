"""Fragmentation soak for the KV block pool (ISSUE 6).

Churns seeded ragged-length requests — several shared-prefix cohorts
plus unique-prompt traffic — through a ``DecodeEngine`` on a
DELIBERATELY tight ``kv_blocks`` budget, so every pressure path runs
hot: zero-copy splices, boundary-block CoW, trie evictions for blocks,
admission defers, and youngest-slot preemption. The pass criteria:

- every request reaches a terminal state and every greedy finish is
  BIT-IDENTICAL to ``net.generate`` of the same prompt, a request at
  a time (preemption, deferral, and sharing must all be invisible in
  ids);
- zero leaked blocks: once idle, the pool holds exactly the prefix
  trie's references — and after clearing the trie it is FULLY free,
  with every refcount at zero;
- compile counts stay at their budget (one decode, one scatter, one
  token put, <= 2 chunk-continuation variants).

Run standalone (``python scripts/paged_soak.py [--fast]``) or via the
registered tests (tests/test_paged_soak.py: fast variant tier-1, the
full churn ``-m slow``).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Any, Dict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build_net(vocab: int, seed: int, stream_max_t: int):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(transformer_lm(
        n_in=vocab, width=32, n_layers=2, n_heads=4, n_classes=vocab,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _workload(rng, n_requests: int, vocab: int, window: int):
    """Ragged prompts/lengths: three shared-prefix cohorts of
    different lengths (block-aligned and not, so splices hit both the
    CoW and the no-CoW boundary case) interleaved with unique
    prompts."""
    cohorts = [rng.integers(0, vocab, ln).tolist()
               for ln in (8, 11, 5)]
    cases = []
    for i in range(n_requests):
        if i % 2 == 0:
            head = cohorts[(i // 2) % len(cohorts)]
            prompt = head + rng.integers(
                0, vocab, int(rng.integers(1, 6))).tolist()
        else:
            prompt = rng.integers(
                0, vocab, int(rng.integers(1, 15))).tolist()
        cases.append((prompt, int(rng.integers(2, 15))))
    return cases


def run_soak(n_requests: int = 160, seed: int = 0, vocab: int = 12,
             n_slots: int = 4, window: int = 32, block_tokens: int = 4,
             kv_blocks: int = 18, tp: int = 1,
             use_flash_paged=None, host_tier_bytes: int = 0,
             verbose: bool = False) -> Dict[str, Any]:
    """One seeded soak; returns a summary dict and raises
    AssertionError on any gate violation. ``tp > 1`` (ISSUE 12) runs
    the engine SHARDED over attention heads — same pressure
    ladder, same ``net.generate`` parity gate, plus per-shard gates:
    the head-sliced pool shards hold identical byte counts
    (total/TP), and zero blocks leak per shard (block ids are
    shard-invariant, so the host leak audit IS the per-shard audit —
    asserted against the device shards to prove it).

    ``host_tier_bytes > 0`` (ISSUE 17) arms the host-DRAM spill tier
    under the same pressure churn: trie victims spill, later cohort
    hits reload, and the gates extend with — ids STILL bit-identical
    to the reference (spill/reload must be invisible), resident
    host bytes never exceed the budget (peak-tracked every round),
    the tier actually exercised (spills and reloads both non-zero),
    and the tier counters reconcile: spills == reloads + drops +
    resident entries."""
    from scripts._leakcheck import assert_no_leaks, leak_baseline

    from deeplearning4j_tpu.serving import DecodeEngine, Request

    rng = np.random.default_rng(seed)
    cases = _workload(rng, n_requests, vocab, window)
    baseline = leak_baseline()

    # the reference: the net's own generate, a request at a time —
    # the ids every finish must match
    ref_net = _build_net(vocab, 7, window)
    ref = []
    for prompt, n in cases:
        ref_net.rnn_clear_previous_state()
        x = np.zeros((1, vocab, len(prompt)), np.float32)
        x[0, prompt, np.arange(len(prompt))] = 1.0
        ref.append(np.asarray(ref_net.generate(x, n))[0].tolist())

    eng = DecodeEngine(
        _build_net(vocab, 7, window), n_slots=n_slots,
        decode_chunk=4, prefix_cache_rows=8, prefill_chunk=4,
        admission_policy="decode", max_queue=4 * n_requests,
        block_tokens=block_tokens, kv_blocks=kv_blocks, tp=tp,
        use_flash_paged=use_flash_paged,
        kv_host_tier_bytes=host_tier_bytes)
    ids = [eng.submit(Request(list(p), n)) for p, n in cases]
    t0 = time.perf_counter()
    results: Dict[int, Any] = {}
    frag_peak = used_peak = tier_bytes_peak = 0
    while eng.has_work():
        eng.step(results)
        frag_peak = max(frag_peak, eng.stats["frag_tokens"])
        used_peak = max(used_peak, eng.stats["blocks_used"])
        if eng.kv_tier is not None:
            tier_bytes_peak = max(tier_bytes_peak,
                                  eng.kv_tier.host_bytes)
    wall_s = time.perf_counter() - t0

    # -- gates ---------------------------------------------------------
    assert set(results) == set(ids), (
        f"lost requests: {sorted(set(ids) - set(results))[:5]}")
    mismatched = []
    for rid, ref_tokens in zip(ids, ref):
        r = results[rid]
        assert r.finish_reason in ("length", "eos"), (
            f"request {rid}: unexpected terminal {r.finish_reason!r}")
        if r.tokens != ref_tokens:
            mismatched.append(rid)
    assert not mismatched, (
        f"{len(mismatched)} finishes diverged from net.generate: "
        f"{mismatched[:5]}")

    # zero leaked blocks: idle pool holds exactly the trie's blocks;
    # clearing the trie frees EVERYTHING and every refcount is zero
    pool = eng.block_pool
    trie_blocks = set(eng.prefix_cache.block_ids())
    assert pool.used_blocks == len(trie_blocks), (
        f"leak: {pool.used_blocks} blocks used while the trie holds "
        f"{len(trie_blocks)} — a slot or pending admission leaked "
        "references")
    # per-shard audit (ISSUE 12): every shard's head slice of the pool
    # holds total/TP bytes — a shard that leaked device blocks (or was
    # never sharded) breaks the symmetry
    shard_bytes = eng.kv_shard_bytes()
    assert len(shard_bytes) == tp, shard_bytes
    assert len(set(shard_bytes.values())) == 1, (
        f"asymmetric shards: {shard_bytes}")

    eng.prefix_cache.clear()
    assert pool.used_blocks == 0, "blocks survived a trie clear"
    assert pool.free_blocks == eng.kv_blocks
    assert all(pool.refcount(b) == 0 for b in range(eng.kv_blocks))

    counts = eng.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["paged_scatter"] == 1, counts
    assert counts["paged_tok"] == 1, counts
    assert counts["chunk_prefill"] <= 2, counts

    tier_stats = None
    if eng.kv_tier is not None:
        # spill-tier gates (ISSUE 17): budget held at every sampled
        # instant, the churn actually exercised both directions, and
        # the conservation invariant closed the books — every spill
        # is accounted for as a reload, a drop, or a resident entry
        # (the trie clear above dropped whatever was still resident
        # in the TRIE, not the tier, so residents may be non-zero)
        tier_stats = dict(eng.kv_tier.stats)
        assert tier_bytes_peak <= host_tier_bytes, (
            f"host tier peaked at {tier_bytes_peak} bytes over the "
            f"{host_tier_bytes}-byte budget")
        assert tier_stats["spills"] > 0, (
            f"pressure churn never spilled: {tier_stats}")
        assert tier_stats["reloads"] > 0, (
            f"cohort re-hits never reloaded: {tier_stats}")
        assert tier_stats["spills"] == (
            tier_stats["reloads"] + tier_stats["drops"]
            + len(eng.kv_tier)), (
            f"tier books don't reconcile: {tier_stats} vs "
            f"{len(eng.kv_tier)} resident")

    # the engine is in-process (no sockets), but the sharded runtime
    # must not strand helper threads either — the shared soak policy
    assert_no_leaks(baseline)

    summary = {
        "n_requests": n_requests,
        "seed": seed,
        "tp": tp,
        "shard_bytes": shard_bytes,
        "wall_s": round(wall_s, 2),
        "kv_blocks": eng.kv_blocks,
        "used_blocks_peak": used_peak,
        "frag_tokens_peak": frag_peak,
        "prefix_blocks_spliced": eng.stats["prefix_blocks_spliced"],
        "cow_copies": eng.stats["cow_copies"],
        "preempted": eng.stats["preempted"],
        "admissions_deferred": eng.stats["paged_admit_deferred"],
        "trie_evictions": eng.prefix_cache.stats["evictions"],
        "prefill_tokens_skipped": eng.stats["prefill_tokens_skipped"],
        "compile_counts": counts,
        "tier": tier_stats,
        "tier_bytes_peak": tier_bytes_peak,
    }
    if verbose:
        for k, v in summary.items():
            print(f"  {k}: {v}")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="small tier-1 variant (same gates, fewer "
                         "requests)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-blocks", type=int, default=18)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards (ISSUE 12): the "
                         "engine runs sharded over attention "
                         "heads; parity/leak gates gain per-shard "
                         "checks")
    ap.add_argument("--use-flash-paged", default="auto",
                    choices=("auto", "on", "off", "interpret"))
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="arm the host-DRAM spill tier (ISSUE 17) "
                         "with this byte budget; adds the "
                         "spill/reload churn gates (0 = off)")
    args = ap.parse_args(argv)
    if args.tp > 1:
        # a CPU host needs virtual devices for the TP mesh — set
        # BEFORE anything touches jax (the serving import does)
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count="
            f"{max(8, args.tp)}")
    # imported after the XLA_FLAGS setdefault — the driver module
    # pulls in jax, which freezes the device count on first touch
    from deeplearning4j_tpu.cli.driver import FLASH_PAGED_MODES
    toggle = FLASH_PAGED_MODES[args.use_flash_paged]
    n = args.requests or (24 if args.fast else 160)
    print(f"paged soak: {n} requests, seed {args.seed}, "
          f"{args.kv_blocks} blocks, tp {args.tp}")
    summary = run_soak(n_requests=n, seed=args.seed,
                       kv_blocks=args.kv_blocks, tp=args.tp,
                       use_flash_paged=toggle,
                       host_tier_bytes=args.host_tier_bytes,
                       verbose=True)
    print(f"PASS in {summary['wall_s']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
