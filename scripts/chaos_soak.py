"""Seeded chaos soak for the fault-tolerant serving runtime (ISSUE 3).

Churns a few hundred ragged requests through a small prefix-cached,
chunked-admission, paranoid DecodeEngine while an aggressive seeded
:class:`FaultPlan` injects NaN slots, admission failures, stalls, and
prefix-cache corruption — optionally crashing the engine mid-run
(``snapshot()`` -> ``DecodeEngine.restore``). The pass criteria are
the chaos-parity gate's:

- every request reaches a terminal state (no hangs, no losses);
- every request that finished healthily ('length'/'eos') has ids
  BIT-IDENTICAL to the same workload on a fault-free engine;
- capped-retry victims terminate with ``finish_reason="fault"``;
- compile counts stay at the PR 2 budget + one health-check
  executable on every engine involved.

Run standalone (``python scripts/chaos_soak.py [--fast]``) or via the
registered tests (tests/test_chaos_soak.py: the fast variant is
tier-1, the full 200-request soak is ``-m slow``).
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


def _build_net(vocab: int, seed: int, stream_max_t: int = 64):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(transformer_lm(
        n_in=vocab, width=32, n_layers=2, n_heads=4, n_classes=vocab,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _workload(rng, n_requests: int, vocab: int):
    """Ragged prompts/lengths with a shared system-prefix cohort (so
    the prefix cache, and its corruption, actually engage)."""
    shared = rng.integers(0, vocab, 6).tolist()
    cases = []
    for i in range(n_requests):
        if i % 3 == 0:
            prompt = shared + rng.integers(
                0, vocab, int(rng.integers(1, 5))).tolist()
        else:
            prompt = rng.integers(
                0, vocab, int(rng.integers(1, 14))).tolist()
        cases.append((prompt, int(rng.integers(2, 16))))
    return cases


def run_soak(n_requests: int = 200, seed: int = 0, vocab: int = 12,
             n_slots: int = 4, fault_rate: float = 0.12,
             snapshot_mid_run: bool = True,
             verbose: bool = False) -> Dict[str, Any]:
    """One seeded soak; returns a summary dict and raises AssertionError
    on any gate violation. ``n_requests=200`` is the full soak;
    tests use a smaller ``n_requests`` for the tier-1 budget."""
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        FaultPlan,
        Request,
    )

    rng = np.random.default_rng(seed)
    cases = _workload(rng, n_requests, vocab)

    def build(plan, net_seed=7):
        return DecodeEngine(
            _build_net(vocab, net_seed), n_slots=n_slots,
            decode_chunk=4, prefix_cache_rows=4, prefill_chunk=4,
            admission_policy="decode", paranoid=True, fault_plan=plan,
            max_retries=3, max_queue=4 * n_requests)

    # fault-free reference: the ids every healthy finish must match
    ref_eng = build(None)
    ref_ids = [ref_eng.submit(Request(list(p), n)) for p, n in cases]
    ref = ref_eng.run()

    # enough scheduled rounds to cover the whole churn; unconsumed
    # events (rounds past completion) are simply never injected
    plan = FaultPlan.random(seed, rounds=8 * n_requests,
                            rate=fault_rate)
    eng = build(plan)
    ids = [eng.submit(Request(list(p), n)) for p, n in cases]
    t0 = time.perf_counter()
    results: Dict[int, Any] = {}
    restored = False
    stats_pre: Dict[str, Any] = {}
    if snapshot_mid_run:
        target = max(2, n_requests // (2 * n_slots))
        for _ in range(target):
            if not eng.has_work():
                break
            eng.step(results)
        snap = eng.snapshot()
        stats_pre = dict(eng.stats)
        # the restored process inherits the SAME plan: chaos continues
        # across the crash (its round counter restarts, so early
        # events re-fire — deliberately aggressive)
        eng = DecodeEngine.restore(_build_net(vocab, 7), snap,
                                   fault_plan=plan)
        restored = True
    results.update(eng.run())
    wall_s = time.perf_counter() - t0

    def stat(key: str) -> int:
        return eng.stats[key] + stats_pre.get(key, 0)

    # -- gates ---------------------------------------------------------
    assert set(results) == set(ids), (
        f"lost requests: {sorted(set(ids) - set(results))[:5]}")
    mismatched, faulted, retried_ok = [], 0, 0
    for rid, ref_rid in zip(ids, ref_ids):
        r = results[rid]
        if r.finish_reason == "fault":
            faulted += 1
            continue
        assert r.finish_reason in ("length", "eos"), (
            f"request {rid}: unexpected terminal {r.finish_reason!r}")
        if r.retries > 0:
            retried_ok += 1
        if r.tokens != ref[ref_rid].tokens:
            mismatched.append(rid)
    assert not mismatched, (
        f"{len(mismatched)} healthy finishes diverged from the "
        f"fault-free run: {mismatched[:5]}")
    counts = eng.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["paged_scatter"] == 1 and counts["paged_tok"] == 1, (
        counts)
    assert counts["health_check"] == 1, counts
    # a cold row's continuation, a warm table's
    assert 1 <= counts["chunk_prefill"] <= 2, counts

    summary = {
        "n_requests": n_requests,
        "seed": seed,
        "wall_s": round(wall_s, 2),
        "restored_mid_run": restored,
        "faults_injected": stat("faults_injected"),
        "faults_detected": stat("faults_detected"),
        "quarantined": stat("quarantined"),
        "retries": stat("retries"),
        "retried_success": retried_ok,
        "capped_retry_failures": faulted,
        "deadline_expired": stat("deadline_expired"),
        "compile_counts": counts,
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
    ap.add_argument("--fault-rate", type=float, default=0.12)
    args = ap.parse_args(argv)
    n = args.requests or (24 if args.fast else 200)
    print(f"chaos soak: {n} requests, seed {args.seed}, "
          f"fault rate {args.fault_rate}")
    summary = run_soak(n_requests=n, seed=args.seed,
                       fault_rate=args.fault_rate, verbose=True)
    print(f"PASS in {summary['wall_s']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
