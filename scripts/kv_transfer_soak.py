"""KV-transfer-plane chaos soak (ISSUE 14 acceptance).

Seeded churn of streaming clients against N PAGED, async-round gateway
replicas behind a :class:`~deeplearning4j_tpu.serving.ServingRouter`
with the KV transfer plane live, plus the faults the plane must
survive:

- **truncated transfer payloads** — every second donor export arrives
  torn (injected at the router's ``_fetch_kv_payload`` seam), so the
  receiver's import 400s and the request MUST fall back to full
  recompute;
- **a hard replica kill** (``SIGKILL`` / ``hard_kill``) while at
  least ``min_inflight_at_kill`` streams are in flight on the victim
  — a kill that can land mid-transfer on either side of the plane
  (the router's route-around/replay machinery absorbs both).

Pass criteria:

- **zero lost streams**: every submitted request reaches a terminal,
  the journal shows nothing open and nothing lost;
- **bit-identical ids**: every COMPLETED greedy stream equals the
  same request on a fault-free single-engine reference — warm
  imports, torn transfers, replays and async rounds included;
- **the plane actually ran**: >= 1 successful cross-replica transfer
  (shared-prefix cohorts overflow their warm replica under
  bounded-load affinity) AND >= 1 injected transfer fault that fell
  back to recompute;
- **the plane is priced**: ``latency_report``'s ``--fleet`` rows
  carry a populated ``kv_transfer`` histogram row from the same run;
- **zero leaked threads/fds/subprocesses**
  (scripts/_leakcheck.py).

Two modes: ``--fast`` (tier-1, tests/test_kv_transfer_soak.py — 2
in-process replicas, ``hard_kill``); full (``slow`` — 3 SUBPROCESS
replicas, a real ``SIGKILL``). Run standalone:
``python scripts/kv_transfer_soak.py [--fast]``.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

VOCAB = 12
NET_SEED = 11
#: a trie + chunked + ASYNC double-buffered rounds: the full ISSUE 14
#: engine configuration, under churn
ENGINE = dict(n_slots=3, decode_chunk=2, prefix_cache_rows=4, seed=0,
              block_tokens=8, prefill_chunk=4, async_rounds=True)
AFFINITY_BLOCK = 8  # matches block_tokens: cohort prefixes are keys


def _build_net(vocab: int = VOCAB, seed: int = NET_SEED,
               stream_max_t: int = 96):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(transformer_lm(
        n_in=vocab, width=32, n_layers=2, n_heads=4,
        n_classes=vocab, seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _throttle(engine, delay_s: float) -> None:
    orig = engine.step

    def slow(sink=None):
        time.sleep(delay_s)
        return orig(sink)

    engine.step = slow


def _workload(rng, n_clients: int):
    """Shared-prefix cohorts dominate (the transfer plane's reason to
    exist: affinity-keyed traffic whose bounded-load overflow must
    land warm on the sibling) plus a couple of singles; all greedy —
    the parity gate must cover every completed stream. Returns
    ``(cases, cohorts)``: the soak tears every transfer payload whose
    prefix is cohort 1's, so one cohort's transfers succeed and the
    other's deterministically fault-and-fall-back."""
    cohorts = [rng.integers(0, VOCAB, AFFINITY_BLOCK).tolist(),
               rng.integers(0, VOCAB, AFFINITY_BLOCK).tolist()]
    cases = []
    for i in range(n_clients):
        if i % 6 == 5:
            prompt = rng.integers(
                0, VOCAB, int(rng.integers(2, 10))).tolist()
        else:
            prompt = (cohorts[i % 2]
                      + rng.integers(0, VOCAB,
                                     int(rng.integers(1, 4))).tolist())
        cases.append((prompt, int(rng.integers(14, 32))))
    return cases, cohorts


# -- subprocess child mode --------------------------------------------
def run_replica(args) -> int:
    from deeplearning4j_tpu.serving import DecodeEngine, ServingGateway

    engine = DecodeEngine(_build_net(), **ENGINE)
    if args.throttle > 0:
        _throttle(engine, args.throttle)
    gw = ServingGateway(engine, port=args.port,
                        replica_id=args.replica_id,
                        keepalive_s=0.1).start()
    print(f"READY {gw.address}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    try:
        while not stop.is_set():
            stop.wait(0.2)
    finally:
        with contextlib.suppress(Exception):
            gw.close()
    return 0


def _proc_replica(idx: int, throttle: float):
    from deeplearning4j_tpu.serving.replica_proc import (
        ReplicaProcess,
        free_port,
    )

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    port = free_port()
    return ReplicaProcess(
        [sys.executable, os.path.abspath(__file__), "--replica",
         "--port", str(port), "--replica-id", f"kv-{idx}",
         "--throttle", str(throttle)],
        replica_id=f"kv-{idx}", port=port, env=env,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))


def _local_replica(idx: int, net, throttle: float):
    from deeplearning4j_tpu.serving import DecodeEngine
    from deeplearning4j_tpu.serving.replica_proc import LocalReplica

    engine = DecodeEngine(net, **ENGINE)
    if throttle > 0:
        _throttle(engine, throttle)
    return LocalReplica(engine, replica_id=f"kv-{idx}")


# -- the soak proper --------------------------------------------------
def run_soak(n_clients: int = 18, n_replicas: int = 2, seed: int = 0,
             in_process: bool = False, throttle: float = 0.04,
             min_inflight_at_kill: int = 3,
             verbose: bool = False) -> Dict[str, Any]:
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        Request,
        RouterClient,
        ServingRouter,
    )

    rng = np.random.default_rng(seed)
    churn_cases, cohorts = _workload(rng, n_clients)
    # phase-A waves: one cohort at a time floods its rendezvous
    # owner while the sibling idles, so bounded-load overflow walks
    # to a replica with FREE slots — the genuine affinity-miss shape
    # (a fully saturated fleet stays sticky and queues instead, by
    # design). Cohort 0's overflow proves the warm-import success
    # path; cohort 1's payloads are torn, so its overflow proves the
    # fault→recompute fallback ON THE REQUEST PATH.
    wave = max(ENGINE["n_slots"] + 2, min_inflight_at_kill + 1)
    wave_cases = {
        c: [(cohorts[c] + [int(rng.integers(0, VOCAB))],
             int(rng.integers(14, 24)))
            for _ in range(wave)]
        for c in (0, 1)}
    cases = wave_cases[0] + wave_cases[1] + churn_cases
    churn_base = 2 * wave

    # fault-free single-engine reference (greedy workload: every
    # completed stream must match bit for bit)
    net = _build_net()
    ref_eng = DecodeEngine(net, **ENGINE)
    ref_ids = {i: ref_eng.submit(Request(list(p), n))
               for i, (p, n) in enumerate(cases)}
    ref_res = ref_eng.run()
    ref_tokens = {i: ref_res[rid].tokens for i, rid in ref_ids.items()}

    from scripts._leakcheck import assert_no_leaks, leak_baseline

    baseline = leak_baseline()

    if in_process:
        replicas: List[Any] = [_local_replica(i, net, throttle)
                               for i in range(n_replicas)]
    else:
        replicas = [_proc_replica(i, throttle)
                    for i in range(n_replicas)]
        for r in replicas:
            r.wait_ready()

    router = ServingRouter(
        [r.address for r in replicas],
        affinity_block_tokens=AFFINITY_BLOCK,
        health_interval_s=0.1, probe_interval_s=0.5,
        metrics_every=1, failure_threshold=2).start()

    # -- fault seam 1: every donor export for COHORT 1's key arrives
    # truncated — that cohort's transfers must 400 on import and the
    # requests must complete by recompute, bit-identically; cohort
    # 0's transfers prove the success path on the same run
    fetches = {"n": 0, "torn": 0}
    orig_fetch = router._fetch_kv_payload
    torn_prefix = list(cohorts[1])

    def torn_fetch(donor, prompt):
        payload = orig_fetch(donor, prompt)
        if payload is None:
            return None
        fetches["n"] += 1
        if list(prompt[:AFFINITY_BLOCK]) == torn_prefix:
            fetches["torn"] += 1
            return payload[:max(len(payload) // 3, 12)]
        return payload

    router._fetch_kv_payload = torn_fetch

    # wait for capability scrape: the plane only engages once the
    # health loop has learned every replica speaks it
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        st = router.replica_status()
        if all(s["kv_capable"] and s["state"] == "live" for s in st):
            break
        time.sleep(0.05)

    client = RouterClient(router.address, timeout_s=240.0)

    # -- warm phase: one short stream per cohort seeds each key's
    # rendezvous owner (and the router's warm-belief map), so the
    # waves' overflow picks have a genuinely warm donor to pull from
    for cohort in cohorts:
        client.generate(list(cohort), 4)

    t0 = time.perf_counter()
    outcomes: Dict[int, Dict[str, Any]] = {}
    rid_of: Dict[int, int] = {}

    def one_client(i: int) -> None:
        prompt, n_tokens = cases[i]
        out: Dict[str, Any] = {"tokens": []}
        outcomes[i] = out
        try:
            s = client.stream(prompt, n_tokens)
            rid_of[i] = s.id
            for delta in s:
                out["tokens"].extend(delta)
            out["result"] = (s.result or {}).get("finish_reason")
            out["final"] = s.result
        except Exception as e:
            out["result"] = f"crash:{type(e).__name__}:{e}"

    def run_wave(lo: int, hi: int) -> None:
        wave_threads = [threading.Thread(target=one_client,
                                         args=(i,),
                                         name=f"kv-soak-{i}")
                        for i in range(lo, hi)]
        for t in wave_threads:
            t.start()
        for t in wave_threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in wave_threads), (
            "wave client hang")

    # phase A: the two single-cohort overflow waves (see above)
    run_wave(0, wave)
    stats_a = dict(router.stats)
    assert stats_a["kv_transfers"] >= 1, (
        f"cohort-0 wave produced no warm import: {stats_a} "
        f"(overflow={stats_a['affinity_overflow']})")
    run_wave(wave, 2 * wave)
    stats_a = dict(router.stats)
    assert stats_a["kv_transfer_failures"] >= 1, (
        f"cohort-1 torn wave produced no fault fallback: {stats_a} "
        f"(fetches={fetches})")

    # phase B: mixed churn under which the kill lands
    threads = [threading.Thread(target=one_client, args=(i,),
                                name=f"kv-soak-{i}")
               for i in range(churn_base, len(cases))]
    for t in threads:
        t.start()

    # -- fault seam 2: SIGKILL the busiest replica with streams (and
    # possibly transfers) in flight — the kill may land mid-transfer
    # on either side; route-around/replay absorb both
    def open_by_replica() -> Dict[str, int]:
        with router._lock:
            counts: Dict[str, int] = {}
            for e in router._journal.values():
                if not e.done.is_set() and e.replica_address:
                    counts[e.replica_address] = counts.get(
                        e.replica_address, 0) + 1
        return counts

    chaos: Dict[str, Any] = {"killed": None, "inflight_at_kill": 0}
    kill_deadline = time.monotonic() + 120
    victim = None
    while time.monotonic() < kill_deadline:
        counts = open_by_replica()
        ready = [(n, a) for a, n in counts.items()
                 if n >= min_inflight_at_kill]
        if ready:
            addr = max(ready)[1]
            victim = next(r for r in replicas if r.address == addr)
            chaos["inflight_at_kill"] = max(ready)[0]
            break
        if all(not t.is_alive() for t in threads):
            break
        time.sleep(0.005)
    assert victim is not None, (
        f"never reached {min_inflight_at_kill} concurrent streams "
        f"on one replica (peak {open_by_replica()})")
    victim.sigkill()
    chaos["killed"] = victim.replica_id

    for t in threads:
        t.join(timeout=240)
    assert not any(t.is_alive() for t in threads), "client hang"
    wall_s = time.perf_counter() - t0

    # -- gates ---------------------------------------------------------
    crashes = [o for o in outcomes.values()
               if str(o["result"]).startswith("crash")]
    assert not crashes, f"client crashes: {crashes[:3]}"
    assert len(rid_of) == len(cases)
    audit = router.journal_audit()
    assert audit["open"] == [], f"journal still open: {audit['open']}"
    assert audit["lost"] == [], f"journal lost: {audit['lost']}"

    completed = parity_ok = 0
    for i, out in outcomes.items():
        final = out.get("final") or {}
        if final.get("tokens") is not None:
            assert out["tokens"] == final["tokens"], (
                f"client {i}: streamed != terminal")
        if out["result"] in ("length", "eos"):
            completed += 1
            assert out["tokens"] == ref_tokens[i], (
                f"client {i} diverged from the fault-free reference "
                f"(replays={final.get('replays')}) — a torn "
                "transfer or warm import corrupted ids")
            parity_ok += 1
        else:
            raise AssertionError(
                f"greedy client {i} unexpected terminal "
                f"{out['result']!r}")
    assert completed >= len(cases) // 2, (
        f"only {completed}/{len(cases)} completed")

    # the plane ran AND its faults fell back
    stats = dict(router.stats)
    assert stats["kv_transfers"] >= 1, (
        f"no successful cross-replica transfer: {stats}")
    assert stats["kv_transfer_failures"] >= 1, (
        f"no injected transfer fault was exercised: {stats} "
        f"(fetches={fetches})")
    assert fetches["torn"] >= 1, fetches

    # the plane is priced on the fleet surface (latency_report row)
    from scripts.latency_report import fleet_report

    fleet = fleet_report(client.fleet_metrics())
    fleet_phases = {r["phase"]: r for r in fleet["fleet"]}
    assert "kv_transfer" in fleet_phases, fleet_phases.keys()
    assert fleet_phases["kv_transfer"]["count"] >= 1

    router.close()
    for r in replicas:
        r.shutdown()
    leaks = assert_no_leaks(
        baseline, subprocesses=[] if in_process else replicas)

    summary = {
        "n_clients": len(cases),
        "n_replicas": n_replicas,
        "mode": "in-process" if in_process else "subprocess",
        "seed": seed,
        "wall_s": round(wall_s, 2),
        "completed": completed,
        "greedy_parity_ok": parity_ok,
        "killed": chaos["killed"],
        "inflight_at_kill": chaos["inflight_at_kill"],
        "replayed_requests": len(audit["replayed"]),
        "kv_transfers": stats["kv_transfers"],
        "kv_transfer_failures": stats["kv_transfer_failures"],
        "kv_transferred_tokens": stats["kv_transferred_tokens"],
        "payloads_torn": fetches["torn"],
        "fleet_kv_transfer_count":
            fleet_phases["kv_transfer"]["count"],
        "leaked_threads": leaks["leaked_threads"],
        "leaked_fds": leaks["leaked_fds"],
    }
    if verbose:
        for k, v in summary.items():
            print(f"  {k}: {v}")
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fast", action="store_true",
                    help="tier-1-sized in-process variant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--replica", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--replica-id", default="kv",
                    help=argparse.SUPPRESS)
    ap.add_argument("--throttle", type=float, default=0.04,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.replica:
        return run_replica(args)
    if args.fast:
        summary = run_soak(n_clients=args.clients or 14,
                           n_replicas=2, seed=args.seed,
                           in_process=True, verbose=True)
    else:
        summary = run_soak(n_clients=args.clients or 20,
                           n_replicas=3, seed=args.seed,
                           in_process=False, verbose=True)
    print(f"kv transfer soak PASSED: {summary['completed']} "
          f"completed (parity {summary['greedy_parity_ok']}), "
          f"{summary['kv_transfers']} transfers "
          f"({summary['kv_transferred_tokens']} tokens), "
          f"{summary['kv_transfer_failures']} faults fell back, "
          f"killed {summary['killed']} with "
          f"{summary['inflight_at_kill']} in flight, "
          f"in {summary['wall_s']}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
