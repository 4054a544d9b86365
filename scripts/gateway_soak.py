"""Seeded churn soak for the serving gateway (ISSUE 5).

Drives N concurrent STREAMING HTTP clients against a chaos-configured
engine (prefix cache + chunked admission + paranoid quarantine + a
seeded :class:`FaultPlan`) behind a live :class:`ServingGateway`, with
seeded client misbehavior layered on top of the engine faults:

- ``disconnect`` clients vanish mid-stream (socket closed without a
  word) — the gateway must notice and cancel, freeing the slot;
- ``cancel`` clients DELETE their request mid-stream (the polite
  version of the same);
- ``deadline`` clients carry a tiny ``deadline_s`` so the engine's
  own expiry path fires under concurrent load;
- the rest stream to completion.

Pass criteria (the gateway-parity gate):

- every submitted request reaches a terminal result — no hangs, no
  losses, regardless of how its client behaved;
- every stream that COMPLETED has ids bit-identical to the same
  workload on a fault-free in-process engine (chaos-parity, over
  HTTP);
- zero leaked slots: the engine ends fully idle (no occupied slots,
  no reserved admissions, no queue remnants);
- zero leaked threads: after ``close()`` the process is back to its
  pre-gateway thread count (handler threads bounded by the
  util/httpjson socket timeout, stepper joined);
- compile counts stay at the in-process budget — the HTTP layer never
  retraces anything;
- observability (ISSUE 7): every terminal request's
  ``GET /v1/requests/<id>/trace`` parses, its phase sums fit inside
  its e2e wall time, its TTFT equals the terminal's ``ttft_s``,
  retried requests show distinct attempts, ``GET /v1/trace`` exports
  a non-empty Chrome trace, and neither endpoint ever answers 5xx.

Run standalone (``python scripts/gateway_soak.py [--fast]``) or via
the registered tests (tests/test_gateway_soak.py: fast variant tier-1,
full variant ``slow``).
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
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


def _workload(rng, n_clients: int, vocab: int):
    """Ragged prompts with a shared system-prefix cohort (the prefix
    cache must engage through HTTP too) and per-client behavior."""
    shared = rng.integers(0, vocab, 6).tolist()
    cases = []
    for i in range(n_clients):
        if i % 3 == 0:
            prompt = shared + rng.integers(
                0, vocab, int(rng.integers(1, 5))).tolist()
        else:
            prompt = rng.integers(
                0, vocab, int(rng.integers(1, 14))).tolist()
        n_tokens = int(rng.integers(6, 24))
        r = rng.random()
        if r < 0.2:
            behavior = "disconnect"
        elif r < 0.35:
            behavior = "cancel"
        elif r < 0.45:
            behavior = "deadline"
        else:
            behavior = "complete"
        cases.append((prompt, n_tokens, behavior,
                      int(rng.integers(1, 4))))  # deltas before misbehaving
    return cases


def run_soak(n_clients: int = 48, seed: int = 0, vocab: int = 12,
             n_slots: int = 4, fault_rate: float = 0.06,
             verbose: bool = False) -> Dict[str, Any]:
    """One seeded soak; returns a summary dict, raises AssertionError
    on any gate violation."""
    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        FaultPlan,
        GatewayClient,
        GatewayError,
        Request,
        ServingGateway,
    )

    rng = np.random.default_rng(seed)
    cases = _workload(rng, n_clients, vocab)

    def build(plan):
        return DecodeEngine(
            _build_net(vocab, 7), n_slots=n_slots, decode_chunk=4,
            prefix_cache_rows=4, prefill_chunk=4,
            admission_policy="decode", paranoid=True, fault_plan=plan,
            max_retries=3, max_queue=4 * n_clients)

    # fault-free in-process reference: the ids every COMPLETED stream
    # must match bit for bit
    ref_eng = build(None)
    ref_ids = [ref_eng.submit(Request(list(p), n))
               for p, n, _, _ in cases]
    ref = ref_eng.run()
    ref_tokens = [ref[rid].tokens for rid in ref_ids]

    from scripts._leakcheck import assert_no_leaks, leak_baseline

    baseline = leak_baseline()
    plan = FaultPlan.random(seed, rounds=40 * n_clients,
                            rate=fault_rate)
    gw = ServingGateway(build(plan), keepalive_s=0.1,
                        handler_timeout_s=5.0).start()
    client = GatewayClient(gw.address, timeout_s=120.0)
    t0 = time.perf_counter()

    outcomes: Dict[int, Dict[str, Any]] = {}
    rid_of: Dict[int, int] = {}

    def one_client(i: int) -> None:
        prompt, n_tokens, behavior, after = cases[i]
        out: Dict[str, Any] = {"behavior": behavior, "tokens": []}
        outcomes[i] = out
        try:
            kwargs = {}
            if behavior == "deadline":
                kwargs["deadline_s"] = 0.08
            s = client.stream(prompt, n_tokens, **kwargs)
            rid_of[i] = s.id
            n_deltas = 0
            for delta in s:
                out["tokens"].extend(delta)
                n_deltas += 1
                if behavior == "disconnect" and n_deltas >= after:
                    s.close()
                    out["result"] = "disconnected"
                    return
                if behavior == "cancel" and n_deltas >= after:
                    client.cancel(s.id)
                    # keep reading: the cancel terminal ends the
                    # stream cleanly
            out["result"] = (s.result or {}).get("finish_reason")
            out["final"] = s.result
        except GatewayError as e:
            out["result"] = f"error:{e.status}"
        except Exception as e:  # no client thread may die silently
            out["result"] = f"crash:{type(e).__name__}:{e}"

    threads = [threading.Thread(target=one_client, args=(i,),
                                name=f"soak-client-{i}")
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not any(t.is_alive() for t in threads), "client hang"

    # the engine must settle fully idle (disconnect cancels included)
    deadline = time.monotonic() + 60
    eng = gw.engine
    while time.monotonic() < deadline:
        with gw._lock:
            if not eng.has_work() and not eng._terminal:
                break
        time.sleep(0.02)
    wall_s = time.perf_counter() - t0

    # -- gates ---------------------------------------------------------
    crashes = [o for o in outcomes.values()
               if str(o["result"]).startswith("crash")]
    assert not crashes, f"client crashes: {crashes[:3]}"

    # every submitted request reached a terminal
    missing = [rid for rid in rid_of.values()
               if rid not in gw._results]
    assert not missing, f"requests without terminal: {missing[:5]}"

    # -- flight-recorder trace gates (ISSUE 7 satellite): every
    # terminal request's /v1/requests/<id>/trace must parse, its
    # phase sums must fit inside its e2e wall time, its TTFT must be
    # the terminal's exact ttft_s, retries must show as distinct
    # attempts — and the new endpoints must never 5xx under churn
    traced = 0
    for rid in rid_of.values():
        try:
            trace = client.trace(rid)
        except GatewayError as e:
            assert e.status < 500, (
                f"trace endpoint 5xx for request {rid}: {e}")
            raise AssertionError(
                f"terminal request {rid} has no trace: {e}")
        assert not trace.get("running"), (
            f"request {rid} terminal but trace says running")
        timing = trace["timing"]
        phase_sum = (timing["queue_wait_s"] + timing["admission_s"]
                     + timing["decode_s"] + timing["verify_s"]
                     + timing["stall_s"])
        assert phase_sum <= timing["e2e_s"] + 1e-9, (
            f"request {rid}: phase sum {phase_sum} exceeds e2e "
            f"{timing['e2e_s']}")
        term = gw._results[rid]
        assert timing["ttft_s"] == term.ttft_s, (
            f"request {rid}: trace ttft {timing['ttft_s']} != "
            f"terminal ttft {term.ttft_s}")
        assert len(trace["attempts"]) == term.retries + 1, (
            f"request {rid}: {term.retries} retries but "
            f"{len(trace['attempts'])} attempts in the timeline")
        traced += 1
    assert traced == len(rid_of)
    try:
        trace_doc = client.trace_events()
    except GatewayError as e:
        raise AssertionError(f"/v1/trace failed: {e}")
    assert trace_doc["traceEvents"], "empty /v1/trace export"

    completed = parity_ok = 0
    disconnected = cancelled = deadline_hits = faulted = 0
    for i, out in outcomes.items():
        res = out["result"]
        if res in ("length", "eos"):
            completed += 1
            assert out["tokens"] == ref_tokens[i], (
                f"client {i} streamed ids diverged from the "
                f"fault-free reference")
            parity_ok += 1
        elif res == "disconnected":
            disconnected += 1
            term = gw._results[rid_of[i]]
            assert term.finish_reason in (
                "cancelled", "length", "eos"), term
        elif res == "cancelled":
            cancelled += 1
        elif res == "deadline":
            deadline_hits += 1
        elif res == "fault":
            faulted += 1
    assert completed >= 1 and parity_ok == completed

    # zero leaked slots: fully idle engine, nothing reserved
    assert all(s is None for s in eng._slots), eng._slots
    assert not eng._pending and not eng._reserved
    assert eng.scheduler.pending == 0 and not eng._requeue

    counts = eng.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["paged_scatter"] == 1 and counts["paged_tok"] == 1, (
        counts)
    assert counts["health_check"] == 1, counts
    # a cold row's continuation, a warm table's
    assert 1 <= counts["chunk_prefill"] <= 2, counts

    gw.close()
    # zero leaked threads (shared settle-loop gate —
    # scripts/_leakcheck.py): handler threads are timeout-bounded,
    # the stepper and server threads join in close()
    leaks = assert_no_leaks(baseline)

    summary = {
        "n_clients": n_clients,
        "seed": seed,
        "wall_s": round(wall_s, 2),
        "completed": completed,
        "parity_ok": parity_ok,
        "disconnected": disconnected,
        "cancelled": cancelled,
        "deadline": deadline_hits,
        "faulted": faulted,
        "faults_injected": eng.stats["faults_injected"],
        "disconnect_cancels": gw.stats["disconnect_cancels"],
        "engine_cancelled": eng.stats["cancelled"],
        "traced": traced,
        "trace_events": len(trace_doc["traceEvents"]),
        "leaked_threads": leaks["leaked_threads"],
        "compile_counts": counts,
    }
    if verbose:
        for k, v in summary.items():
            print(f"  {k}: {v}")
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fast", action="store_true",
                    help="small tier-1-sized variant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=None)
    args = ap.parse_args()
    n = args.clients or (16 if args.fast else 48)
    summary = run_soak(n_clients=n, seed=args.seed, verbose=True)
    print(f"gateway soak PASSED: {summary['completed']} completed "
          f"(parity {summary['parity_ok']}), "
          f"{summary['disconnected']} disconnected, "
          f"{summary['cancelled']} cancelled, "
          f"{summary['deadline']} deadline, "
          f"{summary['faulted']} faulted "
          f"in {summary['wall_s']}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
