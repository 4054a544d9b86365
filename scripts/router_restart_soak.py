"""Kill-the-router chaos soak (ISSUE 15 acceptance gate).

Every chaos soak to date kills REPLICAS; the router — the fleet's only
unreplicated component — was assumed immortal. This soak SIGKILLs the
router itself, mid-stream, across multiple kill/restart cycles, and
gates that the write-ahead journal + client resumption make the crash
invisible at the token level:

- the router runs as a REAL subprocess (so the kill is a real
  ``SIGKILL``: no atexit, no flush, no goodbye) bound to a fixed port
  with a ``--journal-path`` WAL;
- streaming clients run with ``resumable=True``; when their connection
  dies they reconnect to the SAME address with
  ``Last-Event-ID = tokens received`` and keep consuming — against
  the RESTARTED router, whose recovery replayed their open entries
  from the WAL onto whichever replicas answer healthz;
- the kill lands only once >= ``min_inflight_at_kill`` streams are in
  flight (read from the router's own healthz ``journal_open``), and
  full mode injects one kill mid-drain (``/v1/replicas/drain`` racing
  the SIGKILL) over PAGED replicas, so recovery also lands amid
  KV-transfer-capable affinity traffic.

Pass criteria:

- **zero lost streams**: every client reaches a terminal; the final
  router's journal shows nothing open;
- **zero duplicated / zero lost tokens, at the wire**: every SSE
  event's id equals the client's cumulative token count (the event-id
  stream is gap- and overlap-free across every reconnect), and each
  client's concat equals its terminal ``tokens`` exactly;
- **bit-identical greedy completions** vs the fault-free single-engine
  reference, across every kill/restart cycle;
- **sampling contract**: a sampling stream that already streamed
  tokens when the router died terminates ``fault`` (the PR 3/5
  no-silent-redraw contract, now across router restarts);
- **bounded WAL**: after ``n_cycles`` kill/restart cycles the journal
  file stays under 2x its compaction threshold and compactions
  actually ran;
- **router.recover span**: the restarted router's stitched
  ``/v1/trace`` carries the recovery span with its entry counts;
- **zero leaked threads/fds/subprocesses** (scripts/_leakcheck.py).

Two modes:

- ``--fast`` (tier-1, tests/test_router_restart_soak.py): 2 in-process
  gateway replicas + the subprocess router (the router child imports
  only the router module — no jax — so a boot costs ~1s), 3 cycles.
- full (``slow`` in the registered tests): 3 subprocess PAGED
  replicas + the subprocess router via the same child, kill #2 racing
  a drain.

Run standalone: ``python scripts/router_restart_soak.py [--fast]``.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from scripts.router_soak import (  # noqa: E402
    ENGINE,
    VOCAB,
    _build_net,
    _throttle,
)

#: the router_soak engine config with small blocks and a stated pool
#: (full mode): the same net and geometry
PAGED_ENGINE = dict(ENGINE, block_tokens=4, kv_blocks=96)


# ---------------------------------------------------------------------------
# --router child: the process the soak SIGKILLs
# ---------------------------------------------------------------------------

def run_router(args) -> int:
    """Subprocess router child. Imports ONLY the router module (no
    jax, no engine) so a restart costs ~1s of boot, and prints its
    ready line AFTER start() — recovery replay is already launched
    when clients reconnect."""
    from deeplearning4j_tpu.serving.router import ServingRouter

    router = ServingRouter(
        [a.strip() for a in args.replicas.split(",") if a.strip()],
        port=args.port,
        affinity_block_tokens=4,
        health_interval_s=0.1,
        metrics_every=1,
        failure_threshold=2,
        probe_interval_s=0.5,
        journal_path=args.journal_path,
        fsync=args.fsync,
        wal_compact_bytes=args.wal_compact_bytes).start()
    print(f"ROUTING {router.address} recovered="
          f"{router.stats['recovered_entries']} open="
          f"{router.stats['recovered_open']}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    try:
        while not stop.is_set():
            stop.wait(0.2)
    finally:
        with contextlib.suppress(Exception):
            router.close()
    return 0


def router_argv(port: int, replicas: List[str], journal_path: str,
                fsync: str, wal_compact_bytes: int) -> List[str]:
    return [sys.executable, os.path.abspath(__file__), "--router",
            "--port", str(port), "--replicas", ",".join(replicas),
            "--journal-path", journal_path, "--fsync", fsync,
            "--wal-compact-bytes", str(wal_compact_bytes)]


def spawn_router(port: int, replicas: List[str], journal_path: str,
                 fsync: str = "batched",
                 wal_compact_bytes: int = 1 << 16):
    """The router as a killable subprocess handle (ReplicaProcess —
    the handle protocol is process management, not gateway-specific)."""
    from deeplearning4j_tpu.serving.replica_proc import ReplicaProcess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return ReplicaProcess(
        router_argv(port, replicas, journal_path, fsync,
                    wal_compact_bytes),
        replica_id="router", port=port, env=env,
        ready_pattern="ROUTING",
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# --replica child (full mode): one PAGED gateway process
# ---------------------------------------------------------------------------

def run_replica(args) -> int:
    from deeplearning4j_tpu.serving import DecodeEngine, ServingGateway

    engine = DecodeEngine(_build_net(), **PAGED_ENGINE)
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
         "--port", str(port), "--replica-id", f"rep-{idx}",
         "--throttle", str(throttle)],
        replica_id=f"rep-{idx}", port=port, env=env,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))


def _local_replica(idx: int, net, throttle: float):
    from deeplearning4j_tpu.serving import DecodeEngine
    from deeplearning4j_tpu.serving.replica_proc import LocalReplica

    engine = DecodeEngine(net, **ENGINE)
    if throttle > 0:
        _throttle(engine, throttle)
    return LocalReplica(engine, replica_id=f"rep-{idx}")


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def _workload(rng, n_clients: int):
    """Seeded prompts: a shared-prefix cohort (affinity traffic whose
    warm keyspace must survive the ROUTER dying) plus singles; 1 in 6
    samples (the fault-contract lane)."""
    cohort = rng.integers(0, VOCAB, 8).tolist()
    cases = []
    for i in range(n_clients):
        if i % 3 < 2:
            prompt = (cohort
                      + rng.integers(0, VOCAB,
                                     int(rng.integers(1, 4))).tolist())
        else:
            prompt = rng.integers(
                0, VOCAB, int(rng.integers(4, 10))).tolist()
        n_tokens = int(rng.integers(20, 40))
        temperature = 0.7 if i % 6 == 5 else 0.0
        cases.append((prompt, n_tokens, temperature))
    return cases


# ---------------------------------------------------------------------------
# the resuming client: the tentpole's consumer side
# ---------------------------------------------------------------------------

def resuming_stream(client, prompt: List[int], n_tokens: int,
                    temperature: float,
                    deadline_s: float = 180.0,
                    out: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Run one resumable stream to its terminal, reconnecting through
    router deaths. Asserts the wire-level exactly-once contract as it
    goes: every SSE event id must equal the cumulative token count
    (an id too low = duplicated delivery, too high = lost tokens)."""
    from deeplearning4j_tpu.serving import GatewayError

    if out is None:
        out = {}
    out.setdefault("tokens", [])
    out.setdefault("reconnects", 0)
    out["temperature"] = temperature
    got: List[int] = out["tokens"]
    rid: Optional[int] = None
    deadline = time.monotonic() + deadline_s
    while True:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"stream (rid={rid}) never reached a terminal "
                f"within {deadline_s}s; got {len(got)} tokens")
        stream = None
        try:
            if rid is None:
                kwargs = {"resumable": True}
                if temperature:
                    kwargs["temperature"] = temperature
                stream = client.stream(prompt, n_tokens, **kwargs)
                rid = stream.id
                out["rid"] = rid
            else:
                stream = client.resume(rid, last_event_id=len(got))
                # counted only once the resume stream actually
                # OPENED (a refused connect while the router reboots
                # is a retry, not a resume)
                out["reconnects"] += 1
            for delta in stream:
                got.extend(delta)
                if stream.last_event_id is not None:
                    assert stream.last_event_id == len(got), (
                        f"rid={rid}: event id "
                        f"{stream.last_event_id} != cumulative "
                        f"token count {len(got)} — "
                        + ("duplicated" if stream.last_event_id
                           < len(got) else "lost") + " delivery")
            if stream.result is not None:
                out["final"] = stream.result
                out["result"] = stream.result.get("finish_reason")
                return out
            # stream ended with no terminal: the router died
            # mid-relay — reconnect and resume
        except GatewayError as e:
            if e.status == 0:
                pass  # stream ended terminal-less: router died
            elif e.status == 404 and rid is not None:
                # restarted router evicted/never recovered the rid —
                # would be a LOST stream; let the deadline surface it
                time.sleep(0.1)
            else:
                raise
        except (OSError, ValueError):
            pass  # router down / torn frame mid-death: retry
        finally:
            if stream is not None:
                stream.close()
        time.sleep(0.05)


# ---------------------------------------------------------------------------
# the soak proper
# ---------------------------------------------------------------------------

def run_soak(n_clients_per_wave: int = 12, n_replicas: int = 2,
             n_cycles: int = 3, seed: int = 0,
             in_process: bool = True, throttle: float = 0.05,
             min_inflight_at_kill: int = 8,
             drain_at_cycle: Optional[int] = None,
             fsync: str = "batched",
             wal_compact_bytes: int = 8 << 10,
             verbose: bool = False) -> Dict[str, Any]:
    """One seeded soak; returns a summary dict, raises AssertionError
    on any gate violation. ``drain_at_cycle`` injects a
    ``drain_replica`` immediately before that cycle's SIGKILL (full
    mode: the kill lands mid-drain)."""
    import tempfile

    from deeplearning4j_tpu.serving import (
        DecodeEngine,
        Request,
        RouterClient,
    )
    from deeplearning4j_tpu.serving.replica_proc import free_port
    from scripts._leakcheck import assert_no_leaks, leak_baseline

    rng = np.random.default_rng(seed)
    cases = _workload(rng, n_clients_per_wave * n_cycles)

    # fault-free single-engine reference (same net/config family —
    # greedy ids are layout-invariant, the standing paged-parity gate)
    net = _build_net()
    ref_eng = DecodeEngine(net, **ENGINE)
    greedy_idx = [i for i, (_, _, t) in enumerate(cases) if t == 0]
    ref_ids = {i: ref_eng.submit(Request(list(cases[i][0]),
                                         cases[i][1]))
               for i in greedy_idx}
    ref_res = ref_eng.run()
    ref_tokens = {i: ref_res[rid].tokens
                  for i, rid in ref_ids.items()}

    baseline = leak_baseline()

    if in_process:
        replicas: List[Any] = [_local_replica(i, net, throttle)
                               for i in range(n_replicas)]
    else:
        replicas = [_proc_replica(i, throttle)
                    for i in range(n_replicas)]
        for r in replicas:
            r.wait_ready()
    replica_addrs = [r.address for r in replicas]

    tmp = tempfile.mkdtemp(prefix="router-restart-soak-")
    wal_path = os.path.join(tmp, "router.wal")
    router_port = free_port()
    router_address = f"127.0.0.1:{router_port}"

    def boot_router():
        proc = spawn_router(router_port, replica_addrs, wal_path,
                            fsync=fsync,
                            wal_compact_bytes=wal_compact_bytes)
        proc.wait_ready(timeout_s=120.0)
        return proc

    router_procs = [boot_router()]
    client = RouterClient(router_address, timeout_s=240.0,
                          connect_timeout_s=2.0)
    t0 = time.perf_counter()

    outcomes: Dict[int, Dict[str, Any]] = {}
    crashes: List[str] = []

    def one_client(i: int) -> None:
        prompt, n_tokens, temperature = cases[i]
        out = outcomes[i] = {"tokens": []}
        try:
            resuming_stream(client, prompt, n_tokens, temperature,
                            out=out)
        except Exception as e:  # no client thread dies silently
            crashes.append(f"client {i}: "
                           f"{type(e).__name__}: {e}")

    def journal_open() -> int:
        with contextlib.suppress(Exception):
            return int(client.healthz().get("journal_open", 0))
        return -1  # router down

    threads: List[threading.Thread] = []
    kills = 0
    drained = None
    for cycle in range(n_cycles):
        wave = range(cycle * n_clients_per_wave,
                     (cycle + 1) * n_clients_per_wave)
        for i in wave:
            t = threading.Thread(target=one_client, args=(i,),
                                 name=f"restart-soak-{i}")
            t.start()
            threads.append(t)
        # wait until the router itself reports >= min_inflight open
        # journal entries, then SIGKILL it
        kill_deadline = time.monotonic() + 120
        armed = False
        while time.monotonic() < kill_deadline:
            if journal_open() >= min_inflight_at_kill:
                armed = True
                break
            if all(not t.is_alive() for t in threads):
                break
            time.sleep(0.01)
        assert armed, (
            f"cycle {cycle}: never reached {min_inflight_at_kill} "
            f"in-flight streams (journal_open={journal_open()}) — "
            "grow the wave or the throttle")
        if drain_at_cycle == cycle and n_replicas >= 3:
            # mid-drain kill (full mode): the drain hands work back
            # through the router that is about to die; recovery must
            # pick the pieces up on the survivors
            target = replicas[-1]
            drained = target.replica_id

            def _drain():
                with contextlib.suppress(Exception):
                    client.drain_replica(target.replica_id,
                                         timeout_s=0.2)

            threading.Thread(target=_drain, daemon=True,
                             name="soak-drain").start()
            time.sleep(0.05)  # let the drain reach the replica
        inflight = journal_open()
        router_procs[-1].sigkill()
        kills += 1
        if verbose:
            print(f"  cycle {cycle}: SIGKILL router with "
                  f"{inflight} in flight "
                  f"(WAL {os.path.getsize(wal_path)} bytes)")
        time.sleep(0.2)  # clients notice the break and start retrying
        router_procs.append(boot_router())

    for t in threads:
        t.join(timeout=240)
    assert not any(t.is_alive() for t in threads), "client hang"
    wall_s = time.perf_counter() - t0
    assert not crashes, f"client crashes: {crashes[:3]}"

    # -- gates ---------------------------------------------------------
    completed = parity_ok = faulted = resumed_ok = 0
    for i, out in outcomes.items():
        res = out.get("result")
        final = out.get("final") or {}
        # zero double delivery: the streamed concat IS the terminal
        if final.get("tokens") is not None:
            assert out["tokens"] == final["tokens"], (
                f"client {i}: streamed {len(out['tokens'])} tokens "
                f"!= terminal {len(final['tokens'])}")
        if res in ("length", "eos"):
            completed += 1
            if out["reconnects"]:
                resumed_ok += 1
            if out["temperature"] == 0:
                assert out["tokens"] == ref_tokens[i], (
                    f"client {i} diverged from the fault-free "
                    f"reference after {out['reconnects']} "
                    "reconnects")
                parity_ok += 1
        elif res == "fault":
            faulted += 1
            assert out["temperature"] > 0, (
                f"greedy client {i} faulted: {final}")
        else:
            raise AssertionError(
                f"client {i} unexpected terminal {res!r} "
                f"({final})")
    n_clients = len(cases)
    assert completed >= (n_clients * 2) // 3, (
        f"only {completed}/{n_clients} completed")
    assert resumed_ok >= 1, (
        "no COMPLETED stream ever crossed a router restart — the "
        "chaos never actually exercised recovery")

    # zero lost streams: the final router's journal has nothing open
    settle = time.monotonic() + 30
    while journal_open() > 0 and time.monotonic() < settle:
        time.sleep(0.05)
    final_health = client.healthz()
    assert final_health.get("journal_open") == 0, final_health

    # bounded WAL across the cycles + compactions actually ran (the
    # threshold is sized so this workload MUST cross it — a bound
    # that never engages gates nothing)
    wal_info = final_health.get("wal") or {}
    wal_bytes = os.path.getsize(wal_path)
    assert wal_bytes <= 2 * wal_compact_bytes, (
        f"WAL unbounded: {wal_bytes} bytes after {kills} "
        f"kill/restart cycles (threshold {wal_compact_bytes})")
    total_compactions = int(wal_info.get("compactions", 0))
    # per-process stats die with each kill, so the durable evidence
    # that compaction ran (in ANY of the router's lives) is the file
    # itself: a compacted journal starts with a snapshot record
    from deeplearning4j_tpu.serving.journal import read_records

    records_now, _ = read_records(wal_path)
    compacted_ever = (total_compactions >= 1
                      or (records_now
                          and records_now[0].get("t") == "snap"))
    assert compacted_ever, (
        f"WAL never compacted ({wal_bytes} bytes, threshold "
        f"{wal_compact_bytes}) — the bound was never exercised")

    # the recovery is ON the stitched trace: the final router's lane-0
    # carries router.recover with its entry accounting
    doc = client.trace_events()
    recover_spans = [e for e in doc["traceEvents"]
                     if e.get("name") == "router.recover"]
    assert recover_spans, (
        "no router.recover span on the restarted router's stitched "
        "trace")
    span_args = recover_spans[0].get("args") or {}
    assert span_args.get("entries", 0) >= 1, span_args

    recovered_total = int(wal_info.get("recovered_entries", 0))
    assert recovered_total >= 1, wal_info

    for proc in router_procs:
        proc.shutdown()
    for r in replicas:
        r.shutdown()
    leaks = assert_no_leaks(
        baseline,
        subprocesses=router_procs + (
            [] if in_process else replicas))

    summary = {
        "n_clients": n_clients,
        "n_replicas": n_replicas,
        "mode": "in-process" if in_process else "subprocess",
        "seed": seed,
        "wall_s": round(wall_s, 2),
        "router_kills": kills,
        "completed": completed,
        "greedy_parity_ok": parity_ok,
        "faulted_sampling": faulted,
        "completed_across_restart": resumed_ok,
        "reconnects": sum(o.get("reconnects", 0)
                          for o in outcomes.values()),
        "drained": drained,
        "wal_bytes_final": wal_bytes,
        "wal_compactions": total_compactions,
        "final_recovered_entries": recovered_total,
        "recover_span_entries": span_args.get("entries"),
        "recover_span_open": span_args.get("open"),
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
                    help="tier-1-sized in-process-replica variant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cycles", type=int, default=None)
    # child modes (internal)
    ap.add_argument("--router", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--replica", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--replicas", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--replica-id", default="rep",
                    help=argparse.SUPPRESS)
    ap.add_argument("--throttle", type=float, default=0.05,
                    help=argparse.SUPPRESS)
    ap.add_argument("--journal-path", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--fsync", default="batched",
                    help=argparse.SUPPRESS)
    ap.add_argument("--wal-compact-bytes", type=int,
                    default=1 << 16, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.router:
        return run_router(args)
    if args.replica:
        return run_replica(args)
    if args.fast:
        summary = run_soak(
            n_clients_per_wave=10, n_replicas=2,
            n_cycles=args.cycles or 3, seed=args.seed,
            in_process=True, verbose=True)
    else:
        summary = run_soak(
            n_clients_per_wave=12, n_replicas=3,
            n_cycles=args.cycles or 3, seed=args.seed,
            in_process=False, throttle=0.04,
            drain_at_cycle=1, verbose=True)
    print(f"router restart soak PASSED: {summary['router_kills']} "
          f"SIGKILLs, {summary['completed']} completed "
          f"(greedy parity {summary['greedy_parity_ok']}, "
          f"{summary['completed_across_restart']} across a restart, "
          f"{summary['reconnects']} reconnects, "
          f"{summary['faulted_sampling']} sampling faults), WAL "
          f"{summary['wal_bytes_final']} bytes after "
          f"{summary['wal_compactions']} compaction(s), "
          f"in {summary['wall_s']}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
