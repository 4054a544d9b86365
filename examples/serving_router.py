"""Two-replica serving with the failure-tolerant prefix-aware router
(ISSUE 9) — replicas, router, and clients in one script.

Trains the pattern-following LM from `streaming_decode.py`, runs TWO
:class:`~deeplearning4j_tpu.serving.ServingGateway` replicas over it,
and fronts them with the
:class:`~deeplearning4j_tpu.serving.ServingRouter`:

1. **Prefix-affinity routing** — a cohort of requests sharing a
   system prefix rendezvous-hashes onto ONE replica, where the radix
   prefix cache serves the shared tokens warm; the affinity hit
   counters prove it.
2. **Mid-stream failover** — the replica owning a live stream is
   hard-killed (the network-identical SIGKILL stand-in); the router
   replays the request from its journal onto the survivor and the
   stream resumes bit-identically past the already-delivered tokens.
3. **Replica state machine** — the router's `/v1/healthz` shows the
   breaker opening on the dead replica (live → dead) while the
   survivor keeps serving.
4. **Fleet-wide tracing (ISSUE 10)** — the failover request's
   STITCHED cross-replica timeline from the router's `GET /v1/trace`
   (the dead replica's spans from the router's trace cache, the
   survivor's live, both skew-corrected onto the router clock, with
   the bridging `router.replay` span), and fleet p50/p99 TTFT from
   `GET /v1/fleet/metrics` (replica histograms merged bucket-wise).
5. **Elastic scale-up under load (ISSUE 11)** — a burst of concurrent
   streams overloads the lone survivor; the
   :class:`~deeplearning4j_tpu.serving.FleetController` sees the
   pressure breach, spawns a fresh replica through its factory, warms
   it from the live affinity keys, and swaps it into the rendezvous
   set — the burst finishes bit-identically and the decision is a
   `fleet.scale` span on the same stitched trace.
6. **Multi-tenant QoS (ISSUE 13)** — a tenant table arms the
   weighted-fair scheduler and the router's token buckets: a flooder
   submitting at ~20x its rate quota is 429'd at the front door with
   its OWN Retry-After (the payload names the tenant) while a
   premium stream completes at SLO, bit-identical — and the
   per-tenant `{tenant=...}` latency histograms read back through
   `latency_report --tenant` rows from the federated scrape.
7. **Durable router (ISSUE 15)** — a router armed with a write-ahead
   journal is SIGKILLed mid-stream (step 8): a fresh router recovers
   from the same WAL, replays the open stream through the PR 9 path,
   and the client resumes with `Last-Event-ID` — the concatenation
   of pre-kill and post-recovery deltas is bit-identical to the
   fault-free ids, and the recovery reads as a `router.recover` span
   on the stitched trace.

Run: python examples/serving_router.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("DL4J_EXAMPLES_PLATFORM", "native") == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    RouterClient,
    ServingGateway,
    ServingRouter,
)

VOCAB = 8
PATTERN = [1, 3, 5, 7, 2, 4, 6, 0]
TINY = os.environ.get("DL4J_EXAMPLES_TINY") == "1"


def one_hot_seq(ids):
    x = np.zeros((1, VOCAB, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


def main():
    net = MultiLayerNetwork(transformer_lm(
        n_in=VOCAB, width=32, n_layers=2, n_heads=4, n_classes=VOCAB,
        lr=5e-3, seed=1)).init()
    seq = (PATTERN * 6)[:40]
    for _ in range(100 if TINY else 400):
        net.fit(DataSet(one_hot_seq(seq[:-1]), one_hot_seq(seq[1:])))
    print(f"train loss {float(net.score_value):.4f}")

    # two replicas over the SAME weights/seed (the fleet contract:
    # greedy replay is only bit-identical across true replicas) — a
    # slight per-round throttle keeps the toy engines slow enough to
    # watch the failover happen mid-stream
    def replica(i):
        engine = DecodeEngine(net, n_slots=4, decode_chunk=2,
                              prefix_cache_rows=4)
        orig = engine.step

        def throttled(sink=None):
            time.sleep(0.06)
            return orig(sink)

        engine.step = throttled
        return ServingGateway(engine, replica_id=f"replica-{i}",
                              keepalive_s=0.1).start()

    replicas = [replica(0), replica(1)]
    router = ServingRouter(
        [g.address for g in replicas], affinity_block_tokens=4,
        health_interval_s=0.1, probe_interval_s=0.5,
        metrics_every=1,  # scrape the trace cache every tick, so the
        failure_threshold=2).start()  # kill can't outrun the cache
    client = RouterClient(router.address)
    print(f"router on {router.address} over "
          f"{[g.replica_id for g in replicas]}")
    # let the first health scrape learn the stable replica ids before
    # any affinity key is hashed against them
    while {r["replica_id"] for r in client.healthz()["replicas"]} \
            != {"replica-0", "replica-1"}:
        time.sleep(0.05)

    # 1. shared-system-prompt cohort: rendezvous lands every request
    # on the replica holding the prefix warm
    shared = PATTERN[:4]
    cohort = [shared + [PATTERN[i % len(PATTERN)]] for i in range(6)]
    outs = [client.generate(p, 8) for p in cohort]
    counters = [g.engine.stats["prefill_tokens_skipped"]
                for g in replicas]
    hits = sum(1 for o in outs[1:] if o["prefix_tokens_reused"] > 0)
    print(f"affinity : {hits}/{len(outs) - 1} warm-eligible requests "
          f"hit the warm replica's prefix cache")
    print(f"           prefix_tokens_reused per replica: "
          f"{dict(zip([g.replica_id for g in replicas], counters))}")

    # 2. mid-stream failover: kill the replica that owns the stream
    n_gen = 12 if TINY else 24
    s = client.stream(PATTERN[:3], n_gen)
    got = []
    killed = None
    for delta in s:
        got.extend(delta)
        if killed is None:
            owner_addr = router._journal[s.id].replica_address
            killed = next(g for g in replicas
                          if owner_addr.endswith(
                              str(g._service.port)))
            print(f"stream {s.id} on {killed.replica_id}: "
                  f"got {got} — KILLING {killed.replica_id}")
            # one trace-cache scrape captures the victim's spans so
            # the dead lane of the stitched trace is populated
            time.sleep(0.12)
            killed.hard_kill()
        else:
            print(f"  += {delta}")
    print(f"failover : finish_reason={s.result['finish_reason']} "
          f"after {s.result['replays']} replay(s); "
          f"{len(got)} tokens, no gap, no dupes")
    expected = [PATTERN[(3 + i) % len(PATTERN)] for i in range(n_gen)]
    print(f"           pattern intact across the kill: "
          f"{got == expected}")

    # 3. the breaker opened on the dead replica; the survivor serves
    time.sleep(0.5)
    states = {r["replica_id"]: r["state"]
              for r in client.healthz()["replicas"]}
    print(f"states   : {states}")
    out = client.generate(PATTERN[:5], 6)
    print(f"survivor : request {out['id']} -> "
          f"{out['finish_reason']} on the remaining replica")

    audit = router.journal_audit()
    print(f"journal  : {audit['entries']} entries, "
          f"lost={audit['lost']}, replayed={audit['replayed']}")

    # 4. fleet tracing: the failover request as ONE timeline spanning
    # both replicas' lanes (stitched /v1/trace), then fleet-wide
    # latency quantiles from the federated /v1/fleet/metrics
    tid = s.result["trace"]
    doc = client.trace_events()   # against a router: the STITCH
    lane_names = {e["pid"]: e["args"]["name"]
                  for e in doc["traceEvents"]
                  if e.get("name") == "process_name"}

    def of_trace(e):
        a = e.get("args") or {}
        vals = [a.get("trace")] + list((a.get("traces")
                                        or {}).values())
        return any(v == tid or str(v).startswith(tid + "/")
                   for v in vals if v)

    timeline = sorted(
        (e for e in doc["traceEvents"]
         if of_trace(e) and e.get("ph") == "X"),
        key=lambda e: e["ts"])
    t0_us = timeline[0]["ts"]
    print(f"timeline : request {s.id} (trace {tid}) across "
          f"{len({e['pid'] for e in timeline})} processes:")
    for e in timeline:
        print(f"           +{(e['ts'] - t0_us) / 1e3:8.1f}ms "
              f"{e.get('dur', 0) / 1e3:7.1f}ms  "
              f"{lane_names.get(e['pid'], e['pid']):<22} {e['name']}")
    replay = next(e for e in timeline
                  if e["name"] == "router.replay")
    print(f"           the router.replay span bridges the lanes: "
          f"{replay['args']['from_replica']} -> survivor, "
          f"high-water {replay['args']['high_water']} tokens, "
          f"overlap_ok={replay['args']['overlap_ok']}")

    from scripts.latency_report import fleet_report

    fleet = {r["phase"]: r
             for r in fleet_report(client.fleet_metrics())["fleet"]}
    ttft = fleet["ttft"]
    print(f"fleet    : p50 TTFT {ttft['p50_ms']:.0f}ms, "
          f"p99 TTFT {ttft['p99_ms']:.0f}ms over "
          f"{ttft['count']} requests on both replicas; "
          f"replay gap p50 "
          f"{fleet['replay_gap']['p50_ms']:.0f}ms")

    # 5. elastic scale-up under load (ISSUE 11): overload the lone
    # survivor with a burst; the controller breathes the fleet
    import threading

    from deeplearning4j_tpu.serving import (
        FleetController,
        LocalReplica,
    )

    def spawn_replica(replica_id):
        engine = DecodeEngine(net, n_slots=4, decode_chunk=2,
                              prefix_cache_rows=4)
        orig = engine.step

        def throttled(sink=None):
            time.sleep(0.06)
            return orig(sink)

        engine.step = throttled
        return LocalReplica(engine, replica_id=replica_id)

    controller = FleetController(
        router, replica_factory=spawn_replica,
        min_replicas=1, max_replicas=2, eval_interval_s=0.15,
        pressure_high=1.5, pressure_low=0.3, breach_evals=2,
        cooldown_s=1.0, id_prefix="elastic").start()
    n_burst, burst_gen = 8, 16
    burst_outs = [None] * n_burst

    def one(i):
        s2 = client.stream(PATTERN[:3], burst_gen)
        toks = []
        for delta in s2:
            toks.extend(delta)
        burst_outs[i] = toks

    burst = [threading.Thread(target=one, args=(i,))
             for i in range(n_burst)]
    for t in burst:
        t.start()
    # wait for the scale-up WHILE the burst holds the pressure on —
    # once the streams finish, pressure is gone and the breach
    # streak can never start
    deadline = time.monotonic() + 15
    while (not any(e["action"] == "up" for e in controller.events)
           and time.monotonic() < deadline):
        time.sleep(0.05)
    for t in burst:
        t.join()
    ups = [e for e in controller.events if e["action"] == "up"]
    assert ups, ("controller never scaled up within 15s: last "
                 f"signals {controller.last_signals}")
    up = ups[0]
    expected_burst = [PATTERN[(3 + i) % len(PATTERN)]
                      for i in range(burst_gen)]
    print(f"elastic  : {n_burst} concurrent streams on 1 replica -> "
          f"controller scaled UP ({up['reason']}): spawned "
          f"{up['replica']} (warmed {up['warmed']} affinity "
          f"prefixes) in {up['dur_s']}s")
    states = {r["replica_id"]: r["state"]
              for r in client.healthz()["replicas"]}
    print(f"           fleet now: {states}")
    print(f"           burst bit-identical through the scale-up: "
          f"{all(o == expected_burst for o in burst_outs)}")
    scale_spans = [e for e in router.tracer.events()
                   if e.get("name") == "fleet.scale"]
    print(f"           {len(scale_spans)} fleet.scale span(s) on the "
          f"stitched trace (lane 0)")

    controller.close()
    router.close()
    controller.shutdown_fleet()
    for g in replicas:
        try:
            g.close()
        except Exception:
            pass

    # 6. multi-tenant QoS (ISSUE 13): a flooder is throttled at the
    # front door while a premium tenant's stream completes at SLO —
    # same weights, fresh stack with a tenant table armed
    from deeplearning4j_tpu.serving import (
        GatewayError,
        TenantRegistry,
        TenantSpec,
    )
    from scripts.latency_report import tenant_report

    registry = TenantRegistry((
        TenantSpec("premium", priority=2, weight=4),
        TenantSpec("flood", priority=0, weight=1, max_slots=1,
                   max_queued=2, rate_rps=2.0, burst=2.0)))
    qos_engine = DecodeEngine(net, n_slots=4, decode_chunk=2,
                              tenants=registry)
    orig_step = qos_engine.step
    qos_engine.step = lambda sink=None: (time.sleep(0.03),
                                         orig_step(sink))[1]
    qos_gw = ServingGateway(qos_engine, replica_id="qos-0",
                            keepalive_s=0.1).start()
    qos_router = ServingRouter([qos_gw.address], tenants=registry,
                               health_interval_s=0.1).start()
    qos_client = RouterClient(qos_router.address)
    flood_429 = 0
    flood_hint = None
    for i in range(12):  # ~20x the 2 rps quota
        try:
            qos_client.generate(PATTERN[:3], 6, tenant="flood")
        except GatewayError as e:
            if e.status == 429:
                flood_429 += 1
                flood_hint = (e.payload.get("tenant"),
                              e.retry_after_s)
    t0 = time.monotonic()
    s3 = qos_client.stream(PATTERN[:3], n_gen, tenant="premium")
    prem = []
    for delta in s3:
        prem.extend(delta)
    prem_s = time.monotonic() - t0
    hint = (f"(tenant={flood_hint[0]}, Retry-After "
            f"{flood_hint[1]}s)" if flood_hint is not None
            else "(host too slow to outrun the bucket this run)")
    print(f"tenancy  : flood 20x over quota -> {flood_429}/12 "
          f"throttled with its OWN hint {hint}")
    print(f"           premium stream at SLO through the flood: "
          f"{len(prem)} tokens in {prem_s:.2f}s, bit-identical "
          f"{prem == expected}")
    rows = tenant_report(
        qos_client.fleet_metrics())["tenants"]
    for tid in sorted(rows):
        ttft_row = next((r for r in rows[tid]
                         if r["phase"] == "ttft"), None)
        if ttft_row:
            print(f"           {tid:<8} ttft p99 "
                  f"{ttft_row['p99_ms']:7.1f}ms over "
                  f"{ttft_row['count']} requests "
                  f"({{tenant=\"{tid}\"}} labels end to end)")
    qos_router.close()
    qos_gw.close()

    # 7. KV transfer plane (ISSUE 14): an affinity-miss warm import —
    # a prefix warmed on one PAGED replica ships as serialized KV
    # blocks into a cold peer, whose next admission splices it
    # (prefill skipped) and produces bit-identical ids. The router
    # fires this hook automatically whenever a bounded-load overflow
    # or failover pick lands on a replica that is cold for the key;
    # here the public warm_transfer (the rolling-upgrade warmup path)
    # demonstrates it deterministically.
    from deeplearning4j_tpu.serving import GatewayClient

    def paged_replica(i):
        engine = DecodeEngine(net, n_slots=4, decode_chunk=2,
                              block_tokens=4, prefix_cache_rows=4)
        return ServingGateway(engine, replica_id=f"kv-{i}",
                              keepalive_s=0.1).start()

    kv_replicas = [paged_replica(0), paged_replica(1)]
    kv_router = ServingRouter(
        [g.address for g in kv_replicas], affinity_block_tokens=4,
        health_interval_s=0.1).start()
    kv_client = RouterClient(kv_router.address)
    while not all(r["kv_capable"] and r["state"] == "live"
                  for r in kv_router.replica_status()):
        time.sleep(0.05)
    warm_prompt = PATTERN[:4] + [PATTERN[4]]
    first = kv_client.generate(warm_prompt, n_gen)
    owner = next(e.replica_address
                 for e in kv_router._journal.values())
    cold_gw = next(g for g in kv_replicas
                   if g._service.address.split("://")[-1] != owner)
    shipped = kv_router.warm_transfer(cold_gw.address,
                                      [warm_prompt[:4]])
    cold_direct = GatewayClient(cold_gw.address).generate(
        warm_prompt, n_gen)
    blocks = cold_gw.engine.stats["kv_imported_blocks"]
    print(f"kv plane : affinity-miss warm import -> "
          f"{shipped['imported']} prefix shipped "
          f"({blocks} block(s), "
          f"{cold_gw.engine.stats['kv_imported_tokens']} tokens) "
          f"from the warm owner")
    print(f"           cold replica admission: "
          f"{cold_direct['prefix_tokens_reused']} prompt tokens "
          f"spliced from the IMPORTED blocks (prefill skipped), "
          f"ids identical across replicas: "
          f"{cold_direct['tokens'] == first['tokens']}")
    kv_router.close()
    for g in kv_replicas:
        g.close()

    # 8. Durable router (ISSUE 15): kill the ROUTER mid-stream,
    # restart it against the same write-ahead journal, resume the
    # client with Last-Event-ID — zero duplicated, zero lost tokens,
    # ids identical to the fault-free reference.
    import tempfile

    wal_path = os.path.join(tempfile.mkdtemp(prefix="router-wal-"),
                            "router.wal")
    wal_replicas = [replica(0), replica(1)]
    wal_addrs = [g.address for g in wal_replicas]

    def wal_router():
        return ServingRouter(
            wal_addrs, affinity_block_tokens=4,
            health_interval_s=0.1, probe_interval_s=0.5,
            failure_threshold=2, journal_path=wal_path).start()

    r1 = wal_router()
    c1 = RouterClient(r1.address)
    n_gen = 24
    reference = c1.generate(PATTERN[:5], n_gen)["tokens"]
    stream = c1.stream(PATTERN[:5], n_gen, resumable=True)
    rid = stream.id
    got = []
    for delta in stream:
        got.extend(delta)
        if len(got) >= 6:
            break  # the crash lands mid-stream
    stream.close()
    # SIGKILL stand-in for the in-process router: the WAL freezes,
    # the HTTP service dies abruptly — no drain, no goodbye (the
    # registered soak does this to a real subprocess with a real
    # SIGKILL: scripts/router_restart_soak.py)
    if r1._wal is not None:
        r1._wal.close()
    r1._stopped = True
    r1._service.hard_stop()
    print(f"durable  : router KILLED with stream {rid} at "
          f"{len(got)}/{n_gen} tokens (WAL "
          f"{os.path.getsize(wal_path)} bytes)")

    r2 = wal_router()  # a fresh process would do exactly this
    c2 = RouterClient(r2.address)
    cursor = len(got)
    resumed = c2.resume(rid, last_event_id=cursor)
    for delta in resumed:
        got.extend(delta)
    recover = next(e for e in r2.tracer.events()
                   if e.get("name") == "router.recover")
    print(f"           restarted router recovered "
          f"{r2.stats['recovered_entries']} entries "
          f"({r2.stats['recovered_open']} open, replayed via the "
          f"PR 9 path), router.recover span on the stitched trace: "
          f"{recover['args']}")
    print(f"           client resumed at Last-Event-ID={cursor} "
          f"-> ids identical across the kill: {got == reference}")
    r2.close()
    for g in wal_replicas:
        g.close()


if __name__ == "__main__":
    main()
