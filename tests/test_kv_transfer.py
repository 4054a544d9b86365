"""KV transfer plane: disaggregated prefill/decode with
cross-replica KV-block shipping + async double-buffered decode rounds
(ISSUE 14 tentpole).

The contract under test: a prefix warmed on one replica can be
exported as a framed binary payload (BlockTable + pool block slices),
imported into any peer — at ANY tensor-parallel width, the wire
format is layout-invariant — and the imported prefix is
indistinguishable from a locally-computed one: the next admission
splices it zero-copy and greedy ids are BIT-IDENTICAL to a local
prefill. Correctness never depends on a transfer: every fault
(truncated payload, geometry mismatch, cold donor) falls back to
full recompute. ``async_rounds=True`` double-buffers ``step()``
dispatch with ids bit-identical to the synchronous engine."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    GatewayClient,
    GatewayError,
    KVTransferError,
    Request,
    RouterClient,
    ServingGateway,
    ServingRouter,
    TenantRegistry,
    TenantSpec,
    pack_prefix,
    unpack_prefix,
)
from deeplearning4j_tpu.serving.kv_transfer import MAGIC
from deeplearning4j_tpu.util.httpjson import HttpService, JsonHandler

V = 12


def _net(seed=7, stream_max_t=64):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _engine(tp=1, **kw):
    kw.setdefault("block_tokens", 8)
    kw.setdefault("prefix_cache_rows", 4)
    return DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                        tp=tp, **kw)


SHARED = [1, 4, 7, 2, 5, 9, 3, 3]
PROMPT = SHARED + [1, 6, 2, 0]
CASES = [(SHARED + [1, 6], 8), (SHARED + [2, 0], 5),
         ([9, 3, 3], 11), (SHARED + [4, 8], 7), ([2, 2], 9)]

_REF = {}


def _reference(prompt, n):
    key = (tuple(prompt), n)
    if key not in _REF:
        eng = _engine()
        rid = eng.submit(Request(list(prompt), n))
        _REF[key] = eng.run()[rid].tokens
    return _REF[key]


_PAYLOADS = {}


def _export_payload(prompt=PROMPT, n=6):
    # cached per (prompt, n): a donor engine costs ~2 s of XLA
    # compile, and a dozen tests only need the bytes
    key = (tuple(prompt), n)
    if key not in _PAYLOADS:
        donor = _engine()
        rid = donor.submit(Request(list(prompt), n))
        donor.run()
        payload = donor.export_kv(prompt)
        assert payload is not None
        _PAYLOADS[key] = payload
    return _PAYLOADS[key]


# -- wire format -------------------------------------------------------
class TestWireFormat:
    def test_round_trip(self):
        pk = np.arange(2 * 8 * 4 * 8, dtype=np.float32).reshape(
            2, 8, 4, 8)
        payload = pack_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9], [0, 1],
                              0, 8, [("0", pk, pk * 2.0)])
        parsed = unpack_prefix(payload)
        h = parsed["header"]
        assert h["tokens"] == [1, 2, 3, 4, 5, 6, 7, 8, 9]
        assert h["blocks"] == [0, 1] and h["floor"] == 0
        out_pk, out_pv = parsed["layers"]["0"]
        np.testing.assert_array_equal(out_pk, pk)
        np.testing.assert_array_equal(out_pv, pk * 2.0)

    @pytest.mark.parametrize("cut", [2, 7, 30, -1, -100])
    def test_truncation_raises(self, cut):
        payload = _export_payload()
        with pytest.raises(KVTransferError):
            unpack_prefix(payload[:cut])

    def test_bad_magic_and_trailing_bytes(self):
        payload = _export_payload()
        with pytest.raises(KVTransferError):
            unpack_prefix(b"XXXX" + payload[len(MAGIC):])
        with pytest.raises(KVTransferError):
            unpack_prefix(payload + b"\0\0")

    def test_noncontiguous_blocks_rejected(self):
        pk = np.zeros((2, 8, 4, 8), np.float32)
        payload = pack_prefix(list(range(1, 10)), [0, 2], 0, 8,
                              [("0", pk, pk)])
        with pytest.raises(KVTransferError):
            unpack_prefix(payload)


# -- engine export / import -------------------------------------------
class TestEngineTransfer:
    def test_import_parity_vs_local(self):
        payload = _export_payload(PROMPT, 6)
        recv = _engine()
        out = recv.import_kv(payload)
        assert out["imported"], out
        rid = recv.submit(Request(list(PROMPT), 6))
        res = recv.run()[rid]
        assert res.tokens == _reference(PROMPT, 6)
        # the splice is real: the imported prefix served the prompt
        assert res.prefix_tokens_reused >= len(PROMPT) - 1
        assert recv.stats["kv_imports"] == 1
        counts = recv.compile_counts()
        assert counts["kv_import"] == 1

    @pytest.mark.slow
    def test_import_whole_workload_parity(self):
        donor = _engine()
        for p, n in CASES:
            donor.submit(Request(list(p), n))
        donor.run()
        recv = _engine()
        shipped = 0
        for p, _n in CASES:
            payload = donor.export_kv(p)
            if payload is not None:
                shipped += int(recv.import_kv(payload)["imported"])
        assert shipped >= 1
        rids = [recv.submit(Request(list(p), n)) for p, n in CASES]
        res = recv.run()
        for rid, (p, n) in zip(rids, CASES):
            assert res[rid].tokens == _reference(p, n)

    def test_export_cold_and_no_trie_none(self):
        eng = _engine()
        assert eng.export_kv(PROMPT) is None  # nothing cached yet
        bare = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        bare.submit(Request(list(PROMPT), 4))
        bare.run()
        assert bare.export_kv(PROMPT) is None  # no trie: no plane

    def test_import_into_engine_without_trie_raises(self):
        payload = _export_payload()
        bare = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        with pytest.raises(KVTransferError, match="no prefix trie"):
            bare.import_kv(payload)

    def test_already_warm_declines(self):
        payload = _export_payload()
        recv = _engine()
        assert recv.import_kv(payload)["imported"]
        out = recv.import_kv(payload)
        assert not out["imported"]
        assert out["reason"] == "already_warm"
        assert recv.stats["kv_import_declined"] == 1

    def test_import_never_preempts_live_slots(self):
        # a pool sized to one slot's worst case: with a live slot
        # holding blocks, the import must decline, not preempt
        recv = _engine(kv_blocks=14, prefix_cache_rows=2)
        rid = recv.submit(Request(list(PROMPT), 40))
        for _ in range(3):
            recv.step()
        assert recv._slots[0] is not None
        payload = _export_payload()
        out = recv.import_kv(payload)
        if not out["imported"]:
            assert out["reason"] in ("no_blocks", "trie_full")
        assert recv.stats["preempted"] == 0
        recv.run()

    def test_geometry_mismatch_raises(self):
        payload = _export_payload()
        recv = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                            seed=0, block_tokens=16,
                            prefix_cache_rows=4)
        with pytest.raises(KVTransferError):
            recv.import_kv(payload)  # block_tokens 8 vs 16

    def test_export_cap_raises_before_gather(self):
        donor = _engine()
        rid = donor.submit(Request(list(PROMPT), 6))
        donor.run()
        from deeplearning4j_tpu.serving.kv_transfer import (
            KVTransferTooLarge,
        )

        with pytest.raises(KVTransferTooLarge):
            donor.export_kv(PROMPT, cap_bytes=64)
        assert donor.export_kv(PROMPT, cap_bytes=1 << 20) is not None

    def test_import_before_any_traffic(self):
        # a freshly booted receiver has no device pool yet: the
        # import bootstraps it through the regular prefill path
        payload = _export_payload(PROMPT, 6)
        recv = _engine()
        assert recv._pool is None
        out = recv.import_kv(payload)
        assert out["imported"], out
        rid = recv.submit(Request(list(PROMPT), 6))
        assert recv.run()[rid].tokens == _reference(PROMPT, 6)


# -- cross-width (TP) import ------------------------------------------
class TestCrossWidthTransfer:
    """ISSUE 14 satellite: a TP=2 donor's head-sliced blocks
    reassemble on export and import at TP=1 (and reverse) with greedy
    ids bit-identical to local prefill — the PR 12 layout-invariant
    host bookkeeping carried onto the wire."""

    def _donor_payload(self, tp):
        donor = _engine(tp=tp)
        rid = donor.submit(Request(list(PROMPT), 6))
        ref = donor.run()[rid].tokens
        assert ref == _reference(PROMPT, 6)
        payload = donor.export_kv(PROMPT)
        assert payload is not None
        return payload

    @pytest.mark.parametrize("donor_tp,recv_tp", [(2, 1), (1, 2)])
    def test_cross_width_parity(self, donor_tp, recv_tp):
        payload = self._donor_payload(donor_tp)
        recv = _engine(tp=recv_tp)
        out = recv.import_kv(payload)
        assert out["imported"], out
        rid = recv.submit(Request(list(PROMPT), 6))
        res = recv.run()[rid]
        assert res.tokens == _reference(PROMPT, 6)
        assert res.prefix_tokens_reused >= len(PROMPT) - 1


# -- async double-buffered rounds -------------------------------------
class TestAsyncRounds:
    @pytest.mark.parametrize("kwargs", [
        dict(),
        dict(block_tokens=8, prefix_cache_rows=4,
             prefill_chunk=4, spec_draft_len=3),
    ])
    def test_bit_parity_and_compile_counts(self, kwargs):
        # (the decode-priority admission policy rides the kv soak's
        # async engines — tier-1 keeps the two extreme configs)
        e_sync = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                              seed=0, **kwargs)
        e_async = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               seed=0, async_rounds=True, **kwargs)
        ids_s = [e_sync.submit(Request(list(p), n)) for p, n in CASES]
        ids_a = [e_async.submit(Request(list(p), n))
                 for p, n in CASES]
        rs, ra = e_sync.run(), e_async.run()
        for i_s, i_a in zip(ids_s, ids_a):
            assert rs[i_s].tokens == ra[i_a].tokens
            assert (rs[i_s].finish_reason
                    == ra[i_a].finish_reason)
        assert e_sync.compile_counts() == e_async.compile_counts()

    def test_sampling_parity(self):
        # async landing must not perturb RNG consumption either
        e_sync = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                              seed=3)
        e_async = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               seed=3, async_rounds=True)
        req = dict(temperature=0.9, top_k=4)
        i_s = e_sync.submit(Request(list(PROMPT), 8, **req))
        i_a = e_async.submit(Request(list(PROMPT), 8, **req))
        assert (e_sync.run()[i_s].tokens
                == e_async.run()[i_a].tokens)

    def test_deltas_and_phase_sums(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           async_rounds=True, emit_deltas=True)
        rid = eng.submit(Request(list(PROMPT), 8))
        res, streamed = {}, []
        while eng.has_work():
            eng.step(res)
            for got_rid, toks in eng.drain_deltas().items():
                assert got_rid == rid
                streamed.extend(toks)
        assert streamed == res[rid].tokens
        timing = res[rid].timing
        phase_sum = (timing["queue_wait_s"] + timing["admission_s"]
                     + timing["decode_s"] + timing["verify_s"]
                     + timing["stall_s"])
        assert phase_sum <= timing["e2e_s"] + 1e-6

    def test_cancel_between_dispatch_and_landing(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           async_rounds=True)
        rid = eng.submit(Request(list(PROMPT), 40))
        other = eng.submit(Request(list(CASES[2][0]), 11))
        eng.step()          # admit + dispatch round 1
        eng.step()          # land 1, dispatch 2
        assert eng._inflight is not None
        assert eng.cancel(rid)     # evict mid-flight
        res = eng.run()
        assert res[rid].finish_reason == "cancelled"
        # the neighbour is untouched by the mid-flight eviction
        assert res[other].tokens == _reference(CASES[2][0], 11)

    def test_snapshot_lands_inflight_round(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           async_rounds=True)
        rid = eng.submit(Request(list(PROMPT), 12))
        eng.step()
        eng.step()
        assert eng._inflight is not None
        snap = eng.snapshot()
        assert eng._inflight is None  # landed by the snapshot
        assert snap["config"]["async_rounds"] is True
        restored = DecodeEngine.restore(_net(), snap)
        assert restored.async_rounds is True
        res = restored.run()
        assert res[rid].tokens == _reference(PROMPT, 12)


# -- bounded binary path (util/httpjson satellite) --------------------
class _BinHandler(JsonHandler):
    def do_POST(self):
        body = self.read_binary(64)
        if body is None:
            return
        self.send_json({"n": len(body)}, 200, close=True)

    def do_GET(self):
        self.send_binary(b"\x00\x01\x02binary")


class TestBoundedBinary:
    @pytest.fixture()
    def service(self):
        svc = HttpService(_BinHandler).start()
        yield svc
        svc.stop()

    def _post(self, svc, body, headers=None):
        import http.client

        conn = http.client.HTTPConnection(svc.host, svc.port,
                                          timeout=5.0)
        try:
            conn.request("POST", "/", body=body,
                         headers=headers or {})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def test_ok_and_cap(self, service):
        status, raw = self._post(service, b"x" * 32)
        assert status == 200 and b'"n": 32' in raw
        status, raw = self._post(service, b"x" * 65)
        assert status == 413 and b"cap" in raw

    def test_missing_length_411(self, service):
        import http.client

        conn = http.client.HTTPConnection(service.host, service.port,
                                          timeout=5.0)
        try:
            # hand-rolled request with no Content-Length
            conn.putrequest("POST", "/", skip_accept_encoding=True)
            conn.endheaders()
            status = conn.getresponse().status
        finally:
            conn.close()
        assert status == 411

    def test_binary_get(self, service):
        import http.client

        conn = http.client.HTTPConnection(service.host, service.port,
                                          timeout=5.0)
        try:
            conn.request("GET", "/")
            resp = conn.getresponse()
            assert resp.status == 200
            assert (resp.getheader("Content-Type")
                    == "application/octet-stream")
            assert resp.read() == b"\x00\x01\x02binary"
        finally:
            conn.close()


# -- gateway endpoints -------------------------------------------------
class TestGatewayEndpoints:
    @pytest.fixture(scope="class")
    def warm_gateway(self):
        eng = _engine()
        gw = ServingGateway(eng, replica_id="warm").start()
        client = GatewayClient(gw.address)
        client.generate(PROMPT, 6)
        yield gw, client
        gw.close()

    def test_export_import_over_http(self, warm_gateway):
        gw, client = warm_gateway
        payload = client.kv_export(PROMPT)
        assert payload is not None
        recv_gw = ServingGateway(_engine(), replica_id="cold",
                                 role="decode").start()
        try:
            recv = GatewayClient(recv_gw.address)
            assert recv.kv_export(PROMPT) is None  # 404 while cold
            out = recv.kv_import(payload)
            assert out["imported"], out
            res = recv.generate(PROMPT, 6)
            assert res["tokens"] == _reference(PROMPT, 6)
            assert res["prefix_tokens_reused"] >= len(PROMPT) - 1
            health = recv.healthz()
            assert health["role"] == "decode"
            assert health["kv_transfer"] is True
        finally:
            recv_gw.close()

    def test_bad_query_400_and_cap_413(self, warm_gateway):
        gw, client = warm_gateway
        import http.client

        conn = http.client.HTTPConnection(gw._service.host,
                                          gw._service.port,
                                          timeout=5.0)
        try:
            conn.request("GET", "/v1/kv/export?tokens=abc")
            assert conn.getresponse().status == 400
        finally:
            conn.close()
        small = ServingGateway(_engine(), kv_transfer_cap_bytes=64
                               ).start()
        try:
            with pytest.raises(GatewayError) as e:
                GatewayClient(small.address).kv_import(b"y" * 100)
            assert e.value.status == 413
            with pytest.raises(GatewayError) as e:
                GatewayClient(small.address).kv_import(MAGIC + b"\0")
            assert e.value.status == 400
        finally:
            small.close()

    def test_gateway_without_trie_404(self):
        bare = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0)
        gw = ServingGateway(bare).start()
        try:
            client = GatewayClient(gw.address)
            client.generate(PROMPT, 4)
            assert client.kv_export(PROMPT) is None
            assert client.healthz()["kv_transfer"] is False
        finally:
            gw.close()

    def test_bad_role_rejected(self):
        with pytest.raises(ValueError):
            ServingGateway(_engine(), role="turbo")


# -- router integration -----------------------------------------------
def _mk_fleet(n=2, roles=None, **router_kw):
    gws = []
    for i in range(n):
        role = (roles or {}).get(i, "any")
        gws.append(ServingGateway(
            _engine(prefill_chunk=4), replica_id=f"r{i}",
            role=role).start())
    router_kw.setdefault("affinity_block_tokens", 8)
    router_kw.setdefault("health_interval_s", 0.05)
    router = ServingRouter([g.address for g in gws],
                           **router_kw).start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = router.replica_status()
        if all(s["kv_capable"] and s["state"] == "live" for s in st):
            break
        time.sleep(0.05)
    return router, gws


class TestRouterTransfer:
    """One shared 2-replica fleet for the three transfer-path tests
    (a fleet costs ~5 s of XLA compile; the tests use disjoint
    affinity keys and delta-based stat assertions, so sharing is
    safe)."""

    @pytest.fixture(scope="class")
    def fleet(self):
        router, gws = _mk_fleet(2)
        yield router, gws
        router.close()
        for g in gws:
            g.close()

    @staticmethod
    def _cold_sibling(router):
        with router._lock:
            owner_addr = [e.replica_address
                          for e in router._journal.values()
                          if e.replica_address][-1]
            return next(r for r in router._replicas
                        if r.address != owner_addr)

    def test_warm_import_on_miss(self, fleet):
        router, gws = fleet
        client = RouterClient(router.address)
        ref = _reference(PROMPT, 6)
        out = client.generate(PROMPT, 6)
        assert out["tokens"] == ref
        # the OTHER replica is cold for the key: force the
        # transfer hook against it (the deterministic stand-in
        # for a bounded-load overflow pick)
        other = self._cold_sibling(router)
        before = router.stats["kv_transfers"]
        entry = router._journal_entry(
            list(PROMPT), {"max_new_tokens": 6})
        router._maybe_kv_transfer(entry, other)
        assert router.stats["kv_transfers"] == before + 1
        assert router.stats["kv_transferred_tokens"] > 0
        # the receiver now serves the prompt warm + bit-identical
        res = GatewayClient(other.address).generate(PROMPT, 6)
        assert res["tokens"] == ref
        assert res["prefix_tokens_reused"] >= len(PROMPT) - 1
        # second call: belief map says warm — no second transfer
        entry2 = router._journal_entry(
            list(PROMPT), {"max_new_tokens": 6})
        router._maybe_kv_transfer(entry2, other)
        assert router.stats["kv_transfers"] == before + 1
        # the transfer is priced on the federated surface
        assert router._kv_transfer_hist.count >= 1
        fleet_text = router.fleet_metrics_text()
        assert "serving_kv_transfer_s_bucket" in fleet_text

    def test_transfer_fault_falls_back_to_recompute(self, fleet):
        router, gws = fleet
        client = RouterClient(router.address)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # its own key
        ref = _reference(prompt, 6)
        assert client.generate(prompt, 6)["tokens"] == ref
        # every transfer payload arrives TRUNCATED from now on
        orig = router._fetch_kv_payload
        router._fetch_kv_payload = (
            lambda donor, p: (orig(donor, p) or b"")[:11] or None)
        try:
            other = self._cold_sibling(router)
            ok_before = router.stats["kv_transfers"]
            entry = router._journal_entry(
                list(prompt), {"max_new_tokens": 6})
            router._maybe_kv_transfer(entry, other)
            assert router.stats["kv_transfers"] == ok_before
            assert router.stats["kv_transfer_failures"] >= 1
            # correctness path: the receiver recomputes identically
            res = GatewayClient(other.address).generate(prompt, 6)
            assert res["tokens"] == ref
        finally:
            router._fetch_kv_payload = orig

    def test_warm_transfer_for_upgrade_warmup(self, fleet):
        router, gws = fleet
        client = RouterClient(router.address)
        prompt = [7, 7, 1, 2, 0, 4, 4, 8, 6, 1]  # its own key
        client.generate(prompt, 6)
        newcomer = ServingGateway(_engine(), replica_id="new").start()
        try:
            out = router.warm_transfer(newcomer.address, [prompt[:8]])
            assert out["imported"] == 1, out
            assert out["cold"] == []
            # the newcomer's cache holds the shipped key
            assert GatewayClient(
                newcomer.address).kv_export(prompt[:8]) is not None
        finally:
            newcomer.close()

    def test_fleet_without_tries_never_transfers(self):
        bare = [ServingGateway(
            DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0),
            replica_id=f"d{i}").start() for i in range(2)]
        router = ServingRouter([g.address for g in bare],
                               affinity_block_tokens=8,
                               health_interval_s=0.05).start()
        try:
            time.sleep(0.3)
            client = RouterClient(router.address)
            assert (client.generate(PROMPT, 6)["tokens"]
                    == _reference(PROMPT, 6))
            entry = router._journal_entry(
                list(PROMPT), {"max_new_tokens": 6})
            with router._lock:
                other = router._replicas[1]
            router._maybe_kv_transfer(entry, other)
            assert router.stats["kv_transfers"] == 0
            assert router.stats["kv_transfer_failures"] == 0
        finally:
            router.close()
            for g in bare:
                g.close()


class TestRoles:
    def _router(self, roles):
        router = ServingRouter(["127.0.0.1:1", "127.0.0.1:2"],
                               affinity_block_tokens=4,
                               health_interval_s=3600.0)
        for r, role in zip(router._replicas, roles):
            r.role = role
            r.n_slots = 4
        return router

    def test_affinity_avoids_prefill_tier(self):
        router = self._router(["prefill", "any"])
        for probe in range(8):
            prompt = [probe % V] * 8
            replica, info = router._pick(prompt, set())
            assert replica.role != "prefill"
            replica.open_entries -= 1

    def test_load_route_avoids_decode_tier(self):
        router = self._router(["decode", "any"])
        for _ in range(8):
            replica, info = router._pick([1, 2], set())
            assert replica.role != "decode"
            replica.open_entries -= 1

    def test_lone_tier_still_serves(self):
        router = self._router(["prefill", "prefill"])
        replica, _ = router._pick([1] * 8, set())
        assert replica is not None


# -- CLI plumbing ------------------------------------------------------
class TestCliKnobs:
    def test_serve_role_and_async_rounds_parse(self):
        from deeplearning4j_tpu.cli.driver import build_parser

        args = build_parser().parse_args(
            ["serve", "--model", "m.zip", "--role", "prefill",
             "--async-rounds"])
        assert args.role == "prefill"
        assert args.async_rounds is True
        args = build_parser().parse_args(
            ["serve", "--model", "m.zip"])
        assert args.role == "any" and args.async_rounds is False
        with pytest.raises(SystemExit):
            build_parser().parse_args(
                ["serve", "--model", "m.zip", "--role", "turbo"])
        # the layout switch went from the CLI with the dense layout
        with pytest.raises(SystemExit):
            build_parser().parse_args(
                ["serve", "--model", "m.zip", "--paged-kv"])

    def test_fleet_child_argv_carries_async_rounds(self):
        from deeplearning4j_tpu.cli.driver import (
            _serve_child_argv,
            build_parser,
        )

        args = build_parser().parse_args(
            ["fleet", "--model", "m.zip", "--async-rounds"])
        argv = _serve_child_argv(args, 9999, "child-0")
        assert "--async-rounds" in argv


# -- per-tenant gauge retirement (ISSUE 14 satellite) -----------------
class TestTenantGaugeRetirement:
    def test_idle_tenant_gauges_retire(self):
        tenants = TenantRegistry([TenantSpec("alpha"),
                                  TenantSpec("beta")])
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           tracer=tracer, tenants=tenants)
        rid = eng.submit(Request(list(PROMPT), 4, tenant="alpha"))
        eng.run()
        text = tracer.prometheus_text()
        assert 'serving_tokens_generated{tenant="alpha"}' in text
        # alpha is idle now: one more emission round retains the
        # closing totals, the next retires the tracks
        assert 'serving_ttft_s{tenant="alpha"}' in str(
            eng._tenant_hists.keys())
        eng._emit_tenant_gauges()
        eng._emit_tenant_gauges()
        text = tracer.prometheus_text()
        assert 'serving_tokens_generated{tenant="alpha"}' not in text
        assert "alpha" not in eng.tenant_stats
        # the labeled HISTOGRAM twins outlive the gauges (operators
        # scrape latency distributions minutes later) but retire on
        # the long idle horizon, bounding a churning population
        assert any('tenant="alpha"' in n for n in eng._tenant_hists)
        eng.TENANT_HIST_RETIRE_ROUNDS = 1
        eng._emit_tenant_gauges()
        eng._emit_tenant_gauges()
        assert not any('tenant="alpha"' in n
                       for n in eng._tenant_hists)
        assert ('serving_ttft_s_bucket{tenant="alpha"'
                not in tracer.prometheus_text())
        # a returning tenant starts fresh tracks
        eng.submit(Request(list(PROMPT), 4, tenant="alpha"))
        eng.run()
        text = tracer.prometheus_text()
        assert 'serving_tokens_generated{tenant="alpha"}' in text

    def test_open_tenant_gauges_survive(self):
        tenants = TenantRegistry([TenantSpec("alpha")])
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           tracer=tracer, tenants=tenants)
        eng.submit(Request(list(PROMPT), 30, tenant="alpha"))
        eng.step()
        eng.step()
        eng._emit_tenant_gauges()
        eng._emit_tenant_gauges()
        assert ('serving_tokens_generated{tenant="alpha"}'
                in tracer.prometheus_text())
        eng.run()

    def test_drop_gauge_unit(self):
        tracer = Tracer()
        tracer.gauge("g_one", 3.0)
        assert "g_one 3" in tracer.prometheus_text()
        assert tracer.drop_gauge("g_one") is True
        assert "g_one" not in tracer.prometheus_text()
        assert tracer.drop_gauge("g_one") is False
