"""Tiered KV cache (ISSUE 17 tentpole): trie victims spill to a
host-DRAM (and disk) LRU of packed DKV1 payloads instead of evicting
to recompute, and a later trie miss reloads them through the jitted
``kv_import`` scatter.

The contract under test: spill/reload is INVISIBLE in ids — greedy
finishes are bit-identical across a full spill→reload cycle on every
engine variant (paged / spec / tp2 / async / fused), with zero new
executables beyond the reused ``kv_gather``/``kv_import`` pow2
buckets (the second cycle compiles NOTHING); the tier's books always
reconcile (spills == reloads + drops + resident); quarantine
invalidations never spill (poisoned state must not be resurrected);
and the HTTP surface grows a ``POST /v1/kv/export`` JSON-body variant
that lifts the 8000-token GET query cap plus a lock-free healthz
``kv_tier`` block the router's donor pick reads."""

import json
import os

import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    GatewayClient,
    GatewayError,
    Request,
    ServingGateway,
)
from deeplearning4j_tpu.serving.kv_tier import KVTierStore, _lcp

V = 12


def _net(seed=7, stream_max_t=64):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _engine(**kw):
    kw.setdefault("block_tokens", 4)
    kw.setdefault("prefix_cache_rows", 4)
    kw.setdefault("kv_host_tier_bytes", 1 << 20)
    return DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                        **kw)


PROMPT = [1, 4, 7, 2, 5, 9, 3, 3, 1, 6]


def _pay(n=100):
    return bytes(n)


# -- KVTierStore unit surface ------------------------------------------
class TestKVTierStore:
    def test_needs_a_budget_or_a_path(self):
        with pytest.raises(ValueError):
            KVTierStore(host_budget_bytes=0, disk_path=None)
        with pytest.raises(ValueError):
            KVTierStore(host_budget_bytes=-1)

    def test_host_lru_budget_sheds_oldest(self):
        st = KVTierStore(host_budget_bytes=250)
        assert st.put([1, 2], _pay()) == "host"
        assert st.put([3, 4], _pay()) == "host"
        # third insert busts the budget: the OLDEST key drops
        assert st.put([5, 6], _pay()) == "host"
        assert st.keys() == [(3, 4), (5, 6)]
        assert st.host_bytes == 200
        assert st.stats["drops"] == 1
        # books: 3 spills == 0 reloads + 1 drop + 2 resident
        assert st.stats["spills"] == 3

    def test_match_refreshes_recency(self):
        st = KVTierStore(host_budget_bytes=250)
        st.put([1, 2], _pay())
        st.put([3, 4], _pay())
        assert st.match([1, 2, 9]) is not None  # touches (1, 2)
        st.put([5, 6], _pay())                  # sheds (3, 4) now
        assert st.keys() == [(1, 2), (5, 6)]

    def test_duplicate_put_is_a_refresh_not_a_spill(self):
        st = KVTierStore(host_budget_bytes=1000)
        st.put([1, 2], _pay())
        st.put([1, 2], _pay())
        assert st.stats["spills"] == 1
        assert st.host_bytes == 100

    def test_oversize_for_every_budget_drops(self):
        st = KVTierStore(host_budget_bytes=50)
        assert st.put([1, 2], _pay(100)) == "dropped"
        assert len(st) == 0
        assert st.stats["spills"] == 1 and st.stats["drops"] == 1

    def test_disk_overflow_and_take_unlinks(self, tmp_path):
        ring = str(tmp_path / "ring")
        st = KVTierStore(host_budget_bytes=150, disk_path=ring)
        st.put([1, 2], _pay())
        st.put([3, 4], _pay())  # demotes (1, 2) to disk
        assert st.stats["demotions"] == 1
        assert len(os.listdir(ring)) == 1
        ent = st.match([1, 2, 9])
        assert ent is not None and ent[2] == "disk"
        assert ent[1] == _pay()
        assert st.take([1, 2])
        assert st.stats["reloads"] == 1
        assert os.listdir(ring) == []
        # books: 2 spills == 1 reload + 0 drops + 1 resident
        assert st.stats["spills"] == 2 and len(st) == 1

    def test_disk_budget_drops_oldest_file(self, tmp_path):
        ring = str(tmp_path / "ring")
        st = KVTierStore(host_budget_bytes=0, disk_path=ring,
                         disk_budget_bytes=250)
        assert st.put([1, 2], _pay()) == "disk"
        st.put([3, 4], _pay())
        st.put([5, 6], _pay())
        assert st.keys() == [(3, 4), (5, 6)]
        assert st.disk_bytes == 200
        assert len(os.listdir(ring)) == 2
        assert st.stats["drops"] == 1
        # a payload over the whole disk budget is refused outright
        assert st.put([7, 8], _pay(300)) == "dropped"

    def test_match_prefers_longest_then_host(self, tmp_path):
        st = KVTierStore(host_budget_bytes=1000,
                         disk_path=str(tmp_path / "r"))
        st.put([1, 2, 3], b"short")
        st._disk_put_locked((1, 2, 3, 4), b"longer-but-disk")
        key, payload, tier = st.match([1, 2, 3, 4, 5])
        assert key == (1, 2, 3, 4) and tier == "disk"
        # at equal usable length the HOST copy wins
        key, _, tier = st.match([1, 2, 3, 9])
        assert key == (1, 2, 3) and tier == "host"

    def test_match_needs_a_usable_prefix(self):
        st = KVTierStore(host_budget_bytes=1000)
        st.put([5, 6, 7], _pay())
        assert st.match([1, 2, 3]) is None      # no shared prefix
        assert st.match([5]) is None            # sub-minimum prompt
        # a stored key's full-prompt match is clamped to len-1 usable
        assert st.match([5, 6, 7])[0] == (5, 6, 7)
        assert st.stats["misses"] == 2

    def test_missing_ring_file_self_heals(self, tmp_path):
        ring = str(tmp_path / "ring")
        st = KVTierStore(host_budget_bytes=0, disk_path=ring)
        st.put([1, 2], _pay())
        for f in os.listdir(ring):
            os.unlink(os.path.join(ring, f))
        assert st.match([1, 2, 3]) is None
        assert len(st) == 0 and st.stats["drops"] == 1
        # books still closed: 1 spill == 0 reloads + 1 drop + 0 left
        assert st.stats["spills"] == 1

    def test_clear_counts_drops_and_health_is_plain(self, tmp_path):
        st = KVTierStore(host_budget_bytes=1000,
                         disk_path=str(tmp_path / "r"))
        st.put([1, 2], _pay())
        st._disk_put_locked((3, 4), _pay())
        h = st.health()
        assert h["entries"] == 2 and h["host_entries"] == 1
        assert h["host_budget_bytes"] == 1000
        json.dumps(h)  # healthz block must be JSON-serializable
        assert st.clear() == 2
        assert st.stats["drops"] == 2 and len(st) == 0
        assert st.host_bytes == 0 and st.disk_bytes == 0

    def test_lcp(self):
        assert _lcp((1, 2, 3), (1, 2, 9)) == 2
        assert _lcp((), (1,)) == 0
        assert _lcp((1, 2), (1, 2)) == 2


# -- engine spill -> reload matrix -------------------------------------
def _drain_all(eng):
    while eng.prefix_cache.evict_one():
        pass
    eng.drain_spills()


class TestSpillReloadMatrix:
    """Greedy ids bit-identical across spill→reload on every engine
    variant, with compile-count gates: cycle 1 may compile only the
    ``kv_import``/``kv_gather`` pow2 buckets (the executables the
    cross-replica transfer plane already owns), cycle 2 compiles
    NOTHING — the zero-retrace proof."""

    @pytest.mark.parametrize("kw", [
        {},
        {"spec_draft_len": 2},
        {"tp": 2},
        {"async_rounds": True},
        {"fused_rounds": 2},
    ], ids=["paged", "spec", "tp2", "async", "fused"])
    def test_bit_identical_and_zero_retrace(self, kw):
        eng = _engine(**kw)
        rid = eng.submit(Request(list(PROMPT), 6))
        ref = eng.run()[rid].tokens          # cold compute: reference

        # warm the warm-splice executables (continuation-chunk
        # prefill bucket, CoW copy) through a NORMAL trie re-hit, so
        # the reload cycles below prove tier-specific compiles only
        rid = eng.submit(Request(list(PROMPT), 6))
        assert eng.run()[rid].tokens == ref

        for cycle, allowed in ((1, {"kv_import", "kv_gather"}),
                               (2, set())):
            _drain_all(eng)
            assert len(eng.kv_tier) > 0, eng.kv_tier.stats
            before = eng.compile_counts()
            reloads0 = eng.kv_tier.stats["reloads"]
            rid = eng.submit(Request(list(PROMPT), 6))
            out = eng.run()[rid].tokens
            after = eng.compile_counts()
            assert out == ref, (
                f"cycle {cycle} ({kw}): reload diverged")
            assert eng.kv_tier.stats["reloads"] == reloads0 + 1, (
                f"cycle {cycle}: no tier reload happened "
                f"({eng.kv_tier.stats})")
            delta = {k for k in after
                     if after[k] != before.get(k, 0)}
            assert delta <= allowed, (
                f"cycle {cycle} retraced {delta - allowed}: "
                f"{before} -> {after}")
        s = eng.kv_tier.stats
        assert s["spills"] == (s["reloads"] + s["drops"]
                               + len(eng.kv_tier)), s

    def test_disk_tier_reload(self, tmp_path):
        """host budget 0 → every spill goes straight to the ring;
        the reload path reads the file back bit-identically."""
        eng = _engine(kv_host_tier_bytes=0,
                      kv_disk_tier_path=str(tmp_path / "ring"))
        rid = eng.submit(Request(list(PROMPT), 6))
        ref = eng.run()[rid].tokens
        _drain_all(eng)
        assert eng.kv_tier.health()["disk_entries"] > 0
        rid = eng.submit(Request(list(PROMPT), 6))
        assert eng.run()[rid].tokens == ref
        assert eng.kv_tier.stats["hits_disk"] >= 1
        assert eng.kv_tier.stats["reloads"] >= 1


# -- engine surface ----------------------------------------------------
class TestEngineSurface:
    def test_tier_requires_paged_trie(self):
        with pytest.raises(ValueError):
            DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                         kv_host_tier_bytes=1 << 20)
        with pytest.raises(ValueError):
            _engine(prefix_cache_rows=0)

    def test_quarantine_invalidate_never_spills(self):
        eng = _engine()
        rid = eng.submit(Request(list(PROMPT), 6))
        eng.run()
        assert eng.prefix_cache.stored_rows()
        for row in list(eng.prefix_cache.stored_rows()):
            assert eng.prefix_cache.invalidate_row(row)
        eng.drain_spills()
        assert len(eng.kv_tier) == 0, (
            "a quarantine invalidation spilled — poisoned state "
            "must never be resurrectable from the tier")
        assert eng.kv_tier.stats["spills"] == 0

    def test_export_falls_through_to_tier(self):
        """A trie-cold engine whose tier holds the prefix still
        serves exports — the payload a peer imports bit-identically
        (the router's tier-warm donor pick depends on this)."""
        donor = _engine()
        rid = donor.submit(Request(list(PROMPT), 6))
        ref = donor.run()[rid].tokens
        _drain_all(donor)
        payload = donor.export_kv(PROMPT)
        assert payload is not None
        assert donor.stats["kv_tier_exports"] == 1
        # the export is read-only: the payload stays tier-resident
        assert len(donor.kv_tier) > 0
        recv = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                            seed=0, block_tokens=4,
                            prefix_cache_rows=4)
        out = recv.import_kv(payload)
        assert out["imported"], out
        rid = recv.submit(Request(list(PROMPT), 6))
        assert recv.run()[rid].tokens == ref

    def test_export_tier_cap_413_shape(self):
        from deeplearning4j_tpu.serving.kv_transfer import (
            KVTransferTooLarge,
        )

        eng = _engine()
        eng.submit(Request(list(PROMPT), 6))
        eng.run()
        _drain_all(eng)
        with pytest.raises(KVTransferTooLarge):
            eng.export_kv(PROMPT, cap_bytes=16)

    def test_snapshot_records_knobs_not_payloads(self, tmp_path):
        eng = _engine(kv_disk_tier_path=str(tmp_path / "ring"),
                      kv_disk_tier_bytes=1 << 22)
        rid = eng.submit(Request(list(PROMPT), 6))
        ref = eng.run()[rid].tokens
        _drain_all(eng)
        snap = eng.snapshot()
        cfg = snap["config"]
        assert cfg["kv_host_tier_bytes"] == 1 << 20
        assert cfg["kv_disk_tier_path"] == str(tmp_path / "ring")
        assert cfg["kv_disk_tier_bytes"] == 1 << 22
        assert "kv_tier" not in snap  # payloads are droppable cache
        json.dumps(snap)
        eng2 = DecodeEngine.restore(_net(), snap)
        assert eng2.kv_tier is not None
        assert eng2.kv_tier.host_budget_bytes == 1 << 20
        assert len(eng2.kv_tier) == 0  # contents did NOT ride along
        rid = eng2.submit(Request(list(PROMPT), 6))
        assert eng2.run()[rid].tokens == ref

    def test_spill_cap_bounds_staging(self):
        eng = _engine()
        eng.submit(Request(list(PROMPT), 6))
        eng.run()
        # saturate the staging list, then force one more eviction
        eng._pending_spills = [None] * eng.MAX_PENDING_SPILLS
        skipped0 = eng.stats["kv_tier_spill_skipped"]
        assert eng.prefix_cache.evict_one()
        assert eng.stats["kv_tier_spill_skipped"] == skipped0 + 1
        eng._pending_spills = []


# -- HTTP surface ------------------------------------------------------
class TestGatewayTier:
    @pytest.fixture(scope="class")
    def warm_gateway(self):
        gw = ServingGateway(_engine(), replica_id="tiered").start()
        client = GatewayClient(gw.address)
        client.generate(PROMPT, 6)
        yield gw, client
        gw.close()

    def test_healthz_tier_block(self, warm_gateway):
        gw, client = warm_gateway
        h = client.healthz()
        tier = h["kv_tier"]
        assert tier is not None
        assert tier["host_budget_bytes"] == 1 << 20
        assert set(tier) >= {"entries", "host_bytes", "spills",
                             "reloads", "drops"}

    def test_healthz_tier_none_when_off(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           seed=0, block_tokens=4,
                           prefix_cache_rows=4)
        gw = ServingGateway(eng).start()
        try:
            assert GatewayClient(gw.address).healthz()[
                "kv_tier"] is None
        finally:
            gw.close()

    def test_post_export_matches_get(self, warm_gateway):
        gw, client = warm_gateway
        via_get = client.kv_export(PROMPT)
        assert via_get is not None
        via_post = client._kv_export_post(PROMPT)
        assert via_post == via_get

    def test_post_export_bad_bodies_400(self, warm_gateway):
        gw, client = warm_gateway
        import http.client

        for body in (b"{not json", b"{}", b'{"tokens": []}',
                     b'{"tokens": "1,2,3"}', b'{"tokens": [1, "a"]}'):
            conn = http.client.HTTPConnection(gw._service.host,
                                              gw._service.port,
                                              timeout=5.0)
            try:
                conn.request(
                    "POST", "/v1/kv/export", body=body,
                    headers={"Content-Type": "application/json",
                             "Content-Length": str(len(body))})
                assert conn.getresponse().status == 400, body
            finally:
                conn.close()

    def test_long_prompt_routes_via_post(self, warm_gateway,
                                         monkeypatch):
        """The 8000-token GET cap (PR 14 known fact) is lifted: a
        prompt past the cap ships its FULL token list in the POST
        body — no truncation. Proven by shrinking the cap below the
        prompt length and checking the untruncated export still
        returns the full payload the GET form yields."""
        gw, client = warm_gateway
        ref = client.kv_export(PROMPT)
        monkeypatch.setattr(GatewayClient, "KV_EXPORT_QUERY_TOKENS",
                            4)
        assert client.kv_export(PROMPT) == ref

    def test_post_export_404_when_cold(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           seed=0, block_tokens=4,
                           prefix_cache_rows=4)
        gw = ServingGateway(eng).start()
        try:
            with pytest.raises(GatewayError) as e:
                GatewayClient(gw.address)._kv_export_post(PROMPT)
            assert e.value.status == 404
        finally:
            gw.close()
