"""Tensor-parallel sharded decode engine + fused pallas
paged-attention kernel (ISSUE 12 tentpole).

The contract under test: ``DecodeEngine(tp=N)`` turns the
decode/verify/chunk executables into fully-manual ``shard_map``
programs over a TP mesh axis — attention params column/row-sliced over
heads, every KV leaf sharded on its head axis (per-shard bytes =
total/TP) — while the HOST side (block ids, refcounts, CoW, the radix
trie, the snapshot wire format) stays layout-invariant. Greedy ids are
BIT-IDENTICAL to the single-chip engine at every TP width, across
admission modes x paged on/off x spec on/off, at the single-chip
compile budget; a snapshot taken at TP=2 restores at TP=1. The pallas
paged-attention kernel (interpret mode on CPU) is argmax-bit-parity
with the XLA gather program and preserves the PR 6 value-level NaN
masking.

Engines are BUILT ONCE per config in a module-scoped rig (each build
compiles a shard_map program set — the expensive part) and shared by
the parity/sharding/retrace/byte tests; tier-1 wall time is budgeted.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.layers.attention import (
    AttentionImpl,
    MultiHeadSelfAttention,
    _PAGED_Q_TILE,
    _paged_blocks_per_step,
    _should_use_flash_paged,
    paged_steps_paid,
    paged_walk_counts,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    GatewayClient,
    Request,
    ServingGateway,
    TPContext,
)

V = 12


def _net(seed=7, stream_max_t=64, compute_dtype=None):
    conf = transformer_lm(n_in=V, width=32, n_layers=2, n_heads=4,
                          n_classes=V, seed=seed)
    for c in conf.confs:
        c.compute_dtype = compute_dtype
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return MultiLayerNetwork(conf).init()


# shared-prefix workload: splice + CoW + cold admissions under TP
SHARED = [1, 4, 7, 2, 5, 9, 3, 3]
CASES = [(SHARED + [1, 6], 8), (SHARED + [2, 0], 5),
         ([9, 3, 3], 11), ([2, 2], 9)]


def _submit_run(eng):
    ids = [eng.submit(Request(list(p), n)) for p, n in CASES]
    res = eng.run()
    return {r: res[r].tokens for r in ids}


@pytest.fixture(scope="module")
def rig():
    """Build-once engine cache keyed by config; every engine has run
    the shared workload once (warm — compile counts are frozen)."""
    cache = {}

    def get(tp=1, spec=0, prefill_chunk=0, policy="ttft",
            use_flash_paged=None, compute_dtype=None):
        key = (tp, spec, prefill_chunk, policy, use_flash_paged,
               compute_dtype)
        if key not in cache:
            eng = DecodeEngine(
                _net(compute_dtype=compute_dtype), n_slots=2,
                decode_chunk=2, seed=0,
                prefix_cache_rows=4, block_tokens=8,
                spec_draft_len=spec, prefill_chunk=prefill_chunk,
                admission_policy=policy, tp=tp,
                use_flash_paged=use_flash_paged)
            cache[key] = (eng, _submit_run(eng))
        return cache[key]

    return get


class TestTpParityMatrix:
    """Acceptance gate: greedy bit-parity vs the single-chip engine
    across TP width x spec x admission mode."""

    @pytest.mark.parametrize("spec,prefill_chunk,policy", [
        (0, 0, "ttft"),             # blocking admission
        (3, 4, "decode"),           # spec + chunked
    ])
    def test_tp2_bit_parity(self, rig, spec, prefill_chunk, policy):
        _, ref = rig(1, spec, prefill_chunk, policy)
        eng, got = rig(2, spec, prefill_chunk, policy)
        assert got == ref
        assert eng.tp == 2 and eng.tp_ctx is not None

    @pytest.mark.slow
    @pytest.mark.parametrize("spec,prefill_chunk,policy", [
        (0, 0, "decode"), (3, 4, "ttft")])
    def test_tp2_bit_parity_full_matrix(self, rig, spec,
                                        prefill_chunk, policy):
        """The remaining admission-mode combinations (slow
        tier: tier-1 keeps the two structurally distinct corners
        above within the wall-time budget)."""
        _, ref = rig(1, spec, prefill_chunk, policy)
        _, got = rig(2, spec, prefill_chunk, policy)
        assert got == ref

    def test_tp4_bit_parity_paged_spec(self, rig):
        _, ref = rig(1, 3, 4, "decode")
        _, got = rig(4, 3, 4, "decode")
        assert got == ref

    def test_tp_width_validation(self):
        with pytest.raises(ValueError, match="does not divide"):
            DecodeEngine(_net(), tp=3)  # 4 heads % 3
        with pytest.raises(ValueError, match="tp 0"):
            DecodeEngine(_net(), tp=0)
        # width past the visible devices fails in TPContext (the
        # engine's heads check fires first at non-dividing widths)
        with pytest.raises(ValueError, match="exceeds"):
            TPContext(16, ["0"])


class TestTpCompileDiscipline:
    """The sharded engine holds the SINGLE-CHIP compile budget: one
    decode, one scatter, one paged tok — per TP width — and a warmed
    engine never retraces."""

    @pytest.mark.parametrize("tp", [2, 4])
    def test_no_retrace_and_budget(self, assert_no_retrace, rig, tp):
        eng, first = rig(tp, 3 if tp == 4 else 0,
                         4 if tp == 4 else 0,
                         "decode" if tp == 4 else "ttft")
        # a second pass admits through the now-warm prefix trie — the
        # paged engine's SECOND legitimate chunk_prefill variant (the
        # PR 6 budget: cold accumulation + paged warm continuation)
        _submit_run(eng)
        counts = eng.compile_counts()
        # the PR 6 paged budget, unchanged by sharding
        assert counts["decode"] == 1, counts
        assert counts["paged_scatter"] == 1, counts
        assert counts["paged_tok"] == 1, counts
        assert counts["chunk_prefill"] <= 2, counts
        with assert_no_retrace(eng):
            again = _submit_run(eng)
        assert list(again.values()) == list(first.values())

    @pytest.mark.parametrize("tp", [1, 2])
    def test_plain_and_spec_rounds_share_one_decode(self, rig, tp):
        """ISSUE 28: a plain round hands the decode program the tables
        the host just uploaded (committed replicated under tp), a
        spec round the verify program's output: one lowering takes
        both, and each round made one upload."""
        eng, _ = rig(tp, 3, 4, "decode")
        assert eng.stats["spec_rounds"] > 0
        assert eng.stats["spec_fallback_rounds"] > 0
        counts = eng.compile_counts()
        assert counts["decode"] == 1, counts
        assert 1 <= counts["verify"] <= 3, counts
        assert eng.stats["table_uploads"] >= eng.stats["chunks"]
        tables = eng.kv.pack(eng._kv_tabs)
        assert tables.shape == (2, 2 * eng.kv.kinds[0].ring + 2)
        assert len(tables.sharding.device_set) == tp
        assert tables.sharding.is_fully_replicated


class TestTpSharding:
    """Device-side acceptance: per-shard KV bytes == total/TP, every
    cache leaf actually sharded on its head axis."""

    def test_per_shard_kv_bytes_total_over_tp(self, rig):
        eng1, _ = rig(1)
        total = sum(eng1.kv_shard_bytes().values())
        for tp in (2, 4):
            eng, _ = rig(tp, 3 if tp == 4 else 0,
                         4 if tp == 4 else 0,
                         "decode" if tp == 4 else "ttft")
            per = eng.kv_shard_bytes()
            assert len(per) == tp
            assert all(b == total // tp for b in per.values()), (
                total, per)

    def test_pool_leaves_sharded_on_head_axis(self, rig):
        eng, _ = rig(2)
        for st in eng._pool.values():
            for leaf in (st["pk"], st["pv"]):
                spec = leaf.sharding.spec
                assert "tp" in spec, spec      # head axis (index 2)
                assert spec.index("tp") == 2
        # a cold admission's dense row [1, H, W, dh]: its head axis
        row = jnp.zeros((1, 4, 64, 8))
        for leaf in ("k", "v"):
            spec = eng.tp_ctx._leaf_spec(
                (jax.tree_util.DictKey("0"),
                 jax.tree_util.DictKey(leaf)), row)
            assert spec.index("tp") == 1

    def test_params_head_sliced(self, rig):
        eng, _ = rig(2)
        for layer in eng._params.values():
            if "Wq" not in layer:
                continue
            assert layer["Wq"].sharding.spec.index("tp") == 1
            assert layer["Wo"].sharding.spec.index("tp") == 0

    def test_spec_normalization_no_trailing_none(self):
        """P(None, None, 'tp', None) and P(None, None, 'tp') hash as
        different jit keys — the context must emit the normalized
        form or the first decode after a scatter retraces (the spike
        this caught)."""
        ctx = TPContext(2, ["0"])
        leaf = jnp.zeros((4, 8, 4, 8))
        spec = ctx._leaf_spec(
            (jax.tree_util.DictKey("0"), jax.tree_util.DictKey("pk")),
            leaf)
        assert tuple(spec) == (None, None, "tp")

    def test_tp_context_validation(self):
        with pytest.raises(ValueError, match="exceeds"):
            TPContext(99, ["0"])
        with pytest.raises(ValueError, match="tp 0"):
            TPContext(0, ["0"])


class TestSnapshotLayoutInvariance:
    """Satellite: the snapshot wire format never sees the head axis —
    a snapshot taken at TP=2 restores at TP=1 (and vice versa),
    finishing bit-identically."""

    def _crash_restore(self, snap_tp, restore_tp, rig):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefix_cache_rows=4, block_tokens=8, tp=snap_tp)
        for p, n in CASES:
            eng.submit(Request(list(p), n))
        res = {}
        eng.step(res)
        eng.step(res)
        snap = json.loads(json.dumps(eng.snapshot()))
        assert snap["config"]["tp"] == snap_tp
        restored = DecodeEngine.restore(_net(), snap, tp=restore_tp)
        assert restored.tp == restore_tp
        out = dict(res)
        out.update(restored.run())
        got = {r: t.tokens for r, t in out.items()}
        assert got == rig(1)[1]

    def test_tp2_snapshot_restores_at_tp1(self, rig):
        self._crash_restore(2, 1, rig)

    @pytest.mark.slow
    def test_tp1_snapshot_restores_at_tp2(self, rig):
        self._crash_restore(1, 2, rig)

    def test_restore_defaults_to_snapshot_width(self):
        eng = DecodeEngine(_net(), n_slots=2, tp=2)
        snap = eng.snapshot()
        assert DecodeEngine.restore(_net(), snap).tp == 2


class TestPagedFlashKernel:
    """The pallas paged-attention kernel (interpret mode = the CPU
    parity hook) vs the XLA gather program."""

    @pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
    def test_kernel_bit_parity_sharded(self, rig, compute_dtype):
        """(bfloat16: float32 masters, cast once at construction; the
        tp engine places the cast tree, head-sliced, not the masters)"""
        one, ref = rig(1, compute_dtype=compute_dtype)
        eng, got = rig(2, use_flash_paged="interpret",
                       compute_dtype=compute_dtype)
        assert got == ref
        cast = eng.stats["param_bytes_cast"]
        assert cast == one.stats["param_bytes_cast"]
        assert (cast > 0) == (compute_dtype is not None)
        assert eng.stats["param_bytes"] == one.stats["param_bytes"]
        if compute_dtype:
            wq = eng._params["0"]["Wq"]
            assert wq.dtype == jnp.bfloat16
            assert wq.addressable_shards[0].data.shape[1] * 2 \
                == wq.shape[1]
            assert eng.net.params["0"]["Wq"].dtype == jnp.bfloat16

    def test_kernel_bit_parity_spec_chunked(self, rig):
        _, ref = rig(1, 3, 4, "decode")
        _, got = rig(1, 3, 4, "decode",
                     use_flash_paged="interpret")
        assert got == ref

    def test_auto_mode_fallback_off_tpu(self):
        """None = auto selects the XLA gather off-TPU; True raises
        rather than silently degrading; False is always the gather."""
        assert not _should_use_flash_paged(None, 16, 128)
        assert not _should_use_flash_paged(False, 16, 128)
        assert _should_use_flash_paged("interpret", 2, 8)
        with pytest.raises(ValueError, match="TPU backend"):
            _should_use_flash_paged(True, 16, 128)

    def test_kernel_value_level_nan_masking(self):
        """The PR 6 poisoned-neighbour fix holds INSIDE the kernel: a
        NaN in an unmapped/out-of-span pool block must not reach the
        output (0 x NaN = NaN would survive score-only masking)."""
        lc = MultiHeadSelfAttention(n_in=8, n_out=8, n_heads=2,
                                    stream_max_t=16)
        b, h, t, dh, nb, bt, s_ring = 1, 2, 2, 4, 6, 4, 8
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, h, t, dh))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, dh))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, dh))
        pool_k = jax.random.normal(jax.random.PRNGKey(3),
                                   (nb, bt, h, dh))
        pool_v = jax.random.normal(jax.random.PRNGKey(4),
                                   (nb, bt, h, dh))
        # block 5 is FREE and dirty with NaN (eviction never scrubs)
        pool_v = pool_v.at[5].set(jnp.nan)
        pool_k = pool_k.at[5].set(jnp.nan)
        table = np.full((b, s_ring), -1, np.int32)
        base = np.full((b, s_ring), -1, np.int32)
        # logical blocks 0..2 mapped; row has 9 tokens, writes 2 more
        for g, bid in ((0, 1), (1, 2), (2, 3)):
            table[0, g % s_ring] = bid
            base[0, g % s_ring] = g * bt
        cache = {"pk": pool_k, "pv": pool_v,
                 "table": jnp.asarray(table),
                 "base": jnp.asarray(base),
                 "floor": jnp.zeros((b,), jnp.int32),
                 "filled": jnp.full((b,), 9, jnp.int32)}
        outs = {}
        for toggle in (False, "interpret"):
            lc.use_flash_paged = toggle
            o, _ = AttentionImpl._paged_attend(lc, q, k, v,
                                               dict(cache))
            outs[toggle] = np.asarray(o)
        assert np.isfinite(outs["interpret"]).all(), (
            "NaN leaked through the kernel's masked lanes")
        np.testing.assert_allclose(outs["interpret"], outs[False],
                                   rtol=2e-5, atol=2e-5)


    # ntab = min(s_ring, (tm + t - 2) // bt + 2) is tied to the window
    # and the chunk, so the 9-entry walk (the rehearsal's) exists for
    # short chunks only; 129 is the serving cell's, 130 the flagship's.
    # ``cap`` bounds the compute block so that no ntab is a multiple.
    # ``rows`` names the tables (``_ragged_rows``): what the bounds of
    # the kernel's loop over a row's compute blocks have to get right
    RAGGED = [
        (9, 4, 1, "mixed"), (9, 4, 5, "mixed"),
        (129, 16, 1, "mixed"), (129, 16, 5, "mixed"),
        (129, 16, 128, "mixed"), (129, 16, 256, "mixed"),
        (130, 8, 1, "mixed"), (130, 8, 5, "mixed"),
        (130, 8, 128, "mixed"), (130, 8, 256, "mixed"),
        (9, 4, 1, "idle"), (9, 4, 4, "idle"),
        (17, 4, 1, "first"), (17, 4, 1, "last"), (17, 4, 8, "last"),
        (17, 4, 1, "bottom"), (17, 4, 4, "bottom"),
        (17, 4, 1, "gap"), (17, 4, 8, "gap"),
        (17, 4, 1, "full"), (17, 4, 8, "full"),
        (17, 4, 4, "cross"), (17, 4, 8, "cross"),
        (41, 4, 256, "tiles")]

    @staticmethod
    def _ragged_rows(rows, tm, bt, t, p_blk):
        """``filled``, ``floor``, the live rows, and the (row, logical
        block) mappings to take away again, for a named set of tables:

        - ``mixed``: idle rows between live ones, a slid window, a
          raised floor inside a block, a short row, a hole (an unmapped
          entry between mapped ones);
        - ``idle``: no live row at all;
        - ``first`` / ``last``: one live row, in the first / the last
          slot, every other idle;
        - ``bottom``: a slid window whose oldest compute block (and one
          entry more) is unmapped, so the walk starts above block 0;
        - ``gap``: a whole unmapped compute block between live ones;
        - ``full``: a row whose walk ends in the table's last entry;
        - ``cross``: chunks that cross a pool block's and a compute
          block's boundary;
        - ``tiles``: two query tiles that reach different compute
          blocks of one slid row, beside a row below its own floor."""
        span = p_blk * bt
        if rows == "mixed":
            filled = [0, tm // 2, 0, tm + 3 * bt + 5, 3 * bt + tm // 3,
                      0, 3]
            floor = [0, 0, 0, 0, 2 * bt + 3, 0, 0]
            return filled, floor, [1, 3, 4, 6], [
                (1, (filled[1] // bt) // 2)]
        if rows == "idle":
            return [0, 0, 0], [0, 0, 0], [], []
        if rows in ("first", "last"):
            filled = [0, 0, 0, 0]
            filled[0 if rows == "first" else -1] = tm // 2 + 1
            return filled, [0] * 4, [0 if rows == "first" else 3], []
        if rows == "bottom":
            filled = [0, tm + 2 * span + 3, 0]
            lo = (filled[1] - tm + 1) // bt
            return filled, [0] * 3, [1], [
                (1, lo + e) for e in range(p_blk + 1)]
        if rows == "gap":
            filled = [3 * span + 2, 0, 3 * span + bt]
            return filled, [0] * 3, [0, 2], [
                (0, p_blk + e) for e in range(p_blk)] + [
                (2, 2 * p_blk + e) for e in range(p_blk)]
        if rows == "full":
            ntab = (tm + t - 2) // bt + 2
            top = next(f for f in range(tm, tm + 2 * bt)
                       if (f + t - 1) // bt - (f - tm + 1) // bt
                       == ntab - 1)
            return [0, top, tm // 3], [0] * 3, [1, 2], []
        if rows == "cross":
            return ([2 * bt - 2, 0, span - 2, 2 * span - 1], [0] * 4,
                    [0, 2, 3], [])
        assert rows == "tiles"
        return ([tm + span + 3, 0, 5], [0, 0, 5 + 2 * _PAGED_Q_TILE],
                [0, 2], [])

    def _ragged_case(self, monkeypatch, ntab, cap, t, rows):
        """The layer, its operands and the live rows of one RAGGED
        case: NaN in every pool position no row has written (free
        blocks, a tail block past its row's length, the floor block
        below the floor, a block that lost its mapping)."""
        from deeplearning4j_tpu.nn.layers import attention as att

        monkeypatch.setattr(att, "_PAGED_MAX_BLOCKS", cap)
        h, dh, bt, nb = 2, 8, 8, 640
        tm = (ntab - 2) * bt - t + 2 + 3     # (tm + t - 2) // bt + 2
        s_ring = 2 * ntab + 3
        assert min(s_ring, (tm + t - 2) // bt + 2) == ntab
        p_blk = att._paged_blocks_per_step(bt, h, dh, jnp.float32, ntab)
        assert ntab % p_blk
        lc = MultiHeadSelfAttention(n_in=h * dh, n_out=h * dh,
                                    n_heads=h, stream_max_t=tm)
        rng = np.random.default_rng([ntab, t])
        filled, floor, live, holes = self._ragged_rows(
            rows, tm, bt, t, p_blk)
        b = len(filled)
        lens = rng.integers(1, t + 1, b)
        lens[live[:1]] = t
        table = np.full((b, s_ring), -1, np.int32)
        base = np.full((b, s_ring), -1, np.int32)
        pk = np.full((nb, bt, h, dh), np.nan, np.float32)
        pv = np.full((nb, bt, h, dh), np.nan, np.float32)
        free = list(rng.permutation(nb))
        for r in live:
            lo = max(floor[r], filled[r] - tm + 1, 0) // bt
            for g in range(lo, (filled[r] + t - 1) // bt + 1):
                bid = free.pop()
                table[r, g % s_ring], base[r, g % s_ring] = bid, g * bt
                pos = g * bt + np.arange(bt)
                held = (pos >= floor[r]) & (pos < filled[r])
                if (r, g) in holes:
                    continue
                pk[bid, held] = rng.normal(size=(held.sum(), h, dh))
                pv[bid, held] = rng.normal(size=(held.sum(), h, dh))
        for r, g in holes:
            assert table[r, g % s_ring] >= 0, (r, g)
            table[r, g % s_ring] = base[r, g % s_ring] = -1
        q, k, v = (jnp.asarray(rng.normal(size=(b, h, t, dh)),
                               jnp.float32) for _ in range(3))
        mask = (None if t == 1 else jnp.asarray(
            np.arange(t)[None] < lens[:, None], jnp.float32))
        cache = {"pk": jnp.asarray(pk), "pv": jnp.asarray(pv),
                 "table": jnp.asarray(table), "base": jnp.asarray(base),
                 "floor": jnp.asarray(floor, jnp.int32),
                 "filled": jnp.asarray(filled, jnp.int32)}
        return lc, (q, k, v), cache, mask, live, p_blk

    @pytest.mark.parametrize("ntab,cap,t,rows", RAGGED)
    def test_kernel_parity_on_ragged_tables(self, monkeypatch, ntab,
                                            cap, t, rows):
        """Interpret parity with the gather program where the walk is
        not whole compute blocks and its ends are not the table's
        (``_ragged_rows``), finite whatever the unwritten pool holds."""
        lc, qkv, cache, mask, live, _ = self._ragged_case(
            monkeypatch, ntab, cap, t, rows)
        outs = {}
        for toggle in (False, "interpret"):
            lc.use_flash_paged = toggle
            o, _ = AttentionImpl._paged_attend(lc, *qkv, dict(cache),
                                               mask)
            o = np.asarray(o)
            if mask is not None:
                # pad queries are never read (and may see the NaN
                # their own unwritten chunk positions still hold)
                o = np.where(np.asarray(mask)[:, None, :, None] > 0,
                             o, 0.0)
            outs[toggle] = o
        assert np.isfinite(outs["interpret"]).all(), (
            "NaN leaked through the kernel's masked lanes")
        if live:
            assert np.abs(outs[False][live]).min(axis=(1, 2, 3)
                                                 ).max() > 0
        idle = sorted(set(range(len(outs[False]))) - set(live))
        assert not outs["interpret"][idle].any()
        np.testing.assert_allclose(outs["interpret"], outs[False],
                                   rtol=5e-5, atol=5e-5)

    @pytest.mark.parametrize("ntab,cap,t,rows", RAGGED)
    def test_steps_paid_match_a_brute_force_count(self, monkeypatch,
                                                  ntab, cap, t, rows):
        """``paged_steps_paid`` is the kernel's grid plus, for every
        (row, query tile), the compute blocks between the first and
        the last table entry that is inside the tile's reach and inside
        the row's mapped span: counted here entry by entry. The steps
        that score keys (``walked`` over the compute block) are among
        them."""
        lc, _, cache, _, live, p_blk = self._ragged_case(
            monkeypatch, ntab, cap, t, rows)
        table, base, floor, filled = (np.asarray(cache[k]) for k in (
            "table", "base", "floor", "filled"))
        bt, tm, s_ring = cache["pk"].shape[1], lc.stream_max_t, table.shape[1]
        tq = t if t % _PAGED_Q_TILE else _PAGED_Q_TILE
        want = scoring = 0
        for r in range(len(filled)):
            lo_blk = max(floor[r], filled[r] - tm + 1, 0) // bt
            mapped = [e for e in range(ntab)
                      if table[r, (lo_blk + e) % s_ring] >= 0
                      and base[r, (lo_blk + e) % s_ring]
                      == (lo_blk + e) * bt]
            for i in range(t // tq):
                q0 = filled[r] + i * tq
                reached = [e for e in range(ntab)
                           if (lo_blk + e + 1) * bt - 1 > q0 - tm
                           and (lo_blk + e) * bt <= q0 + tq - 1]
                want += 1                              # the grid step
                if not (mapped and reached):
                    continue
                lo = max(mapped[0], reached[0])
                hi = min(mapped[-1], reached[-1])
                want += len({e // p_blk for e in range(lo, hi + 1)})
                scoring += len({e // p_blk for e in mapped
                                if e in reached})
        geometry = dict(block_tokens=bt, window=tm,
                        blocks_per_step=p_blk, chunk=t)
        paid = paged_steps_paid(table, base, floor, filled, **geometry)
        assert paid == want
        _, walked = paged_walk_counts(table, base, floor, filled,
                                      **geometry)
        assert walked == scoring * p_blk
        grid = len(filled) * (t // tq)
        assert paid >= grid + scoring
        assert (paid == grid) == (not live)
        if rows == "gap":          # the unmapped compute block's trip
            assert paid == grid + scoring + 2

    def test_steps_paid_of_an_idle_dispatch_is_the_grid(self):
        """A dispatch whose slots are all idle pays one grid step a
        slot and query tile, and nothing else."""
        eng = DecodeEngine(_net(), n_slots=3, decode_chunk=2, seed=0,
                           block_tokens=8)
        assert eng.stats["paged_steps_paid"] == 0
        _submit_run(eng)                  # the pool says the geometry
        served = dict(eng.stats)
        assert (served["paged_steps_paid"] * served["paged_blocks_per_step"]
                > served["paged_blocks_walked"] > 0)
        eng.kv.pack([None] * 3)
        assert eng.stats["paged_steps_paid"] - served[
            "paged_steps_paid"] == 3
        eng.kv.pack([None] * 3, chunk=2 * _PAGED_Q_TILE)
        assert eng.stats["paged_steps_paid"] - served[
            "paged_steps_paid"] == 3 + 3 * 2
        assert (eng.stats["paged_blocks_walked"]
                == served["paged_blocks_walked"])

    def test_walk_counters_match_the_tables(self):
        """``paged_blocks_live`` / ``paged_blocks_walked`` are what the
        dispatched tables imply: counted again here from the block
        tables themselves, entry by entry."""
        eng = DecodeEngine(_net(), n_slots=3, decode_chunk=2, seed=0,
                           block_tokens=8,
                           prefill_chunk=4, prefix_cache_rows=4)
        bt, tm = eng.block_tokens, eng.kv.wmax
        want = {"live": 0, "walked": 0}
        inner = eng.kv.pack

        def spy(tabs, chunk=1):
            out = inner(tabs, chunk)
            pk = next(iter(eng._pool.values()))["pk"]
            per_step = _paged_blocks_per_step(
                bt, pk.shape[2], pk.shape[3], pk.dtype,
                min(eng.kv.kinds[0].ring, (tm + chunk - 2) // bt + 2))
            for tab in tabs:
                if tab is None:
                    continue
                tab, = tab.kinds        # one kind of layer: one table
                lo_blk = max(tab.floor, tab.length - tm + 1, 0) // bt
                hit = [g - lo_blk for g in tab.blocks
                       if g >= lo_blk
                       and g * bt <= tab.length + chunk - 1
                       and (g + 1) * bt - 1 > tab.length - tm]
                want["live"] += len(hit)
                want["walked"] += per_step * len(
                    {e // per_step for e in hit})
            return out

        eng.kv.pack = spy
        _submit_run(eng)
        assert eng.stats["paged_blocks_per_step"] >= 1
        assert eng.stats["paged_steps_per_row"] == -(-min(
            eng.kv.kinds[0].ring, (tm - 1) // bt + 2)
            // eng.stats["paged_blocks_per_step"])
        assert want["live"] > 0
        assert eng.stats["paged_blocks_live"] == want["live"]
        assert eng.stats["paged_blocks_walked"] == want["walked"]
        assert want["live"] <= want["walked"]


class TestTpObservability:
    """Satellite: per-shard gauges ({shard=...} labels riding the
    PR 10 labeling scheme) + the serving_tp_dispatch_s histogram,
    asserted over HTTP through the gateway."""

    def test_per_shard_gauges_over_http(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           prefix_cache_rows=4, block_tokens=8, tp=2)
        gw = ServingGateway(eng)
        gw.start()
        try:
            client = GatewayClient(gw.address, timeout_s=60.0)
            client.generate(list(CASES[0][0]), 6)
            text = client.metrics()
        finally:
            gw.close()
        for shard in (0, 1):
            for fam in ("serving_blocks_free", "serving_blocks_used",
                        "serving_frag_tokens",
                        "serving_tp_kv_bytes"):
                assert f'{fam}{{shard="{shard}"}} ' in text, (
                    f"missing {fam} shard {shard}:\n{text}")
        assert "\nserving_tp_shards 2" in text
        assert "serving_tp_dispatch_s_bucket" in text
        assert "serving_tp_dispatch_s_count" in text
        # the histogram actually observed sharded dispatches
        count = [ln for ln in text.splitlines()
                 if ln.startswith("serving_tp_dispatch_s_count")]
        assert count and float(count[0].split()[-1]) >= 1
        # per-shard KV bytes agree with the engine's own accounting
        per = eng.kv_shard_bytes()
        for shard, nbytes in per.items():
            assert f'serving_tp_kv_bytes{{shard="{shard}"}} ' \
                f"{nbytes}" in text

    def test_single_chip_emits_no_shard_labels(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           tracer=tracer)
        eng.submit(Request([1, 4, 7, 2], 4))
        eng.run()
        text = tracer.prometheus_text()
        # (the pool gauges' HELP lines name the label; no SAMPLE does)
        assert not any("{shard=" in ln for ln in text.splitlines()
                       if not ln.startswith("#"))
        assert "\nserving_tp_shards 1" in text
        for ln in text.splitlines():
            if ln.startswith("serving_tp_dispatch_s_count"):
                assert ln.split()[-1] == "0"

    def test_shard_labels_federate_with_replica_labels(self):
        """{shard=...} gauges ride merge_prometheus: the federated
        scrape carries {replica=...,shard=...} samples."""
        texts = {}
        for rid in ("r0", "r1"):
            tr = Tracer()
            tr.gauge('serving_blocks_free{shard="0"}', 7)
            tr.gauge('serving_blocks_free{shard="1"}', 7)
            texts[rid] = tr.prometheus_text()
        assert 'serving_blocks_free{shard="0"} 7' in texts["r0"]
        fleet = Tracer.merge_prometheus(texts)
        for rid in ("r0", "r1"):
            for shard in (0, 1):
                assert (f'serving_blocks_free{{replica="{rid}",'
                        f'shard="{shard}"}} 7') in fleet, fleet
