"""The paged-pool fragmentation soak (scripts/paged_soak.py)
registered as tests: the fast variants ride tier-1, the full churns
are ``slow``. The soak itself asserts the ISSUE 6 gates (bit-parity
vs ``net.generate`` under sharing/CoW/preemption, zero leaked blocks
— pool fully free once idle and the trie cleared, bounded compile
counts) and, with ``tp > 1`` (ISSUE 12), the per-shard gates: the
head-sliced pool shards stay byte-symmetric and the host leak audit
holds per shard."""

import pytest

from scripts.paged_soak import run_soak


def test_paged_soak_fast():
    summary = run_soak(n_requests=24, seed=0)
    assert summary["prefix_blocks_spliced"] >= 1
    assert summary["cow_copies"] >= 1
    assert summary["used_blocks_peak"] <= summary["kv_blocks"]


def test_paged_soak_tp2_fast():
    """ISSUE 12 satellite: pool saturation + preemption + trie
    eviction on SHARDED pools — the same pressure ladder, per-shard
    byte symmetry, zero leaked blocks per shard."""
    summary = run_soak(n_requests=24, seed=0, tp=2)
    assert summary["tp"] == 2
    assert len(summary["shard_bytes"]) == 2
    assert summary["prefix_blocks_spliced"] >= 1
    assert summary["cow_copies"] >= 1
    assert summary["used_blocks_peak"] <= summary["kv_blocks"]


def test_paged_soak_tier_fast():
    """ISSUE 17 satellite: the same pressure churn with the host-DRAM
    spill tier armed — trie victims spill instead of dropping, cohort
    re-hits reload through the jitted import, and the soak's tier
    gates assert bit-parity with ``net.generate`` (spill/reload
    invisible in ids), the budget held at every sampled peak, both
    churn directions exercised, and the conservation invariant
    spills == reloads + drops + resident."""
    summary = run_soak(n_requests=24, seed=0,
                       host_tier_bytes=1 << 20)
    assert summary["tier"]["spills"] > 0
    assert summary["tier"]["reloads"] > 0
    assert summary["tier_bytes_peak"] <= 1 << 20
    assert summary["used_blocks_peak"] <= summary["kv_blocks"]


@pytest.mark.slow
def test_paged_soak_tier_full():
    summary = run_soak(n_requests=160, seed=0,
                       host_tier_bytes=1 << 20)
    assert summary["tier"]["spills"] >= 10
    assert summary["tier"]["reloads"] >= 5
    assert summary["used_blocks_peak"] == summary["kv_blocks"]


@pytest.mark.slow
def test_paged_soak_full():
    summary = run_soak(n_requests=160, seed=0)
    assert summary["prefix_blocks_spliced"] >= 10
    assert summary["cow_copies"] >= 5
    # the tight default budget saturates the pool and exercises
    # slot preemption at least once — parity held regardless
    assert summary["used_blocks_peak"] == summary["kv_blocks"]
    assert summary["preempted"] >= 1


@pytest.mark.slow
def test_paged_soak_tp2_full():
    summary = run_soak(n_requests=160, seed=0, tp=2)
    assert summary["prefix_blocks_spliced"] >= 10
    assert summary["used_blocks_peak"] == summary["kv_blocks"]
    assert summary["preempted"] >= 1
