"""Fault-tolerant serving runtime (ISSUE 3 tentpole).

The contract under test: failure is an input, not an exception path.
Deadlines, cancellations, load shedding, injected faults, and process
restarts each terminate or retry exactly the requests they name, while
every OTHER greedy request finishes with ids bit-identical to a
fault-free run — and none of it compiles more than ONE new executable
(the paranoid finiteness check) beyond the PR 2 budget."""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    FaultEvent,
    FaultPlan,
    ManualClock,
    Request,
    Scheduler,
)

V = 12


def _net(seed=7, stream_max_t=64):
    net = MultiLayerNetwork(transformer_lm(
        n_in=V, width=32, n_layers=2, n_heads=4, n_classes=V,
        seed=seed)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = stream_max_t
    return net


def _one_hot_seq(ids):
    x = np.zeros((1, V, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


def _solo_generate(prompt, n, seed=7):
    net = _net(seed)
    net.rnn_clear_previous_state()
    return np.asarray(net.generate(_one_hot_seq(prompt), n))[0].tolist()


class TestValidation:
    def test_request_deadline_validation(self):
        with pytest.raises(ValueError, match="deadline_s"):
            Request([1], 4, deadline_s=0)
        with pytest.raises(ValueError, match="queue_timeout_s"):
            Request([1], 4, queue_timeout_s=-1.0)

    def test_engine_knob_validation(self):
        with pytest.raises(ValueError, match="shed_policy"):
            DecodeEngine(_net(), n_slots=1, shed_policy="drop-all")
        with pytest.raises(ValueError, match="max_queue"):
            DecodeEngine(_net(), n_slots=1, max_queue=0)
        with pytest.raises(ValueError, match="max_retries"):
            DecodeEngine(_net(), n_slots=1, max_retries=-1)

    def test_fault_event_validation(self):
        with pytest.raises(ValueError, match="fault kind"):
            FaultEvent(0, "meteor")
        with pytest.raises(ValueError, match="fault kind"):
            FaultPlan.random(0, 5, kinds=("meteor",))

    def test_random_plan_is_seed_deterministic(self):
        a = FaultPlan.random(3, 50, rate=0.3)
        b = FaultPlan.random(3, 50, rate=0.3)
        assert a.events == b.events
        assert len(a) > 0


class TestDeadlinesAndTimeouts:
    def test_queued_deadline_expires(self):
        """A queued request whose end-to-end deadline passes before a
        slot frees is terminated without any device work; the running
        neighbour is unaffected."""
        clock = ManualClock()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           clock=clock)
        a = eng.submit(Request([1, 2, 3], 12))
        b = eng.submit(Request([4, 5], 8, deadline_s=1.0))
        res = eng.step()          # admits a; b queued
        clock.advance(2.0)        # blow b's deadline while it waits
        while eng.has_work():
            eng.step(res)
        assert res[b].finish_reason == "deadline"
        assert res[b].tokens == []
        assert res[a].finish_reason == "length"
        assert res[a].tokens == _solo_generate([1, 2, 3], 12)

    def test_queue_timeout_sheds(self):
        """queue_timeout_s bounds QUEUE WAIT: expiry sheds (the
        backpressure outcome), not 'deadline'."""
        clock = ManualClock()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           clock=clock)
        eng.submit(Request([1, 2, 3], 12))
        b = eng.submit(Request([4, 5], 8, queue_timeout_s=0.5))
        res = eng.step()
        clock.advance(1.0)
        while eng.has_work():
            eng.step(res)
        assert res[b].finish_reason == "shed"
        assert eng.stats["queue_timeouts"] == 1

    def test_running_deadline_evicts_with_partial_tokens(self):
        """A deadline blown mid-decode evicts the slot via the normal
        row-zeroing path: partial tokens come back, and the surviving
        neighbour's ids stay bit-identical to its solo run."""
        clock = ManualClock()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           clock=clock)
        doomed = eng.submit(Request([1, 2, 3], 40, deadline_s=5.0))
        healthy = eng.submit(Request([9, 3, 3], 11))
        res = eng.step()          # both admitted, 1 decode chunk
        clock.advance(10.0)
        while eng.has_work():
            eng.step(res)
        assert res[doomed].finish_reason == "deadline"
        n_partial = len(res[doomed].tokens)
        assert 0 < n_partial < 40
        # the partial prefix is the REAL prefix of the solo decode
        assert res[doomed].tokens == _solo_generate(
            [1, 2, 3], 40)[:n_partial]
        assert res[healthy].tokens == _solo_generate([9, 3, 3], 11)

    def test_deadline_mirrors_to_tracer(self):
        clock = ManualClock()
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           tracer=tracer, clock=clock)
        eng.submit(Request([1, 2, 3], 6))
        eng.submit(Request([4, 5], 6, deadline_s=0.5))
        res = eng.step()
        clock.advance(1.0)
        while eng.has_work():
            eng.step(res)
        assert tracer.latest_counters()[
            "serving_deadline_expired"] == 1.0
        assert eng.stats["deadline_expired"] == 1


class TestCancellation:
    def test_cancel_queued(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        eng.submit(Request([1, 2, 3], 10))
        b = eng.submit(Request([4, 5], 10))
        assert eng.cancel(b)
        res = eng.run()
        assert res[b].finish_reason == "cancelled"
        assert res[b].tokens == []

    def test_cancel_running_returns_partial_tokens(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2)
        a = eng.submit(Request([1, 2, 3], 40))
        b = eng.submit(Request([9, 3, 3], 11))
        res = eng.step()          # a holds a slot with >= 1 token
        assert eng.cancel(a)
        while eng.has_work():
            eng.step(res)
        assert res[a].finish_reason == "cancelled"
        n = len(res[a].tokens)
        assert 0 < n < 40
        assert res[a].tokens == _solo_generate([1, 2, 3], 40)[:n]
        assert res[b].tokens == _solo_generate([9, 3, 3], 11)

    def test_cancel_pending_admission_frees_slot(self):
        """Chunked mode: cancelling mid-admission releases the
        reserved slot (and any prefix lease) so the next request can
        use it."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           prefix_cache_rows=2, prefill_chunk=4,
                           admission_policy="decode")
        a = eng.submit(Request(list(range(12)), 6))
        res = eng.step()          # first chunk of a's prefill only
        assert eng._pending and eng._pending[0].request.id == a
        assert eng.cancel(a)
        assert not eng._reserved
        b = eng.submit(Request([4, 5], 5))
        while eng.has_work():
            eng.step(res)
        assert res[a].finish_reason == "cancelled"
        assert res[b].tokens == _solo_generate([4, 5], 5)

    def test_cancel_unknown_or_finished_is_false(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        rid = eng.submit(Request([1, 2], 3))
        eng.run()
        assert not eng.cancel(rid)
        assert not eng.cancel(999)

    def test_cancel_while_idle_delivered_by_next_run(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        rid = eng.submit(Request([1, 2], 3))
        eng.cancel(rid)
        res = eng.run()           # no work left — still delivers
        assert res[rid].finish_reason == "cancelled"


class TestLoadShedding:
    def test_reject_new_policy(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           max_queue=2)
        ids = [eng.submit(Request([i + 1, i + 2], 4))
               for i in range(2)]
        shed = eng.submit(Request([7, 8], 4))   # queue full -> shed
        res = eng.run()
        assert res[shed].finish_reason == "shed"
        assert res[shed].tokens == []
        assert eng.stats["shed"] == 1
        for rid, lo in zip(ids, range(2)):
            assert res[rid].tokens == _solo_generate([lo + 1, lo + 2],
                                                     4)

    def test_shed_oldest_policy(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           max_queue=1, shed_policy="shed-oldest")
        a = eng.submit(Request([1, 2], 6))
        b = eng.submit(Request([3, 4], 6))       # sheds a
        c = eng.submit(Request([5, 6], 6))       # sheds b
        res = eng.run()
        assert res[a].finish_reason == "shed"
        assert res[b].finish_reason == "shed"
        assert res[c].finish_reason == "length"
        assert res[c].tokens == _solo_generate([5, 6], 6)

    def test_shed_mirrors_to_tracer(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           max_queue=1, tracer=tracer)
        eng.submit(Request([1, 2], 3))
        eng.submit(Request([3, 4], 3))
        assert tracer.latest_counters()["serving_shed"] == 1.0


class TestAdaptiveBudget:
    def test_scheduler_steps_budget_down_and_recovers(self):
        s = Scheduler(64, prefill_chunk=4, prefill_budget=16,
                      pressure_high=40, pressure_low=8)
        for _ in range(8):
            s.submit(Request(list(range(10)), 4))
        assert s.pressure() == 8 * 10
        assert s.adapt_budget() == 12      # pressure > high: step down
        assert s.adapt_budget() == 8
        assert s.adapt_budget() == 4
        assert s.adapt_budget() == 4       # floor: one chunk
        while s.pending:
            s.pop()
        assert s.adapt_budget() == 8       # pressure < low: recover
        assert s.adapt_budget() == 12
        assert s.adapt_budget() == 16
        assert s.adapt_budget() == 16      # ceiling: configured budget

    def test_engine_degrades_budget_under_pressure(self):
        """With a deep queue the per-round prefill budget steps toward
        one chunk (decode keeps its cadence); every request still
        finishes with exact ids."""
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           prefix_cache_rows=2, prefill_chunk=4,
                           prefill_budget=16, adaptive_prefill=True,
                           pressure_high=30, pressure_low=5,
                           tracer=tracer)
        cases = [(list(range(1, 9)), 3) for _ in range(8)]
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = eng.run()
        budgets = tracer.counter_values("serving_prefill_budget")
        assert budgets and min(budgets) < 16   # degraded under load
        want = _solo_generate(list(range(1, 9)), 3)
        for rid in ids:
            assert res[rid].tokens == want


class TestFaultInjection:
    def test_nan_fault_quarantined_and_retried(self):
        """A NaN'd slot is detected by the paranoid sweep, quarantined
        (rows zeroed), and the victim re-decodes to the SAME ids; the
        healthy neighbour never notices."""
        plan = FaultPlan([FaultEvent(1, "nan", slot=0)])
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           paranoid=True, fault_plan=plan)
        victim = eng.submit(Request([1, 2, 3], 9))
        healthy = eng.submit(Request([9, 3, 3], 9))
        res = eng.run()
        assert len(plan.injected) == 1
        assert eng.stats["quarantined"] == 1
        assert res[victim].finish_reason == "length"
        assert res[victim].retries == 1
        assert res[victim].tokens == _solo_generate([1, 2, 3], 9)
        assert res[healthy].retries == 0
        assert res[healthy].tokens == _solo_generate([9, 3, 3], 9)

    def test_admit_fail_retries_with_backoff(self):
        plan = FaultPlan([FaultEvent(0, "admit_fail")])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           paranoid=True, fault_plan=plan,
                           retry_backoff_rounds=2)
        rid = eng.submit(Request([1, 2, 3], 5))
        res = eng.run()
        assert eng.stats["retries"] == 1
        assert res[rid].finish_reason == "length"
        assert res[rid].retries == 1
        assert res[rid].tokens == _solo_generate([1, 2, 3], 5)

    def test_capped_retries_end_in_fault_reason(self):
        """Every re-admission fails too: the victim reaches a TERMINAL
        state (finish_reason='fault') instead of looping forever."""
        plan = FaultPlan([FaultEvent(r, "admit_fail")
                          for r in range(8)])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           fault_plan=plan, max_retries=1)
        rid = eng.submit(Request([1, 2, 3], 5))
        res = eng.run()
        assert res[rid].finish_reason == "fault"
        assert res[rid].tokens == []
        assert res[rid].retries == 1
        assert eng.stats["retry_failures"] == 1

    def test_cache_corruption_detected_and_scrubbed(self):
        """Poison a stored prefix row: the next admission that reuses
        it goes NaN, the paranoid sweep traces it back, invalidates
        BOTH poisoned entries (the fetched row and the one the
        admission inserted), and the retry prefills cold to the exact
        ids."""
        shared = [1, 4, 7, 2, 5, 3]
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           prefix_cache_rows=4, paranoid=True)
        warm = eng.submit(Request(shared + [8, 9], 4))
        res = eng.run()
        assert res[warm].tokens == _solo_generate(shared + [8, 9], 4)
        row = eng.prefix_cache.stored_rows()[0]
        eng.fault_plan = FaultPlan(
            [FaultEvent(eng._round, "cache_corrupt", row=row)])
        victim = eng.submit(Request(shared + [10, 11], 6))
        res = eng.run()
        assert eng.stats["quarantined"] == 1
        assert eng.prefix_cache.stats["invalidations"] >= 1
        assert res[victim].finish_reason == "length"
        assert res[victim].retries == 1
        assert res[victim].tokens == _solo_generate(
            shared + [10, 11], 6)

    def test_fault_caught_when_request_finishes_at_admission(self):
        """PR 3's documented blind spot, closed (ISSUE 4 satellite): a
        request that finishes AT admission (max_new_tokens=1) in the
        same round its poisoned prefix row rides in used to elude the
        paranoid sweep (checks ran post-decode only) and deliver a
        garbage terminal. The finiteness check now runs over admitted
        rows before their terminals drain: the victim is quarantined,
        both poisoned cache entries are scrubbed, and the retry
        prefills cold to the exact ids."""
        shared = [1, 4, 7, 2, 5, 3]
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           prefix_cache_rows=4, paranoid=True)
        warm = eng.submit(Request(shared + [8, 9], 4))
        res = eng.run()
        assert res[warm].tokens == _solo_generate(shared + [8, 9], 4)
        row = eng.prefix_cache.stored_rows()[0]
        eng.fault_plan = FaultPlan(
            [FaultEvent(eng._round, "cache_corrupt", row=row)])
        victim = eng.submit(Request(shared + [10, 11], 1))
        res = eng.run()
        assert eng.stats["quarantined"] == 1
        assert eng.prefix_cache.stats["invalidations"] >= 1
        assert res[victim].finish_reason == "length"
        assert res[victim].retries == 1
        assert res[victim].tokens == _solo_generate(
            shared + [10, 11], 1)
        # and the health check stayed the ONE extra executable
        assert eng.compile_counts()["health_check"] == 1

    def test_queue_timeout_exempts_fault_retries(self):
        """queue_timeout_s bounds time-to-FIRST-service: a fault
        victim waiting out its retry backoff in the queue again must
        be retried, not shed — even when its total wait exceeds the
        timeout."""
        clock = ManualClock()
        plan = FaultPlan([FaultEvent(0, "admit_fail")])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           fault_plan=plan, clock=clock)
        rid = eng.submit(Request([1, 2, 3], 5, queue_timeout_s=0.5))
        res = eng.step()          # admission attempt fails -> requeue
        clock.advance(2.0)        # far past the queue timeout
        while eng.has_work():
            eng.step(res)
        assert res[rid].finish_reason == "length"
        assert res[rid].retries == 1
        assert res[rid].tokens == _solo_generate([1, 2, 3], 5)
        assert eng.stats["queue_timeouts"] == 0

    def test_unconsumed_admit_fail_expires_with_its_round(self):
        """An admit_fail scheduled for a round with no admission must
        NOT lie in wait for an unrelated later workload — it is scoped
        to its round."""
        plan = FaultPlan([FaultEvent(0, "admit_fail")])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           fault_plan=plan)
        eng.step()                # round 0: queue empty, fault unused
        rid = eng.submit(Request([1, 2, 3], 5))
        res = eng.run()
        assert res[rid].finish_reason == "length"
        assert res[rid].retries == 0
        assert eng.stats["retries"] == 0

    def test_stall_fault_detected_as_slow_step(self):
        clock = ManualClock()
        plan = FaultPlan([FaultEvent(1, "stall", seconds=2.0)])
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           fault_plan=plan, stall_threshold_s=0.5,
                           clock=clock)
        rid = eng.submit(Request([1, 2, 3], 8))
        res = eng.run()
        assert eng.stats["slow_steps"] == 1
        assert res[rid].tokens == _solo_generate([1, 2, 3], 8)

    def test_undetected_without_paranoid(self):
        """Knob honesty: without paranoid the NaN victim is NOT
        quarantined (garbage ids) — detection is the flag's job, and
        healthy neighbours are still bit-unaffected either way."""
        plan = FaultPlan([FaultEvent(1, "nan", slot=0)])
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           fault_plan=plan)
        victim = eng.submit(Request([1, 2, 3], 9))
        healthy = eng.submit(Request([9, 3, 3], 9))
        res = eng.run()
        assert eng.stats["quarantined"] == 0
        assert res[victim].retries == 0
        assert res[healthy].tokens == _solo_generate([9, 3, 3], 9)


class TestSnapshotResume:
    def test_snapshot_is_plain_json(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           prefix_cache_rows=2, prefill_chunk=4)
        eng.submit(Request([1, 2, 3], 8, deadline_s=30.0))
        eng.step()
        snap = eng.snapshot()
        json.dumps(snap)  # wire format: nothing device-resident

    def test_idle_snapshot_restores_queue(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2)
        a = eng.submit(Request([1, 2, 3], 6))
        b = eng.submit(Request([9, 3, 3], 4))
        snap = eng.snapshot()
        eng2 = DecodeEngine.restore(_net(), snap)
        res = eng2.run()
        assert res[a].tokens == _solo_generate([1, 2, 3], 6)
        assert res[b].tokens == _solo_generate([9, 3, 3], 4)

    def test_mid_run_snapshot_finishes_identical_ids(self):
        """The crash-recovery contract: kill the engine mid-decode,
        restore in a fresh engine (fresh process equivalent), and the
        union of results is bit-identical to the uninterrupted run —
        including requests that were mid-admission and still queued."""
        cases = [([1, 4, 7, 2], 9), ([9, 3, 3], 13),
                 ([5, 2, 8, 1, 6, 0, 4], 6), ([2, 2], 11),
                 ([11, 0, 6], 7)]
        ref_eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               prefix_cache_rows=4, prefill_chunk=4)
        ref_ids = [ref_eng.submit(Request(p, n)) for p, n in cases]
        ref = ref_eng.run()

        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           prefix_cache_rows=4, prefill_chunk=4)
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = {}
        for _ in range(3):        # crash mid-flight
            eng.step(res)
        assert eng.has_work()
        snap = eng.snapshot()

        eng2 = DecodeEngine.restore(_net(), snap)
        res.update(eng2.run())
        for rid, ref_rid in zip(ids, ref_ids):
            assert res[rid].tokens == ref[ref_rid].tokens, (
                f"request {rid} diverged across snapshot/restore")
            assert res[rid].finish_reason == ref[ref_rid].finish_reason

    def test_restore_preserves_ids_and_issues_fresh_ones(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        a = eng.submit(Request([1, 2, 3], 4))
        snap = eng.snapshot()
        eng2 = DecodeEngine.restore(_net(), snap)
        b = eng2.submit(Request([4, 5], 3))
        assert b > a              # no collision with restored ids
        res = eng2.run()
        assert set(res) == {a, b}

    def test_restored_slot_id_keeps_duplicate_guard(self):
        """A request decoding in a slot at snapshot time stays ISSUED
        after restore: replaying its id raises exactly like on the
        live engine."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        a = eng.submit(Request([1, 2, 3], 10))
        eng.step()                # a now holds the slot
        snap = eng.snapshot()
        eng2 = DecodeEngine.restore(_net(), snap)
        with pytest.raises(ValueError, match="already submitted"):
            eng2.submit(Request([4, 5], 3, id=a))

    def test_restore_preserves_elapsed_deadline(self):
        """A deadline half-spent before the crash stays half-spent:
        the restored engine re-arms submit time from the snapshot's
        elapsed seconds."""
        clock = ManualClock()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2,
                           clock=clock)
        eng.submit(Request([1, 2, 3], 30))
        doomed = eng.submit(Request([4, 5], 30, deadline_s=10.0))
        res = eng.step()
        clock.advance(8.0)        # 8s of the 10s budget gone
        snap = eng.snapshot()
        clock2 = ManualClock()
        eng2 = DecodeEngine.restore(_net(), snap, clock=clock2)
        clock2.advance(3.0)       # 8 + 3 > 10: expires in new process
        res.update(eng2.run())
        assert res[doomed].finish_reason == "deadline"


class TestChaosParityGate:
    @pytest.mark.parametrize("block_tokens", [16, 8])
    def test_chaos_parity_with_snapshot_resume(self, assert_no_retrace,
                                               block_tokens):
        """The ISSUE 3 acceptance gate, on the block pool (ISSUE 6
        satellite), at the default block size and a smaller one. A
        seeded FaultPlan hits THREE subsystems (sampler NaN, admission
        failure, prefix-cache corruption) on a chunked + prefix-cached
        + paranoid engine: it poisons slot blocks, fails an admission,
        and bit-rots a stored prefix entry's block inside the shared
        pool.

        - every non-victim greedy request finishes bit-identical to
          the no-fault run;
        - every victim ends terminal — retried-success with the SAME
          ids, or capped-retry failure with finish_reason='fault';
          victims quarantine per-BLOCK (shared blocks are released by
          reference, never scrubbed under an innocent);
        - a mid-run snapshot()->restore() into a fresh engine (the
          snapshot carries block tables + refcounts) finishes the
          remaining requests with identical ids;
        - compile counts stay within the budget plus exactly ONE
          new executable (the paranoid health check)."""
        cases = ([([1, 4, 7, 2, 5] + [i % V], 8) for i in range(4)]
                 + [([9, 3, 3], 12), ([5, 2, 8, 1, 6, 0, 4], 6),
                    ([2, 2], 10), ([11, 0, 6], 7)])

        def build(plan):
            return DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                                prefix_cache_rows=4, prefill_chunk=4,
                                admission_policy="decode",
                                paranoid=True, fault_plan=plan,
                                max_retries=3,
                                block_tokens=block_tokens)

        ref_eng = build(None)
        ref_ids = [ref_eng.submit(Request(p, n)) for p, n in cases]
        ref = ref_eng.run()
        assert all(r.finish_reason in ("length", "eos")
                   for r in ref.values())

        plan = FaultPlan([FaultEvent(2, "nan", slot=0),
                          FaultEvent(3, "admit_fail"),
                          FaultEvent(4, "cache_corrupt"),
                          FaultEvent(6, "nan", slot=1)])
        eng = build(plan)
        ids = [eng.submit(Request(p, n)) for p, n in cases]
        res = {}
        for _ in range(8):
            eng.step(res)
        assert len(plan.injected) >= 3
        assert {"nan", "admit_fail", "cache_corrupt"} <= {
            e.kind for e in plan.injected}
        snap = eng.snapshot()
        json.dumps(snap)
        assert snap["config"]["paged_kv"] is True
        assert snap["paged"]["tables"]          # block tables ride
        assert snap["paged"]["refcounts"]       # refcounts ride

        eng2 = DecodeEngine.restore(_net(), snap)
        assert eng2.block_tokens == block_tokens
        res.update(eng2.run())
        warm_counts = dict(eng2.compile_counts())

        assert set(res) == set(ids)
        n_victims = 0
        for rid, ref_rid in zip(ids, ref_ids):
            r = res[rid]
            if r.retries > 0:
                n_victims += 1
            if r.finish_reason == "fault":
                continue
            assert r.finish_reason in ("length", "eos")
            assert r.tokens == ref[ref_rid].tokens, (
                f"request {rid} (retries={r.retries}) diverged from "
                "the no-fault run")
        assert n_victims >= 1     # the plan actually hurt someone
        # compile budget, on BOTH engines (the faulted one and the
        # restored one): ONE decode, ONE scatter, ONE
        # token put, ONE per-block health check; chunk_prefill covers
        # at most a cold row's continuation + a warm table's; the
        # trie owns no movers at all
        for counts in (eng.compile_counts(), eng2.compile_counts()):
            assert counts["decode"] == 1
            assert "admit" not in counts
            assert counts["paged_scatter"] == 1
            assert counts["paged_tok"] == 1
            assert counts["health_check"] == 1
            assert counts["prefill"] == 1
            assert 1 <= counts["chunk_prefill"] <= 2
            assert counts["paged_copy"] <= 1
            assert counts["paged_zero"] <= 1
            assert "prefix_store" not in counts
            assert "prefix_fetch" not in counts
        # no poisoned block survives once its references drop, and a
        # warmed engine never retraces under continued churn
        assert eng2.block_pool.poisoned == set()
        with assert_no_retrace(eng2):
            more = [eng2.submit(Request(p, n)) for p, n in cases[:3]]
            res2 = eng2.run()
        assert all(res2[m].finish_reason in ("length", "eos")
                   for m in more)
        assert eng2.compile_counts() == warm_counts
