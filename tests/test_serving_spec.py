"""Self-speculative decoding (ISSUE 4 tentpole).

The contract under test: with ``spec_draft_len=K`` the engine drafts
up to K tokens per greedy slot from host-side n-gram tables and
verifies every slot's draft in ONE batched forward pass — and the
emitted greedy ids are BIT-IDENTICAL to the spec-off engine (which PR 1
already pins to sequential ``generate()``) in every admission mode,
with or without the prefix cache, under faults, snapshot/restore, and
mid-run cancellation, while compile counts stay bounded at one verify
executable per pow2 draft-width bucket."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler.tracer import Tracer
from deeplearning4j_tpu.serving import (
    DecodeEngine,
    FaultEvent,
    FaultPlan,
    NgramDraftTable,
    Request,
    Scheduler,
    greedy_acceptance,
    residual_sample,
    stochastic_acceptance,
)

V = 12

#: repetitive prompts — the workload n-gram drafting exists for (the
#: untrained test net also repeats, so acceptance is reliably > 0)
REPEATS = [([1, 2, 3, 1, 2, 3, 1], 10), ([5, 2, 5, 2, 5], 8),
           ([9, 3, 3], 13), ([2, 2], 6), ([1, 4, 7, 2], 9)]


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


def _solo_generate(prompt, n, seed=7, stream_max_t=64):
    net = _net(seed, stream_max_t)
    net.rnn_clear_previous_state()
    return np.asarray(net.generate(_one_hot_seq(prompt), n))[0].tolist()


class TestNgramDraftTable:
    def test_longest_match_wins(self):
        t = NgramDraftTable(max_ngram=3)
        t.seed(0, [7, 1, 2, 9, 0, 1, 2, 3, 1, 2])
        # trailing 2-gram [1, 2] occurred twice; the LONGEST usable
        # suffix match is preferred, and among equals the most recent
        # occurrence's continuation ([3, ...]) wins over the old [9]
        assert t.draft(0, 3) == [3, 1, 2]

    def test_trailing_ngram_never_matches_itself(self):
        t = NgramDraftTable()
        t.seed(0, [1, 2, 3])
        assert t.draft(0, 4) == []  # nothing repeats: no draft

    def test_periodic_context_extends_past_its_end(self):
        """A cyclic context drafts the full k by re-matching against
        the virtual context (ctx + draft-so-far) when the real
        continuation runs dry — a period-1 tail would otherwise cap
        every draft at one token."""
        t = NgramDraftTable()
        t.seed(0, [1, 2, 3, 1, 2, 3, 1, 2])
        assert t.draft(0, 8) == [3, 1, 2, 3, 1, 2, 3, 1]
        t.seed(1, [5, 9, 9, 9])
        assert t.draft(1, 4) == [9, 9, 9, 9]

    def test_extend_matches_seed(self):
        a, b = NgramDraftTable(), NgramDraftTable()
        ids = [1, 2, 3, 1, 2, 4, 1, 2]
        a.seed(0, ids)
        b.seed(0, ids[:3])
        for tok in ids[3:]:
            b.extend(0, [tok])
        assert a.draft(0, 5) == b.draft(0, 5)
        assert a.context(0) == b.context(0)

    def test_drop_forgets_slot(self):
        t = NgramDraftTable()
        t.seed(0, [1, 1, 1])
        t.seed(1, [2, 2, 2])
        t.drop(0)
        assert t.slots() == [1]
        assert t.draft(0, 4) == []

    def test_validation(self):
        with pytest.raises(ValueError, match="min_ngram"):
            NgramDraftTable(min_ngram=0)
        with pytest.raises(ValueError, match="max_ngram"):
            NgramDraftTable(max_ngram=1, min_ngram=2)

    def test_zero_k_drafts_nothing(self):
        t = NgramDraftTable()
        t.seed(0, [1, 1, 1, 1])
        assert t.draft(0, 0) == []


class TestGreedyAcceptance:
    def test_prefix_semantics(self):
        targets = jnp.asarray([[5, 6, 7, 8],    # full accept
                               [5, 9, 7, 8],    # diverge at 1
                               [0, 6, 7, 8],    # diverge at 0
                               [5, 6, 7, 8]])   # pad never accepts
        draft = jnp.asarray([[5, 6, 7, 8],
                             [5, 6, 7, 8],
                             [5, 6, 7, 8],
                             [5, 6, 7, 8]])
        lens = jnp.asarray([4, 4, 4, 2])
        acc = np.asarray(greedy_acceptance(targets, draft, lens))
        assert acc.tolist() == [4, 1, 0, 2]

    def test_rejection_invalidates_later_matches(self):
        """A match AFTER a rejection must not count: those drafts were
        scored against a context containing the rejected token."""
        targets = jnp.asarray([[1, 9, 3]])
        draft = jnp.asarray([[1, 2, 3]])     # position 2 "matches"
        acc = np.asarray(greedy_acceptance(targets, draft,
                                           jnp.asarray([3])))
        assert acc.tolist() == [1]


class TestStochasticAcceptance:
    """The rejection-sampling acceptance rule (ISSUE 16): with the
    n-gram drafter's point-mass q, a draft token is accepted with
    probability p_tau(draft) and a rejection redraws from the residual
    (draft-banned, renormalized) distribution — together the emitted
    marginals are EXACTLY the target model's sampling distribution."""

    def test_greedy_rows_keep_the_equality_rule(self):
        """temps == 0 rows are bit-identical to greedy_acceptance —
        the engine's greedy bit-parity invariant does not depend on
        the accept-draw key."""
        rng = np.random.default_rng(0)
        probs = rng.dirichlet(np.ones(V), size=(4, 3)).astype(
            np.float32)
        draft = jnp.asarray(rng.integers(0, V, (4, 3)), jnp.int32)
        lens = jnp.asarray([3, 3, 2, 0], jnp.int32)
        targets = jnp.argmax(jnp.asarray(probs), axis=-1).astype(
            jnp.int32)
        want = np.asarray(greedy_acceptance(targets, draft, lens))
        for seed in (0, 1, 7):
            got = np.asarray(stochastic_acceptance(
                jnp.asarray(probs), draft, lens,
                jnp.zeros(4), jnp.full(4, V, jnp.int32),
                jax.random.key(seed)))
            assert got.tolist() == want.tolist()

    def test_certain_and_impossible_drafts(self):
        """p_tau(draft) == 1 always accepts (u < 1 for uniform
        [0, 1)); p_tau(draft) == 0 — e.g. a draft outside the top-k
        support — always rejects, regardless of key."""
        probs = np.full((2, 2, V), 1e-9, np.float32)
        probs[:, :, 3] = 1.0                 # point mass on class 3
        draft = jnp.asarray([[3, 3], [3, 5]], jnp.int32)
        lens = jnp.asarray([2, 2], jnp.int32)
        for seed in (0, 5):
            acc = np.asarray(stochastic_acceptance(
                jnp.asarray(probs), draft, lens,
                jnp.ones(2), jnp.full(2, 2, jnp.int32),
                jax.random.key(seed)))
            assert acc.tolist() == [2, 1]

    def test_residual_sample_bans_after_topk(self):
        """The ban applies AFTER the rank filter: banning the top-1
        class of a top_k=2 row must redistribute to the SECOND class,
        never admit the third — and greedy rows ignore the ban."""
        probs = np.zeros((2, V), np.float32)
        probs[:, 0], probs[:, 1], probs[:, 2] = 0.6, 0.3, 0.1
        ban = jnp.asarray([0, 0], jnp.int32)
        do_ban = jnp.asarray([True, True])
        temps = jnp.asarray([1.0, 0.0])
        top_ks = jnp.full(2, 2, jnp.int32)
        for seed in range(8):
            tok = np.asarray(residual_sample(
                jnp.asarray(probs), ban, do_ban, temps, top_ks,
                jax.random.key(seed)))
            assert tok[0] == 1        # only class in residual support
            assert tok[1] == 0        # greedy: argmax despite the ban

    def test_emitted_marginals_match_target_sampling(self):
        """Distribution-level sanity (the ISSUE 16 acceptance gate):
        Monte-Carlo the accept-or-residual pipeline for a FIXED target
        row and drafted token; the emitted-token marginal must match
        p_tau within tolerance. Checked at an unfiltered row and a
        top-k row, each under a temperature that reshapes p."""
        rng = np.random.default_rng(4)
        base = rng.dirichlet(np.ones(V) * 0.7).astype(np.float32)
        n = 4000
        for temp, top_k, drafted in ((0.7, V, 3), (1.3, 4, 1)):
            probs1 = jnp.asarray(base)[None, None, :]   # [1, 1, V]
            temps = jnp.asarray([temp])
            tks = jnp.full(1, top_k, jnp.int32)
            draft = jnp.full((1, 1), drafted, jnp.int32)
            lens = jnp.ones(1, jnp.int32)

            def emit(key):
                ka, kb = jax.random.split(key)
                acc = stochastic_acceptance(
                    probs1, draft, lens, temps, tks, ka)
                rejected = acc < 1
                bonus = residual_sample(
                    jnp.asarray(base)[None, :], draft[:, 0],
                    rejected, temps, tks, kb)
                return jnp.where(acc == 1, drafted, bonus)[0]

            keys = jax.random.split(jax.random.key(11), n)
            toks = np.asarray(jax.vmap(emit)(keys))
            emp = np.bincount(toks, minlength=V) / n
            # the law the pipeline must reproduce: p_tau — temperature
            # + rank-top-k applied to the same row (sampler semantics)
            logp = np.log(np.maximum(base, 1e-30))
            order = np.argsort(-logp, kind="stable")
            keep = order[:top_k]
            scaled = np.full(V, -np.inf)
            scaled[keep] = logp[keep] / temp
            p_tau = np.exp(scaled - scaled.max())
            p_tau /= p_tau.sum()
            assert float(np.abs(emp - p_tau).sum()) < 0.08, (
                temp, top_k, emp, p_tau)


class TestSpecParity:
    """Greedy ids must be bit-identical spec-on vs spec-off across all
    four admission modes x prefix cache on/off (the tentpole gate)."""

    @pytest.mark.parametrize("kwargs", [
        {},                                     # blocking, cold
        {"prefix_cache_rows": 4},               # blocking, warm
        {"prefix_cache_rows": 4, "prefill_chunk": 4},   # chunked ttft
        {"prefix_cache_rows": 4, "prefill_chunk": 4,
         "admission_policy": "decode"},         # chunked decode-prio
        {"prefill_chunk": 4},                   # chunked, no cache
    ])
    def test_greedy_ids_identical_to_spec_off(self, kwargs):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                           spec_draft_len=4, **kwargs)
        ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
        res = eng.run()
        for rid, (p, n) in zip(ids, REPEATS):
            assert res[rid].tokens == _solo_generate(p, n), (
                f"request {rid} diverged under spec with {kwargs}")
        # the speculative path actually ran and accepted something —
        # a parity test that silently fell back would prove nothing
        assert eng.stats["spec_rounds"] > 0
        assert eng.stats["spec_accepted"] > 0

    def test_engine_vs_engine_bit_identity(self):
        """Definitional form of the gate: the same workload through a
        spec-off and a spec-on engine, token lists compared directly,
        with per-request acceptance counters surfaced."""
        off = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0)
        on = DecodeEngine(_net(), n_slots=2, decode_chunk=3, seed=0,
                          spec_draft_len=6)
        ids_off = [off.submit(Request(p, n)) for p, n in REPEATS]
        ids_on = [on.submit(Request(p, n)) for p, n in REPEATS]
        res_off, res_on = off.run(), on.run()
        for a, b in zip(ids_off, ids_on):
            assert res_off[a].tokens == res_on[b].tokens
            assert res_off[a].finish_reason == res_on[b].finish_reason
            assert res_off[a].spec_drafted == 0
        assert sum(res_on[b].spec_accepted for b in ids_on) > 0
        assert on.stats["tokens_generated"] >= sum(
            len(res_on[b].tokens) for b in ids_on)

    def test_prompt_shorter_than_k(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           spec_draft_len=8)
        rid = eng.submit(Request([2, 2], 10))
        res = eng.run()
        assert res[rid].tokens == _solo_generate([2, 2], 10)

    def test_no_match_rounds_fall_back_to_plain_decode(self):
        """Rounds where no slot drafts anything run the PLAIN decode
        executable (speculation is an accelerator, never a
        requirement): with a table that never matches, the whole run
        is fallback rounds, ids stay exact, and the verify executable
        is never even compiled."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           spec_draft_len=4)

        class NeverMatches(NgramDraftTable):
            def draft(self, slot, k):
                return []

        eng.spec = NeverMatches()
        ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
        res = eng.run()
        for rid, (p, n) in zip(ids, REPEATS):
            assert res[rid].tokens == _solo_generate(p, n)
        assert eng.stats["spec_rounds"] == 0
        assert eng.stats["spec_fallback_rounds"] > 0
        assert eng.compile_counts()["verify"] == 0
        assert eng.compile_counts()["decode"] == 1

    def test_adversarial_drafts_still_exact(self):
        """Acceptance=0 robustness: a draft table proposing garbage
        must cost only speed — every round still advances via the
        model's own correction token and ids stay exact."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           spec_draft_len=4)
        base = _solo_generate([1, 2, 3, 1, 2, 3, 1], 10)
        wrong = (base[0] + 1) % V   # never the model's first choice?
        # not guaranteed wrong every step — parity is the assertion

        class Adversary(NgramDraftTable):
            def draft(self, slot, k):
                return [wrong] * k if k > 0 else []

        eng.spec = Adversary()
        ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
        res = eng.run()
        for rid, (p, n) in zip(ids, REPEATS):
            assert res[rid].tokens == _solo_generate(p, n)
        assert eng.stats["spec_rounds"] > 0
        assert eng.stats["spec_accepted"] < eng.stats["spec_drafted"]

    def test_eos_inside_accepted_draft(self):
        """eos landing INSIDE an accepted draft run truncates at the
        FIRST hit exactly like sequential decode (accepted tokens past
        eos already entered the KV cache — they die with the evicted
        slot, never reaching the result). An oracle table drafting the
        true greedy continuation forces full acceptance, so the eos
        token is delivered by an accepted draft, not the bonus."""
        prompt = [9, 3, 3]
        base = _solo_generate(prompt, 24)
        # an eos whose FIRST occurrence is late enough to sit inside
        # an accepted draft (not the admission token / first bonus)
        eos = next(t for i, t in enumerate(base)
                   if base.index(t) == i and i >= 3)
        stop = base.index(eos) + 1
        # K large enough that the FIRST verify pass spans the eos
        # position: the eos then arrives as an accepted draft token
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=4, seed=0,
                           spec_draft_len=16)

        class Oracle(NgramDraftTable):
            def draft(self, slot, k):
                done = len(self._ctx.get(slot, ())) - len(prompt)
                return base[done:done + k] if k >= 1 else []

        eng.spec = Oracle()
        rid = eng.submit(Request(prompt, 50, eos_id=eos))
        res = eng.run()
        assert res[rid].tokens == base[:stop]
        assert res[rid].finish_reason == "eos"
        assert eng.stats["spec_rounds"] > 0
        # the eos itself arrived as an ACCEPTED draft token: every
        # drafted token was the true greedy token, so acceptance
        # covered the stream through (and past) the eos position
        assert res[rid].spec_accepted >= stop

    def test_prompt_at_window_brim(self):
        """Window-saturation cap: a prompt filling stream_max_t leaves
        no rewind headroom, so drafts shrink to zero and the slot
        advances one exact token per round — never a lossy rewind."""
        window = 32
        prompt = ([1, 2, 3, 4] * 8)[:window]
        eng = DecodeEngine(_net(stream_max_t=window), n_slots=2,
                           decode_chunk=2, seed=0, spec_draft_len=8)
        rid = eng.submit(Request(prompt, 12))
        res = eng.run()
        assert res[rid].tokens == _solo_generate(
            prompt, 12, stream_max_t=window)

    def test_sampling_requests_ride_the_verify_pass(self):
        """A temperature>0 request DRAFTS under stochastic acceptance
        (ISSUE 16: the Leviathan p/q rejection rule preserves its
        sampling distribution exactly, so the greedy-only exclusion is
        gone) and shares the pool with a greedy neighbour: the greedy
        neighbour stays bit-exact (its rows keep the equality rule),
        the sampled one is seed-deterministic. The sampled request's
        prompt holds every token of the vocabulary with a continuation,
        so whatever the key stream draws first, its 1-gram has been
        seen and the drafter fires by construction."""
        covering = list(range(V)) + [0]

        def run():
            eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               seed=3, spec_draft_len=4)
            g = eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 10))
            s = eng.submit(Request(covering, 8, temperature=1.0))
            res = eng.run()
            return res[g], res[s], eng.stats["spec_accepted"]

        g1, s1, acc1 = run()
        g2, s2, _ = run()
        assert g1.tokens == _solo_generate([1, 2, 3, 1, 2, 3, 1], 10)
        assert g1.spec_drafted > 0
        assert s1.spec_drafted > 0    # sampling slots draft too now
        assert len(s1.tokens) == 8
        assert s1.tokens == s2.tokens     # seed-deterministic
        assert acc1 > 0


class TestSpecKnobs:
    def test_validation(self):
        with pytest.raises(ValueError, match="spec_draft_len"):
            DecodeEngine(_net(), n_slots=1, spec_draft_len=-1)
        with pytest.raises(ValueError, match="draft_source"):
            DecodeEngine(_net(), n_slots=1, spec_draft_len=4,
                         draft_source="oracle")
        with pytest.raises(ValueError, match="window"):
            DecodeEngine(_net(), n_slots=1, spec_draft_len=64)

    def test_spec_off_has_no_verify_executable(self):
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2)
        assert "verify" not in eng.compile_counts()
        assert eng.spec is None

    def test_k_adaptation_policy(self):
        """Acceptance feedback steps K down (floor 1 = plain decode
        when no draft matches) and back up to the ceiling."""
        s = Scheduler(64, spec_draft_len=8)
        assert s.draft_len == 8
        for _ in range(s.SPEC_ADAPT_ROUNDS):       # terrible rounds
            s.record_acceptance(8, 0)
        assert s.draft_len == 4
        for _ in range(2 * s.SPEC_ADAPT_ROUNDS):
            s.record_acceptance(4, 0)
        assert s.draft_len == 1
        for _ in range(s.SPEC_ADAPT_ROUNDS):       # floor holds
            s.record_acceptance(1, 0)
        assert s.draft_len == 1
        for _ in range(2 * s.SPEC_ADAPT_ROUNDS):   # strong acceptance
            s.record_acceptance(4, 4)
        assert s.draft_len == 4
        for _ in range(s.SPEC_ADAPT_ROUNDS):
            s.record_acceptance(8, 8)
        assert s.draft_len == 8                    # ceiling holds
        # middling acceptance leaves K alone
        for _ in range(s.SPEC_ADAPT_ROUNDS):
            s.record_acceptance(8, 5)
        assert s.draft_len == 8

    def test_no_draft_rounds_do_not_move_k(self):
        s = Scheduler(64, spec_draft_len=8)
        for _ in range(10 * s.SPEC_ADAPT_ROUNDS):
            s.record_acceptance(0, 0)
        assert s.draft_len == 8

    def test_engine_steps_k_down_under_garbage_drafts(self):
        """End-to-end adaptation: always-rejected drafts drive the
        live K to the floor while ids stay exact."""
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           spec_draft_len=8)

        class Adversary(NgramDraftTable):
            def draft(self, slot, k):
                ctx = self._ctx.get(slot)
                if not ctx or k < 1:
                    return []
                return [(ctx[-1] + 1) % V] * k

        eng.spec = Adversary()
        rid = eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 40))
        res = eng.run()
        assert res[rid].tokens == _solo_generate(
            [1, 2, 3, 1, 2, 3, 1], 40)
        assert eng.scheduler.draft_len < 8

    def test_plan_chunks_bills_verify_tokens(self):
        """Verify width charges the same per-round budget prefill
        chunks use — ttft grants shrink, but never below the one-chunk
        floor (admission always progresses, decode-priority stall
        bound unchanged)."""
        s = Scheduler(64, prefill_chunk=4, prefill_budget=16)
        assert len(s.plan_chunks([16])) == 4
        assert len(s.plan_chunks([16], verify_tokens=8)) == 2
        assert len(s.plan_chunks([16], verify_tokens=13)) == 1
        assert len(s.plan_chunks([16], verify_tokens=1000)) == 1
        d = Scheduler(64, prefill_chunk=4, policy="decode")
        assert len(d.plan_chunks([16], verify_tokens=9)) == 1


class TestSpecCompileCounts:
    def test_one_verify_bucket_at_k1_no_retrace(self,
                                                assert_no_retrace):
        """K=1: exactly one draft width exists, so a warmed engine
        must never retrace across further admissions and rounds."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           spec_draft_len=1)
        for p, n in REPEATS[:2]:
            eng.submit(Request(p, n))
        eng.run()
        counts = eng.compile_counts()
        assert counts["verify"] == 1
        assert counts["paged_scatter"] == 1
        assert counts["paged_tok"] == 1
        with assert_no_retrace(eng):
            ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
            res = eng.run()
        for rid, (p, n) in zip(ids, REPEATS):
            assert res[rid].tokens == _solo_generate(p, n)

    def test_verify_buckets_bounded_by_pow2_of_k(self):
        """Variable draft lengths bucket to pow2 widths: at K=4 at
        most 3 verify executables (widths 1, 2, 4) ever exist, and an
        identical rerun compiles nothing new."""
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           spec_draft_len=4)
        ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
        eng.run()
        counts = eng.compile_counts()
        assert 1 <= counts["verify"] <= 3
        assert counts["decode"] <= 1
        assert counts["paged_scatter"] == 1
        assert counts["paged_tok"] == 1
        # continued churn may touch a not-yet-seen SMALLER bucket (the
        # live K adapts), but the pow2 bound and every non-verify
        # executable hold forever
        ids = [eng.submit(Request(p, n)) for p, n in REPEATS]
        eng.run()
        counts2 = eng.compile_counts()
        assert counts2["verify"] <= 3
        for key in ("decode", "paged_scatter", "paged_tok", "prefill",
                    "chunk_prefill"):
            assert counts2[key] == counts[key]


class TestSpecLifecycle:
    def test_cancel_running_drops_draft_state(self):
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           spec_draft_len=4)
        a = eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 40))
        b = eng.submit(Request([5, 2, 5, 2], 11))
        res = eng.step()
        assert len(eng.spec.slots()) == 2
        assert eng.cancel(a)
        assert len(eng.spec.slots()) == 1   # victim's table died
        while eng.has_work():
            eng.step(res)
        assert res[a].finish_reason == "cancelled"
        assert res[b].tokens == _solo_generate([5, 2, 5, 2], 11)
        assert eng.spec.slots() == []       # all evictions cleaned up

    def test_quarantined_slot_drops_draft_state_and_retries(self):
        """A NaN'd slot mid-speculation: drafts die with the KV rows,
        the victim re-admits with a fresh table and decodes the SAME
        ids; the healthy drafting neighbour never notices."""
        plan = FaultPlan([FaultEvent(1, "nan", slot=0)])
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2, seed=0,
                           paranoid=True, fault_plan=plan,
                           spec_draft_len=4)
        victim = eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 9))
        healthy = eng.submit(Request([5, 2, 5, 2], 9))
        res = eng.run()
        assert eng.stats["quarantined"] == 1
        assert res[victim].retries == 1
        assert res[victim].tokens == _solo_generate(
            [1, 2, 3, 1, 2, 3, 1], 9)
        assert res[healthy].tokens == _solo_generate([5, 2, 5, 2], 9)
        assert eng.spec.slots() == []

    def test_deadline_mid_speculation_returns_exact_partial(self):
        from deeplearning4j_tpu.serving import ManualClock

        clock = ManualClock()
        eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                           clock=clock, spec_draft_len=4, seed=0)
        doomed = eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 40,
                                    deadline_s=5.0))
        res = eng.step()
        clock.advance(10.0)
        while eng.has_work():
            eng.step(res)
        assert res[doomed].finish_reason == "deadline"
        n = len(res[doomed].tokens)
        assert 0 < n < 40
        assert res[doomed].tokens == _solo_generate(
            [1, 2, 3, 1, 2, 3, 1], 40)[:n]
        assert eng.spec.slots() == []

    def test_tracer_counters_mirror_spec_stats(self):
        tracer = Tracer()
        eng = DecodeEngine(_net(), n_slots=1, decode_chunk=2, seed=0,
                           spec_draft_len=4, tracer=tracer)
        eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 10))
        eng.run()
        latest = tracer.latest_counters()
        assert latest["serving_spec_drafted"] == eng.stats[
            "spec_drafted"] > 0
        assert latest["serving_spec_accepted"] == eng.stats[
            "spec_accepted"]
        assert 0.0 <= latest["serving_spec_accept_rate"] <= 1.0
        assert latest["serving_spec_draft_len"] >= 1


class TestSpecSnapshotRestore:
    # long enough that the speculative engine (which commits
    # chunk + accepted + 1 per round) still has live slots when the
    # chaos plan's later events fire
    CASES = [([1, 2, 3, 1, 2, 3, 1], 20), ([5, 2, 5, 2, 5], 24),
             ([9, 3, 3], 16), ([2, 2], 18), ([1, 4, 7, 2], 15)]

    def _build(self, plan=None):
        return DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                            prefix_cache_rows=4, prefill_chunk=4,
                            admission_policy="decode", seed=0,
                            paranoid=plan is not None,
                            fault_plan=plan, max_retries=3,
                            spec_draft_len=4)

    def test_snapshot_round_trips_spec_state(self):
        eng = self._build()
        eng.scheduler.draft_len = 2         # as if adaptation stepped
        eng.submit(Request([1, 2, 3, 1, 2, 3, 1], 9))
        res = {}
        while not any(s is not None for s in eng._slots):
            eng.step(res)                   # finish chunked admission
        snap = json.loads(json.dumps(eng.snapshot()))  # wire format
        assert snap["config"]["spec_draft_len"] == 4
        eng2 = DecodeEngine.restore(_net(), snap)
        assert eng2.spec_draft_len == 4
        assert eng2.scheduler.draft_len == 2
        assert eng2.spec.slots()            # table rebuilt from ids

    def test_mid_run_restore_finishes_identical_ids(self):
        """ISSUE 4 satellite: crash mid-speculation, restore in a
        fresh engine, and the union of results is bit-identical —
        draft tables rebuild deterministically from recorded ids."""
        ref_eng = self._build()
        ref_ids = [ref_eng.submit(Request(p, n)) for p, n in self.CASES]
        ref = ref_eng.run()
        eng = self._build()
        ids = [eng.submit(Request(p, n)) for p, n in self.CASES]
        res = {}
        for _ in range(3):
            eng.step(res)
        assert eng.has_work()
        snap = json.loads(json.dumps(eng.snapshot()))
        eng2 = DecodeEngine.restore(_net(), snap)
        res.update(eng2.run())
        for rid, ref_rid in zip(ids, ref_ids):
            assert res[rid].tokens == ref[ref_rid].tokens, (
                f"request {rid} diverged across spec snapshot/restore")
        assert (eng.stats["spec_rounds"] + eng2.stats["spec_rounds"]
                > 0)

    def test_chaos_parity_under_speculation(self, assert_no_retrace):
        """The extended chaos gate: the 3-subsystem FaultPlan plus a
        mid-run crash/restore on a chunked + prefix-cached + paranoid
        + SPECULATIVE engine still finishes every non-victim request
        bit-identical to the fault-free spec-off reference, within the
        PR 3 compile budget plus only the verify buckets."""
        ref_eng = DecodeEngine(_net(), n_slots=2, decode_chunk=2,
                               prefix_cache_rows=4, prefill_chunk=4,
                               admission_policy="decode", seed=0)
        ref_ids = [ref_eng.submit(Request(p, n)) for p, n in self.CASES]
        ref = ref_eng.run()

        plan = FaultPlan([FaultEvent(2, "nan", slot=0),
                          FaultEvent(3, "admit_fail"),
                          FaultEvent(4, "cache_corrupt"),
                          FaultEvent(6, "nan", slot=1)])
        eng = self._build(plan)
        ids = [eng.submit(Request(p, n)) for p, n in self.CASES]
        res = {}
        for _ in range(8):
            eng.step(res)
        assert len(plan.injected) >= 3
        snap = json.loads(json.dumps(eng.snapshot()))

        eng2 = DecodeEngine.restore(_net(), snap)
        res.update(eng2.run())
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
                "the fault-free spec-off run")
        assert n_victims >= 1
        for counts in (eng.compile_counts(), eng2.compile_counts()):
            assert counts["paged_scatter"] == 1
            assert counts["paged_tok"] == 1
            assert counts["health_check"] == 1
            assert counts["decode"] <= 1
            # a cold row's continuation, a warm table's
            assert 1 <= counts["chunk_prefill"] <= 2
            assert 1 <= counts["verify"] <= 3   # pow2 buckets of K=4
        # a warmed restored engine never retraces under churn
        with assert_no_retrace(eng2):
            more = [eng2.submit(Request(p, n))
                    for p, n in self.CASES[:2]]
            res2 = eng2.run()
        assert all(res2[m].finish_reason in ("length", "eos")
                   for m in more)
