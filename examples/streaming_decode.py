"""Autoregressive streaming decode on the transformer flagship.

Trains a tiny causal LM on a repeating token pattern, then decodes it
three ways, fastest first:

1. **Fused ``generate()``** — ONE jitted ``lax.scan`` emits every token
   with the fixed-size KV cache (`MultiHeadSelfAttention.stream_max_t`)
   riding in the scan carry; no host round-trip per token. This is the
   serving-throughput path (an earlier round's bench.py ``decode_tokens_per_sec``).
2. **One ``rnn_time_step`` step** — the per-token path (the reference's
   rnnTimeStep serving contract, extended to attention), kept as a
   parity check that the fused scan streams the same computation.
3. **``serving.DecodeEngine``** — several concurrent requests share one
   compiled batched decode step over a pool of KV-cache slots
   (continuous batching); each request's greedy ids are identical to
   its own solo ``generate()`` call.
4. **Warm admission** — the same engine family with the radix prefix
   cache + chunked prefill (``prefix_cache_rows``/``prefill_chunk``):
   requests sharing a system prompt admit by fetching the cached
   prefix KV state and prefilling only their suffix, in chunks
   interleaved with decode rounds — same greedy ids, a fraction of the
   prefill work (the counters printed at the end show the reuse).
5. **Self-speculative decoding** (``spec_draft_len=K``) — each slot's
   host-side n-gram table proposes the next K tokens from its own
   prompt+output history, ONE batched verify pass scores every slot's
   draft, and accepted tokens ride the round's weight read for free —
   same greedy ids, more tokens per round (the per-request acceptance
   counters printed at the end show how often the free drafts were
   right; this trained pattern-following LM accepts nearly all of
   them).
6. **Fused multi-round decode** (``fused_rounds=K``) — whenever no
   admission/deadline/draft decision is pending, the engine dispatches
   ONE jitted K-round scan instead of K per-round steps: streamed
   deltas arrive ``K * decode_chunk`` tokens at a time (watch the
   delta batch sizes printed below) and greedy ids stay identical to
   the stepped engine — same computation, 1/K the host round-trips.
7. **Tiered KV cache** (``kv_host_tier_bytes``) — the paged engine
   under trie pressure: when another admission EVICTS a warmed
   prefix, its packed payload spills to a budgeted host-DRAM LRU
   instead of being recomputed from scratch on the next visit — the
   reload re-imports through the same jitted scatter a fleet KV
   transfer uses and re-seeds the trie, greedy ids identical to the
   cold run (the cold-vs-reload admission walls printed below show
   the gap; at chip scale the bench row gates it at >= 2x, the
   ISSUE 14 wire-transfer sibling of the same payload measured
   5.8x vs recompute).
8. **Tensor-parallel sharding** (``tp=2``) — the same paged engine
   sharded over attention heads: decode/verify/chunk run as
   ``shard_map`` programs, each shard holds HALF the KV bytes behind
   the SAME host block tables, and greedy ids stay identical to the
   single-chip engine (the per-shard block/byte counters printed at
   the end show the total/TP split).

Run: python examples/streaming_decode.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# step 6 (tensor-parallel) wants >= 2 devices; on a CPU host that
# means virtual XLA devices, declared BEFORE jax initializes
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

if os.environ.get("DL4J_EXAMPLES_PLATFORM", "native") == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

VOCAB = 8
PATTERN = [1, 3, 5, 7, 2, 4, 6, 0]  # the LM learns to continue this


def one_hot_seq(ids):
    x = np.zeros((1, VOCAB, len(ids)), np.float32)
    x[0, ids, np.arange(len(ids))] = 1.0
    return x


def main():
    net = MultiLayerNetwork(transformer_lm(
        n_in=VOCAB, width=32, n_layers=2, n_heads=4, n_classes=VOCAB,
        lr=5e-3, seed=1)).init()

    seq = (PATTERN * 6)[:40]
    x = one_hot_seq(seq[:-1])
    y = one_hot_seq(seq[1:])
    for step in range(400):
        net.fit(DataSet(x, y))
    print(f"train loss {float(net.score_value):.4f}")

    # Fused decode: prefill the prompt, then ONE jitted scan emits all
    # 16 tokens (the per-token loop pays one host round trip per
    # token instead).
    prompt = PATTERN[:3]
    net.rnn_clear_previous_state()
    generated = np.asarray(net.generate(one_hot_seq(prompt), 16))[0].tolist()
    expected = [PATTERN[(3 + i) % len(PATTERN)] for i in range(16)]
    print("prompt   :", prompt)
    print("generated:", generated)
    print("expected :", expected)
    print("match    :", generated == expected)

    # Parity check: ONE per-token rnn_time_step must produce the same
    # next id the fused scan produced — same computation, different
    # dispatch granularity.
    net.rnn_clear_previous_state()
    out = net.rnn_time_step(one_hot_seq(prompt))
    tok0 = int(np.asarray(out)[0, :, -1].argmax())
    out = net.rnn_time_step(one_hot_seq([tok0]))
    tok1 = int(np.asarray(out)[0, :, 0].argmax())
    print("per-token step parity:", [tok0, tok1] == generated[:2])

    # Continuous batching: the engine multiplexes several requests
    # (ragged prompts, ragged decode lengths) onto one compiled batched
    # decode step over 4 KV-cache slots. Greedy ids per request are
    # identical to a solo generate() of the same prompt.
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    engine = DecodeEngine(net, n_slots=4, decode_chunk=4)
    reqs = {
        engine.submit(Request(prompt=PATTERN[:k], max_new_tokens=n)): k
        for k, n in [(3, 16), (5, 8), (2, 12), (4, 10), (6, 6)]
    }
    results = engine.run()
    ok = True
    for rid, result in sorted(results.items()):
        k = reqs[rid]
        net.rnn_clear_previous_state()
        solo = np.asarray(net.generate(
            one_hot_seq(PATTERN[:k]), len(result.tokens)))[0].tolist()
        ok &= result.tokens == solo
        print(f"engine req {rid} (prompt {k} toks): {result.tokens}")
    print("engine == solo generate per request:", ok)
    print("engine compile counts:", engine.compile_counts())

    # Shared-system-prompt serving: every request carries the same
    # long "system prompt" followed by a short user-specific tail —
    # the workload the radix prefix cache exists for. The first
    # admission prefills the whole prompt (cold, in chunks between
    # decode rounds so neighbours never stall); every later admission
    # splices the shared prefix's KV blocks from the cache and prefills
    # ONLY its tail. Greedy ids stay identical to solo generate().
    warm = DecodeEngine(net, n_slots=4, decode_chunk=4,
                        prefix_cache_rows=4, prefill_chunk=8)
    system_prompt = (PATTERN * 3)[:20]
    tails = [[t] for t in range(5)] + [[2, 4], [6, 0, 1]]
    warm_reqs = {
        warm.submit(Request(prompt=system_prompt + tail,
                            max_new_tokens=8)): tail
        for tail in tails
    }
    warm_results = warm.run()
    ok = True
    for rid, result in sorted(warm_results.items()):
        prompt = system_prompt + warm_reqs[rid]
        net.rnn_clear_previous_state()
        solo = np.asarray(net.generate(
            one_hot_seq(prompt), 8))[0].tolist()
        ok &= result.tokens == solo
        print(f"warm req {rid} (tail {warm_reqs[rid]}): reused "
              f"{result.prefix_tokens_reused}/{len(prompt)} prompt "
              f"tokens, ttft {result.ttft_s * 1e3:.1f} ms")
    print("warm engine == solo generate per request:", ok)
    stats = warm.prefix_cache.stats
    total_prompt = sum(len(system_prompt) + len(t) for t in tails)
    print(f"prefix cache: {stats['hits']} hits / "
          f"{stats['misses']} misses, "
          f"{warm.stats['prefill_tokens_skipped']}/{total_prompt} "
          "prompt tokens served from cache")
    print("warm compile counts:", warm.compile_counts())

    # Self-speculative decoding: the trained LM continues the pattern,
    # and the pattern is in every slot's own history — so the n-gram
    # draft tables predict the model's next K tokens almost perfectly
    # and the batched verify pass commits them at one weight read per
    # round. Greedy ids stay identical to solo generate(); the
    # acceptance counters show the drafts were (nearly) all free wins.
    spec = DecodeEngine(net, n_slots=4, decode_chunk=4,
                        spec_draft_len=8)
    spec_reqs = {
        spec.submit(Request(prompt=PATTERN[:k], max_new_tokens=n)): k
        for k, n in [(3, 16), (5, 12), (2, 14), (4, 10), (6, 12)]
    }
    spec_results = spec.run()
    ok = True
    for rid, result in sorted(spec_results.items()):
        k = spec_reqs[rid]
        net.rnn_clear_previous_state()
        solo = np.asarray(net.generate(
            one_hot_seq(PATTERN[:k]), len(result.tokens)))[0].tolist()
        ok &= result.tokens == solo
        rate = (result.spec_accepted / result.spec_drafted
                if result.spec_drafted else 0.0)
        print(f"spec req {rid} (prompt {k} toks): accepted "
              f"{result.spec_accepted}/{result.spec_drafted} drafts "
              f"({rate:.0%})")
    print("spec engine == solo generate per request:", ok)
    print(f"spec rounds: {spec.stats['spec_rounds']} speculative / "
          f"{spec.stats['spec_fallback_rounds']} plain, "
          f"{spec.stats['spec_accepted']}/{spec.stats['spec_drafted']}"
          " drafts accepted overall")
    print("spec compile counts:", spec.compile_counts())

    # The KV block pool: the same shared-system-prompt workload with
    # smaller blocks, and the pool's counters printed — slots and the
    # prefix trie
    # share ONE pool of fixed-size token blocks, so a warm hit is a
    # ZERO-COPY block-table splice (refcount bumps, no row copy) and
    # the only device copy sharing ever pays is a copy-on-write of
    # the boundary block when a slot appends past a shared prefix.
    # Greedy ids stay identical to solo generate().
    paged = DecodeEngine(net, n_slots=4, decode_chunk=4,
                         prefix_cache_rows=4, prefill_chunk=8,
                         block_tokens=8)
    paged_reqs = {
        paged.submit(Request(prompt=system_prompt + tail,
                             max_new_tokens=8)): tail
        for tail in tails
    }
    paged_results = paged.run()
    ok = True
    for rid, result in sorted(paged_results.items()):
        prompt = system_prompt + paged_reqs[rid]
        net.rnn_clear_previous_state()
        solo = np.asarray(net.generate(
            one_hot_seq(prompt), 8))[0].tolist()
        ok &= result.tokens == solo
        print(f"paged req {rid} (tail {paged_reqs[rid]}): reused "
              f"{result.prefix_tokens_reused}/{len(prompt)} prompt "
              "tokens")
    print("paged engine == solo generate per request:", ok)
    print(f"block pool: {paged.kv_blocks} x {paged.block_tokens}-token"
          f" blocks; {paged.stats['prefix_blocks_spliced']} blocks "
          f"spliced zero-copy, {paged.stats['cow_copies']} "
          f"copy-on-write block copies, "
          f"{paged.stats['blocks_used']} blocks held by the trie "
          f"when idle, fragmentation "
          f"{paged.stats['frag_tokens']} tokens")
    print("paged compile counts:", paged.compile_counts())

    # Fused multi-round decode (ISSUE 16): the continuous-batching
    # workload again with fused_rounds=4 — once the queue drains, each
    # dispatch is ONE on-device scan over up to 4 decode rounds, so
    # streamed deltas land 16 tokens (4 rounds x decode_chunk=4) at a
    # time instead of 4, and every greedy id matches the stepped
    # engine's from step 3.
    fused = DecodeEngine(net, n_slots=4, decode_chunk=4,
                         fused_rounds=4, emit_deltas=True)
    fused_reqs = {
        fused.submit(Request(prompt=PATTERN[:k], max_new_tokens=n)): k
        for k, n in [(3, 16), (5, 8), (2, 12), (4, 10), (6, 6)]
    }
    fused_results = {}
    delta_batches = {}
    while fused.has_work():
        fused.step(fused_results)
        for rid, toks in fused.drain_deltas().items():
            delta_batches.setdefault(rid, []).append(len(toks))
    ok = all(
        fused_results[frid].tokens == results[rid].tokens
        for frid, rid in zip(sorted(fused_results), sorted(results)))
    print("fused engine == stepped engine per request:", ok)
    for rid in sorted(delta_batches):
        print(f"fused req {rid} (prompt {fused_reqs[rid]} toks): "
              f"delta batches {delta_batches[rid]}")
    print("fused compile counts:", fused.compile_counts())

    # Tiered KV cache (ISSUE 17): a 2-row trie under admission
    # pressure — every third prompt EVICTS the oldest warmed prefix.
    # Pre-tier, revisiting an evicted prefix recomputed its whole
    # prefill; with the host tier armed, the victim's packed blocks
    # spill to DRAM at eviction (async gather, host pack deferred to
    # the step tail) and the revisit re-imports them through the
    # jitted kv_import scatter instead. Greedy ids stay identical
    # either way — the tier only moves the admission wall.
    tier = DecodeEngine(net, n_slots=2, decode_chunk=4,
                        prefix_cache_rows=2, prefill_chunk=8,
                        block_tokens=8, kv_host_tier_bytes=1 << 20)
    long_prompt = (PATTERN * 4)[:30]

    def tier_admit(prompt):
        rid = tier.submit(Request(prompt=list(prompt),
                                  max_new_tokens=6))
        return tier.run()[rid]

    # warm-up cycle: the engine's first admission compiles the
    # prefill executables and the first reload compiles the import
    # bucket — excluded from the walls printed below, like every
    # post-warmup measurement in this repo
    tier_admit(long_prompt)
    tier_admit([2] * 12)                  # two fresh prompts overflow
    tier_admit([4] * 12)                  # the 2-row trie: the LRU
    #                                       victim spills to host DRAM
    tier_admit(long_prompt)               # first reload
    # measured cycle, all executables warm: a SECOND long prompt pays
    # the full chunked prefill cold, is evicted by the same pressure,
    # and comes back as a host-DRAM reload
    long_prompt2 = ((PATTERN[1:] + PATTERN[:1]) * 4)[:30]
    cold = tier_admit(long_prompt2)       # full chunked prefill
    tier_admit([2] * 12)
    tier_admit([4] * 12)                  # evicts + spills it again
    reloaded = tier_admit(long_prompt2)   # steady-state reload
    net.rnn_clear_previous_state()
    solo = np.asarray(net.generate(
        one_hot_seq(long_prompt2), 6))[0].tolist()
    print("tier reload == cold run == solo generate:",
          reloaded.tokens == cold.tokens == solo)
    print(f"tier admission wall: cold {cold.ttft_s * 1e3:.1f} ms -> "
          f"host-DRAM reload {reloaded.ttft_s * 1e3:.1f} ms "
          f"({cold.ttft_s / max(reloaded.ttft_s, 1e-9):.1f}x on this "
          "toy net; bench_kv_tier gates >= 2x at thrash scale, the "
          "ISSUE 14 wire sibling measured 5.8x vs recompute)")
    ts = tier.kv_tier.stats
    print(f"tier stats: {ts['spills']} spills, {ts['reloads']} "
          f"reloads, {ts['drops']} drops, {len(tier.kv_tier)} "
          f"resident ({tier.kv_tier.host_bytes} bytes of "
          f"{1 << 20}-byte budget)")
    print("tier compile counts:", tier.compile_counts())

    # Tensor-parallel sharded decode (ISSUE 12): the paged engine
    # again, sharded 2-ways over attention heads. The host block
    # tables, refcounts, and trie are LAYOUT-INVARIANT — only the
    # device bytes split — so the same warm-admission workload runs
    # unchanged and every greedy id matches the single-chip run above.
    import jax as _jax

    if len(_jax.devices()) < 2:
        print("tp: skipped (needs >= 2 devices)")
        return
    tp_eng = DecodeEngine(net, n_slots=4, decode_chunk=4,
                          prefix_cache_rows=4, prefill_chunk=8,
                          block_tokens=8, tp=2)
    tp_reqs = {
        tp_eng.submit(Request(prompt=system_prompt + tail,
                              max_new_tokens=8)): tail
        for tail in tails
    }
    tp_results = tp_eng.run()
    ok = all(tp_results[rid].tokens == paged_results[prid].tokens
             for rid, prid in zip(sorted(tp_results),
                                  sorted(paged_results)))
    print("tp=2 engine == single-chip paged engine:", ok)
    shard_bytes = tp_eng.kv_shard_bytes()
    for shard in sorted(shard_bytes):
        print(f"  shard {shard}: {tp_eng.stats['blocks_used']} pool "
              f"blocks held ({tp_eng.stats['blocks_free']} free), "
              f"{shard_bytes[shard]} KV bytes "
              "(= total/2 — head-sliced)")
    print("tp compile counts:", tp_eng.compile_counts())


if __name__ == "__main__":
    main()
