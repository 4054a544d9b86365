"""serving/kv_memory.py: the kinds of a net's declared caches, their
pools and a slot's tables, alone (host arrays, no net) and through an
engine that names none of them.

(a) the memory alone, for a full kind, a window kind and an aligned
    kind beside a chunked-entry kind: the block counts that
    ``test_afmoe_lm.py`` and ``test_evabyte_lm.py`` pin through a whole
    engine;
(b) a toy bean's cache, which no model here has, served its tables;
(c) the arrows point one way: ``engine.py`` names no mixer,
    ``kv_memory.py`` does not import the engine;
(d) every key of ``engine.stats`` for the four served cells, as written
    from the parent (PR 44's ``ff8e6fc``).
"""

import dataclasses
import os
import re

import numpy as np
import pytest

from benchmark import common
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.conf.layers import PagedCache
from deeplearning4j_tpu.nn.layers import attention as att
from deeplearning4j_tpu.nn.layers import eva, register_impl
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import DecodeEngine, Request
from deeplearning4j_tpu.serving.kv_memory import KvMemory, unpack_tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVING = os.path.join(ROOT, "deeplearning4j_tpu", "serving")


# -- (a) the memory alone -------------------------------------------------
def full(window, **kw):
    return (PagedCache(window, token_width=8, **kw),)


#: declared caches (layer -> caches), block_tokens, an admission's chunk
CASES = {
    # every layer agrees: one kind, nothing ever released inside it
    "full": ({"0": full(128), "1": full(128)}, 8, 16),
    # test_afmoe_lm's net: layer 3 reads the whole context, the others
    # a window of 32
    "window": ({"1": full(32), "2": full(32), "3": full(128),
                "4": full(32)}, 8, 16),
    # test_evabyte_lm's: window 32 aligned, a summary a chunk of 4
    "aligned": ({name: eva.paged_caches(window=32, chunk=4, longest=160,
                                        token_width=8)
                 for name in "123"}, 4, 16),
}


def memory(case, n_slots=2, **kw):
    declared, bt, chunk = CASES[case]
    mem = KvMemory(list(declared.items()), bt)
    stats = {}
    mem.size(kv_blocks=None, n_slots=n_slots, round_write=5,
             dispatch=mem.wmax if len(mem.kinds) == 1 else chunk,
             decode_steps=4, stats=stats, **kw)
    row = np.zeros((1, 2, 1, 4), np.float32)      # [1, H, W, dh]
    mem.make_pool({name: {"k": row, "v": row} for name in declared}, None)
    return mem, stats


def grow(mem, tab, n, chunk):
    """A sequence's ``n`` more tokens through its tables, the engine's
    way: blocks before the write, the expired ones after it."""
    assert mem.ensure(tab, n)
    tabs = mem.pack([tab], chunk=chunk, tokens=n)
    tab.length += n
    mem.expire(tab)
    return tabs


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_sequences_blocks_by_kind(case):
    declared, bt, chunk = CASES[case]
    mem, stats = memory(case)
    tab = mem.new_table()
    most = [0] * len(mem.kinds)
    for n in [chunk] * 5 + [10] + [4] * 3:       # a prompt of 90, 12 more
        tabs = grow(mem, tab, n, chunk if n > 4 else 1)
        most = [max(m, len(t.blocks)) for m, t in zip(most, tab.kinds)]
    assert tab.length == 102
    rings = [k.ring for k in mem.kinds]
    assert tabs.shape == (1, 2 * sum(rings) + len(rings) + 1)
    assert stats["table_uploads"] == 9
    spans = -(-tab.length // bt)
    if case == "full":
        (kind,) = mem.kinds
        assert kind.layers == ["0", "1"] and mem.wmax == 128
        assert most == [spans] and kind.expired == 0
    elif case == "window":
        wide, narrow = mem.kinds
        assert (wide.window, narrow.window) == (128, 32)
        assert wide.layers == ["3"] and narrow.layers == ["1", "2", "4"]
        # never more than the window, one dispatch's writes and slack;
        # the wide kind holds the whole context
        assert most[1] <= -(-32 // bt) + chunk // bt + 2
        assert most[0] == spans and narrow.expired > 0 == wide.expired
    else:
        summary, window = mem.kinds
        assert (summary.span, window.span) == (4 * 4, 4)
        assert (summary.leaves, window.aligned) == (("sk", "sv"), True)
        s, w = tab.kinds
        floor = tab.length // 32 * 32
        # the window's kind holds nothing below the aligned floor once
        # a round has ended, and everything from it up; a summary block
        # a ``C`` chunks, none ever released
        assert min(w.blocks) * 4 >= floor
        assert all(g in w.blocks for g in range(floor // 4, spans))
        assert all(g in s.blocks for g in range(-(-tab.length // 16)))
        assert window.expired == 3 * (32 // 4) and summary.expired == 0
        mem.refresh_stats()
        assert stats["eva_window_blocks_released"] == window.expired
        assert stats["eva_window_blocks_allocated"] == window.allocated
        assert stats["eva_summary_entries_read"] > 0
        assert stats["prefill_eva_window_pairs_scored"] > 0
    used = [k.pool.used_blocks for k in mem.kinds]
    assert used == [len(t.blocks) for t in tab.kinds]
    mem.refresh_stats(admitting=[tab])
    assert stats["blocks_used"] == sum(used)
    mem.free(tab)
    assert all(k.pool.used_blocks == 0 for k in mem.kinds)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_packed_operand_is_the_tables(case):
    mem, stats = memory(case)
    tab = mem.new_table()
    grow(mem, tab, 16, 16)
    mem.tabs[1] = tab                             # slot 0 idle
    packed = np.asarray(mem.pack(mem.tabs))
    for kind, t, rows in zip(mem.kinds, tab.kinds, unpack_tables(
            packed, [k.ring for k in mem.kinds])):
        table, base = t.arrays(kind.ring)
        assert (rows["table"][1] == table).all()
        assert (rows["base"][1] == base).all()
        assert rows["filled"][1] == 16 and rows["floor"][1] == 0
        assert (rows["table"][0] == -1).all() and rows["filled"][0] == 0
    mem.count_held([1])
    for kind in mem.kinds:
        assert stats[f"kv_blocks_spanned_w{kind.window}"] == -(
            -16 // kind.span)


def test_a_full_pool_asks_for_relief_and_says_when_none_came():
    asked = []

    def relieve(n, protect, kind):
        asked.append((n, kind.window))
        return kind.pool.free_blocks >= n

    declared, bt, _ = CASES["full"]
    mem = KvMemory(list(declared.items()), bt)
    mem.size(kv_blocks=24, n_slots=1, dispatch=128, round_write=5,
             stats={}, relieve=relieve)
    tab = mem.new_table()
    assert mem.ensure(tab, 128) and asked == [(16, 128)]
    tab.length += 128
    other = mem.new_table()
    assert not mem.ensure(other, 128) and not other.blocks
    assert mem.cover(200) is None
    mem.free(tab)
    (covered,) = mem.cover(200).kinds             # the last 128 of 200
    assert (covered.floor, covered.length) == (72, 200)
    assert sorted(covered.blocks) == list(range(72 // 8, 200 // 8))


@pytest.mark.parametrize("option,kw", [
    ("block_tokens", dict(block_tokens=6, prefill_chunk=16)),
    ("prefill_chunk", dict(block_tokens=4, prefill_chunk=24)),
    ("prefill_chunk", dict(block_tokens=4, prefill_chunk=0)),
])
def test_sizes_the_declared_caches_cannot_have(option, kw):
    mem = KvMemory(list(CASES["aligned"][0].items()), kw["block_tokens"])
    assert [m[0] for m in mem.misfits(kw["prefill_chunk"])] == [option]
    assert not list(KvMemory(list(CASES["aligned"][0].items()),
                             4).misfits(16))


def test_layers_of_several_caches_are_served_where_all_agree():
    two = eva.paged_caches(window=32, chunk=4, longest=160, token_width=8)
    with pytest.raises(ValueError, match="several paged caches"):
        KvMemory([("1", two), ("2", full(160))], 4)
    with pytest.raises(ValueError, match="must pass"):
        eva.paged_caches(window=32, chunk=4, longest=32, token_width=8)


# -- (b) a cache no model here has ----------------------------------------
class ToyBlock(att.MultiHeadSelfAttention):
    """Besides its attention cache, an aligned window of 16 tokens under
    tables and leaves of its own (which its program does not read)."""

    def serving_caches(self):
        return (att.attention_cache(self),
                PagedCache(16, aligned=True, token_width=32,
                           leaves=("wk", "wv"),
                           operands=("wtable", "wbase"), name="toy_window"))


class ToyBlockImpl(att.AttentionImpl):
    SAW = []

    @classmethod
    def apply(cls, conf, params, x, state=None, **kw):
        own = ("wk", "wv", "wtable", "wbase")
        held = {k: state[k] for k in own if state and k in state}
        if held:
            cls.SAW.append({k: v.shape for k, v in held.items()})
            state = {k: v for k, v in state.items() if k not in held}
        out, new = super().apply(conf, params, x, state=state, **kw)
        return out, dict(new, **held) if held else new


register_impl(ToyBlock, ToyBlockImpl)


def _lm(toy: bool):
    conf = transformer_lm(n_in=12, width=32, n_layers=2, n_heads=4,
                          n_classes=12, seed=7)
    for c in conf.confs:
        if isinstance(c.layer, att.MultiHeadSelfAttention):
            c.layer.stream_max_t = 64
            if toy:
                c.layer = ToyBlock(**{
                    f.name: getattr(c.layer, f.name)
                    for f in dataclasses.fields(c.layer)})
    return MultiLayerNetwork(conf).init()


def test_a_toy_beans_cache_is_served_its_tables():
    reqs = [([1, 4, 7, 2, 5, 9, 3, 8, 6, 0, 2], 30), ([9, 3, 3], 6)]
    served = []
    for toy in (False, True):
        eng = DecodeEngine(_lm(toy), n_slots=2, decode_chunk=4, seed=0,
                           block_tokens=8, prefill_chunk=8 if toy else 0)
        ids = [eng.submit(Request(p, n)) for p, n in reqs]
        res = eng.run()
        served.append([res[i].tokens for i in ids])
    assert served[0] == served[1]
    full_kind, toy_kind = eng.kv.kinds
    assert (toy_kind.window, toy_kind.aligned) == (16, True)
    assert toy_kind.layers == full_kind.layers and len(toy_kind.layers) == 2
    # every program handed the layer its second tables and took its
    # leaves back, an admission's chunk and a decode round
    shapes = {(s["wtable"], s["wk"]) for s in ToyBlockImpl.SAW}
    assert shapes == {
        ((b, toy_kind.ring), (toy_kind.pool.n_blocks, 8, 4, 8))
        for b in (1, 2)}
    assert set(eng._pool[toy_kind.layers[0]]) == {"pk", "pv", "wk", "wv"}
    # the row of 41 tokens crossed the window's end twice
    assert eng.stats["toy_window_blocks_released"] >= 2 * (16 // 8)
    assert (eng.stats["toy_window_blocks_allocated"]
            > eng.stats["toy_window_blocks_released"])
    assert eng.stats["paged_blocks_live_w16"] > 0
    assert all(k.pool.used_blocks == 0 for k in eng.kv.kinds)
    with open(os.path.join(SERVING, "engine.py")) as f:
        assert "toy" not in f.read().lower()


# -- (c) the arrows point one way -----------------------------------------
def test_the_engine_names_no_mixer_and_the_memory_no_engine():
    with open(os.path.join(SERVING, "engine.py")) as f:
        engine = f.read()
    named = [line for line in engine.splitlines()
             if re.search(r"\beva\b|eva_|_eva|mamba|short_conv", line, re.I)]
    assert not named, named
    for gone in ("serving_state", "_KvKind", "kind.aligned", "BlockPool("):
        assert gone not in engine, gone
    with open(os.path.join(SERVING, "kv_memory.py")) as f:
        memory_src = f.read()
    assert not re.search(r"serving(\.| import )engine", memory_src)
    with open(os.path.join(SERVING, "block_pool.py")) as f:
        assert "kv_memory" not in f.read()


# -- (d) every key of engine.stats, from the parent -----------------------
#: ``sorted(DecodeEngine(...).stats)`` at PR 44's commit (ff8e6fc), for
#: a net of one kind; a net of two kinds has the second's four as well
PARENT_KEYS = """admitted blocks_free blocks_used cancelled chunks
chunks_scheduled cow_copies deadline_expired decode_time_s
eva_summaries_written eva_summary_entries_read eva_summary_pairs_scored
eva_window_blocks_allocated eva_window_blocks_released
eva_window_entries_read eva_window_pairs_scored evicted faults_detected
faults_injected frag_tokens kv_bytes_per_token kv_dtype_bytes kv_exported_tokens
kv_exports kv_import_declined kv_imported_blocks kv_imported_tokens
kv_imports kv_tier_demotions kv_tier_disk_bytes kv_tier_drops
kv_tier_exports kv_tier_hits_disk kv_tier_hits_host kv_tier_host_bytes
kv_tier_reload_declined kv_tier_reload_faults kv_tier_reloads
kv_tier_spill_skipped kv_tier_spills moe_experts_touched moe_layer_steps
moe_load_max moe_picks moe_picks_held occupancy_sum paged_admit_deferred
paged_blocks_live paged_blocks_per_step paged_blocks_walked
paged_steps_paid paged_steps_per_row param_bytes param_bytes_cast
preempted prefill_eva_summaries_written prefill_eva_summary_entries_read
prefill_eva_summary_pairs_scored prefill_eva_window_entries_read
prefill_eva_window_pairs_scored prefill_moe_experts_touched
prefill_moe_layer_steps prefill_moe_load_max prefill_moe_picks
prefill_moe_picks_held prefill_ssm_state_rows prefill_tokens
prefill_tokens_skipped prefix_blocks_spliced qos_preempted quarantined
queue_timeouts requests_finished retries retry_failures shed slow_steps
spec_accepted spec_drafted spec_fallback_rounds spec_rounds ssm_state_rows
table_uploads tokens_generated""".split()
BY_KIND = ("kv_blocks_held", "kv_blocks_spanned", "paged_blocks_live",
           "prefill_paged_blocks_live")


@pytest.mark.parametrize("cell,windows", [
    ("cgpt1p3b-serve.chat-steady", (128,)),
    ("granite4hs-serve.chat-steady-g4hs", (128,)),
    ("trinity-large-serve.docs-mixed-tlp", (128, 32)),
    ("evabyte-serve.docs-batch-eva", (128, 32)),
])
def test_every_key_of_the_stats_is_the_parents(cell, windows):
    _, cfg, _, model = common.find_cell(common.load_benchmark(), cell, True)
    dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
    dep["use_flash_paged"] = False     # (the keys are the same; quicker)
    eng = DecodeEngine(model.build_net(cfg, 5), seed=5, **dep)
    assert [k.window for k in eng.kv.kinds] == list(windows)
    rng = np.random.default_rng(3)
    ids = [eng.submit(Request(rng.integers(0, cfg["vocab_size"], n).tolist(),
                              3)) for n in (9, 5)]
    res = eng.run()
    assert all(len(res[i].tokens) == 3 for i in ids)
    assert sorted(eng.stats) == sorted(PARENT_KEYS + [
        f"{name}_w{w}" for name in BY_KIND for w in windows])
    assert eng.stats["table_uploads"] == (
        eng.stats["chunks"] + (eng.stats["chunks_scheduled"]
                               if len(windows) > 1 else 0))
