"""The serving engine's KV memory: which caches its layers hold, in
which pools, under which block tables, and what a dispatch counts of
them.

A layer DECLARES the caches it pages (``bean.serving_caches()``:
:class:`~deeplearning4j_tpu.nn.conf.layers.PagedCache`). Declared caches
that agree form a KIND: one pool of blocks that no other kind allocates
from (serving/block_pool.py) and one block table a slot, so that a
narrow window's layers hold only what they can still reach. The engine
keeps one :class:`KvMemory` and asks it (``engine -> kv_memory ->
block_pool``, never back); relief under pressure (evict a trie entry,
preempt a slot) is the engine's policy, handed in as a callable. The
device pool lives here, beside the bookkeeping that can move it
(copy-on-write, scrub); the engine's programs take and return it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.layers import PagedCache
from deeplearning4j_tpu.nn.layers import eva
from deeplearning4j_tpu.nn.layers.attention import (
    _paged_blocks_per_step,
    _paged_table_entries,
    paged_walk_stats,
)
from deeplearning4j_tpu.profiler.scopes import scope
from deeplearning4j_tpu.serving.block_pool import (
    BlockPool,
    BlockTable,
    KindTables,
)


def unpack_tables(tabs, rings=None):
    """The four block-table operands of a paged dispatch
    (``AttentionImpl._paged_attend`` says what each holds), a dict a
    layer KIND, out of the ONE int32 array they travel in: for each
    kind ``table`` and ``base`` ``[B, S_k]``, then a ``floor`` column a
    kind, then ONE ``filled`` column, the same for every kind (one
    kind: ``[B, 2 S + 2]``). ``rings`` are the kinds' ring widths
    ``S_k`` (None: one kind, its width read off the shape). Slices of a
    device array inside a program, writable views of a numpy array on
    the host."""
    if rings is None:
        rings = ((tabs.shape[1] - 2) // 2,)
    floors = 2 * sum(rings)
    out, at = [], 0
    for k, s in enumerate(rings):
        out.append({"table": tabs[:, at:at + s],
                    "base": tabs[:, at + s:at + 2 * s],
                    "floor": tabs[:, floors + k],
                    "filled": tabs[:, floors + len(rings)]})
        at += 2 * s
    return out


@dataclasses.dataclass
class KvKind:
    """The declared caches that agree (one :class:`PagedCache` of each
    of ``layers``): they share a block table a slot, and a pool of
    blocks. ``ring`` is the table's ring width, ``slot_worst`` the most
    blocks one slot can hold of it, ``span`` the tokens one block
    covers (``block_tokens`` entries of ``entry_tokens``); the rest is
    the declaration's, the first layer's where they may differ."""

    window: int
    layers: List[str]
    entry_tokens: int = 1
    aligned: bool = False
    token_width: int = 0
    group: int = 1
    leaves: Tuple[str, str] = ("pk", "pv")
    operands: Tuple[str, str] = ("table", "base")
    name: str = ""
    reads: Optional[Callable] = None
    span: int = 0
    ring: int = 0
    slot_worst: int = 0
    pool: Optional[BlockPool] = None
    #: blocks allocated, and those released because their row left them
    #: behind (its window slid past, or crossed its aligned end)
    allocated: int = 0
    expired: int = 0


class KvMemory:
    """The kinds of a net's declared caches, widest window first (one
    kind: every net whose layers agree), and, once :meth:`size` has
    made them, their pools, the slots' tables (``tabs``) and the device
    pool (``pool``: ``{layer: {leaf: [blocks, block_tokens, H, dh]}}``,
    made by :meth:`make_pool` at the first admission)."""

    def __init__(self, declared: Sequence[Tuple[str, Tuple[PagedCache, ...]]],
                 block_tokens: int):
        self.block_tokens = int(block_tokens)
        kinds: Dict[tuple, KvKind] = {}
        for name, caches in declared:
            for c in caches:
                key = (c.window, c.entry_tokens, c.aligned, c.leaves,
                       c.operands)
                if key not in kinds:   # (the declaration's fields by name)
                    kinds[key] = KvKind(
                        layers=[], span=self.block_tokens * c.entry_tokens,
                        **vars(c))
                kinds[key].layers.append(name)
        several = [name for name, caches in declared if len(caches) > 1]
        if several and len({caches for _, caches in declared}) > 1:
            raise ValueError(
                f"layers {several} hold several paged caches each; the "
                "engine serves them where every paged layer declares the "
                "same caches (got windows "
                f"{[[c.window for c in cs] for _, cs in declared]})")
        self.kinds: List[KvKind] = sorted(kinds.values(),
                                          key=lambda k: -k.window)
        #: every paged layer, and every pool leaf a layer may hold
        self.layers = {name for name, _ in declared}
        self.leaves = tuple(dict.fromkeys(
            leaf for k in self.kinds for leaf in k.leaves))
        self.wmax = self.kinds[0].window     # the widest kind's window
        self.pool = None

    def misfits(self, prefill_chunk: int):
        """``(option, value, layers, why)`` for each of the two sizes
        that the declared caches cannot have: a chunk is pooled from ONE
        pool block, every query of a dispatch reads one aligned floor."""
        for kind in self.kinds:
            c, w = kind.entry_tokens, kind.window
            if c > 1 and self.block_tokens % c:
                yield ("block_tokens", self.block_tokens, kind.layers,
                       f"an entry's chunk of {c} tokens does not divide "
                       "it: a completed chunk must lie inside one pool "
                       "block")
            if kind.aligned and (prefill_chunk < 1 or w % prefill_chunk):
                yield ("prefill_chunk", prefill_chunk, kind.layers,
                       "an admission goes through the pools in chunks "
                       f"that divide the aligned window {w}, so that a "
                       "chunk never straddles a window's end")

    def size(self, *, kv_blocks: Optional[int], n_slots: int,
             dispatch: int, round_write: int, stats: Dict[str, Any],
             trie_rows: int = 0, decode_steps: int = 1, jit_wrap=None,
             tp_ctx=None, relieve: Optional[Callable] = None,
             span: Optional[Callable] = None) -> None:
        """Size the rings and make the pools: ``kv_blocks`` blocks of
        all kinds together (None: a default), for ``n_slots`` slots, the
        widest single ``dispatch`` and a round's ``round_write`` tokens
        a slot. What the memory counts lands in ``stats``, the engine's
        dict. ``relieve(n, protect, kind)`` makes ``n`` blocks of a
        kind's pool allocatable or says it cannot; ``span`` opens a
        tracer span."""
        bt, longest = self.block_tokens, self.wmax  # the longest prompt
        if bt < 1 or (bt & (bt - 1)):
            raise ValueError(f"block_tokens {bt} must be a power of two")
        if bt > longest:
            raise ValueError(
                f"block_tokens {bt} exceeds the cache window "
                f"({longest}) — a block must fit inside it")
        for kind in self.kinds:
            sp = kind.span
            # ring width: the window, plus the widest single dispatch
            # (a blocking-mode suffix chunk can be a whole window) plus
            # one round's decode/verify writes — sized so a logical
            # block is never recycled while any in-flight query can
            # still reach it (see AttentionImpl._paged_attend)
            kind.ring = (-(-kind.window // sp) + -(-dispatch // sp)
                         + -(-round_write // sp) + 3)
            # one slot's worst-case residency: a full window of
            # blocks, one dispatch of appends, plus boundary slack
            # (the ring width is ADDRESSING span, not occupancy:
            # slid-out blocks free as they expire). A one-kind net's
            # prompts fit its window, so there the dispatch is one
            # round of decode/verify writes; so it is under an ALIGNED
            # window, whose admission chunks never straddle its end and
            # find the window before it released
            kind.slot_worst = (
                -(-kind.window // sp)
                + -(-(round_write if kind.window >= longest
                      or kind.aligned else max(round_write, dispatch))
                    // sp) + 3)
        slot_worst = sum(k.slot_worst for k in self.kinds)
        if kv_blocks is None:
            # default: a whole window for every slot and every trie
            # entry, with per-slot append slack, of every kind
            kv_blocks = max(
                sum(-(-k.window // k.span) for k in self.kinds)
                * (n_slots + int(trie_rows))
                + len(self.kinds) * n_slots * (-(-round_write // bt) + 2),
                slot_worst)
        #: blocks of all kinds together; several kinds share them out
        #: by what a slot can hold of each (``slot_worst``), so that
        #: every kind runs out at the same number of full slots
        self.kv_blocks = int(kv_blocks)
        if self.kv_blocks < slot_worst:
            raise ValueError(
                f"kv_blocks {self.kv_blocks} cannot hold one "
                f"slot's window + one round of writes "
                f"({slot_worst} blocks of {bt} tokens)")
        left = self.kv_blocks
        for i, kind in enumerate(self.kinds):
            n = (left if i == len(self.kinds) - 1 else max(
                kind.slot_worst,
                self.kv_blocks * kind.slot_worst // slot_worst))
            kind.pool = BlockPool(n, kind.span, jit_wrap=jit_wrap)
            left -= n
        self.tabs: List[Optional[KindTables]] = [None] * n_slots
        self._steps, self._tp_ctx = decode_steps, tp_ctx
        self._relieve = relieve or (
            lambda n, protect, kind: kind.pool.free_blocks >= n)
        self._span = span or (lambda name, **a: contextlib.nullcontext())
        self.stats = stats
        stats.update({
            # the pools' gauges (``refresh_stats``; the gateway's
            # /v1/metrics exports them)
            "blocks_free": self.kv_blocks, "blocks_used": 0,
            "cow_copies": 0, "prefix_blocks_spliced": 0,
            "frag_tokens": 0,
            # the paged kernel's walk, summed over dispatches, and its
            # geometry, which lands with the pool (``_count_walk``)
            "paged_blocks_live": 0, "paged_blocks_walked": 0,
            "paged_blocks_per_step": 0, "paged_steps_per_row": 0,
            "paged_steps_paid": 0,
            # the pool's bytes a token over all KV layers and the
            # width of one of its cells; both land with the pool
            "kv_bytes_per_token": 0, "kv_dtype_bytes": 0,
            # host-to-device transfers of the block-table operand: one
            # a dispatch, whatever the number of paged layers
            "table_uploads": 0,
            # by kind (its window): ``_count_walk`` and ``count_held``
            **{f"{name}_w{k.window}": 0 for k in self.kinds
               for name in ("paged_blocks_live",
                            "prefill_paged_blocks_live",
                            "kv_blocks_spanned", "kv_blocks_held")},
            # what a layer counts of its own caches, a named cache's blocks
            **eva.STATS,
            **{f"{k.name}_blocks_{what}": 0 for k in self.kinds if k.name
               for what in ("allocated", "released")}})

    # -- tables: make, grow, shrink -------------------------------------
    def new_table(self) -> KindTables:
        """An empty sequence's tables, one a kind."""
        return KindTables(BlockTable(kind.span) for kind in self.kinds)

    def _alloc(self, kind: KvKind) -> int:
        bid = kind.pool.alloc()
        if bid is None:
            raise AssertionError("reserved allocation failed")
        kind.allocated += 1
        return bid

    def cover(self, length: int) -> Optional[KindTables]:
        """A one-kind net's fresh tables over the last ``min(length,
        wmax)`` positions (what a dense B=1 prefill row holds), blocks
        allocated; None when the pool cannot be relieved."""
        kind, bt = self.kinds[0], self.block_tokens
        floor = max(0, length - self.wmax)
        gs = range(floor // bt, (length - 1) // bt + 1)
        if not self._relieve(len(gs), (), kind):
            return None
        tab = BlockTable(bt, length=length, floor=floor)
        for g in gs:
            tab.blocks[g] = self._alloc(kind)
        return KindTables([tab])

    def splice(self, payload: BlockTable,
               matched: int) -> Tuple[KindTables, int]:
        """Tables over the first ``matched`` tokens of a trie entry
        (``payload``), referencing its blocks, and how many: no gather,
        no row copy. A stored entry rewinds exactly to any shorter
        prefix of itself by referencing only blocks below ``matched``
        (suffix chunks append through the table, copying the boundary
        block on first write if it is still shared)."""
        pool, bt = self.kinds[0].pool, self.block_tokens
        mine = BlockTable(bt, length=matched, floor=payload.floor)
        for g, bid in payload.blocks.items():
            if g * bt < matched and (g + 1) * bt > payload.floor:
                mine.blocks[g] = bid
                pool.ref(bid)
        pool.stats["spliced"] += len(mine.blocks)
        return KindTables([mine]), len(mine.blocks)

    def release(self, bid: int, kind: Optional[KvKind] = None) -> None:
        """Drop one reference to a block of ``kind``'s pool (the
        widest's, a one-kind net's only one, where none is named); a
        block whose LAST reference drops is returned to the free list —
        scrubbed first if the paranoid sweep flagged it (never scrubbed
        while an innocent sharer still reads it; the sweep runs for
        one-kind nets only)."""
        pool = (kind or self.kinds[0]).pool
        if pool.deref(bid):
            if bid in pool.poisoned and self.pool is not None:
                self.pool = pool.scrub_block_device(self.pool, bid)

    def free(self, tab: Optional[KindTables]) -> None:
        if tab is None:
            return
        for kind, t in zip(self.kinds, tab.kinds):
            for bid in list(t.blocks.values()):
                self.release(bid, kind)
            t.blocks.clear()

    def ensure(self, tab: KindTables, n_tokens: int, protect=(),
               rid: Optional[int] = None) -> bool:
        """Make ``tab`` writable for the next ``n_tokens`` appends:
        copy-on-write the partial tail block if the trie or another
        slot still references it (the ONLY device copy sharing ever
        costs — one block, not one row), and allocate the fresh blocks
        the appends will cross into. False = the pool could not be
        relieved (caller defers or preempts).

        Invariant the sizing math rests on: no single append exceeds
        the window (prompts are validated <= window at submit, chunk
        widths are window-clamped), so one append's new blocks always
        fit the ``slot_worst`` floor enforced on ``kv_blocks`` at
        construction — after evicting/preempting everything else a
        lone admission can always proceed (no defer livelock) — and
        one dispatch can never wrap the ring onto itself."""
        for kind, t in zip(self.kinds, tab.kinds):
            # (a block is shared, and so copied on write, in a one-kind
            # net only: the trie is refused to any other)
            pool = kind.pool
            tail = t.tail_block() if n_tokens > 0 else None
            cow = tail is not None and pool.refcount(tail[1]) > 1
            need = len(t.new_logical_blocks(n_tokens)) + (1 if cow else 0)
            if need and not self._relieve(need, protect, kind):
                return False
            if cow:
                g, src = tail
                dst = pool.alloc()
                with self._span("serving.cow_copy", rid=rid, src=src,
                                dst=dst):
                    self.pool = pool.copy_block_device(self.pool, src, dst)
                t.blocks[g] = dst
                self.release(src, kind)
            for g in t.new_logical_blocks(n_tokens):
                old = g - kind.ring
                if old in t.blocks:   # safety: expired ring predecessor
                    self.release(t.blocks.pop(old), kind)
                t.blocks[g] = self._alloc(kind)
        return True

    def expire(self, tab: KindTables) -> None:
        """Release, kind by kind, the blocks that slid entirely out of
        the kind's window, each to its kind's pool (length is monotone
        within a round — the verify rewind lands before this runs — so
        a released block can never swing back into reach)."""
        for kind, t in zip(self.kinds, tab.kinds):
            # (an ALIGNED window's lower edge is the last multiple of
            # the window the row has reached: everything below it goes
            # at once, the round after the row crossed it)
            edge = (t.length // kind.window * kind.window if kind.aligned
                    else t.length - kind.window)
            if edge < kind.span:
                continue    # the context has not left the window yet
            for g in itertools.takewhile(
                    lambda g: (g + 1) * kind.span <= edge,
                    sorted(t.blocks)):
                self.release(t.blocks.pop(g), kind)
                kind.expired += 1

    # -- the device side -------------------------------------------------
    def make_pool(self, rows: Dict[str, Any], computed) -> None:
        """The device pool, from each paged layer's dense B=1 streaming
        state (``rows``: ``{layer: {"k", "v"}}`` ``[1, H, W, dh]``,
        arrays or their shapes): a pair of leaves of each kind the layer
        holds, ``[its kind's blocks, block_tokens, H, dh]``, at the
        dtype the layers compute keys and values in (``computed``, or
        the row's where the net has no compute dtype): a pool cell then
        holds the number the layer made and no zero bits behind it."""
        pool: Dict[str, Dict[str, Any]] = {name: {} for name in rows}
        for kind in self.kinds:
            for name in kind.layers:
                k, v = rows[name]["k"], rows[name]["v"]
                shape = (kind.pool.n_blocks, self.block_tokens,
                         k.shape[1], k.shape[3])
                for leaf, row in zip(kind.leaves, (k, v)):
                    pool[name][leaf] = jnp.zeros(
                        shape, row.dtype if computed is None else computed)
        self.pool = self._tp_ctx.place(pool) if self._tp_ctx else pool
        leaves = jax.tree.leaves(self.pool)
        # what a token costs the pool over all KV layers, and the width
        # of a cell (a net of several kinds: its first kind's leaves)
        self.stats["kv_bytes_per_token"] = sum(
            int(np.prod(leaf.shape[2:])) * leaf.dtype.itemsize
            for leaf in leaves)
        self.stats["kv_dtype_bytes"] = leaves[0].dtype.itemsize

    def operands(self, tabs, filled=None) -> Dict[str, Dict[str, Any]]:
        """Inside a program: each paged layer's table operands, ONE set
        a kind and dispatch, out of the packed ``tabs`` (:meth:`pack`).
        ``filled`` is a scan's carried copy of the only operand a step
        advances."""
        shared: Dict[str, Dict[str, Any]] = {}
        with scope("tables"):
            unpacked = unpack_tables(tabs, tuple(k.ring for k in self.kinds))
        for kind, ops in zip(self.kinds, unpacked):
            if filled is not None:
                ops["filled"] = filled
            if kind.operands != ("table", "base"):
                # a second kind of the same layers: its table rides
                # beside the first's, under its own names
                ops = dict(zip(kind.operands, (ops["table"], ops["base"])))
            for name in kind.layers:
                shared.setdefault(name, {}).update(ops)
        return shared

    def pack(self, tabs, chunk: int = 1, tokens: Optional[int] = None):
        """The block-table operand of a paged dispatch of ``chunk``
        query positions a row (``tokens`` of them real): each row's
        ring-projected block table, its floor and its length (None rows
        — idle slots — map nothing; their writes drop and their keys all
        mask), of every kind, packed into ONE int32 array
        (:func:`unpack_tables`) and uploaded ONCE, whatever the number
        of paged layers and of kinds; each kind counts what one layer
        reads of it on the way. It enters the program as an argument of
        its own beside the donated pool. Under tp it COMMITS replicated
        (``TPContext.replicate``), so that a plain round's operand and a
        spec round's chained verify output share one decode lowering."""
        rings = [k.ring for k in self.kinds]
        packed = np.full((len(tabs), 2 * sum(rings) + len(rings) + 1),
                         -1, np.int32)
        packed[:, 2 * sum(rings):] = 0           # floors, filled
        live = [i for i, tab in enumerate(tabs) if tab is not None]
        for k, (kind, rows) in enumerate(zip(
                self.kinds, unpack_tables(packed, rings))):
            for i in live:
                t = tabs[i].kinds[k]
                rows["table"][i], rows["base"][i] = t.arrays(kind.ring)
                rows["floor"][i] = t.floor
                rows["filled"][i] = t.length
            if kind.reads is None:
                self._count_walk(kind, chunk=chunk, **rows)
                continue
            # (a decode dispatch is one position a row; an admission's
            # chunk is wider, and counts under ``prefill_`` as well)
            for name, n in kind.reads(
                    rows["filled"][live], queries=chunk, tokens=tokens,
                    steps=self._steps).items():
                self.stats[name] = self.stats.get(name, 0) + n
                if chunk > 1:
                    name = "prefill_" + name
                    self.stats[name] = self.stats.get(name, 0) + n
        self.stats["table_uploads"] += 1
        if self._tp_ctx is not None:
            return self._tp_ctx.replicate(packed)
        return jnp.asarray(packed)

    def _count_walk(self, kind: KvKind, table, base, floor, filled,
                    chunk: int) -> None:
        """``paged_blocks_live`` / ``paged_blocks_walked``: the pool
        blocks ONE layer's kernel call of ``kind`` copies for the kind's
        tables and the blocks' worth of keys it scores
        (``paged_walk_stats``; the gather program reads the same live
        blocks), and ``paged_steps_paid``, the grid steps and loop
        trips it pays for them, each summed over the kinds (one layer's
        call of each); ``paged_blocks_live`` also by kind, under
        ``paged_blocks_live_w<window>`` (and the part of that which
        admissions' chunks counted under ``prefill_paged_...``), for a
        reader that weighs a kind by its layers. The geometry is the
        kind's first pool leaf's, local to a tp shard;
        ``paged_blocks_per_step`` and ``paged_steps_per_row`` are the
        widest kind's."""
        pk = self.pool[kind.layers[0]][kind.leaves[0]]
        bt = self.block_tokens
        ntab = _paged_table_entries(kind.ring, kind.window, bt, chunk)
        tp = self._tp_ctx.size if self._tp_ctx else 1
        per_step = _paged_blocks_per_step(
            bt, pk.shape[2] // tp, pk.shape[3], pk.dtype, ntab,
            kind.group, chunk)
        if chunk == 1 and kind is self.kinds[0]:
            self.stats["paged_blocks_per_step"] = per_step
            self.stats["paged_steps_per_row"] = -(-ntab // per_step)
        live, walked, steps = paged_walk_stats(
            table, base, floor, filled, block_tokens=bt,
            window=kind.window, blocks_per_step=per_step, chunk=chunk)
        self.stats["paged_blocks_live"] += live
        self.stats["paged_blocks_walked"] += walked
        self.stats["paged_steps_paid"] += steps
        name = f"paged_blocks_live_w{kind.window}"
        self.stats[name] += live
        if chunk > 1:
            self.stats["prefill_" + name] += live

    # -- counting ----------------------------------------------------------
    def count_held(self, active: List[int]) -> None:
        """By kind, summed over rounds as ``occupancy_sum`` is:
        ``kv_blocks_spanned_w<window>``, the blocks the live contexts
        of the ``active`` slots span, and ``kv_blocks_held_w<window>``,
        those of them the slots' tables still map (blocks reserved
        ahead of the context are neither). The difference is what the
        kind's window released."""
        for k, kind in enumerate(self.kinds):
            spanned = held = 0
            for slot in active:
                t = self.tabs[slot].kinds[k]
                span = -(-t.length // kind.span)
                ahead = span
                while ahead in t.blocks:
                    ahead += 1
                spanned += span
                held += len(t.blocks) - (ahead - span)
            self.stats[f"kv_blocks_spanned_w{kind.window}"] += spanned
            self.stats[f"kv_blocks_held_w{kind.window}"] += held

    def refresh_stats(self, admitting=(), leased=()) -> None:
        """The pools' gauges: ``admitting`` are the tables of admissions
        in flight, ``leased`` the trie entries' (plain ``BlockTable``s
        of the widest kind's pool; only one-kind nets share blocks)."""
        pools = [k.pool for k in self.kinds]
        self.stats["blocks_free"] = sum(p.free_blocks for p in pools)
        self.stats["blocks_used"] = sum(p.used_blocks for p in pools)
        self.stats["cow_copies"] = pools[0].stats["cow_copies"]
        self.stats["prefix_blocks_spliced"] = pools[0].stats["spliced"]
        tabs = [t for t in (*self.tabs, *admitting) if t is not None]
        self.stats["frag_tokens"] = sum(
            p.fragmentation_tokens(
                [t.kinds[k] for t in tabs] + (list(leased) if k == 0
                                              else []))
            for k, p in enumerate(pools))
        for kind in self.kinds:
            if kind.name:
                self.stats[kind.name + "_blocks_allocated"] = kind.allocated
                self.stats[kind.name + "_blocks_released"] = kind.expired
