"""Paged KV memory: ONE device-resident block pool shared by decode
slots and the radix prefix trie (ISSUE 6 tentpole).

A layout of one window-sized KV row a slot (and a second whole-row
pool for cached prefixes) bounds concurrency by ``B x window``
contiguous rows and makes every prefix hit a full-row copy. The
engine's one KV layout is the PagedAttention memory model instead
(Kwon et al. 2023; RadixAttention sharing, Zheng et al. 2024):

- **Blocks** — the pool is ``kv_blocks`` fixed-size token blocks per
  attention layer (``[n_blocks, block_tokens, H, dh]``); a block holds
  ``block_tokens`` consecutive tokens of exactly one logical sequence.
- **Block tables** — each slot (and each trie entry) owns a host-side
  :class:`BlockTable`: logical block index ``g`` (absolute positions
  ``[g*bt, (g+1)*bt)``) -> pool block id. The device sees a fixed-width
  ring projection of it (``g`` at ring slot ``g % S``), so the decode
  executable's shapes never depend on sequence length.
- **Refcounts** — blocks are shared, not copied: a prefix hit splices
  the trie entry's block ids into the slot's table with refcount bumps
  (zero device work), and the one jitted ``copy_block`` executable
  implements copy-on-write when a slot would append into a block still
  referenced by the trie or another slot (only ever the partial
  boundary block — full blocks are immutable once written).
- **Allocation on demand** — the engine reserves blocks only as
  ``filled`` crosses a block boundary, so short requests hold short
  tables and the same device bytes serve strictly more concurrent
  slots than a row a slot would.

The pool itself holds only host bookkeeping; device arrays live in the
engine's rnn-state pytree (``{"pk","pv"}`` per attention layer) so the
existing jitted decode/verify/chunk executables thread them through
``AttentionImpl._paged_attend`` unchanged. The two jits owned here
(``copy_block`` for CoW, ``zero_block`` for quarantine scrubbing)
compile once each, under the engine's bounded-compile-count discipline.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class BlockTable:
    """Host-side view of one logical KV sequence: which pool block
    holds each logical block of the sequence, how many absolute tokens
    exist (``length``), and the earliest valid position (``floor`` —
    nonzero when the sequence's head slid out of the window, or when it
    was spliced from a trie entry that stored a slid window).

    Used for decode slots (mutated as the slot streams), for in-flight
    paged admissions, and as the payload of paged prefix-trie entries
    (frozen after insert)."""

    block_tokens: int
    blocks: Dict[int, int] = dataclasses.field(default_factory=dict)
    length: int = 0
    floor: int = 0

    def block_ids(self) -> List[int]:
        return list(self.blocks.values())

    def tail_block(self) -> Optional[Tuple[int, int]]:
        """(logical g, block id) of the partial tail block the next
        append writes into, or None when length is block-aligned (the
        next append starts a fresh block)."""
        if self.length % self.block_tokens == 0:
            return None
        g = self.length // self.block_tokens
        bid = self.blocks.get(g)
        return None if bid is None else (g, bid)

    def new_logical_blocks(self, n_tokens: int) -> List[int]:
        """Logical block indices an append of ``n_tokens`` tokens
        requires beyond what the table already maps."""
        if n_tokens <= 0:
            return []
        bt = self.block_tokens
        first = (self.length + bt - 1) // bt   # == length//bt aligned
        last = (self.length + n_tokens - 1) // bt
        return [g for g in range(first, last + 1)
                if g not in self.blocks]

    def arrays(self, ring_slots: int) -> Tuple[np.ndarray, np.ndarray]:
        """Device projection: ``(table[S], base[S])`` int32 with block
        ``g`` at ring slot ``g % S`` (-1 = unmapped). Two live logical
        blocks may never collide on a ring slot — the engine sizes S
        past the window plus one round's worst-case writes and frees
        slid-out blocks each round, so a collision is a bookkeeping
        bug, not load."""
        table = np.full(ring_slots, -1, np.int32)
        base = np.full(ring_slots, -1, np.int32)
        n = len(self.blocks)
        if not n:
            return table, base
        # (one pass of numpy: a long context maps a thousand blocks)
        gs = np.fromiter(self.blocks.keys(), np.int64, n)
        s = gs % ring_slots
        table[s] = np.fromiter(self.blocks.values(), np.int64, n)
        base[s] = gs * self.block_tokens
        if np.count_nonzero(table >= 0) != n:
            raise AssertionError(
                f"ring collision: logical blocks {sorted(self.blocks)} "
                f"do not fit {ring_slots} ring slots — expired blocks "
                "were not freed")
        return table, base

    def coverage(self, g: int) -> int:
        """Valid tokens this sequence keeps in logical block ``g``
        (fragmentation accounting: ``block_tokens - coverage`` of a
        tail block is allocated-but-masked pad)."""
        bt = self.block_tokens
        lo = max(self.floor, g * bt)
        hi = min(self.length, (g + 1) * bt)
        return max(0, hi - lo)


class KindTables:
    """One logical sequence's block tables as the engine holds them for
    a slot or an admission: a :class:`BlockTable` a layer KIND (the
    attention layers of one window), widest window first; a list of
    one for a net whose layers agree. Every kind's table has the
    sequence's ``length``; each holds only the blocks its window can
    still reach, ids of ITS kind's pool. Where the engine does not care
    about kinds this reads like a ``BlockTable``: ``length``, ``floor``
    (the widest kind's) and ``blocks``, every kind's, keyed
    ``(kind, g)``. (The trie, the tiers, transfer and snapshots hold
    plain ``BlockTable``s: one kind's, ``kinds[0]``.)"""

    def __init__(self, tabs):
        self.kinds = list(tabs)

    @property
    def block_tokens(self) -> int:
        return self.kinds[0].block_tokens

    @property
    def length(self) -> int:
        return self.kinds[0].length

    @length.setter
    def length(self, n: int) -> None:
        for tab in self.kinds:
            tab.length = n

    @property
    def floor(self) -> int:
        return self.kinds[0].floor

    @property
    def blocks(self) -> Dict[Tuple[int, int], int]:
        return {(k, g): bid for k, tab in enumerate(self.kinds)
                for g, bid in tab.blocks.items()}


class BlockPool:
    """Host-side allocator + refcounts for the shared KV block pool.

    Owns NO device arrays (those ride the engine's rnn pytree); owns
    the free list, per-block refcounts, the poisoned-block set the
    paranoid sweep feeds (a poisoned block is scrubbed by the engine
    the moment its last reference drops — never while an innocent
    sharer still reads it), and the two single-compile jitted helpers
    (``copy_block`` for CoW, ``zero_block`` for scrubbing)."""

    def __init__(self, n_blocks: int, block_tokens: int,
                 jit_wrap=None):
        if n_blocks < 1:
            raise ValueError(f"kv_blocks {n_blocks} < 1")
        if block_tokens < 1 or (block_tokens & (block_tokens - 1)):
            raise ValueError(
                f"block_tokens {block_tokens} must be a power of two")
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        # the engine's compilation entry point (ISSUE 12): a
        # tensor-parallel engine hands its shard_map wrapper in so the
        # pool's movers run per-shard on head-sliced blocks; None = the
        # single-chip plain jax.jit (the pool is engine-agnostic)
        self._jit_wrap = jit_wrap if jit_wrap is not None else jax.jit
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self._ref = np.zeros(self.n_blocks, np.int64)
        self.poisoned: set = set()
        self.stats: Dict[str, int] = {
            "allocs": 0, "frees": 0, "cow_copies": 0,
            "spliced": 0, "scrubbed": 0,
        }
        self._build_jits()

    def _build_jits(self):
        def copy_block(pool, src, dst):
            def cp(a):
                row = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=0)
                return jax.lax.dynamic_update_slice_in_dim(
                    a, row, dst, axis=0)

            return jax.tree_util.tree_map(cp, pool)

        def zero_block(pool, blk):
            def z(a):
                row = jnp.zeros((1,) + a.shape[1:], a.dtype)
                return jax.lax.dynamic_update_slice_in_dim(
                    a, row, blk, axis=0)

            return jax.tree_util.tree_map(z, pool)

        # the pool is donated through every mover: one block changes,
        # the other n_blocks-1 alias in place instead of copying
        self._copy_jit = self._jit_wrap(copy_block, donate_argnums=(0,))
        self._zero_jit = self._jit_wrap(zero_block, donate_argnums=(0,))

    def compile_counts(self) -> Dict[str, int]:
        def n(f):
            return int(getattr(f, "_cache_size", lambda: -1)())

        return {"paged_copy": n(self._copy_jit),
                "paged_zero": n(self._zero_jit)}

    # -- allocation / sharing ------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def alloc(self) -> Optional[int]:
        """One fresh block at refcount 1, or None when the pool is
        exhausted (the engine then evicts trie entries / preempts the
        youngest slot — allocation never blocks)."""
        if not self._free:
            return None
        bid = self._free.pop()
        self._ref[bid] = 1
        self.stats["allocs"] += 1
        return bid

    def ref(self, bid: int) -> None:
        if self._ref[bid] < 1:
            raise AssertionError(f"ref of free block {bid}")
        self._ref[bid] += 1

    def refcount(self, bid: int) -> int:
        return int(self._ref[bid])

    def deref(self, bid: int) -> bool:
        """Drop one reference; returns True when the block just became
        free (the caller scrubs it first if it was poisoned)."""
        if self._ref[bid] < 1:
            raise AssertionError(f"deref of free block {bid}")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)
            self.stats["frees"] += 1
            return True
        return False

    # -- device helpers (pool pytree = {layer: {"pk","pv"}}) -----------
    def copy_block_device(self, pool_pytree, src: int, dst: int):
        """Jitted CoW copy of one block (the only per-hit device work a
        warm prefix admission can pay, and only when the match ends
        inside a block)."""
        self.stats["cow_copies"] += 1
        return self._copy_jit(pool_pytree,
                              jnp.asarray(src, jnp.int32),
                              jnp.asarray(dst, jnp.int32))

    def scrub_block_device(self, pool_pytree, bid: int):
        """Zero one (freed, poisoned) block so the paranoid finiteness
        sweep goes green again without touching live blocks."""
        self.stats["scrubbed"] += 1
        self.poisoned.discard(bid)
        return self._zero_jit(pool_pytree, jnp.asarray(bid, jnp.int32))

    # -- accounting -----------------------------------------------------
    def fragmentation_tokens(self, tables) -> int:
        """Allocated-but-masked tokens across the pool: for every USED
        block, ``block_tokens`` minus the widest valid coverage any
        referent keeps in it (tail pad of live sequences, heads slid
        out of windows). ``tables`` iterates every live
        :class:`BlockTable` (slots, pending admissions, trie entries);
        shared blocks count once."""
        bt = self.block_tokens
        best = np.zeros(self.n_blocks, np.int64)
        for tab in tables:
            if tab is None or not tab.blocks:
                continue
            # (``BlockTable.coverage`` over all of a table's blocks in
            # one pass of numpy: this runs every round)
            n = len(tab.blocks)
            gs = np.fromiter(tab.blocks.keys(), np.int64, n)
            bids = np.fromiter(tab.blocks.values(), np.int64, n)
            cov = (np.minimum(tab.length, (gs + 1) * bt)
                   - np.maximum(tab.floor, gs * bt))
            np.maximum.at(best, bids, cov)
        return int((bt - best[self._ref > 0]).sum())
