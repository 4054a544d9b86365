"""Radix prefix cache: host-side trie over prompt token ids mapping
matched prefixes to blocks of the engine's KV pool (ISSUE 2 tentpole;
block leases since ISSUE 6).

The serving observation (RadixAttention — SGLang, Zheng et al. 2023):
real traffic shares long prompt prefixes (system prompts, few-shot
templates), so the KV state of a prefix computed for one request can
seed the next request's admission, leaving only the divergent *suffix*
to prefill.

The trie is path-compressed (edges carry token runs, split on
divergence) and keyed by prompt token ids. A stored node maps a prefix
to an entry id (``row``) whose payload is a frozen
:class:`~.block_pool.BlockTable`: references to the admitted slot's
own blocks in the shared pool, never a copy. LRU eviction runs over
unleased entries, and ref-count leases pin an entry while an in-flight
admission still reads it. Nothing here touches the device:

- **insert is zero-copy** — the entry references the admitted slot's
  blocks (refcount bumps via ``ref_block``); the slot's subsequent
  appends copy-on-write the shared boundary block instead of mutating
  it.
- **a hit is zero-copy** — the engine splices the payload's block ids
  into the new slot's table.
- **eviction frees references, not bytes** — dropping an entry derefs
  its blocks via ``release_block``; a block shared with a live slot
  stays resident until the slot finishes, so evicting an entry mid-use
  can never corrupt a reader.

Why a hit may be shorter than the entry: K/V at a position are
projections of that token alone, so a stored state rewinds EXACTLY to
any shorter prefix of itself (the engine references only the blocks
below ``matched``). That serves two purposes. (1) A prompt that
diverges ``m`` tokens into a cached entry still reuses those ``m``
tokens, so the hit criterion is any-shared-prefix, not
whole-stored-prompt. (2) Sampling a request's first token needs the
logits at its LAST prompt position, which a cached state does not
carry — so a lookup never consumes the whole prompt: an exact match
stops one token short and the engine re-streams the final prompt token
as a one-token suffix, producing those logits on the regular suffix
path.

The lease exists for bookkeeping honesty: an admission that matched a
prefix holds its entry until the admission completes, so LRU eviction
never recycles an entry the engine still considers live (asserted in
tests).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.serving.block_pool import BlockTable


@dataclasses.dataclass
class PrefixHit:
    """One successful lookup: ``matched`` prompt tokens are served from
    cache entry ``row`` (which may hold more); the entry stays leased
    until ``release``."""

    row: int
    matched: int


class _Node:
    """Radix-trie node: ``edge`` is the token run from the parent,
    ``depth`` the total prefix length here, ``row`` the entry id
    when this exact prefix is cached (structural nodes carry None)."""

    __slots__ = ("edge", "children", "parent", "depth", "row",
                 "last_use")

    def __init__(self, edge: Tuple[int, ...], parent: "_Node | None",
                 depth: int):
        self.edge = edge
        self.children: Dict[int, _Node] = {}
        self.parent = parent
        self.depth = depth
        self.row: Optional[int] = None
        self.last_use = 0


class RadixPrefixCache:
    """Fixed-capacity prefix cache: at most ``rows`` entries behind a
    radix trie over prompt token ids, each entry a lease on blocks of
    the engine's :class:`~.block_pool.BlockPool` (``ref_block`` /
    ``release_block`` take and drop one reference to a block id).

    ``lookup`` returns the longest cached prefix of a prompt (capped at
    ``len(prompt) - 1`` — see module docstring) and leases its entry;
    ``payload`` is the entry's block table for the engine to splice;
    ``insert_blocks`` stores an admitted slot's table under its full
    prompt, evicting the least-recently-used unleased entry when full
    (declining, not evicting, when every entry is leased). ``rows``
    caps the number of ENTRIES; device capacity is governed by the
    block pool itself (``evict_one`` relieves it)."""

    def __init__(self, rows: int, block_tokens: int, ref_block,
                 release_block):
        if rows < 1:
            raise ValueError(f"prefix cache rows {rows} < 1")
        self.rows = int(rows)
        self.block_tokens = int(block_tokens)
        self._ref_block = ref_block
        self._release_block = release_block
        self._payloads: Dict[int, BlockTable] = {}
        self._root = _Node((), None, 0)
        self._free: List[int] = list(range(self.rows))
        self._by_row: Dict[int, _Node] = {}
        self._ref: Dict[int, int] = {}
        self._clock = 0
        #: pressure-eviction hook (ISSUE 17): called as
        #: ``on_evict(prefix_tokens, payload)`` just before an LRU
        #: victim's payload is dropped, so the engine can spill it to
        #: the host/disk KV tier. Fires ONLY for ``evict_one``
        #: pressure evictions — quarantine invalidations bypass it by
        #: design (poisoned state must never be spilled and reloaded).
        self.on_evict = None
        self.stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "inserts": 0, "evictions": 0,
            "declined": 0, "tokens_matched": 0, "invalidations": 0,
        }

    # -- trie ----------------------------------------------------------
    def _walk(self, tokens: Tuple[int, ...]):
        """Descend as far as whole edges match ``tokens``; returns the
        final fully-matched (node, depth)."""
        node, depth = self._root, 0
        while depth < len(tokens):
            child = node.children.get(tokens[depth])
            if child is None:
                break
            n = len(child.edge)
            if (len(tokens) - depth < n
                    or tokens[depth:depth + n] != child.edge):
                break  # tokens end or diverge inside the edge
            node, depth = child, depth + n
        return node, depth

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.last_use = self._clock

    def _shallowest_stored(self, node: _Node) -> Optional[_Node]:
        """Closest stored node at or below ``node`` (the one holding
        the fewest tokens past the shared prefix when its subtree
        shares a prefix with a query that diverged above it)."""
        frontier = [node]
        best: Optional[_Node] = None
        while frontier:
            nd = frontier.pop()
            if nd.row is not None:
                if best is None or nd.depth < best.depth:
                    best = nd
                continue  # anything below is deeper still
            frontier.extend(nd.children.values())
        return best

    def lookup(self, prompt: Sequence[int]) -> Optional[PrefixHit]:
        """Longest reusable cached prefix of ``prompt``; leases the
        entry (pair every hit with ``release``).

        A stored state need not BE a prefix of the prompt to serve it:
        when the prompt diverges ``m`` tokens into a cached entry (or
        ends inside it), the engine references the entry's blocks
        below ``m`` only (K/V are per-token, so that is exactly the
        state after ``prompt[:m]``). That makes the hit criterion
        RadixAttention's:
        any shared prefix with anything cached, not just whole stored
        prompts. Returns None on miss, or when the reusable part is
        empty (a 1-token prompt can never hit: its first token's
        logits must come from a real prefill)."""
        tokens = tuple(int(t) for t in prompt)
        node, depth = self._root, 0
        best: Optional[_Node] = None      # stored node to splice from
        best_m = 0                        # prompt tokens it covers
        while depth < len(tokens):
            child = node.children.get(tokens[depth])
            if child is None:
                break
            limit = min(len(child.edge), len(tokens) - depth)
            common = 0
            while (common < limit
                   and child.edge[common] == tokens[depth + common]):
                common += 1
            if common == len(child.edge):
                node, depth = child, depth + common
                if node.row is not None:
                    best, best_m = node, node.depth
                continue
            # query diverged (or ended) inside the edge: every stored
            # node under `child` shares exactly depth+common tokens
            if common and depth + common > best_m:
                cand = self._shallowest_stored(child)
                if cand is not None:
                    best, best_m = cand, depth + common
            break
        else:
            child = None
        if child is None and depth > best_m:
            # the walk ended at a node boundary (no continuing edge, or
            # the query ran out): every stored node under `node` —
            # siblings diverging here, or longer prompts extending the
            # query — shares exactly `depth` tokens with the query
            cand = self._shallowest_stored(node)
            if cand is not None:
                best, best_m = cand, depth
        if best is not None:
            matched = min(best_m, len(tokens) - 1)
            if matched >= 1:
                self._touch(best)
                self._ref[best.row] = self._ref.get(best.row, 0) + 1
                self.stats["hits"] += 1
                self.stats["tokens_matched"] += matched
                return PrefixHit(row=best.row, matched=matched)
        self.stats["misses"] += 1
        return None

    def payload(self, row: int) -> BlockTable:
        """The frozen block table stored under an entry id returned by
        ``lookup``."""
        return self._payloads[row]

    def release(self, hit: PrefixHit) -> None:
        """Drop the lease taken by ``lookup`` (the entry becomes
        evictable again once unreferenced). An entry invalidated WHILE
        leased (fault quarantine) was only unmapped at that point; the
        last release returns its id to the free list."""
        left = self._ref.get(hit.row, 0) - 1
        if left > 0:
            self._ref[hit.row] = left
        else:
            self._ref.pop(hit.row, None)
            if (hit.row not in self._by_row
                    and hit.row not in self._free):
                self._free.append(hit.row)

    def _drop_node(self, node: _Node) -> int:
        """Unmap a stored node, drop its references to its blocks (a
        slot that spliced them holds its own) and prune now-empty leaf
        chains. The id returns to the free list immediately when
        unleased; an id another in-flight admission still leases is
        only UNMAPPED here (no new lookups can hit it) and ``release``
        frees it when the last lease drops — freeing it now would let
        an insert reuse an id whose lease bookkeeping still points at
        the old occupant. The quarantine path for corrupted entries."""
        row = node.row
        payload = self._payloads.pop(row, None)
        if payload is not None:
            for bid in payload.blocks.values():
                self._release_block(bid)
        node.row = None
        del self._by_row[row]
        if self._ref.get(row, 0) == 0:
            self._ref.pop(row, None)
            self._free.append(row)
        while (node.parent is not None and node.row is None
               and not node.children):
            parent = node.parent
            del parent.children[node.edge[0]]
            node = parent
        return row

    def invalidate_row(self, row: int) -> bool:
        """Drop the entry stored under id ``row`` (fault quarantine:
        the engine detected NaN state traced back to it). Returns
        False when the id holds nothing."""
        node = self._by_row.get(row)
        if node is None:
            return False
        self._drop_node(node)
        self.stats["invalidations"] += 1
        return True

    def invalidate(self, prompt: Sequence[int]) -> bool:
        """Drop the entry stored under exactly ``prompt`` (fault
        quarantine: an admission built on a corrupt splice re-inserted
        its poisoned state under its full prompt — both ends must be
        scrubbed before the retry, or the retry re-splices the
        poison)."""
        tokens = tuple(int(t) for t in prompt)
        node, depth = self._walk(tokens)
        if depth != len(tokens) or node.row is None:
            return False
        self._drop_node(node)
        self.stats["invalidations"] += 1
        return True

    def stored_rows(self) -> List[int]:
        """Ids currently holding entries (fault injection picks its
        corruption target from these)."""
        return sorted(self._by_row)

    def row_prefix(self, row: int) -> Optional[Tuple[int, ...]]:
        """The token prefix currently stored under id ``row`` (None
        when it holds nothing). Quarantine uses this to confirm a
        suspect entry still holds an ancestor of the poisoned prompt
        before invalidating — the id may have been LRU-recycled for
        an unrelated healthy entry since the admission spliced it."""
        node = self._by_row.get(row)
        if node is None:
            return None
        parts = []
        while node is not None:
            parts.append(node.edge)
            node = node.parent
        return tuple(t for edge in reversed(parts) for t in edge)

    def _spill_victim(self, node: _Node) -> None:
        """Spill seam (ISSUE 17): hand the pressure victim's prefix
        tokens + frozen block table to ``on_evict`` while its blocks
        are still referenced — the hook dispatches the jitted
        ``kv_gather`` against the CURRENT pool value (device arrays
        are immutable, so the gathered snapshot survives the blocks'
        recycling). A hook fault must never turn an eviction into an
        engine fault: the tier is an optimization, the drop proceeds
        regardless."""
        prefix = self.row_prefix(node.row)
        payload = self._payloads.get(node.row)
        if prefix is None or payload is None:
            return
        try:
            self.on_evict(prefix, payload)
        except Exception:
            pass

    def evict_one(self) -> bool:
        """Evict the LRU unleased entry: to make room for an insert,
        or to relieve BLOCK-pool pressure (the engine calls this when
        allocation fails). Returns False when nothing is evictable.
        The freed resource is the blocks' references; the entry id
        goes back to the free list."""
        victims = [nd for row, nd in self._by_row.items()
                   if self._ref.get(row, 0) == 0]
        if not victims:
            return False
        node = min(victims, key=lambda nd: nd.last_use)
        if self.on_evict is not None:
            self._spill_victim(node)
        self._drop_node(node)
        self.stats["evictions"] += 1
        return True

    def _alloc_row(self) -> Optional[int]:
        if not self._free and not self.evict_one():
            return None
        return self._free.pop()

    def insert_blocks(self, prompt: Sequence[int], tab) -> bool:
        """Store a prompt's KV footprint as references to ``tab``'s
        blocks (a frozen snapshot of the admitted slot's table —
        refcount +1 per block, zero device work). Duplicate prompts
        refresh recency only; a full cache evicts the LRU unleased
        entry, and with every entry leased declines (never blocks,
        never evicts a leased entry)."""
        tokens = tuple(int(t) for t in prompt)
        if not tokens:
            return False
        node, depth = self._walk(tokens)
        if depth == len(tokens) and node.row is not None:
            self._touch(node)  # already cached: refresh recency
            return False
        row = self._alloc_row()
        if row is None:
            self.stats["declined"] += 1
            return False
        # re-walk AFTER allocation: evicting the LRU entry may have
        # pruned nodes on the first walk's path — grafting from the
        # stale node would extend a detached subtree (unreachable
        # entry now, corrupted prune bookkeeping later)
        node, depth = self._walk(tokens)
        frozen = BlockTable(self.block_tokens, dict(tab.blocks),
                            tab.length, tab.floor)
        for bid in frozen.blocks.values():
            self._ref_block(bid)
        self._payloads[row] = frozen
        node = self._graft(node, depth, tokens)
        node.row = row
        self._by_row[row] = node
        self._touch(node)
        self.stats["inserts"] += 1
        return True

    def _graft(self, node: _Node, depth: int,
               tokens: Tuple[int, ...]) -> _Node:
        """Extend the trie from ``node`` (which matched ``tokens`` up
        to ``depth``) until a node for the full prompt exists, splitting
        a partially-shared edge at the divergence point."""
        while depth < len(tokens):
            child = node.children.get(tokens[depth])
            if child is None:
                leaf = _Node(tokens[depth:], node, len(tokens))
                node.children[tokens[depth]] = leaf
                return leaf
            common = 0
            limit = min(len(child.edge), len(tokens) - depth)
            while (common < limit
                   and child.edge[common] == tokens[depth + common]):
                common += 1
            if common == len(child.edge):
                node, depth = child, depth + common
                continue
            # split child's edge at the divergence (or at prompt end)
            mid = _Node(child.edge[:common], node, node.depth + common)
            child.edge = child.edge[common:]
            child.parent = mid
            mid.children[child.edge[0]] = child
            node.children[tokens[depth]] = mid
            node, depth = mid, depth + common
        return node

    def clear(self) -> int:
        """Drop every stored entry (ids still leased by an in-flight
        admission are unmapped now and freed at the last release).
        Returns the number of entries dropped — the soak's
        pool-fully-free gate empties the trie through this."""
        dropped = 0
        for row in list(self._by_row):
            node = self._by_row.get(row)
            if node is not None:
                self._drop_node(node)
                dropped += 1
        return dropped

    # -- introspection -------------------------------------------------
    @property
    def hit_rate(self) -> float:
        seen = self.stats["hits"] + self.stats["misses"]
        return self.stats["hits"] / seen if seen else 0.0

    def cached_prefixes(self) -> List[Tuple[int, ...]]:
        """Every stored prefix (tests/debugging)."""
        out: List[Tuple[int, ...]] = []

        def rec(node, prefix):
            prefix = prefix + node.edge
            if node.row is not None:
                out.append(prefix)
            for child in node.children.values():
                rec(child, prefix)

        rec(self._root, ())
        return sorted(out)

    def block_ids(self) -> List[int]:
        """Every block id currently referenced by a stored entry
        (soak accounting + fault-injection targeting)."""
        out: List[int] = []
        for payload in self._payloads.values():
            out.extend(payload.blocks.values())
        return out
