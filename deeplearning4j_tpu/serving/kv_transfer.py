"""KV transfer plane: cross-replica shipping of warmed KV blocks
(ISSUE 14 tentpole — ROADMAP item 2b, the DistServe-style half).

A prefix warmed on one replica is cold everywhere else, so affinity
misses, failover replay, and rolling-upgrade warmup all recompute the
full prompt on the receiver — correct (the PR 9 replay discipline),
but wrong for long-prompt traffic at fleet scale. The block pool
already gives KV a serializable block-granular identity
(:class:`~deeplearning4j_tpu.serving.block_pool.BlockTable` + pool
block slices), so a warmed prefix can be a fleet-level resource:

- **Export** (:func:`export_prefix`): the donor looks the prompt up
  in its radix trie, slices the entry's referenced pool blocks out of
  device memory, and frames them as one binary payload
  (:func:`pack_prefix`). The wire format is LAYOUT-INVARIANT: a TP=N
  donor's head-sliced blocks reassemble to full logical
  ``[n, block_tokens, H, dh]`` arrays on the host (the PR 12
  host-bookkeeping contract — block ids and tables never saw the
  head axis), so any receiver width can import them.
- **Import** (:func:`import_prefix`): the receiver validates the
  frame against its own geometry (block size, layer set, head/dh
  shape, dtype, window), allocates fresh pool blocks (evicting LRU
  trie entries if needed — never preempting a live slot for a cache
  import), scatters the shipped slices in through ONE jitted
  executable per pow2 block-count bucket, and seeds its radix trie
  via the existing zero-copy ``insert_blocks`` path. From that moment
  the imported prefix is indistinguishable from a locally-computed
  one: the next admission splices it with the same CoW machinery,
  and greedy ids are bit-identical to a local prefill (gated by
  tests/test_kv_transfer.py across TP widths).

Correctness never depends on a transfer succeeding: every decline or
malformed frame surfaces as ``imported: False`` (or a
:class:`KVTransferError` the HTTP layer maps to 400) and the caller —
the router's warm-import hook, the controller's upgrade warmup —
falls back to full recompute.

Wire format (version 1)::

    b"DKV1" | u32 version | u32 header_len | header JSON | buffers

The header carries the covered prefix's token ids (the radix-trie
key), the block geometry, and per-layer dtype/shape; the buffers are
each layer's selected ``pk`` then ``pv`` blocks, C-contiguous, in
ascending logical-block order. Every size is validated against the
header before any buffer is touched, so a truncated payload (the
soak's injected fault) fails loudly instead of importing garbage.
"""

from __future__ import annotations

import json
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

MAGIC = b"DKV1"
WIRE_VERSION = 1

#: default HTTP-facing payload cap (bytes): large enough for a long
#: prompt's blocks on a real model slice, small enough that a hostile
#: Content-Length cannot balloon the handler (the gateway's
#: ``kv_transfer_cap_bytes`` knob overrides per deployment)
DEFAULT_CAP_BYTES = 64 << 20


class KVTransferError(ValueError):
    """A payload failed structural validation (bad magic, truncated
    buffers, geometry mismatch): the HTTP layer answers 400 and the
    caller falls back to recompute."""


class KVTransferTooLarge(KVTransferError):
    """An export would exceed the transfer cap — detected from the
    block count and leaf shapes BEFORE any device gather runs, so an
    over-cap prompt costs arithmetic, not a wasted device-to-host
    copy under the engine lock. The HTTP layer answers 413."""


def pack_prefix(tokens: Sequence[int], blocks: Sequence[int],
                floor: int, block_tokens: int,
                layers: List[Tuple[str, np.ndarray, np.ndarray]]
                ) -> bytes:
    """Frame one warmed prefix: ``tokens`` is the covered prefix
    (the radix-trie key the receiver re-inserts under), ``blocks``
    the ascending logical block indices covering
    ``[floor, len(tokens))``, ``layers`` a list of
    ``(name, pk [n, bt, H, dh], pv [n, bt, H, dh])`` host arrays in a
    stable order."""
    header: Dict[str, Any] = {
        "block_tokens": int(block_tokens),
        "floor": int(floor),
        "length": len(tokens),
        "tokens": [int(t) for t in tokens],
        "blocks": [int(g) for g in blocks],
        "layers": [],
    }
    buffers: List[bytes] = []
    for name, pk, pv in layers:
        pk = np.ascontiguousarray(pk)
        pv = np.ascontiguousarray(pv)
        if pk.shape != pv.shape or pk.ndim != 4:
            raise KVTransferError(
                f"layer {name}: pk/pv shapes {pk.shape}/{pv.shape} "
                "are not matching [n, bt, H, dh] block stacks")
        header["layers"].append({
            "name": str(name),
            "dtype": str(pk.dtype),
            "heads": int(pk.shape[2]),
            "dh": int(pk.shape[3]),
            "nbytes": int(pk.nbytes),
        })
        buffers.append(pk.tobytes())
        buffers.append(pv.tobytes())
    head = json.dumps(header).encode()
    return b"".join([MAGIC, struct.pack("<II", WIRE_VERSION,
                                        len(head)), head] + buffers)


def unpack_prefix(payload: bytes) -> Dict[str, Any]:
    """Parse + validate one framed payload back to
    ``{"header": {...}, "layers": {name: (pk, pv)}}`` host arrays.
    Raises :class:`KVTransferError` on ANY structural problem —
    magic, version, header JSON, or buffer sizes that disagree with
    the header (the truncated-payload fault the soak injects)."""
    if len(payload) < len(MAGIC) + 8:
        raise KVTransferError(
            f"payload too short ({len(payload)} bytes)")
    if payload[:len(MAGIC)] != MAGIC:
        raise KVTransferError("bad magic (not a KV transfer frame)")
    version, head_len = struct.unpack_from("<II", payload, len(MAGIC))
    if version != WIRE_VERSION:
        raise KVTransferError(f"unsupported wire version {version}")
    off = len(MAGIC) + 8
    if off + head_len > len(payload):
        raise KVTransferError("truncated header")
    try:
        header = json.loads(payload[off:off + head_len])
    except ValueError as e:
        raise KVTransferError(f"bad header JSON: {e}") from None
    off += head_len
    for key in ("block_tokens", "floor", "length", "tokens",
                "blocks", "layers"):
        if key not in header:
            raise KVTransferError(f"header missing {key!r}")
    bt = int(header["block_tokens"])
    length = int(header["length"])
    floor = int(header["floor"])
    tokens = [int(t) for t in header["tokens"]]
    blocks = [int(g) for g in header["blocks"]]
    if bt < 1 or length < 1 or not tokens or len(tokens) != length:
        raise KVTransferError(
            f"inconsistent prefix: length {length}, "
            f"{len(tokens)} tokens")
    if not 0 <= floor < length:
        raise KVTransferError(f"floor {floor} outside [0, {length})")
    want = list(range(floor // bt, (length - 1) // bt + 1))
    if blocks != want:
        raise KVTransferError(
            f"blocks {blocks} do not contiguously cover "
            f"[{floor}, {length}) at block_tokens={bt}")
    layers: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    n = len(blocks)
    for spec in header["layers"]:
        name = str(spec["name"])
        try:
            dtype = np.dtype(str(spec["dtype"]))
        except TypeError as e:
            raise KVTransferError(
                f"layer {name}: unknown dtype "
                f"{spec.get('dtype')!r}: {e}") from None
        heads, dh = int(spec["heads"]), int(spec["dh"])
        if heads < 1 or dh < 1:
            # validated BEFORE the nbytes arithmetic: a negative pair
            # multiplies back to a "consistent" byte count and would
            # surface as a bare reshape ValueError instead of the
            # KVTransferError contract the HTTP 400 mapping rides
            raise KVTransferError(
                f"layer {name}: non-positive heads/dh "
                f"({heads}, {dh})")
        nbytes = int(spec["nbytes"])
        if nbytes != n * bt * heads * dh * dtype.itemsize:
            raise KVTransferError(
                f"layer {name}: declared {nbytes} bytes != "
                f"{n}x{bt}x{heads}x{dh} {dtype} blocks")
        if off + 2 * nbytes > len(payload):
            raise KVTransferError(
                f"truncated payload at layer {name}: need "
                f"{2 * nbytes} more bytes, "
                f"{len(payload) - off} remain")
        shape = (n, bt, heads, dh)
        pk = np.frombuffer(payload, dtype, n * bt * heads * dh,
                           off).reshape(shape)
        off += nbytes
        pv = np.frombuffer(payload, dtype, n * bt * heads * dh,
                           off).reshape(shape)
        off += nbytes
        layers[name] = (pk, pv)
    if off != len(payload):
        raise KVTransferError(
            f"{len(payload) - off} trailing bytes after the declared "
            "buffers")
    header["tokens"] = tokens
    header["blocks"] = blocks
    return {"header": header, "layers": layers}


# -- engine-side export / import --------------------------------------

def export_prefix(engine, prompt: Sequence[int],
                  cap_bytes: Optional[int] = None) -> Optional[bytes]:
    """Serialize the longest cached prefix of ``prompt`` from
    ``engine``'s radix trie (None when nothing reusable is
    cached, or the engine has no trie / no pool yet). The lease
    taken by the lookup pins the entry while the device blocks are
    sliced to host; device arrays are immutable, so the snapshot is
    consistent even against concurrent rounds. Per-shard aware by
    construction: ``np.asarray`` on a TP-sharded pool leaf reassembles
    the full logical array (host bookkeeping never sees the head
    axis), so the payload is identical at any donor width.
    ``cap_bytes`` raises :class:`KVTransferTooLarge` from the block
    arithmetic alone — before any device work runs."""
    if engine._pool is None or engine.prefix_cache is None:
        return None
    hit = engine.prefix_cache.lookup(prompt)
    if hit is None:
        return None
    try:
        tab = engine.prefix_cache.payload(hit.row)
        matched = hit.matched
        if matched <= tab.floor:
            return None
        bt = engine.block_tokens
        want = list(range(tab.floor // bt, (matched - 1) // bt + 1))
        if any(g not in tab.blocks for g in want):
            return None  # entry no longer contiguous: nothing to ship
        bids = [tab.blocks[g] for g in want]
        if cap_bytes is not None:
            buffer_bytes = sum(
                2 * len(bids) * int(np.prod(st["pk"].shape[1:]))
                * st["pk"].dtype.itemsize
                for st in engine._pool.values())
            if buffer_bytes > cap_bytes:
                raise KVTransferTooLarge(
                    f"export of {len(bids)} blocks x "
                    f"{len(engine._pool)} layers needs "
                    f"{buffer_bytes} buffer bytes, over the "
                    f"{cap_bytes}-byte cap")
        # jitted bucketed gather: only the SELECTED blocks cross to
        # host (pow2-padded ids, pad lanes fill zero and are sliced
        # off — one executable per bucket, the import twin's compile
        # discipline), and ``np.asarray`` on the gathered leaves
        # reassembles TP head shards to full logical blocks
        import jax.numpy as jnp

        with engine._span("serving.kv_export", matched=matched,
                          blocks=len(bids)):
            width = _pow2_bucket(len(bids))
            ids = np.full(width, engine.kv_blocks, np.int32)
            ids[:len(bids)] = bids
            gathered = engine._kv_gather_jit(engine._pool,
                                             jnp.asarray(ids))
            layers: List[Tuple[str, np.ndarray, np.ndarray]] = []
            for name in sorted(gathered):
                st = gathered[name]
                pk = np.asarray(st["pk"])[:len(bids)]
                pv = np.asarray(st["pv"])[:len(bids)]
                layers.append((name, pk, pv))
            payload = pack_prefix([int(t) for t in prompt[:matched]],
                                  want, tab.floor, bt, layers)
        engine.stats["kv_exports"] = engine.stats.get(
            "kv_exports", 0) + 1
        engine.stats["kv_exported_tokens"] = engine.stats.get(
            "kv_exported_tokens", 0) + (matched - tab.floor)
        if engine.tracer is not None:
            engine.tracer.incr("serving_kv_exports")
            engine.tracer.incr("serving_kv_exported_tokens",
                               matched - tab.floor)
        return payload
    finally:
        engine.prefix_cache.release(hit)


def _pow2_bucket(n: int, lo: int = 1) -> int:
    b = max(lo, 1)
    while b < n:
        b <<= 1
    return b


def import_prefix(engine, payload: bytes) -> Dict[str, Any]:
    """Splice a shipped prefix into ``engine``'s pool + radix trie.
    Returns a summary dict; ``imported`` is False on any DECLINE
    (already warm, pool pressure, trie full) — soft outcomes the
    caller treats as "stay cold". Structural problems (bad frame,
    geometry mismatch with this engine) raise
    :class:`KVTransferError` instead: those are deployment bugs the
    HTTP layer maps to 400, and recompute still covers correctness."""
    import jax.numpy as jnp

    if engine.prefix_cache is None:
        raise KVTransferError(
            "receiver has no prefix trie (prefix_cache_rows required)")
    parsed = unpack_prefix(payload)
    header, shipped = parsed["header"], parsed["layers"]
    bt = int(header["block_tokens"])
    if bt != engine.block_tokens:
        raise KVTransferError(
            f"block_tokens mismatch: payload {bt} vs engine "
            f"{engine.block_tokens}")
    tokens = header["tokens"]
    bad = [t for t in tokens if not 0 <= t < engine.vocab]
    if bad:
        raise KVTransferError(
            f"prefix ids {bad[:4]} outside vocab [0, {engine.vocab})")
    length, floor = int(header["length"]), int(header["floor"])
    if length - floor > engine.kv.wmax:
        raise KVTransferError(
            f"prefix spans {length - floor} tokens, wider than the "
            f"receiver's cache window ({engine.kv.wmax})")
    if engine._pool is None:
        # a freshly booted receiver has no device pool yet (it
        # allocates lazily at first admission): establish it through
        # the regular prefill path — one tiny prefill at the minimum
        # bucket, the same executable the first cold admission pays
        rnn, _ = engine._prefill_sequence([0])
        engine._ensure_paged_pool(rnn)
    if set(shipped) != set(engine._pool):
        raise KVTransferError(
            f"layer set mismatch: payload {sorted(shipped)} vs "
            f"engine {sorted(engine._pool)}")
    for name, (pk, _pv) in shipped.items():
        leaf = engine._pool[name]["pk"]
        if pk.shape[1:] != tuple(leaf.shape[1:]):
            raise KVTransferError(
                f"layer {name}: shipped block shape "
                f"{pk.shape[1:]} != receiver {tuple(leaf.shape[1:])}")
        # a floating payload of another width is cast into the pool
        # by the import program (float32 cells that a mixed-precision
        # donor filled with bf16 numbers land in a bf16 pool exactly)
        if str(pk.dtype) != str(leaf.dtype) and not (
                jnp.issubdtype(pk.dtype, jnp.floating)
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            raise KVTransferError(
                f"layer {name}: shipped dtype {pk.dtype} != "
                f"receiver {leaf.dtype}")
    n = len(header["blocks"])

    def result(imported: bool, reason: str) -> Dict[str, Any]:
        return {"imported": imported, "reason": reason,
                "prefix_len": length, "tokens": length - floor,
                "blocks": n}

    # already at least as warm: the trie holds this exact prefix (or
    # a longer one through it) — re-importing would duplicate blocks
    node, depth = engine.prefix_cache._walk(tuple(tokens))
    if depth == len(tokens) and (
            node.row is not None
            or engine.prefix_cache._shallowest_stored(node)
            is not None):
        engine.stats["kv_import_declined"] = engine.stats.get(
            "kv_import_declined", 0) + 1
        return result(False, "already_warm")
    # allocation may evict LRU trie entries but must NEVER preempt a
    # live slot: an import is a cache fill, not admitted work
    if not engine._paged_reserve(n, protect=set(range(engine.n_slots))):
        engine.stats["kv_import_declined"] = engine.stats.get(
            "kv_import_declined", 0) + 1
        return result(False, "no_blocks")
    from deeplearning4j_tpu.serving.block_pool import BlockTable, KindTables

    tab = BlockTable(bt, length=length, floor=floor)
    for g in header["blocks"]:
        bid = engine.block_pool.alloc()
        if bid is None:  # _paged_reserve just guaranteed n frees
            raise AssertionError("reserved kv-import alloc failed")
        tab.blocks[g] = bid
    # pad to the pow2 bucket so repeat imports share executables
    # (O(log max-blocks) compiles, the engine's standing discipline);
    # pad ids land out of range and drop inside the scatter
    width = _pow2_bucket(n)
    ids = np.full(width, engine.kv_blocks, np.int32)
    ids[:n] = [tab.blocks[g] for g in header["blocks"]]
    new = {}
    for name in engine._pool:
        pk, pv = shipped[name]
        if width != n:
            pad = ((0, width - n), (0, 0), (0, 0), (0, 0))
            pk = np.pad(pk, pad)
            pv = np.pad(pv, pad)
        new[name] = {"pk": pk, "pv": pv}
    t0 = engine._clock()
    with engine._span("serving.kv_import", prefix_len=length,
                      blocks=n, bytes=len(payload)):
        engine._pool = engine._kv_import_jit(
            engine._pool, new, jnp.asarray(ids))
    ok = engine.prefix_cache.insert_blocks(tokens, tab)
    engine.kv.free(KindTables([tab]))
    if not ok:
        engine.stats["kv_import_declined"] = engine.stats.get(
            "kv_import_declined", 0) + 1
        return result(False, "trie_full")
    dt = engine._clock() - t0
    engine.stats["kv_imports"] = engine.stats.get("kv_imports", 0) + 1
    engine.stats["kv_imported_tokens"] = engine.stats.get(
        "kv_imported_tokens", 0) + (length - floor)
    engine.stats["kv_imported_blocks"] = engine.stats.get(
        "kv_imported_blocks", 0) + n
    engine._observe("serving_kv_import_s", dt)
    if engine.tracer is not None:
        engine.tracer.incr("serving_kv_imports")
        engine.tracer.incr("serving_kv_imported_tokens",
                           length - floor)
    return result(True, "imported")
