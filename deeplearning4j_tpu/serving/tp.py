"""Tensor-parallel sharding context for the serving decode engine
(ISSUE 12 tentpole).

Training composes dp/tp/pp/sp/fsdp/ep over the device mesh, but until
this module the decode engine ran every executable on one chip. Here
the engine's jitted computations — prefill, chunked continuation,
decode, speculative verify, paged scatter, health, block movers —
become **fully-manual ``shard_map`` programs** over a ``tp`` mesh axis
(``parallel/mesh.py:make_mesh`` + ``jax.shard_map``,
the same machinery the trainers ride), sharded Megatron-style over
attention heads:

- **params**: attention ``Wq``/``Wk``/``Wv`` column-sliced
  (``P(None, "tp")`` — each shard owns ``n_heads/TP`` whole heads),
  ``Wo`` row-sliced (``P("tp", None)``); everything else replicated.
  The layer body runs on local heads and all-reduces the output
  projection once (``nn/layers/attention.py:tp_head_shards``).
- **KV state**: every cache leaf shards on its HEAD axis — a cold
  admission's dense row ``[1, H, W, dh]`` at
  ``P(None, "tp", None, None)``, the pool's
  blocks ``[n_blocks, block_tokens, H, dh]`` at
  ``P(None, None, "tp", None)`` — so per-shard KV bytes are exactly
  ``total / TP``, which is what lets a model whose KV working set
  exceeds one chip serve at all.
- **host bookkeeping is layout-invariant**: block ids, refcounts,
  CoW, quarantine, the radix trie, and the snapshot wire format never
  see the head axis, so ``BlockTable``/``RadixPrefixCache``/the PR 6
  pressure ladder work unchanged, and a snapshot taken at one TP
  width restores at any other (device state is rebuilt by re-prefill).

Everything the host reads back (sampled tokens, acceptance counts,
health verdicts) is REPLICATED across shards by construction: logits
are completed by the psum before sampling, and the health reduction
all-reduces its verdict, so the engine's control flow — and therefore
greedy ids — is bit-identical to the single-chip engine at the argmax
level (the PR 6 paged-parity convention; gated by
tests/test_serving_tp.py).

In-spec/out-spec pytrees are derived from leaf KEY PATHS at trace
time (``pk``/``pv``/``k``/``v`` under an attention layer's key ride
the head sharding; everything else replicates), so the polymorphic
cache dicts — a dense row during a cold admission, the pool's
leaves beside one replicated block-table operand during decode — wrap
without per-structure plumbing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.layers.attention import tp_head_shards
from deeplearning4j_tpu.parallel.mesh import make_mesh

#: attention param leaf -> (sharded axis index, spec) under head
#: sharding; params not listed (biases, LN, FFN, Wi) replicate
_ATTN_PARAM_SPECS = {
    "Wq": P(None, "tp"),
    "Wk": P(None, "tp"),
    "Wv": P(None, "tp"),
    "Wo": P("tp", None),
}


def _key_name(entry) -> Optional[str]:
    """The string key of one pytree path entry (DictKey across the
    jax versions this tree supports)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


class TPContext:
    """One engine's tensor-parallel execution context.

    ``attn_keys`` are the param/rnn-state pytree keys of the net's
    attention layers (layer index strings for a MultiLayerNetwork,
    vertex names for a ComputationGraph) — the ONLY subtrees whose
    leaves shard; a leaf named ``Wq`` anywhere else replicates.
    """

    def __init__(self, tp: int, attn_keys: Sequence[str],
                 axis: str = "tp", devices=None):
        if tp < 1:
            raise ValueError(f"tp {tp} < 1")
        n_dev = len(devices if devices is not None else jax.devices())
        if tp > n_dev:
            raise ValueError(
                f"tp {tp} exceeds the {n_dev} visible devices")
        self.size = int(tp)
        self.axis = axis
        self.attn_keys = frozenset(str(k) for k in attn_keys)
        self.mesh = make_mesh({axis: self.size}, devices)

    # -- spec derivation -----------------------------------------------
    def _norm(self, axes) -> P:
        """Drop trailing Nones: ``P(None, None, "tp", None)`` and
        ``P(None, None, "tp")`` mean the same sharding but hash as
        DIFFERENT jit cache keys — executables returning the
        normalized form would retrace against operands placed under
        the verbose one (one extra decode compile per engine, caught
        by the compile-count gate)."""
        axes = list(axes)
        while axes and axes[-1] is None:
            axes.pop()
        return P(*axes)

    def _leaf_spec(self, path, leaf) -> P:
        names = [_key_name(p) for p in path]
        last = names[-1] if names else None
        under_attn = any(n in self.attn_keys for n in names[:-1])
        if under_attn:
            if last in _ATTN_PARAM_SPECS and getattr(
                    leaf, "ndim", 0) == 2:
                spec = _ATTN_PARAM_SPECS[last]
                return self._norm(self.axis if a == "tp" else None
                                  for a in spec)
            if last in ("pk", "pv") and getattr(leaf, "ndim", 0) == 4:
                # paged pool blocks [n_blocks, block_tokens, H, dh]
                return self._norm((None, None, self.axis, None))
            if last in ("k", "v") and getattr(leaf, "ndim", 0) == 4:
                # a cold admission's dense row [1, H, W, dh]
                return self._norm((None, self.axis, None, None))
        return P()

    def spec_tree(self, tree):
        """PartitionSpec pytree for any engine operand/output tree,
        derived from leaf key paths (see module docstring)."""
        return jax.tree_util.tree_map_with_path(self._leaf_spec, tree)

    def sharding_tree(self, tree):
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: NamedSharding(self.mesh,
                                          self._leaf_spec(p, leaf)),
            tree)

    # -- placement ------------------------------------------------------
    def place(self, tree):
        """Commit a host/device pytree onto the mesh under its derived
        sharding (params at init, fresh KV pools at first admission) —
        so the wrapped executables never pay a resharding transfer."""
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: jax.device_put(
                leaf, NamedSharding(self.mesh,
                                    self._leaf_spec(p, leaf))),
            tree)

    def replicate(self, host_array):
        """Commit one host array onto the mesh fully replicated. The
        engine's per-dispatch block-table operand must enter every
        dispatch with the SAME (committed) sharding: a spec round
        chains the verify executable's OUTPUT tables (a committed
        ``P()`` array) into the decode dispatch, while a plain round
        builds the operand fresh on the host — uncommitted vs
        committed hash as different jit keys, which cost the spec+tp
        engine a second decode lowering (caught by the compile-budget
        gate). One array a dispatch, read by every layer: it is an
        argument of its own beside the donated pool (its key path
        names no attention layer, so ``_leaf_spec`` replicates it)."""
        return jax.device_put(host_array,
                              NamedSharding(self.mesh, P()))

    # -- shard_map wrapping --------------------------------------------
    def wrap(self, fn, donate_argnums=()):
        """The TP analogue of ``jax.jit(fn)``: the SAME engine step
        function becomes a fully-manual shard_map program over the tp
        axis, with in/out specs derived per leaf key path at trace
        time and the attention layers switched onto local heads + the
        output-projection all-reduce via ``tp_head_shards``. The
        jitted wrapper keeps the engine's compile-count discipline
        (``_cache_size`` reads through)."""
        axis, size, mesh = self.axis, self.size, self.mesh

        def sharded(*args):
            in_specs = tuple(self.spec_tree(a) for a in args)
            out_struct = jax.eval_shape(fn, *args)
            out_specs = self.spec_tree(out_struct)

            def body(*local):
                with tp_head_shards(axis, size):
                    return fn(*local)

            return shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             check_vma=False)(*args)

        return jax.jit(sharded, donate_argnums=donate_argnums)

    def all_ok(self, ok):
        """Combine a per-shard boolean verdict across shards (health
        sweeps must agree fleet-wide: a NaN lives on ONE shard's head
        slice but poisons the whole row/block)."""
        return jax.lax.psum(jnp.asarray(ok, jnp.int32),
                            self.axis) >= self.size

    # -- accounting -----------------------------------------------------
    def shard_bytes(self, tree) -> Dict[int, int]:
        """Per-shard addressable KV bytes of a (sharded) pytree — the
        ``total/TP`` acceptance arithmetic and the per-shard
        ``serving_tp_kv_bytes`` gauges read this."""
        per: Dict[int, int] = {i: 0 for i in range(self.size)}
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:
                continue
            seen = set()
            for s in shards:
                dev = s.device.id
                idx = self._device_shard_index(dev)
                if idx is None or (idx, id(leaf)) in seen:
                    continue
                seen.add((idx, id(leaf)))
                per[idx] += int(np.prod(s.data.shape)
                                * s.data.dtype.itemsize)
        return per

    def _device_shard_index(self, device_id: int) -> Optional[int]:
        for i, dev in enumerate(self.mesh.devices.flat):
            if dev.id == device_id:
                return i
        return None
