"""dp x pp x tp pipeline parallelism for homogeneous-stage models.

Round-4 review item 3. The packed-row ``PipelineTrainer``
(pipeline_parallel.py) achieves 1/S stage memory for ARBITRARY
heterogeneous stacks by flattening each stage into one row of a [S, K]
buffer — a layout that cannot express per-TENSOR shardings, so pp could
not compose with tp/fsdp there (documented at its "Why pp composes with
dp but not tp" note). But the models that dominate TPU practice —
transformer stacks of identical blocks — don't need the packed row at
all: their stages are structurally identical, so stage parameters can
be STACKED on a leading ``pp`` axis as ordinary pytrees
(leaf [S, k, ...]) with per-tensor PartitionSpecs on the tensor dims.

That unlocks the canonical large-model TPU topology on one mesh:

- **pp** (manual): the GPipe microbatch schedule runs inside a
  shard_map that is manual over ``pp`` only — activations hop
  stage-to-stage via ``lax.ppermute``; each device's local stack slice
  is its stage's k blocks (1/S of the stack).
- **tp** (GSPMD-auto): block weights carry Megatron column/row specs on
  their trailing dims (P("pp", None, None, "tp") etc. — per-tensor
  layouts, exactly what the packed row could not express); XLA inserts
  the two all-reduces per block inside each pipeline tick. Per-device
  stack memory becomes ~1/(S*T) of the model.
- **dp** (GSPMD-auto): the batch dim is sharded over ``dp``; gradient
  all-reduces fall out of the global-batch mean.

Layer grouping: the trainer finds the maximal contiguous run of
structurally identical layers (same bean type, same leaf shapes, same
resolved updater/regularization hyperparameters), requires its length
to be divisible by S, and replicates everything before (``pre`` — e.g.
the flagship's input-projection block) and after (``post`` — final
LayerNorm + output head) on every device. pre/post are the cheap ends
of an LM; the stack is where the memory and FLOPs live.

Trajectory parity with single-device ``net.fit`` on the same batches is
asserted in tests/test_homogeneous_pipeline.py, and the 1/(S*T) stage
bytes in the same file — mirroring test_pipeline_expert.py:634's
accounting for the packed trainer.

**Interleaved virtual stages** (``interleave=V``): each device hosts V
chunks of the stack round-robin (device d holds chunks {j*S + d}), so
chunk c -> c+1 is always one +1 ring hop and the pipeline fill costs a
chunk-time, not a stage-time — bubble (S-1)/(S*V + M - 1) at M = S
(the general M <= S form is (V*(S-M) + M-1)/(S*V + M-1)), ~1/V of
GPipe's at the same microbatch count (Megatron-LM interleaved schedule,
arXiv:2104.04473 §2.2; here the backward schedule is the autodiff
transpose of the same loop). The win matters because GPipe's
alternative — raising M — multiplies live activation memory; V buys
the same bubble at M = S. Enforced: M <= S when V > 1 (keeps the
round-robin schedule collision-free: one chunk-application per device
per tick).

**Sequence parallelism inside the ticks** (``sp_axis``): activations
carry their time axis sharded over sp end-to-end — the blocks'
attention runs the ring (or Ulysses) schedule over sp per tick
(conf-level ``ring_axis``, as in ParallelTrainer's sp), the pp
ppermute hops each (stage, time-shard) slice independently, and the
loss/gradients reduce across time shards with the exact global-mean
scaling. Composes with everything above: dp x pp x sp x tp on one
mesh, plus interleave — trajectory parity asserted for each
(tests/test_homogeneous_pipeline.py TestSequenceParallelComposition).
"""

from __future__ import annotations

import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.optimize.telemetry import (
    batch_counts,
    emit_step_span,
    mesh_args,
    window_counts,
)

Array = jax.Array

# Megatron specs for a stacked TransformerBlock leaf ([S, k] + tensor
# dims): qkv + FFN-in column-parallel, attn-out + FFN-out row-parallel.
_BLOCK_TP_COL = {"Wq", "Wk", "Wv", "W1"}
_BLOCK_TP_ROW = {"Wo", "W2"}
_BLOCK_TP_VEC = {"b1"}  # [dff] vectors, sharded like the col outputs


def _layer_signature(net, i: int):
    """Structural identity key for stacking layer i with its peers."""
    c = net.conf.confs[i]
    leaves = jax.tree.flatten(net.params[str(i)])
    shapes = tuple(
        (tuple(l.shape), str(l.dtype)) for l in leaves[0])
    upd = net._updaters[i]
    return (
        type(c.layer).__name__,
        str(leaves[1]),
        shapes,
        upd.rule,
        tuple(sorted((k, str(v)) for k, v in upd.hp.items())),
        str(c.resolved("gradient_normalization")),
        float(c.resolved("gradient_normalization_threshold")),
        bool(c.use_regularization),
        float(c.resolved("l1") or 0.0),
        float(c.resolved("l2") or 0.0),
        float(c.resolved("learning_rate")),
    )


def interleaved_bubble_fraction(n_stages: int, n_microbatches: int,
                                interleave: int = 1) -> float:
    """Idle fraction of the (possibly interleaved) schedule, in
    chunk-time units: each device computes M*V useful chunk ticks of
    the S*V + M - 1 total. V=1 reduces to GPipe's (S-1)/(M+S-1); at
    M = S, depth V cuts the bubble to (S-1)/(S*V + S - 1) — the
    Megatron-LM interleaving win (arXiv:2104.04473 §2.2), bought with
    V ring hops per microbatch instead of one."""
    s, m, v = n_stages, n_microbatches, interleave
    if v > 1 and m > s:
        raise ValueError(
            f"interleave={v} requires n_microbatches <= n_stages "
            f"({m} > {s}) — the closed form (and the trainer's "
            "schedule) is only defined for the collision-free regime")
    total = s * v + m - 1
    return (total - m * v) / total


def find_homogeneous_run(net):
    """(start, end) of the longest contiguous run of structurally
    identical layers (ties: the earliest)."""
    n = net.n_layers
    sigs = [_layer_signature(net, i) for i in range(n)]
    best = (0, 1)
    i = 0
    while i < n:
        j = i + 1
        while j < n and sigs[j] == sigs[i]:
            j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = j
    return best


class HomogeneousPipelineTrainer:
    """GPipe over stage-STACKED homogeneous blocks, composing dp and tp
    on the same mesh (see module docstring).

    Limitations (enforced): plain-SGD-family full-BPTT training,
    stateless layers (no BatchNorm running stats), no mask arrays, and
    tp requires the stacked block to be a TransformerBlock (the
    Megatron specs are defined for its parameter names).
    """

    def __init__(
        self,
        net,
        mesh: Mesh,
        pp_axis: str = "pp",
        tp_axis: Optional[str] = None,
        dp_axis: Optional[str] = None,
        sp_axis: Optional[str] = None,
        n_microbatches: int = 4,
        interleave: int = 1,
        tracer=None,
    ):
        from deeplearning4j_tpu.nn.conf.enums import (
            BackpropType,
            OptimizationAlgorithm,
        )

        # Optional span sink (ISSUE 8): per-step train.parallel_step
        # spans annotated with the mesh config.
        self.tracer = tracer
        from deeplearning4j_tpu.nn.layers.attention import (
            TransformerBlock,
        )

        net.init()
        if net.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "HomogeneousPipelineTrainer does not support tBPTT")
        algo = net.conf.confs[0].optimization_algo
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            raise ValueError(
                "HomogeneousPipelineTrainer requires "
                f"STOCHASTIC_GRADIENT_DESCENT (got {algo})")
        stateful = [
            si for si, st in (net.state or {}).items()
            if not (isinstance(st, dict) and set(st) <= {"aux_loss"})]
        if stateful:
            raise ValueError(
                f"layers {stateful} carry running state; use the "
                "packed-row PipelineTrainer (ghost-batch-norm) instead")
        self.net = net
        self.mesh = mesh
        self.pp_axis = pp_axis
        self.S = int(mesh.shape[pp_axis])
        self.M = int(n_microbatches)
        # Interleaved (virtual-stage) schedule: each device hosts V
        # chunks of the stack round-robin (device d holds chunks
        # {j*S + d}), so the pipeline fill costs one CHUNK-time instead
        # of one stage-time — bubble (S-1)/(S*V + M - 1) at M = S
        # (general M <= S: (V*(S-M) + M-1)/(S*V + M-1)) vs GPipe's
        # (S-1)/(M+S-1), i.e. ~V x smaller at M = S. The
        # schedule stays collision-free (one chunk-application per
        # device per tick) when M <= S, which is exactly the regime
        # interleaving is FOR: GPipe needs M >> S for a small bubble
        # (activation liveness grows with M); interleave V gets the
        # same bubble at M = S with 1/V of that liveness
        # (Megatron-LM interleaved schedule, arXiv:2104.04473 §2.2,
        # recast for the autodiff-transposed backward).
        self.V = int(interleave)
        if self.V < 1:
            raise ValueError(f"interleave must be >= 1 (got {self.V})")
        if self.V > 1 and self.M > self.S:
            raise ValueError(
                f"interleave={self.V} requires n_microbatches <= pp "
                f"({self.M} > {self.S}): the round-robin schedule is "
                "collision-free only when a microbatch group fits the "
                "ring; raise pp, lower M, or use interleave=1")
        if dp_axis is None and "dp" in mesh.axis_names:
            dp_axis = "dp"
        self.dp_axis = (dp_axis
                        if dp_axis and dp_axis in mesh.axis_names
                        else None)
        self.tp_axis = (tp_axis
                        if tp_axis and tp_axis in mesh.axis_names
                        else None)
        self.R = int(mesh.shape[self.dp_axis]) if self.dp_axis else 1
        # Sequence parallelism INSIDE the pipeline ticks: the time axis
        # of every activation is sharded over sp, the blocks' attention
        # runs the ring/Ulysses schedule over it (conf-level ring_axis,
        # same device as ParallelTrainer's sp), and the pp ppermute
        # hops each (stage, time-shard)'s slice independently — the
        # long-context + large-model topology dp x pp x sp (x tp) on
        # ONE mesh.
        self.sp_axis = (sp_axis
                        if sp_axis and sp_axis in mesh.axis_names
                        else None)
        self.SPn = (int(mesh.shape[self.sp_axis])
                    if self.sp_axis else 1)

        start, end = find_homogeneous_run(net)
        run = end - start
        chunks = self.S * self.V
        if run < chunks or run % chunks:
            raise ValueError(
                f"homogeneous run of {run} identical layers (layers "
                f"{start}..{end - 1}) is not divisible by "
                f"pp x interleave = {self.S} x {self.V}; add/remove "
                "blocks, lower interleave, or use the packed-row "
                "PipelineTrainer")
        self.run = (start, end)
        self.k = run // chunks  # blocks per chunk (per stage when V=1)
        self.pre_idx = list(range(0, start))
        self.post_idx = list(range(end, net.n_layers))
        if not hasattr(net._impls[-1], "loss"):
            raise ValueError("last layer must be an output layer")
        block_bean = net.conf.confs[start].layer
        self._block_is_tb = isinstance(block_bean, TransformerBlock)
        if self.tp_axis:
            if not self._block_is_tb:
                raise ValueError(
                    "tp_axis requires the stacked block to be a "
                    f"TransformerBlock (got "
                    f"{type(block_bean).__name__})")
            T = int(mesh.shape[self.tp_axis])
            if block_bean.n_heads % T:
                raise ValueError(
                    f"n_heads {block_bean.n_heads} not divisible by "
                    f"mesh tp={T}")
        if self.sp_axis:
            # The time axis is SHARDED end-to-end: every layer must
            # either run a sequence-parallel schedule over sp or be
            # per-timestep, or it would silently compute within its
            # local shard (mirrors ParallelTrainer's conf-level sp
            # validation, data_parallel.py — minus GravesLSTM/GRU,
            # whose sp_scan recurrence is not wired into the pipeline
            # tick schedule).
            from deeplearning4j_tpu.nn.conf import layers as L
            from deeplearning4j_tpu.nn.layers.attention import (
                ATTENTION_BEANS,
            )
            from deeplearning4j_tpu.nn.layers.moe import MoeDense

            if self.sp_axis in (self.dp_axis, self.tp_axis, pp_axis):
                raise ValueError(
                    f"sp_axis {self.sp_axis!r} must name a mesh axis "
                    "distinct from dp/pp/tp: the time axis shards over "
                    "its own axis")
            for i, c in enumerate(net.conf.confs):
                lc = c.layer
                if net.conf.preprocessor_for(i) is not None:
                    raise ValueError(
                        f"layer {i}: input preprocessors reshape "
                        "across the sharded time axis and are not "
                        "supported under sp_axis")
                if isinstance(lc, ATTENTION_BEANS):
                    if getattr(lc, "ring_axis", None) != self.sp_axis:
                        raise ValueError(
                            f"layer {i}: sp_axis={self.sp_axis!r} "
                            f"requires {type(lc).__name__}.ring_axis="
                            f"{self.sp_axis!r} (got {lc.ring_axis!r})"
                            " — build the conf with ring_axis (e.g. "
                            "transformer_lm_flagship(ring_axis=...))")
                elif isinstance(lc, (L.RnnOutputLayer,
                                     L.LayerNormalization, MoeDense)):
                    pass  # per-timestep/per-token: shards trivially
                else:
                    raise ValueError(
                        f"layer {i} ({type(lc).__name__}) is not "
                        "time-shardable under the pipelined sp "
                        "schedule: attention beans with "
                        "ring_axis=sp_axis plus LayerNormalization/"
                        "RnnOutputLayer/MoeDense are supported "
                        "(GravesLSTM/GRU sequence parallelism is the "
                        "ParallelTrainer(sp_axis=...) path)")
        self._stack_conf = net.conf.confs[start]
        self._stack_updater = net._updaters[start]
        self._step_cache = {}
        self._state = None  # (pre, stack, post, pre_u, stack_u, post_u)
        self._synced = None
        self._gather_cache = {}  # multihost stacked-leaf gather (jit)

    # -- stacked-state lifecycle --------------------------------------
    def _stack_leaf_spec(self, name: str) -> P:
        """PartitionSpec for stacked leaf ``name`` ([S, k] + tensor
        dims, or [V, S, k] + tensor dims when interleaved): pp on the
        stage axis, Megatron tp on the tensor dims. Chunk j of device d
        (= chunk index j*S + d in execution order) sits at [j, d] — a
        P(None, pp) layout keeps the pp axis contiguous so each device
        holds exactly its V round-robin chunks."""
        tp = self.tp_axis
        if not tp or not self._block_is_tb:
            spec = P(self.pp_axis)
        elif name in _BLOCK_TP_COL:
            spec = P(self.pp_axis, None, None, tp)
        elif name in _BLOCK_TP_ROW:
            spec = P(self.pp_axis, None, tp, None)
        elif name in _BLOCK_TP_VEC:
            spec = P(self.pp_axis, None, tp)
        else:
            spec = P(self.pp_axis)
        if self.V > 1:
            spec = P(None, *spec)
        return spec

    def _layer_of(self, v: int, s: int, b: int) -> int:
        """Conf index of block ``b`` of chunk [v, s] — chunk c = v*S+s
        runs blocks [c*k, (c+1)*k) of the homogeneous run."""
        return self.run[0] + (v * self.S + s) * self.k + b

    def _stack_tree(self, tree):
        """{name: leaf} per stacked layer -> {name: [S, k, ...]} (or
        [V, S, k, ...] interleaved) as HOST numpy (device_put with the
        P(pp, ...) sharding then lands each stage row only on its
        stage's devices — the full stack never materializes on one
        device)."""
        start, _ = self.run
        names = list(tree[str(start)].keys())
        out = {}
        for name in names:
            vs = [
                np.stack([
                    np.stack([
                        np.asarray(tree[str(self._layer_of(v, s, b))][
                            name])
                        for b in range(self.k)])
                    for s in range(self.S)])
                for v in range(self.V)]
            out[name] = np.stack(vs) if self.V > 1 else vs[0]
        return out

    def _gatherable(self, leaf):
        """Stacked leaves are P(pp, ...)-sharded: when the pp axis
        spans processes their shards are non-addressable, and the
        shared helper reshards to replicated first (no-op — and no
        collective — when pp stays within this host)."""
        from deeplearning4j_tpu.parallel.mesh import gather_for_host

        return gather_for_host(self.mesh, leaf, self._gather_cache)

    def _unstack_into(self, tree, stacked):
        for name, leaf in stacked.items():
            mat = np.asarray(jax.device_get(self._gatherable(leaf)))
            if self.V == 1:
                mat = mat[None]
            for v in range(self.V):
                for s in range(self.S):
                    for b in range(self.k):
                        tree[str(self._layer_of(v, s, b))][name] = (
                            mat[v, s, b])

    def _ensure_placed(self):
        net = self.net
        token = (id(net.params), getattr(net, "params_version", 0))
        if self._state is not None and self._synced == token:
            return
        mesh = self.mesh
        rep = NamedSharding(mesh, P())

        def put_rep(tree):
            return jax.device_put(
                jax.tree.map(jnp.asarray, tree), rep)

        pre_p = put_rep({str(i): net.params[str(i)]
                         for i in self.pre_idx})
        post_p = put_rep({str(i): net.params[str(i)]
                          for i in self.post_idx})
        pre_u = put_rep({str(i): net.updater_state[str(i)]
                         for i in self.pre_idx})
        post_u = put_rep({str(i): net.updater_state[str(i)]
                          for i in self.post_idx})
        stack_p = {
            name: jax.device_put(
                leaf, NamedSharding(mesh, self._stack_leaf_spec(name)))
            for name, leaf in self._stack_tree(net.params).items()}
        # updater-state leaves mirror the param leaves they track
        # ({"m": {name: leaf}} for Adam) — shard them identically
        stacked_u_raw = self._stack_updater_state()
        stack_u = {
            slot: {
                name: jax.device_put(
                    leaf,
                    NamedSharding(mesh, self._stack_leaf_spec(name)))
                for name, leaf in sub.items()}
            for slot, sub in stacked_u_raw.items()}
        self._state = (pre_p, stack_p, post_p, pre_u, stack_u, post_u)
        self._synced = token

    def _stack_updater_state(self):
        """updater_state["i"] = {slot: {name: leaf}} -> {slot: {name:
        [S, k, ...]}} ([V, S, k, ...] interleaved; empty for SGD)."""
        ustate = self.net.updater_state
        proto = ustate[str(self.run[0])]

        def stack_one(slot, name):
            vs = [
                np.stack([
                    np.stack([
                        np.asarray(ustate[str(self._layer_of(
                            v, s, b))][slot][name])
                        for b in range(self.k)])
                    for s in range(self.S)])
                for v in range(self.V)]
            return np.stack(vs) if self.V > 1 else vs[0]

        return {
            slot: {name: stack_one(slot, name) for name in proto[slot]}
            for slot in proto}

    def _sync_to_net(self):
        net = self.net
        pre_p, stack_p, post_p, pre_u, stack_u, post_u = self._state
        for i in self.pre_idx + self.post_idx:
            si = str(i)
            src = pre_p if i in self.pre_idx else post_p
            srcu = pre_u if i in self.pre_idx else post_u
            net.params[si] = jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), src[si])
            net.updater_state[si] = jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), srcu[si])
        self._unstack_into(net.params, stack_p)
        for slot, sub in stack_u.items():
            for name, leaf in sub.items():
                mat = np.asarray(jax.device_get(self._gatherable(leaf)))
                if self.V == 1:
                    mat = mat[None]
                for v in range(self.V):
                    for s in range(self.S):
                        for b in range(self.k):
                            net.updater_state[str(self._layer_of(
                                v, s, b))][slot][name] = mat[v, s, b]
        self._synced = (id(net.params),
                        getattr(net, "params_version", 0))

    def per_device_state_bytes(self) -> dict:
        """{device: stacked params+updater bytes resident} — the
        1/(S*T) accounting (replicated pre/post excluded: they are the
        deliberately-shared cheap ends)."""
        self._ensure_placed()
        _, stack_p, _, _, stack_u, _ = self._state
        acc: dict = {}
        leaves = list(stack_p.values()) + [
            leaf for sub in stack_u.values() for leaf in sub.values()]
        for buf in leaves:
            for shard in buf.addressable_shards:
                acc[shard.device] = (acc.get(shard.device, 0)
                                     + shard.data.nbytes)
        return acc

    def total_stack_bytes(self) -> int:
        self._ensure_placed()
        _, stack_p, _, _, stack_u, _ = self._state
        leaves = list(stack_p.values()) + [
            leaf for sub in stack_u.values() for leaf in sub.values()]
        return int(sum(l.size * l.dtype.itemsize for l in leaves))

    # -- the step ------------------------------------------------------
    def _apply_range(self, idxs, params, x, rngs, train):
        """Apply replicated layers ``idxs`` (with preprocessors)."""
        from deeplearning4j_tpu.nn.multilayer import _cast_floating

        net = self.net
        cd = net._compute_dtype
        last = net.n_layers - 1
        for i in idxs:
            c = net.conf.confs[i]
            pp = net.conf.preprocessor_for(i)
            if pp is not None:
                x = pp.pre_process(x, rngs[i] if train else None)
            p = params[str(i)]
            if cd is not None and i == last:
                x = _cast_floating(x, net._dtype)  # f32 output head
            elif cd is not None:
                p = jax.tree.map(
                    functools.partial(_cast_floating, dtype=cd), p)
            x, _ = net._impls[i].apply(
                c, p, x, state=None, train=train, rng=rngs[i],
                mask=None)
        return x

    def _block_apply(self, stack_local, x, rng, train, chunk=None):
        """One chunk's k blocks, sequentially via lax.scan over the
        block axis. stack_local leaves are [1, k, ...] (V=1) or
        [V, 1, k, ...] with ``chunk`` the (traced) local chunk index
        to run this tick."""
        from deeplearning4j_tpu.nn.multilayer import _cast_floating

        net = self.net
        conf = self._stack_conf
        impl = net._impls[self.run[0]]
        cd = net._compute_dtype

        def one(x, inp):
            p, key = inp
            if cd is not None:
                p = jax.tree.map(
                    functools.partial(_cast_floating, dtype=cd), p)

            def apply(pp_, xx):
                y, _ = impl.apply(conf, pp_, xx, state=None,
                                  train=train, rng=key, mask=None)
                return y

            if net.conf.remat:
                apply = jax.checkpoint(apply)
            return apply(p, x), None

        keys = (jax.random.split(rng, self.k) if rng is not None
                else jnp.zeros((self.k, 2), jnp.uint32))
        if self.V == 1:
            # drop the local stage axis ([1, k, ...] -> [k, ...])
            blocks = jax.tree.map(lambda l: l[0], stack_local)
        else:
            # select this tick's chunk ([V, 1, k, ...] -> [k, ...]);
            # a dynamic gather on the leading V axis — XLA keeps the
            # non-selected chunks untouched on-device.
            blocks = jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(
                    l, chunk, 0, keepdims=False)[0], stack_local)
        x, _ = lax.scan(one, x, (blocks, keys))
        return x

    def _build_step(self, feats_shape, labels_shape, scan=False):
        from deeplearning4j_tpu.nn.multilayer import (
            layer_reg_score,
            layer_update,
        )

        net = self.net
        S, M, R, V = self.S, self.M, self.R, self.V
        SP, SPn = self.sp_axis, self.SPn
        axis = self.pp_axis
        cd = net._compute_dtype
        B = feats_shape[0]
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        out_conf = net.conf.confs[-1]
        out_impl = net._impls[-1]
        start, _ = self.run
        hop_dtype = cd if cd is not None else net._dtype

        def local_step(pre_p, stack_p, post_p, pre_u, stack_u, post_u,
                       iteration, rng, feats, labels):
            idx = lax.axis_index(axis)
            if SP:
                # Decorrelate dropout draws across time shards (parity
                # with the unsharded net holds for dropout-free confs,
                # as in ParallelTrainer._sp_body_core).
                rng = jax.random.fold_in(rng, lax.axis_index(SP))

            def loss_fn(theta):
                pre, stack_local, post = theta
                f = feats.astype(cd) if cd is not None else feats
                x_mbs = f.reshape((M, mb) + f.shape[1:])
                y_mbs = labels.reshape((M, mb) + labels.shape[1:])
                # Hop-buffer shape: the block interface [mb, width,
                # T...] probed abstractly on one LOCAL microbatch
                # (under sp the pre group contains ring collectives,
                # so the probe must run inside the manual context and
                # its shapes carry T_local = T/SPn).
                probe_local = jax.eval_shape(
                    lambda xx: self._apply_range(
                        self.pre_idx, pre, xx,
                        [None] * net.n_layers, False),
                    x_mbs[0])
                buf0 = jnp.zeros(probe_local.shape, hop_dtype)
                z = jnp.zeros((), net._dtype)

                def tick(t, carry):
                    buf, loss_acc = carry
                    # Device idx at tick t runs the unit (chunk c =
                    # jc*S + idx, microbatch m = t - c): microbatch m
                    # enters chunk c at tick c + m, and chunk c+1 is
                    # always one ring hop away (device (c+1) % S), so
                    # the +1 ppermute serves every interleave depth.
                    # With M <= S (enforced for V > 1) at most one
                    # (jc, m) is valid per device per tick; V == 1
                    # reduces to the plain GPipe indexing.
                    rel = t - idx
                    jc = (jnp.clip(rel // S, 0, V - 1) if V > 1 else 0)
                    m_raw = rel - jc * S
                    mb_idx = jnp.clip(m_raw, 0, M - 1)
                    valid = (m_raw >= 0) & (m_raw < M)
                    rngs = list(jax.random.split(
                        jax.random.fold_in(rng, mb_idx),
                        net.n_layers))
                    feed = x_mbs[mb_idx]
                    h_pre = self._apply_range(
                        self.pre_idx, pre, feed, rngs, True)
                    entry = ((idx == 0) & (jc == 0) if V > 1
                             else idx == 0)
                    xin = jnp.where(entry, h_pre.astype(hop_dtype),
                                    buf)
                    y = self._block_apply(
                        stack_local, xin,
                        jax.random.fold_in(rngs[start], jc * S + idx),
                        True, chunk=jc if V > 1 else None)
                    out = self._apply_range(
                        self.post_idx, post, y, rngs, True)
                    if cd is not None:
                        out = out.astype(net._dtype)
                    loss_mb = out_impl.loss(
                        out_conf, out, y_mbs[mb_idx], None)
                    write = ((idx == S - 1) & (jc == V - 1) & valid
                             if V > 1 else (idx == S - 1) & valid)
                    loss_acc = loss_acc + jnp.where(write, loss_mb, z)
                    perm = [(i, (i + 1) % S) for i in range(S)]
                    buf = lax.ppermute(
                        y.astype(hop_dtype), axis, perm)
                    return buf, loss_acc

                _, loss_sum = lax.fori_loop(0, S * V + M - 1, tick,
                                            (buf0, z))
                # Local (unreduced) contribution — see
                # pipeline_parallel.py on why the psum must stay
                # OUTSIDE the differentiated function. Replicated
                # pre/post reg divides by S so the pp-psum counts it
                # once; stacked reg is per-stage-local already.
                reg = jnp.zeros((), net._dtype)
                for i in self.pre_idx + self.post_idx:
                    reg = reg + layer_reg_score(
                        net.conf.confs[i],
                        (pre if i in self.pre_idx else post)[str(i)])
                reg = reg / S
                reg_one = lambda tree: layer_reg_score(  # noqa: E731
                    self._stack_conf, tree)
                if V == 1:
                    stack_reg = jax.vmap(reg_one)(
                        jax.tree.map(lambda l: l[0], stack_local))
                else:
                    stack_reg = jax.vmap(jax.vmap(reg_one))(
                        jax.tree.map(lambda l: l[:, 0], stack_local))
                # Under sp each device's loss_mb is the mean over ITS
                # equal-size time shard: the global mean is the psum of
                # local/SPn (reg replicated over sp divides the same
                # way so the sp-psum counts it once).
                return (loss_sum / M + reg + jnp.sum(stack_reg)) / SPn

            score_local, grads = jax.value_and_grad(loss_fn)(
                (pre_p, stack_p, post_p))
            g_pre, g_stack, g_post = grads
            # pre/post gradients live on stage 0 / S-1 only; the ring
            # sum recovers the full gradient (zeros elsewhere). Under
            # sp every gradient also sums across time shards (params
            # replicated over sp; each shard computed a partial term).
            axes = (axis,) + ((SP,) if SP else ())
            g_pre = lax.psum(g_pre, axes)
            g_post = lax.psum(g_post, axes)
            score = lax.psum(score_local, axes)
            if SP:
                g_stack = lax.psum(g_stack, SP)

            # -- updates (dp reduction falls out of the global-batch
            # mean under GSPMD; no explicit dp collective needed) --
            new_pre, new_pre_u = {}, {}
            for i in self.pre_idx:
                si = str(i)
                upd, new_pre_u[si] = layer_update(
                    net.conf.confs[i], net._updaters[i], g_pre[si],
                    pre_u[si], iteration)
                new_pre[si] = jax.tree.map(
                    lambda p, u: p - u, pre_p[si], upd)
            new_post, new_post_u = {}, {}
            for i in self.post_idx:
                si = str(i)
                upd, new_post_u[si] = layer_update(
                    net.conf.confs[i], net._updaters[i], g_post[si],
                    post_u[si], iteration)
                new_post[si] = jax.tree.map(
                    lambda p, u: p - u, post_p[si], upd)

            # stacked: per-(stage, block) layer_update, vmapped twice —
            # identical math to the per-layer loop, batched.
            def upd_block(g, u):
                return layer_update(
                    self._stack_conf, self._stack_updater, g, u,
                    iteration)

            vm_upd = jax.vmap(jax.vmap(upd_block))
            if V > 1:  # extra leading chunk axis [V, 1, k, ...]
                vm_upd = jax.vmap(vm_upd)
            upd_sb, new_stack_u = vm_upd(g_stack, stack_u)
            new_stack = jax.tree.map(
                lambda p, u: p - u, stack_p, upd_sb)
            return (new_pre, new_stack, new_post, new_pre_u,
                    new_stack_u, new_post_u, score)

        if not scan:
            fn = local_step
        else:
            def fn(pre_p, stack_p, post_p, pre_u, stack_u, post_u,
                   iteration, rng, fs, ys):
                def body(carry, inp):
                    a, b, c, d, e, f_, it = carry
                    a, b, c, d, e, f_, score = local_step(
                        a, b, c, d, e, f_, it,
                        jax.random.fold_in(rng, inp["k"]),
                        inp["f"], inp["y"])
                    return (a, b, c, d, e, f_, it + 1), score

                xs = {"f": fs, "y": ys, "k": jnp.arange(fs.shape[0])}
                (pre_p, stack_p, post_p, pre_u, stack_u, post_u,
                 _), scores = lax.scan(
                    body,
                    (pre_p, stack_p, post_p, pre_u, stack_u, post_u,
                     iteration), xs)
                return (pre_p, stack_p, post_p, pre_u, stack_u,
                        post_u, scores)

        rep = P()
        pp_lead = (P(None, self.pp_axis) if self.V > 1
                   else P(self.pp_axis))
        is_arr = lambda x: isinstance(  # noqa: E731
            x, (jax.Array, np.ndarray))
        pre_spec = jax.tree.map(
            lambda _: rep, self._state[0], is_leaf=is_arr)
        post_spec = jax.tree.map(
            lambda _: rep, self._state[2], is_leaf=is_arr)
        preu_spec = jax.tree.map(
            lambda _: rep, self._state[3], is_leaf=is_arr)
        postu_spec = jax.tree.map(
            lambda _: rep, self._state[5], is_leaf=is_arr)
        stack_spec = jax.tree.map(
            lambda _: pp_lead, self._state[1], is_leaf=is_arr)
        stacku_spec = jax.tree.map(
            lambda _: pp_lead, self._state[4], is_leaf=is_arr)
        # Batch specs are P() over the MANUAL axes except the time dim,
        # which splits over sp when sequence parallelism is on; the dp
        # sharding rides the input NamedSharding through the auto axes.
        if self.sp_axis:
            bspec = (P(None, None, None, self.sp_axis) if scan
                     else P(None, None, self.sp_axis))
        else:
            bspec = rep
        manual = {self.pp_axis} | (
            {self.sp_axis} if self.sp_axis else set())
        step = shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(pre_spec, stack_spec, post_spec, preu_spec,
                      stacku_spec, postu_spec, rep, rep, bspec, bspec),
            out_specs=(pre_spec, stack_spec, post_spec, preu_spec,
                       stacku_spec, postu_spec, rep),
            check_vma=False,
            axis_names=frozenset(manual),
        )
        return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4, 5))

    # -- public API ----------------------------------------------------
    def _validate_sp_batch(self, feats_shape, labels_shape):
        """Crafted diagnostics BEFORE device_put (whose PartitionSpec
        rank/divisibility errors are opaque): sp shards the time axis
        of [B, C, T] features AND labels. Shape-only — no host copy."""
        if not self.sp_axis:
            return
        for what, shape in (("features", tuple(feats_shape)),
                            ("labels", tuple(labels_shape))):
            if len(shape) != 3:
                raise ValueError(
                    f"sp_axis shards the time axis of [B, C, T] "
                    f"batches; got {what} of rank {len(shape)} "
                    f"(shape {shape})")
            if shape[2] % self.SPn:
                raise ValueError(
                    f"{what} time axis {shape[2]} not divisible "
                    f"by sp={self.SPn}")

    def _data_sharding(self, stacked=False):
        # batch dim over dp (GSPMD-auto), time dim over sp (manual);
        # replicated over pp/tp
        if self.sp_axis:
            spec = (P(None, self.dp_axis, None, self.sp_axis) if stacked
                    else P(self.dp_axis, None, self.sp_axis))
        elif self.dp_axis is None:
            return NamedSharding(self.mesh, P())
        else:
            spec = (P(None, self.dp_axis) if stacked
                    else P(self.dp_axis))
        return NamedSharding(self.mesh, spec)

    def _trace_args(self, **extra):
        axes = {"pp": self.pp_axis}
        for name, ax in (("dp", self.dp_axis), ("tp", self.tp_axis),
                         ("sp", self.sp_axis)):
            if ax:
                axes[name] = ax
        return mesh_args(self.mesh, "homogeneous_pipeline",
                         n_microbatches=self.M, interleave=self.V,
                         **axes, **extra)

    def _emit_step_span(self, dispatch_s: float, **extra) -> None:
        if self.tracer is not None:
            emit_step_span(self.tracer, dispatch_s,
                           self._trace_args(**extra))

    def fit(self, data, labels=None) -> float:
        from deeplearning4j_tpu.datasets.dataset import DataSet

        net = self.net
        if labels is not None:
            data = DataSet(data, labels)
        batches = [data] if isinstance(data, DataSet) else data
        self._ensure_placed()
        score = float("nan")
        sh = self._data_sharding()
        for ds in batches:
            if ds.features_mask is not None or ds.labels_mask is not None:
                raise ValueError(
                    "HomogeneousPipelineTrainer does not support mask "
                    "arrays; use the packed-row PipelineTrainer")
            self._validate_sp_batch(np.shape(ds.features),
                                    np.shape(ds.labels))
            feats = jax.device_put(
                jnp.asarray(ds.features, net._dtype), sh)
            labs = jax.device_put(
                jnp.asarray(ds.labels, net._dtype), sh)
            key = (feats.shape, labs.shape)
            if key not in self._step_cache:
                self._step_cache[key] = self._build_step(
                    feats.shape, labs.shape)
            net._key, sub = jax.random.split(net._key)
            t0 = time.perf_counter()
            (*state, s) = self._step_cache[key](
                *self._state, net.iteration, sub, feats, labs)
            dispatch_s = time.perf_counter() - t0
            examples, tokens = batch_counts(feats)
            net.train_telemetry.record_step(
                dispatch_s=dispatch_s, examples=examples, tokens=tokens)
            self._emit_step_span(dispatch_s,
                                 iteration=net.iteration + 1)
            self._state = tuple(state)
            net.score_value = s
            net.iteration += 1
            score = float(s)
        self._sync_to_net()
        return score

    def fit_scan(self, features_stacked, labels_stacked):
        net = self.net
        self._ensure_placed()
        self._validate_sp_batch(np.shape(features_stacked)[1:],
                                np.shape(labels_stacked)[1:])
        sh = self._data_sharding(stacked=True)
        fs = jax.device_put(
            jnp.asarray(features_stacked, net._dtype), sh)
        ys = jax.device_put(
            jnp.asarray(labels_stacked, net._dtype), sh)
        key = ("scan", fs.shape, ys.shape)
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(
                fs.shape[1:], ys.shape[1:], scan=True)
        net._key, sub = jax.random.split(net._key)
        t0 = time.perf_counter()
        (*state, scores) = self._step_cache[key](
            *self._state, net.iteration, sub, fs, ys)
        dispatch_s = time.perf_counter() - t0
        k, examples, tokens = window_counts(fs.shape)
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, steps=k, examples=examples,
            tokens=tokens)
        self._emit_step_span(dispatch_s, steps=k,
                             iteration=net.iteration + k, fused="scan")
        self._state = tuple(state)
        net.iteration += int(fs.shape[0])
        net.score_value = scores[-1]
        self._sync_to_net()
        return scores
