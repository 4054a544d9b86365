"""Pipeline parallelism: GPipe-style microbatched stage execution.

NEW capability relative to the reference (SURVEY.md §2.7 "NOT present"
list). Layers are partitioned into S stages laid out along the mesh's
``pp`` axis; a batch is split into M microbatches that stream through the
ring — stage s computes microbatch m while stage s-1 computes m+1 —
activations hop stage-to-stage via ``lax.ppermute`` over ICI. The backward
pass falls out of ``jax.grad`` through the loop: XLA reverses the
collective permutes, giving the symmetric backward pipeline.

Expressed entirely as shard_map + fori_loop: per-device FLOPs drop to 1/S
of the model, bubble fraction = (S-1)/(M+S-1), exactly the GPipe schedule.

Two levels:
- ``pipeline_apply`` / ``make_pipelined_mlp``: the raw schedule on a
  homogeneous hand-built stage function.
- ``PipelineTrainer``: full integration with conf-built
  MultiLayerNetworks — heterogeneous layer widths (stage-boundary
  activations are flattened and padded to a common hop-buffer width),
  per-layer preprocessors, the configured loss on the last stage,
  microbatch gradient accumulation (GPipe sync semantics: grads sum over
  microbatches before one updater step), and the network's own updaters
  — so a PP-trained net follows the single-device trajectory exactly.
"""

from __future__ import annotations

import math
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.conf.enums import OptimizationAlgorithm
from deeplearning4j_tpu.optimize.telemetry import (
    batch_counts,
    emit_step_span,
    mesh_args,
    window_counts,
)

Array = jax.Array


class _StagePacker:
    """Flatten one pytree per stage into rows of a single [S, K] buffer.

    The buffer is the unit of stage sharding: laid out with
    ``P(pp_axis)`` each device holds exactly its own stage's row
    (1/S of the total, plus padding to the widest stage), and the
    per-stage structure is recovered inside ``lax.switch`` branches
    with static per-stage offsets/treedefs.
    """

    def __init__(self, subtrees):
        self.specs = []
        total = 0
        for tree_ in subtrees:
            leaves, treedef = jax.tree.flatten(tree_)
            shapes = [tuple(l.shape) for l in leaves]
            sizes = [int(math.prod(sh)) for sh in shapes]
            n = int(sum(sizes))
            self.specs.append((treedef, shapes, sizes, n))
            total += n
        self.total = total
        self.width = max([sp[3] for sp in self.specs] + [1])

    def pack(self, subtrees, dtype) -> np.ndarray:
        """Host-side pack: numpy rows (the full buffer never lands on a
        single device — device_put with a P(pp) sharding moves each row
        straight to its stage's devices)."""
        rows = []
        for (treedef, shapes, sizes, n), tree_ in zip(self.specs, subtrees):
            leaves = jax.tree.leaves(tree_)
            row = np.zeros((self.width,), dtype)
            off = 0
            for leaf, sz in zip(leaves, sizes):
                row[off:off + sz] = np.ravel(np.asarray(leaf))
                off += sz
            rows.append(row)
        return np.stack(rows)

    def unpack_row(self, s: int, vec):
        """Rebuild stage ``s``'s pytree from its (traced) row vector."""
        treedef, shapes, sizes, _ = self.specs[s]
        leaves = []
        off = 0
        for sh, sz in zip(shapes, sizes):
            leaves.append(vec[off:off + sz].reshape(sh))
            off += sz
        return jax.tree.unflatten(treedef, leaves)

    def pack_row(self, s: int, tree_, dtype):
        """Traced repack of one stage's pytree into a padded row."""
        _, _, _, n = self.specs[s]
        leaves = jax.tree.leaves(tree_)
        if not leaves:
            return jnp.zeros((self.width,), dtype)
        vec = jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])
        return jnp.pad(vec, (0, self.width - n))

    def unpack_to_host(self, buf) -> list:
        """Gather the [S, K] buffer to host and rebuild every stage's
        pytree (numpy leaves) — the end-of-fit sync back to the net."""
        mat = np.asarray(jax.device_get(buf))
        out = []
        for s, (treedef, shapes, sizes, _) in enumerate(self.specs):
            leaves = []
            off = 0
            for sh, sz in zip(shapes, sizes):
                leaves.append(mat[s, off:off + sz].reshape(sh))
                off += sz
            out.append(jax.tree.unflatten(treedef, leaves))
        return out


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe idle fraction: (S-1)/(M+S-1) — each device computes M of
    the M+S-1 schedule ticks."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def schedule_ticks(n_stages: int, n_microbatches: int) -> int:
    """Total pipeline ticks for M microbatches through S stages."""
    return n_microbatches + n_stages - 1


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: Array,
    n_microbatches: int,
    axis_name: str = "pp",
):
    """Run ``stage_fn`` as a pipeline INSIDE shard_map.

    - ``stage_params``: this device's stage parameters (leading stage axis
      already split by shard_map).
    - ``x``: the full LOCAL batch [B, D]; it is cut into M microbatches.
    - ``stage_fn(params, x_mb) -> y_mb`` with matching in/out widths
      (homogeneous inter-stage interface, as in GPipe).

    Returns [B, D_out] — the last stage's outputs, broadcast to the ring.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = n_microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    mb = b // m
    x_mbs = x.reshape((m, mb) + x.shape[1:])

    y_probe = jax.eval_shape(stage_fn, stage_params, x_mbs[0])
    buf0 = jnp.zeros(y_probe.shape, y_probe.dtype)
    outs0 = jnp.zeros((m,) + y_probe.shape, y_probe.dtype)

    def tick(t, carry):
        buf, outs = carry
        # Stage 0 ingests microbatch t (clamped; masked-out later stages
        # simply compute garbage that is never written).
        feed = x_mbs[jnp.minimum(t, m - 1)]
        x_in = jnp.where(idx == 0, feed, buf)
        y = stage_fn(stage_params, x_in)
        # Last stage: tick t completes microbatch t-(n-1).
        out_t = t - (n - 1)
        write = (idx == n - 1) & (out_t >= 0)
        outs = lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(
                write,
                y,
                lax.dynamic_index_in_dim(outs, jnp.maximum(out_t, 0), 0,
                                         keepdims=False),
            ),
            jnp.maximum(out_t, 0),
            0,
        )
        # Activation hops to the next stage.
        perm = [(i, (i + 1) % n) for i in range(n)]
        buf = lax.ppermute(y, axis_name, perm)
        return buf, outs

    _, outs = lax.fori_loop(0, m + n - 1, tick, (buf0, outs0))
    # Broadcast the last stage's outputs to every device.
    outs = lax.psum(
        jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), axis_name
    )
    return outs.reshape((b,) + outs.shape[2:])


def make_pipelined_mlp(
    mesh: Mesh,
    layers_per_stage_params,
    n_microbatches: int,
    axis_name: str = "pp",
    activation: Callable = jax.nn.relu,
):
    """A pipelined homogeneous MLP: ``layers_per_stage_params`` is a pytree
    whose leaves have a leading stage axis of size mesh.shape[axis_name]
    (e.g. W [S, D, D], b [S, D]). Returns f(params, x) -> y jit-able with
    the stage axis sharded over ``pp``."""

    def stage_fn(params, x_mb):
        w, b = params["W"], params["b"]
        return activation(x_mb @ w + b)

    def f(params, x):
        local = jax.tree.map(lambda p: p[0], params)  # drop stage axis
        return pipeline_apply(
            stage_fn, local, x, n_microbatches, axis_name
        )

    pspec = jax.tree.map(
        lambda _: P(axis_name), layers_per_stage_params,
        is_leaf=lambda v: isinstance(v, (jnp.ndarray, jax.Array)),
    )
    return shard_map(
        f,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )


def partition_stages(net, n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous layer ranges, greedily balanced by parameter count
    (heterogeneous widths welcome). Requires n_layers >= n_stages."""
    n_layers = net.n_layers
    if n_layers < n_stages:
        raise ValueError(
            f"{n_layers} layers cannot fill {n_stages} pipeline stages")
    counts = []
    for i in range(n_layers):
        leaves = jax.tree.leaves(net.params[str(i)])
        counts.append(max(1, sum(int(math.prod(p.shape)) for p in leaves)))
    target = sum(counts) / n_stages
    ranges: List[Tuple[int, int]] = []
    start, acc = 0, 0.0
    for i, c in enumerate(counts):
        acc += c
        layers_left = n_layers - (i + 1)
        stages_left = n_stages - len(ranges) - 1
        if stages_left == 0:
            continue
        if acc >= target or layers_left == stages_left:
            ranges.append((start, i + 1))
            start, acc = i + 1, 0.0
    ranges.append((start, n_layers))
    return ranges


class PipelineTrainer:
    """GPipe-train a conf-built MultiLayerNetwork over the mesh's ``pp``
    axis.

    The network's layers are partitioned into S = mesh.shape[pp] contiguous
    stages (``stage_ranges`` or parameter-count balanced). Each optimizer
    step runs the microbatched pipeline forward, computes the configured
    loss on the last stage, accumulates gradients across all M microbatches
    (summed by AD through the schedule loop — GPipe's synchronous
    semantics), all-reduces the per-stage partial grads over ``pp``, and
    applies the network's own updaters — so the parameter trajectory
    matches single-device ``net.fit`` on the same batches to numerical
    tolerance (asserted in tests/test_pipeline_expert.py).

    Stage-boundary activations are flattened and right-padded to the
    widest boundary so the ``lax.ppermute`` hop buffer is homogeneous;
    each stage unpads/reshapes on ingest.

    **Stage-sharded state (memory 1/S per device).** Parameters and
    updater state live packed as ``[S, K]`` buffers laid out with
    ``P(pp)`` — each device stores ONLY its own stage's row (1/S of the
    model + padding to the widest stage), the defining property of
    pipeline parallelism. Gradients are taken INSIDE the shard_map
    w.r.t. the local row (the transpose of the activation ``ppermute``
    carries cross-stage sensitivities), and the per-stage slice of the
    network's updaters runs on-device via ``lax.switch`` — no full
    gradient, parameter, or updater buffer ever materializes on any
    device. ``per_device_state_bytes()`` exposes the accounting.

    **dp x pp composition.** If the mesh also carries a data axis
    (``dp_axis``, autodetected as "dp"), the batch is sharded over it
    and per-stage gradients are ``lax.pmean``-ed across replicas before
    the update — data parallelism composed with pipeline stages on ONE
    mesh, matching the single-device trajectory on the concatenated
    batch.

    Aux-emitting layers (MoeDense) are supported: per-stage weighted aux
    losses are accumulated over the valid microbatch window and psum-ed
    into the training loss (the aux statistic is computed per microbatch,
    so MoE trajectories match single-device in expectation rather than
    bit-for-bit).

    Running-state layers (BatchNormalization) train under GHOST-BATCH-
    NORM semantics: normalization uses each microbatch's own statistics
    and the running averages update once per microbatch (M updates per
    step where single-device fit makes one; under dp the replicas'
    statistics are pmean-averaged). State rows are stage-sharded like
    params.

    Masked time-series batches are supported: each microbatch's
    feature mask feeds its recurrent layers and its label mask the
    output loss; per-microbatch masked means are re-weighted by their
    unmasked counts so the step loss equals the GLOBAL masked mean —
    exact single-device parity even when masks spread unevenly across
    microbatches.

    **tBPTT** (round-4): TRUNCATED_BPTT configs train through the same
    schedule, one window at a time — each time window runs the full
    microbatched pipeline + one optimizer step, and per-(stage,
    replica, microbatch) RNN carries cross windows stage-sharded under
    stop-gradient (reference doTruncatedBPTT :1262 cadence; parity in
    tests/test_pp_tbptt.py). Attention layers carry nothing across
    windows (matching single-device training semantics).

    **Full-batch solvers** (round-4): CONJUGATE_GRADIENT / LBFGS /
    LINE_GRADIENT_DESCENT / HESSIAN_FREE configs run the reference's
    BaseOptimizer loop against a stage-sharded ``PipelinedProblem``
    (see that class) — the solver's flat vector is the [S, K] P(pp)
    theta buffer itself, so solver memory keeps the 1/S property.

    Limitations (documented, enforced): tBPTT trains via fit() (not
    fit_scan) and composes with SGD only (solvers are full-batch,
    matching reference Solver semantics).

    **Why pp composes with dp but not tp/fsdp.** The 1/S memory
    property comes from packing each stage's pytree into one row of a
    [S, K] buffer laid out P(pp) — a single flattened vector per
    device, unpacked with static offsets inside ``lax.switch``. Tensor
    or fsdp sharding needs per-TENSOR layouts, which a flattened padded
    row cannot express; sharding the row itself would force an
    all-gather before every unpack (fsdp-esque memory, none of tp's
    compute split). Models needing tp x pp should use the GSPMD
    ParallelTrainer axes (tp/fsdp compose there, including head-sharded
    attention) — pp's niche is the 1/S-memory schedule for deep stacks.
    """

    def __init__(
        self,
        net,
        mesh: Mesh,
        pp_axis: str = "pp",
        n_microbatches: int = 4,
        stage_ranges: Optional[Sequence[Tuple[int, int]]] = None,
        dp_axis: Optional[str] = None,
        tracer=None,
    ):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        # Optional span sink (ISSUE 8): every pipelined step emits a
        # ``train.parallel_step`` span with the mesh config in its args.
        self.tracer = tracer

        net.init()
        # Aux-only state (MoeDense load-balance loss) is step-local and
        # threaded into the pipeline loss; RUNNING state (BatchNorm
        # mean/var) is stage-sharded like params and updated once per
        # VALID microbatch tick — ghost-batch-norm semantics: each
        # microbatch contributes its own statistics, so running averages
        # see M updates per step where single-device fit sees one
        # (documented deviation; normalization itself uses the current
        # microbatch's batch stats either way).
        self._stateful = sorted(
            si for si, st in (net.state or {}).items()
            if not (isinstance(st, dict) and set(st) <= {"aux_loss"}))
        # tBPTT (round-4 review item 9): windows of the time axis run
        # the full microbatched schedule each, with per-(stage,
        # microbatch) RNN carries held stage-sharded between windows —
        # deep LSTM stacks get the 1/S stage memory (reference
        # doTruncatedBPTT MultiLayerNetwork.java:1262 semantics: one
        # optimizer step per window, stop-gradient carries).
        self.tbptt = (net.conf.backprop_type
                      == BackpropType.TRUNCATED_BPTT)
        # Full-batch solvers (CG/LBFGS/LineGD/HF) ride the same GPipe
        # schedule: fit() hands a stage-sharded PipelinedProblem to the
        # BaseOptimizer loop instead of stepping updaters — the [S, K]
        # P(pp) rows serve as the solver's flat vector, so directions,
        # line-search probes, and L-BFGS history all stay 1/S-sharded
        # (reference Solver.java:42 dispatch; its solvers are full-batch
        # there too, ConjugateGradient.java / LBFGS.java).
        self.algo = net.conf.confs[0].optimization_algo
        if (self.algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
                and self.tbptt):
            raise ValueError(
                "pipelined solvers are full-batch (reference Solver "
                "semantics); truncated-BPTT composes with SGD only "
                f"(got {self.algo})")
        self.net = net
        self.mesh = mesh
        self.pp_axis = pp_axis
        self.n_stages = int(mesh.shape[pp_axis])
        self.n_microbatches = int(n_microbatches)
        self.stage_ranges = list(
            stage_ranges if stage_ranges is not None
            else partition_stages(net, self.n_stages))
        if len(self.stage_ranges) != self.n_stages:
            raise ValueError(
                f"{len(self.stage_ranges)} stage ranges for "
                f"{self.n_stages} pipeline devices")
        flat = [i for s, e in self.stage_ranges for i in range(s, e)]
        if flat != list(range(net.n_layers)):
            raise ValueError(
                f"stage ranges {self.stage_ranges} must cover layers "
                f"0..{net.n_layers - 1} contiguously")
        if dp_axis is None and "dp" in mesh.axis_names:
            dp_axis = "dp"
        if dp_axis is not None and dp_axis not in mesh.axis_names:
            raise ValueError(f"dp axis {dp_axis!r} not in mesh "
                             f"{mesh.axis_names}")
        self.dp_axis = dp_axis
        self.n_replicas = int(mesh.shape[dp_axis]) if dp_axis else 1
        self._step_cache = {}
        self._rnn_dummy = None  # non-tBPTT steps carry a [.,.,1,1] stub
        # Stage-sharded packed training state ([S, K] P(pp) buffers).
        self._theta = None
        self._ustate = None
        self._sstate = None
        self._synced_params = None
        self._gather_cache = {}
        self._p_pack = _StagePacker(
            [self._stage_subtree(net.params, s)
             for s in range(self.n_stages)])
        self._u_pack = _StagePacker(
            [self._stage_subtree(net.updater_state, s)
             for s in range(self.n_stages)])
        self._s_pack = _StagePacker(
            [self._stage_state_subtree(s) for s in range(self.n_stages)])

    def _stage_subtree(self, tree_, s: int):
        start, end = self.stage_ranges[s]
        return {str(i): tree_[str(i)] for i in range(start, end)}

    def _stage_state_subtree(self, s: int):
        """Running-state (non-aux) subtree of stage s, from net.state."""
        start, end = self.stage_ranges[s]
        return {si: self.net.state[si]
                for si in (str(i) for i in range(start, end))
                if si in self._stateful}

    # -- packed-state lifecycle ---------------------------------------
    def _ensure_packed(self):
        """Pack net.params/updater_state into the stage-sharded buffers
        (host rows -> device_put lands each row only on its stage's
        devices). Re-packs if the net's param dict was swapped out."""
        net = self.net
        token = (id(net.params), getattr(net, "params_version", 0))
        if self._theta is not None and self._synced_params == token:
            return
        sh = NamedSharding(self.mesh, P(self.pp_axis))
        theta_host = self._p_pack.pack(
            [self._stage_subtree(net.params, s)
             for s in range(self.n_stages)], np.dtype(net._dtype))
        u_host = self._u_pack.pack(
            [self._stage_subtree(net.updater_state, s)
             for s in range(self.n_stages)], np.dtype(net._dtype))
        s_host = self._s_pack.pack(
            [self._stage_state_subtree(s) for s in range(self.n_stages)],
            np.dtype(net._dtype))
        self._theta = jax.device_put(theta_host, sh)
        self._ustate = jax.device_put(u_host, sh)
        self._sstate = jax.device_put(s_host, sh)
        self._synced_params = token

    def _gatherable(self, buf):
        """Multi-host: a [S, K] P(pp) buffer has non-addressable shards
        when the pp axis spans processes; the shared helper reshards to
        replicated first (one cross-host all-gather) so device_get
        works everywhere — and passes through with NO collective when
        pp stays within this host.

        NOTE: the gather transiently materializes that one buffer
        replicated on-device before the host copy — an explicit
        full-model materialization is what a sync IS; buffers are
        gathered one at a time, so the transient peak is one buffer,
        not all three."""
        from deeplearning4j_tpu.parallel.mesh import gather_for_host

        return gather_for_host(self.mesh, buf, self._gather_cache)

    def _sync_to_net(self):
        """Gather packed state back into net.params / net.updater_state
        as HOST numpy leaves (a device re-upload here would materialize
        the full model on the default device and defeat the 1/S memory
        property; jit transfers leaves on their next use)."""
        net = self.net
        for sub in self._p_pack.unpack_to_host(self._gatherable(self._theta)):
            net.params.update(sub)
        for sub in self._u_pack.unpack_to_host(
                self._gatherable(self._ustate)):
            net.updater_state.update(sub)
        for sub in self._s_pack.unpack_to_host(
                self._gatherable(self._sstate)):
            net.state.update(sub)
        self._synced_params = (
            id(net.params), getattr(net, "params_version", 0))

    def per_device_state_bytes(self) -> dict:
        """{device: bytes of params+updater state resident} — the 1/S
        memory accounting (each device holds only its stage's row)."""
        self._ensure_packed()
        acc: dict = {}
        for buf in (self._theta, self._ustate, self._sstate):
            for shard in buf.addressable_shards:
                d = shard.device
                acc[d] = acc.get(d, 0) + shard.data.nbytes
        return acc

    def total_state_bytes(self) -> int:
        """Unpadded params+updater-state bytes of the whole model."""
        item = np.dtype(self.net._dtype).itemsize
        return (self._p_pack.total + self._u_pack.total) * item

    # -- stage math ----------------------------------------------------
    def _apply_stage(self, s: int, params, x, rngs, train=True,
                     master_from=None, state=None, feature_mask=None,
                     rnn_state=None):
        """Apply layers [start, end) of stage s (with preprocessors).
        Returns (activations, weighted aux-loss sum of the stage, new
        running state of the stage's stateful layers, new RNN carries
        of the stage's recurrent layers).
        ``master_from``: layer index from which activations are cast
        back to the master dtype (the f32 output-layer rule of
        MultiLayerNetwork._forward_fn under mixed precision).
        ``state``: {si: running-state} for this stage's stateful layers
        (BatchNorm mean/var).
        ``feature_mask``: this microbatch's [mb, T] time mask — handed
        to recurrent layers only (the _forward_fn rule).
        ``rnn_state``: {si: carry} for recurrent layers (tBPTT window
        continuation; None carries = zero initial state)."""
        from deeplearning4j_tpu.nn.conf import layers as _L
        from deeplearning4j_tpu.nn.multilayer import _cast_floating

        net = self.net
        start, end = self.stage_ranges[s]
        aux = jnp.zeros((), net._dtype)
        new_state = {}
        new_rnn = {}
        for i in range(start, end):
            si = str(i)
            c = net.conf.confs[i]
            pp = net.conf.preprocessor_for(i)
            if pp is not None:
                x = pp.pre_process(x, rngs[i] if train else None)
            if master_from is not None and i == master_from:
                # AFTER the preprocessor — matching the cast point in
                # MultiLayerNetwork._forward_fn so mixed-precision
                # trajectories agree with single-device fit.
                x = _cast_floating(x, net._dtype)
            is_rec = isinstance(c.layer, _L.RECURRENT_LAYER_TYPES)
            layer_state = (state or {}).get(si)
            if layer_state is None and rnn_state is not None:
                layer_state = rnn_state.get(si)
            x, st = net._impls[i].apply(
                c, params[si], x,
                state=layer_state, train=train, rng=rngs[i],
                mask=feature_mask if is_rec else None,
            )
            w = getattr(c.layer, "aux_weight", None)
            if w and isinstance(st, dict) and "aux_loss" in st:
                aux = aux + w * st["aux_loss"].astype(net._dtype)
            elif st is not None and si in self._stateful:
                # running statistics stay at the master dtype (same rule
                # as _forward_fn's carried-state cast)
                new_state[si] = jax.tree.map(
                    lambda a: _cast_floating(a, net._dtype), st)
            elif st is not None and rnn_state is not None and is_rec:
                new_rnn[si] = jax.tree.map(
                    lambda a: _cast_floating(a, net._dtype), st)
        return x, aux, new_state, new_rnn

    def _boundary_shapes(self, feats_mb_shape):
        """Activation shape entering each stage (index 0 = input)."""
        net = self.net
        shapes = [feats_mb_shape]
        x = jax.ShapeDtypeStruct(feats_mb_shape, net._dtype)
        rngs = [None] * net.n_layers
        for s in range(self.n_stages):
            x = jax.eval_shape(
                lambda xx, _s=s: self._apply_stage(
                    _s, net.params, xx, rngs, train=False,
                    state=self._stage_state_subtree(_s))[0], x)
            shapes.append(x.shape)
        return shapes

    def _rnn_zero_trees(self, feats_mb_shape):
        """Per-stage ZERO RNN-carry pytrees for one microbatch (probed
        via eval_shape; recurrent impls treat a zero carry exactly as
        the lazily-created initial carry).

        Probed with ``train=True`` — the mode the schedule runs in.
        This matters for attention layers (BaseRecurrentLayer
        subclasses): their TRAINING apply carries no state (tBPTT
        windows attend independently, same as single-device fit), while
        inference builds a serving KV cache; a train=False probe would
        collect that cache as a bogus window carry."""
        net = self.net
        rngs = [None] * net.n_layers
        trees = []
        x = jax.ShapeDtypeStruct(feats_mb_shape, net._dtype)
        for s in range(self.n_stages):
            out = jax.eval_shape(
                lambda xx, _s=s: self._apply_stage(
                    _s, net.params, xx, rngs, train=True,
                    state=self._stage_state_subtree(_s),
                    rnn_state={}), x)
            x_struct, _, _, rnn_struct = out
            trees.append(jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), rnn_struct))
            x = x_struct
        return trees

    # -- the jitted step ----------------------------------------------
    def _build_step(self, feats_shape, labels_shape, scan=False,
                    tbptt=False, solver=False):
        from deeplearning4j_tpu.nn.multilayer import (
            layer_reg_score,
            layer_update,
        )

        net = self.net
        S, M = self.n_stages, self.n_microbatches
        axis = self.pp_axis
        dp = self.dp_axis
        R = self.n_replicas
        p_pack, u_pack = self._p_pack, self._u_pack
        B = feats_shape[0]
        if B % (R * M):
            raise ValueError(
                f"batch {B} not divisible by {R} replicas x {M} "
                f"microbatches")
        mb = B // (R * M)  # per-replica microbatch
        feats_mb_shape = (mb,) + tuple(feats_shape[1:])
        shapes = self._boundary_shapes(feats_mb_shape)
        widths = [int(math.prod(sh[1:])) for sh in shapes]
        K = max(widths[1:])  # hop-buffer width (boundaries + final out)
        out_conf = net.conf.confs[-1]
        out_impl = net._impls[-1]
        cd = net._compute_dtype

        from deeplearning4j_tpu.nn.conf import layers as _L

        # Mixed precision: the output layer runs at the master dtype
        # (see MultiLayerNetwork._forward_fn — a bf16 softmax stalls
        # training at a calibration plateau).
        out_f32 = (cd is not None
                   and isinstance(net.conf.confs[-1].layer,
                                  _L.BaseOutputLayer))
        last_layer = net.n_layers - 1
        last_si = str(last_layer)

        s_pack = self._s_pack
        # tBPTT: per-(stage, microbatch) RNN carries, packed like the
        # other stage-sharded buffers (window continuation rows).
        rnn_pack = (_StagePacker(self._rnn_zero_trees(feats_mb_shape))
                    if tbptt else None)

        def branch(s):
            in_shape = shapes[s]

            def run(theta_cd, theta_master, state_vec, rnn_vec, x_feed,
                    fm_mb, buf, y_mb, lm_mb, rngs):
                params = p_pack.unpack_row(s, theta_cd)
                if out_f32 and s == S - 1:
                    # The output layer's params come from the f32 row
                    # (the casted copy of that slice is dead code XLA
                    # drops).
                    params[last_si] = p_pack.unpack_row(
                        s, theta_master)[last_si]
                if s == 0:
                    xin = x_feed
                else:
                    w = widths[s]
                    xin = buf[:, :w].reshape(in_shape)
                y, aux, new_st, new_rnn = self._apply_stage(
                    s, params, xin, rngs,
                    master_from=(last_layer
                                 if out_f32 and s == S - 1 else None),
                    state=s_pack.unpack_row(s, state_vec),
                    feature_mask=fm_mb,
                    rnn_state=(rnn_pack.unpack_row(s, rnn_vec)
                               if rnn_pack else None))
                if s == S - 1:
                    yl = y
                    if cd is not None:
                        yl = yl.astype(net._dtype)
                    loss = out_impl.loss(out_conf, yl, y_mb, lm_mb)
                else:
                    loss = jnp.zeros((), net._dtype)
                yf = y.reshape(mb, -1)
                if cd is not None:
                    yf = yf.astype(cd)  # homogeneous hop-buffer dtype
                yf = jnp.pad(yf, ((0, 0), (0, K - yf.shape[1])))
                # Running statistics carry no gradient (has_aux
                # semantics of the single-device step); keep the stage's
                # old row where it has no stateful layers.
                st_row = (lax.stop_gradient(
                    s_pack.pack_row(s, new_st, net._dtype))
                    if new_st else state_vec)
                # The RNN carry crossing windows is a stop-gradient
                # boundary (reference doTruncatedBPTT semantics; same
                # as MultiLayerNetwork._tbptt_step's stop_gradient).
                rnn_row = (lax.stop_gradient(
                    rnn_pack.pack_row(s, new_rnn, net._dtype))
                    if rnn_pack else rnn_vec)
                return yf, loss, aux, st_row, rnn_row

            return run

        branches = [branch(s) for s in range(S)]

        def reg_branch(s):
            start, end = self.stage_ranges[s]

            def run(theta_vec):
                params = p_pack.unpack_row(s, theta_vec)
                reg = jnp.zeros((), net._dtype)
                for i in range(start, end):
                    reg = reg + layer_reg_score(
                        net.conf.confs[i], params[str(i)])
                return reg

            return run

        reg_branches = [reg_branch(s) for s in range(S)]

        def upd_branch(s):
            start, end = self.stage_ranges[s]

            def run(theta_vec, grad_vec, u_vec, iteration):
                params = p_pack.unpack_row(s, theta_vec)
                grads = p_pack.unpack_row(s, grad_vec)
                upd = u_pack.unpack_row(s, u_vec)
                new_p, new_u = {}, {}
                for i in range(start, end):
                    si = str(i)
                    updates, new_u[si] = layer_update(
                        net.conf.confs[i], net._updaters[i],
                        grads[si], upd[si], iteration)
                    new_p[si] = jax.tree.map(
                        lambda p, u: p - u, params[si], updates)
                return (p_pack.pack_row(s, new_p, net._dtype),
                        u_pack.pack_row(s, new_u, net._dtype))

            return run

        upd_branches = [upd_branch(s) for s in range(S)]

        def make_loss_fn(feats, labels, fm, lm, rng, rnn_in, sstate_row,
                         use_rng=True):
            """The pipelined loss as f(theta_row) — one closure serves
            both the SGD step (value_and_grad -> updaters) and the
            solver functions (value_and_grad / value-only probes), so
            the schedule/masked-mean/aux semantics cannot drift between
            the two paths. ``use_rng=False`` is the solver mode: layer
            rngs are None (no dropout), matching the single-device
            FlatProblem's ``_loss_fn(params, state, None, ...)``."""
            idx = lax.axis_index(axis)

            def loss_fn(theta_row):
                tv = theta_row.astype(cd) if cd is not None else theta_row
                f = feats.astype(cd) if cd is not None else feats
                x_mbs = f.reshape((M, mb) + f.shape[1:])
                y_mbs = labels.reshape((M, mb) + labels.shape[1:])
                fm_mbs = (None if fm is None
                          else fm.reshape((M, mb) + fm.shape[1:]))
                lm_mbs = (None if lm is None
                          else lm.reshape((M, mb) + lm.shape[1:]))
                hop_dtype = cd if cd is not None else net._dtype
                buf0 = jnp.zeros((mb, K), hop_dtype)
                loss0 = jnp.zeros((), net._dtype)
                rnn0 = (rnn_in[0, 0] if tbptt
                        else jnp.zeros((M, 1), net._dtype))

                def tick(t, carry):
                    buf, loss_acc, w_acc, aux_acc, st_vec, rnn_mat = \
                        carry
                    # Stage idx processes microbatch t - idx at tick t;
                    # fold the microbatch index into the rng so each
                    # microbatch draws distinct dropout masks.
                    mb_idx = jnp.clip(t - idx, 0, M - 1)
                    rngs = (list(jax.random.split(
                        jax.random.fold_in(rng, mb_idx), net.n_layers))
                        if use_rng else [None] * net.n_layers)
                    feed_t = jnp.minimum(t, M - 1)
                    feed = x_mbs[feed_t]
                    fm_mb = None if fm_mbs is None else fm_mbs[mb_idx]
                    out_t = jnp.maximum(t - (S - 1), 0)
                    y_mb = y_mbs[out_t]
                    lm_mb = None if lm_mbs is None else lm_mbs[out_t]
                    rnn_vec = rnn_mat[mb_idx]
                    yf, loss, aux, st_new, rnn_new = lax.switch(
                        idx, branches, tv, theta_row, st_vec, rnn_vec,
                        feed, fm_mb, buf, y_mb, lm_mb, rngs)
                    write = (idx == S - 1) & (t - (S - 1) >= 0)
                    # Masked losses are per-microbatch masked MEANS
                    # (ops/losses._reduce: sum(l*m)/max(sum(m),1));
                    # multiplying by max(w,1) inverts that clamped
                    # denominator EXACTLY (incl. fractional masks with
                    # w<1), so loss_acc accumulates raw masked SUMS and
                    # the final quotient by the raw weight total is the
                    # global masked mean (unmasked: weight 1 -> /M).
                    w_mb = (jnp.asarray(1.0, net._dtype) if lm_mbs is None
                            else jnp.sum(lm_mb).astype(net._dtype))
                    loss_acc = loss_acc + jnp.where(
                        write, loss * jnp.maximum(w_mb, 1.0), 0.0)
                    w_acc = w_acc + jnp.where(write, w_mb, 0.0)
                    # Stage idx holds a REAL microbatch only for ticks
                    # in [idx, idx + M); warmup/drain garbage must not
                    # leak into the aux loss, the running statistics
                    # (ghost-BN: one state update per VALID microbatch)
                    # or the tBPTT window carries.
                    valid = (t >= idx) & (t < idx + M)
                    aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
                    st_vec = jnp.where(valid, st_new, st_vec)
                    rnn_mat = lax.dynamic_update_index_in_dim(
                        rnn_mat,
                        jnp.where(valid, rnn_new, rnn_vec), mb_idx, 0)
                    perm = [(i, (i + 1) % S) for i in range(S)]
                    buf = lax.ppermute(yf, axis, perm)
                    return (buf, loss_acc, w_acc, aux_acc, st_vec,
                            rnn_mat)

                (_, loss_sum, w_sum, aux_sum, st_final,
                 rnn_final) = lax.fori_loop(
                    0, M + S - 1, tick,
                    (buf0, loss0, loss0, loss0, sstate_row, rnn0))
                # LOCAL (unreduced) stage contribution: data loss lives
                # on the last stage, aux/reg on each stage. The global
                # score = psum of these, but the psum must happen OUTSIDE
                # the differentiated function: under shard_map the
                # transpose of psum is psum, so differentiating a
                # reduced scalar (whose cotangent is 1 on EVERY device)
                # would scale all gradients by S. Differentiating the
                # local sum is exact — cross-stage sensitivities ride the
                # ppermute transpose. Microbatch losses are per-mb means
                # -> batch mean = mean of the M microbatch means (equal
                # sizes). NB the MoE aux loss is computed per microbatch
                # here vs per batch single-device: a nonlinear
                # statistic, so trajectories with MoE layers match in
                # expectation, not bit-for-bit.
                reg = lax.switch(idx, reg_branches, theta_row)
                # GLOBAL weight total across data replicas: without it,
                # dp x pp would average per-replica masked MEANS, which
                # differs from the global masked mean when masks spread
                # unevenly across shards. w is theta-independent (mask
                # sums only), so this psum has no gradient path and the
                # psum-transpose subtlety above does not apply; each
                # replica's term then composes by SUM over dp (psum'd
                # outside), with aux/reg divided by R to keep their
                # replica-mean/once-only semantics.
                w_g = lax.psum(w_sum, dp) if dp is not None else w_sum
                data = loss_sum / jnp.maximum(w_g, 1.0)
                return (data + aux_sum / (M * R) + reg / R,
                        (st_final, rnn_final))

            return loss_fn

        def local_step(theta, ustate, sstate, rnn_in, iteration, rng,
                       feats, labels, fm, lm):
            # theta [1, Kp]: this device's stage row. feats/labels: this
            # replica's batch shard (full batch when no dp axis).
            # rnn_in [1, 1, M, Kr]: this (stage, replica)'s per-
            # microbatch RNN carries (tBPTT only; [1] dummy otherwise).
            idx = lax.axis_index(axis)
            if dp is not None:
                # Decorrelate dropout across replicas.
                rng = jax.random.fold_in(rng, lax.axis_index(dp))
            loss_fn = make_loss_fn(feats, labels, fm, lm, rng, rnn_in,
                                   sstate[0])

            (score_local, (st_final, rnn_final)), grad = \
                jax.value_and_grad(loss_fn, has_aux=True)(theta[0])
            # Reported score: sum of stage contributions over the ring.
            score = lax.psum(score_local, axis)
            if dp is not None:
                # SUM the per-replica terms (the global quotient already
                # carries the cross-replica weight total); ghost-BN
                # running statistics average across replicas (the
                # per-replica microbatch stats are equal-sized samples).
                # RNN window carries stay per-replica (each replica's
                # batch shard continues its own sequences).
                grad = lax.psum(grad, dp)
                score = lax.psum(score, dp)
                st_final = lax.pmean(st_final, dp)
            new_t, new_u = lax.switch(
                idx, upd_branches, theta[0], grad, ustate[0], iteration)
            rnn_out = rnn_final[None, None] if tbptt else rnn_in
            return (new_t[None], new_u[None], st_final[None], rnn_out,
                    score)

        if solver:
            # Solver mode: expose the pipelined loss as value_and_grad /
            # value-only functions over the [S, Kp] theta buffer — no
            # updater application, no state mutation (single-device
            # FlatProblem parity: loss_flat discards new_state). The
            # grad buffer comes back P(pp)-sharded like theta, so the
            # BaseOptimizer's vector math runs 1/S-sharded under GSPMD.
            def local_vag(theta, sstate, feats, labels, fm, lm):
                loss_fn = make_loss_fn(feats, labels, fm, lm, None,
                                       None, sstate[0], use_rng=False)
                (score_local, _), grad = jax.value_and_grad(
                    loss_fn, has_aux=True)(theta[0])
                score = lax.psum(score_local, axis)
                if dp is not None:
                    grad = lax.psum(grad, dp)
                    score = lax.psum(score, dp)
                return grad[None], score

            def local_val(theta, sstate, feats, labels, fm, lm):
                loss_fn = make_loss_fn(feats, labels, fm, lm, None,
                                       None, sstate[0], use_rng=False)
                score_local, _ = loss_fn(theta[0])
                score = lax.psum(score_local, axis)
                if dp is not None:
                    score = lax.psum(score, dp)
                return score

            bspec = P(dp) if dp is not None else P()
            pp = P(self.pp_axis)
            vag = shard_map(
                local_vag, mesh=self.mesh,
                in_specs=(pp, pp, bspec, bspec, bspec, bspec),
                out_specs=(pp, P()), check_vma=False)
            val = shard_map(
                local_val, mesh=self.mesh,
                in_specs=(pp, pp, bspec, bspec, bspec, bspec),
                out_specs=P(), check_vma=False)
            return jax.jit(vag), jax.jit(val)

        if not scan:
            fn = local_step
            bspec = P(dp) if dp is not None else P()
        else:
            # K fused steps: lax.scan over [K, ...] stacked batches
            # INSIDE the shard_map, so the whole K-step pipelined
            # optimizer run is ONE dispatch (the fit_scan fusion the
            # other trainers have — per-batch dispatch latency
            # otherwise dominates small models).
            def local_steps(theta, ustate, sstate, rnn, iteration, rng,
                            fs, ys, fms, lms):
                def body(carry, inp):
                    th, us, ss, rn, it = carry
                    th, us, ss, rn, score = local_step(
                        th, us, ss, rn, it,
                        jax.random.fold_in(rng, inp["k"]),
                        inp["f"], inp["y"], inp.get("fm"),
                        inp.get("lm"))
                    return (th, us, ss, rn, it + 1), score

                xs = {"f": fs, "y": ys, "k": jnp.arange(fs.shape[0])}
                if fms is not None:
                    xs["fm"] = fms
                if lms is not None:
                    xs["lm"] = lms
                (theta, ustate, sstate, rnn, _), scores = jax.lax.scan(
                    body, (theta, ustate, sstate, rnn, iteration), xs)
                return theta, ustate, sstate, rnn, scores

            fn = local_steps
            bspec = P(None, dp) if dp is not None else P()

        pp = P(self.pp_axis)
        rnnspec = P(self.pp_axis, dp) if dp is not None else P(
            self.pp_axis)
        step = shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(pp, pp, pp, rnnspec, P(), P(), bspec, bspec,
                      bspec, bspec),
            out_specs=(pp, pp, pp, rnnspec, P()),
            check_vma=False,
        )
        jitted = jax.jit(step, donate_argnums=(0, 1, 2, 3))
        # fit() needs the buffer's global shape to (zero-)init the
        # window carries per batch ([1] dummy axes when not tBPTT).
        rnn_shape = (S, R, M, rnn_pack.width) if tbptt else (S, R, 1, 1)
        return jitted, rnn_shape

    # -- public API ----------------------------------------------------
    def _rnn_sharding(self):
        spec = (P(self.pp_axis, self.dp_axis)
                if self.dp_axis is not None else P(self.pp_axis))
        return NamedSharding(self.mesh, spec)

    def _zero_rnn(self, rnn_shape):
        return jax.device_put(
            jnp.zeros(rnn_shape, self.net._dtype), self._rnn_sharding())

    def _trace_args(self, **extra):
        axes = {"pp": self.pp_axis}
        if self.dp_axis:
            axes["dp"] = self.dp_axis
        return mesh_args(self.mesh, "pipeline",
                         n_microbatches=self.n_microbatches,
                         n_stages=self.n_stages, **axes, **extra)

    def _emit_step_span(self, dispatch_s: float, **extra) -> None:
        if self.tracer is not None:
            emit_step_span(self.tracer, dispatch_s,
                           self._trace_args(**extra))

    def _run_step(self, key, build_args, step_args, rnn):
        """Build-or-fetch the step for ``key``, zero-init the RNN
        buffer when absent, run one step. Returns (rnn', score)."""
        net = self.net
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(*build_args)
        step, rnn_shape = self._step_cache[key]
        if rnn is None:
            rnn = self._zero_rnn(rnn_shape)
        net._key, sub = jax.random.split(net._key)
        t0 = time.perf_counter()
        self._theta, self._ustate, self._sstate, rnn, s = step(
            self._theta, self._ustate, self._sstate, rnn,
            net.iteration, sub, *step_args)
        dispatch_s = time.perf_counter() - t0
        examples, tokens = batch_counts(step_args[0])
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, examples=examples, tokens=tokens)
        self._emit_step_span(dispatch_s, iteration=net.iteration + 1)
        net.score_value = s
        net.iteration += 1
        return rnn, s

    def _fit_solver_batch(self, ds) -> float:
        """Run the conf's full-batch solver (CG/LBFGS/LineGD/HF) on one
        batch with the pipelined loss: the BaseOptimizer loop drives a
        ``PipelinedProblem`` whose x IS the stage-sharded theta buffer
        (reference Solver.java:42 dispatch; BaseOptimizer.optimize
        :163-226 loop semantics preserved — same iterations, listeners,
        terminations as the single-device path)."""
        from deeplearning4j_tpu.optimize.solver import _OPTIMIZERS

        try:
            cls = _OPTIMIZERS[self.algo]
        except KeyError:
            raise ValueError(
                f"Unsupported optimization algorithm {self.algo}")
        opt = cls(self.net,
                  problem_factory=lambda net, d: PipelinedProblem(self, d))
        return float(opt.optimize(ds))

    def _fit_tbptt_batch(self, ds, bspec) -> float:
        """Windowed tBPTT through the pipeline (reference
        doTruncatedBPTT :1262-1320): each time window runs the FULL
        microbatched GPipe schedule + one optimizer step; RNN carries
        live stage-sharded per (stage, replica, microbatch) and cross
        windows under stop-gradient."""
        net = self.net
        length = net.conf.tbptt_fwd_length
        feats = jnp.asarray(ds.features, net._dtype)
        labels = jnp.asarray(ds.labels, net._dtype)
        fmask = (None if ds.features_mask is None
                 else jnp.asarray(ds.features_mask, net._dtype))
        lmask = (None if ds.labels_mask is None
                 else jnp.asarray(ds.labels_mask, net._dtype))
        t_total = feats.shape[2]
        rnn = None  # fresh zero carries per batch (reference parity)
        s = float("nan")
        for start in range(0, t_total, length):
            end = min(start + length, t_total)
            fw = jax.device_put(feats[:, :, start:end], bspec)
            lw = jax.device_put(labels[:, :, start:end], bspec)
            fmw = (None if fmask is None else jax.device_put(
                fmask[:, start:end], bspec))
            lmw = (None if lmask is None else jax.device_put(
                lmask[:, start:end], bspec))
            key = ("tbptt", fw.shape, lw.shape,
                   None if fmw is None else fmw.shape,
                   None if lmw is None else lmw.shape)
            rnn, s = self._run_step(
                key, (fw.shape, lw.shape, False, True),
                (fw, lw, fmw, lmw), rnn)
            # Per-WINDOW listener cadence (single-device _fit_tbptt
            # parity: iteration_done after every window).
            if net.listeners and jax.process_count() == 1:
                self._sync_to_net()
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration)
        return float(s)

    def fit(self, data, labels=None) -> float:
        from deeplearning4j_tpu.datasets.dataset import DataSet

        net = self.net
        if labels is not None:
            data = DataSet(data, labels)
        batches = [data] if isinstance(data, DataSet) else data
        score = float("nan")
        self._ensure_packed()
        bspec = (NamedSharding(self.mesh, P(self.dp_axis))
                 if self.dp_axis is not None
                 else NamedSharding(self.mesh, P()))
        for ds in batches:
            if (self.algo
                    != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
                score = self._fit_solver_batch(ds)
                continue
            if self.tbptt:
                score = self._fit_tbptt_batch(ds, bspec)
                continue
            feats = jax.device_put(
                jnp.asarray(ds.features, net._dtype), bspec)
            labs = jax.device_put(
                jnp.asarray(ds.labels, net._dtype), bspec)
            fm = (None if ds.features_mask is None else jax.device_put(
                jnp.asarray(ds.features_mask, net._dtype), bspec))
            lm = (None if ds.labels_mask is None else jax.device_put(
                jnp.asarray(ds.labels_mask, net._dtype), bspec))
            key = (feats.shape, labs.shape,
                   None if fm is None else fm.shape,
                   None if lm is None else lm.shape)
            self._rnn_dummy, s = self._run_step(
                key, (feats.shape, labs.shape),
                (feats, labs, fm, lm), self._rnn_dummy)
            score = float(s)
            if net.listeners and jax.process_count() == 1:
                # Listeners may inspect/checkpoint net.params: sync the
                # packed state back before each callback (listener-free
                # training pays one gather per fit() call instead).
                # Multi-process runs sync once at end-of-fit only: the
                # sync is a cross-host collective, and a host-local
                # `net.listeners` condition would deadlock the gang
                # whenever listeners are attached asymmetrically (e.g.
                # a chief-only checkpoint listener).
                self._sync_to_net()
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration)
        # One host gather per fit() CALL (not per step): keep
        # net.params/updater_state the canonical user-visible copy.
        self._sync_to_net()
        return score

    def fit_scan(self, features_stacked, labels_stacked,
                 features_mask_stacked=None, labels_mask_stacked=None):
        """K fused pipelined steps: one dispatch runs ``lax.scan`` over
        [K, B, ...] pre-stacked batches, each scan iteration the full
        microbatched GPipe schedule + updater — the fit_scan fusion the
        other trainers have, on the stage-sharded pp (x dp) mesh.
        Returns the K per-step scores."""
        net = self.net
        if self.tbptt:
            raise ValueError(
                "fit_scan is the full-BPTT fast path; truncated-BPTT "
                "configs train via fit() (windowed schedule)")
        if (self.algo
                != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
            raise ValueError(
                "fit_scan is the SGD fast path; full-batch solver "
                f"configs ({self.algo}) train via fit()")
        self._ensure_packed()
        ksh = NamedSharding(
            self.mesh,
            P(None, self.dp_axis) if self.dp_axis is not None else P())
        fs = jax.device_put(
            jnp.asarray(features_stacked, net._dtype), ksh)
        ys = jax.device_put(jnp.asarray(labels_stacked, net._dtype), ksh)
        fms = (None if features_mask_stacked is None else jax.device_put(
            jnp.asarray(features_mask_stacked, net._dtype), ksh))
        lms = (None if labels_mask_stacked is None else jax.device_put(
            jnp.asarray(labels_mask_stacked, net._dtype), ksh))
        K = int(fs.shape[0])
        key = ("scan", fs.shape, ys.shape,
               None if fms is None else fms.shape,
               None if lms is None else lms.shape)
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(
                fs.shape[1:], ys.shape[1:], scan=True)
        step, rnn_shape = self._step_cache[key]
        if self._rnn_dummy is None:
            self._rnn_dummy = self._zero_rnn(rnn_shape)
        net._key, sub = jax.random.split(net._key)
        start = net.iteration
        t0 = time.perf_counter()
        (self._theta, self._ustate, self._sstate, self._rnn_dummy,
         scores) = step(
            self._theta, self._ustate, self._sstate, self._rnn_dummy,
            net.iteration, sub, fs, ys, fms, lms,
        )
        dispatch_s = time.perf_counter() - t0
        _, examples, tokens = window_counts(fs.shape)
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, steps=K, examples=examples,
            tokens=tokens)
        self._emit_step_span(dispatch_s, steps=K,
                             iteration=net.iteration + K, fused="scan")
        net.iteration += K
        net.score_value = scores[-1]
        self._sync_to_net()
        from deeplearning4j_tpu.optimize.listeners import fire_crossed

        fire_crossed(net.listeners, net, start, net.iteration)
        return scores


class PipelinedProblem:
    """``FlatProblem`` counterpart on the stage-sharded [S, Kp] buffer.

    The solver's x IS the trainer's packed theta ([S, Kp] laid out
    ``P(pp)``): ``value_and_grad``/``value`` run the full microbatched
    GPipe schedule (forward-only for line-search probes), and every
    vector the BaseOptimizer materializes from x — directions, CG
    conjugates, L-BFGS s/y history — inherits the sharding through
    jnp arithmetic, so per-device solver memory stays at 1/S of the
    model like the SGD path (the property asserted in
    tests/test_pipeline_expert.py:634).

    Listener visibility: ``write_back`` syncs ``net.params`` from the
    packed buffer only when ``jax.process_count() == 1`` — under
    multi-process runs, per-iteration listeners observe stale
    ``net.params`` until the end of ``fit()`` (same contract as the
    SGD path's listener sync; the gather would cost a cross-host
    collective per solver iteration).

    Mirrors optimize/solver.py FlatProblem's surface: ``x0``,
    ``value_and_grad(x) -> (score, grad)``, ``value(x) -> score``,
    ``hessian_vector_product`` (forward-over-reverse jvp through the
    shard_map'd gradient — the pipelined form of the reference R-op,
    MultiLayerNetwork.computeDeltasR :728), ``write_back``.
    """

    def __init__(self, trainer: "PipelineTrainer", ds):
        import jax.numpy as jnp

        net = trainer.net
        trainer._ensure_packed()
        self._trainer = trainer
        bspec = (NamedSharding(trainer.mesh, P(trainer.dp_axis))
                 if trainer.dp_axis is not None
                 else NamedSharding(trainer.mesh, P()))
        self._feats = jax.device_put(
            jnp.asarray(ds.features, net._dtype), bspec)
        self._labels = jax.device_put(
            jnp.asarray(ds.labels, net._dtype), bspec)
        self._fm = (None if ds.features_mask is None else jax.device_put(
            jnp.asarray(ds.features_mask, net._dtype), bspec))
        self._lm = (None if ds.labels_mask is None else jax.device_put(
            jnp.asarray(ds.labels_mask, net._dtype), bspec))
        key = ("solver", self._feats.shape, self._labels.shape,
               None if self._fm is None else self._fm.shape,
               None if self._lm is None else self._lm.shape)
        if key not in trainer._step_cache:
            trainer._step_cache[key] = trainer._build_step(
                self._feats.shape, self._labels.shape, solver=True)
        self._vag, self._val = trainer._step_cache[key]
        self.x0 = trainer._theta

    def value_and_grad(self, x):
        grad, score = self._vag(x, self._trainer._sstate, self._feats,
                                self._labels, self._fm, self._lm)
        return score, grad

    def value(self, x):
        return self._val(x, self._trainer._sstate, self._feats,
                         self._labels, self._fm, self._lm)

    def hessian_vector_product(self, x, v):
        def grad_of(t):
            return self._vag(t, self._trainer._sstate, self._feats,
                             self._labels, self._fm, self._lm)[0]

        return jax.jvp(grad_of, (x,), (v,))[1]

    def write_back(self, x) -> None:
        # x replaces the packed buffer; net.params sync is lazy (end of
        # PipelineTrainer.fit) unless listeners need to observe params
        # after each solver iteration — single-process only, like the
        # SGD path's listener sync (see fit()).
        tr = self._trainer
        tr._theta = x
        if tr.net.listeners and jax.process_count() == 1:
            tr._sync_to_net()
