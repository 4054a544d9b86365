"""Data/tensor-parallel training over a mesh.

TPU-native replacement for the reference's synchronous data-parallel
trainers (SURVEY.md §3.4): where SparkDl4jMultiLayer broadcasts params to
executors (:307), trains clones, and averages through a driver-side
accumulator (:355-361, an O(N) reduction through one process), here the
global batch is sharded over the mesh's ``dp`` axis and gradients are
combined by a compiled all-reduce that XLA derives from the mean-loss
autodiff — the averaging semantics are identical (per-iteration parameter
averaging of SGD == gradient averaging), the communication is ICI.

Tensor parallelism (absent in the reference, added per SURVEY.md §7 stage
10) shards Dense weight matrices Megatron-style: even layers column-
parallel [None, "tp"], odd layers row-parallel ["tp", None]; XLA inserts
the partial-sum all-reduce after row-parallel matmuls.

Also provides K-local-steps-then-average (the reference's
``AVERAGE_EACH_ITERATION=false`` mode, SparkDl4jMultiLayer.java:79,
:275-295) via ``shard_map``: each dp group runs K independent steps on its
local shard, then params and updater state are ``pmean``-ed — byte-for-byte
the Spark semantics, compiled.

Sequence parallelism (``sp_axis``; SURVEY.md §5.7 mandate) shards the TIME
axis of [N, C, T] batches: the whole train step runs inside ``shard_map``
with replicated params, attention layers (ring_axis=sp_axis) execute the
ring-attention schedule over ICI, and the loss/gradient are reconstructed
as exact global (masked) means via count-weighted psums — so a conf-built
transformer trains on sequences P× longer than one device's activation
memory allows, with single-device trajectory parity. Composes with dp
(batch axis shards over dp, time over sp, gradients psum over both).
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.optimize.telemetry import (
    HEALTH_KEYS,
    batch_counts,
    emit_step_span,
    grad_health,
    mesh_args,
    window_counts,
)


def _layer_items(net):
    """Uniform (param_key, layer_bean) iteration for MultiLayerNetwork
    (keys "0".."N-1" over conf.confs) and ComputationGraph (keys =
    layer-vertex names)."""
    if hasattr(net, "_layer_vertices"):
        for name in sorted(net._layer_vertices):
            yield name, net._layer_vertices[name].conf.layer
    else:
        for i, c in enumerate(net.conf.confs):
            yield str(i), c.layer


def tp_param_specs(net, mesh_axis: str = "tp"):
    """PartitionSpec pytree for a network's params: Megatron column/row
    alternation for stacked Dense layers; attention layers shard over
    HEADS (Wq/Wk/Wv column-parallel so each device owns n_heads/T whole
    heads, Wo row-parallel so XLA inserts one all-reduce after the
    output projection — the Megatron self-attention block); replicate
    everything else. MultiLayerNetwork only — the column/row
    alternation is defined by the sequential layer chain, which an
    arbitrary graph DAG lacks."""
    from deeplearning4j_tpu.nn.layers.attention import (
        MultiHeadSelfAttention,
        TransformerBlock,
    )

    if hasattr(net, "_layer_vertices"):
        raise ValueError(
            "tp_param_specs requires a MultiLayerNetwork: Megatron "
            "column/row alternation follows the sequential layer chain; "
            "for ComputationGraphs shard expert (ep) or data (dp) axes")
    specs = {}
    col = True
    for key, lc in _layer_items(net):
        layer_specs = {}
        if isinstance(lc, MultiHeadSelfAttention):
            # Head sharding propagates through the [N,T,D]->[N,H,T,dh]
            # reshape only when the tp size divides the head count
            # (GSPMD splits D into whole heads).
            layer_specs["Wq"] = P(None, mesh_axis)
            layer_specs["Wk"] = P(None, mesh_axis)
            layer_specs["Wv"] = P(None, mesh_axis)
            layer_specs["Wo"] = P(mesh_axis, None)
            layer_specs["b"] = P()
        elif isinstance(lc, TransformerBlock):
            # Megatron block sharding: attention heads column-sharded
            # (as above), FFN W1 column / W2 row — the two all-reduces
            # per block land after Wo and W2. LayerNorm params, biases,
            # and the tiny input projection Wi stay replicated (LN
            # normalizes the full channel axis; sharding it would cost
            # a per-token collective for ~2*d floats of savings).
            layer_specs["Wq"] = P(None, mesh_axis)
            layer_specs["Wk"] = P(None, mesh_axis)
            layer_specs["Wv"] = P(None, mesh_axis)
            layer_specs["Wo"] = P(mesh_axis, None)
            layer_specs["W1"] = P(None, mesh_axis)
            layer_specs["b1"] = P(mesh_axis)
            layer_specs["W2"] = P(mesh_axis, None)
        elif isinstance(lc, (L.DenseLayer,)) and not isinstance(
            lc, L.OutputLayer
        ):
            if col:
                layer_specs["W"] = P(None, mesh_axis)
                layer_specs["b"] = P(mesh_axis)
            else:
                layer_specs["W"] = P(mesh_axis, None)
                layer_specs["b"] = P()
            col = not col
        for name in net.params[key]:
            layer_specs.setdefault(name, P())
        specs[key] = layer_specs
    return specs


def fsdp_param_specs(net, mesh, mesh_axis: str = "fsdp",
                     base: Optional[dict] = None):
    """Overlay ZeRO-3/FSDP sharding onto a param-spec pytree: every
    parameter leaf's LARGEST divisible dimension is sharded over
    ``mesh_axis``, so per-device persistent parameter + updater-state
    memory drops to ~1/F of the model. Under jit, XLA all-gathers each
    tensor at its use site and reduce-scatters its gradient — the
    ZeRO-3 schedule derived by GSPMD instead of hand-written bucketing
    (the TPU-native analogue of torch FSDP / DeepSpeed ZeRO stage 3).
    Leaves already carrying a spec in ``base`` (tp/ep shardings) are
    left alone; leaves with no dimension divisible by F stay
    replicated. Works for MultiLayerNetwork and ComputationGraph."""
    F = int(mesh.shape[mesh_axis])
    specs = dict(base) if base else {}
    for key, _ in _layer_items(net):
        layer_specs = dict(specs.get(key, {}))
        for name, p in net.params[key].items():
            existing = layer_specs.get(name)
            if existing is not None and any(existing):
                continue  # tp/ep laid this tensor out already
            shape = np.shape(p)
            best = None
            for d in sorted(range(len(shape)), key=lambda d: -shape[d]):
                if shape[d] % F == 0 and shape[d] >= F:
                    best = d
                    break
            if best is None:
                layer_specs[name] = P()
            else:
                spec = [None] * len(shape)
                spec[best] = mesh_axis
                layer_specs[name] = P(*spec)
        specs[key] = layer_specs
    if not any(
        mesh_axis in tuple(sp)
        for layer in specs.values() for sp in layer.values()
    ):
        raise ValueError(
            f"fsdp_axis={mesh_axis!r} (size {F}) shards NOTHING: no "
            "parameter dimension is divisible by it — training would "
            "run fully replicated while promising 1/F memory. Pick a "
            "divisor of the layer widths or drop the axis.")
    return specs


def ep_param_specs(net, mesh_axis: str = "ep",
                   base: Optional[dict] = None):
    """Overlay expert sharding onto a param-spec pytree: MoeDense
    expert tensors carry their leading expert axis on ``mesh_axis``;
    under pjit XLA turns the capacity-dispatch einsums into the expert
    all-to-all (GSPMD counterpart of the explicit
    parallel/expert_parallel.make_ep_moe schedule). Works for both
    MultiLayerNetwork layers and ComputationGraph MoE layer vertices."""
    from deeplearning4j_tpu.nn.layers.moe import MoeDense

    n_ep = None
    specs = dict(base) if base else {}
    for key, lc in _layer_items(net):
        layer_specs = dict(specs.get(key, {}))
        if isinstance(lc, MoeDense):
            layer_specs["W_up"] = P(mesh_axis, None, None)
            layer_specs["W_down"] = P(mesh_axis, None, None)
            n_ep = lc.n_experts
        for name in net.params[key]:
            layer_specs.setdefault(name, P())
        specs[key] = layer_specs
    if n_ep is None:
        raise ValueError(
            "ep_axis was configured but the network has no MoeDense "
            "layers to shard")
    return specs


class ParallelTrainer:
    """Synchronous SPMD trainer wrapping a MultiLayerNetwork.

    ``average_each_iteration=True`` (reference default): one global step
    per iteration, gradients all-reduced — train via sharded batch.
    ``average_each_iteration=False`` with ``local_steps=K``: K independent
    local steps per round, then parameter + updater-state averaging.
    """

    def __init__(
        self,
        net,
        mesh: Mesh,
        dp_axis: str = "dp",
        tp_axis: Optional[str] = None,
        ep_axis: Optional[str] = None,
        fsdp_axis: Optional[str] = None,
        sp_axis: Optional[str] = None,
        average_each_iteration: bool = True,
        local_steps: int = 1,
        accumulate_gradients: bool = False,
        divide_gradient: bool = True,
        tracer=None,
    ):
        net.init()
        self.net = net
        self.mesh = mesh
        self.dp_axis = dp_axis
        # Optional span sink: every step emits a ``train.parallel_step``
        # span annotated with the mesh config (ISSUE 8), so a MULTICHIP
        # sweep's per-combo Chrome traces are comparable in Perfetto.
        self.tracer = tracer
        # ComputationGraph duck type: multi-input coercion + dict params
        self.is_graph = hasattr(net, "_coerce_multi")
        self.tp_axis = tp_axis if (tp_axis and tp_axis in mesh.axis_names) else None
        self.ep_axis = ep_axis if (ep_axis and ep_axis in mesh.axis_names) else None
        self.fsdp_axis = (fsdp_axis
                          if (fsdp_axis and fsdp_axis in mesh.axis_names)
                          else None)
        self.sp_axis = (sp_axis
                        if (sp_axis and sp_axis in mesh.axis_names)
                        else None)
        if self.sp_axis:
            self._validate_sp(net)
            self._sp_axes = tuple(
                a for a in
                ((dp_axis if dp_axis in mesh.axis_names else None),
                 self.sp_axis)
                if a)
        # The fsdp axis IS a data axis (as in torch FSDP / ZeRO-3): the
        # batch shards over dp x fsdp jointly, so all D*F devices do
        # data-parallel work while parameters live sharded over fsdp.
        self._batch_axes = (
            (dp_axis, self.fsdp_axis)
            if self.fsdp_axis and self.fsdp_axis != dp_axis
            else (dp_axis,))
        if self.is_graph and self.tp_axis:
            raise ValueError(
                "tensor parallelism (tp_axis) supports MultiLayerNetwork "
                "only: the Megatron column/row alternation follows the "
                "sequential layer chain; ComputationGraphs compose dp "
                "and ep axes")
        if self.tp_axis:
            from deeplearning4j_tpu.nn.layers.attention import (
                ATTENTION_BEANS,
            )

            T = int(mesh.shape[self.tp_axis])
            for _, lc in _layer_items(net):
                if isinstance(lc, ATTENTION_BEANS):
                    if lc.n_heads % T:
                        raise ValueError(
                            f"n_heads {lc.n_heads} not divisible by mesh "
                            f"tp={T}: head sharding needs whole heads "
                            "per device")
                    if (lc.ring_axis
                            and getattr(lc, "sp_mode", "ring")
                            == "ulysses"):
                        raise ValueError(
                            "ulysses sp_mode all-to-alls the HEAD axis "
                            "over sp; it cannot compose with tp head "
                            "sharding — use sp_mode='ring' with tp")
                    if lc.ring_axis and lc.ring_axis != self.sp_axis:
                        # ring + tp COMPOSE when the ring runs over the
                        # trainer's sp axis (2D attention parallelism:
                        # time manual over sp, heads GSPMD-auto over
                        # tp); a standalone ring_axis without sp_axis
                        # has no mesh to ride.
                        raise ValueError(
                            "ring attention (ring_axis) composes with "
                            "head-sharded tp only through "
                            "ParallelTrainer(sp_axis=ring_axis)")
        if self.ep_axis:
            from deeplearning4j_tpu.nn.layers.moe import MoeDense

            for _, lc in _layer_items(net):
                if (isinstance(lc, MoeDense)
                        and lc.n_experts % mesh.shape[ep_axis]):
                    raise ValueError(
                        f"n_experts {lc.n_experts} not divisible "
                        f"by mesh ep={mesh.shape[ep_axis]}")
                if isinstance(lc, MoeDense) and lc.ep_axis:
                    raise ValueError(
                        "MoeDense.ep_axis (explicit shard_map all-to-all)"
                        " and ParallelTrainer ep_axis (GSPMD sharding) "
                        "are alternative dispatch paths; configure one")
        self.average_each_iteration = average_each_iteration
        self.local_steps = max(1, local_steps)
        # Reference engine flags org.deeplearning4j.spark.iteration.
        # {accumgrad,dividegrad} (SparkDl4jMultiLayer.java:80-81): with
        # accumulate_gradients the applied update is the per-worker
        # gradient SUM (divide_gradient=False) or mean (=True; identical
        # to the sharded-batch gradient this trainer already computes).
        self.accumulate_gradients = accumulate_gradients
        self.divide_gradient = divide_gradient
        if accumulate_gradients and not average_each_iteration:
            raise ValueError(
                "accumulate_gradients applies to the per-step synchronous "
                "mode; K-local-steps mode averages parameters instead")
        if (self.ep_axis or self.fsdp_axis) and not average_each_iteration:
            raise ValueError(
                "expert-/fsdp-sharded params require the per-step "
                "synchronous mode (K-local-steps shard_maps with "
                "replicated params)")
        if self.sp_axis and not average_each_iteration:
            raise ValueError(
                "sequence parallelism (sp_axis) is a per-step "
                "synchronous mode: the ring exchanges K/V blocks inside "
                "every step, so K-independent-local-steps semantics do "
                "not apply")
        if self.sp_axis and accumulate_gradients:
            raise ValueError(
                "accumulate_gradients (per-worker gradient SUM) is a dp "
                "engine flag; the sp step applies the exact global mean "
                "gradient")
        if not average_each_iteration and net.state:
            raise ValueError(
                "K-local-steps-then-average mode does not support layers "
                "with running state (BatchNormalization); use "
                "average_each_iteration=True"
            )
        self._place_params()

    # ------------------------------------------------------------------
    def _param_sharding(self):
        if self.tp_axis:
            specs = tp_param_specs(self.net, self.tp_axis)
        else:
            specs = jax.tree.map(
                lambda _: P(), self.net.params,
                is_leaf=lambda x: isinstance(x, jax.Array),
            )
        if self.ep_axis:
            specs = ep_param_specs(self.net, self.ep_axis, base=specs)
        if self.fsdp_axis:
            specs = fsdp_param_specs(self.net, self.mesh, self.fsdp_axis,
                                     base=specs)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _place_params(self) -> None:
        shardings = self._param_sharding()
        self.net.params = jax.device_put(self.net.params, shardings)
        # Updater state: each moment subtree (Adam m/v, Nesterovs v, …)
        # mirrors the layer's param pytree, so it takes the SAME
        # shardings — replicating Adam moments of ep/tp-sharded params
        # would hold the full unsharded tensors on every device and
        # reshard against sharded gradients each step.
        repl = NamedSharding(self.mesh, P())
        ushard = {}
        for si, moments in self.net.updater_state.items():
            layer = {}
            for mk, sub in (moments or {}).items():
                try:
                    layer[mk] = jax.tree.map(lambda s, _: s,
                                             shardings[si], sub)
                except ValueError:  # structure doesn't mirror params
                    layer[mk] = jax.tree.map(
                        lambda _: repl, sub,
                        is_leaf=lambda x: isinstance(x, jax.Array))
            ushard[si] = layer
        self.net.updater_state = jax.device_put(self.net.updater_state, ushard)
        if self.net.state:
            self.net.state = jax.device_put(
                self.net.state, NamedSharding(self.mesh, P())
            )

    def _shard_batch(self, arr):
        return self._put_spec(arr, P(self._batch_axes))

    def _grad_scale(self) -> float:
        """data-worker count under ACCUM_GRADIENT-without-divide (the
        fsdp axis counts: it carries batch shards too), else 1."""
        if self.accumulate_gradients and not self.divide_gradient:
            n = 1.0
            for ax in self._batch_axes:
                n *= float(self.mesh.shape[ax])
            return n
        return 1.0

    def _shard_stacked(self, arr):
        """[K, B, ...] pre-stacked batches: shard B over dp, K stays on
        every device (it is the scan axis)."""
        return jax.device_put(
            jnp.asarray(arr, self.net._dtype),
            NamedSharding(self.mesh, P(None, self._batch_axes)),
        )

    def _trace_args(self, **extra):
        """Mesh-config span annotation for this trainer's steps."""
        axes = {name: ax for name, ax in (
            ("dp", self.dp_axis), ("tp", self.tp_axis),
            ("ep", self.ep_axis), ("fsdp", self.fsdp_axis),
            ("sp", self.sp_axis)) if ax}
        return mesh_args(self.mesh, "data", **axes, **extra)

    def _emit_step_span(self, dispatch_s: float, **extra) -> None:
        if self.tracer is not None:
            emit_step_span(self.tracer, dispatch_s,
                           self._trace_args(**extra))

    def fit_scan(self, features_stacked, labels_stacked,
                 features_mask_stacked=None, labels_mask_stacked=None):
        """K fused global steps: ``lax.scan`` over pre-stacked sharded
        batches ([K, B, ...] with B split over the dp axis) — one host
        dispatch per K synchronous all-reduced steps. The pod-scale
        composition of MultiLayerNetwork/ComputationGraph.fit_scan: XLA
        inserts the gradient all-reduce inside the scan body, so the ICI
        collective pipelines with compute across all K steps. Masked
        time-series batches ride the same fused path: [K, B, T] arrays
        for MultiLayerNetwork, per-input/per-output dicts for
        ComputationGraph."""
        if not self.average_each_iteration:
            raise ValueError(
                "fit_scan is the per-step-synchronous path; "
                "K-local-steps mode already fuses via local_steps")
        t0 = time.perf_counter()
        scores = self._fit_scan_impl(
            features_stacked, labels_stacked,
            features_mask_stacked, labels_mask_stacked)
        self._emit_step_span(
            time.perf_counter() - t0,
            steps=int(jax.tree.leaves(features_stacked)[0].shape[0]),
            iteration=self.net.iteration, fused="scan")
        return scores

    def _fit_scan_impl(self, features_stacked, labels_stacked,
                       features_mask_stacked=None,
                       labels_mask_stacked=None):
        if self.sp_axis:
            return self._fit_scan_sp(
                features_stacked, labels_stacked,
                features_mask_stacked, labels_mask_stacked)
        # Shard then delegate: jnp.asarray inside net.fit_scan preserves
        # the placement, and the net-level guards (tBPTT, non-SGD) and
        # listener cadence apply identically here.
        if self.is_graph:
            # dict of [K, B, ...] inputs / list of [K, B, ...] labels /
            # dict [K, B, T] masks — all dp-sharded leaf-wise
            features_stacked = jax.tree.map(
                self._shard_stacked, features_stacked)
            labels_stacked = jax.tree.map(
                self._shard_stacked, labels_stacked)
            fms = (None if features_mask_stacked is None
                   else jax.tree.map(self._shard_stacked,
                                     features_mask_stacked))
            lms = (None if labels_mask_stacked is None
                   else jax.tree.map(self._shard_stacked,
                                     labels_mask_stacked))
            return self.net.fit_scan(
                features_stacked, labels_stacked,
                masks_stacked=fms, label_masks_stacked=lms,
                grad_scale=self._grad_scale())
        features_stacked = self._shard_stacked(features_stacked)
        labels_stacked = self._shard_stacked(labels_stacked)
        fms = (None if features_mask_stacked is None
               else self._shard_stacked(features_mask_stacked))
        lms = (None if labels_mask_stacked is None
               else self._shard_stacked(labels_mask_stacked))
        return self.net.fit_scan(
            features_stacked, labels_stacked,
            features_mask_stacked=fms, labels_mask_stacked=lms,
            grad_scale=self._grad_scale())

    # ------------------------------------------------------------------
    def fit(self, data, labels=None) -> float:
        """One (or more) global synchronous steps on the given batch."""
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

        if labels is not None:
            data = DataSet(data, labels)
        if isinstance(data, (DataSet, MultiDataSet)):
            batches = [data]
        else:
            batches = data  # iterator
        score = float("nan")
        for ds in batches:
            if self.average_each_iteration:
                score = self._fit_sync(ds)
            else:
                score = self._fit_local_then_average(ds)
        return score

    def _fit_sync(self, ds) -> float:
        net = self.net
        if self.sp_axis:
            return self._fit_sp(ds)
        if self.is_graph:
            # Multi-input/multi-output batch: shard every feature/label/
            # mask leaf over dp (graph _train_step has the same arity as
            # the MLN one, with pytree-valued inputs/labels).
            inputs, labels, fm, lm = net._coerce_multi(ds)
            inputs = jax.tree.map(self._shard_batch, inputs)
            labels = jax.tree.map(self._shard_batch, labels)
            fm = None if fm is None else jax.tree.map(self._shard_batch, fm)
            lm = None if lm is None else jax.tree.map(self._shard_batch, lm)
        else:
            inputs = self._shard_batch(ds.features)
            labels = self._shard_batch(ds.labels)
            fm = self._shard_batch(ds.features_mask)
            lm = self._shard_batch(ds.labels_mask)
        net._key, sub = jax.random.split(net._key)
        t0 = time.perf_counter()
        (net.params, net.state, net.updater_state, score,
         health) = net._train_step(
            net.params, net.state, net.updater_state,
            net.iteration, sub, inputs, labels, fm, lm, self._grad_scale(),
        )
        dispatch_s = time.perf_counter() - t0
        examples, tokens = batch_counts(jax.tree.leaves(inputs)[0])
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, examples=examples, tokens=tokens,
            health=health)
        self._emit_step_span(dispatch_s, iteration=net.iteration + 1)
        net.score_value = score
        net.iteration += 1
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return float(score)

    # ------------------------------------------------------------------
    def _fit_local_then_average(self, ds) -> float:
        """K local steps per dp shard, then pmean of params+updater state
        (reference average-at-end semantics). Works for MultiLayerNetwork
        and ComputationGraph (pytree-valued inputs/labels)."""
        net = self.net
        step = self._local_steps_fn
        if self.is_graph:
            inputs, labs, fmt, lmt = net._coerce_multi(ds)
            feats = jax.tree.map(self._shard_batch, inputs)
            labels = jax.tree.map(self._shard_batch, labs)
            fm = None if fmt is None else jax.tree.map(
                self._shard_batch, fmt)
            lm = None if lmt is None else jax.tree.map(
                self._shard_batch, lmt)
        else:
            feats = self._shard_batch(ds.features)
            labels = self._shard_batch(ds.labels)
            fm = self._shard_batch(ds.features_mask)
            lm = self._shard_batch(ds.labels_mask)
        net._key, sub = jax.random.split(net._key)
        t0 = time.perf_counter()
        net.params, net.updater_state, score = step(
            net.params, net.updater_state, jnp.asarray(net.iteration),
            sub, feats, labels, fm, lm,
        )
        dispatch_s = time.perf_counter() - t0
        examples, tokens = batch_counts(jax.tree.leaves(feats)[0])
        # K-local-steps fuses its own update rule (no per-step health
        # outputs); phase/throughput telemetry still lands.
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, steps=self.local_steps,
            examples=examples * self.local_steps,
            tokens=tokens * self.local_steps)
        self._emit_step_span(
            dispatch_s, steps=self.local_steps,
            iteration=net.iteration + self.local_steps,
            mode="local_then_average")
        net.score_value = score
        net.iteration += self.local_steps
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return float(score)

    @functools.cached_property
    def _local_steps_fn(self):
        net = self.net
        dp = self.dp_axis
        K = self.local_steps

        from deeplearning4j_tpu.nn.multilayer import layer_update

        if self.is_graph:
            items = [
                (name, net._layer_vertices[name].conf, net._updaters[name])
                for name in sorted(net._layer_vertices)
            ]
        else:
            items = [
                (str(i), c, upd)
                for i, (c, upd) in enumerate(
                    zip(net.conf.confs, net._updaters))
            ]

        def local_steps(params, upd_state, iteration, rng, feats, labels,
                        fm, lm):
            def one_step(carry, k):
                params, upd_state = carry
                (score, _), grads = jax.value_and_grad(
                    net._loss_fn, has_aux=True
                )(params, {}, jax.random.fold_in(rng, k), feats, labels,
                  fm, lm)
                new_params = {}
                new_upd = {}
                for key, c, upd in items:
                    updates, new_upd[key] = layer_update(
                        c, upd, grads[key], upd_state[key], iteration + k)
                    new_params[key] = jax.tree.map(
                        lambda p, u: p - u, params[key], updates
                    )
                return (new_params, new_upd), score

            (params, upd_state), scores = jax.lax.scan(
                one_step, (params, upd_state), jnp.arange(K)
            )
            # The reference's average-at-end: params and updater state are
            # mean-combined across workers (UpdaterAggregator semantics).
            params = jax.tree.map(lambda p: jax.lax.pmean(p, dp), params)
            upd_state = jax.tree.map(
                lambda s: jax.lax.pmean(s, dp), upd_state
            )
            return params, upd_state, jax.lax.pmean(scores[-1], dp)

        pspec = jax.tree.map(
            lambda _: P(), self.net.params,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )
        uspec = jax.tree.map(
            lambda _: P(), self.net.updater_state,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )
        fn = shard_map(
            local_steps,
            mesh=self.mesh,
            in_specs=(pspec, uspec, P(), P(), P(dp), P(dp), P(dp), P(dp)),
            out_specs=(pspec, uspec, P()),
            check_vma=False,
        )
        return jax.jit(fn)

    # ------------------------------------------------------------------
    # Sequence parallelism (sp_axis): conf-level ring attention
    # ------------------------------------------------------------------
    def _validate_sp(self, net) -> None:
        """sp_axis shards the TIME axis of [N, C, T] batches over the
        mesh, so every layer must be time-shardable: attention cores run
        the ring/Ulysses schedule (parallel/sequence_parallel.py),
        LSTM/GRU recurrences run as a distributed ``sp_scan`` (carry
        hops the ring — exact full BPTT, O(T/P) memory/device), and
        per-timestep layers (RnnOutputLayer, MoeDense) run on their
        local shard unchanged. Bidirectional LSTM (reverse ring) and
        cross-time preprocessors cannot."""
        from deeplearning4j_tpu.nn.conf.enums import (
            BackpropType,
            OptimizationAlgorithm,
        )
        from deeplearning4j_tpu.nn.layers.attention import (
            ATTENTION_BEANS,
        )
        from deeplearning4j_tpu.nn.layers.moe import MoeDense

        if self.sp_axis == self.dp_axis:
            raise ValueError(
                f"sp_axis {self.sp_axis!r} must name a mesh axis "
                "distinct from dp_axis: the batch axis shards over dp "
                "and the time axis over sp")
        # ComputationGraph composes too (round 4): layer vertices obey
        # the same bean rules as the sequential chain, and the graph's
        # structural vertices are either per-timestep (Merge/
        # ElementWise/Subset concatenate, combine, or slice the FEATURE
        # dim) or cross-time and rejected in _validate_sp_graph
        # (LastTimeStep gathers one global timestep; preprocessors
        # reshape across time; DuplicateToTimeSeries reads a static 2D
        # input, and every sp batch leaf must be time-sharded 3D).
        if self.ep_axis or self.fsdp_axis:
            raise ValueError(
                "sp_axis composes with dp (manual batch/time axes) and "
                "tp (params stay GSPMD-auto inside the partial-manual "
                "shard_map), but not with ep/fsdp param sharding")
        first = (next(iter(net._layer_vertices.values())).conf
                 if self.is_graph else net.conf.confs[0])
        algo = first.optimization_algo
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            raise ValueError(
                f"sp_axis is a plain-SGD-family path (got {algo}); "
                "second-order solvers need unsharded line searches")
        if net.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "sp_axis replaces tBPTT as the long-sequence device "
                "(SURVEY.md §5.7): full-BPTT with the time axis sharded")
        if self.is_graph:
            self._validate_sp_graph(net, ATTENTION_BEANS, L, MoeDense)
            return
        for i, c in enumerate(net.conf.confs):
            lc = c.layer
            if net.conf.preprocessor_for(i) is not None:
                raise ValueError(
                    f"layer {i}: input preprocessors reshape across the "
                    "sharded time axis and are not supported under "
                    "sp_axis")
            if isinstance(lc, ATTENTION_BEANS + (L.GravesLSTM, L.GRU)):
                # attention runs the ring/Ulysses schedule; LSTM/GRU
                # recurrences run as distributed sp_scan (carry hops
                # the ring) — exact full BPTT, O(T/P) memory/device
                if lc.ring_axis != self.sp_axis:
                    raise ValueError(
                        f"layer {i}: {type(lc).__name__}.ring_axis="
                        f"{lc.ring_axis!r} must equal sp_axis="
                        f"{self.sp_axis!r} so the time axis runs "
                        "the sp schedule over the mesh's sp devices")
            elif isinstance(lc, (L.RnnOutputLayer, MoeDense,
                                 L.LayerNormalization)):
                # Per-timestep/per-token layers shard trivially. NOTE:
                # MoeDense capacity routing becomes per-time-shard
                # (each device routes its local tokens against its own
                # capacity) — ghost-routing semantics, the documented
                # deviation, analogous to ghost batch norm under pp.
                pass
            else:
                raise ValueError(
                    f"layer {i} ({type(lc).__name__}) is not "
                    "time-shardable: sp_axis supports "
                    "MultiHeadSelfAttention, TransformerBlock, "
                    "GravesLSTM, and GRU (each with "
                    "ring_axis=sp_axis), plus MoeDense, "
                    "LayerNormalization, and RnnOutputLayer")
        stateful = [
            si for si, st in (net.state or {}).items()
            if not (isinstance(st, dict) and set(st) <= {"aux_loss"})
        ]
        if stateful:
            raise ValueError(
                f"layers {stateful} carry running state; sp_axis "
                "supports stateless / aux-only-state layers")
        if not hasattr(net._impls[-1], "loss"):
            raise ValueError(
                "last layer must be an output layer to compute a score "
                f"(got {type(net.conf.confs[-1].layer).__name__})")

    def _validate_sp_graph(self, net, ATTENTION_BEANS, L,
                           MoeDense) -> None:
        """Vertex-level time-shardability walk for ComputationGraph
        (same bean rules as the sequential chain; structural vertices
        per the _validate_sp comment)."""
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            DuplicateToTimeSeriesVertex,
            LastTimeStepVertex,
            LayerVertex,
            PreprocessorVertex,
        )

        for name, vertex in net.conf.vertices.items():
            if isinstance(vertex, (LastTimeStepVertex,
                                   PreprocessorVertex,
                                   DuplicateToTimeSeriesVertex)):
                raise ValueError(
                    f"vertex {name!r} ({type(vertex).__name__}) "
                    "crosses the sharded time axis (global-timestep "
                    "gather / reshape / static-to-time broadcast) and "
                    "cannot run under sp_axis")
            if not isinstance(vertex, LayerVertex):
                continue  # Merge/ElementWise/Subset/Duplicate/input:
                # feature-dim ops, per-timestep under the shard
            if vertex.preprocessor is not None:
                raise ValueError(
                    f"vertex {name!r}: input preprocessors reshape "
                    "across the sharded time axis and are not "
                    "supported under sp_axis")
            lc = vertex.conf.layer
            if isinstance(lc, ATTENTION_BEANS + (L.GravesLSTM, L.GRU)):
                if lc.ring_axis != self.sp_axis:
                    raise ValueError(
                        f"vertex {name!r}: {type(lc).__name__}"
                        f".ring_axis={lc.ring_axis!r} must equal "
                        f"sp_axis={self.sp_axis!r} so the time axis "
                        "runs the sp schedule over the mesh's sp "
                        "devices")
            elif isinstance(lc, (L.RnnOutputLayer, MoeDense,
                                 L.LayerNormalization)):
                pass  # per-timestep/per-token: shards trivially
            else:
                raise ValueError(
                    f"vertex {name!r} ({type(lc).__name__}) is not "
                    "time-shardable: sp_axis graphs support "
                    "MultiHeadSelfAttention, TransformerBlock, "
                    "GravesLSTM, and GRU (each with "
                    "ring_axis=sp_axis), plus MoeDense, "
                    "LayerNormalization, and RnnOutputLayer vertices")
        stateful = [
            si for si, st in (net.state or {}).items()
            if not (isinstance(st, dict) and set(st) <= {"aux_loss"})
        ]
        if stateful:
            raise ValueError(
                f"vertices {stateful} carry running state; sp_axis "
                "supports stateless / aux-only-state vertices")

    def _sp_body_core(self, params, state, upd_state, iteration, rng,
                      f, y, fm, lm):
        """One synchronous global step on local [N?, C, T_local] shards,
        inside shard_map over (dp?, sp). Exact single-device semantics:
        the data term is the GLOBAL (masked) mean — local masked sums
        and mask counts are psum'd so the step loss and gradient match
        an unsharded step even when masks spread unevenly across time
        shards (the pipeline trainer's masked-mean contract)."""
        from deeplearning4j_tpu.nn.multilayer import _cast_floating

        net = self.net
        axes = self._sp_axes
        ndev = 1
        for a in axes:
            ndev *= int(self.mesh.shape[a])
        # Decorrelate per-device dropout draws; parity with the
        # unsharded net holds for dropout-free confs (tests'
        # configuration) — a sharded dropout mask cannot reproduce the
        # single-device draw pattern under any keying.
        didx = lax.axis_index(self.sp_axis)
        if len(axes) == 2:
            didx = (lax.axis_index(axes[0]) * axis_size(axes[1])
                    + didx)
        rng = jax.random.fold_in(rng, didx)

        def global_masked_term(data, out, lm_term):
            # data is the LOCAL masked mean = local_sum / max(count, 1);
            # recover the sum exactly (count 0 => data 0) and re-weight
            # by the global count.
            rows = out.shape[0] * (out.shape[2] if out.ndim == 3 else 1)
            if lm_term is None:
                count = jnp.asarray(float(rows), data.dtype)
            else:
                count = jnp.sum(lm_term.astype(data.dtype))
            local_sum = data * jnp.maximum(count, 1.0)
            total = jnp.maximum(lax.psum(count, axes), 1.0)
            return local_sum / total

        def loss_fn(p):
            if self.is_graph:
                # Multi-output graph: each output contributes its own
                # global masked mean (the per-output lm lives in a
                # dict keyed by output name).
                acts, new_state, _ = net._forward_fn(
                    p, state, f, rng, True, fm)
                local = jnp.zeros((), net._dtype)
                for out_name, yy in zip(net.conf.network_outputs, y):
                    v = net._layer_vertices[out_name]
                    lm_o = None if lm is None else lm.get(out_name)
                    out = acts[out_name]
                    if net._compute_dtype is not None:
                        out = _cast_floating(out, net._dtype)
                    data = net._impls[out_name].loss(
                        v.conf, out, yy, lm_o)
                    local = local + global_masked_term(data, out, lm_o)
            else:
                out, new_state, _ = net._forward_fn(
                    p, state, f, rng, True, fm)
                if net._compute_dtype is not None:
                    out = _cast_floating(out, net._dtype)
                data = net._impls[-1].loss(
                    net.conf.confs[-1], out, y, lm)
                local = global_masked_term(data, out, lm)
            # reg is computed identically on every device and aux is a
            # per-shard estimate: divide by the device count so the
            # psum of per-device losses (and of their gradients) yields
            # reg once and the device-mean aux.
            local = local + (net._reg_score(p)
                             + net._aux_score(new_state)) / ndev
            return local, new_state

        (loss_local, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = jax.tree.map(lambda g: lax.psum(g, axes), grads)
        score = lax.psum(loss_local, axes)
        new_params, new_upd = net._apply_updates(
            params, upd_state, grads, iteration)
        new_state = jax.tree.map(
            lambda s: lax.pmean(s, axes), new_state)
        # Health from the GLOBAL (psum'd) gradient and the replicated
        # params: identical on every device, out-spec P().
        health = grad_health(grads, params, new_params)
        return new_params, new_state, new_upd, score, health

    def _sp_specs(self):
        dp = self._sp_axes[0] if len(self._sp_axes) == 2 else None
        sp = self.sp_axis
        net = self.net
        is_arr = lambda x: isinstance(x, jax.Array)  # noqa: E731
        pspec = jax.tree.map(lambda _: P(), net.params, is_leaf=is_arr)
        sspec = jax.tree.map(lambda _: P(), net.state, is_leaf=is_arr)
        uspec = jax.tree.map(
            lambda _: P(), net.updater_state, is_leaf=is_arr)
        return pspec, sspec, uspec, P(dp, None, sp), P(dp, sp)

    @functools.cached_property
    def _sp_step_fn(self):
        pspec, sspec, uspec, xspec, mspec = self._sp_specs()
        # Manual only over (dp?, sp): any OTHER mesh axis (tp) stays
        # GSPMD-auto inside the body, so head-sharded attention params
        # keep their tp layout and XLA inserts the Megatron collectives
        # around the ring — 2D/3D attention parallelism on one mesh.
        fn = shard_map(
            self._sp_body_core,
            mesh=self.mesh,
            in_specs=(pspec, sspec, uspec, P(), P(),
                      xspec, xspec, mspec, mspec),
            out_specs=(pspec, sspec, uspec, P(),
                       {k: P() for k in HEALTH_KEYS}),
            check_vma=False,
            axis_names=frozenset(self._sp_axes),
        )
        return jax.jit(fn, donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _sp_scan_fn(self):
        """K fused sp steps: lax.scan over [K, ...] stacked batches
        INSIDE the shard_map, so the per-step psums and ring ppermutes
        pipeline across all K steps in one dispatch."""
        pspec, sspec, uspec, xspec, mspec = self._sp_specs()
        kx = P(*((None,) + tuple(xspec)))
        km = P(*((None,) + tuple(mspec)))

        def steps(params, state, upd_state, iteration, rng,
                  fs, ys, fms, lms):
            def body(carry, inp):
                p, s, u, it = carry
                f, y, fm, lm, k = (
                    inp.get("f"), inp.get("y"), inp.get("fm"),
                    inp.get("lm"), inp["k"])
                p, s, u, score, health = self._sp_body_core(
                    p, s, u, it, jax.random.fold_in(rng, k), f, y, fm, lm)
                return (p, s, u, it + 1), (score, health)

            k_steps = jax.tree.leaves(fs)[0].shape[0]
            xs = {"f": fs, "y": ys, "k": jnp.arange(k_steps)}
            if fms is not None:
                xs["fm"] = fms
            if lms is not None:
                xs["lm"] = lms
            (params, state, upd_state, _), (scores, health) = jax.lax.scan(
                body, (params, state, upd_state, iteration), xs)
            return params, state, upd_state, scores, health

        fn = shard_map(
            steps,
            mesh=self.mesh,
            in_specs=(pspec, sspec, uspec, P(), P(), kx, kx, km, km),
            out_specs=(pspec, sspec, uspec, P(),
                       {k: P() for k in HEALTH_KEYS}),
            check_vma=False,
            axis_names=frozenset(self._sp_axes),
        )
        return jax.jit(fn, donate_argnums=(0, 1, 2))

    def _put_spec(self, arr, spec):
        """Place a host batch on the mesh under ``spec``. Multi-host:
        the caller passes its HOST-LOCAL slice of the global batch (each
        host loads only its shard); assemble the global array from the
        per-host pieces."""
        if arr is None:
            return None
        if jax.process_count() > 1:
            from deeplearning4j_tpu.parallel.multihost import (
                host_local_to_global,
            )

            return host_local_to_global(
                np.asarray(arr, self.net._dtype), self.mesh, spec)
        return jax.device_put(
            jnp.asarray(arr, self.net._dtype),
            NamedSharding(self.mesh, spec))

    def _sp_check_ranks(self, inputs, labels, fm, lm, stacked=False):
        """Reject wrongly-shaped sp-graph leaves with a named error
        before placement (a raw GSPMD sharding failure otherwise).
        Covers both the per-batch fit path ([B, C, T] leaves, [B, T]
        masks) and the fused fit_scan path (leading K axis on each)."""
        net = self.net
        rank = 4 if stacked else 3
        shape_x = "[K, B, C, T]" if stacked else "[B, C, T]"
        shape_m = "[K, B, T]" if stacked else "[B, T]"
        for what, leaves in (("input", inputs.items()),
                             ("label", zip(net.conf.network_outputs,
                                           labels))):
            for name, a in leaves:
                if a.ndim != rank:
                    raise ValueError(
                        f"sp_axis graph {what} {name!r} must be "
                        f"{shape_x} (got rank {a.ndim}); static "
                        "inputs have no time axis to shard")
        for what, masks in (("feature mask", fm), ("label mask", lm)):
            for name, a in (masks or {}).items():
                if a.ndim != rank - 1:
                    raise ValueError(
                        f"sp_axis graph {what} {name!r} must be "
                        f"{shape_m} (got rank {a.ndim}) to shard "
                        "its time axis")

    def _sp_place_multi(self, ds):
        """Graph batch placement: every input/label leaf must be a
        time-sharded [B, C, T] array (static 2D leaves have no time
        axis to shard — rejected with a named error); masks are
        per-name [B, T] dicts."""
        net = self.net
        _, _, _, xspec, mspec = self._sp_specs()
        inputs, labels, fm, lm = net._coerce_multi(ds)
        self._sp_check_ranks(inputs, labels, fm, lm)
        put = lambda a: self._put_spec(a, xspec)  # noqa: E731
        putm = lambda a: self._put_spec(a, mspec)  # noqa: E731
        return (jax.tree.map(put, inputs),
                [put(a) for a in labels],
                None if fm is None else jax.tree.map(putm, fm),
                None if lm is None else jax.tree.map(putm, lm))

    def _fit_sp(self, ds) -> float:
        net = self.net
        _, _, _, xspec, mspec = self._sp_specs()
        if self.is_graph:
            feats, labels, fm, lm = self._sp_place_multi(ds)
        else:
            feats = self._put_spec(ds.features, xspec)
            labels = self._put_spec(ds.labels, xspec)
            fm = self._put_spec(ds.features_mask, mspec)
            lm = self._put_spec(ds.labels_mask, mspec)
        net._key, sub = jax.random.split(net._key)
        t0 = time.perf_counter()
        (net.params, net.state, net.updater_state, score,
         health) = self._sp_step_fn(
            net.params, net.state, net.updater_state,
            jnp.asarray(net.iteration), sub, feats, labels, fm, lm)
        dispatch_s = time.perf_counter() - t0
        examples, tokens = batch_counts(jax.tree.leaves(feats)[0])
        net.train_telemetry.record_step(
            dispatch_s=dispatch_s, examples=examples, tokens=tokens,
            health=health)
        self._emit_step_span(dispatch_s, iteration=net.iteration + 1)
        net.score_value = score
        net.iteration += 1
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return float(score)

    def _fit_scan_sp(self, fs, ys, fms=None, lms=None):
        net = self.net
        _, _, _, xspec, mspec = self._sp_specs()
        kx = P(*((None,) + tuple(xspec)))
        km = P(*((None,) + tuple(mspec)))
        if self.is_graph:
            # [K, B, C, T] leaves in input dicts / label lists
            self._sp_check_ranks(fs, ys, fms, lms, stacked=True)
            fs = jax.tree.map(lambda a: self._put_spec(a, kx), fs)
            ys = jax.tree.map(lambda a: self._put_spec(a, kx), ys)
            fms = (None if fms is None else jax.tree.map(
                lambda a: self._put_spec(a, km), fms))
            lms = (None if lms is None else jax.tree.map(
                lambda a: self._put_spec(a, km), lms))
        else:
            fs = self._put_spec(fs, kx)
            ys = self._put_spec(ys, kx)
            fms = self._put_spec(fms, km)
            lms = self._put_spec(lms, km)
        net._key, sub = jax.random.split(net._key)
        start = net.iteration
        t0 = time.perf_counter()
        net.params, net.state, net.updater_state, scores, health = (
            self._sp_scan_fn(
                net.params, net.state, net.updater_state,
                jnp.asarray(net.iteration), sub, fs, ys, fms, lms))
        k, examples, tokens = window_counts(
            jax.tree.leaves(fs)[0].shape)
        net.train_telemetry.record_step(
            dispatch_s=time.perf_counter() - t0, steps=k,
            examples=examples, tokens=tokens, health=health)
        net.iteration += k
        net.score_value = scores[-1]
        from deeplearning4j_tpu.optimize.listeners import fire_crossed

        fire_crossed(net.listeners, net, start, net.iteration)
        return scores
