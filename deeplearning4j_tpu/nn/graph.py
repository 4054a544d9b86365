"""ComputationGraph: DAG network runtime.

Mirror of reference nn/graph/ComputationGraph.java:59 (1,598 LoC):
topologicalSortOrder :593, computeGradientAndScore :656, feedForward :689,
multi-input/multi-output fit. Same TPU inversion as MultiLayerNetwork: the
whole DAG forward + multi-output loss + backward + update is one jitted XLA
computation; vertex structure is resolved at trace time (static), so XLA
sees a flat fused graph.
"""

from __future__ import annotations

import contextlib
import functools
import os
import pickle
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertex,
    ElementWiseOp,
    ElementWiseVertex,
    LastTimeStepVertex,
    LayerVertex,
    MergeVertex,
    PreprocessorVertex,
    SubsetVertex,
)
from deeplearning4j_tpu.nn.gradient import Gradient
from deeplearning4j_tpu.nn.layers import get_impl
from deeplearning4j_tpu.nn.multilayer import (
    _REGULARIZED_KEYS,
    _carried_state,
    _cast_floating,
    _dtype_of,
    _resolve_compute_dtype,
)
from deeplearning4j_tpu.nn.updater.updaters import (
    make_layer_updater,
    normalize_gradients,
    resolve_lr,
)
from deeplearning4j_tpu.optimize.telemetry import (
    TrainTelemetry,
    batch_counts,
    grad_health,
    window_counts,
)
from deeplearning4j_tpu.profiler.scopes import scope

Array = jax.Array


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        conf.validate()
        for out in conf.network_outputs:
            v = conf.vertices[out]
            if not (
                isinstance(v, LayerVertex)
                and isinstance(v.conf.layer, (L.BaseOutputLayer,))
            ):
                raise ValueError(
                    f"Network output {out!r} must be an output layer vertex "
                    "(OutputLayer/RnnOutputLayer) to compute a loss"
                )
        self.conf = conf
        self.order = conf.topological_order()
        self.params: Dict[str, Dict[str, Array]] = {}
        self.state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.iteration = 0
        self.score_value = float("nan")
        self.listeners: List = []
        # Per-step phase clock (see MultiLayerNetwork.train_telemetry).
        self.train_telemetry = TrainTelemetry()
        self._rnn_state: Dict[str, Any] = {}
        self._generate_fns: Dict[int, Any] = {}
        self._layer_vertices = {
            name: v
            for name, v in conf.vertices.items()
            if isinstance(v, LayerVertex)
        }
        self._impls = {
            name: get_impl(v.conf.layer)
            for name, v in self._layer_vertices.items()
        }
        self._updaters = {
            name: make_layer_updater(v.conf)
            for name, v in self._layer_vertices.items()
        }
        first = next(iter(self._layer_vertices.values()), None)
        self._dtype = _dtype_of(first.conf.dtype if first else "float32")
        self._compute_dtype = _resolve_compute_dtype(
            self._dtype, first.conf.compute_dtype if first else None)
        seed = first.conf.seed if first else 12345
        self._key = jax.random.key(seed)
        self._seed = seed
        self._initialized = False

    # ------------------------------------------------------------------
    def init(self) -> "ComputationGraph":
        if self._initialized:
            return self
        key = jax.random.key(self._seed)
        names = sorted(self._layer_vertices)
        keys = jax.random.split(key, max(1, len(names)))
        for k, name in zip(keys, names):
            v = self._layer_vertices[name]
            impl = self._impls[name]
            self.params[name] = impl.init(k, v.conf, self._dtype)
            st = impl.init_state(v.conf, self._dtype)
            if st is not None:
                self.state[name] = st
            self.updater_state[name] = self._updaters[name].init(
                self.params[name]
            )
        self._initialized = True
        return self

    # ------------------------------------------------------------------
    @property
    def _out_f32_vertices(self) -> set:
        # Output-layer vertices run at the master dtype (same rationale
        # as MultiLayerNetwork._out_at_master_dtype: a bf16 softmax
        # quantizes probabilities coarsely enough to stall training).
        return (set(self.conf.network_outputs)
                if self._compute_dtype is not None else set())

    def compute_params(self, params):
        """``params`` as the forward pass computes with them (the rule
        of ``MultiLayerNetwork.compute_params``, by vertex): every
        floating leaf at the compute dtype except the output vertices',
        a leaf already there returned as the same array."""
        if self._compute_dtype is None:
            return params
        cast = functools.partial(
            _cast_floating, dtype=self._compute_dtype)
        keep = self._out_f32_vertices
        return {
            k: (sub if k in keep else jax.tree_util.tree_map(cast, sub))
            for k, sub in params.items()
        }

    def _forward_fn(
        self,
        params,
        state,
        inputs: Dict[str, Array],
        rng,
        train: bool,
        masks: Optional[Dict[str, Array]] = None,
        rnn_state: Optional[Dict[str, Any]] = None,
        stop_at: Optional[str] = None,
    ):
        """Topological-order forward. Returns
        (activation dict, new_state, new_rnn_state) — ``rnn_state`` is the
        per-vertex recurrent carry (reference ComputationGraph
        rnnActivateUsingStoredState :1233: stored state fed back in for
        streaming inference and truncated-BPTT window chaining).

        Under mixed precision a state leaf a layer was handed leaves
        the pass at the dtype it came in with, and a leaf the pass
        created (no incoming state) at the master dtype
        (``multilayer._carried_state``, the same rule as
        ``MultiLayerNetwork._forward_fn``)."""
        out_f32_vertices = self._out_f32_vertices
        # Mixed precision: bf16 compute, f32 master params (same
        # scheme as MultiLayerNetwork._forward_fn)
        params = self.compute_params(params)
        if self._compute_dtype is not None:
            inputs = {k: _cast_floating(v, self._compute_dtype)
                      for k, v in inputs.items()}
        acts: Dict[str, Array] = dict(inputs)
        new_state = dict(state) if state else {}
        new_rnn: Dict[str, Any] = {}
        # Masks propagate along edges: a vertex inherits its first input's
        # time mask, so stacked recurrent layers stay masked (parity with
        # MultiLayerNetwork, which hands feature_mask to every recurrent
        # layer). Time-collapsing vertices drop the mask.
        vmasks: Dict[str, Optional[Array]] = dict(masks or {})
        n_layers = max(1, len(self._layer_vertices))
        if rng is not None:
            layer_keys = dict(
                zip(
                    sorted(self._layer_vertices),
                    jax.random.split(rng, n_layers),
                )
            )
        else:
            layer_keys = {}
        for name in self.order:
            vertex = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            in_mask = vmasks.get(in_names[0])
            if isinstance(vertex, LastTimeStepVertex):
                vmasks[name] = None  # collapses the time axis
            else:
                vmasks[name] = in_mask
            if isinstance(vertex, LayerVertex):
                x = xs[0]
                if vertex.preprocessor is not None:
                    x = vertex.preprocessor.pre_process(
                        x, layer_keys.get(name) if train else None
                    )
                impl = self._impls[name]
                layer_state = new_state.get(name)
                if layer_state is None and rnn_state:
                    layer_state = rnn_state.get(name)
                is_recurrent = isinstance(
                    vertex.conf.layer, L.RECURRENT_LAYER_TYPES
                )
                mask = in_mask if is_recurrent else None
                if name in out_f32_vertices:
                    x = _cast_floating(x, self._dtype)
                # as ``MultiLayerNetwork._forward_fn``: the bean's
                # group around a layer that names nothing itself
                group = vertex.conf.layer.scope_group
                with (scope(group) if group
                      else contextlib.nullcontext()):
                    out, st = impl.apply(
                        vertex.conf,
                        params[name],
                        x,
                        state=layer_state,
                        train=train,
                        rng=layer_keys.get(name) if train else None,
                        mask=mask,
                    )
                if st is not None:
                    if self._compute_dtype is not None:
                        # carried state goes out at the dtype it came
                        # in with (created here: the master dtype), so
                        # repeated steps see stable input dtypes (no
                        # recompiles)
                        st = _carried_state(st, layer_state, self._dtype)
                    if name in new_state:
                        new_state[name] = st
                    else:
                        # recurrent carry (h, c): returned separately so
                        # rnn_time_step/tBPTT can chain it across calls
                        new_rnn[name] = st
                acts[name] = out
            elif isinstance(vertex, MergeVertex):
                acts[name] = jnp.concatenate(xs, axis=1)
            elif isinstance(vertex, ElementWiseVertex):
                acts[name] = _elementwise(vertex.op, xs)
            elif isinstance(vertex, SubsetVertex):
                acts[name] = xs[0][:, vertex.from_index : vertex.to_index + 1]
            elif isinstance(vertex, PreprocessorVertex):
                acts[name] = vertex.preprocessor.pre_process(xs[0])
            elif isinstance(vertex, LastTimeStepVertex):
                acts[name] = _last_time_step(
                    xs[0], vmasks.get(vertex.mask_input)
                )
            elif isinstance(vertex, DuplicateToTimeSeriesVertex):
                ref = acts[vertex.reference_input]
                acts[name] = jnp.broadcast_to(
                    xs[0][:, :, None],
                    xs[0].shape + (ref.shape[-1],),
                )
            else:
                raise ValueError(f"Unknown vertex type {type(vertex).__name__}")
            if name == stop_at:
                # partial forward (pretraining): downstream vertices are
                # never consumed, so don't trace them at all
                break
        return acts, new_state, new_rnn

    def _loss_fn(self, params, state, rng, inputs, labels, masks, label_masks,
                 rnn_state=None):
        acts, new_state, new_rnn = self._forward_fn(
            params, state, inputs, rng, True, masks, rnn_state
        )
        score = 0.0
        for out_name, y in zip(self.conf.network_outputs, labels):
            impl = self._impls[out_name]
            v = self._layer_vertices[out_name]
            lm = None if label_masks is None else label_masks.get(out_name)
            out = acts[out_name]
            if self._compute_dtype is not None:
                out = _cast_floating(out, dtype=self._dtype)  # loss in f32
            score = score + impl.loss(v.conf, out, y, lm)
        score = score + self._reg_score(params)
        score = score + self._aux_score(new_state)
        return score, (new_state, new_rnn)

    def _aux_score(self, new_state):
        """Auxiliary training losses vertices emit through the state
        channel (MoeDense load-balancing loss), gate-weighted per conf."""
        aux = 0.0
        for name, v in self._layer_vertices.items():
            w = getattr(v.conf.layer, "aux_weight", None)
            st = new_state.get(name) if new_state else None
            if w and st and "aux_loss" in st:
                aux = aux + w * st["aux_loss"]
        return aux

    def _reg_score(self, params):
        reg = 0.0
        for name, v in self._layer_vertices.items():
            c = v.conf
            if not c.use_regularization:
                continue
            l1 = float(c.resolved("l1") or 0.0)
            l2 = float(c.resolved("l2") or 0.0)
            if l1 == 0.0 and l2 == 0.0:
                continue
            for pname, p in params[name].items():
                if pname not in _REGULARIZED_KEYS:
                    continue
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(p))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(p * p)
        return reg

    # ------------------------------------------------------------------
    @scope("update/step")
    def _apply_updates(self, params, upd_state, grads, iteration,
                       grad_scale=1.0):
        """Per-vertex normalize → scale → updater → subtract (shared by
        the standard and tBPTT steps)."""
        new_params = {}
        new_upd = {}
        for name, v in self._layer_vertices.items():
            c = v.conf
            g = normalize_gradients(
                c.resolved("gradient_normalization"),
                grads[name],
                float(c.resolved("gradient_normalization_threshold")),
            )
            # see MultiLayerNetwork._apply_updates: ACCUM-without-divide
            g = jax.tree.map(lambda a: a * grad_scale, g)
            updates, new_upd[name] = self._updaters[name].update(
                g, upd_state[name], resolve_lr(c, iteration), iteration
            )
            new_params[name] = jax.tree.map(
                lambda p, u: p - u, params[name], updates
            )
        return new_params, new_upd

    def _step_body(self, params, state, upd_state, iteration, rng, inputs,
                   labels, masks, label_masks, grad_scale=1.0):
        (score, (new_state, _)), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True
        )(params, state, rng, inputs, labels, masks, label_masks)
        new_params, new_upd = self._apply_updates(
            params, upd_state, grads, iteration, grad_scale)
        # Same-executable gradient-health outputs (see
        # MultiLayerNetwork._step_body).
        health = grad_health(grads, params, new_params)
        return new_params, new_state, new_upd, score, health

    @functools.cached_property
    def _train_step(self):
        return jax.jit(self._step_body, donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _train_steps_scan(self):
        """K graph train steps fused into one lax.scan computation (the
        ComputationGraph counterpart of MultiLayerNetwork.fit_scan).
        Mask dicts ride the scan as extra xs (a dict pytree scans
        leaf-wise): an absent mask is an EMPTY dict, which contributes
        no scan leaves and which the loss path already treats like None
        — one compiled kernel per mask-dict structure, keyed by jit
        itself."""

        def steps(params, state, upd_state, iteration, rng, inputs_k,
                  labels_k, masks_k, lmasks_k, grad_scale=1.0):
            def body(carry, inp):
                p, s, u, it, key = carry
                key, sub = jax.random.split(key)
                xs, ys, m, lm = inp
                p, s, u, score, health = self._step_body(
                    p, s, u, it, sub, xs, ys, m, lm, grad_scale)
                return (p, s, u, it + 1, key), (score, health)

            (p, s, u, it, _), (scores, health) = jax.lax.scan(
                body, (params, state, upd_state, iteration, rng),
                (inputs_k, labels_k, masks_k, lmasks_k))
            return p, s, u, scores, health

        return jax.jit(steps, donate_argnums=(0, 1, 2))

    def fit_scan(self, inputs_stacked, labels_stacked,
                 masks_stacked=None, label_masks_stacked=None,
                 grad_scale: float = 1.0):
        """Run K fused steps over pre-stacked batches. ``inputs_stacked``:
        dict input-name -> [K, B, ...] (or a single array for
        single-input graphs); ``labels_stacked``: list of [K, B, ...]
        per output (or a single array). Optional masks:
        ``masks_stacked`` dict input-name -> [K, B, T] (or a single
        array for single-input graphs), ``label_masks_stacked`` dict
        output-name -> [K, B, T] — they ride the scan as extra xs, so
        masked time-series graphs get the same fused fast path.
        Plain-SGD; returns the K per-step scores lazily (device array)."""
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "fit_scan is the full-BPTT SGD fast path; truncated-BPTT "
                "graphs must train via fit()")
        for name, v in self._layer_vertices.items():
            algo = v.conf.optimization_algo
            if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
                raise ValueError(
                    f"fit_scan only supports SGD, but vertex {name!r} is "
                    f"configured with {algo}; use fit()")
        self.init()
        if not isinstance(inputs_stacked, dict):
            inputs_stacked = {
                self.conf.network_inputs[0]: inputs_stacked}
        if not isinstance(labels_stacked, (list, tuple)):
            labels_stacked = [labels_stacked]
        if set(inputs_stacked) != set(self.conf.network_inputs):
            raise ValueError(
                f"fit_scan got inputs {sorted(inputs_stacked)} but graph "
                f"has inputs {sorted(self.conf.network_inputs)}")
        if len(labels_stacked) != len(self.conf.network_outputs):
            raise ValueError(
                f"fit_scan got {len(labels_stacked)} label arrays but "
                f"graph has {len(self.conf.network_outputs)} outputs")
        inputs_k = {k: jnp.asarray(v, self._dtype)
                    for k, v in inputs_stacked.items()}
        labels_k = [jnp.asarray(y, self._dtype) for y in labels_stacked]
        if masks_stacked is not None and not isinstance(masks_stacked, dict):
            masks_stacked = {self.conf.network_inputs[0]: masks_stacked}
        if (label_masks_stacked is not None
                and not isinstance(label_masks_stacked, dict)):
            label_masks_stacked = {
                self.conf.network_outputs[0]: label_masks_stacked}
        # Mask keys are looked up with .get() downstream, so a mistyped
        # name would silently train unmasked — validate here.
        if masks_stacked is not None:
            bad = set(masks_stacked) - set(self.conf.network_inputs)
            if bad:
                raise ValueError(
                    f"masks_stacked has keys {sorted(bad)} that are not "
                    f"network inputs {sorted(self.conf.network_inputs)}")
        if label_masks_stacked is not None:
            bad = set(label_masks_stacked) - set(self.conf.network_outputs)
            if bad:
                raise ValueError(
                    f"label_masks_stacked has keys {sorted(bad)} that "
                    f"are not network outputs "
                    f"{sorted(self.conf.network_outputs)}")
        masks_k = {k: jnp.asarray(v)
                   for k, v in (masks_stacked or {}).items()}
        lmasks_k = {k: jnp.asarray(v)
                    for k, v in (label_masks_stacked or {}).items()}
        self._key, sub = jax.random.split(self._key)
        start = self.iteration
        t0 = time.perf_counter()
        self.params, self.state, self.updater_state, scores, health = (
            self._train_steps_scan(
                self.params, self.state, self.updater_state,
                self.iteration, sub, inputs_k, labels_k,
                masks_k, lmasks_k, grad_scale))
        k, examples, tokens = window_counts(
            next(iter(inputs_k.values())).shape)
        self.train_telemetry.record_step(
            dispatch_s=time.perf_counter() - t0, steps=k,
            examples=examples, tokens=tokens, health=health)
        self.iteration += k
        self.score_value = scores[-1]
        from deeplearning4j_tpu.optimize.listeners import fire_crossed

        fire_crossed(self.listeners, self, start, self.iteration)
        return scores

    @functools.cached_property
    def _output_fn(self):
        def out(params, state, inputs):
            acts, _, _ = self._forward_fn(params, state, inputs, None, False)
            return [acts[name] for name in self.conf.network_outputs]

        return jax.jit(out)

    # ------------------------------------------------------------------
    def _coerce_multi(self, data) -> Tuple[Dict[str, Array], List[Array], Optional[Dict], Optional[Dict]]:
        """Accept DataSet (single in/out), MultiDataSet, or
        (features-list, labels-list) tuples."""
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

        if isinstance(data, MultiDataSet):
            if len(data.features) != len(self.conf.network_inputs):
                raise ValueError(
                    f"MultiDataSet has {len(data.features)} feature "
                    f"arrays but graph has "
                    f"{len(self.conf.network_inputs)} inputs"
                )
            if len(data.labels) != len(self.conf.network_outputs):
                raise ValueError(
                    f"MultiDataSet has {len(data.labels)} label arrays "
                    f"but graph has {len(self.conf.network_outputs)} "
                    f"outputs"
                )
            inputs = {
                n: jnp.asarray(f, self._dtype)
                for n, f in zip(self.conf.network_inputs, data.features)
            }
            labels = [jnp.asarray(y, self._dtype) for y in data.labels]
            masks = None
            if data.features_masks is not None:
                masks = {
                    n: jnp.asarray(m)
                    for n, m in zip(
                        self.conf.network_inputs, data.features_masks
                    )
                    if m is not None
                } or None
            lmasks = None
            if data.labels_masks is not None:
                lmasks = {
                    n: jnp.asarray(m)
                    for n, m in zip(
                        self.conf.network_outputs, data.labels_masks
                    )
                    if m is not None
                } or None
            return inputs, labels, masks, lmasks
        if isinstance(data, DataSet):
            inputs = {
                self.conf.network_inputs[0]: jnp.asarray(
                    data.features, self._dtype
                )
            }
            labels = [jnp.asarray(data.labels, self._dtype)]
            masks = (
                None
                if data.features_mask is None
                else {
                    self.conf.network_inputs[0]: jnp.asarray(data.features_mask)
                }
            )
            lmasks = (
                None
                if data.labels_mask is None
                else {
                    self.conf.network_outputs[0]: jnp.asarray(data.labels_mask)
                }
            )
            return inputs, labels, masks, lmasks
        features, labels = data  # (list-of-arrays, list-of-arrays)
        inputs = {
            n: jnp.asarray(f, self._dtype)
            for n, f in zip(self.conf.network_inputs, features)
        }
        return inputs, [jnp.asarray(y, self._dtype) for y in labels], None, None

    def _host_multi(self, data):
        """Host-side sibling of ``_coerce_multi``: same name mapping,
        NO device transfer or dtype cast — the windowing/stacking path
        must keep batches in their minimal wire format (u8 pixels,
        int token ids) until the one per-window upload."""
        import numpy as _np

        from deeplearning4j_tpu.datasets.dataset import (
            DataSet,
            MultiDataSet,
        )

        def name_masks(names, masks):
            if masks is None:
                return None
            return {n: _np.asarray(m)
                    for n, m in zip(names, masks)
                    if m is not None} or None

        if isinstance(data, MultiDataSet):
            if len(data.features) != len(self.conf.network_inputs):
                raise ValueError(
                    f"MultiDataSet has {len(data.features)} feature "
                    f"arrays but graph has "
                    f"{len(self.conf.network_inputs)} inputs")
            if len(data.labels) != len(self.conf.network_outputs):
                raise ValueError(
                    f"MultiDataSet has {len(data.labels)} label arrays "
                    f"but graph has {len(self.conf.network_outputs)} "
                    f"outputs")
            inputs = {n: _np.asarray(f) for n, f in zip(
                self.conf.network_inputs, data.features)}
            labels = [_np.asarray(y) for y in data.labels]
            return (inputs, labels,
                    name_masks(self.conf.network_inputs,
                               data.features_masks),
                    name_masks(self.conf.network_outputs,
                               data.labels_masks))
        if isinstance(data, DataSet):
            fm = (None if data.features_mask is None else
                  {self.conf.network_inputs[0]:
                   _np.asarray(data.features_mask)})
            lm = (None if data.labels_mask is None else
                  {self.conf.network_outputs[0]:
                   _np.asarray(data.labels_mask)})
            return ({self.conf.network_inputs[0]:
                     _np.asarray(data.features)},
                    [_np.asarray(data.labels)], fm, lm)
        feats, labels = data
        return ({n: _np.asarray(f) for n, f in zip(
                    self.conf.network_inputs, feats)},
                [_np.asarray(y) for y in labels], None, None)

    def fit_stream(self, iterator, scan_steps: int = 16,
                   ingest=None, ingest_labels=None,
                   sync_each_window: bool = False):
        """Host-fed graph training: the ComputationGraph counterpart of
        ``MultiLayerNetwork.fit_stream`` (see its docstring for the
        windowing/transport rationale; reference AsyncDataSetIterator,
        datasets/iterator/AsyncDataSetIterator.java:1). Consumes
        DataSet/MultiDataSet batches from the iterator, stacks
        ``scan_steps`` of them into [K, B, ...] pytrees host-side (wire
        format preserved until the one per-window upload), and trains
        each window in ONE fused ``fit_scan`` dispatch. ``ingest`` /
        ``ingest_labels`` receive the stacked input DICT / label LIST
        — and also apply on ragged tails (stacked [1, B, ...], then
        trained per-batch via ``fit``). Returns the last window's score
        array."""
        import numpy as _np

        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.nn.streaming_fit import (
            drive_stream_windows,
        )

        self.init()
        scores = None
        in_names = self.conf.network_inputs

        def stack_masks(masks_per_batch, what):
            if all(m is None for m in masks_per_batch):
                return None
            if any(m is None for m in masks_per_batch):
                raise ValueError(
                    f"fit_stream window mixes batches with and "
                    f"without {what}")
            names = set(masks_per_batch[0])
            if any(set(m) != names for m in masks_per_batch):
                raise ValueError(
                    f"fit_stream window mixes {what} name sets")
            return {k: _np.stack([m[k] for m in masks_per_batch])
                    for k in names}

        def stacked(coerced):
            inputs = {
                k: _np.stack([c[0][k] for c in coerced])
                for k in coerced[0][0]
            }
            labels = [
                _np.stack([c[1][i] for c in coerced])
                for i in range(len(coerced[0][1]))
            ]
            fm = stack_masks([c[2] for c in coerced], "feature masks")
            lm = stack_masks([c[3] for c in coerced], "label masks")
            return inputs, labels, fm, lm

        def transform(inputs, labels):
            inputs = {k: jax.device_put(v) for k, v in inputs.items()}
            labels = [jax.device_put(y) for y in labels]
            if sync_each_window:
                # materialize uploads BEFORE dispatching compute (see
                # MultiLayerNetwork.fit_stream transport note)
                for leaf in jax.tree.leaves((inputs, labels)):
                    leaf.block_until_ready()
            if ingest is not None:
                inputs = ingest(inputs)
            if ingest_labels is not None:
                labels = ingest_labels(labels)
            return inputs, labels

        def flush(window, fused):
            nonlocal scores
            if fused:
                inputs, labels, fm, lm = stacked(
                    [self._host_multi(b) for b in window])
                inputs, labels = transform(inputs, labels)
                scores = self.fit_scan(
                    inputs, labels, masks_stacked=fm,
                    label_masks_stacked=lm)
                if sync_each_window:
                    _np.asarray(scores[-1])
                return
            for b in window:  # ragged: correctness over throughput
                inputs, labels, fm, lm = stacked([self._host_multi(b)])
                inputs, labels = transform(inputs, labels)
                self._fit_one(MultiDataSet(
                    [_np.asarray(inputs[n])[0] for n in in_names],
                    [_np.asarray(y)[0] for y in labels],
                    None if fm is None else
                    [fm.get(n, [None])[0] for n in in_names],
                    None if lm is None else
                    [lm.get(n, [None])[0]
                     for n in self.conf.network_outputs]))
            scores = jnp.asarray([self.score_value])

        def batch_shape(ds):
            # full signature: label shapes too — identical features
            # with variable-length labels must also break a window
            inputs, labels, _, _ = self._host_multi(ds)
            return ({k: _np.shape(v) for k, v in inputs.items()},
                    tuple(_np.shape(y) for y in labels))

        drive_stream_windows(iterator, scan_steps, flush, batch_shape,
                             telemetry=self.train_telemetry)
        return scores

    def fit(self, data, labels=None) -> None:
        self.init()
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterator import DataSetIterator

        if labels is not None:
            data = DataSet(data, labels)
        if isinstance(data, DataSetIterator):
            if self.conf.pretrain:
                self.pretrain(data)
                data.reset()
            if not self.conf.backprop:
                return
            it = iter(data)
            while True:
                t0 = time.perf_counter()
                ds = next(it, None)
                self.train_telemetry.add_data_wait(
                    time.perf_counter() - t0)
                if ds is None:
                    break
                self._fit_one(ds)
        else:
            self._fit_one(data)

    def _fit_one(self, data) -> None:
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            self._fit_tbptt(data)
            return
        first_conf = next(iter(self._layer_vertices.values())).conf
        if (first_conf.optimization_algo
                != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
            from deeplearning4j_tpu.optimize.solver import Solver

            Solver(self).optimize(data)
            return
        inputs, labels, masks, lmasks = self._coerce_multi(data)
        n_iter = max(1, first_conf.num_iterations)
        examples, tokens = batch_counts(next(iter(inputs.values())))
        for _ in range(n_iter):
            self._key, sub = jax.random.split(self._key)
            t0 = time.perf_counter()
            (
                self.params,
                self.state,
                self.updater_state,
                score,
                health,
            ) = self._train_step(
                self.params, self.state, self.updater_state,
                self.iteration, sub, inputs, labels, masks, lmasks,
            )
            self.train_telemetry.record_step(
                dispatch_s=time.perf_counter() - t0, examples=examples,
                tokens=tokens, health=health)
            self.score_value = score
            self.iteration += 1
            for listener in self.listeners:
                if listener.invoked_every <= 1 or (
                    self.iteration % listener.invoked_every == 0
                ):
                    listener.iteration_done(self, self.iteration)

    # ------------------------------------------------------------------
    # Truncated BPTT (reference ComputationGraph.doTruncatedBPTT :1349):
    # chop the time axis into fwd-length windows, carry per-vertex
    # recurrent state (stop-gradient) across windows. Non-temporal (2-D)
    # inputs are fed whole into every window, as the reference does.
    # ------------------------------------------------------------------
    def _fit_tbptt(self, data) -> None:
        inputs, labels, masks, lmasks = self._coerce_multi(data)
        length = self.conf.tbptt_fwd_length
        temporal = [v.shape[2] for v in list(inputs.values()) + labels
                    if v.ndim == 3]
        if not temporal:
            raise ValueError(
                "truncated BPTT requires at least one [B, C, T] input or "
                "label")
        t_total = max(temporal)
        rnn_state: Dict[str, Any] = {}
        for start in range(0, t_total, length):
            end = min(start + length, t_total)
            iw = {k: (v[:, :, start:end] if v.ndim == 3 else v)
                  for k, v in inputs.items()}
            lw = [y[:, :, start:end] if y.ndim == 3 else y for y in labels]
            mw = (None if masks is None
                  else {k: m[:, start:end] for k, m in masks.items()})
            lmw = (None if lmasks is None
                   else {k: m[:, start:end] for k, m in lmasks.items()})
            self._key, sub = jax.random.split(self._key)
            t0 = time.perf_counter()
            (self.params, self.state, self.updater_state, rnn_state,
             score, health) = self._tbptt_step(
                self.params, self.state, self.updater_state,
                self.iteration, sub, iw, lw, mw, lmw, rnn_state)
            first_in = next(iter(iw.values()))
            self.train_telemetry.record_step(
                dispatch_s=time.perf_counter() - t0,
                examples=int(first_in.shape[0]),
                tokens=int(first_in.shape[0]) * (end - start),
                health=health)
            self.score_value = score
            self.iteration += 1
            for listener in self.listeners:
                if listener.invoked_every <= 1 or (
                    self.iteration % listener.invoked_every == 0
                ):
                    listener.iteration_done(self, self.iteration)

    @functools.cached_property
    def _tbptt_step(self):
        def step(params, state, upd_state, iteration, rng, inputs, labels,
                 masks, lmasks, rnn_state):
            (score, (new_state, new_rnn)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True
            )(params, state, rng, inputs, labels, masks, lmasks, rnn_state)
            new_params, new_upd = self._apply_updates(
                params, upd_state, grads, iteration)
            new_rnn = jax.lax.stop_gradient(new_rnn)
            health = grad_health(grads, params, new_params)
            return new_params, new_state, new_upd, new_rnn, score, health

        return jax.jit(step)

    # ------------------------------------------------------------------
    # RNN streaming inference (reference ComputationGraph.rnnTimeStep
    # :1196): stateful step-by-step forward carrying hidden state between
    # calls; 2-D inputs are treated as one time step and the output is
    # squeezed back to 2-D, matching the reference's shape contract.
    # ------------------------------------------------------------------
    def rnn_time_step(self, *features) -> List[Array]:
        self.init()
        from deeplearning4j_tpu.nn.layers.attention import (
            guard_streamable,
        )

        guard_streamable(
            (name, lv.conf.layer)
            for name, lv in self._layer_vertices.items())
        # Direct consumers of each network input: a 2-D input consumed by
        # recurrent layers is ONE time step (expand to [B, C, 1], as the
        # reference's BaseRecurrentLayer.rnnTimeStep does internally); a
        # 2-D input consumed by non-recurrent vertices (Dense,
        # DuplicateToTimeSeries) is static and keeps its rank.
        consumers: Dict[str, List[str]] = {}
        for vname, in_names in self.conf.vertex_inputs.items():
            for inp in in_names:
                consumers.setdefault(inp, []).append(vname)
        inputs = {}
        ranks = []
        for n, f in zip(self.conf.network_inputs, features):
            x = jnp.asarray(f, self._dtype)
            ranks.append(x.ndim)
            if x.ndim == 2:
                cons = consumers.get(n, [])
                rec = [c for c in cons
                       if isinstance(self.conf.vertices[c], LayerVertex)
                       and isinstance(self.conf.vertices[c].conf.layer,
                                      L.RECURRENT_LAYER_TYPES)]
                if rec and len(rec) == len(cons):
                    x = x[:, :, None]
                elif rec:
                    raise ValueError(
                        f"Input {n!r} feeds both recurrent ({rec}) and "
                        f"non-recurrent vertices; pass it as 3-D "
                        f"[B, C, 1] to disambiguate one-time-step intent")
            inputs[n] = x
        # squeeze outputs back to 2-D only when ALL inputs were 2-D
        # (mixed-rank calls keep the full time axis — a 3-D input's
        # T-step output must not be truncated to step 0)
        squeeze = bool(ranks) and all(r == 2 for r in ranks)
        acts, _, new_rnn = self._rnn_step_jit(
            self.params, self.state, inputs, self._rnn_state)
        self._rnn_state = new_rnn
        outs = [acts[name] for name in self.conf.network_outputs]
        if squeeze:
            outs = [o[:, :, 0] if o.ndim == 3 else o for o in outs]
        return outs

    @functools.cached_property
    def _rnn_step_jit(self):
        # One jitted computation per streaming step instead of one host
        # dispatch per XLA op (mirrors MultiLayerNetwork._rnn_step_jit).
        def f(params, state, inputs, rnn_state):
            return self._forward_fn(
                params, state, inputs, None, False,
                rnn_state=rnn_state or None,
            )

        return jax.jit(f)

    def rnn_clear_previous_state(self, slots=None) -> None:
        """Reset streaming state (reference rnnClearPreviousState).
        ``slots=[...]`` zeroes only those batch rows across every
        vertex's carried state — the per-slot eviction hook shared
        with MultiLayerNetwork (nn/streaming.py)."""
        from deeplearning4j_tpu.nn.streaming import reset_streaming_state

        self._rnn_state = reset_streaming_state(self._rnn_state, slots)

    def lm_shape(self):
        """(input name, output name, vocab) for an LM-shaped graph:
        single input, single output, first-layer n_in == output n_out.
        Shared by ``generate`` and ``serving.DecodeEngine``; raises
        ValueError for any other topology."""
        if (len(self.conf.network_inputs) != 1
                or len(self.conf.network_outputs) != 1):
            raise ValueError(
                "requires a single-input/single-output LM-shaped graph")
        in_name = self.conf.network_inputs[0]
        out_name = self.conf.network_outputs[0]
        first = None
        for vname, ins in self.conf.vertex_inputs.items():
            if in_name in ins and vname in self._layer_vertices:
                first = self._layer_vertices[vname]
                break
        vocab = getattr(first.conf.layer, "n_in", None) if first else None
        out_bean = self._layer_vertices[out_name].conf.layer
        if vocab is None or vocab != getattr(out_bean, "n_out", None):
            raise ValueError(
                "LM-shaped graph requires input n_in == output n_out "
                f"(got {vocab} vs {getattr(out_bean, 'n_out', None)})")
        return in_name, out_name, vocab

    def generate(self, prompt, n_tokens: int):
        """Greedy autoregressive generation fused on device — the
        ComputationGraph counterpart of
        ``MultiLayerNetwork.generate`` (see its docstring): prefill
        the one-hot prompt [B, V, Tp] through ``rnn_time_step``, then
        ONE jitted ``lax.scan`` emits ``n_tokens`` ids with the
        per-vertex streaming state in the scan carry.

        Requires an LM-shaped single-input/single-output graph
        (input n_in == output n_out). Returns int32 ids
        [B, n_tokens]."""
        if n_tokens < 1:
            raise ValueError(f"n_tokens {n_tokens} < 1")
        self.init()
        in_name, _, vocab = self.lm_shape()
        out = self.rnn_time_step(prompt)[0]
        tok0 = jnp.argmax(out[:, :, -1], axis=1).astype(jnp.int32)
        if n_tokens == 1:
            return tok0[:, None]
        # Scan length bucketed to pow2 with the true length traced —
        # bounded compile count under varied request lengths, same ids
        # and final state (mirrors MultiLayerNetwork.generate).
        from deeplearning4j_tpu.nn.streaming import (
            make_bucketed_generate,
            scan_length_bucket,
        )

        n_rem = n_tokens - 1
        bucket = scan_length_bucket(n_rem)
        gen = self._generate_fns.get(bucket)
        if gen is None:
            def step(params, state, x, rnn):
                acts, _, new_rnn = self._forward_fn(
                    params, state, {in_name: x}, None, False,
                    rnn_state=rnn)
                return acts[self.conf.network_outputs[0]], new_rnn

            gen = self._generate_fns[bucket] = make_bucketed_generate(
                step, vocab, self._dtype, bucket)
        toks, self._rnn_state = gen(
            self.params, self.state, self._rnn_state, tok0,
            jnp.asarray(n_rem, jnp.int32))
        return jnp.concatenate([tok0[:, None], toks[:, :n_rem]], axis=1)

    # ------------------------------------------------------------------
    # Greedy layer-wise pretraining (reference ComputationGraph.pretrain
    # :341-427): for each pretrainable layer vertex in topological order,
    # feed each batch forward (inference mode) to the vertex's input,
    # then run that vertex's unsupervised update (RBM CD-k / AE).
    # ------------------------------------------------------------------
    def pretrain(self, data_iter) -> None:
        self.init()
        from deeplearning4j_tpu.optimize.pretrainer import pretrain_graph

        pretrain_graph(self, data_iter)

    def _pretrain_input(self, name: str, ds) -> Array:
        """Activations feeding vertex ``name`` (inference mode), with the
        vertex's own preprocessor applied — the graph analog of
        MultiLayerNetwork's activationFromPrevLayer. The partial forward
        stops at the feeding vertex (downstream vertices are not traced)
        and is jitted, cached per feeding vertex."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if isinstance(ds, DataSet) and ds.labels is None:
            # feature-only data — the normal input to unsupervised
            # pretraining; _coerce_multi would choke on labels=None
            inputs = {self.conf.network_inputs[0]: jnp.asarray(
                ds.features, self._dtype)}
            masks = (None if ds.features_mask is None else {
                self.conf.network_inputs[0]: jnp.asarray(ds.features_mask)})
        else:
            inputs, _, masks, _ = self._coerce_multi(ds)
        vertex = self.conf.vertices[name]
        in_name = self.conf.vertex_inputs[name][0]
        if in_name in inputs:
            x = inputs[in_name]
        else:
            cache = getattr(self, "_pretrain_fwd_cache", None)
            if cache is None:
                cache = self._pretrain_fwd_cache = {}
            fn = cache.get(in_name)
            if fn is None:
                def fwd(params, state, inputs, masks, _n=in_name):
                    acts, _, _ = self._forward_fn(
                        params, state, inputs, None, False, masks,
                        stop_at=_n)
                    return acts[_n]

                fn = cache[in_name] = jax.jit(fwd)
            x = fn(self.params, self.state, inputs, masks)
        if vertex.preprocessor is not None:
            x = vertex.preprocessor.pre_process(x)
        return x

    # ------------------------------------------------------------------
    def output(self, *features) -> List[Array]:
        self.init()
        inputs = {
            n: jnp.asarray(f, self._dtype)
            for n, f in zip(self.conf.network_inputs, features)
        }
        return self._output_fn(self.params, self.state, inputs)

    def feed_forward(self, *features) -> Dict[str, Array]:
        self.init()
        inputs = {
            n: jnp.asarray(f, self._dtype)
            for n, f in zip(self.conf.network_inputs, features)
        }
        acts, _, _ = self._forward_fn(
            self.params, self.state, inputs, None, False)
        return acts

    def score(self, data=None) -> float:
        if data is None:
            return float(self.score_value)
        self.init()
        inputs, labels, masks, lmasks = self._coerce_multi(data)
        s, _ = self._loss_fn(
            self.params, self.state, None, inputs, labels, masks, lmasks
        )
        return float(s)

    def compute_gradient_and_score(self, data) -> Tuple[float, Gradient]:
        self.init()
        inputs, labels, masks, lmasks = self._coerce_multi(data)
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self.params, self.state, None, inputs, labels, masks, lmasks
        )
        flat = {}
        for name in sorted(grads):
            for pname, g in grads[name].items():
                flat[f"{name}_{pname}"] = g
        return float(score), Gradient(flat)

    def evaluate(self, data_iter):
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        self.init()
        ev = Evaluation()
        for ds in data_iter:
            out = self.output(ds.features)[0]
            if np.asarray(ds.labels).ndim == 3:
                ev.eval_time_series(ds.labels, out, ds.labels_mask)
            else:
                ev.eval(ds.labels, out)
        return ev

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    # ------------------------------------------------------------------
    def params_flat(self) -> Array:
        flat, _ = ravel_pytree(self.params)
        return flat

    def num_params(self) -> int:
        return int(self.params_flat().shape[0])

    def clone(self) -> "ComputationGraph":
        """Deep-copy (buffers AND conf: the train step donates
        params/state, so aliased references would be deleted by the
        donor's next step; conf isolation matches
        MultiLayerNetwork.clone). Skips init() — its random params would
        be immediately overwritten."""
        copy = functools.partial(jax.tree.map, jnp.copy)
        net = ComputationGraph(self.conf.clone())
        net.params = copy(self.params)
        net.updater_state = copy(self.updater_state)
        net.state = copy(self.state)
        net.iteration = self.iteration
        net._initialized = True
        return net

    def save(self, path: str) -> None:
        """One-zip checkpoint (util/model_serializer format)."""
        from deeplearning4j_tpu.util.model_serializer import write_model

        write_model(self, path)

    @staticmethod
    def load(path: str) -> "ComputationGraph":
        from deeplearning4j_tpu.util.model_serializer import restore_model

        net = restore_model(path)
        if not isinstance(net, ComputationGraph):
            raise TypeError(f"{path} holds a {type(net).__name__}")
        return net


def _elementwise(op: ElementWiseOp, xs: Sequence[Array]) -> Array:
    if op == ElementWiseOp.ADD:
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out
    if op == ElementWiseOp.SUBTRACT:
        if len(xs) != 2:
            raise ValueError("SUBTRACT requires exactly 2 inputs")
        return xs[0] - xs[1]
    if op == ElementWiseOp.PRODUCT:
        out = xs[0]
        for x in xs[1:]:
            out = out * x
        return out
    if op == ElementWiseOp.AVERAGE:
        return sum(xs) / len(xs)
    if op == ElementWiseOp.MAX:
        out = xs[0]
        for x in xs[1:]:
            out = jnp.maximum(out, x)
        return out
    raise ValueError(f"Unknown elementwise op {op}")


def _last_time_step(x: Array, mask: Optional[Array]) -> Array:
    if mask is None:
        return x[:, :, -1]
    # Index of last nonzero mask entry per example.
    idx = (
        mask.shape[1]
        - 1
        - jnp.argmax(jnp.flip(mask, axis=1) > 0, axis=1)
    ).astype(jnp.int32)
    return jnp.take_along_axis(x, idx[:, None, None], axis=2)[:, :, 0]
