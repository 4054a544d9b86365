"""MultiLayerNetwork: the sequential-stack network.

Mirror of reference nn/multilayer/MultiLayerNetwork.java:67 (2,343 LoC):
init() :335, fit(DataSetIterator) :1130, feedForward :578-715, backprop
:1176, pretrain :150, doTruncatedBPTT :1262, params pack/unpack :984-1063.

TPU-native inversion (SURVEY.md §3.1 takeaway): where the reference runs
eager op-by-op INDArray dispatch with a JVM->JNI->BLAS crossing per op, here
the entire train step — forward, loss, backward (``jax.value_and_grad``),
gradient normalization, updater — is ONE jitted XLA computation, compiled
once per (shape, dtype) and cached. Backprop is never hand-written; the
per-parameter gradient map ("0_W", "1_b", ...) is recovered from the pytree
for updater/gradient-check parity.
"""

from __future__ import annotations

import contextlib
import functools
import io
import json
import os
import pickle
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.optimize.telemetry import (
    TrainTelemetry,
    batch_counts,
    grad_health,
    window_counts,
)
from deeplearning4j_tpu.profiler.scopes import scope
from deeplearning4j_tpu.profiler.tracer import annotate
from deeplearning4j_tpu.nn.conf.enums import BackpropType, OptimizationAlgorithm
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.gradient import Gradient
from deeplearning4j_tpu.nn.layers import get_impl
from deeplearning4j_tpu.nn.updater.updaters import (
    make_layer_updater,
    normalize_gradients,
    resolve_lr,
)

Array = jax.Array


_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16, "float64": jnp.float64}


def _dtype_of(name: str):
    if name not in _DTYPES:
        raise ValueError(
            f"unknown dtype {name!r} (dtype/compute_dtype accepts "
            f"{sorted(_DTYPES)})")
    return _DTYPES[name]


def _cast_floating(a, dtype):
    """Cast floating arrays, leave ints/bools (masks, indices) alone.
    An array already at ``dtype`` comes back as it is, not a copy."""
    if (hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
            and a.dtype != dtype):
        return a.astype(dtype)
    return a


def _carried_state(new, handed, master_dtype):
    """A layer's new state as a mixed-precision pass hands it on: a
    floating leaf the layer was HANDED (same place in ``handed``) goes
    out at the dtype it came in with, a leaf the pass created at the
    master dtype. What goes in comes out, so repeated steps see stable
    input dtypes and compile once, whatever dtype the holder of the
    state keeps it at."""
    came = (dict(jax.tree_util.tree_flatten_with_path(handed)[0])
            if handed is not None else {})

    def carry(path, leaf):
        was = getattr(came.get(path), "dtype", None)
        if was is None or not jnp.issubdtype(was, jnp.floating):
            was = master_dtype
        return _cast_floating(leaf, was)

    return jax.tree_util.tree_map_with_path(carry, new)


def _resolve_compute_dtype(master_dtype, compute_dtype_name):
    """Mixed-precision compute dtype, or None when it matches master."""
    if not compute_dtype_name:
        return None
    cd = _dtype_of(compute_dtype_name)
    return cd if cd != master_dtype else None


_REGULARIZED_KEYS = ("W", "RW", "W_bwd", "RW_bwd")


def layer_reg_score(c, layer_params):
    """l1/l2 penalty of ONE layer's params — shared by the full-model
    ``_reg_score`` and PipelineTrainer's per-stage reg branches (a fix
    here must apply to both, or PP trajectories drift)."""
    if not c.use_regularization:
        return 0.0
    l1 = float(c.resolved("l1") or 0.0)
    l2 = float(c.resolved("l2") or 0.0)
    if l1 == 0.0 and l2 == 0.0:
        return 0.0
    reg = 0.0
    for name, p in layer_params.items():
        if name not in _REGULARIZED_KEYS:
            continue
        if l1:
            reg = reg + l1 * jnp.sum(jnp.abs(p))
        if l2:
            reg = reg + 0.5 * l2 * jnp.sum(p * p)
    return reg


def layer_update(c, updater, grads, upd_state, iteration, grad_scale=1.0):
    """normalize -> scale -> updater rule for ONE layer; returns
    (updates, new_state) and the caller applies ``params -= updates``.
    Shared by ``_apply_updates`` and PipelineTrainer's per-stage update
    branches.

    grad_scale=1.0 normally; dp-size under ACCUM_GRADIENT-
    without-divide (reference DIVIDE_ACCUM_GRADIENT=false: sum of
    per-worker gradients = mean times worker count). Applied AFTER
    normalization. NOTE: this computes n*normalize(mean), which matches
    the reference's sum-of-per-worker-normalized gradients exactly for
    plain SGD and whenever normalization is inactive or uniform across
    workers; with per-worker clipping that differs between shards the
    reference's sum can diverge from this global form (a documented
    deviation — the global batch here is ONE gradient, not N)."""
    g = normalize_gradients(
        c.resolved("gradient_normalization"),
        grads,
        float(c.resolved("gradient_normalization_threshold")),
    )
    g = jax.tree.map(lambda a: a * grad_scale, g)
    lr = resolve_lr(c, iteration)
    return updater.update(g, upd_state, lr, iteration)


class MultiLayerNetwork:
    """Sequential network over layer conf beans.

    Also usable as a building block the way the reference's
    MultiLayerNetwork implements ``Layer`` (nn/api/Layer.java nesting).
    """

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params: Dict[str, Dict[str, Array]] = {}
        self.state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.iteration = 0
        self.score_value = float("nan")
        self.listeners: List = []
        # Host-side per-step phase clock (data-wait/dispatch walls,
        # throughput counts, latest gradient-health outputs) — stamped
        # by every fit path, drained by TracingIterationListener.
        self.train_telemetry = TrainTelemetry()
        self._impls = [get_impl(c.layer) for c in conf.confs]
        self._updaters = [make_layer_updater(c) for c in conf.confs]
        self._rnn_state: Dict[str, Any] = {}
        self._generate_fns: Dict[int, Any] = {}
        self._initialized = False
        # Bumped by in-place param mutation APIs (set_param) so caches
        # that mirror params (e.g. PipelineTrainer's stage-sharded
        # buffers) can detect staleness without deep comparison.
        self.params_version = 0
        self._dtype = _dtype_of(conf.dtype)
        self._compute_dtype = _resolve_compute_dtype(
            self._dtype, conf.compute_dtype)
        self._key = jax.random.key(conf.seed)

    # ------------------------------------------------------------------
    # Initialization (reference init() :335-370)
    # ------------------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        if self._initialized:
            return self
        key = jax.random.key(self.conf.seed)
        n = len(self.conf.confs)
        keys = jax.random.split(key, n)
        for i, (c, impl) in enumerate(zip(self.conf.confs, self._impls)):
            self.params[str(i)] = impl.init(keys[i], c, self._dtype)
            st = impl.init_state(c, self._dtype)
            if st is not None:
                self.state[str(i)] = st
        for i, upd in enumerate(self._updaters):
            self.updater_state[str(i)] = upd.init(self.params[str(i)])
        self._initialized = True
        return self

    @property
    def n_layers(self) -> int:
        return len(self.conf.confs)

    @property
    def _out_at_master_dtype(self) -> bool:
        # The OUTPUT layer always runs at the master dtype: a bf16
        # softmax quantizes probabilities coarsely enough to stall
        # training at a calibration plateau (measured on LeNet/MNIST:
        # bf16-everywhere pins at 0.905 accuracy / 1.76 loss while f32
        # head converges to ~1.0; the conv/dense bulk keeps the MXU
        # bf16 rate). Casting AFTER the softmax (the loss-side cast
        # in ``_loss_fn``) is too late — the quantization already
        # happened.
        return (self._compute_dtype is not None
                and isinstance(self.conf.confs[-1].layer,
                               L.BaseOutputLayer))

    def compute_params(self, params):
        """``params`` as the forward pass computes with them: under
        mixed precision every floating leaf at the compute dtype,
        except the output layer's, which stay at the master dtype. The
        one place that rule lives: ``_forward_fn`` applies it to what
        it is handed, and a holder of resident weights (the serving
        engine) applies it once. A leaf already at its target dtype
        comes back as the same array, so a tree that went through here
        goes through again as itself."""
        cd = self._compute_dtype
        if cd is None:
            return params
        cast = functools.partial(_cast_floating, dtype=cd)
        keep = ({str(self.n_layers - 1)} if self._out_at_master_dtype
                else ())
        return {
            si: (sub if si in keep else jax.tree_util.tree_map(cast, sub))
            for si, sub in params.items()
        }

    # ------------------------------------------------------------------
    # Pure functional forward (traced under jit)
    # ------------------------------------------------------------------
    def _forward_fn(
        self,
        params,
        state,
        x,
        rng,
        train: bool,
        feature_mask=None,
        rnn_state=None,
        collect: bool = False,
        head_at=None,
        live=None,
        counters=None,
        logits: bool = False,
    ):
        """Returns (final_or_all_activations, new_state, new_rnn_state).

        ``head_at`` ``[N]`` (serving prefill): the LAST layer sees only
        that position of each row, so a head over a wide vocabulary
        runs once a row and not once a position. ``live`` ``[N]`` (a
        caller that batches slots): which rows exist; ``counters``: a
        dict that layers add what they counted in this pass into, by
        name. Both go to the layers whose bean has ``wants_live``,
        training or not (under a gradient the caller hands the dict
        back as part of the loss's auxiliary output). ``logits``: the
        LAST layer stops at its ``logits`` ``[N, T, V]`` float32 (a
        head scored on label ids, ``_loss_fn``) and makes no
        probabilities.

        Under mixed precision a state leaf a layer was handed leaves
        the pass at the dtype it came in with, and a leaf the pass
        created (no incoming state) at the master dtype
        (``_carried_state``)."""
        cd = self._compute_dtype
        out_f32 = self._out_at_master_dtype
        last_si = str(self.n_layers - 1)
        # Mixed precision: compute in cd (bf16 on the MXU), master
        # params stay f32 — the cast's transpose accumulates grads
        # back in f32.
        with scope("cast"):
            params = self.compute_params(params)
            if cd is not None:
                x = _cast_floating(x, cd)
        acts = []
        new_state = dict(state) if state else {}
        new_rnn = {}
        rngs = (
            jax.random.split(rng, self.n_layers)
            if rng is not None
            else [None] * self.n_layers
        )
        for i, (c, impl) in enumerate(zip(self.conf.confs, self._impls)):
            si = str(i)
            pp = self.conf.preprocessor_for(i)
            if pp is not None:
                with scope("embed"):
                    x = pp.pre_process(x, rngs[i] if train else None)
            layer_state = None
            if state and si in state:
                layer_state = state[si]
            elif rnn_state and si in rnn_state:
                layer_state = rnn_state[si]
            is_recurrent = isinstance(c.layer, L.RECURRENT_LAYER_TYPES)
            mask = feature_mask if is_recurrent else None

            if logits and si == last_si:
                def _apply(p, xin, lst, lrng, lmask, _c=c, _impl=impl):
                    return _impl.logits(_c, p, xin), None, {}
            else:
                def _apply(p, xin, lst, lrng, lmask, _c=c, _impl=impl,
                           _wants=getattr(c.layer, "wants_live", False)):
                    # what the layer counts comes back as an OUTPUT (a
                    # dict filled from outside would leak a tracer out
                    # of a recomputed layer) and is added up below
                    counted = {}
                    rows = ({"live": live, "counters": counted}
                            if _wants else {})
                    out, lst = _impl.apply(
                        _c, p, xin, state=lst, train=train, rng=lrng,
                        mask=lmask, **rows,
                    )
                    return out, lst, counted

            if self.conf.remat:
                _apply = jax.checkpoint(_apply)
            if out_f32 and si == last_si:
                with scope("cast"):
                    x = _cast_floating(x, self._dtype)
            layer_params = params[si]
            tie = getattr(c.layer, "tie_to", None)
            if tie is not None:
                # a tied head reads the embedding's rows as its own
                layer_params = dict(layer_params,
                                    E=params[str(tie)]["W"])
            if head_at is not None and si == last_si:
                with scope("head/logits"):
                    x = jnp.take_along_axis(
                        x, head_at.astype(jnp.int32)[:, None, None],
                        axis=2)
                mask = None
            # a layer that names nothing inside itself gets its bean's
            # group here; one that does (``scope_group`` None) is
            # called bare, as the flash program under it must be
            group = c.layer.scope_group
            with (scope(group) if group else contextlib.nullcontext()):
                x, st, counted = _apply(
                    layer_params, x, layer_state,
                    rngs[i] if train else None, mask,
                )
            if counters is not None:
                for name, value in counted.items():
                    counters[name] = counters.get(name, 0) + value
            if st is not None:
                if cd is not None:
                    # carried state goes out at the dtype it came in
                    # with (created here: the master dtype), so
                    # repeated steps see stable input dtypes (no
                    # recompiles)
                    with scope("cast"):
                        st = _carried_state(st, layer_state,
                                            self._dtype)
                if state and si in state:
                    new_state[si] = st
                else:
                    new_rnn[si] = st
            if collect:
                acts.append(x)
        return (acts if collect else x), new_state, new_rnn

    def _loss_fn(
        self, params, state, rng, features, labels, feature_mask, label_mask,
        counters=None,
    ):
        """The training score and the layers' new state. A head whose
        bean has ``takes_label_ids`` is scored on its float32 logits
        against labels that are class ids; every other output layer on
        its activations against labels of their shape. ``counters``: a
        dict the layers add this pass's counts into (``_step_body``)."""
        out, new_state, _ = self._forward_fn(
            params, state, features, rng, True, feature_mask,
            counters=counters, logits=self.takes_label_ids,
        )
        out_conf = self.conf.confs[-1]
        impl = self._impls[-1]
        if not hasattr(impl, "loss"):
            raise ValueError(
                "Last layer must be an output layer to compute a score"
            )
        if self._compute_dtype is not None:
            with scope("cast"):
                out = _cast_floating(out, dtype=self._dtype)  # f32 loss
        with scope("head/loss"):
            score = impl.loss(out_conf, out, labels, label_mask)
            score = score + self._reg_score(params)
            score = score + self._aux_score(new_state)
        return score, new_state

    def _reg_score(self, params):
        reg = 0.0
        for i, c in enumerate(self.conf.confs):
            reg = reg + layer_reg_score(c, params[str(i)])
        return reg

    def _aux_score(self, new_state):
        """Auxiliary training losses layers emit through the state
        channel (MoeDense load-balancing loss), gate-weighted per conf."""
        aux = 0.0
        for i, c in enumerate(self.conf.confs):
            w = getattr(c.layer, "aux_weight", None)
            st = new_state.get(str(i)) if new_state else None
            if w and st and "aux_loss" in st:
                aux = aux + w * st["aux_loss"]
        return aux

    # ------------------------------------------------------------------
    # The jitted train step (whole §3.1 stack as one XLA computation)
    # ------------------------------------------------------------------
    @scope("update/step")
    def _apply_updates(self, params, upd_state, grads, iteration,
                       grad_scale=1.0):
        """Per-layer normalize → scale → updater → subtract (shared by
        the standard and tBPTT steps)."""
        new_params = {}
        new_upd = {}
        for i, (c, upd) in enumerate(zip(self.conf.confs, self._updaters)):
            si = str(i)
            updates, new_upd[si] = layer_update(
                c, upd, grads[si], upd_state[si], iteration, grad_scale)
            new_params[si] = jax.tree.map(
                lambda p, u: p - u, params[si], updates
            )
            # a leaf the layer's impl names is no updater's to move,
            # whatever the rule (weight decay, a gradient that is not
            # exactly 0): it stays the array it was
            frozen = getattr(self._impls[i], "frozen_leaves", None)
            for name in frozen(c.layer) if frozen else ():
                if name in params[si]:
                    new_params[si][name] = params[si][name]
        return new_params, new_upd

    def _step_body(self, params, state, upd_state, iteration, rng, features,
                   labels, feature_mask, label_mask, grad_scale=1.0):
        def loss(p):
            counted = {}
            score, new_state = self._loss_fn(
                p, state, rng, features, labels, feature_mask,
                label_mask, counters=counted)
            return score, (new_state, counted)

        (score, (new_state, counted)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        new_params, new_upd = self._apply_updates(
            params, upd_state, grads, iteration, grad_scale)
        # Gradient-health scalars ride as extra outputs of THE SAME
        # executable whether a listener is attached or not: telemetry
        # on/off cannot change compile counts or the param trajectory
        # (ISSUE 8 invariant). Unfetched, they cost a few reduction ops.
        # What the layers counted in the forward pass (an expert
        # block's ``moe_*``) rides with them, under the layers' names.
        health = dict(grad_health(grads, params, new_params), **counted)
        return new_params, new_state, new_upd, score, health

    @functools.cached_property
    def _train_step(self):
        return jax.jit(self._step_body, donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _train_steps_scan(self):
        """K train steps as ONE XLA computation via ``lax.scan`` — one
        host dispatch per K batches instead of per batch. This is the
        dispatch-latency killer for small models: per-step launches
        otherwise dominate sub-millisecond step times."""

        def steps(params, state, upd_state, iteration, rng, feats, labels,
                  grad_scale=1.0):
            def body(carry, inp):
                p, s, u, it, key = carry
                key, sub = jax.random.split(key)
                f, y = inp
                p, s, u, score, health = self._step_body(
                    p, s, u, it, sub, f, y, None, None, grad_scale)
                return (p, s, u, it + 1, key), (score, health)

            (p, s, u, it, _), (scores, health) = jax.lax.scan(
                body, (params, state, upd_state, iteration, rng),
                (feats, labels))
            return p, s, u, scores, health

        return jax.jit(steps, donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _train_steps_scan_masked(self):
        """Masked variant of _train_steps_scan: the per-batch feature and
        label masks ride the scan as extra xs, so masked time-series
        training gets the same one-dispatch-per-K-batches fast path."""

        def steps(params, state, upd_state, iteration, rng, feats, labels,
                  fms, lms, grad_scale=1.0):
            def body(carry, inp):
                p, s, u, it, key = carry
                key, sub = jax.random.split(key)
                f, y, fm, lm = inp
                p, s, u, score, health = self._step_body(
                    p, s, u, it, sub, f, y, fm, lm, grad_scale)
                return (p, s, u, it + 1, key), (score, health)

            (p, s, u, it, _), (scores, health) = jax.lax.scan(
                body, (params, state, upd_state, iteration, rng),
                (feats, labels, fms, lms))
            return p, s, u, scores, health

        return jax.jit(steps, donate_argnums=(0, 1, 2))

    def fit_scan(self, features_stacked, labels_stacked,
                 features_mask_stacked=None, labels_mask_stacked=None,
                 grad_scale: float = 1.0):
        """Run one scanned pass over pre-stacked batches; returns the K
        per-step scores as a device array (convert with np.asarray to
        force a sync — kept lazy here so chained calls pipeline without
        a host round-trip each).

        Features are ``[K, B, ...]`` at the net's dtype, or ``[K, B, T]``
        token ids for a net whose first layer embeds them
        (``takes_token_ids``): ids stay whole numbers into the gather.
        Labels are ``[K, B, n_out]`` / ``[K, B, n_out, T]`` at the
        net's dtype, or ``[K, B, T]`` class ids for a head scored on
        ids (``takes_label_ids``: the loss is the log-softmax of the
        head's float32 logits gathered at the label, no one-hot).
        Optional masks ``[K, B, T]``. Every first-order updater rule
        (SGD, Adam, ...) runs here; use fit() when tBPTT or a
        second-order solver is configured."""
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "fit_scan is the full-BPTT SGD fast path; truncated-BPTT "
                "configs must train via fit()")
        algo = self.conf.confs[0].optimization_algo
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            raise ValueError(
                f"fit_scan only supports SGD, not {algo}; use fit()")
        self.init()
        feats = self._as_input(features_stacked)
        labels = self._as_labels(labels_stacked)
        self._key, sub = jax.random.split(self._key)
        start = self.iteration
        if features_mask_stacked is not None or labels_mask_stacked is not None:
            # Synthesize the missing mask as all-ones so one masked
            # kernel covers every presence combination.
            fms = (jnp.asarray(features_mask_stacked)
                   if features_mask_stacked is not None
                   else jnp.ones(feats.shape[:2] + (feats.shape[-1],),
                                 self._dtype))
            lms = (jnp.asarray(labels_mask_stacked)
                   if labels_mask_stacked is not None
                   else jnp.ones(labels.shape[:2] + (labels.shape[-1],),
                                 self._dtype))
            step_fn = self._train_steps_scan_masked
            extra = (fms, lms)
        else:
            step_fn = self._train_steps_scan
            extra = ()
        t0 = time.perf_counter()
        with annotate("train.dispatch", step=self.iteration):
            (self.params, self.state, self.updater_state, scores,
             health) = step_fn(
                self.params, self.state, self.updater_state,
                self.iteration, sub, feats, labels, *extra, grad_scale)
        k, examples, tokens = window_counts(
            feats.shape, ids=self.takes_token_ids)
        self.train_telemetry.record_step(
            dispatch_s=time.perf_counter() - t0, steps=k,
            examples=examples, tokens=tokens, health=health)
        self.iteration += k
        self.score_value = scores[-1]  # lazy device scalar, like _fit_batch
        from deeplearning4j_tpu.optimize.listeners import fire_crossed

        fire_crossed(self.listeners, self, start, self.iteration)
        return scores

    def fit_stream(self, iterator, scan_steps: int = 16,
                   ingest=None, ingest_labels=None,
                   sync_each_window: bool = False):
        """Host-fed training: consume a DataSetIterator (typically an
        async prefetcher over on-disk binaries — the reference's
        AsyncDataSetIterator role, datasets/iterator/
        AsyncDataSetIterator.java:1) while keeping the chip busy.

        ``scan_steps`` consecutive batches are stacked host-side,
        shipped in ONE transfer, and trained in ONE fused ``fit_scan``
        dispatch — so disk reads, host stacking, and the next window's
        H2D ride under the previous window's device compute instead of
        costing a per-batch host round-trip. ``ingest`` /
        ``ingest_labels`` are optional jitted device-side transforms on
        the stacked [K, B, ...] feature/label windows (e.g. u8 pixels →
        normalized compute dtype, token ids → one-hot), keeping the
        wire format minimal. ``sync_each_window`` fetches each window's
        last score before uploading the next — on transports where H2D
        cannot overlap compute (seen on an earlier round's host), a
        serialized upload is faster than a degraded concurrent one for
        byte-heavy windows.

        A ragged tail (iterator exhausts mid-window, or a final batch
        smaller than the rest) falls back to per-batch ``fit``. Returns
        the last window's score array."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        scores = None

        def flush(window, fused):
            nonlocal scores
            def stack_masks(attr):
                ms = [getattr(b, attr) for b in window]
                if all(m is None for m in ms):
                    return None
                if any(m is None for m in ms):
                    raise ValueError(
                        f"fit_stream window mixes batches with and "
                        f"without {attr}")
                return np.stack([np.asarray(m) for m in ms])

            if fused:
                feats = jax.device_put(
                    np.stack([np.asarray(b.features) for b in window]))
                labels = jax.device_put(
                    np.stack([np.asarray(b.labels) for b in window]))
                fms = stack_masks("features_mask")
                lms = stack_masks("labels_mask")
                if sync_each_window:
                    # Materialize the upload BEFORE dispatching compute:
                    # on transports where transfers degrade while a
                    # computation is in flight, dispatching fit_scan
                    # first would make the scan stall on a crawling
                    # transfer of its own input.
                    feats.block_until_ready()
                    labels.block_until_ready()
                if ingest is not None:
                    feats = ingest(feats)
                if ingest_labels is not None:
                    labels = ingest_labels(labels)
                scores = self.fit_scan(
                    feats, labels, features_mask_stacked=fms,
                    labels_mask_stacked=lms)
                if sync_each_window:
                    np.asarray(scores[-1])
                return
            for b in window:  # ragged: correctness over throughput
                f = jnp.asarray(np.asarray(b.features)[None])
                y = jnp.asarray(np.asarray(b.labels)[None])
                if ingest is not None:
                    f = ingest(f)
                if ingest_labels is not None:
                    y = ingest_labels(y)
                self._fit_batch(DataSet(
                    f[0], y[0], b.features_mask, b.labels_mask))
            scores = jnp.asarray([self.score_value])

        from deeplearning4j_tpu.nn.streaming_fit import (
            drive_stream_windows,
        )

        drive_stream_windows(
            iterator, scan_steps, flush,
            lambda ds: np.shape(ds.features),
            telemetry=self.train_telemetry)
        return scores

    @functools.cached_property
    def _grad_and_score(self):
        def gs(params, state, rng, features, labels, feature_mask, label_mask):
            (score, new_state), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True
            )(params, state, rng, features, labels, feature_mask, label_mask)
            return score, grads, new_state

        return jax.jit(gs)

    @functools.cached_property
    def _output_fn(self):
        def out(params, state, x):
            y, _, _ = self._forward_fn(params, state, x, None, False)
            return y

        return jax.jit(out)

    # ------------------------------------------------------------------
    # Public training API (reference fit(...) :1130)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None) -> None:
        """fit(DataSet) / fit(features, labels) / fit(DataSetIterator)."""
        self.init()
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if labels is not None:
            self._fit_batch(DataSet(data, labels))
        elif isinstance(data, DataSet):
            self._fit_batch(data)
        else:  # iterator
            if self.conf.pretrain:
                self.pretrain(data)
                data.reset()
            if self.conf.backprop:
                it = iter(data)
                while True:
                    t0 = time.perf_counter()
                    ds = next(it, None)
                    self.train_telemetry.add_data_wait(
                        time.perf_counter() - t0)
                    if ds is None:
                        break
                    self._fit_batch(ds)

    def _fit_batch(self, ds) -> None:
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            self._fit_tbptt(ds)
            return
        algo = self.conf.confs[0].optimization_algo
        if algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            from deeplearning4j_tpu.optimize.solver import Solver

            Solver(self).optimize(ds)
            return
        n_iter = max(1, self.conf.confs[0].num_iterations)
        feats = self._as_input(ds.features)
        labels = self._as_labels(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        examples, tokens = batch_counts(feats, ids=self.takes_token_ids)
        for _ in range(n_iter):
            self._key, sub = jax.random.split(self._key)
            t0 = time.perf_counter()
            self.params, self.state, self.updater_state, score, health = (
                self._train_step(
                    self.params, self.state, self.updater_state,
                    self.iteration, sub, feats, labels, fm, lm,
                )
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

    def _fit_tbptt(self, ds) -> None:
        """Truncated BPTT (reference doTruncatedBPTT :1262-1320): chop the
        time axis into windows, carry rnn state (stop-gradient) across."""
        length = self.conf.tbptt_fwd_length
        feats = jnp.asarray(ds.features, self._dtype)
        labels = jnp.asarray(ds.labels, self._dtype)
        t_total = feats.shape[2]
        rnn_state = None
        for start in range(0, t_total, length):
            end = min(start + length, t_total)
            fw = feats[:, :, start:end]
            lw = labels[:, :, start:end]
            fmw = (
                None
                if ds.features_mask is None
                else jnp.asarray(ds.features_mask)[:, start:end]
            )
            lmw = (
                None
                if ds.labels_mask is None
                else jnp.asarray(ds.labels_mask)[:, start:end]
            )
            self._key, sub = jax.random.split(self._key)
            t0 = time.perf_counter()
            (
                self.params,
                self.state,
                self.updater_state,
                rnn_state,
                score,
                health,
            ) = self._tbptt_step(
                self.params, self.state, self.updater_state,
                self.iteration, sub, fw, lw, fmw, lmw, rnn_state,
            )
            self.train_telemetry.record_step(
                dispatch_s=time.perf_counter() - t0,
                examples=int(fw.shape[0]),
                tokens=int(fw.shape[0]) * int(fw.shape[2]),
                health=health)
            self.score_value = score
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)

    @functools.cached_property
    def _tbptt_step(self):
        def loss(params, state, rng, f, y, fm, lm, rnn_state):
            out, new_state, new_rnn = self._forward_fn(
                params, state, f, rng, True, fm, rnn_state=rnn_state
            )
            if self._compute_dtype is not None:
                with scope("cast"):
                    out = _cast_floating(out, dtype=self._dtype)  # f32
            impl = self._impls[-1]
            with scope("head/loss"):
                score = impl.loss(self.conf.confs[-1], out, y, lm)
                score = score + self._reg_score(params)
                score = score + self._aux_score(new_state)
            return score, (new_state, new_rnn)

        def step(params, state, upd_state, iteration, rng, f, y, fm, lm,
                 rnn_state):
            (score, (new_state, new_rnn)), grads = jax.value_and_grad(
                loss, has_aux=True
            )(params, state, rng, f, y, fm, lm, rnn_state)
            new_params, new_upd = self._apply_updates(
                params, upd_state, grads, iteration)
            new_rnn = jax.lax.stop_gradient(new_rnn)
            health = grad_health(grads, params, new_params)
            return new_params, new_state, new_upd, new_rnn, score, health

        return jax.jit(step)

    # ------------------------------------------------------------------
    # Pretraining (reference pretrain :150-226, §3.3)
    # ------------------------------------------------------------------
    def pretrain(self, data_iter) -> None:
        """Greedy layer-wise pretraining of RBM/AutoEncoder layers."""
        self.init()
        from deeplearning4j_tpu.optimize.pretrainer import pretrain_network

        pretrain_network(self, data_iter)

    # ------------------------------------------------------------------
    # Inference (reference output/feedForward :578-715)
    # ------------------------------------------------------------------
    @property
    def takes_token_ids(self) -> bool:
        """True where the first layer embeds token ids (``[N, T]``
        int32 in) rather than projecting one-hot columns."""
        return bool(getattr(self.conf.confs[0].layer,
                            "takes_token_ids", False))

    def _as_input(self, x) -> Array:
        """Features at the net's dtype; token ids stay whole numbers (a
        bf16 net would round an id over 256)."""
        if self.takes_token_ids:
            return jnp.asarray(x, jnp.int32)
        return jnp.asarray(x, self._dtype)

    @property
    def takes_label_ids(self) -> bool:
        """True where the output layer is scored on class ids
        (``[N, T]`` int32 labels) from its logits rather than on
        one-hot columns from its activations."""
        return bool(getattr(self.conf.confs[-1].layer,
                            "takes_label_ids", False))

    def _as_labels(self, y) -> Array:
        if self.takes_label_ids:
            return jnp.asarray(y, jnp.int32)
        return jnp.asarray(y, self._dtype)

    def output(self, x, train: bool = False) -> Array:
        self.init()
        x = self._as_input(x)
        return self._output_fn(self.params, self.state, x)

    def feed_forward(self, x, train: bool = False) -> List[Array]:
        """All layer activations, input first (reference feedForward)."""
        self.init()
        x = self._as_input(x)
        acts, _, _ = self._forward_fn(
            self.params, self.state, x, None, False, collect=True
        )
        return [x] + list(acts)

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (reference Classifier.predict)."""
        out = self.output(x)
        return np.asarray(jnp.argmax(out, axis=1))

    def score(self, ds=None) -> float:
        if ds is None:
            return float(self.score_value)
        self.init()
        feats = self._as_input(ds.features)
        labels = self._as_labels(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        s, _ = self._loss_eval(self.params, self.state, feats, labels, fm, lm)
        return float(s)

    @functools.cached_property
    def _loss_eval(self):
        def f(params, state, x, y, fm, lm):
            out, _, _ = self._forward_fn(params, state, x, None, False, fm,
                                         logits=self.takes_label_ids)
            if self._compute_dtype is not None:
                out = _cast_floating(out, dtype=self._dtype)  # loss in f32
            impl = self._impls[-1]
            score = impl.loss(self.conf.confs[-1], out, y, lm)
            return score + self._reg_score(params), out

        return jax.jit(f)

    # ------------------------------------------------------------------
    # Gradient access for gradient checks (reference
    # computeGradientAndScore + gradient())
    # ------------------------------------------------------------------
    def compute_gradient_and_score(self, ds) -> Tuple[float, Gradient]:
        self.init()
        feats = self._as_input(ds.features)
        labels = self._as_labels(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        score, grads, _ = self._grad_and_score(
            self.params, self.state, None, feats, labels, fm, lm
        )
        return float(score), Gradient.from_tree(grads)

    # ------------------------------------------------------------------
    # RNN streaming + state (reference rnnTimeStep, stateMap)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _rnn_step_jit(self):
        # One jitted computation per streaming step instead of one host
        # dispatch per XLA op (the serving loop's hot path); retraces
        # only when the rnn-state pytree structure flips from empty
        # (first call) to populated.
        def f(params, state, x, rnn_state):
            return self._forward_fn(
                params, state, x, None, False,
                rnn_state=rnn_state or None,
            )

        return jax.jit(f)

    def rnn_time_step(self, x) -> Array:
        """Stateful single/multi-step inference carrying hidden state
        between calls (reference rnnTimeStep)."""
        self.init()
        from deeplearning4j_tpu.nn.layers.attention import (
            guard_streamable,
        )

        guard_streamable(
            (str(i), c.layer) for i, c in enumerate(self.conf.confs))
        x = self._as_input(x)
        if x.ndim == 2 and not self.takes_token_ids:
            x = x[:, :, None]
        out, _, new_rnn = self._rnn_step_jit(
            self.params, self.state, x, self._rnn_state)
        self._rnn_state = new_rnn
        return out

    def rnn_clear_previous_state(self, slots=None) -> None:
        """Reset streaming state (reference rnnClearPreviousState).

        ``slots=None`` wipes the whole batch. ``slots=[...]`` zeroes
        only those batch rows — the serving engine's per-slot eviction
        hook (nn/streaming.py: a zeroed attention row IS the
        empty-cache state, so the cleared slot streams as fresh while
        its neighbours keep decoding mid-flight)."""
        from deeplearning4j_tpu.nn.streaming import reset_streaming_state

        self._rnn_state = reset_streaming_state(self._rnn_state, slots)

    def generate(self, prompt, n_tokens: int):
        """Greedy autoregressive generation fused on device: prefill
        the one-hot prompt [B, V, Tp] through ``rnn_time_step``, then
        ONE jitted ``lax.scan`` emits ``n_tokens`` ids with the KV
        cache riding in the scan carry — serving throughput without a
        host round-trip per token. The per-token equivalent is a
        ``rnn_time_step`` loop (reference rnnTimeStep streaming,
        nn/layers/recurrent/BaseRecurrentLayer.java:1); numerics are
        identical (tests/test_decode_generate.py).

        The scan length is BUCKETED to the next power of two
        (nn/streaming.py scan_length_bucket) and the true length rides
        as a traced operand: steps past it freeze the carry, so the
        compiled-executable count stays O(log max_tokens) under varied
        request lengths instead of one compile per distinct
        ``n_tokens``, and the rnn state still lands exactly at the
        post-generation position.

        Requires an LM-shaped net (n_classes == n_in, one-hot io).
        Returns int32 ids [B, n_tokens]; leaves the rnn state at the
        post-generation position."""
        from deeplearning4j_tpu.nn.streaming import (
            make_bucketed_generate,
            scan_length_bucket,
        )

        if n_tokens < 1:
            raise ValueError(f"n_tokens {n_tokens} < 1")
        if self.takes_token_ids:
            raise ValueError(
                "generate() feeds one-hot columns; layer 0 "
                f"({type(self.conf.confs[0].layer).__name__}, "
                "sequence) takes token ids. Serve this net "
                "through serving.DecodeEngine, or step it with "
                "rnn_time_step on [N, T] ids")
        self.init()
        vocab = self.conf.confs[0].layer.n_in
        out = self.rnn_time_step(prompt)  # prefill (guards streamable)
        tok0 = jnp.argmax(out[:, :, -1], axis=1).astype(jnp.int32)
        if n_tokens == 1:
            return tok0[:, None]
        n_rem = n_tokens - 1
        bucket = scan_length_bucket(n_rem)
        gen = self._generate_fns.get(bucket)
        if gen is None:
            def step(params, state, x, rnn):
                o, _, new_rnn = self._forward_fn(
                    params, state, x, None, False, rnn_state=rnn)
                return o, new_rnn

            gen = self._generate_fns[bucket] = make_bucketed_generate(
                step, vocab, self._dtype, bucket)
        toks, self._rnn_state = gen(
            self.params, self.state, self._rnn_state, tok0,
            jnp.asarray(n_rem, jnp.int32))
        return jnp.concatenate([tok0[:, None], toks[:, :n_rem]], axis=1)

    # ------------------------------------------------------------------
    # Parameter pack/unpack (reference params() :984-1063)
    # ------------------------------------------------------------------
    def params_flat(self) -> Array:
        flat, _ = ravel_pytree(self.params)
        return flat

    def set_params_flat(self, flat) -> None:
        _, unravel = ravel_pytree(self.params)
        self.params = unravel(jnp.asarray(flat))
        self.params_version += 1

    def num_params(self) -> int:
        return int(self.params_flat().shape[0])

    def param_table(self) -> Dict[str, Array]:
        """Flat "idx_name" -> array view (reference paramTable())."""
        out = {}
        for idx in sorted(self.params, key=int):
            for name, p in self.params[idx].items():
                out[f"{idx}_{name}"] = p
        return out

    def set_param(self, key: str, value) -> None:
        idx, name = key.split("_", 1)
        self.params[idx][name] = jnp.asarray(value, self._dtype)
        self.params_version += 1

    # ------------------------------------------------------------------
    # Evaluation + listeners
    # ------------------------------------------------------------------
    def evaluate(self, data_iter):
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        self.init()
        ev = Evaluation()
        for ds in data_iter:
            out = self.output(ds.features)
            if ds.labels_mask is not None or (
                np.asarray(ds.labels).ndim == 3
            ):
                ev.eval_time_series(ds.labels, out, ds.labels_mask)
            else:
                ev.eval(ds.labels, out)
        return ev

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    # ------------------------------------------------------------------
    # Serialization (reference checkpoint triple: conf JSON + params +
    # updater, SURVEY.md §5.4; here conf JSON + params npz + updater npz)
    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        """One-zip checkpoint (util/model_serializer format)."""
        from deeplearning4j_tpu.util.model_serializer import write_model

        write_model(self, path)

    @staticmethod
    def load(path: str) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.util.model_serializer import restore_model

        net = restore_model(path)
        if not isinstance(net, MultiLayerNetwork):
            raise TypeError(f"{path} holds a {type(net).__name__}")
        return net

    def clone(self) -> "MultiLayerNetwork":
        # Deep-copy the buffers: the train step DONATES params/state, so
        # aliased references in a clone would be deleted by the donor's
        # next step ("Array has been deleted"). Skip init() — its random
        # params would be immediately overwritten.
        copy = functools.partial(jax.tree.map, jnp.copy)
        net = MultiLayerNetwork(self.conf.clone())
        net.params = copy(self.params)
        net.updater_state = copy(self.updater_state)
        net.state = copy(self.state)
        net.iteration = self.iteration
        net._initialized = True
        return net

    def unsharded_clone(self) -> "MultiLayerNetwork":
        """A clone with every bean's mesh-axis fields (``ring_axis``,
        ``ep_axis``) cleared — the single-device serving/eval view of a
        mesh-trained net. The ring/Ulysses and dense attention paths
        (and sp_scan vs lax.scan recurrences, and all-to-all vs dense
        MoE dispatch) are numerically equivalent (parity-tested), so
        scores/outputs match the mesh-trained model; use this for score
        calculators, evaluate(), or rnn_time_step, which run outside
        the mesh.

        Build it ONCE per serving/eval site and refresh weights per
        evaluation (``serving.params = jax.tree.map(jnp.copy,
        net.params)``; likewise ``state``) — a fresh clone per call
        would re-jit the forward every time."""
        net = self.clone()
        for c in net.conf.confs:
            for axis_field in ("ring_axis", "ep_axis"):
                if getattr(c.layer, axis_field, None):
                    setattr(c.layer, axis_field, None)
        return net
