"""The layers of a hybrid state-space / attention mixture-of-experts LM
(the ``granitemoehybrid`` family): after the token embedding
(nn/layers/embedding.py, ``sequence``), a block whose mixer is either
grouped-KV attention or Mamba-2 and whose feed-forward is a dropless
share of gated experts, and a tied head out. The same block, by its
bean's options, is the ``afmoe`` family's (sliding-window and full
attention layers in one net, below) and the ``lfm2_moe`` family's
(``mixer="short_conv"``, the gated short convolution
:func:`short_conv_mixer`, beside attention layers with QK-norm and
rotary positions; no shared expert, ``route_eps`` 1e-6), which is also
TRAINED: the block runs under ``jax.value_and_grad`` through
``fit_scan`` (nn/layers/moe.py ``dropless_moe``), its head is scored on
label ids, and ``expert_bias`` is a leaf no updater moves
(``HybridMoeBlockImpl.frozen_leaves``; under ``freeze_router`` the
router's weights are another, and the gates are constants to the
gradient).

Both keep the framework's ``[N, C, T]`` recurrent layout at their
edges, so they compose with ``MultiLayerNetwork._forward_fn``, the
streaming state channel (``rnn_state``) and the serving engine::

    x = E[ids] * embedding_multiplier      (EmbeddingLayer, ``sequence``)
    x = x + r * mixer(RMSNorm(x))                          (HybridMoeBlock)
    h = RMSNorm(x);  x = x + r * (routed(h) + shared(h))
    logits = RMSNorm(x) @ E^T / logits_scaling             (TiedLMHead)

The attention mixer has no positional term and scales its scores by
``attention_multiplier`` (not ``1 / sqrt(head)``); its ``n_kv_heads``
key/value heads each serve ``n_heads / n_kv_heads`` query heads, and the
cache holds the KV heads only (``AttentionImpl._attend_core``). The
Mamba-2 mixer is nn/layers/mamba2.py, the experts nn/layers/moe.py
``dropless_moe``.

**The ``afmoe`` options** (all off by default). ``qk_norm``: RMSNorm
over ``d_head`` of every query and key head, before the rotation.
``rope_theta`` > 0: rotary positions (rotate-half) on queries and keys
at their ABSOLUTE positions, so the cache holds rotated keys; 0 = no
positional term. ``sliding``: ``stream_max_t`` is the model's sliding
window (key ``j`` seen by query ``i`` iff ``i - window < j <= i``) in
every program, the cold prefill too. ``gated_attention``: the heads'
output times ``sigmoid(Wg a)``, ``a`` the mixer's normed input, before
``Wo``. ``post_norms``: a second RMSNorm on each branch before it joins
the residual (``x + N2(mixer(N1 x))``, ``x + N4(ffn(N3 x))``).
``n_router`` 0: a DENSE layer, whose only feed-forward is the shared
gated one of width ``d_shared``. ``gate_rule``: how the router's
outputs become picks and gates (nn/layers/moe.py ``route``):
``"softmax_topk"`` (the softmax over the picked logits) or
``"sigmoid_bias"`` (sigmoid scores, picked with the per-expert
``expert_bias`` added, normalised over the picks, times
``route_scale``). ``TiedLMHead(tie_to=None)`` is an untied head with
its own ``E``.

**The ``evabyte`` options.** ``mixer="eva"``: EVA attention
(nn/layers/eva.py: exact keys inside an aligned window of ``eva_window``
tokens, one learned summary an ``eva_chunk`` of every earlier window,
one softmax over both; ungrouped heads, rotary positions, the two
pooling vectors ``eva_mu`` / ``eva_phi`` a head). ``norm_unit_offset``:
every RMSNorm's gain is ``1 + w``. ``fp32_residual``: the block hands
on a float32 stream whatever dtype its weights have, and its products
read it rounded to theirs. ``TiedLMHead(n_pred_heads=P)`` holds ``P x
n_out`` rows and serves the first ``n_out`` (the next token's).

**State, rows and counters.** A block's streaming state is its mixer's
and nothing else (the attention cache, ``{"conv", "ssm"}``, the short
convolution's ``{"conv"}``, its last ``conv_kernel - 1`` gated inputs, or
EVA's window, summaries and position). What a
caller that batches slots has to say and wants to know travels beside
it, as two keywords of ``apply`` that ``_forward_fn`` hands to a layer
whose bean has ``wants_live``: ``live`` ``[B]``, which rows exist (an
idle serving slot routes to no expert and its recurrent state is left
alone), and ``counters``, a dict the block adds this call's int32
scalars into (``moe_picks``, ``moe_picks_held``,
``moe_experts_touched``, ``moe_load_max``, ``moe_layer_steps``,
``ssm_state_rows``, ``eva_summaries_written``; under ``train`` also
``moe_pair_rows_worked``). A training step hands them back with its
gradient-health scalars (``MultiLayerNetwork._step_body``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer,
    BaseRecurrentLayer,
)
from deeplearning4j_tpu.nn.conf.serde import register_bean
from deeplearning4j_tpu.nn.layers import eva, mamba2
from deeplearning4j_tpu.nn.layers.attention import (
    AttentionImpl,
    attention_cache,
)
from deeplearning4j_tpu.nn.layers.base import LayerImplBase
from deeplearning4j_tpu.nn.layers.moe import (
    dropless_moe,
    gated_ffn,
    moe_shapes,
)
from deeplearning4j_tpu.ops.losses import label_cross_entropy
from deeplearning4j_tpu.profiler.scopes import scope

MIXERS = ("attention", "mamba2", "short_conv", "eva")


@scope("norm")
def rms_norm(x, w, eps: float, unit_offset: bool = False):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, the mean
    square in float32; ``unit_offset``: the gain is ``1 + w``."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    gain = w.astype(jnp.float32)
    return (y * (1.0 + gain if unit_offset else gain)).astype(x.dtype)


def _residual(x, branch, multiplier: float):
    """``x + multiplier * branch``, the product and the sum in float32
    and ONE rounding to ``x``'s dtype (a bfloat16 ``0.22`` would be off
    by a part in 800 in every layer)."""
    return (x.astype(jnp.float32)
            + multiplier * branch.astype(jnp.float32)).astype(x.dtype)


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def short_conv_shapes(width: int, kernel: int) -> dict:
    return {"W_in": (width, 3 * width), "conv_w": (kernel, width),
            "W_out": (width, width)}


def short_conv_mixer(params, hn, state, mask):
    """The gated short convolution (``lfm2``'s ``conv`` layers) on
    ``hn`` ``[N, T, D]`` (already normed)::

        [B | C | u] = h W_in                                  (D each)
        y_t = C_t * sum_{j < K} w[j] (B u)_{t - (K - 1) + j}  (depthwise, causal, no bias)
        out = y W_out

    ``state`` is ``{"conv": [N, K - 1, D]}``, the last ``K - 1`` gated
    inputs ``B u``, or None (a fresh row); ``mask`` ``[N, T]`` marks
    each row's valid prefix. The taps are summed and gated in float32,
    one rounding to ``hn``'s dtype. Returns ``(out, new state)``."""
    with scope("mixer/proj"):
        proj = hn @ params["W_in"]
        d = proj.shape[-1] // 3
    with scope("mixer/conv"):
        bu = proj[..., :d] * proj[..., 2 * d:]
        lengths = (None if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        acc, tail = mamba2.conv_taps(
            bu, None if state is None else state["conv"],
            params["conv_w"], lengths)
        y = (proj[..., d:2 * d].astype(jnp.float32)
             * acc).astype(hn.dtype)
    with scope("mixer/proj"):
        return y @ params["W_out"], {"conv": tail}


def _heads(hn, w, n_heads: int, d_head: int):
    """``hn @ w`` ``[N, T, H x dh]`` split into heads ``[N, H, T, dh]``."""
    n, t, _ = hn.shape
    return jnp.transpose((hn @ w).reshape(n, t, n_heads, d_head),
                         (0, 2, 1, 3))


@scope("attn/rope")
def rope(q, k, start, theta: float):
    """Rotary positions (rotate-half) on ``q``/``k`` ``[N, H, T, dh]``
    whose first position is ``start`` ``[N]``: the angles in float32,
    one rounding back to the inputs' dtype."""
    dh, t = q.shape[-1], q.shape[2]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    pos = (start[:, None] + jnp.arange(t)[None, :]).astype(jnp.float32)
    ang = pos[:, None, :, None] * inv                   # [N, 1, T, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)

    def turn(x):
        xf = x.astype(jnp.float32)
        half = jnp.concatenate([-xf[..., dh // 2:], xf[..., :dh // 2]],
                               axis=-1)
        return (xf * cos + half * sin).astype(x.dtype)

    return turn(q), turn(k)


# ---------------------------------------------------------------------
# the tied head out
# ---------------------------------------------------------------------
@register_bean("TiedLMHead")
@dataclasses.dataclass
class TiedLMHead(BaseOutputLayer):
    """Conf bean: ``softmax(RMSNorm(x) @ E^T / logits_scaling)`` over
    ``[N, n_in, T]``, ``E`` being layer ``tie_to``'s ``W`` ``[n_out,
    n_in]`` (``MultiLayerNetwork._forward_fn`` hands it over as
    ``params["E"]``; the head's own leaf is the norm's weight).
    ``tie_to=None``: an untied head, ``E`` its own leaf.

    It is scored on class IDS (``takes_label_ids``: ``fit`` and
    ``fit_scan`` keep its labels ``[N, T]`` whole numbers), from its
    float32 logits: the training pass stops at :meth:`TiedLMHeadImpl.
    logits` and the loss is the log-softmax gathered at the label
    (ops/losses.py ``label_cross_entropy``), so no ``[N, T, V]``
    one-hot or probability is made on the host or the device."""

    tie_to: Optional[int] = 0
    init_std: float = 0.02
    logits_scaling: float = 1.0
    rms_eps: float = 1e-5
    #: the norm's gain is ``1 + w`` (``norm_add_unit_offset``)
    norm_unit_offset: bool = False
    #: an untied head of ``n_pred_heads x n_out`` rows, head ``p``
    #: scoring the token ``p + 1`` positions on (a multi-byte
    #: predictor's). ``logits`` and ``apply`` are head 0's, the next
    #: token's; :meth:`TiedLMHeadImpl.all_logits` has every head's
    n_pred_heads: int = 1

    #: read off the bean by ``MultiLayerNetwork``, as ``takes_token_ids``
    takes_label_ids = True
    #: the impl names its own parts (``norm``, ``head/logits``)
    scope_group = None


class TiedLMHeadImpl(LayerImplBase):
    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        lc = conf.layer
        params = {"norm_w": (jnp.zeros if lc.norm_unit_offset
                             else jnp.ones)((lc.n_in,), dtype)}
        if lc.n_pred_heads > 1 and lc.tie_to is not None:
            raise ValueError("n_pred_heads > 1 needs an untied head "
                             "(tie_to=None)")
        if lc.tie_to is None:
            params["E"] = _normal(
                key, (lc.n_pred_heads * lc.n_out, lc.n_in), lc.init_std,
                dtype)
        return params

    @classmethod
    def logits(cls, conf, params, x, every_head: bool = False):
        lc = conf.layer
        hn = rms_norm(jnp.transpose(x, (0, 2, 1)), params["norm_w"],
                      lc.rms_eps, lc.norm_unit_offset)
        with scope("head/logits"):
            e = params["E"]
            if lc.n_pred_heads > 1 and not every_head:
                e = e[:lc.n_out]        # head 0: the next token's rows
            z = jax.lax.dot_general(
                hn.astype(e.dtype), e, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [N, T, V]
            return z / lc.logits_scaling

    @classmethod
    def all_logits(cls, conf, params, x):
        """Every prediction head's float32 logits ``[N, T, P, V]``."""
        z = cls.logits(conf, params, x, every_head=True)
        return z.reshape(*z.shape[:2], conf.layer.n_pred_heads,
                         conf.layer.n_out)

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None):
        z = cls.logits(conf, params, x)
        with scope("head/logits"):
            probs = jax.nn.softmax(z, axis=-1)
            return jnp.transpose(probs, (0, 2, 1)), state

    @classmethod
    def loss(cls, conf, logits, labels, mask=None):
        """The score of :meth:`logits` ``[N, T, V]`` against label ids
        ``[N, T]`` (``mask`` ``[N, T]``)."""
        return label_cross_entropy(logits, labels, mask)


# ---------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------
@register_bean("HybridMoeBlock")
@dataclasses.dataclass
class HybridMoeBlock(BaseRecurrentLayer):
    """Conf bean: one pre-RMSNorm residual block of width ``n_in ==
    n_out``: ``mixer`` ("attention", "mamba2", "short_conv", the
    gated short convolution of width ``conv_kernel``, or "eva", EVA
    attention), then dropless top-k
    routing over ``n_router`` outputs of which this chip holds the
    experts ``experts_held = [lo, hi)`` (None = all), plus a shared
    expert of width ``d_shared`` (0 = none). The gates follow
    ``gate_rule``: ``"softmax_topk"``, the softmax over the picked
    logits, or ``"sigmoid_bias"``, sigmoid scores picked with a
    per-expert bias, normalised and scaled by ``route_scale``
    (nn/layers/moe.py ``route``). ``n_router`` 0 is a dense layer (the
    shared feed-forward alone). The module docstring has the other
    options."""

    mixer: str = "attention"
    rms_eps: float = 1e-5
    residual_multiplier: float = 1.0
    # attention mixer
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 0                 # 0 => n_out / n_heads
    attention_multiplier: float = 0.0   # 0 => 1 / sqrt(d_head)
    causal: bool = True
    use_flash: Optional[bool] = None
    use_flash_paged: Optional[object] = None
    stream_max_t: int = 512
    #: ``stream_max_t`` is a sliding window the model attends through
    #: in every program (see the module docstring)
    sliding: bool = False
    rope_theta: float = 0.0         # 0 => no positional term
    qk_norm: bool = False
    gated_attention: bool = False
    post_norms: bool = False
    # eva mixer (nn/layers/eva.py): ``n_heads`` heads of ``d_head``,
    # rotary positions by ``rope_theta``; exact keys inside an aligned
    # window of ``eva_window`` tokens, one learned summary a chunk of
    # ``eva_chunk`` tokens of every earlier window. ``stream_max_t`` is
    # the longest context (what the summaries' cache has room for)
    eva_window: int = 2048
    eva_chunk: int = 16
    #: every RMSNorm's gain is ``1 + w`` (``norm_add_unit_offset``)
    norm_unit_offset: bool = False
    #: the residual stream stays float32 between the blocks under a
    #: narrower compute dtype (``fp32_skip_add``): the block hands on
    #: float32 and rounds only what its products read
    fp32_residual: bool = False
    # mamba2 mixer
    ssm_heads: int = 8
    ssm_d_head: int = 16
    ssm_d_state: int = 16
    ssm_groups: int = 1
    ssm_d_conv: int = 4
    ssm_chunk: int = 256
    # short_conv mixer: the taps (the config's ``conv_L_cache``)
    conv_kernel: int = 3
    # experts
    n_router: int = 8
    top_k: int = 2
    d_expert: int = 0
    d_shared: int = 0
    experts_held: Optional[tuple] = None
    gate_rule: str = "softmax_topk"
    route_scale: float = 1.0
    route_eps: float = 1e-20
    #: True: routing takes no part in learning. The gates are constants
    #: to the gradient (the router's weights take none, the layer's
    #: input none through them) and the router is a leaf no updater
    #: moves: what a share of the experts trained WITHOUT its exchange
    #: needs, where only held picks add to the output and any gradient
    #: through the gates teaches the stack to pick the held experts
    freeze_router: bool = False
    #: the two Pallas kernels (the one-step state update, the grouped
    #: expert product): None = on a TPU, the plain programs elsewhere;
    #: True / False force; "interpret" = Pallas interpret mode
    use_kernels: Optional[object] = None
    init_std: float = 0.02

    #: what the serving engine reads off a bean (it never asks for a
    #: class): the forward pass takes ``live`` and ``counters``; the
    #: layer is not sharded over ``tp``
    wants_live = True
    shards_over_tp = False
    #: the impl names its own parts (``norm``, ``attn/*``, ``mixer/*``,
    #: ``moe/*``, ``ffn``)
    scope_group = None

    def serving_caches(self):
        """By the mixer: an attention cache; the EVA mixer's two; or
        ``()``, one row a slot (the Mamba-2 mixer's convolution tail
        and SSM state, the short convolution's tail)."""
        if self.mixer == "attention":
            return (attention_cache(self),)
        if self.mixer == "eva":
            return eva.paged_caches(
                window=self.eva_window, chunk=self.eva_chunk,
                longest=self.stream_max_t,
                token_width=self.n_heads * self.head_dim)
        return ()

    @property
    def held(self):
        lo, hi = self.experts_held or (0, self.n_router)
        return int(lo), int(hi)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.n_out // self.n_heads


class HybridMoeBlockImpl(LayerImplBase):
    @classmethod
    def frozen_leaves(cls, lc) -> tuple:
        """Leaves no updater moves (``MultiLayerNetwork._apply_updates``
        asks the impl): the router's selection bias reaches the loss
        through the picks alone, so its gradient is 0 by construction,
        and whoever balances the experts' loads moves it by a rule
        outside the gradient; under ``freeze_router`` the router's
        weights too (their gradient is 0 there as well)."""
        return ("expert_bias", "router") if lc.freeze_router else (
            "expert_bias",)

    @classmethod
    def shapes(cls, lc) -> dict:
        d = lc.n_out
        if lc.mixer == "attention":
            dh = lc.head_dim
            mix = {"Wq": (d, lc.n_heads * dh), "Wk": (d, lc.n_kv_heads * dh),
                   "Wv": (d, lc.n_kv_heads * dh),
                   "Wo": (lc.n_heads * dh, d)}
            if lc.qk_norm:
                mix.update(q_norm_w=(dh,), k_norm_w=(dh,))
            if lc.gated_attention:
                mix["Wg"] = (d, lc.n_heads * dh)
        elif lc.mixer == "mamba2":
            mix = mamba2.mixer_shapes(d, lc.ssm_heads, lc.ssm_d_head,
                                      lc.ssm_d_state, lc.ssm_groups,
                                      lc.ssm_d_conv)
        elif lc.mixer == "short_conv":
            mix = short_conv_shapes(d, lc.conv_kernel)
        elif lc.mixer == "eva":
            dh = lc.head_dim
            mix = {"Wq": (d, lc.n_heads * dh), "Wk": (d, lc.n_heads * dh),
                   "Wv": (d, lc.n_heads * dh), "Wo": (lc.n_heads * dh, d),
                   "eva_mu": (lc.n_heads, dh), "eva_phi": (lc.n_heads, dh)}
        else:
            raise ValueError(
                f"mixer {lc.mixer!r}: expected one of {MIXERS}")
        lo, hi = lc.held
        ffn = moe_shapes(d, lc.n_router, hi - lo, lc.d_expert,
                         lc.d_shared)
        if not lc.n_router:
            # a dense layer: the shared feed-forward alone
            ffn = {k: v for k, v in ffn.items() if k.startswith("Ws_")}
        elif lc.gate_rule == "sigmoid_bias":
            ffn["expert_bias"] = (lc.n_router,)
        post = ({"post1_w": (d,), "post2_w": (d,)} if lc.post_norms
                else {})
        return {"norm1_w": (d,), **mix, "norm2_w": (d,), **ffn, **post}

    @classmethod
    def init(cls, key, conf, dtype=jnp.float32) -> dict:
        """The family's initialisation: N(0, ``init_std``) matrices,
        unit norm weights; Mamba-2's own for what its config does not
        carry (``A`` uniform in [1, 16], ``dt`` log-uniform in
        [1e-3, 1e-1] through the inverse softplus, ``D`` = 1)."""
        lc = conf.layer
        if lc.n_in != lc.n_out:
            raise ValueError(
                f"HybridMoeBlock needs n_in == n_out, got "
                f"{lc.n_in}/{lc.n_out}")
        params = {}
        for j, (name, shape) in enumerate(cls.shapes(lc).items()):
            k = jax.random.fold_in(key, j)
            if name in ("norm1_w", "norm2_w", "norm_w", "D", "post1_w",
                        "post2_w", "q_norm_w", "k_norm_w"):
                params[name] = (jnp.zeros if lc.norm_unit_offset
                                else jnp.ones)(shape, dtype)
            elif name == "A_log":
                params[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                params[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
            elif name in ("conv_b", "expert_bias"):
                params[name] = jnp.zeros(shape, dtype)
            else:
                params[name] = _normal(k, shape, lc.init_std, dtype)
        return params

    @classmethod
    def _attention(cls, lc, params, hn, state, train, mask):
        n, t, _ = hn.shape
        dh = lc.head_dim

        with scope("attn/qkv"):
            q = _heads(hn, params["Wq"], lc.n_heads, dh)
            k = _heads(hn, params["Wk"], lc.n_kv_heads, dh)
            v = _heads(hn, params["Wv"], lc.n_kv_heads, dh)
        if lc.qk_norm:
            q = rms_norm(q, params["q_norm_w"], lc.rms_eps)
            k = rms_norm(k, params["k_norm_w"], lc.rms_eps)
        start = None
        if lc.rope_theta:
            # the chunk's first absolute position, a row: the paged
            # tables' ``filled``, the dense row cache's ``pos``
            with scope("attn/rope"):
                start = (jnp.zeros((n,), jnp.int32) if state is None
                         else state["filled" if "pk" in state else "pos"])
            q, k = rope(q, k, start, lc.rope_theta)
        if lc.attention_multiplier:
            # the core divides by sqrt(d_head): hand it q scaled so
            # that the scores come out times the multiplier
            with scope("attn/qkv"):
                q = (q.astype(jnp.float32) * (
                    lc.attention_multiplier
                    * math.sqrt(dh))).astype(q.dtype)
        # (from outside every scope: ``_attend_core`` places its own)
        o, state = AttentionImpl._attend_core(lc, q, k, v, state, train,
                                              mask)
        if start is not None and state is not None and "pk" not in state:
            # the dense row cache caps ``filled`` at the window: the
            # absolute position rides beside it
            with scope("attn/cache"):
                written = (t if mask is None
                           else jnp.sum(mask.astype(jnp.int32), axis=1))
                state = dict(state, pos=start + written)
        with scope("attn/out"):
            o = jnp.transpose(o, (0, 2, 1, 3)).reshape(
                n, t, lc.n_heads * dh)
            if lc.gated_attention:
                gate = jax.nn.sigmoid(
                    (hn @ params["Wg"]).astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate).astype(o.dtype)
            return o @ params["Wo"], state

    @classmethod
    def _eva(cls, lc, params, hn, state, train, mask):
        """The EVA mixer (nn/layers/eva.py) on ``hn`` ``[N, T, D]``:
        ``(out, new state, summaries written or None)``. The state it
        is handed says which program runs: none, the net's own
        streaming state, or the engine's two pools."""
        n, t, _ = hn.shape
        dh = lc.head_dim
        if lc.eva_window % lc.eva_chunk:
            raise ValueError(
                f"eva_chunk {lc.eva_chunk} does not divide eva_window "
                f"{lc.eva_window}")
        with scope("eva/qkv"):
            q, k, v = (_heads(hn, params[w], lc.n_heads, dh)
                       for w in ("Wq", "Wk", "Wv"))
            paged = state is not None and "pk" in state
            start = (jnp.zeros((n,), jnp.int32) if state is None
                     else state["filled" if paged else "pos"])
        if lc.rope_theta:
            q, k = rope(q, k, start, lc.rope_theta)
        sizes = dict(window=lc.eva_window, chunk=lc.eva_chunk, mask=mask)
        mu, phi = params["eva_mu"], params["eva_phi"]
        written = None
        if state is None:
            o, state = eva.full(q, k, v, mu, phi, **sizes,
                                capacity=lc.stream_max_t,
                                want_state=not train)
        elif paged:
            o, state, written = eva.paged(q, k, v, state, mu, phi, **sizes,
                                          toggle=lc.use_flash_paged)
        else:
            o, state = eva.stream(q, k, v, state, mu, phi, **sizes)
        with scope("eva/out"):
            o = jnp.transpose(o, (0, 2, 1, 3)).reshape(
                n, t, lc.n_heads * dh)
            return o @ params["Wo"], state, written

    @classmethod
    def apply(cls, conf, params, x, state=None, train=False, rng=None,
              mask=None, live=None, counters=None):
        lc = conf.layer
        with scope("norm"):     # the entry re-layout, with what reads it
            xt = jnp.transpose(x, (0, 2, 1))                 # [N, T, D]
            if lc.fp32_residual:
                xt = xt.astype(jnp.float32)
        n, t, d = xt.shape
        # (under ``fp32_residual`` the products read the stream rounded
        # to the weights' dtype)
        wide = params["norm1_w"].dtype
        hn = rms_norm(xt, params["norm1_w"], lc.rms_eps,
                      lc.norm_unit_offset).astype(wide)
        counts = {}
        if lc.mixer == "eva":
            mixed, new_state, written = cls._eva(lc, params, hn, state,
                                                 train, mask)
            if written is not None:
                counts["eva_summaries_written"] = written
        elif lc.mixer == "attention":
            if live is None and state is not None:
                with scope("tables"):
                    live = state["filled"] > 0   # an idle slot: no cache
            mixed, new_state = cls._attention(lc, params, hn, state,
                                              train, mask)
        elif lc.mixer == "short_conv":
            mixed, new_state = short_conv_mixer(params, hn, state, mask)
        else:
            mixed, new_state = mamba2.mamba2_mixer(
                params, hn, state, mask, n_heads=lc.ssm_heads,
                d_head=lc.ssm_d_head, d_state=lc.ssm_d_state,
                n_groups=lc.ssm_groups, chunk=lc.ssm_chunk,
                eps=lc.rms_eps, live=live, kernel=lc.use_kernels)
            with scope("mixer/ssm"):
                counts["ssm_state_rows"] = (
                    jnp.asarray(n, jnp.int32) if live is None
                    else jnp.sum((live > 0).astype(jnp.int32)))
        if lc.post_norms:
            mixed = rms_norm(mixed, params["post1_w"], lc.rms_eps,
                             lc.norm_unit_offset)
        # a branch's residual sum is its group's last operation
        with scope("attn/out" if lc.mixer == "attention"
                   else "eva/out" if lc.mixer == "eva"
                   else "mixer/proj"):
            xt = _residual(xt, mixed, lc.residual_multiplier)

        with scope("moe/route" if lc.n_router else "ffn"):
            valid = None
            if mask is not None:
                valid = mask > 0
            if live is not None:
                rows = jnp.broadcast_to((live > 0)[:, None], (n, t))
                valid = rows if valid is None else valid & rows
        h2 = rms_norm(xt, params["norm2_w"], lc.rms_eps,
                      lc.norm_unit_offset).astype(wide)
        if lc.n_router:
            y, moe_counts = dropless_moe(
                params, h2.reshape(n * t, d),
                None if valid is None else valid.reshape(n * t),
                top_k=lc.top_k, experts_held=lc.held,
                kernel=lc.use_kernels, gate_rule=lc.gate_rule,
                route_scale=lc.route_scale, route_eps=lc.route_eps,
                detach_scores=lc.freeze_router)
            if not train:
                # the rows a TRAINING step's passes covered: a served
                # program returns what it returned (and the engine's
                # ``stats`` name what they named)
                del moe_counts["moe_pair_rows_worked"]
            counts.update(moe_counts,
                          moe_layer_steps=jnp.asarray(1, jnp.int32))
        else:
            with scope("ffn"):
                y = gated_ffn(h2, params["Ws_in"], params["Ws_out"])
        y = y.reshape(n, t, d)
        if lc.post_norms:
            y = rms_norm(y, params["post2_w"], lc.rms_eps,
                         lc.norm_unit_offset)
        with scope("moe/combine" if lc.n_router else "ffn"):
            xt = _residual(xt, y, lc.residual_multiplier)
            if counters is not None:
                for name, v in counts.items():
                    counters[name] = counters.get(name, 0) + v

            out = jnp.transpose(xt, (0, 2, 1))
            if mask is not None:
                out = out * mask[:, None, :].astype(out.dtype)
        return out, (None if train else new_state)
