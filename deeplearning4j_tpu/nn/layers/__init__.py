"""Runtime layer implementations (pure functions over pytrees).

Replaces reference nn/layers/** (BaseLayer.java:327 preOutput, per-type
subclasses) and the LayerFactories indirection (nn/layers/factory/*.java,
used from MultiLayerNetwork.init :351): here the "factory" is a plain
registry from conf-bean class to a stateless impl class.

Impl contract (all classmethods, all pure):
- ``init(key, conf, dtype) -> params`` — parameter pytree for one layer.
- ``init_state(conf, dtype) -> state | None`` — mutable-state pytree
  (e.g. batch-norm running stats), threaded functionally.
- ``apply(conf, params, x, state, train, rng, mask) -> (out, state)``.
- pretrainable impls add ``pretrain_value_and_grad(conf, params, x, rng)``.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import (
    attention,
    convolution,
    dense,
    embedding,
    hybrid,
    moe,
    normalization,
    pretrain,
    recurrent,
)

_IMPLS = {
    L.DenseLayer: dense.DenseImpl,
    L.OutputLayer: dense.OutputImpl,
    L.EmbeddingLayer: embedding.EmbeddingImpl,
    L.ConvolutionLayer: convolution.ConvolutionImpl,
    L.SubsamplingLayer: convolution.SubsamplingImpl,
    L.LocalResponseNormalization: normalization.LRNImpl,
    L.LayerNormalization: normalization.LayerNormImpl,
    L.BatchNormalization: normalization.BatchNormImpl,
    L.GravesLSTM: recurrent.LSTMImpl,
    L.ImageLSTM: recurrent.ImageLSTMImpl,
    L.GravesBidirectionalLSTM: recurrent.BiLSTMImpl,
    L.GRU: recurrent.GRUImpl,
    L.RnnOutputLayer: recurrent.RnnOutputImpl,
    L.RBM: pretrain.RBMImpl,
    L.AutoEncoder: pretrain.AutoEncoderImpl,
    L.RecursiveAutoEncoder: pretrain.RecursiveAutoEncoderImpl,
    attention.MultiHeadSelfAttention: attention.AttentionImpl,
    attention.TransformerBlock: attention.TransformerBlockImpl,
    moe.MoeDense: moe.MoeDenseImpl,
    hybrid.HybridMoeBlock: hybrid.HybridMoeBlockImpl,
    hybrid.TiedLMHead: hybrid.TiedLMHeadImpl,
}


def get_impl(layer_bean: L.Layer):
    """conf bean -> runtime impl (reference LayerFactories.getFactory)."""
    try:
        return _IMPLS[type(layer_bean)]
    except KeyError:
        raise ValueError(
            f"No runtime implementation for layer bean {type(layer_bean).__name__}"
        ) from None


def register_impl(bean_cls, impl_cls) -> None:
    _IMPLS[bean_cls] = impl_cls
