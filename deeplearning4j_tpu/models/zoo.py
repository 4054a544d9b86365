"""Reference-parity architectures (BASELINE.json configs)."""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import WeightInit
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.ops.losses import LossFunction


def mlp(
    sizes: Sequence[int] = (784, 500, 10),
    activation: str = "relu",
    lr: float = 0.1,
    seed: int = 12345,
    updater: Updater = Updater.NESTEROVS,
):
    """BASELINE.json configs[0]: MLP 784-500-10 on MNIST."""
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(updater)
        .momentum(0.9)
        .weight_init(WeightInit.XAVIER)
        .list()
    )
    for i in range(len(sizes) - 2):
        b.layer(
            i,
            L.DenseLayer(
                n_in=sizes[i], n_out=sizes[i + 1], activation=activation
            ),
        )
    b.layer(
        len(sizes) - 2,
        L.OutputLayer(
            n_in=sizes[-2], n_out=sizes[-1], activation="softmax",
            loss_function=LossFunction.MCXENT,
        ),
    )
    return b.build()


def lenet5(
    height: int = 28,
    width: int = 28,
    channels: int = 1,
    n_classes: int = 10,
    lr: float = 0.05,
    seed: int = 12345,
):
    """BASELINE.json configs[1]: LeNet-5-style CNN on MNIST (conv-pool-
    conv-pool-dense-out, the reference's im2col path —
    nn/layers/convolution/ConvolutionLayer.java:135 — as MXU convs)."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.NESTEROVS)
        .momentum(0.9)
        .weight_init(WeightInit.XAVIER)
        .list()
        .layer(
            0,
            L.ConvolutionLayer(
                n_out=20, kernel_size=(5, 5), stride=(1, 1),
                activation="identity",
            ),
        )
        .layer(
            1,
            L.SubsamplingLayer(
                pooling_type=L.PoolingType.MAX,
                kernel_size=(2, 2), stride=(2, 2),
            ),
        )
        .layer(
            2,
            L.ConvolutionLayer(
                n_out=50, kernel_size=(5, 5), stride=(1, 1),
                activation="identity",
            ),
        )
        .layer(
            3,
            L.SubsamplingLayer(
                pooling_type=L.PoolingType.MAX,
                kernel_size=(2, 2), stride=(2, 2),
            ),
        )
        .layer(4, L.DenseLayer(n_out=500, activation="relu"))
        .layer(
            5,
            L.OutputLayer(
                n_out=n_classes, activation="softmax",
                loss_function=LossFunction.MCXENT,
            ),
        )
        .set_input_type(InputType.convolutional(height, width, channels))
        .build()
    )


def wide_cnn(
    height: int = 32,
    width: int = 32,
    channels: int = 3,
    n_classes: int = 10,
    lr: float = 0.05,
    seed: int = 12345,
):
    """CIFAR-scale modern-width CNN (64/128-channel 3x3 convs): the
    conv-MFU control experiment — same conv machinery as lenet5 but
    with contraction sizes the 128x128 MXU can fill, demonstrating the
    framework's conv ceiling when the ARCHITECTURE permits
    (an earlier round's BENCHMARKS.md conv-MFU section)."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.NESTEROVS)
        .momentum(0.9)
        .weight_init(WeightInit.XAVIER)
        .list()
        .layer(0, L.ConvolutionLayer(
            n_out=64, kernel_size=(3, 3), stride=(1, 1),
            padding=(1, 1), activation="relu"))
        .layer(1, L.ConvolutionLayer(
            n_out=64, kernel_size=(3, 3), stride=(1, 1),
            padding=(1, 1), activation="relu"))
        .layer(2, L.SubsamplingLayer(
            pooling_type=L.PoolingType.MAX,
            kernel_size=(2, 2), stride=(2, 2)))
        .layer(3, L.ConvolutionLayer(
            n_out=128, kernel_size=(3, 3), stride=(1, 1),
            padding=(1, 1), activation="relu"))
        .layer(4, L.ConvolutionLayer(
            n_out=128, kernel_size=(3, 3), stride=(1, 1),
            padding=(1, 1), activation="relu"))
        .layer(5, L.SubsamplingLayer(
            pooling_type=L.PoolingType.MAX,
            kernel_size=(2, 2), stride=(2, 2)))
        .layer(6, L.DenseLayer(n_out=256, activation="relu"))
        .layer(7, L.OutputLayer(
            n_out=n_classes, activation="softmax",
            loss_function=LossFunction.MCXENT))
        .set_input_type(InputType.convolutional(height, width, channels))
        .build()
    )


def image_captioner(
    embed_dim: int = 32,
    n_hidden: int = 32,
    vocab: int = 64,
    lr: float = 1e-2,
    seed: int = 12345,
):
    """Karpathy-style captioning stack on the dedicated ImageLSTM
    (reference nn/layers/recurrent/ImageLSTM.java semantics — see
    nn/layers/recurrent.ImageLSTMImpl): input [N, embed_dim, 1+T] whose
    step 0 is the image embedding and steps 1.. are word embeddings; the
    ImageLSTM decodes the word steps to vocab logits [N, vocab, T],
    which the RnnOutputLayer turns into per-step softmax + MCXENT
    against next-word labels [N, vocab, T]."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.ADAM)
        .weight_init(WeightInit.XAVIER)
        .list()
        .layer(0, L.ImageLSTM(n_in=embed_dim, n_out=vocab,
                              n_hidden=n_hidden, activation="tanh"))
        .layer(
            1,
            L.RnnOutputLayer(
                n_in=vocab, n_out=vocab, activation="softmax",
                loss_function=LossFunction.MCXENT,
            ),
        )
        .build()
    )


def lstm_classifier(
    n_in: int,
    n_hidden: int,
    n_classes: int,
    lr: float = 0.05,
    seed: int = 12345,
):
    """Sequence classifier: GravesLSTM -> RnnOutputLayer."""
    return (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.ADAM)
        .activation("tanh")
        .list()
        .layer(0, L.GravesLSTM(n_in=n_in, n_out=n_hidden))
        .layer(
            1,
            L.RnnOutputLayer(
                n_in=n_hidden, n_out=n_classes, activation="softmax",
                loss_function=LossFunction.MCXENT,
            ),
        )
        .build()
    )


def transformer_lm(
    n_in: int = 64,
    width: int = 128,
    n_layers: int = 4,
    n_heads: int = 4,
    n_classes: int = 64,
    lr: float = 1e-3,
    seed: int = 12345,
    ring_axis=None,
    remat: bool = False,
):
    """Causal transformer over [N, C, T] sequences — the long-context
    flagship. NEW capability vs the reference (2015, pre-attention;
    SURVEY.md §5.7 mandates first-class long-context): stacked causal
    multi-head self-attention; ``ring_axis`` turns every attention core
    into ring attention over that mesh axis (sequence parallelism over
    ICI), and ``remat`` rematerializes per-layer activations so depth x
    sequence-length activation memory stays within HBM."""
    from deeplearning4j_tpu.nn.layers.attention import (
        MultiHeadSelfAttention,
    )

    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.ADAM)
        .activation("identity")
        .weight_init(WeightInit.XAVIER)
        .list()
    )
    for i in range(n_layers):
        b.layer(
            i,
            MultiHeadSelfAttention(
                n_in=n_in if i == 0 else width,
                n_out=width,
                n_heads=n_heads,
                causal=True,
                ring_axis=ring_axis,
            ),
        )
    b.layer(
        n_layers,
        L.RnnOutputLayer(
            n_in=width, n_out=n_classes, activation="softmax",
            loss_function=LossFunction.MCXENT,
        ),
    )
    return b.remat(remat).build()


def transformer_lm_flagship(
    vocab: int = 64,
    width: int = 1024,
    n_layers: int = 8,
    n_heads: int = 16,
    lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 1000,
    seed: int = 12345,
    remat: bool = False,
    ring_axis=None,
):
    """The convergence-grade flagship: pre-LN TransformerBlock stack
    (attention + 4x FFN + residuals, nn/layers/attention.py) with Adam
    and linear-warmup + cosine lr decay. Unlike the bare-attention
    ``transformer_lm`` (which diverges at width >= 1024 under any flat
    lr — an earlier round's BENCHMARKS.md flagship section), this configuration trains
    stably at MXU-filling widths; an earlier round's bench.py gates it against the
    analytic Markov entropy floor (datasets/markov.py) at >= 40% MFU.
    """
    from deeplearning4j_tpu.nn.layers.attention import TransformerBlock

    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .lr_policy("warmup_cosine")
        .lr_warmup_steps(warmup_steps)
        .lr_total_steps(total_steps)
        .updater(Updater.ADAM)
        .activation("identity")
        .weight_init(WeightInit.XAVIER)
        .list()
    )
    for i in range(n_layers):
        b.layer(
            i,
            TransformerBlock(
                n_in=vocab if i == 0 else width,
                n_out=width,
                n_heads=n_heads,
                causal=True,
                ring_axis=ring_axis,
            ),
        )
    b.layer(n_layers, L.LayerNormalization(n_in=width, n_out=width))
    b.layer(
        n_layers + 1,
        L.RnnOutputLayer(
            n_in=width, n_out=vocab, activation="softmax",
            loss_function=LossFunction.MCXENT,
        ),
    )
    return b.remat(remat).build()


def granite_moe_hybrid_lm(
    vocab_size: int = 64,
    hidden_size: int = 64,
    layer_types: Sequence[str] = ("mamba", "attention", "mamba"),
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    attention_multiplier: float = 0.0,
    mamba_n_heads: int = 8,
    mamba_d_head: int = 16,
    mamba_d_state: int = 16,
    mamba_n_groups: int = 1,
    mamba_d_conv: int = 4,
    mamba_chunk_size: int = 256,
    num_local_experts: int = 8,
    num_experts_per_tok: int = 2,
    intermediate_size: int = 32,
    shared_intermediate_size: int = 64,
    experts_held=None,
    embedding_multiplier: float = 1.0,
    residual_multiplier: float = 1.0,
    logits_scaling: float = 1.0,
    rms_norm_eps: float = 1e-5,
    max_position_embeddings: int = 512,
    initializer_range: float = 0.02,
    dtype: str = "float32",
    seed: int = 12345,
):
    """A ``granitemoehybrid`` LM (HF ``GraniteMoeHybridForCausalLM``),
    served only, under its config's own key names: the token embedding
    (``EmbeddingLayer``, ``sequence``), one ``HybridMoeBlock`` a ``layer_types`` entry (``"mamba"`` = the
    Mamba-2 mixer, ``"attention"`` = grouped-KV attention with no
    positional term), a tied head (nn/layers/hybrid.py).
    ``num_local_experts`` is the router's width; ``experts_held`` the
    ``[lo, hi)`` of them whose experts this chip holds (None = all);
    ``vocab_size`` the rows of the vocabulary held. ``dtype`` is the
    resident and compute dtype (the published one is bfloat16).
    ``max_position_embeddings`` bounds the attention cache window."""
    from deeplearning4j_tpu.nn.conf.distribution import NormalDistribution
    from deeplearning4j_tpu.nn.layers.hybrid import (
        HybridMoeBlock,
        TiedLMHead,
    )

    mixers = {"mamba": "mamba2", "attention": "attention"}
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .updater(Updater.ADAM)
        .activation("identity")
        .list()
    )
    b.layer(0, L.EmbeddingLayer(
        n_in=vocab_size, n_out=hidden_size, sequence=True,
        multiplier=embedding_multiplier,
        weight_init=WeightInit.DISTRIBUTION,
        dist=NormalDistribution(0.0, initializer_range)))
    for i, kind in enumerate(layer_types):
        b.layer(i + 1, HybridMoeBlock(
            n_in=hidden_size, n_out=hidden_size, mixer=mixers[kind],
            rms_eps=rms_norm_eps,
            residual_multiplier=residual_multiplier,
            n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads,
            attention_multiplier=attention_multiplier,
            stream_max_t=max_position_embeddings,
            ssm_heads=mamba_n_heads, ssm_d_head=mamba_d_head,
            ssm_d_state=mamba_d_state, ssm_groups=mamba_n_groups,
            ssm_d_conv=mamba_d_conv, ssm_chunk=mamba_chunk_size,
            n_router=num_local_experts, top_k=num_experts_per_tok,
            d_expert=intermediate_size,
            d_shared=shared_intermediate_size,
            experts_held=(None if experts_held is None
                          else tuple(experts_held)),
            init_std=initializer_range))
    b.layer(len(layer_types) + 1, TiedLMHead(
        n_in=hidden_size, n_out=vocab_size, tie_to=0,
        logits_scaling=logits_scaling, rms_eps=rms_norm_eps,
        activation="softmax", loss_function=LossFunction.MCXENT))
    conf = b.build()
    for c in conf.confs:
        c.dtype = dtype
    return conf


def afmoe_lm(
    vocab_size: int = 64,
    hidden_size: int = 64,
    layer_types: Sequence[str] = ("sliding_attention", "sliding_attention",
                                  "sliding_attention", "full_attention"),
    layers: Optional[Sequence[int]] = None,
    num_dense_layers: int = 1,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    head_dim: int = 16,
    sliding_window: int = 32,
    rope_theta: float = 10000.0,
    intermediate_size: int = 128,
    moe_intermediate_size: int = 32,
    num_experts: int = 8,
    num_experts_per_tok: int = 2,
    num_shared_experts: int = 1,
    route_scale: float = 1.0,
    experts_held=None,
    mup_enabled: bool = True,
    rms_norm_eps: float = 1e-5,
    max_position_embeddings: int = 512,
    initializer_range: float = 0.02,
    dtype: str = "float32",
    seed: int = 12345,
):
    """An ``afmoe`` LM (HF ``AfmoeForCausalLM``, Trinity), served only,
    under its config's own key names: the token embedding (times
    ``sqrt(hidden_size)`` where ``mup_enabled``), one ``HybridMoeBlock``
    a layer with QK-norm, gated attention and the four sandwich norms,
    an untied head. ``layer_types[i]`` says layer ``i``'s attention:
    ``"sliding_attention"`` (window ``sliding_window``, rotary
    positions) or ``"full_attention"`` (no positional term, the whole
    context up to ``max_position_embeddings``); layers below
    ``num_dense_layers`` have a dense gated feed-forward of width
    ``intermediate_size``, the others sigmoid top-k routing over
    ``num_experts`` outputs plus ``num_shared_experts`` shared experts
    of width ``moe_intermediate_size``. ``layers`` picks which published
    layer indices are built (None = all of ``layer_types``), each keeping
    the kind its index has; ``experts_held`` the ``[lo, hi)`` of the
    router's outputs whose experts this chip holds."""
    from deeplearning4j_tpu.nn.conf.distribution import NormalDistribution
    from deeplearning4j_tpu.nn.layers.hybrid import (
        HybridMoeBlock,
        TiedLMHead,
    )

    picked = list(range(len(layer_types))) if layers is None else list(
        layers)
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .updater(Updater.ADAM)
        .activation("identity")
        .list()
    )
    b.layer(0, L.EmbeddingLayer(
        n_in=vocab_size, n_out=hidden_size, sequence=True,
        multiplier=(hidden_size ** 0.5 if mup_enabled else 1.0),
        weight_init=WeightInit.DISTRIBUTION,
        dist=NormalDistribution(0.0, initializer_range)))
    for i, idx in enumerate(picked):
        sliding = layer_types[idx] == "sliding_attention"
        dense = idx < num_dense_layers
        b.layer(i + 1, HybridMoeBlock(
            n_in=hidden_size, n_out=hidden_size, mixer="attention",
            rms_eps=rms_norm_eps, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, d_head=head_dim,
            stream_max_t=(sliding_window if sliding
                          else max_position_embeddings),
            sliding=sliding, rope_theta=rope_theta if sliding else 0.0,
            qk_norm=True, gated_attention=True, post_norms=True,
            n_router=0 if dense else num_experts,
            top_k=num_experts_per_tok, d_expert=moe_intermediate_size,
            d_shared=(intermediate_size if dense
                      else num_shared_experts * moe_intermediate_size),
            experts_held=(None if dense or experts_held is None
                          else tuple(experts_held)),
            gate_rule="sigmoid_bias", route_scale=route_scale,
            init_std=initializer_range))
    b.layer(len(picked) + 1, TiedLMHead(
        n_in=hidden_size, n_out=vocab_size, tie_to=None,
        rms_eps=rms_norm_eps, init_std=initializer_range,
        activation="softmax", loss_function=LossFunction.MCXENT))
    conf = b.build()
    for c in conf.confs:
        c.dtype = dtype
    return conf


def evabyte_lm(
    vocab_size: int = 320,
    hidden_size: int = 64,
    num_hidden_layers: int = 2,
    num_attention_heads: int = 4,
    head_dim: int = 16,
    intermediate_size: int = 128,
    window_size: int = 32,
    chunk_size: int = 4,
    num_pred_heads: int = 8,
    rope_theta: float = 100000.0,
    rms_norm_eps: float = 1e-5,
    max_position_embeddings: int = 512,
    norm_add_unit_offset: bool = True,
    fp32_skip_add: bool = True,
    initializer_range: float = 0.02,
    dtype: str = "float32",
    seed: int = 12345,
):
    """An ``evabyte`` LM (HF ``EvaByteForCausalLM``, ``attention_class``
    ``eva``), served or scored, under its config's own key names: the
    byte embedding, one ``HybridMoeBlock(mixer="eva")`` a layer (EVA
    attention over an aligned window of ``window_size`` bytes and one
    learned summary a ``chunk_size`` bytes of every earlier window,
    rotary positions, a dense SwiGLU of ``intermediate_size``; RMSNorm
    with the unit offset, the residual sum in float32), and an untied
    head of ``num_pred_heads x vocab_size`` rows of which head 0, the
    next byte's, is what ``output`` and the serving engine read
    (``TiedLMHeadImpl.all_logits`` has all of them; multi-byte
    self-speculation is not built). ``max_position_embeddings`` is the
    longest context a streaming state has room for."""
    from deeplearning4j_tpu.nn.conf.distribution import NormalDistribution
    from deeplearning4j_tpu.nn.layers.hybrid import (
        HybridMoeBlock,
        TiedLMHead,
    )

    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .updater(Updater.ADAM)
        .activation("identity")
        .list()
    )
    b.layer(0, L.EmbeddingLayer(
        n_in=vocab_size, n_out=hidden_size, sequence=True,
        weight_init=WeightInit.DISTRIBUTION,
        dist=NormalDistribution(0.0, initializer_range)))
    for i in range(num_hidden_layers):
        b.layer(i + 1, HybridMoeBlock(
            n_in=hidden_size, n_out=hidden_size, mixer="eva",
            rms_eps=rms_norm_eps, n_heads=num_attention_heads,
            n_kv_heads=num_attention_heads, d_head=head_dim,
            rope_theta=rope_theta, eva_window=window_size,
            eva_chunk=chunk_size, stream_max_t=max_position_embeddings,
            norm_unit_offset=norm_add_unit_offset,
            fp32_residual=fp32_skip_add, n_router=0,
            d_shared=intermediate_size, init_std=initializer_range))
    b.layer(num_hidden_layers + 1, TiedLMHead(
        n_in=hidden_size, n_out=vocab_size, tie_to=None,
        n_pred_heads=num_pred_heads,
        norm_unit_offset=norm_add_unit_offset, rms_eps=rms_norm_eps,
        init_std=initializer_range, activation="softmax",
        loss_function=LossFunction.MCXENT))
    conf = b.build()
    for c in conf.confs:
        c.dtype = dtype
    return conf


def lfm2_moe_lm(
    vocab_size: int = 64,
    hidden_size: int = 64,
    layer_types: Sequence[str] = ("conv", "conv", "full_attention", "conv"),
    layers: Optional[Sequence[int]] = None,
    num_dense_layers: int = 1,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    conv_L_cache: int = 3,
    rope_theta: float = 1000000.0,
    intermediate_size: int = 128,
    moe_intermediate_size: int = 32,
    num_experts: int = 8,
    num_experts_per_tok: int = 2,
    routed_scaling_factor: float = 1.0,
    experts_held=None,
    freeze_router: bool = False,
    norm_eps: float = 1e-5,
    max_position_embeddings: int = 512,
    initializer_range: float = 0.02,
    dtype: str = "float32",
    compute_dtype: Optional[str] = None,
    lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 1000,
    remat: bool = False,
    seed: int = 12345,
):
    """An ``lfm2_moe`` LM (HF ``Lfm2MoeForCausalLM``, LFM2-8B-A1B),
    trained, under its config's own key names: the token embedding, one
    ``HybridMoeBlock`` a layer, a head tied to the embedding.
    ``layer_types[i]`` says layer ``i``'s mixer: ``"conv"``, the gated
    short convolution of ``conv_L_cache`` taps, or
    ``"full_attention"``, grouped-KV causal attention of
    ``hidden_size / num_attention_heads`` a head with QK-norm and
    rotary positions. Layers below ``num_dense_layers`` have a dense
    gated feed-forward of ``intermediate_size``, the others sigmoid
    top-k routing (a selection bias, the picked scores normalised over
    their sum plus 1e-6, times ``routed_scaling_factor``) over
    ``num_experts`` outputs with no shared expert. ``layers`` picks
    which published layer indices are built (None = all of
    ``layer_types``); ``experts_held`` the ``[lo, hi)`` of the router's
    outputs whose experts this chip holds. ``freeze_router`` takes the
    routing out of learning (the gates constants to the gradient, the
    routers' weights not moved): one chip's share trained WITHOUT the
    exchange adds only its held experts to the output, so any gradient
    through the gates teaches the stack to pick them.

    It trains through ``fit_scan`` / ``fit`` on token ids
    ``[.., N, T]`` and label ids of the same shape, with Adam under a
    linear warm-up and cosine decay; ``compute_dtype`` "bfloat16" keeps
    float32 masters and computes in bfloat16; ``remat`` recomputes a
    layer's activations on the way back."""
    from deeplearning4j_tpu.nn.conf.distribution import NormalDistribution
    from deeplearning4j_tpu.nn.layers.hybrid import (
        HybridMoeBlock,
        TiedLMHead,
    )

    mixers = {"conv": "short_conv", "full_attention": "attention"}
    picked = list(range(len(layer_types))) if layers is None else list(
        layers)
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .lr_policy("warmup_cosine")
        .lr_warmup_steps(warmup_steps)
        .lr_total_steps(total_steps)
        .updater(Updater.ADAM)
        .activation("identity")
        .list()
    )
    b.layer(0, L.EmbeddingLayer(
        n_in=vocab_size, n_out=hidden_size, sequence=True,
        weight_init=WeightInit.DISTRIBUTION,
        dist=NormalDistribution(0.0, initializer_range)))
    for i, idx in enumerate(picked):
        dense = idx < num_dense_layers
        b.layer(i + 1, HybridMoeBlock(
            n_in=hidden_size, n_out=hidden_size,
            mixer=mixers[layer_types[idx]], rms_eps=norm_eps,
            n_heads=num_attention_heads, n_kv_heads=num_key_value_heads,
            stream_max_t=max_position_embeddings, rope_theta=rope_theta,
            qk_norm=True, conv_kernel=conv_L_cache,
            n_router=0 if dense else num_experts,
            top_k=num_experts_per_tok, d_expert=moe_intermediate_size,
            d_shared=intermediate_size if dense else 0,
            experts_held=(None if dense or experts_held is None
                          else tuple(experts_held)),
            gate_rule="sigmoid_bias", route_scale=routed_scaling_factor,
            route_eps=1e-6, freeze_router=freeze_router,
            init_std=initializer_range))
    b.layer(len(picked) + 1, TiedLMHead(
        n_in=hidden_size, n_out=vocab_size, tie_to=0, rms_eps=norm_eps,
        init_std=initializer_range, activation="softmax",
        loss_function=LossFunction.MCXENT))
    conf = b.remat(remat).build()
    for c in conf.confs:
        c.dtype = dtype
        if compute_dtype:
            c.compute_dtype = compute_dtype
    return conf


def moe_transformer_lm(
    n_in: int = 64,
    width: int = 128,
    n_blocks: int = 2,
    n_heads: int = 4,
    n_classes: int = 64,
    n_experts: int = 8,
    n_hidden: int = 0,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    lr: float = 1e-3,
    seed: int = 12345,
    ring_axis=None,
    ep_axis=None,
    remat: bool = False,
):
    """Mixture-of-experts transformer: each block is causal multi-head
    self-attention followed by a residual capacity-routed MoE FFN
    (nn/layers/moe.py). ``ep_axis`` shards experts over that mesh axis
    with explicit all-to-all dispatch (parallel/expert_parallel.py);
    ``ring_axis`` adds ring-attention sequence parallelism — the two
    compose for the dryrun's ep mesh."""
    from deeplearning4j_tpu.nn.layers.attention import (
        MultiHeadSelfAttention,
    )
    from deeplearning4j_tpu.nn.layers.moe import MoeDense

    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(Updater.ADAM)
        .activation("identity")
        .weight_init(WeightInit.XAVIER)
        .list()
    )
    li = 0
    for blk in range(n_blocks):
        b.layer(
            li,
            MultiHeadSelfAttention(
                n_in=n_in if blk == 0 else width,
                n_out=width,
                n_heads=n_heads,
                causal=True,
                ring_axis=ring_axis,
            ),
        )
        li += 1
        b.layer(
            li,
            MoeDense(
                n_in=width, n_out=width,
                n_experts=n_experts, n_hidden=n_hidden,
                capacity_factor=capacity_factor, top_k=top_k,
                ep_axis=ep_axis,
            ),
        )
        li += 1
    b.layer(
        li,
        L.RnnOutputLayer(
            n_in=width, n_out=n_classes, activation="softmax",
            loss_function=LossFunction.MCXENT,
        ),
    )
    return b.remat(remat).build()


def dbn(
    sizes: Sequence[int] = (784, 500, 250, 10),
    lr: float = 0.05,
    seed: int = 12345,
    updater: Updater = Updater.SGD,
    momentum: float = 0.9,
):
    """BASELINE.json configs[3]: DBN — stacked RBMs + softmax output,
    pretrain+finetune (reference MultiLayerNetwork.pretrain :150).
    ``momentum`` only takes effect with ``updater=Updater.NESTEROVS``
    (plain SGD, the reference-faithful default, ignores it)."""
    b = (
        NeuralNetConfiguration.Builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(updater)
        .momentum(momentum)
        .activation("sigmoid")
        .list()
    )
    for i in range(len(sizes) - 2):
        b.layer(
            i,
            L.RBM(
                n_in=sizes[i], n_out=sizes[i + 1],
                hidden_unit=L.HiddenUnit.BINARY,
                visible_unit=L.VisibleUnit.BINARY,
                loss_function=LossFunction.RECONSTRUCTION_CROSSENTROPY,
            ),
        )
    b.layer(
        len(sizes) - 2,
        L.OutputLayer(
            n_in=sizes[-2], n_out=sizes[-1], activation="softmax",
            loss_function=LossFunction.MCXENT,
        ),
    )
    return b.pretrain(True).backprop(True).build()
