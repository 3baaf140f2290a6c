"""Smaller zoo models.

Reference analogs in /root/reference/deeplearning4j-zoo/src/main/java/org/
deeplearning4j/zoo/model/: SimpleCNN.java, AlexNet.java, Darknet19.java,
TinyYOLO.java, TextGenerationLSTM.java.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig, ParamTie
from deeplearning4j_tpu.nn.initializers import Distribution

#: the language models' initialisation of every matrix
_NORMAL_02 = Distribution(kind="normal", std=0.02)


def simple_cnn(height=48, width=48, channels=3, n_classes=10, updater=None, seed=12345):
    """(reference: SimpleCNN.java)"""
    return NeuralNetConfig(seed=seed, updater=updater or U.AdaDelta()).list(
        L.ConvolutionLayer(n_out=16, kernel=(3, 3), padding="same", activation="relu"),
        L.BatchNormalization(),
        L.ConvolutionLayer(n_out=16, kernel=(3, 3), padding="same", activation="relu"),
        L.BatchNormalization(),
        L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)),
        L.DropoutLayer(rate=0.25),
        L.ConvolutionLayer(n_out=32, kernel=(3, 3), padding="same", activation="relu"),
        L.BatchNormalization(),
        L.ConvolutionLayer(n_out=32, kernel=(3, 3), padding="same", activation="relu"),
        L.BatchNormalization(),
        L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)),
        L.DropoutLayer(rate=0.25),
        L.DenseLayer(n_out=256, activation="relu"),
        L.DropoutLayer(rate=0.5),
        L.OutputLayer(n_out=n_classes, loss="mcxent"),
        input_type=I.ConvolutionalType(height, width, channels),
    )


def alexnet(height=224, width=224, channels=3, n_classes=1000, updater=None, seed=12345):
    """(reference: AlexNet.java — conv11/5/3 stack + LRN)"""
    return NeuralNetConfig(seed=seed, updater=updater or U.Nesterovs(learning_rate=0.01)).list(
        L.ConvolutionLayer(n_out=96, kernel=(11, 11), stride=(4, 4), activation="relu"),
        L.LocalResponseNormalization(),
        L.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
        L.ConvolutionLayer(n_out=256, kernel=(5, 5), padding="same", activation="relu"),
        L.LocalResponseNormalization(),
        L.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
        L.ConvolutionLayer(n_out=384, kernel=(3, 3), padding="same", activation="relu"),
        L.ConvolutionLayer(n_out=384, kernel=(3, 3), padding="same", activation="relu"),
        L.ConvolutionLayer(n_out=256, kernel=(3, 3), padding="same", activation="relu"),
        L.SubsamplingLayer(kernel=(3, 3), stride=(2, 2)),
        L.DenseLayer(n_out=4096, activation="relu", dropout=0.5),
        L.DenseLayer(n_out=4096, activation="relu", dropout=0.5),
        L.OutputLayer(n_out=n_classes, loss="mcxent"),
        input_type=I.ConvolutionalType(height, width, channels),
    )


def _darknet_conv(n_out, kernel):
    return [L.ConvolutionLayer(n_out=n_out, kernel=kernel, padding="same",
                               has_bias=False, weight_init="relu"),
            L.BatchNormalization(activation="leakyrelu")]


def darknet19(height=224, width=224, channels=3, n_classes=1000, updater=None, seed=12345):
    """(reference: Darknet19.java — conv/BN/leaky-relu backbone)"""
    layers = []
    layers += _darknet_conv(32, (3, 3))
    layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += _darknet_conv(64, (3, 3))
    layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += _darknet_conv(128, (3, 3)) + _darknet_conv(64, (1, 1)) + _darknet_conv(128, (3, 3))
    layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += _darknet_conv(256, (3, 3)) + _darknet_conv(128, (1, 1)) + _darknet_conv(256, (3, 3))
    layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += (_darknet_conv(512, (3, 3)) + _darknet_conv(256, (1, 1)) +
               _darknet_conv(512, (3, 3)) + _darknet_conv(256, (1, 1)) +
               _darknet_conv(512, (3, 3)))
    layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += (_darknet_conv(1024, (3, 3)) + _darknet_conv(512, (1, 1)) +
               _darknet_conv(1024, (3, 3)) + _darknet_conv(512, (1, 1)) +
               _darknet_conv(1024, (3, 3)))
    layers += [L.ConvolutionLayer(n_out=n_classes, kernel=(1, 1), padding="same"),
               L.GlobalPoolingLayer(mode="avg"),
               L.LossLayer(loss="mcxent", activation="softmax")]
    return NeuralNetConfig(seed=seed, updater=updater or U.Adam(learning_rate=1e-3)).list(
        *layers, input_type=I.ConvolutionalType(height, width, channels))


def tiny_yolo(height=416, width=416, channels=3, n_classes=20,
              anchors=((1.08, 1.19), (3.42, 4.41), (6.63, 11.38), (9.42, 5.11),
                       (16.62, 10.52)), updater=None, seed=12345):
    """(reference: TinyYOLO.java — darknet-tiny backbone + Yolo2OutputLayer)"""
    layers = []
    for n_out in (16, 32, 64, 128, 256):
        layers += _darknet_conv(n_out, (3, 3))
        layers += [L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2))]
    layers += _darknet_conv(512, (3, 3))
    layers += _darknet_conv(1024, (3, 3))
    layers += _darknet_conv(1024, (3, 3))
    layers += [L.ConvolutionLayer(n_out=len(anchors) * (5 + n_classes), kernel=(1, 1),
                                  padding="same"),
               L.Yolo2OutputLayer(anchors=tuple(anchors))]
    return NeuralNetConfig(seed=seed, updater=updater or U.Adam(learning_rate=1e-3)).list(
        *layers, input_type=I.ConvolutionalType(height, width, channels))


def text_generation_lstm(vocab_size, hidden=256, seq_len=64, updater=None, seed=12345):
    """Char-RNN (reference: TextGenerationLSTM.java — stacked GravesLSTM +
    RnnOutputLayer; BASELINE.md config #4)."""
    return NeuralNetConfig(seed=seed, updater=updater or U.RmsProp(learning_rate=1e-3)).list(
        L.GravesLSTM(n_out=hidden),
        L.GravesLSTM(n_out=hidden),
        L.RnnOutputLayer(n_out=vocab_size, loss="mcxent"),
        input_type=I.RecurrentType(vocab_size, seq_len),
        backprop_type="tbptt", tbptt_fwd_length=seq_len, tbptt_back_length=seq_len,
    )


def transformer_lm(vocab_size, n_layers=4, d_model=256, n_heads=4,
                   seq_len=128, mlp_ratio=4, updater=None, seed=12345):
    """Decoder-only transformer language model (net-new: the reference has
    no attention — SURVEY.md §5 long-context row; this is the long-context
    tier's flagship config and the fused-attention bench target). Input:
    [B, T] (or [B, T, 1]) integer token ids; output: per-timestep vocab
    softmax trained with cross-entropy."""
    attention = L.MultiHeadAttention(n_out=d_model, n_heads=n_heads,
                                     causal=True)
    return NeuralNetConfig(seed=seed,
                           updater=updater or U.Adam(learning_rate=3e-4)).list(
        L.EmbeddingSequenceLayer(n_in=vocab_size, n_out=d_model,
                                 add_positional=True),
        *[L.TransformerBlock(n_out=d_model, mixer=attention,
                             mlp_ratio=mlp_ratio)
          for _ in range(n_layers)],
        L.RnnOutputLayer(n_out=vocab_size, loss="mcxent"),
        input_type=I.RecurrentType(1, seq_len),
    )


def looped_lm(vocab_size, n_layers=4, d_model=2048, n_heads=16, head_dim=None,
              ffn_width=5632, passes=4, seq_len=2048, rope_theta=1e6,
              norm_eps=1e-6, beta=0.1, updater=None, seed=12345):
    """Looped decoder-only language model (Ouro, arXiv:2510.25741; net-new):
    ``n_layers`` sandwich-norm blocks (RMSNorm before and after each of a
    rotary causal attention and a gated SiLU FFN, no biases) whose ONE set
    of weights runs ``passes`` times a step, a final RMSNorm closing every
    pass, and an exit-weighted head over the passes' states
    (``LoopedLMOutputLayer``). Input: [B, T] integer token ids; labels:
    [B, T] integer next-token ids. The defaults are Ouro-2.6B's published
    widths at four of its 48 layers."""
    block = L.TransformerBlock(
        n_out=d_model,
        mixer=L.MultiHeadAttention(
            n_out=d_model, n_heads=n_heads, causal=True, bias=False,
            rope_theta=rope_theta, head_dim=head_dim,
            weight_init=_NORMAL_02),
        activation="silu", norm="rms", norm_eps=norm_eps, sandwich=True,
        bias=False, ffn="gated", ffn_width=ffn_width, weight_init=_NORMAL_02)
    return NeuralNetConfig(seed=seed,
                           updater=updater or U.Adam(learning_rate=3e-4)).list(
        L.EmbeddingSequenceLayer(n_in=vocab_size, n_out=d_model,
                                 weight_init=_NORMAL_02),
        L.LoopedStack(blocks=(block,) * n_layers, passes=passes,
                      final_norm=L.RMSNorm(eps=norm_eps)),
        L.LoopedLMOutputLayer(n_out=vocab_size, beta=beta,
                              weight_init=_NORMAL_02),
        input_type=I.RecurrentType(1, seq_len),
    )


def _hybrid_decoder(vocab_size, d_model, seq_len, layers, block, final_norm,
                    updater, seed, mtp_weight=None, block_diffusion=None):
    """The one loop behind the hybrid decoders: a token embedding, a
    pre-norm ``TransformerBlock`` for each (mixer, FFN fields) pair of
    ``layers`` (the mixer a layer object or None, the FFN fields on top of
    ``block``'s, which every layer shares), ``final_norm`` and an untied
    softmax head under ``sparse_mcxent``. With ``mtp_weight`` the
    head is a ``MultiTokenLMOutputLayer`` instead, which holds ``final_norm``'s
    gain itself (its module reads the state before that norm), runs one
    more block of the last layer's kind as its multi-token-prediction
    module, and reads the embedding table through the configuration's one
    ``ParamTie``. With ``block_diffusion`` (a ``BlockDiffusionInput``) that
    layer stands before the embedding and the head is a
    ``BlockDiffusionLMOutputLayer`` behind ``final_norm``."""
    blocks = [L.TransformerBlock(**{
        "n_out": d_model, "mixer": mixer, "activation": "silu",
        "norm": "rms", "bias": False, "weight_init": _NORMAL_02, **block,
        **ffn}) for mixer, ffn in layers]
    if block_diffusion is not None:
        head, ties = [final_norm, L.BlockDiffusionLMOutputLayer(
            n_out=vocab_size, weight_init=_NORMAL_02)], ()
    elif mtp_weight is None:
        head, ties = [final_norm,
                      L.RnnOutputLayer(n_out=vocab_size, loss="sparse_mcxent",
                                       has_bias=False,
                                       weight_init=_NORMAL_02)], ()
    else:
        head = [L.MultiTokenLMOutputLayer(
            n_out=vocab_size, block=blocks[-1], norm_eps=final_norm.eps,
            weight_init=_NORMAL_02, mtp_weight=mtp_weight)]
        ties = (ParamTie(layer=len(blocks) + 1, name="embed",
                         source_layer=0, source_name="W"),)
    return NeuralNetConfig(
        seed=seed,
        updater=updater or U.Adam(learning_rate=3e-4)).list(
        *([] if block_diffusion is None else [block_diffusion]),
        L.EmbeddingSequenceLayer(n_in=vocab_size, n_out=d_model,
                                 weight_init=_NORMAL_02),
        *blocks, *head,
        input_type=I.RecurrentType(1, seq_len), ties=ties,
    )


def hybrid_moe_lm(vocab_size, layer_types=("conv", "full_attention"),
                  num_dense_layers=1, d_model=2048, n_heads=32, n_kv_heads=8,
                  head_dim=None, ffn_width=11776, expert_width=1536,
                  n_experts=64, top_k=4, experts_held=(), conv_kernel=3,
                  routed_scale=1.0, seq_len=8192, rope_theta=1e6,
                  norm_eps=1e-5, updater=None, seed=12345):
    """Hybrid conv/attention mixture-of-experts decoder (the LFM2 family's
    ``lfm2_moe``; net-new): a token embedding, one pre-norm block a layer
    whose mixer is a gated short convolution (``"conv"``) or grouped-query
    attention with QK-norm and rotary positions (``"full_attention"``) as
    ``layer_types`` says, and whose FFN is a dense gated SiLU FFN for the
    first ``num_dense_layers`` layers and ``n_experts`` routed experts
    (top-``top_k``, sigmoid scores, an expert bias that moves the
    selection only, weights renormalised over the selected) for the rest; a final RMSNorm and an untied softmax head
    under ``sparse_mcxent``. No bias anywhere. ``experts_held`` = (first,
    end) is the share of every expert layer that this network holds (() =
    all). Input: [B, T] integer token ids; labels: [B, T] integer
    next-token ids. The defaults are LFM2-24B-A2B's published widths."""
    mixers = {
        "conv": L.ShortConv(n_out=d_model, kernel=conv_kernel,
                            weight_init=_NORMAL_02),
        "full_attention": L.MultiHeadAttention(
            n_out=d_model, n_heads=n_heads, causal=True, bias=False,
            rope_theta=rope_theta, head_dim=head_dim, n_kv_heads=n_kv_heads,
            qk_norm=True, qk_norm_eps=norm_eps, weight_init=_NORMAL_02)}
    for i, kind in enumerate(layer_types):
        if kind not in mixers:
            raise ValueError(
                f"layer_types[{i}] is one of {sorted(mixers)}, got "
                f"{kind!r}")
    dense = {"ffn": "gated", "ffn_width": ffn_width}
    moe = {"ffn": "moe", "ffn_width": expert_width, "n_experts": n_experts,
           "top_k": top_k, "experts_held": tuple(experts_held),
           "routed_scale": routed_scale}
    return _hybrid_decoder(
        vocab_size, d_model, seq_len,
        [(mixers[kind], dense if i < num_dense_layers else moe)
         for i, kind in enumerate(layer_types)],
        block={"norm_eps": norm_eps},
        final_norm=L.RMSNorm(eps=norm_eps), updater=updater, seed=seed)


def gated_delta_moe_lm(vocab_size, n_layers=48, full_attention_interval=4,
                       d_model=2048, n_heads=16, n_kv_heads=2, head_dim=256,
                       partial_rotary_factor=0.25, linear_k_heads=16,
                       linear_v_heads=32, linear_k_head_dim=128,
                       linear_v_head_dim=128, conv_kernel=4,
                       expert_width=512, shared_expert_width=512,
                       n_experts=512, top_k=10, experts_held=(),
                       seq_len=4096, rope_theta=1e7, norm_eps=1e-6,
                       updater=None, seed=12345):
    """Hybrid linear/softmax-attention mixture-of-experts decoder (the
    Qwen3-Next family's ``qwen3_next``; net-new), through the loop
    ``hybrid_moe_lm`` runs: layer ``i`` mixes by gated softmax attention
    where ``(i + 1) % full_attention_interval == 0`` (grouped-query, an
    output gate from a doubled query projection, QK-norm, rotary positions
    on the first ``partial_rotary_factor`` of a head) and by the gated
    delta rule elsewhere (``GatedDeltaNet``); every layer's FFN is
    ``n_experts`` routed experts (top-``top_k`` of a float32 softmax over
    all of them, weights renormalised over the selected, no bias) plus one
    shared expert gated by ``sigmoid(x w_sg)``; every RMS norm has its
    gain about zero (``x^ (1 + g)``). No bias anywhere, no dense layer.
    ``experts_held`` as ``hybrid_moe_lm``'s. The defaults are
    Qwen3-Next-80B-A3B's published widths and depth."""
    attention = L.MultiHeadAttention(
        n_out=d_model, n_heads=n_heads, causal=True, bias=False,
        rope_theta=rope_theta, head_dim=head_dim, n_kv_heads=n_kv_heads,
        qk_norm=True, qk_norm_eps=norm_eps, qk_norm_zero_centered=True,
        rotary_dim=int(head_dim * partial_rotary_factor), gate=True,
        weight_init=_NORMAL_02)
    delta = L.GatedDeltaNet(
        n_out=d_model, k_heads=linear_k_heads, v_heads=linear_v_heads,
        head_dim=linear_k_head_dim, v_head_dim=linear_v_head_dim,
        conv_kernel=conv_kernel, norm_eps=norm_eps, weight_init=_NORMAL_02)
    moe = {"ffn": "moe", "ffn_width": expert_width, "n_experts": n_experts,
           "top_k": top_k, "experts_held": tuple(experts_held),
           "router": "softmax", "shared_expert_width": shared_expert_width}
    return _hybrid_decoder(
        vocab_size, d_model, seq_len,
        [(attention if (i + 1) % full_attention_interval == 0 else delta,
          moe) for i in range(n_layers)],
        block={"norm_eps": norm_eps, "norm_zero_centered": True},
        final_norm=L.RMSNorm(eps=norm_eps, zero_centered=True),
        updater=updater, seed=seed)


#: NVIDIA-Nemotron-3-Nano-30B-A3B's 52 layers
NEMOTRON_3_NANO_PATTERN = \
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def state_space_moe_lm(vocab_size, pattern=NEMOTRON_3_NANO_PATTERN,
                       d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
                       ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
                       ssm_state=128, ssm_chunk=128, conv_kernel=4,
                       expert_width=1856, shared_expert_width=3712,
                       n_experts=128, top_k=6, routed_scale=2.5,
                       experts_held=(), seq_len=4096, norm_eps=1e-5,
                       updater=None, seed=12345):
    """Hybrid state-space / attention mixture-of-experts decoder (the
    Nemotron-H family's ``nemotron_h``; net-new), through the loop
    ``hybrid_moe_lm`` runs: every layer is ONE part behind one RMSNorm,
    ``h <- h + part(norm(h))``, and ``pattern`` names it a character a
    layer: ``M`` a Mamba-2 mixer (``Mamba2Mixer``), ``*`` causal
    grouped-query attention with no positional encoding, no QK-norm and
    no gate, ``E`` a mixture of ``n_experts`` UNGATED experts
    ``relu(x Wu)^2 Wd`` (top-``top_k`` by sigmoid scores plus a correction
    bias that moves the selection only, weights renormalised over the
    selected and times ``routed_scale``) plus one shared expert of the
    same form over every token, without a gate; a final RMSNorm and an
    untied softmax head under ``sparse_mcxent``. No bias but the
    convolution's. The Mamba-2 out-projections start divided by the root
    of the depth (``rescale_prenorm_residual``). ``experts_held`` as
    ``hybrid_moe_lm``'s. The defaults
    are NVIDIA-Nemotron-3-Nano-30B-A3B's published widths and its 52-layer
    pattern."""
    alone = {"ffn": "none"}
    parts = {
        "M": (L.Mamba2Mixer(
            n_out=d_model, heads=ssm_heads, head_dim=ssm_head_dim,
            groups=ssm_groups, state=ssm_state, conv_kernel=conv_kernel,
            chunk=ssm_chunk, norm_eps=norm_eps,
            out_scale=len(pattern) ** -0.5, weight_init=_NORMAL_02), alone),
        "*": (L.MultiHeadAttention(
            n_out=d_model, n_heads=n_heads, causal=True, bias=False,
            head_dim=head_dim, n_kv_heads=n_kv_heads,
            weight_init=_NORMAL_02), alone),
        "E": (None, {
            "ffn": "moe", "ffn_width": expert_width, "n_experts": n_experts,
            "top_k": top_k, "experts_held": tuple(experts_held),
            "routed_scale": routed_scale, "expert_gated": False,
            "shared_expert_width": shared_expert_width,
            "shared_expert_gate": False})}
    unknown = sorted(set(pattern) - set(parts))
    if unknown:
        raise ValueError(f"pattern is made of {sorted(parts)} (Mamba-2, "
                         f"attention, experts), got {unknown}")
    return _hybrid_decoder(
        vocab_size, d_model, seq_len, [parts[c] for c in pattern],
        block={"norm_eps": norm_eps, "activation": "relu2"},
        final_norm=L.RMSNorm(eps=norm_eps), updater=updater, seed=seed)


def latent_moe_lm(vocab_size, n_layers=47, num_dense_layers=1, d_model=2048,
                  n_heads=20, q_rank=768, kv_rank=512, nope_dim=192,
                  rope_dim=64, v_dim=256, ffn_width=10240, expert_width=1536,
                  shared_expert_width=1536, n_experts=64, top_k=4,
                  routed_scale=1.8, experts_held=(), mtp_weight=0.3,
                  seq_len=4096, rope_theta=1e6, norm_eps=1e-5, updater=None,
                  seed=12345):
    """Latent-attention mixture-of-experts decoder with a
    multi-token-prediction module (the GLM-4.7-Flash family's
    ``glm4_moe_lite``, after DeepSeek-V3; net-new), through the loop
    ``hybrid_moe_lm`` runs: every layer mixes by multi-head latent
    attention (``LatentAttention``: queries through a latent of ``q_rank``,
    keys and values from one of ``kv_rank``, a head ``nope_dim`` +
    ``rope_dim`` wide with the rotary key shared by the heads, values
    ``v_dim``); its FFN is a dense gated SiLU FFN for the first
    ``num_dense_layers`` layers and, for the rest, ``n_experts`` routed
    gated SiLU experts (top-``top_k`` by sigmoid scores plus a correction
    bias that moves the selection only, weights renormalised over the
    selected and times ``routed_scale``) plus one shared expert of the
    same form over every token, added ungated. The head is a
    ``MultiTokenLMOutputLayer``: the final RMSNorm, an untied softmax
    head, and one module of the last layer's kind that predicts the token
    after the next at ``mtp_weight``, sharing the embedding table (a
    ``ParamTie``) and the head. No bias anywhere. ``experts_held`` as
    ``hybrid_moe_lm``'s, the module's mixture too. The defaults are
    GLM-4.7-Flash's published widths and depth."""
    latent = L.LatentAttention(
        n_out=d_model, n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
        nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim, causal=True,
        rope_theta=rope_theta, norm_eps=norm_eps, weight_init=_NORMAL_02)
    dense = {"ffn": "gated", "ffn_width": ffn_width}
    moe = {"ffn": "moe", "ffn_width": expert_width, "n_experts": n_experts,
           "top_k": top_k, "experts_held": tuple(experts_held),
           "routed_scale": routed_scale,
           "shared_expert_width": shared_expert_width,
           "shared_expert_gate": False}
    return _hybrid_decoder(
        vocab_size, d_model, seq_len,
        [(latent, dense if i < num_dense_layers else moe)
         for i in range(n_layers)],
        block={"norm_eps": norm_eps},
        final_norm=L.RMSNorm(eps=norm_eps), updater=updater, seed=seed,
        mtp_weight=mtp_weight)


def block_diffusion_moe_lm(vocab_size, n_layers=48, d_model=2048, n_heads=32,
                           n_kv_heads=4, head_dim=128, expert_width=768,
                           n_experts=128, top_k=8, experts_held=(),
                           block_len=4, mask_id=None, noise_seed=0,
                           noise_eps=1e-3, recompute_experts=False,
                           seq_len=4096, rope_theta=1e6, norm_eps=1e-6,
                           updater=None, seed=12345):
    """Mixture-of-experts decoder trained by block diffusion (the SDAR
    family's ``sdar_moe``: Qwen3-MoE's layer under BD3-LM's objective,
    arXiv:2510.06303, arXiv:2503.09573; net-new), through the loop
    ``hybrid_moe_lm`` runs: every layer mixes by grouped-query attention
    with QK-norm and rotary positions and routes ``n_experts`` gated SiLU
    experts (top-``top_k`` of a float32 softmax over all of them, weights
    renormalised over the selected; no shared expert, no bias). A
    ``BlockDiffusionInput`` before the embedding masks each token of a
    block of ``block_len`` with the block's own probability (``mask_id``,
    None = the last row of the vocabulary; the draw keyed by
    ``noise_seed`` and the layer's step counter) and hands the decoder the
    noised and the clean copy side by side, 2 ``seq_len`` positions, which
    every attention layer masks by ``BlockDiffusion(seq_len, block_len)``;
    a ``BlockDiffusionLMOutputLayer`` reads the noised copy's rows.
    Outside training the network is the plain causal decoder.
    ``recompute_experts`` makes every layer's routed part again in the
    backward pass (``TransformerBlock.recompute_moe``: 2 ``seq_len``
    positions go through every mixture). Input: [B, ``seq_len``] integer
    token ids; labels: the same ids.
    ``experts_held`` as ``hybrid_moe_lm``'s. The defaults are
    SDAR-30B-A3B-Chat's published widths and depth."""
    attention = L.MultiHeadAttention(
        n_out=d_model, n_heads=n_heads, causal=True, bias=False,
        rope_theta=rope_theta, head_dim=head_dim, n_kv_heads=n_kv_heads,
        qk_norm=True, qk_norm_eps=norm_eps,
        block_diffusion=(seq_len, block_len), weight_init=_NORMAL_02)
    moe = {"ffn": "moe", "ffn_width": expert_width, "n_experts": n_experts,
           "top_k": top_k, "experts_held": tuple(experts_held),
           "router": "softmax", "recompute_moe": bool(recompute_experts)}
    return _hybrid_decoder(
        vocab_size, d_model, seq_len, [(attention, moe)] * n_layers,
        block={"norm_eps": norm_eps}, final_norm=L.RMSNorm(eps=norm_eps),
        updater=updater, seed=seed,
        block_diffusion=L.BlockDiffusionInput(
            seq_len=seq_len, block_len=block_len,
            mask_id=vocab_size - 1 if mask_id is None else mask_id,
            noise_seed=noise_seed, eps=noise_eps))
