"""ResNet50 as a ComputationGraph.

Reference analog: /root/reference/deeplearning4j-zoo/src/main/java/org/
deeplearning4j/zoo/model/ResNet50.java (graph of conv/BN/relu bottleneck
blocks with ElementWise-add shortcuts) — BASELINE.md config #2, the MFU-target
model.

TPU-first: NHWC, bf16-friendly convs (stride-2 downsampling inside blocks),
BN with running stats in state; identity vs projection shortcuts exactly as
ResNet v1. Built programmatically on GraphBuilder.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.graph import ElementWiseVertex, GraphBuilder


def _conv_bn(g, name, inp, n_out, kernel, stride=(1, 1), padding="same",
             activation="relu"):
    g.add_layer(f"{name}_conv",
                L.ConvolutionLayer(n_out=n_out, kernel=kernel, stride=stride,
                                   padding=padding, has_bias=False,
                                   weight_init="relu"), inp)
    g.add_layer(f"{name}_bn", L.BatchNormalization(activation=activation),
                f"{name}_conv")
    return f"{name}_bn"


def _bottleneck(g, name, inp, filters, stride=(1, 1), project=False):
    """1x1 reduce -> 3x3 -> 1x1 expand (4x) with shortcut add."""
    f1, f2, f3 = filters, filters, filters * 4
    x = _conv_bn(g, f"{name}_a", inp, f1, (1, 1), stride=stride)
    x = _conv_bn(g, f"{name}_b", x, f2, (3, 3))
    x = _conv_bn(g, f"{name}_c", x, f3, (1, 1), activation="identity")
    if project:
        shortcut = _conv_bn(g, f"{name}_proj", inp, f3, (1, 1), stride=stride,
                            activation="identity")
    else:
        shortcut = inp
    g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, shortcut)
    g.add_layer(f"{name}_relu", L.ActivationLayer(activation="relu"), f"{name}_add")
    return f"{name}_relu"


def resnet50(height=224, width=224, channels=3, n_classes=1000, updater=None,
             seed=12345, checkpoint_scope=None):
    """``checkpoint_scope="prefix"`` remats each bottleneck block during
    backward (nn/graph.py scope-level checkpointing): only block-boundary
    activations are stashed, the block interior recomputes."""
    g = GraphBuilder(updater=updater or U.Adam(learning_rate=1e-3), seed=seed,
                     checkpoint_scope=checkpoint_scope)
    g.add_inputs("input")
    g.set_input_types(I.ConvolutionalType(height, width, channels))

    x = _conv_bn(g, "stem", "input", 64, (7, 7), stride=(2, 2))
    g.add_layer("stem_pool", L.SubsamplingLayer(kernel=(3, 3), stride=(2, 2),
                                                padding="same", mode="max"), x)
    x = "stem_pool"

    stages = [(64, 3, (1, 1)), (128, 4, (2, 2)), (256, 6, (2, 2)), (512, 3, (2, 2))]
    for si, (filters, blocks, stride) in enumerate(stages):
        for bi in range(blocks):
            x = _bottleneck(g, f"s{si}b{bi}", x, filters,
                            stride=stride if bi == 0 else (1, 1),
                            project=bi == 0)

    g.add_layer("avgpool", L.GlobalPoolingLayer(mode="avg"), x)
    g.add_layer("fc", L.OutputLayer(n_out=n_classes, loss="mcxent",
                                    weight_init="xavier"), "avgpool")
    g.set_outputs("fc")
    return g.build()


def resnet50_mln(height=224, width=224, channels=3, n_classes=1000,
                 updater=None, seed=12345, stages=None, stem_filters=64):
    """ResNet50 as a flat MultiLayerNetwork stack of ResidualBottleneck
    composite layers (same geometry as :func:`resnet50`, block-internal
    shortcuts). This is the PIPELINABLE expression of the flagship:
    parallel/pipeline_general.PipelinedNetwork stages MultiLayerNetwork
    configs, and bottleneck blocks are stage-atomic. ``stages`` overrides
    the (filters, blocks, stride) table for reduced-size variants
    (tests / CPU-mesh loss pins)."""
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig

    stages = stages if stages is not None else [
        (64, 3, (1, 1)), (128, 4, (2, 2)), (256, 6, (2, 2)), (512, 3, (2, 2))]
    layers = [
        L.ConvolutionLayer(n_out=stem_filters, kernel=(7, 7), stride=(2, 2),
                           padding="same", has_bias=False,
                           weight_init="relu"),
        L.BatchNormalization(activation="relu"),
        L.SubsamplingLayer(kernel=(3, 3), stride=(2, 2), padding="same",
                           mode="max"),
    ]
    for filters, blocks, stride in stages:
        for bi in range(blocks):
            layers.append(L.ResidualBottleneck(
                filters=filters, stride=stride if bi == 0 else (1, 1),
                project=bi == 0))
    layers += [
        L.GlobalPoolingLayer(mode="avg"),
        L.OutputLayer(n_out=n_classes, loss="mcxent", weight_init="xavier"),
    ]
    return NeuralNetConfig(seed=seed,
                           updater=updater or U.Adam(learning_rate=1e-3)).list(
        *layers, input_type=I.ConvolutionalType(height, width, channels))


def resnet50_flops_per_example(height=224, width=224, channels=3, n_classes=1000):
    """Approximate forward FLOPs (2*MACs) for MFU accounting.

    2 x the standard ~4.1 GMAC figure at 224x224; round-2 cross-check: XLA
    cost_analysis reports 22.6 GFLOP/example for the full train step, and
    3 x this fwd estimate = 24.6 — the two agree within 9%."""
    base = 2 * 4.1e9  # fwd only, FLOPs = 2*MACs
    scale = (height * width) / (224 * 224)
    return base * scale
