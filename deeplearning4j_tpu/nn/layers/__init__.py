from deeplearning4j_tpu.nn.layers.base import Layer, ParamLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.core import (  # noqa: F401
    DenseLayer, OutputLayer, LossLayer, ActivationLayer, DropoutLayer,
    EmbeddingLayer, EmbeddingSequenceLayer, AutoEncoder,
    TimeDistributedDenseLayer,
)
from deeplearning4j_tpu.nn.layers.conv import (  # noqa: F401
    ConvolutionLayer, Convolution1DLayer, Deconvolution2DLayer,
    SeparableConvolution2DLayer, SubsamplingLayer, Subsampling1DLayer,
    Upsampling1DLayer, Upsampling2DLayer, ZeroPaddingLayer, ZeroPadding1DLayer,
    BatchNormalization, LocalResponseNormalization, GlobalPoolingLayer,
    SpaceToDepthLayer, SpaceToBatchLayer, ResidualBottleneck,
)
from deeplearning4j_tpu.nn.layers.rnn import (  # noqa: F401
    LSTM, GravesLSTM, GravesBidirectionalLSTM, SimpleRnn, RnnOutputLayer,
    RnnLossLayer, LastTimeStep, Bidirectional,
)
from deeplearning4j_tpu.nn.layers.vae import (  # noqa: F401
    VariationalAutoencoder, GaussianReconstruction, BernoulliReconstruction,
    ExponentialReconstruction, CompositeReconstruction,
    LossWrapperReconstruction,
)
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.centerloss import CenterLossOutputLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.norms import (  # noqa: F401
    LayerNormalization, RMSNorm,
)
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention  # noqa: F401
from deeplearning4j_tpu.nn.layers.mixers.short_conv import ShortConv  # noqa: F401
from deeplearning4j_tpu.nn.layers.mixers.gated_delta import GatedDeltaNet  # noqa: F401
from deeplearning4j_tpu.nn.layers.mixers.mamba2 import Mamba2Mixer  # noqa: F401
from deeplearning4j_tpu.nn.layers.mixers.latent_attention import (  # noqa: F401
    LatentAttention,
)
from deeplearning4j_tpu.nn.layers.block import TransformerBlock  # noqa: F401
from deeplearning4j_tpu.nn.layers.looped import (  # noqa: F401
    LoopedLMOutputLayer, LoopedStack,
)
from deeplearning4j_tpu.nn.layers.multitoken import (  # noqa: F401
    MultiTokenLMOutputLayer,
)
from deeplearning4j_tpu.nn.layers.block_diffusion import (  # noqa: F401
    BlockDiffusionInput, BlockDiffusionLMOutputLayer,
)
from deeplearning4j_tpu.nn.layers.moe import MoETransformerBlock  # noqa: F401
