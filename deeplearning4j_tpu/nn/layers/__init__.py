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
from deeplearning4j_tpu.nn.layers.attention import (  # noqa: F401
    GatedDeltaNet, LatentAttention, LayerNormalization, Mamba2Mixer,
    MultiHeadAttention, RMSNorm, ShortConv, TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.looped import (  # noqa: F401
    LoopedLMOutputLayer, LoopedStack,
)
from deeplearning4j_tpu.nn.layers.multitoken import (  # noqa: F401
    MultiTokenLMOutputLayer,
)
from deeplearning4j_tpu.nn.layers.moe import MoETransformerBlock  # noqa: F401
