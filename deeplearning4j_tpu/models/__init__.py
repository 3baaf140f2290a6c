"""Model zoo (reference: deeplearning4j-zoo, SURVEY.md §2.6)."""

from deeplearning4j_tpu.models.lenet import lenet  # noqa: F401
from deeplearning4j_tpu.models.resnet import (  # noqa: F401
    resnet50, resnet50_mln)
from deeplearning4j_tpu.models.vgg import vgg16, vgg19  # noqa: F401
from deeplearning4j_tpu.models.misc import (  # noqa: F401
    alexnet, block_diffusion_moe_lm, darknet19, gated_delta_moe_lm,
    hybrid_moe_lm, latent_moe_lm,
    looped_lm,
    simple_cnn, state_space_moe_lm,
    text_generation_lstm, tiny_yolo, transformer_lm,
)
from deeplearning4j_tpu.models.inception import (  # noqa: F401
    facenet_nn4_small2, googlenet, inception_resnet_v1,
)
from deeplearning4j_tpu.models.zoo import (  # noqa: F401
    PretrainedType, ZooModel, get_model, init_pretrained, model_names,
    register_model,
)
