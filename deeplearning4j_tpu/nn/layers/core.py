"""Core feed-forward layers: Dense, Output/Loss, Activation, Dropout, Embedding, AutoEncoder.

Reference analogs in /root/reference/deeplearning4j-nn/src/main/java/org/
deeplearning4j/nn/: conf/layers/DenseLayer.java + layers/BaseLayer.java:123
(preOutput: z = xW + b), conf/layers/OutputLayer.java + layers/BaseOutputLayer
(loss attached), conf/layers/EmbeddingLayer.java, conf/layers/AutoEncoder.java.

TPU notes: matmuls run in the compute dtype (bf16 on TPU) with f32
accumulation via preferred_element_type — the MXU-native path. The embedding
forward is a gather (jnp.take), whose VJP is a scatter-add that XLA lowers
natively; no host round-trip like the reference's JNI hop.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer, Layer
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config


def matmul(x, w):
    """Compute-dtype matmul with f32 accumulation (MXU path); float64 stays
    float64 for gradient checking."""
    cd, ad = _dtypes.compute_dtypes_for(x.dtype)
    return lax.dot(x.astype(cd), w.astype(cd), preferred_element_type=ad)


@register_config
@dataclasses.dataclass(frozen=True)
class DenseLayer(ParamLayer):
    n_out: int = 0
    has_bias: bool = True

    input_family = _inputs.FeedForwardType

    def output_type(self, input_type):
        it = _inputs.adapted_type(input_type, _inputs.FeedForwardType)
        return _inputs.FeedForwardType(self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = _inputs.adapted_type(input_type, _inputs.FeedForwardType).size
        p = {"W": _init.init_weight(self.weight_init, key, (n_in, self.n_out),
                                    n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def pre_output(self, params, x):
        """z = xW + b (reference: BaseLayer.preOutput)."""
        z = matmul(x, params["W"])
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.activation_fn()(self.pre_output(params, x)), state


@register_config
@dataclasses.dataclass(frozen=True)
class OutputLayer(DenseLayer):
    """Dense + loss head (reference: conf/layers/OutputLayer.java; score at
    MultiLayerNetwork.java:2307). ``compute_loss`` takes the activations;
    under softmax with a cross-entropy the networks' ``loss_fn`` takes the
    loss from ``pre_output`` instead (``losses.from_logits``)."""

    loss: object = "mcxent"
    activation: object = dataclasses.field(default="softmax", kw_only=True)

    def compute_loss(self, predictions, labels, mask=None):
        return _losses.get(self.loss)(predictions, labels, mask)


@register_config
@dataclasses.dataclass(frozen=True)
class LossLayer(Layer):
    """Parameterless loss head (reference: conf/layers/LossLayer.java)."""

    loss: object = "mcxent"
    activation: object = "identity"

    input_family = _inputs.FeedForwardType

    def output_type(self, input_type):
        return _inputs.adapted_type(input_type, _inputs.FeedForwardType)

    def apply(self, params, state, x, *, train=False, rng=None):
        from deeplearning4j_tpu.nn import activations as _act
        return _act.get(self.activation)(x), state

    def compute_loss(self, predictions, labels, mask=None):
        return _losses.get(self.loss)(predictions, labels, mask)


@register_config
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """(reference: conf/layers/ActivationLayer.java)"""

    activation: object = "relu"

    input_family = None  # accepts any family unchanged

    def output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, *, train=False, rng=None):
        from deeplearning4j_tpu.nn import activations as _act
        return _act.get(self.activation)(x), state


@register_config
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout (reference: conf/layers/DropoutLayer.java). The
    ``kind`` selects the reference's dropout variants (nn/conf/dropout/):
    dropout | alpha (SELU-preserving) | gaussian_dropout | gaussian_noise."""

    rate: float = 0.5
    kind: str = "dropout"

    input_family = None  # accepts any family unchanged

    def output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate <= 0.0 or rng is None:
            return x, state
        import jax
        if self.kind == "dropout":
            keep = 1.0 - self.rate
            mask = jax.random.bernoulli(rng, keep, x.shape)
            return jnp.where(mask, x / keep, 0.0), state
        if self.kind == "alpha":
            # SELU alpha-dropout (reference: nn/conf/dropout/AlphaDropout.java)
            alpha_p = -1.7580993408473766
            keep = 1.0 - self.rate
            a = (keep + alpha_p**2 * keep * (1 - keep)) ** -0.5
            b = -a * alpha_p * (1 - keep)
            mask = jax.random.bernoulli(rng, keep, x.shape)
            return a * jnp.where(mask, x, alpha_p) + b, state
        if self.kind == "gaussian_dropout":
            std = (self.rate / (1.0 - self.rate)) ** 0.5
            noise = 1.0 + std * jax.random.normal(rng, x.shape, x.dtype)
            return x * noise, state
        if self.kind == "gaussian_noise":
            return x + self.rate * jax.random.normal(rng, x.shape, x.dtype), state
        raise ValueError(f"Unknown dropout kind {self.kind!r}")


@register_config
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(ParamLayer):
    """Index -> vector lookup (reference: conf/layers/EmbeddingLayer.java;
    input is integer class indices, output [batch, n_out]).

    Forward = gather; backward = scatter-add, both native XLA ops on TPU
    (the reference routes this through libnd4j JNI)."""

    n_in: int = 0  # vocab size
    n_out: int = 0
    has_bias: bool = False
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.FeedForwardType

    def output_type(self, input_type):
        return _inputs.FeedForwardType(self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        p = {"W": _init.init_weight(self.weight_init, key, (self.n_in, self.n_out),
                                    self.n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def apply(self, params, state, x, *, train=False, rng=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        z = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            z = z + params["b"]
        return self.activation_fn()(z), state


@register_config
@dataclasses.dataclass(frozen=True)
class TimeDistributedDenseLayer(DenseLayer):
    """Dense applied independently at every timestep: [B, T, F] ->
    [B, T, n_out], time axis preserved (reference analog: Keras-1
    TimeDistributedDense / DL4J's DenseLayer wrapped in RnnToFeedForward +
    FeedForwardToRnn preprocessors — here the matmul simply broadcasts
    over the leading axes, no fold/unfold round-trip)."""

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        p = {"W": _init.init_weight(self.weight_init, key,
                                    (n_in, self.n_out),
                                    n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def pre_output(self, params, x):
        b, t, f = x.shape
        z = matmul(x.reshape(b * t, f), params["W"]).reshape(
            b, t, self.n_out)
        if self.has_bias:
            z = z + params["b"]
        return z


@register_config
@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(ParamLayer):
    """Per-timestep index -> vector lookup for sequence models: [B, T] (or
    [B, T, 1]) integer ids -> [B, T, n_out], with an optional learned
    positional embedding added (reference analog: EmbeddingSequenceLayer —
    the sequence form of EmbeddingLayer; positions are net-new for the
    transformer tier)."""

    n_in: int = 0   # vocab size
    n_out: int = 0
    add_positional: bool = False
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        import jax
        k1, k2 = jax.random.split(key)
        p = {"W": _init.init_weight(self.weight_init, k1,
                                    (self.n_in, self.n_out),
                                    self.n_in, self.n_out, dtype)}
        if self.add_positional:
            if input_type.timesteps is None:
                raise ValueError("add_positional requires a fixed timesteps "
                                 "in the RecurrentType input")
            p["P"] = _init.init_weight(
                self.weight_init, k2, (input_type.timesteps, self.n_out),
                input_type.timesteps, self.n_out, dtype)
        return p

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3:
            idx = idx[..., 0]
        z = jnp.take(params["W"], idx, axis=0)      # [B, T, D]
        if "P" in params:
            z = z + params["P"][None, :z.shape[1]]
        if mask is not None:
            z = z * mask[..., None].astype(z.dtype)
        return self.activation_fn()(z), state


@register_config
@dataclasses.dataclass(frozen=True)
class AutoEncoder(ParamLayer):
    """Denoising autoencoder layer (reference: conf/layers/AutoEncoder.java +
    layers/feedforward/autoencoder/AutoEncoder.java). In supervised stacks it
    behaves as a dense encoder; ``reconstruct``/``pretrain_loss`` expose the
    unsupervised path (corrupt -> encode -> decode -> reconstruction loss)."""

    n_out: int = 0
    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: object = "mse"
    activation: object = dataclasses.field(default="sigmoid", kw_only=True)

    input_family = _inputs.FeedForwardType

    def output_type(self, input_type):
        return _inputs.FeedForwardType(self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        import jax
        n_in = _inputs.adapted_type(input_type, _inputs.FeedForwardType).size
        k1, _ = jax.random.split(key)
        return {
            "W": _init.init_weight(self.weight_init, k1, (n_in, self.n_out), n_in, self.n_out, dtype),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
            "vb": jnp.zeros((n_in,), dtype),  # visible bias for the decode path
        }

    def apply(self, params, state, x, *, train=False, rng=None):
        z = matmul(x, params["W"]) + params["b"]
        return self.activation_fn()(z), state

    def reconstruct(self, params, x):
        h, _ = self.apply(params, {}, x)
        z = matmul(h, params["W"].T) + params["vb"]
        return self.activation_fn()(z)

    def pretrain_loss(self, params, x, rng):
        import jax
        corrupted = x
        if self.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        recon = self.reconstruct(params, corrupted)
        return _losses.get(self.loss)(recon, x)
