"""Layer normalisation and RMS normalisation over the last axis: the two
norms a ``TransformerBlock`` puts before (and, sandwiched, after) each of
its halves, which the mixers also use on heads and latents."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.utils.serde import register_config


def _nfeat(input_type):
    """Width of the last axis a norm scales."""
    if isinstance(input_type, _inputs.ConvolutionalType):
        return input_type.channels
    return input_type.size


@register_config
@dataclasses.dataclass(frozen=True)
class LayerNormalization(ParamLayer):
    """Per-feature layer norm (gamma/beta over the last axis)."""

    eps: float = 1e-5
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ("beta",)

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        n = _nfeat(input_type)
        return {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["gamma"] + params["beta"]
        return self.activation_fn()(y), state


@register_config
@dataclasses.dataclass(frozen=True)
class RMSNorm(ParamLayer):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019):
    ``x / sqrt(mean(x^2) + eps) * gamma``; no mean, no bias.
    ``zero_centered`` stores the gain about zero: ``... * (1 + gamma)``,
    ``gamma`` starting at 0 (Qwen3-Next's norm; weight decay then pulls the
    gain towards 1, not towards 0)."""

    eps: float = 1e-6
    zero_centered: bool = False
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        make = jnp.zeros if self.zero_centered else jnp.ones
        return {"gamma": make((_nfeat(input_type),), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("rmsnorm"):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            gain = params["gamma"] + 1 if self.zero_centered \
                else params["gamma"]
            y = x * jax.lax.rsqrt(ms + self.eps) * gain
            return self.activation_fn()(y), state
