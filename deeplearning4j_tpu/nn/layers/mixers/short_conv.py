"""The gated short convolution (the LFM2 family's second mixer)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class ShortConv(ParamLayer):
    """Gated short convolution over [B,T,F] (the LFM2 family's second
    mixer): ``[B_, C_, x_] = split3(u W_in)`` in that order, ``z = B_ *
    x_``, a depthwise causal convolution of length ``kernel`` over time
    (zeros before the sequence's start; tap ``kernel - 1`` meets the
    present position), ``out = (C_ * c) W_out``. No bias and no
    activation inside. Both gates and the taps are one op on the
    in-projection's result as it lies (ops/causal_conv.py: two kernels
    under a ``custom_vjp`` where the shape allows, the ``jax.numpy`` form
    under autodiff elsewhere). As a block's mixer its parameters sit under
    ``conv``."""

    n_out: int = 0
    kernel: int = 3
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    param_key = "conv"   # where a block keeps this mixer's parameters

    WEIGHT_KEYS = ("W_in", "conv_w", "W_out")
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in, d = input_type.size, self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "W_in": _init.init_weight(self.weight_init, k1, (n_in, 3 * d),
                                      n_in, 3 * d, dtype),
            "conv_w": _init.init_weight(self.weight_init, k2,
                                        (d, self.kernel), self.kernel, 1,
                                        dtype),
            "W_out": _init.init_weight(self.weight_init, k3, (d, d), d, d,
                                       dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        with jax.named_scope("short_conv"):
            b, t, _ = x.shape
            d = self.n_out
            with jax.named_scope(_scopes.MIX_IN):
                bcx = matmul(x.reshape(b * t, -1), params["W_in"])
            gated, _ = causal_conv(bcx.reshape(b, t, 3 * d),
                                   params["conv_w"], gate_before=True,
                                   gate_after=True)
            with jax.named_scope(_scopes.MIX_OUT):
                y = matmul(gated.reshape(b * t, d), params["W_out"])
            y = y.reshape(b, t, d)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state
