"""The gated delta rule as a sequence mixer (Qwen3-Next's linear
attention)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.layers.norms import RMSNorm
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class GatedDeltaNet(ParamLayer):
    """The gated delta rule as a sequence mixer over [B,T,F] (Qwen3-Next's
    linear attention; Yang et al., arXiv:2412.06464), ``k_heads`` key heads
    of ``head_dim`` serving ``v_heads`` value heads of ``v_head_dim`` (None
    = ``head_dim``), value head ``j`` reading key head ``j // (v_heads //
    k_heads)``:

    ``[q | k | v | z] = x W_qkvz`` and ``[b | a] = x W_ba``, each part
    whole and its heads in order; ``[q | k | v] = silu(conv(.))``, a
    depthwise causal convolution of ``conv_kernel`` taps over the channels
    in that order (zeros before the sequence's start, the last tap meeting
    the present position, no bias), taps and SiLU one op on the
    projection's leading columns as they lie (ops/causal_conv.py);
    ``beta = sigmoid(b)``; ``g = -exp(A_log)
    softplus(a + dt_bias)`` in float32; ``q = l2norm(q) / sqrt(head_dim)``,
    ``k = l2norm(k)`` (``x rsqrt(sum x^2 + 1e-6)``); the recurrence
    ``S = exp(g) S; S += beta k (v - S^T k)^T; o = S^T q`` a value head in
    its chunkwise form (ops/gated_delta.py); a head ``o = o / sqrt(mean(o^2)
    + norm_eps) * norm_w * silu(z)``; ``out = o W_out``. ``A_log`` starts
    at ``log U(0, 16)``, ``dt_bias`` and ``norm_w`` at 1. As a block's
    mixer its parameters sit under ``gdn``."""

    n_out: int = 0
    k_heads: int = 16
    v_heads: int = 32
    head_dim: int = 128
    v_head_dim: int | None = None
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    param_key = "gdn"   # where a block keeps this mixer's parameters

    WEIGHT_KEYS = ("W_qkvz", "W_ba", "conv_w", "W_out")
    BIAS_KEYS = ("dt_bias",)

    L2NORM_EPS = 1e-6

    def _widths(self):
        """(key width, value width) over all heads."""
        dv = self.v_head_dim or self.head_dim
        return self.k_heads * self.head_dim, self.v_heads * dv

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        kw, vw = self._widths()
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)

        def weight(k, shape, fan_in, fan_out):
            return _init.init_weight(self.weight_init, k, shape, fan_in,
                                     fan_out, dtype)

        proj, conv = 2 * kw + 2 * vw, 2 * kw + vw
        return {
            "W_qkvz": weight(k1, (n_in, proj), n_in, proj),
            "W_ba": weight(k2, (n_in, 2 * self.v_heads), n_in,
                           2 * self.v_heads),
            "conv_w": weight(k3, (conv, self.conv_kernel), self.conv_kernel,
                             1),
            "A_log": jnp.log(jax.random.uniform(
                k4, (self.v_heads,), dtype, 1e-3, 16.0)),
            "dt_bias": jnp.ones((self.v_heads,), dtype),
            "norm_w": jnp.ones((vw // self.v_heads,), dtype),
            "W_out": weight(k5, (vw, self.n_out), vw, self.n_out),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        from deeplearning4j_tpu.ops.gated_delta import gated_delta_rule
        with jax.named_scope("gdn"):
            b, t, _ = x.shape
            kw, vw = self._widths()
            hk, hv = self.k_heads, self.v_heads
            _, ad = _dtypes.compute_dtypes_for(x.dtype)
            x2 = x.reshape(b * t, -1)
            with jax.named_scope(_scopes.MIX_IN):
                qkvz = matmul(x2, params["W_qkvz"]).reshape(b, t, -1)
                ba = matmul(x2, params["W_ba"]).reshape(b, t, 2,
                                                        hv).astype(ad)
            with jax.named_scope("gdn_conv"):
                (q, k, v), z = causal_conv(qkvz, params["conv_w"],
                                           activation=True,
                                           split=(kw, kw, vw))
            q = q.reshape(b, t, hk, -1)
            k = k.reshape(b, t, hk, -1)
            v = v.reshape(b, t, hv, -1)

            def l2norm(u):
                return u * jax.lax.rsqrt(
                    jnp.sum(jnp.square(u), -1, keepdims=True)
                    + self.L2NORM_EPS)

            q = l2norm(q) * (self.head_dim ** -0.5)
            k = l2norm(k)
            beta = jax.nn.sigmoid(ba[:, :, 0])
            g = -jnp.exp(params["A_log"].astype(ad)) * jax.nn.softplus(
                ba[:, :, 1] + params["dt_bias"].astype(ad))
            o = gated_delta_rule(q, k, v, g, beta)
            o, _ = RMSNorm(eps=self.norm_eps).apply(
                {"gamma": params["norm_w"]}, {}, o)
            o = o * jax.nn.silu(z.reshape(o.shape))
            with jax.named_scope(_scopes.MIX_OUT):
                y = matmul(o.reshape(b * t, vw), params["W_out"])
            y = y.reshape(b, t, self.n_out)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state
