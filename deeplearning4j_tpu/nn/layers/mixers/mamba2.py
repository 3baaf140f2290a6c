"""Mamba-2's selective state-space mixer (the ``M`` layers of the
Nemotron-H family) and its grouped gated norm."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config


def gated_group_norm(y, z, w, groups, eps):
    """Mamba-2's gated RMS norm over [..., F]: ``u = y * silu(z)``, each
    of the ``groups`` runs of ``F / groups`` channels normed by its own
    root mean square (``u / sqrt(mean(u^2) + eps)``), times the gain ``w``
    [F]."""
    with jax.named_scope("rmsnorm"):
        u = y * jax.nn.silu(z)
        g = u.reshape(*u.shape[:-1], groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        return g.reshape(u.shape) * w


@register_config
@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(ParamLayer):
    """Mamba-2's selective state-space mixer over [B,T,F] (Dao & Gu,
    arXiv:2405.21060; the ``M`` layers of the Nemotron-H family):
    ``heads`` heads of ``head_dim`` (``d_inner`` = their product),
    ``groups`` groups of ``B`` and ``C`` of ``state`` each, head ``h``
    reading group ``h // (heads / groups)``.

    ``[z | x B C | dt] = u W_in`` (widths ``d_inner | d_inner + 2 groups
    state | heads``; one matrix, three products, so that each part lies
    where its consumer reads it); ``[x | B | C] = silu(conv(.) + conv_b)``,
    a depthwise causal convolution of ``conv_kernel`` taps with a bias
    (ops/causal_conv.py); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head, in float32; the recurrence ``S = exp(dt A) S +
    dt x B^T; y = S C + D x`` in its chunkwise form at ``chunk`` positions
    (ops/ssd.py); the grouped gated norm ``gated_group_norm(y, z)`` with
    gain ``norm_w``; ``out = y W_out``. No projection bias.

    The layer's own initialisation: ``A_log = log(1..heads)``, ``D`` 1,
    ``dt_bias`` the inverse softplus of ``exp(U(log 1e-3, log 0.1))``
    floored at 1e-4 (``DT_RANGE``, ``DT_FLOOR``: the family's published
    ``time_step_*``), ``conv_b`` 0, ``norm_w`` 1, ``W_out`` times
    ``out_scale`` (the family divides it by the root of the depth). As a
    block's mixer its parameters sit under ``ssm``."""

    n_out: int = 0
    heads: int = 64
    head_dim: int = 64
    groups: int = 8
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    out_scale: float = 1.0
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    param_key = "ssm"   # where a block keeps this mixer's parameters

    WEIGHT_KEYS = ("W_in", "conv_w", "W_out")
    BIAS_KEYS = ("conv_b", "dt_bias")

    DT_RANGE = (1e-3, 0.1)
    DT_FLOOR = 1e-4

    def _widths(self):
        """(d_inner, the convolution's channels)."""
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads are no multiple of "
                             f"{self.groups} groups")
        inner = self.heads * self.head_dim
        return inner, inner + 2 * self.groups * self.state

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        inner, conv = self._widths()
        k1, k2, k3, k4 = jax.random.split(key, 4)

        def weight(k, shape, fan_in, fan_out):
            return _init.init_weight(self.weight_init, k, shape, fan_in,
                                     fan_out, dtype)

        proj = inner + conv + self.heads
        lo, hi = self.DT_RANGE
        dt = jnp.exp(jax.random.uniform(k3, (self.heads,), dtype,
                                        jnp.log(lo), jnp.log(hi)))
        dt = jnp.maximum(dt, self.DT_FLOOR)
        return {
            "W_in": weight(k1, (n_in, proj), n_in, proj),
            "conv_w": weight(k2, (conv, self.conv_kernel), self.conv_kernel,
                             1),
            "conv_b": jnp.zeros((conv,), dtype),
            "A_log": jnp.log(jnp.arange(1, self.heads + 1, dtype=dtype)),
            "D": jnp.ones((self.heads,), dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "norm_w": jnp.ones((inner,), dtype),
            "W_out": self.out_scale * weight(k4, (inner, self.n_out), inner,
                                             self.n_out),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        from deeplearning4j_tpu.ops.ssd import ssd
        with jax.named_scope("ssm"):
            b, t, _ = x.shape
            inner, conv = self._widths()
            h, g = self.heads, self.groups
            _, ad = _dtypes.compute_dtypes_for(x.dtype)
            x2 = x.reshape(b * t, -1)
            w_in = params["W_in"]
            with jax.named_scope(_scopes.MIX_IN):
                z = matmul(x2, w_in[:, :inner]).reshape(b, t, inner)
                xbc = matmul(x2, w_in[:, inner:inner + conv]).reshape(
                    b, t, conv)
                dt = matmul(x2, w_in[:, inner + conv:]).reshape(b, t, h)
            with jax.named_scope("ssm_conv"):
                (xs, bs, cs), _ = causal_conv(
                    xbc, params["conv_w"], params["conv_b"], activation=True,
                    split=(inner, g * self.state, g * self.state))
            dt = jax.nn.softplus(dt.astype(ad) + params["dt_bias"].astype(ad))
            y = ssd(xs.reshape(b, t, h, -1), dt,
                    -jnp.exp(params["A_log"].astype(ad)),
                    bs.reshape(b, t, g, -1), cs.reshape(b, t, g, -1),
                    params["D"], chunk=self.chunk)
            y = gated_group_norm(y.reshape(b, t, inner), z, params["norm_w"],
                                 g, self.norm_eps)
            with jax.named_scope(_scopes.MIX_OUT):
                y = matmul(y.reshape(b * t, inner), params["W_out"])
            y = y.reshape(b, t, self.n_out)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state
