"""The matrix products of the plain references at three precisions:

* "f32": float32 at `precision="highest"`, the reference proper;
* "bf16": what the configurations state: bfloat16 operands, float32
  accumulation (a convolution's result rounded to bfloat16, as the
  program's convolutions do);
* "fp8": the control, the nearest precision below the stated one, as an
  fp8 training recipe computes: operands rounded to e4m3 on the way
  forward, the incoming gradient rounded to e5m2 on the way back, each
  with one scale per tensor, products accumulated in float32; and where
  the stated precision rounds a result to bfloat16 (a convolution's), the
  control rounds it to e4m3: fp8 activations, the step that tempts a
  model bound by memory traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def _round(x, fmt, top):
    s = jnp.max(jnp.abs(x)) / top + 1e-30
    return ((x / s).astype(fmt).astype(jnp.float32) * s).astype(jnp.bfloat16)


def _e4m3(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _e5m2(x):
    return _round(x, jnp.float8_e5m2, 57344.0)


def _mm_bf16(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def _mm_fp8(x, w):
    return _mm_bf16(_e4m3(x), _e4m3(w))


def _mm_fp8_fwd(x, w):
    xq, wq = _e4m3(x), _e4m3(w)
    return _mm_bf16(xq, wq), (xq, wq)


def _mm_fp8_bwd(res, ct):
    xq, wq = res
    g = _e5m2(ct)
    return _mm_bf16(g, wq.T), _mm_bf16(xq.T, g)


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def matmul(x, w, precision):
    """[M, K] x [K, N] -> float32 [M, N]."""
    if precision == "f32":
        return jnp.matmul(x, w, precision="highest")
    return (_mm_bf16 if precision == "bf16" else _mm_fp8)(x, w)


def _conv_lo(x, w, stride):
    # jax's convolution transpose rule refuses a float32 result from
    # bfloat16 operands, so the result is rounded to bfloat16 too (the
    # MXU still accumulates in float32)
    return lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (stride, stride),
        "SAME", dimension_numbers=_DIMNUMS).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_fp8(x, w, stride):
    return _conv_fp8_fwd(x, w, stride)[0]


def _conv_fp8_fwd(x, w, stride):
    xq, wq = _e4m3(x), _e4m3(w)
    return _e4m3(_conv_lo(xq, wq, stride)).astype(jnp.float32), (xq, wq)


def _conv_fp8_bwd(stride, res, ct):
    xq, wq = res
    _, vjp = jax.vjp(lambda a, b: _conv_lo(a, b, stride), xq, wq)
    dx, dw = vjp(_e5m2(ct).astype(jnp.float32))
    return dx.astype(jnp.float32), dw.astype(jnp.float32)


_conv_fp8.defvjp(_conv_fp8_fwd, _conv_fp8_bwd)


def conv(x, w, stride, precision):
    """NHWC x HWIO, SAME padding -> float32."""
    if precision == "f32":
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME", dimension_numbers=_DIMNUMS,
            precision="highest")
    if precision == "bf16":
        return _conv_lo(x, w, stride)
    return _conv_fp8(x, w, stride)
