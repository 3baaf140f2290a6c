"""Fused conv + batch-norm (+ residual add) + activation (Pallas, TPU).

Reference analog: CudnnConvolutionHelper
(/root/reference/deeplearning4j-cuda/src/main/java/org/deeplearning4j/nn/
layers/convolution/CudnnConvolutionHelper.java:230-239,389-392) — the
reference's "own the conv lowering" fast path, where algo selection and
HALF-math conv descriptors replace the generic im2col route. On TPU the
generic route is XLA's conv custom-call, which is already MXU-tiled; what
it canNOT do is fuse the batch-norm *statistics reduction* into the conv
epilogue — the conv output z is written to HBM, read again for mean/var,
and read a third time for the normalize. PROFILE.md's round-2 analysis
shows ResNet50 pinned at the v5e HBM peak (0.27 MFU), so each avoided
pass over z is direct step-time.

Kernel design (TPU-first):
* Phase 1 (Pallas): the conv as a tiled MXU matmul whose epilogue
  accumulates per-channel sum and sum-of-squares in f32 VMEM scratch while
  the f32 accumulator tile is still resident — z is written ONCE and never
  re-read for statistics. Two kernel variants share the epilogue:
    - 1x1 convs (2 of 3 convs in every ResNet bottleneck + all projection
      shortcuts): [N, Cin] x [Cin, Cout] tiled matmul, N = B*Ho*Wo
      (stride-2 is a pre-slice).
    - stride-1 SAME 3x3 convs: implicit GEMM over batch-row blocks — for
      one output row h across a batch tile, the 9 taps are 9 static
      slice+matmul accumulations against a VMEM-resident [3,3,Cin,Cout]
      weight block; input rows stream with a 1-row halo from the
      zero-padded input. No im2col materialization.
* Phase 2 is pure elementwise (normalize, affine, residual add,
  activation) and is left to XLA, which fuses it into one pass.
* Backward is a jax composition under ``jax.custom_vjp``: train-mode BN
  backward to dz fused by XLA, then dx/dW as MXU matmuls (1x1) or XLA conv
  grads (3x3). Batch mean/var are returned for the running-average state
  update (not differentiated, matching the unfused layer's state path).

Dispatch seam (``enabled()`` / ``supported()``) mirrors the reference's
helper checks at ConvolutionLayer.java:74-84, like ops/lstm_pallas.py and
ops/attention_pallas.py. ``interpret=True`` runs the kernels on CPU for
exactness tests.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _pad_to(n, m):
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# Phase-1 kernels: conv matmul with fused per-channel stats epilogue
# ---------------------------------------------------------------------------

# tile geometry: rows (sublane dim) and Cout lanes; bk tiles the Cin
# reduction of the 1x1 matmul. VMEM at the defaults: f32 acc 256x512 =
# 512 KiB + double-buffered bf16 x/w blocks well under the ~16 MiB budget.
# These are the HAND-PICKED fallbacks — a TuningDB entry for the call's
# shape bucket (tuning/db.py, kernel ids "conv_matmul"/"conv3x3")
# overrides them at trace time.
_BN = 256
_BK = 256
_BJ = 512
_BT_TARGET = 256


def _tuned(kernel, shape, dtype):
    """Trace-time TuningDB lookup (None without a DB/entry — the
    hand-picked defaults above apply)."""
    from deeplearning4j_tpu.tuning.db import tuned_config
    return tuned_config(kernel, shape, dtype)


def _mm_stats_kernel(nk, x_ref, w_ref, z_ref, s_ref, acc_s, st_s):
    """grid (j, i, k): j over Cout tiles, i over row tiles, k over Cin
    tiles (innermost). Stats for Cout tile j accumulate across all i in
    VMEM and are written once at the last row tile."""
    i = pl.program_id(1)
    k = pl.program_id(2)
    ni = pl.num_programs(1)

    @pl.when(k == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)

    acc_s[:] += jnp.dot(x_ref[:], w_ref[:],
                        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        z = acc_s[:]
        z_ref[:] = z.astype(z_ref.dtype)

        @pl.when(i == 0)
        def _():
            st_s[:] = jnp.zeros_like(st_s)

        st_s[0:1] += jnp.sum(z, axis=0, keepdims=True)
        st_s[1:2] += jnp.sum(z * z, axis=0, keepdims=True)

        @pl.when(i == ni - 1)
        def _():
            s_ref[:] = st_s[:]  # rows 0/1 live; 2-7 sublane padding


def _matmul_stats(x2d, w2d, interpret, *, bn=None, bk=None, bj=None):
    """x2d [N, Cin] @ w2d [Cin, Cout] -> (z [N, Cout] in x.dtype,
    stats [2, Cout] f32 = per-channel [sum, sum_of_squares]).

    Pads every axis to tile multiples with zeros; zero rows contribute 0
    to both stats sums, so the caller divides by the REAL row count.
    Tile geometry: explicit ``bn/bk/bj`` (the tuner's candidates) >
    TuningDB winner for the shape bucket > hand-picked defaults; every
    choice is clamped to the padded array like the defaults always were.
    """
    n, cin = x2d.shape
    cout = w2d.shape[1]
    dt = x2d.dtype
    if bn is None or bk is None or bj is None:
        cfg = _tuned("conv_matmul", (n, cin, cout), dt) or {}
        bn = cfg.get("bn", _BN) if bn is None else bn
        bk = cfg.get("bk", _BK) if bk is None else bk
        bj = cfg.get("bj", _BJ) if bj is None else bj
    bn = min(int(bn), _pad_to(n, 8))
    bk = min(int(bk), _pad_to(cin, 128))
    bj = min(int(bj), _pad_to(cout, 128))
    np_, kp, jp = _pad_to(n, bn), _pad_to(cin, bk), _pad_to(cout, bj)
    xp = jnp.pad(x2d, ((0, np_ - n), (0, kp - cin)))
    wp = jnp.pad(w2d, ((0, kp - cin), (0, jp - cout)))
    nk = kp // bk
    z, stats = pl.pallas_call(
        functools.partial(_mm_stats_kernel, nk),
        grid=(jp // bj, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda j, i, k: (i, k)),
            pl.BlockSpec((bk, bj), lambda j, i, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bj), lambda j, i, k: (i, j)),
            # 8-sublane stats block: a 2-row output block trips the TPU
            # (8, 128) tile rule (the round-2 lse lesson) — rows 2-7 pad
            pl.BlockSpec((8, bj), lambda j, i, k: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, jp), dt),
            jax.ShapeDtypeStruct((8, jp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bj), jnp.float32),
                        pltpu.VMEM((8, bj), jnp.float32)],
        interpret=interpret,
        name="conv_bn_fwd_1x1",
    )(xp, wp)
    return z[:n, :cout], stats[:2, :cout]


def _conv3x3_stats_kernel(stride, x0_ref, x1_ref, x2_ref, w_ref, z_ref,
                          s_ref, st_s):
    """grid (j, b, h): one output row h for a batch tile, Cout tile j.
    The three x refs are the same padded input at row offsets
    stride*h+{0,1,2} (the 3x3 halo); taps unroll as 9 static-slice
    matmuls, each tap column-subsampling its row by the stride."""
    b = pl.program_id(1)
    h = pl.program_id(2)
    nb = pl.num_programs(1)
    nh = pl.num_programs(2)

    bt, _, wp_, cinp = x0_ref.shape
    wout = z_ref.shape[2]
    acc = jnp.zeros((bt * wout, w_ref.shape[3]), jnp.float32)
    for dh, row_ref in enumerate((x0_ref, x1_ref, x2_ref)):
        rows = row_ref[:, 0]  # [bt, Wp, Cin]
        for dw in range(3):
            xs = rows[:, dw:dw + stride * (wout - 1) + 1:stride, :]
            xs = xs.reshape(bt * wout, cinp)
            acc += jnp.dot(xs, w_ref[dh, dw],
                           preferred_element_type=jnp.float32)
    z_ref[:] = acc.reshape(bt, 1, wout, -1).astype(z_ref.dtype)

    @pl.when((b == 0) & (h == 0))
    def _():
        st_s[:] = jnp.zeros_like(st_s)

    st_s[0:1] += jnp.sum(acc, axis=0, keepdims=True)
    st_s[1:2] += jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when((b == nb - 1) & (h == nh - 1))
    def _():
        s_ref[:] = st_s[:]


def _conv3x3_stats(x, w, interpret, stride=1, *, bt_target=None, bj=None):
    """SAME 3x3 conv with fused stats, stride 1 or 2. x [B,H,W,Cin] NHWC,
    w [3,3,Cin,Cout] HWIO -> (z [B,Ho,Wo,Cout], stats [2, Cout] f32).

    Stride 2 (torchvision-style ResNet v1.5 b-convs; this repo's
    reference-parity ResNet50 strides its 1x1 convs instead, which the
    matmul kernel already covers): XLA's SAME padding for k=3, s=2 on
    even dims is (lo 0, hi 1); output row h reads padded input rows
    2h..2h+2 (the row index maps do the arithmetic) and every tap
    subsamples its row with a static stride-2 column slice."""
    bsz, h, wd, cin = x.shape
    cout = w.shape[3]
    dt = x.dtype
    cinp = _pad_to(cin, 128)
    ho = -(-h // stride)
    wo = -(-wd // stride)
    if bt_target is None or bj is None:
        cfg = _tuned("conv3x3", (bsz, h, wd, cin, cout), dt) or {}
        bt_target = cfg.get("bt_target", _BT_TARGET) \
            if bt_target is None else bt_target
        bj = cfg.get("bj", _BJ) if bj is None else bj
    bj = min(int(bj), _pad_to(cout, 128))
    jp = _pad_to(cout, bj)
    # batch tile: keep the row-block GEMM M-dim (bt*Wo) near the tuned
    # row target (hand-picked sweet spot: 256) without exceeding it
    # wildly on large images — shared arithmetic with the tuner's static
    # validity estimate (tuning/space.conv3x3_bt)
    from deeplearning4j_tpu.tuning.space import conv3x3_bt
    bt = conv3x3_bt(bt_target, bsz, wo)
    bp = bsz  # batch stays unpadded (bt divides it)
    # zero-pad: spatial halo + channel/cout lane padding. SAME paddings:
    # s=1 -> (1, 1); s=2 on EVEN dims -> (lo 0, hi 1). Odd dims under s=2
    # split SAME padding (1, 1) — supported() refuses them, so direct
    # callers get a clear error rather than a wrong answer.
    if stride == 1:
        pads = pads_w = (1, 1)
    else:
        if h % 2 or wd % 2:
            raise NotImplementedError(
                "stride-2 3x3 kernel needs even spatial dims "
                f"(got {h}x{wd}); check supported(..., x_shape=) first")
        pads = pads_w = (0, 1)
    xp = jnp.pad(x, ((0, 0), pads, pads_w, (0, cinp - cin)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cinp - cin), (0, jp - cout)))
    wp_ = xp.shape[2]
    row_spec = [
        pl.BlockSpec((bt, 1, wp_, cinp),
                     (lambda dh: lambda j, b, hh: (b, stride * hh + dh,
                                                   0, 0))(dh))
        for dh in range(3)
    ]
    z, stats = pl.pallas_call(
        functools.partial(_conv3x3_stats_kernel, stride),
        grid=(jp // bj, bp // bt, ho),
        in_specs=row_spec + [
            pl.BlockSpec((3, 3, cinp, bj), lambda j, b, hh: (0, 0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1, wo, bj), lambda j, b, hh: (b, hh, 0, j)),
            # 8-sublane stats block (see _matmul_stats): rows 2-7 pad
            pl.BlockSpec((8, bj), lambda j, b, hh: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, ho, wo, jp), dt),
            jax.ShapeDtypeStruct((8, jp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((8, bj), jnp.float32)],
        interpret=interpret,
        name="conv_bn_fwd_3x3",
    )(xp, xp, xp, wp)
    return z[:, :, :, :cout], stats[:2, :cout]


# ---------------------------------------------------------------------------
# Fused forward/backward (custom VJP)
# ---------------------------------------------------------------------------


def _act(name, z):
    if name == "relu":
        return jnp.maximum(z, 0.0)
    if name == "identity":
        return z
    raise ValueError(f"fused conv-bn supports relu|identity, got {name!r}")


def _conv_z(x, w, stride, interpret):
    """Dispatch the phase-1 kernel by conv geometry. Returns (z [B,Ho,Wo,
    Cout] in x.dtype, stats [2, Cout] f32)."""
    kh, kw = w.shape[0], w.shape[1]
    if (kh, kw) == (1, 1):
        if stride != (1, 1):
            x = x[:, ::stride[0], ::stride[1], :]
        b, ho, wo, cin = x.shape
        z2d, stats = _matmul_stats(x.reshape(b * ho * wo, cin),
                                   w.reshape(cin, -1), interpret)
        return z2d.reshape(b, ho, wo, -1), stats
    assert (kh, kw) == (3, 3) and stride in ((1, 1), (2, 2))
    return _conv3x3_stats(x, w, interpret, stride=stride[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def fused_conv_bn_act(x, w, gamma, beta, residual,
                      stride=(1, 1), eps=1e-5, act="relu", interpret=False):
    """Train-mode fused conv + BN + (residual add) + activation.

    x [B,H,W,Cin] NHWC, w HWIO ([1,1,Cin,Cout] or [3,3,Cin,Cout] SAME),
    gamma/beta [Cout], residual [B,Ho,Wo,Cout] or None. Returns
    (y, mean, var) — mean/var are the f32 batch statistics for the
    caller's running-average update (never differentiated, matching the
    unfused BatchNormalization state path).
    """
    y, mean, var, _ = _fwd_impl(x, w, gamma, beta, residual,
                                stride, eps, act, interpret)
    return y, mean, var


@jax.named_scope("conv_bn.fwd")
def _fwd_impl(x, w, gamma, beta, residual, stride, eps, act, interpret):
    z, stats = _conv_z(x, w, stride, interpret)
    n_rows = z.shape[0] * z.shape[1] * z.shape[2]
    mean = stats[0] / n_rows
    var = jnp.maximum(stats[1] / n_rows - mean * mean, 0.0)
    invstd = lax.rsqrt(var + eps)
    scale = (gamma.astype(jnp.float32) * invstd)
    shift = beta.astype(jnp.float32) - mean * scale
    ypre = z.astype(jnp.float32) * scale + shift
    if residual is not None:
        ypre = ypre + residual.astype(jnp.float32)
    y = _act(act, ypre).astype(z.dtype)
    return y, mean, var, (z, mean, invstd)


def _fused_fwd(x, w, gamma, beta, residual, stride, eps, act, interpret):
    y, mean, var, (z, _, invstd) = _fwd_impl(
        x, w, gamma, beta, residual, stride, eps, act, interpret)
    has_res = residual is not None
    return (y, mean, var), (x, w, gamma, beta, z, mean, invstd, y, has_res)


@jax.named_scope("conv_bn.bwd")
def _fused_bwd(stride, eps, act, interpret, res, cots):
    x, w, gamma, beta, z, mean, invstd, y, has_res = res
    dy, _, _ = cots  # mean/var feed only the (stop-grad) running stats
    f32 = jnp.float32
    dy = dy.astype(f32)
    if act == "relu":
        dy = dy * (y > 0).astype(f32)
    # dy is now the cotangent of (bn_out + residual)
    dres = dy.astype(z.dtype) if has_res else None
    zf = z.astype(f32)
    xhat = (zf - mean) * invstd
    axes = (0, 1, 2)
    n = z.shape[0] * z.shape[1] * z.shape[2]
    dgamma = jnp.sum(dy * xhat, axis=axes)
    dbeta = jnp.sum(dy, axis=axes)
    dxhat = dy * gamma.astype(f32)
    # train-mode BN backward (batch stats participate in the graph)
    dz = invstd * (dxhat - dbeta * gamma.astype(f32) / n
                   - xhat * (dgamma * gamma.astype(f32) / n))
    dz = dz.astype(z.dtype)
    kh, kw = w.shape[0], w.shape[1]
    if (kh, kw) == (1, 1):
        xs = x[:, ::stride[0], ::stride[1], :] if stride != (1, 1) else x
        b, ho, wo, cin = xs.shape
        x2d = xs.reshape(b * ho * wo, cin)
        dz2d = dz.reshape(b * ho * wo, -1)
        dw2d = jnp.matmul(x2d.T, dz2d, preferred_element_type=f32)
        dw = dw2d.astype(w.dtype).reshape(w.shape)
        dx2d = jnp.matmul(dz2d, w.reshape(cin, -1).T,
                          preferred_element_type=f32).astype(x.dtype)
        dxs = dx2d.reshape(xs.shape)
        if stride != (1, 1):
            dx = jnp.zeros(x.shape, x.dtype)
            dx = dx.at[:, ::stride[0], ::stride[1], :].set(dxs)
        else:
            dx = dxs
    else:
        # conv is linear in each operand: linear_transpose gives the exact
        # dx/dw convolutions for any stride/padding without re-running the
        # forward (the Pallas kernel already produced z)
        dimn = ("NHWC", "HWIO", "NHWC")

        def conv_x(x_):
            return lax.conv_general_dilated(
                x_, w, window_strides=stride, padding="SAME",
                dimension_numbers=dimn)

        def conv_w(w_):
            return lax.conv_general_dilated(
                x, w_, window_strides=stride, padding="SAME",
                dimension_numbers=dimn)

        (dx,) = jax.linear_transpose(conv_x, x)(dz)
        (dw,) = jax.linear_transpose(conv_w, w)(dz)
        dx = dx.astype(x.dtype)
        dw = dw.astype(w.dtype)
    return (dx, dw, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dres)


fused_conv_bn_act.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Dispatch seam
# ---------------------------------------------------------------------------


def enabled():
    """Env flag + TPU backend, like the lstm/attention seams."""
    from deeplearning4j_tpu.ops.attention_pallas import backend_is_tpu
    if os.environ.get("DL4J_TPU_FUSED_CONV", "1") == "0":
        return False
    return backend_is_tpu()


def supported(kernel, stride, padding, dilation, act, x_shape=None):
    """Geometries the phase-1 kernels cover: 1x1 (any stride via
    pre-slice) and SAME 3x3 at stride 1, or stride 2 on even spatial dims
    (pass ``x_shape`` [B,H,W,C] to check the parity — without it, stride-2
    3x3 is conservatively refused). No dilation; relu/identity only. In
    the reference-parity ResNet50 only the 7x7 stem stays on XLA's conv
    (<2% of conv FLOPs); its strided convs are 1x1."""
    if act not in ("relu", "identity"):
        return False
    if tuple(dilation) != (1, 1):
        return False
    k = tuple(kernel)
    if k == (1, 1):
        return True
    if k != (3, 3) or padding != "same":
        return False
    if tuple(stride) == (1, 1):
        return True
    if tuple(stride) != (2, 2):
        return False
    return (x_shape is not None
            and x_shape[1] % 2 == 0 and x_shape[2] % 2 == 0)
