"""The short depthwise causal convolution of the conv and gated-delta
mixers, with the elementwise work beside it, as one op (Pallas, TPU).

What the two layers ask for (under nn/layers/mixers/), on the leading
columns of a projection's result ``p`` [B, T, W] as it lies, ``C =
w.shape[0]`` columns a part in the order ``[gate before | gate after |
x]``, whatever follows them passing through untouched:

    z    = before * x                      (where gate_before)
    pre  = sum_j w[:, taps-1-j] * shift_j(z)     zeros before the start
           + bias                          (where there is one, a column)
    y    = after * silu(pre)               (where gate_after / activation)

``ShortConv`` is both gates over three taps, ``GatedDeltaNet`` four taps
and SiLU over the first ``2 kw + vw`` columns of ``x W_qkvz`` with ``z``'s
columns behind them, ``Mamba2Mixer`` four taps, a bias and SiLU over all
of its ``[x | B | C]`` projection. ``causal_conv`` returns ``(y, rest)``: ``rest`` is
``p``'s columns past the parts (None where there are none), so that the
backward writes the gradient of the WHOLE projection, the three parts'
or the convolution's beside the rest's, into one [B, T, W] array and no
concatenation or pad-and-add follows. With ``split`` (widths that sum to
``C``) ``y`` is a tuple of arrays, ``q``, ``k`` and ``v`` each written
where its consumer reads it and each one's gradient read where its
producer wrote it: no slice is copied out of ``y`` for a kernel that
wants ``v`` alone, and no pad-and-add joins the three gradients.

**The kernels** (``causal_conv_fwd``, ``causal_conv_bwd``, under one
``jax.custom_vjp`` whose residuals are ``p``, ``w`` and the bias and
nothing else): grid (batch, blocks of rows), the rows in order, a block all ``C``
columns of each part wide, read out of ``p`` where it lies (a part is a
block index of the column axis) and as many rows as ``_VMEM`` allows
with every block double-buffered (64 at the benchmark's [4096, 8192],
128 at [8192, 3 x 2048]). Inside a block the columns are walked
``_LANES`` at a time with everything in vector registers: a shift by
``j`` rows is a sublane roll of the chunk with eight rows of its
neighbour block above (forward: the last rows of ``z``, carried in
VMEM) or below it (backward: the first rows of the pre-activation's
gradient, carried as the blocks are walked from the last), and the
backward makes the pre-activation again from ``p`` and eight rows of the
block before (a second, eight-row view of the same array), sums the
taps' gradient as [8, C] partial sums in a block that stays in VMEM
across the rows, and writes each part's gradient at its columns of the
one output block. All of it in float32 (a bfloat16 ``p`` is widened as it
is loaded and the result rounded as it is stored).

``resolve_causal_conv`` is the one place that chooses, from what the call
shows; ``_plain`` (the ``jax.numpy`` form under autodiff, which is what
ran everywhere before PR 40 and what the tests compare with) takes the
rest. Chosen by measured calls on the layer alone over
``lax.conv_general_dilated`` with one group a channel and over a
``jax.numpy`` form with a hand-written backward (PERF.md section 6, PR
40).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import attention_pallas as _ap

_LANE = 128
_LANES = 256           # columns a pass of the loop inside a block
_HALO = 8              # rows of the neighbouring block a shift may reach
_VMEM = 20 << 20       # bytes of a call's double-buffered blocks
_F32 = jnp.float32


def causal_taps(z, w):
    """Depthwise causal convolution of ``z`` [B,T,C] with ``w`` [C,taps]:
    zeros before the sequence's start, the last tap meeting the present
    position."""
    t, taps = z.shape[1], w.shape[1]
    c = z * w[:, taps - 1]
    for back in range(1, taps):
        past = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        c = c + past * w[:, taps - 1 - back]
    return c


def _parts(p, c, gate_before, gate_after):
    """(before, after, x, rest) of ``p``'s columns, None where absent."""
    cut = [p[..., k * c:(k + 1) * c]
           for k in range(gate_before + gate_after + 1)]
    used = len(cut) * c
    before = cut.pop(0) if gate_before else None
    after = cut.pop(0) if gate_after else None
    return before, after, cut[0], (p[..., used:] if used < p.shape[-1]
                                   else None)


def _cut(y, split):
    """``y`` whole, or its columns as ``split`` parts them."""
    ends = list(itertools.accumulate(split))
    return tuple(y[..., e - n:e] for n, e in zip(split, ends)) or y


def _plain(p, w, bias, gate_before, gate_after, activation, split):
    before, after, x, rest = _parts(p, w.shape[0], gate_before, gate_after)
    y = causal_taps(x if before is None else before * x, w.astype(p.dtype))
    if bias is not None:
        y = y + bias.astype(p.dtype)
    if activation:
        y = jax.nn.silu(y)
    return _cut(y if after is None else after * y, split), rest


def resolve_causal_conv(p_shape, w_shape, dtype, gate_before, gate_after,
                        split=()):
    """The whole dispatch decision, from what the call shows: True where
    the two kernels run, False where the ``jax.numpy`` form does. The
    kernels: a TPU backend, parts, the result's pieces and what lies
    behind the parts whole lane tiles wide, at most ``_HALO`` taps,
    float32 or bfloat16 (float64, the gradient checks' dtype, stays with
    XLA), and eight rows of every block, double-buffered, within
    ``_VMEM``."""
    if not _ap.backend_is_tpu():
        return False
    return _supported(p_shape, w_shape, dtype, gate_before, gate_after,
                      split)


def _supported(p_shape, w_shape, dtype, gate_before, gate_after, split):
    (_, t, width), (c, taps) = p_shape, w_shape
    if any(n % _LANE for n in (c, width, *split)) or not 1 < taps <= _HALO:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(_F32), jnp.dtype(jnp.bfloat16)):
        return False
    return _rows(t, width, c, dtype) > 0


def causal_conv(p, w, bias=None, *, gate_before=False, gate_after=False,
                activation=False, split=()):
    """``p`` [B, T, W] a projection's result, ``w`` [C, taps], ``bias``
    [C] or None; see the module's text for the columns' order. Returns ``(y [B, T, C], rest
    [B, T, W - parts C] or None)`` in ``p``'s dtype, ``y`` a tuple of
    [B, T, n] for the ``n`` of ``split``."""
    split = tuple(split)
    used = (1 + gate_before + gate_after) * w.shape[0]
    if used > p.shape[-1]:
        raise ValueError(f"{p.shape[-1]} columns do not hold "
                         f"{used // w.shape[0]} parts of {w.shape[0]}")
    if split and sum(split) != w.shape[0]:
        raise ValueError(f"split {split} of {w.shape[0]} columns")
    if resolve_causal_conv(p.shape, w.shape, p.dtype, gate_before,
                           gate_after, split):
        return causal_conv_kernels(
            p, w, bias, gate_before=gate_before, gate_after=gate_after,
            activation=activation, split=split,
            interpret=not _ap.backend_is_tpu())
    return _plain(p, w, bias, gate_before, gate_after, activation, split)


def _rows(t, width, c, dtype):
    """Rows a block: the most of 512, 256, ... that keep the backward's
    blocks (the parts and the gradient of what lies behind them in, which
    is the whole width; the result's gradient in; the whole width out),
    twice each, within ``_VMEM``; no more than the sequence rounded up to
    the dtype's sublane tile; 0 if not even one tile of rows fits."""
    size = jnp.dtype(dtype).itemsize
    tile = _HALO * 4 // size
    a_row = 2 * size * (width + c + width)
    rows = 512
    while rows >= tile and rows * a_row > _VMEM:
        rows //= 2
    return min(rows, -(-t // tile) * tile) if rows >= tile else 0


def _silu_and_slope(u):
    s = jax.nn.sigmoid(u)
    return u * s, s * (1 + u * (1 - s))


def _shifted(ext, rows, back):
    """``ext`` is ``_HALO`` rows of the block above over the block's own
    ``rows``: the block's rows, each ``back`` rows earlier."""
    return pltpu.roll(ext, back, 0)[_HALO:] if back else ext[_HALO:]


def _folded(x):
    """[rows, n] -> [8, n]: the sum of the sublane tiles, register on
    register."""
    return sum(x[k:k + 8] for k in range(0, x.shape[0], 8))


def _chunks(widths, body):
    """``body(k, columns of the whole, columns of piece k)`` for every
    ``_LANES`` columns of every piece, the pieces side by side."""
    step = _LANE if any(n % _LANES for n in widths) else _LANES
    start = 0
    for k, n in enumerate(widths):
        def one(i, _, k=k, start=start):
            at = pl.multiple_of(i * step, step)
            body(k, pl.ds(start + at, step), pl.ds(at, step))
            return 0

        jax.lax.fori_loop(0, n // step, one, 0)
        start += n


def _fwd_kernel(*refs, rows, bias, gate_before, gate_after, activation):
    refs = list(refs)
    w_ref = refs.pop(0)
    before_ref = refs.pop(0) if gate_before else None
    after_ref = refs.pop(0) if gate_after else None
    x_ref, *y_refs, carry = refs
    taps = w_ref.shape[0] - bias       # the bias is the row after the taps

    @pl.when(pl.program_id(1) == 0)
    def _():
        carry[...] = jnp.zeros(carry.shape, carry.dtype)

    def chunk(k, cols, own):
        z = x_ref[0, :, cols].astype(_F32)
        if gate_before:
            z = z * before_ref[0, :, cols].astype(_F32)
        ext = jnp.concatenate([carry[:, cols], z], axis=0)
        carry[:, cols] = z[rows - _HALO:]
        pre = sum(_shifted(ext, rows, j) * w_ref[taps - 1 - j:taps - j, cols]
                  for j in range(taps))
        if bias:
            pre = pre + w_ref[taps:, cols]
        if activation:
            pre = jax.nn.silu(pre)
        if gate_after:
            pre = pre * after_ref[0, :, cols].astype(_F32)
        y_refs[k][0, :, own] = pre.astype(y_refs[k].dtype)

    _chunks([y.shape[-1] for y in y_refs], chunk)


def _bwd_kernel(*refs, rows, t, halo, bias, gate_before, gate_after,
                activation):
    refs = list(refs)
    w_ref = refs.pop(0)
    before_ref, before_halo = (refs.pop(0), refs.pop(0)) if gate_before \
        else (None, None)
    after_ref = refs.pop(0) if gate_after else None
    x_ref, x_halo, *dy_refs, dp_ref, dw_ref, carry = refs
    taps, c = w_ref.shape[0] - bias, w_ref.shape[1]
    # the result's pieces' gradients, then the gradient of what passed by
    drest_ref = dy_refs.pop() if dp_ref.shape[-1] > (
        1 + gate_before + gate_after) * c else None
    i = pl.program_id(1)
    block = pl.num_programs(1) - 1 - i      # the blocks from the last
    x_at = (gate_before + gate_after) * c

    @pl.when(i == 0)
    def _():
        carry[...] = jnp.zeros(carry.shape, carry.dtype)
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    row = block * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    inside = row < t if t % rows else None     # the last block's tail
    first = block == 0

    def chunk(k, cols, own):
        def part(ref):
            return ref[0, :, cols].astype(_F32)

        # a packed dtype's halo is a whole sublane tile: its last rows
        x, above = part(x_ref), part(x_halo)[halo - _HALO:]
        z = x
        if gate_before:
            before = part(before_ref)
            z = z * before
            above = above * part(before_halo)[halo - _HALO:]
        if inside is not None:
            z = jnp.where(inside, z, 0)
        ext = jnp.concatenate([jnp.where(first, 0, above), z], axis=0)
        past = [_shifted(ext, rows, j) for j in range(taps)]
        d = dy_refs[k][0, :, own].astype(_F32)
        pre = sum(past[j] * w_ref[taps - 1 - j:taps - j, cols]
                  for j in range(taps))
        if bias:
            pre = pre + w_ref[taps:, cols]
        if activation:
            pre, slope = _silu_and_slope(pre)
        if gate_after:
            store(c + cols.start if gate_before else cols.start, cols.size,
                  d * pre)
            d = d * part(after_ref)
        if activation:
            d = d * slope
        if inside is not None:
            d = jnp.where(inside, d, 0)
        # the block's rows over eight of the block below: a roll up by j
        ext = jnp.concatenate([d, carry[:, cols]], axis=0)
        carry[:, cols] = d[:_HALO]
        dz = sum((pltpu.roll(ext, rows + _HALO - j, 0)[:rows] if j else d)
                 * w_ref[taps - 1 - j:taps - j, cols] for j in range(taps))
        for j in range(taps):
            dw_ref[0, taps - 1 - j, :, cols] += _folded(d * past[j])
        if bias:        # its gradient: the pre-activation's, summed
            dw_ref[0, taps, :, cols] += _folded(d)
        if gate_before:
            store(cols.start, cols.size, dz * x)
            dz = dz * before
        store(x_at + cols.start, cols.size, dz)

    def store(start, size, value):
        dp_ref[0, :, pl.ds(pl.multiple_of(start, _LANE), size)] = (
            value.astype(dp_ref.dtype))

    _chunks([d.shape[-1] for d in dy_refs], chunk)
    if drest_ref is not None:
        dp_ref[0, :, x_at + c:] = drest_ref[0]


def _geometry(p, w, gate_before, gate_after):
    (b, t, width), (c, _) = p.shape, w.shape
    parts = 1 + gate_before + gate_after
    rows = _rows(t, width, c, p.dtype)
    return b, t, width, c, parts, rows, pl.cdiv(t, rows)


def _params():
    """Rows in order (the carried rows), batches in any; the blocks that
    ``_rows`` holds within ``_VMEM``, and room for the eight-row views, the
    taps' sums and what the compiler spills."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM + (12 << 20))


# ``_run_fwd`` and ``_run_bwd`` are jitted functions of their own, as in
# ops/gated_delta.py: a model's layers then share one trace and one
# lowering of each kernel
def _taps_rows(w, bias):
    """The taps as rows [taps, C] in float32, the bias one row more."""
    rows = w.T.astype(_F32)
    if bias is None:
        return rows
    return jnp.concatenate([rows, bias[None].astype(_F32)])


@functools.partial(jax.jit, static_argnames=(
    "gate_before", "gate_after", "activation", "split", "interpret"))
def _run_fwd(p, w, bias, gate_before, gate_after, activation, split,
             interpret):
    b, t, width, c, parts, rows, n = _geometry(p, w, gate_before, gate_after)
    wrows = w.shape[1] + (bias is not None)   # taps, and the bias
    a_part = [pl.BlockSpec((1, rows, c), functools.partial(
        lambda k, b, i: (b, i, k), k)) for k in range(parts)]
    ys = pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, bias=bias is not None,
                          gate_before=gate_before, gate_after=gate_after,
                          activation=activation),
        out_shape=[jax.ShapeDtypeStruct((b, t, m), p.dtype)
                   for m in split or (c,)], grid=(b, n),
        in_specs=[pl.BlockSpec((wrows, c), lambda b, i: (0, 0))] + a_part,
        out_specs=[pl.BlockSpec((1, rows, m), lambda b, i: (b, i, 0))
                   for m in split or (c,)],
        scratch_shapes=[pltpu.VMEM((_HALO, c), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="causal_conv_fwd")(
            _taps_rows(w, bias), *[p] * parts)
    return tuple(ys) if split else ys[0]


@functools.partial(jax.jit, static_argnames=(
    "gate_before", "gate_after", "activation", "interpret"))
def _run_bwd(p, w, bias, dys, drest, gate_before, gate_after, activation,
             interpret):
    """``dys`` the gradients of the result's pieces, one if it is whole."""
    b, t, width, c, parts, rows, n = _geometry(p, w, gate_before, gate_after)
    wrows = w.shape[1] + (bias is not None)   # taps, and the bias
    halo = _HALO * 4 // p.dtype.itemsize      # a sublane tile of the dtype
    per = rows // halo

    def at(i):
        return n - 1 - i

    def a_part(k):
        return [pl.BlockSpec((1, rows, c), lambda b, i: (b, at(i), k))]

    def with_halo(k):
        # the sublane tile that ends where the block starts
        return a_part(k) + [pl.BlockSpec(
            (1, halo, c),
            lambda b, i: (b, jnp.maximum(at(i) * per - 1, 0), k))]

    in_specs = [pl.BlockSpec((wrows, c), lambda b, i: (0, 0))]
    args = [_taps_rows(w, bias)]
    if gate_before:
        in_specs += with_halo(0)
        args += [p, p]
    if gate_after:
        in_specs += a_part(parts - 2)
        args += [p]
    in_specs += with_halo(parts - 1) + [
        pl.BlockSpec((1, rows, d.shape[-1]), lambda b, i: (b, at(i), 0))
        for d in dys]
    args += [p, p, *dys]
    if drest is not None:
        in_specs.append(pl.BlockSpec((1, rows, width - parts * c),
                                     lambda b, i: (b, at(i), 0)))
        args.append(drest)
    dp, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, t=t, halo=halo,
                          bias=bias is not None, gate_before=gate_before,
                          gate_after=gate_after, activation=activation),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct((b, wrows, 8, c), _F32)],
        grid=(b, n), in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, rows, width), lambda b, i: (b, at(i), 0)),
                   pl.BlockSpec((1, wrows, 8, c), lambda b, i: (b, 0, 0, 0))],
        scratch_shapes=[pltpu.VMEM((_HALO, c), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="causal_conv_bwd")(*args)
    dw = jnp.sum(dw, axis=(0, 2))
    if bias is None:
        return dp, dw.T.astype(w.dtype), None
    return dp, dw[:-1].T.astype(w.dtype), dw[-1].astype(bias.dtype)


def causal_conv_kernels(p, w, bias=None, *, gate_before=False,
                        gate_after=False, activation=False, split=(),
                        interpret=False):
    """``causal_conv`` as the two kernels, whatever the backend;
    ``interpret=True`` runs them in the interpreter, off the chip."""
    if not _supported(p.shape, w.shape, p.dtype, gate_before, gate_after,
                      split):
        raise ValueError(f"no kernel for p {p.shape} {p.dtype}, w {w.shape}, "
                         f"split {split}")
    return _kernels(p, w, bias, gate_before, gate_after, activation,
                    tuple(split), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _kernels(p, w, bias, gate_before, gate_after, activation, split,
             interpret):
    return _kernels_fwd(p, w, bias, gate_before, gate_after, activation,
                        split, interpret)[0]


def _kernels_fwd(p, w, bias, gate_before, gate_after, activation, split,
                 interpret):
    # the kernels index with 32-bit integers; under the tests' x64 mode
    # their Python constants would trace as 64-bit beside them
    with jax.enable_x64(False):
        y = _run_fwd(p, w, bias, gate_before, gate_after, activation, split,
                     interpret)
    rest = _parts(p, w.shape[0], gate_before, gate_after)[3]
    return (y, rest), (p, w, bias)


def _kernels_bwd(gate_before, gate_after, activation, split, interpret, res,
                 g):
    # jax keeps the call site's scopes for a custom_vjp's backward, under
    # ``transpose(``: the kernel reads as .../gdn_conv/.../causal_conv_bwd
    dy, drest = g
    with jax.enable_x64(False):
        return _run_bwd(*res, dy if split else (dy,), drest, gate_before,
                        gate_after, activation, interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)
