"""Grouped matrix product: rows of one matrix against a stack of matrices,
each run of consecutive rows (a group) against its own (Pallas, TPU).

What a routed-experts layer needs once its token rows are sorted by
expert (nn/layers/moe.py): ``out[r] = x[r] @ w[g]`` for the rows ``r`` of
group ``g``, the group sizes known only on the device. The kernels are the
``gmm`` / ``tgmm`` that ship with jax
(``jax.experimental.pallas.ops.tpu.megablox``): the row tiles a group
touches are listed in scalar-prefetched metadata and the grid's length is
the number of tiles really touched, so the matrix work follows the rows
inside the groups and not the buffer they lie in. This module adds the
tiling, the backward pass in float32 (the library's own VJP hands the
weight gradient back in the operand's dtype, bfloat16 here) and the
interpreter off the chip.

Chosen by one measured call on the chip (PERF.md section 6, PR 32) over
``jax.lax.ragged_dot``, which XLA:TPU lowers to a grouped kernel of its
own with row tiles of 512: the three products of an expert FFN, forward
and backward, over 8 experts of [2048, 1536] in a buffer of 32,768 rows
took 8.84 / 9.84 / 19.16 ms with these kernels against 10.11 / 11.34 /
23.03 ms at 4,096 rows spread evenly, 4,096 unevenly and all 32,768; 0.86
ms of the difference is ``ragged_dot`` writing zeros into the rows past
the last group, which these kernels leave unwritten.

Rows past the last group are NOT computed and the result holds whatever
the buffer held there: callers mask them (``jnp.where``, not a product).

From PR 50 the routed layer reaches these kernels through
``ops/expert_ffn.py``, one function for the expert FFN with its backward
written by hand over ``grouped_matmul`` and the two halves of its backward
pass, ``input_gradient`` and ``weight_gradient`` (the down product's
operand is made again between its two): gate and up are ONE product there
(``Wg ‖ Wu`` joined to [G, d, 2 f], so the input gradient's sum over the
two happens in the product's float32 accumulator and the ``add_any`` over
[M, d] that two ``_grouped_bwd`` results needed is gone), and the
activation between the products is two kernels that skip the row tiles
past the groups as these do. Nothing under the scope ``moe_experts`` walks
all ``M`` slots of a sorted buffer any more; what does, in the layer, is
the two sorts and the weights' scalars in sorted order
(``moe.routed_experts``).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import attention_pallas as _ap

# the package's __init__ rebinds the name `gmm` to the function
_gmm = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

# row tile, contraction tile, column tile: the best of seven measured at
# [32768, 2048] x [8, 2048, 1536] with 512 rows a group (the call above);
# the transposed product of the weight gradient keeps its output tile in
# VMEM and takes 512 cubed. A kernel visits every (group, row tile) pair
# that holds a row, so groups far smaller than the tile pay for a tile
# each: the row tile follows the rows a group expects, down to 128
# (PERF.md section 6, PR 34: at [40960, 2048] x [16, 2048, 512] with 80
# rows a group, forward and backward of an expert FFN 3.53 ms at 512,
# 3.32 at 256, 3.22 at 128, 3.27 at 64; at 400 rows 4.23 / 4.05 / 3.95 /
# 4.37; a weight-gradient tile of 256 loses at every load)
_TM, _TK, _TN = 512, 2048, 512
_TM_LEAST = 128
_T_WGRAD = 512


def _row_tile(m, rows_a_group):
    """The smallest power of two from ``_TM_LEAST`` up to ``_TM`` that
    holds ``rows_a_group`` (``_TM`` where the caller knows none), halved
    until it divides ``m``: the kernels take whole row tiles only."""
    t = _TM_LEAST
    while t < min(_TM if rows_a_group is None else rows_a_group, _TM):
        t *= 2
    while m % t:
        t //= 2
    return t


def _tiling(tm, k, n):
    """A contraction between one and two ``_TK`` long is cut into two equal
    tiles where they are whole lane tiles (3072 = 2 x 1536: the input
    gradient over gate ‖ up at f 1536, 1.47 ms a call against 1.97 where
    the kernel cuts 2048 + 1024 and masks the short tile; PERF.md section
    6, PR 50); otherwise the kernel's own cut stands (2688)."""
    tk = min(_TK, k)
    if _TK < k < 2 * _TK and k % 256 == 0:
        tk = k // 2
    return tm, tk, min(_TN, n)


def _wgrad_tiling(tm, k, n):
    return min(tm, _T_WGRAD), min(_T_WGRAD, k), min(_T_WGRAD, n)


def _kernel(fn, *args, **kw):
    """The library's kernels index with 32-bit integers throughout; under
    `jax_enable_x64` (the tests' gradient-check mode) their Python
    constants would trace as 64-bit beside them."""
    with jax.enable_x64(False):
        return fn(*args, **kw)


def _how(m, rows_a_group):
    """What the caller's shapes and the backend decide for a product over
    ``m`` rows: the interpreter off the chip, and the row tile."""
    return not _ap.backend_is_tpu(), _row_tile(m, rows_a_group)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped(x, w, group_sizes, out_dtype, interpret, tm):
    return _kernel(_gmm.gmm, x, w.astype(x.dtype), group_sizes, out_dtype,
                   _tiling(tm, x.shape[1], w.shape[2]), interpret=interpret)


def _grouped_fwd(x, w, group_sizes, out_dtype, interpret, tm):
    return (_grouped(x, w, group_sizes, out_dtype, interpret, tm),
            (x, w, group_sizes))


def _dx(g, w, group_sizes, dtype, interpret, tm):
    g = g.astype(dtype)  # the matrix units round an operand anyway
    return _kernel(_gmm.gmm, g, w.astype(dtype), group_sizes, dtype,
                   _tiling(tm, w.shape[2], w.shape[1]), transpose_rhs=True,
                   interpret=interpret)


def _dw(x, g, group_sizes, dtype, interpret, tm):
    dw = _kernel(_gmm.tgmm, x.swapaxes(0, 1), g.astype(x.dtype), group_sizes,
                 jnp.float32, _wgrad_tiling(tm, x.shape[1], g.shape[1]),
                 interpret=interpret)
    return dw.astype(dtype)


def _grouped_bwd(out_dtype, interpret, tm, res, g):
    x, w, group_sizes = res
    return (_dx(g, w, group_sizes, x.dtype, interpret, tm),
            _dw(x, g, group_sizes, w.dtype, interpret, tm), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, group_sizes, out_dtype, rows_a_group=None):
    """``x`` [M, K] times ``w`` [G, K, N] by groups of rows -> [M, N] of
    ``out_dtype``, accumulated in float32. ``group_sizes`` int32 [G] sums
    to at most M; group ``g`` is the rows from ``sum(sizes[:g])``. ``x`` is
    float32 or bfloat16 and ``w`` is read at ``x``'s dtype. Differentiable
    in ``x`` and ``w`` (the weight gradient is accumulated in float32 over
    a group's rows, returned in ``w``'s own dtype, and exactly zero for an
    empty group). ``rows_a_group``, where the caller's shapes say how many
    rows a group expects (a router's ``N k / E``), sizes the row tile; the
    result does not depend on it."""
    return _grouped(x, w, group_sizes.astype(jnp.int32), out_dtype,
                    *_how(x.shape[0], rows_a_group))


def input_gradient(g, w, group_sizes, dtype, rows_a_group=None):
    """The first half of ``grouped_matmul``'s backward pass, for a caller
    that writes its own (ops/expert_ffn.py): ``g`` [M, N], the result's
    gradient, against ``w`` [G, K, N] transposed -> [M, K] of ``dtype``,
    both read at ``dtype``."""
    return _dx(g, w, group_sizes.astype(jnp.int32), dtype,
               *_how(g.shape[0], rows_a_group))


def weight_gradient(x, g, group_sizes, dtype, rows_a_group=None):
    """The second half: ``x`` [M, K] and ``g`` [M, N], read at ``x``'s
    dtype -> [G, K, N], accumulated in float32 over a group's rows,
    returned as ``dtype``, exactly zero for an empty group."""
    return _dw(x, g, group_sizes.astype(jnp.int32), dtype,
               *_how(x.shape[0], rows_a_group))
