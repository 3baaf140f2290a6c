"""The expert FFN of a routed-experts layer on rows sorted by expert: the
grouped products with the gate's activation between them, forward and
backward, every part sized by the rows inside the groups (Pallas, TPU).

``ys[p] = (act(xs[p] Wg_e) * (xs[p] Wu_e)) Wd_e`` for the rows ``p`` of
group ``e`` (``act(xs[p] Wu_e) Wd_e`` where the experts have no gate), the
group sizes known only on the device. The products are
``ops/grouped_matmul.py``'s (``grouped_matmul`` forward, and the two halves
of its backward pass), whose grids are the row tiles the groups touch.
What lies between them is written here, because left to XLA
it is elementwise fusions over all ``M`` slots of buffers of which the
first ``r = sum(sizes)`` hold a row (an eighth, where a chip holds an
eighth of the experts):

* **one function with its backward written by hand** (``jax.custom_vjp``).
  It keeps the sorted rows, the pre-activations in the compute dtype and
  the group sizes; ``h``, the down product's operand, is made again in the
  backward by the kernel that reads the pre-activations anyway, and no
  float32 [M, f] array outlives a kernel;
* **two kernels for the activation**, ``moe_act_fwd`` and ``moe_act_bwd``,
  whose row tiles past ``r`` are skipped as ``ops/moe_rows.py`` skips them
  (``r`` scalar-prefetched, the block index held at the last live tile so
  a dead step neither fetches nor writes, the body under ``pl.when``).
  The forward rounds ``act(g) * u``, taken in the accumulation dtype from
  the compute dtype's pre-activations, to the compute dtype: the bits of
  the expression XLA ran. The backward writes the pre-activations'
  gradient and ``h`` from the same three reads. ``act`` is the caller's
  callable, traced into the kernel body with ``jax.vjp`` of it: no table
  of names;
* **gate and up as one product**: ``Wg ‖ Wu`` joined to [G, d, 2 f] and
  read at the compute dtype, so that the kernels read and write one
  [M, 2 f] array, the input gradient is ONE product over ``K = 2 f`` whose float32
  accumulator adds what were two [M, d] results summed by a fusion over
  every slot, and the weight gradient is one [G, d, 2 f] product whose
  halves the updater's fusions read as the two leaves' gradients. The
  join is a pass of its own over the rounded weights (XLA rounds each
  leaf, then joins: 2.8 of 36 ms a step under ``moe_experts`` in
  sdar-train-bd4-t4096), which the products' two passes over the weights
  repay; PERF.md section 6, PR 50 has the measured call against the other
  form, two products and a row movement that adds as it reads.

Rows past the last group are not computed, as in ``grouped_matmul``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.ops import attention_pallas as _ap
from deeplearning4j_tpu.ops.grouped_matmul import (
    grouped_matmul, input_gradient, weight_gradient)
from deeplearning4j_tpu.ops.moe_rows import live_tile

_TILE = 512            # sorted rows a grid step, at most
_PIECE = 512           # columns of a block in flight at a time
_VMEM = 24 << 20       # what a kernel's blocks and pieces may hold
_VMEM_MARGIN = 4 << 20


def act_vmem_bytes(tile, f, gated, itemsize):
    """VMEM the backward kernel holds in one grid step (the forward holds
    less): its blocks, the cotangent and ``h`` [tile, f], the
    pre-activations and their gradient [tile, f] or, gated, [tile, 2 f],
    twice (the pipeline's two buffers) at the compute dtype's size, and
    the pieces in flight, some ten [tile, ``_PIECE``] arrays in the
    accumulation dtype's four bytes. The one count: the tile is chosen by
    it and Mosaic is asked for it."""
    lanes = -(-f // 128) * 128
    blocks = 2 * (6 if gated else 4) * tile * lanes * itemsize
    return blocks + 10 * tile * min(_PIECE, lanes) * 4


def _act_tile(m, f, gated, itemsize):
    """Rows a grid step: the largest power of two up to ``_TILE`` whose
    count is within ``_VMEM``, and no more than the buffer holds. In the
    five cells that is 512 at f 512 and 768 and 256 at f 1536 and at the
    ungated f 1856, all bfloat16, and the chip read 512, 256 and 128 alike
    there (PERF.md section 6, PR 50), so no cell needs the choice: it is
    here for float32 callers on the chip (twice the bytes a block) and for
    an f wider than theirs, which a constant 256 would hand Mosaic past
    its VMEM."""
    tile = _TILE
    while tile > 16 and act_vmem_bytes(tile, f, gated, itemsize) > _VMEM:
        tile //= 2
    return min(tile, -(-m // 16) * 16)


def _pieces(f):
    return [(c, min(_PIECE, f - c)) for c in range(0, f, _PIECE)]


def _fwd_kernel(r_ref, pre_ref, h_ref, *, act, gated, tile):
    f, acc = h_ref.shape[1], jnp.float32

    @pl.when(pl.program_id(0) * tile < r_ref[0])
    def _():
        for c, w in _pieces(f):
            a = act(pre_ref[:, c:c + w].astype(acc))
            if gated:
                a = a * pre_ref[:, f + c:f + c + w].astype(acc)
            h_ref[:, c:c + w] = a.astype(h_ref.dtype)


def _bwd_kernel(r_ref, dh_ref, pre_ref, dpre_ref, h_ref, *, act, gated, tile):
    f, acc = h_ref.shape[1], jnp.float32

    @pl.when(pl.program_id(0) * tile < r_ref[0])
    def _():
        for c, w in _pieces(f):
            dh = dh_ref[:, c:c + w].astype(acc)
            a, back = jax.vjp(act, pre_ref[:, c:c + w].astype(acc))
            if gated:
                u = pre_ref[:, f + c:f + c + w].astype(acc)
                dpre_ref[:, f + c:f + c + w] = (dh * a).astype(dpre_ref.dtype)
                a, dh = a * u, dh * u
            h_ref[:, c:c + w] = a.astype(h_ref.dtype)
            dpre_ref[:, c:c + w] = back(dh)[0].astype(dpre_ref.dtype)


# in a jitted function of its own, as ``gated_delta._run_fwd``: the layers
# of a model share one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=(
    "kernel", "name", "widths", "tile", "act", "gated", "interpret"))
def _act_call(kernel, name, r, operands, widths, *, tile, act, gated,
              interpret):
    """One of the two kernels over the row tiles below ``r``
    (``moe_rows.live_tile``): ``operands`` [M, their width] in, an [M, w]
    array of the operands' dtype out for each of ``widths``, ``tile`` rows
    (``_act_tile``) a grid step."""
    m, dtype = operands[0].shape[0], operands[0].dtype
    f = widths[-1]
    live = lambda i, r_ref: (live_tile(i, r_ref[0], tile), 0)
    return pl.pallas_call(
        functools.partial(kernel, act=act, gated=gated, tile=tile),
        out_shape=[jax.ShapeDtypeStruct((m, w), dtype) for w in widths],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(m, tile),),
            in_specs=[pl.BlockSpec((tile, a.shape[1]), live)
                      for a in operands],
            out_specs=[pl.BlockSpec((tile, w), live) for w in widths]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(act_vmem_bytes(tile, f, gated, dtype.itemsize)
                              + _VMEM_MARGIN)),
        interpret=interpret, name=name)(
            jnp.reshape(r, (1,)).astype(jnp.int32), *operands)


def _act(kernel, name, sizes, operands, widths, act, gated):
    (m, _), itemsize = operands[0].shape, operands[0].dtype.itemsize
    return _act_call(kernel, name, jnp.sum(sizes), operands, widths,
                     tile=_act_tile(m, widths[-1], gated, itemsize), act=act,
                     gated=gated, interpret=not _ap.backend_is_tpu())


def _read_at(dtype, w_up, w_gate=None):
    """The weights as a grouped product reads them: the float32 leaves
    rounded to ``dtype`` here, so that ``grouped_matmul``'s own rounding
    finds nothing to do, and the first product's ``Wg ‖ Wu`` [G, d, 2 f]
    joined before it. A pass over the held experts' weights and no row,
    named apart from the kernel it feeds (``moe_weights_ms.*`` reads the
    name), written in both rules from the leaves: XLA merges the two and
    keeps the copy unless ``recompute_moe`` stands between, where a copy
    kept by hand would be 100 MB a layer."""
    with jax.named_scope(_scopes.MOE_WEIGHTS):
        if w_gate is not None:
            w_up = jnp.concatenate([w_gate, w_up], axis=-1)
        return w_up.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ffn(xs, w_gate, w_up, w_down, sizes, act, out_dtype, rows):
    return _ffn_fwd(xs, w_gate, w_up, w_down, sizes, act, out_dtype, rows)[0]


def _ffn_fwd(xs, w_gate, w_up, w_down, sizes, act, out_dtype, rows):
    gated, f = w_gate is not None, w_down.shape[1]
    pre = grouped_matmul(xs, _read_at(xs.dtype, w_up, w_gate), sizes,
                         xs.dtype, rows)
    h, = _act(_fwd_kernel, "moe_act_fwd", sizes, [pre], (f,), act, gated)
    ys = grouped_matmul(h, _read_at(h.dtype, w_down), sizes, out_dtype, rows)
    return ys, (xs, pre, w_gate, w_up, w_down, sizes)


def _ffn_bwd(act, out_dtype, rows, res, dys):
    """``grouped_matmul``'s backward for the down product, its operand
    ``h`` made again between the two halves, then for the joined one."""
    xs, pre, w_gate, w_up, w_down, sizes = res
    gated, f = w_gate is not None, w_down.shape[1]
    dh = input_gradient(dys, _read_at(xs.dtype, w_down), sizes, xs.dtype,
                        rows)
    dpre, h = _act(_bwd_kernel, "moe_act_bwd", sizes, [dh, pre],
                   (pre.shape[1], f), act, gated)
    dw_down = weight_gradient(h, dys, sizes, w_down.dtype, rows)
    dxs = input_gradient(dpre, _read_at(xs.dtype, w_up, w_gate), sizes,
                         xs.dtype, rows)
    dw_in = weight_gradient(xs, dpre, sizes, w_up.dtype, rows)
    if not gated:
        return dxs, None, dw_in, dw_down, None
    return (dxs, dw_in[..., :f].astype(w_gate.dtype), dw_in[..., f:],
            dw_down, None)


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def expert_ffn(xs, w_gate, w_up, w_down, group_sizes, act, out_dtype,
               rows_a_group=None):
    """``xs`` [M, d] (float32 or bfloat16, the compute dtype) through the
    experts its rows are grouped by -> [M, d] of ``out_dtype``: ``w_gate``
    (or None: experts without a gate), ``w_up`` [G, d, f] and ``w_down``
    [G, f, d] are read at ``xs``'s dtype, every product accumulated in
    float32 and the activation taken there; ``group_sizes`` int32 [G] sums
    to at most M, group ``g`` is the rows from ``sum(sizes[:g])``, and the
    rows past the last group are not computed (the result holds whatever
    the buffer held there). Differentiable in ``xs`` and the weights (the
    weights' gradients accumulated in float32 over a group's rows,
    returned in their own dtype, exactly zero for an empty group).
    ``rows_a_group`` sizes the products' row tile as in
    ``grouped_matmul``."""
    return _ffn(xs, w_gate, w_up, w_down, group_sizes.astype(jnp.int32), act,
                out_dtype, rows_a_group)
