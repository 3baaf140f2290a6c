"""The row movement of a routed-experts layer: token rows into sorted
order, and sorted rows back at their tokens, summed (Pallas, TPU).

What nn/layers/moe.py needs around its grouped products, where ``r``,
the rows inside the groups, is known only on the device:

* ``rows_into_order``: ``out[p] = scale[p] src[tok[p]]`` for the sorted
  slots ``p < r`` (the tokens' rows in the forward pass, the result's
  gradient in the backward pass, which also wants ``<other[p],
  src[tok[p]]>``, the weights' gradient, from the same rows);
* ``rows_back``: ``acc[t] = sum of scale[p] rows[p]`` over the slots
  ``p < r`` with ``tok[p] == t`` (the weighted results in the forward
  pass, the sorted rows' gradient in the backward pass).

Mosaic refuses a one-row slice of a [rows, d] array in HBM ("Slice shape
along dimension 0 must be aligned to tiling (8)"), so a kernel cannot
copy single rows between the buffers as they lie; a dynamic one-row load
or store in VMEM is fine for an unpacked dtype. Both kernels therefore
keep the SMALL side of their movement, the [N, d] float32 array (the
source of the one, the sum of the other; 64 MiB at the benchmark's
[8192, 2048], taken in column blocks where it is larger), resident in
VMEM and walk the sorted buffer a [256, d] tile at a time where it lies:
a row is one load and one store (about 30 bundles a row for the sum by
the compiler's schedule), with no sort and no relayout. Row tiles past
``r`` are skipped in the index maps and in the body, as ``grouped_matmul``
skips the tiles outside its groups, so the work follows the rows routed
here and not the ``N k`` slots of the buffers.

Chosen by measured calls on the chip (PERF.md section 6, PR 33) over
loops of row chunks in XLA, whose scatter-add is a sort of the chunk's
indices, a gather of its rows into that order and a sorted scatter (143
ns a row at [32768, 2048]), and over one-row DMAs on [rows, d / 128, 128]
views, which pay a relayout of every buffer on both sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import attention_pallas as _ap

_TILE = 256            # sorted rows a grid step
_ZERO_ROWS = 64        # rows of the sum cleared a loop pass at the start
_RESIDENT = 64 << 20   # bytes of the [N, columns] array kept in VMEM


def _columns(n, d, itemsize):
    """How many columns of an [n, d] array stay resident at a time: all
    of them if they fit ``_RESIDENT`` (the benchmark's [8192, 2048]
    float32 just does) or ``d`` cannot be cut into whole lane tiles, else
    the widest multiple of 128 dividing ``d`` that fits; the grid then
    walks the column blocks one after the other."""
    if n * d * itemsize <= _RESIDENT or d % 128:
        return d
    return max(c for c in range(128, d, 128)
               if d % c == 0 and (n * c * itemsize <= _RESIDENT or c == 128))


def live_tile(i, r, tile):
    """Row tile of a sorted buffer that grid step ``i`` holds: its own
    while that has a row below ``r``, the last such tile after it (a block
    that does not change is neither fetched nor written again)."""
    return jnp.minimum(i, jnp.maximum((r - 1) // tile, 0))


def _live(tile):
    """Index map of a [tile, columns] block of a sorted buffer
    (``live_tile``)."""
    def index(c, i, tok_ref, r_ref, *_):
        return live_tile(i, r_ref[0], tile), c
    return index


def _back_kernel(tok_ref, r_ref, scale_ref, rows_ref, out_ref, *rest, m,
                 tile, keep):
    kept_ref = rest[0] if keep is not None else None
    acc, sem = rest[-2:]
    c, i = pl.program_id(0), pl.program_id(1)
    base = i * tile
    r = r_ref[0]
    n, cols = acc.shape

    @pl.when(i == 0)
    def _():
        rows = min(_ZERO_ROWS, n)

        def clear(b, _):
            acc[pl.ds(jnp.minimum(b * rows, n - rows), rows), :] = (
                jnp.zeros((rows, cols), acc.dtype))
            return 0

        jax.lax.fori_loop(0, pl.cdiv(n, rows), clear, 0)

    @pl.when(base < r)
    def _():
        if keep is not None:
            kept_ref[...] = rows_ref[...].astype(keep)
        # a packed dtype is read a whole sublane tile at a time
        g = 8 * (4 // min(4, rows_ref.dtype.itemsize))

        def group(j, _):
            blk = rows_ref[pl.ds(pl.multiple_of(j * g, g), g), :].astype(
                acc.dtype)
            for u in range(g):
                p = base + j * g + u
                at = jnp.minimum(p, m - 1)
                t = tok_ref[at]
                add = jnp.where(p < r, blk[u:u + 1, :] * scale_ref[at], 0)
                acc[pl.ds(t, 1), :] = acc[pl.ds(t, 1), :] + add
            return 0

        jax.lax.fori_loop(0, tile // g, group, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        copy = pltpu.make_async_copy(
            acc, out_ref.at[:, pl.ds(c * cols, cols)], sem)
        copy.start()
        copy.wait()


def rows_back(rows, tok, r, n, scale, acc_dtype, keep=None):
    """``acc[t] = sum over p < r, tok[p] == t, of scale[p] rows[p]``:
    ``rows`` [M, d] float32 or bfloat16 in sorted order, ``tok`` int32 [M]
    the token of each slot, ``r`` (traced) how many slots count, ``scale``
    [M] in ``acc_dtype``; the sum [n, d] is taken and returned in
    ``acc_dtype``. Nothing from slot ``r`` on is added, whatever it holds.
    With ``keep`` (a dtype) also a copy of ``rows`` in that dtype, written
    a tile at a time: its tiles past ``r`` are not written."""
    m, d = rows.shape
    tile = min(_TILE, -(-m // 16) * 16)
    size = jnp.dtype(acc_dtype).itemsize
    cols = _columns(n, d, size)
    out_shape = [jax.ShapeDtypeStruct((n, d), acc_dtype)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if keep is not None:
        out_shape.append(jax.ShapeDtypeStruct((m, d), keep))
        out_specs.append(pl.BlockSpec((tile, cols), _live(tile)))
    out = pl.pallas_call(
        functools.partial(_back_kernel, m=m, tile=tile, keep=keep),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // cols, pl.cdiv(m, tile)),
            in_specs=[pl.BlockSpec((tile, cols), _live(tile))],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, cols), acc_dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(n + 6 * tile) * cols * size + (8 << 20)),
        interpret=not _ap.backend_is_tpu(), name="moe_rows_back")(
            tok, jnp.reshape(r, (1,)).astype(jnp.int32),
            scale.astype(acc_dtype), rows)
    return out[0], (out[1] if keep is not None else None)


def _into_kernel(tok_ref, r_ref, src_ref, *rest, m, tile, scaled, dotted):
    rest = list(rest)
    scale_ref = rest.pop(0) if scaled else None
    other_ref = rest.pop(0) if dotted else None
    out_ref = rest.pop(0)
    dots_ref = rest.pop(0) if dotted else None
    src, buf, sem = rest
    c, i = pl.program_id(0), pl.program_id(1)
    base = i * tile
    r = r_ref[0]

    @pl.when(i == 0)
    def _():
        copy = pltpu.make_async_copy(
            src_ref.at[:, pl.ds(c * src.shape[1], src.shape[1])], src, sem)
        copy.start()
        copy.wait()

    @pl.when(base < r)
    def _():
        def group(j, _):
            for u in range(8):
                t = tok_ref[jnp.minimum(base + j * 8 + u, m - 1)]
                buf[pl.ds(pl.multiple_of(j * 8, 8) + u, 1), :] = src[
                    pl.ds(t, 1), :]
            return 0

        jax.lax.fori_loop(0, tile // 8, group, 0)
        rows = buf[...]
        if dotted:
            inside = base + jax.lax.broadcasted_iota(
                jnp.int32, (tile, 1), 0) < r
            dots_ref[...] = jnp.where(inside, jnp.sum(
                other_ref[...].astype(rows.dtype) * rows, axis=-1,
                keepdims=True), 0)
        if scaled:
            rows = rows * scale_ref[...]
        out_ref[...] = rows.astype(out_ref.dtype)


def rows_into_order(src, tok, r, dtype, scale=None, other=None):
    """``out[p] = src[tok[p]]`` (times ``scale[p]``) in ``dtype`` for the
    sorted slots ``p < r``, a tile of rows at a time: ``src`` [N, d]
    (float32: a dynamic one-row load wants an unpacked dtype) is brought
    into VMEM whole, once, and a row is one load and one store there.
    Tiles past ``r`` are NOT written and hold whatever the buffer held.
    With ``other`` [M, d], also ``dots[p] = <other[p], src[tok[p]]>`` [M]
    in ``src``'s dtype, which is the accumulation dtype (zero from ``r``
    to its tile's end, unwritten after it; ``other`` is not read past
    that tile either)."""
    m, (n, d) = tok.shape[0], src.shape
    tile = min(_TILE, -(-m // 16) * 16)
    cols = _columns(n, d, src.dtype.itemsize)
    live = _live(tile)
    args, in_specs = [src], [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [jax.ShapeDtypeStruct((m, d), dtype)]
    out_specs = [pl.BlockSpec((tile, cols), live)]
    if scale is not None:
        args.append(scale.reshape(m, 1).astype(src.dtype))
        in_specs.append(pl.BlockSpec(
            (tile, 1), lambda c, i, *refs: (live(c, i, *refs)[0], 0)))
    if other is not None:
        args.append(other)
        in_specs.append(pl.BlockSpec((tile, cols), live))
        # a column block's share of the products; summed below
        out_shape.append(jax.ShapeDtypeStruct((d // cols, m, 1), src.dtype))
        out_specs.append(pl.BlockSpec(
            (None, tile, 1), lambda c, i, *refs: (c, live(c, i, *refs)[0], 0)))
    out = pl.pallas_call(
        functools.partial(_into_kernel, m=m, tile=tile,
                          scaled=scale is not None,
                          dotted=other is not None),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(d // cols, pl.cdiv(m, tile)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, cols), src.dtype),
                            pltpu.VMEM((tile, cols), src.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=((n + 6 * tile) * cols * src.dtype.itemsize
                              + (8 << 20))),
        interpret=not _ap.backend_is_tpu(), name="moe_rows_fwd")(
            tok, jnp.reshape(r, (1,)).astype(jnp.int32), *args)
    dots = None if other is None else jnp.sum(out[1], axis=0).reshape(m)
    return out[0], dots
