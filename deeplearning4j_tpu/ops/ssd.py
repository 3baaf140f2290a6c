"""The selective state-space scan of Mamba-2 over a sequence, in its
chunkwise ("state-space duality") form: two Pallas kernels where the shapes
are the chip's, ``jax.numpy`` everywhere else, and one function that chooses
(``resolve_ssd``).

Per head ``h``, a state ``S`` in ``R^{P x N}`` starting at zero, with a
scalar decay a head and position:

    a_t = exp(dt_t A)                      A < 0, dt_t > 0
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

(Dao & Gu, "Transformers are SSMs", arXiv:2405.21060; the mixer of the
Nemotron-H family.) ``B`` and ``C`` come in ``G`` groups, head ``h``
reading group ``h // (H / G)``. Run token by token that is ``T`` rank-one
updates in sequence. Here the sequence is cut into chunks of ``chunk``
positions: with ``cum`` the running sum of ``dt A`` inside a chunk,

    L_ij    = exp(cum_i - cum_j)  for j <= i, else 0
    y_local = ((C B^T) o L) (dt x)                     inside the chunk
    S_c     = sum_j exp(cum_last - cum_j) dt_j x_j B_j^T    its closing state
    S_prev  = the state before the chunk: S <- exp(cum_last) S + S_c
    y       = y_local + exp(cum) (S_prev C) + D x

so that no operation walks positions one by one. ``C B^T`` is made once a
GROUP and ``B``, ``C`` are never repeated a head. The running sums, the
decays and the states are float32; the products take their operands at the
policy's compute dtype and accumulate in float32. Both forms compute this,
and the tests hold both to the token-by-token recurrence.

**The kernels** (``ssd_fwd``, ``ssd_bwd``, under one ``jax.custom_vjp``;
PR 44). The grid is (batch, group, steps of ``_STEP_CHUNKS`` chunks), the
last axis in order, and a grid step walks the group's ``R = H / G`` heads:
their states are ONE ``[N, R P]`` float32 array in VMEM scratch from the
sequence's first chunk to its last (a head's ``[N, P]`` at its own lanes),
``B``, ``C`` and ``C B^T`` are loaded and made once a group and chunk, and
the carry ``S <- exp(cum_last) S + S_c`` is float32 on the vector units.
The state goes to HBM once a chunk, as the copy the backward starts that
chunk from, and nothing else is kept: ``L``, ``(C B^T) o L``, ``dt x`` and
``to_end`` live in VMEM for one chunk. ``ssd_bwd`` walks the steps from the
last with the states' gradient resident the same way, makes the chunk's
matrices again from x, dt, B, C and the saved state, and writes dx, dB and
dC (summed over the group's heads in the kernel), d(dt) and the running
sum's gradient once, and ``D``'s as one row a group. x, y and their
gradients are read and written where they lie, as ``[chunk, R P]`` blocks
of the ``[B, T, H P]`` view, B and C as ``[chunk, N]`` blocks of
``[B, T, G N]``. The lanes of a block are walked in UNITS of
``max(P, 128)``, whole lane tiles that hold whole heads (two heads of 64 a
tile): a head's own ``[chunk, chunk]`` product is taken over its whole
unit, which costs the matrix units what the head's lanes alone would, and
a lane mask keeps the head's part, so no slice cuts a tile. dt and ``cum``
arrive a head a column (``[T, R]`` a group) and are spread over the head's
lanes in VMEM by one lane broadcast each, which the decay matrix shares;
``cum`` also arrives a head a row, for ``L``'s other side. The running
sum's gradient is taken from the decay matrix's own gradient, each entry
added at its row and taken at its column, so that the two cancel to the
last bit where the sum over a chunk has to be zero (``dA`` is the sum of it
all). What XLA keeps beside the kernels: the running sum of ``dt A`` and
its two relayouts (1 MB arrays), the reverse running sum of its gradient,
and the sums that end in ``dA`` and ``dD``.

**The ``jax.numpy`` form** (``_chunked``), under autodiff: the chunks' local
products over all chunks at once and the states before each chunk in closed
form (one product with the [chunks, chunks] matrix of decays between chunk
ends, at the highest precision). It runs wherever the kernels do not (the
CPU, float64, widths that are no whole lane tiles) and is their oracle on
the chip (``chip_smoke.py``).

``resolve_ssd`` is the one place that chooses, from shape, dtype, chunk and
backend (as ``resolve_gated_delta`` and ``resolve_causal_conv`` choose
theirs); callers go through ``ssd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import attention_pallas as _ap
from deeplearning4j_tpu.utils import dtypes as _dtypes

#: chunks a grid step of the kernels takes (PERF.md section 6, PR 44: 2
#: measured against 1, a tenth slower, and 4, 3% faster for twice the
#: unrolled body to trace and lower)
_STEP_CHUNKS = 2
#: bytes a kernel may hold in VMEM: its double-buffered blocks, the group's
#: states and one chunk's matrices (``_vmem_bytes``)
_VMEM = 40 << 20

_LANE = 128
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def resolve_ssd(x_shape, b_shape, dtype, chunk=128):
    """The whole dispatch decision, from what the call shows: the function
    that runs the scan for ``x`` [B, T, H, P] and ``B`` [B, T, G, N] of
    ``dtype`` in chunks of ``chunk``. The kernels: a TPU backend; float32
    or bfloat16 (float64, the gradient checks' dtype, has no matrix unit to
    go to); a group's heads ``R P`` and the state ``N`` whole lane tiles
    wide, with ``P`` a divisor or a multiple of a lane tile (a
    ``[chunk, R P]`` block of the ``[B, T, H P]`` view then lies where the
    group lies, and a head's lanes are a tile's part or whole tiles);
    ``chunk`` a multiple of the lane tile (the decay matrix's sides); the
    group's states, a step's blocks and a chunk's matrices within
    ``_VMEM``. The ``jax.numpy`` chunkwise form everywhere else."""
    h, p = x_shape[2:]
    g, n = b_shape[2:]
    if h % g:
        raise ValueError(f"{h} heads are no multiple of {g} groups")
    if not _ap.backend_is_tpu():
        return _chunked
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return _chunked
    if (h // g * p) % _LANE or n % _LANE or chunk % _LANE:
        return _chunked
    if _LANE % p and p % _LANE:
        return _chunked
    if _vmem_bytes(_geometry(x_shape, b_shape, chunk, _STEP_CHUNKS)) > _VMEM:
        return _chunked
    return _kernels


def ssd(x, dt, a, b, c, d, *, chunk=128):
    """``x`` [B, T, H, P]; ``dt`` [B, T, H] (the step, > 0, float32);
    ``a`` [H] (< 0); ``b``, ``c`` [B, T, G, N]; ``d`` [H]. Returns ``y``
    [B, T, H, P] in ``x``'s dtype."""
    with jax.named_scope("ssd_core"):
        return resolve_ssd(x.shape, b.shape, x.dtype, chunk)(
            x, dt, a, b, c, d, chunk)


def _padded(xs, pad):
    """Each ``x`` [B, T, ...] with ``pad`` positions of zeros after its
    last: a padded position decays nothing and writes nothing (dt 0)."""
    if not pad:
        return xs
    return [jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in xs]


def _chunked(x, dt, a, b, c, d, chunk):
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    cd, ad = _dtypes.compute_dtypes_for(x.dtype)
    q = min(chunk, t)
    nc = -(-t // q)
    x, dt, b, c = _padded((x, dt, b, c), nc * q - t)
    # heads before time, chunks apart: [B, nc, G, (R,) Q, ...]
    xc = x.reshape(bsz, nc, q, g, r, p).transpose(0, 1, 3, 4, 2, 5)
    dtc = dt.astype(ad).reshape(bsz, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4).astype(cd)
    cc = c.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4).astype(cd)
    cum = jnp.cumsum(dtc * a.astype(ad).reshape(g, r, 1), axis=-1)  # <= 0
    last = cum[..., -1]                                  # [B, nc, G, R]

    # inside a chunk: masked before the exponential, since above the
    # diagonal the difference is positive and may overflow
    seen = jnp.tril(jnp.ones((q, q), bool))              # j <= i
    diff = cum[..., :, None] - cum[..., None, :]         # [B,nc,G,R,i,j]
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
    cb = jnp.einsum("bcgin,bcgjn->bcgij", cc, bc, preferred_element_type=ad)
    dtx = dtc[..., None] * xc.astype(ad)                 # [B,nc,G,R,Q,P]
    local = (cb[:, :, :, None] * decay).astype(cd)
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip", local, dtx.astype(cd),
                   preferred_element_type=ad)

    # each chunk's closing state, then the states the chunks start from
    to_end = jnp.exp(last[..., None] - cum)              # [B,nc,G,R,Q]
    states = jnp.einsum("bcgrjp,bcgjn->bcgrpn",
                        (to_end[..., None] * dtx).astype(cd), bc,
                        preferred_element_type=ad)
    if nc > 1:
        # ends[c] = sum of ``last`` up to chunk c; chunk e's closing state
        # reaches the start of chunk c > e decayed by exp(ends[c-1] - ends[e])
        ends = jnp.cumsum(last, axis=1)
        before = ends - last
        later = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
        between = jnp.exp(jnp.where(
            later, before[:, :, None] - ends[:, None, :], -jnp.inf))
        prev = jnp.einsum("bcegr,begrpn->bcgrpn", between, states,
                          precision=_HI)
        carried = jnp.einsum("bcgin,bcgrpn->bcgrip", cc, prev.astype(cd),
                             preferred_element_type=ad)
        y = y + jnp.exp(cum)[..., None] * carried
    y = y + d.astype(ad).reshape(g, r, 1, 1) * xc.astype(ad)
    # [B, nc, G, R, Q, P] -> [B, T, H, P]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, nc * q, h, p)
    return y[:, :t].astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# A grid step is one group's ``_STEP_CHUNKS`` chunks with the ``R`` heads it
# serves. Everything a head wide lies in the group's ``[Q, R P]`` layout, a
# head at its own ``P`` lanes, as x lies in HBM. Where a head meets its own
# ``[Q, Q]`` matrix the lanes are walked in UNITS of ``max(P, 128)``: a
# unit is whole lane tiles and holds whole heads (two of 64 in a tile, or
# one head of 256 over two tiles), the product is taken over the whole unit
# (which costs the matrix units what the head's own lanes would) and a lane
# mask keeps the head's part; no slice cuts a tile. The chunk axis is the
# grid's last and runs in order (backward: reversed by the index maps).
#
# What fills first is not the matrix units: the permute units (a lane
# broadcast of a head's column, a sum over lanes) and the one store slot.
# So a head's column is broadcast once a quantity and shared by what needs
# it, and sums over lanes that end in one place are added before they are
# taken (PERF.md section 6, PR 44).

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


def _lanes(rows, width):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _own(v, p, j):
    """[Q, unit] ``v`` with every lane but head ``j``'s (the unit's
    ``j``-th run of ``P``) zero; ``v`` itself where the unit is one head."""
    q, unit = v.shape
    if unit == p:
        return v
    lane = _lanes(q, unit)
    return jnp.where((lane >= j * p) & (lane < (j + 1) * p), v,
                     jnp.zeros_like(v))


def _spread(columns, p, unit):
    """[Q, R P] with head ``h``'s value (``columns[h]``, [Q, >= unit], the
    head's column along the lanes) at each of its ``P`` lanes."""
    k = unit // p
    lane = _lanes(columns[0].shape[0], unit)
    out = []
    for h in range(0, len(columns), k):
        part = columns[h + k - 1][:, :unit]
        for j in range(k - 2, -1, -1):
            part = jnp.where(lane < (j + 1) * p, columns[h + j][:, :unit],
                             part)
        out.append(part)
    return _cat(out)


def _row_sums(*parts):
    """[Q, 1]: the sum over the lanes of all of ``parts`` ([Q, .] each),
    parts as wide as each other added first."""
    total = {}
    for part in parts:
        width = part.shape[1]
        total[width] = part if width not in total else total[width] + part
    return sum(jnp.sum(part, axis=1, keepdims=True)
               for part in total.values())


def _by_head(mats, v, p, unit, dims):
    """[Q, R P]: at head ``h``'s lanes, ``mats[h]`` (a [Q, Q] matrix,
    contracted as ``dims`` say) times head ``h``'s lanes of ``v``."""
    k = unit // p
    lane = _lanes(v.shape[0], unit)
    out = []
    for u in range(v.shape[1] // unit):
        part = v[:, u * unit:(u + 1) * unit]
        res = _dot(mats[(u + 1) * k - 1], part, dims)
        for j in range(k - 2, -1, -1):
            res = jnp.where(lane < (j + 1) * p,
                            _dot(mats[u * k + j], part, dims), res)
        out.append(res)
    return _cat(out)


def _chunk(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, at, p, cd):
    """One chunk's matrices for every head of the group, in VMEM: what
    ``_chunked`` makes over all chunks at once. dt and ``cum`` come a head
    a column [Q, R]: each column is broadcast along the lanes once and
    serves both the [Q, R P] layout (dt, ``exp(cum)``: the decay into the
    chunk, ``exp(cum_last - cum)``: to its end) and the head's decay
    matrix ``L``, whose other side is ``cum`` a head a row [R, Q]
    (positions along the lanes)."""
    q = at.stop - at.start
    unit = max(p, _LANE)
    x = x_ref[0, at, :].astype(_F32)                     # [Q, R P]
    b, c = b_ref[0, at, :].astype(cd), c_ref[0, at, :].astype(cd)
    dt, cum = dt_ref[0, 0, at, :], cum_ref[0, 0, at, :]  # [Q, R]
    cum_rows = rows_ref[0, 0, :, at]                     # [R, Q]
    heads = range(dt.shape[1])

    def columns(cols, width):
        return [jnp.broadcast_to(cols[:, h:h + 1], (q, width)) for h in heads]

    cum_b = columns(cum, max(q, unit))
    cum_w = _spread(cum_b, p, unit)
    seen = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow
    decay = [jnp.exp(jnp.where(seen, cum_b[h][:, :q] - cum_rows[h:h + 1, :],
                               -1e30)) for h in heads]
    cb = _dot(c, b, _NT)                                 # [Q, Q]
    dt_w = _spread(columns(dt, unit), p, unit)
    dtx = dt_w * x
    return dict(x=x, b=b, c=c, cb=cb, dt_w=dt_w, into=jnp.exp(cum_w),
                to_end=jnp.exp(cum_w[q - 1:q, :] - cum_w), dtx=dtx,
                dtx_c=dtx.astype(cd), decay=decay,
                local=[(cb * l).astype(cd) for l in decay])


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, d_ref, y_ref,
                *rest, chunks, q, p, cd):
    st_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])
    unit = max(p, _LANE)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    for m in range(chunks):
        at = slice(m * q, (m + 1) * q)
        ch = _chunk(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, at, p, cd)
        s = s_scr[...]                                   # [N, R P]
        if st_ref is not None:
            st_ref[0, 0, m] = s          # what the backward starts from
        y = (_by_head(ch["local"], ch["dtx_c"], p, unit, _NN)
             + ch["into"] * _dot(ch["c"], s.astype(cd), _NN)
             + d_ref[0] * ch["x"])
        y_ref[0, at, :] = y.astype(y_ref.dtype)
        w = (ch["to_end"] * ch["dtx"]).astype(cd)
        s_scr[...] = ch["into"][q - 1:q, :] * s + _dot(ch["b"], w, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, d_ref, st_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, drows_ref,
                dd_ref, ds_scr, *, chunks, q, p, cd):
    r = dt_ref.shape[-1]
    unit = max(p, _LANE)
    k = unit // p
    head = _lanes(q, r)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    for m in reversed(range(chunks)):
        at = slice(m * q, (m + 1) * q)
        ch = _chunk(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, at, p, cd)
        x, b, c, cb = (ch[n] for n in ("x", "b", "c", "cb"))
        into, to_end, dtx, dtx_c = (ch[n] for n in ("into", "to_end", "dtx",
                                                    "dtx_c"))
        s, ds = st_ref[0, 0, m], ds_scr[...]             # [N, R P]
        s_c, ds_c = s.astype(cd), ds.astype(cd)
        dy = dy_ref[0, at, :].astype(_F32)
        dy_c = dy.astype(cd)
        dye = into * dy                  # through exp(cum) (C S)
        dye_c = dye.astype(cd)
        w = to_end * dtx                 # what the closing state takes in
        dw = _dot(b, ds_c, _NN)                          # [Q, R P]
        carry = into[q - 1:q, :]                         # [1, R P]
        ds_scr[...] = carry * ds + _dot(c, dye_c, _TN)

        # the running sum's gradient but for the decay matrices' share:
        # through exp(cum) (C S), through to_end, and at the chunk's last
        # position the closing state's and the carry's
        dend = dw * w
        closing = (jnp.sum(dend, axis=0, keepdims=True)
                   + carry * jnp.sum(ds * s, axis=0, keepdims=True))
        rest = dye * _dot(c, s_c, _NN) - dend
        rest = jnp.where(last, rest + closing, rest)

        # through the local product, a head at a time: (C B^T) o L's
        # gradient is dy (dt x)^T over the head's own lanes. The decay's
        # share goes to the running sum as it stands, each entry added at
        # its row and taken at its column: the two sides then cancel to
        # the last bit wherever the sum over a chunk has to be zero, which
        # a sum of dy y against one of d(dt) dt, rounded apart, does not
        # (under bfloat16 dA, the sum of it all, read 2-70 times off)
        dcb = jnp.zeros_like(cb)
        dcum = jnp.zeros((q, r), _F32)
        for h in range(r):
            u, j = divmod(h, k)
            lanes = slice(u * unit, (u + 1) * unit)
            dlocal = ch["decay"][h] * _dot(_own(dy_c[:, lanes], p, j),
                                           dtx_c[:, lanes], _NT)
            dcb = dcb + dlocal
            ddecay = dlocal * cb
            dcum = jnp.where(
                head == h, _row_sums(ddecay, _own(rest[:, lanes], p, j)),
                dcum)
            drows_ref[0, 0, h:h + 1, at] = -jnp.sum(ddecay, axis=0,
                                                    keepdims=True)
        dcum_ref[0, 0, at, :] = dcum

        ddtx = _by_head(ch["local"], dy_c, p, unit, _TN) + to_end * dw
        dx_ref[0, at, :] = (d_ref[0] * dy + ch["dt_w"] * ddtx).astype(
            dx_ref.dtype)
        dd_ref[0, 0] += jnp.sum(dy * x, axis=0, keepdims=True)
        ddt_w = ddtx * x
        ddt = jnp.zeros((q, r), _F32)
        for h in range(r):
            u, j = divmod(h, k)
            ddt = jnp.where(
                head == h,
                _row_sums(_own(ddt_w[:, u * unit:(u + 1) * unit], p, j)), ddt)
        ddt_ref[0, 0, at, :] = ddt

        dcb_c = dcb.astype(cd)
        dc_ref[0, at, :] = (_dot(dcb_c, b, _NN)
                            + _dot(dye_c, s_c, _NT)).astype(dc_ref.dtype)
        db_ref[0, at, :] = (_dot(dcb_c, c, _TN)
                            + _dot(w.astype(cd), ds_c, _NT)).astype(
                                db_ref.dtype)


def _geometry(x_shape, b_shape, chunk, step):
    """(B, T padded, G, H / G, P, N, chunk, chunks, chunks a grid step) for
    ``step`` chunks a grid step where the sequence has as many."""
    bsz, t, h, p = x_shape
    g, n = b_shape[2:]
    nc = -(-t // chunk)
    m = min(step, nc)
    nc = -(-nc // m) * m
    return bsz, nc * chunk, g, h // g, p, n, chunk, nc, m


def _vmem_bytes(geometry):
    """What the backward kernel, the larger, holds: the blocks of x, dy, dx,
    the saved states and B, C, dB, dC twice each (float32 at most), the
    states' gradient, and room for one chunk's matrices (a dozen arrays of
    x's width and three a head of the decay matrix's)."""
    _, _, _, r, p, n, q, _, m = geometry
    wide, state, narrow = q * r * p * 4, n * r * p * 4, q * n * 4
    blocks = 2 * m * (3 * wide + state + 4 * narrow)
    return blocks + state + 12 * wide + 3 * r * q * q * 4


def _running_sum(u, reverse=False):
    """The running sum of [B, chunks, Q, H] inside each chunk (``reverse``:
    from the chunk's end), as one product with the triangle of ones at the
    highest precision, all the heads along the lanes. XLA's own
    (``reduce_window``) took 0.047 ms a call at the cell's shape in this
    layout and 0.94 with 8 x 8 behind the summed axis; the product does
    not show among a layer's sixteen largest operations (under 0.011)."""
    q = u.shape[2]
    ones = jnp.tril(jnp.ones((q, q), _F32))
    return jnp.einsum("ji,bcjh->bcih" if reverse else "ij,bcjh->bcih", ones,
                      u, precision=_HI)


def _laid_out(x, dt, a, b, c, d, geometry):
    """The kernels' views: x [B, T', H P], B and C [B, T', G N] as they lie
    (T' = T padded to whole grid steps), dt and the running sum of dt A
    inside each chunk a head a column [B, G, T', R], the running sum a
    head a row [B, G, R, T'], and D over its head's lanes [G, 1, R P]; all
    but x, B, C float32."""
    bsz, tp, g, r, p, n, q, nc, _ = geometry
    x, dt, b, c = _padded((x, dt, b, c), tp - x.shape[1])
    dt = dt.astype(_F32)
    cum = _running_sum((dt * a.astype(_F32)).reshape(bsz, nc, q, g * r))

    def by_group(u):
        return u.reshape(bsz, tp, g, r).transpose(0, 2, 1, 3)

    cum = by_group(cum)
    return (x.reshape(bsz, tp, g * r * p), b.reshape(bsz, tp, g * n),
            c.reshape(bsz, tp, g * n), by_group(dt), cum,
            cum.transpose(0, 1, 3, 2),
            jnp.repeat(d.astype(_F32), p).reshape(g, 1, r * p))


def _specs(geometry, reverse):
    """Block specs of (x, B or C, a column a head, a row a head, D, the
    states) for the grid (B, G, steps); ``reverse`` walks the steps from
    the last."""
    _, _, _, r, p, n, q, nc, m = geometry
    steps = nc // m

    def at(i):
        return steps - 1 - i if reverse else i

    return (pl.BlockSpec((1, m * q, r * p), lambda b, g, i: (b, at(i), g)),
            pl.BlockSpec((1, m * q, n), lambda b, g, i: (b, at(i), g)),
            pl.BlockSpec((1, 1, m * q, r),
                         lambda b, g, i: (b, g, at(i), 0)),
            pl.BlockSpec((1, 1, r, m * q), lambda b, g, i: (b, g, 0, at(i))),
            pl.BlockSpec((1, 1, r * p), lambda b, g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, m, n, r * p),
                         lambda b, g, i: (b, g, at(i), 0, 0)))


#: batches and groups in any order, a group's steps in theirs
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM + (8 << 20))


# ``_run_fwd`` and ``_run_bwd`` are jitted functions of their own: the
# kernels' bodies are unrolled Python, and a model's layers then share one
# trace and one lowering of each (PERF.md section 6, PR 35)
@functools.partial(jax.jit, static_argnames=("how", "save"))
def _run_fwd(x, dt, a, b, c, d, how, save):
    chunk, step, cd, interpret = how
    geometry = bsz, tp, g, r, p, n, q, nc, m = _geometry(x.shape, b.shape,
                                                         chunk, step)
    x_spec, bc_spec, col_spec, row_spec, d_spec, st_spec = _specs(
        geometry, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((bsz, tp, g * r * p), x.dtype)]
    out_specs = [x_spec]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bsz, g, nc, n, r * p), _F32))
        out_specs.append(st_spec)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=m, q=q, p=p, cd=cd),
        out_shape=out_shape, grid=(bsz, g, nc // m),
        in_specs=[x_spec, bc_spec, bc_spec, col_spec, col_spec, row_spec,
                  d_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ssd_fwd")(*_laid_out(x, dt, a, b, c, d, geometry))
    y = out[0].reshape(bsz, tp, g * r, p)[:, :x.shape[1]]
    return y, (out[1] if save else None)


@functools.partial(jax.jit, static_argnames=("how",))
def _run_bwd(x, dt, a, b, c, d, states, dy, how):
    chunk, step, cd, interpret = how
    geometry = bsz, tp, g, r, p, n, q, nc, m = _geometry(x.shape, b.shape,
                                                         chunk, step)
    t, h = x.shape[1], g * r
    x_spec, bc_spec, col_spec, row_spec, d_spec, st_spec = _specs(
        geometry, reverse=True)
    x2, b2, c2, dt2, cum, rows, d_w = _laid_out(x, dt, a, b, c, d, geometry)
    dy = _padded((dy,), tp - t)[0].reshape(x2.shape)
    dx, db, dc, ddt, dcum, drows, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=m, q=q, p=p, cd=cd),
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(b2.shape, b.dtype),
                   jax.ShapeDtypeStruct(c2.shape, c.dtype),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(rows.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, g, 1, r * p), _F32)],
        grid=(bsz, g, nc // m),
        in_specs=[x_spec, bc_spec, bc_spec, col_spec, col_spec, row_spec,
                  d_spec, st_spec, x_spec],
        out_specs=[x_spec, bc_spec, bc_spec, col_spec, col_spec, row_spec,
                   pl.BlockSpec((1, 1, 1, r * p),
                                lambda b, g, i: (b, g, 0, 0))],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ssd_bwd")(x2, b2, c2, dt2, cum, rows, d_w, states, dy)

    def tokens_first(u):                 # [B, G, T', R] -> [B, T', H]
        return u.transpose(0, 2, 1, 3).reshape(bsz, tp, h)

    # cum is a running sum inside the chunk: dt_j A reaches every cum_i,
    # i >= j
    dcum = tokens_first(dcum + drows.transpose(0, 1, 3, 2))
    dda = _running_sum(dcum.reshape(bsz, nc, q, h), reverse=True)
    dda = dda.reshape(bsz, tp, h)[:, :t]
    dt32, a32 = dt.astype(_F32), a.astype(_F32)
    ddt = tokens_first(ddt)[:, :t] + dda * a32
    return (dx.reshape(bsz, tp, h, p)[:, :t], ddt.astype(dt.dtype),
            jnp.sum(dda * dt32, axis=(0, 1)).astype(a.dtype),
            db.reshape(bsz, tp, g, n)[:, :t], dc.reshape(bsz, tp, g, n)[:, :t],
            jnp.sum(dd.reshape(bsz, h, p), axis=(0, 2)).astype(d.dtype))


def ssd_kernels(x, dt, a, b, c, d, *, chunk=128, interpret=False):
    """``ssd`` as the two kernels, whatever the dispatch would say;
    ``interpret=True`` runs them in the interpreter, off the chip."""
    cd, _ = _dtypes.compute_dtypes_for(x.dtype)
    return _scan(x, dt, a, b, c, d,
                 (chunk, _STEP_CHUNKS, jnp.dtype(cd), interpret))


def _kernels(x, dt, a, b, c, d, chunk):
    return ssd_kernels(x, dt, a, b, c, d, chunk=chunk,
                       interpret=not _ap.backend_is_tpu())


# ``how``: (chunk, chunks a grid step, the products' operand dtype,
# interpret), read where the call is made and static from there on
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, how):
    # the kernels index with 32-bit integers; under the tests' x64 mode
    # their Python constants would trace as 64-bit beside them
    with jax.enable_x64(False):
        return _run_fwd(x, dt, a, b, c, d, how, False)[0]


def _scan_fwd(x, dt, a, b, c, d, how):
    with jax.enable_x64(False):
        y, states = _run_fwd(x, dt, a, b, c, d, how, True)
    return y, (x, dt, a, b, c, d, states)


def _scan_bwd(how, res, dy):
    # jax keeps the call site's scopes for a custom_vjp's backward, under
    # ``transpose(``: the kernel reads as .../ssm/ssd_core/ssd_bwd
    with jax.enable_x64(False):
        return _run_bwd(*res, dy, how)


_scan.defvjp(_scan_fwd, _scan_bwd)
