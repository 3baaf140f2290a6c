"""The gated delta rule over a sequence, in its chunkwise form: two Pallas
kernels where the shapes are the chip's, ``jax.numpy`` everywhere else,
and one function that chooses (``resolve_gated_delta``).

Per value head, a state ``S`` in ``R^{dk x dv}`` starting at zero:

    S' = exp(g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(Yang et al., "Gated Delta Networks", arXiv:2412.06464; the linear
attention of Qwen3-Next.) Run token by token that is ``T`` rank-one
updates in sequence. Here the sequence is cut into chunks of ``CHUNK``
positions: with ``G`` the running sum of ``g`` inside a chunk,

    A = strict_lower(beta_i (k_i . k_j) exp(G_i - G_j))
    U = (I + A)^-1 (beta v),   W = (I + A)^-1 (beta exp(G) k)

depend on the chunk alone, and the state goes from chunk to chunk:

    v' = U - W S
    o  = (q exp(G)) S + lower(q k^T exp(G_i - G_j)) v'
    S  = exp(G_last) S + (k exp(G_last - G))^T v'

``(I + A)^-1`` of the unit lower triangular ``I + A`` is exact as a
product of ``log2(CHUNK)`` factors, ``(I - A)(I + A^2)(I + A^4)...``: A is
strictly lower, so ``A^CHUNK = 0`` and the series ends.

The state, the decays and the triangular inverse are float32 (the
inverse's products and the two solves at the highest precision); the
other products take their operands at the policy's compute dtype and
accumulate in float32. A key head serves ``Hv / Hk`` value heads: the
shared products are done once a key head. Both forms compute this, and
the tests hold both to the token-by-token recurrence.

**The kernels** (``gdn_fwd``, ``gdn_bwd``, under one ``jax.custom_vjp``;
PR 35). The grid is (batch, key head, steps of ``_STEP_CHUNKS`` chunks),
the last axis in order, so a value head's state is a [dk, dv] float32
array in VMEM scratch from its first chunk to its last; it goes to HBM
once a chunk, as the copy the backward starts that chunk from, and
nothing else is kept: ``gdn_bwd`` walks the steps from the last, the
state's gradient resident the same way, makes the chunk's matrices again
from q, k, v, g, beta and the saved state, and writes dq, dk (summed
over the key head's value heads), dv, dg, dbeta once. q, k, v, o are
read and written where they lie, as [C, d] blocks of the [B, T, H d]
views; g and beta arrive as rows (positions along the lanes) and are
turned into columns in VMEM. The solve's backward is closed-form: with
``X = (I + A)^-T dY`` the right side's gradient is ``X`` and ``A``'s is
``-X Y^T``, two products where autodiff walks the ten of the inverse.
What XLA keeps beside the kernels: the running sum of g and its
transpose into rows, the reverse running sum of its gradient.

**The ``jax.numpy`` form** (``_chunked``): the chunk-local matrices as
products over all chunks at once, one ``lax.scan`` over the chunk states,
autodiff's backward with the chunk-local matrices computed again
(``_chunk_local``). It runs wherever the kernels do not (the CPU, float64,
head widths that are no whole lane tiles) and is their oracle on the chip
(``chip_smoke.py``). Measured against it on the chip and not kept, PERF.md
section 6, PR 34: the scan carrying the state alone with one product a
step, ``S <- exp(G_last) S - (K'^T W) S + K'^T U``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import attention_pallas as _ap
from deeplearning4j_tpu.utils import dtypes as _dtypes

#: positions a chunk holds. 64 as the published implementations: the
#: inverse is six products of [64, 64], and a head walks T / 64 states
CHUNK = 64

#: chunks a grid step of the kernels takes where the states it saves fit
#: ``_STEP_BYTES`` (PERF.md section 6, PR 35: 4 measured against 1, 2, 8)
_STEP_CHUNKS = 4
#: bytes of chunk-start states a grid step writes, one [dk, dv] float32 a
#: chunk and value head: what sizes the kernels' blocks in VMEM
_STEP_BYTES = 512 * 1024
#: bytes of state a key head's value heads may hold in VMEM between chunks
_STATE_BYTES = 1024 * 1024

_LANE = 128
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C]:
    ``sum_k (-a)^k``, which ends at ``k = C - 1``, as the product
    ``(I + b)(I + b^2)(I + b^4)...`` with ``b = -a``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    power = -a
    inv = eye + power                 # the series below the power 2
    for _ in range((c - 1).bit_length() - 1):   # ... 4, 8, ... up to c
        power = jnp.matmul(power, power, precision=_HI)
        inv = inv + jnp.matmul(inv, power, precision=_HI)
    return inv


def resolve_gated_delta(q_shape, v_shape, dtype):
    """The whole dispatch decision, from what the call shows: True where
    the two kernels run the recurrence, False where the ``jax.numpy``
    chunkwise form does. The kernels: a TPU backend, key and value heads
    that are whole lane tiles wide (a ``[C, d]`` block of a ``[B, T, H d]``
    view then lies where the head lies), value heads a multiple of the key
    heads whose states fit VMEM together, float32 or bfloat16 (float64,
    the gradient checks' dtype, has no matrix unit to go to)."""
    if not _ap.backend_is_tpu():
        return False
    (hk, dk), (hv, dv) = q_shape[2:], v_shape[2:]
    if hv % hk or dk % _LANE or dv % _LANE:
        return False
    if hv // hk * dk * dv * 4 > _STATE_BYTES:
        return False
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16))


def gated_delta_rule(q, k, v, g, beta):
    """``q``, ``k`` [B, T, Hk, dk] (already normalised and scaled as the
    caller wants them), ``v`` [B, T, Hv, dv], ``g`` (log decay, <= 0) and
    ``beta`` (write strength) [B, T, Hv]; value head ``j`` reads key head
    ``j // (Hv // Hk)``. Returns ``o`` [B, T, Hv, dv] in ``v``'s dtype."""
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads are no multiple of "
                         f"{q.shape[2]} key heads")
    with jax.named_scope("gdn_core"):
        if resolve_gated_delta(q.shape, v.shape, v.dtype):
            return gated_delta_kernels(
                q, k, v, g, beta, interpret=not _ap.backend_is_tpu())
        return _chunked(q, k, v, g, beta)


def _padded(xs, pad):
    """Each ``x`` [B, T, ...] with ``pad`` positions of zeros after its
    last: a padded position writes nothing (beta 0) and decays nothing
    (g 0)."""
    return [jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in xs]


@jax.checkpoint
def _chunk_local(q, k, v, g, beta):
    """What the scan reads of every chunk, from products over all chunks
    at once: ``(U, W, q exp(G), lower(q k^T decay), k exp(G_last - G),
    exp(G_last))``. ``q``, ``k`` [B,Hk,N,C,dk], ``v`` [B,Hk,R,N,C,dv],
    ``g``, ``beta`` [B,Hk,R,N,C]. Recomputed in the backward pass, as the
    flash kernels recompute their probabilities: the decay matrix, ``A``
    and the inverse's five squarings and five products would otherwise
    stay in HBM from the forward, eleven [C, C] float32 matrices a chunk
    and value head (0.45 GB a layer at 4,096 tokens and 32 heads)."""
    c = q.shape[-2]
    cd, ad = _dtypes.compute_dtypes_for(v.dtype)
    gsum = jnp.cumsum(g, axis=-1)                        # G, <= 0
    seen = jnp.tril(jnp.ones((c, c), bool))              # j <= i
    diff = gsum[..., :, None] - gsum[..., None, :]
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow, and a where() after it would still hand
    # the overflow's gradient back
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)

    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k, precision=_HI)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[..., None] * kk[:, :, None] * decay, 0.0)
    inv = _unit_lower_inverse(a)                         # [B,Hk,R,N,C,C]
    u = jnp.matmul(inv, beta[..., None] * v, precision=_HI)
    w = jnp.matmul(inv, (beta * jnp.exp(gsum))[..., None] * k[:, :, None],
                   precision=_HI).astype(cd)             # [B,Hk,R,N,C,dk]

    qk = jnp.einsum("bhnid,bhnjd->bhnij", q.astype(cd), k.astype(cd),
                    preferred_element_type=ad)
    local = (qk[:, :, None] * decay).astype(cd)          # [B,Hk,R,N,C,C]
    q_in = (q[:, :, None] * jnp.exp(gsum)[..., None]).astype(cd)
    last = gsum[..., -1:]                                # [B,Hk,R,N,1]
    k_out = (k[:, :, None] * jnp.exp(last - gsum)[..., None]).astype(cd)
    carry = jnp.exp(last[..., 0])                        # [B,Hk,R,N]
    return u, w, q_in, local, k_out, carry


def _chunked(q, k, v, g, beta):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    cd, ad = _dtypes.compute_dtypes_for(v.dtype)
    c = CHUNK
    n = -(-t // c)
    pad = n * c - t
    if pad:
        q, k, v, g, beta = _padded((q, k, v, g, beta), pad)
    # heads before time, chunks apart: [B, Hk, (R,) N, C, ...]
    q = q.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, n, c, dk)
    k = k.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, n, c, dk)
    v = v.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, r, n, c, dv)
    g = g.astype(ad).transpose(0, 2, 1).reshape(b, hk, r, n, c)
    beta = beta.astype(ad).transpose(0, 2, 1).reshape(b, hk, r, n, c)

    u, w, q_in, local, k_out, carry = _chunk_local(q, k, v, g, beta)

    def chunk(s, xs):
        u_i, w_i, q_i, local_i, k_i, carry_i = xs
        v_new = u_i - jnp.matmul(w_i, s.astype(cd),
                                 preferred_element_type=ad)
        o = (jnp.matmul(q_i, s.astype(cd), preferred_element_type=ad)
             + jnp.matmul(local_i, v_new.astype(cd),
                          preferred_element_type=ad))
        s = carry_i[..., None, None] * s + jnp.einsum(
            "bhrcd,bhrce->bhrde", k_i, v_new.astype(cd),
            preferred_element_type=ad)
        return s, o

    def chunks_first(x):
        return jnp.moveaxis(x, 3, 0)

    s0 = jnp.zeros((b, hk, r, dk, dv), ad)
    _, o = jax.lax.scan(chunk, s0, tuple(
        chunks_first(x) for x in (u, w, q_in, local, k_out, carry)))
    # [N, B, Hk, R, C, dv] -> [B, T, Hv, dv]
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)
    return o[:, :t].astype(v.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# A grid step is one key head's ``_STEP_CHUNKS`` chunks with the ``Hv / Hk``
# value heads it serves: q and k are read once for all of them, ``k k^T`` and
# ``q k^T`` made once, and the key head's dq and dk leave as one sum. The
# chunk axis is the grid's last and runs in order (backward: reversed by
# the index maps), so a value head's state (backward: its gradient) is a
# [dk, dv] float32 array in VMEM scratch from its first chunk to its last.

def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _masks(c):
    """(i == j, i >= j, i > j) over a chunk's [C, C]."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row == col, row >= col, row > col


def _as_column(row, eye):
    """[1, C] -> [C, 1], against the diagonal mask: no transpose."""
    return _rowsum(jnp.where(eye, row, 0.0))


def _as_row(col, eye):
    """[C, 1] -> [1, C]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last_lane(row):
    """[1, C] -> its last entry as [1, 1]."""
    c = row.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    return _rowsum(jnp.where(at, row, 0.0))


def _inverses(mats):
    """``_unit_lower_inverse`` of each [C, C] matrix, the same products
    laid out for the matrix units. Two matrices ride side by side along
    the lanes, ``[x_a | x_b]`` [C, 2 C], and meet the block diagonal of
    their right sides, so that a product fills a whole tile of the units
    and not a quarter; the pairs' independent chains are written side by
    side, so that one's latency hides another's; ``b^k`` is zero above its
    ``k``-th subdiagonal, so a product leaves out the rows (down to a
    sublane tile) that can only be zero; and ``inv b^k`` and ``b^k b^k``
    share their right side, so they are one product of the stacked left
    sides."""
    count, c = len(mats), mats[0].shape[-1]
    if count % 2:
        mats = mats + [jnp.zeros_like(mats[0])]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    left = lane < c
    eye = (row == jnp.where(left, lane, lane - c)).astype(_F32)

    def times(x, p):
        """``[x_a p_a | x_b p_b]``."""
        return _dot(x, jnp.concatenate([jnp.where(left, p, 0.0),
                                        jnp.where(left, 0.0, p)], axis=0),
                    _NN, _HI)

    def first_row(k):
        return min(k, c) // 8 * 8

    def stacked(*parts):
        parts = [x for x in parts if x is not None]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def rows(x, start, stop):
        return x[start:stop] if stop > start else None

    powers = [-jnp.concatenate(mats[i:i + 2], axis=1)
              for i in range(0, len(mats), 2)]
    invs = [eye + p for p in powers]                     # sum_{j < k} b^j
    powers = [times(p, p) for p in powers]               # b^k, k = 2
    k = 2
    while k < c:
        lo, lo2 = first_row(k), first_row(2 * k)
        out = [times(stacked(rows(i, lo, c), rows(p, lo2, c)), p)
               for i, p in zip(invs, powers)]
        invs = [stacked(rows(i, 0, lo), i[lo:] + o[:c - lo])
                for i, o in zip(invs, out)]
        if lo2 < c:
            zeros = jnp.zeros((lo2, 2 * c), _F32) if lo2 else None
            powers = [stacked(zeros, o[c - lo:]) for o in out]
        k *= 2
    return [half for i in invs for half in (i[:, :c], i[:, c:])][:count]


def _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, chunks, heads, cd):
    """Every chunk's matrices for every value head of a grid step, in VMEM:
    what ``_chunk_local`` makes over all chunks at once, as a list over the
    chunks of (shared, [a head's]). ``gsum`` and ``beta`` come as rows
    [1, C] (a head's positions along the lanes, as they lie in HBM) and are
    turned into columns in VMEM (``_as_column``)."""
    c, dv = CHUNK, v_ref.shape[-1] // heads
    eye, seen, before = _masks(c)
    out = []
    for m in range(chunks):
        rows = slice(m * c, (m + 1) * c)
        q, k = q_ref[0, rows, :].astype(_F32), k_ref[0, rows, :].astype(_F32)
        shared = dict(q=q, k=k, kk=_dot(k, k, _NT, _HI),
                      qk=_dot(q.astype(cd), k.astype(cd), _NT))
        per_head = []
        for r in range(heads):
            j = r * chunks + m
            gsum, beta = g_ref[0, 0, 0, j:j + 1, :], b_ref[0, 0, 0, j:j + 1, :]
            g_col, b_col = _as_column(gsum, eye), _as_column(beta, eye)
            g_last = _last_lane(gsum)                    # [1, 1]
            decay = jnp.where(
                seen, jnp.exp(jnp.where(seen, g_col - gsum, 0.0)), 0.0)
            per_head.append(dict(
                a=jnp.where(before, b_col * shared["kk"] * decay, 0.0),
                decay=decay, b_col=b_col, e_col=jnp.exp(g_col),
                el_col=jnp.exp(g_last - g_col), carry=jnp.exp(g_last),
                v=v_ref[0, rows, r * dv:(r + 1) * dv].astype(_F32)))
        out.append((shared, per_head))
    flat = [x for _, per_head in out for x in per_head]
    for x, inv in zip(flat, _inverses([x.pop("a") for x in flat])):
        x["inv"] = inv
    for shared, per_head in out:
        q, k = shared["q"], shared["k"]
        for x in per_head:
            b_col, e_col = x["b_col"], x["e_col"]
            x.update(
                u=_dot(x["inv"], b_col * x["v"], _NN, _HI),
                w=_dot(x["inv"], (b_col * e_col) * k, _NN, _HI),
                local=(shared["qk"] * x["decay"]).astype(cd),
                q_in=(q * e_col).astype(cd),
                k_out=(k * x["el_col"]).astype(cd))
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunks,
                heads, cd):
    st_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])
    c, dv = CHUNK, v_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    local = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, chunks, heads, cd)
    for m, (_, per_head) in enumerate(local):
        rows = slice(m * c, (m + 1) * c)
        for r, x in enumerate(per_head):
            cols = slice(r * dv, (r + 1) * dv)
            s = s_scr[r]
            if st_ref is not None:
                st_ref[0, 0, r, m] = s       # what the backward starts from
            s_c = s.astype(cd)
            v_new = (x["u"] - _dot(x["w"].astype(cd), s_c, _NN)).astype(cd)
            o = _dot(x["q_in"], s_c, _NN) + _dot(x["local"], v_new, _NN)
            o_ref[0, rows, cols] = o.astype(o_ref.dtype)
            s_scr[r] = x["carry"] * s + _dot(x["k_out"], v_new, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, chunks, heads, cd):
    c, dv = CHUNK, v_ref.shape[-1] // heads
    eye, _, before = _masks(c)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    local = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, chunks, heads, cd)
    for m, (shared, per_head) in reversed(list(enumerate(local))):
        rows = slice(m * c, (m + 1) * c)
        q, k, kk, qk = (shared[name] for name in ("q", "k", "kk", "qk"))
        q_c, k_c = q.astype(cd), k.astype(cd)
        dq, dk = jnp.zeros_like(q), jnp.zeros_like(k)
        dkk, dqk = jnp.zeros_like(kk), jnp.zeros_like(qk)
        for r, x in enumerate(per_head):
            cols, j = slice(r * dv, (r + 1) * dv), r * chunks + m
            v, decay, b_col = x["v"], x["decay"], x["b_col"]
            e_col, el_col = x["e_col"], x["el_col"]
            s = st_ref[0, 0, r, m]
            s_c, w_c = s.astype(cd), x["w"].astype(cd)
            v_new = (x["u"] - _dot(w_c, s_c, _NN)).astype(cd)
            do = do_ref[0, rows, cols].astype(cd)
            ds = ds_scr[r]
            ds_c = ds.astype(cd)
            # through o, the state's update and v'
            dvn = _dot(x["local"], do, _TN) + _dot(x["k_out"], ds_c, _NN)
            dvn_c = dvn.astype(cd)
            dlocal = _dot(do, v_new, _NT)
            dq_in = _dot(do, s_c, _NT)
            dk_out = _dot(v_new, ds_c, _NT)
            dw = -_dot(dvn_c, s_c, _NT)
            ds_scr[r] = (x["carry"] * ds + _dot(x["q_in"], do, _TN)
                         - _dot(w_c, dvn_c, _TN))
            dcarry = jnp.sum(_rowsum(ds * s), axis=0, keepdims=True)
            # through the triangular system: with X = (I + A)^-T dY,
            # the right side gets X and A gets -X Y^T
            xu = _dot(x["inv"], dvn, _TN, _HI)
            xw = _dot(x["inv"], dw, _TN, _HI)
            da = jnp.where(before, -(_dot(xu, x["u"], _NT, _HI)
                                     + _dot(xw, x["w"], _NT, _HI)), 0.0)
            dv_ref[0, rows, cols] = (b_col * xu).astype(dv_ref.dtype)
            xwk = _rowsum(xw * k)
            dq = dq + dq_in * e_col
            dk = dk + (b_col * e_col) * xw + dk_out * el_col
            akd = da * decay
            db_col = _rowsum(xu * v) + e_col * xwk + _rowsum(akd * kk)
            dkk = dkk + akd * b_col
            dqk = dqk + dlocal * decay
            # d(decay) * decay, the decays' own derivative folded in
            dd = akd * b_col * kk + dlocal * decay * qk
            d_out = _rowsum(dk_out * k) * el_col
            dg_col = (_rowsum(dd) + (b_col * xwk + _rowsum(dq_in * q)) * e_col
                      - d_out)
            dg_row = (_as_row(dg_col, eye) - jnp.sum(dd, axis=0, keepdims=True)
                      + jnp.where(last, jnp.sum(d_out, axis=0, keepdims=True)
                                  + dcarry * x["carry"], 0.0))
            dg_ref[0, 0, 0, j:j + 1, :] = dg_row
            db_ref[0, 0, 0, j:j + 1, :] = _as_row(db_col, eye)
        dqk_c = dqk.astype(cd)
        dq_ref[0, rows, :] = (dq + _dot(dqk_c, k_c, _NN)).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (
            dk + _dot(dqk_c, q_c, _TN) + _dot(dkk, k, _NN, _HI)
            + _dot(dkk, k, _TN, _HI)).astype(dk_ref.dtype)


def _geometry(q, v):
    """(B, T padded, Hk, dk, Hv / Hk, dv, chunks, chunks a grid step)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n, r = -(-t // CHUNK), hv // hk
    m = max(1, min(_STEP_CHUNKS, n, _STEP_BYTES // (r * dk * dv * 4)))
    n = -(-n // m) * m
    return b, n * CHUNK, hk, dk, r, dv, n, m


def _laid_out(q, k, v, g, beta):
    """The kernels' views: q, k [B, T', Hk dk] and v [B, T', Hv dv] as
    they lie (T' = T padded to whole grid steps: a padded position writes
    nothing, beta 0, and decays nothing, g 0), the running sum of g inside
    each chunk and beta as [B, Hk, steps, (Hv / Hk) chunks, C] float32."""
    b, tp, hk, dk, r, dv, n, m = _geometry(q, v)
    pad = tp - q.shape[1]
    if pad:
        q, k, v, g, beta = _padded((q, k, v, g, beta), pad)

    def heads_first(x):
        x = x.astype(_F32).reshape(b, n // m, m, CHUNK, hk, r)
        return x.transpose(0, 4, 1, 5, 2, 3)

    gsum = jnp.cumsum(heads_first(g), axis=-1)
    shape = (b, hk, n // m, r * m, CHUNK)
    return (q.reshape(b, tp, hk * dk), k.reshape(b, tp, hk * dk),
            v.reshape(b, tp, hk * r * dv), gsum.reshape(shape),
            heads_first(beta).reshape(shape))


def _tokens_first(x, geometry, t):
    """[B, Hk, steps, (Hv / Hk) chunks, C] back to [B, T, Hv]."""
    b, tp, hk, _, r, _, n, m = geometry
    x = x.reshape(b, hk, n // m, r, m, CHUNK).transpose(0, 2, 4, 5, 1, 3)
    return x.reshape(b, tp, hk * r)[:, :t]


def _specs(geometry, reverse):
    """Block specs of (q or k, v, g or beta, states) for the grid (B, Hk,
    steps); ``reverse`` walks the steps from the last."""
    b, tp, hk, dk, r, dv, n, m = geometry
    steps = n // m

    def at(i):
        return steps - 1 - i if reverse else i

    return (pl.BlockSpec((1, m * CHUNK, dk), lambda b, h, i: (b, at(i), h)),
            pl.BlockSpec((1, m * CHUNK, r * dv),
                         lambda b, h, i: (b, at(i), h)),
            pl.BlockSpec((1, 1, 1, r * m, CHUNK),
                         lambda b, h, i: (b, h, at(i), 0, 0)),
            pl.BlockSpec((1, 1, r, m, dk, dv),
                         lambda b, h, i: (b, h, 0, at(i), 0, 0)))


#: batches and key heads in any order, a head's steps in theirs
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ``_run_fwd`` and ``_run_bwd`` are jitted functions of their own: the
# kernels' bodies are unrolled Python, and a model's layers then share one
# trace and one lowering of each (traced inline in every layer the cell's
# set-up read 11% longer; PERF.md section 6, PR 35)
@functools.partial(jax.jit, static_argnames=("save", "interpret"))
def _run_fwd(q, k, v, g, beta, save, interpret):
    geometry = b, tp, hk, dk, r, dv, n, m = _geometry(q, v)
    cd, _ = _dtypes.compute_dtypes_for(v.dtype)
    qk_spec, v_spec, g_spec, st_spec = _specs(geometry, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, tp, hk * r * dv), v.dtype)]
    out_specs = [v_spec]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, hk, r, n, dk, dv), _F32))
        out_specs.append(st_spec)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=m, heads=r, cd=cd),
        out_shape=out_shape, grid=(b, hk, n // m),
        in_specs=[qk_spec, qk_spec, v_spec, g_spec, g_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((r, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gdn_fwd")(*_laid_out(q, k, v, g, beta))
    o = out[0].reshape(b, tp, hk * r, dv)[:, :q.shape[1]]
    return o, (out[1] if save else None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_bwd(q, k, v, g, beta, states, do, interpret):
    geometry = b, tp, hk, dk, r, dv, n, m = _geometry(q, v)
    t = q.shape[1]
    cd, _ = _dtypes.compute_dtypes_for(v.dtype)
    qk_spec, v_spec, g_spec, st_spec = _specs(geometry, reverse=True)
    q2, k2, v2, gsum, beta2 = _laid_out(q, k, v, g, beta)
    do = _padded((do,), tp - t)[0].reshape(v2.shape)
    dq, dk_, dv_, dgsum, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=m, heads=r, cd=cd),
        out_shape=[jax.ShapeDtypeStruct(q2.shape, q.dtype),
                   jax.ShapeDtypeStruct(k2.shape, k.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype),
                   jax.ShapeDtypeStruct(gsum.shape, _F32),
                   jax.ShapeDtypeStruct(gsum.shape, _F32)],
        grid=(b, hk, n // m),
        in_specs=[qk_spec, qk_spec, v_spec, g_spec, g_spec, st_spec, v_spec],
        out_specs=[qk_spec, qk_spec, v_spec, g_spec, g_spec],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gdn_bwd")(q2, k2, v2, gsum, beta2, states, do)
    # G is a running sum inside the chunk: g_j reaches every G_i, i >= j
    dg = jax.lax.cumsum(dgsum, axis=dgsum.ndim - 1, reverse=True)
    return (dq.reshape(b, tp, hk, dk)[:, :t], dk_.reshape(b, tp, hk, dk)[:, :t],
            dv_.reshape(b, tp, hk * r, dv)[:, :t],
            _tokens_first(dg, geometry, t).astype(g.dtype),
            _tokens_first(dbeta, geometry, t).astype(beta.dtype))


def gated_delta_kernels(q, k, v, g, beta, interpret=False):
    """``gated_delta_rule`` as the two kernels, whatever the dispatch would
    say; ``interpret=True`` runs them in the interpreter, off the chip."""
    return _kernels(q, k, v, g, beta, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernels(q, k, v, g, beta, interpret):
    # the kernels index with 32-bit integers; under the tests' x64 mode
    # their Python constants would trace as 64-bit beside them
    with jax.enable_x64(False):
        return _run_fwd(q, k, v, g, beta, False, interpret)[0]


def _kernels_fwd(q, k, v, g, beta, interpret):
    with jax.enable_x64(False):
        o, states = _run_fwd(q, k, v, g, beta, True, interpret)
    return o, (q, k, v, g, beta, states)


def _kernels_bwd(interpret, res, do):
    # jax keeps the call site's scopes for a custom_vjp's backward, under
    # ``transpose(``: the kernel reads as .../gdn/gdn_core/gdn_bwd
    with jax.enable_x64(False):
        return _run_bwd(*res, do, interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)
