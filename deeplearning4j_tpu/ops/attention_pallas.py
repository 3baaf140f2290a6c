"""Fused (flash) attention kernel (Pallas, TPU).

Reference analog: none — the reference has no attention anywhere
(SURVEY.md §5 long-context row); this is part of the net-new long-context
tier (nn/layers/attention.py, parallel/sequence.py). The role matches the
cuDNN-helper tier though: the naive path materializes the [B, H, T, T]
logits in HBM, this kernel never does.

Kernel design (FlashAttention-style online softmax, TPU-first):
* Heads fold into the batch: [B, T, H, D] -> [BH, T, D]. Blocks carry the
  head's own width D (no pad to the 128 lanes: at D 64 half of every DMA,
  every MXU operand and the accumulator used to be zeros); the sequence
  pads to a common multiple of the block sizes.
* Grid = (BH, T/Bq, T/Bk) with the KEY dimension innermost: each (bh, iq)
  pair's query block stays VMEM-resident while key/value blocks [Bk, D]
  stream through, carried by the running (max, sum, acc) online-softmax
  recurrence held in VMEM scratch — VMEM use is O(Bq*D + Bk*D), so
  sequence length is bounded by HBM, not VMEM.
* Inside a grid step the [Bq, Bk] tile is walked in [128, 128] pieces: a
  float32 score piece is 16 vector registers of the file's 64 (a whole
  512 x 512 tile is 256, and every softmax step went out to VMEM and
  back). All pieces of a step are one basic block and their score
  products are issued ahead of the softmax, so the matrix and vector
  units overlap. HBM traffic is O(T*D) per query block, never O(T^2).
* Keys run down the sublanes, queries along the lanes (scores are k @ q^T,
  the accumulator is out^T): the softmax state of a query is one lane of
  a [1, Bq] row, reductions over keys are elementwise across registers,
  and nothing is broadcast across lanes inside the walk.
* Causal masking: key blocks entirely above the diagonal skip their
  compute via pl.when (and are not fetched); a tile under the diagonal
  takes no mask at all; on a diagonal tile of equal blocks the dead, cut
  and whole pieces are told apart at trace time. The length mask applies
  only where T is padded and the tile holds the tail; the key padding
  mask on every tile of a masked call.
* The kernel also emits the log-sum-exp per row. Backward is a
  jax.custom_vjp that recomputes probabilities from (q, k, v, lse)
  BLOCKWISE with a lax.scan over key blocks — peak gradient memory is
  O(BH * T * Bk), not O(BH * T^2).

``interpret=True`` runs the same kernel on CPU for tests (slow);
``enabled()`` gates the fast path to real TPU backends plus an env flag,
sharing the backend check with ops/lstm_pallas.py.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import spmd as _spmd

_LANE = 128
_NEG_INF = -1e30


def backend_is_tpu():
    """Single backend gate shared by the fused-kernel dispatch seams. A
    backend that fails to initialize raises here: a chip that cannot start
    must not read as "no chip, take the scan path"."""
    return jax.default_backend() == "tpu"


def enabled():
    if os.environ.get("DL4J_TPU_FUSED_ATTENTION", "1") == "0":
        return False
    return backend_is_tpu()


# Measured v5e crossover (fwd+bwd, bf16, h=8 d=64, chained in-jit timing):
# naive XLA wins at T<=512 (0.4-0.9x), flash wins from T=1024 (1.4x) through
# T=8192 (23x — the [B,H,T,T] logits start thrashing HBM). Dispatch follows
# — unless a TuningDB entry for the shape bucket carries a MEASURED
# decision (tuning/tune.py times the naive path as an implicit candidate).
# That window timed the forward kernel as it was before PR 26 (padded to
# 128 lanes, 3.2x slower at T 1024, D 64): the crossover may now lie below
# 1024 and has not been measured again.
_MIN_SEQ = 1024

#: default block geometry — the fallback when neither the tuning DB nor
#: the env override speaks. Kept at 512 x 512 on the v5e's word (PR 26, bf16
#: causal forward, [BH 64, T 1024, D 64]: 205 us; 256 x 256: 445; 512 x 256:
#: 309; 256 x 512: 333; one 1024 x 1024 step a head: 160, but block_k is also
#: the backward scan's tile, whose [BH, T, Bk] float32 temporaries double
#: with it). The kernel's own pieces come from the blocks (``_sub_tile``).
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 512

#: score pieces the kernel issues ahead of the softmax at hand (see
#: ``_attn_kernel``): the matrix units take work in program order, so this
#: is what lets them run under the vector units' softmax. On the v5e at the
#: shape above 1: 322 us, 4: 245, 8: 220, 16: 205, 32: 204 (PR 26); 16 is a
#: whole 512 x 512 tile's pieces, 1 MiB of VMEM in flight
_SCORES_AHEAD = 16


def _tuned(q_shape, dtype):
    """The TuningDB entry for a [B, T, H, D] call (tuning/db.py), or
    None. Trace-time host lookup — the resolved config compiles into the
    step, so the counters move once per compile."""
    from deeplearning4j_tpu.tuning.db import tuned_config
    return tuned_config("attention", tuple(int(d) for d in q_shape), dtype)


def env_block(name, default=512):
    """Env block-size override, validated: a positive 128-multiple (the
    TPU lane tile rule the kernel's BlockSpecs must satisfy) or the
    default. Malformed values fall back rather than killing a scarce
    live-window leg mid-trace."""
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        return default
    return val if val >= 128 and val % 128 == 0 else default


def resolve_block_sizes(q_shape, dtype):
    """(block_q, block_k, remat) for a [B, T, H, D] call — the ONE
    default table both ``flash_attention`` and ``flash_attention_block``
    resolve through: TuningDB entry (searched winner for this shape
    bucket) > ``DL4J_TPU_FLASH_BLOCK_Q/K`` env override (live-window
    A/B sweeps) > the hand-picked 512x512 default."""
    cfg = _tuned(q_shape, dtype)
    if cfg and cfg.get("backend", "flash") == "flash":
        return (int(cfg.get("block_q", _DEFAULT_BLOCK_Q)),
                int(cfg.get("block_k", _DEFAULT_BLOCK_K)),
                bool(cfg.get("remat", False)))
    return (env_block("DL4J_TPU_FLASH_BLOCK_Q", _DEFAULT_BLOCK_Q),
            env_block("DL4J_TPU_FLASH_BLOCK_K", _DEFAULT_BLOCK_K),
            False)


def resolve_attention(q_shape, k_shape, mask, dtype, *, min_seq=None):
    """The whole dispatch decision in ONE TuningDB lookup: None when the
    naive path should run, else the ``(block_q, block_k, remat)`` to run
    the kernel with. Structural gates first (self-attention shapes only
    — KV-cache decode goes naive; head_dim <= 128; float dtype; masks
    only as key-side [B, Tk] padding, the reference's masking contract
    (MaskedReductionUtil.java) — arbitrary-rank score masks go naive).
    Then the flash-vs-naive crossover: a TuningDB entry for this shape
    bucket carries a MEASURED verdict (``{"backend": "xla"}`` = the
    naive path won there, else the winning block geometry); without one
    the hand-measured _MIN_SEQ heuristic applies (override via
    DL4J_TPU_FUSED_ATTENTION_MIN_SEQ or min_seq=) with the env/default
    block table."""
    if mask is not None:
        mshape = tuple(getattr(mask, "shape", ()))
        if mshape != (q_shape[0], k_shape[1]):
            return None
    if tuple(q_shape) != tuple(k_shape):
        return None
    if q_shape[-1] > _LANE:
        return None
    if not jnp.issubdtype(dtype, jnp.floating):
        return None
    if min_seq is None:
        cfg = _tuned(q_shape, dtype)
        if cfg is not None:
            # measured crossover: the tuner timed the naive XLA path as
            # an implicit candidate at this bucket — its verdict replaces
            # the one-window _MIN_SEQ constant
            if cfg.get("backend", "flash") != "flash":
                return None
            return (int(cfg.get("block_q", _DEFAULT_BLOCK_Q)),
                    int(cfg.get("block_k", _DEFAULT_BLOCK_K)),
                    bool(cfg.get("remat", False)))
        try:
            min_seq = int(os.environ.get("DL4J_TPU_FUSED_ATTENTION_MIN_SEQ",
                                         _MIN_SEQ))
        except ValueError:  # malformed override: keep the measured default
            min_seq = _MIN_SEQ
    if q_shape[1] < min_seq:
        return None
    return (env_block("DL4J_TPU_FLASH_BLOCK_Q", _DEFAULT_BLOCK_Q),
            env_block("DL4J_TPU_FLASH_BLOCK_K", _DEFAULT_BLOCK_K),
            False)


def supported(q_shape, k_shape, mask, dtype, *, min_seq=None):
    """Whether the fast path applies (see ``resolve_attention``, which
    callers on the dispatch path should prefer — it returns the resolved
    block geometry from the SAME single DB lookup)."""
    return resolve_attention(q_shape, k_shape, mask, dtype,
                             min_seq=min_seq) is not None


def _sub_tile(block):
    """Rows (or keys) of one sub-tile of a block: the kernel walks a
    [block_q, block_k] grid step in [sub_q, sub_k] pieces so that the live
    float32 score piece is 16 vector registers of the file's 64, not the
    256 a whole 512 x 512 tile would ask for. 128 where the block is made
    of 128s (every block the dispatch resolves); a block that is not (the
    tests' 8s and 6s) is its own single sub-tile."""
    return _LANE if block % _LANE == 0 else block


def _all(*preds):
    """``and`` over grid predicates that are Python bools where the call's
    shape decides them and traced scalars where the grid position does."""
    if any(p is False for p in preds):
        return False
    traced = [p for p in preds if p is not True]
    return functools.reduce(jnp.logical_and, traced) if traced else True


def _not(pred):
    return (not pred) if isinstance(pred, bool) else jnp.logical_not(pred)


def _when(pred, fn):
    if pred is True:
        fn()
    elif pred is not False:
        pl.when(pred)(fn)


def _attn_kernel(t_true, ragged, causal, scale, sub_q, sub_k, has_mask,
                 q_ref, k_ref, v_ref, *rest):
    """Keys run down the sublanes and queries along the lanes: the score
    piece is k @ q^T, [sub_k, sub_q], so a query's running max and sum are
    one lane of a [1, block_q] row (a reduction over keys is elementwise
    across vector registers, a broadcast back is a sublane broadcast) and
    the accumulator is out^T, [D, block_q], full in the lanes at any head
    width. Rows meet lanes once, when the block's output is written."""
    if has_mask:
        mask_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        mask_ref = None
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    # a power-of-two scale (1/8 at head width 64) multiplies the query
    # exactly in any float dtype; any other stays on the float32 scores
    fold = math.frexp(scale)[0] == 0.5

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def tile(causal_mask, key_masks):
        """One grid step as ONE basic block, walked in [sub_q, sub_k]
        pieces. ``causal_mask``: None (the tile lies under the diagonal),
        "diag" (block_q == block_k and the tile sits on it: which pieces
        are dead, cut or whole is known here, at trace time) or "iota" (any
        other geometry: compare positions on every piece). ``key_masks``:
        the tile takes the length mask (when the call pads T) and the
        key-padding mask (when the call has one)."""
        def run():
            # MXU inputs stay in the native dtype (bf16 under the mixed
            # policy, 4x the f32 matmul rate on v5e) with f32 accumulation;
            # only the softmax state is f32
            q = q_ref[0]                                     # [Bq, D]
            if fold:
                q = q * scale
            pieces = [(r0, c0) for r0 in range(0, block_q, sub_q)
                      for c0 in range(0, block_k, sub_k)
                      if not (causal_mask == "diag" and c0 > r0 + sub_q - 1)]

            def scores(r0, c0):
                return jax.lax.dot_general(
                    k_ref[0, c0:c0 + sub_k, :], q[r0:r0 + sub_q],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [Sk, Sq]

            # the matrix units take their work in program order: score
            # products issued _SCORES_AHEAD pieces early keep them busy
            # while the vector units do the softmax of the piece at hand
            ahead = [scores(*pc) for pc in pieces[:_SCORES_AHEAD]]
            state = {}
            for i, (r0, c0) in enumerate(pieces):
                if i + _SCORES_AHEAD < len(pieces):
                    ahead.append(scores(*pieces[i + _SCORES_AHEAD]))
                s = ahead.pop(0)
                if not fold:
                    s = s * scale
                rows = slice(r0, r0 + sub_q)
                if r0 not in state:
                    state[r0] = (m_s[:, rows], l_s[:, rows], acc_s[:, rows])
                m, l, acc = state[r0]
                v = v_ref[0, c0:c0 + sub_k, :]
                masks = []
                cut = causal_mask == "iota" or (
                    causal_mask == "diag" and c0 + sub_k - 1 > r0)
                if cut or (key_masks and ragged):
                    key = j * block_k + c0 + jax.lax.broadcasted_iota(
                        jnp.int32, (sub_k, 1), 0)
                if key_masks and ragged:
                    masks.append(key < t_true)
                if key_masks and has_mask:       # key padding mask
                    masks.append(jnp.broadcast_to(
                        mask_ref[0, 0:1, c0:c0 + sub_k],
                        (sub_q, sub_k)).T > 0)
                if cut:
                    query = iq * block_q + r0 + jax.lax.broadcasted_iota(
                        jnp.int32, (1, sub_q), 1)
                    masks.append(key <= query)
                valid = functools.reduce(jnp.logical_and, masks) \
                    if masks else None
                if masks:
                    s = jnp.where(valid, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                if key_masks and has_mask:
                    # explicit zeroing: on a fully-masked row m_new == s ==
                    # _NEG_INF and exp(s - m_new) would be 1, silently
                    # averaging v. Zero it so l stays 0 and the row emits 0
                    # (the naive path emits NaN there; 0 is the contract the
                    # masked-output multiply downstream expects). Only a key
                    # mask can empty a row: under the causal and length
                    # masks every row has met key 0 before any masked entry,
                    # so m is finite and the exp underflows to exactly 0
                    p = jnp.where(valid, p, 0.0)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
                acc = alpha * acc + jax.lax.dot_general(
                    v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [D, Sq]
                state[r0] = (m_new, l, acc)
            for r0, (m, l, acc) in state.items():
                rows = slice(r0, r0 + sub_q)
                m_s[:, rows], l_s[:, rows], acc_s[:, rows] = m, l, acc
        return run

    # masks only where a tile needs them: the length mask where the call
    # pads T and this key block holds the tail, the key-padding mask on
    # every tile of a masked call, the causal mask on the diagonal
    tail = _all(ragged, (j + 1) * block_k > t_true)
    keyed = True if has_mask else tail
    if not causal:
        _when(_not(keyed), tile(None, False))
        _when(keyed, tile(None, True))
    elif block_q == block_k:
        _when(_all(j < iq, _not(keyed)), tile(None, False))
        _when(_all(j < iq, keyed), tile(None, True))
        _when(j == iq, tile("diag", has_mask or ragged))
    else:
        live = j * block_k <= (iq + 1) * block_q - 1
        under = _all((j + 1) * block_k - 1 <= iq * block_q, _not(keyed))
        _when(under, tile(None, False))
        _when(_all(live, _not(under)), tile("iota", has_mask or ragged))

    @pl.when(j == nk - 1)
    def _():
        l_safe = jnp.maximum(l_s[:], 1e-30)  # fully-masked padding rows
        o_ref[0] = (acc_s[:] / l_safe).T.astype(o_ref.dtype)
        # lse block is [8, Bq] (8-sublane broadcast): a [1, Bq] block would
        # violate the TPU (8, 128) tile rule — real-TPU compile rejects it
        lse = (m_s[:] + jnp.log(l_safe)).astype(lse_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _run_fwd(q, k, v, mask, h, causal, scale, block_q, block_k, interpret):
    """q,k,v: [BH, T, D]; mask: None, or [B, T] f32 key-validity (1=valid)
    with B = BH // h — the kernel indexes it per batch element (b // h) so
    heads share one mask block. A zero-width [B, 0] mask means "no mask"
    (the custom_vjp needs a real array operand; unmasked calls pay no mask
    traffic in the kernel). Returns (out [BH, T, D], lse [BH, T]). Under a
    declared device mesh the kernel runs once per batch shard (ops/spmd.py:
    the bh = b * h + head fold is batch-major, so a contiguous BH shard is
    a contiguous B shard and the mask shards with it)."""
    if mask is not None and mask.shape[-1] == 0:
        mask = None
    arrays = (q, k, v) if mask is None else (q, k, v, mask)

    def local(q, k, v, mask=None):
        return _run_fwd_local(q, k, v, mask, h, causal, scale, block_q,
                              block_k, interpret)
    return _spmd.per_batch_shard(local, arrays, (0,) * len(arrays), (0, 0))


@jax.named_scope("flash_attn.fwd")
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9), inline=True)
def _run_fwd_local(q, k, v, mask, h, causal, scale, block_q, block_k,
                   interpret):
    # jitted and inlined: the kernel body is some hundreds of operations
    # unrolled, and a model calls it once a layer with the same shapes, so
    # it is traced once and its equations are copied into each caller under
    # the caller's own scopes (24 layers of gpt2-medium: 3 s of set-up)
    bh, t, d = q.shape
    # clamp blocks to the 128-rounded sequence: short sequences would
    # otherwise pad up to the full default block (wasted compute), and
    # blocks larger than the array are invalid
    t128 = -(-t // _LANE) * _LANE
    block_q = min(block_q, t128)
    block_k = min(block_k, t128)
    step = math.lcm(block_q, block_k)
    t_pad = -(-t // step) * step
    # blocks carry the head's own width (a block's last dimension may equal
    # the array's): no pad to the 128 lanes, no slice of the result
    qp, kp, vp = (_pad_to(x, t_pad, 1) for x in (q, k, v))
    grid = (bh, t_pad // block_q, t_pad // block_k)
    sub_q, sub_k = _sub_tile(block_q), _sub_tile(block_k)
    kernel = functools.partial(_attn_kernel, t, t_pad != t, causal, scale,
                               sub_q, sub_k, mask is not None)

    def kv_block(i, j):
        # a key block above the diagonal is skipped by the kernel; naming
        # the last live block again keeps the pipeline from fetching it
        return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k) \
            if causal else j

    scratch = [pltpu.VMEM((1, block_q), jnp.float32),
               pltpu.VMEM((1, block_q), jnp.float32),
               pltpu.VMEM((d, block_q), jnp.float32)]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b, kv_block(i, j), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b, kv_block(i, j), 0)),
        ] + ([
            # mask rides in as [B, 8, t_pad] f32 — the 8-sublane broadcast
            # satisfies the TPU (8, 128) tile rule like the lse output block
            pl.BlockSpec((1, 8, block_k),
                         lambda b, i, j: (b // h, 0, kv_block(i, j))),
        ] if mask is not None else []),
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, t_pad), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attn_fwd",
    )(qp, kp, vp, *(() if mask is None else (
        jnp.broadcast_to(_pad_to(mask.astype(jnp.float32), t_pad, 1)
                         [:, None, :], (bh // h, 8, t_pad)),)))
    return out[:, :t], lse[:, 0, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _attention(q, k, v, mask, causal, scale, block_q, block_k, interpret, h):
    out, _ = _run_fwd(q, k, v, mask, h, causal, scale, block_q, block_k,
                      interpret)
    return out


def _attention_fwd(q, k, v, mask, causal, scale, block_q, block_k,
                   interpret, h):
    out, lse = _run_fwd(q, k, v, mask, h, causal, scale, block_q, block_k,
                        interpret)
    return out, (q, k, v, mask, out, lse)


@jax.named_scope("flash_attn.bwd")
def _bwd_core(causal, scale, block_k, res, g, g_lse=None):
    """Blockwise flash backward in jax: scan over KEY blocks recomputing
    P = exp(S - lse) one [BH, T, Bk] tile at a time. dq accumulates in the
    carry; dk/dv stack per block. Peak memory O(BH*T*Bk), never O(T^2).

    ``g_lse`` (optional, [BH, T]): cotangent on the log-sum-exp output —
    d(lse)/d(s) is the softmax row, so it adds ``p * g_lse`` to ds. Used by
    the ring-attention block primitive whose combination weights depend on
    lse."""
    q, k, v, mask, out, lse = res
    if mask is not None and mask.shape[-1] == 0:   # zero-width = unmasked
        mask = None
    f32 = jnp.float32
    # big einsums stay in the input dtype (bf16 under the mixed policy) with
    # f32 accumulation via preferred_element_type; softmax math is f32
    qf, kf, vf, gf, of = q, k, v, g.astype(q.dtype), out
    bh, t, d = qf.shape
    # same clamp as _run_fwd: an unclamped 512 block would pad short
    # sequences' key blocks with masked-out columns the einsums still chew
    bk = min(block_k, -(-t // _LANE) * _LANE)
    t_pad = -(-t // bk) * bk
    kp = _pad_to(kf, t_pad, 1).reshape(bh, t_pad // bk, bk, d)
    vp = _pad_to(vf, t_pad, 1).reshape(bh, t_pad // bk, bk, d)
    # move the block axis to front for scan
    kp = jnp.moveaxis(kp, 1, 0)                      # [nk, BH, Bk, D]
    vp = jnp.moveaxis(vp, 1, 0)
    if mask is not None:
        # key padding mask, repeated per head ([B, T] -> [BH, T],
        # batch-major to match _fold_heads' bh = b * h + head layout),
        # blocked like k/v
        maskh = jnp.repeat(mask.astype(f32), bh // mask.shape[0], axis=0)
        mp = jnp.moveaxis(_pad_to(maskh, t_pad, 1)
                          .reshape(bh, t_pad // bk, bk), 1, 0)  # [nk,BH,Bk]
    delta = jnp.sum(gf.astype(f32) * of.astype(f32), axis=-1,
                    keepdims=True)                    # [BH, T, 1]
    row = jnp.arange(t)[None, :, None]                # [1, T, 1]

    def body(carry, blk):
        dq_acc, j = carry
        if mask is not None:
            k_j, v_j, m_j = blk                       # [BH, Bk, D], [BH, Bk]
        else:
            k_j, v_j = blk
        col = j * bk + jnp.arange(bk)[None, None, :]  # [1, 1, Bk]
        s = jnp.einsum("bqd,bkd->bqk", qf, k_j,
                       preferred_element_type=f32) * scale
        valid = col < t
        if mask is not None:
            valid = valid & (m_j[:, None, :] > 0)
        if causal:
            valid = valid & (col <= row)
        s = jnp.where(valid, s, _NEG_INF)
        # zero (not exp) masked entries: on fully-masked rows lse is the
        # _NEG_INF sentinel and exp(s - lse) would be ~1, corrupting grads
        p = jnp.where(valid, jnp.exp(s - lse[..., None]), 0.0)  # [BH,T,Bk]
        pc = p.astype(qf.dtype)
        dv_j = jnp.einsum("bqk,bqd->bkd", pc, gf, preferred_element_type=f32)
        dp = jnp.einsum("bqd,bkd->bqk", gf, v_j, preferred_element_type=f32)
        ds = p * (dp - delta)
        if g_lse is not None:
            ds = ds + p * g_lse[..., None].astype(f32)
        ds = ds.astype(qf.dtype)
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, k_j,
                                     preferred_element_type=f32) * scale
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, qf,
                          preferred_element_type=f32) * scale
        return (dq_acc, j + 1), (dk_j, dv_j)

    (dq, _), (dk_blocks, dv_blocks) = jax.lax.scan(
        body, (jnp.zeros(qf.shape, f32), 0),
        (kp, vp) if mask is None else (kp, vp, mp))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, t_pad, d)[:, :t]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, t_pad, d)[:, :t]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _attention_bwd(causal, scale, block_q, block_k, interpret, h, res, g):
    dq, dk, dv = _bwd_core(causal, scale, block_k, res, g)
    return dq, dk, dv, jnp.zeros_like(res[3])


def _fold_heads(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold_heads(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_block(q, k, v, causal, scale, interpret):
    """(out [B,T,H,D], lse [B,H,T]) for ONE ring-attention block pair —
    the fused-kernel replacement for a naive [B,H,Tq,Tk]-logits block in
    parallel/sequence.py. The lse output lets the caller combine blocks by
    log-sum-exp; its cotangent is handled exactly (see _bwd_core). Block
    sizes resolve through the same TuningDB/env/default table as the main
    ``flash_attention`` entry (this entry used to hardcode 512x512 and
    bypass even the env override)."""
    b, t, h, d = q.shape
    bq, bk, _ = resolve_block_sizes(q.shape, q.dtype)
    out, lse = _run_fwd(_fold_heads(q), _fold_heads(k), _fold_heads(v),
                        None, h, causal, scale, bq, bk, interpret)
    return _unfold_heads(out, b, h), lse.reshape(b, h, t)


def _flash_block_fwd(q, k, v, causal, scale, interpret):
    b, t, h, d = q.shape
    bq, bk, _ = resolve_block_sizes(q.shape, q.dtype)
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    out, lse = _run_fwd(qf, kf, vf, None, h, causal, scale, bq, bk,
                        interpret)
    return (_unfold_heads(out, b, h), lse.reshape(b, h, t)), \
        (qf, kf, vf, out, lse, b, h, bk)


def _flash_block_bwd(causal, scale, interpret, res, grads):
    # bk rides the residuals so fwd and bwd tile identically even if the
    # DB/env resolution were to change between the two traces
    qf, kf, vf, out, lse, b, h, bk = res
    g_out, g_lse = grads
    dq, dk, dv = _bwd_core(causal, scale, bk, (qf, kf, vf, None, out, lse),
                           _fold_heads(g_out),
                           g_lse=g_lse.reshape(b * h, -1))
    return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h),
            _unfold_heads(dv, b, h))


flash_attention_block.defvjp(_flash_block_fwd, _flash_block_bwd)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, mask=None, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False):
    """Fused attention over [B, T, H, D] self-attention inputs (same
    contract as nn/layers/attention.py dot_product_attention minus
    cross-length decode). ``mask``: optional [B, Tk] key-side padding mask
    (1 = valid). Fully-masked query rows emit 0 (the naive path emits NaN
    there — 0 is what the downstream masked-output multiply expects).
    ``block_q``/``block_k`` default to ``resolve_block_sizes`` (TuningDB
    winner for the shape bucket > env override > 512x512); explicit
    values win unconditionally (tests, the tuner's own candidates)."""
    b, t, h, d = q.shape
    if block_q is None or block_k is None:
        rq, rk, _ = resolve_block_sizes(q.shape, q.dtype)
        block_q = rq if block_q is None else block_q
        block_k = rk if block_k is None else block_k
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    # custom_vjp needs an array operand in every slot: a zero-width [B, 0]
    # mask is the "no mask" sentinel (kernel + backward skip all mask work)
    maskf = (jnp.zeros((b, 0), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
    out = _attention(_fold_heads(q), _fold_heads(k), _fold_heads(v), maskf,
                     causal, float(scale), block_q, block_k, interpret, h)
    return _unfold_heads(out, b, h)
