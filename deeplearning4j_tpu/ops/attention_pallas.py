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
* Grid = (BH, live steps): a head after a head, and within a head the
  LIVE [Bq, Bk] tiles of its mask alone, from a list made at trace time
  (``step_list``: query block, key block, and whether the step opens or
  closes its outer block) that the kernel and the index maps read
  scalar-prefetched. No grid step is spent on a tile the mask kills, and
  a mask with no dead tile walks the whole rectangle: one construction of
  the grid for every mask and every kernel. The list is in the order of a
  (T/Bq, T/Bk) grid with the KEY dimension innermost: each query block
  stays VMEM-resident while its key/value blocks [Bk, D] stream through,
  carried by the running (max, sum, acc) online-softmax recurrence held in
  VMEM scratch — VMEM use is O(Bq*D + Bk*D), so sequence length is
  bounded by HBM, not VMEM. Live steps of a head's rectangle in 512 x 512
  blocks, at the benchmark cells' calls (tests/test_attention_steps.py):

      sdar-train-bd4-t4096    BlockDiffusion(4096, 4), T 8192    80 / 256
      lfm2-train-t8192        causal, T 8192                    136 / 256
      glm47flash-train-t4096, nemotron3nano-train-packed,
      qwen3next-train-t4096   causal, T 4096                     36 / 64
      ouro-train-t2048        causal, T 2048                     10 / 16
      gpt2m-train-t1024       causal, T 1024                      3 / 4

  A dead tile's turn in a rectangular grid cost 0.10-0.42 us by the head
  width (PERF.md section 6, PR 53): 0.167 at sdar's 128, a quarter of its
  forward call.
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
* Causal masking: key blocks entirely above the diagonal are not in the
  list; a tile under the diagonal takes no mask at all; on a diagonal tile
  of equal blocks the dead, cut and whole pieces are told apart at trace
  time. The length mask applies only where T is padded and the tile holds
  the tail; the key padding mask on every tile of a masked call: neither
  makes a tile dead. ``_walk_tiles`` is the one statement of which tile
  takes which body: the list is that walk run on integers, and the kernel
  runs it again on the step's entries to choose the body.
* Block diffusion (``BlockDiffusion(seq_len, block_len)``, the training
  mask of BD3-LM, arXiv:2503.09573): the sequence is a noised copy of
  ``seq_len`` tokens followed by a clean copy, and three quarters of the
  [2T, 2T] square is dead by whole tiles. ``_walk_block_diffusion`` tells
  a tile dead (not in the list), whole (no mask) or cut (on its copy's
  diagonal: blocks compared on the diagonal pieces alone) from its
  position, as the causal walk does from the diagonal.
* The kernel also emits the log-sum-exp per row. Backward is a
  jax.custom_vjp over a second kernel in the same layout and on the same
  live tiles (``_bwd_kernel``; key block by key block with the query
  blocks innermost, but query-major in the "dq" form): a piece's
  probabilities are recomputed
  from (q, k, v, lse) in VMEM, so the gradient costs no [BH, T, Bk]
  temporary in HBM. One kernel a head with dq^T resident in VMEM (five
  products a piece) where what it holds there, counted from the shape and
  the dtypes (``bwd_vmem_bytes``), is within ``_VMEM_BUDGET``: every cell's
  call, width 256 at T 4,096 among them, for which Mosaic is asked for the
  19 MiB it counts. Past the budget a dK/dV kernel and a dQ kernel (seven
  products): ``_run_bwd`` chooses.
* Both kernels read ONE copy of q, k and v, rounded once to the dtype the
  matrix units multiply in (``_operand_dtype``: bfloat16 for float32
  callers on the chip) inside the forward's custom_vjp rule: the forward
  kernel's operands are the backward's residuals, the backward rounds
  only the cotangent, and the output and the three gradients keep the
  caller's dtype.

``interpret=True`` runs the same kernel on CPU for tests (slow);
``resolve_attention`` is the one place that chooses between this kernel
and the XLA path and that names the blocks, from shape, dtype, mask and
backend (the backend check is shared with ops/lstm_pallas.py).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import spmd as _spmd

_LANE = 128
_NEG_INF = -1e30
#: the widest head the kernels take: two lane tiles. The accumulators are
#: [D, block] float32, so VMEM grows with D; 256 is the widest a cell runs
#: (Qwen3-Next's full attention: [16, 4096, 256] float32, forward 1.26 ms
#: = 55% of its least time, PERF.md section 5, PR 34; the backward alone
#: 2.38 ms as one kernel a head where the split form's two took 3.39, PR
#: 46) and the widest compiled for the chip
_MAX_HEAD = 256


class BlockDiffusion(NamedTuple):
    """The score mask of block-diffusion training over ``2 seq_len``
    positions, a noised copy of a sequence (positions 0..T-1) followed by
    its clean copy (T..2T-1), in blocks of ``block_len`` tokens, ``b(i) =
    (i mod T) // block_len``: a noised query sees the noised keys of its
    own block and the clean keys of the blocks before it; a clean query
    the clean keys of its own block and of those before it (BD3-LM's
    ``M_BD``, ``M_OBC``, ``M_BC``). No clean query sees a noised key and
    every query sees itself. The noised copy lies first so that, keys
    walked in order, every query has met a key it sees (its own block's)
    before the first piece that is cut away whole for it."""

    seq_len: int
    block_len: int

    def dense(self):
        """The mask as a boolean [2T, 2T] array (queries down, keys
        along): what the XLA path applies and the tests compare with."""
        t, l = self.seq_len, self.block_len
        pos = jnp.arange(2 * t)
        clean, blk = pos >= t, (pos % t) // l
        q_clean, k_clean = clean[:, None], clean[None, :]
        q_blk, k_blk = blk[:, None], blk[None, :]
        return jnp.where(
            q_clean, k_clean & (k_blk <= q_blk),
            jnp.where(k_clean, k_blk < q_blk, k_blk == q_blk))

    def join(self, noised, clean):
        """The two copies [B, T, ...] side by side, [B, 2T, ...]: the one
        place that says which lies first."""
        return jnp.concatenate([noised, clean], axis=1)

    def noised_rows(self, x):
        """The noised copy's T rows of ``x`` [B, 2T, ...]."""
        return x[:, :self.seq_len]

    def positions(self):
        """[2T] the position each row stands at: both copies at 0..T-1."""
        return jnp.tile(jnp.arange(self.seq_len), 2)

    def fits(self, t_total, block_q, block_k):
        """Whether the kernels can walk this geometry in these blocks:
        the call is the doubled sequence, each copy is whole tiles of one
        square block, and a block of tokens never straddles a piece."""
        sub = _sub_tile(min(block_q, block_k))
        return (t_total == 2 * self.seq_len and block_q == block_k
                and self.seq_len % block_q == 0
                and sub % self.block_len == 0
                and self.block_len & (self.block_len - 1) == 0)


def backend_is_tpu():
    """Single backend gate shared by the fused-kernel dispatch seams. A
    backend that fails to initialize raises here: a chip that cannot start
    must not read as "no chip, take the scan path"."""
    return jax.default_backend() == "tpu"


# Measured v5e crossover (fwd+bwd, bf16, h=8 d=64, chained in-jit timing):
# naive XLA wins at T<=512 (0.4-0.9x), flash wins from T=1024 (1.4x) through
# T=8192 (23x — the [B,H,T,T] logits start thrashing HBM). That window
# timed the forward kernel as it was before PR 26 (padded to 128 lanes,
# 3.2x slower at T 1024, D 64): the crossover may now lie below 1024 and
# has not been measured again, at width 64 or at the widths the cells run
# since (128 from PR 27, 256 from PR 34: every cell's T is 1024 or more, so
# none sits on the XLA side). A constant until a sweep at the benchmark's
# shapes moves it.
_MIN_SEQ = 1024

#: the block geometry ``resolve_attention`` hands out. 512 x 512 on the
#: v5e's word (PR 26, bf16 causal forward, [BH 64, T 1024, D 64]: 205 us;
#: 256 x 256: 445; 512 x 256: 309; 256 x 512: 333; one 1024 x 1024 step a
#: head: 160, not taken then because the backward was a scan over key
#: blocks of block_k; the backward kernel runs on the same two blocks and
#: has not been timed at 1024). The kernels' own pieces come from the
#: blocks (``_sub_tile``).
_BLOCK_Q = 512
_BLOCK_K = 512

#: score pieces the kernel issues ahead of the softmax at hand (see
#: ``_attn_kernel``): the matrix units take work in program order, so this
#: is what lets them run under the vector units' softmax. On the v5e at the
#: shape above 1: 322 us, 4: 245, 8: 220, 16: 205, 32: 204 (PR 26); 16 is a
#: whole 512 x 512 tile's pieces, 1 MiB of VMEM in flight. The backward
#: issues its two operand-only products (k q^T, v g^T) as far ahead: 2 MiB
_SCORES_AHEAD = 16


def resolve_attention(q_shape, k_shape, mask, dtype, geometry=None):
    """The whole dispatch decision, from what the call shows: None where
    the XLA path should run, else the ``(block_q, block_k)`` to run the
    kernel with. A TPU backend; self-attention shapes only (KV-cache
    decode goes naive); head_dim <= 256 (``_MAX_HEAD``; wider goes naive:
    measured on the chip at 64, 128 and, from PR 34, 256, where the
    backward is one kernel a head to T 4,096 (bfloat16 inputs: 6,656)
    and takes its split form past that: ``_run_bwd``); a float dtype; masks
    only as key-side [B, Tk] padding, the reference's masking contract
    (MaskedReductionUtil.java) — arbitrary-rank score masks go naive; and
    the measured crossover ``_MIN_SEQ``. A ``geometry`` (``BlockDiffusion``)
    stays on the kernel where its copies are whole tiles
    (``BlockDiffusion.fits``) and the call has no key mask. Another block
    for another shape is a branch on the shape here, with the chip run
    that justifies it in PERF.md."""
    if not backend_is_tpu():
        return None
    if geometry is not None and (mask is not None or not geometry.fits(
            q_shape[1], *_geometry(q_shape[1], _BLOCK_Q, _BLOCK_K)[:2])):
        return None
    if mask is not None:
        mshape = tuple(getattr(mask, "shape", ()))
        if mshape != (q_shape[0], k_shape[1]):
            return None
    if tuple(q_shape) != tuple(k_shape):
        return None
    if q_shape[-1] > _MAX_HEAD:
        return None
    if not jnp.issubdtype(dtype, jnp.floating):
        return None
    if q_shape[1] < _MIN_SEQ:
        return None
    return _BLOCK_Q, _BLOCK_K


def _resolved(q, k, mask, geometry=None):
    """``resolve_attention`` for a caller that has already chosen the
    kernel: a call the dispatch would hand to XLA is refused, not given a
    geometry of its own."""
    blocks = resolve_attention(q.shape, k.shape, mask, q.dtype, geometry)
    if blocks is None:
        raise ValueError(
            f"flash attention does not take q {q.shape}, k {k.shape} "
            f"{q.dtype} here (resolve_attention): pass block_q/block_k or "
            "take the XLA path")
    return blocks


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


def _select(pred, a, b):
    """``a`` where ``pred`` else ``b``, for a Python bool (the step list's
    walk on integers) or a traced scalar (the kernel's)."""
    return (a if pred else b) if isinstance(pred, bool) \
        else jnp.where(pred, a, b)


def _when(pred, fn):
    if pred is True:
        fn()
    elif pred is not False:
        pl.when(pred)(fn)


def _cut(causal_mask):
    """A tile's kind of cut: None, "diag" or "iota" (the causal walk's),
    "same" or "under" (a block-diffusion tile on its copy's diagonal,
    which travels as a tuple with what its pieces compare)."""
    return causal_mask[0] if isinstance(causal_mask, tuple) else causal_mask


def _pieces(block_q, block_k, sub_q, sub_k, causal_mask):
    """(r0, c0) of a tile's [sub_q, sub_k] pieces, rows outermost; on a
    "diag" or "under" tile the pieces wholly above the diagonal are left
    out, on a "same" tile all but the diagonal's."""
    kind = _cut(causal_mask)
    return [(r0, c0) for r0 in range(0, block_q, sub_q)
            for c0 in range(0, block_k, sub_k)
            if not (kind in ("diag", "under") and c0 > r0 + sub_q - 1)
            and not (kind == "same" and c0 != r0)]


def _issued_ahead(pieces, product):
    """(piece, product(*piece)) in order, each product issued (traced)
    ``_SCORES_AHEAD`` pieces before the vector work that consumes it: the
    matrix units take their work in program order, so this is what keeps
    them busy under the vector units."""
    ahead = [product(*pc) for pc in pieces[:_SCORES_AHEAD]]
    for i, pc in enumerate(pieces):
        if i + _SCORES_AHEAD < len(pieces):
            ahead.append(product(*pieces[i + _SCORES_AHEAD]))
        yield pc, ahead.pop(0)


def _piece_valid(causal_mask, t_true, mask_ref, iq, j, block_q, block_k, r0,
                 c0, sub_q, sub_k):
    """The entries of a [sub_k, sub_q] piece (keys down the sublanes) that
    a query may see, or None where the piece takes no mask: the piece at
    rows ``r0``, keys ``c0`` of the tile (query block ``iq``, key block
    ``j``). ``causal_mask`` is the tile's (see ``tile``); ``t_true`` the
    true length where the tile takes the length mask, else None;
    ``mask_ref`` the key-padding block where it takes that one, else None."""
    if isinstance(causal_mask, tuple):
        # a block-diffusion tile on its copy's diagonal (square pieces,
        # whole blocks of tokens in each): under the diagonal piece a
        # piece is whole; on it a key's block is compared with the
        # query's, within the piece
        if c0 != r0:
            return None
        kind, shift, *seen = causal_mask
        key = jax.lax.broadcasted_iota(jnp.int32, (sub_k, 1), 0) >> shift
        query = jax.lax.broadcasted_iota(jnp.int32, (1, sub_q), 1) >> shift
        return key == query if kind == "same" else key < query + seen[0]
    masks = []
    cut = causal_mask == "iota" or (
        causal_mask == "diag" and c0 + sub_k - 1 > r0)
    if cut or t_true is not None:
        key = j * block_k + c0 + jax.lax.broadcasted_iota(
            jnp.int32, (sub_k, 1), 0)
    if t_true is not None:
        masks.append(key < t_true)
    if mask_ref is not None:
        masks.append(jnp.broadcast_to(mask_ref[0, 0:1, c0:c0 + sub_k],
                                      (sub_q, sub_k)).T > 0)
    if cut:
        query = iq * block_q + r0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, sub_q), 1)
        masks.append(key <= query)
    return functools.reduce(jnp.logical_and, masks) if masks else None


def _walk_block_diffusion(tile, geometry, iq, j, block):
    """``_walk_tiles`` under a ``BlockDiffusion`` geometry (square blocks,
    each copy ``half`` whole tiles, no key mask and no padding:
    ``BlockDiffusion.fits``). Dead, and so no step of the grid: every
    clean-query x noised-key tile, the noised x noised tiles off the
    diagonal, and the tiles above its copy's diagonal in the two quarters
    with clean keys. Whole: the clean-key tiles under that diagonal. Cut,
    on the diagonal pieces alone: the noised x noised diagonal ("same":
    a key of the query's own block) and the clean-key diagonals ("under":
    a block before the query's, or up to its own for a clean query),
    which share one body."""
    half = geometry.seq_len // block
    shift = geometry.block_len.bit_length() - 1
    noised_q = iq < half
    ii = _select(noised_q, iq, iq - half)     # the tile's place in its copy
    jj = j - half
    _when(_all(j >= half, jj < ii), tile(None, False))
    _when(_all(noised_q, j == iq), tile(("same", shift), False))
    _when(_all(j >= half, jj == ii),
          tile(("under", shift, _select(noised_q, 0, 1)), False))


def _walk_tiles(tile, causal, ragged, has_mask, iq, j, block_q, block_k,
                t_true, geometry=None):
    """Run the tile (query block ``iq``, key block ``j``) as the one
    ``tile(causal_mask, key_masks)`` body it needs, or none where the mask
    kills it whole (above the diagonal): masks only where a tile needs
    them, the length mask where the call pads T and this key block holds
    the tail, the key-padding mask on every tile of a masked call, the
    causal mask on the diagonal. The one statement of a mask's geometry:
    the forward and the backward kernel run it on the step's traced
    entries, where it chooses the body, and ``step_list`` on every
    position as Python integers, where a tile with a body is a step."""
    if geometry is not None:
        return _walk_block_diffusion(tile, geometry, iq, j, block_q)
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


class Steps(NamedTuple):
    """The grid steps of one head (``step_list``): step ``s`` works on
    query block ``qi[s]`` and key block ``kj[s]``; bit 0 of ``edge[s]``
    says that it opens its outer block (the first of its steps: the
    accumulators start), bit 1 that it closes it (the last: the block is
    written). ``rectangle`` is what a grid over every tile would walk."""

    qi: np.ndarray
    kj: np.ndarray
    edge: np.ndarray
    rectangle: int

    @property
    def live(self):
        return len(self.qi)


@functools.lru_cache(maxsize=None)
def step_list(causal, geometry, nq, nk, block_q, block_k, key_major=False):
    """The live tiles of a mask over ``nq`` x ``nk`` blocks, in the order a
    rectangular grid meets them: query block by query block with the key
    blocks innermost (the forward and the "dq" backward), or, ``key_major``,
    key block by key block with the query blocks innermost ("fused",
    "dkv"). Numpy, at trace time; the kernels read it scalar-prefetched.

    There is one statement of a mask's geometry, ``_walk_tiles``: this
    runs it with Python integers for the grid position, and a tile is live
    where the walk runs a body. (The kernels run the same walk on the
    entries they read, which then chooses the body alone.) The length and
    key-padding masks choose among bodies and never between one and none,
    so they are left out here. With neither ``causal`` nor a ``geometry``
    the list is the whole rectangle.

    Every outer block has a live step under every mask the kernels take
    (a query sees itself), so every output block is opened, written and
    closed once: asserted here. The three arrays lie in SMEM for the whole
    call (1 MiB on the v5e): a causal call in 512 x 512 blocks compiles
    for the chip to T 131,072 (32,896 steps) and is refused at 262,144."""
    pairs = []
    outer, inner = (nk, nq) if key_major else (nq, nk)
    for o in range(outer):
        for i in range(inner):
            at = (i, o) if key_major else (o, i)
            _walk_tiles(lambda *kind, at=at: lambda: pairs.append(at),
                        causal, False, False, *at, block_q, block_k, 0,
                        geometry)
    assert len(set(pairs)) == len(pairs), "a tile ran two bodies"
    qi, kj = (np.asarray(x, np.int32) for x in zip(*pairs))
    of = kj if key_major else qi
    assert np.array_equal(np.unique(of), np.arange(outer)), \
        "an outer block with no live step would never be written"
    turn = np.flatnonzero(np.diff(of)) + 1
    edge = np.zeros(len(of), np.int32)
    edge[np.r_[0, turn]] |= 1
    edge[np.r_[turn - 1, len(of) - 1]] |= 2
    for array in (qi, kj, edge):            # kept: nobody writes into them
        array.flags.writeable = False
    return Steps(qi, kj, edge, nq * nk)


def _spec(shape, at, lanes=False, heads=1):
    """The BlockSpec of a ``shape`` block at the step's query block (``at``
    "q") or key block ("k"), read from the step list: along the rows of a
    [BH, T, D] array or, ``lanes``, along the last dimension of a
    [BH, rows, T] one. ``heads``: the array has one entry a batch element
    (the key mask), shared by its heads."""
    def index(b, s, qi, kj, edge):
        lead = b if heads == 1 else b // heads
        block = (qi if at == "q" else kj)[s]
        return (lead, 0, block) if lanes else (lead, block, 0)
    return pl.BlockSpec(shape, index)


def _stepped_call(kernel, steps, operands, in_specs, out_specs, scratch,
                  **kw):
    """``kernel`` on the one grid of every flash kernel: a head after a
    head (``operands[0]`` is [BH, T, D]), and within a head the live steps
    of ``steps`` in its order, which the kernel and the index maps
    (``_spec``) read scalar-prefetched."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(operands[0].shape[0], steps.live),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        **kw)(*map(jnp.asarray, steps[:3]), *operands)


def _step(qi_ref, kj_ref, edge_ref):
    """(query block, key block, opens, closes) of the grid step at hand."""
    s = pl.program_id(1)
    edge = edge_ref[s]
    return qi_ref[s], kj_ref[s], (edge & 1) != 0, (edge & 2) != 0


def _attn_kernel(t_true, ragged, causal, scale, sub_q, sub_k, has_mask,
                 qi_ref, kj_ref, edge_ref, q_ref, k_ref, v_ref, *rest,
                 geometry=None):
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
    iq, j, opens, closes = _step(qi_ref, kj_ref, edge_ref)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    # a power-of-two scale (1/8 at head width 64) multiplies the query
    # exactly in any float dtype; any other stays on the float32 scores
    fold = math.frexp(scale)[0] == 0.5

    @pl.when(opens)
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
            # MXU inputs stay in the dtype they arrive in (``_operand_dtype``:
            # bf16 on the chip, 4x the f32 matmul rate on v5e) with f32
            # accumulation; only the softmax state is f32
            q = q_ref[0]                                     # [Bq, D]
            if fold:
                q = q * scale
            def scores(r0, c0):
                return jax.lax.dot_general(
                    k_ref[0, c0:c0 + sub_k, :], q[r0:r0 + sub_q],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [Sk, Sq]

            state = {}
            for (r0, c0), s in _issued_ahead(
                    _pieces(block_q, block_k, sub_q, sub_k, causal_mask),
                    scores):
                if not fold:
                    s = s * scale
                rows = slice(r0, r0 + sub_q)
                if r0 not in state:
                    state[r0] = (m_s[:, rows], l_s[:, rows], acc_s[:, rows])
                m, l, acc = state[r0]
                v = v_ref[0, c0:c0 + sub_k, :]
                valid = _piece_valid(
                    causal_mask, t_true if key_masks and ragged else None,
                    mask_ref if key_masks else None, iq, j, block_q,
                    block_k, r0, c0, sub_q, sub_k)
                if valid is not None:
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

    _walk_tiles(tile, causal, ragged, has_mask, iq, j, block_q, block_k,
                t_true, geometry)

    @pl.when(closes)
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


def _geometry(t, block_q, block_k):
    """(block_q, block_k, t_pad) a call of length ``t`` runs with, forward
    and backward alike. Blocks clamp to the 128-rounded sequence: short
    sequences would otherwise pad up to the full default block (wasted
    compute), and blocks larger than the array are invalid. The sequence
    pads to a common multiple of the two."""
    t128 = -(-t // _LANE) * _LANE
    block_q, block_k = min(block_q, t128), min(block_k, t128)
    step = math.lcm(block_q, block_k)
    return block_q, block_k, -(-t // step) * step


def _run_fwd(q, k, v, mask, h, causal, scale, block_q, block_k, interpret,
             out_dtype=None, geometry=None):
    """q,k,v: [BH, T, D], as the kernel reads them (the custom_vjp rules
    hand them over rounded, ``_as_operands``); mask: None, or [B, T] f32
    key-validity (1=valid) with B = BH // h — the kernel indexes it per
    batch element (b // h) so heads share one mask block. A zero-width
    [B, 0] mask means "no mask" (the custom_vjp needs a real array operand;
    unmasked calls pay no mask traffic in the kernel). Returns (out
    [BH, T, D] in ``out_dtype``, the caller's, q's where none is named;
    lse [BH, T]). Under a declared device mesh the kernel runs once per
    batch shard (ops/spmd.py: the bh = b * h + head fold is batch-major, so
    a contiguous BH shard is a contiguous B shard and the mask shards with
    it)."""
    if mask is not None and mask.shape[-1] == 0:
        mask = None
    arrays = (q, k, v) if mask is None else (q, k, v, mask)
    out_dtype = jnp.dtype(q.dtype if out_dtype is None else out_dtype)

    def local(q, k, v, mask=None):
        return _run_fwd_local(q, k, v, mask, h, causal, scale, block_q,
                              block_k, interpret, out_dtype, geometry)
    return _spmd.per_batch_shard(local, arrays, (0,) * len(arrays), (0, 0))


@jax.named_scope("flash_attn.fwd")
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10, 11),
                   inline=True)
def _run_fwd_local(q, k, v, mask, h, causal, scale, block_q, block_k,
                   interpret, out_dtype, geometry=None):
    # jitted and inlined: the kernel body is some hundreds of operations
    # unrolled, and a model calls it once a layer with the same shapes, so
    # it is traced once and its equations are copied into each caller under
    # the caller's own scopes (24 layers of gpt2-medium: 3 s of set-up)
    bh, t, d = q.shape
    block_q, block_k, t_pad = _geometry(t, block_q, block_k)
    # blocks carry the head's own width (a block's last dimension may equal
    # the array's): no pad to the 128 lanes, no slice of the result
    qp, kp, vp = (_pad_to(x, t_pad, 1) for x in (q, k, v))
    steps = step_list(causal, geometry, t_pad // block_q, t_pad // block_k,
                      block_q, block_k)
    sub_q, sub_k = _sub_tile(block_q), _sub_tile(block_k)
    kernel = functools.partial(_attn_kernel, t, t_pad != t, causal, scale,
                               sub_q, sub_k, mask is not None)
    if geometry is not None:
        kernel = functools.partial(kernel, geometry=geometry)
    q_spec = _spec((1, block_q, d), "q")
    k_spec = _spec((1, block_k, d), "k")
    scratch = [pltpu.VMEM((1, block_q), jnp.float32),
               pltpu.VMEM((1, block_q), jnp.float32),
               pltpu.VMEM((d, block_q), jnp.float32)]
    # every forward kernel stands under flash_attn.fwd/flash_attn_fwd in a
    # trace (the causal one by its own name); one under a geometry carries
    # its own name inside that scope, which tells it from the causal ones
    name, under = ("flash_attn_fwd", contextlib.nullcontext()) \
        if geometry is None else (
            "flash_attn_bd_fwd", jax.named_scope("flash_attn_fwd"))
    operands, in_specs = [qp, kp, vp], [q_spec, k_spec, k_spec]
    if mask is not None:
        # mask rides in as [B, 8, t_pad] f32 — the 8-sublane broadcast
        # satisfies the TPU (8, 128) tile rule like the lse output block
        operands.append(jnp.broadcast_to(
            _pad_to(mask.astype(jnp.float32), t_pad, 1)[:, None, :],
            (bh // h, 8, t_pad)))
        in_specs.append(_spec((1, 8, block_k), "k", lanes=True, heads=h))
    with under:
        out, lse = _stepped_call(
            kernel, steps, operands, in_specs,
            out_specs=[q_spec, _spec((1, 8, block_q), "q", lanes=True)],
            scratch=scratch,
            out_shape=[
                jax.ShapeDtypeStruct((bh, t_pad, d), out_dtype),
                jax.ShapeDtypeStruct((bh, 8, t_pad), jnp.float32),
            ],
            interpret=interpret,
            name=name)
    return out[:, :t], lse[:, 0, :t]


def _operand_dtype(dtype, interpret):
    """The dtype q, k and v (and, in the backward, g) enter a kernel in,
    for a caller whose arrays are ``dtype``. The matrix units round a
    float32 operand to bfloat16 as they take it (default precision, as the
    einsums of the XLA path): rounded before the call the results are the
    same (the gradients to the bit on the chip, PERF.md, PR 28), the
    kernels read half the bytes and the step keeps one copy of every head
    array, not a float32 one for the forward beside a bfloat16 one for the
    backward. bfloat16 stays; float64 keeps its width; the interpreter
    multiplies in float32."""
    return jnp.dtype(jnp.bfloat16 if dtype == jnp.float32 and not interpret
                     else dtype)


def _as_operands(interpret, *arrays):
    """``arrays`` rounded once to ``_operand_dtype``: what the forward
    kernel reads, the residuals and what the backward kernel reads are
    this one copy. Applied inside the custom_vjp rules, so no gradient
    passes back through the cast."""
    cd = _operand_dtype(arrays[0].dtype, interpret)
    return tuple(x.astype(cd) for x in arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _attention(q, k, v, mask, causal, scale, block_q, block_k, interpret, h,
               geometry):
    return _attention_fwd(q, k, v, mask, causal, scale, block_q, block_k,
                          interpret, h, geometry)[0]


def _attention_fwd(q, k, v, mask, causal, scale, block_q, block_k,
                   interpret, h, geometry):
    out_dtype = q.dtype
    q, k, v = _as_operands(interpret, q, k, v)
    out, lse = _run_fwd(q, k, v, mask, h, causal, scale, block_q, block_k,
                        interpret, out_dtype, geometry)
    return out, (q, k, v, mask, out, lse)


def _bwd_kernel(t_true, ragged, causal, scale, sub_q, sub_k, has_mask, form,
                qi_ref, kj_ref, edge_ref, q_ref, k_ref, v_ref, g_ref, st_ref,
                *rest, geometry=None):
    """The backward in the forward's orientation: a piece's probabilities
    are recomputed as p^T = exp(k q^T * scale - lse), [sub_k, sub_q], from
    the residuals, so ``lse`` and ``delta`` (``st_ref`` rows 0 and 1) are
    [1, sub_q] rows and nothing is broadcast across lanes, and the three
    gradients accumulate transposed, [D, .], full in the lanes at any head
    width. Five products a live piece (k q^T, v g^T, g^T p, q^T ds, k^T ds).

    ``form``: "fused" walks a head key block by key block with the query
    blocks innermost: dk^T / dv^T close with their key block, dq^T stays in
    VMEM for the whole head. "dkv" is the same walk without dq; "dq" walks
    query block by query block with the key blocks innermost."""
    with_dq, with_dkv = form != "dkv", form != "dq"
    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    outs, scratch = rest[:len(rest) // 2], rest[len(rest) // 2:]
    if with_dq:
        dq_ref, dq_s = outs.pop(0), scratch.pop(0)
    if with_dkv:
        (dk_ref, dv_ref), (dk_s, dv_s) = outs, scratch
    # the list's outer block (``step_list``) is the key block, or the
    # query block in the "dq" form: ``opens`` and ``closes`` are its
    iq, j, opens, closes = _step(qi_ref, kj_ref, edge_ref)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    fold = math.frexp(scale)[0] == 0.5       # as the forward's

    if with_dkv:
        @pl.when(opens)
        def _():
            dk_s[:] = jnp.zeros_like(dk_s)
            dv_s[:] = jnp.zeros_like(dv_s)
    if with_dq:
        # "fused": [nq, D, block_q], one slab a query block, zeroed at the
        # head's first step and written at its last; "dq": [1, D, block_q],
        # zeroed as the query block opens and written as it closes
        dq_i = iq if form == "fused" else 0
        dq_opens, dq_closes = (opens, closes) if form == "dq" else (
            pl.program_id(1) == 0,
            pl.program_id(1) == pl.num_programs(1) - 1)

        @pl.when(dq_opens)
        def _():
            dq_s[:] = jnp.zeros_like(dq_s)

    def tile(causal_mask, key_masks):
        """One grid step as one basic block of [sub_q, sub_k] pieces; the
        arguments are ``_attn_kernel``'s."""
        def run():
            q = q_ref[0]                                     # [Bq, D]
            if fold:
                q = q * scale
            g = g_ref[0]

            def products(r0, c0):
                nt = (((1,), (1,)), ((), ()))
                rows, cols = slice(r0, r0 + sub_q), slice(c0, c0 + sub_k)
                return (jax.lax.dot_general(
                            k_ref[0, cols, :], q[rows], nt,
                            preferred_element_type=jnp.float32),
                        jax.lax.dot_general(
                            v_ref[0, cols, :], g[rows], nt,
                            preferred_element_type=jnp.float32))  # [Sk, Sq]

            # the two products that wait for no vector work go ahead of it
            dq_acc, dkv_acc = {}, {}
            for (r0, c0), (s, dp) in _issued_ahead(
                    _pieces(block_q, block_k, sub_q, sub_k, causal_mask),
                    products):
                if not fold:
                    s = s * scale
                rows, cols = slice(r0, r0 + sub_q), slice(c0, c0 + sub_k)
                p = jnp.exp(s - st_ref[0, 0:1, rows])
                valid = _piece_valid(
                    causal_mask, t_true if key_masks and ragged else None,
                    mask_ref if key_masks else None, iq, j, block_q,
                    block_k, r0, c0, sub_q, sub_k)
                if valid is not None:
                    # zeroed, not exponentiated: a row that saw no key has
                    # the sentinel for its lse, and exp(s - lse) overflows
                    p = jnp.where(valid, p, 0.0)
                ds = (p * (dp - st_ref[0, 1:2, rows])).astype(q.dtype)
                tt = (((0,), (1,)), ((), ()))
                if with_dkv:
                    if c0 not in dkv_acc:
                        dkv_acc[c0] = (dk_s[:, cols], dv_s[:, cols])
                    dk, dv = dkv_acc[c0]
                    dkv_acc[c0] = (
                        dk + jax.lax.dot_general(
                            q[rows], ds, tt,
                            preferred_element_type=jnp.float32),
                        dv + jax.lax.dot_general(
                            g[rows], p.astype(g.dtype), tt,
                            preferred_element_type=jnp.float32))  # [D, Sk]
                if with_dq:
                    if r0 not in dq_acc:
                        dq_acc[r0] = dq_s[dq_i, :, rows]
                    dq_acc[r0] = dq_acc[r0] + jax.lax.dot_general(
                        k_ref[0, cols, :], ds, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)      # [D, Sq]
            for c0, (dk, dv) in dkv_acc.items():
                cols = slice(c0, c0 + sub_k)
                dk_s[:, cols], dv_s[:, cols] = dk, dv
            for r0, dq in dq_acc.items():
                dq_s[dq_i, :, r0:r0 + sub_q] = dq
        return run

    _walk_tiles(tile, causal, ragged, has_mask, iq, j, block_q, block_k,
                t_true, geometry)

    # rows meet lanes once, as a block closes. dk took the scale with q
    # where it folds; dq always owes it
    if with_dkv:
        @pl.when(closes)
        def _():
            dk = dk_s[:] if fold else dk_s[:] * scale
            dk_ref[0] = dk.T.astype(dk_ref.dtype)
            dv_ref[0] = dv_s[:].T.astype(dv_ref.dtype)
    if with_dq:
        @pl.when(dq_closes)
        def _():
            for i in range(dq_s.shape[0]):
                dq_ref[0, i * block_q:(i + 1) * block_q, :] = (
                    dq_s[i] * scale).T.astype(dq_ref.dtype)


#: what Mosaic gives a kernel that asks for nothing (its default scoped
#: limit on the v5e, of 128 MiB of VMEM), and what a call keeps free above
#: its own count for what the compiler adds (``bwd_vmem_bytes`` has not read
#: under the compiler's own need at any shape probed)
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_MARGIN = 1024 * 1024

#: the largest count (``bwd_vmem_bytes``) the fused backward takes: set by
#: measurement and not by the chip's 128 MiB, which would hold far more.
#: Every shape a cell, chip_smoke.py or a test names whose count lies
#: between what the default limit admitted (14 MiB) and here was timed in
#: both forms on the v5e and the fused one won, the largest of them [*,
#: 4096, 256] float32 at 19.06 MiB (PERF.md section 6, PR 46). Past it
#: (T 8,192 at width 256 counts 31 MiB) the call stays split: the fused
#: form won there too when timed alone, but no step has run it
_VMEM_BUDGET = 20 * 1024 * 1024


def bwd_vmem_bytes(form, t_pad, d, block_q, block_k, operand_size, grad_size):
    """VMEM the backward holds in one grid step, in the form it would take
    ("fused", or "split": the larger of its two kernels). The operand
    blocks (q, g, k, v) twice (the pipeline's two buffers) at
    ``operand_size``, the residuals' (``_operand_dtype``: 2 for float32
    callers on the chip); the gradient blocks twice at ``grad_size``, the
    cotangent's, which is the caller's; the float32 accumulators, the
    product pieces in flight and, fused, a whole head's dq: its block twice
    at ``grad_size`` and its float32 accumulator, ``t_pad * d`` each. A
    [rows, d] block lies in VMEM in whole lane tiles, so a width under 128
    costs 128 there (the accumulators are [d, rows]: whole sublane tiles).
    The one count: ``_run_bwd`` chooses the form by it and
    ``_run_bwd_local`` asks Mosaic for it. Checked against the least
    ``vmem_limit_bytes`` the compiler takes the fused kernel at (compiled
    for the v5e, 16 heads; PERF.md section 6, PR 46): 19.06 MiB here and
    there at [*, 4096, 256] float32, 16.56 against 15.72 at [*, 8192, 128]
    (PR 28's compiler said 16.46 as it refused the default 16), 14.31
    against 12.73 at [*, 8192, 64], 24.31 against 23.21 at [*, 16384, 64]:
    never under it."""
    lanes = -(-d // _LANE) * _LANE
    d8 = -(-d // 8) * 8
    pieces = 2 * _SCORES_AHEAD * _sub_tile(block_q) * _sub_tile(block_k) * 4
    blocks = 2 * operand_size * lanes * (2 * block_q + 2 * block_k)  # q g k v
    small = 2 * 8 * 4 * (block_q + block_k)                      # stats, mask
    dkv = 2 * grad_size * lanes * 2 * block_k + 2 * d8 * block_k * 4
    if form == "fused":
        return blocks + small + pieces + dkv + (
            2 * grad_size * lanes * t_pad + d8 * t_pad * 4)
    dq = 2 * grad_size * lanes * block_q + d8 * block_q * 4
    return blocks + small + pieces + max(dkv, dq)


def _run_bwd(res, g, g_lse, h, causal, scale, block_q, block_k, interpret,
             geometry=None):
    """(dq, dk, dv) [BH, T, D] in ``g``'s dtype (the caller's) from the
    forward's residuals (q, k, v as the forward kernel read them) and the
    cotangent ``g`` [BH, T, D]; ``g_lse`` [BH, T] or None is the cotangent
    on the log-sum-exp output (``flash_attention_block``). One kernel a
    head with dq resident ("fused", five products a piece) where what that
    holds in VMEM (``bwd_vmem_bytes``) is within ``_VMEM_BUDGET``, else a
    dK/dV and a dQ kernel that each recompute the probabilities ("split",
    seven products): chosen here, from the shape and the dtypes. Once a
    batch shard under a declared mesh, as ``_run_fwd``."""
    q, k, v, mask, out, lse = res
    if mask is not None and mask.shape[-1] == 0:   # zero-width = unmasked
        mask = None
    _, t, d = q.shape
    form = "fused" if bwd_vmem_bytes(
        "fused", _geometry(t, block_q, block_k)[2], d, block_q, block_k,
        q.dtype.itemsize, g.dtype.itemsize) <= _VMEM_BUDGET else "split"
    arrays = [q, k, v, out, lse, g]
    arrays += [] if g_lse is None else [g_lse]
    arrays += [] if mask is None else [mask]

    def local(q, k, v, out, lse, g, *rest):
        return _run_bwd_local(
            q, k, v, out, lse, g, None if g_lse is None else rest[0],
            None if mask is None else rest[-1], h, causal, scale, block_q,
            block_k, interpret, form, geometry)
    return _spmd.per_batch_shard(local, arrays, (0,) * len(arrays),
                                 (0, 0, 0))


@jax.named_scope("flash_attn.bwd")
@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12, 13, 14, 15),
                   inline=True)
def _run_bwd_local(q, k, v, out, lse, g, g_lse, mask, h, causal, scale,
                   block_q, block_k, interpret, form, geometry=None):
    # jitted and inlined for the reason _run_fwd_local is
    bh, t, d = q.shape
    f32 = jnp.float32
    block_q, block_k, t_pad = _geometry(t, block_q, block_k)
    nq, nk = t_pad // block_q, t_pad // block_k
    # d(lse)/d(s) is the softmax row, so a cotangent on lse adds p * g_lse
    # to ds = p * (dp - delta): it folds into delta
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(f32)
    # padded query rows have q = g = 0 and lse = delta = 0: p = 1, ds = 0,
    # nothing reaches dk or dv
    stats = _pad_to(jnp.stack([lse.astype(f32), delta], axis=1), t_pad, 2)
    # q, k, v are the forward's own operands; the cotangent alone is
    # rounded here, to their dtype
    operands = [_pad_to(x, t_pad, 1) for x in (q, k, v, g.astype(q.dtype))]
    operands.append(stats)
    if mask is not None:
        operands.append(jnp.broadcast_to(
            _pad_to(mask.astype(f32), t_pad, 1)[:, None, :],
            (bh // h, 8, t_pad)))

    # Mosaic is asked for VMEM only where this form's count, with the
    # margin, passes what it gives unasked: a branch on the count, which
    # the code observes. A call under it carries no compiler parameter, so
    # a step made of such calls (the fused form at widths 64 and 128 to T
    # 8,192 and 4,096, every split call) lowers the same whatever is asked
    # for elsewhere
    need = bwd_vmem_bytes(form, t_pad, d, block_q, block_k, q.dtype.itemsize,
                          g.dtype.itemsize) + _VMEM_MARGIN
    asked = None if need <= _VMEM_DEFAULT else pltpu.CompilerParams(
        vmem_limit_bytes=need)

    def call(form):
        kernel = functools.partial(
            _bwd_kernel, t, t_pad != t, causal, scale, _sub_tile(block_q),
            _sub_tile(block_k), mask is not None, form)
        if geometry is not None:
            kernel = functools.partial(kernel, geometry=geometry)
        q_spec = _spec((1, block_q, d), "q")
        k_spec = _spec((1, block_k, d), "k")
        in_specs = [q_spec, k_spec, k_spec, q_spec,
                    _spec((1, 2, block_q), "q", lanes=True)]
        if mask is not None:
            in_specs.append(_spec((1, 8, block_k), "k", lanes=True, heads=h))
        dkv_scratch = [pltpu.VMEM((d, block_k), f32)] * 2
        if form == "fused":
            out_specs = [pl.BlockSpec((1, t_pad, d),
                                      lambda b, *_: (b, 0, 0)),
                         k_spec, k_spec]
            scratch = [pltpu.VMEM((nq, d, block_q), f32)] + dkv_scratch
        elif form == "dkv":
            out_specs, scratch = [k_spec, k_spec], dkv_scratch
        else:
            out_specs, scratch = [q_spec], [pltpu.VMEM((1, d, block_q), f32)]
        return _stepped_call(
            kernel, step_list(causal, geometry, nq, nk, block_q, block_k,
                              key_major=form != "dq"),
            operands, in_specs, out_specs, scratch,
            out_shape=[jax.ShapeDtypeStruct((bh, t_pad, d), g.dtype)]
            * len(out_specs), interpret=interpret,
            name=("flash_attn_bwd_" if geometry is None
                  else "flash_attn_bd_bwd_") + form,
            compiler_params=asked)

    if form == "fused":
        dq, dk, dv = call("fused")
    else:
        (dk, dv), (dq,) = call("dkv"), call("dq")
    return dq[:, :t], dk[:, :t], dv[:, :t]


def _attention_bwd(causal, scale, block_q, block_k, interpret, h, geometry,
                   res, g):
    dq, dk, dv = _run_bwd(res, g, None, h, causal, scale, block_q, block_k,
                          interpret, geometry)
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
    log-sum-exp; its cotangent is handled exactly (_run_bwd_local). The
    blocks are ``resolve_attention``'s, as ``flash_attention``'s are."""
    return _flash_block_fwd(q, k, v, causal, scale, interpret)[0]


def _flash_block_fwd(q, k, v, causal, scale, interpret):
    b, t, h, d = q.shape
    bq, bk = _resolved(q, k, None)
    qf, kf, vf = _as_operands(interpret, *map(_fold_heads, (q, k, v)))
    out, lse = _run_fwd(qf, kf, vf, None, h, causal, scale, bq, bk,
                        interpret, q.dtype)
    return (_unfold_heads(out, b, h), lse.reshape(b, h, t)), \
        (qf, kf, vf, out, lse, b, h, bq, bk)


def _flash_block_bwd(causal, scale, interpret, res, grads):
    # the blocks ride the residuals: forward and backward tile alike
    qf, kf, vf, out, lse, b, h, bq, bk = res
    g_out, g_lse = grads
    dq, dk, dv = _run_bwd((qf, kf, vf, None, out, lse), _fold_heads(g_out),
                          g_lse.reshape(b * h, -1), h, causal, scale, bq, bk,
                          interpret)
    return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h),
            _unfold_heads(dv, b, h))


flash_attention_block.defvjp(_flash_block_fwd, _flash_block_bwd)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, mask=None, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False,
                    geometry=None):
    """Fused attention over [B, T, H, D] self-attention inputs (same
    contract as nn/layers/attention.py dot_product_attention minus
    cross-length decode). ``mask``: optional [B, Tk] key-side padding mask
    (1 = valid). Fully-masked query rows emit 0 (the naive path emits NaN
    there — 0 is what the downstream masked-output multiply expects).
    ``block_q``/``block_k`` default to ``resolve_attention``'s, and a
    call it would hand to XLA is then refused; explicit values are taken
    as given (the dispatch passes what it resolved; tests name their own).
    ``geometry`` (``BlockDiffusion``) is the score mask in ``causal``'s
    place, over the doubled sequence it describes; it takes no key mask,
    and blocks it does not fit (``BlockDiffusion.fits``) are refused."""
    b, t, h, d = q.shape
    if block_q is None or block_k is None:
        rq, rk = _resolved(q, k, mask, geometry)
        block_q = rq if block_q is None else block_q
        block_k = rk if block_k is None else block_k
    if geometry is not None:
        if causal or mask is not None:
            raise ValueError("a geometry is the whole score mask: it takes "
                             "neither causal nor a key mask beside it")
        bq, bk, _ = _geometry(t, block_q, block_k)
        if not geometry.fits(t, bq, bk):
            raise ValueError(
                f"{geometry} over {t} positions does not lie in whole "
                f"square tiles of {bq} x {bk} (BlockDiffusion.fits)")
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    # custom_vjp needs an array operand in every slot: a zero-width [B, 0]
    # mask is the "no mask" sentinel (kernel + backward skip all mask work)
    maskf = (jnp.zeros((b, 0), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
    out = _attention(_fold_heads(q), _fold_heads(k), _fold_heads(v), maskf,
                     causal, float(scale), block_q, block_k, interpret, h,
                     geometry)
    return _unfold_heads(out, b, h)
