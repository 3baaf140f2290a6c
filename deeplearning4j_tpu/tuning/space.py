"""Per-kernel Pallas config spaces with static validity pruning.

The Pallas kernels shipped hand-picked constants — attention
``block_q/block_k`` (512x512, chosen once on one v5e window), conv
``bn/bj/bk`` tile geometry and the 3x3 batch-row target, the LSTM
``tile_cols`` column width. This module parameterizes them as searchable
spaces in the TVM mold (PAPERS.md arxiv 1802.04799): enumerate
candidates, then reject statically-invalid ones BEFORE any compile —

* the TPU **(8, 128) tile rule**: a block dimension mapped to the lane
  (minor) axis must be a 128-multiple, the sublane (second-minor) axis an
  8-multiple — real-TPU compiles reject violations with an opaque mosaic
  error, so the space prunes them for free;
* the **VMEM budget**: per-grid-step block residency (double-buffered
  in/out blocks + scratch + the score pieces / accumulator) must fit the
  ~16 MiB scoped VMEM; the estimate uses the same arithmetic the kernel
  docstrings derive (14 MiB budget — the margin ops/lstm_pallas.py
  already uses), for attention of the forward and of the backward kernel;
* **redundant clamps**: blocks larger than the (128-rounded) array are
  clamped by the kernels at trace time, so such candidates duplicate a
  smaller one — measuring them would just burn live-window time;
* kernel-specific divisibility (the LSTM column tile must divide 4H —
  the kernel's own tile-picker constraint).

Pruning is backend-independent on purpose: the DB a CPU smoke populates
exercises the same validity logic a live-TPU window relies on.
"""

from __future__ import annotations

import itertools

import numpy as np

#: scoped-VMEM budget for a candidate's per-grid-step residency; the same
#: ~16 MiB-minus-margin ops/lstm_pallas.py's supported() uses
VMEM_BUDGET = 14 * 1024 * 1024
LANE = 128
SUBLANE = 8

#: searchable dimensions per kernel id. ``remat`` on the attention space
#: is honored by the measurement harness only in fwd+bwd mode (forward
#: timing cannot distinguish it) — see enumerate_space(include_remat=).
SPACES = {
    "attention": {"block_q": (128, 256, 512, 1024),
                  "block_k": (128, 256, 512, 1024),
                  "remat": (False, True)},
    "conv_matmul": {"bn": (128, 256, 512),
                    "bk": (128, 256, 512),
                    "bj": (128, 256, 512)},
    "conv3x3": {"bt_target": (128, 256, 512),
                "bj": (128, 256, 512)},
    "lstm": {"tile_cols": (256, 512, 1024, 2048)},
}


def _round_up(n, m):
    return -(-int(n) // m) * m


def _itemsize(dtype):
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        return 4


def enumerate_space(kernel, *, include_remat=False):
    """Every candidate config dict in ``kernel``'s space (cartesian
    product of the dimensions). The ``remat`` dimension is collapsed to
    False unless ``include_remat`` — forward-only measurement cannot
    tell remat variants apart, so enumerating both would double the
    candidate count for identical timings."""
    dims = dict(SPACES[kernel])
    if "remat" in dims and not include_remat:
        dims["remat"] = (False,)
    keys = sorted(dims)
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(dims[k] for k in keys))]


# ---------------------------------------------------------------------------
# per-kernel validity
# ---------------------------------------------------------------------------

def _attention_valid(cfg, shape, dtype):
    """shape: layer-level [B, T, H, D]. The residency of one grid step of
    ops/attention_pallas.py's forward kernel as it is built: blocks at the
    head's own width (a VMEM block still pads its minor dimension to the
    128 lanes), the out^T accumulator and the max / sum rows with block_q
    along the lanes, and the score pieces in flight — [sub_k, sub_q]
    float32 each, never the whole block_q x block_k tile. Then the
    backward kernel's, in the form the call would take at this length (the
    tuner times gradients): the kernel module's own count."""
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
    _, t, _, d = shape
    if bq % LANE or bk % LANE:
        # block_q rides the LANE axis of the [1, 8, Bq] lse output block,
        # the [1, Bq] softmax state and the [D, Bq] accumulator, block_k
        # the lane axis of the [1, 8, Bk] key-mask block — both must be
        # 128-multiples (the round-2 lse lesson)
        return "tile rule: block_q/block_k must be 128-multiples"
    t128 = _round_up(t, LANE)
    if bq > t128 or bk > t128:
        return "redundant: block exceeds the 128-rounded sequence (clamps)"
    dl = _round_up(d, LANE)
    itm = _itemsize(dtype)
    piece = _ap._sub_tile(bq) * _ap._sub_tile(bk) * 4
    vmem = (
        2 * bq * dl * itm          # q block, double-buffered
        + 2 * 2 * bk * dl * itm    # k + v blocks, double-buffered
        + 2 * bq * dl * itm        # out block
        + 2 * 8 * bq * 4           # lse block (8-sublane broadcast)
        + 2 * 8 * bk * 4           # key-mask block (8-sublane broadcast)
        + _round_up(d, SUBLANE) * bq * 4 + 2 * 8 * bq * 4  # acc/m/l (f32)
        + _ap._SCORES_AHEAD * piece  # the score pieces in flight
    )
    if vmem > VMEM_BUDGET:
        return f"vmem: ~{vmem // 1024} KiB exceeds the {VMEM_BUDGET // 1024} KiB budget"
    t_pad = _ap._geometry(t, bq, bk)[2]
    bwd = min(_ap.bwd_vmem_bytes(form, t_pad, d, bq, bk, itm)
              for form in ("fused", "split"))
    if bwd > VMEM_BUDGET:
        return (f"vmem: the backward's ~{bwd // 1024} KiB exceeds the "
                f"{VMEM_BUDGET // 1024} KiB budget")
    return None


def _conv_matmul_valid(cfg, shape, dtype):
    """shape: (n_rows, cin, cout) of the 1x1-conv GEMM."""
    bn, bk, bj = int(cfg["bn"]), int(cfg["bk"]), int(cfg["bj"])
    n, cin, cout = shape
    if bn % SUBLANE:
        return "tile rule: bn (sublane rows) must be an 8-multiple"
    if bk % LANE or bj % LANE:
        return "tile rule: bk/bj (lane dims) must be 128-multiples"
    if bn > _round_up(n, SUBLANE) or bk > _round_up(cin, LANE) \
            or bj > _round_up(cout, LANE):
        return "redundant: block exceeds the padded array (clamps)"
    itm = _itemsize(dtype)
    vmem = (bn * bj * 4 + 8 * bj * 4          # acc + stats scratch (f32)
            + 2 * (bn * bk + bk * bj) * itm   # x/w blocks, double-buffered
            + 2 * bn * bj * itm + 2 * 8 * bj * 4)  # z + stats out blocks
    if vmem > VMEM_BUDGET:
        return f"vmem: ~{vmem // 1024} KiB exceeds the {VMEM_BUDGET // 1024} KiB budget"
    return None


def conv3x3_bt(bt_target, bsz, wout):
    """The batch-row tile a ``bt_target`` resolves to at this geometry —
    the same arithmetic ops/conv_pallas.py applies (keep the row-block
    GEMM M-dim near the target without exceeding it wildly), shared so
    validation and the kernel agree."""
    bt = max(1, min(int(bsz), max(1, int(bt_target) // max(int(wout), 1))))
    while bsz % bt:
        bt -= 1
    return bt


def _conv3x3_valid(cfg, shape, dtype):
    """shape: (b, h, w, cin, cout) of the SAME 3x3 conv (stride 1)."""
    bj = int(cfg["bj"])
    b, h, w, cin, cout = shape
    if bj % LANE:
        return "tile rule: bj (lane dim) must be a 128-multiple"
    if bj > _round_up(cout, LANE):
        return "redundant: bj exceeds the padded Cout (clamps)"
    bt = conv3x3_bt(cfg["bt_target"], b, w)
    cinp = _round_up(cin, LANE)
    wp = w + 2  # stride-1 SAME halo
    itm = _itemsize(dtype)
    vmem = (3 * 2 * bt * wp * cinp * itm      # 3 halo row refs, dbl-buffered
            + 2 * 9 * cinp * bj * itm         # the [3,3,Cin,Cout] block
            + 2 * bt * w * bj * itm           # z out block
            + bt * w * bj * 4                 # the f32 row accumulator
            + 2 * 8 * bj * 4)                 # stats scratch + out
    if vmem > VMEM_BUDGET:
        return f"vmem: ~{vmem // 1024} KiB exceeds the {VMEM_BUDGET // 1024} KiB budget"
    return None


def _lstm_valid(cfg, shape, dtype):
    """shape: (t, b, hp) with hp the 128-padded hidden size. The tile
    dimension only exists for the tiled (H > 512) kernel — the resident
    kernel holds the whole Wh block."""
    tile = int(cfg["tile_cols"])
    _, b, hp = shape
    four_h = 4 * hp
    if tile % LANE:
        return "tile rule: tile_cols must be a 128-multiple"
    if tile > four_h:
        return "redundant: tile exceeds 4H (clamps)"
    if four_h % tile:
        return "tile_cols must divide 4H (the kernel's column-tile grid)"
    itm = _itemsize(dtype)
    vmem = (b * four_h * 4                    # persistent gate accumulator
            + 2 * b * hp * 4                  # h/c scratch (f32)
            + 2 * hp * tile * itm             # in-flight Wh tiles
            + b * tile * 4                    # xz block (f32 add)
            + 2 * b * hp * itm)               # h/c out blocks
    if vmem > VMEM_BUDGET:
        return f"vmem: ~{vmem // 1024} KiB exceeds the {VMEM_BUDGET // 1024} KiB budget"
    return None


_VALIDATORS = {"attention": _attention_valid,
               "conv_matmul": _conv_matmul_valid,
               "conv3x3": _conv3x3_valid,
               "lstm": _lstm_valid}


def validate(kernel, config, shape, dtype):
    """None when ``config`` may compile at ``shape``/``dtype``; otherwise
    the human-readable rejection reason (tile rule, VMEM budget,
    redundant clamp, divisibility)."""
    return _VALIDATORS[kernel](config, tuple(int(d) for d in shape), dtype)


def prune(kernel, configs, shape, dtype):
    """Split ``configs`` into (valid, rejected) where rejected carries
    ``(config, reason)`` pairs — the static gate that runs before any
    candidate pays a compile."""
    valid, rejected = [], []
    for cfg in configs:
        reason = validate(kernel, cfg, shape, dtype)
        if reason is None:
            valid.append(cfg)
        else:
            rejected.append((cfg, reason))
    return valid, rejected
