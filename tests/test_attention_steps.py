"""The flash kernels' step list (ISSUE 53) against the dense mask: the grid
of every kernel is `(heads, live steps)`, and `attention_pallas.step_list`
says which tiles those are, in which order, and which step opens and closes
an outer block. Numpy only: no kernel runs here (tests/test_ops.py and
tests/test_block_diffusion.py run them under the list)."""

import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention_pallas as ap


def _dense(causal, geometry, t_pad):
    """[t_pad, t_pad] bool, queries down: the score mask whose tiles the
    kernels walk. The length and key-padding masks are not in it: they
    choose a tile's body, never whether it has one."""
    if geometry is not None:
        return np.asarray(geometry.dense())
    return np.tril(np.ones((t_pad, t_pad), bool)) if causal \
        else np.ones((t_pad, t_pad), bool)


def _live_tiles(dense, block_q, block_k):
    nq, nk = dense.shape[0] // block_q, dense.shape[1] // block_k
    return {(i, j) for i in range(nq) for j in range(nk)
            if dense[i * block_q:(i + 1) * block_q,
                     j * block_k:(j + 1) * block_k].any()}


def _check(causal, geometry, t, block_q, block_k):
    block_q, block_k, t_pad = ap._geometry(t, block_q, block_k)
    nq, nk = t_pad // block_q, t_pad // block_k
    live = _live_tiles(_dense(causal, geometry, t_pad), block_q, block_k)
    for key_major in (False, True):
        steps = ap.step_list(causal, geometry, nq, nk, block_q, block_k,
                             key_major=key_major)
        assert all(a.dtype == np.int32 for a in steps[:3])
        pairs = list(zip(steps.qi.tolist(), steps.kj.tolist()))
        # exactly the tiles in which the dense mask has a True
        assert set(pairs) == live and len(pairs) == len(live) == steps.live
        assert steps.rectangle == nq * nk
        # the rectangle's order with the dead steps taken out
        rectangle = [(i, j) for j in range(nk) for i in range(nq)] \
            if key_major else [(i, j) for i in range(nq) for j in range(nk)]
        assert pairs == [p for p in rectangle if p in live]
        # every outer block opens once, at its first step, and closes once,
        # at its last
        of = (steps.kj if key_major else steps.qi).tolist()
        opened = [o for o, e in zip(of, steps.edge.tolist()) if e & 1]
        closed = [o for o, e in zip(of, steps.edge.tolist()) if e & 2]
        assert opened == closed == list(range(nk if key_major else nq))
        for s, e in enumerate(steps.edge.tolist()):
            assert bool(e & 1) == (s == 0 or of[s - 1] != of[s])
            assert bool(e & 2) == (s == len(of) - 1 or of[s + 1] != of[s])
            assert e & ~3 == 0
    return steps


@pytest.mark.parametrize("t,block_q,block_k", [
    (2048, 512, 512), (1024, 256, 256), (128, 128, 128),   # equal blocks
    (512, 128, 256), (512, 256, 128), (1536, 512, 128),    # unequal
    (300, 256, 256), (1100, 512, 512),                     # T padded
    (300, 128, 256), (20, 8, 6), (13, 8, 8), (20, 6, 8)],  # both
    ids=lambda v: str(v))
def test_a_causal_list_is_the_lower_triangles_tiles(t, block_q, block_k):
    _check(True, None, t, block_q, block_k)


@pytest.mark.parametrize("t,block_q,block_k", [
    (1024, 512, 512), (512, 128, 256), (300, 256, 256), (20, 8, 6)])
def test_without_a_mask_the_list_is_the_rectangle(t, block_q, block_k):
    steps = _check(False, None, t, block_q, block_k)
    assert steps.live == steps.rectangle
    inner = steps.rectangle // len(set(steps.kj.tolist()))
    # key-major, the last list made: the query blocks run innermost
    assert steps.qi.tolist()[:inner] == list(range(inner))


@pytest.mark.parametrize("seq_len,block_len,block", [
    (256, 4, 128), (256, 16, 256), (512, 64, 128), (512, 8, 128),
    (1024, 4, 512), (1024, 32, 256), (4096, 4, 512), (128, 4, 128)])
def test_a_block_diffusion_list_is_the_dense_masks_tiles(seq_len, block_len,
                                                        block):
    g = ap.BlockDiffusion(seq_len, block_len)
    assert g.fits(2 * seq_len, block, block)
    steps = _check(False, g, 2 * seq_len, block, block)
    half = seq_len // block
    # the noised diagonal, and the noised x clean and the clean x clean
    # triangles with their diagonals
    assert steps.live == half + 2 * (half * (half + 1) // 2)
    # a noised key block meets its own query block alone: in the key-major
    # list that one step both opens and closes it
    assert steps.edge.tolist()[:half] == [3] * half


#: (causal, BlockDiffusion or None, T of the call) of the flash calls of
#: the seven language cells, in 512 x 512 blocks, and the live steps of a
#: head's rectangle: the module docstring's table and PERF.md's.
#: resnet50-train-b128, the eighth cell, runs no attention
CELLS = {
    "sdar-train-bd4-t4096": ((False, ap.BlockDiffusion(4096, 4), 8192), 80, 256),
    "lfm2-train-t8192": ((True, None, 8192), 136, 256),
    "glm47flash-train-t4096": ((True, None, 4096), 36, 64),
    "nemotron3nano-train-packed": ((True, None, 4096), 36, 64),
    "qwen3next-train-t4096": ((True, None, 4096), 36, 64),
    "ouro-train-t2048": ((True, None, 2048), 10, 16),
    "gpt2m-train-t1024": ((True, None, 1024), 3, 4),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_counts_are_pinned(cell):
    (causal, geometry, t), live, rectangle = CELLS[cell]
    bq, bk = ap._BLOCK_Q, ap._BLOCK_K
    assert (bq, bk) == (512, 512)
    for key_major in (False, True):
        steps = ap.step_list(causal, geometry, t // bq, t // bk, bq, bk,
                             key_major=key_major)
        assert (steps.live, steps.rectangle) == (live, rectangle)
    assert f"{live} / {rectangle}" in ap.__doc__


def test_the_list_is_made_once_a_geometry():
    """A model calls the kernels once a layer with the same mask: the list
    is kept by its arguments, and nobody may write into the kept arrays."""
    a = ap.step_list(True, None, 8, 8, 512, 512)
    assert ap.step_list(True, None, 8, 8, 512, 512) is a
    assert ap.step_list(True, None, 8, 8, 512, 512, key_major=True) is not a
    for array in a[:3]:
        with pytest.raises(ValueError, match="read-only"):
            array[0] = 7


def test_a_mask_that_leaves_an_outer_block_empty_is_refused(monkeypatch):
    """Every output block is opened, written and closed by its own steps,
    so a mask under which a query block (or a key block) meets no live
    tile cannot be walked: the list refuses it where it is made."""
    def strictly_under(tile, causal, ragged, has_mask, iq, j, *rest):
        if j < iq:                  # query block 0 sees nothing
            tile(None, False)()
    monkeypatch.setattr(ap, "_walk_tiles", strictly_under)
    with pytest.raises(AssertionError, match="never be written"):
        ap.step_list.__wrapped__(True, None, 4, 4, 128, 128)
