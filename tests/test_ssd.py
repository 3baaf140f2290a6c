"""The selective state-space scan (ops/ssd.py, ISSUE 43 and 44) on the CPU:
the chunkwise form against the token-by-token recurrence, which is the plain
reference's (outputs and every gradient; a length that is no multiple of
the chunk, fewer groups than heads, chunks of one position and of the whole
sequence, decays near 0 and near 1), the two kernels in the interpreter
against the same recurrence and against the chunkwise form, the dtype
policy's rounding staying small, and the one function that chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from deeplearning4j_tpu.ops import attention_pallas, ssd
from deeplearning4j_tpu.utils import dtypes


def _inputs(t, dtype, seed=0, b=2, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h, p), dtype)
    # steps from 1e-3 to about 5: a head's decay a token runs from all
    # but kept (exp(-1e-3)) to all but forgotten (exp(-20))
    dt = jax.nn.softplus(3 * jax.random.normal(ks[1], (b, t, h), dtype))
    a = -jnp.arange(1, h + 1, dtype=dtype)
    bm = jax.random.normal(ks[2], (b, t, g, n), dtype)
    cm = jax.random.normal(ks[3], (b, t, g, n), dtype)
    d = jax.random.normal(ks[4], (h,), dtype)
    cot = jax.random.normal(ks[5], (b, t, h, p), dtype)
    return (x, dt, a, bm, cm, d), cot


def _token_by_token(x, dt, a, bm, cm, d):
    """The reference's recurrence, a sequence at a time, each head its
    group's B and C."""
    r = x.shape[2] // bm.shape[2]
    bm, cm = jnp.repeat(bm, r, axis=2), jnp.repeat(cm, r, axis=2)
    return jax.vmap(lambda x, dt, bm, cm: ref.selective_scan(
        x, dt, a, bm, cm, d))(x, dt, bm, cm)


@pytest.mark.parametrize("t,chunk", [
    (37, 16), (37, 1), (37, 37), (37, 128), (64, 16), (150, 64)],
    ids=["ragged", "chunks-of-one", "one-chunk", "chunk-over-T", "whole",
         "ragged-long"])
def test_the_chunkwise_form_is_the_recurrence(t, chunk):
    args, cot = _inputs(t, jnp.float64)
    want, pull = jax.vjp(_token_by_token, *args)
    got, pull_got = jax.vjp(lambda *a: ssd.ssd(*a, chunk=chunk), *args)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "d"), pull_got(cot),
                          pull(cot)):
        np.testing.assert_allclose(g, w, rtol=1e-8, atol=1e-10,
                                   err_msg=name)


def test_every_head_its_own_group_and_one_group_for_all():
    """G = H (no sharing) and G = 1 (all heads one B and C)."""
    for g in (4, 1):
        args, _ = _inputs(40, jnp.float64, seed=3, g=g)
        np.testing.assert_allclose(ssd.ssd(*args, chunk=16),
                                   _token_by_token(*args), rtol=1e-9,
                                   atol=1e-11)


def test_heads_that_no_group_count_divides_are_refused(monkeypatch):
    args, _ = _inputs(8, jnp.float32, h=3, g=2)
    with pytest.raises(ValueError, match="no multiple"):
        ssd.ssd(*args)
    assert ssd.resolve_ssd((1, 4096, 64, 64), (1, 4096, 8, 128),
                           jnp.float32) is ssd._chunked
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    assert ssd.resolve_ssd((1, 4096, 64, 64), (1, 4096, 8, 128),
                           jnp.float32) is ssd._kernels
    with pytest.raises(ValueError, match="no multiple"):
        ssd.resolve_ssd((1, 8, 3, 64), (1, 8, 2, 128), jnp.float32)


def test_no_operation_walks_the_positions():
    """The chunkwise form has no loop in its program: neither a scan over
    positions nor one over chunks."""
    args, _ = _inputs(64, jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: ssd.ssd(*a, chunk=16))(*args))
    assert "scan" not in text and "while" not in text


def test_under_the_bf16_policy_the_state_stays_float32():
    """bfloat16 operands of the products, float32 decays and states: the
    result stays within bfloat16's rounding of the float32 recurrence,
    and the result keeps the input's dtype."""
    args, _ = _inputs(100, jnp.float32, seed=5)
    want = _token_by_token(*args)
    try:
        dtypes.bf16_policy()
        got = ssd.ssd(*args, chunk=32)
    finally:
        dtypes.f32_policy()
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert 1e-5 < err < 2e-2     # rounding is there, and is bfloat16's
    exact = ssd.ssd(*args, chunk=32)
    assert float(jnp.abs(exact - want).max() / jnp.abs(want).max()) < 1e-5


# ---------------------------------------------------------------------------
# the kernels (ISSUE 44), in the interpreter
# ---------------------------------------------------------------------------

CELL = ((1, 4096, 64, 64), (1, 4096, 8, 128))


@pytest.mark.parametrize("x_shape,b_shape,dtype,chunk,tpu,kernels", [
    (*CELL, jnp.float32, 128, True, True),
    (*CELL, jnp.bfloat16, 128, True, True),
    ((2, 37, 64, 64), (2, 37, 8, 128), jnp.float32, 128, True, True),
    ((1, 4096, 16, 128), (1, 4096, 16, 128), jnp.float32, 256, True, True),
    ((1, 4096, 32, 16), (1, 4096, 2, 256), jnp.float32, 128, True, True),
    (*CELL, jnp.float32, 128, False, False),
    (*CELL, jnp.float64, 128, True, False),
    (*CELL, jnp.float16, 128, True, False),
    (*CELL, jnp.float32, 64, True, False),
    ((1, 4096, 64, 64), (1, 4096, 64, 128), jnp.float32, 128, True, False),
    ((1, 4096, 64, 48), (1, 4096, 8, 128), jnp.float32, 128, True, False),
    ((1, 4096, 64, 64), (1, 4096, 8, 64), jnp.float32, 128, True, False),
    ((1, 4096, 64, 128), (1, 4096, 1, 128), jnp.float32, 128, True, False),
], ids=["cell", "cell-bf16", "ragged-batch", "a-head-a-group-chunk-256",
        "narrow-heads", "cpu", "float64", "float16", "chunk-64",
        "a-head-alone-half-a-tile", "heads-of-48", "state-of-64",
        "states-past-vmem"])
def test_resolve_ssd_decides_from_backend_shape_and_dtype(
        monkeypatch, x_shape, b_shape, dtype, chunk, tpu, kernels):
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: tpu)
    want = ssd._kernels if kernels else ssd._chunked
    assert ssd.resolve_ssd(x_shape, b_shape, dtype, chunk) is want


def test_ssd_asks_resolve_ssd_and_nothing_else(monkeypatch):
    """What ``resolve_ssd`` returns is what runs, inside ``ssd_core``, with
    the call's own shapes, dtype and chunk."""
    asked = []

    def chosen(x, dt, a, b, c, d, chunk):
        return ("ran", chunk)

    def resolve(*args):
        asked.append(args)
        return chosen
    monkeypatch.setattr(ssd, "resolve_ssd", resolve)
    args, _ = _inputs(40, jnp.float32)
    assert ssd.ssd(*args, chunk=16) == ("ran", 16)
    assert asked == [((2, 40, 4, 8), (2, 40, 2, 16), jnp.float32, 16)]


def _wide(t, seed=1, dt_scale=1.0, **shape):
    """Inputs at widths the kernels take (``R P`` and ``N`` whole lane
    tiles), float64 for the recurrence and float32 for the kernels."""
    shape = {"b": 1, "h": 4, "p": 64, "g": 2, "n": 128, **shape}
    args, cot = _inputs(t, jnp.float64, seed=seed, **shape)
    args = (args[0], args[1] * dt_scale) + args[2:]
    return args, cot, tuple(u.astype(jnp.float32) for u in args + (cot,))


def _gaps(got, want):
    """Largest difference over the largest value, the result and each
    gradient."""
    return {name: float(jnp.abs(g - w).max() / jnp.abs(w).max())
            for name, g, w in zip(("y", "x", "dt", "a", "b", "c", "d"),
                                  got, want)}


KERNEL_CASES = {
    # t, chunks a grid step, dt's scale, shape, float32's tolerance
    "ragged": (260, 2, 1.0, {"b": 2}, 3e-4),
    "shorter-than-a-chunk": (37, 2, 1.0, {}, 3e-4),
    "one-chunk": (128, 2, 1.0, {}, 3e-4),
    "a-chunk-a-step": (140, 1, 1.0, {}, 3e-4),
    "three-chunks-a-step": (400, 3, 1.0, {"h": 2, "g": 1}, 3e-4),
    "eight-heads-a-group": (140, 2, 1.0, {"h": 16, "g": 2}, 3e-4),
    "one-group": (140, 2, 1.0, {"h": 4, "g": 1}, 3e-4),
    "a-head-a-tile": (140, 2, 1.0, {"h": 2, "p": 128, "g": 2}, 3e-4),
    "a-head-two-tiles": (140, 2, 1.0, {"h": 1, "p": 256, "g": 1}, 3e-4),
    "eight-heads-a-tile": (140, 2, 1.0, {"h": 8, "p": 16, "g": 1}, 3e-4),
    "decays-near-one": (140, 2, 1e-3, {}, 3e-4),
    # steps of 20-100 at A down to -4: the running sums reach the tens of
    # thousands, where float32 keeps three decimals of an exponent
    "decays-near-zero": (140, 2, 20.0, {}, 5e-3),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernels_are_the_recurrence(monkeypatch, case):
    """``ssd_fwd`` and ``ssd_bwd`` in the interpreter, float32 throughout:
    the result and the six gradients against the token-by-token recurrence
    in float64 (float32's rounding apart), and no farther from it than the
    chunkwise ``jax.numpy`` form stands."""
    t, step, dt_scale, shape, tol = KERNEL_CASES[case]
    monkeypatch.setattr(ssd, "_STEP_CHUNKS", step)
    args, cot, low = _wide(t, dt_scale=dt_scale, **shape)
    want, pull = jax.vjp(_token_by_token, *args)
    want = (want,) + pull(cot)

    def run(form):
        @jax.jit
        def both(*args):
            got, pull_got = jax.vjp(form, *args[:-1])
            return (got,) + pull_got(args[-1])
        got = both(*low)
        assert got[0].dtype == jnp.float32
        return _gaps(got, want)

    kernels = run(lambda *a: ssd.ssd_kernels(*a, chunk=128, interpret=True))
    chunked = run(lambda *a: ssd._chunked(*a, 128))
    for name, gap in kernels.items():
        assert gap < tol, (name, gap)
        assert gap < 4 * chunked[name] + 1e-5, (name, gap, chunked[name])


@pytest.mark.parametrize("case", ["ragged", "eight-heads-a-group",
                                  "decays-near-one", "decays-near-zero"])
def test_the_kernels_under_the_bf16_policy_keep_the_state_float32(case):
    """bfloat16 operands of the products, float32 running sums, decays and
    states: the result and every gradient stay within bfloat16's rounding
    of the float32 recurrence, dA (the sum of the running sum's gradient
    over every position, which cancels to the last bit only if each
    entry of the decay's gradient is added and taken as one value)
    included."""
    t, _, dt_scale, shape, _ = KERNEL_CASES[case]
    args, cot, low = _wide(t, dt_scale=dt_scale, **shape)
    want, pull = jax.vjp(_token_by_token, *args)
    try:
        dtypes.bf16_policy()
        got, pull_got = jax.vjp(lambda *a: ssd.ssd_kernels(
            *a, chunk=128, interpret=True), *low[:-1])
        gaps = _gaps((got,) + pull_got(low[-1]), (want,) + pull(cot))
    finally:
        dtypes.f32_policy()
    assert got.dtype == jnp.float32
    assert 1e-5 < gaps["y"] < 2e-2   # rounding is there, and is bfloat16's
    assert max(gaps.values()) < 3e-2, gaps


def test_the_kernels_keep_the_inputs_dtype():
    """bfloat16 arrays in, bfloat16 result and gradients out (dt, A and D
    keep theirs)."""
    _, _, low = _wide(130, h=2, g=1)
    x, dt, a, b, c, d, cot = low
    x, b, c, cot = (u.astype(jnp.bfloat16) for u in (x, b, c, cot))
    got, pull = jax.vjp(lambda *a: ssd.ssd_kernels(
        *a, chunk=128, interpret=True), x, dt, a, b, c, d)
    assert got.dtype == jnp.bfloat16
    assert [g.dtype for g in pull(cot)] == [u.dtype for u in
                                            (x, dt, a, b, c, d)]
