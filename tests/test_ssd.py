"""The selective state-space scan (ops/ssd.py, ISSUE 43) on the CPU: the
chunkwise form against the token-by-token recurrence, which is the plain
reference's (outputs and every gradient; a length that is no multiple of
the chunk, fewer groups than heads, chunks of one position and of the whole
sequence, decays near 0 and near 1), the dtype policy's rounding staying
small, and the one function that chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from deeplearning4j_tpu.ops import ssd
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


def test_heads_that_no_group_count_divides_are_refused():
    args, _ = _inputs(8, jnp.float32, h=3, g=2)
    with pytest.raises(ValueError, match="no multiple"):
        ssd.ssd(*args)
    assert ssd.resolve_ssd((1, 4096, 64, 64), (1, 4096, 8, 128),
                           jnp.float32) is ssd._chunked


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
