"""The gated delta rule's two kernels (ISSUE 35) in the interpreter on the
CPU, at lane-aligned toy shapes: outputs and all five gradients against
the token-by-token recurrence (one chunk, one grid step of several chunks,
a ragged length that takes two grid steps; decays near 0 and near 1), a
state that starts at zero for every sequence and head, padded positions
that neither write nor decay, and the decisions of the one place that
chooses between the kernels and the ``jax.numpy`` form. A file of its own
beside test_gated_delta_moe.py (whose helpers it shares): the suite's
workers take whole files, and that one is already the longest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention_pallas, gated_delta
from test_gated_delta_moe import DECAYS, _rule_inputs, _token_by_token

GRADS = ("dq", "dk", "dv", "dg", "dbeta")


def _kernels(*args):
    return gated_delta.gated_delta_kernels(*args, interpret=True)


def _lane_inputs(t, decays, seed=0, b=2):
    """Value heads twice the key heads, every head one lane tile wide."""
    return _rule_inputs(t, jnp.float32, decays, seed=seed, b=b,
                        hk=len(decays) // 2, dk=128, dv=128)


@pytest.mark.parametrize("decays", [DECAYS[:2], DECAYS[2:], DECAYS],
                         ids=["forgotten+kept", "between", "four-heads"])
@pytest.mark.parametrize("t", [64, 256, 300],
                         ids=["one-chunk", "one-step", "ragged-two-steps"])
def test_the_kernels_are_the_recurrence(t, decays):
    """float32 (the f32 policy: no bfloat16 operands) at the tolerances
    the chunkwise ``jax.numpy`` form is held to; every gradient finite at
    decays that underflow. T 300 is five chunks: a grid step of four and
    one of one chunk and three of padding, the state carried between."""
    args, cot = _lane_inputs(t, decays, seed=t)
    want, vjp = jax.vjp(_token_by_token, *args)
    got, got_vjp = jax.vjp(_kernels, *args)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b, name in zip(got_vjp(cot), vjp(cot), GRADS):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)


def test_the_kernels_state_starts_at_zero_for_every_sequence_and_head():
    """Two different sequences in one batch give what each gives alone:
    the state in scratch is zeroed at a (batch, key head)'s first grid
    step, not only at the grid's."""
    args, cot = _lane_inputs(300, DECAYS, seed=5)

    def run(args, cot):
        out, vjp = jax.vjp(_kernels, *args)
        return (out,) + vjp(cot)

    both = run(args, cot)
    for i in range(2):
        alone = run([x[i:i + 1] for x in args], cot[i:i + 1])
        for a, b in zip(alone, both):
            np.testing.assert_allclose(a[0], b[i], rtol=1e-6, atol=1e-7)


def test_a_padded_position_neither_writes_nor_decays_through_the_kernels():
    """T = 70 is two chunks, the second padded by 58: the first 70 outputs
    are those of the same inputs run to T = 128, and so are the gradients
    of a cotangent that is zero from 70 on."""
    args, cot = _lane_inputs(128, DECAYS, seed=3)
    cot = cot.at[:, 70:].set(0.0)
    whole, whole_vjp = jax.vjp(_kernels, *args)
    cut, cut_vjp = jax.vjp(_kernels,
                           *(x[:, :70] for x in args))
    np.testing.assert_allclose(cut, whole[:, :70], rtol=1e-6, atol=1e-7)
    for a, b, name in zip(cut_vjp(cot[:, :70]), whole_vjp(cot), GRADS):
        np.testing.assert_allclose(a, b[:, :70], rtol=1e-5, atol=1e-6,
                                   err_msg=name)


def test_the_rule_takes_the_kernels_where_the_dispatch_says(monkeypatch):
    """``gated_delta_rule`` asks ``resolve_gated_delta`` and nothing else:
    with the answer forced it runs the kernels at the tests' narrow widths'
    neighbour, and left alone on the CPU it runs the ``jax.numpy`` form."""
    args, _ = _lane_inputs(64, DECAYS[:2], seed=7, b=1)
    calls = []
    monkeypatch.setattr(
        gated_delta, "gated_delta_kernels",
        lambda *a, interpret: calls.append(("kernels", interpret)) or a[2])
    monkeypatch.setattr(gated_delta, "_chunked",
                        lambda *a: calls.append("jnp") or a[2])
    gated_delta.gated_delta_rule(*args)
    monkeypatch.setattr(gated_delta, "resolve_gated_delta",
                        lambda *a: True)
    gated_delta.gated_delta_rule(*args)
    assert calls == ["jnp", ("kernels", True)]


@pytest.mark.parametrize("tpu,dk,dv,hk,hv,dtype,want", [
    (True, 128, 128, 16, 32, jnp.float32, True),     # the cell
    (True, 128, 128, 16, 32, jnp.bfloat16, True),
    (True, 256, 128, 2, 2, jnp.float32, True),       # two lane tiles
    (True, 128, 256, 1, 4, jnp.float32, True),
    (False, 128, 128, 16, 32, jnp.float32, False),   # no chip
    (True, 8, 16, 2, 4, jnp.float32, False),         # the CPU tests' widths
    (True, 128, 16, 2, 4, jnp.float32, False),
    (True, 8, 128, 2, 4, jnp.float32, False),
    (True, 192, 128, 2, 4, jnp.float32, False),      # no whole lane tiles
    (True, 128, 128, 3, 4, jnp.float32, False),      # Hv % Hk
    (True, 256, 256, 2, 8, jnp.float32, True),       # 1 MiB of states
    (True, 256, 256, 1, 8, jnp.float32, False),      # 2 MiB: not in VMEM
    (True, 128, 128, 16, 32, jnp.float64, False),    # gradient checks
    (True, 128, 128, 16, 32, jnp.float16, False),
])
def test_resolve_gated_delta_decides_from_backend_shape_and_dtype(
        monkeypatch, tpu, dk, dv, hk, hv, dtype, want):
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: tpu)
    assert gated_delta.resolve_gated_delta(
        (2, 100, hk, dk), (2, 100, hv, dv), dtype) is want


@pytest.mark.parametrize("count", [1, 2, 5])
def test_the_kernels_inverses_are_the_product_forms(count):
    """``_inverses`` lays the same products out for the matrix units (two
    matrices side by side along the lanes, rows that can only be zero left
    out, products that share a right side stacked): every matrix's result
    is ``_unit_lower_inverse``'s, an odd matrix out included."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(count),
                                   (count, 64, 64), jnp.float32), -1) * 0.3
    got = gated_delta._inverses(list(a))
    assert len(got) == count
    for mine, one in zip(got, a):
        np.testing.assert_allclose(
            mine, gated_delta._unit_lower_inverse(one), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(mine @ (jnp.eye(64) + one), jnp.eye(64),
                                   atol=1e-4)


@pytest.mark.parametrize("t,hk,hv,d,want", [
    (4096, 16, 32, 128, (4096, 64, 4)),    # the cell: steps of 4 chunks
    (300, 2, 4, 128, (512, 8, 4)),         # padded to whole steps
    (100, 1, 2, 128, (128, 2, 2)),         # shorter than a step
    (4096, 2, 8, 256, (4096, 64, 1)),      # 1 MiB of states a chunk
])
def test_a_grid_step_takes_the_chunks_whose_states_fit(t, hk, hv, d, want):
    q = jax.ShapeDtypeStruct((1, t, hk, d), jnp.float32)
    v = jax.ShapeDtypeStruct((1, t, hv, d), jnp.float32)
    _, tp, _, _, r, _, n, m = gated_delta._geometry(q, v)
    assert (tp, n, m) == want and r == hv // hk
