"""ops/causal_conv.py: the short causal convolution with its gates and
SiLU as two kernels under one ``custom_vjp``, against the ``jax.numpy``
form under autodiff (CPU, the kernels under ``interpret=True``)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.mixers.gated_delta import GatedDeltaNet
from deeplearning4j_tpu.nn.layers.mixers.short_conv import ShortConv
from deeplearning4j_tpu.ops import attention_pallas, causal_conv

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")


def _reference(p, w, gate_before, gate_after, activation):
    """``causal_taps`` with jnp gates and ``jax.nn.silu`` around it, on
    slices of ``p``: what the two layers were before the op."""
    c = w.shape[0]
    parts = [p[..., k * c:(k + 1) * c]
             for k in range(1 + gate_before + gate_after)]
    x = parts[-1]
    y = causal_conv.causal_taps(parts[0] * x if gate_before else x, w)
    if activation:
        y = jax.nn.silu(y)
    if gate_after:
        y = y * parts[int(gate_before)]
    return y, p[..., len(parts) * c:]


def _loss(fn, shape):
    """Linear in ``y`` (a cotangent that does not move with ``y``'s own
    rounding), quadratic in what passes through."""
    dy = jax.random.normal(jax.random.PRNGKey(9), shape, jnp.float32)

    def loss(p, w):
        y, rest = fn(p, w)
        out = jnp.sum(y.astype(jnp.float32) * dy)
        if rest is not None and rest.shape[-1]:
            out = out + jnp.sum(jnp.square(rest.astype(jnp.float32)))
        return out
    return loss


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("gate_before,gate_after,activation", [
    (False, False, True),     # GatedDeltaNet's
    (True, True, False),      # ShortConv's
    (False, False, False), (True, False, True), (False, True, True)],
    ids=["silu", "gates", "bare", "before_silu", "after_silu"])
@pytest.mark.parametrize("taps,columns,behind,split", [
    # chunks of 128 columns, the result in two pieces, columns passing by
    (4, 384, 128, (128, 256)),
    # two chunks of 256, one result, nothing behind the parts
    (3, 512, 0, ())],
    ids=["taps4", "taps3"])
def test_the_kernels_are_the_plain_form(monkeypatch, taps, columns, behind,
                                        split, gate_before, gate_after,
                                        activation, dtype):
    """Output, the gradient of the whole projection (each part's at its
    columns, what passes through included) and the taps' gradient, over
    five row blocks of which the last holds half a block's rows."""
    parts = 1 + gate_before + gate_after
    width = parts * columns + behind
    # room for sixteen rows of the backward's blocks, twice each
    monkeypatch.setattr(
        causal_conv, "_VMEM", 2 * 16 * jnp.dtype(dtype).itemsize * (
            columns + 2 * width))
    t = 72
    assert causal_conv._rows(t, width, columns, dtype) == 16
    kp, kw = jax.random.split(jax.random.PRNGKey(taps))
    p = jax.random.normal(kp, (2, t, width), jnp.float32).astype(dtype)
    w = (0.5 * jax.random.normal(kw, (columns, taps), jnp.float32)
         ).astype(dtype)
    kw_ = dict(gate_before=gate_before, gate_after=gate_after,
               activation=activation)

    def kernels(p, w):
        y, rest = causal_conv.causal_conv_kernels(
            p, w, split=split, interpret=True, **kw_)
        if split:
            assert [piece.shape[-1] for piece in y] == list(split)
            y = jnp.concatenate(y, axis=-1)
        return y, rest

    def plain(p, w):   # in float32 on the same (rounded) inputs
        return _reference(p.astype(jnp.float32), w.astype(jnp.float32),
                          **kw_)

    y, rest = kernels(p, w)
    want, _ = plain(p, w)
    assert y.dtype == dtype and y.shape == (2, t, columns)
    assert (rest is None) == (behind == 0)
    if behind:
        np.testing.assert_array_equal(np.asarray(rest, np.float32),
                                      np.asarray(p[..., -behind:],
                                                 np.float32))
    got = jax.grad(_loss(kernels, y.shape), (0, 1))(p, w)
    ref = jax.grad(_loss(plain, y.shape), (0, 1))(p, w)
    assert got[0].dtype == dtype and got[0].shape == p.shape
    assert got[1].dtype == dtype and got[1].shape == w.shape
    # float32: rounding of sums in another order; bfloat16: both sides
    # round a float32 result once, and may round a tie apart
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    for a, b in ((y, want), (got[0], ref[0]), (got[1], ref[1])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def test_the_residuals_are_the_inputs_and_nothing_else():
    p = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 384), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 4), jnp.float32)
    _, vjp = jax.vjp(lambda p, w: causal_conv.causal_conv_kernels(
        p, w, gate_before=True, activation=True, interpret=True), p, w)
    kept = jax.tree_util.tree_leaves(vjp)
    assert sorted(k.shape for k in kept) == sorted([p.shape, w.shape])
    for k in kept:
        np.testing.assert_array_equal(k, p if k.shape == p.shape else w)


def test_resolve_causal_conv_decides_from_backend_shape_and_dtype(
        monkeypatch):
    take = ((1, 4096, 12288), (8192, 4), jnp.float32, False, False)
    assert not causal_conv.resolve_causal_conv(*take)     # no chip here
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    assert causal_conv.resolve_causal_conv(*take)
    assert causal_conv.resolve_causal_conv(
        (1, 8192, 6144), (2048, 3), jnp.bfloat16, True, True)
    for p_shape, w_shape, dtype, *split in (
            ((2, 5, 24), (8, 3), jnp.float32),          # no lane tile
            ((1, 64, 320), (256, 4), jnp.float32),      # 64 columns behind
            ((1, 64, 256), (128, 9), jnp.float32),      # past the halo
            ((1, 64, 256), (128, 1), jnp.float32),
            ((1, 64, 256), (128, 4), jnp.float64),
            ((1, 64, 256), (256, 4), jnp.float32, (192, 64)),
            ((1, 64, 1 << 22), (1 << 21, 4), jnp.float32)):   # VMEM
        assert not causal_conv.resolve_causal_conv(
            p_shape, w_shape, dtype, False, False, *split), (p_shape,
                                                             w_shape)
    assert causal_conv.resolve_causal_conv(
        (1, 64, 384), (128, 4), jnp.float32, False, False)


def test_a_shape_the_kernels_do_not_take_goes_the_plain_way(monkeypatch):
    """Eight columns (as ``test_short_conv_is_causal_and_starts_from_
    zeros`` uses) on an open backend gate: the ``jax.numpy`` form, no
    kernel in the jaxpr, the same numbers as before the op."""
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    p = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 24))
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 3))

    def op(p, w):
        return causal_conv.causal_conv(p, w, gate_before=True,
                                       gate_after=True)[0]
    assert "pallas_call" not in str(jax.make_jaxpr(op)(p, w))
    want, rest = _reference(p, w, True, True, False)
    assert rest.shape[-1] == 0
    np.testing.assert_array_equal(op(p, w), want)
    with pytest.raises(ValueError, match="no kernel"):
        causal_conv.causal_conv_kernels(p, w, gate_before=True,
                                        gate_after=True, interpret=True)
    with pytest.raises(ValueError, match="do not hold"):
        causal_conv.causal_conv(p[..., :16], w, gate_before=True,
                                gate_after=True)
    with pytest.raises(ValueError, match="split"):
        causal_conv.causal_conv(p, w, split=(4, 2))
    pieces, _ = causal_conv.causal_conv(p, w, split=(6, 2))
    np.testing.assert_array_equal(
        jnp.concatenate(pieces, -1), causal_conv.causal_taps(p[..., :8], w))


def _mixers(config, monkeypatch):
    """The configuration's conv or gated-delta mixers at its own widths
    and length, with the dispatch answering as on the chip."""
    with open(os.path.join(CONFIGS, config + ".json"),
              encoding="utf-8") as fh:
        args = json.load(fh)["program"]["args"]
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    d, t = args["d_model"], args["seq_len"]
    if "layer_types" in args:
        n = args["layer_types"].count("conv")
        layer = ShortConv(n_out=d, kernel=args["conv_kernel"])
    else:
        n = args["n_layers"] - args["n_layers"] // args[
            "full_attention_interval"]
        layer = GatedDeltaNet(
            n_out=d, k_heads=args["linear_k_heads"],
            v_heads=args["linear_v_heads"],
            head_dim=args["linear_k_head_dim"],
            v_head_dim=args["linear_v_head_dim"],
            conv_kernel=args["conv_kernel"])
        # the recurrence is not this test's: its jax.numpy form
        from deeplearning4j_tpu.ops import gated_delta
        monkeypatch.setattr(gated_delta, "resolve_gated_delta",
                            lambda *a: False)
    from deeplearning4j_tpu.nn.conf import inputs
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), inputs.RecurrentType(d, t), jnp.bfloat16))
    return n, layer, params, jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16)


@pytest.mark.parametrize("config,scope", [
    ("qwen3-next-80b-a3b", "gdn_conv"), ("lfm2-24b-a2b", "short_conv")])
def test_the_benchmarks_mixers_hold_one_kernel_a_pass(monkeypatch, config,
                                                      scope):
    """3 + 3 calls in qwen3next's step and 4 + 4 in lfm2's: every mixer of
    the configuration at the benchmark's widths, length and dtypes takes
    the kernels, forward and backward, under the scope its metric reads,
    and no ``pad`` is left there."""
    n, layer, params, x = _mixers(config, monkeypatch)

    def loss(params, x):
        for _ in range(n):
            x = x + layer.apply(params, {}, x)[0].astype(x.dtype)
        return jnp.sum(x.astype(jnp.float32))
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss)).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = re.findall(r'kernel_name = "(causal_conv_[a-z]+)"', text)
    assert sorted(calls) == ["causal_conv_bwd", "causal_conv_fwd"], calls
    for kernel, name in (("fwd", "_run_fwd"), ("bwd", "_run_bwd")):
        assert len(re.findall(r"call @" + name + r"\b", text)) == n
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    fwd = [p for p in paths if "_run_fwd" in p and scope in p]
    bwd = [p for p in paths if "_run_bwd" in p and scope in p]
    assert fwd and not any("transpose(" in p for p in fwd)
    assert bwd and all("transpose(" in p for p in bwd)
    assert not [p for p in paths if scope in p and "/pad" in p]
