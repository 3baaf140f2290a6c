"""The state-space / attention mixture-of-experts vocabulary (ISSUE 43) at
toy size on the CPU: the grouped gated norm against its definition, the
Mamba-2 mixer against the plain reference's, a block with one half absent
(no parameters, no scopes, no residual add for it), attention without
positions over two key/value heads, the convolution's bias (the plain path
and the kernels, and without a bias what the op gave before), ungated
experts against a loop over experts, the shares of a 128-wide router adding
up to the uncut layer, ReLU^2, serde, and a fit."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from deeplearning4j_tpu import models, telemetry
from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.mixers import mamba2 as A
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import attention_pallas, causal_conv

TOY = dict(pattern="MEMEM*EME", d_model=32, n_heads=4, n_kv_heads=2,
           head_dim=8, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
           ssm_state=16, ssm_chunk=8, expert_width=24,
           shared_expert_width=16, n_experts=16, top_k=3,
           experts_held=(4, 12), seq_len=20)


# ---------------------------------------------------------------------------
# the Mamba-2 mixer and its norm
# ---------------------------------------------------------------------------

def test_the_grouped_gated_norm_is_its_definition():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(ks[0], (2, 5, 24), jnp.float64)
    z = jax.random.normal(ks[1], (2, 5, 24), jnp.float64)
    w = jax.random.normal(ks[2], (24,), jnp.float64)
    got = np.asarray(A.gated_group_norm(y, z, w, 3, 1e-5))
    u = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    for g in range(3):          # each run of 8 channels by its own RMS
        part = u[..., 8 * g:8 * g + 8]
        want = part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got[..., 8 * g:8 * g + 8],
                                   want * np.asarray(w)[8 * g:8 * g + 8],
                                   rtol=1e-12)
    # one group is the plain gated RMS norm; the reference's is the same
    np.testing.assert_allclose(
        A.gated_group_norm(y[0], z[0], w, 3, 1e-5),
        ref.gated_group_norm(y[0], z[0], w, 3, 1e-5), rtol=1e-12)


MODEL = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
         "ssm_state_size": 16, "norm_eps": 1e-5}


@pytest.mark.parametrize("t,chunk", [(37, 8), (16, 128)],
                         ids=["ragged", "one-chunk"])
def test_the_mixer_is_the_references(t, chunk):
    """Output and every parameter's gradient, float64, nonzero bias."""
    layer = L.Mamba2Mixer(n_out=32, heads=4, head_dim=8, groups=2, state=16,
                          chunk=chunk)
    p = layer.init(jax.random.PRNGKey(1), I.RecurrentType(32, t),
                   jnp.float64)
    assert set(p) == {"W_in", "conv_w", "conv_b", "A_log", "D", "dt_bias",
                      "norm_w", "W_out"}
    assert p["W_in"].shape == (32, 2 * 32 + 2 * 32 + 4)
    np.testing.assert_allclose(np.exp(p["A_log"]), [1, 2, 3, 4])
    dt = jax.nn.softplus(p["dt_bias"])      # inside [dt_min, dt_max]
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1001
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    p = {**p, "conv_b": 0.3 * jax.random.normal(ks[0], p["conv_b"].shape,
                                                jnp.float64),
         "D": jax.random.normal(ks[1], p["D"].shape, jnp.float64)}
    x = jax.random.normal(ks[2], (2, t, 32), jnp.float64)

    def as_ref(p):
        return {"w_in": p["W_in"], "conv_w": p["conv_w"],
                "conv_b": p["conv_b"], "a_log": p["A_log"],
                "d_skip": p["D"], "dt_bias": p["dt_bias"],
                "g_y": p["norm_w"], "w_out": p["W_out"]}

    def want(p, x):
        return jax.vmap(lambda u: ref.mamba2(u, as_ref(p), MODEL, "f32"))(x)

    got, pull = jax.vjp(lambda p, x: layer.apply(p, {}, x)[0], p, x)
    exp, pull_ref = jax.vjp(want, p, x)
    np.testing.assert_allclose(got, exp, rtol=1e-8, atol=1e-10)
    cot = jax.random.normal(jax.random.PRNGKey(3), got.shape, jnp.float64)
    (gp, gx), (wp, wx) = pull(cot), pull_ref(cot)
    np.testing.assert_allclose(gx, wx, rtol=1e-7, atol=1e-9)
    for name in p:
        np.testing.assert_allclose(gp[name], wp[name], rtol=1e-7,
                                   atol=1e-9, err_msg=name)


# ---------------------------------------------------------------------------
# a block with one half absent
# ---------------------------------------------------------------------------

_MAMBA = L.Mamba2Mixer(n_out=32, heads=4, head_dim=8, groups=2, state=16,
                       chunk=8, conv_kernel=4)
_ATTENTION = L.MultiHeadAttention(n_out=32, n_heads=4, causal=True,
                                  bias=False, head_dim=8, n_kv_heads=2)


def _block(**kw):
    return L.TransformerBlock(**{
        "n_out": 32, "mixer": _ATTENTION, "norm": "rms", "bias": False,
        "activation": "relu2", **kw})


@pytest.mark.parametrize("kw,keys,scopes,absent", [
    ({"mixer": _MAMBA, "ffn": "none"}, {"ln1", "ssm"},
     {"attn", "ssm", "ssm_conv", "ssd_core"}, {"mlp", "moe"}),
    ({"mixer": _ATTENTION, "ffn": "none"}, {"ln1", "mha"}, {"attn"},
     {"mlp", "moe", "ssm"}),
    ({"mixer": None, "ffn": "moe", "ffn_width": 24, "n_experts": 16,
      "top_k": 3, "experts_held": (4, 12), "expert_gated": False,
      "shared_expert_width": 16, "shared_expert_gate": False},
     {"ln2", "moe_router", "moe_Wu", "moe_Wd", "moe_shared_Wu",
      "moe_shared_Wd"},
     {"mlp", "moe", "moe_route", "moe_experts", "moe_shared"},
     {"attn", "ssm"}),
    ({"mixer": None, "ffn": "gated", "ffn_width": 24},
     {"ln2", "mlp_Wg", "mlp_Wu", "mlp_Wd"}, {"mlp"}, {"attn", "moe"})],
    ids=["mamba2-alone", "attention-alone", "mixture-alone", "ffn-alone"])
def test_a_block_with_one_half_absent(kw, keys, scopes, absent):
    """No parameters and no scopes for the half that is not there, and
    the result is the input plus the one part of its one norm."""
    block = _block(**kw)
    it = I.RecurrentType(32, 12)
    p = block.init(jax.random.PRNGKey(0), it, jnp.float32)
    state = block.init_state(it)
    assert set(p) == keys
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.float32)
    text = jax.jit(lambda p, x: block.apply(p, state, x)[0]).lower(
        p, x).as_text(debug_info=True)
    seen = set(re.findall(r"[A-Za-z0-9_]+", " ".join(
        re.findall(r'loc\("([^"]+)"', text))))
    assert scopes <= seen and not absent & seen, (scopes - seen,
                                                  absent & seen)
    y, _ = block.apply(p, state, x)
    norm, mixer = block._norm(), block.mixer
    part_in, _ = norm.apply(p["ln1" if mixer is not None else "ln2"], {}, x)
    if mixer is not None:
        part, _ = mixer.apply(p[mixer.param_key], {}, part_in)
    elif kw["ffn"] == "moe":
        part, _ = block._moe(p, state, part_in.reshape(24, 32))
    else:
        part = block._ffn(p, part_in.reshape(24, 32))
    np.testing.assert_allclose(y, x + part.reshape(x.shape), rtol=1e-6,
                               atol=1e-6)


def test_a_block_with_neither_half_and_unknown_kinds_are_refused():
    it = I.RecurrentType(32, 12)
    for kw, match in (({"mixer": None, "ffn": "none"}, "or both"),
                      ({"mixer": "mamba2", "ffn": "none"}, "MIGRATION.md"),
                      ({"mixer": None, "ffn": "dense"}, "ffn is"),
                      ({"mixer": None, "ffn": "gated", "sandwich": True},
                       "whole block")):
        with pytest.raises(ValueError, match=match):
            _block(**kw).init(jax.random.PRNGKey(0), it, jnp.float32)


def test_attention_without_positions_over_two_key_value_heads():
    """`rope_theta=None` at `n_kv_heads=2`: plain causal softmax attention,
    query head j reading key/value head j // 2, and a permutation of the
    EARLIER positions leaves the last position's result as it was."""
    layer = L.MultiHeadAttention(n_out=32, n_heads=4, causal=True,
                                 bias=False, head_dim=8, n_kv_heads=2)
    t = 10
    p = layer.init(jax.random.PRNGKey(0), I.RecurrentType(32, t),
                   jnp.float64)
    assert set(p) == {"Wq", "Wkv", "Wo"}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 32), jnp.float64)
    y, _ = layer.apply(p, {}, x)
    want = ref.attention(
        x[0], {"w_q": p["Wq"], "w_k": p["Wkv"][:, :16],
               "w_v": p["Wkv"][:, 16:], "w_o": p["Wo"]},
        {"n_head": 4, "n_kv_head": 2, "head_dim": 8}, "f32")
    # the reference's scale is a float32 constant
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-7)
    perm = jnp.concatenate([jnp.arange(t - 1)[::-1], jnp.array([t - 1])])
    y_perm, _ = layer.apply(p, {}, x[:, perm])
    np.testing.assert_allclose(y_perm[0, -1], y[0, -1], rtol=1e-9)


# ---------------------------------------------------------------------------
# the convolution's bias
# ---------------------------------------------------------------------------

def _conv_definition(p, w, bias, c, activation):
    x = p[..., :c]
    t, taps = x.shape[1], w.shape[1]
    y = sum(jnp.pad(x, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
            * w[:, j] for j in range(taps))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y) if activation else y


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("activation", [True, False], ids=["silu", "bare"])
def test_the_convolutions_bias_is_added_before_the_activation(
        monkeypatch, path, activation):
    """Result, the projection's, the taps' and the bias's gradients,
    against the definition; the kernels over five row blocks, the result
    in three pieces, columns passing by."""
    c, behind, t, split = 384, 128, 72, (128, 128, 128)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    p = jax.random.normal(ks[0], (2, t, c + behind), jnp.float32)
    w = 0.5 * jax.random.normal(ks[1], (c, 4), jnp.float32)
    bias = jax.random.normal(ks[2], (c,), jnp.float32)
    cot = jax.random.normal(ks[3], (2, t, c), jnp.float32)
    if path == "kernels":
        monkeypatch.setattr(causal_conv, "_VMEM",
                            2 * 16 * 4 * (c + 2 * (c + behind)))
        assert causal_conv._rows(t, c + behind, c, jnp.float32) == 16

    def op(p, w, bias):
        if path == "kernels":
            y, rest = causal_conv.causal_conv_kernels(
                p, w, bias, activation=activation, split=split,
                interpret=True)
        else:
            y, rest = causal_conv.causal_conv(
                p, w, bias, activation=activation, split=split)
        assert [piece.shape[-1] for piece in y] == list(split)
        return jnp.sum(jnp.concatenate(y, -1) * cot) + jnp.sum(rest ** 2)

    def want(p, w, bias):
        return (jnp.sum(_conv_definition(p, w, bias, c, activation) * cot)
                + jnp.sum(p[..., c:] ** 2))

    got = jax.value_and_grad(op, (0, 1, 2))(p, w, bias)
    exp = jax.value_and_grad(want, (0, 1, 2))(p, w, bias)
    np.testing.assert_allclose(got[0], exp[0], rtol=1e-5)
    for a, b, name in zip(got[1], exp[1], ("p", "w", "bias")):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.abs(a - b).max()) <= 3e-5 * max(
            1.0, float(jnp.abs(b).max())), name


def test_without_a_bias_the_op_gives_what_it_gave_before(monkeypatch):
    """Bit for bit on the plain path (the expression the op was before the
    bias); the kernels take the same blocks whether a bias rides the taps
    or not, and a zero bias changes no bit of their result."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    p = jax.random.normal(ks[0], (2, 40, 256 + 128), jnp.float32)
    w = 0.5 * jax.random.normal(ks[1], (256, 4), jnp.float32)
    before = jax.nn.silu(causal_conv.causal_taps(p[..., :256], w))
    y, rest = causal_conv.causal_conv(p, w, activation=True)
    np.testing.assert_array_equal(y, before)
    np.testing.assert_array_equal(rest, p[..., 256:])
    k_none, _ = causal_conv.causal_conv_kernels(p, w, activation=True,
                                                interpret=True)
    k_zero, _ = causal_conv.causal_conv_kernels(
        p, w, jnp.zeros((256,)), activation=True, interpret=True)
    np.testing.assert_array_equal(k_none, k_zero)
    np.testing.assert_allclose(k_none, before, rtol=2e-5, atol=2e-6)
    # the dispatch's decision does not read the bias
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    assert causal_conv.resolve_causal_conv(
        (1, 4096, 6144), (6144, 4), jnp.float32, False, False,
        (4096, 1024, 1024)) is True


def test_the_cells_mixer_takes_the_kernels_for_its_convolution(monkeypatch):
    """The Mamba-2 mixer at the cell's widths, length and dtypes lowers for
    the TPU with one convolution kernel a pass under `ssm_conv`, one kernel
    of the recurrence a pass under `ssd_core` (`ssd_fwd` outside
    `transpose(`, `ssd_bwd` inside it), and no `pad` left beside the
    kernels."""
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    layer = L.Mamba2Mixer(n_out=2688)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), I.RecurrentType(2688, 4096), jnp.float32))
    x = jax.ShapeDtypeStruct((1, 4096, 2688), jnp.float32)

    def loss(params, x):
        return jnp.sum(layer.apply(params, {}, x)[0])
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss)).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = re.findall(r'kernel_name = "([a-z_]+)"', text)
    assert sorted(calls) == ["causal_conv_bwd", "causal_conv_fwd", "ssd_bwd",
                             "ssd_fwd"], calls
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("ssm_conv", "ssd_core"):
        assert [p for p in paths if f"jvp(ssm)/{scope}" in p
                and "_run_fwd" in p and "transpose(" not in p], scope
        assert [p for p in paths if f"transpose(jvp(ssm))/{scope}" in p
                and "_run_bwd" in p], scope
        assert not [p for p in paths if f"jvp(ssm)/{scope}" in p
                    and "_run_bwd" in p and "transpose(" not in p], scope
    assert not [p for p in paths if "ssm_conv" in p and "/pad" in p]


def test_the_scans_kernels_are_named_inside_ssd_core(monkeypatch):
    """The recurrence's two kernels in a compiled train step (a one-layer
    `state_space_moe_lm` at lane-aligned widths, the dispatch's answer
    forced off the chip): `ssd_fwd` under `ssm` and `ssd_core` outside
    `transpose(`, `ssd_bwd` under both inside it, as the benchmark's scope
    matcher reads `ssm_ms.tokens`, `ssd_core_ms.tokens` and
    `step_bwd_ms.tokens`: a backward kernel outside its scope would leave
    `ssd_core_ms` reading the forward alone. Read from the compiled step's
    `op_name`s, as `test_step_scopes.py` reads `gdn_fwd` and `gdn_bwd`."""
    from benchmark.readers import trace_scope_ms
    from deeplearning4j_tpu.ops import ssd
    monkeypatch.setattr(ssd, "resolve_ssd", lambda *a: ssd._kernels)
    net = MultiLayerNetwork(models.state_space_moe_lm(
        16, pattern="M", d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
        ssm_heads=2, ssm_head_dim=64, ssm_groups=1, ssm_state=128,
        ssm_chunk=128, expert_width=8, shared_expert_width=8, n_experts=4,
        top_k=2, seq_len=128))
    net.init()
    x = np.arange(128, dtype=np.int32).reshape(1, 128) % 16
    text = net.make_train_step(donate=False).lower(
        net.params, net.state, net.opt_state, x, np.roll(x, -1, axis=1), 0,
        jax.random.PRNGKey(0)).compile().as_text()
    paths = set(re.findall(r'op_name="(jit\(train_step[^"]+)"', text))
    for kernel, backward in (("ssd_fwd", False), ("ssd_bwd", True)):
        named = [p for p in paths if re.search(
            r"(?:^|[/(])" + kernel + r"(?:$|[/)])", p)]
        assert named, kernel
        for scope in ("ssm", "ssd_core", r"L01\.TransformerBlock"):
            match = trace_scope_ms.matcher(
                {"scope": scope, "backward": backward})
            assert all(match(p) for p in named), (kernel, scope, named[:3])


# ---------------------------------------------------------------------------
# ungated experts, the shares
# ---------------------------------------------------------------------------

D, F, FS, E, K, N = 16, 24, 20, 128, 6, 96
ROUTING = {"num_experts_per_tok": K, "experts_held": (0, E),
           "routed_scaling_factor": 2.5}


@pytest.fixture(scope="module")
def layer():
    """An uncut 128-wide layer's float32 weights with its shared expert, a
    correction bias and token rows."""
    k = jax.random.split(jax.random.PRNGKey(7), 7)

    def nrm(key, scale, *shape):
        return scale * jax.random.normal(key, shape, jnp.float32)

    return {"u": nrm(k[0], 1.0, N, D), "bias": nrm(k[6], 0.3, E),
            "p": {"w_r": nrm(k[1], 0.5, D, E),
                  "e_up": nrm(k[2], 0.2, E, D, F),
                  "e_down": nrm(k[3], 0.2, E, F, D),
                  "s_up": nrm(k[4], 0.2, D, FS),
                  "s_down": nrm(k[5], 0.2, FS, D)}}


def _share(p, first, end):
    return {**p, **{n: p[n][first:end] for n in ("e_up", "e_down")}}


def _system(u, p, bias, held):
    return moe.routed_experts(
        u, p["w_r"], None, p["e_up"], p["e_down"], bias, top_k=K, held=held,
        scale=2.5, act=activations.get("relu2"), score="sigmoid")


def test_relu2_is_the_square_of_relu():
    x = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    np.testing.assert_array_equal(activations.get("relu2")(x),
                                  jnp.asarray([0.0, 0.0, 0.25, 9.0]))
    np.testing.assert_array_equal(
        jax.grad(lambda x: activations.get("relu2")(x).sum())(x),
        jnp.asarray([0.0, 0.0, 1.0, 6.0]))


def test_ungated_experts_are_a_loop_over_experts(layer):
    """`w_gate=None`: `E_j(u) = relu(u W_up_j)^2 W_down_j`, weighted and
    summed over the selected (here each token's experts gathered, and the
    reference's loop over experts); the result, the counts and the
    gradients of the router, both stacks and the tokens."""
    u, p, bias = layer["u"], layer["p"], layer["bias"]
    s = jax.nn.sigmoid(jnp.matmul(u, p["w_r"], precision="highest"))
    sel = np.argsort(-np.asarray(s + bias), -1)[:, :K]

    def loop(u, p):
        # each token through its own K experts, gathered: no sorting, no
        # grouped product
        s = jax.nn.sigmoid(jnp.matmul(u, p["w_r"], precision="highest"))
        w = jnp.take_along_axis(s, sel, -1)
        w = w / (w.sum(-1, keepdims=True) + moe.ROUTER_EPS) * 2.5
        h = jnp.square(jax.nn.relu(jnp.einsum(
            "nd,nkdf->nkf", u, p["e_up"][sel], precision="highest")))
        return jnp.einsum("nkf,nkfd,nk->nd", h, p["e_down"][sel], w,
                          precision="highest")

    y, load, away = _system(u, p, bias, (0, E))
    np.testing.assert_allclose(y, loop(u, p), rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(load, np.bincount(sel.ravel(),
                                                    minlength=E))
    assert float(away[0]) == 0
    want, want_load, _ = ref.experts(u, p, bias, ROUTING, "f32")
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(load, want_load)
    r = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    got = jax.grad(lambda u, p: jnp.sum(_system(u, p, bias, (0, E))[0] * r),
                   (0, 1))(u, p)
    exp = jax.grad(lambda u, p: jnp.sum(loop(u, p) * r), (0, 1))(u, p)
    np.testing.assert_allclose(got[0], exp[0], rtol=5e-4, atol=1e-5)
    for name in ("w_r", "e_up", "e_down"):
        np.testing.assert_allclose(got[1][name], exp[1][name], rtol=5e-4,
                                   atol=1e-5, err_msg=name)


def test_the_shares_add_up_to_the_uncut_layer(layer):
    """16 shares of 8 experts (the cell's deployment) of the 128-wide
    router, the shared expert counted once (every chip computes it alike),
    sum to the uncut reference's whole layer."""
    u, p, bias = layer["u"], layer["p"], layer["bias"]
    routed, load, away = ref.experts(u, p, bias, ROUTING, "f32")
    assert float(away[0]) == 0 and float(load.sum()) == N * K
    whole = routed + ref.shared_expert(u, p, "f32")
    parts, rows = [], 0.0
    for first in range(0, E, 8):
        held = (first, first + 8)
        y, here, elsewhere = _system(u, _share(p, *held), bias, held)
        want, want_here, _ = ref.experts(u, _share(p, *held), bias, ROUTING,
                                         "f32", held=held)
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(here, want_here)
        assert float(here.sum() + elsewhere[0]) == N * K   # none dropped
        parts.append(y)
        rows += float(here.sum())
    assert len(parts) == 16 and rows == N * K
    np.testing.assert_allclose(sum(parts) + ref.shared_expert(u, p, "f32"),
                               whole, rtol=2e-5, atol=2e-6)
    # the block's shared expert is the reference's: ungated, no gate on it
    block = _block(mixer=None, ffn="moe", ffn_width=F, n_experts=E,
                   top_k=K, routed_scale=2.5, expert_gated=False,
                   shared_expert_width=FS, shared_expert_gate=False,
                   n_out=D)
    params = {"moe_router": p["w_r"], "moe_Wu": p["e_up"],
              "moe_Wd": p["e_down"], "moe_shared_Wu": p["s_up"],
              "moe_shared_Wd": p["s_down"]}
    state = {"expert_bias": bias, "moe_load": jnp.zeros((E,)),
             "moe_elsewhere": jnp.zeros((1,))}
    y, new = block._moe(params, state, u)
    np.testing.assert_allclose(y, whole, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(new["moe_load"], load)


# ---------------------------------------------------------------------------
# the factory
# ---------------------------------------------------------------------------

def test_the_factory_reads_the_pattern():
    conf = models.state_space_moe_lm(64, **TOY)
    blocks = conf.layers[1:-2]
    kinds = [(type(b.mixer).__name__, b.ffn) for b in blocks]
    assert kinds == [
        {"M": ("Mamba2Mixer", "none"), "*": ("MultiHeadAttention", "none"),
         "E": ("NoneType", "moe")}[c] for c in "MEMEM*EME"]
    assert blocks[5].mixer.rope_theta is None and not blocks[5].mixer.qk_norm
    assert all(not b.expert_gated and not b.shared_expert_gate
               and b.routed_scale == 2.5 and b.router == "sigmoid"
               for b in blocks if b.ffn == "moe")
    assert blocks[0].mixer.out_scale == pytest.approx(1 / 3)
    with pytest.raises(ValueError, match="pattern is made of"):
        models.state_space_moe_lm(64, **{**TOY, "pattern": "MEX"})
    # the defaults are the published widths and the 52-layer pattern
    full = models.state_space_moe_lm(131072)
    kinds = [(type(b.mixer).__name__, b.ffn) for b in full.layers[1:-2]]
    assert len(kinds) == 52
    assert (kinds.count(("Mamba2Mixer", "none")),
            kinds.count(("NoneType", "moe")),
            kinds.count(("MultiHeadAttention", "none"))) == (23, 23, 6)
    e = full.layers[2]
    assert (e.n_out, e.ffn_width, e.shared_expert_width, e.n_experts,
            e.top_k) == (2688, 1856, 3712, 128, 6)
    m = full.layers[1].mixer
    assert (m.heads, m.head_dim, m.groups, m.state, m.chunk,
            m.conv_kernel) == (64, 64, 8, 128, 128, 4)


def test_serde_and_a_fit():
    conf = models.state_space_moe_lm(64, **TOY)
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    net = MultiLayerNetwork(again)
    net.init()
    # the four mixtures are found by the routing's counters though they
    # have no mixer: state on layers 2, 4, 7, 9 and nowhere else
    assert [i for i, s in enumerate(net.state) if s] == [2, 4, 7, 9]
    rng = np.random.RandomState(0)
    x = rng.randint(0, 64, (2, 20)).astype(np.int32)
    y = rng.randint(0, 64, (2, 20)).astype(np.int32)
    first = float(net.score(x, y))
    for _ in range(3):
        net.fit(x, y)
    assert float(net.score(x, y)) < first
    for i in (2, 4, 7, 9):
        s = net.state[i]
        assert float(s["moe_load"].sum() + s["moe_elsewhere"][0]) == 2 * 20 * 3
        np.testing.assert_array_equal(s["expert_bias"], 0)
    # one sampled step: all four mixtures' counts reach the registry
    telemetry.reset()
    telemetry.enable()
    try:
        telemetry.note_step_state(net.state)
        snap = telemetry.get_registry().snapshot()
    finally:
        telemetry.disable()
        telemetry.reset()
    total = lambda name: sum(s["value"] for s in snap[name]["series"])
    assert total("moe_assignments_sampled_total") == 4 * 2 * 20 * 3
    assert 0 < total("moe_rows_here_sampled_total") < 4 * 2 * 20 * 3
