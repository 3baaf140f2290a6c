"""The hybrid linear/softmax-attention mixture-of-experts vocabulary (ISSUE
34) at toy size on the CPU: the gated delta rule's chunkwise form against
the token-by-token recurrence (outputs and every gradient, a length that
is no multiple of the chunk, decays near 0 and near 1), the flash kernels
at head width 256 against the XLA path (forward, the fused and the split
backward), partial rotary, the output gate and the zero-centred norm
against their equations, the softmax router's weights, the gated shared
expert, the shares of a 512-wide router adding up to the uncut layer, the
defaults leaving the older blocks as they were, serde, and a fit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import attention as A
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import attention_pallas, gated_delta


# ---------------------------------------------------------------------------
# the gated delta rule
# ---------------------------------------------------------------------------

def _rule_inputs(t, dtype, decay_scales, seed=0, b=2, hk=2, dk=8, dv=16):
    hv = len(decay_scales)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, t, hk, dk), dtype)
    k = jax.random.normal(ks[1], (b, t, hk, dk), dtype)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, dv), dtype)
    g = -jnp.abs(jax.random.normal(ks[3], (b, t, hv), dtype)) * jnp.asarray(
        decay_scales, dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv), dtype))
    cot = jax.random.normal(ks[5], (b, t, hv, dv), dtype)
    return (q, k, v, g, beta), cot


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence, a sequence at a time."""
    r = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    return jax.vmap(ref.delta_rule)(q, k, v, g, beta)


# a value head each: decay e^-20 a token (the state all but forgotten),
# 0.999 (all but kept), and two in between
DECAYS = (20.0, 1e-3, 1.0, 0.1)


@pytest.mark.parametrize("t", [150, 64, 7], ids=["ragged", "one-chunk",
                                                   "short"])
def test_the_chunkwise_form_is_the_recurrence(t):
    """float64: the two forms are the same function, to rounding."""
    args, cot = _rule_inputs(t, jnp.float64, DECAYS)
    want, vjp = jax.vjp(_token_by_token, *args)
    got, got_vjp = jax.vjp(gated_delta.gated_delta_rule, *args)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)
    for a, b, name in zip(got_vjp(cot), vjp(cot),
                          ("dq", "dk", "dv", "dg", "dbeta")):
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-11,
                                   err_msg=name)


def test_the_chunkwise_form_in_float32_stays_near_the_recurrence():
    """float32, as the step computes it (the f32 policy: no bfloat16
    operands), every gradient finite at decays that underflow."""
    args, cot = _rule_inputs(200, jnp.float32, DECAYS, seed=1)
    want, vjp = jax.vjp(_token_by_token, *args)
    got, got_vjp = jax.vjp(gated_delta.gated_delta_rule, *args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(got_vjp(cot), vjp(cot)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_the_unit_lower_inverse_is_exact_for_a_nilpotent_matrix():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (3, 64, 64),
                                   jnp.float64), -1) * 0.3
    inv = gated_delta._unit_lower_inverse(a)
    np.testing.assert_allclose(inv @ (jnp.eye(64) + a),
                               jnp.broadcast_to(jnp.eye(64), a.shape),
                               atol=1e-9)


def test_a_padded_position_neither_writes_nor_decays():
    """T = 70 is cut into two chunks, the second padded by 58: the first
    70 outputs are those of the same inputs run to T = 128."""
    args, _ = _rule_inputs(128, jnp.float64, DECAYS, seed=3)
    whole = gated_delta.gated_delta_rule(*args)
    cut = gated_delta.gated_delta_rule(*(x[:, :70] for x in args))
    np.testing.assert_allclose(cut, whole[:, :70], rtol=1e-10, atol=1e-13)


def test_the_mixer_layer_is_the_references():
    """`GatedDeltaNet` on the reference's seeded weights: projections,
    the four taps with SiLU, decays, norms, gate and out-projection."""
    model = {"n_embd": 32, "linear_num_key_heads": 2,
             "linear_num_value_heads": 4, "linear_key_head_dim": 8,
             "linear_value_head_dim": 16, "norm_eps": 1e-6}
    layer = L.GatedDeltaNet(n_out=32, k_heads=2, v_heads=4, head_dim=8,
                            v_head_dim=16, conv_kernel=4)
    params = layer.init(jax.random.PRNGKey(0), I.RecurrentType(32, 50),
                        jnp.float32)
    assert {k: v.shape for k, v in params.items()} == {
        "W_qkvz": (32, 2 * 16 + 2 * 64), "W_ba": (32, 8),
        "conv_w": (2 * 16 + 64, 4), "A_log": (4,), "dt_bias": (4,),
        "norm_w": (16,), "W_out": (64, 32)}
    assert float(params["A_log"].max()) <= np.log(16.0)
    p = {"w_qkvz": params["W_qkvz"], "w_ba": params["W_ba"],
         "conv_w": params["conv_w"], "a_log": params["A_log"],
         "dt_bias": params["dt_bias"], "g_o": params["norm_w"],
         "w_out": params["W_out"]}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32), jnp.float32)
    got, _ = layer.apply(params, {}, x)
    want = jnp.stack([ref.gated_delta(x[i], p, model, "f32")
                      for i in range(2)])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    # causal: a later token changes no earlier output
    x2 = x.at[:, 30].add(1.0)
    got2, _ = layer.apply(params, {}, x2)
    np.testing.assert_array_equal(np.asarray(got2[:, :30]),
                                  np.asarray(got[:, :30]))
    assert float(jnp.abs(got2[:, 30:] - got[:, 30:]).max()) > 0


# ---------------------------------------------------------------------------
# the flash kernels at head width 256
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, scale):
    s = jnp.einsum("hqd,hkd->hqk", q, k) * scale
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v)


@pytest.fixture(scope="module")
def wide_heads():
    rs = np.random.RandomState(256)
    return tuple(jnp.asarray(rs.randn(2, 300, 256).astype(np.float32) * 0.5)
                 for _ in range(4))


def test_flash_forward_at_width_256_matches_the_xla_path(wide_heads):
    q, k, v, _ = wide_heads
    out, _ = attention_pallas._run_fwd(q, k, v, None, 2, True, 1 / 16, 256,
                                       256, True)
    np.testing.assert_allclose(out, _naive_attention(q, k, v, 1 / 16),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("form", ["fused", "split"])
def test_flash_backward_at_width_256_matches_the_xla_path(wide_heads, form):
    q, k, v, g = wide_heads
    out, lse = attention_pallas._run_fwd(q, k, v, None, 2, True, 1 / 16,
                                         256, 256, True)
    want = jax.vjp(lambda q, k, v: _naive_attention(q, k, v, 1 / 16),
                   q, k, v)[1](g)
    got = attention_pallas._run_bwd_local(
        q, k, v, out, lse, g, None, None, 2, True, 1 / 16, 256, 256, True,
        form)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=2e-5, err_msg=name)


def test_the_dispatch_takes_width_256_and_its_backward_is_one_kernel(
        monkeypatch):
    """At [1, 4096, 16, 256] `resolve_attention` hands out the kernel's
    blocks (it sent any head wider than 128 to XLA before ISSUE 34), and at
    width 512 the call still goes to XLA. From ISSUE 46 a head's dq at that
    width is within the budget (19.06 MiB by the count, of which Mosaic is
    asked: the default limit gives 16), at T 2,048 too; T 8,192 at width
    256 is past it and splits."""
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    shape = (1, 4096, 16, 256)
    assert attention_pallas.resolve_attention(
        shape, shape, None, jnp.float32) == (512, 512)
    wide = (1, 4096, 16, 512)
    assert attention_pallas.resolve_attention(
        wide, wide, None, jnp.float32) is None
    budget = attention_pallas._VMEM_BUDGET

    def fused(t, d):    # float32 inputs on the chip: bfloat16 operands
        return attention_pallas.bwd_vmem_bytes("fused", t, d, 512, 512, 2, 4)
    assert fused(4096, 256) == int(19.0625 * 2 ** 20) <= budget
    assert fused(4096, 256) + attention_pallas._VMEM_MARGIN \
        > attention_pallas._VMEM_DEFAULT
    assert fused(2048, 256) == int(13.0625 * 2 ** 20)
    assert fused(8192, 256) > budget
    assert attention_pallas.bwd_vmem_bytes(
        "split", 8192, 256, 512, 512, 2, 4) <= attention_pallas._VMEM_DEFAULT
    # the widths the benchmark's other cells run keep the form they had,
    # and Mosaic is asked for nothing there
    for t, d in ((1024, 64), (2048, 128), (8192, 64), (4096, 128)):
        assert fused(t, d) + attention_pallas._VMEM_MARGIN \
            <= attention_pallas._VMEM_DEFAULT


# ---------------------------------------------------------------------------
# partial rotary, the output gate, the zero-centred norm
# ---------------------------------------------------------------------------

def test_partial_rotary_turns_the_first_dimensions_only():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 3, 16), jnp.float32)
    got = A.rope(x, 1e7, rotary_dim=4)
    np.testing.assert_array_equal(np.asarray(got[..., 4:]),
                                  np.asarray(x[..., 4:]))
    # pair (i, i + 2) of the first four turns by t * theta**(-2i/4)
    t = np.arange(9, dtype=np.float64)[None, :, None]
    for i in range(2):
        ang = t * 1e7 ** (-2.0 * i / 4)
        a, b = np.asarray(x[..., i], np.float64), np.asarray(
            x[..., i + 2], np.float64)
        np.testing.assert_allclose(got[..., i], a * np.cos(ang)
                                   - b * np.sin(ang), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[..., i + 2], b * np.cos(ang)
                                   + a * np.sin(ang), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(A.rope(x, 1e7, rotary_dim=16)),
                                  np.asarray(A.rope(x, 1e7)))
    # the reference's own rotation agrees
    np.testing.assert_allclose(got[0], ref._rope(x[0], 1e7, 4), rtol=1e-5,
                               atol=1e-6)


def test_the_zero_centred_norm_is_one_plus_gamma():
    norm = L.RMSNorm(eps=1e-6, zero_centered=True)
    p = norm.init(jax.random.PRNGKey(0), I.RecurrentType(8, 3), jnp.float32)
    assert float(jnp.abs(p["gamma"]).max()) == 0.0
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 8), jnp.float32)
    g = jnp.linspace(-0.5, 0.5, 8)
    got, _ = norm.apply({"gamma": g}, {}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + g)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain, _ = L.RMSNorm(eps=1e-6).apply({"gamma": 1 + g}, {}, x)
    np.testing.assert_allclose(got, plain, rtol=1e-6)


def test_gated_attention_is_the_references():
    """The doubled query projection split a head into q and gate, the
    zero-centred q/k norms, rotary on a quarter of the head, 4 query heads
    over 2 key/value heads, the result times sigmoid(gate)."""
    model = {"n_head": 4, "n_kv_head": 2, "head_dim": 16, "norm_eps": 1e-6,
             "rope_theta": 1e7, "partial_rotary_factor": 0.25}
    mha = L.MultiHeadAttention(
        n_out=32, n_heads=4, causal=True, bias=False, rope_theta=1e7,
        head_dim=16, n_kv_heads=2, qk_norm=True, qk_norm_zero_centered=True,
        rotary_dim=4, gate=True)
    params = mha.init(jax.random.PRNGKey(0), I.RecurrentType(32, 24),
                      jnp.float32)
    assert params["Wq"].shape == (32, 4 * 2 * 16)
    assert float(jnp.abs(params["q_gamma"]).max()) == 0.0
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    params = {**params, "q_gamma": 0.1 * jax.random.normal(ks[0], (16,)),
              "k_gamma": 0.1 * jax.random.normal(ks[1], (16,))}
    p = {"w_q": params["Wq"], "w_k": params["Wkv"][:, :32],
         "w_v": params["Wkv"][:, 32:], "w_o": params["Wo"],
         "g_q": params["q_gamma"], "g_k": params["k_gamma"]}
    x = jax.random.normal(ks[2], (2, 24, 32), jnp.float32)
    got, _ = mha.apply(params, {}, x)
    want = jnp.stack([ref.attention(x[i], p, model, "f32")
                      for i in range(2)])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    with pytest.raises(ValueError, match="n_kv_heads"):
        L.MultiHeadAttention(n_out=32, n_heads=4, bias=False,
                             gate=True).init(
            jax.random.PRNGKey(0), I.RecurrentType(32, 8), jnp.float32)


# ---------------------------------------------------------------------------
# the softmax router, the shared expert, the shares
# ---------------------------------------------------------------------------

D, F, E, K, N = 16, 24, 512, 10, 96
MODEL = {"num_experts_per_tok": K, "experts_held": (0, E)}


@pytest.fixture(scope="module")
def layer():
    """An uncut 512-wide layer's float32 weights with its shared expert,
    and token rows."""
    k = jax.random.split(jax.random.PRNGKey(7), 9)

    def nrm(key, scale, *shape):
        return scale * jax.random.normal(key, shape, jnp.float32)

    return {"u": nrm(k[0], 1.0, N, D),
            "p": {"w_r": nrm(k[1], 0.5, D, E),
                  "e_w1": nrm(k[2], 0.2, E, D, F),
                  "e_w3": nrm(k[3], 0.2, E, D, F),
                  "e_w2": nrm(k[4], 0.2, E, F, D),
                  "s_w1": nrm(k[5], 0.2, D, F), "s_w3": nrm(k[6], 0.2, D, F),
                  "s_w2": nrm(k[7], 0.2, F, D), "w_sg": nrm(k[8], 0.5, D, 1)}}


def _share(p, first, end):
    return {**p, **{n: p[n][first:end] for n in ("e_w1", "e_w3", "e_w2")}}


def _system(u, p, held):
    return moe.routed_experts(
        u, p["w_r"], p["e_w1"], p["e_w3"], p["e_w2"], None, top_k=K,
        held=held, scale=1.0, act=jax.nn.silu, score="softmax")


def test_the_softmax_routers_weights(layer):
    u, p = layer["u"], layer["p"]
    sel, w = ref.route(u, p["w_r"], MODEL)
    probs = jax.nn.softmax(jnp.matmul(u, p["w_r"], precision="highest"), -1)
    assert sel.shape == w.shape == (N, K)
    # the ten largest of 512, weights renormalised over the ten alone
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1), np.sort(
        np.argsort(-np.asarray(probs), -1)[:, :K], -1))
    picked = jnp.take_along_axis(probs, sel, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    # the system's layer over all 512 and the router's gradient agree
    y, load, away = _system(u, p, (0, E))
    want, want_load, _ = ref.experts(u, p, MODEL, "f32")
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(load, want_load)
    assert float(away[0]) == 0 and float(load.sum()) == N * K
    r = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    f = lambda w_r: jnp.sum(_system(u, {**p, "w_r": w_r}, (0, E))[0] * r)
    f_ref = lambda w_r: jnp.sum(
        ref.experts(u, {**p, "w_r": w_r}, MODEL, "f32")[0] * r)
    np.testing.assert_allclose(jax.grad(f)(p["w_r"]),
                               jax.grad(f_ref)(p["w_r"]), rtol=5e-4,
                               atol=1e-6)


@pytest.mark.parametrize("n_held", [32, 16], ids=["16-chips", "32-chips"])
def test_the_shares_add_up_to_the_uncut_layer(layer, n_held):
    """16 shares of 32 experts (ISSUE 34's deployment) and 32 shares of 16
    (the cell's) of the 512-wide router, the shared expert counted once
    (every chip computes it alike), sum to the whole layer."""
    u, p = layer["u"], layer["p"]
    routed, load, away = ref.experts(u, p, MODEL, "f32")
    whole = routed + ref.shared_expert(u, p, "f32")
    parts, rows = [], 0.0
    for first in range(0, E, n_held):
        held = (first, first + n_held)
        y, here, elsewhere = _system(u, _share(p, *held), held)
        want, want_here, _ = ref.experts(u, _share(p, *held), MODEL, "f32",
                                         held=held)
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(here, want_here)
        assert float(here.sum() + elsewhere[0]) == N * K   # none dropped
        parts.append(y)
        rows += float(here.sum())
    assert len(parts) == E // n_held and rows == N * K
    np.testing.assert_allclose(sum(parts) + ref.shared_expert(u, p, "f32"),
                               whole, rtol=2e-5, atol=2e-6)


def test_the_grouped_products_row_tile_follows_the_rows_a_group_expects():
    from deeplearning4j_tpu.ops import grouped_matmul as gm
    # lfm2's layer (8192 x 4 over 64 experts) keeps the tile it was
    # measured at; qwen3next's (4096 x 10 over 512) takes the least
    assert gm._row_tile(32768, 32768 // 64) == 512
    assert gm._row_tile(40960, 40960 // 512) == 128
    assert gm._row_tile(40960, 300) == 512 and gm._row_tile(40960, 200) == 256
    assert gm._row_tile(96, 1) == 32          # whole tiles only
    # the tile changes the work, not the result
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x = jax.random.normal(ks[0], (512, 16), jnp.float32)
    w = jax.random.normal(ks[1], (3, 16, 8), jnp.float32)
    sizes = jnp.asarray([130, 0, 257], jnp.int32)
    small = gm.grouped_matmul(x, w, sizes, jnp.float32, 100)
    large = gm.grouped_matmul(x, w, sizes, jnp.float32)
    np.testing.assert_array_equal(np.asarray(small[:387]),
                                  np.asarray(large[:387]))


def _block(**kw):
    return L.TransformerBlock(**{**dict(
        n_out=D, mixer=L.MultiHeadAttention(n_out=D, n_heads=2, causal=True,
                                            bias=False),
        activation="silu", norm="rms", norm_eps=1e-6, bias=False, ffn="moe",
        ffn_width=F, n_experts=E, top_k=K, experts_held=(64, 96),
        router="softmax"), **kw})


def test_the_blocks_mixture_is_routed_plus_gated_shared(layer):
    """`TransformerBlock._moe`: the share's routed part plus
    `sigmoid(x w_sg) E_shared(x)`, the shared expert held whole whatever
    `experts_held` says, and no expert bias in the state."""
    u, p = layer["u"], _share(layer["p"], 64, 96)
    block = _block(shared_expert_width=F)
    state = block.init_state(I.RecurrentType(D, 8))
    assert set(state) == {"moe_load", "moe_elsewhere"}
    own = block.init(jax.random.PRNGKey(0), I.RecurrentType(D, 8),
                     jnp.float32)
    assert own["moe_shared_gate"].shape == (D, 1)
    assert own["moe_Wg"].shape == (32, D, F)
    assert own["moe_shared_Wg"].shape == (D, F)
    params = {"moe_router": p["w_r"], "moe_Wg": p["e_w1"],
              "moe_Wu": p["e_w3"], "moe_Wd": p["e_w2"],
              "moe_shared_Wg": p["s_w1"], "moe_shared_Wu": p["s_w3"],
              "moe_shared_Wd": p["s_w2"], "moe_shared_gate": p["w_sg"]}
    y, new_state = block._moe(params, state, u)
    routed, load, away = ref.experts(u, p, MODEL, "f32", held=(64, 96))
    gate = jax.nn.sigmoid(jnp.matmul(u, p["w_sg"], precision="highest"))
    shared = gate * ref._gated(u, p["s_w1"], p["s_w3"], p["s_w2"], "f32")
    np.testing.assert_allclose(ref.shared_expert(u, p, "f32"), shared,
                               rtol=1e-6)
    np.testing.assert_allclose(y, routed + shared, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(new_state["moe_load"], load)
    np.testing.assert_array_equal(new_state["moe_elsewhere"], away)
    # without the field the block has no shared expert and no such leaves
    bare = _block().init(jax.random.PRNGKey(0), I.RecurrentType(D, 8),
                         jnp.float32)
    assert not [k for k in bare if k.startswith("moe_shared")]
    with pytest.raises(ValueError, match="shared expert"):
        L.TransformerBlock(n_out=D, bias=False, ffn="gated",
                           shared_expert_width=F).init(
            jax.random.PRNGKey(0), I.RecurrentType(D, 8), jnp.float32)
    with pytest.raises(ValueError, match="router"):
        _block(router="tanh").init(jax.random.PRNGKey(0),
                                   I.RecurrentType(D, 8), jnp.float32)


# ---------------------------------------------------------------------------
# the block and the factory
# ---------------------------------------------------------------------------

TOY = dict(n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
           linear_k_heads=2, linear_v_heads=4, linear_k_head_dim=8,
           linear_v_head_dim=8, expert_width=16, shared_expert_width=16,
           n_experts=16, top_k=3, experts_held=(4, 12), seq_len=40)


def test_the_factory_builds_the_published_pattern():
    conf = models.gated_delta_moe_lm(64, **TOY)
    blocks = conf.layers[1:-2]
    assert [type(b.mixer).__name__ for b in blocks] == \
        ["GatedDeltaNet"] * 3 + ["MultiHeadAttention"]
    assert all(b.ffn == "moe" and b.router == "softmax"
               and b.shared_expert_width == 16 and b.norm_zero_centered
               for b in blocks)
    gated = blocks[3].mixer
    assert gated.gate and gated.rotary_dim == 4 \
        and gated.qk_norm_zero_centered
    assert conf.layers[-2].zero_centered
    full = models.gated_delta_moe_lm(151936)
    kinds = [type(b.mixer).__name__ for b in full.layers[1:-2]]
    assert len(kinds) == 48 and kinds.count("MultiHeadAttention") == 12
    assert all(k == "MultiHeadAttention" for k in kinds[3::4])
    assert full.layers[4].mixer.rotary_dim == 64 \
        and full.layers[4].mixer.head_dim == 256
    # LFM2's factory names the kinds it takes, and the delta rule's widths
    # are not among its arguments
    with pytest.raises(ValueError, match="full_attention"):
        models.hybrid_moe_lm(64, layer_types=("conv", "linear_attention"))
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back == conf


def test_the_older_blocks_are_as_they_were():
    """New fields default to the old arithmetic and the old trees."""
    block = L.TransformerBlock(
        n_out=16, mixer=L.MultiHeadAttention(n_out=16, n_heads=2, bias=False),
        norm="rms", bias=False, ffn="moe", ffn_width=8, n_experts=4, top_k=2)
    state = block.init_state(I.RecurrentType(16, 8))
    assert set(state) == {"expert_bias", "moe_load", "moe_elsewhere"}
    p = block.init(jax.random.PRNGKey(0), I.RecurrentType(16, 8),
                   jnp.float32)
    assert set(p) == {"ln1", "mha", "ln2", "moe_router", "moe_Wg", "moe_Wu",
                      "moe_Wd"}
    assert float(p["ln1"]["gamma"].min()) == 1.0
    assert set(p["mha"]) == {"Wqkv", "Wo"}
    with pytest.raises(ValueError, match="MIGRATION.md"):
        L.TransformerBlock(n_out=16, mixer="gated_delta").init(
            jax.random.PRNGKey(0), I.RecurrentType(16, 8), jnp.float32)


def test_a_fit_through_the_normal_path_learns():
    conf = models.gated_delta_moe_lm(64, **TOY)
    net = MultiLayerNetwork(conf)
    net.init()
    rs = np.random.RandomState(0)
    x = rs.randint(0, 64, (2, 40))
    y = np.roll(x, -1, axis=1)
    first = float(net.score(x, y))
    for _ in range(8):
        net.fit(x, y)
    assert float(net.score(x, y)) < first
    out = net.output(x)
    assert out.shape == (2, 40, 64)
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)
    load = [s["moe_load"] for s in net.state if "moe_load" in s]
    assert len(load) == 4 and all(l.shape == (8,) for l in load)
