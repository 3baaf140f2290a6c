"""The looped language model's parts (ISSUE 27): RMSNorm, rotary
positions, the one transformer block's new fields, `LoopedStack` (one set
of weights used `passes` times) and the exit-weighted output layer, at toy
size on the CPU."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from deeplearning4j_tpu import models, telemetry
from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import losses
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import attention as A
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import serde

D, H, HD, F, V, T, R = 64, 4, 16, 176, 256, 32, 3
IT = I.RecurrentType(D, T)


def _block(**kw):
    return L.TransformerBlock(
        n_out=D, mixer=L.MultiHeadAttention(
            n_out=D, n_heads=H, causal=True, bias=False, rope_theta=1e6,
            head_dim=HD),
        activation="silu", norm="rms", norm_eps=1e-6, sandwich=True,
        bias=False, ffn="gated", ffn_width=F, **kw)


def _net(**kw):
    net = MultiLayerNetwork(models.looped_lm(
        V, n_layers=2, d_model=D, n_heads=H, head_dim=HD, ffn_width=F,
        passes=R, seq_len=T, **kw))
    net.init()
    return net


def _tokens(seed=0, b=3):
    tok = np.random.RandomState(seed).randint(0, V, (b, T + 1))
    return tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)


def test_rmsnorm_is_its_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, D)) * 3.0
    g = jax.random.normal(jax.random.PRNGKey(1), (D,))
    y, _ = L.RMSNorm(eps=1e-6).apply({"gamma": g}, {}, x)
    want = np.asarray(x) / np.sqrt(
        np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6) * np.asarray(g)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6, atol=1e-6)
    assert set(L.RMSNorm().init(None, IT)) == {"gamma"}


def test_rope_scores_depend_only_on_the_distance():
    """The same query and key vector at every position: after the
    rotation q_i . k_j is a function of i - j alone, norms are kept and
    position 0 is not turned."""
    q = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(0), (HD,)),
                         (1, T, 1, HD))
    k = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(1), (HD,)),
                         (1, T, 1, HD))
    rq, rk = A.rope(q, 1e4)[0, :, 0], A.rope(k, 1e4)[0, :, 0]
    s = np.asarray(rq @ rk.T)
    for shift in (1, 5):
        np.testing.assert_allclose(s[shift:, shift:], s[:-shift, :-shift],
                                   rtol=1e-4, atol=1e-4)
    assert abs(s[3, 0] - s[0, 3]) > 1e-3  # and on its sign
    np.testing.assert_allclose(np.linalg.norm(rq, axis=-1),
                               np.linalg.norm(q[0, :, 0], axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(rq[0]), np.asarray(q[0, 0, 0]))


def _parent_block_init(key, n_out, mlp_ratio, dtype=jnp.float32):
    """`TransformerBlock.init` as it stood before ISSUE 27."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ka, kb = jax.random.split(k1)
    hidden = n_out * mlp_ratio
    ln = {"gamma": jnp.ones((n_out,), dtype), "beta": jnp.zeros((n_out,), dtype)}
    w = _init.init_weight
    return {
        "ln1": dict(ln), "ln2": dict(ln),
        "mha": {"Wqkv": w("xavier", ka, (n_out, 3 * n_out), n_out, 3 * n_out,
                          dtype),
                "bqkv": jnp.zeros((3 * n_out,), dtype),
                "Wo": w("xavier", kb, (n_out, n_out), n_out, n_out, dtype),
                "bo": jnp.zeros((n_out,), dtype)},
        "mlp_W1": w("xavier", k3, (n_out, hidden), n_out, hidden, dtype),
        "mlp_b1": jnp.zeros((hidden,), dtype),
        "mlp_W2": w("xavier", k4, (hidden, n_out), hidden, n_out, dtype),
        "mlp_b2": jnp.zeros((n_out,), dtype)}


def _parent_block_apply(params, x, n_heads):
    """`TransformerBlock.apply` (pre-norm, LayerNorm, biased fused QKV,
    GELU MLP) as it stood before ISSUE 27."""
    from deeplearning4j_tpu.nn import activations as _act

    def ln(p, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["gamma"] + p["beta"]

    b, t, f = x.shape
    h = ln(params["ln1"], x)
    qkv = matmul(h.reshape(b * t, -1), params["mha"]["Wqkv"]) \
        + params["mha"]["bqkv"]
    qkv = qkv.reshape(b, t, 3, n_heads, f // n_heads)
    attn = A.dot_product_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                                   causal=True)
    y = matmul(attn.reshape(b * t, f), params["mha"]["Wo"]) \
        + params["mha"]["bo"]
    x = x + y.reshape(b, t, f)
    h = ln(params["ln2"], x)
    m = _act.get("gelu")(matmul(h.reshape(b * t, f), params["mlp_W1"])
                         + params["mlp_b1"])
    m = matmul(m, params["mlp_W2"]) + params["mlp_b2"]
    return x + m.reshape(b, t, f)


def test_the_default_block_is_the_parents_bit_for_bit():
    block = L.TransformerBlock(n_out=32, mixer=L.MultiHeadAttention(
        n_out=32, n_heads=4, causal=True))
    key = jax.random.PRNGKey(3)
    got = block.init(key, I.RecurrentType(32, 8))
    want = _parent_block_init(key, 32, 4)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 32), jnp.float32)
    y = jax.jit(lambda p, x: block.apply(p, {}, x)[0])(got, x)
    y0 = jax.jit(lambda p, x: _parent_block_apply(p, x, 4))(got, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


@pytest.mark.parametrize("conf", [
    L.RMSNorm(eps=1e-6),
    L.MultiHeadAttention(n_out=D, n_heads=H, causal=True, bias=False,
                         rope_theta=1e6, head_dim=HD),
    _block(),
    L.LoopedStack(blocks=(_block(), _block()), passes=R,
                  final_norm=L.RMSNorm(eps=1e-6)),
    L.LoopedLMOutputLayer(n_out=V, beta=0.25),
], ids=lambda c: type(c).__name__)
def test_serde_round_trip_of_a_layer(conf):
    back = serde.from_json(serde.to_json(conf))
    assert back == conf and type(back) is type(conf)


def test_serde_round_trip_of_the_whole_model():
    conf = models.looped_lm(V, n_layers=2, d_model=D, n_heads=H, head_dim=HD,
                            ffn_width=F, passes=R, seq_len=T)
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back == conf
    stack = back.layers[1]
    assert isinstance(stack.blocks, tuple) and stack.passes == R
    assert stack.blocks[0].sandwich and stack.blocks[0].ffn == "gated"


def test_the_blocks_parameters_exist_once():
    net = _net()
    per_block = 4 * D + D * 3 * H * HD + H * HD * D + 3 * D * F
    assert net.num_params() == V * D + 2 * per_block + D + D * V + D + 1
    assert sorted(net.params[1]) == ["B00", "B01", "final_norm"]
    assert sorted(net.params[1]["B00"]) == [
        "ln1", "ln1_post", "ln2", "ln2_post", "mha", "mlp_Wd", "mlp_Wg",
        "mlp_Wu"]
    assert sorted(net.params[1]["B00"]["mha"]) == ["Wo", "Wqkv"]


def test_one_pass_is_the_same_blocks_listed_singly():
    blocks = (_block(), _block())
    stack = L.LoopedStack(blocks=blocks, passes=1)
    p = stack.init(jax.random.PRNGKey(0), IT)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, D), jnp.float32)
    got, _ = stack.apply(p, {}, x)
    h = x
    for j, b in enumerate(blocks):
        h, _ = b.apply(p[f"B{j:02d}"], {}, h)
    assert got.shape == (1, 2, T, D)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(h))


def test_r_passes_are_r_untied_copies_with_their_gradients_summed():
    blocks = (_block(), _block())
    norm = L.RMSNorm(eps=1e-6)
    tied = L.LoopedStack(blocks=blocks, passes=R, final_norm=norm)
    p = tied.init(jax.random.PRNGKey(0), IT)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, D), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (R, 2, T, D), jnp.float32)

    def untied_states(copies, x):
        out, h = [], x
        for c in copies:
            for j, b in enumerate(blocks):
                h, _ = b.apply(c[f"B{j:02d}"], {}, h)
            h, _ = norm.apply(c["final_norm"], {}, h)
            out.append(h)
        return jnp.stack(out)

    np.testing.assert_allclose(
        np.asarray(tied.apply(p, {}, x)[0]),
        np.asarray(untied_states([p] * R, x)), rtol=1e-6, atol=1e-6)
    g_tied = jax.grad(lambda p: jnp.sum(tied.apply(p, {}, x)[0] * w))(p)
    g_each = jax.grad(lambda cs: jnp.sum(untied_states(cs, x) * w))([p] * R)
    g_sum = jax.tree_util.tree_map(lambda *g: sum(g), *g_each)
    for a, b in zip(jax.tree_util.tree_leaves(g_tied),
                    jax.tree_util.tree_leaves(g_sum)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5)
    # and no single copy's gradient is the whole of it
    one = jax.tree_util.tree_leaves(g_each[0])[-1]
    assert not np.allclose(np.asarray(one),
                           np.asarray(jax.tree_util.tree_leaves(g_tied)[-1]),
                           rtol=1e-2)


def test_the_networks_recomputation_changes_no_gradient():
    """`LoopedStack` keeps what its blocks save; the network's own
    `gradient_checkpointing` wraps the whole entry, every pass in one."""
    x, y = _tokens()
    plain = _net()
    other = MultiLayerNetwork(dataclasses.replace(
        plain.conf, gradient_checkpointing=True))
    other.init()
    l0, _, g0 = plain.compute_gradients(plain.params, plain.state, x, y)
    l1, _, g1 = other.compute_gradients(plain.params, plain.state, x, y)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-7)


def _head_and_states(bias=0.0, seed=0):
    head = L.LoopedLMOutputLayer(n_out=V, beta=0.1, bias_init=bias)
    p = head.init(jax.random.PRNGKey(seed), IT)
    p["gate_W"] = p["gate_W"] * 20.0  # gates well away from one half
    feats = jax.random.normal(jax.random.PRNGKey(seed + 1), (R, 2, T, D),
                              jnp.float32)
    return head, p, feats


def test_the_exit_distribution_sums_to_one_and_is_the_stick_breaking_one():
    head, p, feats = _head_and_states()
    log_p = head.exit_log_probs(p, feats.reshape(R, 2 * T, D))
    prob = np.exp(np.asarray(log_p, np.float64))
    np.testing.assert_allclose(prob.sum(0), 1.0, rtol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-(np.asarray(
        feats.reshape(R, 2 * T, D), np.float64) @ np.asarray(
            p["gate_W"], np.float64)[:, 0] + float(p["gate_b"][0]))))
    want = [lam[0], lam[1] * (1 - lam[0]), (1 - lam[0]) * (1 - lam[1])]
    np.testing.assert_allclose(prob, np.stack(want), rtol=1e-4, atol=1e-7)
    assert prob.min() < 0.2 < 0.5 < prob.max()


def test_the_loss_is_the_formula():
    head, p, feats = _head_and_states()
    _, y = _tokens(b=2)
    loss, preds, _ = head.loss_from_features(p, {}, feats, jnp.asarray(y))
    assert preds is None
    s = np.asarray(feats.reshape(R, 2 * T, D), np.float64)
    z = s @ np.asarray(p["W"], np.float64)
    lse = np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) + z.max(-1)
    ce = lse - np.take_along_axis(z, y.reshape(1, -1, 1), -1)[..., 0]
    prob = np.exp(np.asarray(head.exit_log_probs(
        p, feats.reshape(R, 2 * T, D)), np.float64))
    want = np.mean((prob * ce).sum(0) + 0.1 * (prob * np.log(prob)).sum(0))
    assert float(loss) == pytest.approx(want, rel=1e-5)


def test_a_gate_shut_at_minus_30_leaves_the_last_passes_cross_entropy():
    head, p, feats = _head_and_states(bias=-30.0)
    p["gate_W"] = p["gate_W"] * 0.0
    _, y = _tokens(b=2)
    loss, _, _ = head.loss_from_features(p, {}, feats, jnp.asarray(y))
    z = matmul(feats[-1].reshape(2 * T, D), p["W"])
    ce = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, jnp.asarray(y).reshape(-1, 1), 1)[:, 0]
    assert float(loss) == pytest.approx(float(jnp.mean(ce)), rel=1e-5)


def test_a_label_mask_weights_the_tokens():
    head, p, feats = _head_and_states()
    _, y = _tokens(b=2)
    mask = np.zeros((2, T), np.float32)
    mask[0, :5] = 1.0
    masked, _, _ = head.loss_from_features(p, {}, feats, jnp.asarray(y),
                                           jnp.asarray(mask))
    part, _, _ = head.loss_from_features(p, {}, feats[:, :1, :5],
                                         jnp.asarray(y[:1, :5]))
    assert float(masked) == pytest.approx(float(part), rel=1e-5)


def _plain_loss(head, p, feats, y, mask=None):
    """The objective with every pass's head and cross-entropy as the two
    plain lines under autodiff (what `losses.head_xent` replaces)."""
    r, b, t, f = feats.shape
    s = feats.reshape(r, b * t, f)
    z = matmul(s.reshape(r * b * t, f), p["W"]).reshape(r, b * t, -1)
    ce = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, jnp.broadcast_to(y.reshape(1, b * t, 1), (r, b * t, 1)), -1)[..., 0]
    if r > 1:
        log_p = head.exit_log_probs(p, s)
        per = jnp.sum(jnp.exp(log_p) * (ce + head.beta * log_p), axis=0)
    else:
        per = ce[0]
    w = jnp.ones_like(per) if mask is None else mask.reshape(b * t)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def _head_case(passes, masked, dtype=jnp.float32):
    head, p, _ = _head_and_states()
    feats = jax.random.normal(jax.random.PRNGKey(7), (passes, 2, T, D), dtype)
    p, (_, y) = jax.tree_util.tree_map(lambda a: a.astype(dtype), p), \
        _tokens(b=2)
    mask = None
    if masked:
        mask = jnp.asarray(np.random.RandomState(3).rand(2, T) > 0.3, dtype)
    return head, p, feats, jnp.asarray(y), mask


def _assert_leaves_close(got, want, rtol, atol):
    """Leaf by leaf, `atol` as a share of the wanted leaf's largest entry."""
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol * float(jnp.max(jnp.abs(b)) + 1e-30),
            err_msg=str(path))


# 2 T = 64 rows a pass: in blocks of 24 (groups of 96) neither the blocks
# nor the groups end where the passes do, and the last group is padded
@pytest.mark.parametrize("rows", [512, 24])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("passes", [1, 4])
def test_the_heads_loss_and_gradients_are_the_plain_lines(passes, masked,
                                                          rows, monkeypatch):
    monkeypatch.setattr(losses, "_HEAD_ROWS", rows)
    head, p, feats, y, mask = _head_case(passes, masked)
    want, g_want = jax.value_and_grad(
        lambda p, s: _plain_loss(head, p, s, y, mask), argnums=(0, 1))(
            p, feats)
    got, g_got = jax.value_and_grad(
        lambda p, s: head.loss_from_features(p, {}, s, y, mask)[0],
        argnums=(0, 1))(p, feats)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    if passes == 1:  # one pass has no gate
        assert not np.any(np.asarray(g_got[0]["gate_W"]))
    _assert_leaves_close(g_got, g_want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("passes", [1, 4])
def test_the_heads_gradients_pass_a_float64_check(passes, masked,
                                                  monkeypatch):
    monkeypatch.setattr(losses, "_HEAD_ROWS", 24)
    head, p, feats, y, mask = _head_case(passes, masked, dtype=jnp.float64)
    check_grads(lambda p, s: head.loss_from_features(p, {}, s, y, mask)[0],
                (p, feats), order=1, modes=["rev"], atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("passes", [1, 4])
def test_the_heads_loss_and_gradients_are_the_float32_references(
        passes, monkeypatch):
    """`benchmark/reference/ouro.py`'s objective on the same states: its
    own head, gates and exit distribution (float32 under precision
    "highest", a sequence at a time), every leaf's gradient and the
    states'."""
    from benchmark.reference import ouro
    head, p, feats, y, _ = _head_case(passes, False)
    # the reference keeps the exit distribution in probabilities, not in
    # logs: gates within its range
    p["gate_W"] = p["gate_W"] / 10.0
    model = {"total_ut_steps": passes, "exit_entropy_beta": head.beta}

    def reference(p, feats):
        leaves = {"head_w": p["W"], "gate_w": p["gate_W"],
                  "gate_b": p["gate_b"]}
        total = 0.0
        for i in range(feats.shape[1]):
            monkeypatch.setattr(ouro, "states_one",
                                lambda *a, i=i: list(feats[:, i]))
            total += ouro.loss_sum_one(leaves, None, y[i], model)
        return total / y.size

    want, g_want = jax.value_and_grad(reference, argnums=(0, 1))(p, feats)
    got, g_got = jax.value_and_grad(
        lambda p, s: head.loss_from_features(p, {}, s, y)[0],
        argnums=(0, 1))(p, feats)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_leaves_close(g_got, g_want, rtol=1e-3, atol=1e-5)


def test_output_is_the_last_passes_softmax():
    net = _net()
    x, _ = _tokens()
    out = np.asarray(net.output(x))
    assert out.shape == (3, T, V)
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
    states, _ = net.apply_fn(net.params, net.state, jnp.asarray(x),
                             layer_limit=2)
    assert states.shape == (R, 3, T, D)
    z = matmul(states[-1].reshape(3 * T, D), net.params[2]["W"])
    np.testing.assert_allclose(out.reshape(3 * T, V),
                               np.asarray(jax.nn.softmax(z, -1)), rtol=1e-5,
                               atol=1e-8)


def test_float_labels_are_refused():
    net = _net()
    x, y = _tokens()
    with pytest.raises(TypeError, match="integer labels"):
        net.score(x, np.eye(V, dtype=np.float32)[y])


def test_integer_labels_reach_the_hook_as_int32(monkeypatch):
    """From `fit` through StepDriver to `loss_from_features`, nothing
    casts the [B, T] class ids to float (or to one-hot)."""
    seen = []
    real = L.LoopedLMOutputLayer.loss_from_features

    def spy(self, params, state, feats, labels, mask=None, train=True):
        seen.append((labels.dtype, labels.shape))
        return real(self, params, state, feats, labels, mask, train=train)

    monkeypatch.setattr(L.LoopedLMOutputLayer, "loss_from_features", spy)
    net = _net()
    x, y = _tokens()
    net.fit((x, y), epochs=1)
    assert seen and all(d == jnp.int32 and s == (3, T) for d, s in seen)


def test_fit_trains_through_the_step_driver():
    x, y = _tokens()
    net = _net()
    before = net.score(x, y)
    telemetry.enable()
    try:
        net.fit((x, y), epochs=5)
    finally:
        telemetry.disable()
    assert net.score(x, y) < before


_SCOPES = ["L01.LoopedStack", "loop", "ut0", "ut1", "ut2", "B00", "B01", "attn",
           "mlp", "rope", "rmsnorm", "loss", "exit_head", "exit_gate"]


@pytest.fixture(scope="module")
def step_paths():
    net = _net()
    x, y = _tokens()
    lowered = net.make_train_step().lower(
        net.params, net.state, net.opt_state, jnp.asarray(x), jnp.asarray(y),
        0, jax.random.PRNGKey(0), None)
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("scope", _SCOPES)
def test_the_lowered_step_carries_the_scope(step_paths, scope):
    rx = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    hits = [p for p in step_paths if rx.search(p)]
    assert hits, scope
    assert any("transpose(" in p for p in hits), f"{scope}: no backward"
    if scope in ("exit_head", "exit_gate"):
        assert all("loss" in p for p in hits if p.startswith("jit("))
    if scope in ("ut0", "B01", "rope"):
        assert any(re.search(r"L01\.LoopedStack.*/loop/", p) for p in hits)


def test_the_lowered_step_runs_each_pass_once(step_paths):
    """What shows how many passes a step ran: the `ut<r>` scopes under
    `loop`, forward and backward, one for each of the R passes."""
    for mark in (False, True):
        ran = {m for p in step_paths if ("transpose(" in p) == mark
               for m in re.findall(r"/loop/(ut\d+)/", p)}
        assert ran == {f"ut{r}" for r in range(R)}, (mark, ran)


@pytest.mark.parametrize("layer", [
    L.TransformerBlock(n_out=D, ffn="swiglu"),
    L.TransformerBlock(n_out=D, ffn="gated"),          # gated with biases
    L.TransformerBlock(n_out=D, norm="batch"),
    L.LoopedStack(blocks=(L.TransformerBlock(n_out=D // 2),), passes=2),
], ids=["ffn", "gated_bias", "norm", "type"])
def test_a_field_outside_its_values_is_refused_at_init(layer):
    with pytest.raises((ValueError, AssertionError)):
        layer.init(jax.random.PRNGKey(0), IT)
