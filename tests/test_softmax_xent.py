"""The softmax head's loss from its logits (nn/losses.softmax_xent): value
and gradient against ``mcxent(softmax(z))`` under autodiff, the networks'
seam (``losses.from_logits``) against the path on probabilities, every
other head untouched, and what the train step keeps between forward and
backward."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.test_util import check_grads

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import losses
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.graph import ComputationGraph, GraphBuilder
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

V = 7
CLIPPED = 2  # the class whose logit the first row drives below the clip


def _case(shape, labels, mask, dtype, seed=0):
    """Logits, labels and mask of one parametrised case. The first row's
    logit of its own class sits 40 under the others: p < 1e-8 there."""
    r = np.random.default_rng(seed)
    rows = shape[:-1]
    z = r.normal(size=shape) * 3.0
    idx = r.integers(0, V, size=rows)
    idx[(0,) * len(rows)] = CLIPPED
    z[(0,) * len(rows) + (CLIPPED,)] = -40.0
    if labels == "onehot":
        y = np.eye(V)[idx]
    elif labels == "soft":  # legal mcxent input: rows need not sum to 1
        y = r.random(size=shape) * 1.3
        y[(0,) * len(rows)] = np.eye(V)[CLIPPED]
    else:
        y = idx
    m = {"none": None, "some": (r.random(size=rows) > 0.4),
         "zero": np.zeros(rows, bool)}[mask]
    if m is not None:
        m = m.astype(np.float64)
        if mask == "some":
            m[(0,) * len(rows)] = 1.0  # the clipped row counts
    as_float = lambda a: None if a is None else jnp.asarray(a, dtype)  # noqa: E731
    return (as_float(z), jnp.asarray(y) if labels == "int" else as_float(y),
            as_float(m))


def _reference(z, y, m, sparse):
    fn = losses.sparse_mcxent if sparse else losses.mcxent
    return fn(jax.nn.softmax(z, axis=-1), y, m)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("mask", ["none", "some", "zero"])
@pytest.mark.parametrize("labels", ["onehot", "soft", "int"])
@pytest.mark.parametrize("shape", [(6, V), (3, 4, V)])
def test_value_and_gradient_are_autodiffs(shape, labels, mask, dtype):
    z, y, m = _case(shape, labels, mask, dtype)
    sparse = labels == "int"
    want, g_want = jax.value_and_grad(_reference)(z, y, m, sparse)
    got, g_got = jax.value_and_grad(losses.softmax_xent)(z, y, m, sparse)
    assert got.dtype == want.dtype and g_got.dtype == z.dtype
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(g_got, g_want, rtol=0, atol=1e-6)
    first = (0,) * (len(shape) - 1)
    if mask != "zero":
        # the clipped row: its loss is -log(1e-8) and nothing flows back
        alone = jnp.zeros(shape[:-1], dtype).at[first].set(1.0)
        if labels != "soft":
            np.testing.assert_allclose(
                losses.softmax_xent(z, y, alone, sparse), -np.log(1e-8),
                rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(g_got[first]), 0.0)
    else:
        assert float(got) == 0.0 and not np.any(np.asarray(g_got))


@pytest.mark.parametrize("mask", ["none", "some"])
def test_labels_and_mask_gradients_are_autodiffs(mask):
    z, y, m = _case((3, 4, V), "soft", mask, jnp.float64)
    argnums = (1,) if m is None else (1, 2)
    want = jax.grad(_reference, argnums)(z, y, m, False)
    got = jax.grad(losses.softmax_xent, argnums)(z, y, m)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-9)


def test_only_a_softmax_cross_entropy_head_takes_the_logits():
    takes = lambda layer: losses.from_logits(layer) is not None  # noqa: E731
    assert takes(L.OutputLayer(n_out=3))
    assert takes(L.OutputLayer(n_out=3, loss="negativeloglikelihood"))
    assert takes(L.RnnOutputLayer(n_out=3, loss="sparse_mcxent"))
    assert takes(L.OutputLayer(n_out=3, loss=losses.mcxent))
    assert not takes(L.OutputLayer(n_out=3, loss="xent",
                                   activation="sigmoid"))
    assert not takes(L.OutputLayer(n_out=3, loss="mse",
                                   activation="identity"))
    assert not takes(L.OutputLayer(n_out=3, loss="mcxent",
                                   activation="sigmoid"))
    assert not takes(L.OutputLayer(n_out=3, loss=lambda p, y, m: 0.0))
    assert not takes(L.LossLayer(loss="mcxent", activation="softmax"))
    assert not takes(L.CenterLossOutputLayer(n_out=3))
    assert not takes(L.DenseLayer(n_out=3, activation="softmax"))


def _mln_ff(**head):
    return MultiLayerNetwork(NeuralNetConfig(
        seed=3, updater=U.Adam(learning_rate=0.01), l2=1e-4).list(
            L.DenseLayer(n_out=8, activation="tanh"),
            L.OutputLayer(n_out=V, **head),
            input_type=I.FeedForwardType(5)))


def _mln_rnn(**head):
    return MultiLayerNetwork(NeuralNetConfig(
        seed=3, updater=U.Adam(learning_rate=0.01)).list(
            L.LSTM(n_out=8), L.RnnOutputLayer(n_out=V, **head),
            input_type=I.RecurrentType(5, 6)))


def _cg(**head):
    return ComputationGraph(
        GraphBuilder(updater=U.Adam(learning_rate=0.01), seed=3)
        .add_inputs("in").set_input_types(I.FeedForwardType(5))
        .add_layer("enc", L.DenseLayer(n_out=8, activation="tanh"), "in")
        .add_layer("out", L.OutputLayer(n_out=V, **head), "enc")
        .set_outputs("out").build())


def _feed(net, recurrent, sparse, seed=5):
    r = np.random.default_rng(seed)
    rows = (4, 6) if recurrent else (4,)
    x = r.normal(size=rows + (5,)).astype(np.float32)
    idx = r.integers(0, V, size=rows)
    y = idx.astype(np.int32) if sparse else np.eye(V, dtype=np.float32)[idx]
    if isinstance(net, ComputationGraph):
        return {"in": x}, {"out": y}
    return x, y


def _three_losses(net, x, y):
    net.init()
    step = net.make_train_step(donate=False)
    p, s, o = net.params, net.state, net.opt_state
    out = []
    for i in range(3):
        p, s, o, loss = step(p, s, o, x, y, i, jax.random.PRNGKey(i))
        out.append(float(loss))
    return out


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("build", [_mln_ff, _mln_rnn, _cg])
def test_three_steps_as_the_path_on_probabilities(build, sparse, monkeypatch):
    head = {"loss": "sparse_mcxent" if sparse else "mcxent"}
    x, y = _feed(build(**head), build is _mln_rnn, sparse)
    new = _three_losses(build(**head), x, y)
    monkeypatch.setattr(losses, "from_logits", lambda layer: None)
    old = _three_losses(build(**head), x, y)
    assert new[0] > new[2]  # it trains
    np.testing.assert_allclose(new, old, rtol=2e-6)


def test_output_still_gives_probabilities():
    net = _mln_ff()
    net.init()
    x, y = _feed(net, False, False)
    p = np.asarray(net.output(x))
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)
    _, (_, preds) = net.loss_fn(net.params, net.state, x, y, train=False)
    np.testing.assert_allclose(np.asarray(preds), p, rtol=1e-6)


@pytest.mark.parametrize("build", [_mln_ff, _mln_rnn, _cg])
@pytest.mark.parametrize("head", [
    {"loss": "xent", "activation": "sigmoid"},
    {"loss": "mse", "activation": "identity"},
    {"loss": "mcxent", "activation": "sigmoid"}])
def test_another_head_is_untouched(build, head, monkeypatch):
    net = build(**head)
    net.init()
    x, y = _feed(net, build is _mln_rnn, False)
    loss = lambda p: net.loss_fn(p, net.state, x, y, train=True)[0]  # noqa: E731
    with_seam = str(jax.make_jaxpr(jax.value_and_grad(loss))(net.params))
    monkeypatch.setattr(losses, "from_logits", lambda layer: None)
    without = str(jax.make_jaxpr(jax.value_and_grad(loss))(net.params))
    assert with_seam == without
    assert "softmax_xent" not in with_seam


def _toy_lm():
    b, t, v = 3, 8, 37  # b * t is no width of the model
    net = MultiLayerNetwork(models.transformer_lm(
        vocab_size=v, n_layers=1, d_model=16, n_heads=2, seq_len=t, seed=1))
    net.init()
    x = (np.arange(b * t).reshape(b, t) * 5) % v
    y = np.eye(v, dtype=np.float32)[(x + 1) % v]
    return net, x, y, b * t * v


def test_one_logits_sized_array_is_kept_for_the_backward():
    """Between forward and backward the head keeps its logits and nothing
    else of their size: no probabilities, no log, no clip mask. (The dense
    labels are the caller's array, not one the step makes.)"""
    net, x, y, size = _toy_lm()
    loss = lambda p, y: net.loss_fn(p, net.state, x, y, train=True)[0]  # noqa: E731
    kept = [(aval, why) for aval, why in saved_residuals(loss, net.params, y)
            if aval.size == size and why != "from the argument y"]
    assert len(kept) == 1, kept
    assert jnp.issubdtype(kept[0][0].dtype, jnp.floating)


def test_the_train_step_makes_no_probability_tensor():
    net, x, y, _ = _toy_lm()
    text = net.make_train_step(donate=False).lower(
        net.params, net.state, net.opt_state, x, y, 0,
        jax.random.PRNGKey(0)).as_text()
    wide = re.compile(r"stablehlo\.divide\b.*tensor<(24x37|3x8x37)x")
    assert not [ln for ln in text.splitlines() if wide.search(ln)]
    assert re.search(r"stablehlo\.exponential\b.*tensor<24x37x", text)


# ---- head_xent: the head's product and its cross-entropy as one op ----

HF, HV = 12, 37  # features and vocabulary of the head cases


def _head_case(m, dtype, seed=0):
    r = np.random.default_rng(seed)
    s = jnp.asarray(r.normal(size=(m, HF)), dtype)
    w = jnp.asarray(r.normal(size=(HF, HV)) * 0.5, dtype)
    y = jnp.asarray(r.integers(0, HV, size=m), jnp.int32)
    c = jnp.asarray(r.random(size=m) / m, dtype)
    return s, w, y, c


def _plain_head(s, w, y, c):
    """The two lines the op replaces, under autodiff."""
    z = matmul(s, w)
    ce = jax.nn.logsumexp(z, axis=-1) \
        - jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.sum(c * ce), ce


# rows against blocks of 8 in groups of 4: one group of whole blocks, a padded
# last block, one short block, three groups with the last one padded
@pytest.mark.parametrize("rows", [24, 21, 5, 70])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_head_xent_is_autodiff_of_the_plain_lines(rows, dtype, monkeypatch):
    monkeypatch.setattr(losses, "_HEAD_ROWS", 8)
    s, w, y, c = _head_case(rows, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    (want, ce_want), g_want = jax.value_and_grad(
        _plain_head, argnums=(0, 1, 3), has_aux=True)(s, w, y, c)
    (got, ce_got), g_got = jax.value_and_grad(
        losses.head_xent, argnums=(0, 1, 3), has_aux=True)(s, w, y, c)
    assert got.dtype == want.dtype and ce_got.shape == (rows,)
    np.testing.assert_allclose(got, want, rtol=tol)
    np.testing.assert_allclose(ce_got, ce_want, rtol=tol, atol=tol)
    for a, b, name in zip(g_got, g_want, ("ds", "dW", "dc")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)
    # the primal, a score without a gradient, walks the same blocks
    alone, ce_alone = jax.jit(losses.head_xent)(s, w, y, c)
    np.testing.assert_allclose(alone, want, rtol=tol)
    np.testing.assert_allclose(ce_alone, ce_want, rtol=tol, atol=tol)


@pytest.mark.parametrize("rows", [16, 11])
def test_head_xent_passes_a_float64_gradient_check(rows, monkeypatch):
    monkeypatch.setattr(losses, "_HEAD_ROWS", 8)
    s, w, y, c = _head_case(rows, jnp.float64, seed=1)
    # a cotangent other than 1 reaches ds, dW and dc alike
    f = lambda s, w, c: 1.7 * losses.head_xent(s, w, y, c)[0]  # noqa: E731
    check_grads(f, (s, w, c), order=1, modes=["rev"], atol=1e-6, rtol=1e-6)


def test_head_xent_rounds_as_the_bfloat16_products_do():
    """Under the bfloat16 policy the op's gradient is the plain lines'
    to the products' rounding: dz is rounded once, with the row's weight
    inside, where the product's transpose rounds it."""
    from deeplearning4j_tpu.utils import dtypes
    s, w, y, c = _head_case(64, jnp.float32, seed=2)
    old = dtypes.get_policy()
    dtypes.bf16_policy()
    try:
        want, g_want = jax.value_and_grad(
            lambda *a: _plain_head(*a)[0], argnums=(0, 1, 3))(s, w, y, c)
        got, g_got = jax.value_and_grad(
            lambda *a: losses.head_xent(*a)[0], argnums=(0, 1, 3))(s, w, y, c)
    finally:
        dtypes.set_policy(old.param_dtype, old.compute_dtype, old.accum_dtype)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(g_got, g_want):
        assert a.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-2 * scale)


def _avals(jaxpr):
    """Every value's aval of a jaxpr, the loops' bodies included."""
    for eqn in jaxpr.eqns:
        yield from ((eqn.primitive.name, v.aval) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("what", ["value", "value_and_grad"])
def test_head_xent_makes_no_array_of_all_rows_logits(what, monkeypatch):
    """No [M, V] array in either pass: a block's logits, and under
    differentiation a group's dz. Two products a block (the logits, ds),
    one a group (dW) and none in the backward rule."""
    monkeypatch.setattr(losses, "_HEAD_ROWS", 8)
    s, w, y, c = _head_case(64, jnp.float32)
    f = lambda s, w, c: losses.head_xent(s, w, y, c)[0]  # noqa: E731
    if what == "value_and_grad":
        f = jax.value_and_grad(f, argnums=(0, 1, 2))
    found = list(_avals(jax.make_jaxpr(f)(s, w, c).jaxpr))
    wide = {a.shape[0] for _, a in found
            if len(a.shape) == 2 and a.shape[1] == HV}
    assert wide == ({8, 32, HF} if what == "value_and_grad" else {8})
    dots = [a.shape for name, a in found if name == "dot_general"]
    assert sorted(dots) == sorted(
        [(8, HV), (8, HF), (HF, HV)] if what == "value_and_grad"
        else [(8, HV)])


def test_both_rules_of_head_xent_carry_the_call_sites_scopes():
    """jax keeps the call site's scopes for a `custom_vjp`'s backward rule
    too, so a caller names the scope its readings go by around the call.
    Read from the compiled text's `op_name`s, which is what a trace shows
    (the lowered text names a loop body's operations without the caller's
    scopes): every product and exponential of the gradient's program, and
    the backward rule's work under `transpose(`."""
    s, w, y, c = _head_case(24, jnp.float32)

    def f(s, w, c):  # a cotangent of 1 would leave the backward rule empty
        with jax.named_scope("my_head"):
            return 1.7 * losses.head_xent(s, w, y, c)[0]

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        s, w, c).compile().as_text()
    paths = set(re.findall(r'op_name="(jit\(f\)[^"]+)"', text))
    made = [p for p in paths if re.search(r"dot_general|exp", p)]
    held = re.compile(r"[/(]my_head[/)].*head_xent/")  # jax writes jvp(my_head)
    assert made and all(held.search(p) for p in made), \
        [p for p in made if not held.search(p)]
    assert any("dot_general" in p for p in made)
    assert any("transpose(" in p and held.search(p) for p in paths)
