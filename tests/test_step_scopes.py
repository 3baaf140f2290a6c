"""The names the train step's operations carry (nn/scopes.py): the lowered
text of a toy MultiLayerNetwork and ComputationGraph step, built the plain
way, for truncated BPTT and as the fused K-step scan, holds the layer /
vertex / loss / grad_norm / updater scopes; the backward pass repeats a
layer's scope under JAX's ``transpose(``; the softmax head's loss from its
logits, the flash kernel's forward and backward carry their own."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import scopes
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.graph import (ComputationGraph, ElementWiseVertex,
                                         GraphBuilder)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

CLIP = "clip_l2_per_layer"
B, T = 4, 6


def _paths(lowered):
    """Every op_name path of a lowered computation."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _has(paths, scope, backward=None):
    """Some path holds `scope` as one whole component (optionally: under
    / not under ``transpose(``)."""
    rx = re.compile(r"(?:^|[/(])" + scope + r"(?:$|[/)])")
    return any(rx.search(p) and (backward is None
                                 or ("transpose(" in p) == backward)
               for p in paths)


def _mln(recurrent):
    conf = NeuralNetConfig(
        seed=1, updater=U.Adam(learning_rate=0.01), l2=1e-4,
        gradient_normalization=CLIP)
    if recurrent:
        net = MultiLayerNetwork(conf.list(
            L.LSTM(n_out=8), L.RnnOutputLayer(n_out=3, loss="mcxent"),
            input_type=I.RecurrentType(5, T), backprop_type="tbptt",
            tbptt_fwd_length=3, tbptt_back_length=3))
        x, y = np.ones((B, T, 5), np.float32), np.ones((B, T, 3), np.float32)
    else:
        net = MultiLayerNetwork(conf.list(
            L.DenseLayer(n_out=8, activation="tanh"),
            L.OutputLayer(n_out=3, loss="mcxent"),
            input_type=I.FeedForwardType(5)))
        x, y = np.ones((B, 5), np.float32), np.ones((B, 3), np.float32)
    net.init()
    return net, x, y


def _cg(recurrent):
    kw = dict(updater=U.Adam(learning_rate=0.01), seed=2,
              gradient_normalization=CLIP)
    if recurrent:
        kw.update(backprop_type="tbptt", tbptt_fwd_length=3,
                  tbptt_back_length=3)
        in_type, enc = I.RecurrentType(5, T), L.LSTM(n_out=8)
        out = L.RnnOutputLayer(n_out=3, loss="mcxent")
        x, y = np.ones((B, T, 5), np.float32), np.ones((B, T, 3), np.float32)
    else:
        in_type = I.FeedForwardType(5)
        enc = L.DenseLayer(n_out=8, activation="tanh")
        out = L.OutputLayer(n_out=3, loss="mcxent")
        x, y = np.ones((B, 5), np.float32), np.ones((B, 3), np.float32)
    net = ComputationGraph(
        GraphBuilder(**kw).add_inputs("in").set_input_types(in_type)
        .add_layer("enc/a b", enc, "in")
        .add_vertex("sum", ElementWiseVertex(op="add"), "enc/a b", "enc/a b")
        .add_layer("out", out, "sum").set_outputs("out").build())
    net.init()
    return net, {"in": x}, {"out": y}


def _lower(net, x, y, builder):
    rng = jax.random.PRNGKey(0)
    p, s, o = net.params, net.state, net.opt_state
    if builder == "plain":
        return net.make_train_step(donate=False).lower(p, s, o, x, y, 0, rng)
    if builder == "health":
        return net.make_train_step(donate=False, with_health=True).lower(
            p, s, o, x, y, 0, rng)
    if builder == "fused":
        k = 2
        stack = lambda a: np.stack([a] * k)  # noqa: E731
        xs, ys = jax.tree_util.tree_map(stack, (x, y))
        return net.make_train_steps(k, donate=False).lower(
            p, s, o, xs, ys, 0, rng, np.ones((k, B), np.float32),
            np.ones(k, np.float32))
    assert builder == "tbptt"
    if isinstance(net, ComputationGraph):
        carries = net._zero_carries(B, jnp.float32)
    else:
        carries = [l.zero_carry(B, jnp.float32)
                   if hasattr(l, "zero_carry") else None
                   for l in net.conf.layers]
    return net.make_tbptt_step().lower(p, s, o, carries, x, y, 0, rng)


@pytest.mark.parametrize("builder", ["plain", "tbptt", "fused", "health"])
def test_multilayer_step_carries_its_scopes(builder):
    net, x, y = _mln(recurrent=builder == "tbptt")
    paths = _paths(_lower(net, x, y, builder))
    first = "L00.LSTM" if builder == "tbptt" else "L00.DenseLayer"
    last = "L01.RnnOutputLayer" if builder == "tbptt" else "L01.OutputLayer"
    for scope in (first, last, "loss"):
        assert _has(paths, re.escape(scope), backward=False), scope
        assert _has(paths, re.escape(scope), backward=True), scope
    for scope in ("grad_norm", "updater"):
        assert _has(paths, scope, backward=False), scope
        assert not _has(paths, scope, backward=True), scope
    assert _has(paths, "health") == (builder == "health")


@pytest.mark.parametrize("builder", ["plain", "tbptt", "fused", "health"])
def test_graph_step_carries_its_scopes(builder):
    net, x, y = _cg(recurrent=builder == "tbptt")
    paths = _paths(_lower(net, x, y, builder))
    enc = "LSTM" if builder == "tbptt" else "DenseLayer"
    out = "RnnOutputLayer" if builder == "tbptt" else "OutputLayer"
    # the vertex name's "/" and " " come out made safe
    for scope in (f"V.enc_a_b.{enc}", "V.sum.ElementWiseVertex",
                  f"V.out.{out}", "loss"):
        assert _has(paths, re.escape(scope), backward=False), scope
        assert _has(paths, re.escape(scope), backward=True), scope
    assert not any("V.enc/" in p for p in paths)
    for scope in ("grad_norm", "updater"):
        assert _has(paths, scope, backward=False), scope
    assert _has(paths, "health") == (builder == "health")


@pytest.mark.parametrize("builder", ["plain", "tbptt", "fused", "health"])
@pytest.mark.parametrize("make", [_mln, _cg])
def test_softmax_heads_loss_is_named_inside_loss(make, builder):
    """`softmax_xent`: the forward and the hand-written backward of the
    loss a softmax head takes from its logits, both inside `loss` (what
    `step_loss_ms.*` reads) and neither inside the layer's own scope,
    which keeps the head's matmul."""
    net, x, y = make(recurrent=builder == "tbptt")
    paths = _paths(_lower(net, x, y, builder))
    named = [p for p in paths if re.search(r"(?:^|[/(])softmax_xent/", p)]
    assert {("transpose(" in p) for p in named} == {False, True}
    for p in named:
        assert re.search(r"jvp\(loss\)\)?/softmax_xent/", p), p
        assert "OutputLayer" not in p, p


def test_transformer_block_names_its_halves():
    net = MultiLayerNetwork(NeuralNetConfig(
        seed=3, updater=U.Sgd(learning_rate=0.1)).list(
        L.TransformerBlock(n_out=8, mixer=L.MultiHeadAttention(
            n_out=8, n_heads=2, causal=True)),
        L.RnnOutputLayer(n_out=3, loss="mcxent"),
        input_type=I.RecurrentType(8, T)))
    net.init()
    x, y = np.ones((B, T, 8), np.float32), np.ones((B, T, 3), np.float32)
    paths = _paths(_lower(net, x, y, "plain"))
    for half in ("attn", "mlp"):
        rx = re.compile(r"L00\.TransformerBlock\)*/" + half + r"(?:$|/)")
        assert any(rx.search(p) and "transpose(" not in p for p in paths)
        assert any(rx.search(p) and "transpose(" in p for p in paths)


def test_flash_kernel_names_forward_and_backward():
    from deeplearning4j_tpu.ops import attention_pallas as ap
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q):
        return ap.flash_attention(q, q, q, causal=True, block_q=128,
                                  block_k=128, interpret=True).sum()

    paths = _paths(jax.jit(jax.grad(loss)).lower(q))
    assert _has(paths, r"flash_attn\.fwd", backward=False)
    assert _has(paths, r"flash_attn\.bwd", backward=True)
    # the kernel's own name rides under its scope
    assert any("flash_attn.fwd" in p and "flash_attn_fwd" in p for p in paths)


@pytest.mark.parametrize("raw,want", [
    ("res2a/branch 1", "res2a_branch_1"), ("a.b-c_d", "a.b-c_d"),
    ("x(y)", "x_y_"), (7, "7")])
def test_scope_names_are_made_safe(raw, want):
    assert scopes.safe(raw) == want


@pytest.mark.parametrize("make", [_mln, _cg])
@pytest.mark.parametrize("builder,name", [
    ("plain", "train_step"), ("tbptt", "tbptt_step"), ("fused", "steps_fn")])
def test_the_jitted_step_is_named_with_the_grammars_version(builder, name,
                                                            make):
    """jax leaves op_name out of the persistent cache's key but keeps the
    function's name in it: the stamp is what makes a change of the
    grammar compile anew instead of loading an executable with the old
    names. `s2` from PR 54, whose scopes inside the mixers and the routed
    layer change no computation: without the stamp a warm cache would
    serve both nets' steps with `s1`'s names."""
    net, x, y = make(recurrent=builder == "tbptt")
    text = _lower(net, x, y, builder).as_text()
    assert scopes.GRAMMAR == "s2"
    assert f"module @jit_{name}_{scopes.GRAMMAR} " in text


def test_gated_delta_kernels_are_named_inside_gdn_core(monkeypatch):
    """The recurrence's two kernels in a compiled train step (a one-layer
    `gated_delta_moe_lm` at lane-aligned head widths, the dispatch's
    answer forced off the chip): `gdn_fwd` under `gdn` and `gdn_core`
    outside `transpose(`, `gdn_bwd` under both inside it, as the
    benchmark's scope matcher reads `gdn_ms.tokens`, `gdn_core_ms.tokens`
    and `step_bwd_ms.tokens`: a backward kernel outside its scope would
    leave `gdn_core_ms` reading the forward alone. Read from the compiled
    step's `op_name`s, which is what a trace shows: the kernels sit in
    jitted functions of their own (one trace for every layer), and the
    lowered text names a called function's operations without the
    caller's scopes."""
    from benchmark.readers import trace_scope_ms
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.ops import gated_delta
    monkeypatch.setattr(gated_delta, "resolve_gated_delta", lambda *a: True)
    net = MultiLayerNetwork(models.gated_delta_moe_lm(
        16, n_layers=1, d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
        linear_k_heads=1, linear_v_heads=2, linear_k_head_dim=128,
        linear_v_head_dim=128, expert_width=8, shared_expert_width=8,
        n_experts=4, top_k=2, experts_held=(0, 2), seq_len=64))
    net.init()
    x = np.arange(64, dtype=np.int32).reshape(1, 64) % 16
    text = _lower(net, x, np.roll(x, -1, axis=1), "plain").compile().as_text()
    # whole paths: an operation inside a reduction's own computation keeps
    # the called function's relative name in the CPU compiler's text
    paths = set(re.findall(r'op_name="(jit\(train_step[^"]+)"', text))
    for kernel, backward in (("gdn_fwd", False), ("gdn_bwd", True)):
        named = [p for p in paths if re.search(
            r"(?:^|[/(])" + kernel + r"(?:$|[/)])", p)]
        assert named, kernel
        for scope in ("gdn", "gdn_core", r"L01\.TransformerBlock"):
            match = trace_scope_ms.matcher(
                {"scope": scope, "backward": backward})
            assert all(match(p) for p in named), (kernel, scope, named[:3])
