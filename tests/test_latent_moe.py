"""The fifth mixer of the one block, the parameter tie and the two-term
head (ISSUE 45) at toy size on the CPU: `LatentAttention` against a naive
per-head softmax with the rotary key shared, the block and the factory's
parameter counts at GLM-4.7-Flash's widths, `ParamTie` in the network (one
leaf, both gradients, counted once, saved once), `MultiTokenLMOutputLayer`
against its formula, and `mtp_weight` 0 against the trunk alone."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models import hybrid_moe_lm, latent_moe_lm
from deeplearning4j_tpu.models.misc import _hybrid_decoder
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import (MultiLayerConfiguration,
                                                NeuralNetConfig, ParamTie)
from deeplearning4j_tpu.nn.layers.attention import rope
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import serialization

TOY = dict(vocab_size=80, n_layers=2, num_dense_layers=1, d_model=32,
           n_heads=2, q_rank=16, kv_rank=8, nope_dim=12, rope_dim=4,
           v_dim=16, ffn_width=48, expert_width=24, shared_expert_width=24,
           n_experts=8, top_k=2, experts_held=(2, 6), seq_len=16)


def _count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))


def _batch(seed=0, b=2, t=16, v=80):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, v, (b, t + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


# ---------------------------------------------------------------- the mixer

def _mixer(**kw):
    return L.LatentAttention(**{
        "n_out": 32, "n_heads": 4, "q_rank": 24, "kv_rank": 16,
        "nope_dim": 12, "rope_dim": 4, "v_dim": 16, "causal": True,
        "rope_theta": 1e4, "norm_eps": 1e-5, **kw})


def _naive(layer, p, x):
    """A head at a time, a sequence at a time, in numpy-shaped steps: the
    equations of ISSUE 45 as written."""
    h, dn, dr, dv = (layer.n_heads, layer.nope_dim, layer.rope_dim,
                     layer.v_dim)

    def rms(u, g):
        return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                            + layer.norm_eps) * g

    def turn(u):       # [T, dr] -> rotate-half at positions 0..T-1
        return rope(u[None, :, None, :], layer.rope_theta)[0, :, 0]

    out = []
    for u in x:
        t = u.shape[0]
        c_q = rms(u @ p["W_qa"], p["q_gamma"])
        q = (c_q @ p["W_qb"]).reshape(t, h, dn + dr)
        kva = u @ p["W_kva"]
        kv = (rms(kva[:, :layer.kv_rank], p["kv_gamma"])
              @ p["W_kvb"]).reshape(t, h, dn + dv)
        kr = turn(kva[:, layer.kv_rank:])          # ONE key a token
        seen = jnp.tril(jnp.ones((t, t), bool))
        heads = []
        for j in range(h):
            q_j = jnp.concatenate([q[:, j, :dn], turn(q[:, j, dn:])], -1)
            k_j = jnp.concatenate([kv[:, j, :dn], kr], -1)
            s = q_j @ k_j.T / math.sqrt(dn + dr)
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            heads.append(w @ kv[:, j, dn:])
        out.append(jnp.concatenate(heads, -1) @ p["Wo"])
    return jnp.stack(out)


def test_the_mixer_is_the_naive_per_head_softmax_with_the_rotary_key_shared():
    layer = _mixer()
    it = I.RecurrentType(32, 24)
    p = layer.init(jax.random.PRNGKey(3), it, jnp.float64)
    p = {**p, "q_gamma": p["q_gamma"] * 1.3, "kv_gamma": p["kv_gamma"] * 0.7}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32), jnp.float64)
    y, _ = jax.jit(lambda p: layer.apply(p, {}, x))(p)
    want = jax.jit(lambda p: _naive(layer, p, x))(p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-9,
                               atol=1e-12)
    # and its gradient, every leaf
    got = jax.jit(jax.grad(
        lambda p: jnp.sum(jnp.sin(layer.apply(p, {}, x)[0]))))(p)
    want = jax.jit(jax.grad(
        lambda p: jnp.sum(jnp.sin(_naive(layer, p, x)))))(p)
    assert set(got) == {"W_qa", "q_gamma", "W_qb", "W_kva", "kv_gamma",
                        "W_kvb", "Wo"}
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=1e-8,
                                   atol=1e-12, err_msg=name)


def test_every_head_reads_the_same_rotary_key():
    layer = _mixer()
    p = layer.init(jax.random.PRNGKey(0), I.RecurrentType(32, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    q, k, v = layer.heads(p, x)
    assert q.shape == k.shape == v.shape == (1, 8, 4, 16)
    for j in range(1, 4):
        np.testing.assert_array_equal(np.asarray(k[:, :, j, 12:]),
                                      np.asarray(k[:, :, 0, 12:]))
    assert not np.allclose(np.asarray(k[:, :, 1, :12]),
                           np.asarray(k[:, :, 0, :12]))


def test_a_value_width_that_is_not_the_querys_is_refused():
    with pytest.raises(ValueError, match="value width"):
        _mixer(v_dim=8).init(jax.random.PRNGKey(0), I.RecurrentType(32, 8))


def test_the_block_takes_the_fifth_mixer_and_refuses_a_sixth():
    block = L.TransformerBlock(
        n_out=32, mixer=_mixer(), norm="rms", bias=False, ffn="gated",
        ffn_width=48, activation="silu")
    it = I.RecurrentType(32, 8)
    p = block.init(jax.random.PRNGKey(0), it)
    assert set(p) == {"ln1", "ln2", "mla", "mlp_Wg", "mlp_Wu", "mlp_Wd"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y, _ = block.apply(p, {}, x)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    with pytest.raises(ValueError, match="MIGRATION.md"):
        dataclasses.replace(block, mixer="latent_attention").init(
            jax.random.PRNGKey(0), it)


def test_the_published_widths_count_as_issue_45_counts_them():
    """21,759,232 a latent attention, 106,829,056 a mixture layer at 8
    held, 706,518,528 in all, from shapes alone."""
    conf = latent_moe_lm(19360, n_layers=5, experts_held=(0, 8))
    net = MultiLayerNetwork(conf)
    shapes = jax.eval_shape(lambda: net.init()[0])
    assert _count(shapes[1]["mla"]) == 21_759_232
    assert _count(shapes[1]) == 21_759_232 + 62_914_560 + 2 * 2048
    assert [_count(s) for s in shapes[2:6]] == [106_829_056] * 4
    out = shapes[6]
    assert _count(out["mtp"]) == 115_223_808
    assert "embed" not in out and out["W"].shape == (2048, 19360)
    assert _count(shapes) == 706_518_528
    assert conf.ties == (ParamTie(layer=6, name="embed", source_layer=0,
                                  source_name="W"),)


# ------------------------------------------------------------------ the tie

def _tied_net(**kw):
    net = MultiLayerNetwork(latent_moe_lm(**{**TOY, **kw}))
    net.init()
    return net


def test_the_configuration_with_its_tie_round_trips_through_json():
    conf = latent_moe_lm(**TOY)
    assert isinstance(conf.ties[0], ParamTie)
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again == conf and again.ties == conf.ties
    assert hybrid_moe_lm(64, d_model=32, n_heads=2, n_kv_heads=1,
                         ffn_width=48, expert_width=24, n_experts=8,
                         top_k=2, seq_len=16).ties == ()


def test_one_table_and_one_head_in_the_tree_counted_once():
    net = _tied_net()
    tables = [a for a in jax.tree_util.tree_leaves(net.params)
              if a.shape == (80, 32)]
    heads = [a for a in jax.tree_util.tree_leaves(net.params)
             if a.shape == (32, 80)]
    assert len(tables) == 1 and len(heads) == 1
    assert "embed" not in net.params[-1]
    per_layer = [_count(p) for p in net.params]
    assert net.num_params() == sum(per_layer)
    # the updater's state follows the tree: one moment a leaf
    assert jax.tree_util.tree_structure(net.opt_state["m"]) == \
        jax.tree_util.tree_structure(net.params)
    # the layers read it through the tie, the tree is left as it was
    tied = net._tied(net.params)
    assert tied[-1]["embed"] is net.params[0]["W"]
    assert "embed" not in net.params[-1] and tied[0] is net.params[0]


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    net = _tied_net()
    x, y = _batch()

    def loss(table_in, table_out, head_main, head_mtp):
        """The same loss with each use of a shared leaf given apart."""
        params = list(net.params)
        params[0] = {"W": table_in}
        out = net.conf.layers[-1]
        feats, state = net.apply_fn(params, net.state, x, train=True,
                                    layer_limit=len(params) - 1)
        p = {**params[-1], "embed": table_out}
        # the two heads apart: the main term with one matrix, the
        # module's with the other
        trunk_only = dataclasses.replace(out, mtp_weight=0.0)
        main, _, _ = trunk_only.loss_from_features(
            {**p, "W": head_main}, net.state[-1], feats, y)
        both, _, _ = out.loss_from_features({**p, "W": head_mtp},
                                            net.state[-1], feats, y)
        only_main, _, _ = trunk_only.loss_from_features(
            {**p, "W": head_mtp}, net.state[-1], feats, y)
        return main + (both - only_main)

    w, h = net.params[0]["W"], net.params[-1]["W"]
    parts = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(w, w, h, h)
    _, _, grads = jax.jit(lambda p, s: net.compute_gradients(p, s, x, y))(
        net.params, net.state)
    assert all(float(jnp.abs(g).max()) > 0 for g in parts)
    np.testing.assert_allclose(np.asarray(grads[0]["W"]),
                               np.asarray(parts[0] + parts[1]), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(grads[-1]["W"]),
                               np.asarray(parts[2] + parts[3]), rtol=1e-4,
                               atol=1e-7)
    # rows no input token and no label names get no gradient from either
    used = np.union1d(np.asarray(x), np.asarray(y))
    unused = np.setdiff1d(np.arange(80), used)
    assert unused.size and not np.asarray(grads[0]["W"])[unused].any()


def test_a_save_and_restore_holds_the_tied_table_once(tmp_path):
    net = _tied_net()
    x, y = _batch()
    net.fit(x, y)
    path = str(tmp_path / "net.zip")
    serialization.save_model(net, path)
    again = serialization.load_model(path)
    assert again.conf.ties == net.conf.ties
    assert again.num_params() == net.num_params()
    assert "embed" not in again.params[-1]
    for a, b in zip(jax.tree_util.tree_leaves(net.params),
                    jax.tree_util.tree_leaves(again.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(again.output(x)),
                               np.asarray(net.output(x)), rtol=1e-12)


def test_a_tie_names_what_exists_and_what_the_reader_does_not_make():
    conf = latent_moe_lm(**TOY)
    missing = dataclasses.replace(conf, ties=(ParamTie(
        layer=3, name="embed", source_layer=0, source_name="nope"),))
    with pytest.raises(ValueError, match="no parameter 'nope'"):
        MultiLayerNetwork(missing).init()
    owned = dataclasses.replace(conf, ties=(ParamTie(
        layer=3, name="W", source_layer=0, source_name="W"),))
    with pytest.raises(ValueError, match="one owner"):
        MultiLayerNetwork(owned).init()
    untied = MultiLayerNetwork(dataclasses.replace(conf, ties=()))
    untied.init()
    with pytest.raises(ValueError, match="tie it to this layer"):
        untied.score(*_batch())


def test_a_tie_is_general_any_layer_reads_any_other_layers_parameter():
    """Not this head's special case: a dense layer's matrix read by a
    second layer of the same shape through the network's configuration."""

    @dataclasses.dataclass(frozen=True)
    class Borrowing(L.DenseLayer):
        def init(self, key, input_type, dtype=jnp.float32):
            return {"b": jnp.zeros((self.n_out,), dtype)}

    conf = NeuralNetConfig(seed=1).list(
        L.DenseLayer(n_out=6, activation="tanh"),
        Borrowing(n_out=6, activation="tanh"),
        L.OutputLayer(n_out=3, loss="mcxent", activation="softmax"),
        input_type=I.FeedForwardType(6),
        ties=[ParamTie(layer=1, name="W", source_layer=0, source_name="W")])
    net = MultiLayerNetwork(conf)
    net.init()
    assert set(net.params[1]) == {"b"}
    assert net.num_params() == 6 * 6 + 6 + 6 + 6 * 3 + 3
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 6)))
    y = jax.nn.one_hot(jnp.arange(5) % 3, 3)
    w, b0, b1 = net.params[0]["W"], net.params[0]["b"], net.params[1]["b"]
    want = jnp.tanh(jnp.tanh(x @ w + b0) @ w + b1)
    np.testing.assert_allclose(np.asarray(net.feed_forward(x)[1]),
                               np.asarray(want), rtol=1e-6)
    before = np.asarray(w)
    net.fit(x, y)
    assert set(net.params[1]) == {"b"}
    assert not np.allclose(np.asarray(net.params[0]["W"]), before)


# ------------------------------------------------------------ the two terms

def _formula(net, x, y):
    """ISSUE 45's loss from the parts, written out."""
    out = net.conf.layers[-1]
    p, table = net.params[-1], net.params[0]["W"]
    h, _ = net.apply_fn(net.params, net.state, x, train=True,
                        layer_limit=len(net.params) - 1)

    def rms(u, g):
        return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                            + out.norm_eps) * g["gamma"]

    def ce(z, labels):
        return -jnp.take_along_axis(jax.nn.log_softmax(z, -1),
                                    labels[..., None], -1)[..., 0]

    main = jnp.mean(ce(rms(h, p["final_norm"]) @ p["W"], y))
    m = p["mtp"]
    joined = jnp.concatenate([rms(table[y], m["enorm"]), rms(h, m["hnorm"])],
                             -1) @ m["W_eh"]
    joined, _ = out.block.apply(m["block"], net.state[-1]["mtp"], joined,
                                train=True)
    z2 = rms(joined, m["norm"]) @ p["W"]
    mtp = jnp.mean(ce(z2[:, :-1], y[:, 1:]))
    return main, mtp


@pytest.mark.parametrize("mtp_weight", [0.3, 1.0])
def test_the_loss_is_the_two_terms_of_the_formula(mtp_weight):
    net = _tied_net(mtp_weight=mtp_weight)
    x, y = _batch(1)
    main, mtp = _formula(net, x, y)
    loss, (state, _) = net.loss_fn(net.params, net.state, x, y)
    assert float(loss) == pytest.approx(float(main + mtp_weight * mtp),
                                        rel=1e-12)
    terms = state[-1]["loss_terms"]
    assert float(terms["main"]) == pytest.approx(float(main), rel=1e-6)
    assert float(terms["mtp"]) == pytest.approx(float(mtp), rel=1e-6)
    # the module's mixture counted T rows a sequence
    counts = state[-1]["mtp"]
    assert float(counts["moe_load"].sum() + counts["moe_elsewhere"][0]) \
        == 2 * 16 * 2


def test_a_tied_configuration_is_refused_where_layers_are_staged(
        eight_devices):
    """Two layers that read one leaf may lie on two stages: the pipeline
    says so at construction and does not train a copy a stage."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.pipeline_general import PipelinedNetwork
    mesh = Mesh(np.array(eight_devices[:2]), ("stage",))
    with pytest.raises(AssertionError, match="not stageable"):
        PipelinedNetwork(latent_moe_lm(**TOY), mesh, n_microbatches=2)


def test_a_label_mask_weighs_both_terms():
    net = _tied_net()
    x, y = _batch(3)
    mask = jnp.ones((2, 16)).at[1, 9:].set(0.0)
    loss, _ = net.loss_fn(net.params, net.state, x, y, mask=mask)
    # the masked tail of the second sequence changes nothing
    y2 = y.at[1, 9:].set(0)
    loss2, _ = net.loss_fn(net.params, net.state, x, y2, mask=mask)
    assert np.isfinite(float(loss))
    assert float(loss) == pytest.approx(float(loss2), rel=1e-12)


def test_weight_zero_is_the_trunk_alone_loss_and_gradients():
    """`mtp_weight` 0 against the same decoder under a final RMSNorm and
    `RnnOutputLayer`: the loss and every gradient the two nets share."""
    tied = _tied_net(mtp_weight=0.0)
    latent = L.LatentAttention(
        n_out=32, n_heads=TOY["n_heads"], q_rank=16, kv_rank=8, nope_dim=12,
        rope_dim=4, v_dim=16, causal=True, rope_theta=1e6, norm_eps=1e-5,
        weight_init=tied.conf.layers[0].weight_init)
    plain_conf = _hybrid_decoder(
        80, 32, 16,
        [(latent, {"ffn": "gated", "ffn_width": 48}),
         (latent, {"ffn": "moe", "ffn_width": 24, "n_experts": 8,
                   "top_k": 2, "experts_held": (2, 6), "routed_scale": 1.8,
                   "shared_expert_width": 24, "shared_expert_gate": False})],
        block={"norm_eps": 1e-5},
        final_norm=L.RMSNorm(eps=1e-5), updater=None, seed=12345)
    assert plain_conf.layers[:3] == tied.conf.layers[:3]
    plain = MultiLayerNetwork(plain_conf)
    plain.init()
    plain.params = [*tied.params[:3], tied.params[3]["final_norm"],
                    {"W": tied.params[3]["W"]}]
    x, y = _batch(4)
    l_t, s_t, g_t = jax.jit(lambda p, s: tied.compute_gradients(
        p, s, x, y))(tied.params, tied.state)
    l_p, _, g_p = jax.jit(lambda p, s: plain.compute_gradients(
        p, s, x, y))(plain.params, plain.state)
    # float32; `RnnOutputLayer`'s loss takes its gradient by a backward
    # written by hand, this head's by autodiff: rounding apart, no more
    assert float(l_t) == pytest.approx(float(l_p), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_t[:3]),
                    jax.tree_util.tree_leaves(g_p[:3])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_t[3]["final_norm"]["gamma"]),
                               np.asarray(g_p[3]["gamma"]), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_t[3]["W"]),
                               np.asarray(g_p[4]["W"]), rtol=1e-4,
                               atol=1e-7)
    # the module took no part: no gradient, no counts, no second term
    assert not any(np.asarray(a).any()
                   for a in jax.tree_util.tree_leaves(g_t[3]["mtp"]))
    assert float(s_t[3]["loss_terms"]["mtp"]) == 0.0
    assert not np.asarray(s_t[3]["mtp"]["moe_load"]).any()
    # and the same softmax out of both
    np.testing.assert_allclose(np.asarray(tied.output(x)),
                               np.asarray(plain.output(x)), rtol=1e-5)


def test_the_two_terms_reach_the_registry_through_the_fit_loop():
    from deeplearning4j_tpu.continuous.driver import StepDriver
    telemetry.reset()
    telemetry.enable()
    try:
        net = _tied_net()
        x, y = _batch(5)
        driver = StepDriver(net, lambda: iter([(x, y, None)] * 3))
        driver.run_round(3)
        driver.sync()
        reg = telemetry.get_registry()
        main = telemetry.series_map("train_loss_term_main")[""]
        mtp = telemetry.series_map("train_loss_term_mtp")[""]
        assert main == pytest.approx(
            float(net.state[-1]["loss_terms"]["main"]))
        assert mtp == pytest.approx(float(net.state[-1]["loss_terms"]["mtp"]))
        assert float(net.score_value) == pytest.approx(main + 0.3 * mtp,
                                                       rel=1e-5)
        # the module's mixture is among the routed layers the loop samples:
        # the head's state holds `loss_terms` BESIDE the block's under
        # `mtp`, and the one walk goes on past the first into the second
        assert reg.get("moe_assignments_sampled_total") is not None
        routed = [s for s in net.state if "moe_load" in s]
        routed.append(net.state[-1]["mtp"])
        assert len(routed) >= 2
        assert telemetry.series_map("moe_assignments_sampled_total")[""] == \
            sum(float(s["moe_load"].sum() + s["moe_elsewhere"].sum())
                for s in routed)
        assert telemetry.series_map("moe_load_hottest_rows")[""] == \
            sum(float(s["moe_load"].max()) for s in routed)
    finally:
        telemetry.disable()
        telemetry.reset()


def _two_walks(state):
    """What `note_routing` and `note_loss_terms` read until PR 54 made
    them one: each key's own walk, which stops at a dict that holds it."""
    def holding(state, key):
        if isinstance(state, dict):
            if key in state:
                yield state
            else:
                for v in state.values():
                    yield from holding(v, key)
        elif isinstance(state, (list, tuple)):
            for v in state:
                yield from holding(v, key)
    return ([(s["moe_load"], s["moe_elsewhere"])
             for s in holding(state, "moe_load")],
            [s["loss_terms"] for s in holding(state, "loss_terms")])


@pytest.mark.parametrize("state", [
    # a multi-token head: the terms beside the module's routed block
    [{}, {"moe_load": np.array([3., 1.]), "moe_elsewhere": np.array([2.])},
     {"mtp": {"moe_load": np.array([5., 0.]),
              "moe_elsewhere": np.array([1.])},
      "loss_terms": {"main": np.float32(2.0), "mtp": np.float32(3.0)}}],
    # a graph's vertices, a routed layer inside a layer's own dicts
    {"a": [{"mlp": {"moe_load": np.array([4., 4.]),
                    "moe_elsewhere": np.array([0.])}}],
     "head": {"loss_terms": {"main": np.float32(1.5)}}},
    # routed layers and no head that keeps terms; neither
    [{"moe_load": np.array([1., 2.]), "moe_elsewhere": np.array([3.])}],
    [{}, {"running_mean": np.zeros(3)}],
], ids=["mtp_head", "graph", "routed_only", "neither"])
def test_one_walk_of_the_state_reads_what_the_two_walks_read(state):
    routed, terms = _two_walks(state)
    telemetry.reset()
    telemetry.enable()
    try:
        telemetry.note_step_state(state)
        value = lambda name: telemetry.series_map(name).get("")
        here = sum(float(load.sum()) for load, _ in routed)
        away = sum(float(a.sum()) for _, a in routed)
        want = {
            "moe_rows_here_sampled_total": here,
            "moe_assignments_sampled_total": here + away,
            "moe_load_hottest_rows": sum(float(l.max()) for l, _ in routed),
            "moe_load_mean_rows": sum(float(l.mean()) for l, _ in routed),
        } if routed else dict.fromkeys((
            "moe_rows_here_sampled_total", "moe_assignments_sampled_total",
            "moe_load_hottest_rows", "moe_load_mean_rows"))
        for t in terms:
            want.update({f"train_loss_term_{k}": float(v)
                         for k, v in t.items()})
        assert {name: value(name) for name in want} == want
        if not terms:
            assert value("train_loss_term_main") is None
    finally:
        telemetry.disable()
        telemetry.reset()
