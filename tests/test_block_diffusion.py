"""Block-diffusion training (ISSUE 49): the score mask's geometry in the
flash kernels (interpret mode) against the dense-mask XLA path, forward
and gradients in both backward forms; the walk's tiles against the dense
mask tile by tile, with no dead tile visited and none fetched; the causal
call's jaxpr as it was before the kernels learned the geometry; `rope`
with positions; the draw; the loss mask a layer hands on; the two layers
and the factory."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import (MultiLayerConfiguration,
                                                NeuralNetConfig)
from deeplearning4j_tpu.nn.layers import attention, base, block_diffusion
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import attention_pallas as ap


def _qkv(t2, h, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    return [jax.random.normal(k, (1, t2, h, d), dtype) for k in ks]


# ---------------------------------------------------------------- kernels

@pytest.mark.parametrize("t,block_len,block", [
    (256, 4, 128), (256, 16, 256), (512, 4, 256), (512, 32, 128)])
def test_the_kernels_agree_with_the_dense_mask(t, block_len, block):
    """Forward and `jax.grad` (the fused backward) under the geometry,
    against the XLA path under the same geometry as a dense boolean mask."""
    g = ap.BlockDiffusion(t, block_len)
    q, k, v, ct = _qkv(2 * t, 2, 128)

    def kernel(q, k, v):
        return ap.flash_attention(q, k, v, geometry=g, block_q=block,
                                  block_k=block, interpret=True)

    def dense(q, k, v):
        return attention.dot_product_attention(q, k, v, geometry=g)

    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_the_split_backward_agrees_with_the_fused_one():
    """Past the VMEM budget `_run_bwd` takes a dK/dV and a dQ kernel: both
    walk the geometry as the fused one does."""
    t, g = 256, ap.BlockDiffusion(256, 4)
    q, k, v, ct = (ap._fold_heads(x) for x in _qkv(2 * t, 2, 128))
    out, lse = ap._run_fwd(q, k, v, None, 2, False, 128 ** -0.5, 128, 128,
                           True, geometry=g)
    fused, split = (ap._run_bwd_local(
        q, k, v, out, lse, ct, None, None, 2, False, 128 ** -0.5, 128, 128,
        True, form, g) for form in ("fused", "split"))
    for a, b in zip(fused, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert float(jnp.abs(fused[0]).max()) > 0.1


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("t,block_len,block", [(256, 4, 128), (512, 32, 256),
                                               (128, 8, 128)])
def test_every_backward_form_agrees_with_the_dense_mask(t, block_len, block,
                                                        form):
    """ISSUE 53: the three backward kernels on the step list, key-major
    ("fused", "dkv": a noised key block's ONE live step opens and closes
    it) and query-major ("dq"), with a cotangent on the log-sum-exp too
    (`flash_attention_block`'s path), against autodiff of the dense-mask
    softmax. At (128, 8, 128) each copy is one tile: three steps a head."""
    g = ap.BlockDiffusion(t, block_len)
    q, k, v, ct = (ap._fold_heads(x) for x in _qkv(2 * t, 2, 128))
    ct_lse = jax.random.normal(jax.random.PRNGKey(9), (2, 2 * t), q.dtype)
    scale = 128 ** -0.5

    def dense(q, k, v):
        s = jnp.where(g.dense()[None], jnp.einsum("bqd,bkd->bqk", q, k)
                      * scale, -jnp.inf)
        return (jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))
    (out, lse), pullback = jax.vjp(dense, q, k, v)
    got_out, got_lse = ap._run_fwd(q, k, v, None, 2, False, scale, block,
                                   block, True, geometry=g)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(out),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(lse),
                               atol=2e-5)
    got = ap._run_bwd_local(q, k, v, got_out, got_lse, ct, ct_lse, None, 2,
                            False, scale, block, block, True, form, g)
    for a, b, name in zip(got, pullback((ct, ct_lse)), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=name)


def _walk(monkeypatch, geometry, block):
    """{(iq, j): the tile's kind} of the steps that issue a product, by
    running the kernels' own walk with Python integers for the grid."""
    monkeypatch.setattr(ap, "_when",
                        lambda pred, fn: fn() if bool(pred) else None)
    n = 2 * geometry.seq_len // block
    ran = {}
    for iq in range(n):
        for j in range(n):
            def tile(kind, key_masks, at=(iq, j)):
                def run():
                    assert at not in ran and not key_masks
                    ran[at] = ap._cut(kind)
                return run
            ap._walk_tiles(tile, False, False, False, iq, j, block, block,
                           2 * geometry.seq_len, geometry)
    return ran


@pytest.mark.parametrize("t,block_len,block", [(512, 4, 128), (512, 64, 128),
                                               (1024, 4, 512)])
def test_the_walk_is_the_dense_mask_tile_by_tile(monkeypatch, t, block_len,
                                                 block):
    """A tile runs if and only if the dense mask has a live pair in it; it
    runs unmasked (`None`) if and only if every pair is live; and the
    pieces a cut tile keeps, under the mask it builds, are the dense
    mask's entries."""
    g = ap.BlockDiffusion(t, block_len)
    dense = np.asarray(g.dense())
    ran = _walk(monkeypatch, g, block)
    n = 2 * t // block
    for iq in range(n):
        for j in range(n):
            tile = dense[iq * block:(iq + 1) * block,
                         j * block:(j + 1) * block]
            assert ((iq, j) in ran) == bool(tile.any()), (iq, j)
            if tile.any():
                assert (ran[iq, j] is None) == bool(tile.all()), (iq, j)
    # the cut tiles' own masks, piece by piece, rebuild the dense tile
    sub, shift = ap._sub_tile(block), block_len.bit_length() - 1
    half = t // block
    for (iq, j), kind in ran.items():
        if kind is None:
            continue
        cut = (kind, shift) if kind == "same" else (
            kind, shift, 0 if iq < half else 1)
        got = np.zeros((block, block), bool)
        for r0, c0 in ap._pieces(block, block, sub, sub, cut):
            valid = ap._piece_valid(cut, None, None, iq, j, block, block,
                                    r0, c0, sub, sub)
            got[r0:r0 + sub, c0:c0 + sub] = True if valid is None \
                else np.asarray(valid).T
        np.testing.assert_array_equal(
            got, dense[iq * block:(iq + 1) * block,
                       j * block:(j + 1) * block])


def test_no_dead_tile_is_visited_at_the_cells_length(monkeypatch):
    """ISSUE 49's count: at T 4,096 and 128-wide tiles no more than
    (T^2 + 2 T x 128) / 128^2 grid steps a head issue a product, of the
    (2T / 128)^2 the grid has."""
    t = 4096
    steps = ap.step_list(False, ap.BlockDiffusion(t, 4), 64, 64, 128, 128)
    ran = _walk(monkeypatch, ap.BlockDiffusion(t, 4), 128)
    assert len(ran) == (t * t + 2 * t * 128) // 128 ** 2 == 1088
    assert (2 * t // 128) ** 2 == 4096
    # the grid a head walks is the list: 1,088 steps, not 4,096
    assert (steps.live, steps.rectangle) == (1088, 4096)


@pytest.mark.parametrize("t,block", [(512, 128), (1024, 512)])
def test_the_list_holds_the_live_steps_and_no_other(monkeypatch, t, block):
    """The grid is the step list (ISSUE 53: the index maps that named a
    live block again for a dead step went with the dead steps): in both
    orders it holds the tiles the walk runs, each once, an outer block's
    steps together, the first of them opening it and the last closing."""
    g = ap.BlockDiffusion(t, 4)
    n = 2 * t // block
    lists = [ap.step_list(False, g, n, n, block, block, key_major=km)
             for km in (False, True)]
    ran = _walk(monkeypatch, g, block)
    for steps, outer in zip(lists, (0, 1)):
        pairs = list(zip(steps.qi.tolist(), steps.kj.tolist()))
        assert sorted(pairs) == sorted(ran) and steps.rectangle == n * n
        assert pairs == sorted(pairs, key=lambda p: (p[outer], p[1 - outer]))
        of = [p[outer] for p in pairs]
        for s, edge in enumerate(steps.edge.tolist()):
            assert bool(edge & 1) == (s == 0 or of[s - 1] != of[s])
            assert bool(edge & 2) == (s == len(of) - 1 or of[s + 1] != of[s])


def test_a_geometry_that_does_not_fit_is_refused_or_left_to_xla(
        kernel_dispatch):
    g = ap.BlockDiffusion(192, 4)              # a copy is 1.5 tiles of 128
    q = jnp.zeros((1, 384, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="BlockDiffusion.fits"):
        ap.flash_attention(q, q, q, geometry=g, block_q=128, block_k=128,
                           interpret=True)
    with pytest.raises(ValueError, match="whole score mask"):
        ap.flash_attention(q, q, q, geometry=ap.BlockDiffusion(128, 4),
                           causal=True, block_q=128, block_k=128,
                           interpret=True)
    with kernel_dispatch():
        assert ap.resolve_attention(q.shape, q.shape, None, q.dtype,
                                    g) is None
        fine = ap.BlockDiffusion(512, 4)
        shape = (1, 1024, 2, 128)
        assert ap.resolve_attention(shape, shape, None, q.dtype,
                                    fine) == (512, 512)
        assert ap.resolve_attention(shape, shape, jnp.ones((1, 1024)),
                                    q.dtype, fine) is None
        # blocks of 3 tokens straddle the pieces; 2T is not the call
        assert ap.resolve_attention(shape, shape, None, q.dtype,
                                    ap.BlockDiffusion(512, 3)) is None
        assert ap.resolve_attention(shape, shape, None, q.dtype,
                                    ap.BlockDiffusion(1024, 4)) is None


#: sha256 of `str(make_jaxpr(grad(causal flash_attention)))` at two shapes:
#: the causal call's kernels, forward and fused backward. Taken anew in PR
#: 53, which edited them on purpose (the grid is the step list: three
#: scalar-prefetched operands, the opening and closing conditions read
#: from it; the hashes of PR 49, equal from 8c8f25e to PR 52, were
#: 09631d0f... and 7c12fea2...). A PR that edits the causal kernels on
#: purpose takes the hashes anew and says so.
CAUSAL_JAXPRS = {
    (512, 64, 256):
        "2de49a0b91bb7ee2ff149db031ac33aab28c39d9f2231a200d80d48c87c9cb43",
    (300, 128, 128):
        "569143fdc77f1c4cbefc650c73e1ef1ba9e2634a8bc856066cb5830a9653045b"}


@pytest.mark.parametrize("shape", sorted(CAUSAL_JAXPRS))
def test_a_causal_call_traces_to_the_jaxpr_it_did_before(shape):
    t, d, block = shape
    with jax.enable_x64(False):
        q = jnp.zeros((1, t, 2, d), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(ap.flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                interpret=True))
        text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert "flash_attn_fwd" in text and "flash_attn_bwd_fused" in text
    assert "flash_attn_bd" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == CAUSAL_JAXPRS[shape]


def test_the_kernels_under_a_geometry_carry_names_of_their_own():
    g = ap.BlockDiffusion(128, 4)
    q = jnp.zeros((1, 256, 1, 128), jnp.float32)

    def loss(q):
        return jnp.sum(ap.flash_attention(q, q, q, geometry=g, block_q=128,
                                          block_k=128, interpret=True))
    text = str(jax.make_jaxpr(jax.grad(loss))(q))
    assert "name=flash_attn_bd_fwd" in text
    assert "name=flash_attn_bd_bwd_fused" in text
    assert "name=flash_attn_fwd" not in text


# ------------------------------------------------------------------- rope

def _rope_before(x, theta):
    """`rope` as it stood before it took positions."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rope_without_positions_is_bit_equal_to_what_it_was(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16), dtype)
    np.testing.assert_array_equal(np.asarray(attention.rope(x, 1e6)),
                                  np.asarray(_rope_before(x, 1e6)))
    np.testing.assert_array_equal(
        np.asarray(attention.rope(x, 1e4, rotary_dim=8)),
        np.asarray(jnp.concatenate([_rope_before(x[..., :8], 1e4),
                                    x[..., 8:]], -1)))
    np.testing.assert_array_equal(
        np.asarray(attention.rope(x, 1e6, positions=jnp.arange(24))),
        np.asarray(attention.rope(x, 1e6)))


def test_rope_turns_both_copies_by_the_same_positions():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16), jnp.float32)
    twice = jnp.concatenate([x, x], axis=1)
    got = attention.rope(twice, 1e6, positions=jnp.tile(jnp.arange(8), 2))
    np.testing.assert_array_equal(np.asarray(got[:, :8]),
                                  np.asarray(got[:, 8:]))
    np.testing.assert_array_equal(np.asarray(got[:, :8]),
                                  np.asarray(attention.rope(x, 1e6)))


# --------------------------------------------------------------- the draw

def test_the_draw():
    """The mean masked share within 3 sigma of E[t]; a block's tokens
    share one level; two steps differ; the same counter repeats."""
    b, t, block = 4, 4096, 4
    masked, level = block_diffusion.draw_noise(49, 0, b, t, block, 1e-3)
    assert masked.shape == level.shape == (b, t)
    assert masked.dtype == jnp.bool_ and level.dtype == jnp.float32
    blocks = np.asarray(level).reshape(b, t // block, block)
    assert (blocks == blocks[..., :1]).all()
    assert len(np.unique(blocks[..., 0])) > 0.99 * b * t / block
    assert 1e-3 <= blocks.min() and blocks.max() <= 1.0
    # E[m] = E[t] = eps + (1 - eps) / 2; Var[mean m] <= 1 / (4 n) a token,
    # and a block's tokens share t: allow the blocks' variance, n = B T / L
    n = b * t / block
    assert abs(float(masked.mean()) - (1e-3 + 0.999 / 2)) < 3 * (
        0.25 / n) ** 0.5 * 1.2
    again, _ = block_diffusion.draw_noise(49, 0, b, t, block, 1e-3)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(again))
    other, other_level = block_diffusion.draw_noise(49, 1, b, t, block, 1e-3)
    assert 0.4 < float((other != masked).mean()) < 0.6
    assert float(jnp.abs(other_level - level).mean()) > 0.2
    seeded, _ = block_diffusion.draw_noise(50, 0, b, t, block, 1e-3)
    assert float((seeded != masked).mean()) > 0.4
    # the counter may be a traced int32, as the layer's state hands it
    traced, _ = jax.jit(lambda n: block_diffusion.draw_noise(
        49, n, b, t, block, 1e-3))(jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(other))


def test_the_input_layer_noises_doubles_counts_and_hands_weights_on():
    """The copies lie as `BlockDiffusion` says (`join`, `noised_rows`,
    `positions`: the one place that knows), the counter moves by one."""
    layer = L.BlockDiffusionInput(seq_len=16, block_len=4, mask_id=99,
                                  noise_seed=5)
    state = layer.init_state(I.RecurrentType(1, 16))
    assert state["noise_step"].dtype == jnp.int32
    x = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 90, jnp.int32)
    same, untouched = layer.apply({}, state, x, train=False)
    assert same is x and untouched is state
    y, new = layer.apply({}, state, x, train=True)
    masked, level = block_diffusion.draw_noise(5, 0, 3, 16, 4, 1e-3)
    assert y.shape == (3, 32) and y.dtype == x.dtype
    geometry = ap.BlockDiffusion(16, 4)
    np.testing.assert_array_equal(np.asarray(y[:, 16:]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(geometry.noised_rows(y)),
                                  np.where(np.asarray(masked), 99,
                                           np.asarray(x)))
    np.testing.assert_array_equal(np.asarray(geometry.join(y[:, :16], x)),
                                  np.asarray(y))
    np.testing.assert_array_equal(np.asarray(geometry.positions()),
                                  np.tile(np.arange(16), 2))
    np.testing.assert_allclose(np.asarray(new["loss_mask"]),
                               np.asarray(masked / level), rtol=1e-6)
    assert int(new["noise_step"]) == 1
    assert set(new) == {"noise_step", "loss_mask"}
    _, after = layer.apply({}, {k: new[k] for k in state}, x, train=True)
    assert int(after["noise_step"]) == 2
    assert (np.asarray(after["loss_mask"])
            != np.asarray(new["loss_mask"])).any()
    with pytest.raises(ValueError, match="fed mask"):
        layer.apply({}, state, x, train=True, mask=jnp.ones((3, 16)))
    with pytest.raises(ValueError, match="seq_len"):
        layer.apply({}, state, x[:, :12], train=True)


# ---------------------------------------------------------- the mask flow

def _tagger(weights):
    """A layer that hands `weights` on to the loss, as a test double."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Tagger(base.Layer):
        input_family = I.RecurrentType
        hands_loss_mask = True

        def apply(self, params, state, x, *, train=False, rng=None):
            return x, ({**state, "loss_mask": weights} if train else state)
    return Tagger()


def _rnn_net(*extra):
    conf = NeuralNetConfig(seed=3).list(
        *extra, L.RnnOutputLayer(n_out=5, loss="mcxent"),
        input_type=I.RecurrentType(4, 6))
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def test_the_mask_flow_with_an_ordinary_output_layer():
    """A fed mask reaches an ordinary output layer as it always did; a
    mask a layer hands on reaches it as per-position weights where no
    label mask is fed, is applied by no layer on the way, leaves no trace
    in the state, and yields to a fed label mask."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 4))
    y = jax.nn.one_hot(jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                          5), 5)
    fed = jnp.asarray([[1, 1, 1, 0, 0, 0], [1, 1, 1, 1, 1, 0]], jnp.float32)
    w = jnp.asarray([[2.0, 0, 0, 1, 0, 0], [0, 0.5, 0, 0, 0, 3]])
    plain, tagged = _rnn_net(), _rnn_net(_tagger(w))
    tagged.params = [{}, *plain.params]

    def loss(net, **kw):
        value, (state, _) = net.loss_fn(net.params, net.state, x, y, **kw)
        assert all("loss_mask" not in s for s in state)
        return float(value)

    probs = plain.output(x)
    per = -np.sum(np.asarray(y) * np.log(np.asarray(probs)), -1)
    assert loss(plain) == pytest.approx(per.mean(), rel=1e-6)
    assert loss(plain, mask=fed) == pytest.approx(
        (per * np.asarray(fed)).sum() / float(fed.sum()), rel=1e-6)
    assert loss(tagged) == pytest.approx(
        (per * np.asarray(w)).sum() / float(w.sum()), rel=1e-6)
    assert loss(tagged, label_mask=fed) == pytest.approx(
        loss(plain, mask=fed), rel=1e-6)
    # outside training the double hands nothing on
    assert loss(tagged, train=False) == pytest.approx(loss(plain), rel=1e-6)
    # the fit loop's step carries it too
    step = tagged.make_train_step(donate=False)
    out = step(tagged.params, tagged.state, tagged.opt_state, x, y, 0,
               jax.random.PRNGKey(0))
    assert float(out[3]) == pytest.approx(loss(tagged), rel=1e-6)
    assert jax.tree_util.tree_structure(out[1]) == \
        jax.tree_util.tree_structure(tagged.state)


def test_pop_loss_mask_takes_the_last_one_and_cleans_the_states():
    a, b = jnp.ones((2, 3)), jnp.zeros((2, 3))
    states = [{"k": 1, "loss_mask": a}, {}, {"loss_mask": b, "n": 2}, None]
    mask, cleaned = base.pop_loss_mask(states)
    assert mask is b and cleaned == [{"k": 1}, {}, {"n": 2}, None]
    assert "loss_mask" in states[0]         # the argument is left alone
    assert base.pop_loss_mask([{}, {"x": 1}]) == (None, [{}, {"x": 1}])


# --------------------------------------------------- the layers, the model

def _toy(**kw):
    args = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                expert_width=16, n_experts=8, top_k=2, experts_held=(2, 6),
                block_len=4, seq_len=16, noise_seed=7)
    return models.block_diffusion_moe_lm(64, **{**args, **kw})


def test_the_factory_builds_a_network_that_trains_and_round_trips():
    conf = _toy()
    assert [type(l).__name__ for l in conf.layers] == [
        "BlockDiffusionInput", "EmbeddingSequenceLayer", "TransformerBlock",
        "TransformerBlock", "RMSNorm", "BlockDiffusionLMOutputLayer"]
    assert conf.layers[0].mask_id == 63
    mixer = conf.layers[2].mixer
    assert mixer.block_diffusion == (16, 4) and mixer.causal
    assert MultiLayerConfiguration.from_json(conf.to_json()) == conf
    net = MultiLayerNetwork(conf)
    net.init()
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64, jnp.int32)
    losses = []
    for _ in range(3):
        net.fit(x, x)
        losses.append(float(net.score_value))
    assert all(np.isfinite(losses)) and len(set(losses)) == 3
    assert int(net.state[0]["noise_step"]) == 3
    assert set(net.state[0]) == {"noise_step"}
    assert net.output(x).shape == (2, 16, 64)
    assert np.isfinite(net.score(x, x))


def test_outside_training_the_network_is_the_plain_causal_decoder():
    """`train=False`: the input layer passes the ids through and every
    attention layer is what it is without the field."""
    conf = _toy()
    net = MultiLayerNetwork(conf)
    net.init()
    import dataclasses
    plain_blocks = [dataclasses.replace(
        b, mixer=dataclasses.replace(b.mixer, block_diffusion=()))
        for b in conf.layers[2:4]]
    plain = MultiLayerNetwork(dataclasses.replace(
        conf, layers=(*conf.layers[:2], *plain_blocks, *conf.layers[4:])))
    plain.init()
    plain.params = net.params
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64, jnp.int32)
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(plain.output(x)), rtol=1e-6)
    # causal: a later token does not move an earlier position's output
    moved = x.at[:, 9].set((x[:, 9] + 1) % 64)
    np.testing.assert_allclose(np.asarray(net.output(moved))[:, :9],
                               np.asarray(net.output(x))[:, :9], rtol=1e-6)


def test_the_attention_layer_under_the_geometry():
    """In training, 2 seq_len positions: the dense-mask attention at
    positions [0..T-1, 0..T-1], and any other length is refused. Outside
    training the layer is what it is without the field AT ANY LENGTH: 2
    seq_len positions are one plain causal sequence, and a length between
    is no error (the input layer decides the doubling, by `train`; the
    attention does not guess it from the shape)."""
    import dataclasses
    mha = L.MultiHeadAttention(
        n_out=32, n_heads=4, causal=True, bias=False, rope_theta=1e4,
        head_dim=8, n_kv_heads=2, qk_norm=True, block_diffusion=(8, 4))
    params = mha.init(jax.random.PRNGKey(0), I.RecurrentType(32, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y, _ = mha.apply(params, {}, x, train=True)
    # the clean copy's rows do not depend on the noised copy's
    other = x.at[:, :8].set(0.0)
    y2, _ = mha.apply(params, {}, other, train=True)
    np.testing.assert_allclose(np.asarray(y[:, 8:]), np.asarray(y2[:, 8:]),
                               rtol=1e-6, atol=1e-9)
    # a noised block reads itself and the clean blocks BEFORE it: noised
    # block 0 (rows 0-3) sees nothing of the clean copy
    y3, _ = mha.apply(params, {}, x.at[:, 8:].set(0.0), train=True)
    np.testing.assert_allclose(np.asarray(y[:, :4]), np.asarray(y3[:, :4]),
                               rtol=1e-6, atol=1e-9)
    assert float(jnp.abs(y[:, 4:8] - y3[:, 4:8]).max()) > 1e-3
    with pytest.raises(ValueError, match="the two copies"):
        mha.apply(params, {}, jnp.zeros((2, 12, 32)), train=True)
    plain = dataclasses.replace(mha, block_diffusion=())
    for t in (8, 12, 16):
        got, _ = mha.apply(params, {}, x[:, :t])
        want, _ = plain.apply(params, {}, x[:, :t], train=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(mha.apply(params, {}, x)[0] - y).max()) > 1e-3


def test_recomputing_the_experts_changes_nothing_of_the_result():
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64, jnp.int32)
    out = []
    for recompute in (False, True):
        conf = _toy(recompute_experts=recompute)
        assert conf.layers[2].recompute_moe is recompute
        net = MultiLayerNetwork(conf)
        net.init()
        out.append(jax.jit(lambda p, s, n=net: n.compute_gradients(
            p, s, x, x, rng=jax.random.PRNGKey(0)))(net.params, net.state))
    (loss_a, state_a, grads_a), (loss_b, state_b, grads_b) = out
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves((state_a, grads_a)),
                    jax.tree_util.tree_leaves((state_b, grads_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-8)


def test_the_output_layer_reads_the_noised_rows_and_the_weights():
    head = L.BlockDiffusionLMOutputLayer(n_out=11)
    params = head.init(jax.random.PRNGKey(0), I.RecurrentType(6, 8))
    feats = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 6))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 11,
                                jnp.int32)
    w = jnp.asarray([[0.0, 2.0, 0.0, 1.5], [4.0, 0.0, 0.0, 0.0]])
    loss, _, _ = head.loss_from_features(params, {}, feats, labels, w)
    z = np.asarray(feats[:, :4] @ params["W"])
    ce = (np.log(np.exp(z).sum(-1))
          - np.take_along_axis(z, np.asarray(labels)[..., None], -1)[..., 0])
    assert float(loss) == pytest.approx((ce * np.asarray(w)).sum() / 8,
                                        rel=1e-6)
    # the clean copy's rows carry no logits: they do not move the loss
    moved, _, _ = head.loss_from_features(
        params, {}, feats.at[:, 4:].set(9.0), labels, w)
    assert float(moved) == float(loss)
    plain, _, _ = head.loss_from_features(params, {}, feats[:, :4], labels)
    assert float(plain) == pytest.approx(ce.mean(), rel=1e-6)
    with pytest.raises(ValueError, match="neither T nor 2T"):
        head.loss_from_features(params, {}, feats[:, :6], labels, w)
    with pytest.raises(TypeError, match="integer labels"):
        head.loss_from_features(params, {}, feats, w, w)


@pytest.mark.parametrize("path", ["tbptt", "fsdp_stream", "pipeline"])
def test_a_loss_path_that_does_not_read_the_weights_refuses_the_layer(
        path, eight_devices):
    """`loss_mask` is popped in `MultiLayerNetwork.loss_fn` alone: the
    truncated-BPTT step, the streamed loss and the pipelined network would
    drop the weights and carry the key in the state, so they refuse a
    layer that hands one on (`hands_loss_mask`), whatever the head."""
    net = _rnn_net(_tagger(jnp.ones((2, 6))))
    assert net.conf.layers[0].hands_loss_mask
    assert L.BlockDiffusionInput(seq_len=8).hands_loss_mask
    with pytest.raises(ValueError, match="hands the loss"):
        if path == "tbptt":
            net.make_tbptt_step()
        elif path == "fsdp_stream":
            from deeplearning4j_tpu.parallel import ParallelTrainer
            ParallelTrainer(net, shard_params="fsdp_stream").init()
        else:
            from deeplearning4j_tpu.parallel.pipeline_general import \
                PipelinedNetwork
            from jax.sharding import Mesh
            PipelinedNetwork(net.conf, Mesh(np.array(eight_devices[:2]),
                                            ("stage",)), n_microbatches=2)


def test_no_square_score_array_in_the_lowered_step(monkeypatch):
    """The whole train step, lowered for the TPU with the dispatch as on
    the chip: the attention is the kernels under the geometry, and no
    [.., 2T, 2T] array exists anywhere in it."""
    monkeypatch.setattr(ap, "backend_is_tpu", lambda: True)
    conf = models.block_diffusion_moe_lm(
        256, n_layers=1, d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
        expert_width=128, n_experts=8, top_k=2, experts_held=(0, 4),
        seq_len=512, recompute_experts=True)
    net = MultiLayerNetwork(conf)
    with jax.enable_x64(False):
        params, state = jax.eval_shape(lambda: net.init())
        opt = jax.eval_shape(conf.updater.init, params)
        x = jax.ShapeDtypeStruct((1, 512), jnp.int32)
        text = net.make_train_step().trace(
            params, state, opt, x, x, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "flash_attn_bd_fwd"' in text
    assert 'kernel_name = "flash_attn_bd_bwd_fused"' in text
    assert "1024x1024" not in text
