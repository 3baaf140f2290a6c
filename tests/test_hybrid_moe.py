"""The hybrid conv/attention mixture-of-experts vocabulary (ISSUE 32) at toy
size on the CPU: the routed-experts layer against the benchmark's plain
reference (the shares adding up to the uncut layer, all-here and none-here
routing, a bias that moves the selection and not the weights), the grouped
product against `jax.lax.ragged_dot`, the gated short convolution's
causality, grouped-query attention with QK-norm against repeat-and-attend,
the defaults leaving the older blocks as they were, serde, and a fit."""

import collections
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from deeplearning4j_tpu import models, telemetry
from deeplearning4j_tpu.continuous.driver import StepDriver
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import expert_ffn as ffn
from deeplearning4j_tpu.ops import moe_rows
from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul

D, F, E, K, N = 16, 24, 8, 2, 64
MODEL = {"num_experts_per_tok": K, "routed_scaling_factor": 1.0,
         "experts_held": (0, E)}


@pytest.fixture(scope="module")
def layer():
    """An uncut expert layer's float32 weights, a bias and token rows."""
    k = jax.random.split(jax.random.PRNGKey(3), 6)

    def nrm(key, scale, *shape):
        return scale * jax.random.normal(key, shape, jnp.float32)

    return {"u": nrm(k[0], 1.0, N, D), "bias": nrm(k[5], 0.1, E),
            "p": {"w_r": nrm(k[1], 0.3, D, E), "e_w1": nrm(k[2], 0.2, E, D, F),
                  "e_w3": nrm(k[3], 0.2, E, D, F),
                  "e_w2": nrm(k[4], 0.2, E, F, D)}}


def _share(p, first, end):
    return {"w_r": p["w_r"], **{n: p[n][first:end]
                                for n in ("e_w1", "e_w3", "e_w2")}}


def _system(u, p, bias, held, top_k=K, score="sigmoid"):
    return moe.routed_experts(
        u, p["w_r"], p["e_w1"], p["e_w3"], p["e_w2"], bias, top_k=top_k,
        held=held, scale=1.0, act=jax.nn.silu, score=score)


def test_the_shares_add_up_to_the_uncut_layer(layer):
    u, p, bias = layer["u"], layer["p"], layer["bias"]
    whole, load, away = ref.experts(u, p, bias, MODEL, "f32")
    assert float(away[0]) == 0 and float(load.sum()) == N * K
    parts, rows = [], 0.0
    for first in range(0, E, 2):
        held = (first, first + 2)
        y, here, elsewhere = _system(u, _share(p, *held), bias, held)
        want, want_here, _ = ref.experts(u, _share(p, *held), bias, MODEL,
                                         "f32", held=held)
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(here, want_here)
        assert float(here.sum() + elsewhere[0]) == N * K  # none dropped
        parts.append(y)
        rows += float(here.sum())
    assert rows == N * K
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    y, here, _ = _system(u, p, bias, (0, E))
    np.testing.assert_allclose(y, whole, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(here, load)


@pytest.mark.parametrize("push,rows_here", [(10.0, N * K), (-10.0, 0)],
                         ids=["all-here", "none-here"])
def test_every_token_or_none_routed_here_gives_the_references_result(
        layer, push, rows_here):
    """A bias that sends every assignment to the held experts, and one
    that sends none: the buffers are the same, the rows differ."""
    u, p = layer["u"], layer["p"]
    held = (2, 4)
    bias = jnp.zeros((E,)).at[2:4].set(push)
    share = _share(p, *held)

    def loss(share, f):
        y, here, away = f(share)
        return jnp.sum(y * y), (y, here, away)

    (_, (y, here, away)), g = jax.value_and_grad(loss, has_aux=True)(
        share, lambda s: _system(u, s, bias, held))
    (_, (want, want_here, want_away)), want_g = jax.value_and_grad(
        loss, has_aux=True)(
        share, lambda s: ref.experts(u, s, bias, MODEL, "f32", held=held))
    assert float(here.sum()) == rows_here == float(want_here.sum())
    assert float(away[0]) == N * K - rows_here == float(want_away[0])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    for name in share:
        assert np.all(np.isfinite(g[name]))
        np.testing.assert_allclose(g[name], want_g[name], rtol=2e-4,
                                   atol=2e-6)
    if not rows_here:
        assert float(jnp.abs(y).max()) == 0.0


def test_the_bias_moves_the_selection_and_not_the_weights(layer):
    u, p = layer["u"], layer["p"]
    bias = jnp.zeros((E,)).at[5].set(0.5)
    s = jax.nn.sigmoid(jnp.matmul(u, p["w_r"], precision="highest"))
    sel0, _ = ref.route(u, p["w_r"], jnp.zeros((E,)), MODEL)
    sel, w = ref.route(u, p["w_r"], bias, MODEL)
    assert int((sel == 5).sum()) > int((sel0 == 5).sum())
    picked = jnp.take_along_axis(s, sel, axis=-1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the system agrees, through the whole layer, and in the router's
    # gradient (which flows through the weights alone)
    f = lambda w_r: jnp.sum(_system(u, {**p, "w_r": w_r}, bias, (0, E))[0])
    f_ref = lambda w_r: jnp.sum(
        ref.experts(u, {**p, "w_r": w_r}, bias, MODEL, "f32")[0])
    np.testing.assert_allclose(jax.grad(f)(p["w_r"]),
                               jax.grad(f_ref)(p["w_r"]), rtol=2e-4,
                               atol=1e-6)


# the layer's widths; a contraction of 3072 (gate ‖ up at f 1536), which
# `_tiling` cuts into two tiles of 1536, in the product and, as the
# result's width, in its input gradient
@pytest.mark.parametrize("k_, n_", [(D, F), (3072, 128), (128, 3072)],
                         ids=["layer", "k3072", "n3072"])
def test_grouped_matmul_follows_the_groups(layer, k_, n_):
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (64, k_), jnp.float32)
    w = jax.random.normal(k[1], (4, k_, n_), jnp.float32)
    r = jax.random.normal(k[2], (64, n_), jnp.float32)
    sizes = jnp.array([17, 0, 30, 5], jnp.int32)   # 52 of 64 rows
    valid = (jnp.arange(64) < 52)[:, None]

    def through(f):
        return lambda x, w: jnp.sum(jnp.where(valid, f(x, w), 0.0) * r)

    def close(got, want):
        np.testing.assert_allclose(
            got, want, rtol=1e-5,
            atol=1e-5 * max(1.0, float(jnp.abs(want).max())))

    mine = through(lambda x, w: grouped_matmul(x, w, sizes, jnp.float32))
    want = through(lambda x, w: jax.lax.ragged_dot(
        x, w, sizes, precision="highest"))
    close(grouped_matmul(x, w, sizes, jnp.float32)[:52],
          jax.lax.ragged_dot(x, w, sizes, precision="highest")[:52])
    (dx, dw), (dx_w, dw_w) = (jax.grad(f, argnums=(0, 1))(x, w)
                              for f in (mine, want))
    close(dx[:52], dx_w[:52])
    close(dw, dw_w)
    assert float(jnp.abs(dw[1]).max()) == 0.0      # the empty group


def test_a_contraction_past_one_tile_is_cut_in_two_equal_ones():
    from deeplearning4j_tpu.ops import grouped_matmul as gm
    assert gm._tiling(512, 3072, 2048) == (512, 1536, 512)
    # no two whole lane tiles, or no longer than one, or two full ones:
    # the kernel's own cut
    assert gm._tiling(128, 2688, 1856)[1] == 2048
    assert gm._tiling(128, 1536, 2048)[1] == 1536
    assert gm._tiling(128, 4096, 512)[1] == 2048


def _sorted_case(sizes, n, k):
    """``n k`` assignments of which ``sum(sizes)`` fall to the held experts
    in those counts (the rest behind the sentinel), as the layer sorts
    them: order, its inverse, the rows here and who is here."""
    m, n_held = n * k, len(sizes)
    local = np.full((m,), n_held, np.int32)
    picks = np.random.RandomState(7).permutation(m)[:sum(sizes)]
    local[picks] = np.repeat(np.arange(n_held), sizes)
    local = jnp.asarray(local)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    return order, inv, jnp.int32(sum(sizes)), (local < n_held).reshape(n, k)


# 96 slots in tiles of 32: a boundary between groups inside a tile, an
# empty group, a lone row, every row
@pytest.mark.parametrize("sizes,dtype", [
    ((0, 0, 0), jnp.float32), ((0, 1, 0), jnp.float32),
    ((17, 0, 30), jnp.float32), ((25, 30, 10), jnp.float32),
    ((40, 16, 40), jnp.float32), ((17, 0, 30), jnp.float64)],
    ids=["none", "one-row", "uneven-with-an-empty-group",
         "boundary-inside-a-tile", "all", "float64"])
def test_the_row_movement_reads_and_moves_only_the_rows_inside_the_groups(
        monkeypatch, sizes, dtype):
    """Dispatch, combine and both backward passes against a plain
    take-and-sum, with NaN in every row of the sorted buffers from the
    count on: nothing there is read or reaches ``y``, ``dx`` or ``dw``."""
    monkeypatch.setattr(moe_rows, "_TILE", 32)
    n, k, d = 48, 2, 24
    order, inv, r, here = _sorted_case(sizes, n, k)
    rows = int(r)
    tok = order // k
    inside = (jnp.arange(n * k) < r)[:, None]
    key = jax.random.split(jax.random.PRNGKey(11), 5)
    x, dy = (jax.random.normal(kk, (n, d), dtype) for kk in key[:2])
    ys, dxs = (jax.random.normal(kk, (n * k, d), dtype) for kk in key[2:4])
    w = jax.random.uniform(key[4], (n, k), dtype, 0.1, 1.0)
    planted = lambda a: jnp.where(inside, a, jnp.nan)

    def take_and_sum(rows_, w_):
        picked = jnp.where(inside, rows_, 0)[inv].reshape(n, k, d)
        return jnp.sum(picked * jnp.where(here, w_, 0)[..., None], axis=1)

    xs, back = jax.vjp(lambda x: moe._dispatch(k, dtype, x, tok, r), x)
    np.testing.assert_array_equal(xs[:rows], x[tok][:rows])
    dx, = back(planted(dxs))
    want_dx, = jax.vjp(lambda x: x[tok], x)[1](jnp.where(inside, dxs, 0))
    np.testing.assert_allclose(dx, want_dx, rtol=1e-6, atol=1e-6)

    w_sorted = w.reshape(-1)[order]
    y, back = jax.vjp(lambda ys, w: moe._combine(ys, w, order, w_sorted, r),
                      planted(ys), w)
    want_y, want_back = jax.vjp(take_and_sum, ys, w)
    np.testing.assert_allclose(y, want_y, rtol=1e-6, atol=1e-6)
    dys, dw = back(dy)
    want_dys, want_dw = want_back(dy)
    np.testing.assert_allclose(dys[:rows], want_dys[:rows], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(jnp.where(here, 0, dw)).max()) == 0.0
    for a in (y, dx, dw):
        assert a.dtype == dtype and np.all(np.isfinite(a))


def test_the_row_kernels_walk_column_blocks_when_the_rows_do_not_fit(
        monkeypatch):
    """An [N, d] array too large to keep resident is taken 128 columns at
    a time (the benchmark's [8192, 2048] float32 is one block): the same
    results, the weights' products summed over the blocks."""
    n, k, d = 32, 2, 384
    assert moe_rows._columns(8192, 2048, 4) == 2048
    assert moe_rows._columns(16384, 2048, 4) == 1024
    monkeypatch.setattr(moe_rows, "_RESIDENT", n * 128 * 4)
    assert moe_rows._columns(n, d, 4) == 128
    order, inv, r, _ = _sorted_case((9, 0, 20), n, k)
    tok, rows = order // k, int(r)
    key = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(key[0], (n, d), jnp.float32)
    ys, other = (jax.random.normal(kk, (n * k, d), jnp.float32)
                 for kk in key[1:3])
    w = jax.random.uniform(key[3], (n * k,), jnp.float32, 0.1, 1.0)
    out, dots = moe_rows.rows_into_order(x, tok, r, jnp.float32, scale=w,
                                         other=other)
    np.testing.assert_allclose(out[:rows], (x[tok] * w[:, None])[:rows],
                               rtol=1e-6)
    np.testing.assert_allclose(
        dots[:rows], jnp.sum(other * x[tok], axis=-1)[:rows], rtol=1e-5,
        atol=1e-5)
    acc, kept = moe_rows.rows_back(ys, tok, r, n, w, jnp.float32,
                                   keep=jnp.float32)
    inside = (jnp.arange(n * k) < r)[:, None]
    np.testing.assert_allclose(
        acc, jnp.zeros((n, d)).at[tok].add(
            jnp.where(inside, ys * w[:, None], 0)), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(kept[:rows], ys[:rows])


def _count(jaxpr, found):
    """Equations of a jaxpr, and of the jaxprs inside them, for which
    ``found`` holds (a kernel's own body is not entered)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += bool(found(eqn))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += _count(sub, found)
    return total


def test_no_gather_of_every_slot_is_left_beside_the_kernels(layer):
    """The guard of ISSUE 33's mechanism: forward and backward of the
    routed layer hold no gather or scatter whose rows are the sorted
    buffer's (``N k`` rows of the model's width; the parent held five
    such gathers), and the four movements are the two kernels, twice
    each, whose grids skip the tiles past the rows here."""
    u, p, bias = layer["u"], layer["p"], layer["bias"]
    held = (2, 6)

    def loss(u, share):
        return jnp.sum(_system(u, share, bias, held)[0] ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        u, _share(p, *held)).jaxpr
    whole = lambda e: e.primitive.name in ("gather", "scatter-add") and any(
        v.aval.shape == (N * K, D) for v in (*e.invars, *e.outvars))
    assert _count(jaxpr, whole) == 0
    for name in ("moe_rows_fwd", "moe_rows_back"):
        assert _count(jaxpr, lambda e: e.primitive.name == "pallas_call"
                      and e.params["name"] == name) == 2


@jax.custom_vjp
def _indexed_combine(ys, w, order, inv, r):
    """``moe._combine`` as it stood until ISSUE 51: the weights brought
    into sorted order, and their gradient back, an index at a time."""
    return _indexed_combine_fwd(ys, w, order, inv, r)[0]


def _indexed_combine_fwd(ys, w, order, inv, r):
    tok, w_sorted = order // w.shape[1], w.reshape(-1)[order]
    y, kept = moe_rows.rows_back(ys, tok, r, w.shape[0], w_sorted, w.dtype,
                                 keep=ys.dtype)
    return y.astype(ys.dtype), (kept, w_sorted, tok, inv, r)


def _indexed_combine_bwd(res, dy):
    ys, w_sorted, tok, inv, r = res
    dys, dw_sorted = moe_rows.rows_into_order(dy, tok, r, ys.dtype,
                                              scale=w_sorted, other=ys)
    dw = jnp.where(inv < r, dw_sorted[inv], 0).reshape(dy.shape[0], -1)
    return dys, dw.astype(w_sorted.dtype), None, None, None


_indexed_combine.defvjp(_indexed_combine_fwd, _indexed_combine_bwd)


def _indexed_system(x, p, bias, held, score, seen):
    """`_system` with the four indexed expressions that ISSUE 51 replaced:
    ``bincount`` for the counts, ``w[order]`` and ``dw_sorted[inv]`` around
    the combine, and the selected scores, with their gradient, through
    ``top_k`` / ``take_along_axis``. The kernels are the layer's own.
    ``seen`` takes what the layer hands its combine."""
    first, end = held
    n_held, top_k = end - first, K
    logits = jnp.matmul(x, p["w_r"], precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        w, sel = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:
        s = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(s + bias, top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + moe.ROUTER_EPS)
    local = sel.reshape(-1).astype(jnp.int32) - first
    local = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    counts = jnp.bincount(local, length=n_held + 1).astype(jnp.int32)
    sizes = counts[:n_held]
    r = jnp.sum(sizes)
    seen.update(order=order, r=r,
                w_sorted=jax.lax.stop_gradient(w).reshape(-1)[order])
    xs = moe._dispatch(top_k, x.dtype, x, order // top_k, r)
    ys = ffn.expert_ffn(xs, p["e_w1"], p["e_w3"], p["e_w2"], sizes,
                        jax.nn.silu, x.dtype, xs.shape[0] // E)
    return _indexed_combine(ys, w, order, inv, r), sizes, counts[n_held:]


# the router's first input is a constant 1, so its first row of weights
# adds a number an expert to every token's logits under either score
# function (the sigmoid's bias follows it): every assignment here, none,
# and a held expert that gets no row
_PUSHED = {"as-drawn": ({}, None), "all-here": ({2: 12.0, 3: 12.0}, N * K),
           "none-here": ({2: -12.0, 3: -12.0, 4: -12.0, 5: -12.0}, 0),
           "an-empty-held-expert": ({3: -12.0}, None)}


@pytest.mark.parametrize("pushed,rows_here", _PUSHED.values(), ids=_PUSHED)
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_the_routing_without_indexing_gives_the_indexed_routings_bits(
        monkeypatch, layer, score, pushed, rows_here):
    """ISSUE 51's four expressions against the indexed ones they replace,
    through the whole layer and its gradients, operation by operation (no
    `jit`: a fusion may round otherwise): the counts, the stable order (16
    slots a tile, so equal keys lie across tile boundaries), the weights
    in sorted order, the result, and the gradients of the tokens, of the
    router (the weights' gradient back, then the selected scores') and of
    the experts, all `np.array_equal`."""
    monkeypatch.setattr(moe_rows, "_TILE", 16)
    held, bias = (2, 6), (None if score == "softmax" else layer["bias"])
    u = layer["u"].at[:, 0].set(1.0)
    share = _share(layer["p"], *held)
    for expert, push in pushed.items():
        share["w_r"] = share["w_r"].at[0, expert].set(push)
        if bias is not None:
            bias = bias.at[expert].set(float(np.sign(push)))
    handed, seen = {}, {}
    real = moe._combine

    def spy(ys, w, order, w_sorted, r):
        handed.update(order=order, w_sorted=w_sorted, r=r)
        return real(ys, w, order, w_sorted, r)

    monkeypatch.setattr(moe, "_combine", spy)

    def run(system, *more):
        def loss(u, share):
            y, here, away = system(u, share, bias, held, *more)
            return jnp.sum(y * jnp.cos(y)), (y, here, away)

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            u, share)

    got = run(_system, K, score)
    want = run(_indexed_system, score, seen)
    here, rows = want[0][1][1], int(seen["r"])
    assert rows == int(handed["r"]) == int(here.sum())
    if rows_here is None:
        assert 0 < rows < N * K and (not pushed or int(here[1]) == 0)
    else:
        assert rows == rows_here
    for name in ("order", "w_sorted"):
        np.testing.assert_array_equal(handed[name], seen[name])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and np.all(np.isfinite(a))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (float(jnp.abs(got[1][1]["w_r"]).max()) > 0) == (rows > 0)


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_nothing_beside_the_kernels_indexes_the_assignments(layer, score):
    """The guard of ISSUE 51's mechanism: forward and backward of the
    routed layer hold no ``gather``, ``scatter`` or ``scatter-add`` outside
    the kernels on anything as large as the tokens (XLA:TPU walks such an
    operation an index at a time; the parent held five: ``bincount``,
    ``w[order]``, ``dw_sorted[inv]`` and the selected scores with their
    gradient). What is left of those primitives is the grouped products'
    own bookkeeping, a few entries a group."""
    u, held = layer["u"], (2, 6)
    bias = None if score == "softmax" else layer["bias"]

    def loss(u, share):
        return jnp.sum(_system(u, share, bias, held, score=score)[0] ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        u, _share(layer["p"], *held)).jaxpr
    indexed = lambda e: e.primitive.name in (
        "gather", "scatter", "scatter-add") and any(
            v.aval.size >= N for v in (*e.invars, *e.outvars))
    assert _count(jaxpr, indexed) == 0
    assert _count(jaxpr, lambda e: e.primitive.name == "sort") == 2


def _three_products(xs, w_gate, w_up, w_down, sizes, act, out_dtype):
    """The expert FFN as `routed_experts` wrote it until PR 50: three
    grouped products with the activation between them as XLA's, and jax's
    own differentiation."""
    cd = xs.dtype
    u = grouped_matmul(xs, w_up, sizes, cd)
    if w_gate is None:
        h = act(u.astype(out_dtype)).astype(cd)
    else:
        g = grouped_matmul(xs, w_gate, sizes, cd)
        h = (act(g.astype(out_dtype)) * u.astype(out_dtype)).astype(cd)
    return grouped_matmul(h, w_down, sizes, out_dtype)


def _ffn_case(gated, dtype, m=96, d=16, f=24, groups=3):
    k = jax.random.split(jax.random.PRNGKey(13), 5)
    w = lambda key, *shape: 0.3 * jax.random.normal(key, shape, jnp.float32)
    return (jax.random.normal(k[0], (m, d), jnp.float32).astype(dtype),
            w(k[1], groups, d, f) if gated else None, w(k[2], groups, d, f),
            w(k[3], groups, f, d), jax.random.normal(k[4], (m, d),
                                                     jnp.float32))


_EXPERTS = {"gated-silu": (True, "silu"), "ungated-relu2": (False, "relu2")}
# 96 slots in tiles of 32: nothing here, rows in one tile with an empty
# group, every slot
_SIZES = {"none": (0, 0, 0), "one-tile": (17, 0, 10), "all": (40, 16, 40)}


@pytest.mark.parametrize("sizes", _SIZES.values(), ids=_SIZES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("experts", _EXPERTS.values(), ids=_EXPERTS)
def test_the_expert_ffn_is_the_three_products_with_the_activation_between(
        monkeypatch, experts, dtype, sizes):
    """`ops/expert_ffn.py` against the expression it replaced: the
    forward's bits on every row inside a group, and the four gradients
    (the input's now summed over gate and up in the product's float32
    accumulator and rounded once, where two rounded results were added)."""
    monkeypatch.setattr(ffn, "_TILE", 32)
    gated, act = experts[0], activations.get(experts[1])
    xs, w_gate, w_up, w_down, cot = _ffn_case(gated, dtype)
    sz, r = jnp.asarray(sizes, jnp.int32), sum(sizes)
    inside = (jnp.arange(xs.shape[0]) < r)[:, None]

    def through(fn):
        def loss(xs, w_gate, w_up, w_down):
            ys = fn(xs, w_gate, w_up, w_down, sz, act, jnp.float32)
            return jnp.sum(jnp.where(inside, ys, 0) * cot), ys
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3) if gated
                                  else (0, 2, 3), has_aux=True)

    (_, ys), mine = through(ffn.expert_ffn)(xs, w_gate, w_up, w_down)
    (_, want_ys), want = through(_three_products)(xs, w_gate, w_up, w_down)
    assert ys.dtype == jnp.float32 and ys.shape == xs.shape
    np.testing.assert_array_equal(np.asarray(ys[:r]), np.asarray(want_ys[:r]))
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for got, ref_ in zip(mine, want):
        assert got.dtype == ref_.dtype and got.shape == ref_.shape
        if got.shape == xs.shape:                     # dx: the rows held
            got, ref_ = got[:r], ref_[:r]
        got, ref_ = (np.asarray(a, np.float32) for a in (got, ref_))
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(
            got, ref_, rtol=tol, atol=tol * max(1.0, np.abs(ref_).max(
                initial=0.0)))
    if r == 0:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in mine[1:])
    elif sizes[1] == 0:                               # the empty group
        assert all(float(jnp.abs(g[1]).max()) == 0.0 for g in mine[1:])


@pytest.mark.parametrize("sizes", [(0, 0, 0), (17, 0, 10), (25, 30, 10)],
                         ids=["none", "one-tile", "boundary-inside-a-tile"])
@pytest.mark.parametrize("experts", _EXPERTS.values(), ids=_EXPERTS)
def test_nothing_past_the_rows_held_reaches_the_expert_ffns_results(
        monkeypatch, experts, sizes):
    """NaN in every row of the pre-activations and of `h`'s gradient from
    the count on, planted where the two activation kernels take their
    operands: the result's rows inside the groups and all four gradients
    are the unplanted run's to the bit."""
    monkeypatch.setattr(ffn, "_TILE", 32)
    gated, act = experts[0], activations.get(experts[1])
    xs, w_gate, w_up, w_down, cot = _ffn_case(gated, jnp.float32)
    sz, r = jnp.asarray(sizes, jnp.int32), sum(sizes)
    inside = (jnp.arange(xs.shape[0]) < r)[:, None]

    def run():
        ys, back = jax.vjp(lambda *a: ffn.expert_ffn(
            *a, sz, act, jnp.float32), xs, w_gate, w_up, w_down)
        grads = back(jnp.where(inside, cot, jnp.nan))
        return [ys[:r], grads[0][:r], *(g for g in grads[1:]
                                        if g is not None)]

    clean = run()
    kernels, calls = ffn._act_call, []

    def planted(kernel, name, r_, operands, widths, **kw):
        calls.append(name)
        return kernels(kernel, name, r_, [jnp.where(inside, a, jnp.nan)
                                          for a in operands], widths, **kw)

    monkeypatch.setattr(ffn, "_act_call", planted)
    dirty = run()
    assert calls == ["moe_act_fwd", "moe_act_bwd"]
    for a, b in zip(clean, dirty):
        assert np.all(np.isfinite(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _names_slots(jaxpr, shapes, found):
    """Into ``found``: the primitive of every equation outside a kernel
    that takes or gives an array of one of ``shapes`` and is neither a
    kernel nor a call that only hands its operands on."""
    passes_on = ("pallas_call", "pjit", "jit", "custom_vjp_call",
                 "custom_jvp_call", "checkpoint", "remat", "closed_call")
    for eqn in jaxpr.eqns:
        if eqn.primitive.name not in passes_on and any(
                getattr(v.aval, "shape", None) in shapes
                for v in (*eqn.invars, *eqn.outvars)):
            found.append(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _names_slots(sub, shapes, found)
    return found


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
@pytest.mark.parametrize("experts", _EXPERTS.values(), ids=_EXPERTS)
def test_nothing_under_the_experts_walks_every_slot(layer, experts,
                                                    recompute):
    """The guard of ISSUE 50's mechanism: forward and backward of the
    routed layer hold no operation outside a kernel on an [N k, f],
    [N k, 2 f] or [N k, d] array but the axis swap that `tgmm` swaps back
    (no activation pass, no cast, no sum of two input gradients), and the
    activation is the two kernels, once each (the forward's twice where
    the layer is made again in the backward pass)."""
    gated, act = experts[0], activations.get(experts[1])
    u, bias, held = layer["u"], layer["bias"], (2, 6)
    share = _share(layer["p"], *held)
    if not gated:
        share = {**share, "e_w1": None}

    def routed(u, share):
        return moe.routed_experts(
            u, share["w_r"], share["e_w1"], share["e_w3"], share["e_w2"],
            bias, top_k=K, held=held, scale=1.0, act=act)[0]

    if recompute:
        routed = jax.checkpoint(routed)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda u, share: jnp.sum(routed(u, share) ** 2), argnums=(0, 1)))(
            u, share).jaxpr
    slots = {(N * K, F), (N * K, 2 * F), (N * K, D)}
    assert set(_names_slots(jaxpr, slots, [])) <= {"transpose"}
    for name, times in (("moe_act_fwd", 1 + recompute), ("moe_act_bwd", 1)):
        assert _count(jaxpr, lambda e: e.primitive.name == "pallas_call"
                      and e.params["name"] == name) == times


def _moe_block(**kw):
    return L.TransformerBlock(
        n_out=D, mixer=None, activation="silu", norm="rms", norm_eps=1e-6,
        bias=False, ffn="moe", ffn_width=F, n_experts=E, top_k=K,
        experts_held=(2, 6), router="softmax", **kw)


def test_a_block_that_makes_its_experts_again_gives_the_same_gradients():
    """`recompute_moe` through the expert FFN's hand-written backward:
    `jax.checkpoint` runs its forward rule twice and keeps nothing of it,
    and the block's result and gradients are those of the block that
    keeps the residuals."""
    it = I.RecurrentType(D, 8)
    kept, again = _moe_block(), _moe_block(recompute_moe=True)
    p = kept.init(jax.random.PRNGKey(0), it)
    state = kept.init_state(it)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, D), jnp.float32)

    def through(block):
        def loss(p, x):
            y, s = block.apply(p, state, x, train=True)
            return jnp.sum(y ** 2), s["moe_load"]
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    (v0, load0), g0 = through(kept)
    (v1, load1), g1 = through(again)
    assert float(load0.sum()) > 0
    np.testing.assert_array_equal(np.asarray(load0), np.asarray(load1))
    np.testing.assert_allclose(v0, v1, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("ffn_kind", ["gated", "mlp"])
def test_a_dense_block_lowers_none_of_the_experts_kernels(ffn_kind):
    """The dense cells' blocks take nothing of ISSUE 50's change: forward
    and backward hold no kernel at all, so no `moe_act_*` one."""
    block = L.TransformerBlock(n_out=D, mixer=None, activation="silu",
                               norm="rms", bias=False, ffn=ffn_kind,
                               ffn_width=F)
    p = block.init(jax.random.PRNGKey(0), I.RecurrentType(D, 8))
    x = jnp.ones((2, 8, D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, x: jnp.sum(block.apply(p, {}, x)[0] ** 2)))(p, x).jaxpr
    assert _count(jaxpr, lambda e: e.primitive.name == "pallas_call") == 0
    routed, it = _moe_block(), I.RecurrentType(D, 8)
    with_experts = jax.make_jaxpr(
        lambda p, x: routed.apply(p, routed.init_state(it), x)[0])(
        routed.init(jax.random.PRNGKey(0), it), x)
    assert _count(with_experts.jaxpr,
                  lambda e: e.primitive.name == "pallas_call"
                  and e.params["name"] == "moe_act_fwd") == 1


def test_short_conv_is_causal_and_starts_from_zeros():
    conv = L.ShortConv(n_out=8, kernel=3)
    p = conv.init(jax.random.PRNGKey(0), I.RecurrentType(8, 12))
    p["conv_w"] = jax.random.normal(jax.random.PRNGKey(1), (8, 3))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 8))
    y, _ = conv.apply(p, {}, x)
    later = x.at[:, 7:].set(jax.random.normal(jax.random.PRNGKey(3),
                                              (2, 5, 8)))
    y2, _ = conv.apply(p, {}, later)
    np.testing.assert_array_equal(np.asarray(y[:, :7]), np.asarray(y2[:, :7]))
    assert not np.allclose(y[:, 7:], y2[:, 7:])
    # the first two positions: zeros before the sequence's start
    bcx = (x @ p["W_in"]).reshape(2, 12, 3, 8)
    gate_b, gate_c, xx = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    z, w = gate_b * xx, p["conv_w"]
    np.testing.assert_allclose(
        y[:, 0], (gate_c[:, 0] * (w[:, 2] * z[:, 0])) @ p["W_out"],
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, 1], (gate_c[:, 1] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1]))
        @ p["W_out"], rtol=1e-5, atol=1e-6)
    # and the plain reference's, a sequence at a time
    want = ref.short_conv(x[0], {"w_in": p["W_in"], "conv_w": w,
                                 "w_out": p["W_out"]}, "f32")
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)


def test_grouped_query_attention_with_qk_norm_is_repeat_and_attend():
    d, h, kv, dh, t = 16, 4, 2, 8, 12
    mha = L.MultiHeadAttention(n_out=d, n_heads=h, n_kv_heads=kv,
                               head_dim=dh, causal=True, bias=False,
                               rope_theta=1e4, qk_norm=True,
                               qk_norm_eps=1e-5)
    p = mha.init(jax.random.PRNGKey(0), I.RecurrentType(d, t))
    assert set(p) == {"Wq", "Wkv", "Wo", "q_gamma", "k_gamma"}
    assert p["Wq"].shape == (d, h * dh) and p["Wkv"].shape == (d, 2 * kv * dh)
    p["q_gamma"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (dh,))
    p["k_gamma"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (dh,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, t, d))
    y, _ = mha.apply(p, {}, x)
    model = {"n_head": h, "n_kv_head": kv, "head_dim": dh, "norm_eps": 1e-5,
             "rope_theta": 1e4}
    want = ref.attention(x[1], {
        "w_q": p["Wq"], "w_k": p["Wkv"][:, :kv * dh],
        "w_v": p["Wkv"][:, kv * dh:], "w_o": p["Wo"],
        "g_q": p["q_gamma"], "g_k": p["k_gamma"]}, model, "f32")
    np.testing.assert_allclose(y[1], want, rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="no multiple"):
        L.MultiHeadAttention(n_out=d, n_heads=4, n_kv_heads=3,
                             bias=False).init(jax.random.PRNGKey(0),
                                              I.RecurrentType(d, t))


def _primitives(jaxpr, into=None):
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    _primitives(getattr(sub.jaxpr, "jaxpr", sub.jaxpr), into)
                elif hasattr(sub, "eqns"):
                    _primitives(sub, into)
    return into


@pytest.mark.parametrize("block,keys,dots", [
    (L.TransformerBlock(n_out=16, mixer=L.MultiHeadAttention(
        n_out=16, n_heads=2, causal=True)),
     {"ln1", "mha", "ln2", "mlp_W1", "mlp_W2", "mlp_b1", "mlp_b2"}, 6),
    (L.TransformerBlock(n_out=16, mixer=L.MultiHeadAttention(
        n_out=16, n_heads=2, causal=True, bias=False, rope_theta=1e6,
        head_dim=8), activation="silu", norm="rms", norm_eps=1e-6,
        sandwich=True, bias=False, ffn="gated", ffn_width=24),
     {"ln1", "ln1_post", "mha", "ln2", "ln2_post", "mlp_Wg", "mlp_Wu",
      "mlp_Wd"}, 7),
], ids=["gpt2-block", "ouro-block"])
def test_the_defaults_leave_the_older_blocks_as_they_were(block, keys, dots):
    """The parameter trees and the matrix products of the two blocks the
    benchmark already ran: the new fields at their defaults add no leaf,
    no state and no product."""
    it = I.RecurrentType(16, 8)
    p = block.init(jax.random.PRNGKey(0), it)
    assert set(p) == keys
    assert set(p["mha"]) == ({"Wqkv", "Wo", "bqkv", "bo"} if block.bias
                             else {"Wqkv", "Wo"})
    assert block.init_state(it) == {}
    x = jnp.ones((2, 8, 16))
    jaxpr = jax.make_jaxpr(lambda p, x: block.apply(p, {}, x)[0])(p, x)
    counts = _primitives(jaxpr.jaxpr)
    assert counts["dot_general"] == dots
    assert not {"sort", "top_k", "ragged_dot", "pallas_call",
                "cumsum"} & set(counts)


def _toy_conf(**kw):
    args = dict(layer_types=("conv", "full_attention", "conv"),
                num_dense_layers=1, d_model=16, n_heads=4, n_kv_heads=2,
                head_dim=4, ffn_width=24, expert_width=12, n_experts=8,
                top_k=2, experts_held=(2, 6), seq_len=16)
    args.update(kw)
    return models.hybrid_moe_lm(32, **args)


def test_serde_round_trip_of_the_new_fields():
    conf = _toy_conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again == conf
    block = again.layers[2]
    assert (type(block.mixer).__name__, block.ffn, block.experts_held,
            block.top_k, block.mixer.n_kv_heads, block.mixer.qk_norm) == (
        "MultiHeadAttention", "moe", (2, 6), 2, 2, True)
    assert type(again.layers[1].mixer).__name__ == "ShortConv"
    assert again.layers[-1].has_bias is False
    # a configuration saved before the block held its mixer
    with pytest.raises(ValueError, match="MIGRATION.md"):
        L.TransformerBlock(n_out=16, mixer="short_conv").init(
            jax.random.PRNGKey(0), I.RecurrentType(16, 8))
    with pytest.raises(ValueError, match="experts_held"):
        L.TransformerBlock(n_out=16, bias=False, ffn="moe", n_experts=4,
                           experts_held=(2, 6)).init(
            jax.random.PRNGKey(0), I.RecurrentType(16, 8))


def test_hybrid_moe_lm_trains_through_fit_and_counts_its_routing():
    net = MultiLayerNetwork(_toy_conf())
    net.init()
    assert set(net.params[3]) == {"ln1", "conv", "ln2", "moe_router",
                                  "moe_Wg", "moe_Wu", "moe_Wd"}
    assert net.params[3]["moe_Wg"].shape == (4, 16, 12)
    assert set(net.params[-1]) == {"W"}
    assert set(net.state[2]) == {"expert_bias", "moe_load", "moe_elsewhere"}
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 32)
    y = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 32)
    bias = np.asarray(0.3 * jax.random.normal(jax.random.PRNGKey(2), (8,)))
    net.state[2]["expert_bias"] = jnp.asarray(bias)  # the step donates it
    first = net.score(x, y)
    telemetry.reset()
    telemetry.enable()
    try:
        net.fit(x, y, epochs=6)
        # the counters fill where the loop's round boundary already waits
        driver = StepDriver(net, lambda: itertools.cycle([(x, y, None)]))
        driver.run_round(2)
        driver.sync()
        driver.close_source()
        snap = telemetry.get_registry().snapshot()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert float(net.score_value) < first
    for s in net.state[2:4]:
        assert float(s["moe_load"].sum() + s["moe_elsewhere"][0]) == 2 * 16 * 2
    np.testing.assert_array_equal(net.state[2]["expert_bias"], bias)
    total = lambda name: sum(s["value"] for s in snap[name]["series"])
    assert total("moe_assignments_sampled_total") > 0
    assert 0 < total("moe_rows_here_sampled_total") < total("moe_assignments_sampled_total")
    assert total("moe_load_hottest_rows") >= total("moe_load_mean_rows") > 0
    out = net.output(x)
    assert out.shape == (2, 16, 32)
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)


def test_the_hybrid_step_carries_its_scopes_forward_and_backward():
    """`short_conv`, `moe`, `moe_route` and `moe_experts` as whole
    components of the lowered step's paths, outside and inside JAX's
    `transpose(`, the routed ones inside `moe`."""
    import re
    net = MultiLayerNetwork(_toy_conf())
    net.init()
    x = jnp.zeros((2, 16), jnp.int32)
    lowered = net.make_train_step(donate=False).lower(
        net.params, net.state, net.opt_state, x, x, 0,
        jax.random.PRNGKey(0), None)
    paths = set(re.findall(r'loc\("([^"]+)"',
                           lowered.as_text(debug_info=True)))

    def has(scope, backward):
        rx = re.compile(r"(?:^|[/(])" + scope + r"(?:$|[/)])")
        return any(rx.search(p) and ("transpose(" in p) == backward
                   for p in paths)

    for scope in ("short_conv", "moe", "moe_route", "moe_experts",
                  "moe/moe_route", "moe/moe_experts", "attn/short_conv",
                  "mlp/moe", r"L02\.TransformerBlock"):
        assert has(scope, False) and has(scope, True), scope
