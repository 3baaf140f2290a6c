"""Plain reference of ResNet-50 (He et al., arXiv:1512.03385, table 1,
50-layer column, ImageNet 224x224): 7x7/2 stem convolution, 3x3/2 max
pool, four stages of [3, 4, 6, 3] bottleneck blocks (1x1 reduce, 3x3, 1x1
expand by 4; the stride-2 of a stage sits on its first 1x1, as in the
paper; a projection shortcut where the shape changes), batch normalisation
after every convolution, global average pool, a 1000-way linear layer,
mean softmax cross-entropy. NHWC, float32 `jax.numpy`/`lax` convolutions
under precision "highest"; nothing imported from the program.

Training-mode batch normalisation: statistics of the batch (biased
variance), eps 1e-5, running statistics kept with decay 0.9 (the DL4J zoo
model's setting; the paper does not give one).

`precision` (`lowp.py`): "f32" (the reference), "bf16" (what the
configuration states), "fp8" (the control: the nearest precision below
the stated one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import seeds
from benchmark.reference import lowp

STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))
BN_EPS = 1e-5
BN_DECAY = 0.9


def _block_shapes(c_in):
    """[(stage, block, c_in, filters, stride, project)] in network order."""
    out = []
    for si, (f, n, s) in enumerate(STAGES):
        for bi in range(n):
            out.append((si, bi, c_in, f, s if bi == 0 else 1, bi == 0))
            c_in = 4 * f
    return out


def init(seed, model):
    """He-normal convolutions, unit scale and zero shift in every batch
    norm, a small normal linear layer; one jitted call from the seed."""
    n_classes, channels = model["n_classes"], model["channels"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 64))

        def conv(kh, cin, cout):
            std = (2.0 / (kh * kh * cin)) ** 0.5
            return {"w": std * jax.random.normal(
                        next(keys), (kh, kh, cin, cout), jnp.float32),
                    "g": jnp.ones((cout,), jnp.float32),
                    "b": jnp.zeros((cout,), jnp.float32)}

        p = {"stem": conv(7, channels, 64), "blocks": []}
        for _, _, cin, f, _, project in _block_shapes(64):
            blk = {"a": conv(1, cin, f), "b": conv(3, f, f),
                   "c": conv(1, f, 4 * f)}
            if project:
                blk["proj"] = conv(1, cin, 4 * f)
            p["blocks"].append(blk)
        p["fc_w"] = 0.01 * jax.random.normal(next(keys), (2048, n_classes),
                                             jnp.float32)
        p["fc_b"] = jnp.zeros((n_classes,), jnp.float32)
        return p

    return make(seeds.key(seed, seeds.WEIGHTS))


def init_state(model):
    """Running (mean, var) of every batch norm: zeros and ones."""
    def bn(c):
        return {"mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32)}

    st = {"stem": bn(64), "blocks": []}
    for _, _, _, f, _, project in _block_shapes(64):
        blk = {"a": bn(f), "b": bn(f), "c": bn(4 * f)}
        if project:
            blk["proj"] = bn(4 * f)
        st["blocks"].append(blk)
    return st


def program_layout(params, state=None):
    """The same numbers under the program's vertex names. Pure
    re-arrangement; returns (params, state)."""
    state = state if state is not None else init_state(None)
    pp, ss = {}, {}

    def put(name, p, s):
        pp[f"{name}_conv"] = {"W": p["w"]}
        pp[f"{name}_bn"] = {"gamma": p["g"], "beta": p["b"]}
        ss[f"{name}_bn"] = {"mean": s["mean"], "var": s["var"]}

    put("stem", params["stem"], state["stem"])
    for (si, bi, *_), blk, bst in zip(_block_shapes(64), params["blocks"],
                                      state["blocks"]):
        for part in blk:
            put(f"s{si}b{bi}_{part}", blk[part], bst[part])
    pp["fc"] = {"W": params["fc_w"], "b": params["fc_b"]}
    return pp, ss


def _bn(x, p, s):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean((x - mean) ** 2, (0, 1, 2))
    y = (x - mean) / jnp.sqrt(var + BN_EPS) * p["g"] + p["b"]
    new = {"mean": BN_DECAY * s["mean"] + (1 - BN_DECAY) * mean,
           "var": BN_DECAY * s["var"] + (1 - BN_DECAY) * var}
    return y, new


def _cbr(x, p, s, stride, precision, relu=True):
    y, ns = _bn(lowp.conv(x, p["w"], stride, precision), p, s)
    return (jax.nn.relu(y) if relu else y), ns


def _bottleneck(x, p, s, stride, precision):
    ns = {}
    y, ns["a"] = _cbr(x, p["a"], s["a"], stride, precision)
    y, ns["b"] = _cbr(y, p["b"], s["b"], 1, precision)
    y, ns["c"] = _cbr(y, p["c"], s["c"], 1, precision, relu=False)
    if "proj" in p:
        x, ns["proj"] = _cbr(x, p["proj"], s["proj"], stride, precision,
                             relu=False)
    return jax.nn.relu(y + x), ns


def logits(params, state, x, precision="f32"):
    """[B, H, W, C] images -> ([B, classes] logits, new running stats),
    batch normalisation in training mode."""
    ns = {"blocks": []}
    h, ns["stem"] = _cbr(x, params["stem"], state["stem"], 2, precision)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for (_, _, _, _, stride, _), p, s in zip(_block_shapes(64),
                                             params["blocks"],
                                             state["blocks"]):
        h, bs = jax.checkpoint(functools.partial(
            _bottleneck, stride=stride, precision=precision))(h, p, s)
        ns["blocks"].append(bs)
    h = jnp.mean(h, (1, 2))
    z = lowp.matmul(h, params["fc_w"], precision)
    return z + params["fc_b"], ns


def _loss(params, state, x, y, precision):
    z, ns = logits(params, state, x, precision)
    lse = jax.nn.logsumexp(z, axis=-1)
    ce = lse - jnp.take_along_axis(z, y[:, None], 1)[:, 0]
    return jnp.mean(ce), ns


@functools.partial(jax.jit, static_argnames=("precision",))
def _loss_and_grad(params, state, x, y, precision):
    (l, ns), g = jax.value_and_grad(_loss, has_aux=True)(params, state, x, y,
                                                         precision)
    return l, g, ns


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch, its gradient and the new running
    statistics. The batch is one block: batch normalisation couples its
    rows. `x` float32 [B, H, W, C], `y` int32 [B]."""
    return _loss_and_grad(params, state, x, y, precision)
