"""Plain reference of a GPT-2 shaped decoder (Radford et al. 2019; the
published `config.json` keys `n_embd`, `n_layer`, `n_head`, `n_positions`,
`vocab_size`): learned token and position embeddings, pre-norm blocks
(LayerNorm, causal multi-head attention, residual; LayerNorm, 4x MLP with
the tanh GELU, residual), a linear head, mean cross-entropy over every
position. float32 `jax.numpy` under matmul precision "highest"; no kernel,
nothing imported from the program.

Departures from the published model, which the configuration file lists
too, because they are the program's: the head is a separate matrix with a
bias (not tied to the embedding) and there is no final LayerNorm.

`precision` selects what the matrix multiplications see (`lowp.py`): "f32"
(the reference), "bf16" (what the configuration states) and "fp8" (the
control: the nearest precision below the stated one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02  # GPT-2's initializer range
LN_EPS = 1e-5


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device."""
    v, d, n, t = (model["vocab_size"], model["n_embd"], model["n_layer"],
                  model["n_positions"])

    @jax.jit
    def make(key):
        k = jax.random.split(key, 4)

        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        def zeros(shape):
            return jnp.zeros(shape, jnp.float32)

        kb = jax.random.split(k[2], 4)
        blocks = {
            "ln1_g": ones((n, d)), "ln1_b": zeros((n, d)),
            "ln2_g": ones((n, d)), "ln2_b": zeros((n, d)),
            "w_qkv": nrm(kb[0], (n, d, 3 * d)), "b_qkv": zeros((n, 3 * d)),
            "w_o": nrm(kb[1], (n, d, d)), "b_o": zeros((n, d)),
            "w_fc": nrm(kb[2], (n, d, 4 * d)), "b_fc": zeros((n, 4 * d)),
            "w_proj": nrm(kb[3], (n, 4 * d, d)), "b_proj": zeros((n, d)),
        }
        return {"wte": nrm(k[0], (v, d)), "wpe": nrm(k[1], (t, d)),
                "blocks": blocks,
                "head_w": nrm(k[3], (d, v)), "head_b": zeros((v,))}

    return make(seeds.key(seed, seeds.WEIGHTS))


def program_layout(params, state=None):
    """The same numbers arranged as `transformer_lm`'s parameter list
    (embedding, one dict per block, output layer). Pure re-arrangement."""
    b = params["blocks"]
    n = b["w_qkv"].shape[0]
    layers = [{"W": params["wte"], "P": params["wpe"]}]
    for i in range(n):
        layers.append({
            "ln1": {"gamma": b["ln1_g"][i], "beta": b["ln1_b"][i]},
            "ln2": {"gamma": b["ln2_g"][i], "beta": b["ln2_b"][i]},
            "mha": {"Wqkv": b["w_qkv"][i], "bqkv": b["b_qkv"][i],
                    "Wo": b["w_o"][i], "bo": b["b_o"][i]},
            "mlp_W1": b["w_fc"][i], "mlp_b1": b["b_fc"][i],
            "mlp_W2": b["w_proj"][i], "mlp_b2": b["b_proj"][i]})
    layers.append({"W": params["head_w"], "b": params["head_b"]})
    return layers, [{} for _ in layers]


def init_state(model):
    return None


_mm = lowp.matmul


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _block(h, p, n_head, precision):
    t, d = h.shape
    dh = d // n_head
    a = _ln(h, p["ln1_g"], p["ln1_b"])
    qkv = _mm(a, p["w_qkv"], precision) + p["b_qkv"]
    # the program's fused projection is laid out [3, heads, head_dim]
    qkv = qkv.reshape(t, 3, n_head, dh)
    q, k, v = (qkv[:, i].transpose(1, 0, 2) for i in range(3))
    s = jnp.stack([_mm(q[i], k[i].T, precision) for i in range(n_head)])
    s = s / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.stack([_mm(w[i], v[i], precision) for i in range(n_head)])
    o = o.transpose(1, 0, 2).reshape(t, d)
    h = h + _mm(o, p["w_o"], precision) + p["b_o"]
    a = _ln(h, p["ln2_g"], p["ln2_b"])
    m = jax.nn.gelu(_mm(a, p["w_fc"], precision) + p["b_fc"],
                    approximate=True)
    return h + _mm(m, p["w_proj"], precision) + p["b_proj"]


def logits_one(params, tokens, n_head, precision="f32"):
    """[T] token ids -> [T, V] logits of one sequence."""
    t = tokens.shape[0]
    h = params["wte"][tokens] + params["wpe"][:t]

    def body(h, p):
        return jax.checkpoint(
            functools.partial(_block, n_head=n_head, precision=precision))(
                h, p), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return _mm(h, params["head_w"], precision) + params["head_b"]


def loss_sum_one(params, tokens, targets, n_head, precision="f32"):
    """Summed next-token cross-entropy of one sequence."""
    lg = logits_one(params, tokens, n_head, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, targets[:, None], 1)[:, 0])


@functools.partial(jax.jit, static_argnames=("n_head", "precision", "n_tok"),
                   donate_argnums=(3,))
def _add_one(params, tok, tgt, acc, tot, n_head, precision, n_tok):
    l, g = jax.value_and_grad(loss_sum_one)(params, tok, tgt, n_head,
                                            precision)
    return (jax.tree_util.tree_map(lambda a, b: a + b / n_tok, acc, g),
            tot + l / n_tok)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch and its gradient, one sequence (one block
    of rows) at a time so that float32 activations fit beside nothing
    else. `x`, `y`: int32 [B, T] inputs and targets. Returns
    (loss, grads, state)."""
    n_tok = x.shape[0] * x.shape[1]
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    tot = jnp.float32(0.0)
    for i in range(x.shape[0]):
        acc, tot = _add_one(params, x[i], y[i], acc, tot, model["n_head"],
                            precision, n_tok)
    return tot, acc, state
