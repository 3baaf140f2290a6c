"""Plain reference of Ouro, a looped decoder (ByteDance, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741; the published
`config.json` keys `hidden_size`, `num_attention_heads`, `head_dim`,
`intermediate_size`, `total_ut_steps`, `rope_theta`, `rms_norm_eps`,
`vocab_size`): a token embedding with no position embedding, then the SAME
`n_layer` blocks, with the same weights, run `total_ut_steps` times. A block
is a sandwich: RMSNorm, causal multi-head attention with rotary positions
(rotate-half over the whole head width), RMSNorm, residual; RMSNorm, gated
SiLU FFN, RMSNorm, residual; no bias anywhere. The final RMSNorm closes
every pass and the normed state goes on to the next pass. Every pass's
normed state gets the (untied) head and an exit gate, and the loss is the
expected cross-entropy under the gate's exit distribution less `beta`
times that distribution's entropy (stage I of the report):

    lam_r = sigmoid(s_r w_gate + b_gate)
    p_r   = lam_r prod_{j<r} (1 - lam_j)   for r < R;   p_R = prod_{j<R} (1 - lam_j)
    loss  = mean over tokens of [ sum_r p_r CE(z_r, y) - beta H(p) ]

float32 `jax.numpy` under matmul precision "highest"; no kernel, nothing
imported from the program; the passes are a Python loop. At the cell's
sizes the activations of 16 block passes and four `[T, V]` logits do not
fit beside the reference's own Adam, so `loss_and_grad` takes the batch a
sequence at a time (the loss is a mean of per-token terms, so the sums
add), recomputes a block at a time and each pass's head in the backward
pass, and sums the gradients. That changes what is kept, not what is
computed.

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

INIT_STD = 0.02  # assumed: the family's usual initializer range

_mm = lowp.matmul


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device."""
    v, d, n = model["vocab_size"], model["n_embd"], model["n_layer"]
    inner = model["n_head"] * model["head_dim"]
    f = model["intermediate_size"]

    @jax.jit
    def make(key):
        k = jax.random.split(key, 4)

        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        kb = jax.random.split(k[1], 7)
        blocks = {
            "g1": ones((n, d)), "g2": ones((n, d)),
            "g3": ones((n, d)), "g4": ones((n, d)),
            "w_q": nrm(kb[0], (n, d, inner)), "w_k": nrm(kb[1], (n, d, inner)),
            "w_v": nrm(kb[2], (n, d, inner)), "w_o": nrm(kb[3], (n, inner, d)),
            "w_gate": nrm(kb[4], (n, d, f)), "w_up": nrm(kb[5], (n, d, f)),
            "w_down": nrm(kb[6], (n, f, d)),
        }
        kg = jax.random.split(k[3])
        return {"wte": nrm(k[0], (v, d)), "blocks": blocks,
                "g_final": ones((d,)), "head_w": nrm(k[2], (d, v)),
                "gate_w": nrm(kg[0], (d, 1)),
                "gate_b": jnp.zeros((1,), jnp.float32)}

    return make(seeds.key(seed, seeds.WEIGHTS))


def program_layout(params, state=None):
    """The same numbers arranged as `looped_lm`'s parameter list (the
    embedding, the looped stack with each shared block once, the
    exit-weighted output layer). The program's query, key and value
    projections are one matrix laid out [3, heads, head_dim]. Pure
    re-arrangement."""
    b = params["blocks"]
    stack = {"final_norm": {"gamma": params["g_final"]}}
    for i in range(b["w_q"].shape[0]):
        stack[f"B{i:02d}"] = {
            "ln1": {"gamma": b["g1"][i]}, "ln1_post": {"gamma": b["g2"][i]},
            "ln2": {"gamma": b["g3"][i]}, "ln2_post": {"gamma": b["g4"][i]},
            "mha": {"Wqkv": jnp.concatenate(
                [b["w_q"][i], b["w_k"][i], b["w_v"][i]], axis=1),
                "Wo": b["w_o"][i]},
            "mlp_Wg": b["w_gate"][i], "mlp_Wu": b["w_up"][i],
            "mlp_Wd": b["w_down"][i]}
    layers = [{"W": params["wte"]}, stack,
              {"W": params["head_w"], "gate_W": params["gate_w"],
               "gate_b": params["gate_b"]}]
    return layers, [{} for _ in layers]


def init_state(model):
    return None


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [heads, T, D]; position t turns pair (i, i + D/2) by
    t * theta**(-2i/D)."""
    _, t, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def _block(h, p, n_head, theta, eps, precision):
    t, _ = h.shape
    dh = p["w_q"].shape[1] // n_head
    a = _rms(h, p["g1"], eps)
    q, k, v = (_mm(a, p[w], precision).reshape(t, n_head, dh)
               .transpose(1, 0, 2) for w in ("w_q", "w_k", "w_v"))
    q, k = _rope(q, theta), _rope(k, theta)
    s = jnp.stack([_mm(q[i], k[i].T, precision) for i in range(n_head)])
    s = s / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.stack([_mm(w[i], v[i], precision) for i in range(n_head)])
    o = o.transpose(1, 0, 2).reshape(t, n_head * dh)
    h = h + _rms(_mm(o, p["w_o"], precision), p["g2"], eps)
    m = _rms(h, p["g3"], eps)
    m = jax.nn.silu(_mm(m, p["w_gate"], precision)) \
        * _mm(m, p["w_up"], precision)
    return h + _rms(_mm(m, p["w_down"], precision), p["g4"], eps)


def states_one(params, tokens, model, precision="f32"):
    """[T] token ids -> the R normed states [T, d] of one sequence."""
    block = jax.checkpoint(functools.partial(
        _block, n_head=model["n_head"], theta=model["rope_theta"],
        eps=model["rms_norm_eps"], precision=precision))
    h = params["wte"][tokens]
    states = []
    for _ in range(model["total_ut_steps"]):
        h, _ = jax.lax.scan(lambda h, p: (block(h, p), None), h,
                            params["blocks"])
        h = _rms(h, params["g_final"], model["rms_norm_eps"])
        states.append(h)
    return states


def logits_one(params, tokens, model, precision="f32"):
    """[T] token ids -> [T, V] logits of the last pass (what inference
    reads: the published early-exit threshold is 1, so no pass leaves
    early)."""
    return _mm(states_one(params, tokens, model, precision)[-1],
               params["head_w"], precision)


def _cross_entropy(s, head_w, targets, precision):
    z = _mm(s, head_w, precision)
    return (jax.nn.logsumexp(z, axis=-1)
            - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])


def loss_sum_one(params, tokens, targets, model, precision="f32"):
    """The loss's per-token terms of one sequence, summed."""
    states = states_one(params, tokens, model, precision)
    ce = [jax.checkpoint(functools.partial(_cross_entropy,
                                           precision=precision))(
        s, params["head_w"], targets) for s in states]
    lam = [jax.nn.sigmoid(_mm(s, params["gate_w"], precision)[:, 0]
                          + params["gate_b"][0]) for s in states[:-1]]
    stay, p = jnp.ones_like(ce[0]), []
    for lam_r in lam:
        p.append(lam_r * stay)
        stay = stay * (1.0 - lam_r)
    p.append(stay)
    expected = sum(p_r * ce_r for p_r, ce_r in zip(p, ce))
    entropy = -sum(p_r * jnp.log(p_r) for p_r in p)
    return jnp.sum(expected - model["exit_entropy_beta"] * entropy)


@functools.partial(jax.jit, static_argnames=("model", "precision", "n_tok"),
                   donate_argnums=(3,))
def _add_one(params, tok, tgt, acc, tot, model, precision, n_tok):
    l, g = jax.value_and_grad(loss_sum_one)(params, tok, tgt, dict(model),
                                            precision)
    return (jax.tree_util.tree_map(lambda a, b: a + b / n_tok, acc, g),
            tot + l / n_tok)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch and its gradient, one sequence at a time.
    `x`, `y`: int32 [B, T] inputs and targets. Returns (loss, grads,
    state)."""
    n_tok = x.shape[0] * x.shape[1]
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    tot = jnp.float32(0.0)
    sizes = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float))))
    for i in range(x.shape[0]):
        acc, tot = _add_one(params, x[i], y[i], acc, tot, sizes, precision,
                            n_tok)
    return tot, acc, state
