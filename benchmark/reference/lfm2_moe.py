"""Plain reference of LFM2-MoE, a hybrid conv/attention mixture-of-experts
decoder (LiquidAI; `model_type` `lfm2_moe`, the published `config.json`
keys `hidden_size`, `layer_types`, `num_dense_layers`, `intermediate_size`,
`moe_intermediate_size`, `num_experts`, `num_experts_per_tok`,
`num_attention_heads`, `num_key_value_heads`, `conv_L_cache`,
`norm_eps`, `rope_parameters`, `routed_scaling_factor`, `use_expert_bias`,
`norm_topk_prob`): a token embedding with no position embedding, then per
layer `h = h + mixer(rms(h))`, `h = h + ffn(rms(h))`, a final RMSNorm and
the head. No bias anywhere.

* mixer, `layer_types[i] == "conv"`: the gated short convolution,
  `[B, C, x] = split3(u W_in)`, `z = B * x`, a depthwise causal
  convolution of length `conv_L_cache` over time, `out = (C * c) W_out`;
* mixer, `"full_attention"`: 32 query heads over 8 key/value heads (query
  head j reads key/value head j // 4), an RMSNorm over each head's width
  on q and on k before the rotation (rotate-half over the whole head),
  causal, scale 1/sqrt(head width);
* ffn, `i < num_dense_layers`: `(silu(u W1) * (u W3)) W2`;
* ffn, else: `s = sigmoid(u W_r)` in float32, `sel = top_k(s + b)`, `w =
  s[sel] / (sum(s[sel]) + 1e-6) * routed_scaling_factor`, `y = sum_j w_j
  E_j(u)` over the selected experts `j` THAT ARE HELD HERE
  (`experts_held`, the chip's share of the layer: what the experts on the
  other chips would add is left out, in the program and here alike).

Departures from the published model, each also in the configuration file:
the head is not tied to the embedding; the expert bias `b` is state, held
fixed (its update rule is not in `config.json`); the router's product is
float32 at every `precision` (the configuration states float32 routing,
and an fp8 recipe keeps its routers out of fp8 too).

float32 `jax.numpy` under matmul precision "highest"; no kernel, nothing
imported from the program. The experts are a loop of dense products over
every token, weighted by zero where a token did not choose the expert;
attention runs a block of queries at a time under `jax.checkpoint`, a
head at a time, so that no `[heads, T, T]` scores exist; a layer is
recomputed in the backward pass (`jax.checkpoint`), which changes what is
kept, not what is computed. `precision` selects what the matrix
multiplications see (`lowp.py`): "f32" (the reference), "bf16" (what the
configuration states) and "fp8" (the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02   # assumed: the family's usual initializer range
ROUTER_EPS = 1e-6  # modeling_lfm2_moe.py: added to the selected scores' sum
QUERY_BLOCK = 512
# the harness's `init_state(model)` is handed no seed: the expert bias is
# the same draw on every seed
BIAS_KEY = 20260928

_mm = lowp.matmul


def _kinds(model):
    """[(mixer, ffn)] per layer."""
    return [(t, "dense" if i < model["num_dense_layers"] else "moe")
            for i, t in enumerate(model["layer_types"])]


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device."""
    v, d = model["vocab_size"], model["n_embd"]
    dh = model["head_dim"]
    q_inner, kv_inner = model["n_head"] * dh, model["n_kv_head"] * dh
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    first, end = model["experts_held"]
    held, e = end - first, model["num_experts"]
    kc = model["conv_L_cache"]
    kinds = _kinds(model)

    @jax.jit
    def make(key):
        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for (mixer, ffn), kl in zip(kinds,
                                    jax.random.split(k_layers, len(kinds))):
            k = jax.random.split(kl, 10)
            p = {"g_op": ones((d,)), "g_ffn": ones((d,))}
            if mixer == "conv":
                p.update(w_in=nrm(k[0], (d, 3 * d)), conv_w=nrm(k[1], (d, kc)),
                         w_out=nrm(k[2], (d, d)))
            else:
                p.update(w_q=nrm(k[0], (d, q_inner)),
                         w_k=nrm(k[1], (d, kv_inner)),
                         w_v=nrm(k[2], (d, kv_inner)),
                         w_o=nrm(k[3], (q_inner, d)),
                         g_q=ones((dh,)), g_k=ones((dh,)))
            if ffn == "dense":
                p.update(w1=nrm(k[4], (d, f)), w3=nrm(k[5], (d, f)),
                         w2=nrm(k[6], (f, d)))
            else:
                p.update(w_r=nrm(k[7], (d, e)),
                         e_w1=nrm(k[4], (held, d, fe)),
                         e_w3=nrm(k[5], (held, d, fe)),
                         e_w2=nrm(k[6], (held, fe, d)))
            layers.append(p)
        return {"wte": nrm(k_emb, (v, d)), "layers": layers,
                "g_final": ones((d,)), "head_w": nrm(k_head, (d, v))}

    return make(seeds.key(seed, seeds.WEIGHTS))


def init_state(model):
    """Per layer: None, or an expert layer's `expert_bias` [num_experts]
    (N(0, expert_bias_std), fixed) and its zeroed counts."""
    first, end = model["experts_held"]
    keys = jax.random.split(jax.random.PRNGKey(BIAS_KEY),
                            len(model["layer_types"]))
    return [None if ffn == "dense" else {
        "expert_bias": model["expert_bias_std"] * jax.random.normal(
            k, (model["num_experts"],), jnp.float32),
        "moe_load": jnp.zeros((end - first,), jnp.float32),
        "moe_elsewhere": jnp.zeros((1,), jnp.float32)}
        for (_, ffn), k in zip(_kinds(model), keys)]


def program_layout(params, state=None):
    """The same numbers arranged as `hybrid_moe_lm`'s parameter list (the
    embedding, a block a layer, the final norm, the head) and its state
    list. The program's key and value projections are one matrix laid out
    [2, kv heads, head width]. Pure re-arrangement."""
    blocks = []
    for p in params["layers"]:
        b = {"ln1": {"gamma": p["g_op"]}, "ln2": {"gamma": p["g_ffn"]}}
        if "w_in" in p:
            b["conv"] = {"W_in": p["w_in"], "conv_w": p["conv_w"],
                         "W_out": p["w_out"]}
        else:
            b["mha"] = {"Wq": p["w_q"], "Wo": p["w_o"],
                        "Wkv": jnp.concatenate([p["w_k"], p["w_v"]], axis=1),
                        "q_gamma": p["g_q"], "k_gamma": p["g_k"]}
        if "w1" in p:
            b.update(mlp_Wg=p["w1"], mlp_Wu=p["w3"], mlp_Wd=p["w2"])
        else:
            b.update(moe_router=p["w_r"], moe_Wg=p["e_w1"], moe_Wu=p["e_w3"],
                     moe_Wd=p["e_w2"])
        blocks.append(b)
    layers = [{"W": params["wte"]}, *blocks, {"gamma": params["g_final"]},
              {"W": params["head_w"]}]
    states = [{} for _ in layers]
    if state is not None:
        for i, s in enumerate(state):
            if s is not None:
                states[1 + i] = dict(s)
    return layers, states


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [T, heads, D]; position t turns pair (i, i + D/2) by
    t * theta**(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def short_conv(u, p, precision):
    """The gated short convolution of one sequence, [T, d] -> [T, d]."""
    t, d = u.shape
    bcx = _mm(u, p["w_in"], precision)
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = gate_b * x
    taps = p["conv_w"].shape[1]
    c = jnp.zeros_like(z)
    for j in range(taps):                 # tap j meets z[t - (taps-1-j)]
        back = taps - 1 - j
        c = c + jnp.pad(z, ((back, 0), (0, 0)))[:t] * p["conv_w"][:, j]
    return _mm(gate_c * c, p["w_out"], precision)


def attention(u, p, model, precision):
    """Grouped-query causal attention of one sequence, [T, d] -> [T, d],
    a block of queries at a time."""
    t, _ = u.shape
    nh, nkv, dh = model["n_head"], model["n_kv_head"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    q = _mm(u, p["w_q"], precision).reshape(t, nh, dh)
    k = _mm(u, p["w_k"], precision).reshape(t, nkv, dh)
    v = _mm(u, p["w_v"], precision).reshape(t, nkv, dh)
    q = _rope(_rms(q, p["g_q"], eps), theta)
    k = _rope(_rms(k, p["g_k"], eps), theta)
    bq = min(QUERY_BLOCK, t)
    if t % bq:
        raise ValueError(f"T {t} is no multiple of the query block {bq}")
    pos_k = jnp.arange(t)

    @jax.checkpoint
    def block(qb, start, k, v):
        seen = (start + jnp.arange(bq))[:, None] >= pos_k[None, :]

        @jax.checkpoint
        def head(j):
            at = functools.partial(jax.lax.dynamic_index_in_dim, axis=1,
                                   keepdims=False)
            kv = j // (nh // nkv)
            s = _mm(at(qb, j), at(k, kv).T, precision) / jnp.sqrt(
                jnp.float32(dh))
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return _mm(w, at(v, kv), precision)

        # one head at a time: the loop's body is compiled once, and
        # recomputed in the backward pass so that the loop keeps no scores
        return jax.lax.map(head, jnp.arange(nh)).transpose(1, 0, 2)

    starts = jnp.arange(0, t, bq)
    o = jax.lax.map(lambda a: block(a[0], a[1], k, v),
                    (q.reshape(t // bq, bq, nh, dh), starts))
    return _mm(o.reshape(t, nh * dh), p["w_o"], precision)


def _gated(u, w1, w3, w2, precision):
    return _mm(jax.nn.silu(_mm(u, w1, precision)) * _mm(u, w3, precision),
               w2, precision)


def route(u, w_r, bias, model):
    """(sel [T, k], w [T, k]): the selected experts and their weights.
    The bias moves the selection only; the weights are the unbiased scores
    renormalised over the selected. float32 at every precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_r, precision="highest"))
    _, sel = jax.lax.top_k(s + bias, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return sel, w * model["routed_scaling_factor"]


def experts(u, p, bias, model, precision, held=None):
    """The part of the expert layer's result that the experts `held` =
    (first, end) give, and the counts of assignments per held expert.
    `p["e_w*"]` hold those experts' weights in order."""
    first, end = held or model["experts_held"]
    sel, w = route(u, p["w_r"], bias, model)

    @jax.checkpoint
    def add_expert(y, expert):
        j, w1, w3, w2 = expert
        chose = sel == j
        w_j = jnp.sum(jnp.where(chose, w, 0.0), axis=-1)
        return (y + w_j[:, None] * _gated(u, w1, w3, w2, precision),
                jnp.sum(chose))

    # one expert at a time over every token: the body is compiled once, and
    # recomputed in the backward pass so that the loop keeps only its sums
    y, load = jax.lax.scan(add_expert, jnp.zeros_like(u),
                           (jnp.arange(first, end), p["e_w1"], p["e_w3"],
                            p["e_w2"]))
    load = load.astype(jnp.float32)
    return y, load, (sel.size - jnp.sum(load))[None]


def _layer(h, p, bias, model, precision):
    """One decoder layer of one sequence; (h, the expert layer's counts
    or None)."""
    u = _rms(h, p["g_op"], model["norm_eps"])
    if "w_in" in p:
        h = h + short_conv(u, p, precision)
    else:
        h = h + attention(u, p, model, precision)
    u = _rms(h, p["g_ffn"], model["norm_eps"])
    if "w1" in p:
        return h + _gated(u, p["w1"], p["w3"], p["w2"], precision), None
    y, load, elsewhere = experts(u, p, bias, model, precision)
    return h + y, (load, elsewhere)


def logits_one(params, biases, tokens, model, precision="f32"):
    """[T] token ids -> ([T, V] logits, each layer's counts or None)."""
    h = params["wte"][tokens]
    counts = []
    for p, bias in zip(params["layers"], biases):
        h, c = jax.checkpoint(functools.partial(
            _layer, model=model, precision=precision))(h, p, bias)
        counts.append(c)
    h = _rms(h, params["g_final"], model["norm_eps"])
    return _mm(h, params["head_w"], precision), counts


def loss_sum_one(params, biases, tokens, targets, model, precision="f32"):
    """The per-token cross-entropies of one sequence, summed."""
    z, counts = logits_one(params, biases, tokens, model, precision)
    ce = (jax.nn.logsumexp(z, axis=-1)
          - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])
    return jnp.sum(ce), counts


def _static(model):
    """The model's sizes as a hashable for `jit`."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, list, tuple))))


@functools.partial(jax.jit, static_argnames=("model", "precision", "n_tok"),
                   donate_argnums=(4,))
def _add_one(params, biases, tok, tgt, acc, tot, model, precision, n_tok):
    (l, counts), g = jax.value_and_grad(loss_sum_one, has_aux=True)(
        params, biases, tok, tgt, dict(model), precision)
    return (jax.tree_util.tree_map(lambda a, b: a + b / n_tok, acc, g),
            tot + l / n_tok, counts)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch and its gradient, one sequence at a time,
    and the state with this step's counts. `x`, `y`: int32 [B, T] inputs
    and targets. Returns (loss, grads, state)."""
    n_tok = x.shape[0] * x.shape[1]
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    tot = jnp.float32(0.0)
    biases = [None if s is None else s["expert_bias"] for s in state]
    totals = [None] * len(state)
    for i in range(x.shape[0]):
        acc, tot, counts = _add_one(params, biases, x[i], y[i], acc, tot,
                                    _static(model), precision, n_tok)
        totals = [c if t is None or c is None else (t[0] + c[0], t[1] + c[1])
                  for t, c in zip(totals, counts)]
    new_state = [None if s is None else
                 {**s, "moe_load": c[0], "moe_elsewhere": c[1]}
                 for s, c in zip(state, totals)]
    return tot, acc, new_state
