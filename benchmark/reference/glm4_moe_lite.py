"""Plain reference of GLM-4.7-Flash, a latent-attention mixture-of-experts
decoder with a multi-token-prediction module (zai-org; `model_type`
`glm4_moe_lite`, the published `config.json` keys `hidden_size`,
`num_attention_heads`, `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `intermediate_size`,
`moe_intermediate_size`, `n_routed_experts`, `n_shared_experts`,
`num_experts_per_tok`, `first_k_dense_replace`, `routed_scaling_factor`,
`norm_topk_prob`, `rms_norm_eps`, `rope_theta`, `num_nextn_predict_layers`):
a token embedding with no position embedding, then per layer `h = h +
mla(rms(h))`, `h = h + ffn(rms(h))`, a final RMSNorm and the head; beside
them ONE more layer of the same kind, the multi-token-prediction module,
which reads the trunk's state before the final norm and the embedding of
the next token, and shares the embedding table and the head matrix with the
trunk. No bias anywhere.

* mla (multi-head latent attention, on `u = rms(h)` [T, d], H heads):
  `c_q = rms(u W_qa)`, `[qn_j | qr_j] = (c_q W_qb)_j`; `[c_kv | kr] = u
  W_kva`, `[kn_j | v_j] = (rms(c_kv) W_kvb)_j`; `q_j = [qn_j | rope(qr_j)]`,
  `k_j = [kn_j | rope(kr)]`: ONE rotary key a token, the same for every
  head; causal softmax at scale 1/sqrt(nope + rope); `[o_1 .. o_H] W_o`.
  `rope` turns the whole rotary part, rotate-half;
* ffn, layer `i < first_k_dense_replace`: `(silu(u W_g) * (u W_u)) W_d`;
* ffn, else: `s = sigmoid(u W_r)` in float32, `sel = top_k(s + b)`, `w =
  s[sel] / (sum(s[sel]) + 1e-6) * routed_scaling_factor`, `y = sum_j w_j
  E_j(u)` over the selected experts `j` THAT ARE HELD HERE (`experts_held`,
  the chip's share: what the experts on the other chips would add is left
  out, in the program and here alike) `+ E_shared(u)`, the shared expert
  over every token, ungated; experts gated SiLU;
* the module (DeepSeek-V3, arXiv:2412.19437, section 2.2), `y_i` the label
  of position `i` (the next token): `m_i = [rms_e(Emb(y_i)) | rms_h(h_i)]
  W_eh`, `m = layer(m)` (latent attention, then the mixture; causal,
  positions as the trunk's), `z'_i = rms_s(m_i) W_head`;
  `loss = mean_{i<=T} CE(z_i, y_i) + lambda mean_{i<=T-1} CE(z'_i, y_{i+1})`.
  `Emb` and `W_head` are the trunk's own: each is ONE leaf here, used
  twice and differentiated once.

Departures and assumptions are in the configuration file. float32
`jax.numpy` under matmul precision "highest"; no kernel, nothing imported
from the program. The experts are a loop of dense products over every
token, weighted by zero where a token did not choose the expert; attention
runs a block of queries at a time under `jax.checkpoint`, a head at a
time, the shared rotary key joined to each head's own part there, so that
no `[heads, T, T]` scores exist; a layer and each of the two heads are
recomputed in the backward pass (`jax.checkpoint`), which changes what is
kept, not what is computed. `precision` selects what the matrix
multiplications see (`lowp.py`): "f32" (the reference), "bf16" (what the
configuration states) and "fp8" (the control); the router's product is
float32 at every one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02    # assumed: the family's usual initializer range
ROUTER_EPS = 1e-6  # added to the selected scores' sum (as lfm2's cell)
QUERY_BLOCK = 512

_mm = lowp.matmul


def _ffn_kinds(model):
    """"dense" | "moe" for the trunk's layers and then the module's."""
    n = model["n_layer"] + model["num_nextn_predict_layers"]
    return ["dense" if i < model["num_dense_layers"] else "moe"
            for i in range(n)]


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device."""
    v, d, h = model["vocab_size"], model["n_embd"], model["n_head"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    first, end = model["experts_held"]
    held, e = end - first, model["num_experts"]
    kinds = _ffn_kinds(model)
    if model["num_nextn_predict_layers"] != 1:
        raise ValueError("one multi-token-prediction module, as published")

    @jax.jit
    def make(key):
        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        def layer(kl, ffn):
            k = jax.random.split(kl, 12)
            p = {"g_op": ones((d,)), "g_ffn": ones((d,)),
                 "w_qa": nrm(k[0], (d, rq)), "g_q": ones((rq,)),
                 "w_qb": nrm(k[1], (rq, h * (dn + dr))),
                 "w_kva": nrm(k[2], (d, rkv + dr)), "g_kv": ones((rkv,)),
                 "w_kvb": nrm(k[3], (rkv, h * (dn + dv))),
                 "w_o": nrm(k[4], (h * dv, d))}
            if ffn == "dense":
                p.update(w_g=nrm(k[5], (d, f)), w_u=nrm(k[6], (d, f)),
                         w_d=nrm(k[7], (f, d)))
            else:
                p.update(w_r=nrm(k[8], (d, e)),
                         e_wg=nrm(k[5], (held, d, fe)),
                         e_wu=nrm(k[6], (held, d, fe)),
                         e_wd=nrm(k[7], (held, fe, d)),
                         s_wg=nrm(k[9], (d, fe)), s_wu=nrm(k[10], (d, fe)),
                         s_wd=nrm(k[11], (fe, d)))
            return p

        k_emb, k_head, k_eh, k_layers = jax.random.split(key, 4)
        layers = [layer(kl, ffn) for kl, ffn in
                  zip(jax.random.split(k_layers, len(kinds)), kinds)]
        return {"wte": nrm(k_emb, (v, d)), "layers": layers[:-1],
                "g_final": ones((d,)), "head_w": nrm(k_head, (d, v)),
                "mtp": {"g_e": ones((d,)), "g_h": ones((d,)),
                        "w_eh": nrm(k_eh, (2 * d, d)), "layer": layers[-1],
                        "g_s": ones((d,))}}

    return make(seeds.key(seed, seeds.WEIGHTS))


def _mixture_state(model):
    first, end = model["experts_held"]
    return {"expert_bias": jnp.zeros((model["num_experts"],), jnp.float32),
            "moe_load": jnp.zeros((end - first,), jnp.float32),
            "moe_elsewhere": jnp.zeros((1,), jnp.float32)}


def init_state(model):
    """A trunk layer's: None, or a mixture's correction bias [num_experts]
    (zeros, fixed) and its zeroed counts; the module's mixture's; and the
    two terms of the last step's loss."""
    kinds = _ffn_kinds(model)
    return {"layers": [None if ffn == "dense" else _mixture_state(model)
                       for ffn in kinds[:-1]],
            "mtp": _mixture_state(model),
            "loss_terms": {"main": jnp.zeros((), jnp.float32),
                           "mtp": jnp.zeros((), jnp.float32)}}


def _block_layout(p):
    b = {"ln1": {"gamma": p["g_op"]}, "ln2": {"gamma": p["g_ffn"]},
         "mla": {"W_qa": p["w_qa"], "q_gamma": p["g_q"], "W_qb": p["w_qb"],
                 "W_kva": p["w_kva"], "kv_gamma": p["g_kv"],
                 "W_kvb": p["w_kvb"], "Wo": p["w_o"]}}
    if "w_g" in p:
        b.update(mlp_Wg=p["w_g"], mlp_Wu=p["w_u"], mlp_Wd=p["w_d"])
    else:
        b.update(moe_router=p["w_r"], moe_Wg=p["e_wg"], moe_Wu=p["e_wu"],
                 moe_Wd=p["e_wd"], moe_shared_Wg=p["s_wg"],
                 moe_shared_Wu=p["s_wu"], moe_shared_Wd=p["s_wd"])
    return b


def program_layout(params, state=None):
    """The same numbers arranged as `latent_moe_lm`'s parameter list (the
    embedding, a block a layer, the output layer with the final norm, the
    head and the module) and its state list. The embedding table and the
    head appear once each, as here. Pure re-arrangement."""
    m = params["mtp"]
    out = {"final_norm": {"gamma": params["g_final"]}, "W": params["head_w"],
           "mtp": {"enorm": {"gamma": m["g_e"]}, "hnorm": {"gamma": m["g_h"]},
                   "W_eh": m["w_eh"], "block": _block_layout(m["layer"]),
                   "norm": {"gamma": m["g_s"]}}}
    layers = [{"W": params["wte"]},
              *[_block_layout(p) for p in params["layers"]], out]
    states = [{} for _ in layers]
    if state is not None:
        for i, s in enumerate(state["layers"]):
            if s is not None:
                states[1 + i] = dict(s)
        states[-1] = {"mtp": dict(state["mtp"]),
                      "loss_terms": dict(state["loss_terms"])}
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


def latent_attention(u, p, model, precision):
    """Multi-head latent attention of one sequence, [T, d] -> [T, d], a
    block of queries at a time."""
    t, _ = u.shape
    nh, rkv = model["n_head"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    c_q = _rms(_mm(u, p["w_qa"], precision), p["g_q"], eps)
    q = _mm(c_q, p["w_qb"], precision).reshape(t, nh, dn + dr)
    kva = _mm(u, p["w_kva"], precision)
    c_kv = _rms(kva[:, :rkv], p["g_kv"], eps)
    kv = _mm(c_kv, p["w_kvb"], precision).reshape(t, nh, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kr = _rope(kva[:, None, rkv:], theta)[:, 0]       # [T, dr], one a token
    bq = min(QUERY_BLOCK, t)
    if t % bq:
        raise ValueError(f"T {t} is no multiple of the query block {bq}")
    pos_k = jnp.arange(t)
    scale = 1.0 / jnp.sqrt(jnp.float32(dn + dr))

    @jax.checkpoint
    def block(qb, start, kn, kr, v):
        seen = (start + jnp.arange(bq))[:, None] >= pos_k[None, :]

        @jax.checkpoint
        def head(j):
            at = functools.partial(jax.lax.dynamic_index_in_dim, axis=1,
                                   keepdims=False)
            k_j = jnp.concatenate([at(kn, j), kr], -1)
            s = _mm(at(qb, j), k_j.T, precision) * scale
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return _mm(w, at(v, j), precision)

        # one head at a time: the loop's body is compiled once, and
        # recomputed in the backward pass so that the loop keeps no scores
        return jax.lax.map(head, jnp.arange(nh)).transpose(1, 0, 2)

    starts = jnp.arange(0, t, bq)
    o = jax.lax.map(lambda a: block(a[0], a[1], kn, kr, v),
                    (q.reshape(t // bq, bq, nh, dn + dr), starts))
    return _mm(o.reshape(t, nh * dv), p["w_o"], precision)


def _gated(u, w_g, w_u, w_d, precision):
    return _mm(jax.nn.silu(_mm(u, w_g, precision)) * _mm(u, w_u, precision),
               w_d, precision)


def route(u, w_r, bias, model):
    """(sel [T, k], w [T, k]): the selected experts and their weights. The
    bias moves the selection only; the weights are the unbiased scores
    renormalised over the selected, times the scaling factor. float32 at
    every precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_r, precision="highest"))
    _, sel = jax.lax.top_k(s + bias, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return sel, w * model["routed_scaling_factor"]


def routed(u, p, bias, model, precision, held=None):
    """The part of the routed experts' result that the experts `held` =
    (first, end) give, and the counts of assignments per held expert.
    `p["e_w*"]` hold those experts' weights in order."""
    first, end = held or model["experts_held"]
    sel, w = route(u, p["w_r"], bias, model)

    @jax.checkpoint
    def add_expert(y, expert):
        j, w_g, w_u, w_d = expert
        chose = sel == j
        w_j = jnp.sum(jnp.where(chose, w, 0.0), axis=-1)
        return (y + w_j[:, None] * _gated(u, w_g, w_u, w_d, precision),
                jnp.sum(chose))

    # one expert at a time over every token: the body is compiled once, and
    # recomputed in the backward pass so that the loop keeps only its sums
    y, load = jax.lax.scan(add_expert, jnp.zeros_like(u),
                           (jnp.arange(first, end), p["e_wg"], p["e_wu"],
                            p["e_wd"]))
    load = load.astype(jnp.float32)
    return y, load, (sel.size - jnp.sum(load))[None]


def shared(u, p, precision):
    """The shared expert over every token, ungated."""
    return _gated(u, p["s_wg"], p["s_wu"], p["s_wd"], precision)


def _layer(h, p, bias, model, precision):
    """One decoder layer of one sequence; (h, the mixture's counts or
    None)."""
    u = _rms(h, p["g_op"], model["norm_eps"])
    h = h + latent_attention(u, p, model, precision)
    u = _rms(h, p["g_ffn"], model["norm_eps"])
    if "w_g" in p:
        return h + _gated(u, p["w_g"], p["w_u"], p["w_d"], precision), None
    y, load, elsewhere = routed(u, p, bias, model, precision)
    return h + y + shared(u, p, precision), (load, elsewhere)


def _run_layer(h, p, bias, model, precision):
    return jax.checkpoint(functools.partial(
        _layer, model=model, precision=precision))(h, p, bias)


def trunk_one(params, biases, tokens, model, precision="f32"):
    """[T] token ids -> ([T, d] the last layer's output before the final
    norm, each layer's counts or None)."""
    h = params["wte"][tokens]
    counts = []
    for p, bias in zip(params["layers"], biases["layers"]):
        h, c = _run_layer(h, p, bias, model, precision)
        counts.append(c)
    return h, counts


def main_logits(params, h, model, precision="f32"):
    return _mm(_rms(h, params["g_final"], model["norm_eps"]),
               params["head_w"], precision)


def module_one(params, biases, h, targets, model, precision="f32"):
    """The module's states before its norm-and-head, [T, d], from the
    trunk's `h` and the labels' embeddings (the trunk's own table), and its
    mixture's counts."""
    m, eps = params["mtp"], model["norm_eps"]
    e = params["wte"][targets]
    x = _mm(jnp.concatenate([_rms(e, m["g_e"], eps),
                             _rms(h, m["g_h"], eps)], -1),
            m["w_eh"], precision)
    return _run_layer(x, m["layer"], biases["mtp"], model, precision)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _ce_sum(s, w, y, precision):
    """Summed cross-entropy of states [N, d] under the head `w`."""
    z = _mm(s, w, precision)
    return jnp.sum(jax.nn.logsumexp(z, axis=-1)
                   - jnp.take_along_axis(z, y[:, None], 1)[:, 0])


def loss_terms_one(params, biases, tokens, targets, model, precision="f32"):
    """One sequence's summed cross-entropies: (over its T positions under
    the main head, over its T - 1 positions that have a second label under
    the module's), and every mixture's counts."""
    eps = model["norm_eps"]
    h, counts = trunk_one(params, biases, tokens, model, precision)
    main = _ce_sum(_rms(h, params["g_final"], eps), params["head_w"],
                   targets, precision)
    m, c = module_one(params, biases, h, targets, model, precision)
    mtp = _ce_sum(_rms(m, params["mtp"]["g_s"], eps)[:-1], params["head_w"],
                  targets[1:], precision)
    return main, mtp, counts + [c]


def _static(model):
    """The model's sizes as a hashable for `jit`."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, list, tuple))))


@functools.partial(jax.jit, static_argnames=("model", "precision", "batch"))
def _one(params, biases, tok, tgt, model, precision, batch):
    """One sequence's share of the batch's mean loss, its gradient, its two
    terms and its counts."""
    model = dict(model)
    t = tok.shape[0]

    def share(params):
        main, mtp, counts = loss_terms_one(params, biases, tok, tgt, model,
                                           precision)
        main, mtp = main / (batch * t), mtp / (batch * (t - 1))
        return main + model["mtp_loss_weight"] * mtp, (main, mtp, counts)

    (l, aux), g = jax.value_and_grad(share, has_aux=True)(params)
    return g, l, aux


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch and its gradient, one sequence at a time,
    and the state with this step's counts and the loss's two terms. `x`,
    `y`: int32 [B, T] inputs and targets. Returns (loss, grads, state).
    The first sequence's gradient is the sum's start, so a batch of one
    holds one tree of gradients and no accumulator beside it."""
    biases = {"layers": [None if s is None else s["expert_bias"]
                         for s in state["layers"]],
              "mtp": state["mtp"]["expert_bias"]}
    acc = tot = main = mtp = totals = None
    for i in range(x.shape[0]):
        g, l, (m1, m2, counts) = _one(params, biases, x[i], y[i],
                                      _static(model), precision, x.shape[0])
        if acc is None:
            acc, tot, main, mtp, totals = g, l, m1, m2, counts
        else:
            acc, tot, main, mtp = _add(acc, g), tot + l, main + m1, mtp + m2
            totals = [c if t is None or c is None
                      else (t[0] + c[0], t[1] + c[1])
                      for t, c in zip(totals, counts)]

    def counted(s, c):
        return None if s is None else {**s, "moe_load": c[0],
                                       "moe_elsewhere": c[1]}

    new_state = {"layers": [counted(s, c) for s, c in
                            zip(state["layers"], totals[:-1])],
                 "mtp": counted(state["mtp"], totals[-1]),
                 "loss_terms": {"main": main, "mtp": mtp}}
    return tot, acc, new_state
