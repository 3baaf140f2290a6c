"""Plain reference of Qwen3-Next, a hybrid linear/softmax-attention
mixture-of-experts decoder (Qwen; `model_type` `qwen3_next`, the published
`config.json` keys `hidden_size`, `full_attention_interval`, `head_dim`,
`num_attention_heads`, `num_key_value_heads`, `partial_rotary_factor`,
`linear_conv_kernel_dim`, `linear_key_head_dim`, `linear_num_key_heads`,
`linear_num_value_heads`, `linear_value_head_dim`, `moe_intermediate_size`,
`shared_expert_intermediate_size`, `num_experts`, `num_experts_per_tok`,
`norm_topk_prob`, `rms_norm_eps`, `rope_theta`): a token embedding with no
position embedding, then per layer `h = h + mixer(norm(h))`, `h = h +
moe(norm(h))`, a final norm and the head. No bias anywhere. Every norm is
`x / sqrt(mean(x^2) + eps) * (1 + g)`, `g` starting at zero.

* mixer, `layer_types[i] == "linear_attention"`: the gated delta rule.
  `[q | k | v | z] = u W_qkvz`, `[b | a] = u W_ba`, each part whole and its
  heads in order; `[q | k | v] = silu(conv4(.))`, depthwise and causal;
  `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`; `q =
  l2norm(q) / sqrt(dk)`, `k = l2norm(k)`; a value head (reading key head
  j // 2) `S = exp(g_t) S; S = S + beta_t k_t (v_t - S^T k_t)^T; o_t = S^T
  q_t`, RUN TOKEN BY TOKEN, which is the definition; then a head `o / sqrt(
  mean(o^2) + eps) * w * silu(z)` and `W_out`;
* mixer, `"full_attention"`: `[q | gate]` a head from a doubled query
  projection, 16 query heads over 2 key/value heads (query head j reads
  key/value head j // 8), the norm over each head's width on q and on k,
  rotate-half over the first `partial_rotary_factor` of a head, causal,
  scale 1/sqrt(head width), the result times `sigmoid(gate)`, `W_o`;
* ffn: `p = softmax(u W_r)` in float32 over all experts, `sel = top_k(p)`,
  `w = p[sel] / sum(p[sel])`, `y = sum_j w_j E_j(u)` over the selected
  experts `j` THAT ARE HELD HERE (`experts_held`, the chip's share: what
  the experts on the other chips would add is left out, in the program and
  here alike) `+ sigmoid(u w_sg) E_shared(u)`, every expert a gated SiLU
  FFN.

Departures from the published model, each also in the configuration file:
no multi-token-prediction layer, no dropout. The router's product and the
recurrence (its state, its decays, its two products a token) are float32
at every `precision`: the configuration states both so, and an fp8 recipe
keeps its routers and recurrent states out of fp8 too.

float32 `jax.numpy` under matmul precision "highest"; no kernel, nothing
imported from the program. The recurrence is a `lax.scan` over positions,
in blocks recomputed in the backward pass so that it keeps one state a
block and not one a token; the experts are a loop of dense products over
every token, weighted by zero where a token did not choose the expert;
attention runs a block of queries at a time, a head at a time, so that no
`[heads, T, T]` scores exist; a layer is recomputed in the backward pass
(`jax.checkpoint`), which changes what is kept, not what is computed.
`precision` selects what the matrix multiplications see (`lowp.py`):
"f32" (the reference), "bf16" (what the configuration states) and "fp8"
(the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02     # assumed: the family's usual initializer range
A_RANGE = (1e-3, 16.0)  # A_log = log U(0, 16); the low end keeps it finite
L2_EPS = 1e-6       # inside the l2 norm's root, as the published code
QUERY_BLOCK = 512
SCAN_BLOCK = 64     # positions of the recurrence recomputed together

_mm = lowp.matmul


def _widths(model):
    """(key width, value width) of the gated delta rule, over all heads."""
    return (model["linear_num_key_heads"] * model["linear_key_head_dim"],
            model["linear_num_value_heads"] * model["linear_value_head_dim"])


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device."""
    v, d = model["vocab_size"], model["n_embd"]
    dh = model["head_dim"]
    q_inner, kv_inner = model["n_head"] * dh, model["n_kv_head"] * dh
    fe, fs = (model["moe_intermediate_size"],
              model["shared_expert_intermediate_size"])
    first, end = model["experts_held"]
    held, e = end - first, model["num_experts"]
    kw, vw = _widths(model)
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    kinds = list(model["layer_types"])

    @jax.jit
    def make(key):
        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def zeros(shape):
            return jnp.zeros(shape, jnp.float32)

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for mixer, kl in zip(kinds, jax.random.split(k_layers, len(kinds))):
            k = jax.random.split(kl, 14)
            p = {"g_in": zeros((d,)), "g_ffn": zeros((d,))}
            if mixer == "linear_attention":
                p.update(w_qkvz=nrm(k[0], (d, 2 * kw + 2 * vw)),
                         w_ba=nrm(k[1], (d, 2 * hv)),
                         conv_w=nrm(k[2], (2 * kw + vw, taps)),
                         a_log=jnp.log(jax.random.uniform(
                             k[3], (hv,), jnp.float32, *A_RANGE)),
                         dt_bias=jnp.ones((hv,), jnp.float32),
                         g_o=jnp.ones((dv,), jnp.float32),
                         w_out=nrm(k[4], (vw, d)))
            else:
                p.update(w_q=nrm(k[0], (d, 2 * q_inner)),
                         w_k=nrm(k[1], (d, kv_inner)),
                         w_v=nrm(k[2], (d, kv_inner)),
                         w_o=nrm(k[3], (q_inner, d)),
                         g_q=zeros((dh,)), g_k=zeros((dh,)))
            p.update(w_r=nrm(k[5], (d, e)),
                     e_w1=nrm(k[6], (held, d, fe)),
                     e_w3=nrm(k[7], (held, d, fe)),
                     e_w2=nrm(k[8], (held, fe, d)),
                     s_w1=nrm(k[9], (d, fs)), s_w3=nrm(k[10], (d, fs)),
                     s_w2=nrm(k[11], (fs, d)), w_sg=nrm(k[12], (d, 1)))
            layers.append(p)
        return {"wte": nrm(k_emb, (v, d)), "layers": layers,
                "g_final": zeros((d,)), "head_w": nrm(k_head, (d, v))}

    return make(seeds.key(seed, seeds.WEIGHTS))


def init_state(model):
    """Per layer the zeroed routing counts: no bias moves a softmax
    router's selection, so there is nothing else."""
    first, end = model["experts_held"]
    return [{"moe_load": jnp.zeros((end - first,), jnp.float32),
             "moe_elsewhere": jnp.zeros((1,), jnp.float32)}
            for _ in model["layer_types"]]


def program_layout(params, state=None):
    """The same numbers arranged as `gated_delta_moe_lm`'s parameter list
    (the embedding, a block a layer, the final norm, the head) and its
    state list. The program's key and value projections are one matrix
    laid out [2, kv heads, head width]. Pure re-arrangement."""
    blocks = []
    for p in params["layers"]:
        b = {"ln1": {"gamma": p["g_in"]}, "ln2": {"gamma": p["g_ffn"]},
             "moe_router": p["w_r"], "moe_Wg": p["e_w1"],
             "moe_Wu": p["e_w3"], "moe_Wd": p["e_w2"],
             "moe_shared_Wg": p["s_w1"], "moe_shared_Wu": p["s_w3"],
             "moe_shared_Wd": p["s_w2"], "moe_shared_gate": p["w_sg"]}
        if "w_qkvz" in p:
            b["gdn"] = {"W_qkvz": p["w_qkvz"], "W_ba": p["w_ba"],
                        "conv_w": p["conv_w"], "A_log": p["a_log"],
                        "dt_bias": p["dt_bias"], "norm_w": p["g_o"],
                        "W_out": p["w_out"]}
        else:
            b["mha"] = {"Wq": p["w_q"], "Wo": p["w_o"],
                        "Wkv": jnp.concatenate([p["w_k"], p["w_v"]], axis=1),
                        "q_gamma": p["g_q"], "k_gamma": p["g_k"]}
        blocks.append(b)
    layers = [{"W": params["wte"]}, *blocks, {"gamma": params["g_final"]},
              {"W": params["head_w"]}]
    states = [{} for _ in layers]
    if state is not None:
        for i, s in enumerate(state):
            states[1 + i] = dict(s)
    return layers, states


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta, rotary):
    """x: [T, heads, D]; over the first `rotary` of a head position t turns
    pair (i, i + rotary/2) by t * theta**(-2i/rotary); the rest pass."""
    t = x.shape[0]
    xr, rest = x[..., :rotary], x[..., rotary:]
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                          / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-xr[..., rotary // 2:], xr[..., :rotary // 2]],
                             -1)
    return jnp.concatenate([xr * cos + turned * sin, rest], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, a token at a time. `q`, `k` [T, H, dk], `v`
    [T, H, dv], `g`, `beta` [T, H] -> `o` [T, H, dv]."""
    t, h, dk = q.shape
    pad = -t % SCAN_BLOCK
    # a padded position decays nothing (g 0) and writes nothing (beta 0)
    xs = tuple(jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
               .reshape(-1, SCAN_BLOCK, *x.shape[1:])
               for x in (q, k, v, g, beta))

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        read = jnp.einsum("hd,hde->he", k_t, s, precision="highest")
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - read)[:, None, :]
        return s, jnp.einsum("hde,hd->he", s, q_t, precision="highest")

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    s0 = jnp.zeros((h, dk, v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(block, s0, xs)
    return o.reshape(-1, h, v.shape[-1])[:t]


def gated_delta(u, p, model, precision):
    """The gated delta rule's mixer of one sequence, [T, d] -> [T, d]."""
    t, _ = u.shape
    kw, vw = _widths(model)
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk = model["linear_key_head_dim"]
    qkvz = _mm(u, p["w_qkvz"], precision)
    ba = _mm(u, p["w_ba"], precision)
    qkv, z = qkvz[:, :2 * kw + vw], qkvz[:, 2 * kw + vw:]
    taps = p["conv_w"].shape[1]
    c = jnp.zeros_like(qkv)
    for j in range(taps):                 # tap j meets x[t - (taps-1-j)]
        back = taps - 1 - j
        c = c + jnp.pad(qkv, ((back, 0), (0, 0)))[:t] * p["conv_w"][:, j]
    qkv = jax.nn.silu(c)
    q = qkv[:, :kw].reshape(t, hk, dk)
    k = qkv[:, kw:2 * kw].reshape(t, hk, dk)
    v = qkv[:, 2 * kw:].reshape(t, hv, -1)

    def l2norm(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q = jnp.repeat(l2norm(q) / jnp.sqrt(jnp.float32(dk)), hv // hk, axis=1)
    k = jnp.repeat(l2norm(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + model["norm_eps"]) * p["g_o"]
    o = o * jax.nn.silu(z.reshape(o.shape))
    return _mm(o.reshape(t, vw), p["w_out"], precision)


def attention(u, p, model, precision):
    """Gated grouped-query causal attention of one sequence, [T, d] ->
    [T, d], a block of queries at a time."""
    t, _ = u.shape
    nh, nkv, dh = model["n_head"], model["n_kv_head"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    rotary = int(dh * model["partial_rotary_factor"])
    qg = _mm(u, p["w_q"], precision).reshape(t, nh, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(u, p["w_k"], precision).reshape(t, nkv, dh)
    v = _mm(u, p["w_v"], precision).reshape(t, nkv, dh)
    q = _rope(_norm(q, p["g_q"], eps), theta, rotary)
    k = _rope(_norm(k, p["g_k"], eps), theta, rotary)
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
    o = o.reshape(t, nh, dh) * jax.nn.sigmoid(gate)
    return _mm(o.reshape(t, nh * dh), p["w_o"], precision)


def _gated(u, w1, w3, w2, precision):
    return _mm(jax.nn.silu(_mm(u, w1, precision)) * _mm(u, w3, precision),
               w2, precision)


def route(u, w_r, model):
    """(sel [T, k], w [T, k]): the selected experts and their weights, the
    softmax's probabilities renormalised over the selected. float32 at
    every precision."""
    p = jax.nn.softmax(jnp.matmul(u, w_r, precision="highest"), axis=-1)
    w, sel = jax.lax.top_k(p, model["num_experts_per_tok"])
    return sel, w / jnp.sum(w, -1, keepdims=True)


def experts(u, p, model, precision, held=None):
    """The part of the routed result that the experts `held` = (first,
    end) give, and the counts of assignments per held expert and
    elsewhere. `p["e_w*"]` hold those experts' weights in order."""
    first, end = held or model["experts_held"]
    sel, w = route(u, p["w_r"], model)

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


def shared_expert(u, p, precision):
    """What every chip computes alike: the shared expert times its gate."""
    return jax.nn.sigmoid(_mm(u, p["w_sg"], precision)) * _gated(
        u, p["s_w1"], p["s_w3"], p["s_w2"], precision)


def _layer(h, p, model, precision):
    """One decoder layer of one sequence; (h, the routing's counts)."""
    u = _norm(h, p["g_in"], model["norm_eps"])
    if "w_qkvz" in p:
        h = h + gated_delta(u, p, model, precision)
    else:
        h = h + attention(u, p, model, precision)
    u = _norm(h, p["g_ffn"], model["norm_eps"])
    y, load, elsewhere = experts(u, p, model, precision)
    return h + y + shared_expert(u, p, precision), (load, elsewhere)


def logits_one(params, tokens, model, precision="f32"):
    """[T] token ids -> ([T, V] logits, each layer's counts)."""
    h = params["wte"][tokens]
    counts = []
    for p in params["layers"]:
        h, c = jax.checkpoint(functools.partial(
            _layer, model=model, precision=precision))(h, p)
        counts.append(c)
    h = _norm(h, params["g_final"], model["norm_eps"])
    return _mm(h, params["head_w"], precision), counts


def loss_sum_one(params, tokens, targets, model, precision="f32"):
    """The per-token cross-entropies of one sequence, summed."""
    z, counts = logits_one(params, tokens, model, precision)
    ce = (jax.nn.logsumexp(z, axis=-1)
          - jnp.take_along_axis(z, targets[:, None], 1)[:, 0])
    return jnp.sum(ce), counts


def _static(model):
    """The model's sizes as a hashable for `jit`."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, list, tuple))))


@functools.partial(jax.jit, static_argnames=("model", "precision", "n_tok"))
def _one(params, tok, tgt, model, precision, n_tok):
    """One sequence's share of the batch's mean loss, its gradient and
    its counts."""
    def share(params):
        total, counts = loss_sum_one(params, tok, tgt, dict(model),
                                     precision)
        return total / n_tok, counts

    return jax.value_and_grad(share, has_aux=True)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """Mean loss over the batch and its gradient, one sequence at a time
    (a batch of one keeps a single gradient tree alive), and the state
    with this step's counts. `x`, `y`: int32 [B, T] inputs and targets.
    Returns (loss, grads, state)."""
    n_tok = x.shape[0] * x.shape[1]
    acc, tot, totals = None, jnp.float32(0.0), None
    for i in range(x.shape[0]):
        (l, counts), g = _one(params, x[i], y[i], _static(model), precision,
                              n_tok)
        acc = g if acc is None else _add(acc, g)
        tot = tot + l
        totals = counts if totals is None else [
            (t[0] + c[0], t[1] + c[1]) for t, c in zip(totals, counts)]
    new_state = [{**s, "moe_load": c[0], "moe_elsewhere": c[1]}
                 for s, c in zip(state, totals)]
    return tot, acc, new_state
