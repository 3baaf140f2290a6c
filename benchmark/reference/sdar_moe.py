"""Plain reference of SDAR-MoE, a mixture-of-experts decoder trained by
block diffusion (JetLM; `model_type` `sdar_moe`, the published
`config.json` keys `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `num_experts`, `num_experts_per_tok`,
`moe_intermediate_size`, `norm_topk_prob`, `rms_norm_eps`, `rope_theta`):
Qwen3-MoE's layer under the objective of BD3-LM (arXiv:2503.09573), which
the SDAR report (arXiv:2510.06303) adopts.

**The step.** A batch holds B sequences `x` of T ids, in blocks of
`block_length` = L tokens, `b(i) = i // L`. With the step's counter `n`
(the state's `noise_step`) and `key = fold_in(PRNGKey(noise_seed), n)`,
split once into `k_t`, `k_u`:

    t[s, c]  = eps + (1 - eps) * uniform(k_t, [B, T / L])     one level a sequence and block
    m[s, i]  = uniform(k_u, [B, T]) < t[s, b(i)]               masked or not, a token
    xt[s, i] = mask_token_id if m[s, i] else x[s, i]
    w[s, i]  = m[s, i] / t[s, b(i)]                            the linear schedule's weight

The network sees 2T positions a sequence, the noised copy `xt` and then
the clean copy `x`, both at positions 0..T-1: a token embedding with no
position embedding, then per layer `h = h + attn(rms(h))`, `h = h +
moe(rms(h))`, a final RMSNorm, and the head over the NOISED copy's T rows:

    loss = (1 / (B T)) sum_{s, i} w[s, i] * -log softmax(z[s, i])[x[s, i]]

* attn: 32 query heads over 4 key/value heads (query head j reads
  key/value head j // 8), an RMSNorm over each head's width on q and on k
  before the rotation (rotate-half over the whole head, at the copy's own
  positions), scale 1/sqrt(head width), and the mask, built here from
  `b(i)` by comparison: a noised query sees the noised keys of its own
  block and the clean keys of the blocks before it; a clean query the
  clean keys of its own block and of those before it;
* moe: `p = softmax(u W_r)` in float32 over all experts, `sel =
  top_k(p)`, `w = p[sel] / sum(p[sel])`, `y = sum_j w_j E_j(u)` over the
  selected experts `j` THAT ARE HELD HERE (`experts_held`, the chip's
  share: what the experts on the other chips would add is left out, in
  the program and here alike), every expert a gated SiLU FFN. No shared
  expert, no bias, no auxiliary loss.

float32 `jax.numpy` under matmul precision "highest"; no kernel, nothing
imported from the program. The experts are a loop of dense products over
every position, weighted by zero where a position did not choose the
expert; attention runs a block of queries at a time under
`jax.checkpoint`, a head at a time, so that no `[heads, 2T, 2T]` scores
exist; a layer is recomputed in the backward pass, which changes what is
kept, not what is computed. Everything of the clean copy's last layer is
computed, though only its keys and values reach the loss. `precision`
selects what the matrix multiplications see (`lowp.py`): "f32" (the
reference), "bf16" (what the configuration states) and "fp8" (the
control); the router's product is float32 at every precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02   # assumed: the family's initializer_range, every matrix
QUERY_BLOCK = 512

_mm = lowp.matmul


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device:
    every matrix N(0, 0.02), the token embedding N(0, `embedding_std`),
    which the configuration states with its reason (at 0.02 the routers'
    inputs past the first layer are nearly one vector)."""
    v, d = model["vocab_size"], model["n_embd"]
    dh = model["head_dim"]
    q_inner, kv_inner = model["n_head"] * dh, model["n_kv_head"] * dh
    fe = model["moe_intermediate_size"]
    first, end = model["experts_held"]
    held, e = end - first, model["num_experts"]
    n_layers = len(model["layer_types"])

    @jax.jit
    def make(key):
        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for kl in jax.random.split(k_layers, n_layers):
            k = jax.random.split(kl, 8)
            layers.append({
                "g_in": ones((d,)), "g_ffn": ones((d,)),
                "w_q": nrm(k[0], (d, q_inner)),
                "w_k": nrm(k[1], (d, kv_inner)),
                "w_v": nrm(k[2], (d, kv_inner)),
                "w_o": nrm(k[3], (q_inner, d)),
                "g_q": ones((dh,)), "g_k": ones((dh,)),
                "w_r": nrm(k[4], (d, e)),
                "e_w1": nrm(k[5], (held, d, fe)),
                "e_w3": nrm(k[6], (held, d, fe)),
                "e_w2": nrm(k[7], (held, fe, d))})
        wte = model["embedding_std"] * jax.random.normal(
            k_emb, (v, d), jnp.float32)
        return {"wte": wte, "layers": layers,
                "g_final": ones((d,)), "head_w": nrm(k_head, (d, v))}

    return make(seeds.key(seed, seeds.WEIGHTS))


def init_state(model):
    """The noise's step counter, and per layer the zeroed routing
    counts."""
    first, end = model["experts_held"]
    return {"noise": {"noise_step": jnp.zeros((), jnp.int32)},
            "layers": [{"moe_load": jnp.zeros((end - first,), jnp.float32),
                        "moe_elsewhere": jnp.zeros((1,), jnp.float32)}
                       for _ in model["layer_types"]]}


def program_layout(params, state=None):
    """The same numbers arranged as `block_diffusion_moe_lm`'s parameter
    list (the input layer, which has none; the embedding; a block a layer;
    the final norm; the head) and its state list. The program's key and
    value projections are one matrix laid out [2, kv heads, head width].
    Pure re-arrangement."""
    blocks = [{"ln1": {"gamma": p["g_in"]}, "ln2": {"gamma": p["g_ffn"]},
               "mha": {"Wq": p["w_q"], "Wo": p["w_o"],
                       "Wkv": jnp.concatenate([p["w_k"], p["w_v"]], axis=1),
                       "q_gamma": p["g_q"], "k_gamma": p["g_k"]},
               "moe_router": p["w_r"], "moe_Wg": p["e_w1"],
               "moe_Wu": p["e_w3"], "moe_Wd": p["e_w2"]}
              for p in params["layers"]]
    layers = [{}, {"W": params["wte"]}, *blocks,
              {"gamma": params["g_final"]}, {"W": params["head_w"]}]
    states = [{} for _ in layers]
    if state is not None:
        states[0] = dict(state["noise"])
        for i, s in enumerate(state["layers"]):
            states[2 + i] = dict(s)
    return layers, states


def draw(step, x, model):
    """Step `step`'s noise for the batch `x` [B, T], written out from the
    definition: (xt [B, T], w [B, T], masked [B, T])."""
    b, t = x.shape
    block, eps = model["block_length"], model["noise_eps"]
    key = jax.random.fold_in(jax.random.PRNGKey(model["noise_seed"]), step)
    k_t, k_u = jax.random.split(key)
    level = eps + (1.0 - eps) * jax.random.uniform(
        k_t, (b, t // block), jnp.float32)
    level = jnp.repeat(level, block, axis=1)          # t[s, b(i)]
    masked = jax.random.uniform(k_u, (b, t), jnp.float32) < level
    xt = jnp.where(masked, jnp.int32(model["mask_token_id"]), x)
    return xt, masked.astype(jnp.float32) / level, masked


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: [N, heads, D]; the position pos[n] turns pair (i, i + D/2) by
    pos[n] * theta**(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def sees(q_clean, q_block, k_clean, k_block):
    """Whether a query (of the clean copy or not, in block `q_block`) sees
    a key: BD3-LM's three masks, by comparison of the blocks."""
    return jnp.where(q_clean, k_clean & (k_block <= q_block),
                     jnp.where(k_clean, k_block < q_block,
                               k_block == q_block))


def attention(u, p, model, precision):
    """Grouped-query attention of one sequence's two copies, [2T, d] ->
    [2T, d] (the noised copy's rows first), under the block-diffusion
    mask, a block of queries at a time."""
    n, _ = u.shape
    t = n // 2
    nh, nkv, dh = model["n_head"], model["n_kv_head"], model["head_dim"]
    eps, theta = model["norm_eps"], model["rope_theta"]
    pos = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    clean = jnp.arange(n) >= t
    blk = pos // model["block_length"]
    q = _mm(u, p["w_q"], precision).reshape(n, nh, dh)
    k = _mm(u, p["w_k"], precision).reshape(n, nkv, dh)
    v = _mm(u, p["w_v"], precision).reshape(n, nkv, dh)
    q = _rope(_rms(q, p["g_q"], eps), pos, theta)
    k = _rope(_rms(k, p["g_k"], eps), pos, theta)
    bq = min(QUERY_BLOCK, n)
    if n % bq:
        raise ValueError(f"2T {n} is no multiple of the query block {bq}")

    @jax.checkpoint
    def block(qb, start, k, v):
        rows = start + jnp.arange(bq)
        seen = sees(clean[rows][:, None], blk[rows][:, None], clean[None, :],
                    blk[None, :])

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

    starts = jnp.arange(0, n, bq)
    o = jax.lax.map(lambda a: block(a[0], a[1], k, v),
                    (q.reshape(n // bq, bq, nh, dh), starts))
    return _mm(o.reshape(n, nh * dh), p["w_o"], precision)


def _gated(u, w1, w3, w2, precision):
    return _mm(jax.nn.silu(_mm(u, w1, precision)) * _mm(u, w3, precision),
               w2, precision)


def route(u, w_r, model):
    """(sel [N, k], w [N, k]): the selected experts and their weights, the
    softmax's probabilities renormalised over the selected
    (`norm_topk_prob`). float32 at every precision."""
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

    # one expert at a time over every position: the body is compiled once,
    # and recomputed in the backward pass so that the loop keeps only its
    # sums
    y, load = jax.lax.scan(add_expert, jnp.zeros_like(u),
                           (jnp.arange(first, end), p["e_w1"], p["e_w3"],
                            p["e_w2"]))
    load = load.astype(jnp.float32)
    return y, load, (sel.size - jnp.sum(load))[None]


def _layer(h, p, model, precision):
    """One decoder layer of one sequence's 2T positions; (h, the routing's
    counts)."""
    h = h + attention(_rms(h, p["g_in"], model["norm_eps"]), p, model,
                      precision)
    y, load, elsewhere = experts(_rms(h, p["g_ffn"], model["norm_eps"]), p,
                                 model, precision)
    return h + y, (load, elsewhere)


def loss_sum_one(params, xt, x, w, model, precision="f32"):
    """One sequence: the noised ids `xt`, the clean ids `x` and the
    weights `w`, [T] each -> (sum_i w_i CE_i, each layer's counts)."""
    t = x.shape[0]
    h = params["wte"][jnp.concatenate([xt, x])]
    counts = []
    for p in params["layers"]:
        h, c = jax.checkpoint(functools.partial(
            _layer, model=model, precision=precision))(h, p)
        counts.append(c)
    h = _rms(h, params["g_final"], model["norm_eps"])
    z = _mm(h[:t], params["head_w"], precision)       # the noised copy's
    ce = (jax.nn.logsumexp(z, axis=-1)
          - jnp.take_along_axis(z, x[:, None], 1)[:, 0])
    return jnp.sum(w * ce), counts


def _static(model):
    """The model's sizes as a hashable for `jit`."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, list, tuple))))


@functools.partial(jax.jit, static_argnames=("model", "precision", "n_tok"))
def _one(params, xt, x, w, model, precision, n_tok):
    """One sequence's share of the batch's loss, its gradient and its
    counts."""
    def share(params):
        total, counts = loss_sum_one(params, xt, x, w, dict(model),
                                     precision)
        return total / n_tok, counts

    return jax.value_and_grad(share, has_aux=True)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grad(params, state, x, y, model, precision="f32"):
    """The step's loss and its gradient, one sequence at a time (a batch
    of one keeps a single gradient tree alive), and the state after it:
    the counter one on, this step's counts. `x`: int32 [B, T] ids; `y`,
    the labels the traffic feeds, are the ids themselves and are not read.
    Returns (loss, grads, state)."""
    n_tok = x.shape[0] * x.shape[1]
    noise = state["noise"]
    xt, w, _ = draw(noise["noise_step"], x, model)
    acc, tot, totals = None, jnp.float32(0.0), None
    for i in range(x.shape[0]):
        (l, counts), g = _one(params, xt[i], x[i], w[i], _static(model),
                              precision, n_tok)
        acc = g if acc is None else _add(acc, g)
        tot = tot + l
        totals = counts if totals is None else [
            (t[0] + c[0], t[1] + c[1]) for t, c in zip(totals, counts)]
    new_state = {
        "noise": {"noise_step": noise["noise_step"] + 1},
        "layers": [{**s, "moe_load": c[0], "moe_elsewhere": c[1]}
                   for s, c in zip(state["layers"], totals)]}
    return tot, acc, new_state
