"""Plain reference of Nemotron-H as NVIDIA-Nemotron-3-Nano-30B-A3B
configures it, a hybrid Mamba-2 / attention mixture-of-experts decoder
(`model_type` `nemotron_h`; the published `config.json` keys `hidden_size`,
`hybrid_override_pattern`, `mamba_num_heads`, `mamba_head_dim`, `n_groups`,
`ssm_state_size`, `conv_kernel`, `use_conv_bias`, `time_step_min|max|floor`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`n_routed_experts`, `num_experts_per_tok`, `moe_intermediate_size`,
`moe_shared_expert_intermediate_size`, `routed_scaling_factor`,
`norm_topk_prob`, `mlp_hidden_act`, `layer_norm_epsilon`,
`rescale_prenorm_residual`): a token embedding with no position embedding,
then per layer ONE part, `h = h + part(norm(h))`, `norm(x) = x /
sqrt(mean(x^2) + eps) * g`; a final norm and an untied head. The pattern
names the part a character a layer:

* `M`, Mamba-2: `[z | x B C | dt] = u W_in`; `[x | B | C] =
  silu(conv4(.) + b_conv)`, depthwise and causal; a head `dt = softplus(dt
  + dt_bias)`, `A = -exp(A_log)`; a head (reading group h // (H / G)) a
  state `S` in `R^{P x N}`: `S = exp(dt_t A) S + dt_t x_t B_t^T`, `y_t = S
  C_t + D x_t`, RUN TOKEN BY TOKEN, which is the definition; then `y *
  silu(z)`, RMS-normed over each of the G groups of channels apart, times a
  gain; `W_out`;
* `*`, attention: 32 query heads over 2 key/value heads (query head j reads
  key/value head j // 16), causal, scale 1/sqrt(head width), no bias, NO
  positional encoding, no norm on q or k, no gate;
* `E`, the mixture: `s = sigmoid(u W_r)` in float32 over all experts, `sel
  = top_k(s + e_score_correction_bias)` (one group: the group limit is a
  no-op), `w = s[sel] / (sum(s[sel]) + eps) * routed_scaling_factor`, `y =
  sum_j w_j E_j(u)` over the selected experts `j` THAT ARE HELD HERE
  (`experts_held`, the chip's share: what the experts on the other chips
  would add is left out, in the program and here alike) `+ E_shared(u)`,
  every expert UNGATED: `E(u) = relu(u W_up)^2 W_down`; no gate on the
  shared expert.

Departures from the published model, each also in the configuration file:
no dropout (none is published); the renormalisation adds 1e-6 to the
selected scores' sum where the published code adds 1e-20 (the sum of six
sigmoids is of order 3: the difference is 3e-7 of a weight); the
correction bias is held fixed at its initial zeros (no key of the config
gives its update rule). The router's product and the recurrence (its
state, its decays, its products a token) are float32 at every `precision`:
the configuration states both so, and an fp8 recipe keeps its routers and
recurrent states out of fp8 too.

float32 `jax.numpy` under matmul precision "highest"; no kernel, nothing
imported from the program. The recurrence is a `lax.scan` over positions,
in blocks recomputed in the backward pass so that it keeps one state a
block and not one a token; the experts are a loop of dense products over
every token, weighted by zero where a token did not choose the expert;
attention runs a block of queries at a time, a head at a time, so that no
`[heads, T, T]` scores exist; the head and the loss run a block of rows at
a time; a layer is recomputed in the backward pass (`jax.checkpoint`),
which changes what is kept, not what is computed. `precision` selects what
the matrix multiplications see (`lowp.py`): "f32" (the reference), "bf16"
(what the configuration states) and "fp8" (the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import seeds
from benchmark.reference import lowp

INIT_STD = 0.02     # assumed: the family's usual initializer_range
ROUTER_EPS = 1e-6   # added to the selected scores' sum (published: 1e-20)
QUERY_BLOCK = 512
SCAN_BLOCK = 64     # positions of the recurrence recomputed together
LOSS_BLOCK = 1024   # rows of the head and the loss computed together

_mm = lowp.matmul


def _ssm_widths(model):
    """(d_inner, groups x state) of a Mamba-2 mixer."""
    return (model["mamba_num_heads"] * model["mamba_head_dim"],
            model["n_groups"] * model["ssm_state_size"])


def init(seed, model):
    """Weights from the seed, float32, in one jitted call on the device.
    Every matrix and the embedding N(0, 0.02); a Mamba-2 out-projection
    that divided by the root of the PUBLISHED depth
    (`rescale_prenorm_residual`); the convolution's taps and bias U(-1/2,
    1/2) (torch's Conv1d default at 4 taps a channel, which the published
    initialisation leaves alone); `A_log = log(1..H)`, `D = 1`, `dt_bias`
    the inverse softplus of `exp(U(log time_step_min, log time_step_max))`
    floored at `time_step_floor`; every norm's gain 1."""
    v, d = model["vocab_size"], model["n_embd"]
    dh = model["head_dim"]
    q_inner, kv_inner = model["n_head"] * dh, model["n_kv_head"] * dh
    fe = model["moe_intermediate_size"]
    fs = model["moe_shared_expert_intermediate_size"]
    first, end = model["experts_held"]
    held, e = end - first, model["num_experts"]
    inner, gn = _ssm_widths(model)
    hs, taps = model["mamba_num_heads"], model["conv_kernel"]
    depth = model["num_hidden_layers_published"]
    lo, hi = model["time_step_min"], model["time_step_max"]
    pattern = model["pattern"]

    @jax.jit
    def make(key):
        def nrm(key, shape):
            return INIT_STD * jax.random.normal(key, shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for kind, kl in zip(pattern, jax.random.split(k_layers,
                                                      len(pattern))):
            k = jax.random.split(kl, 6)
            p = {"g": ones((d,))}
            if kind == "M":
                bound = taps ** -0.5
                dt = jnp.exp(jax.random.uniform(
                    k[3], (hs,), jnp.float32, jnp.log(lo), jnp.log(hi)))
                dt = jnp.maximum(dt, model["time_step_floor"])
                p.update(
                    w_in=nrm(k[0], (d, 2 * inner + 2 * gn + hs)),
                    conv_w=jax.random.uniform(
                        k[1], (inner + 2 * gn, taps), jnp.float32, -bound,
                        bound),
                    conv_b=jax.random.uniform(
                        k[2], (inner + 2 * gn,), jnp.float32, -bound, bound),
                    a_log=jnp.log(jnp.arange(1, hs + 1, dtype=jnp.float32)),
                    d_skip=ones((hs,)),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    g_y=ones((inner,)),
                    w_out=nrm(k[4], (inner, d)) / jnp.sqrt(
                        jnp.float32(depth)))
            elif kind == "*":
                p.update(w_q=nrm(k[0], (d, q_inner)),
                         w_k=nrm(k[1], (d, kv_inner)),
                         w_v=nrm(k[2], (d, kv_inner)),
                         w_o=nrm(k[3], (q_inner, d)))
            else:
                p.update(w_r=nrm(k[0], (d, e)),
                         e_up=nrm(k[1], (held, d, fe)),
                         e_down=nrm(k[2], (held, fe, d)),
                         s_up=nrm(k[3], (d, fs)), s_down=nrm(k[4], (fs, d)))
            layers.append(p)
        return {"wte": nrm(k_emb, (v, d)), "layers": layers,
                "g_final": ones((d,)), "head_w": nrm(k_head, (d, v))}

    return make(seeds.key(seed, seeds.WEIGHTS))


def init_state(model):
    """Per layer: None, or a mixture's correction bias [num_experts]
    (zeros, as the published code initialises it; fixed) and its zeroed
    counts."""
    first, end = model["experts_held"]
    return [None if kind != "E" else {
        "expert_bias": jnp.zeros((model["num_experts"],), jnp.float32),
        "moe_load": jnp.zeros((end - first,), jnp.float32),
        "moe_elsewhere": jnp.zeros((1,), jnp.float32)}
        for kind in model["pattern"]]


def program_layout(params, state=None):
    """The same numbers arranged as `state_space_moe_lm`'s parameter list
    (the embedding, a block a layer, the final norm, the head) and its
    state list. A block holds its one part and that part's norm. The
    program's key and value projections are one matrix laid out [2, kv
    heads, head width]. Pure re-arrangement."""
    blocks = []
    for p in params["layers"]:
        if "w_in" in p:
            b = {"ln1": {"gamma": p["g"]},
                 "ssm": {"W_in": p["w_in"], "conv_w": p["conv_w"],
                         "conv_b": p["conv_b"], "A_log": p["a_log"],
                         "D": p["d_skip"], "dt_bias": p["dt_bias"],
                         "norm_w": p["g_y"], "W_out": p["w_out"]}}
        elif "w_q" in p:
            b = {"ln1": {"gamma": p["g"]},
                 "mha": {"Wq": p["w_q"], "Wo": p["w_o"],
                         "Wkv": jnp.concatenate([p["w_k"], p["w_v"]],
                                                axis=1)}}
        else:
            b = {"ln2": {"gamma": p["g"]}, "moe_router": p["w_r"],
                 "moe_Wu": p["e_up"], "moe_Wd": p["e_down"],
                 "moe_shared_Wu": p["s_up"], "moe_shared_Wd": p["s_down"]}
        blocks.append(b)
    layers = [{"W": params["wte"]}, *blocks, {"gamma": params["g_final"]},
              {"W": params["head_w"]}]
    states = [{} for _ in layers]
    if state is not None:
        for i, s in enumerate(state):
            if s is not None:
                states[1 + i] = dict(s)
    return layers, states


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def selective_scan(x, dt, a, b, c, d_skip):
    """The recurrence itself, a token at a time. `x` [T, H, P], `dt` [T,
    H], `a` [H], `b`, `c` [T, H, N] (each head its group's), `d_skip` [H]
    -> `y` [T, H, P]."""
    t, h, p = x.shape
    pad = -t % SCAN_BLOCK
    # a padded position decays nothing and writes nothing (dt 0)
    xs = tuple(jnp.pad(u, [(0, pad)] + [(0, 0)] * (u.ndim - 1))
               .reshape(-1, SCAN_BLOCK, *u.shape[1:])
               for u in (x, dt, b, c))

    def step(s, u):
        x_t, dt_t, b_t, c_t = u
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, c_t, precision="highest")
        return s, y + d_skip[:, None] * x_t

    @jax.checkpoint
    def block(s, u):
        return jax.lax.scan(step, s, u)

    s0 = jnp.zeros((h, p, b.shape[-1]), x.dtype)
    _, y = jax.lax.scan(block, s0, xs)
    return y.reshape(-1, h, p)[:t]


def gated_group_norm(y, z, g, groups, eps):
    """`y * silu(z)`, each of the `groups` runs of channels normed by its
    own root mean square, times the gain `g`. [T, F] -> [T, F]."""
    u = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)
    return u.reshape(y.shape) * g


def mamba2(u, p, model, precision):
    """The Mamba-2 mixer of one sequence, [T, d] -> [T, d]."""
    t, _ = u.shape
    inner, gn = _ssm_widths(model)
    hs, groups = model["mamba_num_heads"], model["n_groups"]
    proj = _mm(u, p["w_in"], precision)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    taps = p["conv_w"].shape[1]
    conv = jnp.zeros_like(xbc)
    for j in range(taps):                 # tap j meets x[t - (taps-1-j)]
        back = taps - 1 - j
        conv = conv + jnp.pad(xbc, ((back, 0), (0, 0)))[:t] * p["conv_w"][:, j]
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :inner].reshape(t, hs, -1)
    b = xbc[:, inner:inner + gn].reshape(t, groups, -1)
    c = xbc[:, inner + gn:].reshape(t, groups, -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(p["a_log"]),
                       jnp.repeat(b, hs // groups, axis=1),
                       jnp.repeat(c, hs // groups, axis=1), p["d_skip"])
    y = gated_group_norm(y.reshape(t, inner), z, p["g_y"], groups,
                         model["norm_eps"])
    return _mm(y, p["w_out"], precision)


def attention(u, p, model, precision):
    """Grouped-query causal attention of one sequence without positions,
    [T, d] -> [T, d], a block of queries at a time."""
    t, _ = u.shape
    nh, nkv, dh = model["n_head"], model["n_kv_head"], model["head_dim"]
    q = _mm(u, p["w_q"], precision).reshape(t, nh, dh)
    k = _mm(u, p["w_k"], precision).reshape(t, nkv, dh)
    v = _mm(u, p["w_v"], precision).reshape(t, nkv, dh)
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


def _ungated(u, w_up, w_down, precision):
    return _mm(jnp.square(jax.nn.relu(_mm(u, w_up, precision))), w_down,
               precision)


def route(u, w_r, bias, model):
    """(sel [T, k], w [T, k]): the selected experts and their weights.
    The bias moves the selection only; the weights are the unbiased scores
    renormalised over the selected, times the scaling factor. float32 at
    every precision."""
    s = jax.nn.sigmoid(jnp.matmul(u, w_r, precision="highest"))
    _, sel = jax.lax.top_k(s + bias, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return sel, w * model["routed_scaling_factor"]


def experts(u, p, bias, model, precision, held=None):
    """The part of the routed result that the experts `held` = (first,
    end) give, and the counts of assignments per held expert and
    elsewhere. `p["e_*"]` hold those experts' weights in order."""
    first, end = held or model["experts_held"]
    sel, w = route(u, p["w_r"], bias, model)

    @jax.checkpoint
    def add_expert(y, expert):
        j, w_up, w_down = expert
        chose = sel == j
        w_j = jnp.sum(jnp.where(chose, w, 0.0), axis=-1)
        return (y + w_j[:, None] * _ungated(u, w_up, w_down, precision),
                jnp.sum(chose))

    # one expert at a time over every token: the body is compiled once, and
    # recomputed in the backward pass so that the loop keeps only its sums
    y, load = jax.lax.scan(add_expert, jnp.zeros_like(u),
                           (jnp.arange(first, end), p["e_up"], p["e_down"]))
    load = load.astype(jnp.float32)
    return y, load, (sel.size - jnp.sum(load))[None]


def shared_expert(u, p, precision):
    """What every chip computes alike: the shared expert, ungated."""
    return _ungated(u, p["s_up"], p["s_down"], precision)


def _layer(h, p, bias, model, precision):
    """One layer of one sequence; (h, the routing's counts or None)."""
    u = _norm(h, p["g"], model["norm_eps"])
    if "w_in" in p:
        return h + mamba2(u, p, model, precision), None
    if "w_q" in p:
        return h + attention(u, p, model, precision), None
    y, load, elsewhere = experts(u, p, bias, model, precision)
    return h + y + shared_expert(u, p, precision), (load, elsewhere)


def hidden_one(params, biases, tokens, model, precision="f32"):
    """[T] token ids -> ([T, d] the final norm's result, each layer's
    counts)."""
    h = params["wte"][tokens]
    counts = []
    for p, bias in zip(params["layers"], biases):
        h, c = jax.checkpoint(functools.partial(
            _layer, model=model, precision=precision))(h, p, bias)
        counts.append(c)
    return _norm(h, params["g_final"], model["norm_eps"]), counts


def logits_one(params, biases, tokens, model, precision="f32"):
    """[T] token ids -> ([T, V] logits, each layer's counts)."""
    h, counts = hidden_one(params, biases, tokens, model, precision)
    return _mm(h, params["head_w"], precision), counts


def loss_sum_one(params, biases, tokens, targets, model, precision="f32"):
    """The per-token cross-entropies of one sequence, summed, the head a
    block of rows at a time."""
    h, counts = hidden_one(params, biases, tokens, model, precision)
    rows = min(LOSS_BLOCK, h.shape[0])
    if h.shape[0] % rows:
        raise ValueError(f"T {h.shape[0]} is no multiple of {rows}")

    @jax.checkpoint
    def block(hb_tb):
        hb, tb = hb_tb
        z = _mm(hb, params["head_w"], precision)
        return jnp.sum(jax.nn.logsumexp(z, axis=-1)
                       - jnp.take_along_axis(z, tb[:, None], 1)[:, 0])

    ce = jax.lax.map(block, (h.reshape(-1, rows, h.shape[1]),
                             targets.reshape(-1, rows)))
    return jnp.sum(ce), counts


def _static(model):
    """The model's sizes as a hashable for `jit`."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, str, list, tuple))))


@functools.partial(jax.jit, static_argnames=("model", "precision", "n_tok"))
def _one(params, biases, tok, tgt, model, precision, n_tok):
    """One sequence's share of the batch's mean loss, its gradient and
    its counts."""
    def share(params):
        total, counts = loss_sum_one(params, biases, tok, tgt, dict(model),
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
    biases = [None if s is None else s["expert_bias"] for s in state]
    acc, tot, totals = None, jnp.float32(0.0), None
    for i in range(x.shape[0]):
        (l, counts), g = _one(params, biases, x[i], y[i], _static(model),
                              precision, n_tok)
        acc = g if acc is None else _add(acc, g)
        tot = tot + l
        totals = counts if totals is None else [
            c if c is None else (t[0] + c[0], t[1] + c[1])
            for t, c in zip(totals, counts)]
    new_state = [None if s is None else
                 {**s, "moe_load": c[0], "moe_elsewhere": c[1]}
                 for s, c in zip(state, totals)]
    return tot, acc, new_state
