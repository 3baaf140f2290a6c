"""Routed experts, two layers that share no logic.

``routed_experts`` is ``TransformerBlock``'s ``ffn="moe"``: one chip's
share of a dropless top-k layer. A float32 router scores all the experts
(sigmoid scores with an expert bias that moves the selection only, or a
softmax over all of them), the ``N k`` assignments are sorted by held
expert (those routed elsewhere behind a sentinel) and the held experts
run as grouped products over the counts with the gate's activation
between them (ops/expert_ffn.py over ops/grouped_matmul.py's kernels).
Every shape is static and no token is dropped; the matrix work, from PR 33
the row movement around it (tokens into sorted order, results back, and
both backward passes, all written by hand; ops/moe_rows.py) and from PR 50
the activation between the products, forward and backward, follow the rows
really routed here: kernels whose grids skip the row tiles past the count
read from the group sizes.

``MoETransformerBlock`` is the older Switch-style block monolith, kept
for its users (ROADMAP D6(a)). Reference analog: none, DL4J has no MoE;
it completes the dp/tp/sp/pp/ep parallelism set
(``__graft_entry__.dryrun_multichip`` exercises every axis). Top-1
router with a capacity limit ``C = ceil(tokens / E * capacity_factor)``,
overflow tokens passing through the residual unchanged; dispatch and
combine are dense einsums against a ``[N, E, C]`` one-hot tensor
(gather-free, differentiable through the router probability); expert
weights are stacked on a leading axis that GSPMD partitions over
``model`` (parallel/data_parallel.py's param-spec rule), inserting the
all-to-alls; Switch's load-balancing loss ``E * sum_e f_e * p_e`` goes
through ``aux_loss`` in the layer state to the container's objective.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.norms import LayerNormalization
from deeplearning4j_tpu.ops.expert_ffn import expert_ffn
from deeplearning4j_tpu.ops.moe_rows import rows_back, rows_into_order
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config

ROUTER_EPS = 1e-6  # added to the selected scores' sum before the division


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dispatch(k, dtype, x, tok, r):
    """Token rows ``x`` [N, d] laid out by sorted assignment, [N k, d] in
    ``dtype``: slot ``p < r`` holds the row of token ``tok[p]``, rounded
    as it is stored; the slots from the tile after ``r`` on are not
    written. Backward: a token's gradient is the float32 sum of its slots'
    below ``r``, rounded to ``dtype`` as the forward's rows were."""
    return _dispatch_fwd(k, dtype, x, tok, r)[0]


def _dispatch_fwd(k, dtype, x, tok, r):
    return rows_into_order(x, tok, r, dtype)[0], (tok, r)


def _dispatch_bwd(k, dtype, res, dxs):
    tok, r = res
    _, ad = _dtypes.compute_dtypes_for(dxs.dtype)
    dx, _ = rows_back(dxs, tok, r, tok.shape[0] // k,
                      jnp.ones(tok.shape, ad), ad)
    return dx.astype(dtype).astype(ad), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, order, w_sorted, r):
    """``y[t] = sum_j w[t, j] ys[p]``, ``p`` the slot of assignment
    ``t k + j`` (``order[p] = t k + j``), over the assignments held here
    (``p < r``): the sorted rows' results [N k, d] back at their tokens,
    weighted, summed in float32. The weights are read as ``w_sorted``,
    ``w.reshape(-1)[order]`` as the sort that made ``order`` carried it,
    and only below ``r``; ``w`` [N, k] is there for its gradient, which the
    inverse sort carries back: an assignment computed elsewhere adds
    nothing and gets a zero gradient."""
    return _combine_fwd(ys, w, order, w_sorted, r)[0]


def _combine_fwd(ys, w, order, w_sorted, r):
    cd, ad = _dtypes.compute_dtypes_for(ys.dtype)
    n, k = w.shape
    y, ys_kept = rows_back(ys, order // k, r, n, w_sorted, ad, keep=cd)
    return y.astype(ys.dtype), (ys_kept, w_sorted, order, r)


def _combine_bwd(res, dy):
    ys, w_sorted, order, r = res
    n, slots = dy.shape[0], order.shape[0]
    # the rows this brings into sorted order serve both gradients: the
    # weights' is taken there and goes back as N k scalars. The rows'
    # own leaves in the dtype the grouped product's backward rounds its
    # operand to anyway: the same bits, and no float32 buffer between
    dys, dw_sorted = rows_into_order(dy, order // (slots // n), r, ys.dtype,
                                     scale=w_sorted, other=ys)
    # sorting by ``order``, a permutation, puts slot p's value at
    # assignment order[p]: the gather ``dw_sorted[argsort(order)]`` without
    # indexing a scalar at a time
    held = jax.lax.iota(jnp.int32, slots) < r
    _, dw = jax.lax.sort((order, jnp.where(held, dw_sorted, 0)), num_keys=1)
    return dys, dw.reshape(n, -1).astype(w_sorted.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x, router_w, w_gate, w_up, w_down, expert_bias, *,
                   top_k, held, scale, act, score="sigmoid"):
    """One chip's share of a dropless top-``top_k`` routed-experts layer.

    ``x`` [N, d]; ``router_w`` [d, E] scores ALL ``E`` experts in float32,
    by one of two score functions. ``score="sigmoid"`` (LFM2): ``s =
    sigmoid(x W_r)``, ``sel = top_k(s + expert_bias)`` (the bias moves the
    selection only), ``w = s[sel] / (sum(s[sel]) + 1e-6) * scale``.
    ``score="softmax"`` (Qwen3-Next): ``s = softmax(x W_r)`` over all
    ``E``, ``sel = top_k(s)``, ``w = s[sel] / sum(s[sel]) * scale``;
    ``expert_bias`` is None. Either way the weights are renormalised over
    the selected experts wherever they live. A shared expert that every
    token passes through is the block's (``TransformerBlock._moe``), not
    this layer's: every chip holds it whole, and no routing touches it.
    ``held = (first, end)`` names the experts whose weights
    ``w_gate`` / ``w_up`` [n_held, d, f] and ``w_down`` [n_held, f, d]
    are: the result is ``sum_{j in sel, first <= j < end} w_j E_j(x)``,
    ``E_j(x) = (act(x Wg_j) * (x Wu_j)) Wd_j``, or, with ``w_gate`` None
    (ungated experts: the Nemotron-H family's ReLU^2 ones), ``E_j(x) =
    act(x Wu_j) Wd_j``, two grouped products and not three; what the
    other experts would add is left out (their chips add it).

    No token is dropped and every shape is static: the ``N k``
    assignments are sorted by held expert, those routed elsewhere behind
    a sentinel, and the counts per held expert are the group sizes of the
    grouped products. The sorted buffers are sized for ``N k`` rows;
    ``r = sum(sizes)`` of them (between 0 and ``N k``) lie inside a group,
    and that is how many the layer moves, multiplies and activates: the
    grouped products touch the row tiles of the groups, and so do the two
    kernels of the row movement under the scope ``moe_permute``
    (ops/moe_rows.py: ``moe_rows_fwd`` brings token rows into sorted order
    and, in the backward pass, the result's gradient, with the weights'
    gradient taken from the same rows; ``moe_rows_back`` adds the weighted
    results back at their tokens and, in the backward pass, the sorted
    rows' gradient) and, from PR 50, the two kernels of the activation
    under ``moe_experts`` (ops/expert_ffn.py: ``moe_act_fwd``,
    ``moe_act_bwd``; the expert FFN is one function there with its
    backward written by hand). Gate and up are ONE grouped product over
    ``Wg ‖ Wu`` [n_held, d, 2 f], joined at the compute dtype from the
    float32 leaves: the activation kernels read and write one
    [N k, 2 f] array, and the input gradient is one product over ``K =
    2 f`` whose float32 accumulator does the sum that two products left to
    a pass over all ``N k`` rows of [N k, d]. Slots from the tile after
    ``r`` on are left unwritten in every sorted buffer and nothing reads
    them. What still walks all ``N k`` slots is none of it under
    ``moe_experts``, and from PR 51 none of it indexes scalars one at a
    time (XLA:TPU walks a gather or a scatter of scalars at 8.6 ns an
    element: the four that stood here were 19 ms of a 244 ms step): one
    stable sort of ``(local, iota, w)`` gives ``order`` with the weights
    in sorted order as its payload, one sort by ``order`` in the backward
    pass carries their gradient back, the counts are a comparison summed
    over the assignments, and the selected scores a comparison with the
    experts' numbers summed over ``E``, whose gradient autodiff makes a
    select summed over ``k``. What ``moe_experts`` holds beside
    its kernels is the weights' own traffic, the float32 leaves rounded
    (and gate joined with up) once a pass that reads them and the float32
    gradients written.

    Returns ``(y [N, d], load [n_held], elsewhere [1])``: the counts of
    assignments per held expert and of those routed to experts not held.
    """
    first, end = held
    n_held = end - first
    cd, ad = _dtypes.compute_dtypes_for(x.dtype)
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x.astype(ad), router_w.astype(ad),
                            precision=jax.lax.Precision.HIGHEST)
        if score == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            ranked, eps = s, 0.0
        else:
            s = jax.nn.sigmoid(logits)
            ranked, eps = s + expert_bias.astype(ad), ROUTER_EPS
        _, sel = jax.lax.top_k(jax.lax.stop_gradient(ranked), top_k)
        # no gather or scatter of scalars from here on (the docstring has
        # why). An expert is selected at most once a token, so one term of
        # the sum over E is not zero and the bits are the gather's
        experts = jax.lax.iota(jnp.int32, s.shape[1])
        w = jnp.sum(jnp.where(sel[..., None] == experts, s[:, None, :], 0),
                    axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
        local = sel.reshape(-1).astype(jnp.int32) - first
        here = (local >= 0) & (local < n_held)
        local = jnp.where(here, local, n_held)
        _, order, w_sorted = jax.lax.sort(
            (local, jax.lax.iota(jnp.int32, local.shape[0]),
             jax.lax.stop_gradient(w).reshape(-1)), num_keys=1,
            is_stable=True)
        bins = jax.lax.iota(jnp.int32, n_held + 1)
        counts = jnp.sum(local == bins[:, None], axis=1, dtype=jnp.int32)
        sizes = counts[:n_held]
        r = jnp.sum(sizes)
        with jax.named_scope("moe_permute"):
            xs = _dispatch(top_k, cd, x.astype(ad), order // top_k, r)
    # what the shapes say a group holds: N k assignments over E experts
    rows = xs.shape[0] // router_w.shape[1]
    with jax.named_scope("moe_experts"):
        ys = expert_ffn(xs, w_gate, w_up, w_down, sizes, act, ad, rows)
    with jax.named_scope("moe_route"), jax.named_scope("moe_permute"):
        y = _combine(ys, w, order, w_sorted, r)
    return y.astype(x.dtype), sizes, counts[n_held:]


@register_config
@dataclasses.dataclass(frozen=True)
class MoETransformerBlock(Layer):
    """Pre-norm block: LN -> MHA -> residual, LN -> MoE-MLP -> residual.

    The MoE-MLP replaces TransformerBlock's dense MLP with ``n_experts``
    expert MLPs behind a top-1 router.
    """

    n_out: int = 0
    n_heads: int = 4
    n_experts: int = 4
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    causal: bool = False
    activation: object = "gelu"

    input_family = _inputs.RecurrentType

    def _parts(self):
        return (LayerNormalization(),
                MultiHeadAttention(n_out=self.n_out, n_heads=self.n_heads,
                                   causal=self.causal),
                LayerNormalization())

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        assert input_type.size == self.n_out, \
            "MoETransformerBlock requires input size == n_out (residual)"
        ln1, mha, ln2 = self._parts()
        k1, k1b, k2, k3, k4, k5 = jax.random.split(key, 6)
        d, e = self.n_out, self.n_experts
        hidden = d * self.mlp_ratio
        it = _inputs.RecurrentType(d, input_type.timesteps)

        def expert_stack(k, shape, fan_in, fan_out):
            ks = jax.random.split(k, e)
            return jnp.stack([_init.init_weight("xavier", kk, shape,
                                                fan_in, fan_out, dtype)
                              for kk in ks])

        return {
            "ln1": ln1.init(k1, it, dtype),
            "mha": mha.init(k1b, it, dtype),
            "ln2": ln2.init(k2, it, dtype),
            "router_W": _init.init_weight("xavier", k3, (d, e), d, e, dtype),
            "expert_W1": expert_stack(k4, (d, hidden), d, hidden),
            "expert_b1": jnp.zeros((e, hidden), dtype),
            "expert_W2": expert_stack(k5, (hidden, d), hidden, d),
            "expert_b2": jnp.zeros((e, d), dtype),
        }

    def _moe_mlp(self, params, x2d):
        """x2d [N, d] -> (y [N, d], aux_loss scalar)."""
        e = self.n_experts
        n = x2d.shape[0]
        cap = int(-(-n // e) * self.capacity_factor) or 1

        logits = x2d.astype(jnp.float32) @ params["router_W"].astype(
            jnp.float32)                                   # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top = jnp.argmax(probs, axis=-1)                   # [N]
        onehot = jax.nn.one_hot(top, e, dtype=jnp.float32)  # [N, E]

        # position of each token within its expert's queue (Switch capacity)
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0    # [N, E], -1 if not routed
        keep = (pos >= 0) & (pos < cap)
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, cap - 1).astype(jnp.int32),
                                cap, dtype=jnp.float32)    # [N, E, C]
        dispatch = pos_oh * keep[..., None]                # [N, E, C]
        gate = jnp.sum(probs * onehot, axis=-1)            # [N] router prob
        combine = dispatch * gate[:, None, None]           # [N, E, C]

        # dispatch -> per-expert batches -> expert MLPs -> combine
        xe = jnp.einsum("nec,nd->ecd", dispatch, x2d.astype(jnp.float32))
        act = _act.get(self.activation)
        h = act(jnp.einsum("ecd,edh->ech", xe,
                           params["expert_W1"].astype(jnp.float32))
                + params["expert_b1"][:, None].astype(jnp.float32))
        ye = jnp.einsum("ech,ehd->ecd", h,
                        params["expert_W2"].astype(jnp.float32)) \
            + params["expert_b2"][:, None].astype(jnp.float32)
        y = jnp.einsum("nec,ecd->nd", combine, ye)         # [N, d]

        # Switch load-balancing loss: E * sum_e (fraction routed) * (mean prob)
        frac = jnp.mean(onehot, axis=0)
        mean_p = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * mean_p)
        return y.astype(x2d.dtype), aux

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        ln1, mha, ln2 = self._parts()
        h, _ = ln1.apply(params["ln1"], {}, x)
        attn, _ = mha.apply(params["mha"], {}, h, mask=mask)
        x = x + attn
        h, _ = ln2.apply(params["ln2"], {}, x)
        b, t, d = h.shape
        y, aux = self._moe_mlp(params, h.reshape(b * t, d))
        out_state = state
        if train:
            # input-dependent loss term: stashed in state for ONE step; the
            # container's loss_fn pops it (state structure stays stable)
            out_state = dict(state)
            out_state["aux_loss"] = self.aux_loss_weight * aux
        return x + y.reshape(b, t, d), out_state

    def regularization_penalty(self, params):
        return 0.0
