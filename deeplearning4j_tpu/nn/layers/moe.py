"""Routed experts: the dropless top-k layer that is ``TransformerBlock``'s
``ffn="moe"`` (``routed_experts``, below), and the older Switch-style
block monolith (``MoETransformerBlock``, top-1 with a capacity drop and
an auxiliary loss, partitioned by GSPMD over a stacked expert axis).

Mixture-of-Experts transformer block with expert parallelism.

Reference analog: none — DL4J has no MoE (nor attention); net-new for the
TPU scale goals, completing the dp/tp/sp/pp/ep parallelism set (driver
contract: __graft_entry__.dryrun_multichip exercises every axis).

Design (Switch-Transformer style, TPU-first):
* Top-1 router with a capacity limit: tokens route to their argmax expert,
  each expert processes at most C = ceil(tokens/E * capacity_factor);
  overflow tokens pass through the residual unchanged (standard Switch
  semantics — keeps every shape static for XLA).
* Dispatch/combine are dense einsums against a [N, E, C] one-hot dispatch
  tensor — gather-free, MXU-friendly, and differentiable through the
  router probabilities (combine carries the router prob).
* Expert weights are STACKED with a leading expert axis. Under a mesh,
  sharding that axis over ``model`` (see parallel/data_parallel.py's
  param-spec rule) makes GSPMD partition the per-expert einsums and insert
  the all-to-alls — expert parallelism without manual collectives.
* Load-balancing auxiliary loss (Switch eq. 4): E * sum_e f_e * p_e, where
  f_e is the fraction of tokens dispatched to expert e and p_e the mean
  router probability — exposed via ``aux_loss`` in the layer state so the
  container can add it to the objective.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.attention import (LayerNormalization,
                                                    MultiHeadAttention)
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config

ROUTER_EPS = 1e-6  # added to the selected scores' sum before the division


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(k, x, order, inv, valid):
    """Token rows ``x`` [N, d] laid out by sorted assignment, [N k, d]:
    row ``p`` is the token of assignment ``order[p]`` (assignment ``a``
    belongs to token ``a // k``). ``inv`` is ``order``'s inverse, so the
    backward pass is a gather too: a token's gradient is the sum of its
    ``k`` rows'. ``valid`` marks the rows inside a group here."""
    return _dispatch_fwd(k, x, order, inv, valid)[0]


def _dispatch_fwd(k, x, order, inv, valid):
    return x[order // k], (inv, valid)


def _dispatch_bwd(k, res, dxs):
    inv, valid = res
    n = inv.shape[0] // k
    dxs = jnp.where(valid[:, None], dxs, 0)
    _, ad = _dtypes.compute_dtypes_for(dxs.dtype)
    dx = jnp.sum(dxs[inv].astype(ad).reshape(n, k, -1), axis=1)
    return dx.astype(dxs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, order, inv, valid):
    """``y[t] = sum_j w[t, j] ys[inv[t k + j]]``: the sorted rows' results
    [N k, d] back at their tokens, weighted. ``w`` [N, k] is zero for an
    assignment computed elsewhere; rows of ``ys`` past the groups are
    masked before they are read."""
    return _combine_fwd(ys, w, order, inv, valid)[0]


def _combine_fwd(ys, w, order, inv, valid):
    cd, _ = _dtypes.compute_dtypes_for(ys.dtype)
    ys = jnp.where(valid[:, None], ys, 0)
    n, k = w.shape
    y = jnp.sum(ys[inv].reshape(n, k, -1) * w[..., None].astype(ys.dtype),
                axis=1)
    return y, (ys.astype(cd), w, order, inv)


def _combine_bwd(res, dy):
    ys, w, order, inv = res
    n, k = w.shape
    # a row past the groups carries weight zero: its gradient is zero
    dys = dy[order // k] * w.reshape(-1)[order][:, None].astype(dy.dtype)
    dw = jnp.sum(ys[inv].reshape(n, k, -1).astype(dy.dtype)
                 * dy[:, None, :], axis=-1)
    return dys, dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x, router_w, w_gate, w_up, w_down, expert_bias, *,
                   top_k, held, scale, act):
    """One chip's share of a dropless top-``top_k`` routed-experts layer.

    ``x`` [N, d]; ``router_w`` [d, E] scores ALL ``E`` experts in float32:
    ``s = sigmoid(x W_r)``, ``sel = top_k(s + expert_bias)`` (the bias
    moves the selection only), ``w = s[sel] / (sum(s[sel]) + 1e-6) *
    scale``, renormalised over the selected experts wherever they live.
    ``held = (first, end)`` names the experts whose weights
    ``w_gate`` / ``w_up`` [n_held, d, f] and ``w_down`` [n_held, f, d]
    are: the result is ``sum_{j in sel, first <= j < end} w_j E_j(x)``,
    ``E_j(x) = (act(x Wg_j) * (x Wu_j)) Wd_j``; what the other experts
    would add is left out (their chips add it).

    No token is dropped and every shape is static: the ``N k``
    assignments are sorted by held expert, those routed elsewhere behind
    a sentinel; the counts per held expert are the group sizes of the
    grouped products, whose work follows the rows really routed here
    (between 0 and ``N k``) while the buffers are sized for ``N k``.

    Returns ``(y [N, d], load [n_held], elsewhere [1])``: the counts of
    assignments per held expert and of those routed to experts not held.
    """
    n, _ = x.shape
    first, end = held
    n_held = end - first
    cd, ad = _dtypes.compute_dtypes_for(x.dtype)
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x.astype(ad), router_w.astype(ad),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(s + expert_bias.astype(ad), top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS) * scale
        local = sel.reshape(-1).astype(jnp.int32) - first
        here = (local >= 0) & (local < n_held)
        local = jnp.where(here, local, n_held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        counts = jnp.bincount(local, length=n_held + 1).astype(jnp.int32)
        sizes = counts[:n_held]
        valid = jnp.arange(n * top_k, dtype=jnp.int32) < jnp.sum(sizes)
        xs = _dispatch(top_k, x.astype(cd), order, inv, valid)
        w_here = jnp.where(here.reshape(n, top_k), w, 0.0)
    with jax.named_scope("moe_experts"):
        g = grouped_matmul(xs, w_gate, sizes, cd)
        u = grouped_matmul(xs, w_up, sizes, cd)
        h = (act(g.astype(ad)) * u.astype(ad)).astype(cd)
        ys = grouped_matmul(h, w_down, sizes, ad)
    with jax.named_scope("moe_route"):
        y = _combine(ys, w_here, order, inv, valid)
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
