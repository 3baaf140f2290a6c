"""Looped (weight-shared) block stacks and their exit-weighted head.

Net-new (the reference has no transformer): the looped language models of
Zhu et al., "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741), run ONE stack of blocks ``passes`` times in a step.
``LoopedStack`` is that loop as a single entry of ``conf.layers``: one
parameter subtree, used ``passes`` times, so the gradient of each leaf is
the sum over its uses and the updater sees one leaf. ``LoopedLMOutputLayer``
is the training objective that goes with it: a head and an exit gate on the
normed state of every pass, the passes' cross-entropies weighted by the
gate's exit distribution, less ``beta`` times that distribution's entropy.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import Layer, ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class LoopedStack(Layer):
    """``blocks`` applied in order, ``passes`` times over, with the same
    parameters every pass; ``final_norm`` (a norm layer, or None) closes
    each pass and the normed state goes on to the next.

    Returns the ``passes`` normed states stacked on a new leading axis,
    ``[passes, B, T, F]``: what an exit-weighted head reads. Parameters:
    ``{"B00": ..., "B01": ..., "final_norm": ...}``, each block's subtree
    once. Every block's activations are kept for the backward pass; the
    network's ``gradient_checkpointing`` recomputes the whole entry, all
    the passes in one.

    The passes are a Python loop (``passes`` x ``len(blocks)`` block
    instances in the HLO, a scope ``ut<r>`` a pass), not a ``lax.scan``:
    a scan stacks what it keeps for the backward pass in float32, which
    at Ouro-2.6B's widths does not fit one chip without recomputing a
    block at a time, and that costs 16% of the rate (PERF.md section 6,
    PR 27 has both forms' readings)."""

    blocks: tuple = ()
    passes: int = 1
    final_norm: object = None

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return self._block_types(input_type)[1]

    def _block_types(self, input_type):
        """(each block's input type, the stack's output type)."""
        types = []
        for b in self.blocks:
            types.append(input_type)
            input_type = b.output_type(input_type)
        return types, input_type

    def init(self, key, input_type, dtype=jnp.float32):
        types, out_type = self._block_types(input_type)
        if self.passes > 1 and out_type != input_type:
            raise ValueError("a stack run more than once has to return its "
                             f"input's type: {input_type} -> {out_type}")
        keys = jax.random.split(key, len(self.blocks) + 1)
        p = {f"B{j:02d}": b.init(k, t, dtype)
             for j, (b, k, t) in enumerate(zip(self.blocks, keys, types))}
        if self.final_norm is not None:
            p["final_norm"] = self.final_norm.init(keys[-1], out_type, dtype)
        return p

    def _one_pass(self, params, h, rng, mask, train):
        kw = {} if mask is None else {"mask": mask}
        for j, block in enumerate(self.blocks):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            with jax.named_scope(f"B{j:02d}"):
                h, _ = block.apply(params[f"B{j:02d}"], {}, h, train=train,
                                   rng=sub, **kw)
        if self.final_norm is not None:
            h, _ = self.final_norm.apply(params["final_norm"], {}, h)
        return h

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        states, h = [], x
        with jax.named_scope("loop"):
            for r in range(self.passes):
                sub = None
                if rng is not None:
                    rng, sub = jax.random.split(rng)
                with jax.named_scope(f"ut{r}"):
                    h = self._one_pass(params, h, sub, mask, train)
                states.append(h)
            return jnp.stack(states), state


@register_config
@dataclasses.dataclass(frozen=True)
class LoopedLMOutputLayer(ParamLayer):
    """Exit-weighted language-model head over a ``LoopedStack``'s states
    ``s_r`` [R, B, T, F] and INTEGER labels [B, T] (stage I of
    arXiv:2510.25741):

        z_r   = s_r W                       logits of pass r
        lam_r = sigmoid(s_r gate_W + gate_b)
        p_r   = lam_r prod_{j<r}(1 - lam_j)   (r < R),   p_R = prod_{j<R}(1 - lam_j)
        loss  = mean over tokens of [ sum_r p_r CE(z_r, y) - beta H(p) ]

    Log-softmax is taken from the logits (no clip of probabilities) and the
    exit distribution is kept in logs. The exit distribution is made first
    (it reads the gates alone), then all R passes' heads and cross-entropies
    are ONE call of ``losses.head_xent`` on their R*B*T rows with the weight
    ``p_r / (B T)`` a row: it walks the rows by blocks, so no [B*T, n_out]
    logits array exists, makes the gradient where the logits are, so none
    are made again in the backward pass, and sums the head's weight
    gradient over the passes in one accumulator. The gate's gradient
    reaches it through that weight. ``apply`` (inference) gives
    ``softmax(z_R)``: the last pass never exits early."""

    n_out: int = 0
    beta: float = 0.1

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W", "gate_W")
    BIAS_KEYS = ("gate_b",)

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        k1, k2 = jax.random.split(key)
        return {"W": _init.init_weight(self.weight_init, k1,
                                       (n_in, self.n_out), n_in, self.n_out,
                                       dtype),
                "gate_W": _init.init_weight(self.weight_init, k2, (n_in, 1),
                                            n_in, 1, dtype),
                "gate_b": jnp.full((1,), self.bias_init, dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        _, b, t, f = x.shape
        z = matmul(x[-1].reshape(b * t, f), params["W"])
        return jax.nn.softmax(z, axis=-1).reshape(b, t, self.n_out), state

    def exit_log_probs(self, params, feats):
        """log p_r per token, [R, N], from states [R, N, F]."""
        r, n, f = feats.shape
        # the last pass has no gate: whatever has not left, leaves there
        g = matmul(feats[:-1].reshape((r - 1) * n, f), params["gate_W"])
        g = g.reshape(r - 1, n) + params["gate_b"]
        log_stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)
        before = jnp.concatenate([jnp.zeros_like(g[:1]), log_stay[:-1]])
        return jnp.concatenate([jax.nn.log_sigmoid(g) + before,
                                log_stay[-1:]])

    def loss_from_features(self, params, state, feats, labels, mask=None,
                           train=True):
        r, b, t, f = feats.shape
        if not jnp.issubdtype(labels.dtype, jnp.integer):
            raise TypeError("LoopedLMOutputLayer takes integer labels "
                            f"[B, T], got {labels.dtype} {labels.shape}")
        n = b * t
        feats = feats.reshape(r, n, f)
        with jax.named_scope("exit_gate"):
            # the last pass has no gate, so one pass leaves there whole
            log_p = self.exit_log_probs(params, feats) if r > 1 \
                else jnp.zeros((1, n), feats.dtype)
            p = jnp.exp(log_p)
            if mask is None:
                weight = jnp.full((n,), 1.0 / n, p.dtype)
            else:
                weight = mask.reshape(n).astype(p.dtype)
                weight = weight / jnp.maximum(jnp.sum(weight), 1.0)
            c = (p * weight).reshape(r * n)
        with jax.named_scope("exit_head"):
            loss, _ = _losses.head_xent(feats.reshape(r * n, f), params["W"],
                                        jnp.tile(labels.reshape(n), r), c)
        with jax.named_scope("exit_gate"):
            loss = loss + self.beta * jnp.sum(p * log_p * weight)
        return loss, None, state
