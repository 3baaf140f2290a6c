"""A language-model head with a multi-token-prediction module.

Net-new (the reference has no transformer): the training objective of
DeepSeek-V3 (arXiv:2412.19437, section 2.2), which the GLM-4.7-Flash
family's ``nextn`` layer follows. Beside the next token, position ``i``
predicts the token after it through ONE more block that reads the trunk's
state and the embedding of the next token, and that shares the trunk's
embedding table and head matrix. ``MultiTokenLMOutputLayer`` owns the head
and is handed the table by a ``ParamTie`` of the network's configuration,
so the parameter tree holds each once and each receives both gradients.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.layers.norms import RMSNorm
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class MultiTokenLMOutputLayer(ParamLayer):
    """Softmax head over the trunk's last state ``h`` [B, T, F], BEFORE
    its final norm, and INTEGER labels ``y`` [B, T] (``y_i`` the token
    after position ``i``), with one multi-token-prediction module:

        z_i  = rmsnorm_f(h_i) W                                the main logits
        e_i  = Emb(y_i)                                        the tied table
        m_i  = [rmsnorm_e(e_i) | rmsnorm_h(h_i)] W_eh          W_eh [2F, F]
        m    = block(m)                                        one layer of the trunk's kind
        z'_i = rmsnorm_s(m_i) W                                the SAME head
        loss = mean_i CE(z_i, y_i)  +  mtp_weight mean_{i<T-1} CE(z'_i, y_{i+1})

    ``block`` is a ``TransformerBlock`` (its state, a mixture's counts and
    bias, lives under ``"mtp"`` in this layer's); it runs at all ``T``
    positions, the last carrying no weight in the second mean. ``Emb`` is
    the parameter ``embed`` [n_out, F], which this layer does not make:
    the configuration ties it to the embedding layer's table
    (``ParamTie(layer=<this>, name="embed", ...)``). ``mtp_weight=0``
    leaves the first term alone: the plain head behind a final norm, its
    loss and gradients those of ``RMSNorm`` + ``RnnOutputLayer``. Both
    heads' [B T, n_out] logits are kept for the backward pass.
    The two terms of the last step's loss stay in the state under
    ``loss_terms`` (``main``, ``mtp``) for ``telemetry.note_step_state``.
    ``apply`` (inference) gives ``softmax(z)``."""

    n_out: int = 0
    block: object = None
    mtp_weight: float = 0.3
    norm_eps: float = 1e-6

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W",)
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def _norm(self):
        return RMSNorm(eps=self.norm_eps)

    def init(self, key, input_type, dtype=jnp.float32):
        f = input_type.size
        k_head, k_eh, k_block = jax.random.split(key, 3)
        norm = self._norm()
        return {"final_norm": norm.init(None, input_type, dtype),
                "W": _init.init_weight(self.weight_init, k_head,
                                       (f, self.n_out), f, self.n_out, dtype),
                "mtp": {
                    "enorm": norm.init(None, input_type, dtype),
                    "hnorm": norm.init(None, input_type, dtype),
                    "W_eh": _init.init_weight(self.weight_init, k_eh,
                                              (2 * f, f), 2 * f, f, dtype),
                    "block": self.block.init(k_block, input_type, dtype),
                    "norm": norm.init(None, input_type, dtype)}}

    def init_state(self, input_type, dtype=jnp.float32):
        return {"mtp": self.block.init_state(input_type, dtype),
                "loss_terms": {"main": jnp.zeros((), dtype),
                               "mtp": jnp.zeros((), dtype)}}

    def regularization_penalty(self, params):
        return 0.0

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, f = x.shape
        h, _ = self._norm().apply(params["final_norm"], {}, x)
        z = matmul(h.reshape(b * t, f), params["W"])
        return jax.nn.softmax(z, axis=-1).reshape(b, t, self.n_out), state

    @staticmethod
    def _head_ce(w, s, y):
        """Per-token cross-entropy [N] of states [N, F] under the head."""
        with jax.named_scope("lm_head"):
            z = matmul(s, w)
            picked = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(z, axis=-1) - picked

    def _module(self, params, state, feats, labels, mask, train):
        """The module's states [B, T, F] and its block's new state."""
        norm, p = self._norm(), params["mtp"]
        e = jnp.take(params["embed"], labels, axis=0)
        e, _ = norm.apply(p["enorm"], {}, e.astype(feats.dtype))
        h, _ = norm.apply(p["hnorm"], {}, feats)
        b, t, f = feats.shape
        m = matmul(jnp.concatenate([e, h], axis=-1).reshape(b * t, 2 * f),
                   p["W_eh"]).reshape(b, t, f)
        kw = {} if mask is None else {"mask": mask}
        m, block_state = self.block.apply(p["block"], state["mtp"], m,
                                          train=train, **kw)
        m, _ = norm.apply(p["norm"], {}, m)
        return m, block_state

    def loss_from_features(self, params, state, feats, labels, mask=None,
                           train=True):
        b, t, f = feats.shape
        if not jnp.issubdtype(labels.dtype, jnp.integer):
            raise TypeError("MultiTokenLMOutputLayer takes integer labels "
                            f"[B, T], got {labels.dtype} {labels.shape}")

        def mean(ce, w):
            return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)

        h, _ = self._norm().apply(params["final_norm"], {}, feats)
        ce = self._head_ce(params["W"], h.reshape(b * t, f),
                           labels.reshape(b * t))
        w = jnp.ones((b, t), ce.dtype) if mask is None \
            else mask.reshape(b, t).astype(ce.dtype)
        main = mean(ce, w.reshape(b * t))
        mtp, block_state = jnp.zeros_like(main), state["mtp"]
        if self.mtp_weight:
            if "embed" not in params:
                raise ValueError(
                    "the module embeds the labels with the trunk's table: "
                    "tie it to this layer as 'embed' (conf.ties, ParamTie)")
            with jax.named_scope("mtp"):
                m, block_state = self._module(params, state, feats, labels,
                                              mask, train)
                # position i's second label is y_{i+1}; the last position
                # has none and weighs nothing (its label here is a filler)
                after = jnp.concatenate([labels[:, 1:], labels[:, :1]],
                                        axis=1)
                w_after = jnp.concatenate(
                    [w[:, :-1] * w[:, 1:], jnp.zeros_like(w[:, :1])], axis=1)
                ce2 = self._head_ce(params["W"], m.reshape(b * t, f),
                                    after.reshape(b * t))
                mtp = mean(ce2, w_after.reshape(b * t))
        dt = state["loss_terms"]["main"].dtype
        new_state = {**state, "mtp": block_state,
                     "loss_terms": {"main": main.astype(dt),
                                    "mtp": mtp.astype(dt)}}
        return main + self.mtp_weight * mtp, None, new_state
