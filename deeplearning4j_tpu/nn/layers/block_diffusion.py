"""The block-diffusion training objective as two layers around a decoder.

Net-new (the reference has no transformer): the objective of BD3-LM
(arXiv:2503.09573), which the SDAR models (arXiv:2510.06303) are trained
with. A sequence is cut into blocks of ``block_len`` tokens; a step masks
each token of a block with that block's own probability ``t`` and the
network sees the noised copy and the clean copy side by side, 2T
positions under one structured attention mask
(``ops/attention_pallas.BlockDiffusion``): a noised block reads itself
and the clean blocks before it, so one pass trains every block as
generation will meet it. ``BlockDiffusionInput`` draws the noise and
doubles the ids; ``BlockDiffusionLMOutputLayer`` reads the noised copy's
rows and weighs a position by whether it was masked, over ``t``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import Layer, ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils.serde import register_config


def _geometry(seq_len, block_len):
    from deeplearning4j_tpu.ops.attention_pallas import BlockDiffusion
    return BlockDiffusion(seq_len, block_len)


def draw_noise(noise_seed, step, batch, seq_len, block_len, eps):
    """(masked [B, T] bool, level [B, T] float32): step ``step``'s draw.
    ``key = fold_in(PRNGKey(noise_seed), step)`` split once into ``k_t``,
    ``k_u``; a level a sequence and block, ``t = eps + (1 - eps)
    uniform(k_t, [B, T / block_len])``; a token is masked where
    ``uniform(k_u, [B, T]) < t`` of its block. float32 whatever the
    default float is, so a step's draw is the same everywhere."""
    key = jax.random.fold_in(jax.random.PRNGKey(noise_seed), step)
    k_t, k_u = jax.random.split(key)
    t = eps + (1.0 - eps) * jax.random.uniform(
        k_t, (batch, seq_len // block_len), jnp.float32)
    level = jnp.repeat(t, block_len, axis=1)
    return jax.random.uniform(k_u, (batch, seq_len), jnp.float32) < level, \
        level


@register_config
@dataclasses.dataclass(frozen=True)
class BlockDiffusionInput(Layer):
    """Before the embedding: integer ids ``x`` [B, T] in, and in training
    ``[xt | x]`` [B, 2T] out, the noised copy first: ``xt`` is ``x`` with
    ``mask_id`` where step ``n``'s draw masked a token (``draw_noise``:
    one level ``t`` a sequence and block of ``block_len`` tokens, linear
    schedule, so a masked position weighs ``1 / t``). The weights ``w =
    masked / t`` [B, T] go to the loss as the layer's ``loss_mask``
    (``base.pop_loss_mask``); no layer after this one applies them.

    The noise is keyed by a counter and not by the network's ``rng``:
    ``n`` is the state's ``noise_step`` (int32, +1 a training step), saved
    and restored with the network, so a resumed job continues the same
    noise and a reference that is handed the state and no key draws the
    same step.

    ``T`` must be ``seq_len``, which the attention layers' geometry is
    built from, and a multiple of ``block_len``; a fed mask is refused
    (sequences are packed whole). Outside training (``train=False``) the
    layer passes the ids through, the attention layers (which double as
    this layer does, by ``train``) see a plain sequence of any length and
    the network is the plain causal decoder. ``attention_pallas.
    BlockDiffusion`` alone says how the two copies lie."""

    seq_len: int = 0
    block_len: int = 4
    mask_id: int = 0
    noise_seed: int = 0
    eps: float = 1e-3

    input_family = _inputs.RecurrentType
    hands_loss_mask = True

    def output_type(self, input_type):
        return input_type

    def init_state(self, input_type, dtype=jnp.float32):
        return {"noise_step": jnp.zeros((), jnp.int32)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train:
            return x, state
        if mask is not None:
            raise ValueError("BlockDiffusionInput takes whole packed "
                             "sequences: it has no use for a fed mask")
        ids = x[..., 0] if x.ndim == 3 else x
        b, t = ids.shape
        if t != self.seq_len or t % self.block_len:
            raise ValueError(
                f"BlockDiffusionInput(seq_len={self.seq_len}, block_len="
                f"{self.block_len}) trains on sequences of seq_len tokens, "
                f"whole blocks; got {t}")
        with jax.named_scope("bd_noise"):
            masked, level = draw_noise(self.noise_seed, state["noise_step"],
                                       b, t, self.block_len, self.eps)
            noised = jnp.where(masked, jnp.asarray(self.mask_id, ids.dtype),
                               ids)
            new_state = {"noise_step": state["noise_step"] + 1,
                         "loss_mask": masked / level}
            return _geometry(t, self.block_len).join(noised, ids), new_state


@register_config
@dataclasses.dataclass(frozen=True)
class BlockDiffusionLMOutputLayer(ParamLayer):
    """Softmax head of a block-diffusion step, over the decoder's normed
    states and INTEGER labels ``x`` [B, T], the clean ids themselves:

        loss = (1 / (B T)) sum_{s, i} w[s, i] CE(z[s, i], x[s, i])

    ``z`` the logits of the NOISED copy's T rows, the first T of the 2T
    positions ``BlockDiffusionInput`` made (no logits are formed for the
    clean copy, whose rows serve as keys and values alone), ``w`` the
    float mask that layer handed on (masked over its noise level): a
    masked position predicts its own token. States of T positions (a
    plain sequence: scoring outside training) are read whole, and ``w``
    is 1 where no mask arrives. ``apply`` (inference) gives
    ``softmax(z)``."""

    n_out: int = 0

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W",)
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        f = input_type.size
        return {"W": _init.init_weight(self.weight_init, key,
                                       (f, self.n_out), f, self.n_out, dtype)}

    def regularization_penalty(self, params):
        return 0.0

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, f = x.shape
        z = matmul(x.reshape(b * t, f), params["W"])
        return jax.nn.softmax(z, axis=-1).reshape(b, t, self.n_out), state

    def loss_from_features(self, params, state, feats, labels, mask=None,
                           train=True):
        if not jnp.issubdtype(labels.dtype, jnp.integer):
            raise TypeError("BlockDiffusionLMOutputLayer takes integer "
                            f"labels [B, T], got {labels.dtype} "
                            f"{labels.shape}")
        b, t = labels.shape
        if feats.shape[1] not in (t, 2 * t):
            raise ValueError(f"{feats.shape[1]} positions of states for "
                             f"{t} labels: neither T nor 2T")
        with jax.named_scope("bd_loss"):
            if feats.shape[1] == 2 * t:
                feats = _geometry(t, 1).noised_rows(feats)
            rows = feats.reshape(b * t, feats.shape[-1])
            z = matmul(rows, params["W"])
            picked = jnp.take_along_axis(
                z, labels.reshape(b * t, 1).astype(jnp.int32), axis=-1)[:, 0]
            ce = jax.nn.logsumexp(z, axis=-1) - picked
            if mask is not None:
                ce = ce * mask.reshape(b * t).astype(ce.dtype)
            return jnp.sum(ce) / (b * t), None, state
