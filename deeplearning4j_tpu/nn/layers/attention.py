"""Softmax attention: the rotary embedding, the one attention dispatch and
the multi-head layer (a ``TransformerBlock``'s first mixer; the others are
a module each under ``nn/layers/mixers/``, the norms in ``norms.py``).

The reference has NO attention anywhere (SURVEY.md §5 long-context row: its
only long-sequence mechanisms are masking + truncated BPTT). These layers are
the north-star-mandated long-context capability, designed TPU-first:

- scaled dot-product attention runs as batched MXU matmuls in bf16 with f32
  accumulation;
- RecurrentAttentionLayer-style usage = MultiHeadAttention over [B,T,F];
- sequence parallelism (ring attention over the mesh 'seq' axis) lives in
  deeplearning4j_tpu/parallel/sequence.py and reuses this layer's projections.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.layers.norms import RMSNorm
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config


def rope(x, theta, rotary_dim=None, positions=None):
    """Rotary position embedding (Su et al. 2021) of ``x`` [B, T, H, D] at
    ``positions`` [T] (None: 0..T-1), in the rotate-half convention over
    the whole head width: pair ``i`` is (x[i], x[i + D/2]) and turns by
    ``t * theta**(-2i/D)``. With ``rotary_dim`` < D (partial rotary) the
    first ``rotary_dim`` of a head turn so, as a head of that width would,
    and the rest pass through."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], theta, positions=positions),
             x[..., rotary_dim:]], axis=-1)
    with jax.named_scope("rope"):
        t, d = x.shape[1], x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        at = jnp.arange(t, dtype=jnp.float32) if positions is None \
            else positions.astype(jnp.float32)
        ang = at[:, None] * inv[None, :]
        cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)


def dot_product_attention(q, k, v, *, mask=None, causal=False, scale=None,
                          geometry=None):
    """q,k,v: [B, T, H, D]. Returns [B, T, H, D]. bf16 matmuls, f32 softmax.
    ``geometry`` (``attention_pallas.BlockDiffusion``) is a score mask of
    its own in ``causal``'s place.

    On TPU, attention (incl. [B, Tk] key-padding-masked batches) dispatches
    to the fused flash kernel (ops/attention_pallas.py) — O(T*D) HBM
    traffic instead of the [B,H,T,T] logits tensor; the dispatch seam
    mirrors the LSTM fused path. The XLA path below takes a geometry as
    its dense boolean mask."""
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    # the kernel needs a static scale; read once per trace, and jit keeps
    # the chosen blocks in the compiled step
    blocks = (_ap.resolve_attention(q.shape, k.shape, mask, q.dtype,
                                    geometry)
              if scale is None or isinstance(scale, (int, float)) else None)
    if blocks is not None:
        return _ap.flash_attention(q, k, v, mask=mask, causal=causal,
                                   scale=scale, block_q=blocks[0],
                                   block_k=blocks[1], geometry=geometry)
    cd, ad = _dtypes.compute_dtypes_for(q.dtype)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, ad))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(cd), k.astype(cd),
                        preferred_element_type=ad) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    if geometry is not None:
        logits = jnp.where(geometry.dense(), logits, -jnp.inf)
    if mask is not None:
        # mask: [B, Tk] -> key-side masking
        logits = jnp.where(mask[:, None, None, :] > 0, logits, -jnp.inf)
    if mask is not None:
        # fully-masked query rows (e.g. left padding under causal): softmax
        # over all -inf is NaN fwd AND bwd — substitute a finite row before
        # the softmax and zero its output after, matching the fused
        # kernel's contract so dispatch choice never changes NaN behavior.
        # (Pure-causal rows always see >= 1 valid key; no guard needed.)
        any_valid = (logits > -jnp.inf).any(axis=-1, keepdims=True)
        logits = jnp.where(any_valid, logits, 0.0)
        weights = jax.nn.softmax(logits, axis=-1)
        weights = jnp.where(any_valid, weights, 0.0)
    else:
        weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(cd), v.astype(cd),
                     preferred_element_type=ad)
    return out


@register_config
@dataclasses.dataclass(frozen=True)
class MultiHeadAttention(ParamLayer):
    """Self-attention over [B,T,F] with fused QKV projection.

    Model-definition fields beyond the original four (their defaults keep
    the original parameter tree and arithmetic): ``bias=False`` drops
    ``bqkv`` / ``bo``; ``rope_theta`` turns q and k by their positions
    before the attention; ``head_dim`` sets the head width apart from
    ``n_out / n_heads``; ``n_kv_heads`` (grouped-query attention) gives
    ``n_heads / n_kv_heads`` query heads one key/value head, query head
    ``j`` reading key/value head ``j // group``, with the projections
    apart (``Wq`` [n_in, H D], ``Wkv`` [n_in, 2 Hkv D]) in place of
    ``Wqkv``; ``qk_norm`` puts an RMSNorm over each head's width on q and
    on k (gains ``q_gamma`` / ``k_gamma`` [D], shared by the heads, eps
    ``qk_norm_eps``, about zero with ``qk_norm_zero_centered``) before the
    rotation; ``rotary_dim`` turns only the first that many of a head
    (``rope``); ``gate`` (with ``n_kv_heads``) doubles the query
    projection, ``Wq`` [n_in, H 2D] laid out a head [q | gate], and
    multiplies the attention's result by ``sigmoid(gate)`` elementwise
    before ``Wo`` (Qwen3-Next's gated attention). ``block_diffusion`` =
    (seq_len, block_len) makes the layer the attention of a
    block-diffusion training step: with ``train=True`` the input is what
    ``BlockDiffusionInput`` made in training, the two copies of a
    sequence, ``2 seq_len`` positions masked and rotated as
    ``attention_pallas.BlockDiffusion`` lays them out; with
    ``train=False`` that layer passed the ids through, and this one is
    what it is without the field, at any length. As a block's mixer its
    parameters sit under ``mha``."""

    n_out: int = 0     # model dim (also output dim)
    n_heads: int = 4
    causal: bool = False
    bias: bool = True
    rope_theta: float | None = None
    head_dim: int | None = None
    n_kv_heads: int | None = None
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    qk_norm_zero_centered: bool = False
    rotary_dim: int | None = None
    gate: bool = False
    block_diffusion: tuple = ()
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    param_key = "mha"   # where a block keeps this mixer's parameters

    WEIGHT_KEYS = ("Wqkv", "Wq", "Wkv", "Wo")
    BIAS_KEYS = ("bqkv", "bo")

    def _head_dim(self):
        if self.head_dim is not None:
            return self.head_dim
        assert self.n_out % self.n_heads == 0
        return self.n_out // self.n_heads

    def _grouped(self):
        """Key/value heads where the projections are apart (they are
        fewer than the query heads, or the query projection carries the
        gate), else None (plain multi-head: one fused projection)."""
        kv = self.n_kv_heads
        if kv is None and self.gate:
            raise ValueError("the output gate rides the query projection "
                             "of the grouped form: set n_kv_heads")
        if kv is None or (kv == self.n_heads and not self.gate):
            return None
        if self.n_heads % kv:
            raise ValueError(f"n_heads {self.n_heads} is no multiple of "
                             f"n_kv_heads {kv}")
        if self.bias:
            raise ValueError("grouped-query projections have no biases: "
                             "set bias=False")
        return kv

    def _geometry(self, t, train):
        """The score mask's geometry for ``t`` positions: None (a plain
        sequence) outside training or without the field; in training
        ``BlockDiffusionInput`` has doubled the sequence, and the layer
        masks and rotates it as that layer laid it out."""
        if not (train and self.block_diffusion):
            return None
        from deeplearning4j_tpu.ops.attention_pallas import BlockDiffusion
        geometry = BlockDiffusion(*map(int, self.block_diffusion))
        if t != 2 * geometry.seq_len:
            raise ValueError(
                f"block_diffusion {tuple(self.block_diffusion)}: a training "
                f"step brings the two copies of a sequence, "
                f"{2 * geometry.seq_len} positions; got {t}")
        return geometry

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        inner = self.n_heads * self._head_dim()
        k1, k2 = jax.random.split(key)
        kv = self._grouped()
        p = {"Wo": _init.init_weight(self.weight_init, k2, (inner, self.n_out),
                                     inner, self.n_out, dtype)}
        if kv is None:
            p["Wqkv"] = _init.init_weight(self.weight_init, k1,
                                          (n_in, 3 * inner), n_in, 3 * inner,
                                          dtype)
        else:
            kq, kkv = jax.random.split(k1)
            kv_inner = 2 * kv * self._head_dim()
            q_inner = 2 * inner if self.gate else inner
            p["Wq"] = _init.init_weight(self.weight_init, kq,
                                        (n_in, q_inner), n_in, q_inner, dtype)
            p["Wkv"] = _init.init_weight(self.weight_init, kkv,
                                         (n_in, kv_inner), n_in, kv_inner,
                                         dtype)
        if self.qk_norm:
            make = jnp.zeros if self.qk_norm_zero_centered else jnp.ones
            p["q_gamma"] = make((self._head_dim(),), dtype)
            p["k_gamma"] = make((self._head_dim(),), dtype)
        if self.bias:
            p["bqkv"] = jnp.zeros((3 * inner,), dtype)
            p["bo"] = jnp.zeros((self.n_out,), dtype)
        return p

    def heads(self, params, x, geometry=None):
        """Project to q,k,v [B,T,H,D] and the output gate's logits
        [B,T,H,D] (None without ``gate``); rotated at ``geometry``'s
        positions where one is given, else at 0..T-1."""
        b, t, _ = x.shape
        h, d = self.n_heads, self._head_dim()
        kv = self._grouped()
        gate = None
        x2 = x.reshape(b * t, -1)
        if kv is None:
            with jax.named_scope(_scopes.MIX_IN):
                qkv = matmul(x2, params["Wqkv"])
                if self.bias:
                    qkv = qkv + params["bqkv"]
            qkv = qkv.reshape(b, t, 3, h, d)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            with jax.named_scope(_scopes.MIX_IN):
                q = matmul(x2, params["Wq"]).reshape(b, t, h, -1)
            if self.gate:
                q, gate = q[..., :d], q[..., d:]
            with jax.named_scope(_scopes.MIX_IN):
                k_v = matmul(x2, params["Wkv"]).reshape(b, t, 2, kv, d)
            k, v = k_v[:, :, 0], k_v[:, :, 1]
        if self.qk_norm:
            norm = RMSNorm(eps=self.qk_norm_eps,
                           zero_centered=self.qk_norm_zero_centered)
            q, _ = norm.apply({"gamma": params["q_gamma"]}, {}, q)
            k, _ = norm.apply({"gamma": params["k_gamma"]}, {}, k)
        if self.rope_theta is not None:
            at = None if geometry is None else geometry.positions()
            q = rope(q, self.rope_theta, self.rotary_dim, at)
            k = rope(k, self.rope_theta, self.rotary_dim, at)
        if kv is not None and kv != h:
            # each key/value head serves its group of query heads; autodiff
            # sums the group's gradients back onto the one head
            with jax.named_scope(_scopes.KV_REPEAT):
                k = jnp.repeat(k, h // kv, axis=2)
                v = jnp.repeat(v, h // kv, axis=2)
        return q, k, v, gate

    def out_proj(self, params, attn):
        b, t, h, d = attn.shape
        with jax.named_scope(_scopes.MIX_OUT):
            y = matmul(attn.reshape(b * t, h * d), params["Wo"])
            if self.bias:
                y = y + params["bo"]
        return y.reshape(b, t, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope(_scopes.MHA):
            geometry = self._geometry(x.shape[1], train)
            q, k, v, gate = self.heads(params, x, geometry)
            attn = dot_product_attention(
                q, k, v, mask=mask, causal=self.causal and geometry is None,
                geometry=geometry)
            if gate is not None:
                with jax.named_scope("attn_gate"):
                    attn = attn * jax.nn.sigmoid(gate).astype(attn.dtype)
            y = self.out_proj(params, attn)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state

