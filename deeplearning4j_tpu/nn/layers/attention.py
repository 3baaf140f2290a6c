"""Attention layers + layer normalization.

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
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer, Layer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class LayerNormalization(ParamLayer):
    """Per-feature layer norm (gamma/beta over the last axis)."""

    eps: float = 1e-5
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ("beta",)

    def _nfeat(self, input_type):
        if isinstance(input_type, _inputs.ConvolutionalType):
            return input_type.channels
        return input_type.size

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        n = self._nfeat(input_type)
        return {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["gamma"] + params["beta"]
        return self.activation_fn()(y), state


def dot_product_attention(q, k, v, *, mask=None, causal=False, scale=None):
    """q,k,v: [B, T, H, D]. Returns [B, T, H, D]. bf16 matmuls, f32 softmax.

    On TPU, attention (incl. [B, Tk] key-padding-masked batches) dispatches
    to the fused flash kernel (ops/attention_pallas.py) — O(T*D) HBM
    traffic instead of the [B,H,T,T] logits tensor; the dispatch seam
    mirrors the LSTM fused path."""
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    resolved = (_ap.resolve_attention(q.shape, k.shape, mask, q.dtype)
                if (_ap.enabled() and (scale is None
                                       or isinstance(scale, (int, float))))
                else None)
    if resolved is not None:
        # one DB lookup decides dispatch AND geometry: TuningDB winner >
        # the DL4J_TPU_FLASH_BLOCK_Q/K env knobs (live-window A/B
        # sweeps) > the hand-picked 512x512. Read once per trace — jit
        # caches the chosen blocks into the compiled step. A tuned
        # remat=True wraps the kernel in jax.checkpoint: the backward
        # recomputes the forward instead of saving out/lse residuals
        # (the searched memory-for-time dimension).
        bq, bk, remat = resolved

        def flash(q, k, v):
            return _ap.flash_attention(q, k, v, mask=mask, causal=causal,
                                       scale=scale, block_q=bq, block_k=bk)

        return (jax.checkpoint(flash) if remat else flash)(q, k, v)
    cd, ad = _dtypes.compute_dtypes_for(q.dtype)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, ad))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(cd), k.astype(cd),
                        preferred_element_type=ad) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
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
    """Self-attention over [B,T,F] with fused QKV projection."""

    n_out: int = 0     # model dim (also output dim)
    n_heads: int = 4
    causal: bool = False
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("Wqkv", "Wo")
    BIAS_KEYS = ("bqkv", "bo")

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        assert self.n_out % self.n_heads == 0
        k1, k2 = jax.random.split(key)
        return {
            "Wqkv": _init.init_weight(self.weight_init, k1, (n_in, 3 * self.n_out),
                                      n_in, 3 * self.n_out, dtype),
            "bqkv": jnp.zeros((3 * self.n_out,), dtype),
            "Wo": _init.init_weight(self.weight_init, k2, (self.n_out, self.n_out),
                                    self.n_out, self.n_out, dtype),
            "bo": jnp.zeros((self.n_out,), dtype),
        }

    def heads(self, params, x):
        """Project to q,k,v [B,T,H,D]."""
        b, t, _ = x.shape
        h, d = self.n_heads, self.n_out // self.n_heads
        qkv = matmul(x.reshape(b * t, -1), params["Wqkv"]) + params["bqkv"]
        qkv = qkv.reshape(b, t, 3, h, d)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def out_proj(self, params, attn):
        b, t, h, d = attn.shape
        y = matmul(attn.reshape(b * t, h * d), params["Wo"]) + params["bo"]
        return y.reshape(b, t, h * d)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        q, k, v = self.heads(params, x)
        attn = dot_product_attention(q, k, v, mask=mask, causal=self.causal)
        y = self.out_proj(params, attn)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_config
@dataclasses.dataclass(frozen=True)
class TransformerBlock(Layer):
    """Pre-norm transformer block: LN -> MHA -> residual, LN -> MLP -> residual."""

    n_out: int = 0
    n_heads: int = 4
    mlp_ratio: int = 4
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
            "TransformerBlock requires input size == n_out (residual)"
        ln1, mha, ln2 = self._parts()
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hidden = self.n_out * self.mlp_ratio
        it = _inputs.RecurrentType(self.n_out, input_type.timesteps)
        return {
            "ln1": ln1.init(k1, it, dtype),
            "mha": mha.init(k1, it, dtype),
            "ln2": ln2.init(k2, it, dtype),
            "mlp_W1": _init.init_weight("xavier", k3, (self.n_out, hidden),
                                        self.n_out, hidden, dtype),
            "mlp_b1": jnp.zeros((hidden,), dtype),
            "mlp_W2": _init.init_weight("xavier", k4, (hidden, self.n_out),
                                        hidden, self.n_out, dtype),
            "mlp_b2": jnp.zeros((self.n_out,), dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn import activations as _act
        ln1, mha, ln2 = self._parts()
        with jax.named_scope("attn"):
            h, _ = ln1.apply(params["ln1"], {}, x)
            attn, _ = mha.apply(params["mha"], {}, h, mask=mask)
            x = x + attn
        with jax.named_scope("mlp"):
            h, _ = ln2.apply(params["ln2"], {}, x)
            b, t, f = h.shape
            act = _act.get(self.activation)
            m = act(matmul(h.reshape(b * t, f), params["mlp_W1"])
                    + params["mlp_b1"])
            m = matmul(m, params["mlp_W2"]) + params["mlp_b2"]
            return x + m.reshape(b, t, f), state

    def regularization_penalty(self, params):
        return 0.0
