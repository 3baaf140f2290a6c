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


def _nfeat(input_type):
    """Width of the last axis a norm scales."""
    if isinstance(input_type, _inputs.ConvolutionalType):
        return input_type.channels
    return input_type.size


@register_config
@dataclasses.dataclass(frozen=True)
class LayerNormalization(ParamLayer):
    """Per-feature layer norm (gamma/beta over the last axis)."""

    eps: float = 1e-5
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ("beta",)

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        n = _nfeat(input_type)
        return {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["gamma"] + params["beta"]
        return self.activation_fn()(y), state


@register_config
@dataclasses.dataclass(frozen=True)
class RMSNorm(ParamLayer):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019):
    ``x / sqrt(mean(x^2) + eps) * gamma``; no mean, no bias."""

    eps: float = 1e-6
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        return {"gamma": jnp.ones((_nfeat(input_type),), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("rmsnorm"):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            y = x * jax.lax.rsqrt(ms + self.eps) * params["gamma"]
            return self.activation_fn()(y), state


def rope(x, theta):
    """Rotary position embedding (Su et al. 2021) of ``x`` [B, T, H, D] at
    positions 0..T-1, in the rotate-half convention over the whole head
    width: pair ``i`` is (x[i], x[i + D/2]) and turns by
    ``t * theta**(-2i/D)``."""
    with jax.named_scope("rope"):
        t, d = x.shape[1], x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)


def dot_product_attention(q, k, v, *, mask=None, causal=False, scale=None):
    """q,k,v: [B, T, H, D]. Returns [B, T, H, D]. bf16 matmuls, f32 softmax.

    On TPU, attention (incl. [B, Tk] key-padding-masked batches) dispatches
    to the fused flash kernel (ops/attention_pallas.py) — O(T*D) HBM
    traffic instead of the [B,H,T,T] logits tensor; the dispatch seam
    mirrors the LSTM fused path."""
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    # the kernel needs a static scale; read once per trace, and jit keeps
    # the chosen blocks in the compiled step
    blocks = (_ap.resolve_attention(q.shape, k.shape, mask, q.dtype)
              if scale is None or isinstance(scale, (int, float)) else None)
    if blocks is not None:
        return _ap.flash_attention(q, k, v, mask=mask, causal=causal,
                                   scale=scale, block_q=blocks[0],
                                   block_k=blocks[1])
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
    """Self-attention over [B,T,F] with fused QKV projection.

    Model-definition fields beyond the original four (their defaults keep
    the original parameter tree and arithmetic): ``bias=False`` drops
    ``bqkv`` / ``bo``; ``rope_theta`` turns q and k by their positions
    before the attention; ``head_dim`` sets the head width apart from
    ``n_out / n_heads``."""

    n_out: int = 0     # model dim (also output dim)
    n_heads: int = 4
    causal: bool = False
    bias: bool = True
    rope_theta: float | None = None
    head_dim: int | None = None
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("Wqkv", "Wo")
    BIAS_KEYS = ("bqkv", "bo")

    def _head_dim(self):
        if self.head_dim is not None:
            return self.head_dim
        assert self.n_out % self.n_heads == 0
        return self.n_out // self.n_heads

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        inner = self.n_heads * self._head_dim()
        k1, k2 = jax.random.split(key)
        p = {
            "Wqkv": _init.init_weight(self.weight_init, k1, (n_in, 3 * inner),
                                      n_in, 3 * inner, dtype),
            "Wo": _init.init_weight(self.weight_init, k2, (inner, self.n_out),
                                    inner, self.n_out, dtype),
        }
        if self.bias:
            p["bqkv"] = jnp.zeros((3 * inner,), dtype)
            p["bo"] = jnp.zeros((self.n_out,), dtype)
        return p

    def heads(self, params, x):
        """Project to q,k,v [B,T,H,D]."""
        b, t, _ = x.shape
        h, d = self.n_heads, self._head_dim()
        qkv = matmul(x.reshape(b * t, -1), params["Wqkv"])
        if self.bias:
            qkv = qkv + params["bqkv"]
        qkv = qkv.reshape(b, t, 3, h, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.rope_theta is not None:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        return q, k, v

    def out_proj(self, params, attn):
        b, t, h, d = attn.shape
        y = matmul(attn.reshape(b * t, h * d), params["Wo"])
        if self.bias:
            y = y + params["bo"]
        return y.reshape(b, t, self.n_out)

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
    """Transformer block: norm -> MHA -> residual, norm -> FFN -> residual.

    The defaults are the original pre-norm block (LayerNorm, biased fused
    QKV, a ``mlp_ratio`` x GELU MLP). The other fields define other
    published blocks on the same code: ``norm`` "layer" | "rms" (with
    ``norm_eps``, None = the norm's own default); ``sandwich`` adds a norm
    after the mixer and after the FFN, before each residual add
    (``ln1_post`` / ``ln2_post``); ``bias=False`` drops every bias;
    ``rope_theta`` and ``head_dim`` go to the attention; ``ffn`` "mlp" |
    "gated" (``act(x Wg) * (x Wu)`` then ``Wd``) of width ``ffn_width``
    (None = ``n_out * mlp_ratio``)."""

    n_out: int = 0
    n_heads: int = 4
    mlp_ratio: int = 4
    causal: bool = False
    activation: object = "gelu"
    norm: str = "layer"
    norm_eps: float | None = None
    sandwich: bool = False
    bias: bool = True
    rope_theta: float | None = None
    head_dim: int | None = None
    ffn: str = "mlp"
    ffn_width: int | None = None
    weight_init: object = "xavier"

    input_family = _inputs.RecurrentType

    def _norm(self):
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm is 'layer' or 'rms', got {self.norm!r}")
        cls = LayerNormalization if self.norm == "layer" else RMSNorm
        return cls() if self.norm_eps is None else cls(eps=self.norm_eps)

    def _parts(self):
        return (self._norm(),
                MultiHeadAttention(n_out=self.n_out, n_heads=self.n_heads,
                                   causal=self.causal, bias=self.bias,
                                   rope_theta=self.rope_theta,
                                   head_dim=self.head_dim,
                                   weight_init=self.weight_init),
                self._norm())

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        assert input_type.size == self.n_out, \
            "TransformerBlock requires input size == n_out (residual)"
        if self.ffn not in ("mlp", "gated"):
            raise ValueError(f"ffn is 'mlp' or 'gated', got {self.ffn!r}")
        if self.bias and self.ffn == "gated":
            raise ValueError("the gated FFN has no biases: set bias=False")
        ln1, mha, ln2 = self._parts()
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hidden = self.ffn_width or self.n_out * self.mlp_ratio
        it = _inputs.RecurrentType(self.n_out, input_type.timesteps)

        def weight(k, n_in, n_out):
            return _init.init_weight(self.weight_init, k, (n_in, n_out),
                                     n_in, n_out, dtype)

        p = {"ln1": ln1.init(k1, it, dtype),
             "mha": mha.init(k1, it, dtype),
             "ln2": ln2.init(k2, it, dtype)}
        if self.sandwich:
            p["ln1_post"] = ln1.init(k1, it, dtype)
            p["ln2_post"] = ln2.init(k2, it, dtype)
        if self.ffn == "gated":
            k3g, k3u = jax.random.split(k3)
            p["mlp_Wg"] = weight(k3g, self.n_out, hidden)
            p["mlp_Wu"] = weight(k3u, self.n_out, hidden)
            p["mlp_Wd"] = weight(k4, hidden, self.n_out)
        else:
            p["mlp_W1"] = weight(k3, self.n_out, hidden)
            p["mlp_W2"] = weight(k4, hidden, self.n_out)
        if self.bias:
            p["mlp_b1"] = jnp.zeros((hidden,), dtype)
            p["mlp_b2"] = jnp.zeros((self.n_out,), dtype)
        return p

    def _ffn(self, params, h):
        from deeplearning4j_tpu.nn import activations as _act
        act = _act.get(self.activation)
        if self.ffn == "gated":
            m = act(matmul(h, params["mlp_Wg"])) * matmul(h, params["mlp_Wu"])
            return matmul(m, params["mlp_Wd"])
        m = matmul(h, params["mlp_W1"])
        m = act(m + params["mlp_b1"] if self.bias else m)
        m = matmul(m, params["mlp_W2"])
        return m + params["mlp_b2"] if self.bias else m

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        ln1, mha, ln2 = self._parts()
        with jax.named_scope("attn"):
            h, _ = ln1.apply(params["ln1"], {}, x)
            attn, _ = mha.apply(params["mha"], {}, h, mask=mask)
            if self.sandwich:
                attn, _ = ln1.apply(params["ln1_post"], {}, attn)
            x = x + attn
        with jax.named_scope("mlp"):
            h, _ = ln2.apply(params["ln2"], {}, x)
            b, t, f = h.shape
            m = self._ffn(params, h.reshape(b * t, f))
            if self.sandwich:
                m, _ = ln2.apply(params["ln2_post"], {}, m)
            return x + m.reshape(b, t, f), state

    def regularization_penalty(self, params):
        return 0.0
