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
    ``x / sqrt(mean(x^2) + eps) * gamma``; no mean, no bias.
    ``zero_centered`` stores the gain about zero: ``... * (1 + gamma)``,
    ``gamma`` starting at 0 (Qwen3-Next's norm; weight decay then pulls the
    gain towards 1, not towards 0)."""

    eps: float = 1e-6
    zero_centered: bool = False
    activation: object = dataclasses.field(default="identity", kw_only=True)

    input_family = None

    WEIGHT_KEYS = ("gamma",)
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        make = jnp.zeros if self.zero_centered else jnp.ones
        return {"gamma": make((_nfeat(input_type),), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        with jax.named_scope("rmsnorm"):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            gain = params["gamma"] + 1 if self.zero_centered \
                else params["gamma"]
            y = x * jax.lax.rsqrt(ms + self.eps) * gain
            return self.activation_fn()(y), state


def rope(x, theta, rotary_dim=None):
    """Rotary position embedding (Su et al. 2021) of ``x`` [B, T, H, D] at
    positions 0..T-1, in the rotate-half convention over the whole head
    width: pair ``i`` is (x[i], x[i + D/2]) and turns by
    ``t * theta**(-2i/D)``. With ``rotary_dim`` < D (partial rotary) the
    first ``rotary_dim`` of a head turn so, as a head of that width would,
    and the rest pass through."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate([rope(x[..., :rotary_dim], theta),
                                x[..., rotary_dim:]], axis=-1)
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
    before ``Wo`` (Qwen3-Next's gated attention)."""

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
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

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

    def heads(self, params, x):
        """Project to q,k,v [B,T,H,D] and the output gate's logits
        [B,T,H,D] (None without ``gate``)."""
        b, t, _ = x.shape
        h, d = self.n_heads, self._head_dim()
        kv = self._grouped()
        gate = None
        if kv is None:
            qkv = matmul(x.reshape(b * t, -1), params["Wqkv"])
            if self.bias:
                qkv = qkv + params["bqkv"]
            qkv = qkv.reshape(b, t, 3, h, d)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            x2 = x.reshape(b * t, -1)
            q = matmul(x2, params["Wq"]).reshape(b, t, h, -1)
            if self.gate:
                q, gate = q[..., :d], q[..., d:]
            k_v = matmul(x2, params["Wkv"]).reshape(b, t, 2, kv, d)
            k, v = k_v[:, :, 0], k_v[:, :, 1]
        if self.qk_norm:
            norm = RMSNorm(eps=self.qk_norm_eps,
                           zero_centered=self.qk_norm_zero_centered)
            q, _ = norm.apply({"gamma": params["q_gamma"]}, {}, q)
            k, _ = norm.apply({"gamma": params["k_gamma"]}, {}, k)
        if self.rope_theta is not None:
            q = rope(q, self.rope_theta, self.rotary_dim)
            k = rope(k, self.rope_theta, self.rotary_dim)
        if kv is not None and kv != h:
            # each key/value head serves its group of query heads; autodiff
            # sums the group's gradients back onto the one head
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
        return q, k, v, gate

    def out_proj(self, params, attn):
        b, t, h, d = attn.shape
        y = matmul(attn.reshape(b * t, h * d), params["Wo"])
        if self.bias:
            y = y + params["bo"]
        return y.reshape(b, t, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        q, k, v, gate = self.heads(params, x)
        attn = dot_product_attention(q, k, v, mask=mask, causal=self.causal)
        if gate is not None:
            with jax.named_scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(gate).astype(attn.dtype)
        y = self.out_proj(params, attn)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_config
@dataclasses.dataclass(frozen=True)
class ShortConv(ParamLayer):
    """Gated short convolution over [B,T,F] (the LFM2 family's second
    mixer): ``[B_, C_, x_] = split3(u W_in)`` in that order, ``z = B_ *
    x_``, a depthwise causal convolution of length ``kernel`` over time
    (zeros before the sequence's start; tap ``kernel - 1`` meets the
    present position), ``out = (C_ * c) W_out``. No bias and no
    activation inside. Both gates and the taps are one op on the
    in-projection's result as it lies (ops/causal_conv.py: two kernels
    under a ``custom_vjp`` where the shape allows, the ``jax.numpy`` form
    under autodiff elsewhere)."""

    n_out: int = 0
    kernel: int = 3
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W_in", "conv_w", "W_out")
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in, d = input_type.size, self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "W_in": _init.init_weight(self.weight_init, k1, (n_in, 3 * d),
                                      n_in, 3 * d, dtype),
            "conv_w": _init.init_weight(self.weight_init, k2,
                                        (d, self.kernel), self.kernel, 1,
                                        dtype),
            "W_out": _init.init_weight(self.weight_init, k3, (d, d), d, d,
                                       dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        with jax.named_scope("short_conv"):
            b, t, _ = x.shape
            d = self.n_out
            bcx = matmul(x.reshape(b * t, -1), params["W_in"])
            gated, _ = causal_conv(bcx.reshape(b, t, 3 * d),
                                   params["conv_w"], gate_before=True,
                                   gate_after=True)
            y = matmul(gated.reshape(b * t, d), params["W_out"])
            y = y.reshape(b, t, d)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state


@register_config
@dataclasses.dataclass(frozen=True)
class GatedDeltaNet(ParamLayer):
    """The gated delta rule as a sequence mixer over [B,T,F] (Qwen3-Next's
    linear attention; Yang et al., arXiv:2412.06464), ``k_heads`` key heads
    of ``head_dim`` serving ``v_heads`` value heads of ``v_head_dim`` (None
    = ``head_dim``), value head ``j`` reading key head ``j // (v_heads //
    k_heads)``:

    ``[q | k | v | z] = x W_qkvz`` and ``[b | a] = x W_ba``, each part
    whole and its heads in order; ``[q | k | v] = silu(conv(.))``, a
    depthwise causal convolution of ``conv_kernel`` taps over the channels
    in that order (zeros before the sequence's start, the last tap meeting
    the present position, no bias), taps and SiLU one op on the
    projection's leading columns as they lie (ops/causal_conv.py);
    ``beta = sigmoid(b)``; ``g = -exp(A_log)
    softplus(a + dt_bias)`` in float32; ``q = l2norm(q) / sqrt(head_dim)``,
    ``k = l2norm(k)`` (``x rsqrt(sum x^2 + 1e-6)``); the recurrence
    ``S = exp(g) S; S += beta k (v - S^T k)^T; o = S^T q`` a value head in
    its chunkwise form (ops/gated_delta.py); a head ``o = o / sqrt(mean(o^2)
    + norm_eps) * norm_w * silu(z)``; ``out = o W_out``. ``A_log`` starts
    at ``log U(0, 16)``, ``dt_bias`` and ``norm_w`` at 1."""

    n_out: int = 0
    k_heads: int = 16
    v_heads: int = 32
    head_dim: int = 128
    v_head_dim: int | None = None
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W_qkvz", "W_ba", "conv_w", "W_out")
    BIAS_KEYS = ("dt_bias",)

    L2NORM_EPS = 1e-6

    def _widths(self):
        """(key width, value width) over all heads."""
        dv = self.v_head_dim or self.head_dim
        return self.k_heads * self.head_dim, self.v_heads * dv

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        kw, vw = self._widths()
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)

        def weight(k, shape, fan_in, fan_out):
            return _init.init_weight(self.weight_init, k, shape, fan_in,
                                     fan_out, dtype)

        proj, conv = 2 * kw + 2 * vw, 2 * kw + vw
        return {
            "W_qkvz": weight(k1, (n_in, proj), n_in, proj),
            "W_ba": weight(k2, (n_in, 2 * self.v_heads), n_in,
                           2 * self.v_heads),
            "conv_w": weight(k3, (conv, self.conv_kernel), self.conv_kernel,
                             1),
            "A_log": jnp.log(jax.random.uniform(
                k4, (self.v_heads,), dtype, 1e-3, 16.0)),
            "dt_bias": jnp.ones((self.v_heads,), dtype),
            "norm_w": jnp.ones((vw // self.v_heads,), dtype),
            "W_out": weight(k5, (vw, self.n_out), vw, self.n_out),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        from deeplearning4j_tpu.ops.gated_delta import gated_delta_rule
        with jax.named_scope("gdn"):
            b, t, _ = x.shape
            kw, vw = self._widths()
            hk, hv = self.k_heads, self.v_heads
            _, ad = _dtypes.compute_dtypes_for(x.dtype)
            x2 = x.reshape(b * t, -1)
            qkvz = matmul(x2, params["W_qkvz"]).reshape(b, t, -1)
            ba = matmul(x2, params["W_ba"]).reshape(b, t, 2, hv).astype(ad)
            with jax.named_scope("gdn_conv"):
                (q, k, v), z = causal_conv(qkvz, params["conv_w"],
                                           activation=True,
                                           split=(kw, kw, vw))
            q = q.reshape(b, t, hk, -1)
            k = k.reshape(b, t, hk, -1)
            v = v.reshape(b, t, hv, -1)

            def l2norm(u):
                return u * jax.lax.rsqrt(
                    jnp.sum(jnp.square(u), -1, keepdims=True)
                    + self.L2NORM_EPS)

            q = l2norm(q) * (self.head_dim ** -0.5)
            k = l2norm(k)
            beta = jax.nn.sigmoid(ba[:, :, 0])
            g = -jnp.exp(params["A_log"].astype(ad)) * jax.nn.softplus(
                ba[:, :, 1] + params["dt_bias"].astype(ad))
            o = gated_delta_rule(q, k, v, g, beta)
            o, _ = RMSNorm(eps=self.norm_eps).apply(
                {"gamma": params["norm_w"]}, {}, o)
            o = o * jax.nn.silu(z.reshape(o.shape))
            y = matmul(o.reshape(b * t, vw), params["W_out"])
            y = y.reshape(b, t, self.n_out)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state


def gated_group_norm(y, z, w, groups, eps):
    """Mamba-2's gated RMS norm over [..., F]: ``u = y * silu(z)``, each
    of the ``groups`` runs of ``F / groups`` channels normed by its own
    root mean square (``u / sqrt(mean(u^2) + eps)``), times the gain ``w``
    [F]."""
    with jax.named_scope("rmsnorm"):
        u = y * jax.nn.silu(z)
        g = u.reshape(*u.shape[:-1], groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        return g.reshape(u.shape) * w


@register_config
@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(ParamLayer):
    """Mamba-2's selective state-space mixer over [B,T,F] (Dao & Gu,
    arXiv:2405.21060; the ``M`` layers of the Nemotron-H family):
    ``heads`` heads of ``head_dim`` (``d_inner`` = their product),
    ``groups`` groups of ``B`` and ``C`` of ``state`` each, head ``h``
    reading group ``h // (heads / groups)``.

    ``[z | x B C | dt] = u W_in`` (widths ``d_inner | d_inner + 2 groups
    state | heads``; one matrix, three products, so that each part lies
    where its consumer reads it); ``[x | B | C] = silu(conv(.) + conv_b)``,
    a depthwise causal convolution of ``conv_kernel`` taps with a bias
    (ops/causal_conv.py); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head, in float32; the recurrence ``S = exp(dt A) S +
    dt x B^T; y = S C + D x`` in its chunkwise form at ``chunk`` positions
    (ops/ssd.py); the grouped gated norm ``gated_group_norm(y, z)`` with
    gain ``norm_w``; ``out = y W_out``. No projection bias.

    The layer's own initialisation: ``A_log = log(1..heads)``, ``D`` 1,
    ``dt_bias`` the inverse softplus of ``exp(U(log 1e-3, log 0.1))``
    floored at 1e-4 (``DT_RANGE``, ``DT_FLOOR``: the family's published
    ``time_step_*``), ``conv_b`` 0, ``norm_w`` 1, ``W_out`` times
    ``out_scale`` (the family divides it by the root of the depth)."""

    n_out: int = 0
    heads: int = 64
    head_dim: int = 64
    groups: int = 8
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    out_scale: float = 1.0
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W_in", "conv_w", "W_out")
    BIAS_KEYS = ("conv_b", "dt_bias")

    DT_RANGE = (1e-3, 0.1)
    DT_FLOOR = 1e-4

    def _widths(self):
        """(d_inner, the convolution's channels)."""
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads are no multiple of "
                             f"{self.groups} groups")
        inner = self.heads * self.head_dim
        return inner, inner + 2 * self.groups * self.state

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        inner, conv = self._widths()
        k1, k2, k3, k4 = jax.random.split(key, 4)

        def weight(k, shape, fan_in, fan_out):
            return _init.init_weight(self.weight_init, k, shape, fan_in,
                                     fan_out, dtype)

        proj = inner + conv + self.heads
        lo, hi = self.DT_RANGE
        dt = jnp.exp(jax.random.uniform(k3, (self.heads,), dtype,
                                        jnp.log(lo), jnp.log(hi)))
        dt = jnp.maximum(dt, self.DT_FLOOR)
        return {
            "W_in": weight(k1, (n_in, proj), n_in, proj),
            "conv_w": weight(k2, (conv, self.conv_kernel), self.conv_kernel,
                             1),
            "conv_b": jnp.zeros((conv,), dtype),
            "A_log": jnp.log(jnp.arange(1, self.heads + 1, dtype=dtype)),
            "D": jnp.ones((self.heads,), dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "norm_w": jnp.ones((inner,), dtype),
            "W_out": self.out_scale * weight(k4, (inner, self.n_out), inner,
                                             self.n_out),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.causal_conv import causal_conv
        from deeplearning4j_tpu.ops.ssd import ssd
        with jax.named_scope("ssm"):
            b, t, _ = x.shape
            inner, conv = self._widths()
            h, g = self.heads, self.groups
            _, ad = _dtypes.compute_dtypes_for(x.dtype)
            x2 = x.reshape(b * t, -1)
            w_in = params["W_in"]
            z = matmul(x2, w_in[:, :inner]).reshape(b, t, inner)
            xbc = matmul(x2, w_in[:, inner:inner + conv]).reshape(b, t, conv)
            dt = matmul(x2, w_in[:, inner + conv:]).reshape(b, t, h)
            with jax.named_scope("ssm_conv"):
                (xs, bs, cs), _ = causal_conv(
                    xbc, params["conv_w"], params["conv_b"], activation=True,
                    split=(inner, g * self.state, g * self.state))
            dt = jax.nn.softplus(dt.astype(ad) + params["dt_bias"].astype(ad))
            y = ssd(xs.reshape(b, t, h, -1), dt,
                    -jnp.exp(params["A_log"].astype(ad)),
                    bs.reshape(b, t, g, -1), cs.reshape(b, t, g, -1),
                    params["D"], chunk=self.chunk)
            y = gated_group_norm(y.reshape(b, t, inner), z, params["norm_w"],
                                 g, self.norm_eps)
            y = matmul(y.reshape(b * t, inner), params["W_out"])
            y = y.reshape(b, t, self.n_out)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state


@register_config
@dataclasses.dataclass(frozen=True)
class LatentAttention(ParamLayer):
    """Multi-head latent attention over [B,T,F] in its training form
    (DeepSeek-V2, arXiv:2405.04434 section 2.1; the GLM-4.7-Flash family's
    ``glm4_moe_lite``): queries through a low-rank latent with an RMSNorm
    on it, keys and values expanded from ONE normalised latent a token,
    and a rotary key part computed once a token and shared by all heads:

        c_q           = rmsnorm(u W_qa)                   W_qa [F, q_rank]
        [qn_j | qr_j] = (c_q W_qb)_j                      W_qb [q_rank, H (nope + rope)]
        [c_kv | kr]   = u W_kva                           W_kva [F, kv_rank + rope]
        [kn_j | v_j]  = (rmsnorm(c_kv) W_kvb)_j           W_kvb [kv_rank, H (nope + v)]
        q_j = [qn_j | rope(qr_j)],  k_j = [kn_j | rope(kr)]
        o_j = softmax(q_j k_j^T / sqrt(nope + rope)) v_j,  out = [o_1 .. o_H] Wo

    ``rope`` turns all ``rope_dim`` of the rotary parts (rotate-half). The
    attention itself goes through the one dispatch
    (``dot_product_attention``: the flash kernels where
    ``resolve_attention`` says so), so a head's value width is its
    query's: ``v_dim == nope_dim + rope_dim``. The up-projections are not
    folded into the query and the output (the absorbed form is decode's:
    at training lengths it widens the score product to ``kv_rank``). No
    bias."""

    n_out: int = 0
    n_heads: int = 4
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    causal: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("W_qa", "W_qb", "W_kva", "W_kvb", "Wo")
    BIAS_KEYS = ()

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        if self.v_dim != self.nope_dim + self.rope_dim:
            raise ValueError(
                f"a head's value width {self.v_dim} is not its query's "
                f"{self.nope_dim} + {self.rope_dim}: the one attention "
                "dispatch takes q, k and v of one width")
        n_in, h = input_type.size, self.n_heads
        keys = jax.random.split(key, 5)

        def weight(k, n_in, n_out):
            return _init.init_weight(self.weight_init, k, (n_in, n_out),
                                     n_in, n_out, dtype)

        return {
            "W_qa": weight(keys[0], n_in, self.q_rank),
            "q_gamma": jnp.ones((self.q_rank,), dtype),
            "W_qb": weight(keys[1], self.q_rank,
                           h * (self.nope_dim + self.rope_dim)),
            "W_kva": weight(keys[2], n_in, self.kv_rank + self.rope_dim),
            "kv_gamma": jnp.ones((self.kv_rank,), dtype),
            "W_kvb": weight(keys[3], self.kv_rank,
                            h * (self.nope_dim + self.v_dim)),
            "Wo": weight(keys[4], h * self.v_dim, self.n_out),
        }

    def heads(self, params, x):
        """q, k, v [B,T,H,D], the rotary parts turned."""
        b, t, _ = x.shape
        h, dn, dr = self.n_heads, self.nope_dim, self.rope_dim
        norm = RMSNorm(eps=self.norm_eps)
        x2 = x.reshape(b * t, -1)
        c_q, _ = norm.apply({"gamma": params["q_gamma"]}, {},
                            matmul(x2, params["W_qa"]))
        q = matmul(c_q, params["W_qb"]).reshape(b, t, h, dn + dr)
        kva = matmul(x2, params["W_kva"])
        c_kv, _ = norm.apply({"gamma": params["kv_gamma"]}, {},
                             kva[:, :self.kv_rank])
        kv = matmul(c_kv, params["W_kvb"]).reshape(b, t, h, dn + self.v_dim)
        # one rotary key a token: every head reads it, and autodiff sums
        # the heads' gradients back onto it
        kr = rope(kva[:, self.kv_rank:].reshape(b, t, 1, dr),
                  self.rope_theta)
        q = jnp.concatenate([q[..., :dn],
                             rope(q[..., dn:], self.rope_theta)], axis=-1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(kr, (b, t, h, dr))], axis=-1)
        return q, k, kv[..., dn:]

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("mla"):
            b, t, _ = x.shape
            q, k, v = self.heads(params, x)
            attn = dot_product_attention(q, k, v, mask=mask,
                                         causal=self.causal)
            y = matmul(attn.reshape(b * t, -1), params["Wo"])
            y = y.reshape(b, t, self.n_out)
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
    ``norm_eps``, None = the norm's own default; ``norm_zero_centered``
    the RMS norms' gains about zero, the attention's q/k norms too);
    ``sandwich`` adds a norm after the mixer and after the FFN, before
    each residual add (``ln1_post`` / ``ln2_post``); ``bias=False`` drops
    every bias; ``rope_theta``, ``rotary_dim``, ``head_dim``,
    ``n_kv_heads``, ``qk_norm`` (with ``norm_eps``) and ``attn_gate`` go to
    the attention; ``mixer`` "attention" | "short_conv" | "gated_delta" |
    "mamba2" | "latent_attention" puts in the attention's place a
    ``ShortConv`` of length ``conv_kernel`` (parameters under ``conv``,
    not ``mha``), a
    ``GatedDeltaNet`` of ``linear_k_heads`` key and ``linear_v_heads``
    value heads of ``linear_head_dim`` / ``linear_v_head_dim``, its
    convolution ``conv_kernel`` taps (parameters under ``gdn``), or a
    ``Mamba2Mixer`` of ``ssm_heads`` heads of ``ssm_head_dim``,
    ``ssm_groups`` groups of ``ssm_state``, chunks of ``ssm_chunk``, its
    convolution ``conv_kernel`` taps and its out-projection initialised
    times ``ssm_out_scale`` (parameters under ``ssm``), or, the fifth
    mixer, a ``LatentAttention`` of ``n_heads`` heads whose queries pass a
    latent of ``q_rank`` and whose keys and values are expanded from one
    of ``kv_rank``, a head ``nope_dim`` + ``rope_dim`` wide (the rotary
    part turned at ``rope_theta``, its key shared by the heads) with
    values of ``v_dim`` (parameters under ``mla``); ``ffn`` "mlp" |
    "gated" (``act(x Wg) * (x Wu)`` then ``Wd``) | "moe" of width
    ``ffn_width`` (None = ``n_out * mlp_ratio``). **Either half may be
    absent**: ``mixer="none"`` is a block of norm -> FFN -> residual
    alone, ``ffn="none"`` one of norm -> mixer -> residual alone (the
    single-part layers of the Nemotron-H family); the absent half has no
    norm, no parameters, no scope and no residual add. ``"moe"`` is a
    dropless top-``top_k`` router (``router`` "sigmoid" | "softmax") over
    ``n_experts`` experts of that width, gated (``act(x Wg) * (x Wu)``
    then ``Wd``) or, with ``expert_gated=False``, ungated (``act(x Wu)``
    then ``Wd``, no ``moe_Wg``), of which this layer holds
    ``experts_held`` = (first, end) (() = all of them) and computes their
    part of the result (``moe.routed_experts``); the last step's
    ``moe_load`` / ``moe_elsewhere`` and the sigmoid router's
    ``expert_bias`` live in the layer's state. ``shared_expert_width`` > 0
    adds the block's shared expert, an FFN of that width and the experts'
    form over every token, times ``sigmoid(x w_sg)`` unless
    ``shared_expert_gate=False`` (``moe_shared_*``; held whole whatever
    ``experts_held`` says, as every chip of a deployment would)."""

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
    mixer: str = "attention"
    conv_kernel: int = 3
    n_kv_heads: int | None = None
    qk_norm: bool = False
    n_experts: int = 0
    top_k: int = 1
    experts_held: tuple = ()
    routed_scale: float = 1.0
    router: str = "sigmoid"
    shared_expert_width: int = 0
    norm_zero_centered: bool = False
    rotary_dim: int | None = None
    attn_gate: bool = False
    linear_k_heads: int = 0
    linear_v_heads: int = 0
    linear_head_dim: int = 0
    linear_v_head_dim: int | None = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_chunk: int = 128
    ssm_out_scale: float = 1.0
    expert_gated: bool = True
    shared_expert_gate: bool = True
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0

    input_family = _inputs.RecurrentType

    MIXER_KEYS = {"attention": "mha", "short_conv": "conv",
                  "gated_delta": "gdn", "mamba2": "ssm",
                  "latent_attention": "mla"}

    def _held(self):
        """(first, end) of the experts this layer holds."""
        first, end = self.experts_held or (0, self.n_experts)
        if not 0 <= first < end <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in 0..{self.n_experts}")
        return int(first), int(end)

    def _eps(self, field):
        """``norm_eps`` as the keyword ``field`` of a part that takes one;
        nothing where the part's own default stands."""
        return {} if self.norm_eps is None else {field: self.norm_eps}

    def _norm(self):
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm is 'layer' or 'rms', got {self.norm!r}")
        kw = self._eps("eps")
        if self.norm == "layer":
            if self.norm_zero_centered:
                raise ValueError("norm_zero_centered is the RMS norm's")
            return LayerNormalization(**kw)
        return RMSNorm(zero_centered=self.norm_zero_centered, **kw)

    def _parts(self):
        """(norm, mixer, norm); the mixer's parameters sit under
        ``_mixer_key()``, and it is None where the block has none."""
        if self.mixer == "none":
            mixer = None
        elif self.mixer == "mamba2":
            mixer = Mamba2Mixer(
                n_out=self.n_out, heads=self.ssm_heads,
                head_dim=self.ssm_head_dim, groups=self.ssm_groups,
                state=self.ssm_state, conv_kernel=self.conv_kernel,
                chunk=self.ssm_chunk, out_scale=self.ssm_out_scale,
                weight_init=self.weight_init, **self._eps("norm_eps"))
        elif self.mixer == "short_conv":
            mixer = ShortConv(n_out=self.n_out, kernel=self.conv_kernel,
                              weight_init=self.weight_init)
        elif self.mixer == "gated_delta":
            mixer = GatedDeltaNet(
                n_out=self.n_out, k_heads=self.linear_k_heads,
                v_heads=self.linear_v_heads, head_dim=self.linear_head_dim,
                v_head_dim=self.linear_v_head_dim,
                conv_kernel=self.conv_kernel, weight_init=self.weight_init,
                **self._eps("norm_eps"))
        elif self.mixer == "latent_attention":
            mixer = LatentAttention(
                n_out=self.n_out, n_heads=self.n_heads, q_rank=self.q_rank,
                kv_rank=self.kv_rank, nope_dim=self.nope_dim,
                rope_dim=self.rope_dim, v_dim=self.v_dim,
                causal=self.causal, rope_theta=self.rope_theta,
                weight_init=self.weight_init, **self._eps("norm_eps"))
        elif self.mixer == "attention":
            mixer = MultiHeadAttention(
                n_out=self.n_out, n_heads=self.n_heads, causal=self.causal,
                bias=self.bias, rope_theta=self.rope_theta,
                head_dim=self.head_dim, n_kv_heads=self.n_kv_heads,
                qk_norm=self.qk_norm,
                qk_norm_zero_centered=self.norm_zero_centered,
                rotary_dim=self.rotary_dim, gate=self.attn_gate,
                weight_init=self.weight_init, **self._eps("qk_norm_eps"))
        else:
            raise ValueError("mixer is 'attention', 'short_conv', "
                             "'gated_delta', 'mamba2', 'latent_attention' "
                             f"or 'none', got {self.mixer!r}")
        return self._norm(), mixer, self._norm()

    def _mixer_key(self):
        return self.MIXER_KEYS[self.mixer]

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        assert input_type.size == self.n_out, \
            "TransformerBlock requires input size == n_out (residual)"
        if self.ffn not in ("mlp", "gated", "moe", "none"):
            raise ValueError("ffn is 'mlp', 'gated', 'moe' or 'none', got "
                             f"{self.ffn!r}")
        if self.ffn == self.mixer == "none":
            raise ValueError("a block has a mixer, an FFN or both")
        if self.sandwich and "none" in (self.ffn, self.mixer):
            raise ValueError("the sandwich norms belong to a whole block")
        if self.bias and self.ffn not in ("mlp", "none"):
            raise ValueError(f"the {self.ffn} FFN has no biases: set "
                             "bias=False")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError("router is 'sigmoid' or 'softmax', got "
                             f"{self.router!r}")
        if self.shared_expert_width and self.ffn != "moe":
            raise ValueError("the shared expert belongs to ffn='moe'")
        ln1, mha, ln2 = self._parts()
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hidden = self.ffn_width or self.n_out * self.mlp_ratio
        it = _inputs.RecurrentType(self.n_out, input_type.timesteps)

        def weight(k, n_in, n_out):
            return _init.init_weight(self.weight_init, k, (n_in, n_out),
                                     n_in, n_out, dtype)

        p = {}
        if mha is not None:
            p.update({"ln1": ln1.init(k1, it, dtype),
                      self._mixer_key(): mha.init(k1, it, dtype)})
        if self.ffn != "none":
            p["ln2"] = ln2.init(k2, it, dtype)
        if self.sandwich:
            p["ln1_post"] = ln1.init(k1, it, dtype)
            p["ln2_post"] = ln2.init(k2, it, dtype)
        if self.ffn == "gated":
            k3g, k3u = jax.random.split(k3)
            p["mlp_Wg"] = weight(k3g, self.n_out, hidden)
            p["mlp_Wu"] = weight(k3u, self.n_out, hidden)
            p["mlp_Wd"] = weight(k4, hidden, self.n_out)
        elif self.ffn == "moe":
            first, end = self._held()
            kr, kg, ku = jax.random.split(k3, 3)

            def experts(k, n_in, n_out):
                return jnp.stack([weight(kk, n_in, n_out) for kk in
                                  jax.random.split(k, end - first)])

            p["moe_router"] = weight(kr, self.n_out, self.n_experts)
            if self.expert_gated:
                p["moe_Wg"] = experts(kg, self.n_out, hidden)
            p["moe_Wu"] = experts(ku, self.n_out, hidden)
            p["moe_Wd"] = experts(k4, hidden, self.n_out)
            if self.shared_expert_width:
                ks = jax.random.split(jax.random.fold_in(k3, 1), 4)
                fs = self.shared_expert_width
                if self.expert_gated:
                    p["moe_shared_Wg"] = weight(ks[0], self.n_out, fs)
                p["moe_shared_Wu"] = weight(ks[1], self.n_out, fs)
                p["moe_shared_Wd"] = weight(ks[2], fs, self.n_out)
                if self.shared_expert_gate:
                    p["moe_shared_gate"] = weight(ks[3], self.n_out, 1)
        elif self.ffn == "mlp":
            p["mlp_W1"] = weight(k3, self.n_out, hidden)
            p["mlp_W2"] = weight(k4, hidden, self.n_out)
        if self.bias and self.ffn == "mlp":
            p["mlp_b1"] = jnp.zeros((hidden,), dtype)
            p["mlp_b2"] = jnp.zeros((self.n_out,), dtype)
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        if self.ffn != "moe":
            return {}
        first, end = self._held()
        counts = {"moe_load": jnp.zeros((end - first,), dtype),
                  "moe_elsewhere": jnp.zeros((1,), dtype)}
        if self.router == "softmax":     # no bias moves its selection
            return counts
        return {"expert_bias": jnp.zeros((self.n_experts,), dtype), **counts}

    def _moe(self, params, state, h):
        """The routed experts' part of the result, with the shared
        expert's where the block has one, and the state with this step's
        row counts."""
        from deeplearning4j_tpu.nn import activations as _act
        from deeplearning4j_tpu.nn.layers import moe as _moe
        if "moe_load" not in state:
            raise ValueError(
                "ffn='moe' keeps its load (and the sigmoid router its "
                "expert bias) in the layer's state; this caller hands the "
                "block none")
        act = _act.get(self.activation)
        with jax.named_scope("moe"):
            y, load, elsewhere = _moe.routed_experts(
                h, params["moe_router"], params.get("moe_Wg"),
                params["moe_Wu"], params["moe_Wd"],
                state.get("expert_bias"), top_k=self.top_k,
                held=self._held(), scale=self.routed_scale, act=act,
                score=self.router)
            if self.shared_expert_width:
                with jax.named_scope("moe_shared"):
                    if self.expert_gated:
                        m = (act(matmul(h, params["moe_shared_Wg"]))
                             * matmul(h, params["moe_shared_Wu"]))
                    else:
                        m = act(matmul(h, params["moe_shared_Wu"]))
                    if self.shared_expert_gate:
                        gate = jax.nn.sigmoid(
                            matmul(h, params["moe_shared_gate"]))
                        y = y + gate * matmul(m, params["moe_shared_Wd"])
                    else:
                        y = y + matmul(m, params["moe_shared_Wd"])
        dt = state["moe_load"].dtype
        return y, {**state, "moe_load": load.astype(dt),
                   "moe_elsewhere": elsewhere.astype(dt)}

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
        if mha is not None:
            with jax.named_scope("attn"):
                h, _ = ln1.apply(params["ln1"], {}, x)
                attn, _ = mha.apply(params[self._mixer_key()], {}, h,
                                    mask=mask)
                if self.sandwich:
                    attn, _ = ln1.apply(params["ln1_post"], {}, attn)
                x = x + attn
        if self.ffn == "none":
            return x, state
        with jax.named_scope("mlp"):
            h, _ = ln2.apply(params["ln2"], {}, x)
            b, t, f = h.shape
            if self.ffn == "moe":
                m, state = self._moe(params, state, h.reshape(b * t, f))
            else:
                m = self._ffn(params, h.reshape(b * t, f))
            if self.sandwich:
                m, _ = ln2.apply(params["ln2_post"], {}, m)
            return x + m.reshape(b, t, f), state

    def regularization_penalty(self, params):
        return 0.0
