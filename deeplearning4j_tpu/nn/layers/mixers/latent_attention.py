"""Multi-head latent attention in its training form (DeepSeek-V2; the
GLM-4.7-Flash family)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.attention import (dot_product_attention,
                                                    rope)
from deeplearning4j_tpu.nn.layers.base import ParamLayer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.layers.norms import RMSNorm
from deeplearning4j_tpu.utils.serde import register_config


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
    bias. As a block's mixer its parameters sit under ``mla``."""

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

    param_key = "mla"   # where a block keeps this mixer's parameters

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
        with jax.named_scope(_scopes.MIX_IN):
            q_a = matmul(x2, params["W_qa"])
        c_q, _ = norm.apply({"gamma": params["q_gamma"]}, {}, q_a)
        with jax.named_scope(_scopes.MIX_IN):
            q = matmul(c_q, params["W_qb"]).reshape(b, t, h, dn + dr)
            kva = matmul(x2, params["W_kva"])
        c_kv, _ = norm.apply({"gamma": params["kv_gamma"]}, {},
                             kva[:, :self.kv_rank])
        with jax.named_scope(_scopes.MIX_IN):
            kv = matmul(c_kv, params["W_kvb"]).reshape(b, t, h,
                                                       dn + self.v_dim)
        # one rotary key a token: every head reads it, and autodiff sums
        # the heads' gradients back onto it
        kr = rope(kva[:, self.kv_rank:].reshape(b, t, 1, dr),
                  self.rope_theta)
        with jax.named_scope(_scopes.HEAD_JOIN):
            qn, qr = q[..., :dn], q[..., dn:]
        qr = rope(qr, self.rope_theta)
        with jax.named_scope(_scopes.HEAD_JOIN):
            q = jnp.concatenate([qn, qr], axis=-1)
            k = jnp.concatenate([kv[..., :dn],
                                 jnp.broadcast_to(kr, (b, t, h, dr))],
                                axis=-1)
        return q, k, kv[..., dn:]

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("mla"):
            b, t, _ = x.shape
            q, k, v = self.heads(params, x)
            attn = dot_product_attention(q, k, v, mask=mask,
                                         causal=self.causal)
            with jax.named_scope(_scopes.MIX_OUT):
                y = matmul(attn.reshape(b * t, -1), params["Wo"])
            y = y.reshape(b, t, self.n_out)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state
