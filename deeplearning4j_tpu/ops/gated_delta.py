"""The gated delta rule over a sequence, in its chunkwise form.

Per value head, a state ``S`` in ``R^{dk x dv}`` starting at zero:

    S' = exp(g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(Yang et al., "Gated Delta Networks", arXiv:2412.06464; the linear
attention of Qwen3-Next.) Run token by token that is ``T`` rank-one
updates in sequence. Here the sequence is cut into chunks of ``CHUNK``
positions: with ``G`` the running sum of ``g`` inside a chunk,

    A = strict_lower(beta_i (k_i . k_j) exp(G_i - G_j))
    U = (I + A)^-1 (beta v),   W = (I + A)^-1 (beta exp(G) k)

are matrix products over all chunks at once, and one ``lax.scan`` over
the chunks carries the state from chunk to chunk:

    v' = U - W S
    o  = (q exp(G)) S + lower(q k^T exp(G_i - G_j)) v'
    S  = exp(G_last) S + (k exp(G_last - G))^T v'

(Measured against it on the chip and not kept, PERF.md section 6, PR 34:
the scan carrying the state alone with one product a step, ``S <-
exp(G_last) S - (K'^T W) S + K'^T U`` with ``K'^T W`` and ``K'^T U`` made
beforehand, and ``v'`` and ``o`` as products over all chunks afterwards.)

``(I + A)^-1`` of the unit lower triangular ``I + A`` is exact as a
product of ``log2(CHUNK)`` factors, ``(I - A)(I + A^2)(I + A^4)...``: A is
strictly lower, so ``A^CHUNK = 0`` and the series ends. The backward pass
is autodiff's through the same products and the same scan, reversed, with
the chunk-local matrices computed again there and not kept
(``_chunk_local``).

The state, the decays and the triangular inverse are float32 (the
inverse's products at the highest precision); the other products take
their operands at the policy's compute dtype and accumulate in float32.
A key head serves ``Hv / Hk`` value heads: the shared products are done
once a key head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.utils import dtypes as _dtypes

#: positions a chunk holds. 64 as the published implementations: the
#: inverse is six products of [64, 64], and the scan has T / 64 steps
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C]:
    ``sum_k (-a)^k``, which ends at ``k = C - 1``, as the product
    ``(I + b)(I + b^2)(I + b^4)...`` with ``b = -a``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    power = -a
    inv = eye + power                 # the series below the power 2
    for _ in range((c - 1).bit_length() - 1):   # ... 4, 8, ... up to c
        power = jnp.matmul(power, power, precision=_HI)
        inv = inv + jnp.matmul(inv, power, precision=_HI)
    return inv


def gated_delta_rule(q, k, v, g, beta):
    """``q``, ``k`` [B, T, Hk, dk] (already normalised and scaled as the
    caller wants them), ``v`` [B, T, Hv, dv], ``g`` (log decay, <= 0) and
    ``beta`` (write strength) [B, T, Hv]; value head ``j`` reads key head
    ``j // (Hv // Hk)``. Returns ``o`` [B, T, Hv, dv] in ``v``'s dtype."""
    with jax.named_scope("gdn_core"):
        return _chunked(q, k, v, g, beta)


@jax.checkpoint
def _chunk_local(q, k, v, g, beta):
    """What the scan reads of every chunk, from products over all chunks
    at once: ``(U, W, q exp(G), lower(q k^T decay), k exp(G_last - G),
    exp(G_last))``. ``q``, ``k`` [B,Hk,N,C,dk], ``v`` [B,Hk,R,N,C,dv],
    ``g``, ``beta`` [B,Hk,R,N,C]. Recomputed in the backward pass, as the
    flash kernels recompute their probabilities: the decay matrix, ``A``
    and the inverse's five squarings and five products would otherwise
    stay in HBM from the forward, eleven [C, C] float32 matrices a chunk
    and value head (0.45 GB a layer at 4,096 tokens and 32 heads)."""
    c = q.shape[-2]
    cd, ad = _dtypes.compute_dtypes_for(v.dtype)
    gsum = jnp.cumsum(g, axis=-1)                        # G, <= 0
    seen = jnp.tril(jnp.ones((c, c), bool))              # j <= i
    diff = gsum[..., :, None] - gsum[..., None, :]
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow, and a where() after it would still hand
    # the overflow's gradient back
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)

    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k, precision=_HI)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[..., None] * kk[:, :, None] * decay, 0.0)
    inv = _unit_lower_inverse(a)                         # [B,Hk,R,N,C,C]
    u = jnp.matmul(inv, beta[..., None] * v, precision=_HI)
    w = jnp.matmul(inv, (beta * jnp.exp(gsum))[..., None] * k[:, :, None],
                   precision=_HI).astype(cd)             # [B,Hk,R,N,C,dk]

    qk = jnp.einsum("bhnid,bhnjd->bhnij", q.astype(cd), k.astype(cd),
                    preferred_element_type=ad)
    local = (qk[:, :, None] * decay).astype(cd)          # [B,Hk,R,N,C,C]
    q_in = (q[:, :, None] * jnp.exp(gsum)[..., None]).astype(cd)
    last = gsum[..., -1:]                                # [B,Hk,R,N,1]
    k_out = (k[:, :, None] * jnp.exp(last - gsum)[..., None]).astype(cd)
    carry = jnp.exp(last[..., 0])                        # [B,Hk,R,N]
    return u, w, q_in, local, k_out, carry


def _chunked(q, k, v, g, beta):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if hv % hk:
        raise ValueError(f"{hv} value heads are no multiple of {hk} key "
                         "heads")
    r = hv // hk
    cd, ad = _dtypes.compute_dtypes_for(v.dtype)
    c = CHUNK
    n = -(-t // c)
    pad = n * c - t
    if pad:
        # a padded position writes nothing (beta 0) and decays nothing (g 0)
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    # heads before time, chunks apart: [B, Hk, (R,) N, C, ...]
    q = q.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, n, c, dk)
    k = k.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, n, c, dk)
    v = v.astype(ad).transpose(0, 2, 1, 3).reshape(b, hk, r, n, c, dv)
    g = g.astype(ad).transpose(0, 2, 1).reshape(b, hk, r, n, c)
    beta = beta.astype(ad).transpose(0, 2, 1).reshape(b, hk, r, n, c)

    u, w, q_in, local, k_out, carry = _chunk_local(q, k, v, g, beta)

    def chunk(s, xs):
        u_i, w_i, q_i, local_i, k_i, carry_i = xs
        v_new = u_i - jnp.matmul(w_i, s.astype(cd),
                                 preferred_element_type=ad)
        o = (jnp.matmul(q_i, s.astype(cd), preferred_element_type=ad)
             + jnp.matmul(local_i, v_new.astype(cd),
                          preferred_element_type=ad))
        s = carry_i[..., None, None] * s + jnp.einsum(
            "bhrcd,bhrce->bhrde", k_i, v_new.astype(cd),
            preferred_element_type=ad)
        return s, o

    def chunks_first(x):
        return jnp.moveaxis(x, 3, 0)

    s0 = jnp.zeros((b, hk, r, dk, dv), ad)
    _, o = jax.lax.scan(chunk, s0, tuple(
        chunks_first(x) for x in (u, w, q_in, local, k_out, carry)))
    # [N, B, Hk, R, C, dv] -> [B, T, Hv, dv]
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)
    return o[:, :t].astype(v.dtype)
