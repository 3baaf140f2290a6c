"""Sequence/context parallelism: ring attention over the mesh 'seq' axis.

The reference's only long-sequence mechanism is truncated BPTT + masking
(SURVEY.md §5); this module provides the TPU-native long-context capability
the north star requires: sequences sharded across devices on the 'seq' mesh
axis, with attention computed blockwise while K/V blocks rotate around the
ring via ppermute (Liu et al. ring attention). Communication rides ICI and
overlaps with the blockwise matmuls; memory per device is O(T/N).

Numerics: online-softmax accumulation (running max m, denominator l,
numerator acc) in f32 — mathematically exact vs full attention, verified by
tests against the single-device reference on the virtual 8-device CPU mesh.

Also provided: all_to_all "Ulysses"-style head-parallel attention — sequence
is gathered per head group via all_to_all so each device computes full
attention for a subset of heads. Cheaper at moderate T, ring wins at long T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.utils import dtypes as _dtypes


def _block_attn(q, k, v, *, scale, block_mask=None):
    """Blockwise logits/numerator for online softmax.

    q: [B,Tq,H,D], k/v: [B,Tk,H,D]. Returns (m_blk [B,H,Tq], num [B,Tq,H,D],
    den [B,H,Tq]) where m_blk is the block's row max.
    """
    cd, ad = _dtypes.compute_dtypes_for(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(cd), k.astype(cd),
                        preferred_element_type=ad) * scale
    if block_mask is not None:
        logits = jnp.where(block_mask, logits, -jnp.inf)
    m_blk = jnp.max(logits, axis=-1)                         # [B,H,Tq]
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m_blk), m_blk, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    den = jnp.sum(p, axis=-1)                                # [B,H,Tq]
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cd), v.astype(cd),
                     preferred_element_type=ad)              # [B,Tq,H,D]
    return m_safe, num, den


def _naive_block(q, k, v, scale, block_mask):
    """(out_b, lse_b) for one block pair via materialized logits."""
    m_safe, num, den = _block_attn(q, k, v, scale=scale,
                                   block_mask=block_mask)
    den_safe = jnp.maximum(den, 1e-30)
    out = (num.astype(jnp.float32)
           / den_safe.transpose(0, 2, 1)[..., None])
    lse = jnp.where(den > 0, m_safe + jnp.log(den_safe), -jnp.inf)
    return out, lse


def ring_self_attention(q, k, v, *, axis_name="seq", causal=False,
                        scale=None, use_flash=None, interpret=False):
    """Exact self-attention with q/k/v sharded over ``axis_name`` on the time
    axis. Call inside shard_map/pjit. Shapes per device: [B, T_local, H, D].

    Blocks combine by log-sum-exp: each block pair yields (out_b, lse_b) and
    the total is sum_b out_b * exp(lse_b - logsumexp_b lse_b) — the flash
    combination identity. Per-block compute dispatches to the fused Pallas
    kernel (ops/attention_pallas.flash_attention_block) when eligible, so
    long local sequences never materialize [B,H,Tq,Tk] logits on device;
    the naive blockwise path is the fallback (and the CPU/test path).
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    d = q.shape[-1]
    # the kernel needs a STATIC scale; a traced scale falls back to the
    # naive blocks (same guard as dot_product_attention's dispatch seam)
    static_scale = scale is None or isinstance(scale, (int, float))
    scale_f = (float(scale) if isinstance(scale, (int, float))
               else 1.0 / float(d) ** 0.5 if scale is None else scale)
    t_local = q.shape[1]
    f32 = jnp.float32
    if use_flash is None:
        from deeplearning4j_tpu.ops import attention_pallas as _ap
        use_flash = static_scale and _ap.resolve_attention(
            q.shape, q.shape, None, q.dtype) is not None
    elif use_flash and not static_scale:
        raise ValueError("flash ring blocks need a static (python float) "
                         "scale; got a traced value")

    def block(k_blk, v_blk, causal_diag):
        if use_flash:
            from deeplearning4j_tpu.ops.attention_pallas import \
                flash_attention_block
            out, lse = flash_attention_block(q, k_blk, v_blk, causal_diag,
                                             scale_f, interpret)
            return out.astype(f32), lse
        mask = None
        if causal_diag:
            pos = jnp.arange(t_local)
            mask = (pos[:, None] >= pos[None, :])[None, None]
        return _naive_block(q, k_blk, v_blk, scale_f, mask)

    def combine(acc, lse_run, out_b, lse_b):
        lse_new = jnp.logaddexp(lse_run, lse_b)
        w_old = jnp.where(jnp.isfinite(lse_run),
                          jnp.exp(lse_run - lse_new), 0.0)
        w_new = jnp.where(jnp.isfinite(lse_b),
                          jnp.exp(lse_b - lse_new), 0.0)
        acc = (acc * w_old.transpose(0, 2, 1)[..., None]
               + out_b * w_new.transpose(0, 2, 1)[..., None])
        return acc, lse_new

    perm = [(j, (j + 1) % n) for j in range(n)]

    # diagonal block first (the only one needing an intra-block causal mask;
    # the kernel's causal flag must be static, so it sits outside the loop)
    acc, lse_run = block(k, v, causal)
    k_blk = jax.lax.ppermute(k, axis_name, perm)
    v_blk = jax.lax.ppermute(v, axis_name, perm)

    def body(i, carry):
        k_blk, v_blk, acc, lse_run = carry
        src_idx = (my_idx - i) % n  # which shard this block originated from
        out_b, lse_b = block(k_blk, v_blk, False)
        if causal:
            # off-diagonal blocks are all-or-nothing: visible iff src < mine
            lse_b = jnp.where(src_idx < my_idx, lse_b, -jnp.inf)
        acc, lse_run = combine(acc, lse_run, out_b, lse_b)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, acc, lse_run

    _, _, acc, _ = jax.lax.fori_loop(1, n, body, (k_blk, v_blk, acc, lse_run))
    return acc.astype(q.dtype)


def ulysses_self_attention(q, k, v, *, axis_name="seq", causal=False, scale=None):
    """All-to-all head-parallel attention: redistribute [B, T/N, H, D] ->
    [B, T, H/N, D] via all_to_all, compute full attention per head subset,
    redistribute back (DeepSpeed-Ulysses pattern)."""
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention

    # [B, T/N, H, D] -> [B, T, H/N, D]: split heads across devices, gather time
    def scatter_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    q2, k2, v2 = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = dot_product_attention(q2, k2, v2, causal=causal, scale=scale)
    # inverse: [B, T, H/N, D] -> [B, T/N, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def make_ring_attention_fn(mesh: Mesh, *, causal=False, seq_axis="seq",
                           use_flash=None, interpret=False):
    """shard_map-wrapped ring attention: takes full [B,T,H,D] arrays,
    returns full attention output, computed sequence-parallel."""
    from jax import shard_map

    spec = P(None, seq_axis, None, None)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    def fn(q, k, v):
        return ring_self_attention(q, k, v, axis_name=seq_axis, causal=causal,
                                   use_flash=use_flash, interpret=interpret)

    return fn
