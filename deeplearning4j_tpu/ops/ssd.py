"""The selective state-space scan of Mamba-2 over a sequence, in its
chunkwise ("state-space duality") form, and one function that chooses the
implementation (``resolve_ssd``).

Per head ``h``, a state ``S`` in ``R^{P x N}`` starting at zero, with a
scalar decay a head and position:

    a_t = exp(dt_t A)                      A < 0, dt_t > 0
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

(Dao & Gu, "Transformers are SSMs", arXiv:2405.21060; the mixer of the
Nemotron-H family.) ``B`` and ``C`` come in ``G`` groups, head ``h``
reading group ``h // (H / G)``. Run token by token that is ``T`` rank-one
updates in sequence. Here the sequence is cut into chunks of ``chunk``
positions: with ``cum`` the running sum of ``dt A`` inside a chunk,

    L_ij    = exp(cum_i - cum_j)  for j <= i, else 0
    y_local = ((C B^T) o L) (dt x)                     inside the chunk
    S_c     = sum_j exp(cum_last - cum_j) dt_j x_j B_j^T    its closing state
    S_prev  = the states before each chunk: a recurrence over the chunks,
              taken in closed form as one product with the [chunks, chunks]
              matrix of decays between chunk ends
    y       = y_local + exp(cum) (S_prev C) + D x

so that no operation walks positions one by one. ``C B^T`` is made once a
GROUP and ``B``, ``C`` are never repeated a head. The running sums, the
decays and the states are float32 (the states' recurrence at the highest
precision); the other products take their operands at the policy's
compute dtype and accumulate in float32.

There is one implementation, ``_chunked``, in ``jax.numpy`` under autodiff:
it runs on every backend and is what the tests hold to the token-by-token
recurrence. ``resolve_ssd`` is where a kernel will be chosen from shape,
dtype and backend once there is one (as ``resolve_gated_delta`` and
``resolve_causal_conv`` choose theirs); callers go through ``ssd``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.utils import dtypes as _dtypes

_HI = jax.lax.Precision.HIGHEST


def resolve_ssd(x_shape, b_shape, dtype):
    """The whole dispatch decision, from what the call shows: the function
    that runs the scan for ``x`` [B, T, H, P] and ``B`` [B, T, G, N] of
    ``dtype``. Today every shape, dtype and backend gets the ``jax.numpy``
    chunkwise form."""
    if x_shape[2] % b_shape[2]:
        raise ValueError(f"{x_shape[2]} heads are no multiple of "
                         f"{b_shape[2]} groups")
    return _chunked


def ssd(x, dt, a, b, c, d, *, chunk=128):
    """``x`` [B, T, H, P]; ``dt`` [B, T, H] (the step, > 0, float32);
    ``a`` [H] (< 0); ``b``, ``c`` [B, T, G, N]; ``d`` [H]. Returns ``y``
    [B, T, H, P] in ``x``'s dtype."""
    with jax.named_scope("ssd_core"):
        return resolve_ssd(x.shape, b.shape, x.dtype)(x, dt, a, b, c, d,
                                                      chunk)


def _chunked(x, dt, a, b, c, d, chunk):
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    cd, ad = _dtypes.compute_dtypes_for(x.dtype)
    q = min(chunk, t)
    nc = -(-t // q)
    pad = nc * q - t
    if pad:
        # a padded position decays nothing and writes nothing (dt 0)
        x, dt, b, c = (jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (u.ndim - 2))
                       for u in (x, dt, b, c))
    # heads before time, chunks apart: [B, nc, G, (R,) Q, ...]
    xc = x.reshape(bsz, nc, q, g, r, p).transpose(0, 1, 3, 4, 2, 5)
    dtc = dt.astype(ad).reshape(bsz, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4).astype(cd)
    cc = c.reshape(bsz, nc, q, g, n).transpose(0, 1, 3, 2, 4).astype(cd)
    cum = jnp.cumsum(dtc * a.astype(ad).reshape(g, r, 1), axis=-1)  # <= 0
    last = cum[..., -1]                                  # [B, nc, G, R]

    # inside a chunk: masked before the exponential, since above the
    # diagonal the difference is positive and may overflow
    seen = jnp.tril(jnp.ones((q, q), bool))              # j <= i
    diff = cum[..., :, None] - cum[..., None, :]         # [B,nc,G,R,i,j]
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
    cb = jnp.einsum("bcgin,bcgjn->bcgij", cc, bc, preferred_element_type=ad)
    dtx = dtc[..., None] * xc.astype(ad)                 # [B,nc,G,R,Q,P]
    local = (cb[:, :, :, None] * decay).astype(cd)
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip", local, dtx.astype(cd),
                   preferred_element_type=ad)

    # each chunk's closing state, then the states the chunks start from
    to_end = jnp.exp(last[..., None] - cum)              # [B,nc,G,R,Q]
    states = jnp.einsum("bcgrjp,bcgjn->bcgrpn",
                        (to_end[..., None] * dtx).astype(cd), bc,
                        preferred_element_type=ad)
    if nc > 1:
        # ends[c] = sum of ``last`` up to chunk c; chunk e's closing state
        # reaches the start of chunk c > e decayed by exp(ends[c-1] - ends[e])
        ends = jnp.cumsum(last, axis=1)
        before = ends - last
        later = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
        between = jnp.exp(jnp.where(
            later, before[:, :, None] - ends[:, None, :], -jnp.inf))
        prev = jnp.einsum("bcegr,begrpn->bcgrpn", between, states,
                          precision=_HI)
        carried = jnp.einsum("bcgin,bcgrpn->bcgrip", cc, prev.astype(cd),
                             preferred_element_type=ad)
        y = y + jnp.exp(cum)[..., None] * carried
    y = y + d.astype(ad).reshape(g, r, 1, 1) * xc.astype(ad)
    # [B, nc, G, R, Q, P] -> [B, T, H, P]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, nc * q, h, p)
    return y[:, :t].astype(x.dtype)
