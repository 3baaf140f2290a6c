"""Loss-function catalog with per-example masking and label weights.

Reference analog: ND4J ``LossFunctions.LossFunction`` enum + ILossFunction
implementations consumed by dl4j output layers (/root/reference/
deeplearning4j-nn/.../nn/conf/layers/OutputLayer.java lossFn field; score
computed at MultiLayerNetwork.java:2307). All losses here take
``(predictions, labels, mask)`` where predictions are post-activation network
outputs, and return the scalar mean-over-examples score the reference reports,
plus elementwise variants for evaluation plumbing. The one exception is
``softmax_xent``: a softmax head under a cross-entropy hands the networks'
``loss_fn`` its pre-activation output (``from_logits`` says which heads), and
the loss and its gradient come from the logits.

Masking follows the reference's time-series convention: mask has shape
[batch] or [batch, time] and zeroes out padded steps from both score and
gradient (MaskedReductionUtil in the reference).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.utils import dtypes as _dtypes

_EPS = 1e-8
_LOG_EPS = math.log(_EPS)


def _flatten_tail(x):
    """[B, ..., F] -> [B*, F] collapsing any time dims into batch."""
    return x.reshape((-1, x.shape[-1]))


def _apply_mask_and_mean(per_example, mask):
    """per_example: [N] loss per (example, step); mask broadcastable to it."""
    if mask is None:
        return jnp.mean(per_example)
    mask = mask.reshape(-1).astype(per_example.dtype)
    total = jnp.sum(per_example * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return total / denom


def mse(pred, labels, mask=None, weights=None):
    d = (pred - labels) ** 2
    if weights is not None:
        d = d * weights
    per = jnp.mean(_flatten_tail(d), axis=-1)
    return _apply_mask_and_mean(per, mask)


def mae(pred, labels, mask=None, weights=None):
    d = jnp.abs(pred - labels)
    if weights is not None:
        d = d * weights
    per = jnp.mean(_flatten_tail(d), axis=-1)
    return _apply_mask_and_mean(per, mask)


l1 = mae
l2 = mse


def xent(pred, labels, mask=None, weights=None):
    """Binary cross-entropy on sigmoid outputs (reference: LossBinaryXENT)."""
    p = jnp.clip(pred, _EPS, 1.0 - _EPS)
    ce = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log(1.0 - p))
    if weights is not None:
        ce = ce * weights
    per = jnp.sum(_flatten_tail(ce), axis=-1)
    return _apply_mask_and_mean(per, mask)


def mcxent(pred, labels, mask=None, weights=None):
    """Multi-class cross-entropy on softmax outputs (reference: LossMCXENT).

    ``pred`` is a probability distribution (post-softmax), matching the
    reference convention where the output layer applies its activation before
    the loss. Internally uses logs with clipping for stability.
    """
    logp = jnp.log(jnp.clip(pred, _EPS, 1.0))
    ce = -labels * logp
    if weights is not None:
        ce = ce * weights
    per = jnp.sum(_flatten_tail(ce), axis=-1)
    return _apply_mask_and_mean(per, mask)


negativeloglikelihood = mcxent


def sparse_mcxent(pred, labels, mask=None, weights=None):
    """mcxent with integer class labels (TPU-friendly: no one-hot transfer)."""
    logp = jnp.log(jnp.clip(pred, _EPS, 1.0))
    flat = _flatten_tail(logp)
    idx = labels.reshape(-1).astype(jnp.int32)
    per = -jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]
    if weights is not None:
        per = per * weights.reshape(-1)
    return _apply_mask_and_mean(per, mask)


def softmax_xent(logits, labels, mask=None, sparse=False):
    """``mcxent(softmax(logits), labels, mask)``, or ``sparse_mcxent`` of it
    with ``sparse``, computed from the logits with its backward written
    out (reference: LossMCXENT.computeGradient special-cases a softmax
    activation the same way). The same mathematics, the clip of the
    probabilities at 1e-8 included, and no probability tensor: the forward
    keeps the logits and three vectors a row, and the backward is one pass,

        dz = g w_i / denom (exp(z - lse) sum_v(m y) - m y),   m = [p >= 1e-8]

    where autodiff walks log, clip and softmax's vjp at full width. Dense
    labels may be soft: nothing assumes ``sum(y) = 1``."""
    z = _flatten_tail(logits)
    w = None if mask is None else mask.reshape(-1).astype(z.dtype)
    return _softmax_xent(z, labels, w, sparse)


def _clipped_log_probs(z, lse):
    """log(clip(softmax(z), 1e-8, 1)), and which entries the clip leaves
    alone (the gradient through the others is zero)."""
    logp = z - lse[:, None]
    live = logp >= _LOG_EPS
    return jnp.where(live, logp, _LOG_EPS), live


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _softmax_xent(z, labels, w, sparse):
    """z [N, V] logits; labels as the caller holds them, N rows of V dense
    or N integers; w [N] or None."""
    return _softmax_xent_fwd(z, labels, w, sparse)[0]


def _softmax_xent_fwd(z, labels, w, sparse):
    with jax.named_scope("softmax_xent"):
        lse = jax.nn.logsumexp(z, axis=-1)
        if sparse:
            idx = labels.reshape(-1, 1).astype(jnp.int32)
            logp, live = _clipped_log_probs(
                jnp.take_along_axis(z, idx, axis=-1), lse)
            per, live_y = -logp[:, 0], live[:, 0].astype(z.dtype)
        else:
            y = labels.reshape(z.shape)
            logp, live = _clipped_log_probs(z, lse)
            per = -jnp.sum(y * logp, axis=-1)
            live_y = jnp.sum(jnp.where(live, y, 0.0), axis=-1)
        return _apply_mask_and_mean(per, w), (z, labels, w, lse, per, live_y)


def _softmax_xent_bwd(sparse, res, g):
    z, labels, w, lse, per, live_y = res
    with jax.named_scope("softmax_xent"):
        # the masked mean's weights, as _apply_mask_and_mean takes it
        if w is None:
            c, dw = jnp.full_like(per, g / per.shape[0]), None
        else:
            n_live = jnp.sum(w)
            denom = jnp.maximum(n_live, 1.0)
            c = g * w / denom
            dw = g / denom * (per - jnp.where(n_live > 1.0,
                                              jnp.sum(per * w) / denom, 0.0))
        p = jnp.exp(z - lse[:, None])
        if sparse:
            hot = jax.nn.one_hot(labels.reshape(-1).astype(jnp.int32),
                                 z.shape[-1], dtype=z.dtype)
            return (c * live_y)[:, None] * (p - hot), None, dw
        y = labels.reshape(z.shape)
        logp, live = _clipped_log_probs(z, lse)
        dz = c[:, None] * (p * live_y[:, None] - jnp.where(live, y, 0.0))
        return dz, (-c[:, None] * logp).reshape(labels.shape), dw


_softmax_xent.defvjp(_softmax_xent_fwd, _softmax_xent_bwd)


# head_xent walks its rows in blocks of _HEAD_ROWS, the logits of one block
# live at a time, and takes the weight gradient of _HEAD_GROUP blocks as one
# product (ouro-train-t2048 on a v5e, PERF.md section 6, PR 52: a 100 MB
# block of float32 logits lies in VMEM; a product into the [F, V] float32
# accumulator a block is bound by reading and writing the accumulator)
_HEAD_ROWS = 512
_HEAD_GROUP = 4


def head_xent(s, w, y, c):
    """A language-model head and its cross-entropy as one op: features
    ``s`` [M, F], the head's weight ``w`` [F, V], integer labels ``y`` [M]
    and a weight a row ``c`` [M] give

        total = sum_m c_m ce_m,    ce_m = logsumexp(s_m w) - (s_m w)[y_m]

    and ``ce`` [M], which is for reading (it carries no gradient; the
    gradient to ``c`` is ``ce``). The products are ``core.matmul``'s: the
    compute dtype in, the accumulation dtype out.

    The rows are walked ``_HEAD_ROWS`` at a time, so no [M, V] array exists
    in either pass, and under differentiation the gradient is made where
    the logits are: ``c`` is an input, not a cotangent, so each block forms
    ``dz = c (softmax(z) - onehot(y))`` while its logits are live, rounds it
    to the compute dtype as the product's transpose would, and takes
    ``ds = dz w^T``; the ``dz`` of ``_HEAD_GROUP`` blocks is kept for one
    product ``dw += s^T dz`` into ONE accumulator for all rows. The backward
    pass multiplies ``ds``, ``dw`` and ``ce`` by the scalar cotangent and
    makes no logits again. Several uses of one head (the passes of a looped
    model) are one call on their rows laid end to end.

    Both rules' operations carry the scopes of the call site (jax keeps
    them for a ``custom_vjp``'s backward rule), so a caller names the scope
    its readings go by around the call."""
    total, ce = _head_xent(s, w, y.astype(jnp.int32), c)
    return total, lax.stop_gradient(ce)


def _head_blocks(s, y, c):
    """s, y, c as [groups, blocks, rows, ...], padded with rows of weight
    0."""
    m = s.shape[0]
    rows = min(_HEAD_ROWS, m)
    blocks = min(_HEAD_GROUP, -(-m // rows))
    groups = -(-m // (blocks * rows))
    pad = [(0, groups * blocks * rows - m)]
    if pad[0][1]:
        s, y, c = jnp.pad(s, pad + [(0, 0)]), jnp.pad(y, pad), jnp.pad(c, pad)
    lead = (groups, blocks, rows)
    return s.reshape(*lead, -1), y.reshape(lead), c.reshape(lead)


def _head_block_ce(sc, wc, y, accum):
    """One block's cross-entropy [rows], its softmax and its one-hot
    labels [rows, V], from the block's features and the weight, both in
    the compute dtype."""
    z = lax.dot(sc, wc, preferred_element_type=accum)
    hot = lax.broadcasted_iota(jnp.int32, z.shape, 1) == y[:, None]
    top = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z - top)
    norm = jnp.sum(e, axis=-1, keepdims=True)
    ce = (jnp.log(norm) + top)[:, 0] - jnp.sum(jnp.where(hot, z, 0.0), axis=-1)
    return ce, e / norm, hot


@jax.custom_vjp
def _head_xent(s, w, y, c):
    with jax.named_scope("head_xent"):
        cd, ad = _dtypes.compute_dtypes_for(s.dtype)
        wc = w.astype(cd)

        def block(_, xs):
            s_i, y_i = xs
            return None, _head_block_ce(s_i.astype(cd), wc, y_i, ad)[0]

        s_b, y_b, _ = _head_blocks(s, y, c)
        rows = s_b.shape[2]
        _, ce = lax.scan(block, None, (s_b.reshape(-1, rows, s.shape[1]),
                                       y_b.reshape(-1, rows)))
        ce = ce.reshape(-1)[:s.shape[0]]
        return jnp.sum(c * ce), ce


def _head_xent_fwd(s, w, y, c):
    with jax.named_scope("head_xent"):
        cd, ad = _dtypes.compute_dtypes_for(s.dtype)
        wc = w.astype(cd)

        def block(_, xs):
            s_i, y_i, c_i = xs
            ce, p, hot = _head_block_ce(s_i.astype(cd), wc, y_i, ad)
            dz = (c_i[:, None].astype(ad) * (p - hot.astype(ad))).astype(cd)
            ds = lax.dot_general(dz, wc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=ad)
            return None, (dz, ds.astype(s.dtype), ce)

        def group(dw, xs):
            _, (dz, ds, ce) = lax.scan(block, None, xs)
            s_g = xs[0].reshape(-1, s.shape[1]).astype(cd)
            dw = dw + lax.dot_general(s_g, dz.reshape(s_g.shape[0], -1),
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=ad)
            return dw, (ds, ce)

        dw, (ds, ce) = lax.scan(group, jnp.zeros(w.shape, ad),
                                _head_blocks(s, y, c))
        m = s.shape[0]
        ds, ce = ds.reshape(-1, s.shape[1])[:m], ce.reshape(-1)[:m]
        return (jnp.sum(c * ce), ce), (ds, dw.astype(w.dtype), ce)


def _head_xent_bwd(res, g):
    ds, dw, ce = res
    with jax.named_scope("head_xent"):
        g = g[0]
        return (g.astype(ds.dtype) * ds, g.astype(dw.dtype) * dw, None,
                g.astype(ce.dtype) * ce)


_head_xent.defvjp(_head_xent_fwd, _head_xent_bwd)


def hinge(pred, labels, mask=None, weights=None):
    """labels in {-1, +1} (reference: LossHinge)."""
    h = jnp.maximum(0.0, 1.0 - labels * pred)
    if weights is not None:
        h = h * weights
    per = jnp.sum(_flatten_tail(h), axis=-1)
    return _apply_mask_and_mean(per, mask)


def squared_hinge(pred, labels, mask=None, weights=None):
    h = jnp.maximum(0.0, 1.0 - labels * pred) ** 2
    if weights is not None:
        h = h * weights
    per = jnp.sum(_flatten_tail(h), axis=-1)
    return _apply_mask_and_mean(per, mask)


def kl_divergence(pred, labels, mask=None, weights=None):
    p = jnp.clip(pred, _EPS, 1.0)
    q = jnp.clip(labels, _EPS, 1.0)
    kl = labels * (jnp.log(q) - jnp.log(p))
    if weights is not None:
        kl = kl * weights
    per = jnp.sum(_flatten_tail(kl), axis=-1)
    return _apply_mask_and_mean(per, mask)


def cosine_proximity(pred, labels, mask=None, weights=None):
    pf, lf = _flatten_tail(pred), _flatten_tail(labels)
    pn = pf / (jnp.linalg.norm(pf, axis=-1, keepdims=True) + _EPS)
    ln = lf / (jnp.linalg.norm(lf, axis=-1, keepdims=True) + _EPS)
    per = -jnp.sum(pn * ln, axis=-1)
    return _apply_mask_and_mean(per, mask)


def poisson(pred, labels, mask=None, weights=None):
    p = jnp.clip(pred, _EPS, None)
    loss = p - labels * jnp.log(p)
    if weights is not None:
        loss = loss * weights
    per = jnp.sum(_flatten_tail(loss), axis=-1)
    return _apply_mask_and_mean(per, mask)


def mean_squared_log_error(pred, labels, mask=None, weights=None):
    d = (jnp.log1p(jnp.clip(pred, 0, None)) - jnp.log1p(jnp.clip(labels, 0, None))) ** 2
    if weights is not None:
        d = d * weights
    per = jnp.mean(_flatten_tail(d), axis=-1)
    return _apply_mask_and_mean(per, mask)


def mean_absolute_percentage_error(pred, labels, mask=None, weights=None):
    d = 100.0 * jnp.abs((labels - pred) / jnp.clip(jnp.abs(labels), _EPS, None))
    if weights is not None:
        d = d * weights
    per = jnp.mean(_flatten_tail(d), axis=-1)
    return _apply_mask_and_mean(per, mask)


_CATALOG = {
    "mse": mse,
    "mae": mae,
    "l1": l1,
    "l2": l2,
    "xent": xent,
    "mcxent": mcxent,
    "sparse_mcxent": sparse_mcxent,
    "negativeloglikelihood": negativeloglikelihood,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "kl_divergence": kl_divergence,
    "cosine_proximity": cosine_proximity,
    "poisson": poisson,
    "mean_squared_log_error": mean_squared_log_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
}


def get(name):
    if callable(name):
        return name
    try:
        return _CATALOG[name.lower()]
    except KeyError:
        raise KeyError(f"Unknown loss {name!r}. Known: {sorted(_CATALOG)}") from None


def names():
    return sorted(_CATALOG)


def from_logits(layer):
    """``layer``'s loss as a function ``(logits, labels, mask)`` of its
    pre-activation output, where its configuration says softmax under a
    cross-entropy over the classes and it can hand its logits out
    (``pre_output``); None for every other head, which keeps
    ``compute_loss`` on its activations."""
    if not (hasattr(layer, "pre_output") and hasattr(layer, "compute_loss")) \
            or _act.get(layer.activation) is not _act.softmax:
        return None
    loss = get(layer.loss)
    if loss is mcxent:
        return softmax_xent
    if loss is sparse_mcxent:
        return functools.partial(softmax_xent, sparse=True)
    return None
