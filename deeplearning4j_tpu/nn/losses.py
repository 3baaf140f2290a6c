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

from deeplearning4j_tpu.nn import activations as _act

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
