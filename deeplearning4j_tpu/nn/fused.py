"""Fused multi-step training: K train steps per dispatch via ``lax.scan``.

The per-step tax of the Python-over-XLA split — one jit dispatch, one
host->device batch copy, one listener round-trip per minibatch — caps the
step rate of fast models well below what the device sustains (SURVEY.md
§7; the prefetch-overlap cure is the tf.data pattern, arxiv 1605.08695).
This module amortizes that tax K-fold:

* ``make_train_steps(net, k)`` wraps the net's single train step in a
  ``jax.lax.scan`` over a stacked super-batch ``[K, B, ...]``: params,
  state, opt_state, the iteration counter and the RNG chain are carried
  ON DEVICE across the K steps, so K steps cost ONE dispatch.
* Ragged shapes never recompile (shape bucketing):
  ``datasets.iterator.SuperBatchIterator`` pads ragged minibatches to the
  bucketed batch shape — validity folded into the loss mask, exact
  because the masked mean divides by the real example count — and pads a
  ragged K-tail with zeroed no-op steps whose updates the scan discards
  via ``step_valid`` (a zero-mask batch still carries regularization
  gradients and updater-state decay, so masking the loss alone would NOT
  be a no-op; the carry must be ``where()``-kept).
* The input pipeline overlaps compute: super-batch stacking +
  ``device_put`` run on ``AsyncDataSetIterator``'s producer thread
  (double-buffered, ``queue_size=2``) while the current fused dispatch
  executes, and the consumed super-batch's buffers are donated back to
  XLA so its HBM is free for the next prefetch.
* Scores and health bundles come back as STACKED ``[K]`` arrays fetched
  one DISPATCH late through the existing ``ScorePipeline`` /
  ``HealthMonitor`` — the same pipelining discipline as the K=1 loop,
  now one fetch per K steps. Listener skew grows accordingly: callbacks
  for the K steps of dispatch *i* fire while dispatch *i+1* runs (see
  PROFILE.md / the StepRecordEmitter note).

Caveat (documented, not hidden): bucketing padding is exact for the loss
and gradients, but batch-statistics layers (BatchNorm in train mode) see
the zero rows in their batch moments on the padded tail step. Datasets
divisible by the batch size — or ``drop_last`` — sidestep this, exactly
as they did for the reference's ragged-batch handling.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.utils import compile_cache as _cc

__all__ = ["make_train_steps", "fit_fused"]


def _silence_unusable_donation(fn):
    """Donated super-batch buffers rarely match an output shape, so XLA
    cannot reuse them and jax warns once per compile; the donation is
    still wanted — it releases the consumed super-batch's device memory
    for the prefetcher's next ``device_put``. Filter exactly that
    warning, keeping ``_cache_size`` visible for recompile telemetry."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn(*args, **kwargs)
    if hasattr(fn, "_cache_size"):
        call._cache_size = fn._cache_size
    return call


class _ManifestDispatch:
    """Manifest-first dispatch for the fused K-step engine: the first call
    at each input signature deserializes the scan executable from the warm
    manifest (zero compiles on a warm restart) or live-compiles through
    ``compile_cache.aot_compile`` — which serializes the result back into
    the manifest, so saving the bundle after a cold run makes the next
    restart warm. A lowering failure or a call the executable rejects
    raises: nothing here falls back to the plain jit."""

    def __init__(self, jitted, manifest, kind):
        self._jit = jitted
        self._manifest = manifest
        self._kind = kind
        self._by_sig = {}  # signature -> executable

    def _cache_size(self):
        # recompile telemetry (devices.note_jit_cache) keys off the inner
        # jit's cache: manifest-served signatures never touch it, so a
        # warm restart reads 0 new compiles — exactly the claim under test
        return self._jit._cache_size()

    def __call__(self, *args):
        # params/state/opt_state (args[:3]) are the net's own device
        # trees, shape-invariant for this engine's lifetime (conf-fixed
        # architecture) — so the per-dispatch key normalizes and hashes
        # the BATCH args only: O(batch leaves), not O(model leaves).
        # asarray gives one signature for Python-int scalars (step0) at
        # lower time AND call time; leaf-wise — xs/ys may be dict
        # pytrees (CG inputs)
        leaves, treedef = jax.tree_util.tree_flatten(args[3:])
        leaves = [jnp.asarray(l) for l in leaves]
        args = args[:3] + tuple(jax.tree_util.tree_unflatten(treedef,
                                                             leaves))
        key = (treedef, tuple((l.shape, l.dtype.name) for l in leaves))
        ex = self._by_sig.get(key)
        if ex is None:
            ex, _src = _cc.aot_compile(self._jit, *args,
                                       manifest=self._manifest,
                                       kind=self._kind,
                                       signature=_cc.signature_of(args))
            self._by_sig[key] = ex
        return ex(*args)


def make_train_steps(net, k, donate=True, jit=True, with_health=False,
                     donate_batch=True, base_step=None):
    """Build the fused K-step engine over ``net``'s single train step:

    ``(params, state, opt_state, xs, ys, step0, rng, masks, step_valid)
    -> (params, state, opt_state, losses[K][, health{key: [K]}])``

    ``xs``/``ys``/``masks`` are stacked ``[K, B, ...]`` super-batches
    (pytrees stack leaf-wise — the ComputationGraph dict form works
    unchanged); ``step_valid`` is the K-tail bucketing vector. The scan
    carries params/state/opt_state, the iteration counter and the RNG
    chain on device, splitting a fresh subkey per step, so the K steps
    run back-to-back inside ONE XLA computation — one dispatch, no
    host round-trips between steps. Works for any net exposing the
    ``make_train_step`` contract (MultiLayerNetwork, ComputationGraph).

    ``base_step`` substitutes the single-step body (same signature as
    ``make_train_step(jit=False)``): ParallelTrainer injects its ZeRO
    step here, so the sharded optimizer state and the explicit
    reduce-scatter/all-gather grad→update boundary are carried through
    all K scanned steps, not just the K=1 path. The fsdp_stream tier
    rides the same seam: its injected step holds an INNER ``lax.scan``
    over the stacked trunk (per-block gather-use-discard), so a K-step
    dispatch is a scan-of-scans whose carry — params, opt state, RNG
    chain — stays in the streamed ``P('data')`` storage layout for all
    K steps; the full param tree never materializes across the whole
    dispatch, not just within one step (parity pinned K=4 == K=1
    replicated in tests/test_zero.py).
    """
    if base_step is not None and with_health:
        # the injected step's contract is the PLAIN 4-tuple; the scan
        # body would otherwise fail mid-trace with an opaque unpack
        # error ("expected 5, got 4") when the watchdog is armed
        raise ValueError(
            "make_train_steps: base_step and with_health=True don't "
            "compose — an injected step returns (params, state, opt, "
            "loss) without the health bundle; build the health variant "
            "into base_step or leave it to net.make_train_step")
    base = (base_step if base_step is not None
            else net.make_train_step(donate=False, jit=False,
                                     with_health=with_health))

    def steps_fn(params, state, opt_state, xs, ys, step0, rng, masks,
                 step_valid):
        def body(carry, inp):
            params, state, opt_state, step, rng = carry
            x, y, m, sv = inp
            rng, sub = jax.random.split(rng)
            out = base(params, state, opt_state, x, y, step, sub, m)
            if with_health:
                new_p, new_s, new_o, loss, hb = out
            else:
                (new_p, new_s, new_o, loss), hb = out, ()
            # K-tail no-op: a zero-mask padded step still has
            # regularization gradients and updater-state decay, so the
            # carry must be where()-kept, not just loss-masked
            keep = functools.partial(
                jax.tree_util.tree_map,
                lambda new, old: jnp.where(sv > 0, new, old))
            carry = (keep(new_p, params), keep(new_s, state),
                     keep(new_o, opt_state),
                     step + (sv > 0).astype(jnp.int32), rng)
            return carry, (loss, hb)

        carry0 = (params, state, opt_state, jnp.asarray(step0, jnp.int32),
                  rng)
        (params, state, opt_state, _, _), (losses, health) = jax.lax.scan(
            body, carry0, (xs, ys, masks, step_valid))
        if with_health:
            return params, state, opt_state, losses, health
        return params, state, opt_state, losses

    if not jit:
        return steps_fn
    manifest = getattr(net, "_warm_manifest", None)
    if manifest is not None:
        # a serializable executable must NOT bake in donation: a
        # deserialized executable loses jax's dispatch-time aliasing
        # guard, so donating a numpy-backed (zero-copy) super-batch or a
        # checkpoint-restored param tree frees memory the CALLER still
        # owns — heap corruption at best. The warm path trades the
        # donation's HBM reuse for restart-safe executables; K=1 and
        # manifest-less fused fits keep the donating engine unchanged.
        if donate:
            # say so: a model fit near device-memory capacity that
            # resumes via a bundle would otherwise see peak HBM grow
            # (params/opt_state no longer reused in-place) with nothing
            # in the logs explaining why
            warnings.warn(
                "warm manifest attached: buffer donation is disabled for "
                "the fused train engine (serialized executables lose "
                "jax's aliasing guard), so peak device memory for "
                "params/opt_state is higher than a manifest-less fit — "
                "detach the manifest (attach_manifest(net, None)) if "
                "memory-bound", stacklevel=2)
        donate = False
    donate_argnums = (0, 1, 2) if donate else ()
    if donate and donate_batch:
        donate_argnums += (3, 4, 7)  # the consumed super-batch
    fused = jax.jit(_scopes.stamped(steps_fn), donate_argnums=donate_argnums)
    if manifest is not None:
        # warm restart: the K-step scan executable deserializes from the
        # checkpoint's manifest (utils/compile_cache) instead of paying
        # the fused retrace+compile — and a live compile serializes back
        # in, so the NEXT restart is warm
        fused = _ManifestDispatch(fused, manifest,
                                  kind=f"fused:k={int(k)}"
                                       f":health={int(bool(with_health))}")
    return _silence_unusable_donation(fused) if donate_argnums else fused


def _steps_fn_for(net, k, with_health):
    """Per-net cache of compiled fused engines, keyed (k, with_health).

    Each entry remembers the manifest it was built against, so
    ``attach_manifest`` after a cold fit rebuilds the engine on the next
    one — REPLACING the stale entry (never accumulating one engine, and
    one manifest's worth of executable blobs, per attach cycle)."""
    cache = getattr(net, "_train_steps_fused", None)
    if cache is None:
        cache = net._train_steps_fused = {}
    manifest = getattr(net, "_warm_manifest", None)
    key = (int(k), bool(with_health))
    entry = cache.get(key)
    if entry is not None and entry[1] is manifest:
        return entry[0]
    fn = make_train_steps(net, k, with_health=with_health)
    cache[key] = (fn, manifest)
    return fn


def fit_fused(net, batch_factory, *, epochs, k, batch_size=None,
              prefetch=True):
    """The fused-dispatch fit loop shared by MultiLayerNetwork and
    ComputationGraph (both expose the same trainer-state contract:
    params/state/opt_state/iteration/epoch/listeners/_rng/score_value).

    ``batch_factory`` is a zero-arg callable returning a fresh
    ``(x, y, mask)`` iterable per epoch (the net's batch generator). The
    stream is bucketed + stacked by ``SuperBatchIterator`` and, with
    ``prefetch``, assembled and ``device_put`` on an
    ``AsyncDataSetIterator`` producer thread while the current dispatch
    runs (double buffering) — the thread is joined in ``finally`` so a
    fit exception never leaves a dangling producer.

    The loop itself lives in ``continuous/driver.py`` (``StepDriver``
    with the fused engine — the resumable round API the continuous
    trainer checkpoints between); this wrapper is the historical entry
    point the ``fit(steps_per_dispatch=K)`` facades call.
    """
    from deeplearning4j_tpu.continuous.driver import StepDriver
    drv = StepDriver(net, batch_factory, k=k, batch_size=batch_size,
                     prefetch=prefetch,
                     fit_span_kw={"net": type(net).__name__, "fused_k": k})
    return drv.run(epochs)
