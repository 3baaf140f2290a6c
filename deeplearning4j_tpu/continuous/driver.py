"""StepDriver: the resumable dispatch loop shared by every fit path.

Before this module, the repo had THREE copies of the same loop — the K=1
bodies in ``nn/multilayer.py`` and ``nn/graph.py``, the fused K-step body
in ``nn/fused.py``, and the ParallelTrainer pair in
``parallel/data_parallel.py`` — each hand-maintaining the identical
pipelining discipline (one-step-late score fetch, one-late health
bundles, trace handoff, flight records). None of them could STOP: a fit
ran to epoch end or died, which is exactly what a continuous-learning
loop cannot accept (the stream never ends) and what the distributed and
serving tiers could never share.

``StepDriver`` is that loop, once, with an explicit round boundary:

* ``run_round(k_dispatches)`` consumes up to K dispatches from the
  current epoch and RETURNS — params/opt_state/RNG chain are live on the
  net, the score pipeline and health monitor each hold at most one
  pending entry.
* ``sync()`` drains both pipelines (the watchdog's policy may raise
  ``NumericsError`` here, one round late — the continuous trainer's
  rollback trigger).
* ``checkpoint(path)`` = ``sync()`` + ``save_bundle``: one resumable
  unit (checkpoint + opt_state + RNG chain + manifest) between any two
  rounds.
* ``restore(bundle)`` re-arms params/state/opt_state, the RNG chain and
  the iteration counter from a bundle — the compiled step functions are
  keyed on shapes/dtypes, so a rollback re-dispatches with ZERO
  recompiles, and the re-armed RNG chain makes resume bit-exact
  (tests/test_continuous.py pins both).
* ``run(epochs)`` is the classic fit loop: N epochs of
  ``run_round(None)`` with the historical telemetry/exception contract
  (fit span, crash flight dump, fit-end listener hooks) — what the
  ``fit()`` facades now delegate to.

Engines plug the dispatch body: ``_PlainEngine`` (the K=1 single-step
jit), ``_FusedEngine`` (the ``lax.scan`` K-step engine with prefetch),
and the ParallelTrainer pair (``_ShardedPlainEngine`` /
``_ShardedFusedEngine`` — ``instrumented=False`` preserves that loop's
deliberately lighter telemetry). The instrumented body is the audited
moved code of the MLN/CG loops — span names, trace roots, meta schema
and emit ordering are unchanged, so every existing parity/fused/health/
trace test passes against this module without edits.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import telemetry as _tm
from deeplearning4j_tpu.telemetry import devices as _devices
from deeplearning4j_tpu.telemetry import flight as _flight
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.nn import listeners as _listeners
from deeplearning4j_tpu.utils import compile_cache as _cc

__all__ = ["StepDriver", "RoundResult"]


@dataclasses.dataclass
class RoundResult:
    """What one ``run_round`` consumed: ``dispatches`` device dispatches
    covering ``steps`` optimizer steps; ``epoch_done`` marks source
    exhaustion (epoch-end listeners already fired)."""

    dispatches: int = 0
    steps: int = 0
    epoch_done: bool = False


# ---------------------------------------------------------------------------
# engines: what ONE dispatch is
# ---------------------------------------------------------------------------


class _PlainEngine:
    """K=1: one (x, y, mask) minibatch per dispatch through the net's
    cached single-step jit (``net._train_step`` / the health variant —
    the same cache attributes the historical loops used, so a driver fit
    and a legacy fit share compiled executables)."""

    fused = False
    trace_root = "train.step"

    def __init__(self, net, use_health, tbptt_fn=None):
        self.net = net
        self.use_health = use_health
        self.tbptt_fn = tbptt_fn
        if use_health:
            if net._train_step_health is None:
                net._train_step_health = net.make_train_step(
                    with_health=True)
            self.step_fn = net._train_step_health
        else:
            if net._train_step is None:
                net._train_step = net.make_train_step()
            self.step_fn = net._train_step

    def build_source(self, batch_factory):
        return batch_factory()  # fresh (x, y, m) generator per epoch

    def prepare(self, item):
        x, y, m = item
        # leaf-wise: x/y may be dict pytrees (the ComputationGraph form)
        x = jax.tree_util.tree_map(jnp.asarray, x)
        y = jax.tree_util.tree_map(jnp.asarray, y)
        m = jnp.asarray(m) if m is not None else None
        return x, y, m

    def note_input(self, prep):
        # listener convention (activation visualizers, PerformanceListener
        # batch-size inference): the first input array, unsliced
        x = prep[0]
        self.net.last_input = (next(iter(x.values()))
                               if isinstance(x, dict) else x)

    def n_real(self, item):
        return 1

    def dispatch(self, prep):
        net = self.net
        x, y, m = prep
        if self.tbptt_fn is not None and self.tbptt_fn(x, y):
            # TBPTT runs its own chunked on-device scan; the watchdog
            # bundle covers the plain step only
            return net._fit_tbptt(x, y, m), None
        net._rng, step_rng = jax.random.split(net._rng)
        if self.use_health:
            (net.params, net.state, net.opt_state, loss, hb) = self.step_fn(
                net.params, net.state, net.opt_state, x, y, net.iteration,
                step_rng, m)
        else:
            (net.params, net.state, net.opt_state, loss) = self.step_fn(
                net.params, net.state, net.opt_state, x, y, net.iteration,
                step_rng, m)
            hb = None
        net.score_value = loss
        net.iteration += 1
        # cold-start gauge (compile_cache): stamped once, then a dict read
        _cc.note_first_step()
        return loss, hb

    def cache_fn(self):
        return self.step_fn

    def to_host(self):
        return self.net

    def rearm(self, restored):
        _rearm_net(self.net, restored)


class _FusedEngine:
    """K>1: one stacked super-batch per dispatch through the ``lax.scan``
    K-step engine (nn/fused.py), super-batches assembled + device_put on
    the prefetch thread."""

    fused = True
    trace_root = "train.dispatch"

    def __init__(self, net, k, use_health, batch_size=None, prefetch=True):
        from deeplearning4j_tpu.nn import fused as _fused
        self.net = net
        self.k = int(k)
        self.use_health = use_health
        self.batch_size = batch_size
        self.prefetch = prefetch
        self.steps_fn = _fused._steps_fn_for(net, k, use_health)

    def build_source(self, batch_factory):
        from deeplearning4j_tpu.datasets.iterator import (
            AsyncDataSetIterator, SuperBatchIterator)
        sbit = SuperBatchIterator(batch_factory, self.k,
                                  batch_size=self.batch_size)
        return (AsyncDataSetIterator(sbit, queue_size=2,
                                     trace_root="train.dispatch")
                if self.prefetch else sbit)

    def prepare(self, sb):
        # prefetched super-batches are already on device; asarray is then
        # a no-op per leaf
        xs = jax.tree_util.tree_map(jnp.asarray, sb.features)
        ys = jax.tree_util.tree_map(jnp.asarray, sb.labels)
        ms = jnp.asarray(sb.labels_mask)
        sv = jnp.asarray(sb.step_valid)
        return xs, ys, ms, sv

    def note_input(self, prep):
        net = self.net
        if net.listeners:
            # listener convention only — the [0] slice is a device op, so
            # don't dispatch it for nobody
            xs = prep[0]
            first = (next(iter(xs.values())) if isinstance(xs, dict)
                     else xs)
            net.last_input = first[0]

    def n_real(self, item):
        return item.n_steps

    def dispatch(self, prep):
        net = self.net
        xs, ys, ms, sv = prep
        n_real = self._n_real  # the SuperBatch's n_steps, via the driver
        step0 = net.iteration
        net._rng, step_rng = jax.random.split(net._rng)
        if self.use_health:
            (net.params, net.state, net.opt_state, losses, hb) = \
                self.steps_fn(net.params, net.state, net.opt_state,
                              xs, ys, step0, step_rng, ms, sv)
        else:
            (net.params, net.state, net.opt_state, losses) = \
                self.steps_fn(net.params, net.state, net.opt_state,
                              xs, ys, step0, step_rng, ms, sv)
            hb = None
        # last REAL step's loss; device scalar, no sync
        net.score_value = losses[n_real - 1]
        net.iteration += n_real
        _cc.note_first_step()
        return losses, hb

    def cache_fn(self):
        return self.steps_fn

    def to_host(self):
        return self.net

    def rearm(self, restored):
        _rearm_net(self.net, restored)


class _ShardedPlainEngine:
    """ParallelTrainer K=1: one ``trainer.step`` per dispatch. Batches
    whose leading dim is not divisible by the mesh 'data' axis are
    SKIPPED and counted (``trainer.examples_dropped``) — the historical
    array-path behavior."""

    fused = False

    def __init__(self, trainer):
        self.trainer = trainer
        self._data_size = trainer.mesh.shape["data"]

    def build_source(self, batch_factory):
        return batch_factory()

    def dispatch(self, item):
        bx, by, bm = item
        t = self.trainer
        if bx.shape[0] % self._data_size:
            t.examples_dropped += int(bx.shape[0])
            return None  # skipped: not a dispatch
        loss = t.step(bx, by, bm)
        return loss, 1, t.iteration

    def fan(self, score, meta):
        for li in self.trainer.listeners:
            li.iteration_done(self.trainer, meta, score)

    def to_host(self):
        return self.trainer.sync_to_net()

    def rearm(self, restored):
        t = self.trainer
        _rearm_net(t.net, restored)
        t.adopt_net_state()


class _ShardedFusedEngine:
    """ParallelTrainer K>1: sharded fused dispatch, super-batches
    assembled + sharded ``device_put`` on the prefetch thread."""

    fused = True

    def __init__(self, trainer, k):
        self.trainer = trainer
        self.k = int(k)
        self._data_size = trainer.mesh.shape["data"]
        fns = getattr(trainer, "_steps_fns_fused", None)
        if fns is None:
            fns = trainer._steps_fns_fused = {}
        if k not in fns:
            fns[k] = trainer._build_steps_fused(k, trainer.donate)
        self.fused_fn = fns[k]
        self.batch_size = None  # set by the fit wrapper

    def build_source(self, batch_factory):
        from deeplearning4j_tpu.datasets.iterator import (
            AsyncDataSetIterator, SuperBatchIterator)
        from deeplearning4j_tpu.parallel import mesh as _mesh
        sbit = SuperBatchIterator(batch_factory, self.k,
                                  batch_size=self.batch_size)
        # prefetch thread assembles + device_puts the next super-batch
        # ALREADY SHARDED while the current dispatch runs
        return AsyncDataSetIterator(
            sbit, queue_size=2,
            sharding=_mesh.superbatch_sharded(self.trainer.mesh))

    def dispatch(self, sb):
        t = self.trainer
        feats = (next(iter(sb.features.values()))
                 if isinstance(sb.features, dict) else sb.features)
        if feats.shape[1] % self._data_size:
            raise ValueError(
                f"bucketed batch size {feats.shape[1]} not divisible by "
                f"the data-axis size {self._data_size}")
        (t.params, t.state, t.opt_state, losses, t._rng) = self.fused_fn(
            t.params, t.state, t.opt_state, sb.features, sb.labels,
            t.iteration, t._rng, sb.labels_mask, jnp.asarray(sb.step_valid))
        n = sb.n_steps
        t.iteration += n
        t.score_value = losses[n - 1]
        return losses, n, {"iteration": t.iteration, "k": n}

    def fan(self, scores, meta):
        self.trainer._fan_listener_scores(scores, meta)

    def to_host(self):
        return self.trainer.sync_to_net()

    def rearm(self, restored):
        t = self.trainer
        _rearm_net(t.net, restored)
        t.adopt_net_state()


def _rearm_net(net, restored):
    """Copy a restored checkpoint's trees + counters + RNG chain onto the
    LIVE net object (engines and compiled steps hold references to it) —
    restored arrays share the live trees' shapes/dtypes, so the cached
    jitted steps re-dispatch without a single recompile."""
    net.params = restored.params
    net.state = restored.state
    if restored.opt_state is not None:
        net.opt_state = restored.opt_state
    rng = getattr(restored, "_rng", None)
    if rng is not None:
        net._rng = jnp.asarray(rng)
    net.iteration = restored.iteration
    net.epoch = restored.epoch


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


class StepDriver:
    """Resumable dispatch loop over one engine (see module docstring).

    ``batch_factory`` is a zero-arg callable returning a fresh
    ``(x, y, mask)`` iterable per epoch (the historical fit-loop
    contract); fused engines wrap it in ``SuperBatchIterator`` +
    prefetch once and re-enter it on epoch reset.

    ``instrumented=False`` is the ParallelTrainer profile: the score
    pipeline feeds its 3-arg listeners only — the round's boundary spans
    (``fit.round``, ``fit.next``, ``fit.dispatch``, ``fit.sync``) but no
    causal traces, flight records or health monitor.
    """

    def __init__(self, net, batch_factory, *, k=1, batch_size=None,
                 prefetch=True, tbptt_fn=None, engine=None,
                 instrumented=True, fit_span_kw=None):
        self.net = net
        self.batch_factory = batch_factory
        self.k = int(k)
        self.instrumented = instrumented
        hm = self._hm = _health.get_monitor()
        # one read per driver: the watchdog variant of the step is picked
        # (and compiled) at build, not mid-epoch — the fit-entry contract
        self._use_health = instrumented and hm.active
        if engine is None:
            if self.k > 1:
                engine = _FusedEngine(net, self.k, self._use_health,
                                      batch_size=batch_size,
                                      prefetch=prefetch)
            else:
                engine = _PlainEngine(net, self._use_health,
                                      tbptt_fn=tbptt_fn)
        self.engine = engine
        self._fit_span_kw = fit_span_kw or {"net": type(net).__name__}
        self._pipe = _tm.ScorePipeline()
        if instrumented:
            reg, step_h, etl_h, iters_c, score_g = _tm.train_metrics()
            self._reg = reg
            self._frec = _flight.get_recorder()
            self._emitter = _tm.scorepipe.StepRecordEmitter(
                net, step_h, etl_h, iters_c, score_g, self._frec)
            if reg.enabled:
                # open the goodput window with the first instrumented
                # driver: every fit loop gets the wall-clock ledger
                # (compute/etl/idle split on /health) without wiring
                _tm.goodput.get_ledger().ensure_started()
        self._src = None     # persistent fused source (owns a prefetcher)
        self._it = None      # current epoch iterator
        self._tctx = None    # last dispatch's trace (exception cleanup)
        self.profile = None  # armed ProfileSchedule (profile_round)
        self.last_score = None

    # -- epoch plumbing -------------------------------------------------

    def _epoch_source(self):
        if self.engine.fused:
            if self._src is None:
                self._src = self.engine.build_source(self.batch_factory)
            return self._src
        return self.engine.build_source(self.batch_factory)

    def start_epoch(self):
        if self.instrumented:
            # the ParallelTrainer contract has never had on_epoch_start
            for l in self.net.listeners:
                l.on_epoch_start(self.net)
        self._it = iter(self._epoch_source())

    def end_epoch(self):
        # drain the score pipeline at the epoch edge so the last
        # iteration's record/callback lands before on_epoch_end (one sync
        # per epoch, not per step)
        tail = self._pipe.flush()
        if tail is not None:
            self._emit(tail)
        if self.instrumented:
            for l in self.net.listeners:
                l.on_epoch_end(self.net)
            self.net.epoch += 1
        else:
            # lite epoch edges (epoch-end listeners, the empty-epoch
            # checks, the epoch counter) belong to the trainer wrapper,
            # which sees the RoundResult first
            pass
        self._it = None

    def _emit(self, resolved):
        if self.instrumented:
            self._emitter.emit(*resolved)
        else:
            self.engine.fan(*resolved)

    # -- rounds ---------------------------------------------------------

    def profile_round(self, rounds_from_now, logdir, force=None):
        """Arm a windowed ``jax.profiler`` capture around the n-th future
        :meth:`run_round` (``rounds_from_now=1`` is the next one): exactly
        that round runs inside a profiler session writing to ``logdir``.
        Guarded no-op off-TPU (telemetry/profiling.py) — the idle cost is
        one attribute check per round, and the PR 8 span annotations only
        land on the device timeline while the window is open."""
        from deeplearning4j_tpu.telemetry import profiling as _profiling
        if self.profile is None:
            self.profile = _profiling.ProfileSchedule()
        self.profile.arm(rounds_from_now, logdir, force=force)
        return self.profile

    def run_round(self, k_dispatches=None):
        """Consume up to ``k_dispatches`` dispatches from the current
        epoch (starting one if none is open; ``None`` = run to epoch
        end). Returns a :class:`RoundResult`; the score pipeline and
        health monitor may each hold one pending entry afterwards — call
        :meth:`sync` (or :meth:`checkpoint`) to resolve them. An armed
        :meth:`profile_round` schedule brackets exactly its round in a
        profiler window."""
        if self.profile is not None and self.profile.armed:
            with self.profile.window():
                return self._run_round(k_dispatches)
        return self._run_round(k_dispatches)

    def _run_round(self, k_dispatches=None):
        with _tm.span("fit.round"):
            if self._it is None:
                self.start_epoch()
            rr = RoundResult()
            while k_dispatches is None or rr.dispatches < k_dispatches:
                try:
                    # the wait on the iterator: batch assembly, or the
                    # prefetch queue
                    with _tm.span("fit.next"):
                        item = next(self._it)
                except StopIteration:
                    rr.epoch_done = True
                    break
                steps = (self._dispatch_one(item) if self.instrumented
                         else self._dispatch_lite(item))
                if steps == 0:
                    continue  # skipped (lite non-divisible batch)
                rr.dispatches += 1
                rr.steps += steps
            if rr.epoch_done:
                self.end_epoch()
            return rr

    def run(self, epochs):
        """The classic fit loop: N epochs to exhaustion under the
        historical telemetry/exception contract. The ``fit()`` facades
        delegate here."""
        hm = self._hm
        try:
            if self.instrumented:
                with _tm.span("fit", **self._fit_span_kw):
                    for _ in range(epochs):
                        self.run_round(None)
                if self._use_health:
                    # resolve the tail bundle; an anomaly on the last step
                    # still runs the policy (may raise) before fit returns
                    hm.flush()
            else:
                for _ in range(epochs):
                    self.run_round(None)
        except BaseException as e:
            if self._use_health:
                try:
                    hm.flush(apply_policy=False)  # final health into ring
                except Exception:
                    pass
            if self._tctx is not None:
                # the step that crashed never reached the pipeline —
                # close its trace here (idempotent if it did)
                self._tctx.abandon()
            if self.instrumented:
                _flight.crash_dump(e)
            raise
        finally:
            self._pipe.abandon()  # no-op after flush; closes the pending
            #                       step's trace on the exception path
            self.close_source()
            if self.instrumented:
                _listeners.run_fit_end_hooks(self.net)
        return self.net

    # -- dispatch bodies ------------------------------------------------

    def _dispatch_one(self, item):
        """One instrumented dispatch — the audited moved body of the
        MLN/CG fit loops (see nn/multilayer.py history for the span/
        pipeline rationale comments)."""
        eng, net = self.engine, self.net
        reg = self._reg
        # with prefetch the trace originated on the producer thread
        # (assembly + device_put spans already recorded); attach so the
        # etl/step spans below parent under it
        tctx = getattr(item, "_trace_ctx", None)
        if tctx is None:
            tctx = _tm.tracectx.maybe_start(eng.trace_root)
        self._tctx = tctx
        with _tm.tracectx.attach(tctx):
            etl_start = time.perf_counter()
            with _tm.span("fit.etl"):
                prep = eng.prepare(item)
            etl_time = time.perf_counter() - etl_start
            eng.note_input(prep)
            hb = None
            step0 = net.iteration
            rec = reg.enabled  # one read: a mid-iteration enable() must
            #                    not see half-initialized locals
            want_score = rec or bool(net.listeners)
            resolved = meta = None
            n_real = eng.n_real(item)
            span_kw = ({"iteration": step0, "fused_k": n_real}
                       if eng.fused else {"iteration": step0})
            step_start = time.perf_counter()
            with _tm.span("fit.step", **span_kw):
                if eng.fused:
                    eng._n_real = n_real
                with _tm.span("fit.dispatch"):
                    loss, hb = eng.dispatch(prep)
                if want_score:
                    # queue this dispatch, resolve the previous one INSIDE
                    # the span: the blocking fetch overlaps the dispatch
                    # just issued (the one-late ScorePipeline discipline)
                    meta = {"step": step0, "iteration": net.iteration,
                            "etl_time_s": etl_time, "rec": rec,
                            "health": self._use_health,
                            "step_time_s": 0.0,
                            "trace": tctx,
                            "trace_id": (None if tctx is None
                                         else tctx.trace_id)}
                    if eng.fused:
                        meta["k"] = n_real
                    with _tm.span("fit.score_fetch") as fetch:
                        resolved = self._pipe.push(loss, meta)
                    if resolved is not None:
                        prev_t = resolved[1].get("trace")
                        if prev_t is not None:
                            # the one-late fetch of dispatch i-1 happens
                            # HERE, overlapped by dispatch i — record it
                            # in ITS trace, not this one's, from the
                            # span's own pair of clock reads
                            prev_t.add_span("train.score_fetch",
                                            *fetch.interval())
        if meta is None and tctx is not None:
            tctx.finish()  # nobody resolves scores
        if meta is not None:
            meta["step_time_s"] = time.perf_counter() - step_start
        if resolved is not None:
            self._emitter.emit(*resolved)
        elif self._use_health and not want_score:
            # watchdog-only run: flight-record the dispatch shape without
            # fetching a score
            kw = {"fused_k": n_real} if eng.fused else {}
            self._frec.note(step=step0,
                            step_time_s=time.perf_counter() - step_start,
                            etl_time_s=etl_time, **kw)
        if rec:
            _devices.note_jit_cache("fit.step", eng.cache_fn())
        if hb is not None:
            # queues this bundle, resolves the previous one (policy may
            # raise NumericsError one dispatch late)
            if eng.fused:
                self._hm.on_step(hb, step=step0, k=n_real)
            else:
                self._hm.on_step(hb, step=step0)
        self.last_score = net.score_value
        return n_real

    def _dispatch_lite(self, item):
        """One ParallelTrainer dispatch: the ``fit.dispatch`` span (and
        ``fit.next`` / ``fit.round`` from the round loop) but no causal
        traces, flight records or step metrics — the score pipeline feeds
        the trainer's 3-arg listeners one step late, exactly as that loop
        always has."""
        with _tm.span("fit.dispatch"):
            out = self.engine.dispatch(item)
        if out is None:
            return 0  # skipped batch (counted by the engine)
        loss, n, meta = out
        # the representative score is whatever the engine stamped on the
        # trainer (last REAL step's device scalar), not the raw stacked
        # losses the pipeline fans
        self.last_score = self.net.score_value
        if self.net.listeners:
            resolved = self._pipe.push(loss, meta)
            if resolved is not None:
                self.engine.fan(*resolved)
        return n

    # -- resumability ---------------------------------------------------

    def sync(self, apply_policy=True):
        """Resolve everything in flight: the score pipeline's tail record
        is emitted and the health monitor's pending bundle resolves —
        under ``policy='raise'`` a sick round surfaces as
        ``NumericsError`` HERE, one round late (the continuous trainer's
        rollback trigger)."""
        with _tm.span("fit.sync"):
            tail = self._pipe.flush()
            if tail is not None:
                self._emit(tail)
            if self._use_health:
                self._hm.flush(apply_policy=apply_policy)
            if self.instrumented and self._reg.enabled:
                # the routed-experts layers' counts of the last step, and
                # the terms a head keeps apart of its loss: one walk of
                # the state and one fetch for both
                _tm.note_step_state(self.net.state)

    def checkpoint(self, path, *, buckets=None, save_updater=True):
        """``sync()`` then write one resumable ``save_bundle`` unit —
        checkpoint + opt_state + RNG chain (+ attached warm manifest) —
        between rounds. ``restore`` of the result is bit-exact."""
        from deeplearning4j_tpu.utils import serialization as _ser
        self.sync()
        t0 = time.perf_counter()
        # the step loop holds device trees; a checkpoint is a DELIBERATE
        # host sync between rounds, not a hidden per-step one
        net = self.engine.to_host()
        out = _ser.save_bundle(net, path, buckets=buckets,
                               save_updater=save_updater)
        if self.instrumented:
            # checkpoint seconds are wall clock the step loop did not
            # compute in — the goodput ledger's `checkpoint` category
            _tm.goodput.get_ledger().note(
                "checkpoint", time.perf_counter() - t0)
        return out

    def restore(self, path_or_bundle):
        """Roll back / resume: abandon anything in flight, then re-arm
        params/state/opt_state, the RNG chain and the iteration counter
        from a bundle (path, file object, or a loaded ``Bundle``). The
        cached compiled steps re-dispatch with zero recompiles."""
        from deeplearning4j_tpu.utils import serialization as _ser
        self.abandon_pending()
        b = (path_or_bundle if hasattr(path_or_bundle, "net")
             else _ser.load_bundle(path_or_bundle))
        self.engine.rearm(b.net)
        return b

    def abandon_pending(self):
        """Drop in-flight pipeline state without resolving it (rollback /
        exception path): the pending score's trace closes, the pending
        health bundle records without re-running the policy."""
        self._pipe.abandon()
        if self._use_health:
            try:
                self._hm.flush(apply_policy=False)
            except Exception:
                pass
        self._tctx = None

    def close_source(self):
        """Stop the prefetch producer (fused sources); safe to call
        repeatedly. A later ``run_round`` rebuilds the source."""
        if self._src is not None and hasattr(self._src, "close"):
            self._src.close()
        self._src = None
        self._it = None
