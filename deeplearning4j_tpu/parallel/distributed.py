"""Multi-node distributed training: the TrainingMaster tier, TPU-native.

Reference analog (SURVEY.md §2.5, §3.3): the Spark layer —
``TrainingMaster`` SPI (dl4j-spark/.../spark/api/TrainingMaster.java),
``ParameterAveragingTrainingMaster`` (impl/paramavg/
ParameterAveragingTrainingMaster.java:73-74,287-293 — workers fit
``batchSizePerWorker x averagingFrequency`` examples, params + updater state
tree-aggregated and averaged per split) and ``SharedTrainingMaster``
(dl4j-spark-parameterserver/.../training/SharedTrainingMaster.java:469 —
threshold-compressed gradient deltas relayed over Aeron UDP by
VoidParameterServer), fronted by the ``SparkDl4jMultiLayer`` facade.

TPU-native re-expression — none of the user-space transport survives:

* The cluster is a ``jax.sharding.Mesh`` whose ``data`` axis enumerates
  workers (devices, possibly spanning hosts via ``initialize_distributed``,
  the jax.distributed multi-host runtime that replaces Spark's driver/executor
  topology). Spark RPC/broadcast/treeAggregate and Aeron UDP both become XLA
  collectives (``psum``/``pmean``) lowered onto ICI/DCN.
* **Parameter averaging** keeps its exact reference semantics — each worker
  runs ``averaging_frequency`` *independent* local SGD steps on its own
  replica (no collectives inside the local loop), then params (and optionally
  updater state, cf. ParallelWrapper.java:338-370) are averaged — expressed
  as a single jitted ``shard_map``: per-worker replicas are pytrees with a
  leading worker axis sharded over ``data``; the local loop is a
  ``lax.scan``; the average is one ``lax.pmean``.
* **Gradient sharing** keeps the reference's threshold-compression semantics
  (EncodingHandler.java:28: extract the ±τ contribution of every element with
  |residual| ≥ τ, carry the un-sent residual, adapt τ toward a target
  message density) but runs it *inside* the jitted step: quantize-with-
  residual is pure XLA elementwise math and the "message" is just the tensor
  handed to ``psum``. The sparse-index/bitmap wire formats (threshold_codec)
  are host-side concerns that only exist off-device — see
  ``EncodedGradientsAccumulator`` for the host-thread variant.
  With ``threshold=None`` the exchange is an exact per-step all-reduce,
  strictly stronger than the reference's lossy async scheme.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry as _tm
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.native import codec as _codec
from deeplearning4j_tpu.native.queue import FancyBlockingQueue
from deeplearning4j_tpu.parallel import mesh as _mesh

tree_map = jax.tree_util.tree_map


# ----------------------------------------------------------------------
# multi-host runtime (replaces Spark cluster + Aeron transport)
# ----------------------------------------------------------------------

#: cached ``str(jax.process_index())`` for metric labels; reset whenever
#: the process joins or leaves a jax.distributed generation (the index is
#: only meaningful within one)
_HOST_LABEL = None


def _host_label():
    global _HOST_LABEL
    if _HOST_LABEL is None:
        try:
            _HOST_LABEL = str(jax.process_index())
        except Exception:  # noqa: BLE001 — backend not up yet
            _HOST_LABEL = "0"
    return _HOST_LABEL


def _init_counter():
    reg = _tm.get_registry()
    c = reg.counter(
        "distributed_init_total",
        "jax.distributed coordinator joins, by outcome (ok = joined, "
        "retried = one connect attempt failed and was retried with "
        "backoff, failed = the retry budget ran out)")
    if reg.enabled:
        # pre-register every outcome series at zero so a retried/failed
        # join that never happens still charts as an explicit 0 and a
        # failure mid-re-form lands in the SLO window it happens in
        for outcome in ("ok", "retried", "failed"):
            c.inc(0, outcome=outcome)
    return c


def _probe_coordinator(address, deadline_s):
    """TCP-probe the coordinator before handing the address to
    jax.distributed: a client whose RegisterTask RPC never answers dies by
    a C++ ``LOG(FATAL)`` that no Python ``except`` can see (re-checked on
    jax 0.9.0: DEADLINE_EXCEEDED, process terminated) — so the common
    failure (coordinator dead, port
    unreachable, generation torn down) is converted HERE into a
    catchable, counted, retryable error. A listener that accepts TCP but
    is not a coordination service still reaches jax's own (bounded)
    ``initialization_timeout`` path."""
    import socket as _socket

    host, _, port = str(address).rpartition(":")
    deadline = time.monotonic() + max(float(deadline_s), 0.2)
    last = None
    while time.monotonic() < deadline:
        try:
            with _socket.create_connection((host or "127.0.0.1", int(port)),
                                           timeout=1.0):
                return
        except OSError as e:
            last = e
            time.sleep(0.2)
    raise RuntimeError(
        f"jax.distributed coordinator {address} unreachable after "
        f"{deadline_s}s: {last}")


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None, local_device_ids=None, *,
                           initialization_timeout=None, connect_retries=0,
                           retry_backoff_s=1.0):
    """Join the jax.distributed multi-host runtime.

    Reference analog: SharedTrainingMaster.java:469's
    ``VoidParameterServer.getInstance().init(...)`` + Spark cluster setup —
    after this, ``jax.devices()`` spans all hosts and every collective in the
    masters below rides ICI/DCN transparently. No-op (returns False) when no
    coordinator is given and the job is single-process.

    Hardened for the elastic tier (ISSUE 15): ``initialization_timeout``
    bounds the coordinator connect (jax's default is 300 s — an elastic
    supervisor re-forming generations wants seconds), and a failed connect
    retries up to ``connect_retries`` times with exponential backoff
    (``retry_backoff_s * 2**attempt``), every outcome counted in
    ``distributed_init_total{outcome=ok|retried|failed}`` so a worker that
    cannot join is a fast, observable failure instead of an uncounted
    5-minute hang. Partial state from a failed attempt is torn down via
    :func:`shutdown_distributed` before the next try.
    """
    if coordinator_address is None and (num_processes is None
                                        or num_processes <= 1):
        return False
    global _HOST_LABEL
    reg = _tm.get_registry()
    counter = _init_counter()
    budget = (None if initialization_timeout is None
              else float(initialization_timeout))
    for attempt in range(int(connect_retries) + 1):
        kw = {}
        if budget is not None:
            kw["initialization_timeout"] = int(budget)
        try:
            if coordinator_address is not None and process_id not in (None,
                                                                      0):
                # process 0 BINDS the coordinator; everyone else probes
                # it first (see _probe_coordinator: the fatal-abort path
                # this converts into a retryable Python error). The probe
                # SPENDS from the same per-attempt budget — what it used
                # waiting for the port comes off jax's own timeout, so
                # one initialization_timeout bounds one whole attempt
                t_probe = time.monotonic()
                _probe_coordinator(coordinator_address,
                                   budget if budget is not None else 10.0)
                if budget is not None:
                    kw["initialization_timeout"] = max(
                        2, int(round(budget
                                     - (time.monotonic() - t_probe))))
            jax.distributed.initialize(coordinator_address=coordinator_address,
                                       num_processes=num_processes,
                                       process_id=process_id,
                                       local_device_ids=local_device_ids,
                                       **kw)
        except Exception:  # noqa: BLE001 — connect/timeout; retry or raise
            shutdown_distributed()  # clear partial client state for a rejoin
            if attempt >= int(connect_retries):
                if reg.enabled:
                    counter.inc(outcome="failed")
                raise
            if reg.enabled:
                counter.inc(outcome="retried")
            time.sleep(float(retry_backoff_s) * (2 ** attempt))
        else:
            if reg.enabled:
                counter.inc(outcome="ok")
            _HOST_LABEL = None  # process_index is generation-scoped
            return True


def shutdown_distributed():
    """Leave the jax.distributed runtime so this process can join a NEW
    generation (the elastic supervisor re-forms at a new world size with
    a fresh coordinator). Returns True when a live runtime was shut down,
    False when there was nothing to leave. Never raises: teardown rides
    failure paths where a half-initialized client is exactly what is
    being cleaned up."""
    global _HOST_LABEL
    _HOST_LABEL = None
    try:
        from jax._src import distributed as _dist
        state = _dist.global_state
        if (getattr(state, "client", None) is None
                and getattr(state, "service", None) is None):
            return False
    except Exception:  # noqa: BLE001 — internals moved; try the public API
        pass
    try:
        jax.distributed.shutdown()
        return True
    except Exception:  # noqa: BLE001 — nothing initialized
        return False


# ----------------------------------------------------------------------
# TrainingMaster SPI
# ----------------------------------------------------------------------

class TrainingMaster:
    """SPI mirroring spark/api/TrainingMaster.java: a strategy that executes
    distributed training of a network over a data source."""

    def execute_training(self, net, data, labels=None, *, epochs=1):
        raise NotImplementedError

    # stats hook (reference: TrainingMaster.setCollectTrainingStats)
    def training_stats(self):
        return dict(self._stats) if hasattr(self, "_stats") else {}

    @staticmethod
    def _round_metrics():
        """(registry, round_hist, rounds_counter) — per-round sync/averaging
        time series shared by every master, split by ``master`` and ``host``
        labels (host = ``jax.process_index()``: without it, multi-process
        rounds collapse every host into one series on ``/metrics``)."""
        reg = _tm.get_registry()
        return (reg,
                reg.histogram(
                    "distributed_round_seconds",
                    "wall time of one distributed round (local steps + "
                    "parameter/gradient exchange), labeled by master and "
                    "host"),
                reg.counter("distributed_rounds_total",
                            "distributed rounds executed, labeled by master "
                            "and host"))

    @staticmethod
    def _worker_health_rollup(wh, master, step):
        """Fetch the stacked per-worker health leaves (ONE batched transfer)
        and fold them into gauges + the numerics watchdog.

        ``wh`` is a dict of [n_workers]-shaped arrays: ``nonfinite`` plus a
        per-worker norm (``grad_norm`` for the per-step master,
        ``param_norm`` for the local-SGD master — grads don't cross its scan
        boundary). A worker whose replica diverged is visible HERE even
        though the pmean would smear it across the fleet one exchange later.
        """
        # the rollup span parents under the round trace when the caller
        # attached one — a slow round decomposes into collective vs rollup
        with _tm.span("distributed.worker_rollup", master=master):
            vals = jax.device_get(wh)
            reg = _tm.get_registry()
            host = _host_label()
            g_nf = reg.gauge("distributed_worker_nonfinite",
                             "1 when this worker's last round saw NaN/Inf, "
                             "labeled by master, host and worker")
            norm_key = "grad_norm" if "grad_norm" in vals else "param_norm"
            g_norm = reg.gauge(f"distributed_worker_{norm_key}",
                               f"per-worker {norm_key.replace('_', ' ')} "
                               "at the last exchange, labeled by master, "
                               "host and worker")
            flags = np.asarray(vals["nonfinite"]).reshape(-1)
            norms = np.asarray(vals[norm_key]).reshape(-1)
            for w in range(len(flags)):
                g_nf.set(1.0 if flags[w] else 0.0, master=master, host=host,
                         worker=str(w))
                g_norm.set(float(norms[w]), master=master, host=host,
                           worker=str(w))
            bad = [int(w) for w in np.nonzero(flags)[0]]
        if bad:
            _health.get_monitor().note_anomaly(
                "distributed_nonfinite", step=step, master=master,
                workers=bad, n_workers=len(flags))
        else:
            _health.get_monitor().note_healthy()


def _stack_worker_dim(tree, n):
    return tree_map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)


# ----------------------------------------------------------------------
# ZeRO exchange primitives (Xu et al. 2020, arxiv 2004.13336): the wire
# form of "shard the weight update across workers" inside a shard_map —
# flatten each leaf, pad to a multiple of the worker count, and
# reduce-scatter so each worker owns exactly its 1/w slice of the mean.
# lax.psum_scatter lowers to a LITERAL `reduce-scatter` HLO op (asserted
# in tests/test_zero.py), where the jit/GSPMD trainers get whatever the
# partitioner picks per backend.
# ----------------------------------------------------------------------

def _flat_pad(a, w):
    v = jnp.ravel(a)
    pad = (-v.size) % w
    return jnp.pad(v, (0, pad)) if pad else v


def _scatter_mean(tree, w, axis="data"):
    """Reduce-scatter each leaf's mean over ``axis``: worker i receives
    flat slice i of mean(tree) — 1/w of the bytes a pmean would hand
    every worker."""
    def leaf(a):
        return jax.lax.psum_scatter(_flat_pad(a, w), axis,
                                    scatter_dimension=0, tiled=True) / w
    return tree_map(leaf, tree)


def _scatter_pmean(tree, w, axis="data"):
    """``lax.pmean`` decomposed into psum_scatter + all_gather (the
    canonical lowering of an all-reduce, made explicit): each worker
    averages only its flat 1/w shard before the gather, so the transient
    exchange buffer is shard-sized — the ZeRO discipline applied to the
    PA master's updater-state averaging. Bit-identical result."""
    def leaf(a):
        s = jax.lax.psum_scatter(_flat_pad(a, w), axis,
                                 scatter_dimension=0, tiled=True) / w
        g = jax.lax.all_gather(s, axis, axis=0, tiled=True)
        return g[:a.size].reshape(a.shape)
    return tree_map(leaf, tree)


def _local_shard(tree, w, axis="data"):
    """Worker i's flat 1/w slice of each (replicated) leaf."""
    idx = jax.lax.axis_index(axis)
    return tree_map(lambda a: _flat_pad(a, w).reshape(w, -1)[idx], tree)


def _gather_like(shard_tree, like_tree, axis="data"):
    """all_gather each flat shard and reshape back to the template's
    leaf shapes (the params leaving the sharded update)."""
    def leaf(s, a):
        g = jax.lax.all_gather(s, axis, axis=0, tiled=True)
        return g[:a.size].reshape(a.shape)
    return tree_map(leaf, shard_tree, like_tree)


def _apply_net_constraints(net, params, it):
    """The constraint half of the net's apply_update, applied to params
    reassembled from a sharded update (the updater half ran on the flat
    shards). Delegates to ``net.apply_constraints`` — ONE definition on
    the net (identity for ComputationGraph), so the sharded and
    replicated update paths can never drift."""
    fn = getattr(net, "apply_constraints", None)
    return params if fn is None else fn(params, it)


def _put(tree, mesh, *specs):
    sh = NamedSharding(mesh, P(*specs))
    return tree_map(lambda a: jax.device_put(a, sh), tree)


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Synchronous parameter averaging over the mesh ``data`` axis.

    Reference: ParameterAveragingTrainingMaster.java:287-293 — per split,
    every worker fits ``averaging_frequency`` minibatches of
    ``batch_size_per_worker`` examples on its own model replica, then the
    driver averages params (+ updater state when ``average_updaters``). The
    tree-aggregation ``aggregationDepth`` knob is subsumed by XLA's reduction
    lowering; ``lax.pmean`` IS the aggregator.
    """

    def __init__(self, mesh: Mesh | None = None, *, batch_size_per_worker=32,
                 averaging_frequency=5, average_updaters=True):
        if averaging_frequency < 1:
            raise ValueError("averaging_frequency must be >= 1")
        self.mesh = mesh if mesh is not None else _mesh.make_mesh()
        self.n_workers = self.mesh.shape["data"]
        self.batch_size_per_worker = int(batch_size_per_worker)
        self.averaging_frequency = int(averaging_frequency)
        self.average_updaters = bool(average_updaters)
        self._split_fn = None
        self._split_fns = {}  # keyed by watchdog flag
        self._net = None
        self._stats = {"splits": 0, "worker_steps": 0}

    # -- jitted split executor ----------------------------------------
    def _build(self, net, with_health):
        base_step = net.make_train_step(jit=False)
        avg_upd = self.average_updaters
        n_workers = self.n_workers

        def split_step(params, state, opt, xs, ys, it0, rngs):
            # inside shard_map: leading worker dim is 1 on every stacked leaf
            sq = lambda t: tree_map(lambda a: a[0], t)
            params, state, opt = sq(params), sq(state), sq(opt)
            xs, ys, rng = xs[0], ys[0], rngs[0]

            def body(carry, xy):
                p, s, o, i, r = carry
                x, y = xy
                r, sub = jax.random.split(r)
                p, s, o, loss = base_step(p, s, o, x, y, it0 + i, sub, None)
                return (p, s, o, i + 1, r), loss

            (p, s, o, _, _), losses = jax.lax.scan(
                body, (params, state, opt, 0, rng), (xs, ys))
            ex = lambda t: tree_map(lambda a: a[None], t)
            if with_health:
                # per-worker rollup BEFORE the average smears divergence
                # across the fleet: which replica went NaN, and how big its
                # params grew over the local steps
                wh = ex({"nonfinite": jnp.any(~jnp.isfinite(losses)),
                         "param_norm": jnp.sqrt(_health.tree_sq_sum(p))})
            p = jax.lax.pmean(p, "data")
            if avg_upd:
                # updater-state averaging sharded (ZeRO discipline):
                # reduce-scatter + all-gather instead of pmean-ing the
                # full opt tree — same result bit-for-bit, but each
                # worker's transient exchange buffer is 1/w of the tree
                # and the HLO carries a literal reduce-scatter
                o = _scatter_pmean(o, n_workers)
            out = (ex(p), ex(s), ex(o),
                   jax.lax.pmean(jnp.mean(losses), "data"))
            return out + (wh,) if with_health else out

        out_specs = (P("data"), P("data"), P("data"), P())
        if with_health:
            out_specs = out_specs + (P("data"),)
        fn = jax.shard_map(
            split_step, mesh=self.mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data"), P("data"),
                      P(), P("data")),
            out_specs=out_specs,
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1, 2))

    def execute_training(self, net, data, labels=None, *, epochs=1):
        """Fit ``net`` (a MultiLayerNetwork) on host arrays (x, y)."""
        # compiled variants cached per watchdog flag (like the trainers'
        # _train_step/_train_step_health pair): toggling the watchdog
        # between calls must not re-pay the shard_map compile
        with_health = _health.get_monitor().active
        if self._net is not net:
            self._split_fns = {}
            self._net = net
        self._split_fn = self._split_fns.get(with_health)
        if self._split_fn is None:
            self._split_fn = self._split_fns[with_health] = \
                self._build(net, with_health)
        self._built_with_health = with_health
        n, w, f, b = (len(data), self.n_workers, self.averaging_frequency,
                      self.batch_size_per_worker)
        split_examples = w * f * b
        if n < split_examples:
            raise ValueError(
                f"need at least {split_examples} examples per split "
                f"(workers {w} x freq {f} x batch {b}), got {n}")

        mesh = self.mesh
        params = _put(_stack_worker_dim(net.params, w), mesh, "data")
        state = _put(_stack_worker_dim(net.state, w), mesh, "data")
        opt = _put(_stack_worker_dim(net.opt_state, w), mesh, "data")

        it0 = int(getattr(net, "iteration", 0))  # resume-aware schedules
        rng = jax.random.PRNGKey(net.conf.seed + 1)
        loss = None
        listeners = list(getattr(net, "listeners", []))
        reg, round_h, rounds_c = self._round_metrics()
        # listener scores resolve ONE ROUND LATE so the host fetch
        # overlaps the next round's device work (graftlint R1; same
        # pattern as the fit loops / HealthMonitor)
        pipe = _tm.ScorePipeline()
        rem = n % split_examples
        for ep in range(epochs):
            # rotate the window each epoch so a ragged tail is not always the
            # same dropped examples; count what this epoch leaves out
            start = (ep * rem) % (rem + 1) if rem else 0
            self._stats["examples_dropped"] = self._stats.get(
                "examples_dropped", 0) + rem
            for s0 in range(start, n - split_examples + 1, split_examples):
                t_round = time.perf_counter()
                # round trace: the averaging round and its per-worker
                # rollup become one causal timeline in the slow-trace ring
                tctx = _tm.tracectx.maybe_start("distributed.round",
                                                master="parameter_averaging")
                with _tm.tracectx.attach(tctx):
                    with _tm.span("distributed.round",
                                  master="parameter_averaging"):
                        xs = np.asarray(data[s0:s0 + split_examples]).reshape(
                            (w, f, b) + data.shape[1:])
                        ys = np.asarray(labels[s0:s0 + split_examples]).reshape(
                            (w, f, b) + labels.shape[1:])
                        rng, *subs = jax.random.split(rng, w + 1)
                        rngs = _put(jnp.stack(subs), mesh, "data")
                        out = self._split_fn(
                            params, state, opt,
                            _put(jnp.asarray(xs), mesh, "data"),
                            _put(jnp.asarray(ys), mesh, "data"),
                            it0, rngs)
                        params, state, opt, loss = out[:4]
                        if reg.enabled:
                            # block inside the span so the round time covers the
                            # collective, not just the async dispatch; disabled,
                            # no extra sync is added to the round loop
                            jax.block_until_ready(loss)  # graftlint: disable=R1 -- deliberate, telemetry-gated: the round span must cover the collective, not just its dispatch
                    if reg.enabled:
                        round_h.observe(time.perf_counter() - t_round,
                                        master="parameter_averaging",
                                        host=_host_label())
                        rounds_c.inc(master="parameter_averaging",
                                     host=_host_label())
                    if self._built_with_health:
                        self._worker_health_rollup(out[4],
                                                   "parameter_averaging",
                                                   it0)
                if tctx is not None:
                    tctx.finish()
                it0 += f
                self._stats["splits"] += 1
                self._stats["worker_steps"] += w * f
                if listeners:  # per-split callback, fetched one round late
                    resolved = pipe.push(loss, it0)
                    if resolved is not None:
                        for l in listeners:
                            l.iteration_done(net, resolved[1], resolved[0])
        tail = pipe.flush()
        if tail is not None:
            for l in listeners:
                l.iteration_done(net, tail[1], tail[0])
        # replicas are identical post-average for params/opt; state (e.g. BN
        # running stats) stays per-worker in the reference too — fold by mean
        first = lambda t: tree_map(lambda a: np.asarray(jax.device_get(a[0])), t)

        def _fold_leaf(a):
            if jnp.issubdtype(a.dtype, jnp.floating):
                return np.asarray(jax.device_get(a)).mean(0)
            return np.asarray(jax.device_get(a[0]))

        fold = lambda t: tree_map(_fold_leaf, t)
        net.params = first(params)
        net.opt_state = first(opt) if self.average_updaters else fold(opt)
        net.state = fold(state)
        net.iteration = it0  # training position survives re-save/resume
        net.epoch = int(getattr(net, "epoch", 0)) + epochs
        return None if loss is None else float(jax.device_get(loss))


class SharedTrainingMaster(TrainingMaster):
    """Per-step gradient sharing over the mesh ``data`` axis.

    Reference: SharedTrainingMaster.java + EncodingHandler.java:28 +
    SilentTrainingDriver — every worker computes a local gradient, adds it to
    a per-worker residual, extracts the ±τ quantized part, and the quantized
    updates are exchanged and applied by everyone. Here the exchange is a
    ``psum`` and the quantization is elementwise XLA math; ``threshold=None``
    degenerates to the exact synchronous all-reduce (the recommended mode on
    ICI — exact and faster than any lossy host-side scheme).

    Adaptive τ (EncodingHandler threshold/minThreshold/thresholdStep
    semantics): if the flagged density exceeds the bitmap break-even (1/16)
    τ doubles; if it falls under 1% τ decays by ``threshold_step`` toward
    ``min_threshold``.
    """

    def __init__(self, mesh: Mesh | None = None, *, batch_size_per_worker=32,
                 threshold=None, min_threshold=1e-5, threshold_step=1e-5,
                 shard_updater_state=True):
        if threshold is not None and threshold <= 0:
            raise ValueError(
                "threshold must be positive; pass threshold=None for exact "
                "(uncompressed) gradient all-reduce")
        self.mesh = mesh if mesh is not None else _mesh.make_mesh()
        self.n_workers = self.mesh.shape["data"]
        self.batch_size_per_worker = int(batch_size_per_worker)
        self.threshold = threshold
        self.min_threshold = float(min_threshold)
        self.threshold_step = float(threshold_step)
        # ZeRO (default): updater state lives SHARDED across workers —
        # each worker stores flat slice i of every opt leaf, the gradient
        # exchange is a reduce-scatter into exactly that slice, the update
        # runs on the shard, and one all-gather rebuilds the params every
        # worker needs for the next forward. Per-worker updater-state
        # bytes drop to 1/w; the exchanged bytes are the all-reduce's own
        # canonical decomposition, so the wire cost is unchanged.
        self.shard_updater_state = bool(shard_updater_state)
        self._step_fn = None
        self._step_fns = {}  # keyed by watchdog flag
        self._net = None
        self._stats = {"steps": 0,
                       "updater_state_sharded": self.shard_updater_state}

    def _build(self, net, with_health):
        compress = self.threshold is not None
        min_t, t_step = self.min_threshold, self.threshold_step
        zero = self.shard_updater_state
        w = self.n_workers

        def step(params, state, opt, resid, tau, x, y, it, rng):
            loss, new_state, grads = net.compute_gradients(
                params, state, x, y, rng=rng)
            if with_health:
                # per-worker rollup BEFORE the psum mixes everyone's
                # gradients: the worker whose batch produced the NaN is
                # identifiable, not just "the fleet went NaN"
                wh = tree_map(
                    lambda a: a[None],
                    {"nonfinite": (_health.any_nonfinite(grads)
                                   | ~jnp.isfinite(loss)),
                     "grad_norm": jnp.sqrt(_health.tree_sq_sum(grads))})
            if compress:
                sq = lambda t: tree_map(lambda a: a[0], t)
                resid = sq(resid)
                resid = tree_map(lambda r, g: r + g, resid, grads)
                flags = tree_map(
                    lambda r: (jnp.abs(r) >= tau).astype(r.dtype), resid)
                q = tree_map(lambda r, f: jnp.sign(r) * tau * f, resid, flags)
                resid = tree_map(lambda r, qq: r - qq, resid, q)
                exchange = q
                # adaptive tau from the global flag density
                nflag = sum(jnp.sum(f) for f in jax.tree_util.tree_leaves(flags))
                ntot = sum(f.size for f in jax.tree_util.tree_leaves(flags))
                density = jax.lax.pmean(nflag / ntot, "data")
                tau = jnp.where(density > 1.0 / 16.0,
                                jnp.minimum(tau * 2.0, 1.0),
                                jnp.where(density < 0.01,
                                          jnp.maximum(tau - t_step, min_t),
                                          tau))
                resid = tree_map(lambda a: a[None], resid)
            else:
                exchange = grads
            if zero:
                # opt enters stacked [w, S]-flat, sharded over 'data':
                # this worker's slice is its WHOLE local copy
                opt_shard = tree_map(lambda a: a[0], opt)
                # reduce-scatter the (possibly quantized) grads straight
                # into the shard this worker updates — no worker ever
                # materializes the full mean-gradient tree
                g_shard = _scatter_mean(exchange, w)
                p_shard = _local_shard(params, w)
                upd, new_opt_shard = net.conf.updater.update(
                    g_shard, opt_shard, p_shard, it)
                new_p_shard = tree_map(jnp.add, p_shard, upd)
                new_params = _gather_like(new_p_shard, params)
                new_params = _apply_net_constraints(net, new_params, it)
                new_opt = tree_map(lambda a: a[None], new_opt_shard)
            else:
                shared = jax.lax.pmean(exchange, "data")
                new_params, new_opt = net.apply_update(params, opt, shared,
                                                       it)
            # BN-style running stats: average float leaves across workers
            new_state = tree_map(
                lambda a: jax.lax.pmean(a, "data")
                if jnp.issubdtype(a.dtype, jnp.inexact) else a, new_state)
            out = (new_params, new_state, new_opt, resid, tau,
                   jax.lax.pmean(loss, "data"))
            return out + (wh,) if with_health else out

        opt_spec = P("data") if zero else P()
        out_specs = (P(), P(), opt_spec, P("data"), P(), P())
        if with_health:
            out_specs = out_specs + (P("data"),)
        fn = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(P(), P(), opt_spec, P("data"), P(), P("data"),
                      P("data"), P(), P()),
            out_specs=out_specs,
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3))

    def execute_training(self, net, data, labels=None, *, epochs=1):
        # compiled variants cached per watchdog flag (cf. the trainers)
        with_health = _health.get_monitor().active
        if self._net is not net:
            self._step_fns = {}
            self._net = net
        self._step_fn = self._step_fns.get(with_health)
        if self._step_fn is None:
            self._step_fn = self._step_fns[with_health] = \
                self._build(net, with_health)
        self._built_with_health = with_health
        mesh, w, b = self.mesh, self.n_workers, self.batch_size_per_worker
        n = len(data)
        step_examples = w * b
        if n < step_examples:
            raise ValueError(f"need >= {step_examples} examples per step")

        repl = lambda t: _put(t, mesh)
        params, state = repl(net.params), repl(net.state)
        if self.shard_updater_state:
            # opt state ships as [w, S]-flat leaves sharded over 'data':
            # worker i's row is its 1/w slice of the (param-shaped) state
            # a replicated checkpoint holds — resume re-slices here, and
            # the fit's end re-assembles, so the wire format round-trips
            # replicated ↔ sharded transparently
            opt = _put(tree_map(
                lambda a: _flat_pad(jnp.asarray(a), w).reshape(w, -1),
                net.opt_state), mesh, "data")
        else:
            opt = repl(net.opt_state)
        from deeplearning4j_tpu.telemetry import devices as _devices
        _devices.note_train_tree_bytes(params=params, opt_state=opt,
                                       site="shared_master")
        resid = _put(_stack_worker_dim(
            tree_map(lambda a: jnp.zeros_like(a), net.params), w), mesh, "data")
        tau = jnp.asarray(self.threshold if self.threshold is not None
                          else 0.0, jnp.float32)
        data_sh = _mesh.data_sharded(mesh)
        rng = jax.random.PRNGKey(net.conf.seed + 2)
        it = int(getattr(net, "iteration", 0))  # resume-aware schedules
        loss = None
        listeners = list(getattr(net, "listeners", []))
        reg, round_h, rounds_c = self._round_metrics()
        pipe = _tm.ScorePipeline()  # listener scores: one step late
        rem = n % step_examples
        for ep in range(epochs):
            start = (ep * rem) % (rem + 1) if rem else 0
            self._stats["examples_dropped"] = self._stats.get(
                "examples_dropped", 0) + rem
            for s0 in range(start, n - step_examples + 1, step_examples):
                t_round = time.perf_counter()
                tctx = _tm.tracectx.maybe_start("distributed.round",
                                                master="shared")
                with _tm.tracectx.attach(tctx):
                    with _tm.span("distributed.round", master="shared"):
                        x = jax.device_put(
                            jnp.asarray(data[s0:s0 + step_examples]), data_sh)
                        y = jax.device_put(
                            jnp.asarray(labels[s0:s0 + step_examples]), data_sh)
                        rng, sub = jax.random.split(rng)
                        out = self._step_fn(
                            params, state, opt, resid, tau, x, y, it, sub)
                        params, state, opt, resid, tau, loss = out[:6]
                        if reg.enabled:
                            jax.block_until_ready(loss)  # graftlint: disable=R1 -- deliberate, telemetry-gated: the round span must cover the all-reduce, not just its dispatch
                    if reg.enabled:
                        round_h.observe(time.perf_counter() - t_round,
                                        master="shared", host=_host_label())
                        rounds_c.inc(master="shared", host=_host_label())
                    if self._built_with_health:
                        self._worker_health_rollup(out[6], "shared", it)
                if tctx is not None:
                    tctx.finish()
                it += 1
                self._stats["steps"] += 1
                if listeners:  # per-step callback, fetched one step late
                    resolved = pipe.push(loss, it)
                    if resolved is not None:
                        for l in listeners:
                            l.iteration_done(net, resolved[1], resolved[0])
        tail = pipe.flush()
        if tail is not None:
            for l in listeners:
                l.iteration_done(net, tail[1], tail[0])
        get = lambda t: tree_map(lambda a: np.asarray(jax.device_get(a)), t)
        net.params, net.state = get(params), get(state)
        if self.shard_updater_state:
            # reassemble the [w, S]-flat shards back into the net's
            # param-shaped opt tree (its pre-fit leaves are the shape
            # template) so checkpoints/save_model see the usual layout
            net.opt_state = tree_map(
                lambda st, t: np.asarray(jax.device_get(st)).reshape(-1)[
                    :np.asarray(t).size].reshape(np.asarray(t).shape),
                opt, net.opt_state)
        else:
            net.opt_state = get(opt)
        net.iteration = it  # training position survives re-save/resume
        net.epoch = int(getattr(net, "epoch", 0)) + epochs
        self._stats["final_threshold"] = float(jax.device_get(tau))
        return None if loss is None else float(jax.device_get(loss))


# ----------------------------------------------------------------------
# facade (reference: SparkDl4jMultiLayer / SparkComputationGraph)
# ----------------------------------------------------------------------

class DistributedMultiLayer:
    """Facade pairing a network with a TrainingMaster, mirroring
    SparkDl4jMultiLayer (impl/multilayer/SparkDl4jMultiLayer.java): the user
    hands over a net + master and calls fit; evaluation/inference run on the
    already-synced local copy."""

    def __init__(self, net, training_master: TrainingMaster):
        self.net = net
        self.master = training_master
        if net.params is None:
            net.init()

    def fit(self, data, labels=None, *, epochs=1):
        if labels is None:  # iterator of (x, y) batches
            xs, ys = zip(*list(data))
            data = np.concatenate([np.asarray(a) for a in xs])
            labels = np.concatenate([np.asarray(a) for a in ys])
        return self.master.execute_training(self.net, np.asarray(data),
                                            np.asarray(labels), epochs=epochs)

    def output(self, x, **kw):
        return self.net.output(x, **kw)

    def score(self, x, y, **kw):
        return self.net.score(x, y, **kw)

    def training_stats(self):
        return self.master.training_stats()


# ----------------------------------------------------------------------
# host-side encoded accumulator (reference: EncodedGradientsAccumulator)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _WorkerSlot:
    consumer: int
    residual: np.ndarray
    schedule: _codec.AdaptiveThreshold


class EncodedGradientsAccumulator:
    """Host-thread gradient exchange with threshold compression.

    Reference: EncodedGradientsAccumulator.java (634 LoC) +
    FancyBlockingQueue.java — N host workers publish threshold-encoded
    updates; every worker consumes every message exactly once (including its
    own, which keeps replicas bit-identical). On TPU this path only matters
    for host-mediated exchange (e.g. across processes without
    jax.distributed); on-mesh training uses the in-jit path above.
    """

    def __init__(self, n_params: int, n_workers: int, *, threshold=1e-3,
                 min_threshold=1e-5, threshold_step=1e-5, shake_frequency=0,
                 capacity=256):
        self.n_params = int(n_params)
        self.queue = FancyBlockingQueue(capacity=capacity)
        self._lock = threading.Lock()
        self._slots: dict[int, _WorkerSlot] = {}
        for w in range(n_workers):
            self._slots[w] = _WorkerSlot(
                consumer=self.queue.register_consumer(),
                residual=np.zeros(self.n_params, np.float32),
                schedule=_codec.AdaptiveThreshold(
                    initial=threshold, min_threshold=min_threshold,
                    step=threshold_step, shake_frequency=shake_frequency))
        self.bytes_published = 0
        self.messages_published = 0

    def store_update(self, worker: int, gradient, timeout=None) -> bool:
        """Encode this worker's gradient (+ carried residual) and publish."""
        slot = self._slots[worker]
        g = np.asarray(jax.device_get(gradient), np.float32).reshape(-1)
        if g.size != self.n_params:
            raise ValueError(f"gradient size {g.size} != {self.n_params}")
        slot.residual += g
        tau = slot.schedule.current()
        msg = _codec.encode(slot.residual, tau)
        slot.schedule.observe(msg)
        ok = self.queue.put(msg, timeout=timeout)
        if ok:
            with self._lock:
                self.bytes_published += msg.nbytes()
                self.messages_published += 1
        else:
            # undelivered: restore the extracted mass into the residual so it
            # is carried (not lost) — encode() subtracted it in place
            _codec.decode(msg, slot.residual)
        return ok

    def apply_updates(self, worker: int, target: np.ndarray) -> int:
        """Drain and decode all pending messages into ``target`` (flat f32).
        Returns the number of messages applied."""
        slot = self._slots[worker]
        applied = 0
        while self.queue.pending(slot.consumer) > 0:
            msg = self.queue.poll(slot.consumer, timeout=1.0)
            if msg is None:
                break
            _codec.decode(msg, target)
            applied += 1
        return applied

    def has_anything(self, worker: int) -> bool:
        return self.queue.pending(self._slots[worker].consumer) > 0

    def close(self):
        self.queue.close()
