"""Production inference engine: continuous batching over AOT-warmed buckets.

The serving half of the TensorFlow system paper (PAPERS.md, arxiv
1605.08695) as this framework's request path, grown from
``parallel/inference.py``'s ParallelInference:

* **Continuous (dynamic) batching** — a single worker drains whatever is
  queued the moment the accelerator frees (no per-slot waits), pads the
  ragged request batch to the nearest registered bucket
  (datasets/iterator.py ``BucketRegistry`` + ``pad_batch`` row padding) and
  runs ONE compiled forward, so arbitrary traffic shapes keep
  ``recompiles_total`` flat.
* **AOT warmup** — at startup every registered bucket (and its per-mesh
  shardings) is lowered and compiled via ``jax.jit(...).lower().compile()``
  (the whole-program AOT stance of the Julia-to-TPU paper, arxiv
  1810.09868), so time-to-first-request is the same histogram bucket as
  steady state: no user request ever pays a compile.
* **SLO + admission control** — per-model p50/p99 latency gauges, a bounded
  admission queue, deadline-aware shedding: a full queue rejects at
  ``submit()`` with :class:`ServingOverloaded`, and requests whose deadline
  passed while queued are shed before wasting a forward on them — the
  "load shedding beats queueing collapse" discipline of serving heavy
  traffic.

Hot swap: the compiled state lives in ONE immutable :class:`BucketedForward`
(params + apply_fn + executables); ``update_model`` builds and warms a fresh
one off to the side, then atomically rebinds — a batch can never mix one
model's params with another's apply_fn, and no queued request is dropped.
"""

from __future__ import annotations

import collections
import json
import os
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry as _tm
from deeplearning4j_tpu.telemetry import tracectx as _tracectx
from deeplearning4j_tpu.datasets.iterator import BucketRegistry, ShapeBuckets
from deeplearning4j_tpu.ops import spmd as _spmd
from deeplearning4j_tpu.serving import metering as _metering
from deeplearning4j_tpu.utils import compile_cache as _cc

#: fill-ratio buckets: eighths of the padded bucket (shared with
#: ParallelInference — "how much of each compiled forward was real work")
FILL_BUCKETS = tuple(i / 8.0 for i in range(1, 9))


class ServingOverloaded(RuntimeError):
    """Request shed by admission control: the bounded queue is full, or the
    request's deadline passed before a worker picked it up. ``reason``
    (``"queue_full"`` / ``"deadline"`` / ...) is machine-readable — the
    fleet wire protocol must not sniff it out of the message text (which
    embeds the free-form model name). A future re-raised fresh chains
    ``from`` the original, so the reason survives on ``__cause__``."""

    reason = None


def _overloaded(msg, reason):
    e = ServingOverloaded(msg)
    e.reason = reason
    return e


def shed_reason(exc):
    """The structured shed reason off a ServingOverloaded — directly, or
    from the original it was re-raised ``from`` (InferenceFuture.get
    raises a fresh copy chained to the one that carries the attr)."""
    for e in (exc, getattr(exc, "__cause__", None)):
        r = getattr(e, "reason", None)
        if r is not None:
            return r
    return None


def _origin_labels(meta):
    """Metric labels for a queue entry's request meta: synthetic traffic
    gets ``origin=...`` series (which every default SLO rule excludes);
    organic traffic keeps the unlabeled series it always had."""
    origin = (meta or {}).get("origin")
    return {"origin": str(origin)} if origin else {}


class ServingShutdown(RuntimeError):
    """Request failed because the engine stopped before serving it."""


class InferenceFuture:
    """Future-like holder for one submitted request (the reference's
    observable-completion contract, hardened): ``done()`` polls, ``get()``
    blocks, and a failed request raises a FRESH exception chained from the
    original (``raise ... from e``) — re-raising one shared instance across
    waiter threads would mutate its traceback concurrently."""

    # __weakref__ so graftsan (analysis/sanitizer.py) can track instances
    # without keeping them alive
    __slots__ = ("_event", "_value", "_error", "latency_s", "trace_id",
                 "__weakref__")

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._error = None
        #: submit-to-result seconds, stamped by the serving worker when the
        #: request completes (None until then / on the direct path)
        self.latency_s = None
        #: causal trace id for this request (telemetry.tracectx), stamped
        #: at submit when tracing is on — `latency_s` decomposes into the
        #: queue-wait/pad/exec/fetch child spans of that trace
        self.trace_id = None

    def done(self):
        """True once a result or error is set (never blocks)."""
        return self._event.is_set()

    def _set(self, v):
        self._value = v
        self._event.set()

    def _set_error(self, e):
        self._error = e
        self._event.set()

    def get(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("inference result not ready")
        err = self._error
        if err is not None:
            try:
                fresh = type(err)(*err.args)
            except Exception:
                fresh = RuntimeError(f"{type(err).__name__}: {err}")
            raise fresh from err
        return self._value


def _example_structs(input_spec, batch, dtype, seq=None):
    """Pytree of ``jax.ShapeDtypeStruct`` for a ``batch``-sized input.

    ``input_spec`` is a per-example shape tuple, or a dict of them (the
    ComputationGraph multi-input form). With ``seq`` (2-D shape buckets),
    the per-example leading axis — the sequence axis of a ``[T, ...]``
    spec — is replaced by the bucketed length.
    """
    def struct(shape):
        shape = tuple(int(d) for d in shape)
        if seq is not None:
            if not shape:
                raise ValueError(
                    "seq-bucketed serving needs a per-example input spec "
                    "with a leading sequence axis (got a scalar spec)")
            shape = (int(seq),) + shape[1:]
        return jax.ShapeDtypeStruct((batch,) + shape, dtype)
    if isinstance(input_spec, dict):
        return {k: struct(v) for k, v in input_spec.items()}
    return struct(input_spec)


def _as_input(x):
    """Host-normalize one request input: a dict is the ComputationGraph
    multi-input pytree (each value coerced per key); anything else —
    ndarray, list, tuple, scalar row — is ONE array. Feeding lists through
    tree_map directly would explode them into per-scalar leaves."""
    if isinstance(x, dict):
        return {k: np.asarray(v) for k, v in x.items()}
    return np.asarray(x)


def _pad_rows_np(tree, target, seq_target=None):
    """Zero-pad every leaf to ``target`` rows along axis 0 (host-side).
    With ``seq_target`` (2-D shape bucket) leaves carrying a sequence
    axis (``ndim >= 2``) are zero-padded along axis 1 as well — the
    exact pad whose real-row/real-step slice is bit-identical to the
    unpadded forward."""
    def pad(a):
        a = np.asarray(a)
        n = a.shape[0]
        if n != target:
            a = np.concatenate(
                [a, np.zeros((target - n,) + a.shape[1:], a.dtype)])
        if seq_target is not None and a.ndim >= 2 \
                and a.shape[1] != seq_target:
            width = [(0, 0)] * a.ndim
            width[1] = (0, seq_target - a.shape[1])
            a = np.pad(a, width)
        return a
    return jax.tree_util.tree_map(pad, tree)


def _slice_seq(tree, padded_seq, real_seq):
    """Undo the seq-axis pad on a forward's outputs: slice axis 1 back to
    ``real_seq`` on every leaf whose axis 1 is the padded length. A
    pooled ``[B, C]`` head (no time axis) passes through untouched unless
    C collides with the padded length — callers that pool to exactly the
    bucket width should size buckets away from their class count."""
    if real_seq == padded_seq:
        return tree
    def cut(a):
        if a.ndim >= 2 and a.shape[1] == padded_seq:
            return a[:, :real_seq]
        return a
    return jax.tree_util.tree_map(cut, tree)


class BucketedForward:
    """One model's compiled, bucketed forward — IMMUTABLE once built, so a
    hot swap is a single reference rebind and a running batch keeps a
    consistent (params, state, apply_fn, executables) snapshot.

    ``warmup(input_spec)`` AOT-compiles every registered bucket; a request
    size with no compiled bucket falls back to a lazy compile, counted into
    ``recompiles_total{site=}`` and the engine's ``aot`` stats — a rising
    ``lazy_compiles`` means the registered buckets don't cover live traffic.

    With a warm ``manifest`` (utils/compile_cache.WarmManifest) the warmup
    DESERIALIZES each bucket's executable instead of compiling it — a warm
    restart performs zero compiles for manifest-covered signatures; any
    key mismatch falls back to a live compile, counted separately
    (``compile_cache_total{event=miss}`` + the ``manifest_misses`` stat).
    A manifest built for a different architecture or backend is dropped at
    construction (``manifest: "mismatch"`` in the aot stats) rather than
    trusted.
    """

    def __init__(self, net, buckets: BucketRegistry, mesh=None,
                 site="serving", dtype=np.float32, manifest=None):
        self.net = net
        self.mesh = mesh
        self.site = site
        self._manifest_state = "none"
        if manifest is not None:
            if manifest.matches(net):
                self._manifest_state = "attached"
            else:
                # counted, surfaced, and refused — executables for another
                # architecture/backend fail at call time with opaque XLA
                # errors, not a clean fallback
                self._manifest_state = "mismatch"
                _cc.count_event("mismatch_drop")
                manifest = None
        self.manifest = manifest
        # dtype=None: serve requests in whatever dtype they arrive
        # (ParallelInference back-compat); a FIXED dtype is what lets the
        # serving engine promise one jit signature per bucket
        self.dtype = None if dtype is None else np.dtype(dtype)
        if mesh is not None:
            # imported here, not at module top: parallel/__init__ pulls in
            # ParallelInference, which is itself rebased on this module
            from deeplearning4j_tpu.parallel import mesh as _mesh
            nd = mesh.shape["data"]
            buckets = buckets.round_up_to_multiple(nd)
            self._repl = _mesh.replicated(mesh)
            data_sh = _mesh.data_sharded(mesh)
            self._place = lambda x: jax.tree_util.tree_map(
                lambda a: jax.device_put(a, data_sh), x)

            def raw(p, s, x):
                # Pallas kernels reached while tracing run per batch shard
                with _spmd.kernel_mesh(mesh):
                    return net.apply_fn(p, s, x, train=False)[0]
            self._jit = jax.jit(raw, in_shardings=(self._repl, self._repl,
                                                   data_sh),
                                out_shardings=data_sh)
        else:
            self._repl = None
            self._place = lambda x: jax.tree_util.tree_map(jnp.asarray, x)

            def raw(p, s, x):
                return net.apply_fn(p, s, x, train=False)[0]
            self._jit = jax.jit(raw)
        # params/state are read LIVE from the net on every call (a net
        # trained in place between requests serves its current weights —
        # and never a donated stale buffer); the mesh replication below is
        # cached by tree identity so steady-state serving pays zero
        # placement dispatches
        self._placed = None       # (params_repl, state_repl)
        self._placed_src = None   # (net.params, net.state) they came from
        self.buckets = buckets
        #: 2-D (batch, seq) grid vs the 1-D batch-only registry — decides
        #: the pad/slice path and the warmup iteration space
        self.seq_aware = isinstance(buckets, ShapeBuckets)
        # mesh executables bake in shardings over a concrete device set:
        # scope the manifest key by mesh shape + device count so a pod
        # topology change can never resurrect a stale executable. The 2-D
        # seq grid folds in too (AFTER any mesh rounding): a grid change
        # must invalidate stale executables, not resurrect shapes the new
        # grid never declares
        kind = ("serving" if mesh is None else
                f"serving:mesh={sorted(mesh.shape.items())}"
                f":ndev={len(jax.devices())}")
        if self.seq_aware:
            kind += f":grid={buckets.signature()}"
        self._manifest_kind = kind
        self._compiled = {}  # input signature -> AOT executable (False=jit)
        self._warmed = False  # has an AOT warmup declared coverage?
        self._lock = threading.Lock()
        self._aot = {"warmed": 0, "lazy_compiles": 0, "hits": 0,
                     "jit_serves": 0, "manifest_hits": 0,
                     "manifest_misses": 0}
        reg = self._reg = _tm.get_registry()
        self._m_fill = reg.histogram(
            "serving_batch_fill_ratio",
            "fraction of each padded device batch holding real examples",
            buckets=FILL_BUCKETS)
        self._m_token_fill = reg.histogram(
            "serving_batch_token_fill_ratio",
            "fraction of each padded (batch, seq) device shape holding "
            "real tokens — the padded-FLOPs waste signal; equals the row "
            "fill on batch-only (1-D) buckets",
            buckets=FILL_BUCKETS)
        self._m_aot = reg.counter(
            "serving_aot_cache_total",
            "compiled-bucket lookups (site=, result=hit/miss); misses pay "
            "a lazy compile and also count into recompiles_total")
        self._c_comp = reg.counter(
            "compiles_total",
            "jit cache entries created, labeled by site "
            "(first-fill warm-up included)")
        self._c_rec = reg.counter(
            "recompiles_total",
            "jit cache misses beyond the first fill, labeled "
            "by site — a rising series is a recompile storm")

    def warmup(self, input_spec):
        """Lower + compile the forward for every registered bucket (and the
        mesh shardings baked into the jit), then CALL each executable once
        on zeros: on jax 0.9.0 the first call of an AOT executable pays a
        one-off dispatch set-up (~10x the median on CPU) that would
        otherwise land on the first request. Returns the wall seconds
        spent — the startup cost that buys a compile-free request path."""
        t0 = time.perf_counter()
        dtype = self.dtype if self.dtype is not None else np.dtype("float32")
        if self.seq_aware:
            # the full (batch, seq) grid: len(batch) * len(seq) executables
            structs = [_example_structs(input_spec, b, dtype, seq=s)
                       for b, s in self.buckets]
        else:
            structs = [_example_structs(input_spec, b, dtype)
                       for b in self.buckets]
        params, state = self._resolve()
        for x_struct in structs:
            ex = self._ensure_compiled(x_struct, warm=True)
            zeros = jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), x_struct)
            jax.block_until_ready(ex(params, state, self._place(zeros)))
        self._warmed = True
        return time.perf_counter() - t0

    @staticmethod
    def _signature(x_struct):
        """Cache key: the full (shape, dtype) signature — two dtypes (or a
        malformed request shape) must not collide on one executable."""
        return tuple((tuple(l.shape), str(l.dtype))
                     for l in jax.tree_util.tree_leaves(x_struct))

    def _ensure_compiled(self, x_struct, warm=False):
        """The AOT executable for this input signature (compiling on miss)."""
        key = self._signature(x_struct)
        with self._lock:
            ex = self._compiled.get(key)
            if ex is not None:
                if not warm:
                    if ex is False:
                        # a jit-fallback entry is NOT an AOT hit: counting
                        # it as one would let "lazy_compiles: 0" read as a
                        # healthy AOT path on a server with no working
                        # executables at all
                        self._aot["jit_serves"] += 1
                    else:
                        self._aot["hits"] += 1
                        self._m_aot.inc(result="hit", site=self.site)
                return ex
            # compile under the lock: two threads racing the same bucket
            # would otherwise both pay (and double-count) the compile.
            # Manifest-first: a warm restart deserializes the executable
            # (src == "manifest", ZERO compiles) and only a key miss pays
            # a live lower+compile. Serialize-back is warmup-only: a LAZY
            # compile runs under this lock on the request path, and
            # serializing there would stall every in-flight request —
            # export_manifest's save-time walk covers lazy executables
            # instead.
            try:
                # fresh: any serving executable may be serialized later
                # (this write-back, or export_manifest's save-time walk)
                with _cc.fresh_compile():
                    ex, src = _cc.aot_compile(
                        self._jit, self.net.params, self.net.state,
                        x_struct, manifest=self.manifest,
                        kind=self._manifest_kind,
                        signature=json.dumps(key), serialize_back=warm)
            except Exception:
                if warm:
                    # startup/update_model warmup must fail FAST: a spec
                    # the model rejects, reported as "warmed", would serve
                    # nothing but errors (or silent lazy compiles)
                    raise
                ex, src = False, "compile"
                # odd request signature: serve via the jit path, which
                # surfaces any real shape error
            self._compiled[key] = ex
            if src == "manifest":
                self._aot["manifest_hits"] += 1
            elif self.manifest is not None:
                self._aot["manifest_misses"] += 1
            if warm:
                self._aot["warmed"] += 1
            else:
                if src != "manifest":
                    # a lazy manifest hit compiles nothing — neither the
                    # lazy counter nor the aot result="miss" series may
                    # move for it, or hit-ratio alerts fire on requests
                    # that never paid a compile
                    self._aot["lazy_compiles"] += 1
                    self._m_aot.inc(result="miss", site=self.site)
                if self._warmed and src != "manifest":
                    # a compile the warmup sweep claimed to cover but
                    # didn't IS a recompile (a shape outside the
                    # registered buckets); cold lazy compiles on an
                    # unwarmed forward are just first-fill
                    self._c_rec.inc(site=self.site)
            if src != "manifest":
                # a manifest-served executable performed no compile —
                # counting it would make a warm restart's "zero compiles"
                # claim unfalsifiable
                self._c_comp.inc(site=self.site)
            return ex

    def aot_stats(self):
        with self._lock:
            return dict(self._aot, manifest=self._manifest_state)

    def export_manifest(self):
        """The warm manifest covering every executable this forward has
        compiled (or restored): the attached manifest — autofilled by
        ``aot_compile`` as live compiles happen — or a fresh one built
        from the compiled buckets. Save it beside the checkpoint and the
        next restart's warmup performs zero compiles."""
        m = self.manifest
        if m is None:
            m = _cc.WarmManifest.for_net(self.net)
        with self._lock:
            compiled = dict(self._compiled)
        for key, ex in compiled.items():
            sig = json.dumps(key)  # the signature aot_compile looks up
            if ex is False or m.has(self._manifest_kind, sig):
                continue  # jit fallback entries have no executable to ship
            m.put(self._manifest_kind, sig, ex)
        return m

    def _resolve(self):
        """The (params, state) to serve THIS call: always the net's live
        trees. With a mesh they are replicated on first use and the
        placement is reused until the net rebinds them (post-fit trees are
        new objects, so the identity check catches every update)."""
        net = self.net
        params, state = net.params, net.state
        if self._repl is None:
            return params, state
        with self._lock:
            if self._placed_src is not None \
                    and self._placed_src[0] is params \
                    and self._placed_src[1] is state:
                return self._placed
            placed = (jax.device_put(params, self._repl),
                      jax.device_put(state, self._repl))
            self._placed_src = (params, state)
            self._placed = placed
            return placed

    def _run(self, x_padded, _phases=None):
        """One compiled forward at the padded signature; the jit path
        serves only a signature whose lazy AOT compile failed (counted as
        ``jit_serves``).
        ``_phases`` (when given) collects measured ``(name, t0, t1, args)``
        windows — AOT-cache lookup, device exec — that the serving worker
        copies into every request trace of the batch."""
        x_struct = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x_padded)
        t0 = time.perf_counter() if _phases is not None else 0.0
        ex = self._ensure_compiled(x_struct)
        if _phases is not None:
            _phases.append(("serving.aot_lookup", t0, time.perf_counter(),
                            {"aot": ex is not False}))
        params, state = self._resolve()
        x_dev = self._place(x_padded)
        t0 = time.perf_counter() if _phases is not None else 0.0
        try:
            if ex is not False:
                return ex(params, state, x_dev)
            return self._jit(params, state, x_dev)
        finally:
            if _phases is not None:
                _phases.append(("serving.device_exec", t0,
                                time.perf_counter(), {}))

    def __call__(self, x, _phases=None, _usage=None):
        """Padded, bucketed forward of a host batch (any leading size):
        chunks by the largest batch bucket, pads each chunk up to its
        nearest registered bucket — BOTH axes under a 2-D grid: rows to
        the batch bucket, the sequence axis to the seq bucket — and
        slices real rows (and real timesteps) back out. ``_phases``
        collects per-phase timing windows for causal tracing (serving
        worker); ``_usage`` (a list) collects one
        ``{rows, seq, batch_bucket, seq_bucket}`` record per device chunk
        so the caller can meter padded vs real tokens exactly."""
        x = _as_input(x)
        first = jax.tree_util.tree_leaves(x)[0]
        n = first.shape[0]
        seq_in = (first.shape[1]
                  if self.seq_aware and first.ndim >= 2 else None)
        if self.seq_aware and seq_in is None:
            raise ValueError(
                f"{self.site}: seq-bucketed serving requires inputs with "
                f"a sequence axis ([rows, steps, ...]); got shape "
                f"{tuple(first.shape)}")
        outs = []
        step = self.buckets.max
        for i in range(0, n, step):
            t0 = time.perf_counter() if _phases is not None else 0.0
            chunk = jax.tree_util.tree_map(
                lambda a: np.asarray(a[i:i + step], dtype=self.dtype), x)
            real = jax.tree_util.tree_leaves(chunk)[0].shape[0]
            if self.seq_aware:
                shape = self.buckets.bucket_for(real, seq_in)
                if shape is None:
                    raise ValueError(
                        f"{self.site}: sequence of {seq_in} steps exceeds "
                        f"the largest registered seq bucket "
                        f"({self.buckets.max_seq}) — sequences cannot be "
                        "chunked")
                bucket, seq_bucket = shape
                fill = real / bucket
                token_fill = (real * seq_in) / (bucket * seq_bucket)
            else:
                bucket, seq_bucket = self.buckets.bucket_for(real), None
                fill = token_fill = real / bucket
            padded = _pad_rows_np(chunk, bucket, seq_target=seq_bucket)
            if _usage is not None:
                _usage.append({"rows": real, "seq": seq_in or 1,
                               "batch_bucket": bucket,
                               "seq_bucket": seq_bucket or 1})
            if _phases is not None:
                _phases.append(("serving.pad", t0, time.perf_counter(),
                                {"bucket": bucket,
                                 "seq_bucket": seq_bucket,
                                 "fill": round(fill, 4),
                                 "token_fill": round(token_fill, 4)}))
            with _tm.span("serving.forward", fill=fill, bucket=bucket,
                          seq_bucket=seq_bucket):
                y = self._run(padded, _phases)
                t0 = time.perf_counter() if _phases is not None else 0.0
                y = jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:real], y)
                if seq_bucket is not None:
                    y = _slice_seq(y, seq_bucket, seq_in)
                if _phases is not None:
                    _phases.append(("serving.fetch", t0,
                                    time.perf_counter(), {}))
            if self._reg.enabled:
                self._m_fill.observe(fill, site=self.site)
                # token fill rides beside row fill: a full batch of short
                # prompts padded to a long seq bucket reads 1.0 rows but
                # near-zero tokens — the waste row fill can't see
                self._m_token_fill.observe(token_fill, site=self.site)
            outs.append(y)
        if len(outs) == 1:
            return outs[0]
        return jax.tree_util.tree_map(
            lambda *parts: np.concatenate(parts), *outs)


class ServingEngine:
    """Continuous-batching inference server for ONE named model.

    ``submit()`` is the async request path (bounded admission queue,
    deadline-aware shedding); ``output()`` is the synchronous direct path
    (same compiled buckets, no queue). ``update_model()`` hot-swaps the
    served model atomically. ``stats()`` is the /serving status payload.
    """

    def __init__(self, net, *, name="default", input_spec=None,
                 buckets=None, seq_buckets=None, max_batch_size=32,
                 mesh=None, max_queue=256,
                 default_deadline_s=None, batch_window_s=0.0,
                 dtype=np.float32, warmup=None, warm_manifest=None):
        self.name = name
        self.mesh = mesh
        self.batch_window_s = batch_window_s
        self.default_deadline_s = default_deadline_s
        self._input_spec = input_spec
        self._dtype = np.dtype(dtype)
        if isinstance(warm_manifest, (str, os.PathLike)):
            # a path: the instant-restart artifact saved beside the
            # checkpoint (save_warm_manifest / utils.serialization bundle).
            # A truncated/non-zip file degrades to a cold warmup — the
            # manifest tier never turns a working server into a crash
            warm_manifest = _cc.WarmManifest.load_lenient(
                warm_manifest, context=f"warm manifest {warm_manifest!r}")
        self._warm_manifest = warm_manifest
        if not isinstance(buckets, ShapeBuckets):
            if buckets is None:
                buckets = BucketRegistry.powers_of_two(max_batch_size)
            elif not isinstance(buckets, BucketRegistry):
                buckets = BucketRegistry(buckets)
            if seq_buckets is not None:
                # the 2-D grid: batch sizes x declared seq edges
                buckets = ShapeBuckets(buckets, seq_buckets)
        self._fwd = BucketedForward(net, buckets, mesh,
                                    site=f"serving:{name}", dtype=dtype,
                                    manifest=warm_manifest)
        self.max_queue = max_queue
        self._pending_rows = 0  # queued EXAMPLES (a batched entry is n)
        self._stop = threading.Event()
        self._thread = None
        self._lock = threading.Lock()
        # seq-aware continuous batching: one deque PER SEQ BUCKET (a
        # single None key on 1-D registries, which keeps the historical
        # one-global-queue behavior bit-for-bit), so requests coalesce
        # within a seq bucket and a short prompt is never padded into a
        # long batch. The condition shares the admission lock: enqueue,
        # drain and the pending-rows bound stay one atomic story.
        self._queues = {}
        self._not_empty = threading.Condition(self._lock)
        self._counts = {"submitted": 0, "served": 0, "shed_queue_full": 0,
                        "shed_deadline": 0, "errors": 0, "swaps": 0}
        self._recent_latencies = []   # bounded ring; /serving works even
        self._warmup_s = None         # with telemetry disabled
        reg = self._reg = _tm.get_registry()
        self._m_depth = reg.gauge(
            "serving_admission_queue_depth",
            "pending requests in the bounded admission queue, per model")
        self._m_latency = reg.histogram(
            "serving_model_latency_seconds",
            "submit-to-result request latency, per model")
        self._m_p50 = reg.gauge(
            "serving_latency_p50_seconds",
            "rolling p50 request latency per model (SLO gauge)")
        self._m_p99 = reg.gauge(
            "serving_latency_p99_seconds",
            "rolling p99 request latency per model (SLO gauge)")
        self._m_requests = reg.counter(
            "serving_model_requests_total",
            "requests by model and outcome "
            "(submitted/served/shed_queue_full/shed_deadline/error)")
        self._m_shed = reg.counter(
            "serving_shed_total",
            "load-shed requests per model and reason "
            "(queue_full / deadline / shutdown)")
        self._m_warm = reg.gauge(
            "serving_warmup_seconds",
            "wall seconds the AOT bucket warmup took at startup, per model")
        self._m_seq_len = reg.histogram(
            "serving_request_seq_len",
            "requested sequence lengths (steps) per model — the demand "
            "distribution seq grid edges derive from "
            "(datasets.iterator.seq_edges_from_demand)",
            buckets=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192))
        if reg.enabled:
            # pre-register every outcome series at zero (the prober
            # idiom): a shed/error series born mid-storm contributes
            # nothing to the SLO delta window it first appears in
            for outcome in ("submitted", "served", "served_direct",
                            "shed_queue_full", "shed_deadline", "error"):
                self._m_requests.inc(0, model=self.name, outcome=outcome)
        if warmup is None:
            warmup = input_spec is not None
        if warmup:
            self.warmup()

    # ---- lifecycle ----

    def warmup(self):
        """AOT-compile every registered bucket now, so no request pays a
        compile. Requires ``input_spec`` (per-example shape, or a dict of
        them for multi-input graphs)."""
        if self._input_spec is None:
            raise ValueError(
                "warmup needs input_spec (per-example feature shape)")
        self._warmup_s = self._fwd.warmup(self._input_spec)
        self._m_warm.set(self._warmup_s, model=self.name)
        return self._warmup_s

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        """Stop the worker and FAIL every request it never picked up with
        :class:`ServingShutdown` — a stopped engine must not leave waiters
        blocked until their own get() timeout. ``submit()`` after stop
        raises immediately."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self._fail_pending()

    def _pop_locked(self, dq):
        """Pop one entry off ``dq`` (holding the lock), releasing its
        admission rows (the submit side charged them)."""
        entry = dq.popleft()
        self._pending_rows -= entry[5] or 1  # graftlint: disable=R6 -- every caller holds self._not_empty (the _locked contract)
        return entry

    def _fail_pending(self):
        """Drain every seq-bucket queue, failing every pending request
        with :class:`ServingShutdown` (stop(), and submit()'s race
        guard)."""
        err = ServingShutdown(
            f"serving engine {self.name!r} stopped before serving this "
            f"request")
        with self._not_empty:
            drained = []
            for dq in self._queues.values():
                while dq:
                    drained.append(self._pop_locked(dq))
        for _, fut, _t, _dl, tctx, _n, _meta in drained:
            if not fut.done():
                fut._set_error(err)
                self._count("errors")
                if self._reg.enabled:
                    self._m_shed.inc(model=self.name, reason="shutdown")
            if tctx is not None:
                # a drained request's trace never completed its causal
                # story — close it without ringing
                tctx.abandon()

    @property
    def running(self):
        return self._thread is not None and self._thread.is_alive()

    @property
    def net(self):
        return self._fwd.net

    @property
    def buckets(self):
        return self._fwd.buckets

    def update_model(self, net, warm=None, *, manifest=None):
        """Hot-swap the served model. The replacement BucketedForward is
        built and (by default, when the engine knows its input spec) AOT-
        warmed OFF the serving path, then atomically rebound — in-flight
        batches finish on the old snapshot, later batches use the new one,
        and no queued request is dropped or errored by the swap. The
        engine's shape grid (1-D or 2-D) is reused as-is: a swap changes
        weights, never shapes. ``manifest``: warm manifest shipped WITH
        the replacement (a bundle's instant-restart artifact); it
        replaces the construction-time one for this and later swaps.
        Callers gating grids should validate it first
        (serving.registry.ModelRegistry.update_model does)."""
        if manifest is not None:
            if isinstance(manifest, (str, os.PathLike)):
                manifest = _cc.WarmManifest.load_lenient(
                    manifest, context=f"warm manifest {manifest!r}")
            if manifest is not None:
                self._warm_manifest = manifest
        fresh = BucketedForward(net, self._fwd.buckets, self.mesh,
                                site=f"serving:{self.name}",
                                dtype=self._dtype,
                                manifest=self._warm_manifest)
        if warm is None:
            warm = self._input_spec is not None
        if warm:
            if self._input_spec is None:
                raise ValueError(
                    "update_model(warm=True) needs input_spec")
            fresh.warmup(self._input_spec)
        self._fwd = fresh
        self._count("swaps")

    def export_warm_manifest(self):
        """The warm manifest covering every executable the served forward
        holds (utils/compile_cache.WarmManifest) — the instant-restart
        artifact. Returns None when nothing is serializable."""
        m = self._fwd.export_manifest()
        return m if len(m) else None

    def save_warm_manifest(self, path):
        """Serialize the served executables to ``path`` (zip). A restart
        that passes ``warm_manifest=path`` then warms up with ZERO
        compiles for every covered bucket. Returns the path, or None when
        no executable was serializable (the backend cannot export — the
        persistent compile cache tier still applies)."""
        m = self.export_warm_manifest()
        if m is None:
            return None
        return m.save(path)

    # ---- request paths ----

    def output(self, x):
        """Synchronous direct inference (no queue): pads/buckets internally,
        same compiled executables as the batched path. Counted into
        ``stats()``/the SLO ring like any served traffic — a server driven
        synchronously must not read as idle on /serving."""
        enabled = self._reg.enabled
        # direct-path trace: same root name as the queued path would be
        # misleading (no queue-wait exists), so it rings separately
        tctx = _tracectx.maybe_start("serving.request_direct",
                                     model=self.name)
        t0 = time.perf_counter()
        try:
            with _tracectx.attach(tctx):
                with _tm.span("serving.output", model=self.name):
                    out = self._fwd(x)  # asarray/bucketing per chunk
        except BaseException:
            if tctx is not None:
                # a failed direct call still completes its causal story
                # (and must not leave the trace open forever)
                tctx.finish(status="error")
            raise
        dt = time.perf_counter() - t0
        _cc.note_first_request()
        if tctx is not None:
            tctx.finish()
        n = jax.tree_util.tree_leaves(out)[0].shape[0]
        self._count("served", n)
        # ctxs: the direct request's trace stamps its latency bucket's
        # exemplar exactly like the queued path's does
        self._note_latencies([dt], ctxs=[tctx])
        if enabled:
            self._m_requests.inc(n, model=self.name, outcome="served_direct")
        return out

    def submit(self, x, deadline_s=None, *, batched=False, tctx=None,
               tenant=None, origin=None):
        """Queue ONE example (or, with ``batched=True``, one MULTI-example
        batch — leading axis = examples); returns ONE
        :class:`InferenceFuture`. A batched future resolves to the stacked
        ``[n, ...]`` outputs of its rows; the rows ride the same
        assemble/pad path as single-example requests, so a client holding
        a natural batch pays one submit and one wait instead of n.

        Admission control bounds queued EXAMPLES: a batched submit of n
        rows spends n of the ``max_queue`` slots, so batching cannot
        smuggle unbounded work past the bound. A full queue sheds the
        request here
        (``ServingOverloaded``, counted per model) rather than letting the
        backlog grow without bound; ``deadline_s`` (or the engine default)
        sheds it later if it goes stale while queued.

        ``tctx``: an already-rooted TraceContext to adopt instead of
        starting a fresh ``serving.request`` — the fleet worker passes
        its remote-parented context here so the device-side spans land
        on the ROUTER's trace (wire-propagated tracing).

        ``tenant`` attributes the request in the usage ledger
        (serving/metering.py); ``origin="probe"`` marks synthetic
        traffic — its counter series carry an ``origin`` label (which
        every default SLO rule excludes) and it never enters the rolling
        p50/p99 latency ring, so organic SLIs stay untouched by canaries
        and health checks. Probe traffic IS still metered: device time
        is device time, and the usage ledger must balance against router
        row accounting exactly.
        """
        if self._stop.is_set():
            raise ServingShutdown(
                f"serving engine {self.name!r} is stopped")
        meta = None
        if tenant is not None or origin is not None:
            meta = {"tenant": tenant, "origin": origin}
        olab = {"origin": str(origin)} if origin else {}
        fut = InferenceFuture()
        # the request's causal trace starts HERE: the root span is the
        # submit->resolve window, and the drain thread attaches via the
        # handoff carried in the queue tuple. Tracing off: None, a branch.
        if tctx is None:
            tctx = _tracectx.maybe_start("serving.request", model=self.name)
        if tctx is not None:
            fut.trace_id = tctx.trace_id
        now = time.perf_counter()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        deadline = None if deadline_s is None else now + deadline_s
        self._count("submitted")
        if self._reg.enabled:
            self._m_requests.inc(model=self.name, outcome="submitted",
                                 **olab)
        try:
            # _as_input, not plain asarray: x may be the dict multi-input
            # form (ComputationGraph) the warmup spec and output() support.
            # The queue carries [n, ...] ROWS for every entry — a single
            # example is wrapped to n=1 and unwrapped at resolve, so the
            # worker has ONE assemble path (concatenate) for both forms.
            item = _as_input(x)
            if batched:
                # every leaf must carry the examples on a SHARED axis 0:
                # a multi-input dict with disagreeing leading dims would
                # be admitted on leaf one's count and detonate inside the
                # drain batch, failing innocent co-batched requests
                dims = {(int(np.shape(l)[0]) if np.ndim(l) else -1)
                        for l in jax.tree_util.tree_leaves(item)}
                if len(dims) != 1 or -1 in dims:
                    raise ValueError(
                        "batched submit requires every input leaf to "
                        "carry the examples on axis 0 with one shared "
                        f"length; got leading dims {sorted(dims)}")
                nrows = dims.pop()
                if nrows == 0:
                    # a 0-row entry would still count as one drain slot
                    # and shift every other request's resolve slice —
                    # refuse it here, where the caller can see why
                    raise ValueError(
                        "batched submit requires at least one example "
                        "(got a 0-row batch)")
                if nrows > self.max_queue:
                    # can NEVER be admitted: shedding it would read as
                    # transient load and send a well-behaved client into
                    # a retry-forever loop — fail it as a sizing error
                    raise ValueError(
                        f"batched submit of {nrows} rows exceeds the "
                        f"admission bound (max_queue={self.max_queue}) "
                        "and could never be admitted — split the batch "
                        "or raise max_queue")
            else:
                nrows = None
                item = jax.tree_util.tree_map(lambda a: a[None], item)
            skey = None
            if self._fwd.seq_aware:
                lead = jax.tree_util.tree_leaves(item)[0]
                if lead.ndim < 2:
                    raise ValueError(
                        f"model {self.name!r} serves 2-D (batch, seq) "
                        "buckets: requests need a sequence axis "
                        "([steps, ...] per example)")
                seq = int(lead.shape[1])
                skey = self._fwd.buckets.seq.bucket_for(seq)
                if skey is None:
                    # a sizing error, not load: shedding it would read as
                    # transient and retry forever (same stance as an
                    # inadmissibly large batched submit)
                    raise ValueError(
                        f"model {self.name!r}: sequence of {seq} steps "
                        f"exceeds the largest registered seq bucket "
                        f"({self._fwd.buckets.max_seq})")
                # the demand distribution grid edges derive from; and the
                # wire/meter view of the seq the engine bucketed
                meta = dict(meta or {}, seq=seq)
                if self._reg.enabled:
                    self._m_seq_len.observe(seq, model=self.name, **olab)
        except BaseException:
            if tctx is not None:
                # malformed input (asarray raised): the request never
                # entered the queue — close its trace, don't leak it
                tctx.abandon()
            raise
        rows = 1 if nrows is None else nrows
        try:
            with self._not_empty:
                # admission bounds queued EXAMPLES, not queue entries: a
                # batched entry spends one slot per row, so batching
                # cannot smuggle unbounded work past the load-shedding
                # contract max_queue documents
                if self._pending_rows + rows > self.max_queue:
                    raise queue.Full
                self._pending_rows += rows
                self._queues.setdefault(
                    skey, collections.deque()).append(
                        (item, fut, now, deadline,
                         None if tctx is None else tctx.handoff(),
                         nrows, meta))
                self._not_empty.notify()
        except queue.Full:
            self._count("shed_queue_full")
            if self._reg.enabled:
                self._m_shed.inc(model=self.name, reason="queue_full",
                                 **olab)
                self._m_requests.inc(model=self.name,
                                     outcome="shed_queue_full", **olab)
            if tctx is not None:
                # shed decision as a child span, then the trace completes
                # (a shed IS an end-to-end outcome worth ringing: the p99
                # story under overload is "we shed you")
                tctx.add_span("serving.shed", now, time.perf_counter(),
                              reason="queue_full")
                tctx.finish(status="shed")
            raise _overloaded(
                f"model {self.name!r}: admission queue full "
                f"({self.max_queue} pending)", "queue_full") from None
        if self._stop.is_set():
            # raced stop(): its drain may already have run, leaving this
            # request in a queue nobody reads — fail it (and any other
            # stragglers) rather than hang the waiter forever
            self._fail_pending()
        if self._reg.enabled:
            self._m_depth.set(self._pending_rows, model=self.name)
        return fut

    # ---- worker ----

    def _drain(self):
        """Continuous-batching drain: block briefly for the FIRST request,
        then take everything already queued in ITS seq bucket (no
        per-slot waits), then — only if the batch still has room and a
        batch window is configured — wait under ONE shared deadline for
        same-bucket stragglers. The worst-case added latency is
        ``batch_window_s`` total, not per empty slot.

        Seq-awareness: a drain batch is drawn from exactly ONE seq-bucket
        queue — the one whose head request has waited longest (arrival
        order across buckets, so no bucket starves) — because co-batching
        requests across seq buckets would pad every short prompt in the
        batch to the longest one's bucket, which is precisely the waste
        the 2-D grid exists to cut. On a 1-D registry there is a single
        ``None`` bucket and this is the historical global-queue drain."""
        cap = self._fwd.buckets.max

        def entry_rows(e):
            # entries carry [n, ...] rows (batched submits n > 1); the cap
            # bounds device-batch ROWS, not queue entries
            return e[5] or 1

        def oldest_key():
            # (found, key): the 1-D path queues under key None, so None
            # itself can't double as the "nothing queued" signal
            live = [k for k, dq in self._queues.items() if dq]
            if not live:
                return False, None
            return True, min(live, key=lambda k: self._queues[k][0][2])

        batch, rows = [], 0
        with self._not_empty:
            found, skey = oldest_key()
            if not found:
                self._not_empty.wait(timeout=0.05)
                found, skey = oldest_key()
                if not found:
                    return []
            dq = self._queues[skey]
            while dq and rows < cap:
                e = self._pop_locked(dq)
                batch.append(e)
                rows += entry_rows(e)
            if rows < cap and self.batch_window_s > 0:
                deadline = time.perf_counter() + self.batch_window_s
                while rows < cap:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0 or \
                            not self._not_empty.wait(timeout=remaining):
                        break
                    # woken: stragglers may have landed in OUR bucket (a
                    # notify for another bucket's arrival just loops)
                    dq = self._queues.get(skey)
                    while dq and rows < cap:
                        e = self._pop_locked(dq)
                        batch.append(e)
                        rows += entry_rows(e)
        return batch

    def _worker(self):
        while not self._stop.is_set():
            batch = self._drain()
            if not batch:
                continue
            now = time.perf_counter()
            live = []
            for item in batch:
                _x, fut, t_sub, deadline, tctx, _n, meta = item
                olab = _origin_labels(meta)
                if deadline is not None and now > deadline:
                    # stale request: shed it instead of spending a forward
                    # on an answer nobody is waiting for (deadline-aware
                    # load shedding)
                    self._count("shed_deadline")
                    if self._reg.enabled:
                        self._m_shed.inc(model=self.name, reason="deadline",
                                         **olab)
                        self._m_requests.inc(model=self.name,
                                             outcome="shed_deadline",
                                             **olab)
                    if tctx is not None:
                        tctx.add_span("serving.queue_wait", t_sub, now)
                        tctx.add_span("serving.shed", now, now,
                                      reason="deadline")
                        tctx.finish(status="shed")
                    # error LAST: a waiter that wakes on the future must
                    # see a COMPLETE trace (the fleet worker ships the
                    # doc back on the wire right after fut.get())
                    fut._set_error(_overloaded(
                        f"model {self.name!r}: deadline exceeded while "
                        f"queued ({1e3 * (now - t_sub):.1f} ms)",
                        "deadline"))
                    continue
                live.append(item)
            if self._reg.enabled:
                self._m_depth.set(self._pending_rows, model=self.name)
            if not live:
                continue
            # a failing forward (bad input shape, mid-swap architecture
            # mismatch) must fail THESE requests, not kill the serving loop
            try:
                # phase windows (assemble/pad/aot/exec/fetch) are measured
                # once per device batch and copied into EVERY member
                # request's trace — the batch is one device-side event
                # shared by N causal stories
                phases = ([] if any(it[4] is not None for it in live)
                          else None)
                n_rows = sum(it[5] or 1 for it in live)
                seq_aware = self._fwd.seq_aware
                with _tm.span("serving.batch", model=self.name,
                              size=n_rows):
                    t_asm = time.perf_counter()
                    # every entry is [n, ...] rows (single submits n=1, so
                    # this is the old stack): concatenate dict inputs too.
                    # A seq-aware drain batch is seq-bucket-uniform, but
                    # real lengths inside the bucket still vary — pad each
                    # entry's seq axis to the batch max (still <= the
                    # bucket BucketedForward pads to) so the concat is
                    # rectangular
                    parts = [b[0] for b in live]
                    batch_seq = None
                    if seq_aware:
                        batch_seq = max((b[6] or {}).get("seq", 1)
                                        for b in live)
                        parts = [
                            _pad_rows_np(p, b[5] or 1, seq_target=batch_seq)
                            for p, b in zip(parts, live)]
                    xs = jax.tree_util.tree_map(
                        lambda *leaves: np.concatenate(leaves), *parts)
                    if phases is not None:
                        phases.append(("serving.assemble", t_asm,
                                       time.perf_counter(),
                                       {"size": n_rows}))
                    t_fwd = time.perf_counter()
                    usage = []
                    ys = self._fwd(xs, _phases=phases,  # one atomic
                                   _usage=usage)        # model snapshot
                done = time.perf_counter()
                device_s = done - t_fwd
                # FLOPs priced at the padded (batch, seq) device shapes
                # the forward ACTUALLY ran — the 2-D grid makes this fall
                # for short prompts; the 1-D path degenerates to the old
                # padded-rows charge (seq bucket 1)
                padded_rows = sum(u["batch_bucket"] for u in usage)
                padded_tokens = sum(u["batch_bucket"] * u["seq_bucket"]
                                    for u in usage)
                flops = _metering.estimate_flops(
                    self._param_count(), padded_rows,
                    padded_tokens=padded_tokens)
                meter = _metering.get_meter()
                _cc.note_first_request()
                lats, ctxs, origins, off = [], [], [], 0
                for x_in, fut, t_sub, _dl, tctx, n, meta in live:
                    width = n or 1
                    real_seq = (meta or {}).get("seq", 1) if seq_aware \
                        else 1
                    # the usage ledger: every served row is attributed
                    # (probe traffic included — device time is device
                    # time), device wall, FLOPs and padded tokens
                    # prorated by rows; seq_tokens are the entry's REAL
                    # tokens, so padded - seq is the waste column
                    meter.record(
                        self.name, rows=width,
                        tokens=sum(int(np.size(l)) for l in
                                   jax.tree_util.tree_leaves(x_in)),
                        seq_tokens=width * real_seq,
                        padded_tokens=padded_tokens * width / n_rows,
                        queue_s=now - t_sub,
                        device_s=device_s * width / n_rows,
                        flops=flops * width / n_rows,
                        tenant=(meta or {}).get("tenant"))
                    y = jax.tree_util.tree_map(
                        lambda a: a[off:off + width], ys)
                    if batch_seq is not None:
                        # back to the entry's REAL length before the row
                        # axis is dropped (axis 1 is still the seq axis)
                        y = _slice_seq(y, batch_seq, real_seq)
                    if n is None:
                        y = jax.tree_util.tree_map(lambda a: a[0], y)
                    off += width
                    lats.append(done - t_sub)
                    ctxs.append(tctx)
                    origins.append((meta or {}).get("origin"))
                    if tctx is not None:
                        tctx.add_span("serving.queue_wait", t_sub, now)
                        for nm, a, b, kw in phases:
                            tctx.add_span(nm, a, b, **kw)
                        tctx.add_span("serving.resolve", done,
                                      time.perf_counter())
                        tctx.finish()
                    fut.latency_s = done - t_sub
                    # resolve LAST: a waiter that wakes here must see a
                    # COMPLETE trace (the fleet worker reads the doc and
                    # ships it back on the wire right after fut.get())
                    fut._set(y)
                self._count("served", n_rows)
                self._note_latencies(lats, outcome="served", ctxs=ctxs,
                                     origins=origins)
            except Exception as e:  # noqa: BLE001 — propagate to waiters
                for _, fut, _t, _dl, tctx, _n, meta in live:
                    if tctx is not None:
                        tctx.finish(status="error")
                    if not fut.done():
                        fut._set_error(e)
                    if self._reg.enabled:
                        self._m_requests.inc(model=self.name,
                                             outcome="error",
                                             **_origin_labels(meta))
                self._count("errors", len(live))

    def _count(self, key, n=1):
        with self._lock:
            self._counts[key] += n

    def _note_latencies(self, lats, outcome=None, ctxs=None, origins=None):
        """Record request latencies into the rolling SLO ring and refresh
        the p50/p99 gauges; with ``outcome`` each also counts into the
        per-model requests counter (the direct path counts its examples
        separately, so it passes None). ``ctxs`` (aligned with ``lats``)
        attaches each request's trace context around its observation, so
        the latency histogram's tail bucket carries that request's
        exemplar — the p99 gauge links to a concrete trace. ``origins``
        (aligned) marks synthetic requests: they observe into origin-
        labeled histogram series but NEVER enter the rolling ring or the
        p50/p99 gauges — a canary storm cannot move an organic SLI."""
        organic = [dt for i, dt in enumerate(lats)
                   if not (origins and origins[i])]
        with self._lock:
            self._recent_latencies.extend(organic)
            del self._recent_latencies[:-512]
            recent = list(self._recent_latencies)
        if self._reg.enabled:
            for i, dt in enumerate(lats):
                olab = ({"origin": str(origins[i])}
                        if origins and origins[i] else {})
                with _tracectx.attach(ctxs[i] if ctxs else None):
                    self._m_latency.observe(dt, model=self.name, **olab)
                if outcome is not None:
                    self._m_requests.inc(model=self.name, outcome=outcome,
                                         **olab)
            if recent:
                self._m_p50.set(float(np.percentile(recent, 50)),
                                model=self.name)
                self._m_p99.set(float(np.percentile(recent, 99)),
                                model=self.name)

    def _param_count(self):
        """Parameter count of the CURRENTLY served forward (recomputed
        cheaply per batch so a hot swap re-prices FLOPs); 0 when the net
        doesn't expose params — metering degrades to zero-FLOPs rows,
        never an error on the serving path."""
        try:
            return sum(int(np.size(l)) for l in
                       jax.tree_util.tree_leaves(self._fwd.net.params))
        except Exception:
            return 0

    # ---- status ----

    def health(self):
        """The per-process health export the fleet wire protocol ships
        (fleet/worker.py ``/health``): the engine's serving stats plus
        the compile-cache events and recompile counters a supervisor
        needs to counter-assert "this worker warm-started and is not
        compiling on the request path" without reaching into the
        process, plus this model's slice of the usage ledger (the
        per-model demand signal fleet /health aggregation folds up)."""
        from deeplearning4j_tpu.telemetry import devices as _devices
        usage = _metering.get_meter().usage()["models"].get(self.name)
        return {"stats": self.stats(),
                "compile_cache_events": _cc.event_counts(),
                "recompiles": _devices.recompile_counts(),
                "usage": usage}

    def latency_percentiles(self):
        """(p50_s, p99_s) over the recent-latency ring, or (None, None)."""
        with self._lock:
            recent = list(self._recent_latencies)
        if not recent:
            return None, None
        return (float(np.percentile(recent, 50)),
                float(np.percentile(recent, 99)))

    def stats(self):
        """The /serving status payload for this model."""
        with self._lock:
            counts = dict(self._counts)
        p50, p99 = self.latency_percentiles()
        fwd = self._fwd
        return {
            "model": self.name,
            "running": self.running,
            # 1-D: flat batch sizes (the historical payload); 2-D: the
            # batch axis, with the seq axis beside it — wire consumers
            # (fleet describe/health) keep reading ints either way
            "buckets": (fwd.buckets.batch.sizes() if fwd.seq_aware
                        else fwd.buckets.sizes()),
            "seq_buckets": (fwd.buckets.seq.sizes() if fwd.seq_aware
                            else None),
            "mesh": None if self.mesh is None else dict(self.mesh.shape),
            "max_queue": self.max_queue,
            "queue_depth": self._pending_rows,  # EXAMPLES, matching
            #                                  the admission bound
            "requests": counts,
            "aot": self._fwd.aot_stats(),
            "warmup_s": self._warmup_s,
            "latency_ms": {
                "p50": None if p50 is None else round(1e3 * p50, 3),
                "p99": None if p99 is None else round(1e3 * p99, 3)},
        }


