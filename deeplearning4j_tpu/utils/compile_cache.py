"""Compile-artifact cache tier: persistent XLA cache + warm AOT manifests.

Every process start pays the full retrace+compile bill — ``serve`` re-runs
``jit(...).lower().compile()`` per registered bucket, ``train`` recompiles
the fused K-step scan, and a flight-recorder crash→resume restarts from a
stone-cold jit cache. At fleet scale (autoscaling, hot-swap deploys) that
is the dominant time-to-first-request cost. Compiled executables are
artifacts to persist and ship, not side effects to re-derive (the
whole-program AOT stance of the Julia-to-TPU paper, PAPERS.md arxiv
1810.09868; the deployment story of the TensorFlow whitepaper, arxiv
1603.04467). Two complementary tiers:

* **Persistent compilation cache** — :func:`enable_persistent_cache`
  turns on jax's on-disk compile cache with the min-compile-time/
  min-entry-size thresholds opened up so even small executables persist.
  One rule for where it lives: ``$JAX_COMPILATION_CACHE_DIR`` when that is
  set (no directory is set in code then), otherwise ``<checkout>/.jax_cache``
  — a fixed path, because the path is part of the cache key. Called
  unconditionally by the CLI verbs, ``fleet/worker.py``, ``bench.py`` and
  ``chip_smoke.py`` before anything compiles.
* **Warm manifest** — :class:`WarmManifest` serializes *specific* AOT
  executables (``jax.experimental.serialize_executable``) keyed by
  (model fingerprint, backend+jax version, input shape signature) into an
  artifact stored beside the checkpoint. ``ServingEngine`` warmup and the
  fused K-step engine deserialize their executables from it instead of
  compiling — zero compiles on a warm restart — falling back to a live
  compile on any key mismatch (counted separately, never trusted
  silently).

Trust model: manifest entries carry pickled jax pytree defs (the
``serialize_executable`` wire format), so **loading a warm manifest
executes pickle** — treat manifests and bundles like the checkpoints
they ship with: trusted deployment artifacts, never untrusted uploads.
(The plain ``save_model`` zip remains pickle-free; only the
``warm_manifest.zip`` member carries pickled data.)

Observability: ``compile_cache_total{event=hit|miss|serialize|
deserialize_fail}`` counts every manifest interaction, and the
``time_to_first_step_ms`` / ``time_to_first_request_ms`` gauges record the
realized cold-start tax (surfaced on ``/health`` and in the ``coldstart``
bench). :func:`startup_marks` keeps where the process started, where the
program was entered and when the first step was enqueued on one clock, and
with telemetry on jax's own compile phases land in the tracer's buffer as
``compile.trace`` / ``compile.lower`` / ``compile.backend`` /
``compile.cache_load`` spans (:func:`enable_persistent_cache` registers
the listener). All jax interaction goes through :func:`aot_compile` —
graftlint R3 flags raw ``.lower().compile()`` chains elsewhere, so no
compile site can silently bypass the manifest tier.
"""

from __future__ import annotations

import contextlib
import hashlib
import io
import json
import os
import pickle
import threading
import time
import warnings
import zipfile

import jax
import numpy as np

from deeplearning4j_tpu import _PROGRAM_ENTERED as PROGRAM_ENTERED

__all__ = ["DEFAULT_CACHE_DIR", "WarmManifest", "aot_compile",
           "attach_manifest", "backend_fingerprint",
           "enable_persistent_cache", "fresh_compile",
           "model_fingerprint",
           "note_first_request", "note_first_step", "signature_of",
           "startup_marks", "status"]

#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is unset: a fixed path under the checkout (never a temporary name, pid or
#: time — a directory that moves never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: 2: entries carry the ids of the devices the executable was compiled for
MANIFEST_VERSION = 2

def _process_start_anchor():
    """The perf_counter value at PROCESS start — /proc-derived on Linux
    so the first-step/first-request gauges genuinely include interpreter
    + jax import (the documented claim, and the dominant fixed cost on
    CPU); falls back to the package's first line elsewhere, and never
    lies after it (/proc counts in clock ticks)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # fields after the parenthesized comm; starttime is stat
            # field 22 -> index 19 here, in clock ticks since boot
            fields = f.read().rsplit(b")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        age_s = uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
        if age_s > 0:
            return min(time.perf_counter() - age_s, PROGRAM_ENTERED)
    except Exception:
        pass
    return PROGRAM_ENTERED


#: perf_counter at process start (see _process_start_anchor) — the zero
#: point of the time_to_first_step/request cold-start gauges
PROCESS_T0 = _process_start_anchor()

_lock = threading.Lock()
_first_marks: dict = {}


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def _instruments():
    from deeplearning4j_tpu import telemetry as _tm
    reg = _tm.get_registry()
    return (reg,
            reg.counter(
                "compile_cache_total",
                "warm-manifest interactions by event: hit (executable "
                "deserialized, no compile), miss (no entry — live "
                "compile), serialize (executable written into the "
                "manifest), serialize_fail (backend cannot export), "
                "deserialize_fail (entry present but unloadable — live "
                "compile fallback), mismatch_drop (manifest built for "
                "another model/backend, refused at load)"),
            reg.gauge(
                "time_to_first_step_ms",
                "wall ms from process start to the return of the first "
                "train dispatch (traced, lowered, compiled or loaded, and "
                "enqueued: the device has not finished it) — the realized "
                "training cold-start tax"),
            reg.gauge(
                "time_to_first_request_ms",
                "wall ms from process start to the first served inference "
                "request — the realized serving cold-start tax"))


def count_event(event, n=1):
    """Count one ``compile_cache_total`` interaction (hit/miss/serialize/
    deserialize_fail)."""
    _, c, _, _ = _instruments()
    c.inc(n, event=event)


def event_counts():
    """{event: count} snapshot of ``compile_cache_total`` (for /health and
    the coldstart bench legs)."""
    from deeplearning4j_tpu import telemetry as _tm
    c = _tm.get_registry().get("compile_cache_total")
    if c is None:
        return {}
    return {ls.get("event", ""): c.value(**ls) for ls in c.labelsets()}


def note_first_step():
    """Stamp ``time_to_first_step_ms`` once per process, as the first
    train dispatch returns: trace, lowering and compile or load are behind
    it and the step is enqueued, not finished. Subsequent calls are two
    dict reads and a branch."""
    return _note_first("step", "time_to_first_step_ms")


def note_first_request():
    """Stamp ``time_to_first_request_ms`` once per process (first served
    inference request)."""
    return _note_first("request", "time_to_first_request_ms")


def _note_first(mark, gauge_name):
    if mark in _first_marks:                # cheap unlocked fast path
        return None
    with _lock:
        if mark in _first_marks:
            return None
        ms = 1e3 * (time.perf_counter() - PROCESS_T0)
        _first_marks[mark] = ms
    reg, _, g_step, g_req = _instruments()
    (g_step if mark == "step" else g_req).set(ms)
    return ms


def first_marks():
    """{mark: ms} of the stamped first-step/first-request marks."""
    with _lock:
        return dict(_first_marks)


def reset_marks():
    """Forget the once-per-process gauges (test isolation — called from
    ``telemetry.reset()``)."""
    with _lock:
        _first_marks.clear()


def startup_marks():
    """{mark: ``perf_counter`` seconds}, the tracer's clock: where the
    process started (``process_start``, :data:`PROCESS_T0`), where the
    package's first line ran (``program_entered``) and, once a fit loop's
    first dispatch has returned, ``first_step`` (the stamp of
    :func:`note_first_step`: the step is enqueued, not finished)."""
    marks = {"process_start": PROCESS_T0, "program_entered": PROGRAM_ENTERED}
    step_ms = first_marks().get("step")
    if step_ms is not None:
        marks["first_step"] = PROCESS_T0 + step_ms / 1e3
    return marks


def status():
    """The /health ``compile_cache`` payload: persistent-cache dir, event
    counts, the realized cold-start gauges and the start-up marks."""
    marks = first_marks()
    return {
        "persistent_cache_dir": jax.config.jax_compilation_cache_dir,
        "events": event_counts(),
        "time_to_first_step_ms": marks.get("step"),
        "time_to_first_request_ms": marks.get("request"),
        "startup_marks": startup_marks(),
    }


# ---------------------------------------------------------------------------
# persistent compilation cache (tier a)
# ---------------------------------------------------------------------------

#: jax's own clock reads of a compile's phases (``jax/_src/dispatch.py``
#: ``log_elapsed_time``, ``jax/_src/compiler.py`` around the cache's read)
#: and the span each becomes in the tracer's buffer
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}

_compile_listener = None


def _listen_to_compile_phases():
    """Register, once a process, the listener that files jax's compile
    phases as complete events in the tracer's buffer. jax reports a
    length (from ``time.time()`` or ``time.monotonic()``); the tracer's
    clock is ``perf_counter``: an event ends at the listener's own read
    and starts the reported length before it, so it nests inside the
    ``fit.dispatch`` or ``net.init`` span that caused it, a jitted
    function traced inside another's trace inside that one's
    ``compile.trace``, the cache's load inside its ``compile.backend``.
    The function's name rides in the event's args (``fun``) and in no
    registry label. All four events are ``record_event_duration_secs``'s,
    so one listener serves them."""
    global _compile_listener
    if _compile_listener is not None:
        return
    from deeplearning4j_tpu import telemetry as _tm
    tracer = _tm.get_tracer()

    def on_duration(event, duration_secs, fun_name=None, **_):
        if not _tm.enabled():
            return
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        dur_us = duration_secs * 1e6
        tracer.add_complete(
            name, tracer.now_us() - dur_us, dur_us,
            None if fun_name is None else {"fun": fun_name})

    with _lock:
        if _compile_listener is None:
            jax.monitoring.register_event_duration_secs_listener(on_duration)
            _compile_listener = on_duration


def enable_persistent_cache():
    """Turn on jax's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set — jax reads it itself, so
    no directory is set in code; otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`. The min-compile-time and min-entry-size
    thresholds are opened so sub-second compiles persist too (jax's 1 s
    default would skip most of a cold start's executables). Also registers
    the compile phases' listener (once a process; see
    :func:`_listen_to_compile_phases`).
    """
    _listen_to_compile_phases()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


_fresh_lock = threading.Lock()
_fresh_depth = 0      # nested/concurrent fresh_compile() blocks
_fresh_restore = True  # jax_enable_compilation_cache as the first one found it


@contextlib.contextmanager
def fresh_compile():
    """Compile inside this block without reading or writing the persistent
    cache. An executable that may be SERIALIZED into a warm manifest must
    be a fresh compile: on jax 0.9.0 an XLA:CPU executable served from the
    persistent cache serializes and deserializes cleanly and then fails at
    its first call (``NOT_FOUND: Function ... not found``) — and the
    manifest is the stronger cache for that signature anyway. jax latches
    "is the cache used" per process, hence the resets around the flag
    flip; the depth count keeps one thread's exit from re-enabling the
    cache under another thread still inside its own block."""
    from jax.experimental.compilation_cache import compilation_cache as _jcc
    global _fresh_depth, _fresh_restore
    if not jax.config.jax_compilation_cache_dir:
        yield       # no persistent cache in this process: nothing to bypass
        return
    with _fresh_lock:
        _fresh_depth += 1
        if _fresh_depth == 1:
            _fresh_restore = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            _jcc.reset_cache()
    try:
        yield
    finally:
        with _fresh_lock:
            _fresh_depth -= 1
            if _fresh_depth == 0:
                jax.config.update("jax_enable_compilation_cache",
                                  _fresh_restore)
                _jcc.reset_cache()


# ---------------------------------------------------------------------------
# fingerprints + signatures
# ---------------------------------------------------------------------------

def backend_fingerprint():
    """Backend identity an executable is bound to: jax version + platform
    + device kind. A manifest from another backend must never load."""
    dev = jax.devices()[0]
    return f"jax-{jax.__version__}/{dev.platform}/{dev.device_kind}"


def model_fingerprint(net):
    """Architecture fingerprint: config JSON + param/state tree paths,
    shapes and dtypes. Deliberately value-free — XLA executables depend on
    shapes, not weights, so a retrained checkpoint of the same
    architecture reuses its manifest."""
    h = hashlib.sha256()
    conf = getattr(net, "conf", None)
    try:
        h.update(conf.to_json().encode())
    except Exception:
        h.update(repr(type(net)).encode())
    trees = (getattr(net, "params", None), getattr(net, "state", None))
    for path, leaf in jax.tree_util.tree_flatten_with_path(trees)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(tuple(np.shape(leaf))).encode())
        h.update(str(getattr(leaf, "dtype", type(leaf).__name__)).encode())
    return h.hexdigest()


def signature_of(args):
    """Canonical input-signature string for a pytree of arrays / structs:
    tree structure + per-leaf (shape, dtype). The manifest key a warm
    process can recompute without compiling anything."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = [(tuple(int(d) for d in np.shape(l)),
            str(getattr(l, "dtype", None) or np.asarray(l).dtype))
           for l in leaves]
    return json.dumps([str(treedef), sig], separators=(",", ":"))


# ---------------------------------------------------------------------------
# warm manifest (tier b)
# ---------------------------------------------------------------------------

class WarmManifest:
    """Serialized AOT executables keyed by (kind, input signature), scoped
    to ONE (model fingerprint, backend fingerprint) pair.

    ``put`` serializes a compiled executable
    (``jax.experimental.serialize_executable``) into the manifest together
    with the ids of the devices it was compiled for; ``load_executable``
    deserializes it back ONTO THOSE DEVICES (jax 0.9.0 otherwise loads over
    every visible device, and a one-device executable then dies at call
    time expecting one shard per device) — every interaction counts into
    ``compile_cache_total``. ``save``/``load`` round-trip the whole
    manifest as a zip (one entry per executable + a JSON header), and
    ``to_bytes``/``from_bytes`` embed it inside a checkpoint bundle
    (utils/serialization.save_bundle)."""

    def __init__(self, model_fp=None, backend_fp=None):
        self.model_fp = model_fp
        self.backend_fp = backend_fp or backend_fingerprint()
        # (kind, signature) -> pickled (payload, in_tree, out_tree, device ids)
        self._entries = {}
        self._mlock = threading.Lock()

    @classmethod
    def for_net(cls, net):
        """A fresh manifest scoped to ``net``'s architecture on this
        backend."""
        return cls(model_fingerprint(net))

    def matches(self, net):
        """True when this manifest's executables were built for ``net``'s
        architecture on the running backend — the load-time gate before
        any executable is trusted."""
        return (self.model_fp == model_fingerprint(net)
                and self.backend_fp == backend_fingerprint())

    def __len__(self):
        with self._mlock:
            return len(self._entries)

    def keys(self):
        with self._mlock:
            return sorted(self._entries)

    def has(self, kind, signature):
        """Uncounted membership probe (export paths — not a cache read)."""
        with self._mlock:
            return (str(kind), str(signature)) in self._entries

    # -- executables ---------------------------------------------------

    def put(self, kind, signature, compiled):
        """Serialize ``compiled`` under (kind, signature). Returns True on
        success; an executable the backend cannot serialize is counted
        (``serialize_fail``) and skipped — the manifest never hard-fails a
        working compile. The blob is not test-loaded here: what makes it
        runnable after a restart is that callers hand in fresh compiles
        only (see ``fresh_compile``)."""
        from jax.experimental import serialize_executable as _se
        try:
            payload, in_tree, out_tree = _se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = pickle.dumps((payload, in_tree, out_tree, device_ids),
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:
            count_event("serialize_fail")
            return False
        with self._mlock:
            self._entries[(str(kind), str(signature))] = blob
        count_event("serialize")
        return True

    def load_executable(self, kind, signature):
        """The deserialized executable for (kind, signature), loaded onto
        the devices it was compiled for, or None (counted as miss /
        deserialize_fail — the caller live-compiles)."""
        with self._mlock:
            blob = self._entries.get((str(kind), str(signature)))
        if blob is None:
            count_event("miss")
            return None
        from jax.experimental import serialize_executable as _se
        try:
            payload, in_tree, out_tree, device_ids = pickle.loads(blob)
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception:
            count_event("deserialize_fail")
            return None
        count_event("hit")
        return loaded

    # -- persistence ---------------------------------------------------

    def _write_zip(self, z):
        with self._mlock:
            entries = dict(self._entries)
        names = []
        for i, ((kind, sig), blob) in enumerate(sorted(entries.items())):
            fname = f"exec_{i:04d}.bin"
            names.append({"kind": kind, "signature": sig, "file": fname})
            z.writestr(fname, blob)
        z.writestr("manifest.json", json.dumps({
            "manifest_version": MANIFEST_VERSION,
            "model_fp": self.model_fp,
            "backend_fp": self.backend_fp,
            "jax_version": jax.__version__,
            "entries": names}, indent=1))

    @classmethod
    def _read_zip(cls, z):
        meta = json.loads(z.read("manifest.json"))
        if meta.get("manifest_version", 0) > MANIFEST_VERSION:
            raise ValueError(
                f"warm manifest version {meta['manifest_version']} is "
                f"newer than supported {MANIFEST_VERSION}")
        m = cls(meta.get("model_fp"), meta.get("backend_fp"))
        for e in meta.get("entries", ()):
            m._entries[(e["kind"], e["signature"])] = z.read(e["file"])
        return m

    def save(self, path):
        """Write the manifest zip (atomic: tmp + rename, so a crashed
        writer never leaves a truncated manifest a warm restart would
        choke on)."""
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as z:
                self._write_zip(z)
            os.replace(tmp, path)
        except BaseException:
            # a failed write (disk full, serialization error) must not
            # leave orphan temp blobs accumulating beside the checkpoint
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    @classmethod
    def load(cls, path):
        with zipfile.ZipFile(path) as z:
            return cls._read_zip(z)

    @classmethod
    def load_lenient(cls, source, context="warm manifest"):
        """``load`` (path) / ``from_bytes`` (bytes) that degrades instead
        of raising: a truncated or non-zip artifact warns, counts a
        ``deserialize_fail``, and returns None — the cache tier must
        never turn a working restart into a crash. The one shared
        corrupt-manifest path for ServingEngine, load_bundle and the
        sharded-checkpoint extras."""
        try:
            if isinstance(source, bytes):
                return cls.from_bytes(source)
            return cls.load(source)
        except FileNotFoundError:
            # not-yet-created is the normal FIRST cold start of the
            # documented save-after-warmup loop — no warning, no
            # deserialize_fail (that counter means a POISONED artifact)
            return None
        except Exception:
            warnings.warn(
                f"{context} is unreadable (corrupt or not a manifest "
                "zip) — ignoring it; the next warmup/fit pays live "
                "compiles", stacklevel=3)
            count_event("deserialize_fail")
            return None

    def to_bytes(self):
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            self._write_zip(z)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, data):
        with zipfile.ZipFile(io.BytesIO(data)) as z:
            return cls._read_zip(z)


# ---------------------------------------------------------------------------
# the one blessed compile site
# ---------------------------------------------------------------------------

def aot_compile(jitted, *args, manifest=None, kind="jit", signature=None,
                serialize_back=True):
    """Manifest-first AOT compile: the ONE ``.lower().compile()`` site.

    Returns ``(executable, source)`` with source ``"manifest"`` (warm —
    deserialized, zero compiles) or ``"compile"`` (live — lowered and
    compiled now, and serialized back into the manifest so the NEXT
    restart is warm). ``serialize_back=False`` skips that write-back —
    for compiles on a latency-sensitive path (a serving lazy compile
    under the forward lock), where the export walk at save time picks
    the executable up instead. graftlint R3 flags raw
    ``.lower().compile()`` chains outside this module, so serving/fused
    compiles cannot silently bypass the cache tier."""
    sig = str(signature if signature is not None else signature_of(args))
    if manifest is not None:
        ex = manifest.load_executable(kind, sig)
        if ex is not None:
            _note_step_peak(kind, ex)
            return ex, "manifest"
    write_back = manifest is not None and serialize_back
    with warnings.catch_warnings(), (
            fresh_compile() if write_back else contextlib.nullcontext()):
        # donated buffers rarely match an output shape; the warning is
        # per-compile noise, the donation is still wanted (see nn/fused)
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        ex = jitted.lower(*args).compile()
    if write_back:
        manifest.put(kind, sig, ex)
    _note_step_peak(kind, ex)
    return ex, "compile"


def _note_step_peak(kind, ex):
    """Every executable through the blessed compile site exports its XLA
    memory ledger into the ``step_peak_bytes`` gauges (site ``aot:<kind>``)
    — step-peak observability rides the compile path for free. Best
    effort: deserialized executables without memory_analysis record
    nothing, and telemetry failures never fail a compile."""
    try:
        from deeplearning4j_tpu.telemetry import devices as _devices
        base = str(kind).split(":", 1)[0]
        _devices.note_step_peak_bytes(f"aot:{base}", ex, layout=kind)
    except Exception:
        pass


def attach_if_matches(net, manifest, context):
    """The ONE restore-side refusal policy: attach ``manifest`` when it
    was built for ``net`` on this backend; otherwise warn with
    ``context``, count a ``mismatch_drop``, and return None (the
    checkpoint itself still restores — the next fit pays a live
    compile). Shared by load_bundle and the sharded-checkpoint extras."""
    if manifest is None:
        return None
    if manifest.matches(net):
        attach_manifest(net, manifest)
        return manifest
    warnings.warn(
        f"{context}: warm manifest was built for "
        f"model={manifest.model_fp!r} on backend={manifest.backend_fp!r} "
        "— not this net/backend; dropping it (state restored; the next "
        "fit pays a live compile)", stacklevel=3)
    count_event("mismatch_drop")
    return None


def attach_manifest(net, manifest):
    """Bind ``manifest`` to ``net`` so the fused fit engine
    (nn/fused.make_train_steps) serves its K-step scan executable from it.
    A manifest built for a different architecture/backend is refused —
    an executable that half-matches would fail at call time with an
    opaque XLA error instead of a clean fallback."""
    if manifest is not None and not manifest.matches(net):
        raise ValueError(
            "warm manifest does not match this net/backend "
            f"(manifest model={manifest.model_fp!r} "
            f"backend={manifest.backend_fp!r}, "
            f"net model={model_fingerprint(net)!r} "
            f"backend={backend_fingerprint()!r})")
    net._warm_manifest = manifest
    return net
