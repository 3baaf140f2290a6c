"""Host-side span tracing: Chrome trace events + xprof correlation.

Reference analog: libnd4j's OpProfiler gives the reference per-op host
timing; on TPU the device timeline belongs to XLA's profiler (xprof), so
the missing piece is the HOST side — where did the step loop spend its
wall time when the device was idle (ETL stall? queue wait? averaging
round?). A ``span("etl")`` context manager records a Chrome trace-event
(the ``chrome://tracing`` / Perfetto JSON format, same as TensorBoard's
trace_viewer) AND forwards into ``jax.profiler.TraceAnnotation`` so that
when a jax trace is active the host span shows up on the xprof timeline
aligned with the XLA device ops it enclosed — TensorFlow's
monitoring/tracing split (Abadi et al., 2016) reproduced host-side.

Near-zero overhead when disabled: ``span()`` returns one shared no-op
context manager — a function call and a branch, no allocation, no clock
read, no jax import.
"""

from __future__ import annotations

import json
import os
import threading
import time

from deeplearning4j_tpu.telemetry import registry as _registry
from deeplearning4j_tpu.telemetry import tracectx as _tracectx

_enabled = _registry.env_enabled()
_tracectx.set_enabled(_enabled)

_ANNOTATION = None
_ANNOTATION_TRIED = False
_PROFILE_STATE = False  # False: unprobed; None: unavailable; else state obj


def set_enabled(flag):
    global _enabled
    _enabled = bool(flag)
    # span tracing and causal trace contexts share ONE toggle — a span
    # recording while its trace silently drops (or vice versa) was the
    # same support trap as metrics-without-spans
    _tracectx.set_enabled(_enabled)


def enabled():
    return _enabled


def _trace_annotation():
    """jax.profiler.TraceAnnotation, resolved lazily and at most once —
    tracing must keep working (Chrome-trace-only) where jax is absent or
    its profiler API moved."""
    global _ANNOTATION, _ANNOTATION_TRIED
    if not _ANNOTATION_TRIED:
        _ANNOTATION_TRIED = True
        try:
            from jax.profiler import TraceAnnotation as _A
            _ANNOTATION = _A
        except Exception:
            _ANNOTATION = None
    return _ANNOTATION


def _xprof_active():
    """True while a jax profiler trace (xprof) is collecting.

    Entering TraceAnnotation with NO active session is pure overhead —
    and measurably worse than the ~0.4us standalone cost when a producer
    thread annotates while the consumer is inside a jit dispatch (the
    TraceMe machinery contends with jax's own dispatch instrumentation;
    several percent of fused steps/s at CPU bench shapes). So spans
    forward to xprof only when there is an xprof to land on. The probe is
    a private jax attribute; when it's unavailable, annotate always (the
    old behavior — never silently lose xprof rows)."""
    global _PROFILE_STATE
    if _PROFILE_STATE is False:
        try:
            from jax._src.profiler import _profile_state
            _PROFILE_STATE = _profile_state
        except Exception:
            _PROFILE_STATE = None
    st = _PROFILE_STATE
    if st is None:
        return True
    return st.profile_session is not None


class Tracer:
    """Bounded in-memory buffer of Chrome trace 'X' (complete) events.

    Spans from any thread land here; ``tid`` is the recording thread so the
    trace viewer renders the training loop, the ETL prefetch thread and the
    serving worker as separate, correlated rows. The buffer is bounded —
    an always-on tracer in a long-lived serving process must not grow
    without limit; overflow drops new events and counts them.
    """

    def __init__(self, max_events=200_000):
        self._lock = threading.Lock()
        self.max_events = int(max_events)
        self.events = []
        self.dropped = 0
        self.epoch = time.perf_counter()
        # cached: os.getpid() is a real syscall on hardened kernels
        # (several us — it would dominate the span record cost)
        self._pid = os.getpid()

    def now_us(self):
        return (time.perf_counter() - self.epoch) * 1e6

    def add_complete(self, name, ts_us, dur_us, args=None, tid=None):
        ev = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
              "pid": self._pid,
              "tid": threading.get_ident() if tid is None else tid}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(ev)

    def add_instant(self, name, args=None):
        """Point event ('i' phase) — markers like trace-start or hot-swap."""
        ev = {"name": name, "ph": "i", "s": "t", "ts": self.now_us(),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(ev)

    def chrome_trace(self):
        """The trace as a chrome://tracing / Perfetto-loadable dict."""
        with self._lock:
            evs = list(self.events)
            dropped = self.dropped
        out = {"traceEvents": evs, "displayTimeUnit": "ms"}
        if dropped:
            out["droppedEventCount"] = dropped
        return out

    def export(self, path):
        """Write the Chrome trace JSON; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def clear(self):
        with self._lock:
            self.events = []
            self.dropped = 0
            self.epoch = time.perf_counter()


_tracer = Tracer()


def get_tracer():
    return _tracer


class _NullSpan:
    """Shared do-nothing span — the entire disabled-path cost."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def interval(self):
        t = time.perf_counter()
        return t, t


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "_t0", "_t1", "_ann", "_ctx", "_tok")

    def __init__(self, name, args):
        self.name = name
        self.args = args

    def set(self, **attrs):
        """Attach attributes discovered mid-span (batch size, hit/miss)."""
        self.args.update(attrs)
        return self

    def interval(self):
        """(start, end) of a closed span, ``perf_counter`` seconds: for a
        caller that files the same two clock reads elsewhere too."""
        return self._t0, self._t1

    def __enter__(self):
        self._ann = None
        if _xprof_active():
            ann = _trace_annotation()
            if ann is not None:
                try:
                    self._ann = ann(self.name)
                    self._ann.__enter__()
                except Exception:
                    self._ann = None
        # causal linkage: with a TraceContext attached to this thread the
        # span becomes a child of the innermost enclosing span and pushes
        # itself as the new parent for anything nested (tracectx). No
        # context attached -> one contextvar read, nothing else.
        parent = _tracectx._cvar.get()
        if parent is not None:
            self._ctx = parent.child()
            self._tok = _tracectx._cvar.set(self._ctx)
        else:
            self._ctx = self._tok = None
        # start the host clock AFTER the annotation so the Chrome span
        # nests inside (not around) its xprof twin
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = self._t1 = time.perf_counter()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        args = self.args or None
        ctx = self._ctx
        if ctx is not None:
            _tracectx._cvar.reset(self._tok)
            span_args = dict(self.args) if self.args else {}
            if exc and exc[0] is not None:
                span_args["error"] = type(exc[0]).__name__
            ctx.trace.add(self.name, self._t0, t1, span_id=ctx.span_id,
                          parent_id=ctx.parent_id, **span_args)
            # the Chrome event carries the ids too, so a Perfetto row and
            # a /traces timeline cross-reference by trace_id
            args = dict(self.args) if self.args else {}
            args["trace_id"] = ctx.trace_id
            args["span_id"] = ctx.span_id
        tr = _tracer
        ts = (self._t0 - tr.epoch) * 1e6
        tr.add_complete(self.name, ts, (t1 - self._t0) * 1e6, args)
        return False


def span(name, **attrs):
    """Context manager timing a host-side region.

    When telemetry is enabled: records a Chrome trace event into the
    process tracer and brackets the region in jax.profiler.TraceAnnotation
    (visible in xprof when a jax trace is active). Disabled: a shared
    no-op. Nest freely — nesting is reconstructed from timestamps by the
    trace viewer.
    """
    if not _enabled:
        return _NULL_SPAN
    return _Span(name, attrs)
