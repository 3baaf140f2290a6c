"""Unified telemetry: metrics registry + host-side span tracing.

One coherent observability layer over what the reference scatters across
PerformanceListener / BaseStatsListener / OpProfiler (SURVEY.md §5):

* ``get_registry()`` — process-wide MetricsRegistry (counters, gauges,
  fixed-bucket histograms; JSONL + Prometheus exporters). Instrumented
  layers: the fit loops (step/ETL time, score), ParallelInference (queue
  depth, batch fill, request latency), the distributed training masters
  (per-round sync time), dataset caching/prefetch (hits, stalls) and the
  UIServer (scrape ``/metrics``).
* ``span("name")`` — host-side tracing into a Chrome trace-event buffer
  (``get_tracer().export(path)``), forwarded to
  ``jax.profiler.TraceAnnotation`` so host spans line up with XLA device
  ops in xprof.
* ``tracectx`` — causal trace contexts over those spans: a request/step
  trace carried via contextvars, handed across thread boundaries with
  ``ctx.handoff()`` / ``tracectx.attach(token)``, completed traces
  ringing into the N-slowest-per-root flight ring (``/traces`` endpoint,
  ``traces`` CLI verb) and stamping histogram exemplars on ``/metrics``.
* ``health`` — numerics watchdog: ``health.enable(policy="raise")`` folds
  NaN/Inf flags + grad norms + update/weight ratios into the jitted train
  step and applies the policy (record/warn/``NumericsError``).
* ``devices`` — HBM gauges (``device_bytes_in_use``, ``live_array_bytes``)
  and the ``recompiles_total`` jit-cache-miss counter (recompile storms).
* ``flight`` — ring-buffer flight recorder of the last N step records;
  auto-dumps JSON on watchdog anomaly, uncaught fit exception, or SIGTERM
  (``flight.install_signal_handler()``); pretty-print with the
  ``flightrec`` CLI verb.
* ``federate`` — cluster metrics federation: scrape every member's
  ``/metrics``, merge series under stable ``instance`` labels, count
  dead members instead of hanging (``/metrics?federate=1``).
* ``slo`` — the verdict layer over the series: declarative SloRules
  (windowed rate/ratio/threshold, multi-window burn rate, EWMA drift)
  evaluated over the local registry or a federated scrape, alert state
  ok|warning|firing counted into ``slo_alerts_total{rule,state}``
  (``/slo`` endpoint, ``slo`` CLI verb, flight dumps name burning
  rules).
* ``goodput`` — the wall-clock goodput ledger: every second of a run
  classified compute|etl_stall|exchange|checkpoint|rollback_lost|idle
  from the instruments the fit loops already emit, plus tokens/s and
  an MFU estimate (``/health`` under ``goodput``, the hostfleet
  done-line, every bench record).
* ``timeline`` — cluster timeline: clock-pair offset estimation + the
  merge of per-process trace rings/flight dumps into one time-aligned
  view (``/traces?cluster=1``, ``traces --cluster``).
* ``profiling`` — windowed ``jax.profiler`` capture around exactly one
  round (``profile_round``; guarded no-op off-TPU).
* ``reset()`` — drop all recorded state across the subsystem (tests).

Off by default; switch on per process with ``DL4J_TPU_TELEMETRY=1`` or at
runtime::

    from deeplearning4j_tpu import telemetry
    telemetry.enable()
    net.fit(x, y, epochs=2)
    print(telemetry.get_registry().to_prometheus())
    telemetry.get_tracer().export("/tmp/host_trace.json")

Disabled, the instrumentation costs one branch per site — no allocations,
no clock reads, and never a device->host sync.
"""

from __future__ import annotations

from deeplearning4j_tpu.telemetry import tracing as _tracing
from deeplearning4j_tpu.telemetry.registry import (DEFAULT_BUCKETS, Counter,
                                                   Gauge, Histogram,
                                                   MetricsRegistry,
                                                   get_registry, write_jsonl)
from deeplearning4j_tpu.telemetry.tracing import Tracer, get_tracer, span
from deeplearning4j_tpu.telemetry import (devices, federate, flight, goodput,
                                          health, history, profiling,
                                          scorepipe, slo, timeline, tracectx)
from deeplearning4j_tpu.telemetry.health import NumericsError
from deeplearning4j_tpu.telemetry.scorepipe import ScorePipeline
from deeplearning4j_tpu.telemetry.tracectx import TraceContext

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "Tracer",
           "DEFAULT_BUCKETS", "get_registry", "get_tracer", "span",
           "write_jsonl", "enable", "disable", "enabled", "reset",
           "series_map", "note_step_state",
           "health", "devices", "flight", "scorepipe", "ScorePipeline",
           "NumericsError", "tracectx", "TraceContext",
           "federate", "timeline", "profiling", "slo", "goodput",
           "history"]


def enable():
    """Turn on metrics recording and span tracing process-wide (the
    default registry's ``enabled`` setter flips both)."""
    get_registry().enabled = True


def disable():
    get_registry().enabled = False


def enabled():
    return get_registry().enabled


def reset():
    """Drop every piece of recorded telemetry state — registry series,
    tracer buffer, watchdog state (back to inactive), recompile baselines,
    flight-recorder ring — without discarding instrument objects. The test
    isolation entry point (ISSUE 2): one call instead of per-module
    teardown. Does not change the registry's enabled flag."""
    get_registry().reset()
    get_tracer().clear()
    health.get_monitor().reset()
    devices.reset()
    flight.get_recorder().clear()
    tracectx.get_ring().clear()
    tracectx.reset_open_count()
    timeline.clear_source_providers()
    federate.clear_target_providers()
    slo.reset()
    goodput.reset()
    history.reset()
    # demand plane (usage ledger, prober): lazy imports — these modules
    # import telemetry back (same pattern as compile_cache)
    from deeplearning4j_tpu.serving import metering as _metering
    _metering.reset()
    from deeplearning4j_tpu.fleet import prober as _prober
    _prober.reset()
    # once-per-process cold-start gauges (time_to_first_step/request):
    # lazy import — utils.compile_cache imports telemetry lazily back
    from deeplearning4j_tpu.utils import compile_cache as _cc
    _cc.reset_marks()


def series_map(name):
    """``{"label=value|label2=value2": value}`` flattening of one metric's
    series (``""`` keys an unlabeled series; ``{}`` when the metric does
    not exist) — the wire form subprocess workers and bench legs embed in
    their JSON records and the check scripts key on. ONE definition so
    the string format the gates parse cannot drift per emit site."""
    m = get_registry().get(name)
    if m is None:
        return {}
    return {("|".join(f"{k}={v}" for k, v in sorted(s["labels"].items()))
             or ""): s["value"] for s in m.snapshot()["series"]}


def train_metrics():
    """(registry, step_hist, etl_hist, iterations_counter, score_gauge) —
    the per-iteration instruments shared by the MultiLayerNetwork and
    ComputationGraph fit loops (one naming authority, so the dashboards and
    the /metrics scrape see a single series family whichever trainer ran)."""
    reg = get_registry()
    return (reg,
            reg.histogram("train_step_seconds",
                          "wall time of one optimizer step (fit loop)"),
            reg.histogram("train_etl_seconds",
                          "host-side batch assembly/placement per iteration"),
            reg.counter("train_iterations_total",
                        "optimizer iterations completed"),
            reg.gauge("train_score", "last training score (loss)"))


def _states_holding(state, keys):
    """``(key, dict)`` for every dict that holds one of ``keys`` in a net's
    state tree (a list a layer, a dict a vertex, a layer's own dicts nested
    inside), in one walk: ``moe_load`` marks a routed-experts layer's
    state, ``loss_terms`` a head that keeps the parts of its loss apart. A
    key found is sought no deeper, the others are: a multi-token head holds
    ``loss_terms`` beside its module's block, whose routed layer holds
    ``moe_load``."""
    if isinstance(state, dict):
        for k in keys:
            if k in state:
                yield k, state
        keys = tuple(k for k in keys if k not in state)
        state = state.values()
    elif not isinstance(state, (list, tuple)):
        return
    if keys:
        for v in state:
            yield from _states_holding(v, keys)


def note_step_state(state):
    """What the last step left in the net's state for the registry, in
    ONE walk of the tree and ONE small fetch; the fit loop calls it where
    it already waits for the device (``StepDriver.sync``), and only while
    the registry records.

    The routing counts (``moe_load``: rows per held expert;
    ``moe_elsewhere``: assignments routed to experts not held): the
    counters ``moe_rows_here_sampled_total`` and
    ``moe_assignments_sampled_total`` grow by that ONE step's counts summed
    over the expert layers (a sample of a round's steps, one a call: their
    ratio is a share, neither is a total of the run), the gauges
    ``moe_load_hottest_rows`` and ``moe_load_mean_rows`` hold each layer's
    hottest and mean held expert, summed over the layers. The terms of the
    loss that a layer left apart (``loss_terms``: a multi-token head's
    ``main`` and ``mtp``): the gauges ``train_loss_term_<name>``."""
    reg = get_registry()
    if not reg.enabled:
        return
    routed, terms = [], []
    for key, s in _states_holding(state, ("moe_load", "loss_terms")):
        if key == "moe_load":
            routed.append((s["moe_load"], s["moe_elsewhere"]))
        else:
            terms.append(s["loss_terms"])
    if not (routed or terms):
        return
    import jax
    routed, terms = jax.device_get((routed, terms))
    if routed:
        here = hottest = mean = elsewhere = 0.0
        for load, away in routed:
            here += float(load.sum())
            hottest += float(load.max())
            mean += float(load.mean())
            elsewhere += float(away.sum())
        reg.counter("moe_rows_here_sampled_total",
                    "assignments computed by the experts held here, over "
                    "the sampled steps alone (one a fit.sync)").inc(here)
        reg.counter("moe_assignments_sampled_total",
                    "assignments routed, here and elsewhere, over the "
                    "sampled steps alone (one a fit.sync)").inc(
                        here + elsewhere)
        reg.gauge("moe_load_hottest_rows",
                  "rows of each layer's hottest held expert, summed over "
                  "the expert layers, last sampled step").set(hottest)
        reg.gauge("moe_load_mean_rows",
                  "mean rows a held expert, summed over the expert layers, "
                  "last sampled step").set(mean)
    for layer_terms in terms:
        for name, value in layer_terms.items():
            reg.gauge(f"train_loss_term_{name}",
                      f"the term {name!r} of the last sampled step's "
                      "loss, before its weight").set(float(value))
