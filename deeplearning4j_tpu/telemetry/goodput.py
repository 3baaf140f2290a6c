"""Wall-clock goodput ledger: where every second of a training run went.

MFU and tokens/s say how fast the compute was; they say nothing about
how much of the wall clock was compute at all. This ledger classifies
the run's wall time into categories from the instruments the fit loops
already emit — no new hot-path timers:

* ``compute``       — Δ ``train_step_seconds``.sum (the optimizer steps
                      themselves), minus seconds later invalidated;
* ``etl_stall``     — Δ ``train_etl_seconds``.sum (host-side batch
                      assembly/placement between steps);
* ``exchange``      — explicitly noted collective/exchange seconds
                      (the hostfleet round's exchange span);
* ``checkpoint``    — explicitly noted snapshot/bundle-write seconds;
* ``rollback_lost`` — compute seconds invalidated by a rollback (the
                      ContinuousTrainer estimates lost-steps x mean
                      step time when it rewinds); subtracted from
                      ``compute`` so a second is never counted twice;
* ``idle``          — the window remainder (scheduling gaps, producer
                      waits, everything unattributed).

The categories therefore sum to the observed window by construction
(up to clock skew between the histograms' own timers and the ledger's
window — the tier-1 gate checks ±5%). On top of the split: live
tokens/s (``note_tokens``) and an MFU estimate from analyzed flops per
step x steps / (window x peak flops).

Surfaces: ``/health`` under ``goodput``, the hostfleet done-line, and
every ``bench.py`` record — BENCH history carries a goodput trajectory.
Noted seconds also count into ``goodput_seconds_total{category}`` so
the SLO engine can rule on them like any other counter.

The process-default ledger (``get_ledger()``) starts lazily with the
first instrumented StepDriver; ``start()`` rebases the window (bench
legs rebase around exactly the fit they measure).
"""

from __future__ import annotations

import threading
import time

from deeplearning4j_tpu.telemetry import registry as _registry

#: classification buckets, in display order
CATEGORIES = ("compute", "etl_stall", "exchange", "checkpoint",
              "rollback_lost", "idle")

#: categories note() accepts. compute/etl_stall are normally DERIVED
#: from the train histograms; noted seconds ADD to the derived deltas
#: (loops that run uninstrumented drivers — the hostfleet worker — time
#: their round edges directly and note them here instead)
NOTED = ("compute", "etl_stall", "exchange", "checkpoint",
         "rollback_lost")


class GoodputLedger:
    """Wall-clock classification of a training window (thread-safe)."""

    def __init__(self, registry=None):
        self._reg = registry or _registry.get_registry()
        self._lock = threading.Lock()
        self._t0 = None
        self._base_step_sum = 0.0
        self._base_etl_sum = 0.0
        self._base_steps = 0
        self._noted = {k: 0.0 for k in NOTED}
        self._tokens = 0.0
        self._flops_per_step = None
        self._peak_flops = None
        self._m_noted = self._reg.counter(
            "goodput_seconds_total",
            "wall seconds noted into the goodput ledger by category "
            "(exchange / checkpoint / rollback_lost)")

    # ---- lifecycle ----

    @property
    def active(self):
        with self._lock:
            return self._t0 is not None

    def _hists(self):
        reg = self._reg
        return (reg.histogram("train_step_seconds",
                              "wall time of one optimizer step (fit loop)"),
                reg.histogram("train_etl_seconds",
                              "host-side batch assembly/placement per "
                              "iteration"))

    def start(self, now=None):
        """(Re)base the window at ``now``: later snapshots cover only
        work from here on. Carries no category seconds across."""
        step_h, etl_h = self._hists()
        with self._lock:
            self._t0 = time.monotonic() if now is None else float(now)
            self._base_step_sum = float(step_h.sum())
            self._base_etl_sum = float(etl_h.sum())
            self._base_steps = int(step_h.count())
            self._noted = {k: 0.0 for k in NOTED}
            self._tokens = 0.0
        return self

    def ensure_started(self, now=None):
        """start() only if the window is not already open — the lazy
        entry point the instrumented StepDriver calls, so any fit loop
        gets a ledger without wiring."""
        with self._lock:
            started = self._t0 is not None
        if not started:
            self.start(now=now)
        return self

    # ---- accounting ----

    def note(self, category, seconds):
        """Attribute ``seconds`` of the window to an explicit category.
        No-op while the window is closed or for non-positive amounts."""
        if category not in NOTED:
            raise ValueError(f"goodput category {category!r} is derived "
                             f"or unknown; note() takes one of {NOTED}")
        s = float(seconds)
        if s <= 0:
            return
        with self._lock:
            if self._t0 is None:
                return
            self._noted[category] += s
        if self._reg.enabled:
            self._m_noted.inc(s, category=category)

    def note_tokens(self, n):
        """Count ``n`` training tokens (or examples — the caller picks
        the unit) into the window for the tokens/s line."""
        if n <= 0:
            return
        with self._lock:
            if self._t0 is None:
                return
            self._tokens += float(n)

    def set_flops_per_step(self, flops):
        """Analyzed FLOPs of one optimizer step (cost analysis or
        batch-shape arithmetic) — enables the MFU estimate."""
        with self._lock:
            self._flops_per_step = None if flops is None else float(flops)

    def set_peak_flops(self, flops):
        """Aggregate peak FLOP/s of the devices under this run."""
        with self._lock:
            self._peak_flops = None if flops is None else float(flops)

    # ---- reporting ----

    def snapshot(self, now=None):
        """The goodput block: per-category seconds + fractions summing
        to the window, tokens/s, steps, MFU (None without flops)."""
        step_h, etl_h = self._hists()
        step_sum, etl_sum = float(step_h.sum()), float(etl_h.sum())
        steps = int(step_h.count())
        with self._lock:
            if self._t0 is None:
                return {"active": False}
            t = time.monotonic() if now is None else float(now)
            window = max(t - self._t0, 0.0)
            noted = dict(self._noted)
            tokens = self._tokens
            fps = self._flops_per_step
            peak = self._peak_flops
            d_step = max(step_sum - self._base_step_sum, 0.0)
            d_etl = max(etl_sum - self._base_etl_sum, 0.0)
            d_steps = max(steps - self._base_steps, 0)
        gross_compute = d_step + noted["compute"]
        rollback_lost = min(noted["rollback_lost"], gross_compute)
        compute = gross_compute - rollback_lost
        seconds = {
            "compute": compute,
            "etl_stall": d_etl + noted["etl_stall"],
            "exchange": noted["exchange"],
            "checkpoint": noted["checkpoint"],
            "rollback_lost": rollback_lost,
        }
        measured = sum(seconds.values())
        seconds["idle"] = max(window - measured, 0.0)
        out = {
            "active": True,
            "window_s": window,
            "seconds": {k: round(seconds[k], 6) for k in CATEGORIES},
            "fractions": {k: (round(seconds[k] / window, 6)
                              if window > 0 else 0.0)
                          for k in CATEGORIES},
            "goodput_fraction": (round(compute / window, 6)
                                 if window > 0 else 0.0),
            "steps": d_steps,
            "tokens": tokens,
            "tokens_per_s": (round(tokens / window, 3)
                             if window > 0 and tokens else 0.0),
            "mfu": None,
            "flops_per_step": fps,
        }
        if fps and peak and window > 0:
            out["mfu"] = round(fps * d_steps / (window * peak), 6)
        return out


# ---- process-default ledger ----

_default_ledger = None
_default_lock = threading.Lock()


def get_ledger():
    global _default_ledger
    with _default_lock:
        if _default_ledger is None:
            _default_ledger = GoodputLedger()
        return _default_ledger


def reset():
    """Drop the process-default ledger (telemetry.reset())."""
    global _default_ledger
    with _default_lock:
        _default_ledger = None


def device_peak_flops():
    """Aggregate bf16 peak FLOP/s of the local devices for the MFU
    denominator, from the one peaks table (telemetry/devices.py): None on
    a non-TPU platform, an error for a TPU kind the table does not know."""
    import jax

    from deeplearning4j_tpu.telemetry import devices as _devices
    peaks = _devices.device_peaks()
    return None if peaks is None else peaks["bf16_flops"] * len(jax.devices())
