"""Device memory + XLA recompilation observability.

Two TPU-stack failure modes the metrics tier could not see:

* **HBM creep** — live-array bytes and per-device ``memory_stats()`` grow
  until an OOM kills the run hours in. ``poll_memory()`` samples both into
  the shared registry each recorded iteration (guarded: CPU backends return
  ``None`` from ``memory_stats()`` — the per-device walk latches off after
  the first empty poll; the live-array census still works everywhere).
* **Recompile storms** — the canonical TPU perf trap (Fischer & Saba 2018,
  §4: every new shape signature re-enters XLA compilation, turning a
  microseconds step into seconds). ``note_jit_cache(site, fn)`` tracks a
  jitted callable's compile-cache size; growth beyond the first fill counts
  into ``recompiles_total{site=...}`` — a rising series IS the storm, now
  scrapeable from /metrics instead of diagnosed by staring at wall clocks.

Everything here is registry-gated: with telemetry disabled these functions
are never called by the instrumented loops, and calling them anyway records
nothing.
"""

from __future__ import annotations

import threading

import jax

from deeplearning4j_tpu.telemetry import registry as _registry

#: Published per-chip peaks, keyed by the EXACT ``device_kind`` jax reports
#: (a v5e chip reports "TPU v5 lite"). The one table every utilization or
#: roofline number divides by. Source for "TPU v5 lite": Google Cloud
#: documentation, "TPU v5e" system architecture page — 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30},
}


def device_peaks(device=None):
    """The :data:`DEVICE_PEAKS` row for ``device`` (default: the first
    local device), or None on a non-TPU platform — callers then OMIT their
    utilization fields. A TPU kind that is not in the table raises: a
    device nobody looked up is an error, not a default."""
    dev = jax.devices()[0] if device is None else device
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no peaks recorded for TPU device_kind {dev.device_kind!r}: "
            "add its published numbers, with the source, to "
            "telemetry/devices.DEVICE_PEAKS")
    return DEVICE_PEAKS[dev.device_kind]


def device_stamp():
    """The device identity every measurement record carries, as jax
    reports it (bench.py records, cold-start legs, chip_smoke.py lines)."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "jax_version": jax.__version__}


#: recompiles-per-site at which /health flips to "warn": a couple of
#: recompiles are normal warm-up (ragged final batch, eval shapes); a storm
#: is one per step
RECOMPILE_STORM_THRESHOLD = 8

_lock = threading.Lock()
_cache_sizes = {}        # (site, id(fn)) -> last observed jit cache size
_mem_unsupported = False  # latched: this backend has no memory_stats()
_train_bytes = {}        # site -> last note_train_tree_bytes snapshot
_step_peak = {}          # site -> last note_step_peak_bytes snapshot


def reset():
    """Drop recompile baselines + the memory-support latch (test isolation;
    part of telemetry.reset())."""
    global _mem_unsupported
    with _lock:
        _cache_sizes.clear()
        _train_bytes.clear()
        _step_peak.clear()
        _mem_unsupported = False


def _instruments():
    reg = _registry.get_registry()
    return (reg,
            reg.gauge("device_bytes_in_use",
                      "per-device HBM bytes in use (memory_stats), "
                      "labeled by device"),
            reg.gauge("device_bytes_limit",
                      "per-device HBM capacity bytes, labeled by device"),
            reg.gauge("live_array_bytes",
                      "total bytes of live jax arrays in this process"),
            reg.counter("compiles_total",
                        "jit cache entries created, labeled by site "
                        "(first-fill warm-up included)"),
            reg.counter("recompiles_total",
                        "jit cache misses beyond the first fill, labeled "
                        "by site — a rising series is a recompile storm"))


def poll_memory(include_live_arrays=True):
    """Sample device memory into the shared registry gauges.

    Returns a small dict (``live_array_bytes``, ``device_bytes_in_use``:
    max across devices) for callers that want the numbers inline (the fit
    loops put them on flight-recorder step records), or ``None`` when the
    registry is disabled.
    """
    global _mem_unsupported
    reg, g_use, g_lim, g_live, _, _ = _instruments()
    if not reg.enabled:
        return None
    out = {}
    if not _mem_unsupported:
        max_use = None
        saw_stats = False
        for d in jax.devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            saw_stats = True
            dev = f"{d.platform}:{d.id}"
            use = stats.get("bytes_in_use")
            if use is not None:
                g_use.set(use, device=dev)
                max_use = use if max_use is None else max(max_use, use)
            limit = (stats.get("bytes_limit")
                     or stats.get("bytes_reservable_limit"))
            if limit:
                g_lim.set(limit, device=dev)
        if not saw_stats:
            _mem_unsupported = True  # don't re-walk devices every step
        if max_use is not None:
            out["device_bytes_in_use"] = int(max_use)
    if include_live_arrays:
        try:
            live = int(sum(a.nbytes for a in jax.live_arrays()))
        except Exception:
            live = None
        if live is not None:
            g_live.set(live)
            out["live_array_bytes"] = live
    return out


def memory_summary():
    """Registry-independent snapshot — ``{devices: {dev: {bytes_in_use,
    bytes_limit}}, live_array_bytes}`` — for bench records and /health.
    CPU backends yield an empty ``devices`` map, never an error."""
    out = {"devices": {}, "live_array_bytes": 0}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out["devices"][f"{d.platform}:{d.id}"] = {
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)
                               or stats.get("bytes_reservable_limit", 0)
                               or 0)}
    try:
        out["live_array_bytes"] = int(sum(a.nbytes
                                          for a in jax.live_arrays()))
    except Exception:
        pass
    return out


def tree_shard_bytes(tree):
    """``(logical_bytes, per_device_bytes)`` for a pytree of arrays.

    ``logical`` counts every element once — the model's size on paper.
    ``per_device`` is addressable-shard-aware: what ONE device actually
    stores, via ``sharding.shard_shape`` — a ZeRO/FSDP layout reads ~1/N
    of the replicated number HERE, which is the whole point of the layout.
    Host numpy leaves (no sharding) count their full nbytes into both."""
    logical = per_dev = 0
    for a in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(a, "nbytes", None)
        if nbytes is None:
            continue
        logical += int(nbytes)
        try:
            shard = a.sharding.shard_shape(a.shape)
            n = 1
            for d in shard:
                n *= int(d)
            per_dev += n * a.dtype.itemsize
        except Exception:
            per_dev += int(nbytes)
    return logical, per_dev


def note_train_tree_bytes(params=None, opt_state=None, site="trainer"):
    """Record the HBM ledger of a training job's persistent trees:
    ``param_bytes`` / ``opt_state_bytes`` gauges labeled
    ``{site, scope=logical|per_device}`` plus a registry-independent
    snapshot for ``/health`` (``train_memory_summary``) and bench records.
    Called once per trainer init/restore — the 1/N saving of a sharded
    weight-update layout becomes a number in the flight recorder, not a
    claim. Returns the snapshot dict."""
    snap = {}
    if params is not None:
        lg, pd = tree_shard_bytes(params)
        snap["param_bytes"] = {"logical": lg, "per_device": pd}
    if opt_state is not None:
        lg, pd = tree_shard_bytes(opt_state)
        snap["opt_state_bytes"] = {"logical": lg, "per_device": pd}
    with _lock:
        _train_bytes[site] = snap
    reg = _registry.get_registry()
    if reg.enabled:
        for name, vals in snap.items():
            g = reg.gauge(name,
                          "bytes of the training job's persistent "
                          f"{'params' if name.startswith('param') else 'updater state'}"
                          ", labeled by site and scope (logical = every "
                          "element once; per_device = addressable-shard-"
                          "aware resident bytes on ONE device — ~1/N "
                          "under a ZeRO/FSDP layout)")
            for scope, v in vals.items():
                g.set(float(v), site=site, scope=scope)
    return snap


def step_peak_stats(compiled):
    """The compiled executable's XLA memory ledger as a plain dict —
    ``compiled.memory_analysis()`` (CompiledMemoryStats) read into
    ``{temp_bytes, argument_bytes, output_bytes, alias_bytes,
    peak_bytes}`` — or None when this backend/executable has no analysis
    (deserialized warm-manifest executables on some jax releases).

    ``temp`` is XLA's scratch allocation for the step — under the ZeRO
    layouts this is where the gathered params live, so it is THE
    within-step number the steady-state ``tree_shard_bytes`` gauges
    cannot see (a whole-tree fsdp gather parks the full params here; the
    streamed tier parks one block). ``peak`` approximates the step's
    device footprint as arguments + outputs + temp − aliased (donated
    buffers counted once)."""
    try:
        ma = compiled.memory_analysis()
        out = {f"{k}_bytes": int(getattr(ma, f"{k}_size_in_bytes"))
               for k in ("temp", "argument", "output", "alias")}
    except Exception:
        return None
    out["peak_bytes"] = (out["temp_bytes"] + out["argument_bytes"]
                         + out["output_bytes"] - out["alias_bytes"])
    return out


def note_step_peak_bytes(site, compiled, layout="default"):
    """Export a step executable's memory ledger into
    ``step_peak_bytes{site, layout, component}`` gauges plus the
    registry-independent snapshot ``train_memory_summary`` folds in under
    ``step_peak_bytes`` (and /health shows next to the steady-state
    ledger). Called from ``compile_cache.aot_compile`` for every
    AOT-compiled executable and from
    ``ParallelTrainer.step_memory_analysis``. Returns the stats dict or
    None (no analysis on this backend — nothing recorded)."""
    stats = compiled if isinstance(compiled, dict) \
        else step_peak_stats(compiled)
    if stats is None:
        return None
    snap = dict(stats, layout=str(layout))
    with _lock:
        _step_peak[site] = snap
    reg = _registry.get_registry()
    if reg.enabled:
        g = reg.gauge("step_peak_bytes",
                      "XLA memory ledger of a compiled step executable "
                      "(memory_analysis), labeled by site, storage "
                      "layout and component (temp = scratch incl. "
                      "gathered params; peak = argument + output + temp "
                      "- alias) — the WITHIN-step HBM the steady-state "
                      "param/opt gauges cannot see")
        for comp in ("temp", "argument", "output", "alias", "peak"):
            g.set(float(stats[f"{comp}_bytes"]), site=site,
                  layout=str(layout), component=comp)
    return stats


def train_memory_summary():
    """{site: {param_bytes: {logical, per_device}, opt_state_bytes: ...,
    step_peak_bytes: {temp_bytes, ..., layout}}} — the last
    note_train_tree_bytes / note_step_peak_bytes snapshots per site,
    registry-independent (for /health next to memory_summary)."""
    with _lock:
        out = {k: dict(v) for k, v in _train_bytes.items()}
        for site, snap in _step_peak.items():
            out.setdefault(site, {})["step_peak_bytes"] = dict(snap)
    return out


def note_jit_cache(site, fn):
    """Observe a jitted callable's compile-cache size after a call.

    The first observation baselines the expected warm-up compile(s); any
    growth after that is a cache miss at a site that should be steady-state
    — counted into ``recompiles_total{site=...}``. Keyed by (site, fn) so
    two networks sharing a site name each get their own baseline. Returns
    the number of NEW recompiles seen (0 on baseline or unsupported fn).
    """
    try:
        size = fn._cache_size()
    except Exception:
        return 0
    key = (site, id(fn))
    with _lock:
        last = _cache_sizes.get(key)
        _cache_sizes[key] = size
    reg, *_, c_comp, c_rec = _instruments()
    if last is None:
        if size:
            c_comp.inc(size, site=site)
        return 0
    new = size - last
    if new <= 0:
        return 0
    c_comp.inc(new, site=site)
    c_rec.inc(new, site=site)
    return new


def recompile_counts():
    """{site: recompiles} from the shared registry (for /health)."""
    reg = _registry.get_registry()
    c = reg.get("recompiles_total")
    if c is None:
        return {}
    return {ls.get("site", ""): c.value(**ls) for ls in c.labelsets()}
