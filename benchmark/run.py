"""`python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell in one process. Sets up, warms the
cell's own shapes, measures for `--seconds`, checks the output against the
plain reference, prints one JSON line last and exits. Everything else
worth reading goes on earlier lines."""

import time

T0 = time.perf_counter()  # process start, as near as this module sees it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from benchmark import spec  # noqa: E402


def require_chips(n):
    """The devices this run may use; exits non-zero off the chip. (Tests
    patch this one function to rehearse the rest on the CPU.)"""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark.run: jax platform is {devs[0].platform!r}, "
                 "not 'tpu'; a benchmark number comes only from the chip")
    if len(devs) < n:
        sys.exit(f"benchmark.run: the cell asks for {n} chips, jax sees "
                 f"{len(devs)}")
    return devs[:n]


class Context:
    """What a runner is handed, and what the readers read afterwards."""

    def __init__(self, args, bench, cell, workload, config, devices, out_dir):
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.bench, self.cell = bench, cell
        self.workload, self.config = workload, config
        self.devices, self.out_dir = devices, out_dir
        self.cache_events = {"requests": 0, "hits": 0}
        self.trace_dir = os.path.join(out_dir, f"trace-{cell['name']}")
        self._tracing = False
        self.profiler_s = 0.0  # wall spent starting and stopping the trace
        self.setup_s = self.window_t0 = self.window_t1 = None
        self.counters_open = self.counters_close = None
        self.memory_peak_bytes = None
        self.memory_parts = {}  # the readings memory_peak_bytes is made of

    def window_opens(self):
        self.counters_open = _registry_totals()
        self.window_t0 = time.perf_counter()
        self.setup_s = self.window_t0 - T0

    def window_closes(self):
        """Reads the runtime's byte counters of the fullest chip. Its
        `peak_bytes_in_use` covers live buffers only: what a loaded
        program holds beyond its arguments while it runs (XLA's temp) is
        reserved apart and counted in `bytes_reserved`. So the peak is the
        larger of the counter's own peak (set-up's transients) and the
        live bytes as the window closes plus the reservation; the parts
        go into the device record beside it."""
        self.window_t1 = time.perf_counter()
        self.counters_close = _registry_totals()
        peaks = []
        for d in self.devices:
            s = d.memory_stats() or {}
            parts = {
                "memory_counter_peak_bytes": s.get("peak_bytes_in_use", 0),
                "memory_live_bytes": s.get("bytes_in_use", 0),
                "memory_reserved_bytes": s.get("bytes_reserved", 0)}
            peak = max(parts["memory_counter_peak_bytes"],
                       parts["memory_live_bytes"]
                       + parts["memory_reserved_bytes"])
            peaks.append((peak, parts))
        self.memory_peak_bytes, self.memory_parts = max(
            peaks, key=lambda p: p[0])
        print("memory", self.memory_peak_bytes, self.memory_parts, flush=True)

    def trace_start(self):
        import jax
        t = time.perf_counter()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        self.profiler_s += time.perf_counter() - t

    def trace_stop(self):
        if self._tracing:
            import jax
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self._tracing = False
            self.profiler_s += time.perf_counter() - t


def _registry_totals():
    """{metric: total over its series}: a counter's value, a histogram's
    sum. Read at both ends of the window; readers take the difference."""
    from deeplearning4j_tpu import telemetry
    out = {}
    for name, snap in telemetry.get_registry().snapshot().items():
        vals = [s["value"] for s in snap["series"]]
        out[name] = sum(v["sum"] if isinstance(v, dict) else v for v in vals)
    return out


def _count_cache_events(ctx):
    import jax

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            ctx.cache_events["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            ctx.cache_events["hits"] += 1

    jax.monitoring.register_event_listener(on_event)


def _metrics(ctx, result):
    """(name -> {value, unit}, the trace or None) for this run: the cell's
    end-to-end metrics with `--trace 0`, its per-layer metrics with
    `--trace 1`."""
    name = ctx.cell["name"]
    out, trace = {}, None
    if not ctx.trace:
        quantities = {**result["quantities"], "setup_s": ctx.setup_s}
        named = {"setup_s": "setup_s", **ctx.workload["end_to_end"]}
        for m in spec.cell_metrics(ctx.bench, name, "end_to_end"):
            out[m["name"]] = {"value": quantities[named[m["name"]]],
                              "unit": m["unit"]}
        return out, trace
    if os.path.isdir(ctx.trace_dir):
        from benchmark import trace as _trace
        trace = _trace.load(ctx.trace_dir)
    obs = {"ctx": ctx, "result": result, "trace": trace}
    for m in spec.cell_metrics(ctx.bench, name, "per_layer"):
        lm = spec.layer_metric(m["name"])
        value = spec.module("readers", lm["reader"]).read(
            obs, lm.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out, trace


def main(argv=None, root=spec.REPO_ROOT, out_dir=None):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, workload, config = spec.load_cell(args.workload, root)

    devices = require_chips(cell["chips"])
    from deeplearning4j_tpu.utils import compile_cache
    print("compile_cache_dir", compile_cache.enable_persistent_cache(),
          flush=True)
    ctx = Context(args, bench, cell, workload, config, devices,
                  out_dir or os.path.join(root, ".bench_out"))
    _count_cache_events(ctx)

    result = spec.module("runners", workload["runner"]).run(ctx)

    if result.get("check_detail"):
        os.makedirs(ctx.out_dir, exist_ok=True)
        with open(os.path.join(ctx.out_dir, f"check-{cell['name']}.json"),
                  "w", encoding="utf-8") as fh:
            json.dump({"seed": args.seed, **result["check_detail"]}, fh)
    correct = True
    for n, v, limit, ok in result["rows"]:
        print(f"check {n} = {v:.6g} (limit {limit:g}) "
              f"{'ok' if ok else 'NOT OK'}", flush=True)
        correct = correct and ok
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes,
              **ctx.memory_parts}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    line["metrics"], trace = _metrics(ctx, result)
    if trace is not None:
        device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
        line["breakdown"] = trace.breakdown()
    line["device"] = device
    print("cache_events", json.dumps(ctx.cache_events), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
