"""A train cell: the normal fit loop (`StepDriver`, which `fit()`
delegates to) driven by its own round boundary, the clock read at rounds.

Set-up builds ONE object (network, compiled step, optimizer state, feed),
drives it through its first three steps by `run_round(1)` for the output
check, warms three rounds, and hands the same object to the window:
rounds of `steps_per_round` dispatches until `--seconds` have passed, each
closed by `sync()` and a fetch of the round's last loss, which
data-depends on the whole round. The rate is all the rounds' work over the
whole window's wall; each round's wall, and how much of it the host spent
dispatching, go on earlier lines so that a slow round can be placed."""

from __future__ import annotations

import gc
import itertools
import time

import jax

from benchmark import check_train, program, spec, stats
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.continuous.driver import StepDriver

WARM_ROUNDS = 3
TRACE_ROUNDS = 3


def _round(driver, net, steps):
    """One round: the loop's own boundary, then the fetch that ends it.
    Returns (the round's last loss, the seconds the dispatching took)."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.fit_round"):
        driver.run_round(steps)
    dispatch_s = time.perf_counter() - t0
    with jax.profiler.TraceAnnotation("bench.fit_sync"):
        driver.sync()
        return float(net.score_value), dispatch_s


def _seeded(ref, seed, model):
    """(params, state) from the seed, in the program's layout."""
    return ref.program_layout(ref.init(seed, model), ref.init_state(model))


def _say_reference_start(devices):
    """What the reference starts beside: the runtime's byte counters of
    the fullest chip (None on the CPU: printed as 0) and the bytes of
    every array still alive, which should be the `plain` batches."""
    s = max((d.memory_stats() or {} for d in devices),
            key=lambda held: held.get("bytes_in_use", 0)
            + held.get("bytes_reserved", 0))
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"reference_start bytes_in_use {s.get('bytes_in_use', 0)} "
          f"bytes_reserved {s.get('bytes_reserved', 0)} "
          f"bytes_limit {s.get('bytes_limit', 0)} "
          f"live_arrays_bytes {live}", flush=True)


def run(ctx):
    wl, config, seed = ctx.workload, ctx.config, ctx.seed
    ref = spec.module("reference", config["reference"])
    model, opt = config["model"], config["optimizer"]
    steps = wl["steps_per_round"]
    if ctx.trace:
        telemetry.enable()  # counters are read in the traced run only

    net = program.build(config, seed)
    program.load_weights(net, *_seeded(ref, seed, model))
    traffic = spec.module("traffic", wl["traffic"]["kind"]).make(
        seed, wl["traffic"], model)
    items = [program.feed_item(net, x, y) for x, y in traffic["feed"]]
    driver = StepDriver(net, lambda: itertools.cycle(items))

    got = check_train.ProgramReadings(net, opt)
    for _ in range(check_train.STEPS):
        got.after_step(_round(driver, net, 1)[0])
    got.after_last(program.lay_over(net.params,
                                    _seeded(ref, seed, model)[0]))
    for _ in range(WARM_ROUNDS):
        _round(driver, net, steps)

    ctx.window_opens()
    walls, dispatch, t_open = [], [], time.perf_counter()
    cpu_open, traced_from = time.process_time(), None
    while True:
        elapsed = time.perf_counter() - t_open
        if elapsed >= ctx.seconds:
            break
        if ctx.trace and traced_from is None and elapsed >= ctx.seconds / 2:
            ctx.trace_start()
            traced_from = len(walls)
        t0 = time.perf_counter()
        dispatch.append(_round(driver, net, steps)[1])
        walls.append(time.perf_counter() - t0)
        if traced_from is not None and len(walls) == traced_from + TRACE_ROUNDS:
            ctx.trace_stop()
    ctx.trace_stop()
    window_wall = time.perf_counter() - t_open - ctx.profiler_s
    cpu_s = time.process_time() - cpu_open
    ctx.window_closes()

    units = steps * traffic["units_per_batch"]
    rates = {"window_rate": stats.window_rate(units, len(walls), window_wall),
             "median_rate": stats.median_rate(units, walls)}
    print("round_walls_s", " ".join(f"{w:.6f}" for w in walls), flush=True)
    print("round_dispatch_s", " ".join(f"{d:.6f}" for d in dispatch),
          flush=True)
    print(f"rate_window {rates['window_rate']:.4f} "
          f"rate_median {rates['median_rate']:.4f} "
          f"stall_share {stats.stall_share(walls, window_wall):.6f} "
          f"window_wall_s {window_wall:.6f} host_cpu_s {cpu_s:.3f} "
          f"setup_s {ctx.setup_s:.3f}", flush=True)
    driver.close_source()
    plain = traffic["plain"]
    del driver, items, traffic, net
    gc.collect()
    _say_reference_start(ctx.devices)

    t_ref = time.perf_counter()
    want = check_train.follow_reference(ref, config, seed, plain)
    rows = check_train.compare(got.readings(), want, wl["limits"],
                               config.get("check_leaves_left_out"))
    leaves = check_train.detail(got.readings(), want)
    print(f"reference_s {time.perf_counter() - t_ref:.3f}", flush=True)
    return {"rows": rows, "check_detail": leaves, "attempted": len(walls),
            "failed": 0, "walls": walls, "window_wall": window_wall,
            "units_per_round": units, "quantities": rates}
