"""Three readings that back a claim in PERF.md and are not part of a run:

`python3 -m benchmark.diagnose plain-fit --workload <cell> --seed <n>`
    the cell's configuration trained as a user would, with none of the
    harness in the way: the factory, `net.init()`'s own weights and
    `net.fit(batches)`, on the cell's batches. Says what rate a plain
    `fit()` reads, beside the harness's.

`python3 -m benchmark.diagnose memory --workload <cell> --seed <n>`
    how much device memory the timed step really needs. The runtime's
    `peak_bytes_in_use` leaves XLA's temp allocation out, so the step is
    run again and again while a ballast of plain buffers grows, until it
    no longer fits: what the chip holds less the largest ballast the step
    still ran beside is the step's real peak, to compare with the
    `memory_peak_bytes` a run reports (`bytes_in_use` plus
    `bytes_reserved`).

`python3 -m benchmark.diagnose budget --workload <cell>`
    what a configuration asks of a chip, from shapes alone (no chip
    needed): the parameters the reference's `init` makes, 12 B of each for
    the program's resident state (float32 weight and Adam's two moments)
    and 16 B for the reference's (weight, gradient, two moments; its peak
    of live arrays measured 20.4 B on the chip, PR 42). Each has to fit
    with its own activations; they never share the chip
    (`benchmark/README.md`, "How `correct` is decided"). Where a TPU is
    present its `bytes_limit` stands beside them.
"""

import argparse
import itertools
import math
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import program, run, spec
from deeplearning4j_tpu.continuous.driver import StepDriver

MIB = 2 ** 20


def _cell(args):
    _, cell, workload, config = spec.load_cell(args.workload, args.root)
    devices = run.require_chips(cell["chips"])
    from deeplearning4j_tpu.utils import compile_cache
    compile_cache.enable_persistent_cache()
    net = program.build(config, args.seed)
    traffic = spec.module("traffic", workload["traffic"]["kind"]).make(
        args.seed, workload["traffic"], config["model"])
    items = [program.feed_item(net, x, y) for x, y in traffic["feed"]]
    return net, items, traffic["units_per_batch"], devices, config


def plain_fit(args):
    net, items, units, _, _ = _cell(args)

    def fit(n):
        t0 = time.perf_counter()
        net.fit(itertools.islice(itertools.cycle(items), n))
        float(net.score_value)
        return time.perf_counter() - t0

    print(f"first fit of {len(items)} steps (compiles or loads) "
          f"{fit(len(items)):.3f} s", flush=True)
    fit(args.steps // 4)
    for _ in range(3):
        wall = fit(args.steps)
        print(f"plain_fit {args.steps} steps in {wall:.4f} s: "
              f"{args.steps * units / wall:.2f} units/s, "
              f"{1e3 * wall / args.steps:.3f} ms/step", flush=True)
    return 0


def memory(args):
    net, items, _, devices, config = _cell(args)
    ref = spec.module("reference", config["reference"])
    program.load_weights(net, *ref.program_layout(
        ref.init(args.seed, config["model"]), ref.init_state(config["model"])))
    driver = StepDriver(net, lambda: itertools.cycle(items))
    dev = devices[0]

    def step():
        driver.run_round(1)
        driver.sync()
        return float(net.score_value)

    step(), step()
    stats = dev.memory_stats()
    print("memory_stats", {k: v for k, v in sorted(stats.items())},
          flush=True)
    limit, live = stats["bytes_limit"], stats["bytes_in_use"]
    scratch = stats.get("bytes_reserved", 0)
    print(f"bytes_limit {limit} bytes_in_use {live} bytes_reserved "
          f"{scratch}: the counters leave {limit - live - scratch} free",
          flush=True)
    chunk = args.chunk_mib * MIB
    ballast, held = [], 0
    # coarse first: up to 1 GiB short of what the counters say is free
    coarse = max(0, (limit - live - scratch - 1024 * MIB) // chunk)
    try:
        for i in itertools.count():
            ballast.append(jax.block_until_ready(
                jnp.zeros((chunk,), jnp.uint8)))
            held += chunk
            if i < coarse and (i + 1) % 8:
                continue
            step()
            print(f"ballast {held} ({held / 2 ** 30:.3f} GiB): the step "
                  f"ran; it needs at most {limit - held} in all, "
                  f"{limit - held - live} beyond the live bytes",
                  flush=True)
    except Exception as e:  # the runtime's out-of-memory error
        print(f"ballast {held} ({held / 2 ** 30:.3f} GiB): "
              f"{type(e).__name__}: {str(e).splitlines()[0][:300]}",
              flush=True)
    return 0


def budget(args):
    _, _, _, config = spec.load_cell(args.workload, args.root)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(args.seed, config["model"]))
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))
    print(f"parameters {n:,} ({n / 1e6:.1f} M), counted from the shapes of "
          f"the reference's init", flush=True)
    for who, each in (("program", 12), ("reference", 16)):
        print(f"{who} {each} B a parameter: {each * n:,} bytes "
              f"({each * n / 2 ** 30:.3f} GiB), before its activations",
              flush=True)
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        limit = dev.memory_stats()["bytes_limit"]
        print(f"bytes_limit {limit:,} ({limit / 2 ** 30:.3f} GiB) on "
              f"{dev.device_kind}: {limit - 16 * n:,} left for the "
              f"reference's activations", flush=True)
    return 0


def main(argv=None, root=spec.REPO_ROOT):
    ap = argparse.ArgumentParser(prog="benchmark.diagnose")
    ap.add_argument("what", choices=("plain-fit", "memory", "budget"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--chunk-mib", type=int, default=128)
    args = ap.parse_args(argv)
    args.root = root
    return {"plain-fit": plain_fit, "memory": memory,
            "budget": budget}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
