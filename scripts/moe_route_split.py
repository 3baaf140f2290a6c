"""The scope `moe_route` parted by operation, from a device trace.

`python3 scripts/moe_route_split.py layer [--root DIR] [--cells a,b]`
    one routed layer's value-and-grad alone (`moe.routed_experts` under the
    bfloat16 policy: float32 token rows and leaves, bfloat16 products) at the routed
    cells' shapes, five traced calls each, and the device's self time a
    call under `moe_route` by operation. `--root` names another checkout
    (the parent's, from `git archive`) whose package is traced instead.

`python3 scripts/moe_route_split.py trace DIR [--scope NAME]`
    the same split of a traced benchmark run (`python3 -m benchmark.run
    --trace 1` leaves `.bench_out/trace-<cell>`), a `fit.step` span;
    `--scope exit_head` parts another of the program's scopes the same way.

Both need the chip (`chiprun -- python3 scripts/moe_route_split.py ...`):
a CPU trace has no device plane, and the split then prints nothing.
PERF.md section 5 has the readings (PR 51)."""

import argparse
import collections
import os
import re
import sys
import tempfile

SCOPE = "moe_route"
# N tokens, width d, E experts, top k, experts held, score, expert width f,
# gated, activation, the layer made again in the backward pass
CELLS = {
    "sdar": (8192, 2048, 128, 8, 16, "softmax", 768, True, "silu", True),
    "qwen3next": (4096, 2048, 512, 10, 16, "softmax", 512, True, "silu",
                  False),
    "lfm2": (8192, 2048, 64, 4, 8, "sigmoid", 1536, True, "silu", False),
    "nemotron3nano": (4096, 2688, 128, 6, 8, "sigmoid", 1856, False,
                      "relu2", False),
    "glm47flash": (4096, 2048, 64, 4, 8, "sigmoid", 1536, True, "silu",
                   False),
}


def split(view, steps, t0=float("-inf"), t1=float("inf"), out=sys.stdout,
          scope=SCOPE):
    """Self time a step of the device operations under `scope` inside
    [t0, t1], by pass, opcode and result shape, largest first."""
    from benchmark.readers.trace_scope_ms import window_self_times
    rows, chips = window_self_times(view, t0, t1)
    by_kind = collections.defaultdict(lambda: [0.0, 0, "", ""])
    for op_name, label, self_ns, _ in rows:
        if scope in op_name:
            name, _, kind = label.partition(" ")
            kind = re.sub(r"\{[^}]*\}", "", kind)  # the layouts
            row = by_kind["bwd " + kind if "transpose(" in op_name
                          else "fwd " + kind]
            row[0] += self_ns
            row[1] += 1
            row[2], row[3] = name, op_name
    per = lambda ns: ns / max(chips, 1) / steps * 1e-6
    print(f"{per(sum(r[2] for r in rows)):9.4f} ms a step busy, "
          f"{per(sum(r[0] for r in by_kind.values())):9.4f} under {scope} "
          f"({steps} steps)", file=out)
    for kind, (ns, n, name, op_name) in sorted(by_kind.items(),
                                               key=lambda kv: -kv[1][0]):
        print(f"{per(ns):9.4f} ms  x{n / max(chips, 1) / steps:<5g} {kind}  "
              f"[{name}: {op_name[-90:]}]", file=out)


def layer_case(name, calls=5):
    import jax
    import jax.numpy as jnp

    from benchmark import xspace
    from deeplearning4j_tpu.nn import activations
    from deeplearning4j_tpu.nn.layers import moe
    from deeplearning4j_tpu.utils import dtypes

    dtypes.bf16_policy()
    n, d, e, k, held, score, f, gated, act, again = CELLS[name]
    key = jax.random.split(jax.random.PRNGKey(51), 6)
    nrm = lambda kk, scale, *shape: scale * jax.random.normal(
        kk, shape, jnp.float32)
    x = nrm(key[0], 1.0, n, d)
    leaves = (nrm(key[1], d ** -0.5, d, e),
              nrm(key[2], 0.02, held, d, f) if gated else None,
              nrm(key[3], 0.02, held, d, f), nrm(key[4], 0.02, held, f, d))
    bias = None if score == "softmax" else jnp.zeros((e,), jnp.float32)

    def routed(x, leaves):
        return moe.routed_experts(
            x, *leaves, bias, top_k=k, held=(0, held), scale=1.0,
            act=activations.get(act), score=score)

    if again:
        routed = jax.checkpoint(routed)

    def loss(x, leaves):
        y, here, _ = routed(x, leaves)
        return jnp.sum(jnp.square(y)), jnp.sum(here)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    (_, rows), _ = jax.block_until_ready(step(x, leaves))
    jax.block_until_ready(step(x, leaves))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(calls):
            out = step(x, leaves)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        view = xspace.load_dir(trace_dir)
        print(f"== {name}: N {n} d {d} E {e} k {k} held {held} {score} "
              f"f {f}, {int(rows)} of {n * k} rows here, "
              f"{jax.devices()[0].device_kind}", flush=True)
        if view is not None:
            split(view, calls)
        sys.stdout.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.partition("\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    one = sub.add_parser("layer")
    one.add_argument("--root", default=None)
    one.add_argument("--cells", default=",".join(CELLS))
    step = sub.add_parser("trace")
    step.add_argument("dir")
    step.add_argument("--scope", default=SCOPE)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(getattr(args, "root", None) or here))
    if args.what == "layer":
        for name in args.cells.split(","):
            layer_case(name)
        return 0
    from benchmark import trace, xspace
    from benchmark.readers.trace_scope_ms import STEP_SPAN
    view, window = xspace.load_dir(args.dir), trace.load(args.dir)
    if view is None or window is None:
        print(f"no device trace under {args.dir}")
        return 1
    steps = len(view.host_spans([STEP_SPAN], window.t0, window.t1))
    split(view, steps or 1, window.t0, window.t1, scope=args.scope)
    return 0


if __name__ == "__main__":
    sys.exit(main())
