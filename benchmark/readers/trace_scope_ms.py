"""Device time a step spends under one of the program's scopes, in ms:
the summed **self** time (an event's duration less the events nested in
it on the `XLA Ops` line: a `while` covers its body's operations) of the
traced window's operations whose `op_name` holds a component matching
`args["scope"]` (a regular expression for one whole component of the
path, so `loss` does not match `V.loss_head.Dense`), averaged over the
chips and divided by the number of `fit.step` spans in the same window
of the same trace: steps are counted where the work happens.

`args["backward"]`, where given, keeps the operations whose path does
(true) or does not (false) hold `transpose(`, which is how JAX marks the
backward pass's part of a scope. `args["invert"]` keeps the operations
that match *no* such component. A fusion that spans two scopes has one
`op_name`, its root's: it goes to that scope whole, which is what the
trace gives (a weight gradient fused with the updater's arithmetic is
booked to whichever of them XLA made the root).

`op_name` comes from the raw `XSpace` proto's event metadata
(`benchmark/xspace.py`), not from xprof's `hlo_stats` table: the table
sums over the whole trace, and these readings are cut to the window that
`device.busy_s` is cut to, so that the step's parts can be held against
it. An operation the compiler made and left without a name (a copy into
another layout, a fusion rooted at a convert it inserted) is lent its
nearest named neighbour's (`benchmark/hlo_names.py`). With
`args["sum_with"]` (names of other metrics of this reader) the reading is
printed beside theirs, their sum, the busy time of a step and the time
in operations with a lent name. A trace without `fit.step` spans, or
without a matching operation, gives nothing."""

from __future__ import annotations

import functools
import re

from benchmark import nesting, spec, xspace

STEP_SPAN = "fit.step"
BACKWARD_MARK = "transpose("


def matcher(args):
    """op_name -> bool, from a metric's `args`."""
    rx = re.compile(r"(?:^|[/(])(?:" + args["scope"] + r")(?:$|[/):])")
    backward, invert = args.get("backward"), bool(args.get("invert"))

    def match(op_name):
        if bool(rx.search(op_name)) == invert:
            return False
        return backward is None or (BACKWARD_MARK in op_name) == backward
    return match


@functools.lru_cache(maxsize=2)
def window_self_times(view, t0, t1):
    """[(op_name, label, self ns, lent)] of the operations inside
    [t0, t1], every chip's, and the number of chips (kept: a cell's
    metrics of this reader share one pass over the events)."""
    rows = []
    for ops in view.device_ops.values():
        clipped = [(max(s, t0), min(e, t1), (op_name, label, lent))
                   for s, e, op_name, label, lent in ops
                   if e > t0 and s < t1]
        rows += [(key[0], key[1], self_ns, key[2])
                 for key, self_ns, _, _ in nesting.self_times(clipped)]
    return rows, len(view.device_ops)


def scope_ms(rows, n_chips, steps, args):
    """(ms a step, matched [(label, op_name, ns)]) for one metric."""
    match = matcher(args)
    hit = [(label, op_name, ns) for op_name, label, ns, _ in rows
           if match(op_name)]
    return sum(ns for _, _, ns in hit) / n_chips / steps * 1e-6, hit


def read(obs, args):
    tr, ctx = obs["trace"], obs["ctx"]
    if tr is None:
        return None
    view = xspace.load_dir(ctx.trace_dir)
    steps = len(view.host_spans([STEP_SPAN], tr.t0, tr.t1))
    if not steps:
        return None
    rows, n_chips = window_self_times(view, tr.t0, tr.t1)
    value, hit = scope_ms(rows, n_chips, steps, args)
    if not hit:
        return None
    largest = {}
    for label, op_name, ns in hit:
        key = (label.split(" ")[0], op_name)
        largest[key] = largest.get(key, 0.0) + ns
    top = sorted(largest.items(), key=lambda kv: -kv[1])[:3]
    print(f"scope_ms {args['scope']!r} backward={args.get('backward')} "
          f"invert={bool(args.get('invert'))}: {len(hit)} events in {steps} "
          f"steps, {value:.4f} ms a step; largest: " + "; ".join(
              f"{name} [{op_name}] {ns / n_chips / steps * 1e-6:.3f} ms"
              for (name, op_name), ns in top), flush=True)
    if args.get("sum_with"):
        parts = {name: scope_ms(rows, n_chips, steps,
                                spec.layer_metric(name)["args"])[0]
                 for name in args["sum_with"]}
        total = value + sum(parts.values())
        lent = sum(r[2] for r in rows if r[3]) / n_chips / steps * 1e-6
        print("step parts: " + " + ".join(
            f"{n} {v:.4f}" for n, v in parts.items())
            + f" + this {value:.4f} = {total:.4f} ms; device busy "
            f"{tr.busy_s / steps * 1e3:.4f} ms a step ({steps} steps); "
            f"{lent:.4f} ms of it in operations the compiler made, named "
            "by their nearest named neighbour", flush=True)
    return value
