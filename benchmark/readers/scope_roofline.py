"""A scope's share of its roofline: the least time the chip could take for
what one step does under one of the program's scopes (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from the function
`benchmark/kernels/<function>.py` at the cell's shapes) over the device
self time a step under that scope (`trace_scope_ms`'s reading), in
percent. Says which of the two bounds it.

`args["shapes"]` maps the function's arguments to numbers, or to names
looked up in the configuration's `model` and then in the cell's `traffic`;
a list of names is their product, a `[first, end)` pair found under a name
is its length, and `expert_layers` is the number of layers past
`num_dense_layers`. The function counts at the EXPECTED rows; with
`args["rows_counters"]` (rows computed here, rows routed, two of the
program's registry counters) the share of rows the sampled steps really
computed is printed beside the reading, so that a share above 100 can be
told from a count that is too generous."""

from benchmark import peaks, spec, xspace
from benchmark.readers import trace_scope_ms


def _size(ctx, name):
    model, traffic = ctx.config["model"], ctx.workload["traffic"]
    if name == "expert_layers":
        return len(model["layer_types"]) - model["num_dense_layers"]
    v = model.get(name, traffic.get(name))
    if isinstance(v, (list, tuple)):
        return v[1] - v[0]
    return v


def _shape(ctx, v):
    if isinstance(v, str):
        return _size(ctx, v)
    if isinstance(v, list):
        n = 1
        for name in v:
            n *= _size(ctx, name)
        return n
    return v


def read(obs, args):
    tr, ctx = obs["trace"], obs["ctx"]
    if tr is None:
        return None
    view = xspace.load_dir(ctx.trace_dir)
    steps = len(view.host_spans([trace_scope_ms.STEP_SPAN], tr.t0, tr.t1))
    if not steps:
        return None
    rows, n_chips = trace_scope_ms.window_self_times(view, tr.t0, tr.t1)
    ms, hit = trace_scope_ms.scope_ms(rows, n_chips, steps, args)
    if not hit:
        return None
    flops, nbytes = spec.module("kernels", args["function"]).flops_and_bytes(
        **{k: _shape(ctx, v) for k, v in args["shapes"].items()})
    peak = peaks.for_kind(ctx.devices[0].device_kind)
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    least_ms = max(t_flops, t_bytes) * 1e3
    said = ""
    if args.get("rows_counters") and ctx.counters_close is not None:
        here, routed = (ctx.counters_close.get(n, 0.0)
                        - ctx.counters_open.get(n, 0.0)
                        for n in args["rows_counters"])
        if routed:
            said = (f"; rows the sampled steps computed here {here:.0f} of "
                    f"{routed:.0f} routed ({100.0 * here / routed:.3f}%)")
    print(f"scope_roofline {args['scope']!r}: {ms:.4f} ms a step, least "
          f"{least_ms:.4f} ms (bound by "
          f"{'compute' if t_flops >= t_bytes else 'memory'}: "
          f"{t_flops * 1e3:.4f} ms compute, {t_bytes * 1e3:.4f} ms bytes)"
          + said, flush=True)
    return 100.0 * least_ms / ms
