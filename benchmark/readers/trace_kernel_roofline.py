"""A kernel's share of its roofline: the least time the chip could take
for one call (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, from the function `benchmark/kernels/<function>.py` at the
cell's shapes) over the mean device duration of the trace's events whose
name matches `pattern`, in percent. Says which of the two bounds it."""

from benchmark import peaks, spec


def read(obs, args):
    tr, ctx = obs["trace"], obs["ctx"]
    if tr is None:
        return None
    durations = tr.op_durations(args["pattern"])
    if not durations:
        return None
    for label, n, mean_s in tr.matched_labels(args["pattern"])[:4]:
        print(f"roofline match: {n} x {label} mean {mean_s * 1e6:.1f} us",
              flush=True)
    shapes = {k: (ctx.config["model"].get(v, ctx.workload["traffic"].get(v))
                  if isinstance(v, str) else v)
              for k, v in args["shapes"].items()}
    flops, nbytes = spec.module("kernels", args["function"]) \
        .flops_and_bytes(**shapes)
    peak = peaks.for_kind(ctx.devices[0].device_kind)
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    mean = sum(durations) / len(durations)
    print(f"roofline {args['pattern']!r}: {len(durations)} calls, mean "
          f"{mean * 1e6:.1f} us, least {max(t_flops, t_bytes) * 1e6:.1f} us "
          f"(bound by {'compute' if t_flops >= t_bytes else 'memory'}: "
          f"{t_flops * 1e6:.1f} us compute, {t_bytes * 1e6:.1f} us bytes)",
          flush=True)
    return 100.0 * max(t_flops, t_bytes) / mean
