"""Model FLOP/s utilisation: the operations forward and backward require
per unit of work (the configuration's function under
`benchmark/kernels/`), times the rate over the whole window (the same
number the end-to-end rate is), over chips times the published bf16
peak, in percent."""

from benchmark import peaks, spec


def read(obs, args):
    ctx, r = obs["ctx"], obs["result"]
    rate = r.get("quantities", {}).get("window_rate")
    if not rate:
        return None
    per_unit = spec.module("kernels", ctx.config["flops"]) \
        .train_flops_per_unit(ctx.config["model"], ctx.workload["traffic"])
    peak = peaks.for_kind(ctx.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_unit * rate / (len(ctx.devices) * peak)
