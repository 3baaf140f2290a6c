"""The growth of one of the program's registry metrics (a counter's
value, a histogram's sum) inside the window. With `per_window_wall` the
growth is given as a share of the window's wall, in percent. The registry
records only in the traced run, which switches telemetry on."""


def read(obs, args):
    ctx = obs["ctx"]
    if ctx.counters_open is None or ctx.counters_close is None:
        return None
    name = args["metric"]
    if name not in ctx.counters_close and not args.get("absent_is_zero"):
        return None
    delta = (ctx.counters_close.get(name, 0.0)
             - ctx.counters_open.get(name, 0.0))
    if args.get("per_window_wall"):
        return 100.0 * delta / (ctx.window_t1 - ctx.window_t0)
    return delta
