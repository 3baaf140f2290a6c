"""One of the program's registry metrics over another, times `scale`
(100 for a share in percent). By default both are taken as their growth
inside the window (two counters); with `"at": "close"` as they stand when
the window closes (two gauges, which hold the last value set). The
registry records only in the traced run; a metric the program does not
have, or a denominator of zero, gives nothing."""


def read(obs, args):
    ctx = obs["ctx"]
    if ctx.counters_open is None or ctx.counters_close is None:
        return None
    names = args["numerator"], args["denominator"]
    if any(n not in ctx.counters_close for n in names):
        return None
    num, den = (ctx.counters_close[n] for n in names)
    if args.get("at") != "close":
        num -= ctx.counters_open.get(names[0], 0.0)
        den -= ctx.counters_open.get(names[1], 0.0)
    if not den:
        return None
    return args.get("scale", 1.0) * num / den
