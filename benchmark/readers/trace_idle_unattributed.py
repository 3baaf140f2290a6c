"""The share of the device's idle time that no span of the program
accounts for, in percent. The idle gaps of the traced window are found as
`Trace.idle_gaps` finds them (the first chip's, between its operations,
inside the benchmark's spans); each is put down to the innermost (the
shortest) **program** span of the `/host:CPU` plane that is open at its
middle: a span whose name starts with one of `args["prefixes"]` (`fit.`,
`etl.`), which the program's tracer forwards to the profiler while a
trace is open. The reading is the idle time of the gaps that fall in no
such span over all idle time; the five longest gaps go on an earlier
line with their span. A trace without a program span gives nothing."""

from __future__ import annotations

from benchmark import trace, xspace


def attribute(gaps, spans):
    """[(span name or None, gap length)] for [(start, end)] gaps and
    [(name, start, end)] spans, longest gap first."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1]):
        mid = (s + e) / 2
        open_ = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
        out.append((min(open_)[1] if open_ else None, e - s))
    return out


def read(obs, args):
    tr, ctx = obs["trace"], obs["ctx"]
    if tr is None:
        return None
    spans = xspace.load_dir(ctx.trace_dir).host_spans(args["prefixes"])
    if not spans:
        return None
    ops = next(iter(tr.devices.values()))
    found = trace.gaps([(s, e) for _, s, e in tr._clipped(ops)],
                       tr.t0, tr.t1)
    named = attribute(found, spans)
    idle = sum(length for _, length in named)
    if not idle:
        return 0.0
    print("idle gaps by program span: " + "; ".join(
        f"{name or 'unattributed'} {length * trace.NS * 1e3:.3f} ms"
        for name, length in named[:5]), flush=True)
    by_span = {}
    for name, length in named:
        by_span[name] = by_span.get(name, 0.0) + length
    print("idle time by program span: " + "; ".join(
        f"{name or 'unattributed'} {100.0 * t / idle:.1f}%"
        for name, t in sorted(by_span.items(), key=lambda kv: -kv[1])),
        flush=True)
    return 100.0 * by_span.get(None, 0.0) / idle
