"""The share of the window's wall that the host spent in some of the
program's own spans: the self time (a span's duration less the spans
nested in it on the same thread) of the spans named `args["spans"]` that
lie inside the measured window, over the window's wall, in percent.

The spans are the program's (`telemetry.span`, which records only in the
traced run: that run switches telemetry on), read from the tracer's
in-memory buffer, which holds the whole window and not only the profiled
rounds. A span's clock is `perf_counter`, as the window's is. With
`args["longest_round"]` the reader also prints the window's longest
`fit.round` with the self time of each `fit.*` span inside it, so that a
stalled round names the boundary it sat in. A program without the named
spans (or with telemetry off) gives nothing."""

from __future__ import annotations

from benchmark import nesting


def window_self_times(events, epoch, t0, t1):
    """[(name, self seconds, start, end, tid)] of the complete ("X")
    Chrome events that lie inside [t0, t1], `perf_counter` seconds."""
    by_thread = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        start = epoch + ev["ts"] * 1e-6
        end = start + ev["dur"] * 1e-6
        if start >= t0 and end <= t1:
            by_thread.setdefault(ev["tid"], []).append(
                (start, end, ev["name"]))
    return [(name, self_s, s, e, tid) for tid, ivs in by_thread.items()
            for name, self_s, s, e in nesting.self_times(ivs)]


def longest_round(rows, round_name="fit.round", prefix="fit."):
    """(round's seconds, {span name: self seconds inside it}) for the
    longest `round_name` span of `rows`, or None."""
    rounds = [r for r in rows if r[0] == round_name]
    if not rounds:
        return None
    _, _, r0, r1, tid = max(rounds, key=lambda r: r[3] - r[2])
    inside = {}
    for name, self_s, s, e, t in rows:
        if t == tid and s >= r0 and e <= r1 and name.startswith(prefix):
            inside[name] = inside.get(name, 0.0) + self_s
    return r1 - r0, inside


def read(obs, args):
    from deeplearning4j_tpu import telemetry
    ctx = obs["ctx"]
    tracer = telemetry.get_tracer()
    rows = window_self_times(tracer.chrome_trace()["traceEvents"],
                             tracer.epoch, ctx.window_t0, ctx.window_t1)
    if args.get("longest_round"):
        found = longest_round(rows)
        if found:
            print(f"longest fit.round {found[0]:.6f} s, self seconds "
                  "inside: " + " ".join(
                      f"{n}={t:.6f}" for n, t in sorted(found[1].items())),
                  flush=True)
    mine = [r[1] for r in rows if r[0] in args["spans"]]
    if not mine:
        return None
    print(f"span_share {'+'.join(args['spans'])}: {len(mine)} spans, "
          f"{sum(mine):.6f} s self", flush=True)
    return 100.0 * sum(mine) / obs["result"]["window_wall"]
