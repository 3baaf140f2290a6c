"""`setup_s` of the traced run, parted from inside the program: the
interval `[window_t0 - setup_s, window_t0]` on `perf_counter`, the clock of
the window, of the program's tracer and of its start-up marks
(`utils.compile_cache.startup_marks()`).

* `before_program`: the mark `program_entered` less the interval's start
  (interpreter, `import jax`, `jax.devices()`, the harness's imports),
  clamped to the interval: a process that imported the package before the
  run began reads 0;
* `net_init`, `trace`, `lower`, `backend`, `cache_load`: the self time (a
  span's length less the spans nested in it) of the spans `net.init`,
  `compile.trace`, `compile.lower`, `compile.backend`,
  `compile.cache_load` that lie inside the interval on the thread that ran
  set-up (the reader's own: the harness runs both on one thread);
* `steps`: the self time of every `fit.*` span there (the checked steps
  and the warm rounds as the loop ran them, less the compile spans nested
  in their `fit.dispatch`);
* `unattributed`: the rest, taken from the other side: the gaps between
  the program's spans (the harness's weights, feed and readings, the
  program's imports after its first line) and the self time of spans
  that belong to no part above;
* `first_step`: the mark `first_step` (the first step traced, lowered,
  compiled or loaded, and enqueued) less the interval's start; nothing
  where the process's first step lies before the interval.

The first eight add up to `setup_s`, which `read` checks to 1 ms before it
gives any of them. `args["part"]` chooses the one a metric reports. Once a
run the reader prints one line, `setup_parts`, with all nine, the longest
`compile.*` spans with the function jax names, the functions that took
most self time over all their spans with how often each was traced,
lowered or compiled and the longest of those (jax reports a trace served
from its cache too, in microseconds: a kernel's body traced anew in every
layer shows as `n` times `longest_self_s`, one traced once as `n` spans
whose sum is the longest), the four compile parts by what they lay within
(`net.init`, a `fit.*` span, or no span of the program: the harness's own
jitted functions), the longest gaps with the spans on either side of each,
and what lay on other threads (printed, not summed). A program
without the marks gives nothing."""

from __future__ import annotations

import json
import threading

from benchmark import nesting

SPAN_PART = {"net.init": "net_init", "compile.trace": "trace",
             "compile.lower": "lower", "compile.backend": "backend",
             "compile.cache_load": "cache_load"}
STEP_PREFIX, COMPILE_PREFIX = "fit.", "compile."
SUMMED = ("before_program", "unattributed", "net_init", "steps", "trace",
          "lower", "backend", "cache_load")
LONGEST, MOST = 3, 8


def _part_of(name):
    if name.startswith(STEP_PREFIX):
        return "steps"
    return SPAN_PART.get(name)


def _cause_of(outermost):
    """Whose compile it was, by the outermost span around it: the weights'
    draws (`net.init`), the fit loop's (`fit`), or no span of the program
    (`outside`: the harness's own jitted functions)."""
    if outermost == "net.init":
        return outermost
    return "fit" if outermost.startswith(STEP_PREFIX) else "outside"


def split(events, epoch, t_start, t_end, marks, tid):
    """(parts, detail) of the interval [t_start, t_end] from the tracer's
    Chrome events (`ts` and `dur` in microseconds after `epoch`): `parts`
    holds the eight summed values and `first_step` (or None); `detail`
    the longest compile spans, the compile parts by the span they lay
    within, the longest gaps and the other threads' spans by name."""
    mine, elsewhere = [], {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        start = epoch + ev["ts"] * 1e-6
        end = start + ev["dur"] * 1e-6
        if start < t_start or end > t_end:
            continue
        name = ev["name"]
        if ev["tid"] == tid:
            mine.append((start, end, (name, ev.get("args", {}).get("fun"))))
        else:
            elsewhere[name] = elsewhere.get(name, 0.0) + end - start
    entered = min(max(marks["program_entered"], t_start), t_end)
    parts = dict.fromkeys(SUMMED, 0.0)
    parts["before_program"] = entered - t_start
    other_spans, compiles, gaps, by_fun, within = 0.0, [], [], {}, {}
    at, left = entered, "program_entered" if entered > t_start else "start"
    outermost = ""
    for (name, fun), self_s, s, e in nesting.self_times(mine):
        part = _part_of(name)
        if part is None:
            other_spans += self_s
        else:
            parts[part] += self_s
        label = f"{name}[{fun}]" if fun else name
        if s >= at:  # not nested in the span before it: a gap may lie between
            gaps.append((s - at, left, label))
        if e > at:
            at, left, outermost = e, label, name
        if name.startswith(COMPILE_PREFIX):
            compiles.append((e - s, self_s, name, fun))
            n, total, longest = by_fun.get((name, fun), (0, 0.0, 0.0))
            by_fun[name, fun] = n + 1, total + self_s, max(longest, self_s)
            cause = within.setdefault(_cause_of(outermost), {})
            cause[part] = cause.get(part, 0.0) + self_s
    gaps.append((t_end - at, left, "window"))
    parts["unattributed"] = sum(g[0] for g in gaps) + other_spans
    first = marks.get("first_step")
    parts["first_step"] = (first - t_start
                           if first is not None and first >= t_start else None)
    detail = {
        "other_spans_self": other_spans,
        "longest_compiles": [
            {"span": n, "fun": f, "s": d, "self_s": ss} for d, ss, n, f in
            sorted(compiles, key=lambda c: -c[0])[:LONGEST]],
        "compiles_by_fun": [
            {"span": n, "fun": f, "n": k, "self_s": t, "longest_self_s": m}
            for (n, f), (k, t, m) in
            sorted(by_fun.items(), key=lambda kv: -kv[1][1])[:MOST]],
        "compile_within": within,
        "longest_gaps": [
            {"after": a, "before": b, "s": g} for g, a, b in
            sorted(gaps, key=lambda g: -g[0])[:LONGEST]],
        "other_threads": elsewhere}
    return parts, detail


def _startup_marks():
    from deeplearning4j_tpu.utils import compile_cache
    marks = getattr(compile_cache, "startup_marks", None)
    return marks() if marks else None


def _parts(obs):
    """The run's parts, computed and printed once: `obs` is the one dict
    the harness hands every reader of a run."""
    if "setup_parts" in obs:
        return obs["setup_parts"]
    ctx, marks, parts = obs["ctx"], _startup_marks(), None
    if marks is not None and ctx.setup_s is not None:
        from deeplearning4j_tpu import telemetry
        tracer = telemetry.get_tracer()
        parts, detail = split(
            tracer.chrome_trace()["traceEvents"], tracer.epoch,
            ctx.window_t0 - ctx.setup_s, ctx.window_t0, marks,
            threading.get_ident())
        total = sum(parts[p] for p in SUMMED)
        if abs(total - ctx.setup_s) >= 1e-3:
            raise AssertionError(
                f"setup_parts: the parts sum to {total:.6f} s, setup_s is "
                f"{ctx.setup_s:.6f}: {parts}")
        print("setup_parts", json.dumps(
            {"setup_s": ctx.setup_s, **parts, **detail}), flush=True)
    obs["setup_parts"] = parts
    return parts


def read(obs, args):
    parts = _parts(obs)
    return None if parts is None else parts[args["part"]]
