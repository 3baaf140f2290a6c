"""Report cross-run regressions over BENCH artifacts.

``python analyze_bench.py [--gate] [paths...]`` — every BENCH_*.json stream
in the repo (JSONL appended run over run by tier1.sh) is loaded, records
are aligned per config/variant IN FILE ORDER, and the latest record of each
series is compared against the median of its predecessors. A headline
drifting past ``--tolerance`` percent in the bad direction (direction
inferred from the unit: ms/seconds regress UP, throughput regresses DOWN;
goodput fractions regress DOWN) is flagged; ``--gate`` turns flags into a
nonzero exit so a regression fails the run the same way a broken test
does. Failed records never count; preflight and chip records never mix
(``preflight``, ``platform`` and ``device_kind`` are variant fields, so they
live in different series).
"""

import argparse
import glob
import json
import os
import sys

#: record fields that distinguish A/B variants of one config, and the
#: device stamp every bench record carries
VARIANT_FIELDS = ("batch", "hw", "remat", "fused_conv", "hidden", "masked",
                  "seq", "fused_kernel", "d_model", "n_layers",
                  "fused_attention", "vocab", "dim", "n_chips",
                  "flash_block", "preflight", "platform", "device_kind",
                  "device_count")

#: units where a LARGER value is the regression (latencies, walls)
LOWER_IS_BETTER_UNITS = ("ms", "s/iter", "seconds", "sec/")


def load(path):
    """Records from one artifact: a JSON doc with results[], a JSON
    list, or a JSONL stream (BENCH_smoke.json) — event lines and
    non-record lines are dropped either way."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        data = [json.loads(ln) for ln in text.splitlines() if _is_json(ln)]
    if isinstance(data, dict):
        recs = data.get("results") or data.get("records") or []
    else:
        recs = data
    if isinstance(recs, dict):
        recs = list(recs.values())
    return [r for r in recs if isinstance(r, dict)]


def _is_json(line):
    line = line.strip()
    if not line or not line.startswith("{"):
        return False
    try:
        json.loads(line)
        return True
    except ValueError:
        return False


def fmt(v):
    return "-" if v is None else (f"{v:.4g}" if isinstance(v, float) else v)


# ---- cross-run regression reporting ------------------------------------

def series_key(rec):
    """One comparable series: config + every variant field the record
    carries. Records that differ in shape/preflight/device never
    compare against each other."""
    return (rec.get("config") or rec.get("metric"),) + tuple(
        (f, str(rec.get(f))) for f in VARIANT_FIELDS if f in rec)


def _usable(rec):
    return (rec.get("config") or rec.get("metric")) \
        and "FAILED" not in str(rec.get("metric", "")) \
        and isinstance(rec.get("value"), (int, float))


def _headlines(rec):
    """{name: (value, higher_is_better)} of the record's gateable
    numbers."""
    out = {}
    unit = str(rec.get("unit") or "")
    lower = any(u in unit for u in LOWER_IS_BETTER_UNITS)
    out["value"] = (float(rec["value"]), not lower)
    if isinstance(rec.get("mfu"), (int, float)):
        out["mfu"] = (float(rec["mfu"]), True)
    gp = rec.get("goodput")
    if isinstance(gp, dict) and \
            isinstance(gp.get("goodput_fraction"), (int, float)) \
            and gp.get("steps"):
        # only windows that saw real steps: a serving-only config's
        # all-idle ledger is not a trainer regression signal
        out["goodput_fraction"] = (float(gp["goodput_fraction"]), True)
    fleet = rec.get("fleet")
    if isinstance(fleet, dict):
        # the demand plane's externally-measured numbers: the probe's
        # wire-path p50 (lower is better) and the usage ledger's served
        # rows (a shrinking ledger on the same legs means lost demand
        # accounting, not a faster run)
        if isinstance(fleet.get("probe_latency_p50_ms"), (int, float)):
            out["probe_latency_p50_ms"] = (
                float(fleet["probe_latency_p50_ms"]), False)
        if isinstance(fleet.get("ledger_rows"), (int, float)):
            out["usage_ledger_rows"] = (float(fleet["ledger_rows"]), True)
    if isinstance(rec.get("padded_waste_ratio"), (int, float)):
        # the 2-D shape grid's padded/real token ratio on its grid leg:
        # 1.0 is zero padding, growth means the seq buckets stopped
        # fitting the workload (the headline the grid exists to hold
        # down; the record's `value` carries the flat-vs-grid cut)
        out["padded_waste_ratio"] = (float(rec["padded_waste_ratio"]),
                                     False)
    return out


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


def regressions(paths, tolerance_pct=25.0):
    """Align records per series across ``paths`` (file order = run
    order) and compare each series' LATEST record against the median of
    its predecessors. Returns (flags, summaries): flags are dicts for
    every headline drifting past tolerance in the bad direction,
    summaries describe every series with >= 2 comparable records."""
    by_series = {}
    for path in paths:
        try:
            recs = load(path)
        except (OSError, ValueError):
            continue
        for rec in recs:
            if _usable(rec):
                by_series.setdefault(series_key(rec), []).append(rec)
    flags, summaries = [], []
    for key, recs in sorted(by_series.items()):
        if len(recs) < 2:
            continue
        latest, history = recs[-1], recs[:-1]
        for name, (cur, higher_better) in _headlines(latest).items():
            hist_vals = [h[0] for h in
                         (_headlines(r).get(name) for r in history)
                         if h is not None]
            if not hist_vals:
                continue
            base = _median(hist_vals)
            if base == 0:
                continue
            delta_pct = 100.0 * (cur - base) / abs(base)
            regressed = (delta_pct < -tolerance_pct if higher_better
                         else delta_pct > tolerance_pct)
            row = {"config": key[0], "series": key, "headline": name,
                   "baseline": base, "latest": cur,
                   "delta_pct": round(delta_pct, 1),
                   "n_prior_runs": len(hist_vals),
                   "higher_is_better": higher_better,
                   "regressed": regressed}
            summaries.append(row)
            if regressed:
                flags.append(row)
    return flags, summaries


def report_regressions(paths, tolerance_pct=25.0, gate=False):
    flags, summaries = regressions(paths, tolerance_pct)
    if not summaries:
        print("analyze_bench: no series with >= 2 comparable records "
              f"across {len(paths)} artifact(s) — nothing to compare")
        return 0
    print(f"== cross-run regression report ({len(paths)} artifact(s), "
          f"tolerance {tolerance_pct:g}%) ==")
    print(f"{'config':>14} {'headline':>18} {'baseline':>10} "
          f"{'latest':>10} {'delta%':>8} {'runs':>5}  verdict")
    for row in summaries:
        verdict = "REGRESSED" if row["regressed"] else "ok"
        print(f"{str(row['config']):>14} {row['headline']:>18} "
              f"{fmt(row['baseline']):>10} {fmt(row['latest']):>10} "
              f"{row['delta_pct']:>+8.1f} {row['n_prior_runs']:>5}  "
              f"{verdict}")
    if flags:
        print(f"\n{len(flags)} headline(s) regressed past "
              f"{tolerance_pct:g}%:")
        for row in flags:
            direction = "down" if row["higher_is_better"] else "up"
            print(f"  {row['config']}.{row['headline']}: "
                  f"{fmt(row['baseline'])} -> {fmt(row['latest'])} "
                  f"({row['delta_pct']:+.1f}%, bad direction: {direction})")
    else:
        print("\nno regressions past tolerance")
    return 1 if (gate and flags) else 0


def default_artifacts():
    """Every BENCH_*.json next to this script."""
    here = os.path.dirname(os.path.abspath(__file__))
    return sorted(glob.glob(os.path.join(here, "BENCH_*.json")))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="*",
                   help="artifacts to analyze (default: every "
                        "BENCH_*.json next to this script)")
    p.add_argument("--gate", action="store_true",
                   help="exit 1 when any headline regressed past "
                        "tolerance")
    p.add_argument("--tolerance", type=float, default=25.0,
                   help="regression tolerance band, percent (default 25)")
    args = p.parse_args(argv)
    return report_regressions(args.paths or default_artifacts(),
                              tolerance_pct=args.tolerance, gate=args.gate)


if __name__ == "__main__":
    sys.exit(main())
