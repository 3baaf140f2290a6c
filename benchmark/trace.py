"""The reduction from a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to numbers: device busy time as the union of
the intervals in which an operation ran, the idle share of the traced
window, a kernel's summed time, the operations that took most time, and
the longest idle gaps by the benchmark's host span open at the time.

What a TPU trace looks like (looked at by hand, PERF.md section 6): one
plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one event
per executed HLO operation (start and duration in ns); the host's
`TraceAnnotation` spans are events of the `/host:CPU` plane's thread
lines, on the same clock. The traced window is the extent of the
benchmark's own spans (`bench.*`): a round opens on an idle device and
closes on a fetch, so the device's work for those spans lies inside it."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NS = 1e-9


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(trace_dir):
    """The newest trace under `trace_dir`, or None if there is none or it
    holds no device plane (a CPU rehearsal)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    import jax
    tr = Trace.from_profile(jax.profiler.ProfileData.from_file(path))
    return tr if tr.devices else None


def op_label(text, width=120):
    """A short, stable label for a device operation from the HLO text the
    trace names it by (`%name = shape opcode(operands), attributes`):
    `name opcode[:kind or custom-call target] shape`. Operands are left
    out, so a pattern matches the operation itself and never its inputs."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:width]
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.partition("(")[0]
    tag = re.search(r'custom_call_target="([^"]+)"|kind=(\w+)', rest)
    if tag:
        opcode += ":" + (tag.group(1) or tag.group(2))
    return f"{name.lstrip('%')} {opcode} {shape}"[:width]


def union_length(intervals):
    """Total length covered by [(start, end)] intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s if cur_e is not None else 0.0)


def gaps(intervals, t0, t1):
    """[(start, end)] of the parts of [t0, t1] no interval covers."""
    out, at = [], t0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """`devices`: {plane name: [(op label, start_ns, end_ns)]};
    `spans`: [(span name, start_ns, end_ns)] of the benchmark's spans."""

    def __init__(self, devices, spans):
        self.devices, self.spans = devices, spans
        if spans:
            self.t0 = min(s for _, s, _ in spans)
            self.t1 = max(e for _, _, e in spans)
        else:  # no span of ours: the extent of the device's own work
            ops = [o for d in devices.values() for o in d]
            self.t0 = min((s for _, s, _ in ops), default=0.0)
            self.t1 = max((e for _, _, e in ops), default=0.0)

    @classmethod
    def from_profile(cls, profile):
        devices, spans = {}, []
        for plane in profile.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[plane.name] = [
                            (op_label(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans += [(e.name, e.start_ns,
                               e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls(devices, spans)

    def _clipped(self, ops):
        return [(n, max(s, self.t0), min(e, self.t1)) for n, s, e in ops
                if e > self.t0 and s < self.t1]

    @property
    def window_s(self):
        return (self.t1 - self.t0) * NS

    @property
    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [union_length([(s, e) for _, s, e in self._clipped(ops)])
                    for ops in self.devices.values()]
        return sum(per_chip) / len(per_chip) * NS

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s

    def op_durations(self, pattern):
        """Durations in seconds of the window's device operations whose
        name matches `pattern`, over all chips."""
        rx = re.compile(pattern)
        return [(e - s) * NS for ops in self.devices.values()
                for n, s, e in self._clipped(ops) if rx.search(n)]

    def matched_labels(self, pattern):
        """[(label, calls, mean seconds)] of the operations `pattern`
        matches, most calls first: what to read before trusting it."""
        rx, seen = re.compile(pattern), {}
        for ops in self.devices.values():
            for n, s, e in self._clipped(ops):
                if rx.search(n):
                    c, t = seen.get(n, (0, 0.0))
                    seen[n] = (c + 1, t + (e - s) * NS)
        return sorted(((n, c, t / c) for n, (c, t) in seen.items()),
                      key=lambda x: -x[1])

    def top_ops(self, k=10):
        """[(name, seconds)]: the operations with the largest summed time
        in the window, averaged over the chips."""
        total = {}
        for ops in self.devices.values():
            for n, s, e in self._clipped(ops):
                total[n] = total.get(n, 0.0) + (e - s) * NS
        n_chips = len(self.devices)
        return sorted(((n, t / n_chips) for n, t in total.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k=5):
        """[(span name, seconds)]: the longest idle gaps of the first
        chip, each named by the innermost benchmark span open at its
        middle ("unattributed" if none)."""
        ops = next(iter(self.devices.values()))
        found = gaps([(s, e) for _, s, e in self._clipped(ops)],
                     self.t0, self.t1)
        found.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in found[:k]:
            mid = (s + e) / 2
            open_ = [(se - ss, n) for n, ss, se in self.spans
                     if ss <= mid <= se]
            out.append((min(open_)[1] if open_ else "unattributed",
                        (e - s) * NS))
        return out

    def breakdown(self):
        return {"device_ops": [[n, t] for n, t in self.top_ops(10)],
                "idle_gaps": [[n, t] for n, t in self.idle_gaps(5)]}


def describe(path, out):
    """Every plane and line with its first events and their stats: what
    one reads by hand before writing a reduction against a trace."""
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines), "lines", file=out)
        for line in lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), "events", file=out)
            for e in events[:6]:
                print("     ", e.name, e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:12]), file=out)


if __name__ == "__main__":
    import sys
    describe(find_xplane(sys.argv[1]) or sys.argv[1], sys.stdout)
