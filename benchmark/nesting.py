"""Self time of nested intervals: an interval's length less the parts of
it that the intervals nested directly inside it cover. The device's
`XLA Ops` line nests a `while` around its body's operations, and a
thread's spans nest by construction; in both, two intervals either nest
or lie apart, so the self times of a line add up to the length of the
union of its intervals."""

from __future__ import annotations


def self_times(intervals):
    """[(start, end, key)] -> [(key, self length, start, end)], in order
    of start. An interval that starts inside another and ends after it
    (clock jitter at a boundary) is cut to its parent's end."""
    out, stack = [], []  # stack of [start, end, key, covered by children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, key, covered = stack.pop()
            out.append((key, (e - s) - covered, s, e))
            if stack:
                stack[-1][3] += e - s

    for s, e, key in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
        stack.append([s, e, key, 0.0])
    close(float("inf"))
    out.sort(key=lambda r: r[2])
    return out
