"""The arithmetic of a reading. A run takes many round walls. The
end-to-end rate is all the work of the window over all of its wall; the
work of one round over the MEDIAN round wall and the stall share stand
beside it as per-layer statistics that a rare slow round does not move."""

from __future__ import annotations

import statistics


def window_rate(units_per_round, rounds, window_wall):
    """All the work of the window over all of its wall."""
    return units_per_round * rounds / window_wall


def median_rate(units_per_round, walls):
    """Work of one round over the median round wall."""
    return units_per_round / statistics.median(walls)


def stall_share(walls, window_wall=None):
    """Share of the window's wall beyond `rounds x median`: what the rate
    over the whole window lost to slow rounds."""
    window_wall = sum(walls) if window_wall is None else window_wall
    return 1.0 - len(walls) * statistics.median(walls) / window_wall


def quartile_spread(values):
    """Distance between the first and third quartile over the median, as
    the driver takes it (`statistics.quantiles(values, n=4)`)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
