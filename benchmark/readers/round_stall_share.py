"""The benchmark's round clock: the share of the window's wall beyond
`rounds x median round`, in percent: what the whole-window rate lost to
slow rounds."""

from benchmark import stats


def read(obs, args):
    r = obs["result"]
    if not r.get("walls"):
        return None
    return 100.0 * stats.stall_share(r["walls"], r["window_wall"])
