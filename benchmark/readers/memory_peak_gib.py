"""The run's `memory_peak_bytes` of the fullest chip in GiB, from the
runtime's byte counters as the window closes: the larger of
`peak_bytes_in_use` and `bytes_in_use + bytes_reserved`
(`run.Context.window_closes`). `peak_bytes_in_use` alone covers live
buffers only; XLA's temp allocation is reserved apart."""


def read(obs, args):
    peak = obs["ctx"].memory_peak_bytes
    return peak / 2 ** 30 if peak else None
