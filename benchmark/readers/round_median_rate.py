"""The benchmark's round clock: the work of one round over the median
round wall. The steadier statistic beside the end-to-end rate, which is
taken over the whole window: a rare slow round moves that one and
`fit_stall_share.*`, and not this."""


def read(obs, args):
    return obs["result"].get("quantities", {}).get("median_rate")
