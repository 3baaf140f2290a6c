"""One place that turns `--seed` into jax keys. The driver's seeds pass
2**31, which a 32-bit key seed does not hold, so the high bits are folded
in; `stream` keeps weights, traffic and samples apart."""

import jax

WEIGHTS, TRAFFIC, SAMPLE = 0, 1, 2


def key(seed, stream):
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)
