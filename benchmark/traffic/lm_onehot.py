"""Packed language-model batches: `pool` distinct batches of `batch`
sequences of `seq_len` uniform random tokens, made on the device from the
seed in one jitted call; the target of a position is the next token, fed
as the one-hot float32 [B, T, V] that `RnnOutputLayer(loss="mcxent")`
takes. Every seed gives the same sizes."""

import jax
import jax.numpy as jnp

from benchmark import seeds


def make(seed, p, model):
    pool, b, t, v = p["pool"], p["batch"], p["seq_len"], model["vocab_size"]

    @jax.jit
    def gen(key):
        tok = jax.random.randint(key, (pool, b, t + 1), 0, v, jnp.int32)
        x, y = tok[..., :-1], tok[..., 1:]
        return (tuple(x[i] for i in range(pool)),
                tuple(jax.nn.one_hot(y[i], v, dtype=jnp.float32)
                      for i in range(pool)),
                tuple(y[i] for i in range(pool)))

    xs, hot, ys = gen(seeds.key(seed, seeds.TRAFFIC))
    return {"feed": tuple(zip(xs, hot)), "plain": tuple(zip(xs, ys)),
            "units_per_batch": b * t}
