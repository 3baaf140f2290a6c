"""Packed language-model batches with integer labels: `pool` distinct
batches of `batch` sequences of `seq_len` uniform random tokens over the
whole vocabulary, made on the device from the seed in one jitted call; the
target of a position is the next token, fed as int32 [B, T] (what an
output layer that takes class ids reads; `lm_onehot` feeds the same tokens
as one-hot float32). Every seed gives the same sizes."""

import jax
import jax.numpy as jnp

from benchmark import seeds


def make(seed, p, model):
    pool, b, t, v = p["pool"], p["batch"], p["seq_len"], model["vocab_size"]

    @jax.jit
    def gen(key):
        tok = jax.random.randint(key, (pool, b, t + 1), 0, v, jnp.int32)
        return (tuple(tok[i, :, :-1] for i in range(pool)),
                tuple(tok[i, :, 1:] for i in range(pool)))

    xs, ys = gen(seeds.key(seed, seeds.TRAFFIC))
    pairs = tuple(zip(xs, ys))
    return {"feed": pairs, "plain": pairs, "units_per_batch": b * t}
