"""Packed language-model batches for a denoising objective: `pool`
distinct batches of `batch` sequences of `seq_len` uniform random tokens
over the whole vocabulary, made on the device from the seed in one jitted
call as `lm_tokens` makes them, fed as int32 [B, T]; **the labels are the
ids themselves** (a masked position predicts its own token: the network
makes the noised copy, not the feed). Every seed gives the same sizes.

Two rules this kind brings. **A token carried by two positions is one
unit**: `units_per_batch` is `batch x seq_len`, the T data tokens a
sequence holds, though a block-diffusion step runs 2T positions for them
(a noised and a clean copy), so a rate in tokens/s stays a rate of data
consumed. **A stochastic step stays comparable with a reference that is
handed no key** by keying its noise on a counter kept as layer state: the
draw of step `n` is a function of the configuration's `noise_seed` and
`n` alone, the reference is handed the state (`loss_and_grad(params,
state, x, y, model, precision)`) and writes the same draw out for itself,
and `state_first_norm_gap` holds both counters to the same step."""

import jax
import jax.numpy as jnp

from benchmark import seeds


def make(seed, p, model):
    pool, b, t, v = p["pool"], p["batch"], p["seq_len"], model["vocab_size"]

    @jax.jit
    def gen(key):
        tok = jax.random.randint(key, (pool, b, t), 0, v, jnp.int32)
        # the labels are an array of their own, as every kind's are: what
        # is alive where the reference starts is then counted by the pair
        return (tuple(tok[i] for i in range(pool)),
                tuple(tok[i] + 0 for i in range(pool)))

    xs, ys = gen(seeds.key(seed, seeds.TRAFFIC))
    pairs = tuple(zip(xs, ys))
    return {"feed": pairs, "plain": pairs, "units_per_batch": b * t}
