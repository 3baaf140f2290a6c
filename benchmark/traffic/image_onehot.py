"""Image classification batches: `pool` distinct batches of `batch`
standard-normal float32 images with uniform random classes, made on the
device from the seed in one jitted call; labels fed one-hot float32, as
`OutputLayer(loss="mcxent")` takes them. Every seed gives the same
sizes."""

import jax
import jax.numpy as jnp

from benchmark import seeds


def make(seed, p, model):
    pool, b = p["pool"], p["batch"]
    h, w, c, n = (model["height"], model["width"], model["channels"],
                  model["n_classes"])

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (pool, b, h, w, c), jnp.float32)
        y = jax.random.randint(ky, (pool, b), 0, n, jnp.int32)
        return (tuple(x[i] for i in range(pool)),
                tuple(jax.nn.one_hot(y[i], n, dtype=jnp.float32)
                      for i in range(pool)),
                tuple(y[i] for i in range(pool)))

    xs, hot, ys = gen(seeds.key(seed, seeds.TRAFFIC))
    return {"feed": tuple(zip(xs, hot)), "plain": tuple(zip(xs, ys)),
            "units_per_batch": b}
