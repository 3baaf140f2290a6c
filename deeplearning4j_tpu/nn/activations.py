"""Activation catalog.

Reference analog: the ND4J ``Activation`` enum + ``IActivation`` classes used
throughout the layer configs (e.g. /root/reference/deeplearning4j-nn/src/main/
java/org/deeplearning4j/nn/conf/layers/BaseLayer.java activationFn). Here each
activation is a pure jnp function; jit/XLA fuses them into the surrounding
matmul, which is the TPU-native replacement for libnd4j's fused transform ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def identity(x):
    return x


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jax.nn.relu6(x)


def leakyrelu(x, alpha=0.01):
    return jax.nn.leaky_relu(x, negative_slope=alpha)


def elu(x):
    return jax.nn.elu(x)


def selu(x):
    return jax.nn.selu(x)


def gelu(x):
    return jax.nn.gelu(x)


def swish(x):
    return jax.nn.silu(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def hardsigmoid(x):
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def tanh(x):
    return jnp.tanh(x)


def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


def rationaltanh(x):
    # 1.7159 * tanh(2x/3) approximation used by ND4J's RationalTanh
    a = jnp.abs(2.0 * x / 3.0)
    tanh_approx = jnp.sign(x) * (1.0 - 1.0 / (1.0 + a + a * a + 1.41645 * a**4))
    return 1.7159 * tanh_approx


def rectifiedtanh(x):
    return jnp.maximum(0.0, jnp.tanh(x))


def softplus(x):
    return jax.nn.softplus(x)


def softsign(x):
    return jax.nn.soft_sign(x)


def softmax(x):
    return jax.nn.softmax(x, axis=-1)


def logsoftmax(x):
    return jax.nn.log_softmax(x, axis=-1)


def cube(x):
    return x**3


def thresholdedrelu(x, theta=1.0):
    return jnp.where(x > theta, x, 0.0)


def relu2(x):
    """Squared ReLU (So et al. 2021, "Primer"; the Nemotron-H experts)."""
    return jnp.square(jax.nn.relu(x))


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


_CATALOG = {
    "identity": identity,
    "linear": identity,
    "relu": relu,
    "relu6": relu6,
    "relu2": relu2,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "gelu": gelu,
    "swish": swish,
    "silu": swish,
    "sigmoid": sigmoid,
    "hardsigmoid": hardsigmoid,
    "tanh": tanh,
    "hardtanh": hardtanh,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "softplus": softplus,
    "softsign": softsign,
    "softmax": softmax,
    "logsoftmax": logsoftmax,
    "cube": cube,
    "thresholdedrelu": thresholdedrelu,
    "mish": mish,
}


def get(name):
    """Resolve an activation by name (or pass a callable through).

    Parameterized spelling: ``("leakyrelu", {"alpha": 0.3})`` (list or tuple,
    JSON-serde friendly) binds keyword arguments onto the named activation —
    the analog of DL4J's parameterized IActivation instances (e.g.
    ActivationLReLU(alpha))."""
    if isinstance(name, (tuple, list)) and name:
        import functools
        kwargs = dict(name[1]) if len(name) > 1 and name[1] else {}
        return functools.partial(get(name[0]), **kwargs)
    if callable(name):
        return name
    try:
        return _CATALOG[name.lower()]
    except KeyError:
        raise KeyError(f"Unknown activation {name!r}. Known: {sorted(_CATALOG)}") from None


def names():
    return sorted(_CATALOG)
