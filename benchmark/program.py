"""The one place the benchmark touches the system under test for a train
cell: build the network through the configuration's factory, put the
benchmark's own weights into it, and read its optimizer state. The weights
come from the plain reference's `init` (made from the seed, never by the
program), arranged by the reference's `program_layout`."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def _resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def build(config, seed):
    """The network, initialised the program's own way (so every field a
    fit loop expects exists); `load_weights` then replaces its numbers."""
    prog = config["program"]
    _resolve(prog["policy"])()
    conf = _resolve(prog["factory"])(**prog["args"],
                                     seed=int(seed) & 0x7FFFFFFF)
    net = _resolve(prog["net"])(conf)
    net.init()
    check_optimizer(net, config["optimizer"])
    return net


def check_optimizer(net, opt):
    """The reference follows the optimizer the configuration file states;
    refuse to run if the factory built another."""
    upd = net.conf.updater
    got = {"kind": type(upd).__name__.lower(),
           **{k: getattr(upd, k) for k in opt if k != "kind"}}
    if got != opt:
        raise ValueError(f"the factory's updater is {got}, the "
                         f"configuration file states {opt}")


def lay_over(own, given, path="params"):
    """`given` (the reference's numbers in program layout) laid over
    `own` (the program's tree): every array leaf of `own` must be given
    with the same shape and dtype; containers without leaves stay."""
    if isinstance(own, dict):
        with_leaves = {k for k in own if jax.tree_util.tree_leaves(own[k])}
        if with_leaves != set(given):
            raise ValueError(f"{path}: program has {sorted(with_leaves)}, "
                             f"reference gives {sorted(given)}")
        return {k: (lay_over(own[k], given[k], f"{path}.{k}")
                    if k in with_leaves else own[k]) for k in own}
    if isinstance(own, (list, tuple)):
        if len(own) != len(given):
            raise ValueError(f"{path}: {len(own)} entries against "
                             f"{len(given)}")
        return type(own)(lay_over(o, g, f"{path}[{i}]")
                         for i, (o, g) in enumerate(zip(own, given)))
    if own.shape != given.shape or own.dtype != given.dtype:
        raise ValueError(f"{path}: program has {own.shape} {own.dtype}, "
                         f"reference gives {given.shape} {given.dtype}")
    return given


def load_weights(net, params, state):
    net.params = lay_over(net.params, params)
    if jax.tree_util.tree_leaves(net.state):
        net.state = lay_over(net.state, state, "state")
    net.opt_state = net.conf.updater.init(net.params)


def first_moment(net):
    """Adam's first moment after one step is (1 - beta1) x the gradient
    the optimizer was given."""
    return net.opt_state["m"]


def feed_item(net, x, y):
    """One (x, y, mask) item as the net's own `fit` would yield it."""
    conf = net.conf
    if hasattr(conf, "inputs"):  # ComputationGraph: dict-keyed
        return {conf.inputs[0]: x}, {conf.outputs[0]: y}, None
    return x, y, None


@jax.jit
def leaf_norms(tree):
    """L2 norm of every array leaf, in `tree_leaves` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


@jax.jit
def delta_norms(new, old):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, new, old))
