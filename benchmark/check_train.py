"""How `correct` is decided for a train cell.

The timed object (the compiled step with its state) is driven through its
first three steps by the window's own call and feed, and four kinds of
number are kept from it: each step's loss, the norm per leaf of the first
gradient as the optimizer got it (from Adam's first moment after one
step), the norm per leaf of the parameters' change after the three, and,
where the model carries state (batch norm's running statistics), the norm
per leaf of the state's change in the first step: a forward quantity that
no later step's noise has touched. The plain reference
follows the same three steps from the same seed in float32 with its own
Adam, once the window has closed and the program's state is freed. Gaps
are taken by the worst leaf and by the median leaf: |program's norm -
reference's norm| over the reference's norm of that leaf or of the median
leaf, whichever is larger. The cell's `limits` name the numbers that
decide."""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import program as _program

STEPS = 3


@functools.partial(jax.jit, static_argnames=("step", "hyper"),
                   donate_argnums=(0, 2, 3))
def _adam(params, grads, m, v, step, hyper):
    lr, b1, b2, eps = hyper
    bc = (1 - b2 ** (step + 1.0)) ** 0.5 / (1 - b1 ** (step + 1.0))
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = tm(lambda p, m, v: p - lr * bc * m / (jnp.sqrt(v) + eps),
                params, m, v)
    return params, m, v


def adam_step(params, grads, m, v, step, opt):
    """Adam (Kingma & Ba) with bias correction folded into the step size,
    as the configuration's optimizer section states it."""
    return _adam(params, grads, m, v, step,
                 (opt["learning_rate"], opt["beta1"], opt["beta2"],
                  opt["epsilon"]))


def follow_reference(ref, config, seed, plain, precision="f32"):
    """The reference's readings over the first STEPS batches of `plain`
    (cycled): losses, first-gradient leaf norms, update leaf norms, all
    in the program's leaf order."""
    model, opt = config["model"], config["optimizer"]
    params = ref.init(seed, model)
    state = ref.init_state(model)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for step in range(STEPS):
        x, y = plain[step % len(plain)]
        loss, grads, state = ref.loss_and_grad(params, state, x, y, model,
                                               precision)
        if step == 0:
            first_g, first_s = ref.program_layout(grads, state)
            grad_norms = np.asarray(_program.leaf_norms(first_g))
            del first_g
        params, m, v = adam_step(params, grads, m, v, step, opt)
        del grads
        losses.append(float(loss))
    del m, v  # the starting weights are made again below: 12 B, not 20
    end_p = ref.program_layout(params, state)[0]
    start_p, start_s = ref.program_layout(ref.init(seed, model),
                                          ref.init_state(model))
    out = {"losses": losses, "grad_norms": grad_norms,
           "update_norms": np.asarray(_program.delta_norms(end_p, start_p)),
           "leaf_names": _names(end_p), "state_first_norms": None}
    if jax.tree_util.tree_leaves(first_s):
        out["state_first_norms"] = np.asarray(
            _program.delta_norms(first_s, start_s))
        out["state_names"] = _names(first_s)
    return out


def _names(tree):
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


class ProgramReadings:
    """Collects the timed object's readings while set-up drives its first
    steps; keeps vectors of norms only, no copy of any tree, and lets go
    of the network with its last reading: the reference has the chip to
    itself."""

    def __init__(self, net, opt):
        self.net, self.opt = net, opt
        self.losses, self.grad_norms, self.update_norms = [], None, None
        self.state_first_norms = None
        # the step donates its state: keep the (small) starting one
        self.start_state = jax.tree_util.tree_map(lambda a: a + 0, net.state)

    def after_step(self, loss):
        self.losses.append(float(loss))
        if len(self.losses) == 1:
            m = np.asarray(_program.leaf_norms(
                _program.first_moment(self.net)))
            self.grad_norms = m / (1.0 - self.opt["beta1"])
            if jax.tree_util.tree_leaves(self.start_state):
                self.state_first_norms = np.asarray(_program.delta_norms(
                    self.net.state, self.start_state))

    def after_last(self, start_params):
        self.update_norms = np.asarray(
            _program.delta_norms(self.net.params, start_params))
        self.net = self.start_state = None

    def readings(self):
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "update_norms": self.update_norms,
                "state_first_norms": self.state_first_norms}


def leaf_gaps(got, want):
    """|got - want| per leaf over the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return np.full(want.shape, np.inf)
    floor = max(statistics.median(want.tolist()), 1e-30)
    return np.abs(got - want) / np.maximum(want, floor)


def _kept(names, left_out):
    """Which leaves count towards the worst leaf: all but those whose
    name holds one of the configuration's `left_out` strings (leaves whose
    exact gradient is zero or a sum that all but cancels, which the stated
    precision itself leaves noisy; the configuration file says which and
    why). The median leaf is taken over all of them."""
    if not names or not left_out:
        return slice(None)
    return np.array([not any(s in n for s in left_out) for n in names])


def worst_leaf_gap(got, want, names=None, left_out=()):
    return float(np.max(leaf_gaps(got, want)[_kept(names, left_out)]))


def median_leaf_gap(got, want):
    """The gap of the median leaf, over all leaves: steady from seed to
    seed where the worst leaf swings."""
    return float(np.median(leaf_gaps(got, want)))


def _say_worst(what, got, want, names, k=3):
    gaps = leaf_gaps(got, want)
    for i in np.argsort(-gaps)[:k]:
        print(f"worst {what} leaf {names[i] if names else i}: gap "
              f"{gaps[i]:.4g}, program {np.asarray(got).flat[i]:.6g}, "
              f"reference {np.asarray(want).flat[i]:.6g}", flush=True)


def detail(got, want):
    """Every leaf's norms, for whoever sets a limit: kept as a file beside
    the run's traces, not printed."""
    return {"leaf_names": want.get("leaf_names"),
            "state_names": want.get("state_names"),
            "losses": [list(map(float, got["losses"])),
                       list(map(float, want["losses"]))],
            **{what: [np.asarray(got[what], np.float64).tolist(),
                      np.asarray(want[what], np.float64).tolist()]
               for what in ("grad_norms", "update_norms",
                            "state_first_norms")
               if got.get(what) is not None and want.get(what) is not None}}


def compare(got, want, limits, left_out=None):
    """[(name, value, limit, ok)] for every number the cell's `limits`
    name. `left_out`: {"grad_norms" | "update_norms" | "state_first_norms":
    [strings]} from the configuration file, leaves kept out of that
    kind's worst-leaf gap."""
    left_out = left_out or {}
    loss_gap = max((abs(a - b) / abs(b)
                    for a, b in zip(got["losses"], want["losses"])),
                   default=float("inf"))
    if len(got["losses"]) != len(want["losses"]):
        loss_gap = float("inf")
    print("losses program", " ".join(f"{x:.6f}" for x in got["losses"]),
          "reference", " ".join(f"{x:.6f}" for x in want["losses"]),
          flush=True)
    numbers = {"loss_gap": loss_gap}
    for what, stem, names in (
            ("grad_norms", "grad_norm", want.get("leaf_names")),
            ("update_norms", "update_norm", want.get("leaf_names")),
            ("state_first_norms", "state_first_norm",
             want.get("state_names"))):
        if want.get(what) is None:
            continue  # a model without state has no state numbers
        mine = got.get(what)
        if mine is None:
            mine = np.full(np.shape(want[what]), np.inf)
        _say_worst(what, mine, want[what], names)
        numbers[f"{stem}_gap"] = worst_leaf_gap(
            mine, want[what], names, left_out.get(what, ()))
        numbers[f"{stem}_median_gap"] = median_leaf_gap(mine, want[what])
    unknown = set(limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits name {sorted(unknown)}; the numbers are "
                       f"{sorted(numbers)}")
    for n in sorted(set(numbers) - set(limits)):
        print(f"not compared in this cell: {n} = {numbers[n]:.6g}",
              flush=True)
    return [(n, numbers[n], limits[n],
             bool(np.isfinite(numbers[n]) and numbers[n] <= limits[n]))
            for n in numbers if n in limits]
