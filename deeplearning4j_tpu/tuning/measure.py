"""Measurement harness: chained in-jit candidate timing + parity gate.

Per-dispatch host overhead swamps a single kernel launch, so each
candidate is timed as **one jitted call that runs the kernel ``iters``
times chained** — a ``lax.fori_loop`` whose carry feeds back into the next
iteration's input (a data dependence XLA cannot elide) — and the barrier
is a host fetch of the final carry, the same discipline bench.py's timed
windows use. dt = elapsed / iters, best of ``reps`` windows (host jitter
does not survive a best-of; a real difference does).

Every candidate is **parity-gated against the reference path before it
may win**: the candidate's raw output is compared leafwise to the
reference's (default tol 1e-6, NaN-poisoned comparisons fail). A
candidate that fails parity counts a ``tuning_db_total{event=reject}``
and can never be persisted — a fast wrong kernel is not a winner.

Candidate compiles route through the blessed
``utils/compile_cache.aot_compile`` site (graftlint R3 exempts the
jit-into-aot_compile idiom inside the candidate loop: one deliberate,
manifest-aware compile per candidate is the autotuner working, not a
recompile hazard).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.tuning import db as _db
from deeplearning4j_tpu.utils.compile_cache import aot_compile


@dataclasses.dataclass
class Measurement:
    """One candidate's outcome: parity diff, per-iteration seconds (None
    when rejected), and the rejection reason when it never ran."""
    config: dict
    seconds_per_iter: float | None = None
    parity: float | None = None
    rejected: str | None = None

    @property
    def ok(self):
        return self.rejected is None


def chain_repeat(fn, iters):
    """``fn(*args)`` repeated ``iters`` times inside one trace, each
    iteration data-dependent on the last (the first float arg is
    perturbed by ``carry * 0``), returning a scalar whose host fetch is
    the completion barrier."""
    def chained(*args):
        chain_idx = next(
            (i for i, a in enumerate(args)
             if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)), None)

        def body(_, carry):
            a2 = list(args)
            if chain_idx is not None:
                a = a2[chain_idx]
                a2[chain_idx] = a + (carry * 0).astype(a.dtype)
            out = fn(*a2)
            leaf = jax.tree_util.tree_leaves(out)[0]
            return leaf.reshape(-1)[0].astype(jnp.float32)

        return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

    return chained


def parity_diff(out, ref):
    """Max abs elementwise difference across the two pytrees' leaves in
    f32, or inf on structure/shape mismatch — the number the ≤tol parity
    gate compares. NaN anywhere returns inf (a NaN-emitting candidate
    must fail, not slide through a ``<=`` that is False-but-passing)."""
    lo, to = jax.tree_util.tree_flatten(out)
    lr, tr = jax.tree_util.tree_flatten(ref)
    if to != tr or len(lo) != len(lr):
        return float("inf")
    worst = 0.0
    for a, b in zip(lo, lr):
        a = np.asarray(jax.device_get(a), dtype=np.float32)
        b = np.asarray(jax.device_get(b), dtype=np.float32)
        if a.shape != b.shape:
            return float("inf")
        d = float(np.max(np.abs(a - b))) if a.size else 0.0
        if not np.isfinite(d):
            return float("inf")
        worst = max(worst, d)
    return worst


def time_callable(fn, args, *, iters=4, warmup=1, reps=2):
    """Best-of-``reps`` chained in-jit seconds-per-iteration of
    ``fn(*args)``. The compile routes through ``aot_compile`` (blessed
    site); the executable is reused across windows so only device time
    is in the window."""
    chained = chain_repeat(fn, iters)
    jitted = jax.jit(chained)
    ex, _src = aot_compile(jitted, *args)

    def call():
        try:
            return ex(*args)
        except TypeError:  # AOT arg-passing quirk: fall back to the jit
            return jitted(*args)

    for _ in range(max(1, warmup)):
        jax.device_get(call())
    best = None
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.device_get(call())
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best


def search(kernel, candidates, build, args, ref_fn, *, build_check=None,
           tol=1e-6, iters=4, warmup=1, reps=2, log=None):
    """Measure ``candidates`` and return ``(winner, results)``.

    ``build(config)`` -> the timed callable; ``build_check(config)`` (or
    ``build`` itself) -> the callable whose output is parity-compared to
    ``ref_fn(*args)``. A candidate whose check output differs from the
    reference by more than ``tol`` (or whose build/run raises) is
    REJECTED — counted, never timed, never a winner. ``winner`` is the
    fastest surviving Measurement, or None when everything rejected."""
    ref_out = ref_fn(*args)
    results, winner = [], None
    for cfg in candidates:
        m = Measurement(dict(cfg))
        try:
            check_fn = (build_check or build)(cfg)
            m.parity = parity_diff(check_fn(*args), ref_out)
            if not (m.parity <= tol):
                raise _ParityError(
                    f"parity {m.parity:.3g} exceeds tol {tol:.3g}")
            timed = build(cfg) if build_check is not None else check_fn
            # one deliberate compile per candidate, through the blessed
            # manifest-aware site (graftlint R3's autotune idiom)
            m.seconds_per_iter = time_callable(
                timed, args, iters=iters, warmup=warmup, reps=reps)
        except Exception as e:
            m.rejected = str(e) or type(e).__name__
            _db.count_event("reject")
            results.append(m)
            if log:
                log(f"  {kernel} {cfg}: REJECTED ({m.rejected})")
            continue
        results.append(m)
        if winner is None or m.seconds_per_iter < winner.seconds_per_iter:
            winner = m
        if log:
            log(f"  {kernel} {cfg}: {1e3 * m.seconds_per_iter:.3f} ms/iter"
                f" (parity {m.parity:.2g})")
    return winner, results


class _ParityError(ValueError):
    pass
