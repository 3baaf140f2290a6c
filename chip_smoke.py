#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of the ``longcontext`` configuration (``models.transformer_lm(8192,
n_layers=6, d_model=512, n_heads=8, seq_len=4096)`` under
``dtypes.bf16_policy()``; weights random from the conf seed):

* **train** — ``MultiLayerNetwork.fit`` on a seeded in-memory batch: steps at
  ``steps_per_dispatch=1`` (the ``StepDriver`` loop) and one dispatch at
  ``steps_per_dispatch=4`` (``nn/fused.py``), then save / ``load_bundle`` /
  resume with the warm manifest attached;
* **serve** — ``serving.get_model_registry().register(...)`` on the trained
  net with a (batch, seq) bucket grid, AOT-warmed, eight requests of mixed
  length through ``engine.submit(x).get(timeout=)``, checked against a
  direct ``net.output`` on the same rows;
* **kernels** — every Pallas kernel the default dispatch can reach, run
  against the ``jax.numpy`` path it replaces;
* ``--chips 4`` — the same model through ``ParallelTrainer`` over a
  ``data=4`` mesh and ``ServingEngine(mesh=)``: buffers on all four devices,
  the loss sequence against a one-device run, warm-manifest round trips
  with four devices visible.

``python chip_smoke.py`` refuses any platform other than ``tpu`` (non-zero
exit, one line saying why). Every check is an ``_expect`` that raises, and
nothing here catches: a failed phase cannot leave exit code 0. One JSON line
per phase, then the contract line. The timings printed are set-up
observations, not metrics.

The phase functions take their sizes as arguments, so
``tests/test_chip_smoke.py`` calls them at toy size on the CPU (kernels in
interpret mode); only ``main`` insists on a chip. One process uses the chip:
this script starts no other.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np


def _expect(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def _say(rec):
    print(json.dumps(rec, default=str), flush=True)
    return rec


def _device_doc():
    from deeplearning4j_tpu.telemetry import devices
    return devices.device_stamp()


def _peak_bytes(device=None):
    """``peak_bytes_in_use`` of one device, or None where the backend does
    not report it (the CPU)."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _rel_err(got, want):
    """Worst leaf of max|got - want| over max|want|: one number per
    comparison that does not blow up on the near-zero entries of a large
    tensor. ``got``/``want`` are arrays or matching pytrees of them."""
    import jax
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        worst = max(worst,
                    float(np.abs(g - w).max() / (np.abs(w).max() + 1e-30)))
    return worst


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# ---------------------------------------------------------------------------
# the model and its data
# ---------------------------------------------------------------------------

def build_net(*, vocab, n_layers, d_model, n_heads, seq_len):
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    net = MultiLayerNetwork(models.transformer_lm(
        vocab, n_layers=n_layers, d_model=d_model, n_heads=n_heads,
        seq_len=seq_len))
    net.init()
    return net


def lm_batch(batch, seq_len, vocab, seed=0):
    """One seeded next-token batch in the LM input contract: ids as float32
    ``[B, T, 1]``, labels one-hot float32 ``[B, T, V]``. The step takes the
    head's loss from its logits (``losses.softmax_xent``), not from the
    probabilities ``output()`` returns."""
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq_len))
    x = ids[..., None].astype(np.float32)
    y = np.zeros((batch, seq_len, vocab), np.float32)
    np.put_along_axis(y, np.roll(ids, -1, axis=1)[..., None], 1.0, axis=2)
    return x, y


def _fit_losses(net, x, y, *, steps, batch, k=1):
    """``steps`` optimizer steps on ONE repeated batch through ``net.fit``;
    returns the per-step losses the listeners saw."""
    from deeplearning4j_tpu.nn.listeners import CollectScoresListener
    scores = CollectScoresListener()
    net.listeners.append(scores)
    try:
        if k == 1:
            net.fit(x, y, epochs=steps, batch_size=batch)
        else:
            _expect(steps % k == 0, "fused steps must be a multiple of k")
            net.fit(np.tile(x, (k, 1, 1)), np.tile(y, (k, 1, 1)),
                    epochs=steps // k, batch_size=batch,
                    steps_per_dispatch=k)
    finally:
        net.listeners.remove(scores)
    return [float(s) for s in scores.scores]


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(net, *, vocab, seq_len, batch, steps, k, workdir,
                flash_calls=None):
    """K=1 steps, one K-step fused dispatch with a warm manifest attached,
    then save -> load_bundle -> resume. ``flash_calls``: how many
    ``tpu_custom_call``s the compiled step must contain, the flash forward
    and backward kernel of every layer (None off-chip, where the dispatch
    gate is closed and the count is 0)."""
    import jax

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.telemetry import devices as _devices
    from deeplearning4j_tpu.utils import compile_cache as cc
    from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                        save_bundle)

    telemetry.enable()
    t0 = time.perf_counter()
    x, y = lm_batch(batch, seq_len, vocab)

    # -- steps_per_dispatch=1: the StepDriver loop --------------------------
    warm = _fit_losses(net, x, y, steps=2, batch=batch)       # warm-up
    recompiles0 = dict(_devices.recompile_counts())
    k1 = warm + _fit_losses(net, x, y, steps=steps, batch=batch)
    _expect(np.isfinite(k1).all(), f"K=1 loss not finite: {k1}")
    _expect(k1[-1] < k1[0], f"K=1 loss did not fall on a repeated "
                            f"batch: {k1}")
    _expect(_devices.recompile_counts() == recompiles0,
            f"recompiles_total moved after warm-up: {recompiles0} -> "
            f"{_devices.recompile_counts()}")
    compiles = telemetry.series_map("compiles_total")
    _expect(compiles.get("site=fit.step", 0) >= 1,
            f"the recompile telemetry never saw fit.step: {compiles}")

    # the program that just ran, compiled again for its text (a persistent
    # cache hit, not a second compile): does it hold the flash kernel?
    step_ex, _ = cc.aot_compile(
        net._train_step, net.params, net.state, net.opt_state,
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(y.shape, y.dtype), net.iteration,
        jax.random.PRNGKey(0), None, kind="smoke:k1_text",
        signature="k1")
    n_calls = step_ex.as_text().count("tpu_custom_call")
    del step_ex
    if flash_calls is not None:
        _expect(n_calls == flash_calls,
                f"compiled train step holds {n_calls} tpu_custom_call(s), "
                f"expected {flash_calls} (the flash forward and backward "
                f"kernel of each layer)")

    # -- steps_per_dispatch=k: nn/fused.py, manifest attached ---------------
    events0 = dict(cc.event_counts())
    cc.attach_manifest(net, cc.WarmManifest.for_net(net))
    fused = _fit_losses(net, x, y, steps=k, batch=batch, k=k)
    _expect(len(fused) == k and np.isfinite(fused).all(),
            f"K={k} dispatch losses: {fused}")
    _expect(fused[-1] < k1[0], f"K={k} loss {fused} not below the first "
                               f"K=1 loss {k1[0]}")
    fused_engine = next(iter(net._train_steps_fused.values()))[0]
    n_fused_calls = (next(iter(fused_engine._by_sig.values())).as_text()
                     .count("tpu_custom_call"))
    if flash_calls is not None:
        _expect(n_fused_calls == flash_calls,
                f"the K={k} executable that ran holds {n_fused_calls} "
                f"tpu_custom_call(s), expected {flash_calls}")

    # -- save / load_bundle / resume on the warm manifest -------------------
    bundle = os.path.join(workdir, "smoke_bundle.zip")
    save_bundle(net, bundle)
    restored = load_bundle(bundle).net
    _expect(getattr(restored, "_warm_manifest", None) is not None,
            "load_bundle did not attach the warm manifest")
    resumed = _fit_losses(restored, x, y, steps=k, batch=batch, k=k)
    _expect(np.isfinite(resumed).all() and resumed[-1] < fused[-1],
            f"resumed losses {resumed} did not continue below {fused[-1]}")
    events = {e: n - events0.get(e, 0) for e, n in cc.event_counts().items()}
    _expect(events.get("hit", 0) > 0,
            f"resume took no warm-manifest hit: {events}")
    for bad in ("deserialize_fail", "serialize_fail", "mismatch_drop"):
        _expect(not events.get(bad), f"compile_cache_total{{event={bad}}} "
                                     f"= {events.get(bad)}")
    _expect(_devices.recompile_counts() == recompiles0,
            "recompiles_total moved during the fused/resumed dispatches")

    return _say({"phase": "train", **_device_doc(),
                 "losses_k1": [round(v, 4) for v in k1],
                 f"losses_k{k}": [round(v, 4) for v in fused],
                 "losses_resumed": [round(v, 4) for v in resumed],
                 "tpu_custom_calls": {"k1": n_calls, f"k{k}": n_fused_calls},
                 "compiles_total": compiles,
                 "recompiles_total": recompiles0,
                 "compile_cache_events": events,
                 "peak_bytes_in_use": _peak_bytes(),
                 "wall_s": round(time.perf_counter() - t0, 1)})


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(net, *, vocab, batch_buckets, seq_buckets, lengths, tol,
                mesh=None, warm_manifest=None, name="smoke_lm"):
    """Register ``net`` on a (batch, seq) grid, AOT-warmed; one request per
    entry of ``lengths`` — the first half one at a time, the rest submitted
    together so the batch axis fills too — each answer checked against a
    direct ``net.output`` on the same row at its own length."""
    from deeplearning4j_tpu import serving

    t0 = time.perf_counter()
    registry = serving.get_model_registry()
    engine = registry.register(
        name, net, input_spec=(max(seq_buckets), 1),
        buckets=list(batch_buckets), seq_buckets=list(seq_buckets),
        mesh=mesh, warm_manifest=warm_manifest)
    try:
        warmup_s = engine.stats()["warmup_s"]
        rs = np.random.RandomState(1)
        rows = [rs.randint(0, vocab, (n, 1)).astype(np.float32)
                for n in lengths]
        half = len(rows) // 2
        futs = []
        for row in rows[:half]:
            futs.append(engine.submit(row))
            futs[-1].get(timeout=600)
        futs += [engine.submit(row) for row in rows[half:]]
        answers = [f.get(timeout=600) for f in futs]
        latencies_ms = [round(1e3 * f.latency_s, 2) for f in futs]

        worst = 0.0
        for row, got in zip(rows, answers):
            _expect(got.shape == (row.shape[0], vocab),
                    f"answer shape {got.shape} for a {row.shape[0]}-step "
                    "request")
            _expect(np.isfinite(got).all(), "non-finite answer")
            want = np.asarray(net.output(row[None]))[0]
            worst = max(worst, _rel_err(got, want))
        _expect(worst <= tol, f"served answers differ from net.output by "
                              f"{worst:.4g} (max abs / max abs, tol {tol})")
        stats = engine.stats()
        aot = stats["aot"]
        _expect(aot["lazy_compiles"] == 0 and aot["jit_serves"] == 0,
                f"request path compiled: {aot}")
        _expect(stats["requests"]["served"] == len(rows)
                and not stats["requests"]["errors"],
                f"request counts: {stats['requests']}")
    finally:
        registry.stop()
    _expect(not engine.running, "registry.stop() left the worker running")
    return _say({"phase": "serve", **_device_doc(),
                 "mesh": None if mesh is None else dict(mesh.shape),
                 "buckets": stats["buckets"],
                 "seq_buckets": stats["seq_buckets"],
                 "aot": aot, "warmup_s": round(warmup_s, 2),
                 "request_lengths": list(lengths),
                 "first_request_ms": latencies_ms[0],
                 "median_request_ms": float(np.median(latencies_ms)),
                 "latencies_ms": latencies_ms,
                 "max_rel_err_vs_net_output": float(f"{worst:.3g}"),
                 "tolerance": tol,
                 "wall_s": round(time.perf_counter() - t0, 1)})


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _reference_path():
    """Traces inside this block take the ``jax.numpy`` path: the one
    backend gate both kernel dispatch seams read is held closed."""
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    saved = _ap.backend_is_tpu
    _ap.backend_is_tpu = lambda: False
    try:
        yield
    finally:
        _ap.backend_is_tpu = saved


def _compare(name, kernel_fn, ref_fn, args, tol):
    """Forward values and gradients (of a weighted sum of the output,
    w.r.t. every arg) of the kernel against the path it replaces.
    ``tol``: {"fwd": ..., "bwd": ...} as ``_rel_err``."""
    import jax
    import jax.numpy as jnp

    def with_grads(fn):
        def f(*a):
            out = fn(*a)
            # a fixed non-uniform weighting, so a permuted or shifted
            # output cannot hide inside a plain sum
            loss = sum(jnp.sum(o.astype(jnp.float32) * jnp.cos(
                jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)))
                for o in jax.tree_util.tree_leaves(out))
            return loss, out
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(len(args))),
                                          has_aux=True))

    t0 = time.perf_counter()
    (_, out_k), grads_k = with_grads(kernel_fn)(*args)
    with _reference_path():
        (_, out_r), grads_r = with_grads(ref_fn)(*args)
    for leaf in jax.tree_util.tree_leaves((out_k, grads_k)):
        _expect(bool(jnp.isfinite(leaf).all()), f"{name}: non-finite value")
    errs = {"fwd": _rel_err(out_k, out_r), "bwd": _rel_err(grads_k, grads_r)}
    _expect(errs["fwd"] <= tol["fwd"] and errs["bwd"] <= tol["bwd"],
            f"{name}: kernel vs jax.numpy path {errs} (tol {tol})")
    return {"kernel": name, "fwd_rel_err": float(f"{errs['fwd']:.3g}"),
            "bwd_rel_err": float(f"{errs['bwd']:.3g}"),
            "wall_s": round(time.perf_counter() - t0, 1)}


def _flash_case(name, *, b, t, h, d, causal, masked, block, interpret, tol,
                backward=None):
    """``backward``: the form ``_run_bwd`` has to choose for this call
    ("fused" or "split"), by the kernels' names in the traced gradient."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.ops import attention_pallas as _ap
    from deeplearning4j_tpu.parallel import sequence as _seq
    from deeplearning4j_tpu.utils import dtypes as _dtypes

    cd, _ = _dtypes.compute_dtypes_for(jnp.float32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(t + d), 3)
    q, k, v = (jax.random.normal(key, (b, t, h, d), jnp.float32).astype(cd)
               for key in (kq, kk, kv))
    mask = None
    if masked:
        lens = np.linspace(t // 2, t, b).astype(np.int32)
        mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None],
                           jnp.float32)
    if not interpret:
        _expect(_ap.resolve_attention(q.shape, k.shape, mask,
                                      q.dtype) is not None,
                f"{name}: the dispatch gate does not admit this shape")
    if block:
        scale = 1.0 / float(d) ** 0.5

        def kernel(q, k, v):
            return _ap.flash_attention_block(q, k, v, causal, scale,
                                             interpret)

        def ref(q, k, v):
            bm = None
            if causal:
                pos = jnp.arange(t)
                bm = (pos[:, None] >= pos[None, :])[None, None]
            out, lse = _seq._naive_block(q, k, v, scale, bm)
            return out.astype(q.dtype), lse
    else:
        def kernel(q, k, v):
            return _ap.flash_attention(q, k, v, mask=mask, causal=causal,
                                       interpret=interpret)

        def ref(q, k, v):
            return dot_product_attention(q, k, v, mask=mask,
                                         causal=causal).astype(q.dtype)
    if backward is not None:
        traced = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(kernel(*a).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v))
        for form, names in (("fused", ("flash_attn_bwd_fused",)),
                            ("split", ("flash_attn_bwd_dkv",
                                       "flash_attn_bwd_dq"))):
            for wanted in names:
                _expect((wanted in traced) == (form == backward),
                        f"{name}: the backward should be {backward} alone, "
                        f"and {wanted} is "
                        f"{'' if wanted in traced else 'not '}there")
    return _compare(name, kernel, ref, (q, k, v), tol)


def _flash_backward_time(name, *, b, t, h, d, interpret, iters=20):
    """Milliseconds of one flash backward alone: the pullback of a causal
    call on float32 operands, as the train cells hand them (the kernel and
    what ``flash_attn.bwd`` holds beside it, the head folds around them),
    on the host's clock around ``iters`` calls."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention_pallas as _ap

    t0 = time.perf_counter()
    q, k, v, g = (jax.random.normal(key, (b, t, h, d), jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(t + d), 4))
    _, pullback = jax.vjp(
        lambda q, k, v: _ap.flash_attention(q, k, v, causal=True,
                                            interpret=interpret), q, k, v)
    run = jax.jit(pullback)
    jax.block_until_ready(run(g))
    t1 = time.perf_counter()
    for _ in range(iters):
        grads = run(g)
    jax.block_until_ready(grads)
    ms = (time.perf_counter() - t1) / iters * 1e3
    for leaf in grads:
        _expect(bool(jnp.isfinite(leaf).all()), f"{name}: non-finite value")
    return {"kernel": name, "bwd_ms": float(f"{ms:.4g}"),
            "wall_s": round(time.perf_counter() - t0, 1)}


def _flash_steps_time(name, *, bh, t, d, block_diffusion=None, block=512,
                      interpret=False, iters=50):
    """Milliseconds a call of the forward kernel and of the backward, each
    alone on folded [BH, T, D] operands as the custom_vjp rules hand them
    (bfloat16 q, k, v; float32 out and cotangent; the backward with the
    delta it computes beside its kernel), causal or under
    ``BlockDiffusion(*block_diffusion)``, beside the grid steps a head
    walks (``live``) and the tiles of its rectangle: a dead tile's turn
    cost 0.10-0.42 us by the head width while the grid was the rectangle
    (PERF.md section 6, PR 53), and this is the call that reads a step's
    cost again. The host's clock around ``iters`` calls."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention_pallas as _ap

    t0 = time.perf_counter()
    geometry = block_diffusion and _ap.BlockDiffusion(*block_diffusion)
    causal, scale = geometry is None, d ** -0.5
    block_q, block_k, t_pad = _ap._geometry(t, block, block)
    steps = _ap.step_list(causal, geometry, t_pad // block_q,
                          t_pad // block_k, block_q, block_k)
    q, k, v, g = (jax.random.normal(key, (bh, t, d), jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(t + d), 4))
    q, k, v = _ap._as_operands(interpret, q, k, v)
    fwd = jax.jit(lambda q, k, v: _ap._run_fwd(
        q, k, v, None, 1, causal, scale, block, block, interpret,
        jnp.float32, geometry))
    bwd = jax.jit(lambda q, k, v, out, lse, g: _ap._run_bwd(
        (q, k, v, None, out, lse), g, None, 1, causal, scale, block, block,
        interpret, geometry))

    def ms(fn, *args):
        jax.block_until_ready(fn(*args))
        t1 = time.perf_counter()
        for _ in range(iters):
            got = fn(*args)
        jax.block_until_ready(got)
        return (time.perf_counter() - t1) / iters * 1e3, got
    fwd_ms, (out, lse) = ms(fwd, q, k, v)
    bwd_ms, grads = ms(bwd, q, k, v, out, lse, g)
    for leaf in (out, *grads):
        _expect(bool(jnp.isfinite(leaf).all()), f"{name}: non-finite value")
    return {"kernel": name, "fwd_ms": float(f"{fwd_ms:.4g}"),
            "bwd_ms": float(f"{bwd_ms:.4g}"), "live": steps.live,
            "rectangle": steps.rectangle,
            "wall_s": round(time.perf_counter() - t0, 1)}


def _flash_rounded_once_case(name, *, b, t, h, d, interpret):
    """Both flash kernels read q, k and v rounded once to bfloat16
    (``attention_pallas._operand_dtype``), which is what the matrix units
    did to a float32 operand as they took it: so a causal call on float32
    inputs and one on the same inputs rounded to bfloat16 and widened
    again give the same out, dq, dk and dv to the bit. The digests name
    the bits, so that two checkouts' records compare. Under the
    interpreter the operands stay float32 and nothing is expected."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention_pallas as _ap

    t0 = time.perf_counter()
    q, k, v, g = (jax.random.normal(key, (b, t, h, d), jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(t + d), 4))

    @jax.jit
    def run(q, k, v, g):
        out, pullback = jax.vjp(
            lambda q, k, v: _ap.flash_attention(q, k, v, causal=True,
                                                interpret=interpret), q, k, v)
        return (out, *pullback(g))
    names = ("out", "dq", "dk", "dv")
    given = dict(zip(names, run(q, k, v, g)))
    rounded = dict(zip(names, run(*(
        x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k, v)), g)))
    for n in names:
        _expect(given[n].dtype == jnp.float32
                and bool(jnp.isfinite(given[n]).all()),
                f"{name}: {n} is {given[n].dtype} or not finite")
    equal = {n: bool(jnp.array_equal(given[n], rounded[n])) for n in names}
    _expect(interpret or all(equal.values()),
            f"{name}: float32 inputs and the same rounded to bfloat16 "
            f"differ: {equal}")
    return {"kernel": name, "bit_equal": equal,
            "max_abs_diff": {n: float(jnp.abs(given[n] - rounded[n]).max())
                             for n in names},
            "sha256": {n: hashlib.sha256(
                np.asarray(given[n]).tobytes()).hexdigest()[:16]
                for n in names},
            "wall_s": round(time.perf_counter() - t0, 1)}


def _gated_delta_case(name, *, b, t, hk, hv, d, interpret, tol):
    """The gated delta rule's two kernels alone against the ``jax.numpy``
    chunkwise form: outputs and the five gradients, q and k normalised as
    the mixer hands them over, decays from all but kept to all but
    forgotten inside a few tokens."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import gated_delta as _gd

    keys = jax.random.split(jax.random.PRNGKey(t + d), 5)
    q, k = (jax.random.normal(key, (b, t, hk, d), jnp.float32)
            for key in keys[:2])
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)) / d ** 0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True))
    v = jax.random.normal(keys[2], (b, t, hv, d), jnp.float32)
    g = -jnp.abs(jax.random.normal(keys[3], (b, t, hv), jnp.float32)) * (
        10.0 ** jnp.linspace(-3.0, 0.5, hv, dtype=jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, hv),
                                            jnp.float32))
    if not interpret:
        _expect(_gd.resolve_gated_delta(q.shape, v.shape, v.dtype),
                f"{name}: the dispatch gate does not admit this shape")

    def kernels(q, k, v, g, beta):
        return _gd.gated_delta_kernels(q, k, v, g, beta, interpret=interpret)

    return _compare(name, kernels, _gd._chunked, (q, k, v, g, beta), tol)


def _causal_conv_case(name, *, b, t, width, columns, taps, gates, split,
                      interpret, tol, bias=False):
    """The short causal convolution's two kernels alone against the
    ``jax.numpy`` form under autodiff: the result (whole or in ``split``
    pieces, with what passes by behind it) and the gradients of the
    projection, of the taps and, with ``bias`` (the Mamba-2 mixer's), of
    the bias a column; ``gates`` the conv mixer's two, else the
    gated-delta mixer's SiLU."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import causal_conv as _cc

    kp, kw, kb = jax.random.split(jax.random.PRNGKey(t + columns), 3)
    p = jax.random.normal(kp, (b, t, width), jnp.float32)
    w = 0.5 * jax.random.normal(kw, (columns, taps), jnp.float32)
    more = (jax.random.normal(kb, (columns,), jnp.float32),) if bias else ()
    kwargs = dict(gate_before=gates, gate_after=gates, activation=not gates)
    if not interpret:
        _expect(_cc.resolve_causal_conv(p.shape, w.shape, p.dtype, gates,
                                        gates, split),
                f"{name}: the dispatch gate does not admit this shape")

    def kernels(p, w, *bias):
        return _cc.causal_conv_kernels(p, w, *bias, split=split,
                                       interpret=interpret, **kwargs)

    def plain(p, w, *bias):
        return _cc.causal_conv(p, w, *bias, split=split, **kwargs)

    return _compare(name, kernels, plain, (p, w, *more), tol)


def _ssd_inputs(b, t, h, p, g, n):
    """(x, dt, A, B, C, D) with steps from all but kept to all but
    forgotten inside a few tokens, and the recurrence run position by
    position over them (the benchmark's plain reference's)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as _plain

    keys = jax.random.split(jax.random.PRNGKey(t + n), 5)
    x = jax.random.normal(keys[0], (b, t, h, p), jnp.float32)
    dt = jax.nn.softplus(3.0 * jax.random.normal(keys[1], (b, t, h),
                                                 jnp.float32))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) / 8.0
    bm, cm = (jax.random.normal(key, (b, t, g, n), jnp.float32) * n ** -0.5
              for key in keys[2:4])
    d = jax.random.normal(keys[4], (h,), jnp.float32)

    def position_by_position(x, dt, a, bm, cm, d):
        bh, ch = (jnp.repeat(u, h // g, axis=2) for u in (bm, cm))
        return jax.vmap(lambda x, dt, bh, ch: _plain.selective_scan(
            x, dt, a, bh, ch, d))(x, dt, bh, ch)

    return (x, dt, a, bm, cm, d), position_by_position


def _ssd_case(name, *, b, t, h, p, g, n, chunk, tol):
    """The selective state-space scan's chunkwise form (``ops/ssd.py``,
    whatever ``resolve_ssd`` chooses here) against the recurrence run
    position by position: outputs and the six gradients."""
    from deeplearning4j_tpu.ops import ssd as _ssd

    args, position_by_position = _ssd_inputs(b, t, h, p, g, n)

    def chunkwise(x, dt, a, bm, cm, d):
        return _ssd.ssd(x, dt, a, bm, cm, d, chunk=chunk)

    return _compare(name, chunkwise, position_by_position, args, tol)


def _ssd_kernels_case(name, *, b, t, h, p, g, n, chunk, interpret, tol):
    """The scan's two kernels alone against the ``jax.numpy`` chunkwise
    form and against the recurrence run position by position: outputs and
    the six gradients. On the chip ``resolve_ssd`` has to choose them."""
    from deeplearning4j_tpu.ops import ssd as _ssd

    args, position_by_position = _ssd_inputs(b, t, h, p, g, n)
    if not interpret:
        x, bm = args[0], args[3]
        _expect(_ssd.resolve_ssd(x.shape, bm.shape, x.dtype, chunk)
                is _ssd._kernels,
                f"{name}: the dispatch does not choose the kernels here")

    def kernels(x, dt, a, bm, cm, d):
        return _ssd.ssd_kernels(x, dt, a, bm, cm, d, chunk=chunk,
                                interpret=interpret)

    def chunked(x, dt, a, bm, cm, d):
        return _ssd._chunked(x, dt, a, bm, cm, d, chunk)

    out = _compare(name, kernels, chunked, args, tol)
    far = _compare(name, kernels, position_by_position, args, tol)
    return {**out, "recurrence_fwd_rel_err": far["fwd_rel_err"],
            "recurrence_bwd_rel_err": far["bwd_rel_err"],
            "wall_s": round(out["wall_s"] + far["wall_s"], 1)}


def _lstm_case(name, *, t, b, hsz, peephole, masked, interpret, tol):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.layers.core import matmul
    from deeplearning4j_tpu.ops import lstm_pallas as _lp
    from deeplearning4j_tpu.utils import dtypes as _dtypes

    n_in = 32
    layer = (L.GravesLSTM if peephole else L.LSTM)(n_out=hsz)
    params = layer.init(jax.random.PRNGKey(hsz), I.RecurrentType(n_in, t))
    x = jax.random.normal(jax.random.PRNGKey(t), (b, t, n_in), jnp.float32)
    mask = None
    if masked:
        lens = np.linspace(max(t // 4, 1), t, b).astype(np.int32)
        mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None],
                           jnp.float32)
    if not interpret:
        _expect(layer._fused_eligible(x, mask),
                f"{name}: the dispatch gate does not admit this shape")
    cd, _ = _dtypes.compute_dtypes_for(x.dtype)

    def kernel(params, x):
        # LSTM.apply's fused branch, with the interpret switch it lacks
        xz = matmul(x.reshape(b * t, -1), params["Wx"]) + params["b"]
        xz = xz.reshape(b, t, 4 * hsz).transpose(1, 0, 2)
        zeros = jnp.zeros((b, hsz), cd)
        wp = params.get("Wp")
        hs, _ = _lp.fused_sequence_padded(
            xz.astype(cd), params["Wh"].astype(cd), zeros, zeros,
            wp=None if wp is None else wp.astype(cd),
            mask=None if mask is None else mask.T, interpret=interpret)
        y = hs.transpose(1, 0, 2).astype(jnp.float32)
        return y if mask is None else y * mask[..., None]

    def ref(params, x):
        return layer.apply(params, {}, x, mask=mask)[0].astype(jnp.float32)

    return _compare(name, kernel, ref, (params, x), tol)


def _looped_block_case(name, *, b, t, width, h, d, ffn, interpret, tol):
    """Forward and gradient of one sandwich block with rotary positions
    (RMSNorm before and after each of a causal attention and a gated SiLU
    FFN, no biases: the ouro-train-t2048 cell's block) through the
    dispatch, which takes the flash kernel on the chip, against the same
    block on the naive branch."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.ops import attention_pallas as _ap

    block = models.looped_lm(8, n_layers=1, d_model=width, n_heads=h,
                             head_dim=d, ffn_width=ffn,
                             seq_len=t).layers[1].blocks[0]
    params = block.init(jax.random.PRNGKey(width), I.RecurrentType(width, t))
    x = jax.random.normal(jax.random.PRNGKey(t), (b, t, width), jnp.float32)
    if not interpret:
        _expect(_ap.resolve_attention(
            (b, t, h, d), (b, t, h, d), None, jnp.float32) is not None,
            f"{name}: the dispatch gate does not admit this shape")

    def apply(params, x):
        return block.apply(params, {}, x)[0]

    return _compare(name, apply, apply, (params, x), tol)


def _rows_inside(rows, r):
    """A sorted buffer's rows below ``r``, zeros past them."""
    import jax.numpy as jnp
    return jnp.where((jnp.arange(rows.shape[0]) < r)[:, None], rows, 0)


def _gather_back(ys, w, order, w_sorted, r):
    """``moe._combine`` as plain gathers of every slot under autodiff."""
    import jax.numpy as jnp
    inv = jnp.argsort(order)
    w = jnp.where((inv < r).reshape(w.shape), w, 0)
    return jnp.sum(_rows_inside(ys, r)[inv].reshape(*w.shape, -1)
                   * w[..., None].astype(ys.dtype), axis=1)


@contextlib.contextmanager
def _naive_routed_layers():
    """The routed layer's naive branch for the length of a trace: the
    expert FFN as three ``jax.lax.ragged_dot`` with the activation between
    them under autodiff, the row movement as plain gathers of every slot."""
    import jax

    from deeplearning4j_tpu.nn.layers import moe as _moe

    def three_products(xs, w_gate, w_up, w_down, sizes, act, out, rows=None):
        dot = lambda a, w, to: jax.lax.ragged_dot(
            a, w.astype(a.dtype), sizes, preferred_element_type=to)
        h = dot(xs, w_up, xs.dtype).astype(out)
        h = act(h) if w_gate is None else act(
            dot(xs, w_gate, xs.dtype).astype(out)) * h
        return dot(h.astype(xs.dtype), w_down, out)

    saved = _moe.expert_ffn, _moe._dispatch, _moe._combine
    _moe.expert_ffn = three_products
    _moe._dispatch = lambda k, dtype, x, tok, r: _rows_inside(
        x[tok], r).astype(dtype)
    _moe._combine = _gather_back
    try:
        yield
    finally:
        _moe.expert_ffn, _moe._dispatch, _moe._combine = saved


def _hybrid_step_case(name, *, t, vocab, tol):
    """The hybrid conv/attention mixture-of-experts step at a small depth
    and the lfm2-train-t8192 cell's widths (a short convolution with the
    dense FFN, grouped-query attention with QK-norm and 8 of 64 routed
    experts, a short convolution with 8 more; routing fixed by the expert
    bias, so that no near-tie decides differently on the two branches): the
    compiled train step's
    kernel count (the flash forward and backward, twelve kernels an
    expert layer: six grouped products, gate with up as one and down, each
    forward, for the input gradient and for the weight gradient, the
    activation's two, ``moe_act_fwd`` and ``moe_act_bwd``, and the row
    movement's two kernels twice each; from PR 40 two a short
    convolution, ``causal_conv_fwd`` and ``causal_conv_bwd``), then logits
    and every
    gradient through the dispatch against the naive branch (attention in
    ``jax.numpy``, the grouped products as ``jax.lax.ragged_dot``, the
    routed layer's row movement as plain gathers of every slot under
    autodiff)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(models.hybrid_moe_lm(
        vocab, layer_types=("conv", "full_attention", "conv"),
        num_dense_layers=1, experts_held=(0, 8), seq_len=t))
    net.init()
    # routing by the bias alone (router weights zero, so every score is a
    # half): a token near a tie would pick another expert on the naive
    # branch than through the dispatch, and one such token moves the
    # largest difference by its whole FFN. Experts 1, 2 and 5 of the four
    # chosen are held in one layer, 0 and 3 in the other; the router's
    # gradient still flows through the weights
    for i, picks in ((2, (1, 2, 5, 40)), (3, (0, 3, 10, 20))):
        net.params[i]["moe_router"] = jnp.zeros_like(
            net.params[i]["moe_router"])
        net.state[i]["expert_bias"] = jnp.zeros(
            (64,), jnp.float32).at[jnp.array(picks)].set(1.0)
    x = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (1, t)),
                    jnp.int32)
    labels = jnp.roll(x, -1, axis=1)
    step = net.make_train_step(donate=False)
    text = step.lower(net.params, net.state, net.opt_state, x, labels, 0,
                      jax.random.PRNGKey(0), None).compile().as_text()
    n_calls, want = text.count("tpu_custom_call"), 2 + 2 * 12 + 2 * 2
    _expect(n_calls == want,
            f"{name}: compiled train step holds {n_calls} "
            f"tpu_custom_call(s), expected {want}")
    state = net.state

    def logits(params):
        return net.apply_fn(params, state, x, train=True, logits=True)[0]

    def naive(params):
        with _naive_routed_layers():
            return logits(params)

    out = _compare(name, logits, naive, (net.params,), tol)
    return {**out, "tpu_custom_calls": n_calls}


def _gated_delta_step_case(name, *, t, vocab, tol):
    """The hybrid linear/softmax-attention mixture-of-experts step at three
    layers and the qwen3next-train-t4096 cell's widths (gated delta rule,
    gated attention at head width 256, gated delta rule; every layer the
    softmax top-10 mixture over 512 experts with 16 held and its gated
    shared expert; router weights zero, so that every probability is
    1/512, the ten lowest-numbered experts are chosen on both branches and
    no near-tie decides differently): the compiled train step's kernel
    count (the flash forward and, from PR 46, the fused backward's one
    kernel, and twelve kernels an expert layer as ``_hybrid_step_case``
    counts them), then logits and every gradient through the dispatch against
    the naive branch (the recurrence token by token as the benchmark's
    plain reference runs it, attention in ``jax.numpy``, the grouped
    products as ``jax.lax.ragged_dot``, the row movement as plain
    gathers). From PR 35 the recurrence is two kernels a gated-delta
    layer, ``gdn_fwd`` and ``gdn_bwd``, from PR 40 its convolution with
    SiLU two more, ``causal_conv_fwd`` and ``causal_conv_bwd``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next as _plain
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import gated_delta as _gd

    net = MultiLayerNetwork(models.gated_delta_moe_lm(
        vocab, n_layers=3, full_attention_interval=2, experts_held=(0, 16),
        seq_len=t))
    net.init()
    for i in (1, 2, 3):
        net.params[i]["moe_router"] = jnp.zeros_like(
            net.params[i]["moe_router"])
    x = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (1, t)),
                    jnp.int32)
    labels = jnp.roll(x, -1, axis=1)
    step = net.make_train_step(donate=False)
    text = step.lower(net.params, net.state, net.opt_state, x, labels, 0,
                      jax.random.PRNGKey(0), None).compile().as_text()
    n_calls, want = text.count("tpu_custom_call"), 2 + 3 * 12 + 2 * (2 + 2)
    _expect(n_calls == want,
            f"{name}: compiled train step holds {n_calls} "
            f"tpu_custom_call(s), expected {want}")
    for kernel in ("flash_attn_fwd", "flash_attn_bwd_fused", "gdn_fwd",
                   "gdn_bwd", "causal_conv_fwd", "causal_conv_bwd"):
        _expect(kernel in text, f"{name}: no {kernel} in the compiled step")
    for kernel in ("flash_attn_bwd_dkv", "flash_attn_bwd_dq"):
        _expect(kernel not in text,
                f"{name}: {kernel} in the compiled step")
    state = net.state

    def logits(params):
        return net.apply_fn(params, state, x, train=True, logits=True)[0]

    def token_by_token(q, k, v, g, beta):
        r = v.shape[2] // q.shape[2]
        return jax.vmap(_plain.delta_rule)(
            jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta)

    def naive(params):
        saved, _gd.gated_delta_rule = _gd.gated_delta_rule, token_by_token
        try:
            with _naive_routed_layers():
                return logits(params)
        finally:
            _gd.gated_delta_rule = saved

    out = _compare(name, logits, naive, (net.params,), tol)
    return {**out, "tpu_custom_calls": n_calls}


def kernel_cases(interpret):
    """The widths the dispatch gates admit on the chip; toy widths for the
    interpret-mode CPU test (same kernels, same variants)."""
    if interpret:
        fl = dict(b=2, t=128, h=1, d=16)
        return (
            [("flash_causal", dict(fl, causal=True, masked=False,
                                   block=False, backward="fused")),
             ("flash_padding_mask", dict(fl, causal=False, masked=True,
                                         block=False)),
             ("flash_block", dict(fl, causal=True, masked=False,
                                  block=True))],
            [("lstm_resident", dict(t=4, b=8, hsz=128, peephole=False,
                                    masked=False)),
             ("lstm_resident_peephole_masked",
              dict(t=4, b=8, hsz=128, peephole=True, masked=True)),
             ("lstm_tiled_masked", dict(t=3, b=8, hsz=640, peephole=False,
                                        masked=True))],
            [("gated_delta_kernels", dict(b=1, t=100, hk=1, hv=2, d=128))],
            [("causal_conv_silu", dict(b=2, t=100, width=512, columns=384,
                                       taps=4, gates=False,
                                       split=(128, 256))),
             ("causal_conv_gates", dict(b=1, t=40, width=768, columns=256,
                                        taps=3, gates=True, split=())),
             ("causal_conv_bias_silu", dict(b=1, t=40, width=384,
                                            columns=384, taps=4, gates=False,
                                            split=(128, 256), bias=True))],
            [("ssd_chunkwise", dict(b=1, t=100, h=4, p=8, g=2, n=16,
                                    chunk=32))],
            [("ssd_kernels", dict(b=1, t=150, h=4, p=64, g=2, n=128,
                                  chunk=128))])
    return (
        [("flash_causal_t4096_h8_d64",
          dict(b=1, t=4096, h=8, d=64, causal=True, masked=False,
               block=False)),
         ("flash_causal_t1024_h16_d64",   # the gpt2m-train-t1024 cell's call
          dict(b=4, t=1024, h=16, d=64, causal=True, masked=False,
               block=False)),
         ("flash_causal_t2048_h4_d128",
          dict(b=1, t=2048, h=4, d=128, causal=True, masked=False,
               block=False)),
         # the two width-256 cells' length: one backward kernel a head,
         # 19.06 MiB of VMEM asked of Mosaic at float32 inputs (PR 46)
         ("flash_causal_t4096_h4_d256",
          dict(b=1, t=4096, h=4, d=256, causal=True, masked=False,
               block=False, backward="fused")),
         # past the budget, where no cell is: the split form still
         # compiles and matches
         ("flash_causal_t8192_h4_d256_split",
          dict(b=1, t=8192, h=4, d=256, causal=True, masked=False,
               block=False, backward="split")),
         ("flash_padding_mask_t1024",
          dict(b=4, t=1024, h=8, d=64, causal=False, masked=True,
               block=False)),
         ("flash_block_t1024",
          dict(b=2, t=1024, h=8, d=64, causal=True, masked=False,
               block=True))],
        [("lstm_resident_h512",
          dict(t=128, b=64, hsz=512, peephole=False, masked=False)),
         ("lstm_resident_h512_peephole",
          dict(t=128, b=64, hsz=512, peephole=True, masked=False)),
         ("lstm_resident_h512_masked",
          dict(t=128, b=64, hsz=512, peephole=False, masked=True)),
         ("lstm_resident_h512_peephole_masked",
          dict(t=128, b=64, hsz=512, peephole=True, masked=True)),
         ("lstm_tiled_h1024",
          dict(t=32, b=64, hsz=1024, peephole=False, masked=False)),
         ("lstm_tiled_h1024_peephole_masked",
          dict(t=32, b=64, hsz=1024, peephole=True, masked=True))],
        [("gated_delta_t4096_h16_32_d128",   # qwen3next-train-t4096's call
          dict(b=1, t=4096, hk=16, hv=32, d=128))],
        [("causal_conv_silu_t4096_c8192",    # qwen3next-train-t4096's call
          dict(b=1, t=4096, width=12288, columns=8192, taps=4, gates=False,
               split=(2048, 2048, 4096))),
         ("causal_conv_gates_t8192_c2048",   # lfm2-train-t8192's call
          dict(b=1, t=8192, width=6144, columns=2048, taps=3, gates=True,
               split=())),
         ("causal_conv_bias_silu_t4096_c6144",   # nemotron3nano's call
          dict(b=1, t=4096, width=6144, columns=6144, taps=4, gates=False,
               split=(4096, 1024, 1024), bias=True))],
        [("ssd_t4096_h64_p64_g8_n128",       # nemotron3nano-train-packed's
          dict(b=1, t=4096, h=64, p=64, g=8, n=128, chunk=128))],
        [("ssd_kernels_t4096_h64_p64_g8_n128",   # the same call
          dict(b=1, t=4096, h=64, p=64, g=8, n=128, chunk=128)),
         ("ssd_kernels_t1000_ragged",
          dict(b=2, t=1000, h=64, p=64, g=8, n=128, chunk=128))])


def kernels_phase(*, interpret, tol):
    t0 = time.perf_counter()
    flash, lstm, gated_delta, causal_conv, ssd, ssd_kernels = kernel_cases(
        interpret)
    results = [_flash_case(n, interpret=interpret, tol=tol, **kw)
               for n, kw in flash]
    results += [_gated_delta_case(n, interpret=interpret, tol=tol, **kw)
                for n, kw in gated_delta]
    results += [_causal_conv_case(n, interpret=interpret, tol=tol, **kw)
                for n, kw in causal_conv]
    results += [_ssd_case(n, tol=tol, **kw) for n, kw in ssd]
    results += [_ssd_kernels_case(n, interpret=interpret, tol=tol, **kw)
                for n, kw in ssd_kernels]
    if not interpret:  # through the dispatch: nothing to choose off the chip
        results.append(_looped_block_case(
            "looped_lm_t2048_h16_d128", b=2, t=2048, width=2048, h=16, d=128,
            ffn=5632, interpret=False, tol=tol))
        results.append(_hybrid_step_case(
            "hybrid_moe_lm_t2048_3layers", t=2048, vocab=1024, tol=tol))
        # three layers of bfloat16 rounding on each branch: the gradients
        # read 0.0299 with the recurrence in jax.numpy (PR 34) and 0.0362
        # with the kernels (PR 35), both forms 0.003-0.004 a layer from the
        # float32 recurrence (PERF.md section 6): 0.03 was at the edge
        results.append(_gated_delta_step_case(
            "gated_delta_moe_lm_t2048_3layers", t=2048, vocab=1024,
            tol={**tol, "bwd": 0.05}))
        results += [   # three train cells' calls (the last: glm47flash's)
            _flash_backward_time("flash_bwd_t1024_h16_d64_f32", b=4, t=1024,
                                 h=16, d=64, interpret=False),
            _flash_backward_time("flash_bwd_t2048_h16_d128_f32", b=2, t=2048,
                                 h=16, d=128, interpret=False),
            _flash_backward_time("flash_bwd_t4096_h20_d256_f32", b=1, t=4096,
                                 h=20, d=256, interpret=False),
            # each kernel alone with the steps it walks: sdar's call
            # (80 live of 256), lfm2's (136 of 256), glm47flash's (36 of 64)
            _flash_steps_time("flash_steps_bd4_t8192_h32_d128", bh=32,
                              t=8192, d=128, block_diffusion=(4096, 4)),
            _flash_steps_time("flash_steps_causal_t8192_h32_d64", bh=32,
                              t=8192, d=64),
            _flash_steps_time("flash_steps_causal_t4096_h20_d256", bh=20,
                              t=4096, d=256),
            # glm47flash's call and gpt2m's: [20, 4096, 256], [64, 1024, 64]
            _flash_rounded_once_case("flash_rounded_once_t4096_h20_d256",
                                     b=1, t=4096, h=20, d=256,
                                     interpret=False),
            _flash_rounded_once_case("flash_rounded_once_t1024_h16_d64",
                                     b=4, t=1024, h=16, d=64,
                                     interpret=False)]
    results += [_lstm_case(n, interpret=interpret, tol=tol, **kw)
                for n, kw in lstm]
    return _say({"phase": "kernels", **_device_doc(), "interpret": interpret,
                 "tolerance": tol, "results": results,
                 "peak_bytes_in_use": _peak_bytes(),
                 "wall_s": round(time.perf_counter() - t0, 1)})


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def multichip_phase(make_net, *, vocab, seq_len, n_chips, global_batch,
                    parity_batch, steps, loss_tol, batch_buckets,
                    seq_buckets, lengths, serve_tol, workdir):
    """The same model over a ``data=n_chips`` mesh.

    * ``ParallelTrainer`` at ``global_batch``: finite, falling losses;
      parameters, optimizer state and batch shards on every device.
    * Loss parity at ``parity_batch`` — the largest global batch ONE chip
      also holds — against a plain one-device ``net.fit`` on the same rows.
    * Warm-manifest round trips with ``n_chips`` devices visible: a mesh
      engine (``serving:mesh=`` kind) and a plain one-device engine, each
      restored from what the first start wrote, with zero compiles.
    """
    import jax

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.parallel import (MeshSpec, ParallelTrainer,
                                             make_mesh)
    from deeplearning4j_tpu.parallel import mesh as _pmesh
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.utils import compile_cache as cc

    telemetry.enable()
    t0 = time.perf_counter()
    devices = jax.devices()
    _expect(len(devices) >= n_chips,
            f"--chips {n_chips} needs {n_chips} devices, jax sees "
            f"{len(devices)}")
    mesh = make_mesh(MeshSpec(data=n_chips), devices=devices[:n_chips])
    want_devs = set(devices[:n_chips])

    # -- data-parallel train at the global batch ----------------------------
    x, y = lm_batch(global_batch, seq_len, vocab)
    trainer = ParallelTrainer(make_net(), mesh).init()
    xs, ys = _pmesh.shard_batch(mesh, (x, y))
    losses = [float(trainer.step(xs, ys)) for _ in range(steps)]
    _expect(np.isfinite(losses).all() and losses[-1] < losses[0],
            f"data-parallel losses {losses}")
    leaves = jax.tree_util.tree_leaves((trainer.params, trainer.opt_state))
    for leaf in leaves + [xs, ys]:
        _expect(set(leaf.sharding.device_set) == want_devs,
                f"a {leaf.shape} buffer lives on "
                f"{sorted(d.id for d in leaf.sharding.device_set)}, not on "
                f"all {n_chips} devices")
    _expect([s.data.shape[0] for s in xs.addressable_shards]
            == [global_batch // n_chips] * n_chips,
            "the batch is not split evenly over the data axis")
    sharded_opt = sum(1 for leaf in
                      jax.tree_util.tree_leaves(trainer.opt_state)
                      if not leaf.sharding.is_fully_replicated)
    bytes_in_use = {f"{d.platform}:{d.id}":
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in devices[:n_chips]}
    if devices[0].platform == "tpu":
        _expect(all(bytes_in_use.values()),
                f"a device holds no bytes: {bytes_in_use}")
    peak = {f"{d.platform}:{d.id}": _peak_bytes(d) for d in devices[:n_chips]}
    del trainer, xs, ys, leaves

    # -- loss parity against one device, same rows --------------------------
    xp, yp = x[:parity_batch], y[:parity_batch]
    del x, y
    one = make_net()
    ref = _fit_losses(one, xp, yp, steps=steps, batch=parity_batch)
    del one
    dp = ParallelTrainer(make_net(), mesh).init()
    xps, yps = _pmesh.shard_batch(mesh, (xp, yp))
    got = [float(dp.step(xps, yps)) for _ in range(steps)]
    loss_err = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))
                            / np.abs(ref)))
    _expect(loss_err <= loss_tol,
            f"loss sequence on {n_chips} chips {got} vs one chip {ref}: "
            f"max relative {loss_err:.4g} (tol {loss_tol})")
    net = dp.sync_to_net()      # host copies; serve them from the device
    net.params, net.state = jax.device_put((net.params, net.state))
    del dp, xps, yps

    # -- warm-manifest round trips with n_chips devices visible -------------
    events0 = dict(cc.event_counts())
    trips = {}
    for label, m, buckets in (("mesh", mesh, [n_chips]),
                              ("one_device", None, list(batch_buckets))):
        path = os.path.join(workdir, f"wm_{label}.zip")
        cold = ServingEngine(net, name=f"cold_{label}",
                             input_spec=(max(seq_buckets), 1),
                             buckets=buckets, seq_buckets=list(seq_buckets),
                             mesh=m, warm_manifest=cc.WarmManifest.for_net(net))
        _expect(cold.save_warm_manifest(path) == path,
                f"{label}: no executable was serializable")
        warmed = cold.stats()["aot"]["warmed"]
        del cold
        doc = serve_phase(net, vocab=vocab, batch_buckets=buckets,
                          seq_buckets=seq_buckets, lengths=lengths,
                          tol=serve_tol, mesh=m, warm_manifest=path,
                          name=f"warm_{label}")
        _expect(doc["aot"]["manifest_hits"] == doc["aot"]["warmed"] == warmed
                and not doc["aot"]["manifest_misses"],
                f"{label}: warm start compiled: {doc['aot']}")
        trips[label] = {"warmed": warmed,
                        "manifest_hits": doc["aot"]["manifest_hits"]}
    events = {e: n - events0.get(e, 0) for e, n in cc.event_counts().items()}
    for bad in ("deserialize_fail", "serialize_fail", "mismatch_drop"):
        _expect(not events.get(bad), f"compile_cache_total{{event={bad}}} "
                                     f"= {events.get(bad)}")

    return _say({"phase": "multichip", **_device_doc(),
                 "mesh": dict(mesh.shape), "global_batch": global_batch,
                 "losses": [round(v, 4) for v in losses],
                 "buffers_on_devices": sorted(d.id for d in want_devs),
                 "opt_state_leaves_sharded": sharded_opt,
                 "batch_shard_rows": global_batch // n_chips,
                 "bytes_in_use": bytes_in_use, "peak_bytes_in_use": peak,
                 "parity_batch": parity_batch,
                 "losses_one_chip": [round(v, 4) for v in ref],
                 f"losses_{n_chips}_chips": [round(v, 4) for v in got],
                 "loss_max_rel_err": float(f"{loss_err:.3g}"),
                 "loss_tolerance": loss_tol,
                 "warm_manifest_round_trips": trips,
                 "compile_cache_events": events,
                 "wall_s": round(time.perf_counter() - t0, 1)})


# ---------------------------------------------------------------------------
# entry point: the chip, at full width
# ---------------------------------------------------------------------------

#: the ``longcontext`` configuration at full width
MODEL = dict(vocab=8192, n_layers=6, d_model=512, n_heads=8, seq_len=4096)

#: serving grid and the request mix over it (both seq buckets, exact and
#: padded lengths; below and above the flash crossover at T 1024)
BATCH_BUCKETS, SEQ_BUCKETS = (1, 4), (1024, 4096)
LENGTHS = (1024, 4096, 700, 3000, 1024, 4096, 700, 3000)

#: bf16 tolerances, as ``_rel_err``, each about three times the worst value
#: seen on the chip in PR 21. The served and the direct forward run the same
#: bf16 program at different paddings (and, below T 1024, the flash kernel
#: against the naive path): 0.008 seen. Kernels run against the jax.numpy
#: path under the same bf16 policy, gradients through up to 128 recurrent
#: steps: forward 0.006, backward 0.010 seen.
SERVE_TOL = 3e-2
KERNEL_TOL = {"fwd": 2e-2, "bwd": 3e-2}
#: four chips reduce the same bf16 gradients in another order: 1.1e-6
#: relative seen over four steps
LOSS_TOL = 1e-3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from deeplearning4j_tpu.utils import compile_cache as cc
    from deeplearning4j_tpu.utils import dtypes

    cache_dir = cc.enable_persistent_cache()     # before anything compiles
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax platform is {dev.platform!r}, not 'tpu' — "
              "this script only proves the chip path", file=sys.stderr)
        return 2
    _expect(len(jax.devices()) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, jax sees "
            f"{len(jax.devices())}")
    cache = {"requests": 0, "hits": 0}

    def count_cache_events(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
    jax.monitoring.register_event_listener(count_cache_events)
    entries_before = _cache_entries(cache_dir)
    t0 = time.perf_counter()
    dtypes.bf16_policy()
    shape = {k: MODEL[k] for k in ("vocab", "seq_len")}

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 1:
            net = build_net(**MODEL)
            phases = [
                train_phase(net, **shape, batch=4, steps=4, k=4,
                            workdir=workdir,
                            flash_calls=2 * MODEL["n_layers"]),
                serve_phase(net, vocab=MODEL["vocab"],
                            batch_buckets=BATCH_BUCKETS,
                            seq_buckets=SEQ_BUCKETS, lengths=LENGTHS,
                            tol=SERVE_TOL),
                kernels_phase(interpret=False, tol=KERNEL_TOL)]
        else:
            phases = [multichip_phase(
                lambda: build_net(**MODEL), **shape, n_chips=4,
                global_batch=16, parity_batch=8, steps=4,
                loss_tol=LOSS_TOL, batch_buckets=BATCH_BUCKETS,
                seq_buckets=SEQ_BUCKETS, lengths=LENGTHS[:4],
                serve_tol=SERVE_TOL, workdir=workdir)]

    from importlib import metadata
    _say({"summary": True, **_device_doc(), "chips": args.chips,
          "phases": [p["phase"] for p in phases],
          "jaxlib": jaxlib.__version__,
          "libtpu": metadata.version("libtpu"),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries": {"before": entries_before,
                                    "after": _cache_entries(cache_dir)},
          "persistent_cache": cache,
          "wall_s": round(time.perf_counter() - t0, 1)})
    # the contract line: the device as jax reports it
    _say({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
