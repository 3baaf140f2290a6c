"""Benchmarks for the BASELINE.md config matrix.

Default (driver-run): streams ONE JSON line per config as each completes
(lenet, resnet50, lstm, word2vec, parallel, transformer), so a late crash can never erase
earlier results, then a final headline summary line
{"metric", "value", "unit", "vs_baseline", ...}. A single config can be
selected via ``python bench.py <config>`` or ``BENCH_CONFIG``:

  lenet     LeNet MNIST MLN train samples/sec          (BASELINE.md #1)
  resnet50  ResNet50 CG train samples/sec + MFU        (BASELINE.md #2)
  word2vec  SkipGram-negative-sampling words/sec       (BASELINE.md #3)
  lstm      GravesLSTM char-RNN train tokens/sec       (BASELINE.md #4)
  parallel  data-parallel LeNet scaling over all chips (BASELINE.md #5)

Where it runs: the measurement path needs a chip. Without
``BENCH_PREFLIGHT=1`` a run on any platform other than ``tpu`` exits
non-zero; with it (tier-1 stages 2-14, ``JAX_PLATFORMS=cpu``) shapes shrink
and the records are counter/parity gates, never device numbers. Every record
carries ``platform``, ``device_kind``, ``device_count`` and ``jax_version``.
A config that raises emits a ``<name>_FAILED`` record, the sweep continues,
and the exit code is non-zero. One process holds the chip: the parent stays
off jax for ``coldstart``, whose legs are fresh processes that each need it.

MFU accounting: the train step is AOT-lowered once; XLA's own
``cost_analysis()`` FLOPs are recorded next to the analytic
``resnet50_flops_per_example`` estimate so the two can be cross-checked
(reference role: CudnnConvolutionHelper.java:389 — the fast path must be
*shown* executing, with bf16 visible in the HLO).

The reference publishes no in-repo numbers (BASELINE.json published:{});
``vs_baseline`` compares against recorded order-of-magnitude estimates for
DL4J 0.9 on nd4j-native CPU (documented per config below) until measured
reference numbers exist.
"""

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

from deeplearning4j_tpu.telemetry.registry import write_jsonl as _emit

# order-of-magnitude DL4J 0.9 CPU estimates (see module docstring)
BASELINES = {
    "lenet": 500.0,       # samples/sec, LeNet minibatch train
    "resnet50": 2.0,      # samples/sec, ResNet50 batch train on CPU
    "word2vec": 300e3,    # words/sec, AggregateSkipGram multithreaded
    "lstm": 20e3,         # tokens/sec, GravesLSTM char-RNN
    "parallel": 500.0,    # per-chip LeNet baseline (scaling config)
}

def _peak_flops():
    """bf16 peak FLOP/s of ONE local device from the one peaks table
    (telemetry/devices.py), or None off-TPU — utilization fields are then
    omitted, not computed against a chip that is not there."""
    from deeplearning4j_tpu.telemetry import devices as _devices
    peaks = _devices.device_peaks()
    return None if peaks is None else peaks["bf16_flops"]


def _cost_analysis(lowered):
    """(compiled, cost dict) of an AOT-lowered step."""
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return compiled, dict(ca or {})


def _timed_window(loop, iters):
    """One timed window: ``loop()`` runs all ``iters`` dispatches and
    returns a value that data-depends on the whole chain; the host clock
    stops after that value is fetched, so the window covers all device
    work. Returns (dt_per_iter, host_val)."""
    import jax

    t0 = time.perf_counter()
    host_val = jax.device_get(loop())
    return (time.perf_counter() - t0) / iters, host_val


def _train_bench(raw_step, p, s, o, args, warmup, iters):
    """AOT-compile a donated train step, time it with state threaded through
    (so donation is real), and return (dt_per_iter, xla_info). The timed
    loop threads state through every iteration and ends with one fetch of
    the final loss (see ``_timed_window``)."""
    import jax

    jitted = jax.jit(raw_step, donate_argnums=(0, 1, 2))
    lowered = jitted.lower(p, s, o, *args)
    info = {"bf16_in_hlo": "bf16" in lowered.as_text()}
    step, ca = _cost_analysis(lowered)
    if ca.get("flops"):
        info["xla_flops_per_step"] = float(ca["flops"])
    if ca.get("bytes accessed"):
        info["xla_bytes_per_step"] = float(ca["bytes accessed"])

    loss = None
    for _ in range(warmup):
        p, s, o, loss = step(p, s, o, *args)
    jax.device_get(loss)
    # BENCH_PROFILE=<dir>: capture an xprof/TensorBoard trace of the timed
    # window (per-op device time, HBM traffic, MXU utilization — the data
    # behind any MFU improvement claim)
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)

    def loop():
        nonlocal p, s, o, loss
        for _ in range(iters):
            p, s, o, loss = step(p, s, o, *args)
        return loss

    dt, final_loss = _timed_window(loop, iters)
    if profile_dir:
        jax.profiler.stop_trace()
        info["profile_dir"] = profile_dir
    info["final_loss"] = float(final_loss)
    return dt, info


def _preflight():
    return os.environ.get("BENCH_PREFLIGHT", "0") == "1"


def bench_lenet(batch=256, warmup=3, iters=100):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.utils import dtypes

    if _preflight():
        batch, iters = 64, 5
    dtypes.bf16_policy()  # bf16 compute on the MXU, f32 params/accum
    net = MultiLayerNetwork(lenet())
    net.init()
    raw = net.make_train_step(donate=True, jit=False)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, batch)])
    rng = jax.random.PRNGKey(0)

    dt, info = _train_bench(raw, net.params, net.state, net.opt_state,
                            (x, y, 0, rng, None), warmup, iters)
    sps = batch / dt
    return {"metric": "lenet_mnist_train_samples_per_sec",
            "value": round(sps, 1), "unit": "samples/sec/chip",
            "vs_baseline": round(sps / BASELINES["lenet"], 2),
            "step_time_ms": round(1e3 * dt, 2), "batch": batch, **info}


def bench_resnet50(batch=64, hw=224, warmup=2, iters=30):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.models.resnet import resnet50_flops_per_example
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.utils import dtypes

    if _preflight():
        batch, hw, warmup, iters = 8, 64, 1, 3  # BENCH_BATCH ignored: keep tiny
    else:
        try:
            batch = int(os.environ.get("BENCH_BATCH", batch))
        except ValueError:
            _emit({"event": "bad_BENCH_BATCH",
                   "value": os.environ.get("BENCH_BATCH")})
    dtypes.bf16_policy()
    # BENCH_REMAT=1: block-level activation rematerialization (A/B knob for
    # the HBM-traffic-vs-FLOPs trade; see models/resnet.py docstring)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    net = ComputationGraph(resnet50(
        height=hw, width=hw, n_classes=1000,
        checkpoint_scope="prefix" if remat else None))
    net.init()
    raw = net.make_train_step(donate=True, jit=False)
    rs = np.random.RandomState(0)
    x = {net.conf.inputs[0]:
         jnp.asarray(rs.rand(batch, hw, hw, 3).astype(np.float32))}
    y = {net.conf.outputs[0]:
         jnp.asarray(np.eye(1000, dtype=np.float32)[
             rs.randint(0, 1000, batch)])}
    rng = jax.random.PRNGKey(0)

    dt, info = _train_bench(raw, net.params, net.state, net.opt_state,
                            (x, y, 0, rng, None), warmup, iters)
    sps = batch / dt
    # analytic estimate: train step ~ 3x fwd FLOPs
    analytic = 3.0 * resnet50_flops_per_example(hw, hw) * batch
    # MFU counts USEFUL model FLOPs: under remat XLA's cost analysis also
    # counts the recompute (inflating MFU), so that leg uses analytic
    flops = analytic if remat else (info.get("xla_flops_per_step")
                                    or analytic)
    rec = {"metric": "resnet50_train_samples_per_sec",
           "value": round(sps, 2), "unit": "samples/sec/chip",
           "vs_baseline": round(sps / BASELINES["resnet50"], 2),
           "step_time_ms": round(1e3 * dt, 2), "batch": batch, "hw": hw,
           "remat": remat,
           "analytic_flops_per_step": analytic,
           "flops_source": ("analytic_3x_fwd"
                            if flops is analytic
                            else "xla_cost_analysis"), **info}
    peak = _peak_flops()
    if peak is not None:
        rec["mfu"] = round(flops / dt / peak, 4)
    return rec


def bench_lstm(batch=64, seq=128, hidden=512, vocab=96, warmup=2, iters=30):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import text_generation_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.utils import dtypes

    if _preflight():
        batch, seq, hidden, warmup, iters = 8, 32, 256, 1, 3
    else:
        try:
            # H-sweep knob for the tiled large-H kernel A/B (VERDICT r2 #5)
            hidden = int(os.environ.get("BENCH_LSTM_HIDDEN", hidden))
        except ValueError:
            _emit({"event": "bad_BENCH_LSTM_HIDDEN",
                   "value": os.environ.get("BENCH_LSTM_HIDDEN")})
    dtypes.bf16_policy()
    conf = text_generation_lstm(vocab, hidden=hidden, seq_len=seq)
    net = MultiLayerNetwork(conf)
    net.init()
    raw = net.make_train_step(donate=True, jit=False)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, seq))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)])
    rng = jax.random.PRNGKey(0)
    # BENCH_LSTM_MASKED=1: a variable-length batch (25-100% of T) — the
    # masked fused-kernel path (state freezing; VERDICT r3 #4 coverage on
    # hardware)
    masked = os.environ.get("BENCH_LSTM_MASKED", "0") == "1"
    mask = None
    if masked:
        lens = rs.randint(seq // 4, seq + 1, batch)
        mask = jnp.asarray((np.arange(seq)[None, :] < lens[:, None])
                           .astype(np.float32))

    dt, info = _train_bench(raw, net.params, net.state, net.opt_state,
                            (x, y, 0, rng, mask), warmup, iters)
    tps = batch * seq / dt
    # report whether the fused kernel actually DISPATCHES for these
    # shapes+mask — enabled() alone would label a scan-path run as fused.
    # Asks the layer's own dispatch predicate so bench can never diverge
    # from the real decision.
    fused = bool(net.conf.layers[0]._fused_eligible(x, mask))
    return {"metric": "graveslstm_charnn_train_tokens_per_sec",
            "value": round(tps, 1), "unit": "tokens/sec/chip",
            "vs_baseline": round(tps / BASELINES["lstm"], 2),
            "step_time_ms": round(1e3 * dt, 2), "batch": batch, "seq": seq,
            "hidden": hidden, "masked": masked,
            "fused_kernel": fused, **info}


def bench_word2vec(n_sentences=20000, sent_len=20, vocab=5000, dim=128):
    """BENCH_W2V_SCALE=production: V=100k / D=300 / 10M words — the scale
    InMemoryLookupTable.java (736 LoC) actually served (VERDICT r2 #6;
    round-2 measured only V=5k). Memory accounting at that scale: syn0 +
    syn1neg = 2 * V * D * 4 B = 240 MB on-device (v5e HBM 16 GB — single
    chip is fine; vocab-sharding over a mesh is only needed ~50x beyond)."""
    from deeplearning4j_tpu.text.word2vec import Word2Vec

    scale = os.environ.get("BENCH_W2V_SCALE", "")
    if scale == "production":
        vocab, dim, sent_len = 100_000, 300, 20
        n_sentences = 500_000  # 10M words
    if _preflight():
        n_sentences = 2000
        vocab, dim = min(vocab, 5000), min(dim, 128)
    rs = np.random.RandomState(0)
    # zipfian corpus
    ranks = np.arange(1, vocab + 1)
    probs = (1.0 / ranks); probs /= probs.sum()
    words = rs.choice(vocab, (n_sentences, sent_len), p=probs)
    # int-token sentences go straight to fit() (tokens are opaque dict
    # keys): string-formatting 10M words would dominate corpus build time,
    # which is not the path under test
    sents = words.tolist()

    def make():
        return Word2Vec(vector_size=dim, min_count=1, negative=5, epochs=1,
                        seed=1, batch_size=2048)

    # cold fit over the FULL corpus compiles every shape the timed fit will
    # see (scanned-epoch chunk + each tail size); a subset warm-up misses the
    # scan jit and the timed run then measures XLA compilation, not training
    t0 = time.perf_counter()
    make().fit(sents)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    make().fit(sents)
    dt = time.perf_counter() - t0
    wps = n_sentences * sent_len / dt
    return {"metric": "word2vec_sgns_words_per_sec",
            "value": round(wps, 1), "unit": "words/sec",
            "vs_baseline": round(wps / BASELINES["word2vec"], 2),
            "total_s": round(dt, 2),
            "warmup_s": round(warm_s, 2),  # compile + one cold epoch
            "vocab": vocab, "dim": dim,
            "n_words": n_sentences * sent_len,
            "table_mb": round(2 * vocab * dim * 4 / 1e6, 1)}


def bench_parallel(batch_per_chip=256, warmup=2, iters=50):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import MeshSpec, ParallelTrainer, make_mesh

    if _preflight():
        batch_per_chip, warmup, iters = 32, 1, 3
    from deeplearning4j_tpu.parallel import mesh as _pmesh

    n = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n, model=1))
    net = MultiLayerNetwork(lenet())
    net.init()
    trainer = ParallelTrainer(net, mesh)
    rs = np.random.RandomState(0)
    b = batch_per_chip * n
    # pre-shard once, like a steady-state training loop: trainer.step
    # then skips its per-step device_put dispatches
    x, y = _pmesh.shard_batch(mesh, (
        jnp.asarray(rs.rand(b, 28, 28, 1).astype(np.float32)),
        jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, b)])))

    def run():
        return trainer.step(x, y)

    for _ in range(warmup):
        out = run()
    jax.device_get(out)

    def loop():
        out = None
        for _ in range(iters):
            out = run()
        return out

    dt, _ = _timed_window(loop, iters)
    sps = b / dt
    per_chip = sps / n

    rec = {"metric": "parallel_lenet_train_samples_per_sec",
           "value": round(sps, 1), "unit": f"samples/sec/{n}chips",
           "vs_baseline": round(per_chip / BASELINES["parallel"], 2),
           "per_chip": round(per_chip, 1), "n_chips": n,
           "step_time_ms": round(1e3 * dt, 2)}
    if n > 1:
        # scaling efficiency vs a single-device run of the same per-chip
        # batch (BASELINE.md config #5's "scaling efficiency vs 1 chip")
        net1 = MultiLayerNetwork(lenet())
        net1.init()
        mesh1 = make_mesh(MeshSpec(data=1, model=1),
                          devices=jax.devices()[:1])
        tr1 = ParallelTrainer(net1, mesh1)
        # pre-shard the baseline's slice onto ITS mesh too — a slice of
        # the n-device array would re-dispatch a cross-mesh copy every
        # timed iteration, inflating scaling_efficiency
        x1, y1 = _pmesh.shard_batch(mesh1, (x[:batch_per_chip],
                                            y[:batch_per_chip]))
        for _ in range(warmup):
            out = tr1.step(x1, y1)
        jax.device_get(out)

        def loop1():
            out = None
            for _ in range(iters):
                out = tr1.step(x1, y1)
            return out

        dt1, _ = _timed_window(loop1, iters)
        single_sps = batch_per_chip / dt1
        rec["single_chip_samples_per_sec"] = round(single_sps, 1)
        rec["scaling_efficiency"] = round(per_chip / single_sps, 3)
    return rec


def bench_transformer(batch=32, seq=512, d_model=512, n_layers=6,
                      n_heads=8, vocab=8192, warmup=2, iters=30,
                      metric="transformer_lm_train_tokens_per_sec"):
    """Decoder-only LM tokens/sec — the net-new long-context config and the
    fused-attention (ops/attention_pallas.py) A/B target; no BASELINE.md
    analog exists because the reference has no attention."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import attention_pallas
    from deeplearning4j_tpu.utils import dtypes

    if _preflight():
        batch, seq, d_model, n_layers, vocab = 4, 64, 64, 2, 256
        warmup, iters = 1, 3
    dtypes.bf16_policy()
    conf = transformer_lm(vocab, n_layers=n_layers, d_model=d_model,
                          n_heads=n_heads, seq_len=seq)
    net = MultiLayerNetwork(conf)
    net.init()
    raw = net.make_train_step(donate=True, jit=False)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (batch, seq))
    x = jnp.asarray(ids[..., None].astype(np.float32))
    # one-hot on device: a np.eye(vocab) gather would allocate vocab^2 host
    # bytes (256 MiB at the default 8192)
    y = jax.nn.one_hot(jnp.asarray(np.roll(ids, -1, axis=1)), vocab,
                       dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)

    dt, info = _train_bench(raw, net.params, net.state, net.opt_state,
                            (x, y, 0, rng, None), warmup, iters)
    tps = batch * seq / dt
    # report whether the fused kernel actually DISPATCHES for these shapes
    q_shape = (batch, seq, n_heads, d_model // n_heads)
    fused = attention_pallas.resolve_attention(
        q_shape, q_shape, None, jnp.bfloat16) is not None
    # MFU by the standard LM accounting: train FLOPs/token ~ 6*P where P
    # counts MATMUL-path params only (the input embedding + positional
    # tables are a gather — counting them would inflate MFU ~14% at the
    # default config), + 12*L*d*T for attention scores/values
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(net.params))
    n_embed = sum(int(np.prod(p.shape)) for p in
                  jax.tree_util.tree_leaves(net.params[0]))
    flops_per_token = (6.0 * (n_params - n_embed)
                       + 12.0 * n_layers * d_model * seq)
    rec = {"metric": metric,
           "value": round(tps, 1), "unit": "tokens/sec/chip",
           "vs_baseline": None,  # net-new capability: no reference analog
           "step_time_ms": round(1e3 * dt, 2), "batch": batch, "seq": seq,
           "d_model": d_model, "n_layers": n_layers,
           "n_params": n_params, "fused_attention": fused, **info}
    peak = _peak_flops()
    if peak is not None:
        rec["mfu"] = round(flops_per_token * tps / peak, 4)
    return rec


def bench_fused(batch=128, n_batches=48, epochs=2):
    """K-sweep of the fused multi-step dispatch engine (nn/fused.py): the
    same tiny-MLP fit at ``steps_per_dispatch=K`` for each K in
    ``BENCH_FUSED_KS`` (the ``--steps-per-dispatch 1,4`` flag), end-to-end
    through the real fit loop — prefetch thread, shape bucketing and the
    one-dispatch-late score pipeline included, so the curve measures the
    dispatch amortization users actually get. The dataset is deliberately
    ragged (n % batch != 0) so every leg exercises the bucketed tail.
    CPU-smoke friendly: tier1.sh runs it under BENCH_PREFLIGHT=1."""
    import jax
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    ks = [int(s) for s in
          os.environ.get("BENCH_FUSED_KS", "1,4").split(",") if s.strip()]
    if _preflight():
        batch, n_batches, epochs = 32, 12, 2
    rs = np.random.RandomState(0)
    n = batch * n_batches - batch // 2  # ragged tail on purpose
    x = rs.rand(n, 64).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]
    steps_per_epoch = -(-n // batch)

    def make():
        conf = NeuralNetConfig(seed=3, updater=U.Adam(learning_rate=1e-3)) \
            .list(L.DenseLayer(n_out=128, activation="relu"),
                  L.DenseLayer(n_out=128, activation="relu"),
                  L.OutputLayer(n_out=10, loss="mcxent"),
                  input_type=I.FeedForwardType(64))
        net = MultiLayerNetwork(conf)
        net.init()
        return net

    def barrier(net):
        # fit keeps the loss pipeline one dispatch late: fetch a param
        # leaf so the timed window covers ALL device work
        jax.device_get(jax.tree_util.tree_leaves(net.params)[0])

    sweep = []
    for k in ks:
        net = make()
        net.fit(x, y, epochs=1, batch_size=batch, steps_per_dispatch=k)
        barrier(net)  # compile + warm epoch excluded from the window
        t0 = time.perf_counter()
        net.fit(x, y, epochs=epochs, batch_size=batch,
                steps_per_dispatch=k)
        barrier(net)
        dt = time.perf_counter() - t0
        steps = epochs * steps_per_epoch
        sweep.append({"k": k, "steps_per_sec": round(steps / dt, 1),
                      "samples_per_sec": round(steps * batch / dt, 1),
                      "wall_s": round(dt, 3)})
    best = max(sweep, key=lambda r: r["steps_per_sec"])
    base_leg = next((r for r in sweep if r["k"] == 1), sweep[0])
    return {"metric": "fused_dispatch_ksweep_steps_per_sec",
            "value": best["steps_per_sec"], "unit": "steps/sec",
            # speedup of the best K over the K=1 leg of THIS run — the
            # dispatch-amortization factor, not a cross-machine baseline
            "vs_baseline": round(best["steps_per_sec"]
                                 / max(base_leg["steps_per_sec"], 1e-9), 2),
            "best_k": best["k"], "batch": batch, "n_examples": n,
            "steps_per_epoch": steps_per_epoch, "ksweep": sweep}


def bench_serving(duration_s=2.0, probe_s=0.4, max_requests_per_point=6000):
    """Latency vs offered load through the production serving tier
    (deeplearning4j_tpu/serving): AOT-warm every bucket, probe the
    engine's capacity with a flat-out submit burst, then sweep offered
    loads from well under to well past saturation, recording p50/p99
    request latency and shed counts per point — the curve that shows
    where load shedding takes over from queueing (the admission-control
    story of the TF-Serving half of the system paper). The model is
    deliberately heavy enough that the Python submit loop can outrun the
    engine, so the past-saturation points genuinely saturate on CPU."""
    import jax  # noqa: F401 — backend pinned by main() before we build

    hidden = 2048
    if _preflight():
        hidden, duration_s, probe_s = 512, 0.6, 0.25
        max_requests_per_point = 1200
    # span tracing ON for the sweep (metrics stay as configured): every
    # request then carries a trace id, and each offered-load point can
    # name its worst request's causal timeline (`traces --trace-id ...`
    # against the ring / a flight dump) — BENCH rows become traceable
    from deeplearning4j_tpu.telemetry import tracing as _tracing
    _trace_prev = _tracing.enabled()
    _tracing.set_enabled(True)
    engine_box = []
    try:
        return _bench_serving_sweep(hidden, duration_s, probe_s,
                                    max_requests_per_point, engine_box)
    finally:
        # restore even when a point raises mid-sweep: a multi-config
        # `bench.py serving fused ...` run must not measure the LATER
        # configs with tracing silently left on (and the engine worker
        # must not outlive its sweep)
        for eng in engine_box:
            try:
                eng.stop()
            except Exception:
                pass
        _tracing.set_enabled(_trace_prev)


def _bench_serving_sweep(hidden, duration_s, probe_s,
                         max_requests_per_point, engine_box):
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import ServingEngine, ServingOverloaded

    conf = NeuralNetConfig(seed=7, updater=U.Sgd(learning_rate=0.1)).list(
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.OutputLayer(n_out=10, loss="mcxent"),
        input_type=I.FeedForwardType(64))
    net = MultiLayerNetwork(conf)
    net.init()
    deadline_s = 0.25
    engine = ServingEngine(net, name="bench", input_spec=(64,),
                           buckets=(1, 2, 4, 8, 16), max_queue=64,
                           default_deadline_s=deadline_s,
                           batch_window_s=0.001)
    engine_box.append(engine)  # caller's finally owns stop-on-failure
    warm_s = engine._warmup_s
    engine.start()
    rs = np.random.RandomState(0)
    xs = rs.rand(64, 64).astype(np.float32)

    def drain(futs):
        """(latencies, shed, worst_trace_id) from a point's futures — the
        worst trace id names the slowest served request's causal trace."""
        lats, shed, worst = [], 0, (None, None)
        for f in futs:
            try:
                f.get(timeout=30)
                lats.append(f.latency_s)
                if worst[0] is None or f.latency_s > worst[0]:
                    worst = (f.latency_s, f.trace_id)
            except ServingOverloaded:
                shed += 1
        return lats, shed, worst[1]

    # capacity probe: submit flat-out; the bounded queue sheds the excess,
    # and requests served per wall second IS the engine's capacity
    served0 = engine.stats()["requests"]["served"]
    futs = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < probe_s:
        try:
            futs.append(engine.submit(xs[i % 64]))
        except ServingOverloaded:
            time.sleep(0.0005)
        i += 1
    drain(futs)
    probe_dt = time.perf_counter() - t0
    capacity = max((engine.stats()["requests"]["served"] - served0)
                   / probe_dt, 1.0)

    curve = []
    for ratio in (0.3, 0.7, 1.5, 3.0):
        rps = capacity * ratio
        n = max(1, min(int(rps * duration_s), max_requests_per_point))
        interval = 1.0 / rps
        futs, shed_at_submit = [], 0
        t0 = time.perf_counter()
        for j in range(n):
            target = t0 + j * interval
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            try:
                futs.append(engine.submit(xs[j % 64]))
            except ServingOverloaded:
                shed_at_submit += 1
        offered_dt = max(time.perf_counter() - t0, 1e-9)
        lats, shed_deadline, worst_tid = drain(futs)
        # serve rate over the WHOLE window including the post-submit queue
        # drain — rating it over the submit window alone would credit the
        # backlog to throughput and report served_rps above real capacity
        total_dt = max(time.perf_counter() - t0, 1e-9)
        point = {"offered_rps": round(n / offered_dt, 1),
                 "load_ratio": ratio,
                 "served": len(lats),
                 "served_rps": round(len(lats) / total_dt, 1),
                 "shed": shed_at_submit + shed_deadline,
                 "shed_queue_full": shed_at_submit,
                 "shed_deadline": shed_deadline,
                 "worst_trace_id": worst_tid}
        if lats:
            point["p50_ms"] = round(1e3 * float(np.percentile(lats, 50)), 2)
            point["p99_ms"] = round(1e3 * float(np.percentile(lats, 99)), 2)
        curve.append(point)
    stats = engine.stats()
    engine.stop()
    peak = max(p["served_rps"] for p in curve)
    return {"metric": "serving_offered_load_sweep",
            "value": round(peak, 1), "unit": "requests/sec",
            "vs_baseline": None,  # net-new tier: no reference analog
            "hidden": hidden, "warmup_s": round(warm_s, 3),
            "capacity_probe_rps": round(capacity, 1),
            "buckets": stats["buckets"], "max_queue": stats["max_queue"],
            "deadline_ms": round(1e3 * deadline_s, 1),
            "aot": stats["aot"], "curve": curve}


def bench_seq_serving(n_requests=240):
    """The 2-D shape grid's padded-FLOPs claim, measured (ISSUE 20): one
    ragged-length RNN workload served twice through the REAL engine —
    once on a (batch, seq) grid, once padded flat to max_seq (the
    pre-grid behavior, expressed as a single-seq-bucket grid so both
    legs meter in the same token units) — and the usage ledger's
    padded-vs-real token columns read back per leg. The record carries
    the waste cut (flat waste ratio / grid waste ratio) as its headline;
    scripts/check_seq_serving.py gates on LEDGER EXACTNESS, COUNTERS and
    PARITY (rows and real tokens balance exactly against the submitted
    workload, zero lazy compiles once warmed, FLOPs priced exactly at
    2*params*padded_tokens, grid == flat outputs <= 1e-6, waste cut
    >= 2x) — never wall time on CPU."""
    import jax  # noqa: F401 — backend pinned by main() before we build

    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving import metering as _metering

    n_in, hidden = 8, 16
    buckets, seq_buckets, max_seq = (1, 2, 4), (32, 64, 128, 256), 256
    if _preflight():
        buckets, seq_buckets, max_seq = (1, 2), (16, 32, 64), 64
        n_requests = 60

    net = MultiLayerNetwork(NeuralNetConfig(seed=11).list(
        L.SimpleRnn(n_out=hidden),
        L.RnnOutputLayer(n_out=4, loss="mcxent"),
        input_type=I.RecurrentType(n_in, max_seq)))
    net.init()

    # ragged workload, skewed short the way prompt traffic is: 70% in
    # the first seq bucket, 20% mid, 10% near max — the flat leg pads
    # every one of them to max_seq
    rng = np.random.default_rng(3)
    lo, mid = seq_buckets[0], seq_buckets[len(seq_buckets) // 2]
    lengths = [int(rng.integers(2, lo + 1)) if u < 0.7
               else int(rng.integers(lo + 1, mid + 1)) if u < 0.9
               else int(rng.integers(mid + 1, max_seq + 1))
               for u in rng.random(n_requests)]
    xs = [rng.standard_normal((t, n_in)).astype(np.float32)
          for t in lengths]

    def run_leg(name, leg_seq_buckets):
        engine = ServingEngine(net, name=name, input_spec=(max_seq, n_in),
                               buckets=buckets,
                               seq_buckets=leg_seq_buckets,
                               max_queue=max(64, n_requests),
                               default_deadline_s=60.0,
                               batch_window_s=0.002)
        try:
            engine.start()
            futs = [engine.submit(x) for x in xs]
            outs = [np.asarray(f.get(timeout=60)) for f in futs]
            stats = engine.stats()
        finally:
            engine.stop()
        led = _metering.get_meter().usage()["models"].get(name, {})
        ledger = {f: led.get(f) for f in ("rows", "seq_tokens",
                                          "padded_tokens", "flops")}
        waste = (float(ledger["padded_tokens"] or 0)
                 / max(float(ledger["seq_tokens"] or 0), 1.0))
        return outs, {"buckets": stats["buckets"],
                      "seq_buckets": stats["seq_buckets"],
                      "served": stats["requests"]["served"],
                      "ledger": ledger,
                      "waste_ratio": round(waste, 4),
                      "aot": {k: v for k, v in stats["aot"].items()
                              if k != "manifest"}}, waste

    grid_outs, grid_leg, grid_waste = run_leg("seqgrid", seq_buckets)
    flat_outs, flat_leg, flat_waste = run_leg("seqflat", (max_seq,))

    # parity: the two legs served the same requests — identical real
    # steps, different padding, so outputs must agree; plus a handful of
    # direct references through the net itself
    max_err = max(float(np.max(np.abs(g - f)))
                  for g, f in zip(grid_outs, flat_outs))
    checked = 0
    for i in range(0, n_requests, max(1, n_requests // 5)):
        ref = np.asarray(net.output(xs[i][None]))[0]
        max_err = max(max_err, float(np.max(np.abs(grid_outs[i] - ref))))
        checked += 1
    waste_cut = flat_waste / max(grid_waste, 1e-9)
    return {"metric": "seq_serving_padded_waste",
            "value": round(waste_cut, 2), "unit": "x padded-waste cut",
            "vs_baseline": None,  # net-new claim: no reference analog
            "requests": n_requests,
            "real_seq_tokens": int(sum(lengths)),
            "seq_length_dist": {
                "min": int(min(lengths)),
                "p50": int(np.percentile(lengths, 50)),
                "max": int(max(lengths))},
            "param_count": int(net.num_params()),
            # the grid leg's padded/real token ratio: the analyzer's
            # lower-is-better headline (1.0 would be zero padding)
            "padded_waste_ratio": round(grid_waste, 4),
            "legs": {"grid": grid_leg, "flat": flat_leg},
            "parity": {"max_abs_err": max_err, "checked": checked}}


def bench_fleet(duration_s=1.2, probe_s=0.35):
    """The fleet tier end to end (deeplearning4j_tpu/fleet): N worker
    PROCESSES from one checkpoint + warm manifest behind the admission/
    routing front — capacity probe, offered-load sweep, and the
    kill-a-worker chaos leg (SIGKILL mid-sweep, router retries onto the
    survivors, supervisor respawns, the REPLACEMENT warm-starts with
    zero compiles). scripts/check_fleet.py gates on COUNTERS AND PARITY
    (fleet answers == single-engine answers <=1e-6, warm starts
    counter-asserted, zero uncounted request losses) — never wall time
    on CPU. One BENCH JSON record."""
    import shutil
    import signal
    import tempfile

    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.fleet import FleetRouter, FleetSupervisor
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import ServingEngine, ServingOverloaded
    from deeplearning4j_tpu.utils.serialization import save_model

    telemetry.enable()
    n_workers = 3
    hidden = 1024
    if _preflight():
        hidden, duration_s, probe_s = 256, 0.8, 0.25
    conf = NeuralNetConfig(seed=7, updater=U.Sgd(learning_rate=0.1)).list(
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.OutputLayer(n_out=10, loss="mcxent"),
        input_type=I.FeedForwardType(64))
    net = MultiLayerNetwork(conf)
    net.init()
    buckets = (1, 2, 4, 8)
    workdir = tempfile.mkdtemp(prefix="fleet_bench_")
    sup = router = None
    try:
        ckpt = os.path.join(workdir, "ckpt.zip")
        save_model(net, ckpt)
        # the instant-restart artifact every worker AND every elastic
        # replacement restores executables from (PR 9 tier) — built once
        # in THIS process; also the single-engine parity reference
        engine = ServingEngine(net, name="default", input_spec=(64,),
                               buckets=buckets)
        wm = engine.save_warm_manifest(os.path.join(workdir, "wm.zip"))
        rs = np.random.RandomState(0)
        xs = rs.rand(64, 64).astype(np.float32)
        ref = np.asarray(engine.output(xs[:16]))
        engine.stop()

        t0 = time.perf_counter()
        sup = FleetSupervisor(n_workers, model_path=ckpt,
                              buckets=buckets, warm_manifest=wm,
                              probe_interval_s=0.25, max_missed_probes=2)
        router = FleetRouter(name="default", max_queue=96,
                             default_deadline_s=0.5)
        sup.attach(router)
        sup.start()
        spawn_s = time.perf_counter() - t0
        worker_warm = {
            w.wid: {"warm": FleetSupervisor.replacement_is_warm(
                w.ready_doc), "aot": (w.ready_doc or {}).get("aot")}
            for w in sup._workers.values()}

        # parity: fleet answers == the single-engine answers (<=1e-6)
        futs = [router.submit(xs[i], deadline_s=30.0) for i in range(16)]
        got = np.stack([np.asarray(f.get(timeout=30)) for f in futs])
        parity = float(np.nanmax(np.abs(got - ref)))

        def drain(futs):
            lats, shed, errors = [], 0, 0
            for f in futs:
                try:
                    f.get(timeout=30)
                    lats.append(f.latency_s)
                except ServingOverloaded:
                    shed += 1
                except Exception:
                    errors += 1
            return lats, shed, errors

        def point(n_or_probe, rps=None):
            """Submit a load leg; returns the curve point dict."""
            futs, shed_submit = [], 0
            t0 = time.perf_counter()
            if rps is None:  # flat-out capacity probe
                i = 0
                while time.perf_counter() - t0 < probe_s:
                    try:
                        futs.append(router.submit(xs[i % 64]))
                    except ServingOverloaded:
                        shed_submit += 1
                        time.sleep(0.0005)
                    i += 1
            else:
                interval = 1.0 / rps
                for j in range(n_or_probe):
                    target = t0 + j * interval
                    now = time.perf_counter()
                    if target > now:
                        time.sleep(target - now)
                    try:
                        futs.append(router.submit(xs[j % 64]))
                    except ServingOverloaded:
                        shed_submit += 1
            offered_dt = max(time.perf_counter() - t0, 1e-9)
            lats, shed_late, errors = drain(futs)
            total_dt = max(time.perf_counter() - t0, 1e-9)
            pt = {"offered": len(futs) + shed_submit,
                  "offered_rps": round((len(futs) + shed_submit)
                                       / offered_dt, 1),
                  "served": len(lats),
                  "served_rps": round(len(lats) / total_dt, 1),
                  "shed": shed_submit + shed_late, "errors": errors}
            if lats:
                pt["p50_ms"] = round(
                    1e3 * float(np.percentile(lats, 50)), 2)
                pt["p99_ms"] = round(
                    1e3 * float(np.percentile(lats, 99)), 2)
            return pt

        probe_pt = point(None)
        capacity = max(probe_pt["served_rps"], 1.0)
        curve = []
        for ratio in (0.5, 1.5):
            n = max(1, min(int(capacity * ratio * duration_s), 3000))
            pt = point(n, rps=capacity * ratio)
            pt["load_ratio"] = ratio
            curve.append(pt)

        # --- kill-a-worker chaos leg: SIGKILL mid-sweep ---
        kill_rps = max(capacity * 0.6, 4.0)
        n = max(8, min(int(kill_rps * duration_s * 2), 3000))
        futs, shed_submit = [], 0
        killed_at = n // 3
        t0 = time.perf_counter()
        for j in range(n):
            if j == killed_at:
                sup.kill_worker("w0", sig=signal.SIGKILL)
            target = t0 + j / kill_rps
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            try:
                futs.append(router.submit(xs[j % 64]))
            except ServingOverloaded:
                shed_submit += 1
        lats, shed_late, errors = drain(futs)
        kill_leg = {"killed": "w0", "offered": n,
                    "served": len(lats),
                    "shed": shed_submit + shed_late, "errors": errors}
        if lats:
            kill_leg["p99_ms"] = round(
                1e3 * float(np.percentile(lats, 99)), 2)

        # elastic replacement: wait for the respawn ledger entry, then
        # prove the fleet recovered inside one more probe window
        respawn = None
        t_wait = time.perf_counter()
        while time.perf_counter() - t_wait < 90:
            evs = sup.status()["respawns"]
            if evs and evs[-1].get("spawn_s") is not None:
                respawn = evs[-1]
                break
            time.sleep(0.2)
        kill_leg["respawn"] = respawn
        recovery_pt = point(None)
        kill_leg["recovery_probe"] = recovery_pt
        futs = [router.submit(xs[i], deadline_s=30.0) for i in range(16)]
        got = np.stack([np.asarray(f.get(timeout=30)) for f in futs])
        kill_leg["post_parity_max_diff"] = float(
            np.nanmax(np.abs(got - ref)))

        counts = router.stats()["requests"]
        losses = (counts["submitted"] - counts["served"]
                  - counts["shed_queue_full"] - counts["shed_deadline"]
                  - counts["shed_no_worker"] - counts["shed_worker"]
                  - counts["errors"])
        peak = max(p["served_rps"] for p in curve + [probe_pt])
        return {"metric": "fleet_offered_load_sweep",
                "value": round(peak, 1), "unit": "requests/sec",
                "vs_baseline": None,  # net-new tier: no reference analog
                "workers": n_workers, "hidden": hidden,
                "buckets": list(buckets),
                "spawn_s": round(spawn_s, 2),
                "worker_warm_starts": worker_warm,
                "parity_max_diff": parity,
                "capacity_probe": probe_pt,
                "curve": curve, "kill_leg": kill_leg,
                "accounting": dict(counts, uncounted_losses=losses)}
    finally:
        try:
            if router is not None:
                router.stop()
            if sup is not None:
                sup.stop()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


def bench_cluster_obs(n_requests=12):
    """Cluster observability plane end to end (ISSUE 16): a REAL
    2-worker fleet with telemetry on BOTH sides of the wire. Four legs,
    one record, all gated STRUCTURALLY by scripts/check_cluster_obs.py
    (never wall time; the tracing-cost claim rides the existing
    trace_overhead stage's <=5% gate):

    * TRACE — a routed request's ring doc must hold ONE trace spanning
      admission→dispatch→worker-device→resolve: the worker process's
      serving.queue_wait/serving.device_exec spans grafted under the
      dispatching fleet.attempt with every parent link resolvable;
    * FEDERATE — ``/metrics?federate=1`` semantics via
      router.federated_metrics(): every live worker's counters under
      stable instance labels, and the federated per-instance values of
      ``serving_model_requests_total`` summing to the same total as
      per-member individual scrapes;
    * TIMELINE — router.timeline_sources() merged into one time-aligned
      view naming the router and both worker instances;
    * DEAD MEMBER — SIGKILL w0, federate again: the corpse is a COUNTED
      scrape error (federate_scrape_total{outcome=error}) inside a
      bounded wall, never a hang."""
    import shutil
    import signal
    import tempfile

    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.fleet import FleetRouter, FleetSupervisor
    from deeplearning4j_tpu.fleet.supervisor import default_worker_env
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.telemetry import federate as _fed
    from deeplearning4j_tpu.telemetry import timeline as _tl
    from deeplearning4j_tpu.telemetry import tracectx as _tracectx
    from deeplearning4j_tpu.utils.serialization import save_model

    telemetry.enable()
    hidden = 128 if _preflight() else 256
    conf = NeuralNetConfig(seed=5, updater=U.Sgd(learning_rate=0.1)).list(
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.OutputLayer(n_out=10, loss="mcxent"),
        input_type=I.FeedForwardType(32))
    net = MultiLayerNetwork(conf)
    net.init()
    workdir = tempfile.mkdtemp(prefix="cluster_obs_bench_")
    sup = router = None
    try:
        ckpt = os.path.join(workdir, "ckpt.zip")
        save_model(net, ckpt)
        # workers must trace too: the wire-propagated half of the story
        env = default_worker_env()
        env["DL4J_TPU_TELEMETRY"] = "1"
        # long probe interval: the dead-member leg needs the corpse to
        # still be a federation target when we scrape it
        sup = FleetSupervisor(2, model_path=ckpt, buckets=[1], env=env,
                              probe_interval_s=5.0, max_missed_probes=5)
        # a 2-row dispatch window makes least-outstanding spread a burst
        # across BOTH workers (one big window would coalesce the whole
        # burst into a single chunk to w0 and leave w1 uncounted)
        router = FleetRouter(name="default", request_timeout_s=30.0,
                             max_inflight_rows=2, max_dispatch_rows=2)
        sup.attach(router)
        sup.start()
        xs = np.random.RandomState(0).rand(8, 32).astype(np.float32)

        # --- TRACE leg ------------------------------------------------
        futs = [router.submit(xs[i % 8], deadline_s=30.0)
                for i in range(n_requests)]
        for f in futs:
            f.get(timeout=30)
        # the LAST future: the ring keeps the most recent 8 docs per
        # name, so an early trace may have been evicted by the burst
        doc = None
        for docs in _tracectx.get_ring().snapshot().values():
            for d in docs:
                if d.get("trace_id") == futs[-1].trace_id:
                    doc = d
        spans = (doc or {}).get("spans") or []
        names = [s.get("name") for s in spans]
        by_id = {s.get("span_id"): s for s in spans}
        wroot = next((s for s in spans
                      if s.get("name") == "fleet.worker_submit"), None)
        trace_leg = {
            "trace_id": futs[-1].trace_id,
            "n_spans": len(spans),
            "span_names": sorted(set(names)),
            "has_attempt": "fleet.attempt" in names,
            "has_remote_device_exec": "serving.device_exec" in names,
            "has_remote_queue_wait": "serving.queue_wait" in names,
            "remote_instance": ((wroot or {}).get("args") or {}
                                ).get("instance"),
            "parents_resolve": all(
                s.get("parent_id") in by_id for s in spans
                if s.get("parent_id") is not None)}

        # --- FEDERATE leg ---------------------------------------------
        metric = "serving_model_requests_total"

        def metric_sum(snap):
            m = snap.get(metric) or {}
            return sum(s.get("value") or 0 for s in m.get("series") or ())

        per_member = {wid: metric_sum(_fed.member_snapshot(
            addr + "/metrics", timeout_s=5.0))
            for wid, addr in router.endpoints()}
        fed = router.federated_metrics(timeout_s=5.0)
        by_inst = {}
        for s in (fed["metrics"].get(metric) or {}).get("series") or ():
            inst = s["labels"].get("instance")
            by_inst[inst] = by_inst.get(inst, 0) + (s.get("value") or 0)
        fed_leg = {"metric": metric, "per_member": per_member,
                   "federated_by_instance": by_inst,
                   "per_member_total": sum(per_member.values()),
                   "federated_total": sum(by_inst.values()),
                   "members": {i: m["ok"]
                               for i, m in fed["members"].items()},
                   "scrapes": fed["scrapes"]}

        # --- TIMELINE leg ---------------------------------------------
        merged = _tl.merge(router.timeline_sources(timeout_s=5.0))
        timeline_leg = {"instances": merged["instances"],
                        "n_traces": merged["n_traces"]}

        # --- DEAD MEMBER leg ------------------------------------------
        pid = sup.kill_worker("w0", sig=signal.SIGKILL)
        deadline = time.perf_counter() + 10
        while time.perf_counter() < deadline:
            try:
                os.kill(pid, 0)
                time.sleep(0.05)
            except OSError:
                break  # the corpse is real; connections now refuse
        t0 = time.perf_counter()
        fed2 = router.federated_metrics(timeout_s=2.0)
        wall = time.perf_counter() - t0
        dead_leg = {"killed": "w0", "wall_s": round(wall, 2),
                    "bounded": wall < 10.0,
                    "members": {i: m["ok"]
                                for i, m in fed2["members"].items()},
                    "scrapes": fed2["scrapes"]}

        return {"metric": "cluster_obs", "value": n_requests,
                "unit": "requests",
                "vs_baseline": None,  # net-new plane: no reference analog
                "workers": 2, "hidden": hidden,
                "trace": trace_leg, "federation": fed_leg,
                "timeline": timeline_leg, "dead_member": dead_leg,
                "counters": {"federate_scrape_total":
                             telemetry.series_map("federate_scrape_total")}}
    finally:
        try:
            if router is not None:
                router.stop()
            if sup is not None:
                sup.stop()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


def bench_slo_goodput():
    """SLO engine + goodput ledger end to end (ISSUE 17). Three legs,
    one record, all gated STRUCTURALLY by scripts/check_slo.py (never
    wall time):

    * INERT — the default ruleset evaluated repeatedly over the live
      registry with nothing injected: ZERO firing rules (a healthy
      process must not page anyone);
    * LEDGER — a real fit through the instrumented StepDriver with the
      goodput window rebased around exactly it: the six wall-clock
      categories must sum to the observed window (±5% gate), steps > 0;
    * STORM — a deterministic injected shed storm (serving_shed_total /
      serving_model_requests_total incremented directly, the engine
      evaluated on an explicit synthetic clock spanning the rule
      window): ``serving_shed_ratio`` walks ok -> firing, the
      transition lands in ``slo_alerts_total{rule,state}``, and a
      flight-recorder dump written mid-storm carries an ``slo`` section
      naming the burning rule — the SIGTERM-postmortem path, driven
      deterministically."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.continuous import chaos
    from deeplearning4j_tpu.continuous.driver import StepDriver
    from deeplearning4j_tpu.telemetry import flight as _flight
    from deeplearning4j_tpu.telemetry import goodput as _goodput
    from deeplearning4j_tpu.telemetry import slo as _slo

    telemetry.enable()
    reg = telemetry.get_registry()
    engine = _slo.get_engine()
    engine.clear()
    # the storm is injected, so the clock can be synthetic too: explicit
    # `now` values make every delta window deterministic regardless of
    # how fast this bench actually runs
    t0 = 1000.0

    # the storm's counters must EXIST (zero-valued) before the first
    # sample: the delta discipline ignores a series' first appearance,
    # so a series born mid-storm would contribute nothing that interval
    shed = reg.counter("serving_shed_total",
                       "load-shed requests per model and reason "
                       "(queue_full / deadline / shutdown)")
    req = reg.counter("serving_model_requests_total",
                      "requests by model and outcome (submitted/served/"
                      "shed_queue_full/shed_deadline/error)")
    shed.inc(0, model="slo_bench", reason="queue_full")
    req.inc(0, model="slo_bench", outcome="submitted")

    # --- INERT leg ----------------------------------------------------
    for i in range(3):
        engine.evaluate(now=t0 + 30.0 * i)
    st = engine.status()
    alerts0 = telemetry.series_map("slo_alerts_total")
    inert_leg = {"evaluations": st["evaluations"],
                 "firing": st["firing"], "warning": st["warning"],
                 "rules": len(st["rules"]),
                 "alerts_total": alerts0}

    # --- LEDGER leg ---------------------------------------------------
    iters = 12 if _preflight() else 60
    net = chaos.smoke_net(seed=11)
    net.init()
    batches = chaos.gen_batches(77, iters, batch=16)
    driver = StepDriver(net, lambda: ((x, y, None) for x, y in batches))
    ledger = _goodput.get_ledger()
    ledger.start()  # rebase the window around exactly this fit
    driver.run_round(None)  # whole epoch: iters instrumented steps
    driver.sync()
    ledger.note("exchange", 0.0015)  # the noted path, deterministically
    goodput_leg = ledger.snapshot()

    # --- STORM leg ----------------------------------------------------
    # 60 sheds / 120 submissions between samples: ratio 0.5 >= fire 0.20
    # with the denominator far past min_den — unambiguous, not marginal
    req.inc(120, model="slo_bench", outcome="submitted")
    shed.inc(60, model="slo_bench", reason="queue_full")
    engine.evaluate(now=t0 + 90.0)
    storm_status = engine.status()
    alerts1 = telemetry.series_map("slo_alerts_total")
    dump_path = _flight.get_recorder().dump("bench_slo_storm")
    dump_slo = None
    if dump_path:
        with open(dump_path) as f:
            dump_slo = json.load(f).get("slo")
    # recovery: healthy traffic (submissions, zero sheds) after the
    # window slides past the storm — state walks back to ok, and THAT
    # transition is counted too (without fresh denominator traffic the
    # rule would correctly HOLD firing: no data is not good news)
    req.inc(100, model="slo_bench", outcome="submitted")
    engine.evaluate(now=t0 + 400.0)
    recovered = engine.state("serving_shed_ratio")
    alerts2 = telemetry.series_map("slo_alerts_total")

    return {"metric": "slo_goodput", "value": len(engine.rules),
            "unit": "rules",
            "vs_baseline": None,  # net-new plane: no reference analog
            "inert": inert_leg,
            "goodput": goodput_leg,
            "fit_iters": iters,
            "storm": {"rule": "serving_shed_ratio",
                      "state": "firing" if "serving_shed_ratio"
                               in storm_status["firing"] else
                               engine.state("serving_shed_ratio"),
                      "firing": storm_status["firing"],
                      "value": next(
                          (r["value"] for r in storm_status["rules"]
                           if r["name"] == "serving_shed_ratio"), None),
                      "recovered_state": recovered,
                      "flight_dump": dump_path,
                      "flight_dump_slo": dump_slo},
            "alerts_before": alerts0, "alerts_after_storm": alerts1,
            "alerts_after_recovery": alerts2}


def bench_demand_obs():
    """Demand observability end to end (ISSUE 18). Three legs, one
    record, all gated STRUCTURALLY by scripts/check_demand.py (counters,
    ledger balance and parity — never wall time):

    * HISTORY — a real fit sampled into a MetricsHistory ring on a
      synthetic clock, persisted as atomic JSONL segments, and
      ``rate_over`` checked against the live SLO delta discipline fed
      the SAME sample points (the <=1e-6 parity acceptance);
    * FLEET — a REAL 2-worker fleet left ORGANICALLY IDLE while a
      FleetProber canaries it through the router wire path: probe_total
      advances while every unlabeled organic series stays exactly zero
      (the isolation acceptance), then tenant-labeled organic traffic
      runs and the per-model usage ledger (worker /usage, folded by
      router.health()) must balance EXACTLY against the router's
      served_rows;
    * STORM — a wrong-answer canary (pinned reference deliberately
      off) driven against an in-process engine on a synthetic clock:
      ``probe_failure_ratio`` walks ok -> firing -> ok with both
      transitions counted in ``slo_alerts_total``."""
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.continuous import chaos
    from deeplearning4j_tpu.continuous.driver import StepDriver
    from deeplearning4j_tpu.fleet import (FleetProber, FleetRouter,
                                          FleetSupervisor)
    from deeplearning4j_tpu.fleet.supervisor import default_worker_env
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.telemetry import slo as _slo
    from deeplearning4j_tpu.telemetry.history import MetricsHistory, load_dir
    from deeplearning4j_tpu.utils.serialization import save_model

    telemetry.enable()
    reg = telemetry.get_registry()
    workdir = tempfile.mkdtemp(prefix="demand_obs_bench_")
    sup = router = None
    try:
        # --- HISTORY leg ----------------------------------------------
        hist_dir = os.path.join(workdir, "history")
        store = MetricsHistory(history_dir=hist_dir, segment_samples=2,
                               max_segments=16)
        live = _slo._DeltaTrack(keep_s=3600.0)
        metric = "train_iterations_total"
        iters = 8 if _preflight() else 24
        net = chaos.smoke_net(seed=21)
        net.init()
        batches = chaos.gen_batches(33, iters, batch=16)
        driver = StepDriver(net, lambda: ((x, y, None) for x, y in batches))
        t0 = 1000.0
        store.sample_now(now=t0)
        live.sample(t0, _slo._select(reg.snapshot(), metric, {}))
        for i in range(4):
            driver.run_round(max(iters // 4, 1))
            t = t0 + 30.0 * (i + 1)
            store.sample_now(now=t)
            live.sample(t, _slo._select(reg.snapshot(), metric, {}))
        driver.sync()
        store.flush()
        t_end = t0 + 30.0 * 4
        parity = {}
        for window in (60.0, 120.0):
            want = live.rate(window, t_end)
            got = store.rate_over(metric, window, now=t_end)
            parity[f"{window:g}s"] = {
                "live": want, "history": got,
                "abs_err": (None if want is None or got is None
                            else abs(got - want))}
        reloaded, corrupt = load_dir(hist_dir)
        history_leg = {
            "metric": metric, "samples": len(store.samples()),
            "segments": len(store.segment_paths()),
            "reloaded_samples": len(reloaded), "corrupt": corrupt,
            "rate_parity": parity,
            "history_counters": {
                "history_samples_total":
                    telemetry.series_map("history_samples_total"),
                "history_segment_total":
                    telemetry.series_map("history_segment_total")}}

        # --- FLEET leg ------------------------------------------------
        hidden = 64 if _preflight() else 128
        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn import updaters as U
        from deeplearning4j_tpu.nn.conf import inputs as I
        from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = NeuralNetConfig(seed=5,
                               updater=U.Sgd(learning_rate=0.1)).list(
            L.DenseLayer(n_out=hidden, activation="relu"),
            L.OutputLayer(n_out=10, loss="mcxent"),
            input_type=I.FeedForwardType(32))
        fnet = MultiLayerNetwork(conf)
        fnet.init()
        ckpt = os.path.join(workdir, "ckpt.zip")
        save_model(fnet, ckpt)
        env = default_worker_env()
        env["DL4J_TPU_TELEMETRY"] = "1"
        sup = FleetSupervisor(2, model_path=ckpt, buckets=[1], env=env,
                              probe_interval_s=5.0, max_missed_probes=5)
        router = FleetRouter(name="demand", request_timeout_s=30.0)
        sup.attach(router)
        sup.start()
        xs = np.random.RandomState(0).rand(8, 32).astype(np.float32)
        # pinned references from the LOCAL net: the wire carries float32
        # exactly, so a correct fleet answers within 1e-6
        refs = np.asarray(fnet.output(xs))
        # the organic-facing series this process holds BEFORE any probe
        canaries = [{"name": f"c{i}", "x": xs[i], "expect": refs[i],
                     "model": "demand"} for i in range(2)]
        prober = FleetProber(router, canaries, tol=1e-6, timeout_s=20.0)
        rounds = 3
        lat_ms = []
        for _ in range(rounds):
            for r in prober.probe_once():
                if r["latency_ms"] is not None:
                    lat_ms.append(r["latency_ms"])
        idle_fleet_series = telemetry.series_map("fleet_requests_total")
        idle_probe_total = telemetry.series_map("probe_total")
        # now ORGANIC traffic, tenant-attributed — after the idle check
        futs = [router.submit(xs[i % 8], deadline_s=30.0,
                              tenant=("acme" if i % 2 else "zenith"))
                for i in range(8)]
        for f in futs:
            f.get(timeout=30)
        served_rows = router.stats()["requests"]["served_rows"]
        health = router.health()
        usage_fold = health.get("usage") or {}
        fleet_leg = {
            "rounds": rounds, "probes": prober.status()["probes"],
            "probe_ok": prober.status()["ok"],
            "idle_fleet_requests_total": idle_fleet_series,
            "idle_probe_total": idle_probe_total,
            "organic_requests": 8,
            "served_rows": served_rows,
            "usage_by_model": usage_fold,
            # the workers serve the checkpoint under THEIR model name;
            # the balance is per model, and this fleet serves exactly one
            "ledger_rows": sum((m or {}).get("rows") or 0
                               for m in usage_fold.values()),
            "fleet_requests_total":
                telemetry.series_map("fleet_requests_total"),
            "probe_total": telemetry.series_map("probe_total"),
            "probe_latency_p50_ms": (statistics.median(lat_ms)
                                     if lat_ms else None)}

        # --- STORM leg ------------------------------------------------
        engine = ServingEngine(fnet, name="storm", input_spec=(32,),
                               buckets=[1], batch_window_s=0.0).start()
        slo_engine = _slo.SloEngine(rules=_slo.default_rules(),
                                    registry=reg)
        x0 = xs[0]
        good = refs[0]
        ok_prober = FleetProber(engine, [{"x": x0, "expect": good,
                                          "model": "storm"}], tol=1e-6,
                                timeout_s=20.0)
        bad_prober = FleetProber(engine, [{"x": x0, "expect": good + 1.0,
                                           "model": "storm"}], tol=1e-6,
                                 timeout_s=20.0)
        ts = 5000.0
        states = []

        def drive(p, n, t):
            for _ in range(n):
                p.probe_once()
            st = slo_engine.evaluate(now=t)
            return {r["name"]: r for r in st["rules"]}[
                "probe_failure_ratio"]

        r0 = drive(ok_prober, 4, ts)            # healthy baseline
        states.append(r0["state"])
        r1 = drive(ok_prober, 4, ts + 60.0)
        states.append(r1["state"])
        r2 = drive(bad_prober, 8, ts + 120.0)   # the wrong-answer storm
        states.append(r2["state"])
        r3 = drive(ok_prober, 8, ts + 180.0)    # recovery
        r4 = drive(ok_prober, 8, ts + 400.0)    # window slides past storm
        states.extend([r3["state"], r4["state"]])
        engine.stop()
        storm_leg = {"rule": "probe_failure_ratio", "states": states,
                     "storm_value": r2["value"],
                     "alerts_total": telemetry.series_map(
                         "slo_alerts_total")}

        return {"metric": "demand_obs",
                "value": fleet_leg["probe_latency_p50_ms"], "unit": "ms",
                "vs_baseline": None,  # net-new plane: no reference analog
                "workers": 2, "hidden": hidden, "fit_iters": iters,
                "history": history_leg, "fleet": fleet_leg,
                "storm": storm_leg,
                "usage_rows_total":
                    telemetry.series_map("usage_rows_total")}
    finally:
        try:
            if router is not None:
                router.stop()
            if sup is not None:
                sup.stop()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


def bench_continuous():
    """The continuous-learning loop under injected faults (ISSUE 13):
    a REAL runner subprocess trains from a live pubsub stream while the
    harness kills the producer mid-stream (a replacement resumes it),
    poisons one batch with NaN (watchdog -> rollback -> resume), and
    delays one batch past the staleness bound (counted admission drop) —
    then an uninterrupted offline reference over the same deterministic
    stream must match the chaos run's state digest EXACTLY (params +
    opt_state + RNG chain + iteration). A second leg SIGTERMs a run
    mid-round (flight ring dumps) and resumes it from the on-disk bundle,
    again to digest equality. scripts/check_continuous.py gates on
    COUNTERS AND PARITY — never wall time on CPU. One BENCH JSON
    record."""
    import json as _json
    import shutil
    import signal
    import subprocess
    import tempfile

    from deeplearning4j_tpu.fleet.supervisor import default_worker_env
    from deeplearning4j_tpu.streaming.pubsub import StreamingBroker

    n, poison, stale, seed = 10, 4, 6, 42
    good_steps = n - 2  # poison rolled back, stale dropped
    workdir = tempfile.mkdtemp(prefix="continuous_bench_")
    env = default_worker_env()
    env["DL4J_TPU_FLIGHT_DIR"] = workdir
    runner_cmd = [sys.executable, "-m",
                  "deeplearning4j_tpu.continuous.runner"]
    pub_cmd = [sys.executable, "-m", "deeplearning4j_tpu.continuous.chaos"]

    _spawn_n = [0]

    def spawn(argv):
        # stderr to a FILE, not a pipe: the harness reads stdout
        # line-by-line while children run, and a child spewing more
        # than the pipe buffer to an undrained stderr would deadlock
        _spawn_n[0] += 1
        efpath = os.path.join(workdir, f"proc{_spawn_n[0]}.stderr")
        p = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                             stderr=open(efpath, "w"), text=True)
        p.efpath = efpath
        return p

    def errtail(proc):
        try:
            with open(proc.efpath) as f:
                return f.read()[-2000:]
        except OSError:
            return "<no stderr>"

    def read_ready(proc):
        while True:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("runner died before ready: "
                                   + errtail(proc))
            line = line.strip()
            if line.startswith("{") and "continuous_ready" in line:
                return _json.loads(line)

    def done_line(out, proc):
        for line in reversed(out.strip().splitlines()):
            if line.startswith("{") and "continuous_done" in line:
                return _json.loads(line)
        raise RuntimeError("no done line; stderr tail: " + errtail(proc))

    broker = StreamingBroker().start()
    try:
        # --- chaos leg: producer death + NaN poison + stale batch ------
        # the staleness bound must separate the INJECTED delay from the
        # leg's own scheduling jitter by orders of magnitude: a noisy CPU
        # can queue a legitimate batch for seconds behind a hot-swap
        # compile, and a counted-but-unexpected drop would break the
        # deterministic parity gate. 600s-old vs a 45s bound is
        # unambiguous on any machine that finishes the stage at all.
        chaos_args = runner_cmd + [
            "--snapshot", os.path.join(workdir, "chaos.zip"),
            "--broker-port", str(broker.port), "--gen-seed", str(seed),
            "--staleness-s", "45", "--quiet-timeout-s", "1.0",
            "--ingest-retries", "8", "--until-steps", str(good_steps),
            "--serve-registry"]
        runner = spawn(chaos_args)
        read_ready(runner)
        pub_args = pub_cmd + [
            "--port", str(broker.port), "--n", str(n),
            "--gen-seed", str(seed), "--poison", str(poison),
            "--delay-index", str(stale), "--delay-s", "600",
            "--interval-s", "0.08"]
        p1 = spawn(pub_args + ["--die-after", "3"])
        p1.communicate(timeout=120)  # dies abruptly after 3 publishes
        time.sleep(1.2)              # quiet stream: the retry path ticks
        p2 = spawn(pub_args + ["--start", "3"])
        out, _ = runner.communicate(timeout=240)
        p2.communicate(timeout=120)
        chaos_done = done_line(out, runner)

        ref = spawn(runner_cmd + [
            "--snapshot", os.path.join(workdir, "ref.zip"),
            "--offline-n", str(n), "--gen-seed", str(seed),
            "--offline-skip", f"{poison},{stale}"])
        rout, _ = ref.communicate(timeout=240)
        ref_done = done_line(rout, ref)

        # --- SIGTERM leg: dump mid-round, resume bit-exact -------------
        sn, sseed = 8, 55
        term = spawn(runner_cmd + [
            "--snapshot", os.path.join(workdir, "term.zip"),
            "--offline-n", str(sn), "--gen-seed", str(sseed),
            "--install-sigterm", "--round-lines",
            "--round-sleep-s", "0.35"])
        read_ready(term)
        rounds_seen = 0
        while rounds_seen < 2:
            line = term.stdout.readline().strip()
            if not line:
                raise RuntimeError("SIGTERM-leg runner exited early: "
                                   + errtail(term))
            if line.startswith("{") and '"round"' in line:
                rounds_seen = _json.loads(line).get("round", 0)
        os.kill(term.pid, signal.SIGTERM)
        term.wait(timeout=60)
        term_rc = term.returncode
        term.stdout.close()
        dump_reason = None
        dumps = sorted(f for f in os.listdir(workdir)
                       if f.startswith("dl4j_tpu_flight_"
                                       f"{term.pid}_"))
        if dumps:
            with open(os.path.join(workdir, dumps[-1])) as f:
                dump_reason = _json.load(f).get("reason")

        resumed = spawn(runner_cmd + [
            "--snapshot", os.path.join(workdir, "term.zip"), "--resume",
            "--offline-n", str(sn), "--gen-seed", str(sseed),
            "--offline-start", "-1"])
        ref2 = spawn(runner_cmd + [
            "--snapshot", os.path.join(workdir, "ref_full.zip"),
            "--offline-n", str(sn), "--gen-seed", str(sseed)])
        mout, _ = resumed.communicate(timeout=240)
        fout, _ = ref2.communicate(timeout=240)
        resume_done = done_line(mout, resumed)
        full_done = done_line(fout, ref2)

        return {
            "metric": "continuous_chaos",
            "value": int(chaos_done["iteration"]), "unit": "steps",
            "vs_baseline": None,  # net-new tier: no reference analog
            "n_batches": n, "poison_index": poison, "stale_index": stale,
            "expected_steps": good_steps,
            "chaos": {k: chaos_done[k]
                      for k in ("digest", "iteration", "summary",
                                "counters", "serving_probe_diff",
                                "flight_dumps")},
            "ref_digest": ref_done["digest"],
            "parity": chaos_done["digest"] == ref_done["digest"],
            "sigterm": {"rc": term_rc,
                        "expected_rc": -int(signal.SIGTERM),
                        "dump_reason": dump_reason,
                        "rounds_before_signal": rounds_seen,
                        "resume_digest": resume_done["digest"],
                        "resume_iteration": resume_done["iteration"],
                        "ref_digest": full_done["digest"],
                        "parity": (resume_done["digest"]
                                   == full_done["digest"])},
        }
    finally:
        broker.close()
        shutil.rmtree(workdir, ignore_errors=True)


def bench_hostfleet():
    """Elastic multi-host training under injected host death (ISSUE 15):
    a TrainingFleetSupervisor runs N training processes (one per
    simulated host, each with its own local device mesh and the zero1/
    fsdp sharded update) to a fixed round count, checkpointing a
    layout-free bundle at every round boundary. Three legs, one record:

    * CLEAN — N hosts, no faults: every host's final state digest must
      agree, zero recompiles, the snapshot->registry serving handoff
      probe <= 1e-6;
    * KILL — one host SIGKILLed mid-round; the wedged generation is torn
      down, re-formed at N-1 with the bundle RESHARDED into the smaller
      topology, and the finished run must be digest-EXACT with a
      fault-free reference fleet on that same final topology resuming
      from the same rollback bundle (the post-recovery snapshot also
      serves, probe-checked);
    * RESPAWN — same kill, but the generation re-forms at full size N:
      the final digest must equal the CLEAN leg's exactly (the clean run
      IS the fault-free reference on that topology).

    scripts/check_hostfleet.py gates on COUNTERS AND DIGEST PARITY
    (every death/generation/rollback counted, zero recompiles within a
    generation, no uncounted losses) — never wall time on CPU. The
    cross-host transport of this gate is the host-mediated round averaging
    (hostfleet/exchange.py); jax.distributed join/teardown per generation
    is real either way, and the gspmd in-step path has not run under a
    gate. One BENCH JSON record."""
    import shutil
    import tempfile

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.hostfleet import TrainingFleetSupervisor

    telemetry.enable()
    n_hosts, rounds, disp = 3, 4, 2
    local_devices, shard = 2, "fsdp"
    kill_after_round = 1
    workroot = tempfile.mkdtemp(prefix="hostfleet_bench_")

    def trim(res, wall):
        return {k: res[k] for k in
                ("digests", "iterations", "final_world", "final_generation",
                 "mode", "layout", "serving_probe_diff", "step_recompiles",
                 "tally", "generations", "chaos_kills",
                 "worker_counters")} | {"wall_s": round(wall, 1)}

    def leg(tag, *, world=n_hosts, respawn=False, kill=False,
            seed_bundle=None, serve=False):
        wd = os.path.join(workroot, tag)
        os.makedirs(wd, exist_ok=True)
        if seed_bundle is not None:
            shutil.copyfile(seed_bundle, os.path.join(wd, "bundle.zip"))
        t0 = time.perf_counter()
        sup = TrainingFleetSupervisor(
            world, workdir=wd, total_rounds=rounds,
            dispatches_per_round=disp, local_devices=local_devices,
            shard_params=shard, respawn=respawn, round_timeout_s=60,
            spawn_timeout_s=180,
            round_sleep_s=0.3 if kill else 0.0, serve_registry=serve)
        sup.start()
        try:
            if kill:
                # land the SIGKILL mid-round: host 0 has reported round
                # `kill_after_round` (its line lands AFTER the bundle
                # write, so the rollback target exists), the victim is
                # inside the next round, and the survivors wedge at that
                # round's exchange
                sup.wait_for_round(kill_after_round, timeout=180, host=0)
                sup.kill_host(world - 1)
            res = sup.wait(timeout=280)
        finally:
            sup.stop()
        return trim(res, time.perf_counter() - t0)

    try:
        clean = leg("clean", serve=True)
        kill = leg("kill", kill=True, serve=True)
        rb = kill["generations"][0].get("rollback_bundle")
        ref = (leg("kill_ref", world=n_hosts - 1, seed_bundle=rb)
               if rb else None)
        respawn = leg("respawn", respawn=True, kill=True)

        def agree(d):
            return len(set(d)) == 1

        parity = {
            "clean_hosts_agree": agree(clean["digests"]),
            "kill_hosts_agree": agree(kill["digests"]),
            "respawn_hosts_agree": agree(respawn["digests"]),
            "kill_vs_ref": (ref is not None
                            and kill["digests"][0] == ref["digests"][0]),
            "respawn_vs_clean":
                respawn["digests"][0] == clean["digests"][0],
        }

        return {"metric": "hostfleet_elastic", "unit": "steps",
                "value": kill["iterations"][0],
                "vs_baseline": None,  # net-new tier: no reference analog
                "hosts": n_hosts, "rounds": rounds,
                "dispatches_per_round": disp,
                "local_devices_per_host": local_devices, "layout": shard,
                "killed_after_round": kill_after_round,
                "clean": clean, "kill": kill, "kill_ref": ref,
                "respawn": respawn, "parity": parity,
                "counters": {name: telemetry.series_map(name) for name in (
                    "hostfleet_generations_total",
                    "hostfleet_rollback_rounds_total",
                    "distributed_hosts_alive")}}
    finally:
        shutil.rmtree(workroot, ignore_errors=True)


def bench_trace_overhead(reps=8):
    """Causal-tracing overhead on the fused step path: the same fused CPU
    fit measured with span/trace recording OFF and ON in adjacent
    (off, on) leg pairs, reported as the MEDIAN of the per-pair ratios —
    adjacent pairs share whatever throughput drift the host has, and the
    median rejects the noisy-neighbor outliers that make best-of
    comparisons swing double digits on a shared machine. The contract
    (tier1.sh gates on it): tracing a run costs a handful of contextvar
    ops + dict appends per DISPATCH, so fused steps/s must not regress
    more than a few percent."""
    import jax  # noqa: F401 — backend pinned by main() before we build
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.telemetry import tracing as _tracing
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    batch, n, hidden, k, epochs = 64, 2048, 256, 8, 2
    if _preflight():
        # smaller net, MORE epochs: each timed leg must be long enough
        # (>~100 ms) that scheduler jitter doesn't swamp the few-percent
        # effect the tier-1 gate is looking for. k stays at 8 — the trace
        # cost is per DISPATCH (root + producer spans + ring offer), so
        # the gate measures it at the fused engine's representative
        # amortization, not at a worst-case K=1
        n, hidden, epochs = 1024, 128, 10
    conf = NeuralNetConfig(seed=11, updater=U.Sgd(learning_rate=0.05)).list(
        L.DenseLayer(n_out=hidden, activation="relu"),
        L.OutputLayer(n_out=10, loss="mcxent"),
        input_type=I.FeedForwardType(32))
    net = MultiLayerNetwork(conf)
    net.init()
    rs = np.random.RandomState(3)
    x = rs.rand(n, 32).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]
    steps = epochs * (n // batch)
    net.fit(x, y, epochs=1, batch_size=batch, steps_per_dispatch=k)  # warm

    prev = _tracing.enabled()
    pairs = []  # (off_steps_per_sec, on_steps_per_sec) per adjacent pair
    try:
        for i in range(reps):
            pair = {}
            # adjacent legs share any drift; alternating which mode goes
            # first cancels the directional bias of a ramp (cooling /
            # warming host) that would otherwise tax one mode every pair
            order = (False, True) if i % 2 == 0 else (True, False)
            for on in order:
                _tracing.set_enabled(on)
                t0 = time.perf_counter()
                net.fit(x, y, epochs=epochs, batch_size=batch,
                        steps_per_dispatch=k)
                pair[on] = steps / (time.perf_counter() - t0)
            pairs.append((pair[False], pair[True]))
    finally:
        _tracing.set_enabled(prev)
        telemetry.tracectx.get_ring().clear()
    ratios = sorted(on / off for off, on in pairs)
    med_ratio = ratios[len(ratios) // 2]
    best_ratio = ratios[-1]
    best_off = max(p[0] for p in pairs)
    best_on = max(p[1] for p in pairs)
    regress_pct = round(100.0 * (1.0 - med_ratio), 2)
    # the tier-1 gate reads THIS one: a real regression (added sync, per-
    # step churn) taxes every adjacent pair, so even the best pair shows
    # it; noisy-neighbor jitter hits some pairs and not others, and the
    # best pair sails through. Median stays in the record as the honest
    # central estimate.
    gate_regress_pct = round(100.0 * (1.0 - best_ratio), 2)
    return {"metric": "trace_overhead_fused_steps_per_sec",
            "value": round(best_on, 1), "unit": "steps/sec",
            # overhead of tracing ON vs OFF in THIS run, not a
            # cross-machine baseline
            "vs_baseline": None,
            "off_steps_per_sec": round(best_off, 1),
            "on_steps_per_sec": round(best_on, 1),
            "median_on_off_ratio": round(med_ratio, 4),
            "regress_pct": regress_pct,
            "gate_regress_pct": gate_regress_pct,
            "pairs": [(round(o, 1), round(n, 1)) for o, n in pairs],
            "batch": batch, "k": k, "steps_per_leg": steps}


def bench_coldstart():
    """The instant-restart A/B (utils/compile_cache): four FRESH
    subprocesses — train and serve, each cold then warm — sharing one
    workdir. The cold legs populate the persistent XLA cache and save the
    instant-restart artifacts (train bundle with warm manifest; serving
    warm manifest); the warm legs restore them. Each leg reports its
    realized time-to-first-step / time-to-first-request (wall ms from
    process start) plus the compile_cache_total counters
    scripts/check_coldstart.py gates on: a warm restart must perform ZERO
    compiles for manifest-covered signatures (hits > 0, no misses, fused
    jit cache empty). Timings are recorded, not gated — on CPU both legs
    are dominated by interpreter+jax import, and the compile delta is the
    claim under test."""
    import shutil
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    leg_script = os.path.join(repo, "scripts", "coldstart_leg.py")
    workdir = tempfile.mkdtemp(prefix="coldstart_")
    # the cache itself is what this config measures, so the four legs share
    # a directory that starts empty — through the one variable the cache
    # rule reads
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(workdir, "xla_cache"))
    legs = {}
    try:
        for kind in ("train", "serve"):
            for mode in ("cold", "warm"):
                t0 = time.perf_counter()
                r = subprocess.run(
                    [sys.executable, leg_script, kind, mode, workdir],
                    capture_output=True, text=True, timeout=600, env=env)
                wall_s = time.perf_counter() - t0
                if r.returncode != 0:
                    tail = (r.stderr.strip().splitlines()
                            or ["<no stderr>"])[-1]
                    raise RuntimeError(
                        f"coldstart leg {kind}/{mode} rc={r.returncode}: "
                        f"{tail[:400]}")
                doc = json.loads(r.stdout.strip().splitlines()[-1])
                doc["leg_wall_s"] = round(wall_s, 3)
                legs.setdefault(kind, {})[mode] = doc
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    def ratio(kind, key):
        cold = legs[kind]["cold"].get(key)
        warm = legs[kind]["warm"].get(key)
        if not cold or not warm:
            return None
        return round(cold / warm, 2)

    warm_ttfr = legs["serve"]["warm"].get("time_to_first_request_ms")
    return {**legs["serve"]["warm"]["device"],
            "metric": "coldstart_time_to_first_request_ms",
            "value": round(warm_ttfr, 1) if warm_ttfr else 0,
            "unit": "ms (warm restart)",
            # cold/warm speedup measured in THIS run, not a cross-machine
            # baseline
            "vs_baseline": ratio("serve", "time_to_first_request_ms"),
            "first_step_cold_over_warm":
                ratio("train", "time_to_first_step_ms"),
            "train": legs["train"], "serving": legs["serve"]}


def bench_zero(batch_per_chip=32, n_batches=16, epochs=3):
    """ZeRO A/B (ISSUES 10+14, arxiv 2004.13336 + 1910.02054): the same
    data-parallel fit under the four weight-update/storage layouts —

      replicated   opt state a full copy per replica (the pre-PR-10 default)
      zero1        opt state sharded over 'data', reduce-scattered update
                   (the ParallelTrainer default)
      fsdp         params ALSO stored sharded, whole-tree gather at step
                   entry (ZeRO-3 storage)
      fsdp_stream  the homogeneous trunk scanned block-by-block, each
                   block gathered INSIDE the scan body and discarded
                   (ZeRO-3 streamed: step-peak = one block, not the model)

    — recording steps/s, addressable-shard-aware per-device param/opt
    bytes, the ANALYZED step-peak bytes per leg
    (``compiled.memory_analysis()`` via step_memory_analysis — the
    within-step number the steady-state gauges cannot see), the jit
    compile count (recompiles must stay flat: the sharded layouts add no
    shape churn), and max param divergence vs the replicated leg (the
    layouts are bit-exact re-expressions, so this must be ~0). A fifth
    COMPOSED leg runs the DP×TP×PP path (ComposedTrainer, 2×2×2 mesh)
    against the DP-only reference — per-step loss and end params ≤1e-6 —
    plus a ragged fit riding the pad_batch bucketing, pinned bit-exact
    vs manually padded steps. Layer dims are divisible by the data-axis
    size so the ideal 1/N per-device ratio is visible, not blurred by
    replicated ragged leaves. scripts/check_zero.py gates the bytes
    ratios, the streamed-vs-fsdp peak ratio, compile counters and the
    composed parity in tier1.sh (stage 6 pins an 8-device CPU mesh via
    XLA_FLAGS); steps/s is recorded, not gated — CPU legs jitter
    ±15-30%."""
    import jax
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import (MeshSpec, ParallelTrainer,
                                             make_mesh)
    from deeplearning4j_tpu.telemetry import devices as _devices

    hidden, trunk = 256, 4
    if _preflight():
        batch_per_chip, n_batches, epochs, hidden = 16, 8, 2, 128
    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev, model=1))
    batch = batch_per_chip * n_dev
    rs = np.random.RandomState(0)
    n = batch * n_batches
    x = rs.rand(n, 64).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rs.randint(0, 8, n)]

    def make_trainer(mode):
        # a homogeneous 4-deep hidden trunk so the streamed leg has a
        # stacked slab to scan (the entry layer maps 64 -> hidden and
        # stays outside it, like an embedding)
        conf = NeuralNetConfig(seed=5, updater=U.Adam(learning_rate=1e-3)) \
            .list(L.DenseLayer(n_out=hidden, activation="relu"),
                  *[L.DenseLayer(n_out=hidden, activation="relu")
                    for _ in range(trunk)],
                  L.OutputLayer(n_out=8, loss="mcxent"),
                  input_type=I.FeedForwardType(64))
        net = MultiLayerNetwork(conf)
        return ParallelTrainer(
            net, mesh,
            shard_optimizer_state=(mode != "replicated"),
            shard_params=(mode if mode in ("fsdp", "fsdp_stream")
                          else None)).init()

    legs = {}
    ref_w = None
    for mode in ("replicated", "zero1", "fsdp", "fsdp_stream"):
        tr = make_trainer(mode)
        tr.fit(x, y, batch_size=batch, epochs=1)      # compile + warm epoch
        jax.device_get(jax.tree_util.tree_leaves(tr.params)[0])
        compiles_warm = tr._step_fn._cache_size()
        t0 = time.perf_counter()
        tr.fit(x, y, batch_size=batch, epochs=epochs)
        jax.device_get(jax.tree_util.tree_leaves(tr.params)[0])
        dt = time.perf_counter() - t0
        steps = epochs * n_batches
        p_log, p_dev = _devices.tree_shard_bytes(tr.params)
        o_log, o_dev = _devices.tree_shard_bytes(tr.opt_state)
        recompiles = tr._step_fn._cache_size() - compiles_warm
        w = np.asarray(tr.params[1]["W"])   # a trunk block's weights
        if mode == "replicated":
            ref_w = w
        legs[mode] = {
            "steps_per_sec": round(steps / dt, 1),
            "samples_per_sec": round(steps * batch / dt, 1),
            "param_bytes_logical": p_log, "param_bytes_per_device": p_dev,
            "opt_state_bytes_logical": o_log,
            "opt_state_bytes_per_device": o_dev,
            "compiles": compiles_warm,
            "recompiles": recompiles,
            "final_loss": float(np.asarray(tr.score_value)),
            "max_param_diff_vs_replicated":
                float(np.abs(w - ref_w).max()),
            # the within-step XLA ledger (analysis-only AOT compile,
            # AFTER the counters above so it cannot blur the recompile
            # claim); None when the backend has no memory_analysis
            "step_peak": tr.step_memory_analysis(x[:batch], y[:batch]),
        }
    composed = _bench_zero_composed()
    z, r = legs["zero1"], legs["replicated"]
    peak = {m: (legs[m].get("step_peak") or {}).get("peak_bytes")
            for m in ("replicated", "fsdp", "fsdp_stream")}
    return {"metric": "zero_sharded_update_ab",
            "value": z["steps_per_sec"], "unit": "steps/sec",
            # speedup (or cost) of the sharded update vs the replicated
            # leg of THIS run — the A/B factor, not a cross-machine number
            "vs_baseline": round(z["steps_per_sec"]
                                 / max(r["steps_per_sec"], 1e-9), 2),
            "n_devices": n_dev, "batch": batch, "hidden": hidden,
            "trunk_layers": trunk,
            "opt_bytes_ratio": round(
                r["opt_state_bytes_per_device"]
                / max(z["opt_state_bytes_per_device"], 1), 2),
            "fsdp_param_bytes_ratio": round(
                r["param_bytes_per_device"]
                / max(legs["fsdp"]["param_bytes_per_device"], 1), 2),
            # step-peak: the number the streamed tier exists to shrink
            "stream_peak_ratio": (
                round(peak["fsdp"] / peak["fsdp_stream"], 3)
                if peak["fsdp"] and peak["fsdp_stream"] else None),
            "composed": composed,
            "legs": legs}


def _bench_zero_composed():
    """The DP×TP×PP composed-parity leg of ``bench.py zero``: a tiny
    ComposedTrainer on a 2×2×2 mesh vs the SAME model on a DP-only mesh
    (Sgd updater so fp noise is not Adam-eps-amplified — the claim under
    test is the parallel composition, not the optimizer conditioning),
    plus a ragged fit through the pad_batch bucketing pinned bit-exact
    against manually padded steps. Counters and parity only — never wall
    time."""
    import jax
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.parallel import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.composed import (ComposedParallelLM,
                                                      ComposedTrainer)

    devs = jax.devices()
    if len(devs) < 8:
        # the 2×2×2 composition needs 8 devices; the CI gate always has
        # them (XLA_FLAGS), a smaller live topology records the skip
        return {"skipped": f"needs 8 devices, have {len(devs)}"}
    cfg = dict(vocab_size=32, n_layers=2, d_model=16, n_heads=2, seq_len=8,
               n_microbatches=2)
    mesh_c = make_mesh(MeshSpec(data=2, model=2, seq=1, stage=2),
                       devices=devs[:8])
    mesh_d = make_mesh(MeshSpec(data=8, model=1, seq=1, stage=1),
                       devices=devs[:8])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 32, (16, 8))
    labels = np.roll(ids, -1, axis=1)

    def make(mesh):
        return ComposedTrainer(ComposedParallelLM(
            mesh=mesh, updater=U.Sgd(learning_rate=0.1), **cfg).init())

    tr, ref = make(mesh_c), make(mesh_d)
    loss_diffs = [abs(float(tr.step(ids, labels))
                      - float(ref.step(ids, labels))) for _ in range(3)]
    pdiff = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        tr.params, ref.params)))

    # ragged stream through the bucketing machinery == manual padding
    t_fit, t_man = make(mesh_c), make(mesh_c)
    t_fit.fit(ids[:12], labels[:12], batch_size=8)
    t_man.step(ids[:8], labels[:8], np.ones(8, np.float32))
    m = np.zeros(8, np.float32)
    m[:4] = 1
    xp = np.zeros((8, 8), ids.dtype)
    xp[:4] = ids[8:12]
    yp = np.zeros((8, 8), labels.dtype)
    yp[:4] = labels[8:12]
    t_man.step(xp, yp, m)
    ragged = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        t_fit.params, t_man.params)))
    return {"mesh": "2x2x2", "steps": 3,
            "max_loss_diff_vs_dp": max(loss_diffs),
            "max_param_diff_vs_dp": pdiff,
            "ragged_pad_param_diff": ragged,
            "masked_compiles": t_fit.lm._step_fn_masked._cache_size()}


def bench_longcontext():
    """Long-sequence decoder LM: seq 4096 is past the measured flash-attention
    crossover, so this config exercises the fused kernel (the naive path's
    [B,H,T,T] logits would be ~1 GiB/layer here). Under preflight,
    bench_transformer's own tiny-shape override applies."""
    return bench_transformer(batch=4, seq=4096, iters=10,
                             metric="transformer_lm_4k_train_tokens_per_sec")


CONFIGS = {"lenet": bench_lenet, "resnet50": bench_resnet50,
           "lstm": bench_lstm, "word2vec": bench_word2vec,
           "parallel": bench_parallel, "transformer": bench_transformer,
           "longcontext": bench_longcontext, "fused": bench_fused,
           "serving": bench_serving, "trace_overhead": bench_trace_overhead,
           "coldstart": bench_coldstart, "zero": bench_zero,
           "fleet": bench_fleet,
           "continuous": bench_continuous, "hostfleet": bench_hostfleet,
           "cluster_obs": bench_cluster_obs,
           "slo_goodput": bench_slo_goodput,
           "demand_obs": bench_demand_obs,
           "seq_serving": bench_seq_serving}
DEFAULT_ORDER = ["lenet", "resnet50", "lstm", "word2vec", "parallel",
                 "transformer", "longcontext", "fused", "serving", "zero"]

#: configs whose legs are fresh processes that each need the device: the
#: parent must not touch jax before they ran (a process that has, holds
#: the chip), so the legs stamp the record themselves
_CHILD_DEVICE_CONFIGS = {"coldstart"}


def _device_stamp():
    """The device identity every record carries. Refuses a platform other
    than ``tpu`` unless this is a ``BENCH_PREFLIGHT=1`` counter/parity run:
    the measurement path fails without a chip, it does not fall back."""
    from deeplearning4j_tpu.telemetry import devices as _devices
    stamp = _devices.device_stamp()
    if stamp["platform"] != "tpu" and not _preflight():
        sys.exit(f"bench.py: platform is {stamp['platform']!r}, not 'tpu' — "
                 "the measurement path needs a chip (BENCH_PREFLIGHT=1 "
                 "runs the CPU counter/parity gates)")
    return stamp


def _attach_observability(rec):
    """Health/memory/goodput summaries on every bench record (ISSUES 2,
    17): a record whose run leaked HBM or went NaN mid-measure must say so
    next to its samples/sec, not in a separate tool. The goodput window
    was rebased at config start (``_run_config``)."""
    from deeplearning4j_tpu.telemetry import devices as _devices
    from deeplearning4j_tpu.telemetry import goodput as _goodput
    from deeplearning4j_tpu.telemetry import health as _health
    mem = _devices.memory_summary()
    if mem.get("devices") or mem.get("live_array_bytes"):
        rec["device_memory"] = mem
    hs = _health.get_monitor().summary()
    if hs["steps_checked"] or hs["anomalies"]:
        rec["health"] = {k: hs[k] for k in
                         ("policy", "steps_checked", "nonfinite_steps",
                          "anomalies")}
    rec.setdefault("goodput", _goodput.get_ledger().snapshot())
    return rec


def _run_config(n):
    """Run one config and emit its record; a config that raises emits a
    ``<name>_FAILED`` record and returns None (main exits non-zero)."""
    from deeplearning4j_tpu.telemetry import goodput as _goodput
    t0 = time.perf_counter()
    try:
        stamp = {} if n in _CHILD_DEVICE_CONFIGS else _device_stamp()
        # per-config goodput window: the record's goodput block describes
        # THIS config's wall clock, not the whole sweep's
        _goodput.get_ledger().start()
        rec = CONFIGS[n]()
        rec.update(stamp, config=n, preflight=_preflight(),
                   wall_s=round(time.perf_counter() - t0, 1))
        _attach_observability(rec)
        _emit(rec)
        return rec
    except Exception as e:
        tb = traceback.format_exc().splitlines()
        _emit({"config": n, "metric": f"{n}_FAILED",
               "error": f"{type(e).__name__}: {e}"[:500],
               "traceback_tail": tb[-4:],
               "wall_s": round(time.perf_counter() - t0, 1)})
        return None


def _parse_steps_flag(argv):
    """``--steps-per-dispatch 1,4`` (or ``=1,4``): stash the K list in
    BENCH_FUSED_KS and strip the flag from argv. Returns True when the flag was present —
    with no explicit config name that selects the ``fused`` K-sweep."""
    for i, a in enumerate(list(argv)):
        if a == "--steps-per-dispatch" and i + 1 < len(argv):
            os.environ["BENCH_FUSED_KS"] = argv[i + 1]
            del argv[i:i + 2]
            return True
        if a.startswith("--steps-per-dispatch="):
            os.environ["BENCH_FUSED_KS"] = a.split("=", 1)[1]
            del argv[i:i + 1]
            return True
    return False


def main():
    ksweep_flag = _parse_steps_flag(sys.argv)
    name = (sys.argv[1] if len(sys.argv) > 1
            else ("fused" if ksweep_flag
                  else os.environ.get("BENCH_CONFIG", "all")))
    names = DEFAULT_ORDER if name == "all" else [name]

    from deeplearning4j_tpu.utils import compile_cache
    _emit({"event": "bench_start", "preflight": _preflight(),
           "compile_cache_dir": compile_cache.enable_persistent_cache()})
    results = {n: rec for n in names if (rec := _run_config(n)) is not None}
    # final headline line: resnet50 when it ran, else the first result
    headline = results.get("resnet50") or next(iter(results.values()), None)
    if headline is not None:
        _emit(headline)
    sys.exit(0 if len(results) == len(names) else 1)


if __name__ == "__main__":
    main()
