#!/usr/bin/env python
"""One cold-start bench leg in a FRESH process (bench.py coldstart spawns
four: train/serve x cold/warm).

A leg measures the realized cold-start tax — wall time from process start
(utils/compile_cache.PROCESS_T0, stamped at import) to the return of the
first train dispatch (the step compiled or loaded and enqueued, not
finished) / first served inference request — with the instant-restart
tier on:

* both modes share one persistent compilation cache: bench.py starts
  every leg with ``JAX_COMPILATION_CACHE_DIR=<workdir>/xla_cache`` (the
  cold leg POPULATES it, the fleet story);
* the cold leg runs with a fresh warm manifest attached and SAVES the
  instant-restart artifact (train: ``utils.serialization.save_bundle``;
  serve: ``ServingEngine.save_warm_manifest``);
* the warm leg RESTORES that artifact, so every covered signature
  deserializes instead of compiling — the check_coldstart.py gate asserts
  zero compiles from the counters this leg prints.

Prints ONE JSON line: {kind, mode, time_to_first_*_ms, events, ...}.

Usage: coldstart_leg.py {train|serve} {cold|warm} <workdir>
"""

import json
import os
import sys

# invoked by path from bench.py: sys.path[0] is scripts/, the package
# lives one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_net():
    """The leg model, rebuilt identically in every process (fingerprint
    equality across legs is what lets the manifest match)."""
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.nn import updaters as U
    from deeplearning4j_tpu.nn.conf import inputs as I
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = NeuralNetConfig(seed=7, updater=U.Adam(learning_rate=1e-3)).list(
        L.DenseLayer(n_out=64, activation="relu"),
        L.OutputLayer(n_out=10, loss="mcxent"),
        input_type=I.FeedForwardType(32))
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def _data():
    import numpy as np
    rs = np.random.RandomState(0)
    x = rs.rand(96, 32).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 96)]
    return x, y


def _train_leg(mode, workdir):
    from deeplearning4j_tpu.utils import compile_cache as cc
    from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                        save_bundle)

    bundle = os.path.join(workdir, "bundle.zip")
    if mode == "warm":
        net = load_bundle(bundle).net  # manifest attached when it matches
    else:
        net = _make_net()
        cc.attach_manifest(net, cc.WarmManifest.for_net(net))
    x, y = _data()
    # 3 minibatches at K=2: one full dispatch + a padded K-tail — both at
    # ONE bucketed signature, so the manifest fully covers a warm restart
    net.fit(x, y, epochs=1, batch_size=32, steps_per_dispatch=2)
    if mode == "cold":
        save_bundle(net, bundle)
    fused_compiles = sum(fn._cache_size()
                         for fn, _m in net._train_steps_fused.values())
    manifest = getattr(net, "_warm_manifest", None)
    return {"time_to_first_step_ms": cc.first_marks().get("step"),
            "fused_jit_compiles": fused_compiles,
            "manifest_entries": 0 if manifest is None else len(manifest)}


def _serve_leg(mode, workdir):
    from deeplearning4j_tpu.serving.engine import ServingEngine
    from deeplearning4j_tpu.utils import compile_cache as cc

    wm = os.path.join(workdir, "warm_manifest.zip")
    x, _ = _data()
    engine = ServingEngine(_make_net(), input_spec=(32,), buckets=[1, 8],
                           warm_manifest=wm if mode == "warm" else None)
    engine.start()
    try:
        engine.submit(x[0]).get(timeout=60)
        if mode == "cold":
            engine.save_warm_manifest(wm)
        aot = engine.stats()["aot"]
    finally:
        engine.stop()
    return {"time_to_first_request_ms": cc.first_marks().get("request"),
            "warmup_s": round(engine.stats()["warmup_s"], 4),
            "aot": aot}


def main(argv):
    kind, mode, workdir = argv[1], argv[2], argv[3]
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.utils import compile_cache as cc

    from deeplearning4j_tpu.telemetry import devices

    telemetry.enable()  # the gate reads compile_cache_total counters
    cache_dir = cc.enable_persistent_cache()
    # each leg holds the device itself (the parent stays off jax), so it
    # is the leg that knows and refuses the platform
    device = devices.device_stamp()
    if device["platform"] != "tpu" \
            and os.environ.get("BENCH_PREFLIGHT") != "1":
        sys.exit(f"coldstart leg: platform is {device['platform']!r}, not "
                 "'tpu' (set BENCH_PREFLIGHT=1 for the CPU counter gate)")
    out = (_train_leg if kind == "train" else _serve_leg)(mode, workdir)
    out.update(kind=kind, mode=mode, events=cc.event_counts(),
               compile_cache_dir=cache_dir, device=device)
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
