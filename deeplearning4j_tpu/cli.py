"""Command-line entry points: data-parallel training + UI server + bench.

Reference analog: parallelism/main/ParallelWrapperMain.java (JCommander
flags --modelPath/--workers/--averagingFrequency/--modelOutputPath/--uiUrl)
and PlayUIServer's CLI. Invoke as::

    python -m deeplearning4j_tpu train --model-path ckpt.zip \\
        --data features.npy --labels labels.npy --epochs 2 \\
        --averaging-frequency 5 --model-output-path out.zip
    python -m deeplearning4j_tpu train --zoo lenet --data x.npy --labels y.npy
    python -m deeplearning4j_tpu ui --port 9000
    python -m deeplearning4j_tpu serve --model-path ckpt.zip --max-batch 32
    python -m deeplearning4j_tpu bench lenet

"workers" in the reference = replica threads on N GPUs; here the worker
count IS the mesh data axis (defaults to every local device), and
averaging-frequency selects between the per-step gradient-sharing master
(frequency 1, exact psum) and the local-SGD parameter-averaging master
(frequency k > 1) — the same semantics ParallelWrapper exposes.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def _build_parser():
    p = argparse.ArgumentParser(
        prog="deeplearning4j_tpu",
        description="TPU-native dl4j: train / serve UI / bench")
    sub = p.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="data-parallel training over the mesh")
    src = t.add_mutually_exclusive_group(required=True)
    src.add_argument("--model-path", help="checkpoint zip to resume")
    src.add_argument("--zoo", help="zoo model name (e.g. lenet)")
    t.add_argument("--data", required=True,
                   help=".npy features, or a labelled .csv/.dat file")
    t.add_argument("--labels", help=".npy labels (one-hot); unused for CSV")
    t.add_argument("--label-column", type=int, default=-1,
                   help="CSV label column (default: last)")
    t.add_argument("--n-classes", type=int,
                   help="one-hot CSV labels to this many classes")
    t.add_argument("--skip-lines", type=int, default=0,
                   help="CSV header lines to skip")
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--workers", type=int, default=0,
                   help="mesh data-axis size (0 = all local devices)")
    t.add_argument("--batch-size-per-worker", type=int, default=32)
    t.add_argument("--averaging-frequency", type=int, default=1,
                   help="1 = per-step gradient psum; k>1 = local SGD with "
                        "parameter averaging every k steps")
    t.add_argument("--no-average-updaters", action="store_true")
    t.add_argument("--model-output-path", help="save checkpoint here")
    t.add_argument("--ui-port", type=int,
                   help="start the training dashboard on this port")
    t.add_argument("--report-score", action="store_true")

    u = sub.add_parser("ui", help="standalone training dashboard server")
    u.add_argument("--port", type=int, default=9000)

    sv = sub.add_parser(
        "serve",
        help="production inference server: continuous batching over "
             "AOT-warmed shape buckets, bounded admission queue with "
             "load shedding, /serving status on the dashboard port")
    sv.add_argument("--warm-manifest", metavar="PATH",
                    help="warm AOT manifest (utils/compile_cache "
                         "WarmManifest zip): when PATH exists, warmup "
                         "DESERIALIZES each bucket's executable instead "
                         "of compiling — zero compiles on a warm restart; "
                         "the manifest is (re)saved to PATH after warmup "
                         "so the next restart covers every bucket")
    svsrc = sv.add_mutually_exclusive_group(required=True)
    svsrc.add_argument("--model-path", help="checkpoint zip to serve")
    svsrc.add_argument("--zoo", help="zoo model name (fresh init)")
    sv.add_argument("--name", default="default",
                    help="model name in the registry (default: 'default')")
    sv.add_argument("--max-batch", type=int, default=32,
                    help="largest serving batch (= largest bucket)")
    sv.add_argument("--buckets",
                    help="comma-separated batch buckets to AOT-warm "
                         "(default: powers of two up to --max-batch)")
    sv.add_argument("--input-shape",
                    help="per-example feature shape, e.g. 28,28,1 "
                         "(default: derived from the model's input type)")
    sv.add_argument("--max-queue", type=int, default=256,
                    help="admission queue bound; a full queue sheds "
                         "requests with ServingOverloaded")
    sv.add_argument("--deadline-ms", type=float,
                    help="default request deadline; requests stale in the "
                         "queue past this are shed, not served")
    sv.add_argument("--batch-window-ms", type=float, default=2.0,
                    help="max extra wait to fill a batch once at least "
                         "one request is in hand (ONE shared deadline)")
    sv.add_argument("--port", type=int, default=9000,
                    help="dashboard/status port (/serving, /metrics)")
    sv.add_argument("--smoke", type=int, metavar="N",
                    help="serve N synthetic requests, print the stats, "
                         "and exit (CI smoke mode)")

    fl = sub.add_parser(
        "fleet",
        help="multi-process serving fleet (fleet/): N worker processes "
             "from one checkpoint + warm manifest behind one admission/"
             "routing front with elastic worker replacement; /fleet "
             "status on the dashboard port")
    flsrc = fl.add_mutually_exclusive_group(required=True)
    flsrc.add_argument("--model-path", help="checkpoint zip every worker "
                                            "serves")
    flsrc.add_argument("--zoo", help="zoo model name (fresh init per "
                                     "worker)")
    fl.add_argument("--workers", type=int, default=2,
                    help="worker processes to spawn (default 2)")
    fl.add_argument("--name", default="default",
                    help="served model name (default: 'default')")
    fl.add_argument("--max-batch", type=int, default=32)
    fl.add_argument("--buckets",
                    help="comma-separated batch buckets each worker "
                         "AOT-warms (default: powers of two up to "
                         "--max-batch)")
    fl.add_argument("--input-shape",
                    help="per-example feature shape, e.g. 28,28,1 "
                         "(default: derived from the model conf)")
    fl.add_argument("--warm-manifest", metavar="PATH",
                    help="serving warm manifest every worker (and every "
                         "elastic REPLACEMENT) restores executables "
                         "from — the zero-compile respawn contract")
    fl.add_argument("--max-queue", type=int, default=256,
                    help="front admission bound (queued examples); a "
                         "full front sheds with ServingOverloaded")
    fl.add_argument("--max-inflight", type=int, default=64,
                    help="per-worker bounded in-flight window (rows)")
    fl.add_argument("--deadline-ms", type=float,
                    help="default request deadline (front AND workers "
                         "shed stale requests)")
    fl.add_argument("--port", type=int, default=9000,
                    help="dashboard/status port (/fleet, /metrics)")
    fl.add_argument("--smoke", type=int, metavar="N",
                    help="serve N synthetic requests through the fleet, "
                         "print the front + worker status, and exit")

    e = sub.add_parser("eval", help="evaluate a checkpoint on a dataset")
    esrc = e.add_mutually_exclusive_group(required=True)
    esrc.add_argument("--model-path", help="checkpoint zip")
    esrc.add_argument("--zoo", help="zoo model name (fresh init)")
    e.add_argument("--data", required=True,
                   help=".npy features, or a labelled .csv/.dat file")
    e.add_argument("--label-column", type=int, default=-1)
    e.add_argument("--n-classes", type=int)
    e.add_argument("--skip-lines", type=int, default=0)
    e.add_argument("--labels",
                   help=".npy labels (one-hot or class indices); "
                        "unused for CSV")
    e.add_argument("--batch-size", type=int, default=128)
    e.add_argument("--regression", action="store_true",
                   help="report regression metrics instead of classification")

    b = sub.add_parser("bench", help="run a BASELINE.md bench config")
    b.add_argument("config", nargs="?", default="all")

    cn = sub.add_parser(
        "continuous",
        help="continuous-learning loop (continuous/): streaming ingest "
             "with bounded staleness -> watchdog-policed StepDriver "
             "rounds with rollback-to-last-good-bundle -> periodic "
             "snapshot + serving hot-swap handoff; all arguments forward "
             "to continuous.runner (use `continuous --help-runner` or "
             "`python -m deeplearning4j_tpu.continuous.runner --help`)")
    cn.add_argument("--help-runner", action="store_true",
                    help="print the runner's own argument reference")
    cn.add_argument("runner_args", nargs=argparse.REMAINDER)

    tl = sub.add_parser(
        "telemetry",
        help="dump a metrics snapshot (local registry, or scrape a "
             "running server's /metrics)")
    tl.add_argument("--url",
                    help="scrape this /metrics endpoint (e.g. "
                         "http://127.0.0.1:9000/metrics) instead of the "
                         "local registry")
    tl.add_argument("--format", choices=("prom", "json", "jsonl"),
                    default="prom",
                    help="local-registry output format (scrapes are always "
                         "the server's Prometheus text)")
    tl.add_argument("--chrome-trace",
                    help="also export the host-span Chrome trace JSON here")

    ln = sub.add_parser(
        "lint",
        help="graftlint: JAX-aware static analysis (hidden host syncs, "
             "jit purity, recompile hazards) — see analysis/")
    ln.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the "
                         "deeplearning4j_tpu package)")
    ln.add_argument("--rules",
                    help="comma-separated rule subset (e.g. R1,R4); "
                         "default all")
    ln.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ln.add_argument("--format", choices=("human", "json"), default="human")
    ln.add_argument("--baseline",
                    help="baseline file (default: "
                         "<repo>/graftlint.baseline.json)")
    ln.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report every finding")
    ln.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings "
                         "and exit 0")
    ln.add_argument("--strict-baseline", action="store_true",
                    help="CI mode: stale baseline entries (fixed debt "
                         "still in the ledger) also fail")
    ln.add_argument("--verbose", action="store_true",
                    help="also print baselined findings")
    ln.add_argument("--diff", metavar="REF",
                    help="pre-commit mode: analyse everything (project "
                         "rules need the whole tree) but only REPORT "
                         "findings whose statement touches a line changed "
                         "vs this git ref (e.g. HEAD, origin/main)")
    ln.add_argument("--emit-schema", action="store_true",
                    help="instead of linting, write the harvested wire+"
                         "metric contract (routes, headers, response "
                         "keys, metric series with label sets) to "
                         "SCHEMA.json and METRICS.md — the same registry "
                         "rules R10/R11/R13 enforce")
    ln.add_argument("--schema-dir", metavar="DIR",
                    help="where --emit-schema writes (default: repo root)")
    ln.add_argument("--san-report", metavar="JSON",
                    help="merge a graftsan runtime report (Sanitizer.dump "
                         "/ GRAFTSAN_REPORT) with the static R9 lock "
                         "graph: maps observed acquisition orders onto "
                         "static lock identities and fails on cycles in "
                         "the MERGED graph — orders only runtime saw "
                         "compose with orders only the code declares")

    sl = sub.add_parser(
        "slo",
        help="SLO engine verdicts (telemetry/slo.py): evaluate the "
             "default ruleset over the local registry, or read a "
             "running server's /slo endpoint, and print every rule's "
             "ok|warning|firing state")
    sl.add_argument("--url",
                    help="read this /slo endpoint (e.g. "
                         "http://127.0.0.1:9000/slo — append ?federate=1 "
                         "for the cluster-wide evaluation) instead of "
                         "evaluating the local registry")
    sl.add_argument("--history", metavar="PATH",
                    help="replay a metrics-history dir (or one segment "
                         "file) through the engine before evaluating — "
                         "judge the minutes BEFORE a dump/restart, not "
                         "just the instant of death (the flightrec "
                         "'history' section names the dir)")
    sl.add_argument("--samples", type=int, default=2,
                    help="local mode: evaluation passes (rates need >=2 "
                         "samples spanning time; default 2)")
    sl.add_argument("--interval", type=float, default=2.0,
                    help="local mode: seconds between passes (default 2)")
    sl.add_argument("--gate", action="store_true",
                    help="exit nonzero when any rule is firing "
                         "(scriptable health check)")
    sl.add_argument("--json", action="store_true",
                    help="raw status JSON instead of the table")

    tc = sub.add_parser(
        "traces",
        help="inspect the slow-trace flight ring (telemetry/tracectx.py): "
             "list the slowest complete causal traces per root span and "
             "pretty-print one as an indented timeline")
    tc.add_argument("--url",
                    help="scrape a running server's /traces endpoint "
                         "(e.g. http://127.0.0.1:9000/traces) instead of "
                         "the local ring")
    tc.add_argument("--file", action="append", metavar="PATH",
                    help="read traces from JSON file(s) — a /traces "
                         "payload, a raw ring snapshot, a flight-recorder "
                         "dump (its 'traces' key) — or a DIRECTORY of "
                         "dumps (a dead generation's postmortem). "
                         "Repeatable; every source merges into one view")
    tc.add_argument("--name",
                    help="only this root-span name (e.g. serving.request)")
    tc.add_argument("--trace-id",
                    help="print the timeline of this trace id (the id a "
                         "/metrics exemplar or BENCH worst_trace_id "
                         "points at)")
    tc.add_argument("--cluster", action="store_true",
                    help="merge every source (--file/--url, or the live "
                         "cluster providers when neither is given) into "
                         "ONE time-aligned timeline: per-instance trace "
                         "rows, per-host round clocks, and the stalled "
                         "host of a dead hostfleet generation")
    tc.add_argument("--chrome", metavar="PATH",
                    help="with --cluster: also write the merged timeline "
                         "as a Chrome trace-event file (chrome://tracing "
                         "/ Perfetto)")
    tc.add_argument("--json", action="store_true",
                    help="raw JSON passthrough instead of the timeline")

    fr = sub.add_parser(
        "flightrec",
        help="pretty-print a crash flight-recorder dump "
             "(telemetry/flight.py JSON)")
    fr.add_argument("path", help="dump file written on anomaly/crash/SIGTERM")
    fr.add_argument("--last", type=int, default=10,
                    help="show only the last N step records (default 10; "
                         "0 = all)")
    fr.add_argument("--json", action="store_true",
                    help="raw JSON passthrough instead of the table")
    return p


def _load_model(args):
    if args.model_path:
        # sniffs the zip layout: this framework's format OR a reference
        # ModelSerializer zip (MLN or ComputationGraph) both load — the
        # CLI is the migration path's front door
        from deeplearning4j_tpu.models.zoo import restore_checkpoint
        return restore_checkpoint(args.model_path)
    from deeplearning4j_tpu.models import zoo
    try:
        builder = zoo.get_model(args.zoo).builder
    except KeyError:
        raise SystemExit(
            f"unknown zoo model {args.zoo!r}; known: {zoo.model_names()}")
    conf = builder()
    from deeplearning4j_tpu.nn.graph import ComputationGraph, GraphConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    net = (ComputationGraph(conf) if isinstance(conf, GraphConfiguration)
           else MultiLayerNetwork(conf))
    net.init()
    return net




def _load_xy(args):
    """Features+labels from .npy pairs or a single labelled CSV.

    --data model.csv with --label-column/--n-classes routes through
    datasets.records.csv_dataset (the RecordReaderDataSetIterator CLI
    shape); .npy keeps the original contract."""
    if args.data.endswith(".csv") or args.data.endswith(".dat"):
        if getattr(args, "labels", None):
            raise SystemExit(
                "--labels cannot be combined with a labelled CSV --data "
                "file: the CSV's --label-column is the label source. "
                "Drop --labels, or pass .npy features instead.")
        from deeplearning4j_tpu.datasets.records import csv_dataset
        x, y = csv_dataset(args.data, label_column=args.label_column,
                           n_classes=args.n_classes,
                           skip_lines=args.skip_lines)
        if y.ndim == 1:
            # no --n-classes: raw label column — make it an explicit
            # [N, 1] regression target (a 1-D y would silently broadcast
            # into a wrong loss downstream)
            y = y[:, None]
        return x, y
    if not getattr(args, "labels", None):
        raise SystemExit("--labels is required with .npy features")
    x = np.load(args.data)
    y = np.load(args.labels)
    return x, y

def _enable_compile_cache():
    """Turn jax's persistent compile cache on BEFORE any jax work compiles
    — the instant-restart tier every CLI verb shares
    ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)."""
    from deeplearning4j_tpu.utils import compile_cache as _cc
    print(f"persistent compile cache: {_cc.enable_persistent_cache()}")


def _cmd_train(args):
    import jax
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.distributed import (
        DistributedMultiLayer, ParameterAveragingTrainingMaster,
        SharedTrainingMaster)

    _enable_compile_cache()

    # CLI training is the preemptable long-running entry point: a SIGTERM
    # (scheduler eviction) leaves a flight-recorder dump behind
    from deeplearning4j_tpu.telemetry import flight as _flight
    _flight.install_signal_handler()

    x, y = _load_xy(args)
    n_devices = len(jax.devices())
    n_workers = args.workers or n_devices
    if n_workers > n_devices:
        raise SystemExit(f"--workers {n_workers} exceeds the {n_devices} "
                         f"available device(s)")
    mesh = Mesh(np.array(jax.devices()[:n_workers]), ("data",))
    net = _load_model(args)

    ui_server = None
    if args.ui_port:
        from deeplearning4j_tpu.ui import (InMemoryStatsStorage,
                                           StatsListener, UIServer)
        storage = InMemoryStatsStorage()
        if hasattr(net, "add_listener"):
            net.add_listener(StatsListener(storage, session_id="cli"))
        ui_server = UIServer(port=args.ui_port).attach(storage).start()
        print(f"dashboard: http://127.0.0.1:{ui_server.port}/")

    if args.averaging_frequency <= 1:
        master = SharedTrainingMaster(
            mesh, batch_size_per_worker=args.batch_size_per_worker,
            threshold=None)
    else:
        master = ParameterAveragingTrainingMaster(
            mesh, batch_size_per_worker=args.batch_size_per_worker,
            averaging_frequency=args.averaging_frequency,
            average_updaters=not args.no_average_updaters)
    dist = DistributedMultiLayer(net, master)
    loss = dist.fit(x, y, epochs=args.epochs)
    if args.report_score and loss is not None:
        print(f"final loss: {loss}")
    print(f"training stats: {master.training_stats()}")

    if args.model_output_path:
        from deeplearning4j_tpu.utils.serialization import save_model
        save_model(net, args.model_output_path)
        print(f"saved: {args.model_output_path}")
    if ui_server is not None:
        ui_server.stop()
    return 0


def _serve_input_spec(args, net):
    """Per-example input shape for AOT warmup: --input-shape wins, else the
    model conf's input type (FeedForwardType(6) -> (6,))."""
    if args.input_shape:
        return tuple(int(d) for d in args.input_shape.split(",") if d.strip())
    input_type = getattr(net.conf, "input_type", None)
    if input_type is None:
        raise SystemExit(
            "--input-shape is required: the model conf carries no input "
            "type to derive the warmup shape from")
    return tuple(input_type.shape(1)[1:])


def _cmd_serve(args):
    """The production serving entry point (ROADMAP 'serving heavy
    traffic'): AOT-warm every registered bucket so no request pays a
    compile, then serve with continuous batching + admission control."""
    import time

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.serving import get_model_registry
    from deeplearning4j_tpu.ui import UIServer

    telemetry.enable()  # SLO gauges/counters are the point of a server
    _enable_compile_cache()
    net = _load_model(args)
    input_spec = _serve_input_spec(args, net)
    buckets = None
    if args.buckets:
        buckets = [int(b) for b in args.buckets.split(",") if b.strip()]
    # a not-yet-created path is the normal first cold start: the engine
    # loads it leniently (missing -> None, no warning)
    warm_manifest = args.warm_manifest or None
    registry = get_model_registry()
    engine = registry.register(
        args.name, net, input_spec=input_spec,
        max_batch_size=args.max_batch, buckets=buckets,
        max_queue=args.max_queue,
        default_deadline_s=(None if args.deadline_ms is None
                            else args.deadline_ms / 1e3),
        batch_window_s=args.batch_window_ms / 1e3,
        warm_manifest=warm_manifest)
    st = engine.stats()
    aot = st["aot"]
    src = (f"{aot['manifest_hits']} from warm manifest, "
           f"{aot['warmed'] - aot['manifest_hits']} compiled"
           if warm_manifest else "compiled")
    print(f"model {args.name!r}: AOT-warmed buckets {st['buckets']} "
          f"in {st['warmup_s']:.2f}s ({src}; input {input_spec})")
    if args.warm_manifest:
        # (re)save AFTER warmup so a cold start's live compiles make the
        # NEXT restart warm — the instant-restart loop closes here.
        # Export ONCE: each export serializes (and verify-deserializes)
        # every executable not already in the manifest
        manifest = engine.export_warm_manifest()
        if manifest is not None:
            manifest.save(args.warm_manifest)
            print(f"warm manifest: {args.warm_manifest} "
                  f"({len(manifest)} executable(s))")
        else:
            print("warm manifest: backend cannot serialize executables "
                  "(persistent compile cache still applies)")
    ui_server = UIServer(port=args.port).start()
    print(f"serving status: http://127.0.0.1:{ui_server.port}/serving "
          f"(metrics on /metrics)")

    try:
        if args.smoke:
            import json

            import numpy as np
            from deeplearning4j_tpu.serving import ServingOverloaded
            rs = np.random.RandomState(0)
            xs = rs.rand(args.smoke, *input_spec).astype(np.float32)
            futs, shed = [], 0
            for i in range(args.smoke):
                # a smoke burst bigger than --max-queue legitimately sheds
                # (that's the admission control working): back off briefly
                # and keep going rather than crash the smoke
                for _ in range(1000):
                    try:
                        futs.append(engine.submit(xs[i]))
                        break
                    except ServingOverloaded:
                        time.sleep(0.001)
                else:
                    raise SystemExit("smoke: admission queue never drained")
            for f in futs:
                try:
                    f.get(timeout=30)
                except ServingOverloaded:
                    shed += 1  # stale-in-queue deadline shed (--deadline-ms)
            if shed:
                print(f"smoke: {shed} request(s) shed by deadline")
            print(json.dumps(registry.status()["models"][args.name],
                             indent=1))
            return 0
        # SIGTERM (docker stop / systemd) must route through the same
        # clean-stop path as Ctrl-C: killing the interpreter with the
        # serving worker mid-XLA-call aborts the process hard
        import signal

        def _term(signum, frame):
            raise KeyboardInterrupt
        signal.signal(signal.SIGTERM, _term)
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        registry.stop()
        ui_server.stop()
    return 0


def _cmd_fleet(args):
    """The multi-process serving entry point (ROADMAP's "millions of
    users" tier): spawn N workers from one checkpoint + warm manifest,
    put the admission/routing front before them, and keep the pool
    elastic — a worker death is a respawn, not an outage."""
    import time

    from deeplearning4j_tpu import fleet, telemetry
    from deeplearning4j_tpu.ui import UIServer

    telemetry.enable()
    _enable_compile_cache()
    if args.model_path is None:
        # zoo mode: workers init the model themselves (same seed = same
        # params); a checkpoint is the production path
        print("note: --zoo workers each init fresh (same seed); use "
              "--model-path for a real deployment")
    input_shape = (tuple(int(d) for d in args.input_shape.split(",")
                         if d.strip()) if args.input_shape else None)
    buckets = ([int(b) for b in args.buckets.split(",") if b.strip()]
               if args.buckets else None)
    supervisor = fleet.FleetSupervisor(
        args.workers, model_path=args.model_path, zoo=args.zoo,
        name=args.name, buckets=buckets, input_shape=input_shape,
        warm_manifest=args.warm_manifest or None,
        max_queue=args.max_queue, max_batch=args.max_batch,
        deadline_ms=args.deadline_ms)
    router = fleet.FleetRouter(
        name=args.name, max_queue=args.max_queue,
        max_inflight_rows=args.max_inflight,
        default_deadline_s=(None if args.deadline_ms is None
                            else args.deadline_ms / 1e3))
    supervisor.attach(router)
    print(f"fleet: spawning {args.workers} worker(s)...")
    t0 = time.perf_counter()
    supervisor.start()
    fleet.set_default_front(router=router, supervisor=supervisor)
    starts = ", ".join(
        f"{w.wid}:" + ("warm" if fleet.FleetSupervisor
                       .replacement_is_warm(w.ready_doc) else "cold")
        for w in supervisor._workers.values())
    print(f"fleet: {args.workers} worker(s) ready in "
          f"{time.perf_counter() - t0:.1f}s ({starts})")
    ui_server = UIServer(port=args.port).start()
    print(f"fleet status: http://127.0.0.1:{ui_server.port}/fleet "
          f"(metrics on /metrics)")
    try:
        if args.smoke:
            import json

            import numpy as np
            from deeplearning4j_tpu.serving import ServingOverloaded
            spec = input_shape
            if spec is None:
                # read one worker's bucket spec indirectly: derive from
                # the model conf like the workers do
                net = _load_model(args)
                spec = _serve_input_spec(args, net)
            rs = np.random.RandomState(0)
            xs = rs.rand(args.smoke, *spec).astype(np.float32)
            futs, shed = [], 0
            for i in range(args.smoke):
                for _ in range(1000):
                    try:
                        futs.append(router.submit(xs[i]))
                        break
                    except ServingOverloaded:
                        time.sleep(0.001)
                else:
                    raise SystemExit("fleet smoke: admission queue "
                                     "never drained")
            for f in futs:
                try:
                    f.get(timeout=60)
                except ServingOverloaded:
                    shed += 1
            if shed:
                print(f"fleet smoke: {shed} request(s) shed")
            print(json.dumps({"router": router.stats(),
                              "workers": supervisor.status()},
                             indent=1, default=str))
            return 0
        import signal

        def _term(signum, frame):
            raise KeyboardInterrupt
        signal.signal(signal.SIGTERM, _term)
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        router.stop()
        supervisor.stop()
        fleet.reset()
        ui_server.stop()
    return 0


def _cmd_ui(args):
    from deeplearning4j_tpu.ui import UIServer
    server = UIServer(port=args.port).start()
    print(f"UI server on http://127.0.0.1:{server.port}/ (Ctrl-C to stop)")
    try:
        import time
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


def _cmd_bench(args):
    import os
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.join(repo, "bench.py")]
    if args.config != "all":
        cmd.append(args.config)
    return subprocess.call(cmd)


def _cmd_eval(args):
    """(reference role: Evaluation printed from MultiLayerNetwork.evaluate /
    the examples' eval.stats() tail — here as a CLI verb)."""
    _enable_compile_cache()
    net = _load_model(args)
    x, y = _load_xy(args)
    preds = []
    for i in range(0, x.shape[0], args.batch_size):
        out = net.output(x[i:i + args.batch_size])
        if isinstance(out, dict):  # multi-output graph: first output head
            out = next(iter(out.values()))
        preds.append(np.asarray(out))
    preds = np.concatenate(preds)
    if args.regression:
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        if y.ndim == 1:  # single-target vector -> column
            y = y[:, None]
        ev = RegressionEvaluation()
        ev.eval(y, preds)
        print(ev.stats())
        return 0
    from deeplearning4j_tpu.eval.classification import Evaluation
    n_classes = preds.shape[-1]
    if n_classes == 1:
        # single sigmoid output: Evaluation handles 1-column labels natively
        if y.ndim == 1:
            y = y[:, None]
    elif y.ndim == 1 or (y.ndim == 2 and y.shape[-1] == 1):
        y = np.eye(n_classes, dtype=np.float32)[y.astype(int).ravel()]
    ev = Evaluation()
    ev.eval(y, preds)
    print(ev.stats())
    return 0


def _cmd_telemetry(args):
    """Dump the unified telemetry snapshot — the 'what is this process (or
    that server) doing right now' CLI verb."""
    import json

    from deeplearning4j_tpu import telemetry

    if args.url:
        if args.chrome_trace:
            raise SystemExit(
                "--chrome-trace cannot be combined with --url: the host-span "
                "tracer lives in the traced process, and this fresh CLI "
                "process has recorded nothing — export the trace from the "
                "instrumented process instead "
                "(telemetry.get_tracer().export(path)).")
        import urllib.request
        with urllib.request.urlopen(args.url, timeout=10) as r:
            sys.stdout.write(r.read().decode())
    else:
        reg = telemetry.get_registry()
        if not any(m["series"] for m in reg.snapshot().values()):
            # a fresh CLI process has recorded nothing — say so instead of
            # letting an empty dump read as "telemetry is broken"
            print("note: local registry is empty (each process has its "
                  "own); run instrumented work in THIS process, or scrape "
                  "a live server with --url http://host:port/metrics",
                  file=sys.stderr)
        if args.format == "json":
            print(json.dumps(reg.snapshot(), indent=1, default=str))
        elif args.format == "jsonl":
            reg.to_jsonl(sys.stdout)
        else:
            sys.stdout.write(reg.to_prometheus())
    if args.chrome_trace:
        path = telemetry.get_tracer().export(args.chrome_trace)
        print(f"chrome trace: {path}", file=sys.stderr)
    return 0


def _cmd_lint(args):
    """graftlint CLI: exit 0 when every finding is fixed/suppressed/
    baselined, non-zero otherwise — the tier-1 gating contract."""
    import os

    from deeplearning4j_tpu import analysis
    from deeplearning4j_tpu.analysis import reporters

    if args.list_rules:
        for name, rule in analysis.all_rules().items():
            print(f"{name} [{rule.slug}]\n    {rule.description}")
        return 0

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(pkg_dir)
    paths = args.paths or [pkg_dir]
    rules = args.rules.split(",") if args.rules else None

    if args.emit_schema:
        mods, errors = analysis.parse_paths(paths, root=root)
        if errors:
            for f in errors:
                print(f.human(), file=sys.stderr)
            raise SystemExit("graftlint: cannot emit a schema over "
                             "unparseable sources")
        schema = analysis.build_schema(mods)
        out_dir = args.schema_dir or root
        jp, mp = reporters.write_schema(schema, out_dir)
        print(f"graftlint: schema written: {jp}, {mp}", file=sys.stderr)
        return 0
    if args.san_report:
        return _lint_san_report(args, paths, root)
    if args.diff and args.update_baseline:
        raise SystemExit("graftlint: --diff filters findings to changed "
                         "lines; rewriting the baseline from that subset "
                         "would drop real debt — run --update-baseline "
                         "without --diff")

    try:
        findings = analysis.lint_paths(paths, rules=rules, root=root)
    except analysis.LintError as e:
        raise SystemExit(f"graftlint: {e}")

    if args.diff:
        changed = _git_changed_lines(args.diff, root)
        # a finding's statement spans sup_start (decorators included —
        # editing only a decorator line must still surface the finding
        # it causes on the def) through end_line
        findings = [f for f in findings
                    if any(ln in changed.get(f.path, ())
                           for ln in range(min(f.sup_start or f.line,
                                               f.line),
                                           max(f.end_line, f.line) + 1))]

    if args.no_baseline:
        baseline = {}
    else:
        bpath = args.baseline or analysis.default_baseline_path()
        if args.update_baseline:
            analysis.save_baseline(bpath, findings)
            print(f"graftlint: baseline rewritten with {len(findings)} "
                  f"finding(s): {bpath}", file=sys.stderr)
            return 0
        baseline = analysis.load_baseline(bpath)
    new, known, stale = analysis.apply_baseline(findings, baseline)
    if args.diff:
        # off-diff baselined debt is invisible here, so "stale" is
        # meaningless — the full (non-diff) CI run owns that check
        stale = []

    if args.format == "json":
        reporters.report_json(new, known, stale)
    else:
        reporters.report_human(new, known, stale, verbose=args.verbose)
    if new:
        return 1
    if stale and args.strict_baseline:
        return 1
    return 0


def _git_changed_lines(ref, root):
    """{repo-relative posix path: set of NEW-side line numbers} changed vs
    ``ref`` (committed AND working-tree changes — pre-commit wants both).
    Hunk headers only (-U0): pure deletions contribute no lines."""
    import re
    import subprocess
    from pathlib import Path

    try:
        out = subprocess.run(
            ["git", "-C", root, "diff", "--unified=0", ref, "--", "*.py"],
            capture_output=True, text=True, check=True).stdout
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        raise SystemExit(f"graftlint: git diff {ref} failed: "
                         f"{detail.strip()}")
    changed, cur = {}, None
    hunk = re.compile(r"^@@ -\d+(?:,\d+)? \+(\d+)(?:,(\d+))? @@")
    for line in out.splitlines():
        if line.startswith("+++ b/"):
            cur = line[6:]
        elif line.startswith("+++"):
            cur = None                      # /dev/null: file deleted
        elif cur is not None and line.startswith("@@"):
            m = hunk.match(line)
            if m:
                start = int(m.group(1))
                count = int(m.group(2)) if m.group(2) is not None else 1
                if count:
                    changed.setdefault(cur, set()).update(
                        range(start, start + count))
    # untracked files never appear in `git diff` hunks but ARE pending
    # changes — every line of them counts
    untracked = subprocess.run(
        ["git", "-C", root, "ls-files", "--others", "--exclude-standard",
         "--", "*.py"],
        capture_output=True, text=True).stdout
    for path in untracked.splitlines():
        if not path:
            continue
        try:
            with open(Path(root) / path, encoding="utf-8",
                      errors="replace") as fh:
                n = sum(1 for _ in fh)
        except OSError:
            continue
        changed.setdefault(path, set()).update(range(1, n + 1))
    return changed


def _lint_san_report(args, paths, root):
    """lint --san-report: one lock graph from both prongs. Static R9
    edges come in lock-id space; observed graftsan edges come keyed by
    allocation site (file:line) and map onto the SAME identity via the
    lock registry — so an order only runtime saw composes with an order
    only the code declares, and the merged cycle is reported even though
    neither prong alone had it."""
    import json
    from pathlib import Path, PurePosixPath

    from deeplearning4j_tpu import analysis
    from deeplearning4j_tpu.analysis.dataflow import project_facts

    with open(args.san_report, encoding="utf-8") as fh:
        report = json.load(fh)
    mods, parse_errors = analysis.parse_paths(paths, root=root)
    static = analysis.lint_modules(mods, rules=["R9"])
    facts = project_facts(mods)

    site_to_id = {f"{info['path']}:{info['line']}": lid
                  for lid, info in facts.locks.items()}

    def norm(site):
        fname, _, line = site.rpartition(":")
        try:
            rel = Path(fname).resolve().relative_to(Path(root).resolve())
        except ValueError:
            rel = Path(fname)
        return f"{PurePosixPath(rel)}:{line}"

    def ident(site):
        n = norm(site)
        return site_to_id.get(n, n)        # unmapped sites keep file:line

    merged = {}
    for src, dst, _mod, _node, _via in facts.lock_edges:
        if src != dst:          # self-edges are static R9's own call
            merged.setdefault(src, set()).add(dst)  # (RLock re-entry legal)
    observed = []
    for e in report.get("lock_order_edges", ()):
        a, b = ident(e["from"]), ident(e["to"])
        observed.append((a, b, e.get("count", 1)))
        if a != b:
            merged.setdefault(a, set()).add(b)

    from deeplearning4j_tpu.analysis.dataflow import reaches
    cycles = set()
    for a in sorted(merged):
        for b in sorted(merged[a]):
            if reaches(merged, b, a):
                cycles.add(tuple(sorted((a, b))))

    runtime_findings = report.get("findings", ())
    print(f"graftsan report: {len(observed)} observed lock-order edge(s), "
          f"{len(runtime_findings)} runtime finding(s)")
    for a, b, count in observed:
        print(f"  observed {a} -> {b} (x{count})")
    for f in runtime_findings:
        tail = f" [{f['site']}]" if f.get("site") else ""
        print(f"RUNTIME {f['kind']}: {f['message']}{tail}")
    for f in static:
        print(f"STATIC {f.human()}")
    for f in parse_errors:
        print(f"STATIC {f.human()}")
    for cyc in sorted(cycles):
        print("MERGED lock-order cycle: "
              + " -> ".join(cyc + (cyc[0],)))
    bad = bool(runtime_findings or static or parse_errors or cycles)
    if not bad:
        print("graftsan: static + observed lock graphs merge clean")
    return 1 if bad else 0


def _cmd_slo(args):
    """The metrics plane's verdict, on the command line: which rules
    are burning, and by how much (`slo --gate` scripts it)."""
    import json
    import time

    if args.url:
        import urllib.request
        with urllib.request.urlopen(args.url, timeout=10) as r:
            status = json.loads(r.read().decode())
    else:
        from deeplearning4j_tpu import telemetry
        reg = telemetry.get_registry()
        if not any(m["series"] for m in reg.snapshot().values()):
            print("note: local registry is empty (each process has its "
                  "own); run instrumented work in THIS process, or read "
                  "a live server with --url http://host:port/slo",
                  file=sys.stderr)
        engine = telemetry.slo.get_engine()
        if getattr(args, "history", None):
            # postmortem replay: judge the persisted minutes, not this
            # (possibly freshly-restarted, empty) process's instant. The
            # samples carry their own unix clocks, so mixing in live
            # monotonic-clock passes would corrupt the delta windows —
            # with --history the replay IS the evaluation.
            from deeplearning4j_tpu.telemetry import history as _history
            samples, corrupt = _history.load_dir(args.history)
            if not samples:
                print(f"slo --history: no samples under {args.history} "
                      f"({corrupt} corrupt segment(s))", file=sys.stderr)
                return 1
            status = None
            for s in samples:
                status = engine.evaluate(metrics=s["metrics"], now=s["t"])
            span_s = samples[-1]["t"] - samples[0]["t"]
            print(f"slo --history: replayed {len(samples)} sample(s) "
                  f"spanning {span_s:.0f}s ({corrupt} corrupt segment(s) "
                  f"skipped)", file=sys.stderr)
        else:
            status = engine.evaluate()
            for _ in range(max(args.samples - 1, 0)):
                time.sleep(max(args.interval, 0.0))
                status = engine.evaluate()
    if args.json:
        print(json.dumps(status, indent=1, default=str))
    else:
        rules = status.get("rules", [])
        w_name = max([len(r["name"]) for r in rules] + [4])
        print(f"{'rule'.ljust(w_name)}  state    value        bound  "
              f"kind        metric")
        for r in rules:
            v = r.get("value")
            if isinstance(v, dict):  # burn_rate: short/long pair
                vtxt = "/".join(f"{x:.3g}" for x in v.values())
            else:
                vtxt = "-" if v is None else f"{v:.4g}"
            bound = f"{'<=' if r.get('op') == 'lt' else '>='}" \
                    f"{r.get('fire'):g}"
            print(f"{r['name'].ljust(w_name)}  {r['state']:<7}  "
                  f"{vtxt:<11}  {bound:<5}  {r['kind']:<10}  "
                  f"{r['metric']}")
        firing = status.get("firing", [])
        warning = status.get("warning", [])
        print(f"firing: {firing or 'none'}  warning: {warning or 'none'} "
              f" ({status.get('evaluations')} evaluation(s))")
    if args.gate and status.get("firing"):
        return 1
    return 0


def _load_trace_rings(args):
    """{root name: [trace docs]} from --file / --url / the local ring.
    Accepts the three shapes traces travel in: a /traces payload
    ({"traces": {...}}), a raw ring snapshot ({name: [...]}), or a
    flight-recorder dump carrying a "traces" key. ``--file`` repeats and
    accepts directories of dumps; every source's rings merge."""
    import json

    if args.file:
        from deeplearning4j_tpu.telemetry import timeline as _tl
        rings = {}
        for src in _tl.load_paths(args.file):
            for name, docs in src["rings"].items():
                rings.setdefault(name, []).extend(docs)
        return rings
    if args.url:
        import urllib.request
        with urllib.request.urlopen(args.url, timeout=10) as r:
            doc = json.loads(r.read().decode())
        return doc.get("traces", doc)
    from deeplearning4j_tpu import telemetry
    rings = telemetry.tracectx.get_ring().snapshot()
    if not rings:
        print("note: local slow-trace ring is empty (each process has its "
              "own); run traced work in THIS process, scrape a live "
              "server with --url http://host:port/traces, or read a "
              "flight dump with --file", file=sys.stderr)
    return rings


def _print_trace_timeline(doc):
    """One trace as an indented timeline: spans sorted by start time,
    indented by causal depth — the 'where did the p99 request spend its
    time' view, readable without a trace viewer."""
    dur = doc.get("duration_s")
    head = f"trace {doc.get('trace_id')} {doc.get('name')}"
    if dur is not None:
        head += f" {1e3 * dur:.3f} ms"
    if doc.get("status") not in (None, "ok"):
        head += f" [{doc['status']}]"
    print(head)
    spans = [s for s in doc.get("spans", []) if isinstance(s, dict)]
    depth = {}
    by_id = {s.get("span_id"): s for s in spans}

    def depth_of(s):
        d, seen = 0, set()
        while s is not None and s.get("parent_id") is not None \
                and s.get("span_id") not in seen:
            seen.add(s.get("span_id"))
            s = by_id.get(s.get("parent_id"))
            d += 1
        return d

    for s in spans:
        depth[s.get("span_id")] = depth_of(s)
    for s in sorted(spans, key=lambda s: (s.get("t0_s", 0.0),
                                          depth[s.get("span_id")])):
        pad = "  " * depth[s.get("span_id")]
        d = s.get("dur_s")
        dtxt = "?" if d is None else f"{1e3 * d:.3f} ms"
        line = (f"  {1e3 * s.get('t0_s', 0.0):>10.3f}  {pad}"
                f"{s.get('name')}  {dtxt}  [{s.get('thread', '?')}]")
        if s.get("args"):
            line += "  " + " ".join(f"{k}={v}"
                                    for k, v in sorted(s["args"].items()))
        print(line)


def _cmd_traces_cluster(args):
    """``traces --cluster``: one time-aligned timeline over every source
    — a directory of a dead generation's dumps, multiple --file scrapes,
    or the live cluster providers — ending with the per-host round
    clocks and the stalled host (the postmortem's first question)."""
    import json

    from deeplearning4j_tpu.telemetry import timeline as _tl

    if args.file:
        merged = _tl.merge(_tl.load_paths(args.file))
    elif args.url:
        import urllib.request
        with urllib.request.urlopen(args.url, timeout=10) as r:
            doc = json.loads(r.read().decode())
        src = _tl._source_from_doc(doc, args.url)
        merged = _tl.merge([src] if src is not None else [])
    else:
        merged = _tl.cluster_snapshot()
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(_tl.to_chrome(merged), f)
        print(f"chrome trace written to {args.chrome}", file=sys.stderr)
    if args.json:
        print(json.dumps(merged, indent=1, default=str))
        return 0
    print(f"cluster timeline: {merged['n_traces']} trace(s) across "
          f"{len(merged['instances'])} instance(s)")
    base = merged.get("t0_unix")
    for t in merged["traces"]:
        if args.name and t["name"] != args.name:
            continue
        rel = ("?" if (t["t0_unix"] is None or base is None)
               else f"{t['t0_unix'] - base:+.3f}s")
        dur = t.get("duration_s")
        dtxt = "?" if dur is None else f"{1e3 * dur:.3f} ms"
        line = f"  {rel:>10}  {t['instance']}  {t['name']}  {dtxt}"
        if t.get("status") not in (None, "ok"):
            line += f" [{t['status']}]"
        print(line)
    if merged["hosts"]:
        print()
        for inst in sorted(merged["hosts"]):
            h = merged["hosts"][inst]
            print(f"host {inst}: last round {h['last_round']}")
        if merged.get("stalled") is not None:
            h = merged["hosts"][merged["stalled"]]
            print(f"stalled: {merged['stalled']} — round clock stopped "
                  f"at round {h['last_round']} while peers advanced")
    return 0


def _cmd_traces(args):
    """The gauge->exemplar->timeline landing: `traces --trace-id <id>`
    renders the causal story a p99 exemplar points at."""
    import json

    if args.cluster:
        return _cmd_traces_cluster(args)
    rings = _load_trace_rings(args)
    if args.name:
        rings = {args.name: rings.get(args.name, [])}
    if args.trace_id:
        for docs in rings.values():
            for doc in docs:
                if doc.get("trace_id") == args.trace_id:
                    if args.json:
                        print(json.dumps(doc, indent=1, default=str))
                    else:
                        _print_trace_timeline(doc)
                    return 0
        print(f"traces: no trace {args.trace_id!r} in the ring",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(rings, indent=1, default=str))
        return 0
    slowest = None
    for name in sorted(rings):
        docs = rings[name]
        if not docs:
            continue
        durs = [d.get("duration_s") or 0.0 for d in docs]
        print(f"{name}: {len(docs)} trace(s), slowest "
              f"{1e3 * max(durs):.3f} ms, fastest kept "
              f"{1e3 * min(durs):.3f} ms")
        for d in docs:
            if slowest is None or (d.get("duration_s") or 0.0) > \
                    (slowest.get("duration_s") or 0.0):
                slowest = d
    if slowest is not None:
        print()
        _print_trace_timeline(slowest)
    return 0


#: flight-record columns in display order; only those present in the dump
#: are rendered (health fields appear when the watchdog annotated the ring)
_FLIGHT_COLS = ("step", "score", "loss", "step_time_s", "etl_time_s",
                "grad_norm", "loss_nonfinite", "grad_nonfinite",
                "trace_id", "device_bytes_in_use", "live_array_bytes")


def _cmd_flightrec(args):
    """Postmortem reader: the last-N-steps table a human scans for 'where
    did it go wrong' without hand-parsing the dump JSON."""
    import json

    with open(args.path) as f:
        doc = json.load(f)
    if args.json:
        print(json.dumps(doc, indent=1, default=str))
        return 0
    recs = doc.get("records", [])
    print(f"flight dump: reason={doc.get('reason')} "
          f"dumped_at={doc.get('dumped_at')} pid={doc.get('pid')} "
          f"records={len(recs)}")
    if doc.get("error"):
        print(f"error: {doc['error']}")
    if doc.get("anomaly"):
        print(f"anomaly: {doc['anomaly']}")
    hist = doc.get("history")
    if hist:
        # where to find the minutes BEFORE this dump: the persisted
        # metrics-history segments replay with `slo --history <dir>`
        print(f"history: {hist.get('samples', 0)} sample(s) in ring, "
              f"{hist.get('segments', 0)} segment(s) persisted"
              + (f" under {hist['dir']} (replay: slo --history "
                 f"{hist['dir']})" if hist.get("dir") else
                 " (persistence off: no history dir configured)"))
    show = recs[-args.last:] if args.last else recs

    def _fmt(v):
        if isinstance(v, bool):
            return "YES" if v else "-"
        if isinstance(v, float):
            return f"{v:.4g}"
        return "-" if v is None else str(v)

    cols = [c for c in _FLIGHT_COLS if any(c in r for r in show)]
    if cols:
        rows = [[_fmt(r.get(c)) for c in cols] for r in show]
        widths = [max(len(c), *(len(row[i]) for row in rows))
                  for i, c in enumerate(cols)]
        print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
        for row in rows:
            print("  ".join(v.ljust(w) for v, w in zip(row, widths)))
    flagged = [r for r in recs
               if r.get("loss_nonfinite") or r.get("grad_nonfinite")]
    if flagged:
        print(f"{len(flagged)} record(s) flagged nonfinite; first at step "
              f"{flagged[0].get('step')}")
    return 0


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "continuous":
        # forwarded verbatim BEFORE argparse: REMAINDER cannot capture
        # leading option-style args, so `continuous --snapshot ...`
        # would otherwise die with "unrecognized arguments"
        rest = list(argv[1:])
        if rest and rest[0] == "--":
            rest = rest[1:]
        if "--help-runner" in rest:
            rest = ["--help"]
        from deeplearning4j_tpu.continuous import runner
        return runner.main(rest)
    args = _build_parser().parse_args(argv)
    if args.command == "train":
        return _cmd_train(args)
    if args.command == "ui":
        return _cmd_ui(args)
    if args.command == "serve":
        return _cmd_serve(args)
    if args.command == "fleet":
        return _cmd_fleet(args)
    if args.command == "bench":
        return _cmd_bench(args)
    if args.command == "eval":
        return _cmd_eval(args)
    if args.command == "telemetry":
        return _cmd_telemetry(args)
    if args.command == "flightrec":
        return _cmd_flightrec(args)
    if args.command == "traces":
        return _cmd_traces(args)
    if args.command == "slo":
        return _cmd_slo(args)
    if args.command == "lint":
        return _cmd_lint(args)
    return 1


if __name__ == "__main__":
    sys.exit(main())
