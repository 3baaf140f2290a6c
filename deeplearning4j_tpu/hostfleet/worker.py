"""One training host of the elastic fleet (the supervisor's subprocess).

``python -m deeplearning4j_tpu.hostfleet.worker`` runs ONE host of ONE
generation: join ``jax.distributed`` (hardened ``initialize_distributed``
— bounded timeout, counted retries), build the deterministic smoke net
(or resume it from the layout-free bundle, RESHARDED into this
generation's topology by ``ParallelTrainer.adopt_net_state``), then train
``total_rounds`` rounds of ``StepDriver.run_round`` with the zero1/fsdp
sharded update over this host's local device mesh and a cross-host
exchange at every round boundary. Line protocol on stdout (the
supervisor's contract):

* ready: ``{"hostfleet_ready": true, "process": i, "generation": g,
  "platform": "cpu", "clock": {mono, unix}, ...}`` — the clock pair seeds
  the supervisor's per-host clock-offset estimate (cluster timeline
  alignment); ``platform`` is the backend the host actually holds;
* round: ``{"round": r, "iteration": n, "process": i, "trace": doc}``
  after each completed round (exchange + heartbeat + snapshot done) —
  the ``hostfleet.round`` trace doc (steps/exchange/heartbeat/checkpoint
  child spans) rides the line so the supervisor's ring shows which host
  stalled a generation;
* snapshot (process 0): ``{"snapshot": path, "round": r}``;
* done:  ``{"hostfleet_done": true, "digest": ..., "counters": ...}`` —
  digests are ``continuous.chaos.state_digest``, so the harness asserts
  cross-host agreement and fault/fault-free parity by string equality.

Failure protocol: init failure exits ``RC_INIT_FAILED`` (13), a broken
round exchange exits ``RC_EXCHANGE_FAILED`` (14) — each with ONE JSON
error line — so the supervisor (and a 5-minute test timeout) never has to
infer a cause from silence.

Exchange modes (see hostfleet/exchange.py): ``gspmd`` spans hosts inside
the step (accelerator backends; also the trivial world-size-1 case),
``hostavg`` averages params+opt at round boundaries through the
supervisor's ExchangeServer (the reference's ParameterAveraging
semantics, and the only cross-process transport the CPU backend can
execute). ``auto`` picks hostavg iff the job is multi-process on CPU.

Heartbeats: after every round the worker atomically rewrites
``<heartbeat-dir>/host<i>.json`` with ``{round, iteration, ts}`` — the
supervisor's round watchdog reads these (plus the exchange server's own
progress clock) to bound a wedged round without any HTTP surface.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

RC_INIT_FAILED = 13
RC_EXCHANGE_FAILED = 14


def _emit(doc):
    print(json.dumps(doc), flush=True)


def _atomic_write(path, text):
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def _host_tree(net):
    """The exchanged state: params + opt_state + mutable layer state
    (host numpy leaves, flat) — everything the round average must cover.
    The RNG chain and counters are NOT exchanged: every host advances the
    identical chain (same seed, same dispatch count), which is what makes
    the post-exchange digests equal across hosts."""
    import jax
    return jax.tree_util.tree_flatten(
        {"params": net.params, "opt": net.opt_state, "state": net.state})


class _GlobalHostSync:
    """Host copy of a trainer whose trees are sharded across PROCESSES
    (the gspmd mode on a real multi-host backend): ``sync_to_net``'s
    plain ``device_get`` cannot read non-addressable shards, so each tree
    is first pulled to a replicated layout by a cached jitted identity
    (an all-gather collective every process runs) and fetched from the
    local replica. Single-process jobs skip all of this."""

    def __init__(self, trainer):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.trainer = trainer
        self._repl = NamedSharding(trainer.mesh, P())
        self._fns = {}

    def _pull(self, key, tree, fetch):
        import jax
        import numpy as np
        fn = self._fns.get(key)
        if fn is None:
            sh = jax.tree_util.tree_map(lambda _: self._repl, tree)
            fn = self._fns[key] = jax.jit(lambda t: t, out_shardings=sh)  # graftlint: disable=R3 -- built once per tree key (cached in self._fns), re-dispatched every round
        gathered = fn(tree)
        if not fetch:
            return None
        return jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), gathered)

    def __call__(self, fetch=True):
        """``fetch=False`` runs ONLY the replicating collective (which
        every process must dispatch for anyone's pull to complete) and
        skips the device->host transfer — the non-snapshot hosts' side of
        a round whose host copy nobody consumes. Returns None then."""
        import jax
        t, net = self.trainer, self.trainer.net
        params = self._pull("params", t.params, fetch)
        state = self._pull("state", t.state, fetch)
        opt = self._pull("opt", t.opt_state, fetch)
        if not fetch:
            return None
        net.params, net.state, net.opt_state = params, state, opt
        net._rng = jax.device_get(t._rng)
        net.iteration = t.iteration
        net.epoch = t.epoch
        return net


def main(argv=None):
    p = argparse.ArgumentParser(description="hostfleet training worker")
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--generation", type=int, default=0)
    p.add_argument("--coordinator", default=None,
                   help="host:port of this generation's jax.distributed "
                        "coordinator (omit to skip the runtime)")
    p.add_argument("--init-timeout-s", type=int, default=20)
    p.add_argument("--init-retries", type=int, default=2)
    p.add_argument("--exchange-port", type=int, default=None,
                   help="supervisor ExchangeServer port (hostavg mode)")
    p.add_argument("--exchange", default="auto",
                   choices=("auto", "gspmd", "hostavg"))
    p.add_argument("--round-timeout-s", type=float, default=120.0)
    # model/stream shape (must match the reference legs)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--features", type=int, default=12)
    p.add_argument("--hidden", type=int, default=16)
    p.add_argument("--classes", type=int, default=3)
    p.add_argument("--gen-seed", type=int, default=123)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--shard-params", default="zero1",
                   choices=("replicated", "zero1", "fsdp", "fsdp_stream"))
    # loop shape
    p.add_argument("--bundle", required=True,
                   help="layout-free save_bundle path: written by process "
                        "0 after every round, the rollback/resume source")
    p.add_argument("--resume", action="store_true",
                   help="restore from --bundle (resharded into THIS "
                        "topology) instead of a fresh net")
    p.add_argument("--total-rounds", type=int, required=True)
    p.add_argument("--dispatches-per-round", type=int, default=1)
    p.add_argument("--heartbeat-dir", required=True)
    p.add_argument("--round-sleep-s", type=float, default=0.0,
                   help="sleep between the local steps and the exchange "
                        "(chaos harnesses land a SIGKILL mid-round here)")
    p.add_argument("--serve-registry", action="store_true",
                   help="process 0: hot-swap an in-process ModelRegistry "
                        "from every published snapshot (the snapshot -> "
                        "serving handoff, measured post-recovery)")
    p.add_argument("--profile-round", type=int, default=None,
                   help="capture a jax.profiler window around exactly the "
                        "n-th round this process runs (1 = the first; "
                        "no-op off-TPU unless DL4J_TPU_PROFILE_FORCE=1)")
    p.add_argument("--profile-dir", default=None,
                   help="xprof logdir root for --profile-round (default "
                        "<heartbeat-dir>/profile/host<i>)")
    args = p.parse_args(argv)

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.telemetry import goodput as _goodput
    from deeplearning4j_tpu.telemetry import timeline as _timeline
    from deeplearning4j_tpu.telemetry import tracectx as _tracectx
    telemetry.enable()

    from deeplearning4j_tpu.parallel.distributed import (
        initialize_distributed, shutdown_distributed)

    me, world = args.process_id, args.num_processes
    if args.coordinator is not None:
        try:
            initialize_distributed(
                coordinator_address=args.coordinator, num_processes=world,
                process_id=me,
                initialization_timeout=args.init_timeout_s,
                connect_retries=args.init_retries)
        except Exception as e:  # noqa: BLE001 — counted, reported, distinct rc
            _emit({"hostfleet_error": str(e)[:500], "stage": "distributed_init",
                   "process": me, "generation": args.generation,
                   "distributed_init_total":
                       telemetry.series_map("distributed_init_total")})
            return RC_INIT_FAILED

    import jax
    import numpy as np

    mode = args.exchange
    if mode == "auto":
        # multi-process CPU runs (the tier-1 gates) use the host-mediated
        # exchange: its fixed process-id reduction order is what their
        # digest-parity references lean on, and a dead contributor times
        # out there instead of wedging a collective. (jax 0.9.0's CPU
        # client CAN execute a multi-process computation; ``--exchange
        # gspmd`` selects that path.)
        mode = ("hostavg" if (jax.process_count() > 1
                              and jax.default_backend() == "cpu")
                else "gspmd")
    if mode == "hostavg" and world > 1 and args.exchange_port is None:
        _emit({"hostfleet_error": "hostavg exchange needs --exchange-port",
               "stage": "setup", "process": me})
        return RC_INIT_FAILED

    from deeplearning4j_tpu.continuous import chaos
    from deeplearning4j_tpu.continuous.driver import (StepDriver,
                                                      _ShardedPlainEngine)
    from deeplearning4j_tpu.hostfleet.exchange import (ExchangeClient,
                                                       ExchangeError)
    from deeplearning4j_tpu.parallel import mesh as _mesh
    from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
    from deeplearning4j_tpu.utils.serialization import (load_bundle,
                                                        save_bundle)

    if args.resume:
        net = load_bundle(args.bundle).net
    else:
        net = chaos.smoke_net(seed=args.seed, features=args.features,
                              hidden=args.hidden, classes=args.classes)
        net.init()

    # the per-host compute mesh: this host's local devices only under
    # hostavg (cross-process dispatch is the exchange's job), the global
    # device set under gspmd (collectives ride ICI/DCN inside the step)
    devices = (jax.devices() if mode == "gspmd" else jax.local_devices())
    mesh = _mesh.make_mesh(_mesh.MeshSpec(data=len(devices)),
                           devices=devices)
    shard = None if args.shard_params in ("replicated", "zero1") else \
        args.shard_params
    trainer = ParallelTrainer(
        net, mesh, shard_params=shard,
        shard_optimizer_state=args.shard_params != "replicated")
    # adopt covers fresh init AND resume: the bundle's replicated host
    # trees are placed into THIS trainer's layouts on THIS topology — the
    # reshard-into-the-new-world step of the elastic story
    trainer.adopt_net_state()
    trainer.examples_dropped = 0  # the engine's indivisible-batch counter
    if mode == "gspmd" and jax.process_count() > 1:
        host_sync = _GlobalHostSync(trainer)
    else:
        def host_sync(fetch=True):  # single-process: device_get is cheap
            return trainer.sync_to_net()

    D = args.dispatches_per_round
    start_iter = int(trainer.iteration)
    start_round = start_iter // D
    # per-host deterministic stream under hostavg (each host trains its
    # own shard of the data); ONE shared stream under gspmd (the global
    # batch is sharded over the global mesh inside the step)
    host_seed = (args.gen_seed if mode == "gspmd"
                 else args.gen_seed + 7919 * me)
    batches = chaos.gen_batches(host_seed, args.total_rounds * D,
                                batch=args.batch, features=args.features,
                                classes=args.classes)[start_iter:]

    def factory():
        return ((x, y, None) for x, y in batches)

    driver = StepDriver(trainer, factory,
                        engine=_ShardedPlainEngine(trainer),
                        instrumented=False)
    if args.profile_round is not None:
        driver.profile_round(
            args.profile_round,
            args.profile_dir or os.path.join(args.heartbeat_dir,
                                             "profile", f"host{me}"))

    registry = None
    serve_update = None
    if args.serve_registry and me == 0:
        from deeplearning4j_tpu.continuous.trainer import registry_updater
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        registry = ModelRegistry()
        registry.register("hostfleet", net, buckets=[args.batch],
                          input_spec=(args.features,))
        serve_update = registry_updater(registry, "hostfleet")

    client = None
    if mode == "hostavg" and world > 1:
        try:
            client = ExchangeClient(args.exchange_port, me,
                                    timeout_s=args.round_timeout_s)
        except ExchangeError as e:
            _emit({"hostfleet_error": str(e)[:500], "stage": "exchange",
                   "process": me})
            return RC_EXCHANGE_FAILED

    os.makedirs(args.heartbeat_dir, exist_ok=True)
    hb_path = os.path.join(args.heartbeat_dir, f"host{me}.json")
    _emit({"hostfleet_ready": True, "process": me, "world": world,
           "generation": args.generation, "pid": os.getpid(),
           "mode": mode, "resumed": bool(args.resume),
           "start_round": start_round,
           "local_devices": len(jax.local_devices()),
           # the launcher pins hosts to the CPU by default: say so, so
           # nobody reads a hostfleet run as a chip run
           "platform": jax.local_devices()[0].platform,
           "layout": trainer.layout,
           "clock": _timeline.clock_pair()})

    # the worker's StepDriver is uninstrumented (no train_step_seconds
    # observes on fleet hosts), so the goodput ledger is fed from the
    # round edges the trace spans already time — window = the round loop
    ledger = _goodput.get_ledger().start()
    cache_sizes = []
    try:
        for rnd in range(start_round, args.total_rounds):
            # one causal trace per round: steps/exchange/heartbeat/
            # checkpoint as child spans, the doc riding the round line —
            # the supervisor's merged timeline shows which host stalled
            tctx = _tracectx.maybe_start("hostfleet.round", round=rnd,
                                         process=me,
                                         generation=args.generation)
            t_steps = time.perf_counter()
            driver.run_round(D)
            driver.sync()
            ledger.note("compute", time.perf_counter() - t_steps)
            ledger.note_tokens(D * args.batch)
            if tctx is not None:
                tctx.add_span("hostfleet.steps", t_steps,
                              time.perf_counter(), dispatches=D)
            if args.round_sleep_s:
                time.sleep(args.round_sleep_s)
            # only hosts with a consumer pay the device->host transfer:
            # the exchange (hostavg) or the bundle write (process 0);
            # gspmd peers still dispatch the replicating collective
            t_exch = time.perf_counter()
            host_net = host_sync(fetch=(client is not None or me == 0))
            if client is not None:
                leaves, treedef = _host_tree(host_net)
                avg = client.allreduce_mean(rnd, leaves)
                merged = jax.tree_util.tree_unflatten(treedef, avg)
                host_net.params = merged["params"]
                host_net.opt_state = merged["opt"]
                host_net.state = merged["state"]
                # re-arm the mesh trees from the averaged host copy —
                # identical shapes/shardings, so the cached jitted step
                # re-dispatches with ZERO recompiles (gated below)
                trainer.adopt_net_state()
            ledger.note("exchange", time.perf_counter() - t_exch)
            if tctx is not None:
                tctx.add_span("hostfleet.exchange", t_exch,
                              time.perf_counter(), mode=mode)
            if trainer._step_fn is not None:
                cache_sizes.append(trainer._step_fn._cache_size())
            t_hb = time.perf_counter()
            _atomic_write(hb_path, json.dumps(
                {"round": rnd, "iteration": int(trainer.iteration),
                 "ts": time.time()}))
            if tctx is not None:
                tctx.add_span("hostfleet.heartbeat", t_hb,
                              time.perf_counter())
            if me == 0:
                t_ck = time.perf_counter()
                tmp = args.bundle + ".tmp"
                save_bundle(host_net, tmp)
                os.replace(tmp, args.bundle)  # a resume never sees a
                #                               half-written bundle
                ledger.note("checkpoint", time.perf_counter() - t_ck)
                if tctx is not None:
                    tctx.add_span("hostfleet.checkpoint", t_ck,
                                  time.perf_counter())
                _emit({"snapshot": args.bundle, "round": rnd})
                if serve_update is not None:
                    serve_update(args.bundle)
            line = {"round": rnd, "iteration": int(trainer.iteration),
                    "process": me}
            if tctx is not None:
                tctx.finish()
                line["trace"] = tctx.trace.to_doc()
            _emit(line)
    except ExchangeError as e:
        _emit({"hostfleet_error": str(e)[:500], "stage": "exchange",
               "process": me, "generation": args.generation})
        return RC_EXCHANGE_FAILED
    finally:
        if client is not None:
            client.close()

    final_net = host_sync()
    serving_probe_diff = None
    if registry is not None:
        probe = chaos.gen_batches(args.gen_seed + 7, 1, batch=args.batch,
                                  features=args.features,
                                  classes=args.classes)[0][0]
        served = np.asarray(registry.output("hostfleet", probe))
        direct = np.asarray(final_net.output(probe))
        serving_probe_diff = float(np.max(np.abs(served - direct)))
        registry.unregister("hostfleet")

    # jax's jitted step re-traces once under a flipped trace context
    # after the first call (pre-existing, layout-independent — see
    # scripts/check_zero.py); steady state is reached by the end of the
    # second round, and any growth past it is a REAL recompile
    steady = cache_sizes[min(1, len(cache_sizes) - 1)] if cache_sizes else 0
    recompiles = (cache_sizes[-1] - steady) if cache_sizes else 0

    _emit({"hostfleet_done": True, "process": me, "world": world,
           "generation": args.generation, "mode": mode,
           "digest": chaos.state_digest(final_net),
           "iteration": int(trainer.iteration),
           "rounds": args.total_rounds - start_round,
           "start_round": start_round,
           "serving_probe_diff": serving_probe_diff,
           "step_recompiles": int(recompiles),
           "goodput": ledger.snapshot(),
           "counters": {name: telemetry.series_map(name) for name in (
               "distributed_init_total", "recompiles_total",
               "compiles_total")}})
    shutdown_distributed()  # leave cleanly: a rejoin starts a NEW generation
    return 0


if __name__ == "__main__":
    sys.exit(main())
