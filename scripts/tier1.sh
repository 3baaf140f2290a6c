#!/usr/bin/env bash
# Tier-1 verify — the exact ROADMAP.md command plus the CPU gate stages.
# Everything here is pinned to the CPU backend (`JAX_PLATFORMS=cpu`): these
# are correctness, counter and parity gates, never device numbers, and they
# must not take a chip another process holds. Run from anywhere:
#
#   scripts/tier1.sh            # full fast tier (~4.5 min)
#   scripts/tier1.sh tests/test_health.py   # extra pytest args pass through
set -o pipefail
cd "$(dirname "$0")/.."

# Stage 0: graftlint — the static-analysis gate (analysis/ package),
# running the FULL rule set R1-R13 (the interprocedural dataflow rules
# R7-R9 and the wire/metric contract rules R10-R13 register alongside
# R1-R6; nothing to opt into). Fails on any non-baselined finding AND
# (--strict-baseline) on stale baseline entries, so
# graftlint.baseline.json only ever shrinks.
echo "== graftlint =="
env JAX_PLATFORMS=cpu \
  python -m deeplearning4j_tpu lint --strict-baseline || {
    echo "tier1: graftlint gate FAILED (fix, suppress with justification,"
    echo "tier1: or update graftlint.baseline.json)"; exit 1; }

# Stage 0 (cont.): schema drift — SCHEMA.json/METRICS.md must match a
# fresh harvest of the wire+metric contract (lint --emit-schema), and
# every series bench.py / analyze_bench.py / scripts/*.py read by name
# must exist in it (R11b extended to the unlinted driver files).
echo "== schema drift (SCHEMA.json / METRICS.md) =="
env JAX_PLATFORMS=cpu \
  python scripts/check_schema.py || {
    echo "tier1: schema drift gate FAILED (regenerate with:"
    echo "tier1:   python -m deeplearning4j_tpu lint --emit-schema)"
    exit 1; }

# Stage 0b: graftsan — the runtime concurrency sanitizer over the
# threaded/donating test modules (analysis/sanitizer.py via the
# GRAFTSAN=1 conftest fixture): observed lock inversions, leaked
# non-daemon threads, never-resolved futures and unlocked cross-thread
# RMW fail the stage; the observed-order report feeds `lint
# --san-report` for the static-x-runtime lock-graph merge.
echo "== graftsan (runtime concurrency sanitizer) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu \
  GRAFTSAN=1 GRAFTSAN_REPORT=/tmp/graftsan_tier1.json \
  python -m pytest tests/test_serving.py tests/test_fused.py \
  tests/test_streaming.py tests/test_parallel.py tests/test_native.py \
  tests/test_ui.py tests/test_sanitizer.py tests/test_fleet.py \
  tests/test_continuous.py tests/test_hostfleet.py \
  tests/test_demand.py tests/test_seq_buckets.py \
  -q -m 'not slow' \
  -p no:cacheprovider -p no:xdist -p no:randomly || {
    echo "tier1: graftsan stage FAILED"; exit 1; }
env JAX_PLATFORMS=cpu \
  python -m deeplearning4j_tpu lint --san-report /tmp/graftsan_tier1.json \
  || { echo "tier1: lint --san-report merge FAILED"; exit 1; }

# Stage 1: the fast test tier (the exact ROADMAP.md command).
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
  python -m pytest "${@:-tests/}" -q -m 'not slow' \
  --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# Stage 2: fused-dispatch bench smoke (nn/fused.py) — the K-sweep on a
# tiny MLP at CPU preflight shapes, streaming BENCH JSON into
# BENCH_smoke.json so every tier-1 run refreshes the dispatch-amortization
# trajectory record next to the test signal.
echo "== fused bench smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py fused --steps-per-dispatch 1,4 \
  | tee BENCH_smoke.json || {
    echo "tier1: fused bench smoke FAILED"; exit 1; }

# Stage 3: serving bench smoke (deeplearning4j_tpu/serving) — the
# latency-vs-offered-load sweep at small CPU loads, appended into
# BENCH_smoke.json so every tier-1 run also refreshes the serving tier's
# p50/p99/shed curve next to the dispatch-amortization record.
echo "== serving bench smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py serving \
  | tee -a BENCH_smoke.json || {
    echo "tier1: serving bench smoke FAILED"; exit 1; }

# Stage 4: trace-overhead smoke (telemetry/tracectx, ISSUE 8) — causal
# tracing must stay near-free on the fused step path: adjacent off/on
# fused-fit leg pairs, gated on the BEST pair's ratio (a real regression
# — an added sync, per-dispatch churn — taxes every pair; noisy-neighbor
# jitter doesn't survive the best-of). Fail tier-1 if even the best pair
# regresses steps/s more than 5%.
echo "== trace-overhead smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py trace_overhead \
  > /tmp/_trace_overhead.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_trace_overhead.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_trace_overhead.py /tmp/_trace_overhead.jsonl 5.0 \
  || { echo "tier1: trace-overhead smoke FAILED (>5% fused steps/s"
       echo "tier1: regression with tracing on)"; exit 1; }

# Stage 5: cold-start smoke (utils/compile_cache, ISSUE 9) — the
# instant-restart A/B: four fresh subprocesses (train/serve x cold/warm)
# sharing one workdir; the warm legs must restore every executable from
# the warm manifest (compile_cache_total hits only, zero compiles —
# counter-gated by scripts/check_coldstart.py; wall times recorded, not
# gated). The record lands in BENCH_smoke.json next to the other smokes.
echo "== cold-start smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py coldstart \
  > /tmp/_coldstart.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_coldstart.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_coldstart.py /tmp/_coldstart.jsonl \
  || { echo "tier1: cold-start smoke FAILED (warm restart recompiled,"
       echo "tier1: or a leg crashed)"; exit 1; }

# Stage 6: ZeRO sharded-weight-update smoke (ISSUES 10+14) — the A/B row:
# replicated vs zero1 vs fsdp vs fsdp_stream layouts of the same
# data-parallel fit on an 8-device CPU mesh (XLA_FLAGS pins the device
# count; the other stages run single-device and don't want it), plus the
# DP×TP×PP composed-parity leg (2×2×2 ComposedTrainer vs the DP-only
# reference). scripts/check_zero.py gates on COUNTERS AND BYTES, never
# wall time: per-device opt_state (and fsdp/fsdp_stream param) bytes must
# realize the 1/N sharding, the streamed leg's analyzed step-peak bytes
# (memory_analysis) sit strictly below plain fsdp, each leg compiles once
# with zero recompiles, the sharded legs' params match the replicated
# leg's, and the composed leg matches its DP-only reference ≤1e-6 with a
# bit-exact ragged bucketed fit. steps/s lands in the record, ungated.
echo "== zero sharded-update smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  timeout -k 10 300 python bench.py zero \
  > /tmp/_zero.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_zero.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_zero.py /tmp/_zero.jsonl \
  || { echo "tier1: zero smoke FAILED (sharded layout not 1/N, a leg"
       echo "tier1: recompiled, or sharded params diverged)"; exit 1; }

# (There is no stage 7: README and PROFILE.md cite the numbers below.)

# Stage 8: fleet serving smoke (deeplearning4j_tpu/fleet, ISSUE 12) —
# the multi-process pool end to end: 3 worker processes warm-started
# from one checkpoint + manifest behind the router, capacity probe +
# offered-load sweep + the kill-a-worker chaos leg (SIGKILL mid-sweep,
# retry onto survivors, elastic respawn). scripts/check_fleet.py gates
# on COUNTERS AND PARITY (every worker and the replacement warm-start
# with zero compiles, fleet answers == single-engine answers <=1e-6,
# zero uncounted request losses) — never wall time on CPU.
echo "== fleet serving smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py fleet \
  > /tmp/_fleet.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_fleet.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_fleet.py /tmp/_fleet.jsonl \
  || { echo "tier1: fleet smoke FAILED (a worker cold-started, the"
       echo "tier1: replacement recompiled, requests were lost"
       echo "tier1: uncounted, or fleet/single-engine parity broke)"; exit 1; }

# Stage 9: continuous-learning chaos smoke (deeplearning4j_tpu/continuous,
# ISSUE 13) — the streaming loop end to end under injected faults: a REAL
# runner subprocess trains from the pubsub stream while the producer is
# killed mid-stream (replacement resumes it), one batch is NaN-poisoned
# (watchdog -> rollback to the last bundle -> resume) and one arrives
# past the staleness bound (counted drop); a second leg SIGTERMs the run
# mid-round (flight dump) and resumes from the bundle.
# scripts/check_continuous.py gates on COUNTERS AND PARITY (faulted run
# == clean reference digest-EXACT incl. the RNG chain, every fault
# counted, zero recompiles on rollback, serving handoff healthy, zero
# hangs) — never wall time on CPU.
echo "== continuous chaos smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py continuous \
  > /tmp/_continuous.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_continuous.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_continuous.py /tmp/_continuous.jsonl \
  || { echo "tier1: continuous chaos smoke FAILED (rollback/resume not"
       echo "tier1: bit-exact, a fault went uncounted, ingest went"
       echo "tier1: fatal, or the SIGTERM dump/resume path broke)"; exit 1; }

# Stage 10: elastic multi-host training chaos smoke
# (deeplearning4j_tpu/hostfleet, ISSUE 15) — N REAL training processes
# under the TrainingFleetSupervisor: clean leg, kill-one-host leg (SIGKILL
# mid-round -> round watchdog/teardown -> re-form jax.distributed at N-1
# -> restore the layout-free bundle RESHARDED into the new topology ->
# resume -> serve), and a respawn leg re-forming at full size.
# scripts/check_hostfleet.py gates on COUNTERS AND DIGEST PARITY (faulted
# runs digest-EXACT vs fault-free references on the same final topology,
# every death/generation/rollback counted, zero recompiles within a
# generation, post-recovery serving probe <=1e-6) — never wall time on
# CPU.
echo "== hostfleet elastic-training chaos smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py hostfleet \
  > /tmp/_hostfleet.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_hostfleet.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_hostfleet.py /tmp/_hostfleet.jsonl \
  || { echo "tier1: hostfleet smoke FAILED (recovery not digest-exact,"
       echo "tier1: a death/rollback went uncounted, a generation"
       echo "tier1: recompiled, or the fleet wedged)"; exit 1; }

# Stage 11: cluster-observability smoke (telemetry federation/timeline +
# fleet wire tracing, ISSUE 16) — a REAL 2-worker fleet with telemetry on
# both sides of the wire: one routed request must yield ONE trace whose
# ring doc contains the worker process's serving.queue_wait/device_exec
# spans grafted under the dispatching attempt; /metrics?federate=1
# semantics (per-instance federated sums == per-member scrape sums); the
# merged cluster timeline names router + both workers; a SIGKILLed member
# is a COUNTED scrape error, never a hang. scripts/check_cluster_obs.py
# gates STRUCTURALLY (span graph, counter sums, scrape outcomes) — never
# wall time; the tracing-cost claim rides stage 4's <=5% gate.
echo "== cluster-observability smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py cluster_obs \
  > /tmp/_cluster_obs.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_cluster_obs.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_cluster_obs.py /tmp/_cluster_obs.jsonl \
  || { echo "tier1: cluster-observability smoke FAILED (the router trace"
       echo "tier1: lost the worker-side spans, federation sums drifted,"
       echo "tier1: or a dead member hung/went uncounted)"; exit 1; }

# Stage 12: SLO-engine + goodput-ledger smoke (telemetry/slo +
# telemetry/goodput, ISSUE 17) — the metrics plane turned into verdicts:
# the default ruleset must stay SILENT over a healthy process (zero
# firing rules, zero alert transitions), a deterministic injected shed
# storm must walk serving_shed_ratio ok -> firing -> (on healthy
# traffic) ok with every transition counted MONOTONE in
# slo_alerts_total, a flight dump written mid-storm must name the
# burning rule, and the goodput ledger's six wall-clock categories over
# a real instrumented fit must sum to the observed window within 5%.
# scripts/check_slo.py gates STRUCTURALLY — never wall time.
echo "== slo-engine + goodput smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py slo_goodput \
  > /tmp/_slo_goodput.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_slo_goodput.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_slo.py /tmp/_slo_goodput.jsonl \
  || { echo "tier1: slo/goodput smoke FAILED (a healthy run fired, the"
       echo "tier1: injected storm did not, a transition went uncounted,"
       echo "tier1: or the goodput ledger lost wall-clock seconds)"; exit 1; }

# Stage 13: demand-observability smoke (telemetry/history +
# serving/metering + fleet/prober, ISSUE 18) — the demand plane end to
# end: a real fit sampled into the metrics-history ring and persisted as
# atomic segments with rate_over parity <=1e-6 against the live SLO
# delta discipline; a REAL 2-worker fleet left organically idle while a
# synthetic prober canaries it through the router wire path (probe_total
# advances, every unlabeled organic series stays exactly zero); the
# per-model usage ledger folded from worker /usage must balance EXACTLY
# against the router's served_rows; and a wrong-answer canary must walk
# probe_failure_ratio ok -> firing -> ok with both transitions counted.
# scripts/check_demand.py gates STRUCTURALLY (counters, ledger balance,
# parity) — never wall time.
echo "== demand-observability smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py demand_obs \
  > /tmp/_demand_obs.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_demand_obs.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_demand.py /tmp/_demand_obs.jsonl \
  || { echo "tier1: demand-observability smoke FAILED (history parity"
       echo "tier1: drifted, probe traffic leaked into organic series,"
       echo "tier1: the usage ledger did not balance, or the probe gate"
       echo "tier1: never fired/recovered)"; exit 1; }

# Stage 14: seq-serving padded-waste smoke (2-D shape grid, ISSUE 20) —
# one ragged-length RNN workload served twice through the real engine
# (seq grid vs pad-to-max), the usage ledger's padded-vs-real token
# columns read back per leg. scripts/check_seq_serving.py gates on
# LEDGER EXACTNESS, COUNTERS AND PARITY (rows and real tokens balance
# exactly, FLOPs priced at 2*params*padded_tokens, full grid warmed with
# zero lazy compiles, grid == flat == reference <= 1e-6, padded-waste
# cut >= 2x) — never wall time on CPU.
echo "== seq-serving padded-waste smoke =="
env JAX_PLATFORMS=cpu BENCH_PREFLIGHT=1 \
  timeout -k 10 300 python bench.py seq_serving \
  > /tmp/_seq_serving.jsonl \
  && tee -a BENCH_smoke.json < /tmp/_seq_serving.jsonl > /dev/null \
  && env JAX_PLATFORMS=cpu \
    python scripts/check_seq_serving.py /tmp/_seq_serving.jsonl \
  || { echo "tier1: seq-serving smoke FAILED (ledger drifted, a shape"
       echo "tier1: leaked a lazy compile, parity broke, or the 2-D"
       echo "tier1: grid stopped cutting padded waste >= 2x)"; exit 1; }

exit $rc
