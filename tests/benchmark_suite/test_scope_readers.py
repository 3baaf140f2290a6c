"""The readers of the program's own names (PR 25), on made-up input: a
`while` whose nested operations are counted once, a span with children,
an idle gap in no program span. The made-up trace is a real `XSpace`
file, so `benchmark.trace` and `benchmark.xspace` both parse it."""

import os
import types

import pytest

from benchmark import nesting, spec, trace, xspace
from benchmark.readers import (span_share, trace_idle_unattributed,
                               trace_scope_ms)

STEP = "jit(train_step)/"
OPS = [  # (hlo text, tf_op, start ns, end ns)
    ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     STEP + "updater/mul:", 500, 1200),            # cut to the window: 200
    ("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p), kind=kOutput",
     STEP + "jvp(L00.DenseLayer)/dot_general:", 2000, 3000),
    ("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
     STEP + "transpose(jvp(L00.DenseLayer))/while:", 3000, 6000),
    ("%fusion.3 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p), kind=kOutput",
     STEP + "transpose(jvp(L00.DenseLayer))/while/body/dot_general:",
     3100, 4100),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     STEP + "transpose(jvp(loss))/while/body/mul:", 4200, 5200),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     STEP + "updater/add:", 6000, 6500),
    ("%copy.6 = f32[8]{0} copy(f32[8]{0} %p)", None, 6500, 6600),
    ("%fusion.7 = f32[]{:T(256)} fusion(f32[8]{0} %p), kind=kInput",
     STEP + "jvp(loss)/reduce_sum:", 7000, 7400),
]
SPANS = [("bench.fit_round", 1000, 9000), ("bench.fit_sync", 9000, 11000),
         ("fit.round", 1050, 8100), ("fit.step", 1500, 4000),
         ("fit.dispatch", 1550, 1900), ("fit.step", 4500, 8000),
         ("fit.sync", 9000, 9100), ("loss_fetch_of_the_harness", 9100, 9900)]
SCOPES = {"fwd": {"scope": r"L\d+\.[^/()]+|V\.[^/()]+|loss",
                  "backward": False},
          "bwd": {"scope": r"L\d+\.[^/()]+|V\.[^/()]+|loss",
                  "backward": True},
          "updater": {"scope": "updater|grad_norm|health"},
          "unscoped": {"scope": r"L\d+\.[^/()]+|V\.[^/()]+|loss|updater|"
                                "grad_norm|health", "invert": True},
          "loss": {"scope": "loss"}}


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    """An `.xplane.pb` made from OPS and SPANS where the profiler would
    have put one."""
    pb2 = xspace._pb2()
    xs = pb2.XSpace()
    dev = xs.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "tf_op"
    line = dev.lines.add(name=trace.OPS_LINE, timestamp_ns=0)
    for i, (text, tf_op, s, e) in enumerate(OPS, start=1):
        md = dev.event_metadata[i]
        md.id, md.name = i, text
        if tf_op is not None:
            md.stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=s * 1000,
                        duration_ps=(e - s) * 1000)
    host = xs.planes.add(name=trace.HOST_PLANE)
    thread = host.lines.add(name="python3", timestamp_ns=100)
    for i, (name, s, e) in enumerate(SPANS, start=1):
        host.event_metadata[i].id, host.event_metadata[i].name = i, name
        thread.events.add(metadata_id=i, offset_ps=(s - 100) * 1000,
                          duration_ps=(e - s) * 1000)
    root = tmp_path_factory.mktemp("made_up")
    where = root / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(xs.SerializeToString())
    return str(root)


def _obs(trace_dir, **result):
    ctx = types.SimpleNamespace(trace_dir=trace_dir)
    return {"ctx": ctx, "trace": trace.load(trace_dir), "result": result}


def test_self_time_counts_a_nested_interval_once():
    rows = nesting.self_times([(0, 100, "while"), (10, 40, "a"),
                               (40, 70, "b"), (45, 60, "b.inner"),
                               (200, 250, "c")])
    assert rows == [("while", 40, 0, 100), ("a", 30, 10, 40),
                    ("b", 15, 40, 70), ("b.inner", 15, 45, 60),
                    ("c", 50, 200, 250)]
    assert sum(r[1] for r in rows) == trace.union_length(
        [(r[2], r[3]) for r in rows])
    # an interval that overhangs its parent's end is cut to it
    assert nesting.self_times([(0, 10, "p"), (5, 12, "q")]) == \
        [("p", 5, 0, 10), ("q", 5, 5, 10)]


def test_both_parsers_read_the_made_up_file_alike(trace_dir):
    tr, view = trace.load(trace_dir), xspace.load_dir(trace_dir)
    assert (tr.t0, tr.t1) == (1000, 11000)
    ops = view.device_ops["/device:TPU:0"]
    assert [(s, e) for s, e, *_ in ops] == \
        [(s, e) for _, s, e in tr.devices["/device:TPU:0"]]
    assert ops[1][2:4] == (STEP + "jvp(L00.DenseLayer)/dot_general:",
                          "fusion.1 fusion:kOutput f32[8,8]{1,0}")
    assert ops[6][2] == ""
    assert [n for n, _, _ in view.host_spans(["fit.step"], 1000, 11000)] \
        == ["fit.step"] * 2


@pytest.mark.parametrize("part,ns_a_step", [
    ("fwd", (1000 + 400) / 2),            # fusion.1, the loss's reduce
    ("bwd", (1000 + 1000 + 1000) / 2),    # while's self, its two bodies
    ("updater", (200 + 500) / 2),         # the first cut to the window
    ("unscoped", 100 / 2), ("loss", (1000 + 400) / 2)])
def test_scope_ms_on_the_made_up_trace(trace_dir, part, ns_a_step, capsys):
    got = trace_scope_ms.read(_obs(trace_dir), SCOPES[part])
    assert got == pytest.approx(ns_a_step * 1e-6)
    assert "in 2 steps" in capsys.readouterr().out


def test_the_step_parts_add_up_to_the_busy_time(trace_dir):
    obs = _obs(trace_dir)
    parts = [trace_scope_ms.read(obs, SCOPES[p])
             for p in ("fwd", "bwd", "updater", "unscoped")]
    assert sum(parts) * 2 == pytest.approx(obs["trace"].busy_s * 1e3)
    assert obs["trace"].busy_s == pytest.approx(5200e-9)


def test_scope_ms_gives_nothing_without_a_match_or_a_step(trace_dir):
    obs = _obs(trace_dir)
    assert trace_scope_ms.read(obs, {"scope": r"flash_attn\.fwd"}) is None
    # `loss` is one whole component: no part of a longer name matches
    match = trace_scope_ms.matcher({"scope": "loss"})
    assert match("jit(f)/jvp(loss)/mul:") and match("jit(f)/loss/mul:")
    assert not match("jit(f)/jvp(V.loss_head.DenseLayer)/mul:")
    assert not match("jit(f)/my_loss/mul:")
    no_trace = {"ctx": obs["ctx"], "trace": None, "result": {}}
    assert trace_scope_ms.read(no_trace, SCOPES["fwd"]) is None


def test_sum_with_prints_the_parts_beside_the_busy_time(trace_dir, capsys,
                                                        monkeypatch):
    monkeypatch.setattr(spec, "layer_metric",
                        lambda name: {"args": SCOPES[name]})
    args = {**SCOPES["unscoped"], "sum_with": ["fwd", "bwd", "updater"]}
    assert trace_scope_ms.read(_obs(trace_dir), args) == \
        pytest.approx(50e-6)
    out = capsys.readouterr().out
    assert "= 0.0026 ms; device busy 0.0026 ms a step (2 steps)" in out


def test_idle_gaps_go_to_the_innermost_program_span(trace_dir, capsys):
    spans = [(n, s, e) for n, s, e in SPANS if n.startswith("fit.")]
    named = trace_idle_unattributed.attribute(
        [(1200, 2000), (6600, 7000), (7400, 11000)], spans)
    # 9200 lies in the harness's fetch alone, which is no program span
    assert named == [(None, 3600), ("fit.dispatch", 800), ("fit.step", 400)]
    got = trace_idle_unattributed.read(_obs(trace_dir),
                                       {"prefixes": ["fit.", "etl."]})
    assert got == pytest.approx(100.0 * 3600 / 4800)
    assert "unattributed 0.004 ms; fit.dispatch 0.001 ms" in \
        capsys.readouterr().out
    assert trace_idle_unattributed.read(
        _obs(trace_dir), {"prefixes": ["serving."]}) is None


def _chrome(name, ts_s, dur_s, tid=1):
    return {"name": name, "ph": "X", "ts": ts_s * 1e6, "dur": dur_s * 1e6,
            "pid": 1, "tid": tid}


EVENTS = [
    _chrome("fit.round", -2.0, 1.0),                 # before the window
    _chrome("fit.round", 1.0, 4.0), _chrome("fit.next", 1.0, 0.1),
    _chrome("fit.step", 1.2, 3.0), _chrome("fit.dispatch", 1.3, 0.5),
    _chrome("fit.score_fetch", 2.0, 2.0), _chrome("fit.sync", 5.5, 1.0),
    _chrome("fit.round", 7.0, 1.0), _chrome("fit.next", 7.2, 0.5),
    _chrome("etl.assemble", 1.5, 1.0, tid=2),        # another thread
    {"name": "marker", "ph": "i", "ts": 2e6, "pid": 1, "tid": 1},
]


def test_span_self_time_leaves_out_children_on_the_same_thread():
    rows = span_share.window_self_times(EVENTS, 100.0, 100.0, 110.0)
    self_s = {}
    for name, s, *_ in rows:
        self_s.setdefault(name, []).append(round(s, 6))
    assert self_s == {"fit.round": [0.9, 0.5], "fit.next": [0.1, 0.5],
                      "fit.step": [0.5], "fit.dispatch": [0.5],
                      "fit.score_fetch": [2.0], "fit.sync": [1.0],
                      "etl.assemble": [1.0]}
    wall, inside = span_share.longest_round(rows)
    assert wall == pytest.approx(4.0)
    assert {n: round(t, 6) for n, t in inside.items()} == {
        "fit.round": 0.9, "fit.next": 0.1, "fit.step": 0.5,
        "fit.dispatch": 0.5, "fit.score_fetch": 2.0}


@pytest.mark.parametrize("spans,share", [
    (["fit.dispatch"], 5.0), (["fit.next"], 6.0),
    (["fit.score_fetch", "fit.sync"], 30.0)])
def test_span_share_reads_the_programs_tracer(spans, share, capsys):
    from deeplearning4j_tpu import telemetry
    telemetry.reset()
    tracer = telemetry.get_tracer()
    try:
        for ev in EVENTS:
            if ev["ph"] == "X":
                tracer.add_complete(ev["name"], ev["ts"], ev["dur"],
                                    tid=ev["tid"])
        ctx = types.SimpleNamespace(window_t0=tracer.epoch,
                                    window_t1=tracer.epoch + 10.0)
        obs = {"ctx": ctx, "trace": None, "result": {"window_wall": 10.0}}
        got = span_share.read(obs, {"spans": spans, "longest_round": True})
        assert got == pytest.approx(share)
        assert "longest fit.round 4.000000 s" in capsys.readouterr().out
        assert span_share.read(obs, {"spans": ["serving.batch"]}) is None
    finally:
        telemetry.reset()


def test_every_new_metric_file_names_a_reader_and_its_arguments():
    bench = spec.load_benchmark()
    new = [m for m in bench["per_layer"] if spec.layer_metric(m["name"])[
        "reader"] in ("trace_scope_ms", "span_share",
                      "trace_idle_unattributed")]
    assert len(new) >= 21 and all(m["better"] == "lower" for m in new)
    for m in new:
        lm = spec.layer_metric(m["name"])
        if lm["reader"] == "trace_scope_ms":
            trace_scope_ms.matcher(lm["args"])
            for other in lm["args"].get("sum_with", []):
                assert spec.layer_metric(other)["reader"] == lm["reader"]
        assert os.path.isfile(os.path.join(
            spec.PACKAGE_DIR, "readers", lm["reader"] + ".py"))


def test_a_traced_run_on_the_cpu_reports_the_span_shares(
        monkeypatch, tmp_path, capsys):
    """The real entry point at toy size with the new metrics laid over
    the toy benchmark: the program's spans are read from its tracer, and
    the trace readers find no device plane and give nothing."""
    import json

    import jax

    from benchmark import peaks, run
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.utils import dtypes
    toy = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
    cell = "toy-gpt2-train"
    new = [{**m, "workloads": [cell]}
           for m in spec.load_benchmark()["per_layer"]
           if m["name"].endswith(".tokens") and spec.layer_metric(
               m["name"])["reader"] in ("trace_scope_ms", "span_share",
                                        "trace_idle_unattributed")]
    real = spec.load_benchmark

    def laid_over(root=spec.REPO_ROOT):
        bench = real(root)
        bench["per_layer"] = bench["per_layer"] + new
        return bench

    monkeypatch.setattr(spec, "load_benchmark", laid_over)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    try:
        rc = run.main(["--workload", cell, "--seed", "11", "--seconds",
                       "0.5", "--trace", "1"], root=toy,
                      out_dir=str(tmp_path))
    finally:
        dtypes.f32_policy()
        telemetry.reset()
        telemetry.disable()
    out = capsys.readouterr().out
    m = json.loads(out.strip().splitlines()[-1])["metrics"]
    assert rc == 0
    for name in ("fit_dispatch_share.tokens", "fit_input_wait_share.tokens",
                 "fit_device_wait_share.tokens"):
        assert 0 < m[name]["value"] < 100 and m[name]["unit"] == "%"
    assert "longest fit.round" in out
    assert not [n for n in m if n.startswith(("step_", "attn_", "idle_"))]


HLO = """HloModule jit_train_step, is_scheduled=true

fused_computation.7 {
  param_0.1 = f32[8]{0} parameter(0)
  mul.3 = f32[8]{0} multiply(param_0.1, param_0.1), metadata={op_name="jit(train_step)/transpose(jvp(L01.OutputLayer))/mul"}
  convert.9 = bf16[8]{0} convert(mul.3)
  ROOT bitcast.2 = bf16[8,1]{1,0} bitcast(convert.9)
}

fused_computation.8 {
  param_0.2 = (f32[8]{0}, f32[8]{0}) parameter(0)
  ROOT add.5 = f32[8]{0} add(param_0.2, param_0.2), metadata={op_name="jit(train_step)/jvp(L00.DenseLayer)/add"}
}

ENTRY main.9 {
  p.1 = f32[8]{0} parameter(0), metadata={op_name="params[0]['W']"}
  copy.4 = f32[8]{0:S(1)} copy(p.1)
  copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(copy.4)
  copy-done.1 = f32[8]{0} copy-done(copy-start.1)
  fusion.8 = f32[8]{0} fusion(copy-done.1), kind=kLoop, calls=fused_computation.8, metadata={op_name="jit(train_step)/jvp(L00.DenseLayer)/add"}
  fusion.7 = bf16[8,1]{1,0} fusion(fusion.8), kind=kLoop, calls=fused_computation.7
  neg.1 = f32[8]{0} negate(fusion.8), metadata={op_name="jit(train_step)/neg"}
  copy.5 = f32[8]{0} copy(neg.1)
  ROOT tuple.1 = (bf16[8,1]{1,0}, f32[8]{0}) tuple(fusion.7, copy.5)
}
"""


def test_a_nameless_operation_is_lent_its_nearest_named_neighbours_name():
    from benchmark import hlo_names
    m = hlo_names.Module(HLO)
    # a fusion rooted at the compiler's convert: back from its root
    assert m.resolve("%fusion.7") == \
        "jit(train_step)/transpose(jvp(L01.OutputLayer))/mul"
    # a copy and its asynchronous twin: forward to the fusion they feed
    for name in ("copy.4", "copy-start.1", "%copy-done.1"):
        assert m.resolve(name) == "jit(train_step)/jvp(L00.DenseLayer)/add"
    # what JAX named keeps its name, scoped or not; nothing to lend from
    # past the end of the program; an unknown name gives nothing
    assert m.resolve("neg.1") == "jit(train_step)/neg"
    assert m.resolve("copy.5") == "" and m.resolve("fusion.99") == ""
    assert m.resolve("p.1") == "params[0]['W']"


def test_the_recorded_trace_lends_its_copies_the_matmuls_name():
    """`recorded/tiny.xplane.pb` holds its program's HLO: the two copies
    before each matmul fusion have no `tf_op` of their own."""
    view = xspace.load(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "recorded", "tiny.xplane.pb"))
    ops = view.device_ops["/device:TPU:0"]
    assert len(ops) == 18
    assert [(o[2], o[4]) for o in ops[:3]] == [
        ("jit(<lambda>)/dot_general", True),
        ("jit(<lambda>)/dot_general", True),
        ("jit(<lambda>)/dot_general:", False)]
    assert ops[0][3].startswith("copy-start copy-start (bf16[1024,1024]")


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded")


@pytest.fixture(scope="module")
def scoped_dir(tmp_path_factory):
    """`recorded/scoped.xplane.pb` where the profiler would have put it."""
    import shutil
    root = tmp_path_factory.mktemp("scoped")
    where = root / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(RECORDED, "scoped.xplane.pb"),
                where / "host.xplane.pb")
    return str(root)


def test_scope_readers_on_a_trace_recorded_on_the_chip(scoped_dir, capsys):
    """`recorded/scoped.xplane.pb` (TPU v5 lite, PR 25, before the step's
    name got the grammar's stamp): Dense(256->512, relu) + Output(16)
    with Adam through `StepDriver`, telemetry on, two rounds of two steps
    between `bench.fit_round` / `bench.fit_sync`, a 2 ms sleep in the
    first sync. Worked out by hand from the events' own columns (ns, all
    four steps; `lent` marks operations the compiler made, which take
    their nearest named neighbour's name):

    forward   L00 4 x convolution_add_fusion 1505.0+1502.5+1506.328+
              1502.578 = 6016.406, lent 1201.406; L01 named 1040.156,
              lent 72.812; loss 4 x multiply_reduce_fusion.1 376.172+
              375.078+377.422+375.156 = 1503.828, lent 1388.438
    backward  L00 4 x divide_add_fusion 1251.25+1251.172+1251.25+1251.328
              = 5005.0, lent (the copy-dones of its operands) 7564.53;
              L01 named 3450.782, lent 61.016
    updater   divide_add_fusion.2/.3: 92.656+93.672+92.422+92.5 = 371.25,
              lent 1281.326
    unscoped  the key-split and unstack programs the loop dispatches
              beside each step (`jit(_threefry_split)` 7753.828,
              `jit(_unstack)` 2347.812) and 10531.64 of copies that feed
              only the step's outputs: nothing named to lend from"""
    tr = trace.load(scoped_dir)
    view = xspace.load_dir(scoped_dir)
    ops = view.device_ops["/device:TPU:0"]
    assert len(ops) == 276 and (tr.t0, tr.t1) == (44825319.0, 60890898.0)
    assert sum(o[4] for o in ops) == 112                    # lent a name
    assert sum(not o[2] for o in ops) == 96                 # left without
    assert len(view.host_spans(["fit.step"], tr.t0, tr.t1)) == 4
    obs = _obs(scoped_dir)
    want_ns = {"fwd": 6016.406 + 1201.406 + 1040.156 + 72.812 + 1503.828
               + 1388.438,
               "bwd": 5005.0 + 7564.53 + 3450.782 + 61.016,
               "updater": 371.25 + 1281.326,
               "unscoped": 7753.828 + 2347.812 + 10531.64,
               "loss": 1503.828 + 1388.438}
    got = {p: trace_scope_ms.read(obs, SCOPES[p]) for p in want_ns}
    for part, ns in want_ns.items():
        assert got[part] == pytest.approx(ns / 4 * 1e-6, rel=1e-6), part
    # ProfileData cuts every event to whole ns, the proto keeps ps: the
    # four parts (49590.23 ns) lie 125 ns over `busy_s` (49465 ns)
    four = sum(got[p] for p in ("fwd", "bwd", "updater", "unscoped"))
    assert four * 4e6 == pytest.approx(49590.23, abs=0.01)
    assert tr.busy_s == pytest.approx(49465e-9)
    capsys.readouterr()


def test_idle_reader_on_a_trace_recorded_on_the_chip(scoped_dir, capsys):
    """The three longest gaps of `recorded/scoped.xplane.pb`, by hand:
    48790780 -> 54279510 (5488730 ns, the sync that slept; its middle
    51535145 lies in `fit.sync` 51059638..51688338); 57835121 -> the
    window's end 60890898 (3055777 ns; middle 59363009 in the last
    step's `fit.score_fetch` 59182228..59603078); 46371742 -> 47498374
    (1126632 ns; middle 46935058 in the first step's `fit.dispatch`
    45302828..47642308). In no program span: 702 + 1 ns between the
    key-split's operations of the second round's first step, which the
    device's clock puts at 54280167, 0.4 ms before the host's clock
    opened that round (`fit.round` 54670338): 703 of 16016114 ns idle."""
    got = trace_idle_unattributed.read(_obs(scoped_dir),
                                       {"prefixes": ["fit.", "etl."]})
    assert got == pytest.approx(100.0 * 703 / 16016114)
    out = capsys.readouterr().out
    assert ("idle gaps by program span: fit.sync 5.489 ms; fit.score_fetch "
            "3.056 ms; fit.dispatch 1.127 ms") in out
