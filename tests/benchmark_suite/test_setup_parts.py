"""`benchmark/readers/setup_parts.py`: set-up parted on a hand-made buffer,
and through the real entry point at toy size on the CPU under a copy of the
toy root that lists the nine metrics."""

import json
import os
import shutil
import types

import jax
import pytest

from benchmark import peaks, run, spec
from benchmark.readers import setup_parts
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.utils import dtypes

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
NINE = {"setup_before_program_s": "before_program",
        "setup_unattributed_s": "unattributed",
        "setup_net_init_s": "net_init", "setup_steps_s": "steps",
        "setup_trace_s": "trace", "setup_lower_s": "lower",
        "setup_backend_s": "backend", "setup_cache_load_s": "cache_load",
        "time_to_first_step_s": "first_step"}
EPOCH, T0, WINDOW_T0, TID = 1000.0, 1002.0, 1042.0, 7


def _ev(name, start, end, tid=TID, fun=None, ph="X"):
    """A Chrome event from `perf_counter` seconds."""
    ev = {"name": name, "ph": ph, "ts": (start - EPOCH) * 1e6,
          "dur": (end - start) * 1e6, "pid": 1, "tid": tid}
    if fun:
        ev["args"] = {"fun": fun}
    return ev


# set-up from 1002 to 1042; the program entered at 1012
BUFFER = [
    _ev("fit.round", 990.0, 995.0),                 # before the interval
    _ev("net.init", 1013.0, 1016.0),
    _ev("compile.trace", 1013.5, 1014.0, fun="_normal"),
    _ev("compile.backend", 1014.0, 1015.0, fun="jit(_normal)"),
    _ev("compile.cache_load", 1014.25, 1014.75),
    _ev("fit.round", 1020.0, 1032.0),
    _ev("fit.dispatch", 1020.5, 1030.5),
    _ev("compile.trace", 1021.0, 1025.0, fun="train_step"),
    _ev("compile.trace", 1022.0, 1022.5, fun="_run_fwd"),
    _ev("compile.trace", 1022.5, 1023.5, fun="_run_fwd"),
    _ev("compile.lower", 1025.0, 1027.0, fun="jit(train_step)"),
    _ev("compile.backend", 1027.0, 1030.0, fun="jit(train_step)"),
    _ev("compile.cache_load", 1028.0, 1029.5),
    _ev("fit.sync", 1031.0, 1031.75),
    _ev("serving.batch", 1033.0, 1033.5),           # no part's span
    _ev("fit.round", 1036.0, 1039.0),
    _ev("fit.round", 1041.0, 1043.0),               # straddles window_t0
    _ev("fit.etl", 1020.0, 1021.0, tid=8),          # another thread
    _ev("marker", 1030.0, 1030.0, ph="i"),
]
MARKS = {"process_start": 1001.0, "program_entered": 1012.0,
         "first_step": 1030.5}


def test_the_parts_of_a_hand_made_buffer_sum_to_setup_s():
    parts, detail = setup_parts.split(BUFFER, EPOCH, T0, WINDOW_T0, MARKS,
                                      TID)
    assert parts == pytest.approx({
        "before_program": 10.0,
        "net_init": 3.0 - 0.5 - 1.0,
        "trace": 0.5 + (4.0 - 1.5) + 1.5,
        "lower": 2.0,
        "backend": (1.0 - 0.5) + (3.0 - 1.5),
        "cache_load": 0.5 + 1.5,
        # rounds 12 - 10 - 0.75 and 3, the dispatch 10 - 4 - 2 - 3, the sync
        "steps": 1.25 + 3.0 + 1.0 + 0.75,
        # gaps 1 + 4 + 1 + 2.5 + 3 and the serving span's own 0.5
        "unattributed": 11.5 + 0.5,
        "first_step": 28.5})
    assert sum(parts[p] for p in setup_parts.SUMMED) == pytest.approx(
        WINDOW_T0 - T0)
    assert [(c["span"], c["fun"], c["s"], c["self_s"])
            for c in detail["longest_compiles"]] == [
        ("compile.trace", "train_step", pytest.approx(4.0),
         pytest.approx(2.5)),
        ("compile.backend", "jit(train_step)", pytest.approx(3.0),
         pytest.approx(1.5)),
        ("compile.lower", "jit(train_step)", pytest.approx(2.0),
         pytest.approx(2.0))]
    by_fun = {(c["span"], c["fun"]): (c["n"], c["self_s"])
              for c in detail["compiles_by_fun"]}
    assert [c["longest_self_s"] for c in detail["compiles_by_fun"]
            if c["fun"] == "_run_fwd"] == [pytest.approx(1.0)]
    assert len(by_fun) == 7 and next(iter(by_fun)) == (
        "compile.trace", "train_step")  # most self time first
    assert by_fun["compile.trace", "train_step"] == (1, pytest.approx(2.5))
    assert by_fun["compile.cache_load", None] == (2, pytest.approx(2.0))
    assert by_fun["compile.trace", "_run_fwd"] == (
        2, pytest.approx(1.5))  # traced once a layer
    assert [(g["after"], g["before"], g["s"])
            for g in detail["longest_gaps"]] == [
        ("net.init", "fit.round", pytest.approx(4.0)),
        ("fit.round", "window", pytest.approx(3.0)),
        ("serving.batch", "fit.round", pytest.approx(2.5))]
    assert detail["other_threads"] == {"fit.etl": pytest.approx(1.0)}
    assert detail["other_spans_self"] == pytest.approx(0.5)
    assert detail["compile_within"] == {
        "net.init": pytest.approx(
            {"trace": 0.5, "backend": 0.5, "cache_load": 0.5}),
        "fit": pytest.approx(
            {"trace": 4.0, "lower": 2.0, "backend": 1.5, "cache_load": 1.5})}


def test_a_compile_in_no_span_of_the_program_is_the_harnesss():
    buffer = [_ev("compile.lower", 1013.0, 1015.0, fun="jit(make)"),
              _ev("compile.backend", 1015.0, 1016.0, fun="jit(make)"),
              _ev("compile.cache_load", 1015.5, 1016.0)]
    parts, detail = setup_parts.split(buffer, EPOCH, T0, WINDOW_T0, MARKS,
                                      TID)
    assert detail["compile_within"] == {"outside": pytest.approx(
        {"lower": 2.0, "backend": 0.5, "cache_load": 0.5})}
    assert (parts["lower"], parts["backend"], parts["cache_load"]) == (
        pytest.approx(2.0), pytest.approx(0.5), pytest.approx(0.5))


@pytest.mark.parametrize("marks, before, first", [
    # the package imported before the run began, as the tests do; the
    # process's first step long before it
    ({"process_start": 900.0, "program_entered": 950.0,
      "first_step": 960.0}, 0.0, None),
    # no step yet
    ({"process_start": 1001.0, "program_entered": 1012.0}, 10.0, None),
])
def test_marks_outside_the_interval(marks, before, first):
    parts, detail = setup_parts.split(BUFFER, EPOCH, T0, WINDOW_T0, marks,
                                      TID)
    assert parts["before_program"] == before
    assert parts["first_step"] is first
    assert sum(parts[p] for p in setup_parts.SUMMED) == pytest.approx(
        WINDOW_T0 - T0)
    if not before:
        assert detail["longest_gaps"][0] == {
            "after": "start", "before": "net.init", "s": pytest.approx(11.0)}


def test_a_span_that_starts_where_its_parent_starts_opens_no_gap():
    # jax's lengths laid back from one clock read can give equal starts
    buffer = [_ev("fit.dispatch", 1020.0, 1030.0),
              _ev("compile.trace", 1020.0, 1024.0, fun="train_step"),
              _ev("compile.lower", 1024.0, 1026.0, fun="jit(train_step)")]
    parts, detail = setup_parts.split(buffer, EPOCH, T0, WINDOW_T0, MARKS,
                                      TID)
    assert (parts["trace"], parts["lower"], parts["steps"]) == (
        pytest.approx(4.0), pytest.approx(2.0), pytest.approx(4.0))
    assert parts["unattributed"] == pytest.approx(8.0 + 12.0)
    assert sum(parts[p] for p in setup_parts.SUMMED) == pytest.approx(
        WINDOW_T0 - T0)
    assert [(g["after"], g["before"]) for g in detail["longest_gaps"]] == [
        ("fit.dispatch", "window"),
        ("program_entered", "compile.trace[train_step]")]


def _obs(setup_s=WINDOW_T0 - T0):
    return {"ctx": types.SimpleNamespace(setup_s=setup_s,
                                         window_t0=WINDOW_T0)}


def test_a_program_without_the_marks_gives_nothing(monkeypatch, capsys):
    from deeplearning4j_tpu.utils import compile_cache
    monkeypatch.delattr(compile_cache, "startup_marks")
    for part in NINE.values():
        assert setup_parts.read(_obs(), {"part": part}) is None
    assert setup_parts.read(_obs(setup_s=None), {"part": "trace"}) is None
    assert "setup_parts" not in capsys.readouterr().out


def test_every_metric_file_names_the_reader_and_its_part():
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"]
            if spec.layer_metric(m["name"])["reader"] == "setup_parts"]
    assert [m["name"] for m in mine] == list(NINE)
    # together and in order, wherever a later PR's entries put them
    at = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][at:at + len(NINE)] == mine
    for m in mine:
        lm = spec.layer_metric(m["name"])
        assert lm["args"] == {"part": NINE[m["name"]]}
        assert (m["unit"], m["better"], m["moves"], m["source"]) == (
            "s", "lower", "setup_s", "program_counter")
        assert "workloads" not in m and m["layer"] == lm["layer"]


def test_a_traced_run_on_the_cpu_reports_all_nine(monkeypatch, tmp_path,
                                                  capsys):
    root = str(tmp_path / "toy")
    shutil.copytree(TOY, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as fh:
        bench = json.load(fh)
    real = spec.load_benchmark()["per_layer"]
    bench["per_layer"] += [m for m in real if m["name"] in NINE]
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    telemetry.reset()  # the process's first step is this run's
    try:
        rc = run.main(["--workload", "toy-gpt2-train", "--seed",
                       str(2 ** 31 + 36), "--seconds", "0.5", "--trace",
                       "1"], root=root, out_dir=str(tmp_path / "out"))
    finally:
        dtypes.f32_policy()
        telemetry.reset()
        telemetry.disable()
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    m = json.loads(out[-1])["metrics"]
    assert set(NINE) <= set(m)
    assert all(m[n]["unit"] == "s" for n in NINE)
    (line,) = [ln for ln in out if ln.startswith("setup_parts ")]
    printed = json.loads(line.split(" ", 1)[1])
    eight = [n for n in NINE if n != "time_to_first_step_s"]
    assert sum(m[n]["value"] for n in eight) == pytest.approx(
        printed["setup_s"], abs=1e-3)
    assert all(m[n]["value"] == printed[NINE[n]] for n in NINE)
    # the test process imported the package about when it imported the
    # harness, long before this run: next to nothing before the program
    assert 0 <= m["setup_before_program_s"]["value"] < 1
    for n in ("setup_net_init_s", "setup_steps_s", "setup_trace_s",
              "setup_lower_s", "setup_backend_s"):
        assert m[n]["value"] > 0, n
    assert 0 < m["time_to_first_step_s"]["value"] < printed["setup_s"]
    assert printed["longest_compiles"][0]["fun"]
    assert len(printed["longest_gaps"]) == 3
    assert m["compile_cache_hit_share"]["unit"] == "%"  # stays as it was
