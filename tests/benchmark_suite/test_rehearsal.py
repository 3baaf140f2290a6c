"""Each runner through the real entry point at toy size on the CPU, with
only the look for a chip patched, and a run with the timed path broken
underneath coming out not correct."""

import json
import os

import jax
import pytest

from benchmark import peaks, run, spec
from deeplearning4j_tpu.continuous import driver as _driver
from deeplearning4j_tpu.utils import dtypes

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELLS = ["toy-gpt2-train", "toy-resnet50-train"]


@pytest.fixture
def on_the_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    yield str(tmp_path)
    dtypes.f32_policy()


def _run(capsys, out_dir, cell, seed, trace=0, seconds=0.5):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=TOY,
                  out_dir=out_dir)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_run_end_to_end(cell, on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, cell, 2 ** 31 + 12345)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    bench = spec.load_benchmark(TOY)
    want = {m["name"] for m in spec.cell_metrics(bench, cell, "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes",
                                   "memory_counter_peak_bytes",
                                   "memory_live_bytes",
                                   "memory_reserved_bytes"}
    assert os.path.isfile(os.path.join(on_the_cpu, f"check-{cell}.json"))


def test_a_traced_run_reports_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, CELLS[0], 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["compile_cache_hit_share"]["unit"] == "%"
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert 0 <= m["fit_etl_share.tokens"]["value"] < 100
    assert m["mfu.tokens"]["value"] > 0
    assert m["fit_round_median_rate.tokens"]["unit"] == "tokens/s"
    assert m["fit_round_median_rate.tokens"]["value"] > 0
    # the median round can lie above the mean one: the share can be < 0
    assert -100 < m["fit_stall_share.tokens"]["value"] < 100
    # no device plane in a CPU trace: the trace readers find nothing
    assert "device_idle_share.tokens" not in m


def test_off_the_chip_the_entry_point_refuses(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                 root=TOY)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_broken_timed_path_is_not_correct(fault, on_the_cpu, capsys,
                                            monkeypatch):
    real = _driver._PlainEngine.dispatch

    def broken(self, prep):
        net = self.net
        if fault == "half_the_batch":
            x, y, m = prep
            half = x.shape[0] // 2 + 1
            return real(self, (x[:half], y[:half], m))
        before = net.params, net.opt_state
        copy = jax.tree_util.tree_map(lambda a: a + 0, before)
        out = real(self, prep)
        net.params, net.opt_state = copy  # the step's update thrown away
        return out

    monkeypatch.setattr(_driver._PlainEngine, "dispatch", broken)
    line = _run(capsys, on_the_cpu, CELLS[0], 3)
    assert line["correct"] is False
