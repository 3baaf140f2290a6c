"""The arithmetic of a reading, and the data files: every file the
benchmark names exists, loads and keeps to the contract's name rules."""

import glob
import importlib
import json
import os
import re

import pytest

from benchmark import peaks, spec, stats

ROOT = spec.REPO_ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_one_slow_round_moves_the_window_rate_and_not_the_median_rate():
    level = [1.0] * 19
    slow = level + [3.0]
    # the end-to-end rate: all the work over all the wall
    assert stats.window_rate(4096, 20, sum(level + [1.0])) == 4096
    assert stats.window_rate(4096, 20, sum(slow)) == \
        pytest.approx(4096 * 20 / 22)
    # the per-layer statistics beside it
    assert stats.median_rate(4096, level + [1.0]) == 4096
    assert stats.median_rate(4096, slow) == 4096
    assert stats.stall_share(level + [1.0]) == pytest.approx(0.0)
    assert stats.stall_share(slow) == pytest.approx(2.0 / 22.0)
    # wall outside the rounds (the loop's own bookkeeping) counts too
    assert stats.stall_share(level + [1.0], 25.0) == pytest.approx(0.2)


def test_quartile_spread_is_the_drivers():
    assert stats.quartile_spread([100, 100, 100, 100, 100, 100]) == 0
    assert stats.quartile_spread([98, 99, 100, 100, 101, 102]) == \
        pytest.approx((101.25 - 98.75) / 100)


def test_unknown_device_kind_is_an_error():
    assert peaks.for_kind("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_kind("TPU v99")


def _all_json():
    return sorted(glob.glob(os.path.join(spec.PACKAGE_DIR, "*", "*.json")))


@pytest.mark.parametrize("path", _all_json(),
                         ids=lambda p: os.path.relpath(p, ROOT))
def test_every_data_file_loads_and_is_named_by_the_rules(path):
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    stem = os.path.basename(path)[:-len(".json")]
    assert NAME.match(stem)
    assert doc["name"] == stem


def test_benchmark_json_names_files_that_exist():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in e2e.values())
    cells = {c["name"] for c in bench["workloads"]}
    for cell in bench["workloads"]:
        _, _, workload, config = spec.load_cell(cell["name"])
        assert len(cell["why"]) <= 200 and NAME.match(cell["traffic"])
        for kind, name in (("runners", workload["runner"]),
                           ("traffic", workload["traffic"]["kind"]),
                           ("reference", config["reference"]),
                           ("kernels", config["flops"])):
            importlib.import_module(f"benchmark.{kind}.{name}")
        reported = {m["name"] for m in
                    spec.cell_metrics(bench, cell["name"], "end_to_end")}
        assert reported == {"setup_s", *workload["end_to_end"]}
        assert spec.cell_metrics(bench, cell["name"], "per_layer")
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    for m in bench["per_layer"]:
        lm = spec.layer_metric(m["name"])
        importlib.import_module(f"benchmark.readers.{lm['reader']}")
        assert (lm["layer"], lm["unit"], lm["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)
    used = {c["config"] for c in bench["workloads"]}
    for conf in bench["configs"]:
        assert conf["name"] in used
        doc = json.load(open(os.path.join(ROOT, conf["file"])))
        assert doc["source"] == conf["source"]
        assert doc["reduced"] == conf["reduced"]


def test_the_harness_names_no_cell_and_no_configuration():
    bench = spec.load_benchmark()
    names = [c["name"] for c in bench["workloads"] + bench["configs"]]
    for path in glob.glob(os.path.join(spec.PACKAGE_DIR, "**", "*.py"),
                          recursive=True):
        text = open(path, encoding="utf-8").read()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (path, n)
