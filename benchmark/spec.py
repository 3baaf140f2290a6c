"""Finds everything by name. `BENCHMARK.json` names cells and
configurations; a cell is `<root>/<paths[0]>/workloads/<name>.json`, a
configuration is the `file` its entry gives, a per-layer metric is
`benchmark/layer_metrics/<name>.json`; runners, readers, traffic kinds,
references and FLOPs functions are modules found by the name a data file
gives. Nothing here names a cell or a configuration."""

from __future__ import annotations

import importlib
import json
import os

PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(PACKAGE_DIR)


def _load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_benchmark(root=REPO_ROOT):
    return _load(os.path.join(root, "BENCHMARK.json"))


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(has: {[e['name'] for e in entries]})")


def load_cell(name, root=REPO_ROOT):
    """(benchmark, cell entry, workload file, configuration file)."""
    bench = load_benchmark(root)
    cell = _entry(bench["workloads"], name, "workload")
    conf_entry = _entry(bench["configs"], cell["config"], "config")
    workload = _load(os.path.join(root, bench["paths"][0], "workloads",
                                  f"{name}.json"))
    config = _load(os.path.join(root, conf_entry["file"]))
    for key in ("config", "chips"):
        if workload[key] != cell[key]:
            raise ValueError(f"{name}: workload file says {key}="
                             f"{workload[key]!r}, BENCHMARK.json says "
                             f"{cell[key]!r}")
    return bench, cell, workload, config


def layer_metric(name):
    return _load(os.path.join(PACKAGE_DIR, "layer_metrics", f"{name}.json"))


def module(kind, name):
    """`benchmark.<kind>.<name>`: kind is runners, readers, traffic,
    reference or kernels."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def cell_metrics(bench, cell_name, group):
    """The metrics of `group` ("end_to_end" or "per_layer") that this cell
    reports: those without a `workloads` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]
