"""The output check gives the chip back before the reference runs (ISSUE
42): in every toy train cell, through the real entry point on the CPU, the
network is dead and `jax.live_arrays()` holds the `plain` batches and
little else when `check_train.follow_reference` is entered, and the run
says so on a `reference_start` line; `diagnose budget` sizes a
configuration from shapes alone; and the tests that pin `BENCHMARK.json`'s
entries pin them by name, so a PR that appends a metric or a cell need
edit none of them."""

import copy
import os
import weakref

import jax
import pytest

from benchmark import check_train, diagnose, peaks, program, run, spec
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_CELLS = [("toy", "toy-gpt2-train"), ("toy", "toy-resnet50-train"),
             ("toy_ouro", "toy-ouro-train"), ("toy_lfm2", "toy-lfm2-train"),
             ("toy_qwen3next", "toy-qwen3next-train")]


@pytest.fixture
def on_the_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    yield str(tmp_path)
    dtypes.f32_policy()


def _bytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("root,cell", TOY_CELLS)
def test_the_reference_starts_with_the_program_gone(root, cell, on_the_cpu,
                                                    monkeypatch, capsys):
    nets, seen = [], {}
    build, follow = program.build, check_train.follow_reference
    # what earlier tests of this process left alive is not this run's
    before = jax.live_arrays()
    earlier = {id(a) for a in before}

    def watched_build(config, seed):
        net = build(config, seed)
        nets.append(weakref.ref(net))
        seen["program_bytes"] = _bytes((net.params, net.opt_state))
        seen["leaves"] = len(jax.tree_util.tree_leaves(
            (net.params, net.state)))
        return net

    def watched_follow(ref, config, seed, plain, *args, **kwargs):
        seen["net_alive"] = nets[0]() is not None
        live = jax.live_arrays()
        seen["live_all"] = sum(a.nbytes for a in live)
        seen["live"] = sum(a.nbytes for a in live if id(a) not in earlier)
        seen["plain"] = _bytes(plain)
        return follow(ref, config, seed, plain, *args, **kwargs)

    monkeypatch.setattr(program, "build", watched_build)
    monkeypatch.setattr(check_train, "follow_reference", watched_follow)
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 42),
                   "--seconds", "0.3", "--trace", "0"],
                  root=os.path.join(HERE, root), out_dir=on_the_cpu)
    assert rc == 0
    assert len(nets) == 1 and seen["net_alive"] is False
    # on the CPU numpy shares a fetched vector's buffer with jax, so the
    # readings' own vectors of norms (at most four, 4 B a leaf) are alive
    # beside the batches; the program's weights and moments are not
    slack = 16 * seen["leaves"]
    assert seen["plain"] <= seen["live"] <= seen["plain"] + slack
    assert slack < seen["program_bytes"] / 8
    out = capsys.readouterr().out.splitlines()
    heads = [ln.split(" ", 1)[0] for ln in out]
    at = heads.index("reference_start")
    assert heads.count("reference_start") == 1
    assert heads.index("rate_window") < at < heads.index("reference_s")
    words = out[at].split()
    assert words[1::2] == ["bytes_in_use", "bytes_reserved", "bytes_limit",
                           "live_arrays_bytes"]
    # the CPU's devices give no memory_stats: the three counters print 0
    assert [int(w) for w in words[2::2]] == [0, 0, 0, seen["live_all"]]


@pytest.mark.parametrize("cell,count", [
    ("lfm2-train-t8192", 486_062_208), ("qwen3next-train-t4096", 424_340_544),
    ("gpt2m-train-t1024", 406_334_545), ("ouro-train-t2048", 406_884_353),
    ("resnet50-train-b128", 25_557_032)])
def test_budget_sizes_a_cell_from_shapes_alone(cell, count, capsys):
    assert diagnose.main(["budget", "--workload", cell]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith(f"parameters {count:,} ({count / 1e6:.1f} M)")
    assert out[1].startswith(f"program 12 B a parameter: {12 * count:,} ")
    assert out[2].startswith(f"reference 16 B a parameter: {16 * count:,} ")
    assert len(out) == 3  # no chip here, so no bytes_limit beside them


def _appended(bench):
    """`BENCHMARK.json` as a later PR might leave it: one metric of its own
    after the nine `setup_*` entries, one cell of its own on a list."""
    bench = copy.deepcopy(bench)
    bench["per_layer"].append(
        {"name": "made_up_ms.tokens", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "model step",
         "moves": "train_tokens_per_s", "workloads": ["made-up-train"]})
    moe_ms, = [m for m in bench["per_layer"] if m["name"] == "moe_ms.tokens"]
    moe_ms["workloads"].append("made-up-train")
    return bench


@pytest.mark.parametrize("appended", [False, True])
def test_the_pins_hold_by_name(appended, monkeypatch):
    import test_lfm2_moe
    import test_scope_readers
    import test_setup_parts
    if appended:
        load, metric = spec.load_benchmark, spec.layer_metric
        monkeypatch.setattr(spec, "load_benchmark",
                            lambda root=spec.REPO_ROOT: _appended(load(root)))
        monkeypatch.setattr(
            spec, "layer_metric",
            lambda name: metric("step_loss_ms.tokens" if name
                                == "made_up_ms.tokens" else name))
        assert spec.load_benchmark()["per_layer"][-1]["name"] == (
            "made_up_ms.tokens")
    test_setup_parts.test_every_metric_file_names_the_reader_and_its_part()
    test_lfm2_moe.test_the_configuration_keeps_every_published_width()
    (test_scope_readers
     .test_every_new_metric_file_names_a_reader_and_its_arguments())


def test_the_second_mixture_cell_is_on_the_six_lists():
    bench = spec.load_benchmark()
    for name in ("moe_ms", "moe_route_ms", "moe_experts_ms",
                 "moe_experts_roofline", "moe_rows_here_share",
                 "moe_load_max_over_mean"):
        m, = [m for m in bench["per_layer"] if m["name"] == f"{name}.tokens"]
        assert {"lfm2-train-t8192",
                "qwen3next-train-t4096"} <= set(m["workloads"])
    # what `scope_roofline` looks up in the cell's own `model`
    _, _, _, config = spec.load_cell("qwen3next-train-t4096")
    model = config["model"]
    assert (model["num_experts"], model["experts_held"],
            model["moe_intermediate_size"]) == (512, [0, 16], 512)
    entry, = [c for c in bench["configs"] if c["name"] == "qwen3-next-80b-a3b"]
    assert "16 of 512 experts" in entry["why"] and len(entry["why"]) <= 200
