"""The LFM2-MoE configuration's benchmark files (ISSUE 32) at toy size on
the CPU, through a tree of their own (`toy_lfm2/`): the plain reference
against the system (loss, every gradient leaf, the routing's counts,
`output()`), the fp8 control caught, a run through the real entry point, a
router whose gradient is cut coming out not correct, the two new readers,
and the data files' arithmetic."""

import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_train, control, peaks, program, run, spec, trace
from benchmark.readers import registry_ratio, scope_roofline, trace_scope_ms
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_lfm2")
CELL = "toy-lfm2-train"
REAL_CELL = "lfm2-train-t8192"


@pytest.fixture
def on_the_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    yield str(tmp_path)
    dtypes.f32_policy()


def _run(capsys, out_dir, seed, trace=0, seconds=0.5):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=TOY,
                  out_dir=out_dir)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def seeded():
    """The system under the float32 policy with the reference's seeded
    weights and expert bias laid over it, one batch, and the reference's
    loss, gradients and counts on it."""
    _, _, workload, config = spec.load_cell(CELL, TOY)
    ref = spec.module("reference", config["reference"])
    model = config["model"]
    try:
        net = program.build(config, 11)
        weights, state = ref.init(11, model), ref.init_state(model)
        program.load_weights(net, *ref.program_layout(weights, state))
        traffic = spec.module("traffic", workload["traffic"]["kind"]).make(
            11, workload["traffic"], model)
        x, y = traffic["feed"][0]
        want = ref.loss_and_grad(weights, state, x, y, model)
        fx, fy, _ = program.feed_item(net, x, y)
        got = jax.jit(lambda p, s: net.compute_gradients(
            p, s, fx, fy, rng=jax.random.PRNGKey(0)))(net.params, net.state)
        out = np.asarray(net.output(x))
        yield ref, model, weights, state, x, want, got, out
    finally:
        dtypes.f32_policy()


def test_the_systems_loss_is_the_references(seeded):
    *_, (want_loss, _, _), (loss, _, _), _ = seeded
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, *_, (_, want_grads, _), (_, _, grads), _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    want = np.asarray(program.leaf_norms(ref.program_layout(want_grads)[0]))
    assert got.shape == want.shape == (40,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    assert check_train.worst_leaf_gap(got, want) < 1e-4


def test_the_systems_routing_counts_are_the_references(seeded):
    ref, model, *_, (_, _, want_state), (_, state, _), _ = seeded
    tokens_k = 2 * 64 * model["num_experts_per_tok"]
    seen = 0
    assert len(state) == len(want_state) + 3   # embedding; norm and head
    for got_s, want_s in zip(state[1:-2], [s or {} for s in want_state]):
        assert set(got_s) == set(want_s)
        for name in want_s:
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]))
        if got_s:
            seen += 1
            assert float(got_s["moe_load"].sum()
                         + got_s["moe_elsewhere"][0]) == tokens_k
            assert float(jnp.abs(got_s["expert_bias"]).max()) > 0
    assert seen == 3


def test_output_is_the_references_softmax(seeded):
    ref, model, weights, state, x, _, _, out = seeded
    biases = [None if s is None else s["expert_bias"] for s in state]
    frozen = ref._static(model)
    logits = jax.jit(lambda w, tok: ref.logits_one(
        w, biases, tok, dict(frozen))[0])
    want = np.stack([np.asarray(jax.nn.softmax(logits(weights, x[i]), -1))
                     for i in range(x.shape[0])])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-7)


def test_the_control_is_caught(capsys, tmp_path):
    rc = control.main(["--workload", CELL, "--seeds", "2"],
                      root=TOY, out_dir=str(tmp_path))
    out = capsys.readouterr().out
    assert rc == 0 and "control caught" in out
    dtypes.f32_policy()


def test_a_run_end_to_end(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 2 ** 31 + 12345)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    with open(os.path.join(on_the_cpu, f"check-{CELL}.json")) as fh:
        detail = json.load(fh)
    assert len(detail["state_names"]) == 9   # bias, load, elsewhere x 3
    assert "state_first_norms" in detail


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # the program's counters are read on the CPU too: 4 of 8 experts held,
    # the seeded bias tilting the load
    assert 0 < m["moe_rows_here_share.tokens"]["value"] < 100
    assert m["moe_load_max_over_mean.tokens"]["value"] >= 1.0
    # no device plane in a CPU trace: the trace readers find nothing
    assert not {"moe_ms.tokens", "moe_experts_roofline.tokens",
                "short_conv_ms.tokens"} & set(m)


def test_a_router_whose_gradient_is_cut_is_not_correct(
        on_the_cpu, capsys, monkeypatch):
    """A program that weights the experts' results by constants: the
    forward is untouched, so the first loss is right, and the router's
    gradient norm gives it away."""
    real = moe._combine

    def faulty(ys, w, order, inv, valid):
        return real(ys, jax.lax.stop_gradient(w), order, inv, valid)

    monkeypatch.setattr(moe, "_combine", faulty)
    line = _run(capsys, on_the_cpu, 3)
    assert line["correct"] is False


def test_registry_ratio_reads_counters_in_the_window_and_gauges_at_its_end():
    ctx = types.SimpleNamespace(
        counters_open={"here": 100.0, "all": 1000.0, "hot": 9.0, "mean": 3.0},
        counters_close={"here": 350.0, "all": 3000.0, "hot": 12.0,
                        "mean": 8.0})
    obs = {"ctx": ctx}
    share = {"numerator": "here", "denominator": "all", "scale": 100.0}
    assert registry_ratio.read(obs, share) == pytest.approx(12.5)
    assert registry_ratio.read(obs, {"numerator": "hot", "at": "close",
                                     "denominator": "mean"}) == 1.5
    # a program without the counters (the parent commit) gives nothing
    assert registry_ratio.read(obs, {"numerator": "absent",
                                     "denominator": "all"}) is None
    ctx.counters_close["all"] = 1000.0
    assert registry_ratio.read(obs, share) is None      # nothing routed
    ctx.counters_open = ctx.counters_close = None       # an untraced run
    assert registry_ratio.read(obs, share) is None


def test_scope_roofline_is_least_time_over_the_scopes_self_time(
        tmp_path, capsys):
    """On `recorded/scoped.xplane.pb` (TPU v5 lite, PR 25): the scope
    `loss` read 0.72306650 us a step there; the function's least time at
    made-up shapes, by hand, over it."""
    where = tmp_path / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "recorded", "scoped.xplane.pb"),
                where / "host.xplane.pb")
    ctx = types.SimpleNamespace(
        trace_dir=str(tmp_path),
        config={"model": {"n_embd": 4, "moe_intermediate_size": 8,
                          "num_experts": 8, "num_experts_per_tok": 2,
                          "experts_held": [2, 6], "num_dense_layers": 1,
                          "layer_types": ["conv", "conv", "conv"]}},
        workload={"traffic": {"batch": 2, "seq_len": 16}},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        counters_open={"r": 0.0, "a": 0.0},
        counters_close={"r": 40.0, "a": 128.0})
    args = {"scope": "loss", "function": "moe_experts",
            "shapes": {"tokens": ["batch", "seq_len"],
                       "per_tok": "num_experts_per_tok",
                       "held": "experts_held", "experts": "num_experts",
                       "layers": "expert_layers", "d": "n_embd",
                       "f": "moe_intermediate_size", "dtype_bytes": 2},
            "rows_counters": ["r", "a"]}
    obs = {"ctx": ctx, "trace": trace.load(str(tmp_path))}
    ms = trace_scope_ms.read(obs, {"scope": "loss"})
    rows = 32 * 2 * 4 / 8                       # 32 expected rows
    flops = 2 * 9 * 2 * rows * 4 * 8            # 2 layers
    nbytes = 2 * (2 * 384 * 2 + 4 * 384 + 9 * rows * 12 * 2)
    least_ms = max(flops / 197e12, nbytes / 819e9) * 1e3
    assert scope_roofline.read(obs, args) == pytest.approx(
        100.0 * least_ms / ms, rel=1e-9)
    out = capsys.readouterr().out
    assert "bound by memory" in out and "40 of 128 routed (31.250%)" in out
    assert scope_roofline.read(obs, {**args, "scope": "moe_experts"}) is None
    assert scope_roofline.read({"ctx": ctx, "trace": None}, args) is None


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    model, traffic = config["model"], workload["traffic"]
    flops = spec.module("kernels", config["flops"]).train_flops_per_unit(
        model, traffic)
    conv, attn = 4 * 2 * 4 * 2048 * 2048, 2 * (2 * 2048 * 2048
                                                + 2 * 2048 * 512)
    scores, dense = 2 * 2 * 2048 * 4096, 6 * 2048 * 11776
    held = 4 * 0.5 * 6 * 2048 * 1536
    routers, head = 4 * 2 * 2048 * 64, 2 * 2048 * 8192
    assert flops == 3 * (conv + attn + scores + dense + held + routers + head)
    assert flops == pytest.approx(1.2174e9, rel=1e-4)
    assert 3 * held / flops == pytest.approx(0.093, abs=1e-3)
    # the grouped products' least time a step: compute-bound, 4.7 ms
    fl, nb = spec.module("kernels", "moe_experts").flops_and_bytes(
        8192, 4, 8, 64, 4, 2048, 1536, 2)
    assert fl == pytest.approx(8192 * 3 * held)
    assert fl / 197e12 == pytest.approx(4.709e-3, rel=1e-3)
    assert fl / 197e12 > nb / 819e9


PUBLISHED = {"hidden_size": 2048, "intermediate_size": 11776,
             "moe_intermediate_size": 1536, "num_attention_heads": 32,
             "num_key_value_heads": 8, "num_experts_per_tok": 4,
             "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
             "norm_topk_prob": True, "use_expert_bias": True,
             "routed_scaling_factor": 1, "max_position_embeddings": 128000,
             "model_type": "lfm2_moe",
             "rope_parameters": {"rope_theta": 1000000,
                                 "rope_type": "default"}}


def test_the_configuration_keeps_every_published_width():
    bench, cell, workload, config = spec.load_cell(REAL_CELL)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_dense_layers": 2, "num_experts": 64,
                                   "vocab_size": 65536}
    assert len(config["layer_types"]) == 40        # the source's, whole
    assert config["layer_types"].count("full_attention") == 10
    m, args = config["model"], config["program"]["args"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
        m["n_layer"], m["num_dense_layers"],
        m["experts_held"][1] - m["experts_held"][0], m["vocab_size"]) == (
        5, 1, 8, 8192)
    # floors: a whole period and four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert m["layer_types"] == [config["layer_types"][i]
                                for i in (0, 2, 3, 4, 5)]
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (m["n_embd"], m["n_head"], m["n_kv_head"], m["head_dim"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["num_experts"], m["num_experts_per_tok"], m["experts_held"],
            m["layer_types"], m["num_dense_layers"], m["conv_L_cache"],
            m["vocab_size"]) == (
        args["d_model"], args["n_heads"], args["n_kv_heads"],
        args["head_dim"], args["ffn_width"], args["expert_width"],
        args["n_experts"], args["top_k"], args["experts_held"],
        args["layer_types"], args["num_dense_layers"], args["conv_kernel"],
        args["vocab_size"])
    assert (m["n_embd"], m["n_head"] * m["head_dim"], m["num_experts"]) == (
        2048, 2048, 64)
    assert (m["rope_theta"], m["norm_eps"], m["routed_scaling_factor"]) == (
        args["rope_theta"], args["norm_eps"], args["routed_scale"])
    assert set(config["assumed"]) >= {
        "conv_parts_order", "qk_norm_before_rope", "router", "final_norm",
        "expert_bias", "expert_bias_std", "initializer", "optimizer",
        "precision"}
    assert len(config["departures"]) == 2
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_tokens", "pool": 2, "batch": 1, "seq_len": 8192}
    assert set(workload["limits"]) == {"loss_gap", "grad_norm_gap",
                                       "grad_norm_median_gap",
                                       "update_norm_gap",
                                       "state_first_norm_gap"}
    # the factory's own default, as every sibling configuration's
    assert "learning_rate" not in args
    assert config["optimizer"]["learning_rate"] == 3e-4
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"]
              if "workloads" in m}
    for name in ("short_conv_ms", "moe_ms", "moe_route_ms",
                 "moe_experts_ms", "moe_experts_roofline",
                 "moe_rows_here_share", "moe_load_max_over_mean"):
        assert REAL_CELL in listed[f"{name}.tokens"], name
    roofline, = [m for m in bench["per_layer"]
                 if m["name"] == "flash_attn_fwd_roofline"]
    assert REAL_CELL not in roofline["workloads"]


def test_the_parameters_held_here_are_the_issues_count():
    """486.1 M parameters at the cell's sizes, from shapes alone."""
    _, _, _, config = spec.load_cell(REAL_CELL)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(1, config["model"]))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(486.1e6, rel=1e-3)
    state = jax.eval_shape(lambda: ref.init_state(config["model"]))
    assert [s is None for s in state] == [True, False, False, False, False]


def test_the_reference_imports_nothing_from_the_program():
    with open(spec.module("reference", "lfm2_moe").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text
    assert 'precision="highest"' in text
