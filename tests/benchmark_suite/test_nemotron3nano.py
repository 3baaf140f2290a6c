"""The Nemotron-3-Nano configuration's benchmark files (ISSUE 43) at toy
size on the CPU, through a tree of their own (`toy_nemotron3nano/`): the
plain reference against the system (loss, every gradient leaf, the
routing's counts, `output()`), bfloat16 where float32 is stated failing
the same comparison, the fp8 control caught, a run and a traced run
through the real entry point, the recurrence's and the ungated experts'
roofline counts by hand, `diagnose budget`, and the data files'
arithmetic."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_train, control, diagnose, peaks, program, run, spec
from benchmark.readers import scope_roofline
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_nemotron3nano")
CELL = "toy-nemotron3nano-train"
REAL_CELL = "nemotron3nano-train-packed"
NEW_METRICS = ["ssm_ms.tokens", "ssd_core_ms.tokens",
               "ssd_core_roofline.tokens",
               "moe_experts_ungated_roofline.tokens"]
PARAMETERS = 666_962_944


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
    weights laid over it, one batch, and the reference's loss, gradients
    and counts on it."""
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
        yield ref, model, weights, state, (x, y), want, got, out
    finally:
        dtypes.f32_policy()


def test_the_systems_loss_is_the_references(seeded):
    *_, (want_loss, _, _), (loss, _, _), _ = seeded
    # float32 sums in another order over 160 tokens of 128 classes
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, *_, (_, want_grads, _), (_, _, grads), _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    layout = ref.program_layout(want_grads)[0]
    want = np.asarray(program.leaf_norms(layout))
    # embedding, head, final norm; 4 x (norm + 8) Mamba-2, 4 x (norm + 5)
    # mixtures, norm + 3 attention
    assert got.shape == want.shape == (3 + 4 * 9 + 4 * 6 + 4,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    # float32 against float32: rounding of sums in another order (the
    # chunkwise form against the recurrence, sorted rows against a loop)
    assert check_train.worst_leaf_gap(got, want) < 1e-4
    # and element by element where a leaf's gradient is not rounding-small
    # (a decay's gradient, A_log's and dt_bias's, is 1e-4 of the median)
    floor = 1e-3 * float(np.median(want))
    for a, b, n in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(layout), want):
        if n > floor:
            assert float(jnp.abs(a - b).max()) < 1e-3 * float(
                jnp.abs(b).max())


def test_bfloat16_where_float32_is_stated_fails_the_same_comparison(seeded):
    """The reference itself at bfloat16 products: its leaves' norms lie ten
    times further from the float32 reference than the tolerance above
    allows, so a program that computed in bfloat16 under the float32 policy
    would fail by that limit (the loss, a mean over 160 tokens, moves by
    2e-5 of itself and would pass its own: it is the leaves that tell)."""
    ref, model, weights, state, (x, y), (want_loss, want_grads, _), *_ = seeded
    loss, grads, _ = ref.loss_and_grad(weights, state, x, y, model, "bf16")
    got = np.asarray(program.leaf_norms(ref.program_layout(grads)[0]))
    want = np.asarray(program.leaf_norms(ref.program_layout(want_grads)[0]))
    assert check_train.worst_leaf_gap(got, want) > 1e-3
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-3)


def test_the_systems_routing_counts_are_the_references(seeded):
    ref, model, *_, (_, _, want_state), (_, state, _), _ = seeded
    tokens_k = 2 * 80 * model["num_experts_per_tok"]
    assert len(state) == len(want_state) + 3   # embedding; norm and head
    mixtures = 0
    for got_s, want_s, kind in zip(state[1:-2], want_state,
                                   model["pattern"]):
        if kind != "E":     # a mixer alone carries no state
            assert want_s is None and not got_s
            continue
        mixtures += 1
        assert set(got_s) == set(want_s) == {"expert_bias", "moe_load",
                                             "moe_elsewhere"}
        for name in want_s:
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]))
        assert float(got_s["moe_load"].sum()
                     + got_s["moe_elsewhere"][0]) == tokens_k
        assert 0 < float(got_s["moe_load"].sum()) < tokens_k
    assert mixtures == 4


def test_output_is_the_references_softmax(seeded):
    ref, model, weights, state, (x, _), _, _, out = seeded
    frozen = ref._static(model)
    biases = [None if s is None else s["expert_bias"] for s in state]
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
    assert len(detail["state_names"]) == 12   # bias, load, elsewhere x 4
    assert len(detail["leaf_names"]) == 67
    assert "state_first_norms" in detail


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # the program's counters serve the four mixtures as they are: 8 of 16
    # experts held, 3 a token
    assert 0 < m["moe_rows_here_share.tokens"]["value"] < 100
    assert m["moe_load_max_over_mean.tokens"]["value"] >= 1.0
    # no device plane in a CPU trace: the new trace readers find nothing
    # there and give nothing, as they do on a program without the scopes
    assert not set(NEW_METRICS) & set(m)
    toy = spec.load_benchmark(TOY)
    assert set(NEW_METRICS) <= {p["name"] for p in toy["per_layer"]}


def test_the_new_metric_files_are_read_by_the_readers_the_benchmark_has():
    for name, reader, scope in (
            ("ssm_ms.tokens", "scope_ms", "ssm"),
            ("ssd_core_ms.tokens", "scope_ms", "ssd_core"),
            ("ssd_core_roofline.tokens", "scope_roofline", "ssd_core"),
            ("moe_experts_ungated_roofline.tokens", "scope_roofline",
             "moe_experts")):
        lm = spec.layer_metric(name)
        assert (lm["name"], lm["reader"], lm["args"]["scope"]) == (
            name, reader, scope)
        assert lm["moves"] == "train_tokens_per_s"
        assert callable(spec.module("readers", reader).read)
        if reader == "scope_roofline":
            assert callable(spec.module(
                "kernels", lm["args"]["function"]).flops_and_bytes)
    # every size the two counts ask for is in the cell's model or traffic
    _, _, workload, config = spec.load_cell(REAL_CELL)
    ctx = types.SimpleNamespace(config=config, workload=workload)
    for name in ("ssd_core_roofline.tokens",
                 "moe_experts_ungated_roofline.tokens"):
        args = spec.layer_metric(name)["args"]
        sizes = {k: scope_roofline._shape(ctx, v)
                 for k, v in args["shapes"].items()}
        assert all(isinstance(v, (int, float)) for v in sizes.values())
        fl, nb = spec.module("kernels", args["function"]).flops_and_bytes(
            **sizes)
        assert fl > 0 and nb > 0
        assert sizes["tokens"] == 4096 and sizes["layers"] == 4


def test_the_recurrences_count_by_hand():
    """`kernels/ssd.py` at 128 tokens in one sequence, 4 heads of 8 over 2
    groups of state 16, chunks of 32, one layer, float32."""
    fl, nb = spec.module("kernels", "ssd").flops_and_bytes(
        128, 128, 4, 8, 2, 16, 32, 1, 4)
    cb = 2 * 32 * 16                       # C B^T a token, once a group
    local = 2 * 32 * 8                     # (C B^T o L)(dt x) a head
    closing = carried = 2 * 8 * 16
    before = 2 * 4 * 8 * 16 / 32           # 4 chunk states into each start
    a_token = 2 * cb + 4 * (local + closing + carried + before)
    assert fl == 3 * 128 * a_token
    read = 128 * (4 * 8 + 4 + 2 * 2 * 16)  # x, dt, B, C
    y = 128 * 4 * 8
    states = 4 * 4 * 8 * 16                # 4 chunks x 4 heads of [8, 16]
    assert nb == (3 * read + 2 * y) * 4 + 2 * states * 4
    # at the cell's sizes the recurrence is bound by its bytes
    _, _, workload, config = spec.load_cell(REAL_CELL)
    m = config["model"]
    assert m["num_ssm_layers"] == m["pattern"].count("M") == 4
    assert m["chunk_size"] == config["chunk_size"] == 128
    fl, nb = spec.module("kernels", "ssd").flops_and_bytes(
        4096, 4096, 64, 64, 8, 128, 128, 4, 4)
    assert nb / 819e9 > fl / 197e12
    assert nb / 819e9 == pytest.approx(2.801e-3, rel=1e-3)


def test_the_ungated_experts_count_is_two_products_of_the_gated_three():
    args = (4096, 6, 8, 128, 4, 2688, 1856, 2)
    fl, nb = spec.module("kernels", "moe_experts_ungated").flops_and_bytes(
        *args)
    fl3, nb3 = spec.module("kernels", "moe_experts").flops_and_bytes(*args)
    assert fl * 3 == fl3 * 2 and nb * 3 == nb3 * 2
    rows = 4096 * 6 * 8 / 128               # 1,536 expected, 192 an expert
    assert fl == 4 * 2 * 3 * 2 * rows * 2688 * 1856


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    model, traffic = config["model"], workload["traffic"]
    flops = spec.module("kernels", config["flops"]).train_flops_per_unit(
        model, traffic)
    d = 2688
    ssd = (8 * 2 * 128 * 128
           + 64 * (2 * 128 * 64 + 4 * 64 * 128 + 2 * 32 * 64 * 128 / 128))
    mamba = 2 * d * (4096 + 6144 + 64) + 2 * 4096 * d + ssd
    attn = 2 * (2 * d * 4096 + 2 * d * 256) + 2 * 2 * 4096 * 2048
    moe = 2 * d * 128 + 4 * d * 3712 + 6 * 8 / 128 * 4 * d * 1856
    head = 2 * d * 16384
    assert flops == 3 * (4 * mamba + attn + 4 * moe + head)
    assert flops * 4096 == pytest.approx(8.418e12, rel=1e-3)   # a step
    # the Mamba-2 mixers are 48% of the required operations, the mixtures
    # 28%: the new mixer and the new mixture are three quarters of the step
    assert 3 * 4 * mamba / flops == pytest.approx(0.478, abs=0.005)
    assert 3 * 4 * moe / flops == pytest.approx(0.281, abs=0.005)


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                return row
    return None


PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 2,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True}


def test_the_configuration_keeps_every_published_width():
    bench, cell, workload, config = spec.load_cell(REAL_CELL)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    row = _catalog_config()
    if row is not None:     # the catalog beside the guide, where it is
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
        assert {k: row["config"][k] for k in config["reduced"]} == \
            config["published"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    m, args = config["model"], config["program"]["args"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (
        m["n_layer"], m["experts_held"][1] - m["experts_held"][0],
        m["vocab_size"]) == (9, 8, 16384)
    # floors: a whole period and four layers, 8 experts, an eighth of the
    # vocabulary; the layers that run are the published pattern's first
    assert m["pattern"] == args["pattern"] == "MEMEM*EME" == \
        config["hybrid_override_pattern"][:9]
    assert m["layer_types"] == [{"M": "mamba2", "E": "ffn",
                                 "*": "full_attention"}[c]
                                for c in m["pattern"]]
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert m["num_experts"] == config["published"]["n_routed_experts"]
    assert m["num_hidden_layers_published"] == 52
    assert (m["n_embd"], m["n_head"], m["n_kv_head"], m["head_dim"],
            m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["chunk_size"], m["conv_kernel"],
            m["moe_intermediate_size"],
            m["moe_shared_expert_intermediate_size"], m["num_experts"],
            m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["experts_held"], m["vocab_size"], m["norm_eps"]) == (
        args["d_model"], args["n_heads"], args["n_kv_heads"],
        args["head_dim"], args["ssm_heads"], args["ssm_head_dim"],
        args["ssm_groups"], args["ssm_state"], args["ssm_chunk"],
        args["conv_kernel"], args["expert_width"],
        args["shared_expert_width"], args["n_experts"], args["top_k"],
        args["routed_scale"], args["experts_held"], args["vocab_size"],
        args["norm_eps"])
    assert (m["n_embd"], m["head_dim"], m["n_head"], m["n_kv_head"],
            m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["moe_intermediate_size"],
            m["moe_shared_expert_intermediate_size"],
            m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["norm_eps"]) == (
        config["hidden_size"], config["head_dim"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["n_groups"], config["ssm_state_size"],
        config["moe_intermediate_size"],
        config["moe_shared_expert_intermediate_size"],
        config["num_experts_per_tok"], config["routed_scaling_factor"],
        config["layer_norm_epsilon"])
    assert set(config["assumed"]) >= {
        "no_positional_encoding", "in_projection_order", "conv_taps",
        "ssm_init", "conv_init", "gated_norm", "router", "experts",
        "rescale_prenorm_residual", "initializer", "final_norm",
        "optimizer", "precision"}
    assert len(config["departures"]) == 3
    assert "16 chips" in config["stands_for"]
    assert f"{PARAMETERS:,} parameters" in config["stands_for"]
    assert config["parameters_held"] == PARAMETERS
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_tokens", "pool": 2, "batch": 1, "seq_len": 4096}
    assert workload["runner"] == "train_rounds"
    # the factory's own default, as every sibling configuration's
    assert "learning_rate" not in args
    assert config["optimizer"]["learning_rate"] == 3e-4
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]


def test_the_cell_is_on_the_lists_it_reports_and_off_the_two_it_is_not():
    bench = spec.load_benchmark()
    mine = [p["name"] for p in bench["per_layer"]
            if p.get("workloads") == [REAL_CELL]]
    assert mine == NEW_METRICS
    reported = {p["name"] for p in
                spec.cell_metrics(bench, REAL_CELL, "per_layer")}
    # every `*.tokens` metric that lists the four older language cells
    older = {"gpt2m-train-t1024", "ouro-train-t2048", "lfm2-train-t8192",
             "qwen3next-train-t4096"}
    shared = {p["name"] for p in bench["per_layer"]
              if older <= set(p.get("workloads", ()))}
    assert len(shared) == 18 and shared <= reported
    assert {"attn_fwd_ms.tokens", "attn_bwd_ms.tokens", "mfu.tokens",
            "hbm_peak_gib.tokens", "device_idle_share.tokens",
            "step_loss_ms.tokens"} <= shared
    assert {"moe_ms.tokens", "moe_route_ms.tokens", "moe_experts_ms.tokens",
            "moe_rows_here_share.tokens",
            "moe_load_max_over_mean.tokens"} <= reported
    # the gated count would read half again too high; qwen3next's pin
    assert not {"moe_experts_roofline.tokens", "moe_shared_ms.tokens",
                "flash_attn_fwd_roofline", "gdn_ms.tokens",
                "short_conv_ms.tokens"} & reported
    rate, = [m for m in bench["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    assert rate["workloads"][-1] == REAL_CELL
    assert {m["name"] for m in spec.cell_metrics(
        bench, REAL_CELL, "end_to_end")} == {"train_tokens_per_s",
                                             "setup_s"}


def test_the_parameters_held_here_are_counted_from_the_shapes():
    """666,962,944 parameters at the cell's sizes, from shapes alone."""
    _, _, _, config = spec.load_cell(REAL_CELL)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(1, config["model"]))

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    layers = shapes["layers"]
    d = 2688
    mamba = (d * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * d + d)
    attn = d * 4096 + 2 * d * 256 + 4096 * d + d
    mixture = d * 128 + 2 * d * 3712 + 8 * 2 * d * 1856 + d
    assert [count(l) for l in layers] == [
        {"M": mamba, "*": attn, "E": mixture}[c] for c in "MEMEM*EME"]
    assert (mamba, attn, mixture) == (38_744_896, 23_399_040, 100_125_312)
    assert count(shapes) == PARAMETERS == (
        4 * mamba + attn + 4 * mixture + 2 * 16384 * d + d)
    # the program's own tree, from the factory, holds the same
    prog = config["program"]
    conf = program._resolve(prog["factory"])(**prog["args"])
    net = program._resolve(prog["net"])(conf)
    own = jax.eval_shape(lambda: net.init()[0])
    assert count(own) == PARAMETERS
    state = jax.eval_shape(lambda: ref.init_state(config["model"]))
    assert [None if s is None else set(s) for s in state] == [
        {"expert_bias", "moe_load", "moe_elsewhere"} if c == "E" else None
        for c in "MEMEM*EME"]


def test_budget_sizes_the_cell_from_shapes_alone(capsys):
    assert diagnose.main(["budget", "--workload", REAL_CELL]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith(
        f"parameters {PARAMETERS:,} ({PARAMETERS / 1e6:.1f} M)")
    assert out[1].startswith(
        f"program 12 B a parameter: {12 * PARAMETERS:,} ")
    assert out[2].startswith(
        f"reference 16 B a parameter: {16 * PARAMETERS:,} ")
    # under the ceiling PR 42 measured for a reference like qwen3next's
    assert 20.4 * PARAMETERS + 1.8e9 + 0.3e9 < 16_909_336_064


def test_the_reference_imports_nothing_from_the_program():
    with open(spec.module("reference", "nemotron_h").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text
    assert 'precision="highest"' in text
    # the recurrence is the definition, a token at a time, and no chunk
    # of the program's
    assert "def step(s, u)" in text and "cumsum" not in text
