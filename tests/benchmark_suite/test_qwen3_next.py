"""The Qwen3-Next configuration's benchmark files (ISSUE 34) at toy size on
the CPU, through a tree of their own (`toy_qwen3next/`): the plain
reference against the system (loss, every gradient leaf, the routing's
counts, `output()`), the fp8 control caught, a run and a traced run
through the real entry point, the recurrence's roofline count by hand, and
the data files' arithmetic."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_train, control, peaks, program, run, spec
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_qwen3next")
CELL = "toy-qwen3next-train"
REAL_CELL = "qwen3next-train-t4096"
NEW_METRICS = ["gdn_ms.tokens", "gdn_core_ms.tokens", "moe_shared_ms.tokens",
               "gdn_core_roofline.tokens", "flash_attn_w256_roofline.tokens"]


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
        yield ref, model, weights, state, x, want, got, out
    finally:
        dtypes.f32_policy()


def test_the_systems_loss_is_the_references(seeded):
    *_, (want_loss, _, _), (loss, _, _), _ = seeded
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, *_, (_, want_grads, _), (_, _, grads), _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    layout = ref.program_layout(want_grads)[0]
    want = np.asarray(program.leaf_norms(layout))
    # embedding, head, final norm; 3 x (2 norms + 7 + 8) + (2 + 5 + 8)
    assert got.shape == want.shape == (69,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    assert check_train.worst_leaf_gap(got, want) < 1e-4
    # and element by element where a leaf's gradient is not rounding-small
    # (a decay's gradient, A_log's and dt_bias's, is 1e-6 of the median)
    floor = 1e-3 * float(np.median(want))
    for a, b, n in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(layout), want):
        if n > floor:
            assert float(jnp.abs(a - b).max()) < 1e-3 * float(
                jnp.abs(b).max())


def test_the_systems_routing_counts_are_the_references(seeded):
    ref, model, *_, (_, _, want_state), (_, state, _), _ = seeded
    tokens_k = 2 * 80 * model["num_experts_per_tok"]
    assert len(state) == len(want_state) + 3   # embedding; norm and head
    for got_s, want_s in zip(state[1:-2], want_state):
        assert set(got_s) == set(want_s) == {"moe_load", "moe_elsewhere"}
        for name in want_s:
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]))
        assert float(got_s["moe_load"].sum()
                     + got_s["moe_elsewhere"][0]) == tokens_k
        assert 0 < float(got_s["moe_load"].sum()) < tokens_k


def test_output_is_the_references_softmax(seeded):
    ref, model, weights, state, x, _, _, out = seeded
    frozen = ref._static(model)
    logits = jax.jit(lambda w, tok: ref.logits_one(w, tok, dict(frozen))[0])
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
    assert len(detail["state_names"]) == 8   # load, elsewhere x 4
    assert len(detail["leaf_names"]) == 69
    assert "state_first_norms" in detail


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # the program's counters serve the new layers as they are: 8 of 16
    # experts held, 3 a token
    assert 0 < m["moe_rows_here_share.tokens"]["value"] < 100
    assert m["moe_load_max_over_mean.tokens"]["value"] >= 1.0
    # no device plane in a CPU trace: the new trace readers find nothing
    # there and give nothing, as they do on a program without the scopes
    assert not set(NEW_METRICS) & set(m)
    toy = spec.load_benchmark(TOY)
    assert set(NEW_METRICS) <= {p["name"] for p in toy["per_layer"]}


def test_the_recurrences_count_by_hand():
    """`kernels/gated_delta.py` at 128 tokens, 2 key and 4 value heads of
    8 and 16, chunks of 64, one layer, float32 rows."""
    fl, nb = spec.module("kernels", "gated_delta").flops_and_bytes(
        128, 2, 4, 8, 16, 64, 1, 4)
    shared = 2 * (2 * 64 * 64 * 8) * 2 / 4    # k k^T, q k^T: once a key head
    solve = 64 * 64 * (8 + 16)
    scan = 3 * (2 * 64 * 8 * 16) + 2 * 64 * 64 * 16
    assert fl == 3 * 2 * 4 * (shared + solve + scan)     # 2 chunks, 4 heads
    io = 128 * (2 * 2 * 8 + 2 * 4 * 16 + 2 * 4)          # q k v o g beta
    states = 2 * 4 * 8 * 16
    assert nb == 2 * io * 4 + 2 * states * 4
    # at the cell's sizes the recurrence is bound by its bytes
    _, _, workload, config = spec.load_cell(REAL_CELL)
    args = spec.layer_metric("gdn_core_roofline.tokens")["args"]
    assert args["scope"] == "gdn_core" and args["function"] == "gated_delta"
    m = config["model"]
    assert m["num_linear_layers"] == m["layer_types"].count(
        "linear_attention") == 3
    from deeplearning4j_tpu.ops import gated_delta
    assert m["gated_delta_chunk"] == gated_delta.CHUNK
    fl, nb = spec.module("kernels", "gated_delta").flops_and_bytes(
        4096, 16, 32, 128, 128, 64, 3, 4)
    assert nb / 819e9 > fl / 197e12
    assert nb / 819e9 == pytest.approx(2.466e-3, rel=1e-3)


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    model, traffic = config["model"], workload["traffic"]
    flops = spec.module("kernels", config["flops"]).train_flops_per_unit(
        model, traffic)
    d = 2048
    gdn = 2 * d * (12288 + 64) + 2 * 4096 * d + 6 * 32 * 128 * 128
    attn = 2 * (3 * d * 4096 + 2 * d * 512) + 2 * 2 * 4096 * 2048
    moe = (2 * d * 512 + 6 * d * 512 + 2 * d
           + 10 * 16 / 512 * 6 * d * 512)
    head = 2 * d * 18992
    assert flops == 3 * (3 * gdn + attn + 4 * moe + head)
    assert flops * 4096 == pytest.approx(5.15e12, rel=1e-3)   # a step
    # the new mixers and the new mixture are over two thirds of the step
    assert 3 * (3 * gdn + attn + 4 * moe) / flops > 0.8
    # the one flash-forward call at head width 256: 0.137 TFLOP
    args = spec.layer_metric("flash_attn_w256_roofline.tokens")["args"]
    assert args["shapes"]["width"] == ["n_head", "head_dim"]
    fl, _ = spec.module("kernels", "flash_attn").flops_and_bytes(
        1, 16, 4096, 16 * 256, 4)
    assert fl == pytest.approx(0.1374e12, rel=1e-3)


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Qwen3-Next-80B-A3B-Instruct":
                return row
    return None


PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}


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
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    m, args = config["model"], config["program"]["args"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (
        m["n_layer"], m["experts_held"][1] - m["experts_held"][0],
        m["vocab_size"]) == (4, 16, 18992)
    # floors: a whole period and four layers, 8 experts, an eighth of the
    # vocabulary
    assert m["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert m["layer_types"] == [
        "full_attention" if (i + 1) % config["full_attention_interval"] == 0
        else "linear_attention" for i in range(4)]
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert m["num_dense_layers"] == 0 and m["num_experts"] == 512
    assert (m["n_embd"], m["n_head"], m["n_kv_head"], m["head_dim"],
            m["partial_rotary_factor"], m["linear_num_key_heads"],
            m["linear_num_value_heads"], m["linear_key_head_dim"],
            m["linear_value_head_dim"], m["linear_conv_kernel_dim"],
            m["moe_intermediate_size"],
            m["shared_expert_intermediate_size"], m["num_experts"],
            m["num_experts_per_tok"], m["experts_held"], m["n_layer"],
            m["full_attention_interval"], m["vocab_size"], m["rope_theta"],
            m["norm_eps"]) == (
        args["d_model"], args["n_heads"], args["n_kv_heads"],
        args["head_dim"], args["partial_rotary_factor"],
        args["linear_k_heads"], args["linear_v_heads"],
        args["linear_k_head_dim"], args["linear_v_head_dim"],
        args["conv_kernel"], args["expert_width"],
        args["shared_expert_width"], args["n_experts"], args["top_k"],
        args["experts_held"], args["n_layers"],
        args["full_attention_interval"], args["vocab_size"],
        args["rope_theta"], args["norm_eps"])
    assert (m["n_embd"], m["head_dim"], m["n_head"], m["n_kv_head"]) == (
        config["hidden_size"], config["head_dim"],
        config["num_attention_heads"], config["num_key_value_heads"])
    assert set(config["assumed"]) >= {
        "fused_projection_order", "conv_taps", "l2norm_eps", "decay_init",
        "gate_layout", "qk_norm_before_rope", "router",
        "shared_expert_gate", "final_norm", "initializer", "optimizer",
        "precision"}
    assert len(config["departures"]) == 2
    assert "32 chips" in config["stands_for"]
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_tokens", "pool": 2, "batch": 1, "seq_len": 4096}
    assert workload["runner"] == "train_rounds"
    # the factory's own default, as every sibling configuration's
    assert "learning_rate" not in args
    assert config["optimizer"]["learning_rate"] == 3e-4
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    mine = [p["name"] for p in bench["per_layer"]
            if p.get("workloads") == [REAL_CELL]]
    assert mine == NEW_METRICS
    for name in NEW_METRICS:
        lm = spec.layer_metric(name)
        assert lm["reader"] in ("scope_ms", "scope_roofline")
    reported = {p["name"] for p in
                spec.cell_metrics(bench, REAL_CELL, "per_layer")}
    assert {"attn_fwd_ms.tokens", "attn_bwd_ms.tokens", "mfu.tokens",
            "hbm_peak_gib.tokens"} <= reported
    assert not {"flash_attn_fwd_roofline", "short_conv_ms.tokens"} & reported


def test_the_parameters_held_here_are_counted_from_the_shapes():
    """424,340,544 parameters at the cell's sizes, from shapes alone:
    ISSUE 34's 625,667,136 less 4 layers x 16 experts x 3,145,728, the
    sixteen experts a layer that the output check has no room for."""
    _, _, _, config = spec.load_cell(REAL_CELL)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(1, config["model"]))

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    layers = shapes["layers"]
    mixture = {k: v for k, v in layers[0].items()
               if k[:2] in ("w_", "e_", "s_") and k not in (
                   "w_qkvz", "w_ba", "w_out")}
    assert count(mixture) == 104_859_648 - 16 * 3_145_728
    gdn = {k: layers[0][k] for k in ("w_qkvz", "w_ba", "conv_w", "a_log",
                                     "dt_bias", "g_o", "w_out")}
    assert count(gdn) == 33_718_464
    attn = {k: layers[3][k] for k in ("w_q", "w_k", "w_v", "w_o", "g_q",
                                      "g_k")}
    assert count(attn) == 27_263_488
    assert count(layers[:3]) == 415_746_624 - 3 * 16 * 3_145_728
    assert count(layers[3]) == 132_127_232 - 16 * 3_145_728
    assert count(shapes) == 424_340_544 == 625_667_136 - 4 * 16 * 3_145_728
    # the program's own tree, from the factory, holds the same
    prog = config["program"]
    conf = program._resolve(prog["factory"])(**prog["args"])
    net = program._resolve(prog["net"])(conf)
    own = jax.eval_shape(lambda: net.init()[0])
    assert count(own) == 424_340_544
    state = jax.eval_shape(lambda: ref.init_state(config["model"]))
    assert [set(s) for s in state] == [{"moe_load", "moe_elsewhere"}] * 4


def test_the_reference_imports_nothing_from_the_program():
    with open(spec.module("reference", "qwen3_next").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text
    assert 'precision="highest"' in text
    # the recurrence is the definition, a token at a time, and no chunk
    # of the program's
    assert "def step(s, x)" in text and "_unit_lower" not in text
