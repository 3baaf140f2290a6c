"""The GLM-4.7-Flash configuration's benchmark files (ISSUE 45) at toy size
on the CPU, through a tree of their own (`toy_glm47flash/`): the plain
reference against the system (loss, both of its terms, every gradient
leaf, three Adam steps, the five mixtures' counts, `output()`), the
embedding and the head one leaf each in reference and system alike,
bfloat16 where float32 is stated failing the same comparison, the fp8
control caught, the share test (eight shares' routed parts and the shared
expert once add up to the uncut layer), a run and a traced run through
the real entry point with the network dead where the reference starts,
the latent attention's roofline counts by hand, `diagnose budget`, and the
data files' arithmetic."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_reference_start
from benchmark import check_train, control, diagnose, peaks, program, run, spec
from benchmark.readers import scope_roofline
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_glm47flash")
CELL = "toy-glm47flash-train"
REAL_CELL = "glm47flash-train-t4096"
NEW_METRICS = ["mla_ms.tokens", "mtp_ms.tokens", "mla_roofline.tokens",
               "mla_flash_roofline.tokens", "mtp_loss_over_main.tokens"]
PARAMETERS = 706_518_528


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


def _count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))


@pytest.fixture(scope="module")
def seeded():
    """The system under the float32 policy with the reference's seeded
    weights laid over it, one batch, and the reference's loss, gradients
    and state on it."""
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
        yield ref, model, weights, state, (x, y), want, got, out, config
    finally:
        dtypes.f32_policy()


def test_the_systems_loss_is_the_references_term_by_term(seeded):
    _, model, *_, (want_loss, _, want_state), (loss, state, _), _, _ = seeded
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    got, want = state[-1]["loss_terms"], want_state["loss_terms"]
    for term in ("main", "mtp"):
        assert float(got[term]) == pytest.approx(float(want[term]), rel=2e-5)
    assert float(want_loss) == pytest.approx(
        float(want["main"]) + model["mtp_loss_weight"] * float(want["mtp"]),
        rel=1e-6)
    # uniform ids over 128 classes: both terms start near ln 128
    assert 4.7 < float(want["main"]) < 5.0 and 4.7 < float(want["mtp"]) < 5.0


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, *_, (_, want_grads, _), (_, _, grads), _, _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    layout = ref.program_layout(want_grads)[0]
    want = np.asarray(program.leaf_norms(layout))
    # embedding; a dense layer 2 + 7 + 3; two mixture layers 2 + 7 + 7;
    # final norm, head; the module's three norms, W_eh and its layer
    assert got.shape == want.shape == (1 + 12 + 2 * 16 + 2 + 4 + 16,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    assert check_train.worst_leaf_gap(got, want) < 1e-4
    floor = 1e-3 * float(np.median(want))
    for a, b, n in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(layout), want):
        if n > floor:
            assert float(jnp.abs(a - b).max()) < 1e-3 * float(
                jnp.abs(b).max())


def test_the_table_and_the_head_are_one_leaf_each_on_both_sides(seeded):
    """Used twice, differentiated once: the reference's tree and the
    system's hold ONE [V, d] table and ONE [d, V] head, and each one's
    gradient holds both uses (the module's alone is not zero)."""
    ref, model, weights, state, (x, y), (_, want_grads, _), (_, _, grads), \
        _, config = seeded
    v, d = model["vocab_size"], model["n_embd"]
    for tree in (weights, ref.program_layout(weights)[0], grads):
        shapes = [a.shape for a in jax.tree_util.tree_leaves(tree)]
        assert shapes.count((v, d)) == 1 and shapes.count((d, v)) == 1
    assert set(weights["mtp"]) == {"g_e", "g_h", "w_eh", "layer", "g_s"}
    assert _count(weights) == _count(ref.program_layout(weights)[0])
    # with the second term's weight at zero the table's gradient is the
    # trunk's alone: what the module adds is the difference
    alone = ref.loss_and_grad(weights, state, x, y,
                              {**model, "mtp_loss_weight": 0.0})[1]
    for name in ("wte", "head_w"):
        added = np.asarray(want_grads[name] - alone[name])
        assert np.abs(added).max() > 1e-2 * np.abs(
            np.asarray(alone[name])).max()
    assert not any(np.asarray(a).any()
                   for a in jax.tree_util.tree_leaves(alone["mtp"]))


def test_three_adam_steps_follow_the_reference(seeded):
    ref, model, *_, config = seeded
    _, _, workload, _ = spec.load_cell(CELL, TOY)
    try:
        def start():    # the step donates what it was given: made anew
            return ref.program_layout(ref.init(5, model),
                                      ref.init_state(model))

        net = program.build(config, 5)
        program.load_weights(net, *start())
        traffic = spec.module("traffic", workload["traffic"]["kind"]).make(
            5, workload["traffic"], model)
        got = check_train.ProgramReadings(net, config["optimizer"])
        for step in range(check_train.STEPS):
            x, y = traffic["feed"][step % 2]
            net.fit(x, y)
            got.after_step(net.score_value)
        got.after_last(program.lay_over(net.params, start()[0]))
        want = check_train.follow_reference(ref, config, 5, traffic["plain"])
    finally:
        dtypes.f32_policy()
    rows = check_train.compare(
        got.readings(), want,
        {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "update_norm_gap": 1e-3,
         "state_first_norm_gap": 1e-3})
    assert len(rows) == 4 and all(ok for *_, ok in rows), rows
    assert len(want["leaf_names"]) == 67
    # five mixtures' worth in the full cell; here three: bias, load,
    # elsewhere each, and the loss's two terms
    assert len(want["state_names"]) == 3 * 3 + 2


def test_bfloat16_where_float32_is_stated_fails_the_same_comparison(seeded):
    ref, model, weights, state, (x, y), (want_loss, want_grads, _), *_ = seeded
    loss, grads, _ = ref.loss_and_grad(weights, state, x, y, model, "bf16")
    got = np.asarray(program.leaf_norms(ref.program_layout(grads)[0]))
    want = np.asarray(program.leaf_norms(ref.program_layout(want_grads)[0]))
    assert check_train.worst_leaf_gap(got, want) > 1e-3
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-3)


def test_the_systems_routing_counts_are_the_references(seeded):
    _, model, *_, (_, _, want_state), (_, state, _), _, _ = seeded
    tokens_k = 2 * 80 * model["num_experts_per_tok"]
    assert len(state) == 1 + model["n_layer"] + 1
    mixtures = [*zip(state[1:-1], want_state["layers"]),
                (state[-1]["mtp"], want_state["mtp"])]
    assert want_state["layers"][0] is None and not state[1]
    for got_s, want_s in mixtures[1:]:
        assert set(got_s) == set(want_s) == {"expert_bias", "moe_load",
                                             "moe_elsewhere"}
        for name in want_s:
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]))
        # the module's router too sees every position of both sequences
        assert float(got_s["moe_load"].sum()
                     + got_s["moe_elsewhere"][0]) == tokens_k
        assert 0 < float(got_s["moe_load"].sum()) < tokens_k
        assert not np.asarray(got_s["expert_bias"]).any()
    assert len(mixtures) - 1 == 3


def test_output_is_the_references_softmax(seeded):
    ref, model, weights, state, (x, _), _, _, out, _ = seeded
    frozen = ref._static(model)
    biases = {"layers": [None if s is None else s["expert_bias"]
                         for s in state["layers"]],
              "mtp": state["mtp"]["expert_bias"]}

    @jax.jit
    def logits(w, tok):
        h, _ = ref.trunk_one(w, biases, tok, dict(frozen))
        return ref.main_logits(w, h, dict(frozen))

    want = np.stack([np.asarray(jax.nn.softmax(logits(weights, x[i]), -1))
                     for i in range(x.shape[0])])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-7)


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(seeded):
    """The share test: one mixture at GLM's router (sigmoid over all 64,
    top 4, renormalised, times 1.8) held whole, against eight chips' shares
    of eight experts each: the routed parts add up to the whole layer's
    routed part, the counts to the whole layer's counts, and the shared
    expert, which every chip holds and computes on ITS OWN tokens, is
    added once and not eight times."""
    ref = seeded[0]
    d, fe, e, t = 16, 12, 64, 48
    model = {"num_experts": e, "num_experts_per_tok": 4,
             "routed_scaling_factor": 1.8, "experts_held": [0, e]}
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    p = {"w_r": jax.random.normal(ks[0], (d, e)),
         "e_wg": jax.random.normal(ks[1], (e, d, fe)) * 0.3,
         "e_wu": jax.random.normal(ks[2], (e, d, fe)) * 0.3,
         "e_wd": jax.random.normal(ks[3], (e, fe, d)) * 0.3,
         "s_wg": jax.random.normal(ks[4], (d, fe)) * 0.3,
         "s_wu": jax.random.normal(ks[5], (d, fe)) * 0.3,
         "s_wd": jax.random.normal(ks[6], (fe, d)) * 0.3}
    u = jax.random.normal(ks[7], (t, d))
    bias = jnp.zeros((e,))
    whole, load, elsewhere = ref.routed(u, p, bias, model, "f32")
    assert float(elsewhere[0]) == 0 and float(load.sum()) == t * 4
    uncut = whole + ref.shared(u, p, "f32")

    parts, loads = [], []
    for chip in range(8):
        first, end = 8 * chip, 8 * chip + 8
        mine = {**p, **{k: p[k][first:end]
                        for k in ("e_wg", "e_wu", "e_wd")}}
        y, held_load, away = ref.routed(u, mine, bias, model, "f32",
                                        held=(first, end))
        assert float(held_load.sum() + away[0]) == t * 4
        parts.append(y)
        loads.append(held_load)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(loads)),
                                  np.asarray(load))
    np.testing.assert_allclose(
        np.asarray(sum(parts) + ref.shared(u, p, "f32")), np.asarray(uncut),
        rtol=1e-5, atol=1e-6)
    # the weights of a token sum to the scaling factor whatever is held
    _, w = ref.route(u, p["w_r"], bias, model)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.8, rtol=1e-5)


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
    assert len(detail["state_names"]) == 11   # three mixtures, two terms
    assert len(detail["leaf_names"]) == 67
    assert sum("loss_terms" in n for n in detail["state_names"]) == 2
    assert "state_first_norms" in detail


def test_the_reference_starts_with_the_program_gone(on_the_cpu, monkeypatch,
                                                    capsys):
    test_reference_start.test_the_reference_starts_with_the_program_gone(
        "toy_glm47flash", CELL, on_the_cpu, monkeypatch, capsys)


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # the program's counters serve the three mixtures, the module's among
    # them: 8 of 16 experts held, 3 a token
    assert 0 < m["moe_rows_here_share.tokens"]["value"] < 100
    assert m["moe_load_max_over_mean.tokens"]["value"] >= 1.0
    # the two terms of the loss, apart, from the registry's gauges
    assert 0.9 < m["mtp_loss_over_main.tokens"]["value"] < 1.1
    # no device plane in a CPU trace: the trace readers find nothing there
    # and give nothing, as they do on a program without the scopes
    assert set(NEW_METRICS) & set(m) == {"mtp_loss_over_main.tokens"}
    toy = spec.load_benchmark(TOY)
    assert set(NEW_METRICS) <= {p["name"] for p in toy["per_layer"]}


def test_a_program_without_the_gauges_gives_the_new_counter_nothing():
    """What the parent commit is to this PR's metric: the registry holds
    no `train_loss_term_*`, and the reader returns None and does not
    raise."""
    args = spec.layer_metric("mtp_loss_over_main.tokens")["args"]
    ctx = types.SimpleNamespace(counters_open={"other": 1.0},
                                counters_close={"other": 2.0})
    assert spec.module("readers", "registry_ratio").read(
        {"ctx": ctx}, args) is None


def test_the_new_metric_files_are_read_by_the_readers_the_benchmark_has():
    for name, reader, scope in (
            ("mla_ms.tokens", "scope_ms", "mla"),
            ("mtp_ms.tokens", "scope_ms", "mtp"),
            ("mla_roofline.tokens", "scope_roofline", "mla"),
            ("mla_flash_roofline.tokens", "scope_roofline",
             "flash_attn\\.fwd/flash_attn_fwd")):
        lm = spec.layer_metric(name)
        assert (lm["name"], lm["reader"], lm["args"]["scope"]) == (
            name, reader, scope)
        assert lm["moves"] == "train_tokens_per_s"
        assert callable(spec.module("readers", reader).read)
        if reader == "scope_roofline":
            assert lm["args"]["function"] == "mla"
    lm = spec.layer_metric("mtp_loss_over_main.tokens")
    assert lm["reader"] == "registry_ratio" and lm["args"] == {
        "numerator": "train_loss_term_mtp",
        "denominator": "train_loss_term_main", "at": "close"}
    # every size the counts ask for is in the cell's model or traffic
    _, _, workload, config = spec.load_cell(REAL_CELL)
    ctx = types.SimpleNamespace(config=config, workload=workload)
    least = {}
    for name in ("mla_roofline.tokens", "mla_flash_roofline.tokens",
                 "moe_experts_roofline.tokens"):
        args = spec.layer_metric(name)["args"]
        sizes = {k: scope_roofline._shape(ctx, v)
                 for k, v in args["shapes"].items()}
        assert all(isinstance(v, (int, float)) for v in sizes.values()), name
        fl, nb = spec.module("kernels", args["function"]).flops_and_bytes(
            **sizes)
        assert fl > 0 and nb > 0
        assert sizes["tokens"] == 4096
        # six latent attentions; five mixtures, the module's among them
        assert sizes["layers"] == (5 if name.startswith("moe") else 6)
        least[name] = max(fl / 197e12, nb / 819e9) * 1e3
    assert least["mla_roofline.tokens"] == pytest.approx(31.98, rel=1e-3)
    assert least["mla_flash_roofline.tokens"] == pytest.approx(5.232,
                                                               rel=1e-3)


def test_the_latent_attentions_count_by_hand():
    """`kernels/mla.py` at 64 tokens in one sequence, d 32, 2 heads,
    ranks 16 and 8, a head 12 + 4 wide with values of 16, one layer."""
    mla = spec.module("kernels", "mla")
    args = dict(tokens=64, seq_len=64, d=32, heads=2, q_rank=16, kv_rank=8,
                nope=12, rope=4, v=16, layers=1)
    weights = 32 * 16 + 16 * 2 * 16 + 32 * 12 + 8 * 2 * 28 + 2 * 16 * 32
    assert mla.projection_params(32, 2, 16, 8, 12, 4, 16) == weights
    scores = 2 * 2 * 16 * 32 + 2 * 2 * 16 * 32     # q k^T and w v, at T / 2
    fl, nb = mla.flops_and_bytes(**args, dtype_bytes=2)
    assert fl == 3 * 64 * (2 * weights + scores)
    rows = 64 * ((32 + 16) + (16 + 32) + (32 + 12) + (8 + 56) + (32 + 32))
    assert nb == (2 * weights * 2 + 4 * weights + 3 * rows * 2
                  + 64 * 12 * 32 * 2)
    fl, nb = mla.flops_and_bytes(**args, dtype_bytes=4, flash_forward_only=1)
    assert fl == 64 * scores and nb == 64 * 4 * 32 * 4
    # one call of the forward kernel counts as `kernels/flash_attn.py`
    # counts it, at the cell's [20, 4096, 256]
    one = spec.module("kernels", "flash_attn").flops_and_bytes(
        1, 20, 4096, 20 * 256, 4)
    six = mla.flops_and_bytes(4096, 4096, 2048, 20, 768, 512, 192, 64, 256,
                              6, 4, 1)
    assert six == (6 * one[0], 6 * one[1])
    # the published widths: 21,757,952 projection weights a layer (the two
    # latent norms' 1,280 gains beside them), compute-bound
    assert mla.projection_params(2048, 20, 768, 512, 192, 64, 256) == \
        21_759_232 - 768 - 512
    fl, nb = mla.flops_and_bytes(4096, 4096, 2048, 20, 768, 512, 192, 64,
                                 256, 6, 2)
    assert fl / 197e12 > nb / 819e9


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    model, traffic = config["model"], workload["traffic"]
    kern = spec.module("kernels", config["flops"])
    flops = kern.train_flops_per_unit(model, traffic)
    d = 2048
    proj = 2 * (d * 768 + 768 * 5120 + d * 576 + 512 * 8960 + 5120 * d)
    scores = 2 * 2 * 5120 * 2048
    dense = 2 * 3 * d * 10240
    mixture = 2 * d * 64 + 2 * 3 * d * 1536 + 4 * 8 / 64 * 2 * 3 * d * 1536
    heads = 2 * 2 * d * 19360
    join = 2 * 2 * d * d
    assert flops == 3 * (6 * (proj + scores) + dense + 5 * mixture + heads
                         + join)
    # ISSUE 45's 957 M a token forward, and its parts
    parts = kern.forward_parts_per_token(model, traffic)
    assert sum(parts.values()) == pytest.approx(957e6, rel=1e-3)
    assert parts["mla_scores"] == pytest.approx(251.7e6, rel=1e-3)
    assert parts["mla_projections"] + parts["mla_scores"] == pytest.approx(
        512.8e6, rel=1e-3)
    assert parts["dense_ffn"] == pytest.approx(125.8e6, rel=1e-3)
    assert parts["routers"] + parts["shared_experts"] \
        + parts["held_experts"] == pytest.approx(142.9e6, rel=1e-3)
    assert parts["heads"] == pytest.approx(158.6e6, rel=1e-3)
    assert parts["mtp_join"] == pytest.approx(16.8e6, rel=2e-3)
    assert flops * 4096 == pytest.approx(11.757e12, rel=1e-3)   # a step
    # latent attention is 53% of the required operations, the mixtures 15%
    total = sum(parts.values())
    assert (parts["mla_projections"] + parts["mla_scores"]) / total == \
        pytest.approx(0.536, abs=0.005)
    assert 5 * mixture / total == pytest.approx(0.149, abs=0.005)


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "GLM-4.7-Flash":
                return row
    return None


PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256}


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
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    m, args = config["model"], config["program"]["args"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (
        m["n_layer"], m["experts_held"][1] - m["experts_held"][0],
        m["vocab_size"]) == (5, 8, 19360)
    # floors: the leading dense layer and four after it, 8 experts, an
    # eighth of the vocabulary; the module whole, listed after the trunk
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert m["num_experts"] == config["published"]["n_routed_experts"]
    assert m["layer_types"] == ["latent_attention"] * 6
    assert m["num_mla_layers"] == len(m["layer_types"]) == \
        m["n_layer"] + m["num_nextn_predict_layers"]
    assert m["num_dense_layers"] == config["first_k_dense_replace"] == 1
    assert m["head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"] \
        == m["v_head_dim"] == 256
    assert (m["n_embd"], m["n_head"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["moe_intermediate_size"], m["num_experts"],
            m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["experts_held"], m["vocab_size"], m["norm_eps"],
            m["rope_theta"], m["n_layer"], m["num_dense_layers"],
            m["mtp_loss_weight"]) == (
        args["d_model"], args["n_heads"], args["q_rank"], args["kv_rank"],
        args["nope_dim"], args["rope_dim"], args["v_dim"],
        args["ffn_width"], args["expert_width"],
        args["shared_expert_width"], args["n_experts"], args["top_k"],
        args["routed_scale"], args["experts_held"], args["vocab_size"],
        args["norm_eps"], args["rope_theta"], args["n_layers"],
        args["num_dense_layers"], args["mtp_weight"])
    assert (m["n_embd"], m["n_head"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["norm_eps"], m["rope_theta"], m["n_positions"],
            m["num_nextn_predict_layers"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["q_lora_rank"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"], config["intermediate_size"],
        config["moe_intermediate_size"], config["num_experts_per_tok"],
        config["routed_scaling_factor"], config["rms_norm_eps"],
        config["rope_theta"], config["max_position_embeddings"],
        config["num_nextn_predict_layers"])
    assert set(config["assumed"]) >= {
        "scoring_func", "mtp_loss_weight", "mtp_input", "mtp_concat_order",
        "mtp_positions", "rotary_pairing", "correction_bias", "initializer",
        "optimizer", "precision"}
    assert m["mtp_loss_weight"] == 0.3
    assert len(config["departures"]) == 5
    assert "8 chips share each layer" in config["stands_for"]
    assert "which no stage of the job does" in config["stands_for"]
    assert f"{PARAMETERS:,} parameters" in config["stands_for"]
    assert config["parameters_held"] == PARAMETERS
    assert config["recompute"].startswith("none")
    assert "recompute_heads" not in args      # a knob the layer lost
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_tokens", "pool": 2, "batch": 1, "seq_len": 4096}
    assert workload["runner"] == "train_rounds"
    assert set(workload["limits"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_median_gap",
        "update_norm_gap", "state_first_norm_gap"}
    # the factory's own default, as every sibling configuration's
    assert "learning_rate" not in args
    assert config["optimizer"]["learning_rate"] == 3e-4
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200


def test_the_cell_is_on_the_lists_it_reports_and_off_those_it_does_not():
    """By name and by membership alone: `spec.cell_metrics` asks `in`, so
    a list's order and what later cells append to it carry no meaning."""
    bench = spec.load_benchmark()
    listing = {p["name"]: p.get("workloads", ()) for p in bench["per_layer"]}
    for name in NEW_METRICS:
        assert REAL_CELL in listing[name], name
    reported = {p["name"] for p in
                spec.cell_metrics(bench, REAL_CELL, "per_layer")}
    older = {"gpt2m-train-t1024", "ouro-train-t2048", "lfm2-train-t8192",
             "qwen3next-train-t4096"}
    shared = {name for name, cells in listing.items() if older <= set(cells)}
    assert len(shared) >= 18 and shared <= reported
    assert {"attn_fwd_ms.tokens", "attn_bwd_ms.tokens", "mfu.tokens",
            "hbm_peak_gib.tokens", "device_idle_share.tokens",
            "step_loss_ms.tokens"} <= shared
    # gated experts: the six moe lists, the gated count among them
    assert {"moe_ms.tokens", "moe_route_ms.tokens", "moe_experts_ms.tokens",
            "moe_experts_roofline.tokens", "moe_rows_here_share.tokens",
            "moe_load_max_over_mean.tokens"} <= reported
    # the ungated count would read a third too low; the others read
    # kernels and scopes this step does not run. (`moe_shared_ms.tokens`
    # it does run and is kept off by qwen3next's pin: PERF.md section 7)
    assert not {"moe_experts_ungated_roofline.tokens",
                "flash_attn_fwd_roofline",
                "flash_attn_w256_roofline.tokens", "gdn_ms.tokens",
                "ssm_ms.tokens", "short_conv_ms.tokens"} & reported
    rate, = [m for m in bench["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    assert REAL_CELL in rate["workloads"]
    assert {m["name"] for m in spec.cell_metrics(
        bench, REAL_CELL, "end_to_end")} == {"train_tokens_per_s",
                                             "setup_s"}
    assert [w["name"] for w in bench["workloads"]].count(REAL_CELL) == 1


def test_the_parameters_held_here_are_counted_from_the_shapes():
    """706,518,528 parameters at the cell's sizes, from shapes alone."""
    _, _, _, config = spec.load_cell(REAL_CELL)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(1, config["model"]))
    d = 2048
    mla = d * 768 + 768 + 768 * 5120 + d * 576 + 512 + 512 * 8960 + 5120 * d
    dense = mla + 3 * d * 10240 + 2 * d
    mixture = mla + d * 64 + 3 * d * 1536 + 8 * 3 * d * 1536 + 2 * d
    assert (mla, dense, mixture) == (21_759_232, 84_677_888, 106_829_056)
    assert [_count(l) for l in shapes["layers"]] == [dense] + [mixture] * 4
    module = 2 * d * d + mixture + 3 * d
    assert _count(shapes["mtp"]) == module == 115_223_808
    assert _count(shapes) == PARAMETERS == (
        dense + 4 * mixture + 2 * 19360 * d + d + module)
    # the program's own tree, from the factory, holds the same
    prog = config["program"]
    conf = program._resolve(prog["factory"])(**prog["args"])
    net = program._resolve(prog["net"])(conf)
    own = jax.eval_shape(lambda: net.init()[0])
    assert _count(own) == PARAMETERS
    state = jax.eval_shape(lambda: ref.init_state(config["model"]))
    keys = {"expert_bias", "moe_load", "moe_elsewhere"}
    assert [None if s is None else set(s) for s in state["layers"]] == [
        None, keys, keys, keys, keys]
    assert set(state["mtp"]) == keys
    assert set(state["loss_terms"]) == {"main", "mtp"}


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
    with open(spec.module("reference", "glm4_moe_lite").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text
    assert 'precision="highest"' in text
    # the shared rotary key is joined to a head's own part where the head
    # is computed, and the table is read twice from one leaf
    assert "k_j = jnp.concatenate([at(kn, j), kr], -1)" in text
    assert text.count('params["wte"][') == 2
