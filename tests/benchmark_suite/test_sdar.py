"""The SDAR-30B-A3B-Chat configuration's benchmark files (ISSUE 49) at toy
size on the CPU, through a tree of their own (`toy_sdar/`): the plain
reference against the system (the loss, every gradient leaf, three Adam
steps, the noise's counter and counts, the six mixtures' routing), the
draw written out twice, bfloat16 where float32 is stated failing the same
comparison, the fp8 control caught, the share test (eight shares' routed
parts add up to the uncut layer), a run and a traced run through the real
entry point with the network dead where the reference starts, the new
metric files on a trace recorded on the chip, the required operations by
hand, `diagnose budget`, and the data files' arithmetic."""

import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_reference_start
from benchmark import (check_train, control, diagnose, peaks, program, run,
                       spec, trace)
from benchmark.readers import scope_ms, scope_roofline
from deeplearning4j_tpu.nn.layers import block_diffusion
from deeplearning4j_tpu.utils import dtypes

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_sdar")
CELL = "toy-sdar-train"
REAL_CELL = "sdar-train-bd4-t4096"
NEW_METRICS = ["bd_attn_roofline.tokens", "bd_noise_ms.tokens"]
PARAMETERS = 645_623_296


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
        yield ref, model, weights, state, (x, y), want, got, config
    finally:
        dtypes.f32_policy()


def test_the_traffic_feeds_the_ids_as_their_own_labels():
    _, _, workload, config = spec.load_cell(CELL, TOY)
    make = spec.module("traffic", "lm_denoise").make
    a = make(2 ** 31 + 5, workload["traffic"], config["model"])
    b = make(2 ** 31 + 5, workload["traffic"], config["model"])
    assert len(a["feed"]) == 2 and a["feed"] is a["plain"]
    for (x, y), (x2, _) in zip(a["feed"], b["feed"]):
        assert x.shape == y.shape == (2, 64) and x.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))
        assert 0 <= int(x.min()) and int(x.max()) < 128
    assert (np.asarray(a["feed"][0][0]) != np.asarray(a["feed"][1][0])).any()
    # a token carried by two positions is one unit
    assert a["units_per_batch"] == 2 * 64


def test_the_systems_loss_is_the_references(seeded):
    *_, (want_loss, _, _), (loss, _, _), _ = seeded
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    # sum of m / t CE over B T with E[m / t] = 1: near ln 128 on uniform ids
    assert 3.5 < float(want_loss) < 6.5


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, *_, (_, want_grads, _), (_, _, grads), _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    layout = ref.program_layout(want_grads)[0]
    want = np.asarray(program.leaf_norms(layout))
    # embedding; two layers of 2 norms, 5 attention leaves, 4 mixture
    # leaves; final norm, head. The input layer has none
    assert got.shape == want.shape == (1 + 2 * 11 + 2,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    assert check_train.worst_leaf_gap(got, want) < 1e-4
    floor = 1e-3 * float(np.median(want))
    for a, b, n in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(layout), want):
        if n > floor:
            assert float(jnp.abs(a - b).max()) < 1e-3 * float(
                jnp.abs(b).max())


def test_the_systems_noise_and_routing_are_the_references(seeded):
    _, model, _, _, (x, _), (_, _, want_state), (_, state, _), _ = seeded
    assert len(state) == 1 + 1 + model["n_layer"] + 2
    got = state[0]
    assert set(got) == set(want_state["noise"]) == {"noise_step"}
    assert int(got["noise_step"]) == int(
        want_state["noise"]["noise_step"]) == 1
    assert x.size == 128
    # both copies of both sequences go through every router
    positions_k = 2 * x.size * model["num_experts_per_tok"]
    for got_s, want_s in zip(state[2:-2], want_state["layers"]):
        assert set(got_s) == set(want_s) == {"moe_load", "moe_elsewhere"}
        for name in want_s:
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]))
        assert float(got_s["moe_load"].sum()
                     + got_s["moe_elsewhere"][0]) == positions_k
        assert 0 < float(got_s["moe_load"].sum()) < positions_k
    assert not state[1] and not state[-1] and not state[-2]


def test_program_and_reference_write_the_same_draw_out(seeded):
    """Two implementations of the configuration's definition: the
    layer's and the reference's, bit for bit, at two counters."""
    ref, model, _, _, (x, _), *_ = seeded
    for step in (0, 7):
        xt, w, masked = ref.draw(jnp.int32(step), x, model)
        m2, level = block_diffusion.draw_noise(
            model["noise_seed"], step, *x.shape, model["block_length"],
            model["noise_eps"])
        np.testing.assert_array_equal(np.asarray(masked), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(w), np.asarray(m2 / level))
        np.testing.assert_array_equal(
            np.asarray(xt), np.where(np.asarray(m2),
                                     model["mask_token_id"], np.asarray(x)))
        assert w.dtype == jnp.float32 and xt.dtype == jnp.int32


def test_the_references_mask_is_the_kernels_geometry():
    """Built twice: the reference's by comparison of blocks, the
    program's `BlockDiffusion.dense()`."""
    from deeplearning4j_tpu.ops.attention_pallas import BlockDiffusion
    ref = spec.module("reference", "sdar_moe")
    t, block = 24, 4
    pos = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    clean, blk = jnp.arange(2 * t) >= t, pos // block
    seen = ref.sees(clean[:, None], blk[:, None], clean[None, :],
                    blk[None, :])
    np.testing.assert_array_equal(np.asarray(seen),
                                  np.asarray(BlockDiffusion(t, block).dense()))
    seen = np.asarray(seen)
    assert seen.diagonal().all()            # every row sees itself
    assert not seen[t:, :t].any()           # no clean query, a noised key
    assert seen.sum() == t * t + t * block  # the live pairs


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
    assert len(want["leaf_names"]) == 25
    # the noise's counter and two mixtures' two leaves each; the counter's
    # change over one step is 1 on both sides
    assert len(want["state_names"]) == 1 + 2 * 2
    i = want["state_names"].index("[0]['noise_step']")
    assert want["state_first_norms"][i] == got.readings()[
        "state_first_norms"][i] == 1.0
    # the third step saw the first batch again under another draw
    assert len(set(want["losses"])) == 3


def test_bfloat16_where_float32_is_stated_fails_the_same_comparison(seeded):
    ref, model, weights, state, (x, y), (want_loss, want_grads, _), *_ = seeded
    loss, grads, _ = ref.loss_and_grad(weights, state, x, y, model, "bf16")
    got = np.asarray(program.leaf_norms(ref.program_layout(grads)[0]))
    want = np.asarray(program.leaf_norms(ref.program_layout(want_grads)[0]))
    assert check_train.worst_leaf_gap(got, want) > 1e-3
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-2)


def test_eight_shares_routed_parts_are_the_uncut_layer(seeded):
    """The share test: one mixture at SDAR's router (softmax over all 128,
    top 8, renormalised) held whole, against eight chips' shares of 16
    experts each: the routed parts add up to the whole layer's, the counts
    to the whole layer's counts. What every chip computes alike (the
    router) is counted once: it is the same `w_r` in every share, and
    there is no shared expert to add."""
    ref = seeded[0]
    d, fe, e, t = 16, 12, 128, 40
    model = {"num_experts": e, "num_experts_per_tok": 8,
             "experts_held": [0, e]}
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    p = {"w_r": jax.random.normal(ks[0], (d, e)),
         "e_w1": jax.random.normal(ks[1], (e, d, fe)) * 0.3,
         "e_w3": jax.random.normal(ks[2], (e, d, fe)) * 0.3,
         "e_w2": jax.random.normal(ks[3], (e, fe, d)) * 0.3}
    u = jax.random.normal(ks[4], (t, d))
    whole, load, elsewhere = ref.experts(u, p, model, "f32")
    assert float(elsewhere[0]) == 0 and float(load.sum()) == t * 8

    parts, loads = [], []
    for chip in range(8):
        first, end = 16 * chip, 16 * chip + 16
        mine = {**p, **{k: p[k][first:end]
                        for k in ("e_w1", "e_w3", "e_w2")}}
        y, held_load, away = ref.experts(u, mine, model, "f32",
                                         held=(first, end))
        assert float(held_load.sum() + away[0]) == t * 8
        parts.append(y)
        loads.append(held_load)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(loads)),
                                  np.asarray(load))
    # the weights of a position sum to 1 whatever is held (norm_topk_prob)
    _, w = ref.route(u, p["w_r"], model)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


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
    assert len(detail["state_names"]) == 5    # the noise, two mixtures
    assert len(detail["leaf_names"]) == 25
    assert sum("noise_step" in n for n in detail["state_names"]) == 1
    assert "state_first_norms" in detail


def test_the_reference_starts_with_the_program_gone(on_the_cpu, monkeypatch,
                                                    capsys):
    test_reference_start.test_the_reference_starts_with_the_program_gone(
        "toy_sdar", CELL, on_the_cpu, monkeypatch, capsys)


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # 8 of 16 experts held, 3 a position
    assert 0 < m["moe_rows_here_share.tokens"]["value"] < 100
    assert m["moe_load_max_over_mean.tokens"]["value"] >= 1.0
    # no device plane in a CPU trace: the trace readers find nothing there
    # and give nothing, as they do on a program without the scopes
    assert not set(NEW_METRICS) & set(m)
    toy = spec.load_benchmark(TOY)
    assert set(NEW_METRICS) <= {p["name"] for p in toy["per_layer"]}


def test_the_new_metric_files_are_read_by_the_readers_the_benchmark_has():
    for name, reader, scope in (
            ("bd_attn_roofline.tokens", "scope_roofline",
             "flash_attn_bd_fwd"),
            ("bd_noise_ms.tokens", "scope_ms", "bd_noise")):
        lm = spec.layer_metric(name)
        assert (lm["name"], lm["reader"], lm["args"]["scope"]) == (
            name, reader, scope)
        assert lm["moves"] == "train_tokens_per_s"
        assert callable(spec.module("readers", reader).read)
    # every size the counts ask for is in the cell's model or traffic
    _, _, workload, config = spec.load_cell(REAL_CELL)
    ctx = types.SimpleNamespace(config=config, workload=workload)
    least = {}
    for name in ("bd_attn_roofline.tokens", "moe_experts_roofline.tokens"):
        args = spec.layer_metric(name)["args"]
        sizes = {k: scope_roofline._shape(ctx, v)
                 for k, v in args["shapes"].items()}
        assert all(isinstance(v, (int, float)) for v in sizes.values()), name
        fl, nb = spec.module("kernels", args["function"]).flops_and_bytes(
            **sizes)
        assert fl > 0 and nb > 0 and sizes["layers"] == 6
        least[name] = max(fl / 197e12, nb / 819e9) * 1e3
    # the experts see both copies: 8,192 positions a step, 512 expected
    # rows a held expert (the model's seq_len, before the traffic's)
    args = spec.layer_metric("moe_experts_roofline.tokens")["args"]
    assert scope_roofline._shape(ctx, args["shapes"]["tokens"]) == 8192
    assert least["bd_attn_roofline.tokens"] == pytest.approx(8.380, rel=1e-3)
    # 7.06 ms of products, 7.47 ms of bytes: the weights' three passes
    assert 6 * 9 * 2 * 8192 * 2048 * 768 / 197e12 * 1e3 == pytest.approx(
        7.064, rel=1e-3)
    assert least["moe_experts_roofline.tokens"] == pytest.approx(7.467,
                                                                 rel=1e-3)
    # the scope regex the harness builds tells the new kernel from the
    # causal one, and the accepted attn_fwd_ms.tokens reads both
    from benchmark.readers import trace_scope_ms
    causal = "jit(s)/jvp(L02)/attn/flash_attn.fwd/flash_attn_fwd/pallas_call"
    mine = ("jit(s)/jvp(L02)/attn/flash_attn.fwd/flash_attn_fwd/"
            "flash_attn_bd_fwd/pallas_call")
    new = trace_scope_ms.matcher(spec.layer_metric(
        "bd_attn_roofline.tokens")["args"])
    old = trace_scope_ms.matcher(spec.layer_metric(
        "attn_fwd_ms.tokens")["args"])
    assert new(mine) and not new(causal) and old(mine) and old(causal)


#: the toy cell as the chip ran it for `recorded/sdar_toy.xplane.pb` (PR 49,
#: `TPU v5 lite`): sizes the kernels take (T 512, so 1,024 positions in
#: two square tiles of 512 a copy), one layer, three steps, bf16 policy
RECORDED_MODEL = {"n_head": 2, "head_dim": 128, "n_layer": 1,
                  "block_length": 4, "seq_len": 1024}
RECORDED_TRAFFIC = {"batch": 1, "seq_len": 512}


@pytest.fixture(scope="module")
def recorded_obs(tmp_path_factory):
    """`recorded/sdar_toy.xplane.pb` where the profiler would have put
    it, as a reader sees it."""
    root = tmp_path_factory.mktemp("sdar_toy")
    where = root / "plugins" / "profile" / "2026_10_02"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "recorded", "sdar_toy.xplane.pb"),
                where / "host.xplane.pb")
    ctx = types.SimpleNamespace(
        trace_dir=str(root),
        config={"model": RECORDED_MODEL},
        workload={"traffic": RECORDED_TRAFFIC},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        counters_open=None, counters_close=None)
    return {"ctx": ctx, "trace": trace.load(str(root))}


def test_the_new_metric_files_read_a_trace_recorded_on_the_chip(
        recorded_obs, capsys):
    """The three new metric files through the readers the benchmark has,
    on a trace of the toy cell's step recorded on the chip: the scopes
    are there under the names the files look up, and the roofline is the
    function's least time over the scope's self time, by hand."""
    noise = spec.layer_metric("bd_noise_ms.tokens")["args"]
    roof = spec.layer_metric("bd_attn_roofline.tokens")["args"]
    noise_ms = scope_ms.read(recorded_obs, noise)
    kernel_ms = scope_ms.read(recorded_obs, {"scope": roof["scope"]})
    assert 0 < noise_ms < 0.05 and kernel_ms > 0
    # one call a step of the forward kernel under the geometry, and the
    # accepted attn_fwd_ms.tokens reads the same events by the outer scope
    assert scope_ms.read(recorded_obs, spec.layer_metric(
        "attn_fwd_ms.tokens")["args"]) == kernel_ms
    assert scope_ms.read(recorded_obs, {
        "scope": "flash_attn_bd_bwd_fused"}) > kernel_ms
    out = capsys.readouterr().out
    assert "flash_attn.fwd/flash_attn_fwd/flash_attn_bd_fwd/" in out
    assert "L00.BlockDiffusionInput)/bd_noise/" in out
    # 1 layer x 2 heads x (512^2 + 512 x 4) pairs x 2 products x 2 x 128
    flops = 2 * (512 * 512 + 512 * 4) * 2 * 2 * 128
    nbytes = 2 * 1024 * 128 * (3 * 2 + 4)
    least_ms = max(flops / 197e12, nbytes / 819e9) * 1e3
    got = scope_roofline.read(recorded_obs, roof)
    assert got == pytest.approx(100.0 * least_ms / kernel_ms, rel=1e-9)
    assert 0 < got < 100
    assert "bound by memory" in capsys.readouterr().out   # at toy size
    # a program without the scopes (the parent commit) gives nothing
    assert scope_ms.read(recorded_obs, {"scope": "no_such_scope"}) is None
    assert scope_roofline.read(
        recorded_obs, {**roof, "scope": "no_such_scope"}) is None


def test_the_counts_by_hand():
    """`kernels/block_diffusion_attn.py`: one sequence of 16 tokens in
    blocks of 4 (32 positions), 2 heads of 8, one layer."""
    bd = spec.module("kernels", "block_diffusion_attn")
    # clean on clean: 4 blocks, block c sees 4 (c + 1) keys a row: 160;
    # noised on clean: 4 c keys a row: 96; noised on its own block: 64
    assert bd.live_pairs(16, 4) == 160 + 96 + 64 == 16 * 16 + 16 * 4
    fl, nb = bd.flops_and_bytes(batch=1, heads=2, positions=32, block_len=4,
                                head_dim=8, layers=1, in_bytes=2,
                                out_bytes=4)
    assert fl == 2 * 320 * 2 * 2 * 8
    assert nb == 2 * 32 * 8 * (3 * 2 + 4)
    # against one causal call of length T as `kernels/flash_attn.py`
    # counts it: two of them and the diagonal blocks' own width
    one, _ = spec.module("kernels", "flash_attn").flops_and_bytes(
        1, 32, 4096, 32 * 128, 2)
    six, nbytes = bd.flops_and_bytes(1, 32, 8192, 4, 128, 6, 2, 4)
    assert six == pytest.approx(6 * 2 * one, rel=2e-3) and six > 12 * one
    assert six / 197e12 > nbytes / 819e9          # compute-bound


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    model, traffic = config["model"], workload["traffic"]
    kern = spec.module("kernels", config["flops"])
    flops = kern.train_flops_per_unit(model, traffic)
    d = 2048
    proj = 2 * 2 * (2 * d * 4096 + 2 * d * 512)       # two positions a token
    scores = 2 * 2 * 4096 * (4096 + 4)
    mixture = 2 * (2 * d * 128 + 8 * 16 / 128 * 2 * 3 * d * 768)
    head = 2 * d * 18992
    assert flops == 3 * (6 * (proj + scores + mixture) + head)
    # ISSUE 49's 1,053 M a token forward, and its parts
    parts = kern.forward_parts_per_token(model, traffic)
    total = sum(parts.values())
    assert total == pytest.approx(1053e6, rel=1e-3)
    assert parts["attn_scores"] / total == pytest.approx(0.38, abs=0.005)
    assert parts["attn_projections"] / total == pytest.approx(0.43,
                                                              abs=0.005)
    assert (parts["held_experts"] + parts["routers"]) / total == \
        pytest.approx(0.11, abs=0.005)
    assert parts["head"] / total == pytest.approx(0.07, abs=0.005)
    assert flops * 4096 == pytest.approx(12.94e12, rel=1e-3)    # a step


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "SDAR-30B-A3B-Chat":
                return row
    return None


PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False}


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
                                   "num_experts": 128, "vocab_size": 151936}
    m, args = config["model"], config["program"]["args"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (
        m["n_layer"], m["experts_held"][1] - m["experts_held"][0],
        m["vocab_size"]) == (6, 16, 18992)
    # floors: at least four layers, 8 experts, an eighth of the vocabulary
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert m["num_experts"] == config["published"]["num_experts"]
    assert m["layer_types"] == ["full_attention"] * 6
    assert m["num_dense_layers"] == 0
    assert m["mask_token_id"] == m["vocab_size"] - 1 == args["mask_id"]
    assert (m["n_embd"], m["n_head"], m["n_kv_head"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts"],
            m["num_experts_per_tok"], m["experts_held"], m["vocab_size"],
            m["norm_eps"], m["rope_theta"], m["n_layer"], m["block_length"],
            m["noise_seed"], m["noise_eps"]) == (
        args["d_model"], args["n_heads"], args["n_kv_heads"],
        args["head_dim"], args["expert_width"], args["n_experts"],
        args["top_k"], args["experts_held"], args["vocab_size"],
        args["norm_eps"], args["rope_theta"], args["n_layers"],
        args["block_len"], args["noise_seed"], args["noise_eps"])
    assert (m["n_embd"], m["n_head"], m["n_kv_head"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["norm_eps"], m["rope_theta"], m["n_positions"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_intermediate_size"], config["num_experts_per_tok"],
        config["rms_norm_eps"], config["rope_theta"],
        config["max_position_embeddings"])
    # the positions a sequence takes through the layers, for the readers
    assert m["seq_len"] == 2 * workload["traffic"]["seq_len"] == \
        2 * args["seq_len"]
    assert set(config["assumed"]) >= {
        "block_length", "noise", "mask_token", "prediction_alignment",
        "qk_norm", "rotary_pairing", "routing_precision", "initializer",
        "optimizer", "precision"}
    assert len(config["departures"]) == 5
    assert any("clean copy's last layer" in d for d in config["departures"])
    assert any("counter" in d for d in config["departures"])
    assert "8 chips share each layer" in config["stands_for"]
    assert f"{PARAMETERS:,} parameters" in config["stands_for"]
    assert config["parameters_held"] == PARAMETERS
    # the recompute chosen, with the three compiled sizes beside it
    assert args["recompute_experts"] is True
    for reading in ("16.94", "14.18", "9.98"):
        assert reading in config["recompute"]
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_denoise", "pool": 2, "batch": 1, "seq_len": 4096}
    assert workload["runner"] == "train_rounds"
    assert set(workload["limits"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_median_gap",
        "update_norm_gap", "state_first_norm_gap"}
    # every leaf stands in the check, the six routers among them: with the
    # embedding at the scale the file states, positions route apart
    assert "check_leaves_left_out" not in config
    assert config["model"]["embedding_std"] == 4.0
    assert "N(0, 4)" in config["assumed"]["initializer"]
    for reading in ("16,123.9", "13,770.3"):   # why not the network's option
        assert reading in config["recompute"]
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
        assert listing[name] == [REAL_CELL], name
    reported = {p["name"] for p in
                spec.cell_metrics(bench, REAL_CELL, "per_layer")}
    older = {"gpt2m-train-t1024", "ouro-train-t2048", "lfm2-train-t8192",
             "qwen3next-train-t4096"}
    shared = {name for name, cells in listing.items() if older <= set(cells)}
    assert len(shared) >= 18 and shared <= reported
    assert {"attn_fwd_ms.tokens", "attn_bwd_ms.tokens", "mfu.tokens",
            "hbm_peak_gib.tokens", "device_idle_share.tokens",
            "step_loss_ms.tokens"} <= shared
    assert {"moe_ms.tokens", "moe_route_ms.tokens", "moe_experts_ms.tokens",
            "moe_experts_roofline.tokens", "moe_rows_here_share.tokens",
            "moe_load_max_over_mean.tokens"} <= reported
    # kernels and scopes this step does not run
    assert not {"moe_experts_ungated_roofline.tokens",
                "flash_attn_fwd_roofline", "moe_shared_ms.tokens",
                "flash_attn_w256_roofline.tokens", "gdn_ms.tokens",
                "ssm_ms.tokens", "short_conv_ms.tokens", "mla_ms.tokens",
                "mtp_ms.tokens"} & reported
    rate, = [m for m in bench["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    assert REAL_CELL in rate["workloads"]
    assert {m["name"] for m in spec.cell_metrics(
        bench, REAL_CELL, "end_to_end")} == {"train_tokens_per_s",
                                             "setup_s"}
    assert [w["name"] for w in bench["workloads"]].count(REAL_CELL) == 1
    assert len(bench["workloads"]) == 8


def test_the_parameters_held_here_are_counted_from_the_shapes():
    """645,623,296 parameters at the cell's sizes, from shapes alone."""
    _, _, _, config = spec.load_cell(REAL_CELL)
    ref = spec.module("reference", config["reference"])
    shapes = jax.eval_shape(lambda: ref.init(1, config["model"]))
    d = 2048
    attn = 2 * d * 4096 + 2 * d * 512 + 2 * 128
    layer = attn + 2 * d + d * 128 + 16 * 3 * d * 768
    assert attn == 18_874_624 and layer == 94_638_336
    assert [_count(l) for l in shapes["layers"]] == [layer] * 6
    assert _count(shapes) == PARAMETERS == 6 * layer + 2 * 18992 * d + d
    # the program's own tree, from the factory, holds the same
    prog = config["program"]
    conf = program._resolve(prog["factory"])(**prog["args"])
    net = program._resolve(prog["net"])(conf)
    own = jax.eval_shape(lambda: net.init()[0])
    assert _count(own) == PARAMETERS
    state = jax.eval_shape(lambda: ref.init_state(config["model"]))
    assert set(state["noise"]) == {"noise_step"}
    assert [set(s) for s in state["layers"]] == [
        {"moe_load", "moe_elsewhere"}] * 6
    # the five-layer fallback ISSUE 49 names was not needed
    assert PARAMETERS - layer == 550_984_960


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
    with open(spec.module("reference", "sdar_moe").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text
    assert 'precision="highest"' in text
    # the draw is written out here, and the head reads the noised rows
    assert text.count("jax.random.uniform(") == 2
    assert "fold_in(jax.random.PRNGKey(model[\"noise_seed\"]), step)" in text
    assert "_mm(h[:t], params[\"head_w\"], precision)" in text
