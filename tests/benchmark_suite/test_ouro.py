"""The Ouro configuration's benchmark files (ISSUE 27) at toy size on the
CPU, through a tree of their own (`toy_ouro/`): the plain reference against
the system (loss, first-gradient norms, `output()`), the fp8 control caught,
a run through the real entry point, a faulty loop coming out not correct,
and the data files' arithmetic."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_train, control, peaks, program, run, spec
from deeplearning4j_tpu.nn.layers import looped
from deeplearning4j_tpu.utils import dtypes

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_ouro")
CELL = "toy-ouro-train"
REAL_CELL = "ouro-train-t2048"


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
    weights laid over it, one batch, and the reference's loss and
    gradients on it."""
    _, _, workload, config = spec.load_cell(CELL, TOY)
    ref = spec.module("reference", config["reference"])
    model = config["model"]
    try:
        net = program.build(config, 11)
        weights = ref.init(11, model)
        program.load_weights(net, *ref.program_layout(
            weights, ref.init_state(model)))
        traffic = spec.module("traffic", workload["traffic"]["kind"]).make(
            11, workload["traffic"], model)
        x, y = traffic["feed"][0]
        want = ref.loss_and_grad(weights, None, x, y, model)
        fx, fy, _ = program.feed_item(net, x, y)
        got = net.compute_gradients(net.params, net.state, fx, fy,
                                    rng=jax.random.PRNGKey(0))
        out = np.asarray(net.output(x))
        yield ref, model, weights, x, want, got, out
    finally:
        dtypes.f32_policy()


def test_the_systems_loss_is_the_references(seeded):
    _, _, _, _, (want_loss, _, _), (loss, _, _), _ = seeded
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)


def test_the_systems_first_gradient_is_the_references_leaf_by_leaf(seeded):
    ref, _, _, _, (_, want_grads, _), (_, _, grads), _ = seeded
    got = np.asarray(program.leaf_norms(grads))
    want = np.asarray(program.leaf_norms(ref.program_layout(want_grads)[0]))
    assert got.shape == want.shape == (23,)
    assert np.all(want > 0)  # no leaf whose exact gradient is zero
    assert check_train.worst_leaf_gap(got, want) < 0.02


def test_output_is_the_references_last_pass_softmax(seeded):
    ref, model, weights, x, _, _, out = seeded
    logits = jax.jit(lambda w, tok: ref.logits_one(w, tok, model))
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
    assert os.path.isfile(os.path.join(on_the_cpu, f"check-{CELL}.json"))


def test_a_traced_run_prints_the_per_layer_metrics(on_the_cpu, capsys):
    line = _run(capsys, on_the_cpu, 7, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["fit_recompiles.tokens"]["value"] == 0
    assert m["mfu.tokens"]["value"] > 0
    # no device plane in a CPU trace: the trace readers find nothing
    assert "loop_fwd_ms.tokens" not in m and "exit_head_ms.tokens" not in m


def test_a_loop_that_drops_one_passes_gradient_is_not_correct(
        on_the_cpu, capsys, monkeypatch):
    """A program that drops the first pass's contribution to the shared
    leaves' gradient: the forward is untouched, so the first loss is
    right, and the gradient's norms give it away."""
    real = looped.LoopedStack._one_pass
    calls = []

    def faulty(self, params, h, rng, mask, train):
        calls.append(0)
        if len(calls) % self.passes == 1 and train:
            params = jax.lax.stop_gradient(params)
        return real(self, params, h, rng, mask, train)

    monkeypatch.setattr(looped.LoopedStack, "_one_pass", faulty)
    line = _run(capsys, on_the_cpu, 3)
    assert line["correct"] is False


def test_required_operations_at_the_cells_sizes():
    _, _, workload, config = spec.load_cell(REAL_CELL)
    flops = spec.module("kernels", config["flops"]).train_flops_per_unit(
        config["model"], workload["traffic"])
    assert flops == pytest.approx(7.751e9, rel=1e-3)
    heads = 3 * 4 * 2 * 2048 * 49152
    assert heads / flops == pytest.approx(0.3117, rel=1e-3)
    deep = dict(config["model"], n_layer=48)
    assert heads / spec.module("kernels", "ouro").train_flops_per_unit(
        deep, workload["traffic"]) == pytest.approx(0.036, abs=1e-3)


def test_lm_tokens_feeds_integer_next_token_labels():
    make = spec.module("traffic", "lm_tokens").make
    p, model = {"pool": 2, "batch": 3, "seq_len": 8}, {"vocab_size": 50}
    a, b = make(2 ** 31 + 5, p, model), make(2 ** 31 + 5, p, model)
    assert a["units_per_batch"] == 24 and len(a["feed"]) == 2
    assert a["feed"] is a["plain"] or a["feed"] == a["plain"]
    for (x, y), (x2, y2) in zip(a["feed"], b["feed"]):
        assert x.dtype == y.dtype == jnp.int32 and x.shape == y.shape == (3, 8)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))
        np.testing.assert_array_equal(np.asarray(x[:, 1:]),
                                      np.asarray(y[:, :-1]))
        assert 0 <= int(x.min()) and int(x.max()) < 50
    other = make(2 ** 31 + 6, p, model)
    assert not np.array_equal(np.asarray(a["feed"][0][0]),
                              np.asarray(other["feed"][0][0]))


PUBLISHED = {"hidden_size": 2048, "num_attention_heads": 16,
             "num_key_value_heads": 16, "head_dim": 128,
             "intermediate_size": 5632, "vocab_size": 49152,
             "total_ut_steps": 4, "rope_theta": 1000000,
             "rms_norm_eps": 1e-06, "max_position_embeddings": 65536,
             "early_exit_threshold": 1}


def test_the_configuration_keeps_every_published_width():
    bench, cell, workload, config = spec.load_cell(REAL_CELL)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == config["model"]["n_layer"] >= 4
    m, args = config["model"], config["program"]["args"]
    assert (m["n_embd"], m["n_head"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"], m["total_ut_steps"], m["n_layer"]) == (
        args["d_model"], args["n_heads"], args["head_dim"], args["ffn_width"],
        args["vocab_size"], args["passes"], args["n_layers"])
    assert (m["rope_theta"], m["rms_norm_eps"], m["exit_entropy_beta"]) == (
        args["rope_theta"], args["norm_eps"], args["beta"])
    assert config["recompute"].startswith("none") and "recompute" not in args
    assert set(config["assumed"]) >= {"sandwich_norms", "exit_gate",
                                      "objective", "initializer", "optimizer"}
    assert cell["chips"] == 1 and workload["traffic"] == {
        "kind": "lm_tokens", "pool": 2, "batch": 2, "seq_len": 2048}
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]


def test_the_reference_imports_nothing_from_the_program():
    with open(spec.module("reference", "ouro").__file__,
              encoding="utf-8") as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text
    assert 'precision="highest"' in open(
        spec.module("reference", "lowp").__file__, encoding="utf-8").read()
