"""The two plain references against the system at toy size on the CPU
(forward loss and first-gradient norms), and the control (the reference in
fp8 in the program's place) coming out not correct."""

import os

import jax
import numpy as np
import pytest

from benchmark import check_train, control, program, spec
from deeplearning4j_tpu.utils import dtypes

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELLS = ["toy-gpt2-train", "toy-resnet50-train"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_caught(cell, capsys, tmp_path):
    rc = control.main(["--workload", cell, "--seeds", "1", "2"],
                      root=TOY, out_dir=str(tmp_path))
    out = capsys.readouterr().out
    assert rc == 0 and "control caught" in out
    dtypes.f32_policy()


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_the_system(cell):
    """Forward loss and first-gradient norms, the system under the
    float32 policy against the reference, on the same seeded weights."""
    _, _, workload, config = spec.load_cell(cell, TOY)
    ref = spec.module("reference", config["reference"])
    model = config["model"]
    try:
        net = program.build(config, 11)
        weights = ref.init(11, model)
        program.load_weights(net, *ref.program_layout(
            weights, ref.init_state(model)))
        traffic = spec.module("traffic", workload["traffic"]["kind"]).make(
            11, workload["traffic"], model)
        (x, y_hot), (_, y) = traffic["feed"][0], traffic["plain"][0]
        want_loss, want_grads, _ = ref.loss_and_grad(
            weights, ref.init_state(model), x, y, model)
        fx, fy, _ = program.feed_item(net, x, y_hot)
        loss, _, grads = net.compute_gradients(
            net.params, net.state, fx, fy, rng=jax.random.PRNGKey(0))
        assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
        got = np.asarray(program.leaf_norms(grads))
        want = np.asarray(program.leaf_norms(
            ref.program_layout(want_grads)[0]))
        assert check_train.worst_leaf_gap(got, want) < 0.02
    finally:
        dtypes.f32_policy()


def _readings(grad, update, state=None):
    return {"losses": [2.0, 1.9, 1.8], "grad_norms": np.array(grad),
            "update_norms": np.array(update),
            "state_first_norms": None if state is None else np.array(state),
            "leaf_names": ["['a']['W']", "['a']['bk']", "['b']['W']"],
            "state_names": None if state is None else ["['bn']['var']"]}


def test_the_cell_limits_name_the_numbers_that_decide(capsys):
    want = _readings([1.0, 1.0, 1.0], [1.0, 1.0, 1.0])
    got = _readings([1.0, 1.0, 1.2], [1.0, 1.5, 1.0])
    rows = check_train.compare(got, want, {"loss_gap": 0.01,
                                           "grad_norm_gap": 0.1})
    assert [(n, ok) for n, _, _, ok in rows] == [("loss_gap", True),
                                                 ("grad_norm_gap", False)]
    out = capsys.readouterr().out
    assert "not compared in this cell: update_norm_gap = 0.5" in out
    with pytest.raises(KeyError, match="no_such_gap"):
        check_train.compare(got, want, {"no_such_gap": 1.0})


def test_a_left_out_leaf_counts_in_the_median_and_not_in_the_worst():
    want = _readings([1.0, 1.0, 1.0], [1.0, 1.0, 1.0])
    got = _readings([1.0, 1.0, 1.0], [1.01, 1.5, 1.02])
    limits = {"update_norm_gap": 0.1, "update_norm_median_gap": 0.1}
    rows = dict((n, v) for n, v, *_ in check_train.compare(got, want, limits))
    assert rows["update_norm_gap"] == pytest.approx(0.5)
    rows = dict((n, v) for n, v, *_ in check_train.compare(
        got, want, limits, {"update_norms": ["bk"]}))
    assert rows["update_norm_gap"] == pytest.approx(0.02)
    assert rows["update_norm_median_gap"] == pytest.approx(0.02)


def test_state_numbers_exist_only_where_the_model_carries_state():
    stateless = _readings([1.0] * 3, [1.0] * 3)
    with pytest.raises(KeyError, match="state_first_norm_gap"):
        check_train.compare(stateless, stateless,
                            {"state_first_norm_gap": 0.1})
    want = _readings([1.0] * 3, [1.0] * 3, state=[2.0])
    got = _readings([1.0] * 3, [1.0] * 3, state=[2.1])
    (name, value, _, ok), = check_train.compare(
        got, want, {"state_first_norm_gap": 0.01})
    assert (name, ok) == ("state_first_norm_gap", False)
    assert value == pytest.approx(0.05)
    # a program that kept no state reading where the reference has one
    got["state_first_norms"] = None
    (_, value, _, ok), = check_train.compare(
        got, want, {"state_first_norm_gap": 0.01})
    assert not ok and value == float("inf")
