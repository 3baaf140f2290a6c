"""The spans at StepDriver's boundaries: one ``fit.round`` a round, a
``fit.next`` / ``fit.etl`` / ``fit.step`` / ``fit.dispatch`` /
``fit.score_fetch`` each dispatch, one ``fit.sync``; children inside their
parents on one thread; the lite (ParallelTrainer) path leaves ``fit.next``
and ``fit.dispatch``; with telemetry off nothing is recorded."""

import collections
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.continuous.driver import StepDriver
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.telemetry import tracing


@pytest.fixture(autouse=True)
def _isolate():
    telemetry.reset()
    telemetry.disable()
    yield
    telemetry.reset()
    telemetry.disable()


def _driver(**kw):
    net = MultiLayerNetwork(NeuralNetConfig(
        seed=0, updater=U.Sgd(learning_rate=0.1)).list(
        L.DenseLayer(n_out=8, activation="tanh"),
        L.OutputLayer(n_out=2, loss="mcxent"),
        input_type=I.FeedForwardType(4)))
    net.init()
    x = np.ones((8, 4), np.float32)
    y = np.eye(2, dtype=np.float32)[np.zeros(8, int)]
    return StepDriver(net, lambda: itertools.cycle([(x, y, None)]), **kw)


def _events():
    return [e for e in telemetry.get_tracer().chrome_trace()["traceEvents"]
            if e["ph"] == "X"]


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-3)


def test_a_round_and_a_sync_leave_every_boundary_span():
    telemetry.enable()
    drv = _driver()
    drv.run_round(3)
    drv.sync()
    evs = _events()
    count = collections.Counter(e["name"] for e in evs)
    assert {n: count[n] for n in (
        "fit.round", "fit.next", "fit.etl", "fit.step", "fit.dispatch",
        "fit.score_fetch", "fit.sync")} == {
        "fit.round": 1, "fit.next": 3, "fit.etl": 3, "fit.step": 3,
        "fit.dispatch": 3, "fit.score_fetch": 3, "fit.sync": 1}
    assert len({e["tid"] for e in evs}) == 1
    rnd = next(e for e in evs if e["name"] == "fit.round")
    sync = next(e for e in evs if e["name"] == "fit.sync")
    steps = [e for e in evs if e["name"] == "fit.step"]
    for e in evs:
        if e["name"] in ("fit.next", "fit.etl", "fit.step"):
            assert _inside(e, rnd), e["name"]
        if e["name"] in ("fit.dispatch", "fit.score_fetch"):
            assert sum(_inside(e, s) for s in steps) == 1, e["name"]
    assert sync["ts"] >= rnd["ts"] + rnd["dur"]


class _LiteEngine:
    """What the round loop needs of a ParallelTrainer engine."""
    fused = False

    def __init__(self, net):
        self.net = net

    def build_source(self, batch_factory):
        return batch_factory()

    def dispatch(self, item):
        self.net.score_value = jnp.float32(0.5)
        return self.net.score_value, 1, {}

    def fan(self, score, meta):
        pass


def test_the_lite_path_leaves_next_and_dispatch():
    telemetry.enable()
    net = _driver().net
    drv = StepDriver(net, lambda: itertools.cycle([None]),
                     engine=_LiteEngine(net), instrumented=False)
    assert drv.run_round(2).steps == 2
    drv.sync()
    # the fit loop's own spans: `net.init` and whatever compiled lie beside
    count = collections.Counter(e["name"] for e in _events()
                                if e["name"].startswith("fit."))
    assert count == {"fit.round": 1, "fit.next": 2, "fit.dispatch": 2,
                     "fit.sync": 1}


def test_off_the_span_is_the_shared_null_span_and_nothing_is_kept():
    assert telemetry.span("fit.next") is tracing._NULL_SPAN
    with telemetry.span("fit.score_fetch") as sp:
        pass
    t0, t1 = sp.interval()
    assert t0 == t1
    drv = _driver()
    drv.run_round(3)
    drv.sync()
    drv.net.fit(np.ones((8, 4), np.float32),
                np.eye(2, dtype=np.float32)[np.zeros(8, int)], epochs=2)
    assert telemetry.get_tracer().chrome_trace()["traceEvents"] == []


def test_score_fetch_files_one_pair_of_clock_reads_in_both_records():
    """The program's `fit.score_fetch` span and the causal trace's
    `train.score_fetch` record are the same two clock reads."""
    telemetry.enable()
    drv = _driver()
    drv.run_round(3)
    drv.sync()
    mine = [e["dur"] * 1e-6 for e in _events()
            if e["name"] == "fit.score_fetch"]
    theirs = [s["dur_s"]
              for ring in telemetry.tracectx.get_ring().snapshot().values()
              for doc in ring for s in doc["spans"]
              if s["name"] == "train.score_fetch"]
    assert theirs and len(theirs) <= len(mine)
    for dur in theirs:
        assert any(abs(dur - d) < 2e-9 for d in mine), (dur, mine)
