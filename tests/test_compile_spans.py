"""Set-up's spans and marks (ISSUE 36): jax's compile phases as
``compile.*`` events in the tracer's buffer, ``net.init`` as a span of both
nets, and ``compile_cache.startup_marks()``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring as _monitoring

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
from deeplearning4j_tpu.nn.graph import ComputationGraph, GraphBuilder
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import compile_cache as cc


@pytest.fixture(autouse=True)
def _isolate():
    telemetry.reset()
    cc.enable_persistent_cache()
    yield
    telemetry.reset()
    telemetry.disable()


def _multilayer():
    return MultiLayerNetwork(
        NeuralNetConfig(seed=3, updater=U.Adam(learning_rate=1e-3)).list(
            L.DenseLayer(n_out=8, activation="relu"),
            L.OutputLayer(n_out=2, loss="mcxent"),
            input_type=I.FeedForwardType(4)))


def _graph():
    return ComputationGraph(
        GraphBuilder(updater=U.Adam(learning_rate=1e-3), seed=3)
        .add_inputs("in")
        .set_input_types(I.FeedForwardType(4))
        .add_layer("d1", L.DenseLayer(n_out=8, activation="tanh"), "in")
        .add_layer("out", L.OutputLayer(n_out=2, loss="mcxent"), "d1")
        .set_outputs("out")
        .build())


NETS = pytest.mark.parametrize("make", [_multilayer, _graph],
                               ids=["multilayer", "graph"])


def _events():
    return [e for e in telemetry.get_tracer().chrome_trace()["traceEvents"]
            if e["ph"] == "X"]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def _fresh_jitted():
    """Two jitted functions nobody has called, one traced inside the
    other's trace."""
    @jax.jit
    def inner_of_the_test(x):
        return jnp.tanh(x) * 3.0

    @jax.jit
    def outer_of_the_test(x):
        return inner_of_the_test(x) + 1.0

    return outer_of_the_test


def test_a_fresh_jit_call_leaves_its_phases_inside_the_enclosing_span():
    telemetry.enable()
    x = jnp.arange(6.0)  # made outside the span: its own compiles lie there
    with telemetry.span("around"):
        _fresh_jitted()(x).block_until_ready()
    events = _events()
    around = next(e for e in events if e["name"] == "around")
    mine = {n: [e for e in events if e["name"] == n
                and "of_the_test" in e["args"]["fun"]]
            for n in ("compile.trace", "compile.lower", "compile.backend")}
    assert [e["args"]["fun"] for e in mine["compile.trace"]] == [
        "inner_of_the_test", "outer_of_the_test"]  # in order of their ends
    assert [e["args"]["fun"] for e in mine["compile.lower"]] == [
        "jit(outer_of_the_test)"]
    assert [e["args"]["fun"] for e in mine["compile.backend"]] == [
        "jit(outer_of_the_test)"]
    for group in mine.values():
        for e in group:
            assert e["dur"] > 0 and _inside(e, around)
    inner, outer = mine["compile.trace"]
    assert _inside(inner, outer)
    trace_end = outer["ts"] + outer["dur"]
    lower, backend = mine["compile.lower"][0], mine["compile.backend"][0]
    assert trace_end <= lower["ts"] + lower["dur"] <= backend["ts"] + 1.0


def test_with_telemetry_off_the_buffer_stays_empty():
    assert not telemetry.enabled()
    with telemetry.span("around"):
        _fresh_jitted()(jnp.arange(6.0)).block_until_ready()
    assert telemetry.get_tracer().chrome_trace()["traceEvents"] == []


def test_two_calls_of_enable_persistent_cache_leave_one_listener():
    before = _monitoring.get_event_duration_listeners()
    cc.enable_persistent_cache()
    cc.enable_persistent_cache()
    after = _monitoring.get_event_duration_listeners()
    assert after == before
    assert after.count(cc._compile_listener) == 1


def test_an_event_jax_does_not_time_as_a_compile_phase_leaves_nothing():
    telemetry.enable()
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 1.5)
    assert _events() == []
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    (load,) = _events()
    assert load["name"] == "compile.cache_load" and "args" not in load
    assert load["dur"] == pytest.approx(0.25e6)
    tracer = telemetry.get_tracer()
    assert load["ts"] + load["dur"] <= tracer.now_us()


@NETS
def test_init_leaves_one_net_init_span(make):
    net = make()  # its seed's key compiles here, outside the span
    telemetry.enable()
    net.init()
    (span,) = [e for e in _events() if e["name"] == "net.init"]
    assert span["dur"] > 0
    # whatever the weights' draws compiled lies inside it
    assert all(_inside(e, span) for e in _events()
               if e["name"].startswith("compile."))


@NETS
def test_first_step_is_stamped_by_the_first_dispatch_and_not_before(make):
    marks = cc.startup_marks()
    assert set(marks) == {"process_start", "program_entered"}
    assert marks["process_start"] <= marks["program_entered"]
    net = make()
    net.init()
    assert "first_step" not in cc.startup_marks()
    rs = np.random.RandomState(0)
    x = rs.rand(8, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 8)]
    net.fit(x, y, epochs=1)
    marks = cc.startup_marks()
    assert marks["program_entered"] < marks["first_step"]
    assert marks["first_step"] == pytest.approx(
        marks["process_start"] + cc.first_marks()["step"] / 1e3)
    assert cc.status()["startup_marks"] == marks
    first = marks["first_step"]
    net.fit(x, y, epochs=1)
    assert cc.startup_marks()["first_step"] == first  # once a process
