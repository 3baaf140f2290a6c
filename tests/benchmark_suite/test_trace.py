"""The reduction from a trace to numbers, on made-up intervals and on a
small trace recorded on the chip (`recorded/tiny.xplane.pb`)."""

import os

import pytest

from benchmark import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert trace.union_length(iv) == 30
    assert trace.union_length([]) == 0
    assert trace.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert trace.gaps(iv, 8, 35) == [(20, 30)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def _made_up():
    ops = [("fusion.1", 100, 400), ("custom-call.7", 400, 600),
           ("fusion.1", 900, 1000), ("fusion.2", 50, 90)]  # last: before
    spans = [("bench.fit_round", 100, 700), ("bench.fit_sync", 700, 1100)]
    return trace.Trace({"/device:TPU:0": ops}, spans)


def test_busy_idle_kernel_time_and_gap_attribution():
    tr = _made_up()
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.busy_s == pytest.approx(600e-9)
    assert tr.idle_share == pytest.approx(0.4)
    assert tr.op_durations("custom-call") == [pytest.approx(200e-9)]
    assert tr.top_ops(2) == [("fusion.1", pytest.approx(400e-9)),
                             ("custom-call.7", pytest.approx(200e-9))]
    # 600..900 is split by no op; its middle (750) lies in fit_sync
    assert tr.idle_gaps(2) == [("bench.fit_sync", pytest.approx(300e-9)),
                               ("bench.fit_sync", pytest.approx(100e-9))]
    bd = tr.breakdown()
    assert len(bd["device_ops"]) == 2 and len(bd["idle_gaps"]) == 2


def test_two_chips_average():
    tr = trace.Trace({"/device:TPU:0": [("a", 0, 100)],
                      "/device:TPU:1": [("a", 0, 50)]},
                     [("bench.fit_round", 0, 100)])
    assert tr.busy_s == pytest.approx(75e-9)
    assert tr.top_ops(1) == [("a", pytest.approx(75e-9))]


def test_no_trace_and_no_device_plane_give_nothing(tmp_path):
    assert trace.load(str(tmp_path)) is None


def test_op_label_keeps_the_operation_and_drops_its_operands():
    assert trace.op_label(
        '%fusion.7 = bf16[]{:T(256)} fusion(bf16[8,8]{1,0} %custom-call.1), '
        'kind=kOutput, calls=%fused_computation.1') == \
        "fusion.7 fusion:kOutput bf16[]{:T(256)}"
    assert trace.op_label(
        '%jvp.4 = (f32[64,1024,128]{2,1,0}, f32[64,1024]{1,0}) custom-call('
        'f32[64,1024,128]{2,1,0} %q), custom_call_target="tpu_custom_call"'
    ).startswith("jvp.4 custom-call:tpu_custom_call (f32[64,1024,128]")
    assert trace.op_label("no equals sign") == "no equals sign"


def test_reduction_of_a_trace_recorded_on_the_chip():
    """`recorded/tiny.xplane.pb`: TPU v5 lite, three rounds of two small
    jitted calls each between `bench.fit_round` / `bench.fit_sync` spans, a
    2 ms sleep in the second sync. Every number below was worked out by
    hand from the events' own start and duration columns."""
    import jax
    tr = trace.Trace.from_profile(jax.profiler.ProfileData.from_file(
        os.path.join(RECORDED, "tiny.xplane.pb")))
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    assert len(ops) == 18 and len(tr.spans) == 6  # 6 calls x 3 ops
    # the window is the extent of the benchmark's spans
    assert (tr.t0, tr.t1) == (47065168.0, 53505468.0)
    assert tr.window_s == pytest.approx(6440300e-9)
    # the first call ran 36 us before the first span opened (the device's
    # clock leads the host's by a tenth of a millisecond or so): clipped
    # out; the other five take 13 + 3 (2 once) + 11878 (11879 once) ns each
    assert tr.busy_s == pytest.approx(59470e-9)
    assert tr.idle_share == pytest.approx(1 - 59470 / 6440300)
    assert tr.op_durations("fusion:kOutput") == [
        pytest.approx(d) for d in (11878e-9,) * 4 + (11879e-9,)]
    assert tr.op_durations("custom-call") == []
    name, seconds = tr.top_ops(1)[0]
    assert name.startswith("fusion fusion:kOutput")
    assert seconds == pytest.approx(59391e-9)
    # the longest gap, 48531475 -> 52397701, lies in the sync that slept
    assert tr.idle_gaps(1) == [("bench.fit_sync", pytest.approx(3866226e-9))]
