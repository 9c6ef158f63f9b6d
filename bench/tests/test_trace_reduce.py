"""The reduction from a profiler trace to device metrics.

``data/modmatmul_small.xplane.pb`` is a trace recorded on one TPU v5e of
two calls of the Pallas kernel, ``[3, 8, 256] @ [3, 256, 600]`` (N padded
to 640 by the kernel's 128-wide tiles), inside a ``bench.window``
annotation.
"""
import os

import pytest

from bench import counts, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "modmatmul_small.xplane.pb")

HLO = (
    "%modmatmul_pallas.3 = s32[8,21,3317760]{2,1,0:T(8,128)} custom-call("
    "s32[21,6]{1,0:T(8,128)S(1)} %copy.25, s32[8,6,3317760]{2,1,0:T(8,128)} "
    "%get-tuple-element.38), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={s32[21,6]{1,0}, s32[8,6,3317760]{2,1,0}}"
)


def test_union_and_gaps_clip_to_the_window():
    iv = [(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)]
    assert trace_reduce.union_length(iv, 0, 25) == 3 + 7 + 5
    assert trace_reduce.union_length(iv, 2.5, 6) == 0.5 + 1
    assert trace_reduce.gaps(iv, 0, 25) == [(3, 5), (12, 20)]
    assert trace_reduce.gaps(iv, -1, 31) == [(-1, 0), (3, 5), (12, 20), (30, 31)]
    assert trace_reduce.gaps([], 0, 4) == [(0, 4)]


def test_hlo_text_gives_the_instruction_and_its_operands():
    assert trace_reduce.instruction(HLO) == "modmatmul_pallas"
    assert trace_reduce.op_label(HLO) == "modmatmul_pallas.3 [8,21,3317760]"
    assert trace_reduce.custom_call_operands(HLO) == [(21, 6), (8, 6, 3317760)]
    assert trace_reduce.custom_call_operands("%fusion.1 = s32[4] fusion(s32[4] %x)") == []


def test_device_trace_busy_share_and_named_events():
    dt = trace_reduce.DeviceTrace(window=(100.0, 200.0), ops={
        0: [("%a.1 = f32[2] add()", 110, 130), ("%modmatmul_pallas.2 = s32[1] x", 120, 150),
            ("%b = f32[2] mul()", 190, 220)],
        1: [("%a.1 = f32[2] add()", 100, 110)],
    })
    assert dt.window_s == pytest.approx(1e-7)
    assert dt.busy_s([0]) == pytest.approx(50e-9)
    assert dt.busy_s() == pytest.approx(30e-9)  # (50 + 10) / 2 chips
    assert dt.events_named("modmatmul_pallas") == [(0, "%modmatmul_pallas.2 = s32[1] x", 30)]
    assert dt.idle_gaps(0) == [(100.0, 110), (150, 190)]
    top = dict(dt.top_ops())
    assert top["a.1 [2]"] == pytest.approx(30e-9)
    assert top["b [2]"] == pytest.approx(10e-9)


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [("serve.run", 1.0, 5.0), ("runtime.replay", 2.0, 4.0), ("bench.traffic", 0.0, 6.0)]
    gaps_ns = [(1.5e9, 1.7e9), (2.5e9, 3.5e9), (5.5e9, 5.6e9), (7e9, 8.5e9)]
    got = trace_reduce.attribute_gaps(gaps_ns, spans, offset_ns=0.0)
    assert got == [
        ["outside any span", pytest.approx(1.5)],
        ["runtime.replay", pytest.approx(1.0)],
        ["serve.run", pytest.approx(0.2)],
        ["bench.traffic", pytest.approx(0.1)],
    ]
    shifted = trace_reduce.attribute_gaps([(11.5e9, 11.7e9)], spans, offset_ns=10e9)
    assert shifted[0][0] == "serve.run"


def test_device_clock_is_moved_onto_the_host_clock():
    # (chip, run) -> device start; the host enqueued each run before it began
    starts = {(0, 8): 100.0, (0, 9): 500.0, (1, 8): 90.0, (1, 7): 10.0}
    enqueued = {(0, 8): 1500.0, (0, 9): 1800.0, (1, 8): 95.0}
    # chip 0: run 8 bounds the offset by 1400, run 9 (queued) by 1300
    assert trace_reduce.clock_offsets(starts, enqueued) == {0: 1400.0, 1: 5.0}
    assert trace_reduce.clock_offsets(starts, {}) == {}


def test_recorded_tpu_trace():
    dt = trace_reduce.read_trace(XPLANE)
    assert dt.chips == [0]
    assert 0 < dt.busy_s() < dt.window_s
    # both calls lie inside the window only once the chip's clock, which
    # trails the host's by ~1.4 ms in this trace, is moved onto the host's
    calls = dt.events_named("modmatmul_pallas")
    assert len(calls) == 2
    peaks = counts.load_peaks("TPU v5 lite")
    for _chip, hlo, dur_ns in calls:
        ops = trace_reduce.custom_call_operands(hlo)
        assert ops == [(3, 8, 256), (3, 256, 640)]
        call = counts.unpad(counts.call_from_operands(*ops), [(8, 256, 600)])
        assert call == counts.MatmulCall(3, 8, 256, 600)
        least, bound = counts.least_time_s(call, peaks)
        assert bound == "memory"
        assert 0 < least < dur_ns * 1e-9
