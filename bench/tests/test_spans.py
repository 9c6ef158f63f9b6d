"""The program's spans in the window: per-replay sums on the host clock, and
device time by the span that launched each program, on the trace's clock.

``data/modmatmul_small.xplane.pb`` (see ``test_trace_reduce.py``) holds two
runs of one jitted ``mod_matmul``, each launched inside the JAX dispatch
annotation ``PjitFunction(mod_matmul)``.
"""
import io
import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from bench import spans, trace_reduce
from bench.harness import Cell, load_metric, run
from bench.traffic import Traffic
from bench.workload import Deployment

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "modmatmul_small.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ANNOTATION = "PjitFunction(mod_matmul)"

SPAN_METRICS = {
    "serve.admit_s_per_replay", "serve.encode_s_per_replay", "serve.decode_s_per_replay",
    "runtime.upload_s_per_replay", "runtime.device_wait_s_per_replay",
    "runtime.fetch_s_per_replay", "runtime.h2d_bytes_per_replay",
    "runtime.d2h_bytes_per_replay",
}
DEVICE_METRICS = {"protocol.phase1.device_s_per_replay", "protocol.phase2.device_s_per_replay"}


def test_recorded_runs_go_to_the_annotation_that_launched_them():
    dt = trace_reduce.read_trace(XPLANE)
    pt = spans.read_program_trace(XPLANE)
    assert sorted(run for _, _, run in pt.runs[0]) == [8, 9]
    assert sorted(pt.launches) == [(0, 8), (0, 9)]
    got = pt.device_s_by_span(dt, [ANNOTATION, "bench.window", "no.such.span"])
    # both runs lie inside the innermost of the two nested dispatch
    # annotations, and all the window's busy time is inside those runs
    assert got[ANNOTATION] == pytest.approx(dt.busy_s())
    assert got["bench.window"] == 0.0 and got["no.such.span"] == 0.0
    assert pt.device_s_by_span(dt, ["bench.window"])["bench.window"] == pytest.approx(dt.busy_s())


def test_a_run_goes_to_the_innermost_of_two_nested_spans():
    pt = spans.ProgramTrace(
        runs={0: [(16.0, 30.0, 1), (55.0, 70.0, 2)], 1: [(16.0, 30.0, 1)]},
        launches={(0, 1): 15.0, (0, 2): 50.0, (1, 1): 90.0},
        annotations={"outer": [(0.0, 80.0)], "inner": [(10.0, 20.0)]},
    )
    dt = trace_reduce.DeviceTrace(window=(0.0, 100.0), ops={
        0: [("a", 17.0, 25.0), ("b", 20.0, 27.0), ("c", 56.0, 60.0), ("between", 40.0, 45.0)],
        1: [("d", 17.0, 19.0)],
    })
    got = pt.device_s_by_span(dt, ["outer", "inner"], chips=[0])
    assert got == {"inner": pytest.approx(10e-9), "outer": pytest.approx(4e-9)}
    # chip 1's run was launched under no span; the mean is over both chips
    both = pt.device_s_by_span(dt, ["outer", "inner"])
    assert both == {"inner": pytest.approx(5e-9), "outer": pytest.approx(2e-9)}


def test_a_late_enqueue_is_traced_back_to_its_launch():
    # line 2, a runtime thread, enqueues at t=100 inside the end of flow
    # (5, type 7), which began on line 1 at t=10; a flow of another type
    # with the same id began elsewhere and is not followed
    ends = {2: [(90.0, 120.0, (5, 7))], 1: [(5.0, 30.0, (9, 14))]}
    begins = {(5, 7): (1, 10.0), (5, 14): (3, 50.0), (9, 14): (4, 2.0)}
    assert spans._launch_time(2, 100.0, ends, begins) == 2.0
    assert spans._launch_time(2, 100.0, ends, begins, hops=1) == 10.0
    assert spans._launch_time(2, 130.0, ends, begins) == 130.0  # outside any flow end
    assert spans._launch_time(1, 40.0, ends, begins) == 40.0


def test_device_reader_finds_the_cells_trace(monkeypatch, tmp_path):
    import bench.run

    profile = tmp_path / "cell" / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    shutil.copy(XPLANE, profile / "t.xplane.pb")
    monkeypatch.setattr(bench.run, "TRACE_DIR", str(tmp_path))
    dt = trace_reduce.read_trace(XPLANE)
    ctx = SimpleNamespace(
        device=dt, replay_spans=[(0, 1), (1, 2)], cell=SimpleNamespace(name="cell", chips=1),
    )
    ctx.replays = len(ctx.replay_spans)
    assert spans.device_s_per_replay(ctx, ANNOTATION) == pytest.approx(dt.busy_s() / 2)
    assert spans.device_s_per_replay(ctx, "protocol.phase2") is None
    ctx.cell.name = "other"
    assert spans.device_s_per_replay(ctx, ANNOTATION) is None


def test_a_cpu_run_reports_every_span_and_byte_metric(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    assert SPAN_METRICS | DEVICE_METRICS <= set(entries)
    metrics = [load_metric(n, entries[n]["unit"]) for n in sorted(SPAN_METRICS | DEVICE_METRICS)]
    dep = Deployment.from_dict({
        "name": "small", "projection": "up", "hidden_size": 64, "intermediate_size": 96,
        "scheme": {"method": "age", "s": 2, "t": 2, "z": 2}, "field_p": 65521,
        "max_batch": 2,
        "pool": {"spares": 4, "compute_latency": {"shift": 0.1, "scale": 0.5},
                 "net_scale": 0.3, "traces": 3},
    })
    # untraced, the harness reads the cell's first metric list: the
    # device-trace metrics have no trace to read and are left out
    cell = Cell("small", 1, dep, Traffic("closed", 4, clients=4), metrics, [])
    out, err = io.StringIO(), io.StringIO()
    rc = run(cell, 2 ** 33 + 5, 0.4, False, time.perf_counter(), str(tmp_path / "trace"),
             require_tpu=False, peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
             out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == SPAN_METRICS
    for name in SPAN_METRICS:
        assert result["metrics"][name]["value"] > 0, name
    # 2 requests of 4 rows a replay, k = 64: A's int32 residues alone go to
    # the device; W's are resident there
    h2d = result["metrics"]["runtime.h2d_bytes_per_replay"]["value"]
    assert h2d == 2 * 64 * 4 * 4
