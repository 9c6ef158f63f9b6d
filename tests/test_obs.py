"""The observability layer: tracer semantics, the metrics registry,
the Chrome/Perfetto exporter, and the empty-run guard regressions.

The tracer tests pin the contracts the instrumentation relies on:
disabled tracing allocates nothing on the hot path, sim-clock traces
of byte-identical replays are byte-identical, wall and sim records
live on separable tracks, and the exported JSON is schema-valid.
"""
import dataclasses
import json
import os
import pathlib

import numpy as np
import pytest

from repro import obs
from repro.core.constructions import PlanConfig
from repro.core.planner import (
    BlockShapes,
    decode_check_cache_clear,
    decode_check_cache_info,
    get_plan_for,
)
from repro.core.protocol import Trace
from repro.obs import MetricsRegistry, Tracer
from repro.obs.export import SIM_PID, WALL_PID, to_chrome, to_jsonl, validate_chrome
from repro.obs.tracer import _DISABLED_SPAN
from repro.runtime import AutoPlanner, run_adaptive_over_pool, run_over_pool
from repro.runtime.metrics import PipelineMetrics, summarize
from repro.runtime.pool import sample_trace


@pytest.fixture
def tracer():
    t = Tracer()
    t.enable()
    yield t
    t.disable()


@pytest.fixture
def global_tracing():
    """Enable the module-level TRACER for runtime-integration tests and
    always restore the disabled default."""
    obs.TRACER.clear()
    obs.enable()
    yield obs.TRACER
    obs.disable()
    obs.TRACER.clear()


def _small_setup():
    cfg = PlanConfig("age", 2, 2, 2).resolved()
    m = 4
    plan = get_plan_for(cfg, BlockShapes(k=m, ma=m, mb=m, s=2, t=2), seed=0)
    rng = np.random.default_rng(0)
    a = rng.integers(0, plan.field.p, (m, m))
    b = rng.integers(0, plan.field.p, (m, m))
    return plan, a, b


# ----------------------------------------------------------------------
# tracer semantics
# ----------------------------------------------------------------------
def test_disabled_tracer_allocates_nothing():
    t = Tracer()  # disabled by default
    assert t.span("a") is t.span("b") is _DISABLED_SPAN
    assert t.event("x", k=1) == 0
    assert t.sim_span("y", 0.0, 1.0) == 0
    assert t.sim_event("z", 0.5) == 0
    assert t.events == []
    # the no-op span is a working context manager with the Span surface
    with t.span("a") as sp:
        assert sp.set(extra=1) is sp
        assert sp.id == 0


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs opens/closes."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("open", name))
                return self

            def __exit__(self, *exc):
                log.append(("close", name))
                return False

        return _Ann()


def test_enabled_span_opens_and_closes_one_annotation_of_its_name():
    ann = _Annotations()
    t = Tracer(annotator=ann).enable()
    with t.span("outer"):
        with t.span("inner"):
            t.event("tick")
    t.sim_span("sim", 0.0, 1.0)
    assert ann.log == [
        ("open", "outer"), ("open", "inner"), ("close", "inner"), ("close", "outer"),
    ]
    with pytest.raises(ValueError):
        with t.span("failing"):
            raise ValueError("boom")
    assert ann.log[-2:] == [("open", "failing"), ("close", "failing")]


def test_disabled_tracer_opens_no_annotation():
    ann = _Annotations()
    t = Tracer(annotator=ann)
    assert t.span("a") is t.span("b") is _DISABLED_SPAN
    with t.span("a"):
        pass
    t.enable().disable()
    assert t.span("c") is _DISABLED_SPAN
    assert ann.log == [] and t.events == []


def test_obs_imports_and_traces_without_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # any import of jax now fails\n"
        "import repro.obs as obs\n"
        "t = obs.Tracer().enable()\n"
        "with t.span('s'):\n"
        "    pass\n"
        "assert [e['name'] for e in t.events] == ['s']\n"
    )
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_nested_spans_record_parent_ids(tracer):
    with tracer.span("outer") as outer:
        with tracer.span("inner"):
            tracer.event("tick")
    ev = {e["name"]: e for e in tracer.events}
    assert ev["outer"]["parent"] == 0
    assert ev["inner"]["parent"] == outer.id
    assert ev["tick"]["parent"] == ev["inner"]["id"]
    # completion order: inner closes before outer
    assert [e["name"] for e in tracer.events] == ["tick", "inner", "outer"]


def test_span_set_adds_attributes_midflight(tracer):
    with tracer.span("s", fixed=1) as sp:
        sp.set(late=2)
    (rec,) = tracer.events
    assert rec["attrs"] == {"fixed": 1, "late": 2}


def test_sim_and_wall_tracks_are_separable(tracer):
    with tracer.span("wall_work"):
        pass
    tracer.sim_span("replay", 0.0, 2.5, track=("replay", 0))
    tracer.sim_event("barrier", 1.0, track=("worker", 3))
    sims = tracer.sim_events()
    assert {e["name"] for e in sims} == {"replay", "barrier"}
    assert all(e["clock"] == "sim" for e in sims)
    assert {tuple(e["track"]) for e in sims} == {("replay", 0), ("worker", 3)}
    walls = [e for e in tracer.events if e["clock"] == "wall"]
    assert [e["name"] for e in walls] == ["wall_work"]
    assert isinstance(walls[0]["track"], int)  # thread id, not a lane


def test_event_cap_counts_drops():
    t = Tracer(max_events=2).enable()
    for i in range(5):
        t.sim_event("e", float(i))
    assert len(t.events) == 2
    assert t.dropped == 3
    t.clear()
    assert t.dropped == 0 and t.events == []


def test_identical_replays_trace_identically(global_tracing):
    plan, a, b = _small_setup()
    trace = sample_trace(plan.n_total, seed=7)
    sims = []
    for _ in range(2):
        obs.TRACER.clear()
        run_over_pool(plan, a, b, trace, seed=0)
        # ids are allocation order, not content — compare everything else
        sims.append(
            [
                {k: v for k, v in e.items() if k not in ("id", "parent")}
                for e in obs.TRACER.sim_events()
            ]
        )
    assert sims[0] == sims[1]
    assert len(sims[0]) > 0


def test_tracing_does_not_change_results(global_tracing):
    plan, a, b = _small_setup()
    trace = sample_trace(plan.n_total, seed=7)
    res_on = run_over_pool(plan, a, b, trace, seed=0)
    obs.disable()
    res_off = run_over_pool(plan, a, b, trace, seed=0)
    assert np.array_equal(res_on.y, res_off.y)
    assert res_on.metrics.completion_time == res_off.metrics.completion_time


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    for v in (1.0, 2.0, 3.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["p50"] == 2.0
    reg.reset()
    assert reg.snapshot()["counters"] == {}


def test_empty_histogram_summary_is_defined():
    reg = MetricsRegistry()
    assert reg.histogram("h").summary() == {
        "count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0,
    }


def test_broken_probe_reports_instead_of_raising():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("nope")

    reg.register_probe("bad", boom)
    info = reg.snapshot()["probes"]["bad"]
    assert "error" in info and "nope" in info["error"]


def test_cache_probes_delegate_to_planner():
    """The three legacy cache spellings surface through one snapshot."""
    decode_check_cache_clear()
    plan, a, b = _small_setup()
    run_over_pool(plan, a, b, sample_trace(plan.n_total, seed=1), seed=0)
    snap = obs.snapshot()
    for probe in ("plan_cache", "subset_cache", "decode_check_cache"):
        assert "hits" in snap["probes"][probe], probe
        assert "misses" in snap["probes"][probe], probe
    # the decode-check memo is actually counted now
    info = decode_check_cache_info()
    assert info["hits"] + info["misses"] >= 1
    assert snap["probes"]["decode_check_cache"] == info


def test_runtime_counters_increment(global_tracing):
    plan, a, b = _small_setup()
    before = obs.REGISTRY.counter("runtime.replays").value
    run_over_pool(plan, a, b, sample_trace(plan.n_total, seed=1), seed=0)
    assert obs.REGISTRY.counter("runtime.replays").value == before + 1
    assert json.dumps(obs.snapshot())  # snapshot is JSON-serializable


# ----------------------------------------------------------------------
# Chrome/Perfetto export
# ----------------------------------------------------------------------
def test_chrome_export_is_schema_valid(tracer):
    with tracer.span("wall", k=1):
        pass
    tracer.sim_span("replay", 0.0, 1.0, track=("replay", 0))
    tracer.sim_event("barrier", 0.5, track=("replay", 0))
    chrome = to_chrome(tracer, metrics={"counters": {"c": 1}})
    assert validate_chrome(chrome) == []
    assert chrome["repro_metrics"] == {"counters": {"c": 1}}
    json.dumps(chrome)  # round-trippable


def test_chrome_pids_separate_the_clocks(tracer):
    with tracer.span("wall"):
        pass
    tracer.sim_span("sim", 0.0, 1.0, track=("worker", 2))
    chrome = to_chrome(tracer)
    x = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    pids = {e["name"]: e["pid"] for e in x}
    assert pids == {"wall": WALL_PID, "sim": SIM_PID}
    # sim timestamps are seconds * 1e6 on the exported microsecond axis
    sim = next(e for e in x if e["name"] == "sim")
    assert sim["dur"] == pytest.approx(1e6)
    # lane metadata names the worker thread
    names = {
        (m["pid"], m["tid"]): m["args"]["name"]
        for m in chrome["traceEvents"]
        if m["ph"] == "M" and m["name"] == "thread_name"
    }
    assert names[(SIM_PID, sim["tid"])] == "worker 2"


def test_chrome_wall_track_rebased_to_zero(tracer):
    with tracer.span("first"):
        pass
    with tracer.span("second"):
        pass
    chrome = to_chrome(tracer)
    ts = [e["ts"] for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert min(ts) == 0.0


def test_validate_chrome_flags_malformed():
    assert validate_chrome({"nope": 1})
    assert validate_chrome({"traceEvents": [{"ph": "Q", "pid": 1, "tid": 1}]})
    bad_dur = {
        "traceEvents": [
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": -5, "name": "x"}
        ]
    }
    assert validate_chrome(bad_dur)


def test_jsonl_export_round_trips(tracer):
    tracer.sim_span("replay", 0.0, 1.0, track=("replay", 1), note="hi")
    lines = to_jsonl(tracer).strip().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["name"] == "replay" and rec["track"] == ["replay", 1]


# ----------------------------------------------------------------------
# decision -> replay linkage
# ----------------------------------------------------------------------
def test_adaptive_decisions_link_to_replay_spans(global_tracing):
    cfg = PlanConfig("age", 2, 2, 2)
    m, K, batch = 4, 3, 2
    rng = np.random.default_rng(0)
    a = rng.integers(0, 7, (K, batch, m, m))
    b = rng.integers(0, 7, (K, batch, m, m))
    traces = [sample_trace(cfg.n_total + 2, seed=10 + k) for k in range(K)]
    planner = AutoPlanner([cfg], cost_m=m)
    run = run_adaptive_over_pool(planner, a, b, traces, seed=0)
    assert all(d.obs_id > 0 for d in run.decisions)
    ev = obs.TRACER.events
    decide_ids = {e["id"] for e in ev if e["name"] == "autoplan.decide"}
    replays = [e for e in ev if e["name"] == "replay"]
    assert len(replays) == K
    for rec in replays:
        assert rec["attrs"]["decision_id"] in decide_ids
        assert "config" in rec["attrs"]
        assert rec["attrs"]["wire_bytes_total"] > 0


# ----------------------------------------------------------------------
# empty-run guard regressions
# ----------------------------------------------------------------------
def test_summarize_empty_is_defined():
    assert summarize([]) == {"runs": 0}


def _pm(**kw):
    base = dict(
        depth=2, batch=1, products=2, makespan=4.0,
        completions=np.array([2.0, 4.0]), starts=np.array([0.0, 1.0]),
        occupancy=1.25, phase1_overlap=0.5, trace=Trace(),
    )
    base.update(kw)
    return PipelineMetrics(**base)


def test_pipeline_metrics_guards():
    with pytest.raises(ValueError, match="depth"):
        _pm(depth=0)
    with pytest.raises(ValueError, match="batch"):
        _pm(batch=0)
    with pytest.raises(ValueError, match="makespan"):
        _pm(makespan=float("nan"))
    with pytest.raises(ValueError, match="makespan"):
        _pm(makespan=-1.0)


def test_pipeline_overlap_ratio_zero_makespan():
    pm = _pm(
        makespan=0.0, completions=np.zeros(2), starts=np.zeros(2),
        occupancy=0.0, phase1_overlap=0.0,
    )
    assert pm.overlap_ratio == 0.0
    assert _pm().overlap_ratio == pytest.approx(0.125)
