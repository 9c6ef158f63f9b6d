"""One run of one cell: set up, time the window, check the answers, report.

``run()`` is the whole run after the command line has been read; the
tests drive it with a small cell on the CPU (``require_tpu=False``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, TextIO

from . import counts, layers, reference
from .traffic import Sent, Traffic, Window, drive_closed, drive_open
from .workload import Activations

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")
KERNEL = "modmatmul_pallas"  # the instruction name the trace gives the Pallas kernel
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_WARM_STREAM = 7  # activations for warm-up, apart from the timed stream

# An exact comparison with the layer's reference: both limits are 0.
LIMITS = {"mismatched_elements": 0, "missing_requests": 0}


class CompileLog:
    """Host times of JAX's executable builds and persistent-cache hits."""

    def __init__(self, jax_monitoring):
        self.builds: List[float] = []
        self.hits: List[float] = []
        jax_monitoring.register_event_duration_secs_listener(self._on_duration)
        jax_monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.builds.append(time.perf_counter())

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits.append(time.perf_counter())

    def count(self, t0: float, t1: float) -> tuple:
        """(executables built or loaded, of them cache hits) in [t0, t1]."""
        return (
            sum(t0 <= t <= t1 for t in self.builds),
            sum(t0 <= t <= t1 for t in self.hits),
        )


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable[["Context"], Optional[float]]


def load_metric(name: str, unit: str, metrics_dir: str = METRICS_DIR) -> Metric:
    """The reader ``bench/metrics/<name>.py``, found by the metric's name."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return Metric(name, unit, mod.read)


@dataclass
class Cell:
    name: str
    chips: int
    deployment: Any  # what ``layer.from_dict`` made of the configuration
    traffic: Traffic
    end_to_end: List[Metric]
    per_layer: List[Metric]
    layer: ModuleType = field(default_factory=lambda: layers.load(layers.DEFAULT))


@dataclass
class Served:
    """One request of the window, timed on the host clock."""

    due: float
    run_start: float
    completion: float  # end of the run() that returned its Y; nan unless decoded
    rows: int
    replay: int


@dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    setup_s: float
    window: Window
    requests: List[Served]
    replay_spans: List[tuple]  # (t0, t1) of each runtime.replay in the window
    peaks: Dict[str, float]
    device: Any = None  # trace_reduce.DeviceTrace of the window, when traced

    @property
    def replays(self) -> int:
        return len(self.replay_spans)

    @property
    def done(self) -> List[Served]:
        return [r for r in self.requests if not math.isnan(r.completion)]


def _tracer_spans(tracer, t0: float, t1: float) -> List[dict]:
    return [
        e for e in tracer.events
        if e["kind"] == "span" and e["clock"] == "wall"
        and e["t0"] >= t0 and e["t1"] <= t1
    ]


def _served(window: Window) -> List[Served]:
    from repro.serve import DONE

    out = []
    for s in window.sent:
        req = s.request
        done = req is not None and req.state == DONE
        out.append(Served(
            due=s.due, run_start=s.run_start,
            completion=s.run_end if done else math.nan,
            rows=int(req.x.shape[0]) if req is not None else 0,
            replay=req.replay if done else -1,
        ))
    return out


def _check(window: Window, cell: Cell, weights) -> tuple:
    """(mismatched elements, missing requests, failed requests) against
    the layer's reference, over every request sent in the window."""
    from repro.serve import DONE

    done = [s.request for s in window.sent
            if s.request is not None and s.request.state == DONE]
    missing = len(window.sent) - len(done)
    refs = cell.layer.reference(cell.deployment, weights, [r.x for r in done])
    bad = reference.mismatches([r.y for r in done], refs)
    return sum(bad), missing, missing + sum(1 for b in bad if b)


def run(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
    trace_dir: str, *, require_tpu: bool = True, peaks: Optional[dict] = None,
    out: TextIO = sys.stdout, err: TextIO = sys.stderr,
) -> int:
    """Run ``cell`` once; print the result line; return the exit code."""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}", file=err)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found {len(devices)}", file=err)
        return 3
    kind = devices[0].device_kind
    if peaks is None:
        peaks = counts.load_peaks(kind)

    from repro.obs.tracer import TRACER

    log = CompileLog(jax.monitoring)
    TRACER.clear()
    TRACER.enable()
    dep, traffic = cell.deployment, cell.traffic

    weights = cell.layer.make_weights(dep, seed)
    engine = cell.layer.make_engine(dep, weights, seed, devices[: cell.chips])
    warm = Activations(seed, traffic.rows, dep.in_width, stream=_WARM_STREAM)
    for b in traffic.batch_sizes(dep.max_batch):
        for _ in range(b):
            engine.submit(warm.next(), 0.0)
        engine.run()
    setup_s = time.perf_counter() - t_start

    acts = Activations(seed, traffic.rows, dep.in_width)
    window = Window()

    def submit(due: float):
        return engine.submit(acts.next(), 0.0)

    if traffic.loop == "closed":
        drive = lambda: drive_closed(window, traffic, seconds, submit, engine.run)  # noqa: E731
    else:
        due = traffic.due_times(seconds)
        drive = lambda: drive_open(window, traffic, due, submit, engine.run)  # noqa: E731

    crashed = False
    offset_ns = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        before = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            enter = time.perf_counter()
            try:
                drive()
            except Exception:  # the run reports the failure as not correct
                traceback.print_exc(file=err)
                crashed = True
    finally:
        if trace:
            jax.profiler.stop_trace()
    if math.isnan(window.t0):
        window.t0 = enter
    if math.isnan(window.t1):
        window.t1 = time.perf_counter()

    built, hits = log.count(window.t0, window.t1)
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell.chips]
    )

    spans = _tracer_spans(TRACER, window.t0, window.t1)
    replay_spans = [e for e in spans if e["name"] == "runtime.replay"]
    TRACER.disable()

    device_trace = None
    breakdown = None
    if trace:
        from . import trace_reduce

        device_trace = trace_reduce.read_trace(trace_reduce.find_xplane(trace_dir))
        offset_ns = device_trace.window[0] - 0.5 * (before + enter) * 1e9
        host = [(e["name"], e["t0"], e["t1"]) for e in spans]
        host.append(("bench.traffic (no request in the engine)", window.t0, window.t1))
        gap_list = [g for c in device_trace.chips[: cell.chips] for g in device_trace.idle_gaps(c)]
        breakdown = {
            "device_ops": device_trace.top_ops(10),
            "idle_gaps": trace_reduce.attribute_gaps(gap_list, host, offset_ns, 10),
        }

    ctx = Context(
        cell=cell, setup_s=setup_s, window=window,
        requests=_served(window),
        replay_spans=[(e["t0"], e["t1"]) for e in replay_spans],
        peaks=peaks, device=device_trace,
    )
    del engine  # the program's state goes before the reference runs
    mismatched, missing, failed = _check(window, cell, weights)
    compared = {"mismatched_elements": mismatched, "missing_requests": missing}
    correct = not crashed and all(compared[k] <= LIMITS[k] for k in LIMITS)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    if device_trace is not None:
        device["busy_s"] = device_trace.busy_s(device_trace.chips[: cell.chips])
        device["window_s"] = device_trace.window_s

    late = max(window.late_s, default=0.0)
    print(f"bench: {cell.name} seed={seed} setup_s={setup_s} window_s={window.seconds} "
          f"requests={len(window.sent)} replays={ctx.replays} serve_calls={len(window.runs)}",
          file=err)
    print(f"bench: executables built or loaded inside the window: {built} "
          f"(of them cache hits: {hits})", file=err)
    print(f"bench: traffic generator woke late by at most {late} s "
          f"over {len(window.late_s)} idle waits", file=err)
    for k in LIMITS:
        print(f"correct: {k} {compared[k]} limit {LIMITS[k]}", file=err)
    err.flush()

    result = {
        "correct": correct,
        "attempted": len(window.sent),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": compared[k], "limit": LIMITS[k]} for k in LIMITS}
    print(json.dumps(result), file=out)
    out.flush()
    return 0 if correct else 1
