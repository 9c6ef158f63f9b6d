"""The program's own spans in the timed window, and device time by span.

Host side: the wall-clock spans of ``repro.obs.TRACER`` that lie inside
the window, summed per replay (``per_replay``).

Device side: while the tracer is enabled, each of its spans also opens a
``jax.profiler.TraceAnnotation`` of the same name, so the profiler's
host planes hold the program's spans on the trace's own clock.  A device
program run is linked to the span that launched it like this: its
``XLA Modules`` event carries a ``run_id``; the host's
``DoEnqueueProgram`` of that ``run_id`` may run later on a runtime
thread (on a TPU it waits there for the inputs' transfer), so from it
the trace's flow arrows are followed back (the innermost enclosing event
that ends a flow, ``_c``, to the event that began it, ``_p``) to the
thread and the moment the program was launched.  ``read_program_trace``
keeps:

* each ``XLA Modules`` run (chip, ``run_id``, start, end), moved onto
  the host's clock by the same offsets as ``trace_reduce.read_trace``
  moves the ops;
* each run's launch time on the host, by ``(device_ordinal, run_id)``;
* the host annotations, by name (the Python tracer's ``$``-prefixed
  function events left out).

``ProgramTrace.device_s_by_span`` then gives, per span name, the device
time of the programs launched inside annotations of that name.

A program that records no such span (or no annotation of it) gives
``None`` from every reader here: the metric is left out, not zero.
"""
from __future__ import annotations

import bisect
import functools
import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace_reduce

Interval = Tuple[float, float]


def per_replay(ctx, name: str, attr: Optional[str] = None) -> Optional[float]:
    """Sum over the program's wall spans called ``name`` that lie inside
    the window of their duration (or of their ``attr`` attribute), over
    the window's replays."""
    from repro.obs.tracer import TRACER

    lo, hi = ctx.window.t0, ctx.window.t1
    spans = [
        e for e in TRACER.events
        if e["kind"] == "span" and e["clock"] == "wall" and e["name"] == name
        and e["t0"] >= lo and e["t1"] <= hi
    ]
    if not spans or not ctx.replays:
        return None
    if attr is None:
        total = sum(e["t1"] - e["t0"] for e in spans)
    else:
        total = sum(e["attrs"][attr] for e in spans)
    return total / ctx.replays


@dataclass
class ProgramTrace:
    """Program runs and host annotations of one trace, in nanoseconds on
    the host's clock."""

    runs: Dict[int, List[Tuple[float, float, int]]] = field(default_factory=dict)
    launches: Dict[Tuple[int, int], float] = field(default_factory=dict)
    annotations: Dict[str, List[Interval]] = field(default_factory=dict)

    def device_s_by_span(
        self, device: trace_reduce.DeviceTrace, names: Sequence[str],
        chips: Optional[Sequence[int]] = None,
    ) -> Dict[str, float]:
        """Per name, seconds of device time (union of ``device``'s ops,
        clipped to its window, mean over ``chips``) of the program runs
        launched within an annotation of that name; an op belongs to the
        run it starts in.  A run goes to the innermost such annotation
        (the latest to start) that holds its launch; runs under none of
        ``names`` count for none."""
        chips = list(device.chips if chips is None else chips)
        spans = sorted(
            (s, e, name) for name in set(names) for s, e in self.annotations.get(name, ())
        )
        owner: Dict[Tuple[int, int], str] = {}
        for (chip, run), t in self.launches.items():
            inner = None
            for s, e, name in spans:
                if s > t:
                    break
                if e >= t:
                    inner = name
            if inner is not None:
                owner[(chip, run)] = inner
        lo, hi = device.window
        out = {name: 0.0 for name in names}
        for chip in chips:
            runs = sorted(self.runs.get(chip, []))
            starts = [s for s, _, _ in runs]
            parts: Dict[str, List[Interval]] = {}
            for _, s, e in device.ops.get(chip, []):
                i = bisect.bisect_right(starts, s) - 1
                if i < 0:
                    continue
                _, r1, run = runs[i]
                name = owner.get((chip, run))
                if name is not None and s < r1:
                    parts.setdefault(name, []).append((s, e))
            for name, iv in parts.items():
                out[name] += trace_reduce.union_length(iv, lo, hi) * 1e-9 / len(chips)
        return out


def read_program_trace(path: str) -> ProgramTrace:
    """Load ``path`` and keep its program runs, launches and annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    module_runs: Dict[Tuple[int, int], Interval] = {}
    enqueues: Dict[Tuple[int, int], Tuple[int, float]] = {}  # run -> (line, host time)
    # flows are keyed by (id, type): ids of different types may coincide
    ends: Dict[int, List[tuple]] = {}  # line -> flow ends (start, end, flow)
    begins: Dict[tuple, Tuple[int, float]] = {}  # flow -> (line, host time)
    pt = ProgramTrace()
    lines = 0
    for plane in data.planes:
        dev = trace_reduce._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None:
                if line.name != trace_reduce.MODULES_LINE:
                    continue
                chip = int(dev.group(1))
                for e in line.events:
                    run = trace_reduce._stats(e).get("run_id")
                    if run is not None:
                        module_runs[(chip, int(run))] = (e.start_ns, e.end_ns)
            elif plane.name.startswith("/host:"):
                lines += 1
                for e in line.events:
                    if e.name.startswith("$"):
                        continue
                    st = trace_reduce._stats(e)
                    if "_c" in st:
                        flow = (st["_c"], st.get("_ct"))
                        ends.setdefault(lines, []).append((e.start_ns, e.end_ns, flow))
                    if "_p" in st:
                        begins[(st["_p"], st.get("_pt"))] = (lines, e.start_ns)
                    if e.name == trace_reduce.ENQUEUE_EVENT:
                        if "run_id" in st:
                            key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                            enqueues.setdefault(key, (lines, e.start_ns))
                    else:
                        pt.annotations.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    for evs in ends.values():
        evs.sort()
    for key, (line, t) in enqueues.items():
        pt.launches[key] = _launch_time(line, t, ends, begins)
    offsets = trace_reduce.clock_offsets(
        {key: s for key, (s, _) in module_runs.items()},
        {key: t for key, (_, t) in enqueues.items()},
    )
    for (chip, run), (s, e) in module_runs.items():
        shift = offsets.get(chip, 0.0)
        pt.runs.setdefault(chip, []).append((s + shift, e + shift, run))
    return pt


def _launch_time(line, t, ends, begins, hops: int = 8) -> float:
    """Follow flow arrows back from host time ``t`` on ``line``: while the
    innermost event there that holds ``t`` ends a flow, move to where that
    flow began."""
    for _ in range(hops):
        evs = ends.get(line, [])
        i = bisect.bisect_right(evs, (t, math.inf)) - 1
        while i >= 0 and evs[i][1] < t:
            i -= 1
        if i < 0 or evs[i][2] not in begins:
            break
        line, t = begins[evs[i][2]]
    return t


@functools.lru_cache(maxsize=1)
def _program_trace(path: str, mtime_ns: int) -> ProgramTrace:
    return read_program_trace(path)


def device_s_per_replay(ctx, name: str) -> Optional[float]:
    """Device seconds of the programs launched under span ``name``, per
    replay, from the cell's traced window (``bench/run.py``'s trace
    directory); ``None`` without a trace or without such programs."""
    if ctx.device is None or not ctx.replays:
        return None
    from .run import TRACE_DIR

    try:
        path = trace_reduce.find_xplane(os.path.join(TRACE_DIR, ctx.cell.name))
    except FileNotFoundError:
        return None
    pt = _program_trace(path, os.stat(path).st_mtime_ns)
    chips = ctx.device.chips[: ctx.cell.chips]
    busy = pt.device_s_by_span(ctx.device, [name], chips)[name]
    return busy / ctx.replays if busy > 0 else None
