"""Reduce a JAX profiler trace of the timed window to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Three
things are read from it:

* the window: the host annotation ``bench.window`` the harness wraps
  around the timed traffic;
* device busy time: per chip, the union of the intervals of the
  ``XLA Ops`` line of its ``/device:TPU:<n>`` plane, clipped to the
  window (asynchronous copies on the ``Async XLA Ops`` line overlap
  other work and are not counted as busy).  Device timestamps run on
  the chip's own clock, which trails the host's by about a millisecond;
  they are moved onto the host's clock first (``clock_offsets``);
* kernel calls: ``XLA Ops`` events of one Pallas kernel, found by the
  instruction name the trace gives it (``%modmatmul_pallas.3 = ...``),
  with the operand shapes parsed from the same HLO text.
"""
from __future__ import annotations

import glob
import math
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_ANNOTATION = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE_EVENT = "DoEnqueueProgram"  # host event that hands a program run to a chip
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")

Interval = Tuple[float, float]


@dataclass
class DeviceTrace:
    """What one traced window holds, in nanoseconds on the trace's clock."""

    window: Interval
    ops: Dict[int, List[Tuple[str, float, float]]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self, chips: Optional[Sequence[int]] = None) -> float:
        """Seconds in the window with an op running, averaged over chips."""
        chips = list(self.chips if chips is None else chips)
        if not chips:
            return 0.0
        lo, hi = self.window
        total = sum(
            union_length([(s, e) for _, s, e in self.ops.get(c, [])], lo, hi)
            for c in chips
        )
        return total / len(chips) * 1e-9

    def idle_gaps(self, chip: int) -> List[Interval]:
        lo, hi = self.window
        return gaps([(s, e) for _, s, e in self.ops.get(chip, [])], lo, hi)

    def events_named(self, kernel: str) -> List[Tuple[int, str, float]]:
        """(chip, HLO text, duration ns) of every op whose instruction is
        ``kernel`` (any ``.N`` suffix), inside the window."""
        lo, hi = self.window
        out = []
        for chip, evs in self.ops.items():
            for name, s, e in evs:
                if s >= lo and e <= hi and instruction(name) == kernel:
                    out.append((chip, name, e - s))
        return out

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` instructions with most device time in the window,
        summed over chips: ``[[label, seconds], ...]``."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, s, e in evs:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    key = op_label(name)
                    tot[key] = tot.get(key, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in ranked]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_trace(path: str) -> DeviceTrace:
    """Load ``path`` and keep the window and the device op intervals,
    the latter moved onto the host's clock (see ``clock_offsets``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    module_starts: Dict[Tuple[int, int], float] = {}
    enqueues: Dict[Tuple[int, int], float] = {}
    for plane in data.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None:
                chip = int(dev.group(1))
                if line.name == OPS_LINE:
                    ops.setdefault(chip, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events
                    )
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        run = _stats(e).get("run_id")
                        if run is not None:
                            module_starts[(chip, int(run))] = e.start_ns
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW_ANNOTATION and window is None:
                        window = (e.start_ns, e.end_ns)
                    elif e.name == ENQUEUE_EVENT:
                        st = _stats(e)
                        if "run_id" in st:
                            key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                            enqueues.setdefault(key, e.start_ns)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_ANNOTATION!r} host annotation")
    offsets = clock_offsets(module_starts, enqueues)
    for chip, evs in ops.items():
        shift = offsets.get(chip, 0.0)
        ops[chip] = [(name, s + shift, e + shift) for name, s, e in evs]
    return DeviceTrace(window=window, ops=ops)


def _stats(event) -> Dict[str, object]:
    return {name: value for name, value in event.stats}


def clock_offsets(
    module_starts: Dict[Tuple[int, int], float], enqueues: Dict[Tuple[int, int], float],
) -> Dict[int, float]:
    """Per chip, nanoseconds to add to its device timestamps to put them on
    the host's clock.  A program cannot start on the device before the host
    enqueued it, so each run's ``enqueue - device start`` bounds the offset
    from below; the largest of those bounds (a run that found the device
    idle) is taken.  Chips with no run seen on both sides keep offset 0."""
    out: Dict[int, float] = {}
    for (chip, run), start in module_starts.items():
        host = enqueues.get((chip, run))
        if host is not None:
            out[chip] = max(out.get(chip, -math.inf), host - start)
    return out


def union_length(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def gaps(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` no interval covers, in order."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur and cur < hi:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def instruction(hlo_text: str) -> str:
    """``%modmatmul_pallas.3 = s32[...] ...`` -> ``modmatmul_pallas``."""
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(hlo_text: str) -> str:
    """Instruction name with its result shape, short enough to read."""
    parts = hlo_text.split(" = ", 1)
    head = parts[0].strip().lstrip("%")
    if len(parts) == 1:
        return head[:120]
    shape = _SHAPE.search(parts[1])
    return f"{head} [{shape.group(1)}]" if shape else head


def custom_call_operands(hlo_text: str) -> List[Tuple[int, ...]]:
    """Operand shapes of a ``custom-call(...)`` instruction's HLO text."""
    if "custom-call(" not in hlo_text:
        return []
    args = hlo_text.split("custom-call(", 1)[1]
    args = args.split("custom_call_target", 1)[0]
    return [tuple(int(d) for d in m.group(1).split(",") if d) for m in _SHAPE.finditer(args)]


def attribute_gaps(
    gap_list: Sequence[Interval], host_spans: Sequence[Tuple[str, float, float]],
    offset_ns: float, n: int = 10,
) -> List[list]:
    """Idle device time by what the host was doing: each gap goes to the
    innermost host span (latest start) covering its midpoint; spans are
    ``(name, t0_s, t1_s)`` on ``time.perf_counter``, moved onto the
    trace's clock by ``offset_ns``.  Returns ``[[name, seconds], ...]``,
    most idle time first."""
    spans = sorted(
        ((t0 * 1e9 + offset_ns, t1 * 1e9 + offset_ns, name) for name, t0, t1 in host_spans),
        key=lambda s: s[0],
    )
    tot: Dict[str, float] = {}
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        label = "outside any span"
        for t0, t1, name in spans:
            if t0 > mid:
                break
            if t1 >= mid:
                label = name
        tot[label] = tot.get(label, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in ranked]
