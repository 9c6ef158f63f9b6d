"""Traffic mixes: one general generator, driven by a data file per mix.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

* ``{"loop": "closed", "clients": C, "rows": R}``: C clients, each with
  one request of R activation rows outstanding; a client sends its next
  request when its last one has decoded.
* ``{"loop": "open", "arrivals": "poisson", "rate_per_s": L, "rows": R}``:
  requests due at a mean rate of L per second whatever the server does.

Open-loop arrivals are Poisson in shape and the same for every run: the
gaps are the ``n = round(L * seconds)`` mid-quantiles of the exponential
distribution of rate L, in one fixed shuffled order.  The run's ``--seed`` draws the data
(weights, activations, pool traces), not the arrivals, so runs differ by
data and by the system's own noise, not by how the arrivals happen to
cluster.

The two loops below time requests on the host clock.  They call
``submit(due)`` to hand one request to the server and ``serve()`` to let
it work off everything queued (the engine's ``run()``, which returns
once every queued request has decoded).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List

import numpy as np

_ORDER_SEED = 0  # the one order of the open-loop gaps


@dataclass(frozen=True)
class Traffic:
    loop: str
    rows: int
    clients: int = 0
    rate_per_s: float = 0.0

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        loop = d["loop"]
        if loop == "closed":
            t = cls(loop, int(d["rows"]), clients=int(d["clients"]))
            if t.clients < 1:
                raise ValueError("a closed loop needs at least one client")
        elif loop == "open":
            if d.get("arrivals") != "poisson":
                raise ValueError(f"unknown arrivals {d.get('arrivals')!r}")
            t = cls(loop, int(d["rows"]), rate_per_s=float(d["rate_per_s"]))
            if t.rate_per_s <= 0:
                raise ValueError("rate_per_s must be positive")
        else:
            raise ValueError(f"unknown loop {loop!r}")
        if t.rows < 1:
            raise ValueError("rows must be positive")
        return t

    def batch_sizes(self, max_batch: int) -> List[int]:
        """Every batch size the server can form under this mix: what
        set-up has to warm, and nothing more."""
        if self.loop == "open":
            return list(range(1, max_batch + 1))
        full, rest = divmod(self.clients, max_batch)
        return sorted(({max_batch} if full else set()) | ({rest} if rest else set()))

    def due_times(self, seconds: float) -> List[float]:
        """Open loop: due times in seconds from the window's start."""
        n = max(1, round(self.rate_per_s * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / self.rate_per_s
        rng = np.random.default_rng(_ORDER_SEED)
        return list(np.cumsum(rng.permutation(gaps)))


@dataclass
class Sent:
    """One request as the traffic saw it (host clock, seconds)."""

    due: float
    request: Any
    run_start: float = math.nan  # start of the serve() call that served it
    run_end: float = math.nan  # its end: when the caller holds the decoded Y


@dataclass
class Window:
    """What one timed window produced."""

    t0: float = math.nan
    t1: float = math.nan
    sent: List[Sent] = field(default_factory=list)
    runs: List[tuple] = field(default_factory=list)  # (t0, t1) per serve()
    late_s: List[float] = field(default_factory=list)  # generator wake-up lateness

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _serve(window: Window, serve: Callable[[], None], clock) -> None:
    start = clock()
    served = [s for s in window.sent if math.isnan(s.run_start)]
    for s in served:
        s.run_start = start
    try:
        serve()
    finally:
        end = clock()
        window.runs.append((start, end))
    for s in served:
        s.run_end = end


def drive_closed(
    window: Window, traffic: Traffic, seconds: float,
    submit: Callable[[float], Any], serve: Callable[[], None],
    clock: Callable[[], float] = time.perf_counter,
) -> None:
    """Closed loop: the window opens at the first ``serve()`` and closes at
    the end of the first call that ends ``seconds`` or more later.  Fills
    ``window`` as it goes, so a failed call leaves what was sent."""
    while True:
        now = clock()
        for _ in range(traffic.clients):
            window.sent.append(Sent(now, submit(now)))
        _serve(window, serve, clock)
        window.t0 = window.runs[0][0]
        if window.runs[-1][1] - window.t0 >= seconds:
            break
    window.t1 = window.runs[-1][1]


def drive_open(
    window: Window, traffic: Traffic, due: List[float],
    submit: Callable[[float], Any], serve: Callable[[], None],
    clock: Callable[[], float] = time.perf_counter,
    sleep: Callable[[float], None] = time.sleep,
) -> None:
    """Open loop: each request is handed over at its due time, or as soon
    as the server returns if it was busy then; the window runs until every
    request has decoded.  ``late_s`` records how late the generator woke
    for a due time while the server was idle."""
    window.t0 = clock()
    due_abs = [window.t0 + d for d in due]
    i = 0
    queued = 0
    slept = False
    while i < len(due_abs) or queued:
        now = clock()
        while i < len(due_abs) and due_abs[i] <= now:
            if slept:
                window.late_s.append(now - due_abs[i])
                slept = False
            window.sent.append(Sent(due_abs[i], submit(due_abs[i])))
            i += 1
            queued += 1
        if queued:
            _serve(window, serve, clock)
            queued = 0
            slept = False
        elif i < len(due_abs):
            sleep(max(0.0, due_abs[i] - clock()))
            slept = True
    window.t1 = window.runs[-1][1] if window.runs else clock()
