"""Continuous-batching serving engine for private matmul traffic.

``ServingEngine`` multiplexes many users' requests into the batched
CMPC protocol: requests queue with simulated arrival times, an
admission controller driven by the runtime's fitted
:class:`~repro.runtime.metrics.PoolEstimate` sheds or defers load the
pool cannot carry, and admitted requests fold into protocol replays
appended to an in-flight :class:`~repro.runtime.PipelineSession` — the
request -> batch -> protocol path the ROADMAP's serving tier calls for.

Batching discipline (``mode``):

* ``"continuous"`` — a new batch launches as soon as fewer than
  ``pipe_depth`` replays remain in flight (``session.ready_at``),
  i.e. its Phase-1 upload runs *inside* the tail replay's
  Phase-2/Phase-3 window.  Requests that arrived while the pipeline
  was busy ride the very next upload instead of waiting for the pool
  to drain — that is what bounds tail latency under load.
* ``"boundary"`` — a new batch waits for every in-flight replay to
  decode (``ready_at(1)``): the classic batch-boundary server the
  benchmark compares against.

Admission control: before each launch the engine predicts the replay's
service time from its fitted pool estimate (or the shared
:class:`~repro.runtime.AutoPlanner`'s, when one drives construction
selection) and

* **sheds** a request whose deadline the prediction already rules out
  (``launch + predicted_service > deadline``), and
* **defers** load when pool-health estimates disagree or degrade — a
  recent-window estimate predicting more than ``degrade_factor`` times
  the all-history service (or predicting infeasibility while history
  says healthy) halves the admission cap until the estimates
  reconverge.

Pool reconfiguration: the pipeline's serialized occupancy assumes one
worker set, so when the trace source (e.g. an ``ElasticPool``) changes
size the engine drains in-flight work, rebuilds the session at
``base_time = busy_until()`` (the reconfiguration barrier), re-fits
the construction's spares to the new pool, and resets the hybrid
escalation state; the estimator's observations survive — the master
pool is the same physical fleet, and a post-shrink prediction on the
smaller pool is exactly what makes admission shed.

Byzantine posture: ``decode_mode="hybrid"`` (the default) starts every
pool in cheap detect mode and escalates to Berlekamp-Welch correction
after the first rejected responder — threaded through every replay the
engine launches via the session's shared
:class:`~repro.runtime.HybridState`.

Everything is deterministic per seed: arrivals, traces, the event
loop, and therefore every latency percentile the report publishes.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.constructions import PlanConfig
from ..core.gf import Field
from ..core.layers import choose_scales
from ..core.planner import BlockShapes, CMPCPlan, get_plan_for
from ..obs.tracer import TRACER
from ..runtime.metrics import estimate_pool, observed_run
from ..runtime.pipeline import PipelineRun, PipelineSession
from ..runtime.pool import ElasticPool, WorkerTrace
from ..runtime.scheduler import DEFAULT_SUBSET_TRIES, HybridState
from .request import DEFAULT_WEIGHT, DONE, SHED, EngineReport, Request

TraceSource = Union[WorkerTrace, ElasticPool, Sequence[WorkerTrace]]

# One device program per batch size stacks the resident W residues into
# the replay's [batch, k, out] B operand.
_stack = jax.jit(jnp.stack)


def _trace_list(traces: TraceSource) -> List[WorkerTrace]:
    """Normalize a trace source to a (cycled) list of per-replay traces."""
    if isinstance(traces, WorkerTrace):
        return [traces]
    if isinstance(traces, ElasticPool):
        return list(traces)
    out = list(traces)
    if not out or not all(isinstance(t, WorkerTrace) for t in out):
        raise ValueError(
            "traces must be a WorkerTrace, an ElasticPool, or a non-empty "
            "sequence of WorkerTrace"
        )
    return out


class ServingEngine:
    """Request queue + continuous batcher over private weight matrices.

    ``w``: one [k, out] matrix, or ``{name: [k, out]}`` — the layer
    owner's private operands; a request names the one it multiplies
    against (``submit(..., weight=name)``; an engine of one matrix
    calls it ``"w"``).  Per-request fixed-point scales are chosen from
    each request's own activation range, so one engine serves requests
    of very different magnitudes exactly.  The weights never change, so
    their int32 field residues stay on the device, one array per
    ``(name, scale)``, uploaded on that pair's first request; each
    replay stacks its B operand on the device from them, and only the
    encoded activations cross from the host.  All weights share one
    worker pool, one pipeline session and one planner.

    Rows: ``row_buckets`` is a ladder of row counts, each divisible by
    ``t``; without one, the first request's row count is the ladder's
    one rung.  A request of any count up to the largest rung is padded
    with zero rows to the smallest rung that holds it, and the padding
    is stripped after decode.  A replay batches requests of one shape
    class: one weight shape ``(k, out)`` and one rung.  It holds at
    most ``max_batch`` requests and, under ``max_rows``, no more rows
    than that, but always one request (``batch_cap``): ``max_rows``
    bounds a replay's device memory.  A replay's batch pads with zero
    entries, whose answers are dropped, to the smallest batch the
    engine has already launched for its shape class, whose program is
    compiled (``launch_batch``); so once set-up has launched a ladder
    of batch sizes, the window compiles nothing.

    Usage: ``submit()`` requests (simulated arrival stamps), then one
    ``run()`` to drain the queue; ``report.requests`` carries each
    request's full lifecycle.  ``submit`` after ``run`` starts a new
    load wave on the same engine clock.
    """

    def __init__(
        self,
        w: Union[np.ndarray, Mapping[str, np.ndarray]],
        traces: TraceSource,
        config: Optional[PlanConfig] = None,
        *,
        field: Optional[Field] = None,
        seed: int = 0,
        mode: str = "continuous",
        pipe_depth: int = 2,
        max_batch: int = 8,
        slo: Optional[float] = None,
        admission: bool = True,
        degrade_factor: float = 3.0,
        recent_window: int = 5,
        decode_mode: str = "hybrid",
        verify_extras="auto",
        error_budget="auto",
        master_decode_cost: float = 0.0,
        max_subset_tries: int = DEFAULT_SUBSET_TRIES,
        backend: str = "auto",
        mesh=None,
        axis: str = "workers",
        exchange_mode: str = "all_to_all",
        planner=None,
        plan_seed: int = 0,
        validate: bool = False,
        row_buckets: Optional[Sequence[int]] = None,
        max_rows: Optional[int] = None,
    ):
        if mode not in ("continuous", "boundary"):
            raise ValueError(f"mode must be 'continuous' or 'boundary', got {mode!r}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        named = w if isinstance(w, Mapping) else {DEFAULT_WEIGHT: w}
        if not named:
            raise ValueError("need at least one weight matrix")
        self.weights: Dict[str, np.ndarray] = {
            name: np.asarray(m, np.float64) for name, m in named.items()
        }
        for name, m in self.weights.items():
            if m.ndim != 2:
                raise ValueError(f"weight {name!r} must be [k, out], got {m.shape}")
        self.config = config or PlanConfig()
        self.field = field or Field()
        self.seed = seed
        self.mode = mode
        if pipe_depth < 2:
            raise ValueError(
                f"pipe_depth must be >= 2 (1 is 'boundary' mode), got {pipe_depth}"
            )
        self.pipe_depth = int(pipe_depth)
        self.max_batch = int(max_batch)
        self.slo = slo
        self.admission = admission
        self.degrade_factor = float(degrade_factor)
        self.recent_window = int(recent_window)
        self.planner = planner
        self.validate = validate
        self._session_kw = dict(
            verify_extras=verify_extras,
            master_decode_cost=master_decode_cost,
            mesh=mesh,
            axis=axis,
            mode=exchange_mode,
            backend=backend,
            plan_seed=plan_seed,
            decode_mode=decode_mode,
            error_budget=error_budget,
            max_subset_tries=max_subset_tries,
        )
        self._decode_mode = decode_mode
        self._plan_seed = plan_seed
        for name, m in self.weights.items():
            k, out = m.shape
            if k % self.config.s:
                raise ValueError(
                    f"s={self.config.s} must divide {name}'s inner dim k={k}"
                )
            if out % self.config.t:
                raise ValueError(
                    f"t={self.config.t} must divide {name}'s output dim {out}"
                )
        # None until the first request, whose row count is then the one rung
        self.row_buckets: Optional[Tuple[int, ...]] = None
        if row_buckets is not None:
            self.row_buckets = tuple(sorted({int(b) for b in row_buckets}))
            bad = [b for b in self.row_buckets if b < 1 or b % self.config.t]
            if not self.row_buckets or bad:
                raise ValueError(
                    f"row buckets must be positive multiples of t={self.config.t}, "
                    f"got {list(row_buckets)}"
                )
        self.max_rows = max_rows

        self._traces = _trace_list(traces)
        self._t_idx = 0
        self._w_max = {
            name: float(np.abs(m).max() + 1e-9) for name, m in self.weights.items()
        }
        self._w_dev: dict = {}  # (name, scale) -> int32 residues on the device
        self.rows_served = 0  # request rows launched, across replays
        self.rows_launched = 0  # rows launched, bucket and batch padding included
        self._queue: List[Request] = []
        self._all: List[Request] = []
        self._next_rid = 0
        self._obs: list = []  # engine-side ObservedRun history
        self._session: Optional[PipelineSession] = None
        self._pool_n: Optional[int] = None
        self._cfg_fit: Optional[PlanConfig] = None
        self._plans: dict = {}  # shape class -> plan of the fitted config
        self._launched: dict = {}  # shape class -> batch sizes launched (compiled)
        self._clock = 0.0  # reconfiguration barrier carries across sessions
        self._replays_total = 0  # across sessions/reconfigurations

    # -- submission ------------------------------------------------------

    def submit(
        self,
        x: np.ndarray,
        arrival: float,
        deadline: Optional[float] = None,
        weight: Optional[str] = None,
    ) -> Request:
        """Queue one request: ``x`` [rows, k] activation rows against
        weight ``weight`` (may be omitted when the engine holds one),
        arriving at simulated time ``arrival``.  ``deadline`` is
        absolute; when ``None`` and the engine has an ``slo``, it
        defaults to ``arrival + slo``.  Returns the live
        :class:`Request` record (mutated in place as the engine serves
        it).

        Raises ``ValueError`` where no fixed-point scale keeps the
        product from wrapping mod p: ``k * max|x| * max|w|`` at scale 1
        already reaches ``(p - 1) / 2``."""
        if weight is None:
            if len(self.weights) != 1:
                raise ValueError(
                    f"name the weight: this engine holds {sorted(self.weights)}"
                )
            (weight,) = self.weights
        if weight not in self.weights:
            raise ValueError(f"unknown weight {weight!r} (held: {sorted(self.weights)})")
        k = self.weights[weight].shape[0]
        x = np.asarray(x, np.float64)
        if x.ndim == 1:
            x = x[None]
        if x.ndim != 2 or x.shape[1] != k:
            raise ValueError(f"x must be [rows, k={k}], got {x.shape}")
        rows = int(x.shape[0])
        if self.row_buckets is None:  # the first request's rows: the one rung
            if rows < 1 or rows % self.config.t:
                raise ValueError(f"t={self.config.t} must divide request rows {rows}")
            self.row_buckets = (rows,)
        bucket = next((b for b in self.row_buckets if b >= rows), None)
        if rows < 1 or bucket is None:
            raise ValueError(
                f"request rows {rows} outside the row buckets {self.row_buckets}"
            )
        x_max, w_max = float(np.abs(x).max() + 1e-9), self._w_max[weight]
        if k * x_max * w_max >= (self.field.p - 1) // 2:
            raise ValueError(
                f"no fixed-point scale fits: k={k} * max|x|={x_max} * "
                f"max|{weight}|={w_max} >= (p-1)/2 = {(self.field.p - 1) // 2}, "
                "so the product could wrap mod p"
            )
        if deadline is None and self.slo is not None:
            deadline = float(arrival) + float(self.slo)
        req = Request(
            rid=self._next_rid,
            x=x,
            arrival=float(arrival),
            deadline=deadline,
            weight=weight,
            bucket=bucket,
            scale=choose_scales(k, x_max, w_max, self.field.p),
        )
        self._next_rid += 1
        self._queue.append(req)
        self._all.append(req)
        return req

    # -- pool health / admission ----------------------------------------

    def _estimate_all(self):
        if self.planner is not None:
            return self.planner.estimate()
        return estimate_pool(self._obs)

    def _predicted_service(self) -> tuple:
        """(service prediction or None, degraded flag).

        The prediction is the more pessimistic of the all-history and
        recent-window fits; ``degraded`` flags the two disagreeing by
        more than ``degrade_factor`` (or recent infeasibility), which
        is the defer signal.  ``None`` = no observations yet: admit
        optimistically and let the first replays train the estimator.
        """
        cfg = self._cfg_fit
        args = (cfg.n_workers, cfg.decode_threshold, self._pool_n)
        est_all = self._estimate_all()
        pred_all = (
            est_all.predict_completion(*args) if est_all.n_runs else None
        )
        pred_recent = None
        if len(self._obs) >= self.recent_window:
            est_recent = estimate_pool(self._obs[-self.recent_window:])
            pred_recent = est_recent.predict_completion(*args)
        if pred_all is None and pred_recent is None:
            return None, False
        degraded = (
            pred_all is not None
            and pred_recent is not None
            and math.isfinite(pred_all)
            and (
                not math.isfinite(pred_recent)
                or pred_recent > self.degrade_factor * pred_all
            )
        )
        finite = [
            p for p in (pred_all, pred_recent)
            if p is not None and math.isfinite(p)
        ]
        predicted = max(finite) if finite else float("inf")
        return predicted, degraded

    def _shed(self, req: Request, t: float, reason: str) -> None:
        req.state = SHED
        req.shed_reason = reason
        if TRACER.enabled:
            TRACER.sim_event(
                "serve.shed", float(t), track=("request", req.rid),
                request=req.rid, reason=reason,
            )

    def _admit(self, t_launch: float) -> List[Request]:
        """FIFO admission over requests already arrived at ``t_launch``,
        shedding hopeless deadlines and halving the cap while the pool
        estimates disagree (degraded => defer the tail to later
        launches).  A replay holds one shape class: the first arrived
        request's, and the others wait their turn.  Mutates the queue;
        returns the admitted batch."""
        candidates = [r for r in self._queue if r.arrival <= t_launch + 1e-12]
        limit = self.max_batch
        if candidates:
            head = self._shape_class(candidates[0])
            candidates = [r for r in candidates if self._shape_class(r) == head]
            limit = self.batch_cap(head[2])
        if not self.admission:
            batch = candidates[:limit]
            for r in batch:
                self._queue.remove(r)
            return batch
        predicted, degraded = self._predicted_service()
        cap = limit if not degraded else max(1, limit // 2)
        admitted: List[Request] = []
        for r in candidates:
            if len(admitted) == cap:
                break  # deferred to a later launch, not shed
            if (
                r.deadline is not None
                and predicted is not None
                and t_launch + predicted > r.deadline + 1e-9
            ):
                self._queue.remove(r)
                self._shed(r, t_launch, "deadline")
                continue
            self._queue.remove(r)
            admitted.append(r)
        return admitted

    def _shape_class(self, r: Request) -> tuple:
        """What one compiled replay program serves: ``(k, out, rows)``."""
        return self.weights[r.weight].shape + (r.bucket,)

    def batch_cap(self, rows: int) -> int:
        """Requests a replay of ``rows``-row requests holds at most:
        ``max_batch``, and under ``max_rows`` no more than fit in it, but
        at least one."""
        if self.max_rows is None:
            return self.max_batch
        return max(1, min(self.max_batch, self.max_rows // rows))

    def launch_batch(self, n: int, shape_class: tuple) -> int:
        """The batch a replay of ``n`` requests of ``shape_class``
        launches at: the smallest this engine has launched for the class
        that holds ``n``, or ``n`` itself."""
        return min((b for b in self._launched.get(shape_class, ()) if b >= n), default=n)

    # -- session / pool management --------------------------------------

    def _peek_trace(self) -> WorkerTrace:
        return self._traces[self._t_idx % len(self._traces)]

    def _reconfigure(self, n: int) -> bool:
        """(Re)build the session for a pool of ``n`` workers at the
        reconfiguration barrier.  Returns False when the pool cannot
        seat the construction (caller sheds the remaining queue)."""
        if self._session is not None:
            self._clock = self._session.busy_until()
        try:
            cfg = self.config.fit_to_pool(n)
        except ValueError:
            return False
        self._pool_n = n
        self._cfg_fit = cfg
        hybrid = (
            HybridState() if self._decode_mode == "hybrid" else None
        )
        if self.planner is not None:
            self._session = PipelineSession(
                None, planner=self.planner, seed=self.seed,
                base_time=self._clock, hybrid_state=hybrid,
                **self._session_kw,
            )
        else:
            self._plans = {}
            plan = self._plan_for(self._shape_class(self._queue[0]))
            self._session = PipelineSession(
                plan, seed=self.seed, base_time=self._clock,
                hybrid_state=hybrid, **self._session_kw,
            )
        return True

    def _plan_for(self, shape_class: tuple) -> CMPCPlan:
        """The fitted config's plan for one shape class, looked up once
        per session."""
        if shape_class not in self._plans:
            k, out, rows = shape_class
            cfg = self._cfg_fit
            shapes = BlockShapes(k=k, ma=rows, mb=out, s=cfg.s, t=cfg.t)
            self._plans[shape_class] = get_plan_for(
                cfg, shapes, field=self.field, seed=self._plan_seed
            )
        return self._plans[shape_class]

    # -- the batcher loop ------------------------------------------------

    def run(self) -> EngineReport:
        """Drain the queue: admit, launch, decode, account.  Returns the
        :class:`EngineReport`; every submitted request ends ``done`` or
        ``shed`` — a drained queue leaves nothing in flight."""
        with TRACER.span("serve.run", requests=len(self._queue)):
            while self._queue:
                with TRACER.span("serve.admit") as sp:
                    trace = self._peek_trace()
                    if self._pool_n != trace.n and not self._reconfigure(trace.n):
                        # Pool cannot seat the construction: nothing this
                        # engine launches can complete — shed the queue.
                        t = self._clock
                        for r in list(self._queue):
                            self._shed(r, t, "pool")
                        self._queue.clear()
                        break
                    sp.set(replay=self._session.depth)
                    t_ready = self._session.ready_at(
                        self.pipe_depth if self.mode == "continuous" else 1
                    )
                    t_launch = max(t_ready, min(r.arrival for r in self._queue))
                    batch = self._admit(t_launch)
                if not batch:
                    continue  # everything eligible was shed; queue shrank
                self._t_idx += 1
                shape_class = self._shape_class(batch[0])
                k, _, rows = shape_class
                launched = self.launch_batch(len(batch), shape_class)
                with TRACER.span("serve.encode", replay=self._session.depth) as sp:
                    aq = np.zeros((launched, k, rows), np.int64)  # padding stays 0
                    for i, r in enumerate(batch):
                        aq[i, :, : r.x.shape[0]] = self.field.encode(r.x.T, r.scale)
                    keys = [(r.weight, r.scale) for r in batch]
                    keys += keys[:1] * (launched - len(batch))
                    new = set(keys) - self._w_dev.keys()
                    for name, s in new:
                        self._w_dev[name, s] = jax.device_put(
                            self.field.encode(self.weights[name], s).astype(np.int32)
                        )
                    bq = _stack([self._w_dev[key] for key in keys])  # [launched, k, out]
                    sp.set(
                        bytes=int(aq.nbytes + bq.nbytes),
                        requests=len(batch),
                        w_hits=len(batch) - len(new),
                        w_bytes=sum(int(self._w_dev[key].nbytes) for key in new),
                    )
                self.rows_served += sum(int(r.x.shape[0]) for r in batch)
                self.rows_launched += launched * rows
                self._launched.setdefault(shape_class, set()).add(launched)
                replay = self._session.append(
                    aq, bq, trace, not_before=t_launch,
                    obs_attrs={"n_requests": len(batch)},
                    plan=(
                        None if self.planner is not None
                        else self._plan_for(shape_class)
                    ),
                )
                self._obs.append(observed_run(replay.metrics, start=replay.start))
                self._replays_total += 1
                with TRACER.span("serve.decode", replay=replay.index):
                    yq = np.asarray(replay.y)  # [launched, rows, out] field values
                    for i, r in enumerate(batch):
                        s = r.scale
                        if self.validate:
                            want = self.field.matmul(
                                aq[i].T, self.field.encode(self.weights[r.weight], s)
                            )
                            if not np.array_equal(yq[i], want):
                                raise AssertionError(
                                    f"request {r.rid}: decode disagrees with the "
                                    f"field oracle on replay {replay.index}"
                                )
                        r.y = self.field.decode(yq[i, : r.x.shape[0]], s * s)
                        r.state = DONE
                        r.launch = replay.start
                        r.completion = replay.completion
                        r.replay = replay.index
                        if TRACER.enabled:
                            rtrack = ("request", r.rid)
                            TRACER.sim_span(
                                "serve.queue", r.arrival, replay.start,
                                track=rtrack, request=r.rid, replay=replay.index,
                            )
                            TRACER.sim_span(
                                "serve.service", replay.start, replay.completion,
                                track=rtrack, request=r.rid, replay=replay.index,
                                deadline_met=r.met_deadline,
                            )
        return self.report()

    def report(self) -> EngineReport:
        done = [r for r in self._all if r.state == DONE]
        makespan = 0.0
        if done:
            makespan = max(r.completion for r in done) - min(
                r.arrival for r in self._all
            )
        return EngineReport(
            requests=list(self._all),
            replays=self._replays_total,
            makespan=makespan,
        )

    def forget(self, requests: Sequence[Request]) -> None:
        """Drop served requests from the record ``report()`` reads, so a
        caller that has taken their answers leaves the engine holding
        none of its arrays."""
        gone = {id(r) for r in requests}
        self._all = [r for r in self._all if id(r) not in gone]

    def pipeline_result(self) -> PipelineRun:
        """The underlying session's :class:`PipelineRun` (current pool's
        session only — earlier sessions end at reconfigurations)."""
        if self._session is None:
            raise ValueError("nothing launched yet")
        return self._session.result()
