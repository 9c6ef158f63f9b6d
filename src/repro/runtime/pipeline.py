"""The replay: batched protocol executions over the pool, K in flight.

This module is the one place a replay is built: it shares the operands
on the batched device engine, folds the batch into each worker's
payload (``_batched_compute_closure``), resolves the corruption knobs
and drives the scheduler's event loop.  One batched replay amortizes
the event loop across a *batch* of products; back-to-back replays
would still leave replay k+1's Phase-1 upload waiting for replay k's
decode, even though the master's links and the workers sit idle for
most of that span.  The session overlaps them — the ROADMAP's
"pipelining many batched replays with overlapping traces" item, and its
Phase-1/Phase-2 overlap rule is the "overlapping Phase-1 transfers with
Phase-2 compute" item.

Pipeline timing model (two serial resources, everything else overlaps):

* **master -> worker link**: replay k's share to worker ``w`` starts
  the moment replay k-1's share to ``w`` has *arrived* (store-and-
  forward per link; links to different workers are independent), so
  ``arrive[k, w] = sum_{j <= k} share_delay_j(w)``,
* **worker compute**: worker ``w`` starts replay k's H(alpha_n) at
  ``max(arrive[k, w], finish[k-1, w])`` — one multiply at a time;
  dropped workers never compute, so they release the worker
  immediately.  A worker *abandons* replay k's compute the moment
  replay k's Phase-2 set is announced without it: its H(alpha_n) can
  no longer enter the exchange, so queueing it further would only
  starve replay k+1 (without cancellation a straggler's stale compute
  compounds across replays and pipelining can lose to back-to-back
  execution).

Phases 2 and 3 of each replay proceed independently through the shared
event loop (``scheduler._replay_events``) with these absolute times
injected: each in-flight replay fixes its own fastest-``n_workers``
Phase-2 set, runs its own (link-aware) exchange, and decodes from its
own fastest responder subset — the fastest-subset/decode-subset
machinery is reused per replay, per-replay traces may differ (that is
what "overlapping traces" means), and faults are per-(replay, worker).

The upshot: replay k+1's Phase-1 transfers overlap replay k's Phase-2
compute whenever ``share_delay`` < completion span, which is exactly
the edge regime (fast links, slow/heterogeneous compute).  Aggregate
accounting lands in :class:`~repro.runtime.metrics.PipelineMetrics`
(makespan, per-replay spans, pipeline occupancy, Phase-1 overlap, and
the summed communication ``Trace``).

Every entry point is a :class:`PipelineSession`:

* :class:`PipelineSession` — the stateful core: replays are *appended*
  one at a time against the live link/compute occupancy, so a caller
  (the serving engine) can decide replay k+1's contents *after* seeing
  replay k's outcome, inject request-arrival floors (``not_before``),
  and stop whenever its queue drains.  Nothing about the pipeline is
  fixed up front — not the depth, not the batch sizes, not even the
  construction (per-append planner decisions).
* :func:`run_pipeline_over_pool` — the fixed-K convenience wrapper:
  prepares a ``[K, batch, k, m]`` operand stack and appends each slice
  back-to-back.
* :func:`run_batch_over_pool` / :func:`run_over_pool` — one batch (one
  product) through one trace: a one-append session.  With nothing
  before it, the append's timeline is the standalone one: shares
  arrive at ``share_delay``, H(alpha_n) finishes ``compute_scale *
  compute_delay`` later, and a dropout never enters the event heap.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import protocol as proto
from ..core.distributed import run_phase2_sharded
from ..core.planner import CMPCPlan
from ..obs.metrics import REGISTRY
from ..obs.tracer import TRACER
from .metrics import PipelineMetrics, RunMetrics
from .pool import WorkerTrace
from .scheduler import (
    DEFAULT_SUBSET_TRIES,
    HybridState,
    _build_metrics,
    _check_pool,
    _replay_events,
    _resolve_decode_mode,
    _resolve_error_budget,
    _resolve_hybrid,
    _resolve_verify_extras,
)


@dataclasses.dataclass
class EdgeRun:
    """Result of one execution over the pool."""

    y: np.ndarray
    metrics: RunMetrics


@dataclasses.dataclass
class BatchEdgeRun:
    """Result of one batched execution over the pool.

    One event-loop replay served every product: ``per_product`` metrics
    share the timeline and subsets, differing only in the (per-product)
    communication trace; ``metrics`` carries the whole-batch trace.
    The subset id arrays (``phase2_ids``, ``responder_ids``, ...) are
    shared views across entries and the aggregate — treat them as
    read-only.
    """

    y: np.ndarray  # [batch, ma, mb]
    metrics: RunMetrics  # aggregate (batch-level comm accounting)
    per_product: List[RunMetrics]


def _batched_compute_closure(
    plan: CMPCPlan,
    fa: jnp.ndarray,
    fb: jnp.ndarray,
    rng: np.random.Generator,
    batch: int,
    mesh,
    axis: str,
    mode: str,
    backend: str,
    replay: int = 0,
) -> Callable[[np.ndarray], np.ndarray]:
    """``compute_i_all`` for a batched replay.

    Folds the whole batch into each worker's payload so one Phase-2
    pass serves every product; with ``mesh`` the exchange is the real
    ``shard_map`` collective driven by the scheduler's fastest subset.
    A ``protocol.phase2`` wall span (``replay`` = session index) covers
    each call, so every Phase-2 device program is launched under it.
    """
    bry, bcy = plan.shapes.blk_y

    def compute_i_all(phase2_ids: np.ndarray) -> np.ndarray:
        with TRACER.span("protocol.phase2", replay=replay):
            if mesh is not None:
                # Faithful distributed exchange: per-worker blinding draws,
                # whole batch on one collective, sender subset = the
                # scheduler's fastest n_workers.
                noise = plan.field.random(
                    rng, (batch, plan.n_workers, plan.scheme.z, bry, bcy)
                )
                i_b = run_phase2_sharded(
                    plan, fa, fb, noise, mesh,
                    axis=axis, mode=mode, matmul_backend=backend,
                    worker_ids=phase2_ids,
                )  # [batch, n_total, bry, bcy]
                return np.moveaxis(np.asarray(i_b), 1, 0).reshape(
                    plan.n_total, batch * bry, bcy
                )
            # Dense simulation: fold the batch into the block rows so the
            # existing degree-reduction matmul serves every product at once.
            h = proto.worker_multiply(plan, fa, fb)  # [batch, n_total, bry, bcy]
            h_w = jnp.moveaxis(h, 0, 1).reshape(plan.n_total, batch * bry, bcy)
            return proto.degree_reduce(plan, h_w, rng, worker_ids=phase2_ids)

    return compute_i_all


def _unfold_batched_y(plan: CMPCPlan, coeffs: np.ndarray, batch: int) -> np.ndarray:
    """Per-product assembly: the interpolated coefficients carry the
    batch in their payload; unfold and lay out every Y at once (the
    batched mirror of ``assemble_y``)."""
    t = plan.scheme.t
    sh = plan.shapes
    bry, bcy = sh.blk_y
    blocks = coeffs.reshape(-1, batch, bry, bcy)[: t * t].reshape(
        t, t, batch, bry, bcy
    )  # [l, i, b, ., .]
    return blocks.transpose(2, 1, 3, 0, 4).reshape(batch, sh.ma, sh.mb)


@dataclasses.dataclass
class PipelineRun:
    """Result of K pipelined batched replays.

    ``y[k]`` is replay k's decoded batch; ``replay_metrics[k]`` its
    :class:`RunMetrics` on the absolute pipeline clock (batch-level
    aggregate accounting, like ``BatchEdgeRun.metrics``); ``metrics``
    the cross-replay :class:`PipelineMetrics`.
    """

    y: np.ndarray  # [K, batch, ma, mb]
    replay_metrics: List[RunMetrics]
    metrics: PipelineMetrics


def _prep_pipeline_operands(plan, a, b, depth: int):
    """Promote operands to [K, batch, k, m]; validate against the plan
    when one is fixed up front (auto-planned pipelines pick per-replay
    plans whose block splits differ, but the global dims still bind)."""
    a = np.asarray(a)
    b = np.asarray(b)
    if a.ndim == 3:  # [K, k, m] -> batch-1 replays
        a = a[:, None]
    if b.ndim == 3:
        b = b[:, None]
    if a.ndim != 4 or b.ndim != 4:
        raise ValueError(
            f"expected [K, batch, k, m] operand stacks, got {a.shape} {b.shape}"
        )
    if a.shape[0] != depth or b.shape[0] != depth:
        raise ValueError(
            f"{depth} traces but operand stacks of depth {a.shape[0]} / "
            f"{b.shape[0]}"
        )
    if a.shape[1] != b.shape[1]:
        raise ValueError(f"batch mismatch: {a.shape[1]} vs {b.shape[1]}")
    if a.shape[2] != b.shape[2]:
        raise ValueError(f"inner-dim mismatch: {a.shape[2]} vs {b.shape[2]}")
    if plan is not None:
        sh = plan.shapes
        if a.shape[2:] != (sh.k, sh.ma) or b.shape[2:] != (sh.k, sh.mb):
            raise ValueError(
                f"operands {a.shape[2:]}/{b.shape[2:]} disagree with plan "
                f"shapes ({sh.k}, {sh.ma})/({sh.k}, {sh.mb})"
            )
    return a, b


@dataclasses.dataclass
class PipelineReplay:
    """One appended replay's outcome on the session's absolute clock."""

    index: int  # session replay index (rng lane)
    y: np.ndarray  # [batch, ma, mb]
    metrics: RunMetrics
    start: float  # first Phase-1 send (absolute)
    completion: float  # decode acceptance (absolute)
    batch: int
    decision: Optional[object] = None  # PlanDecision when planner-driven


class PipelineSession:
    """Stateful pipelined replays: append batched replays mid-flight.

    Holds the two serial resources of the pipeline timing model — the
    per-worker master-link occupancy (``upload_free``) and per-worker
    compute occupancy (``worker_free``) — across an *open-ended*
    sequence of :meth:`append` calls.  Each append replays one batch
    through the shared event loop at absolute times derived from the
    live occupancy, exactly as one iteration of the fixed-K pipeline;
    between appends the caller is free to look at results, consult a
    queue, or change the batch size — that is what lets the serving
    engine admit requests into an in-flight pipeline instead of
    waiting for a batch boundary.

    ``not_before`` on an append floors the replay's upload start (a
    request that arrives at t cannot be shared before t); with the
    default 0.0 and ``base_time=0.0`` the session replays
    byte-identically to the historical fixed-K loop — same rng streams
    (``default_rng([seed, k])`` / folded JAX key, ``k`` the session
    replay counter), same timestamps.

    ``base_time`` starts the clock late: a session rebuilt after a pool
    reconfiguration continues on the absolute clock of its predecessor.

    ``decode_mode="hybrid"`` holds one :class:`HybridState` for the
    whole session (pass ``hybrid_state`` to share or pre-escalate it):
    the first rejected responder on any append escalates every later
    append to Berlekamp-Welch correction.

    Pool size is fixed by the first append (the serialized occupancy
    vectors assume a stable worker set); a pool resize needs a new
    session — see the serving engine's reconfiguration barrier.
    """

    def __init__(
        self,
        plan: Optional[CMPCPlan] = None,
        *,
        seed: int = 0,
        verify_extras="auto",
        master_decode_cost: float = 0.0,
        mesh=None,
        axis: str = "workers",
        mode: str = "all_to_all",
        backend: str = "auto",
        planner=None,
        plan_seed: int = 0,
        compute_scale="auto",
        decode_mode: str = "detect",
        error_budget="auto",
        max_subset_tries: int = DEFAULT_SUBSET_TRIES,
        base_time: float = 0.0,
        hybrid_state: Optional[HybridState] = None,
    ):
        if plan is None and planner is None:
            raise ValueError("need a plan or a planner")
        self.plan = plan
        self.planner = planner
        self.seed = seed
        self.base_time = float(base_time)
        self._verify_extras = verify_extras
        self._master_decode_cost = master_decode_cost
        self._mesh = mesh
        self._axis = axis
        self._mode = mode
        self._backend = backend
        self._plan_seed = plan_seed
        self._compute_scale = compute_scale
        self._decode_mode = decode_mode
        self._error_budget = error_budget
        self._max_subset_tries = max_subset_tries
        if hybrid_state is None and decode_mode == "hybrid":
            hybrid_state = HybridState()
        self.hybrid_state = hybrid_state
        self._key = jax.random.PRNGKey(seed)

        self._n: Optional[int] = None  # pool size, fixed at first append
        self._upload_free: Optional[np.ndarray] = None
        self._worker_free: Optional[np.ndarray] = None
        self._replays: List[PipelineReplay] = []
        self._agg_trace = None

    # -- introspection the batcher schedules against --------------------

    @property
    def depth(self) -> int:
        """Replays appended so far."""
        return len(self._replays)

    def next_start(self) -> float:
        """Earliest absolute time the next append's Phase 1 can begin
        (the soonest any master link frees up) — the continuous
        batcher's launch clock."""
        if self._upload_free is None:
            return self.base_time
        return float(self._upload_free.min())

    def busy_until(self) -> float:
        """Latest decode acceptance so far (``base_time`` when empty) —
        the batch-boundary launch clock."""
        if not self._replays:
            return self.base_time
        return max(r.completion for r in self._replays)

    def ready_at(self, pipe_depth: int = 1) -> float:
        """Earliest launch time keeping at most ``pipe_depth`` replays
        in flight (and the master uplink free).

        ``pipe_depth=1`` is the batch-boundary discipline — wait for
        every in-flight replay to decode.  ``pipe_depth>=2`` is
        continuous batching: the next replay's Phase-1 upload launches
        while the tail replay is still in its Phase-2/Phase-3 window,
        so requests overlap the in-flight batch instead of waiting for
        the pool to drain.
        """
        if pipe_depth < 1:
            raise ValueError(f"pipe_depth must be >= 1, got {pipe_depth}")
        t = self.next_start()
        if len(self._replays) >= pipe_depth:
            comps = sorted(r.completion for r in self._replays)
            t = max(t, comps[len(comps) - pipe_depth])
        return t

    # -- the core: one replay against the live occupancy ----------------

    def append(
        self,
        a: np.ndarray,
        b: np.ndarray,
        trace: WorkerTrace,
        *,
        not_before: float = 0.0,
        obs_attrs: Optional[dict] = None,
        plan: Optional[CMPCPlan] = None,
    ) -> PipelineReplay:
        """Replay one batch (a: [batch, k, ma], b: [batch, k, mb]; 2D
        promotes to batch 1) against ``trace`` at the live occupancy.
        ``plan`` serves this replay in place of the session's own (one
        session, one pool, several operand shapes); it must provision
        the same pool.
        Either operand may be a host array or a ``jax.Array`` already on
        the device; a device operand is reduced mod p where it lives.

        ``not_before`` floors the upload start on every master link —
        the serving engine passes the launch time its admission loop
        chose (>= the admitted requests' arrivals).  Raises
        :class:`~repro.runtime.scheduler.DecodeFailure` when the pool
        cannot complete the protocol (too few live workers, or no
        acceptable decode); a failed append leaves the occupancy state
        untouched (the replay never ran).

        With tracing on, a ``runtime.replay`` wall span covers the
        whole append: shares, exchange, the device-to-host copy of the
        I-evaluations and the decode.  Inside it, ``runtime.upload``
        covers the operands' reduction mod p, int32 cast and transfer
        to the device (``bytes``: what crossed from host to device, the
        int32 size of the host operands alone).
        """
        with TRACER.span("runtime.replay", replay=len(self._replays)):
            return self._append(a, b, trace, not_before, obs_attrs, plan)

    def _append(self, a, b, trace, not_before, obs_attrs, plan) -> PipelineReplay:
        k = len(self._replays)
        if self.planner is None:
            decision = None
            plan_k = self.plan if plan is None else plan
            if trace.n != plan_k.n_total:
                raise ValueError(
                    f"trace {k} covers {trace.n} workers, plan provisions "
                    f"{plan_k.n_total}"
                )
        else:
            # Replay-boundary feedback: decide from everything observed
            # so far, re-fitting spares to the pool (same-construction
            # decisions hit the plan cache; spare refits take the
            # replan fast path).
            from .autoplan import plan_for_decision

            a_shape, b_shape = np.shape(a), np.shape(b)
            decision = self.planner.decide(trace.n)
            plan_k = plan_for_decision(
                decision,
                int(a_shape[-2]),
                int(a_shape[-1]),
                int(b_shape[-1]),
                seed=self._plan_seed,
            )
        if self._n is None:
            self._n = trace.n
            self._upload_free = np.full(self._n, self.base_time)
            self._worker_free = np.full(self._n, self.base_time)
        elif trace.n != self._n:
            raise ValueError(
                f"pipelined replays need one pool size, got "
                f"{sorted({self._n, trace.n})}"
            )
        alive = _check_pool(plan_k, trace)
        extras_k = _resolve_verify_extras(self._verify_extras, trace)
        budget_k = _resolve_error_budget(self._error_budget, trace, plan_k)
        mode_k, budget_k, _ = _resolve_hybrid(
            self._decode_mode, self.hybrid_state, budget_k, plan_k
        )
        mode_k = _resolve_decode_mode(mode_k, budget_k)
        rng = np.random.default_rng([self.seed, k])
        if self._compute_scale == "auto":
            scale_k = (
                self.planner.work_factor(decision.config)
                if self.planner is not None
                else 1.0
            )
        else:
            scale_k = float(self._compute_scale)

        # -- pipeline timing: serialize the master links and compute --
        upload_base = np.maximum(self._upload_free, float(not_before))
        start = float(upload_base.min())
        arrive = upload_base + trace.share_delay
        comp_start = np.maximum(arrive, self._worker_free)
        finish = np.where(
            trace.dropout, comp_start, comp_start + scale_k * trace.compute_delay
        )
        # upload_free/worker_free commit only after the replay succeeds
        # (a DecodeFailure must not half-advance the occupancy).

        # -- numeric path: the batched device engine --
        with TRACER.span("runtime.upload", replay=k) as sp:
            a_j, b_j = proto._prep_batched_operands(plan_k, a, b)
            sp.set(bytes=sum(
                int(x_j.nbytes)
                for x, x_j in ((a, a_j), (b, b_j))
                if not isinstance(x, jax.Array)
            ))
        batch = int(a_j.shape[0])
        fa, fb = proto.share_batched(
            plan_k, a_j, b_j, jax.random.fold_in(self._key, k),
            backend=self._backend,
        )
        compute_i_all = _batched_compute_closure(
            plan_k, fa, fb, rng, batch, self._mesh, self._axis, self._mode,
            self._backend, replay=k,
        )
        # Trace annotations: lane index + absolute start, plus the
        # deciding PlanDecision when a planner drives the pipeline
        # (decision_id links the replay span to its autoplan.decide
        # event).
        obs_k = {"replay": k, "t_start": start, "batch": batch}
        if decision is not None:
            obs_k["decision_id"] = decision.obs_id
            obs_k["config"] = decision.config.label()
        if obs_attrs:
            obs_k.update(obs_attrs)
        res = _replay_events(
            plan_k,
            trace,
            alive,
            compute_i_all,
            extras_k,
            rng,
            self._master_decode_cost,
            share_at=arrive,
            finish_at=finish,
            decode_mode=mode_k,
            error_budget=budget_k,
            max_subset_tries=self._max_subset_tries,
            obs_attrs=obs_k,
        )
        self._upload_free = arrive.copy()
        # Straggler cancellation: a worker outside replay k's Phase-2
        # set abandons its (now useless) H-compute when the set is
        # announced, freeing it for replay k+1.  Set members finished
        # at or before the announcement, so they are unaffected.
        in_set = np.zeros(self._n, bool)
        in_set[res.phase2_ids] = True
        abandoned = ~in_set & ~trace.dropout
        self._worker_free = np.where(
            abandoned,
            np.minimum(finish, np.maximum(comp_start, res.phase2_set_time)),
            finish,
        )

        y = _unfold_batched_y(plan_k, res.coeffs, batch)
        m = _build_metrics(plan_k, trace, alive, res, batch=batch)
        if self.hybrid_state is not None:
            self.hybrid_state.note(m)
        if self.planner is not None:
            self.planner.observe(decision.config, m, start=start)
        self._agg_trace = (
            m.trace if self._agg_trace is None else self._agg_trace + m.trace
        )
        replay = PipelineReplay(
            index=k, y=y, metrics=m, start=start,
            completion=m.completion_time, batch=batch, decision=decision,
        )
        self._replays.append(replay)
        return replay

    # -- aggregation ----------------------------------------------------

    def result(self) -> PipelineRun:
        """Aggregate everything appended so far into a
        :class:`PipelineRun` (requires at least one replay; ``y`` is
        stacked only when every append used one batch size, else the
        per-replay ``replay_metrics``/session records are the API)."""
        if not self._replays:
            raise ValueError("need at least one trace/replay")
        depth = len(self._replays)
        starts = np.array([r.start for r in self._replays])
        completions = np.array([r.completion for r in self._replays])
        phase1_lasts = np.array(
            [r.metrics.phase1_last_share for r in self._replays]
        )
        batches = [r.batch for r in self._replays]
        makespan = float(completions.max())
        busy = makespan - self.base_time
        spans = completions - starts
        # Phase-1 upload time of replay k that ran while replay k-1 (or
        # any earlier one) was still in flight — the overlap the
        # sequential runtime forgoes entirely.
        prev_busy_until = np.concatenate(
            ([self.base_time], np.maximum.accumulate(completions)[:-1])
        )
        phase1_overlap = float(
            np.maximum(
                0.0, np.minimum(phase1_lasts, prev_busy_until) - starts
            ).sum()
        )
        metrics = PipelineMetrics(
            depth=depth,
            batch=max(batches),
            products=int(sum(batches)),
            makespan=makespan,
            completions=completions,
            starts=starts,
            occupancy=float(spans.sum() / busy) if busy > 0 else 0.0,
            phase1_overlap=phase1_overlap,
            trace=self._agg_trace,
        )
        REGISTRY.counter("pipeline.runs").inc()
        REGISTRY.gauge("pipeline.occupancy").set(metrics.occupancy)
        REGISTRY.gauge("pipeline.makespan").set(metrics.makespan)
        REGISTRY.gauge("pipeline.overlap_ratio").set(metrics.overlap_ratio)
        uniform = len(set(batches)) == 1
        y = (
            np.stack([r.y for r in self._replays])
            if uniform
            else np.concatenate([r.y for r in self._replays])
        )
        return PipelineRun(
            y=y,
            replay_metrics=[r.metrics for r in self._replays],
            metrics=metrics,
        )


def run_pipeline_over_pool(
    plan: Optional[CMPCPlan],
    a: np.ndarray,
    b: np.ndarray,
    traces: Sequence[WorkerTrace],
    seed: int = 0,
    verify_extras="auto",
    master_decode_cost: float = 0.0,
    mesh=None,
    axis: str = "workers",
    mode: str = "all_to_all",
    backend: str = "auto",
    planner=None,
    plan_seed: int = 0,
    compute_scale="auto",
    decode_mode: str = "detect",
    error_budget="auto",
    max_subset_tries: int = DEFAULT_SUBSET_TRIES,
    hybrid_state: Optional[HybridState] = None,
) -> PipelineRun:
    """Run K batched replays through the pool with overlapping traces.

    a: [K, batch, k, ma], b: [K, batch, k, mb] ([K, k, m] promotes to
    batch 1); ``traces`` holds one :class:`WorkerTrace` per replay
    (they may differ — each replay faces its own latency/fault/link
    draw).  Replay k+1's Phase-1 upload to each worker starts as soon
    as that master link is free, so transfers overlap earlier replays'
    Phase-2 compute; each replay then fixes its own Phase-2 subset and
    decode subset through the shared event loop.  A replay's decode
    failure raises :class:`DecodeFailure` as :meth:`PipelineSession.append`
    does.

    With ``planner`` (an :class:`~repro.runtime.autoplan.AutoPlanner`)
    the construction is chosen *per replay* at the pipeline's replay
    boundaries: the planner decides from everything observed so far,
    the chosen config is re-fitted to the (fixed-size) pool, and the
    replay's outcome feeds back before the next decision — mid-stream
    scheme/lambda/spare switching inside one pipeline.  ``plan`` may
    then be ``None``; pool size must be constant across traces (the
    pipeline's serialized master links and worker occupancy assume a
    stable worker set — elastic pools go through
    :func:`~repro.runtime.autoplan.run_adaptive_over_pool`).

    ``compute_scale``: per-unit-work compute scaling (see
    ``run_batch_over_pool``).  The default ``"auto"`` resolves to the
    planner's per-construction work factor when a planner is given
    (different constructions do different per-worker work on the same
    trace) and to 1.0 otherwise; pass a float to force one scale.

    ``decode_mode`` / ``error_budget`` / ``max_subset_tries``: the
    corruption-handling knobs of ``run_over_pool``, resolved *per
    replay* against each trace's configured fault model (replays in one
    pipeline may face differently-provisioned fault draws);
    ``decode_mode="hybrid"`` escalates to BW correction after the first
    rejected responder (``hybrid_state`` shares/pre-escalates the
    cross-replay state).

    Randomness: replay k draws from ``default_rng([seed, k])`` and the
    folded JAX key, so replays are independent but the whole pipeline
    is reproducible per seed.

    Returns :class:`PipelineRun` with per-replay results on one
    absolute clock plus the aggregate :class:`PipelineMetrics`.
    """
    depth = len(traces)
    if depth == 0:
        raise ValueError("need at least one trace/replay")
    if plan is None and planner is None:
        raise ValueError("need a plan or a planner")
    if planner is None:
        for k, trace in enumerate(traces):
            if trace.n != plan.n_total:
                raise ValueError(
                    f"trace {k} covers {trace.n} workers, plan provisions "
                    f"{plan.n_total}"
                )
    else:
        sizes = {trace.n for trace in traces}
        if len(sizes) != 1:
            raise ValueError(
                f"pipelined replays need one pool size, got {sorted(sizes)}"
            )
    a, b = _prep_pipeline_operands(plan, a, b, depth)
    session = PipelineSession(
        plan,
        seed=seed,
        verify_extras=verify_extras,
        master_decode_cost=master_decode_cost,
        mesh=mesh,
        axis=axis,
        mode=mode,
        backend=backend,
        planner=planner,
        plan_seed=plan_seed,
        compute_scale=compute_scale,
        decode_mode=decode_mode,
        error_budget=error_budget,
        max_subset_tries=max_subset_tries,
        hybrid_state=hybrid_state,
    )
    for k, trace in enumerate(traces):
        session.append(a[k], b[k], trace)
    return session.result()


def _per_product(m: RunMetrics) -> RunMetrics:
    """One product's share of a batched replay's metrics: the replay's
    timeline and subsets, and the communication of one product (every
    count is linear in the batch)."""
    t, n = m.trace, m.batch
    return dataclasses.replace(m, batch=1, trace=proto.Trace(
        phase1_source_to_worker=t.phase1_source_to_worker // n,
        phase2_worker_to_worker=t.phase2_worker_to_worker // n,
        phase3_worker_to_master=t.phase3_worker_to_master // n,
        elem_bytes=t.elem_bytes,
    ))


def run_batch_over_pool(
    plan: CMPCPlan,
    a: np.ndarray,
    b: np.ndarray,
    trace: WorkerTrace,
    seed: int = 0,
    verify_extras="auto",
    master_decode_cost: float = 0.0,
    mesh=None,
    axis: str = "workers",
    mode: str = "all_to_all",
    backend: str = "auto",
    compute_scale: float = 1.0,
    decode_mode: str = "detect",
    error_budget="auto",
    max_subset_tries: int = DEFAULT_SUBSET_TRIES,
    obs_attrs: Optional[dict] = None,
    hybrid_state: Optional[HybridState] = None,
) -> BatchEdgeRun:
    """Replay a whole batch of products through ONE worker trace: a
    one-append :class:`PipelineSession`.

    a: [batch, k, ma], b: [batch, k, mb] (2D operands promote to batch
    1).  The event loop, Phase-2 fastest-subset barrier, and the
    decode-subset search run once for the whole batch: products fold
    into each worker's payload, which is sound because the timeline
    depends only on the trace, and a corrupt/crashed/dropped worker is
    faulty for every product it touches.

    With ``mesh`` the Phase-2 exchange is the real ``shard_map``
    collective (``core.distributed.run_phase2_sharded``, ``mode`` one of
    ``all_to_all`` / ``psum`` / ``psum_scatter``) driven by the
    scheduler's fastest-subset ``worker_ids``.  Without it, Phase 2 is
    the dense single-host simulation (``degree_reduce``).

    ``compute_scale`` multiplies every worker's compute delay — the
    hook for heterogeneous-work comparisons, where one trace's
    ``compute_delay`` is time per unit work and each construction's
    per-worker work (Corollary 10; ``CostPrediction.compute_factor``)
    sets the scale.

    ``decode_mode`` selects corruption handling (``runtime.scheduler``'s
    docstring): ``"detect"`` confirm-and-retry (the default;
    ``verify_extras`` confirmations, subset search bounded by
    ``max_subset_tries``), ``"correct"`` one Berlekamp-Welch decode over
    the fastest ``thr + 2 * error_budget`` responders (each corrupt
    worker's whole folded payload is corrected at once), ``"auto"``
    correct iff the resolved error budget is positive, ``"hybrid"``
    detect until the first rejection recorded in ``hybrid_state`` then
    correct.  ``error_budget="auto"`` resolves from the trace's
    configured fault model.

    Randomness: the session's replay 0 draws from
    ``default_rng([seed, 0])`` and ``fold_in(PRNGKey(seed), 0)``.

    Returns :class:`BatchEdgeRun`; raises :class:`DecodeFailure` when
    the surviving pool cannot serve Phase 2 (fewer than ``n_workers``
    live workers) or the master never accumulates an acceptable
    responder subset.
    """
    session = PipelineSession(
        plan,
        seed=seed,
        verify_extras=verify_extras,
        master_decode_cost=master_decode_cost,
        mesh=mesh,
        axis=axis,
        mode=mode,
        backend=backend,
        compute_scale=compute_scale,
        decode_mode=decode_mode,
        error_budget=error_budget,
        max_subset_tries=max_subset_tries,
        hybrid_state=hybrid_state,
    )
    replay = session.append(a, b, trace, obs_attrs=obs_attrs)
    # one replay served every product, so the per-product metrics are
    # identical by construction: build once, then give each entry its
    # own object (the subset id arrays stay shared read-only views)
    first = _per_product(replay.metrics)
    per_product = [first] + [
        dataclasses.replace(first) for _ in range(replay.batch - 1)
    ]
    return BatchEdgeRun(y=replay.y, metrics=replay.metrics, per_product=per_product)


def run_over_pool(
    plan: CMPCPlan,
    a: np.ndarray,
    b: np.ndarray,
    trace: WorkerTrace,
    seed: int = 0,
    verify_extras="auto",
    master_decode_cost: float = 0.0,
    compute_scale: float = 1.0,
    decode_mode: str = "detect",
    error_budget="auto",
    max_subset_tries: int = DEFAULT_SUBSET_TRIES,
    obs_attrs: Optional[dict] = None,
    hybrid_state: Optional[HybridState] = None,
) -> EdgeRun:
    """Execute Y = A^T B over the simulated pool described by ``trace``:
    the batch-1 case of :func:`run_batch_over_pool` (same knobs, same
    :class:`DecodeFailure`).

    Returns the decoded product and the run's :class:`RunMetrics`.
    """
    run = run_batch_over_pool(
        plan, a, b, trace,
        seed=seed,
        verify_extras=verify_extras,
        master_decode_cost=master_decode_cost,
        compute_scale=compute_scale,
        decode_mode=decode_mode,
        error_budget=error_budget,
        max_subset_tries=max_subset_tries,
        obs_attrs=obs_attrs,
        hybrid_state=hybrid_state,
    )
    return EdgeRun(y=run.y[0], metrics=run.per_product[0])
