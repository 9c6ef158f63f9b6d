"""Edge worker-pool runtime: straggler-aware protocol execution.

The plan layer (``repro.core.planner``) already supports arbitrary
worker subsets — ``n_spare`` extra evaluation points, ``phase2_matrix``
and ``decode_matrix`` for any surviving set — but the core execution
paths assume every worker answers instantly.  This package turns that
static machinery into an execution engine for the paper's actual
setting: heterogeneous, flaky edge workers.

* ``pool``      — latency models (deterministic / shifted-exponential /
                   heavy-tail) and fault injection (stragglers,
                   dropouts, crash-after-phase-2, corrupted responses),
                   sampled into replayable per-worker traces,
* ``scheduler`` — the event loop: dispatch shares, pick the fastest
                   ``n_workers`` for Phase 2, decode from the fastest
                   ``decode_threshold`` responders (with consistency
                   verification against extra responders when corruption
                   is possible, or Byzantine error *correction* via
                   ``decode_mode="correct"`` — one Berlekamp-Welch decode
                   over the fastest ``thr + 2e`` responders that also
                   names the corrupt workers),
* ``metrics``   — per-run timeline, communication (bytes-level
                   ``Trace`` view), effective worker counts and
                   decode-subset statistics, plus aggregation across
                   runs,
* ``pipeline``  — the replay: ``PipelineSession`` shares a batch on
                   the device engine, folds it into each worker's
                   payload (event loop and decode-subset search
                   amortized across the batch; with a mesh the Phase-2
                   exchange is the real ``shard_map`` collective) and
                   *appends* replays one at a time against the live
                   occupancy: master links and worker compute are
                   serial resources, so replay k+1's Phase-1 transfers
                   overlap replay k's Phase-2 compute (optionally
                   floored by a request-arrival ``not_before``), which
                   is what lets the serving tier (``repro.serve``)
                   admit requests into an in-flight pipeline.
                   ``run_pipeline_over_pool`` appends K replays;
                   ``run_batch_over_pool`` / ``run_over_pool`` are
                   one-append sessions.  Aggregate ``PipelineMetrics``
                   report makespan, occupancy, and Phase-1 overlap.

Traces can be link-resolved: ``NetworkModel`` implementations
(``UniformLinks`` / ``AsymmetricLinks`` / ``ClusteredEdge``) sample a
per-``(sender, receiver)`` Phase-2 delay matrix plus master up/down
links, and the scheduler completes a receiver's exchange at the max
over its *incoming* links.

Scenario layer for the auto-planner (``autoplan``): a
``TimeVaryingLinks`` schedule degrades the Phase-2 fabric mid-replay
(the scheduler resolves the matrix in effect when the exchange goes
out), and an ``ElasticPool`` changes the worker membership between
replays.  ``AutoPlanner`` closes the loop — it fits the pool's
straggler tails and fault rates from observed runs (``estimate_pool``)
and picks the construction for each replay, either sequentially
(``run_adaptive_over_pool``) or mid-stream inside the pipeline
(``run_pipeline_over_pool(..., planner=...)``).
"""
from .pool import (  # noqa: F401
    AsymmetricLinks,
    ClusteredEdge,
    Deterministic,
    ElasticPool,
    FaultSpec,
    HeavyTail,
    LatencyModel,
    NetworkModel,
    ShiftedExponential,
    TimeVaryingLinks,
    UniformLinks,
    WorkerTrace,
    sample_trace,
)
from .scheduler import (  # noqa: F401
    DEFAULT_SUBSET_TRIES,
    DecodeFailure,
    HybridState,
)
from .metrics import (  # noqa: F401
    ObservedRun,
    PipelineMetrics,
    PoolEstimate,
    RunMetrics,
    estimate_pool,
    fit_order_stats,
    observed_run,
    order_stat_mean,
    summarize,
)
from .pipeline import (  # noqa: F401
    BatchEdgeRun,
    EdgeRun,
    PipelineReplay,
    PipelineRun,
    PipelineSession,
    run_batch_over_pool,
    run_over_pool,
    run_pipeline_over_pool,
)
from .autoplan import (  # noqa: F401
    AdaptiveRun,
    AutoPlanner,
    PlanDecision,
    plan_for_decision,
    run_adaptive_over_pool,
)
