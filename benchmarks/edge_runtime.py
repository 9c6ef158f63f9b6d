"""Scheme comparison under edge conditions: PolyDot-CMPC vs AGE-CMPC
replayed over identical worker-pool traces.

The paper's headline claim is that AGE-CMPC needs fewer workers than
PolyDot-CMPC; at the edge that translates into completion time, because
fewer required workers means the fastest-subset barrier falls earlier
under the same straggler distribution.  This harness runs both schemes
through ``repro.runtime`` under per-scenario fault/latency models; the
trace is sampled once per (scenario, seed) at the *largest* pool size
and each scheme replays a prefix, so both face byte-identical worker
behaviour.  Every run's decode is validated against the host oracle
(``Field.matmul``) — a silent straggler-decode bug fails the benchmark.

Scenarios:

* ``all_fast``           — deterministic unit latency, no faults (the
                            paper's idealized setting; completion is
                            pure pipeline depth),
* ``stragglers_exp``     — shifted-exponential compute latency plus a
                            20% straggler population at 10x slowdown,
* ``dropouts``           — shifted-exponential latency with exactly
                            ``n_spare`` dropouts (the provisioned
                            tolerance, fully spent),
* ``heavy_tail_corrupt`` — Pareto-tailed latency plus one corrupted
                            responder; the master must spend one extra
                            confirmation before accepting a decode.

Five extra sections ride along:

* ``batched_replay``   — ``run_batch_over_pool`` replays a whole batch
                          of products through ONE straggler trace; the
                          event loop and decode-subset search are paid
                          once, so the per-product cost drops against a
                          loop of ``run_over_pool`` calls,
* ``sharded_batched``  — the same batched replay with the Phase-2
                          exchange on a REAL multi-device mesh
                          (``shard_map`` all_to_all driven by the
                          scheduler's fastest subset), in a subprocess
                          with ``--xla_force_host_platform_device_count``
                          so the forced device split cannot perturb the
                          single-device scenario numbers,
* ``per_link``         — link-resolved network models: asymmetric
                          uplink/downlink (last-mile edge) and a
                          clustered-edge topology (fast intra-cluster,
                          slow inter-cluster D2D); Phase-2 completion
                          becomes the max over each receiver's incoming
                          links, and both schemes replay byte-identical
                          ``(sender, receiver)`` delay matrices,
* ``pipelined``        — ``run_pipeline_over_pool`` keeps K batched
                          replays in flight with overlapping traces;
                          reports makespan vs the back-to-back
                          sequential replays, pipeline occupancy, and
                          the Phase-1/Phase-2 overlap reclaimed,
* ``byzantine``        — detect (confirm-and-retry) vs correct
                          (Berlekamp-Welch) corruption handling replayed
                          on byte-identical traces as the configured
                          corruption rate sweeps 0 -> 25%: per-rate p50
                          completion, responder overhead over the bare
                          decode threshold (thr + 2e vs thr + extras +
                          retries), decode failures, and the rate at
                          which correction's p50 crosses below
                          detection's,
* ``adaptive``         — the ``AutoPlanner`` feedback loop vs every
                          static candidate construction on
                          byte-identical traces, in two drifting
                          scenarios: ``degrading_links`` (the Phase-2
                          fabric slows 8x mid-stream via
                          ``TimeVaryingLinks`` — once mid-replay, then
                          permanently) and ``elastic_pool`` (an
                          ``ElasticPool`` shrinks 40 -> 22 -> 16, below
                          some candidates' worker counts).  Statics that
                          no longer fit a replay are reported with the
                          replays they *could* run; the planner switches
                          construction mid-stream and its per-replay
                          ``PlanConfig`` choices, switch/respare counts,
                          and fitted pool estimate land in the report.

Emits ``BENCH_edge.json`` at the repo root (``make bench-edge``) with
per-scenario completion statistics, worker counts, and the
PolyDot/AGE completion ratio, plus a CSV under results/bench/.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import constructions as C
from repro.core.constructions import PlanConfig
from repro.core.gf import Field
from repro.core.planner import (
    BlockShapes,
    get_plan,
    get_plan_for,
    subset_cache_info,
)
from repro.runtime import (
    AsymmetricLinks,
    AutoPlanner,
    ClusteredEdge,
    DecodeFailure,
    Deterministic,
    ElasticPool,
    FaultSpec,
    HeavyTail,
    ShiftedExponential,
    TimeVaryingLinks,
    UniformLinks,
    observed_run,
    run_adaptive_over_pool,
    run_batch_over_pool,
    run_over_pool,
    run_pipeline_over_pool,
    sample_trace,
    summarize,
)
from repro.obs import TRACER, snapshot, write_chrome
from repro.runtime.autoplan import _replay_seed

from .common import repo_root, run_sharded_child, timeit, write_csv

JSON_NAME = "BENCH_edge.json"

METHODS = ("polydot", "age")

# Batched-replay scenario: products per trace replay, and the forced
# host device count for the sharded child mesh.
BATCH_REPLAY = 8
SHARDED_DEVICES = 8

# Pipelined scenario: replays in flight and products per replay.
PIPELINE_DEPTH = 4
PIPELINE_BATCH = 4


def _per_link_report(plans, field, rng, m, pool, n_runs=8) -> dict:
    """Link-resolved scenarios: AGE vs PolyDot on identical link draws.

    The legacy scenarios model each worker with one scalar network
    delay; these sample a full ``(sender, receiver)`` matrix per trace
    so a receiver's Phase-2 completion is the max over its incoming
    links.  Both schemes share the pool, so the same trace object
    serves both — byte-identical links, not just byte-identical
    workers.
    """
    a = field.random(rng, (m, m))
    b = field.random(rng, (m, m))
    want = field.matmul(a.T, b)
    latency = ShiftedExponential(shift=1.0, scale=1.0)
    networks = {
        # last-mile edge: Phase-3 responses ride an uplink 5x slower
        # than the Phase-1 downlink
        "asymmetric_updown": AsymmetricLinks(
            latency, down_scale=0.1, d2d_scale=0.1, up_scale=0.5
        ),
        # devices hang off 3 access points: D2D inside a cluster is
        # 10x cheaper than crossing between clusters
        "clustered_edge": ClusteredEdge(
            latency, n_clusters=3, intra_scale=0.05, inter_scale=0.5,
            master_scale=0.1,
        ),
    }
    out = {}
    for name, network in networks.items():
        # ONE trace per run, sampled before the method loop: both
        # schemes replay the identical link matrix by construction,
        # not by seed coincidence.
        run_traces = [
            sample_trace(pool, latency, seed=3000 + run_i, network=network)
            for run_i in range(n_runs)
        ]
        per_method = {}
        for meth, plan in plans.items():
            results = []
            for run_i, trace in enumerate(run_traces):
                res = run_over_pool(plan, a, b, trace, seed=run_i)
                if not np.array_equal(res.y, want):
                    raise AssertionError(
                        f"{meth}/{name} run {run_i}: link-model decode "
                        f"disagrees with oracle"
                    )
                results.append(res.metrics)
            agg = summarize(results)
            agg["n_workers"] = plan.n_workers
            agg["oracle_validated"] = True
            per_method[meth] = agg
        per_method["polydot_over_age_p50"] = round(
            per_method["polydot"]["completion_p50"]
            / per_method["age"]["completion_p50"],
            4,
        )
        out[name] = per_method
    return out


def _pipeline_report(plans, field, rng, m, pool) -> dict:
    """K batched replays in flight vs back-to-back sequential replays.

    Each replay gets its own straggler trace (overlapping traces); the
    sequential baseline replays the identical traces through
    ``run_batch_over_pool`` one at a time, so the speedup isolates the
    pipelining — same subsets, same numerics, every decode of every
    in-flight replay validated against the host oracle.
    """
    K, batch = PIPELINE_DEPTH, PIPELINE_BATCH
    a = field.random(rng, (K, batch, m, m))
    b = field.random(rng, (K, batch, m, m))
    want = np.stack(
        [
            np.stack([field.matmul(a[k, i].T, b[k, i]) for i in range(batch)])
            for k in range(K)
        ]
    )
    latency = ShiftedExponential(shift=1.0, scale=1.0)
    faults = FaultSpec(straggler_frac=0.2, straggler_slowdown=10.0)
    traces = [
        sample_trace(pool, latency, faults, seed=5000 + k) for k in range(K)
    ]
    out = {"depth": K, "batch": batch}
    for meth, plan in plans.items():
        res = run_pipeline_over_pool(plan, a, b, traces, seed=9)
        if not np.array_equal(res.y, want):
            raise AssertionError(f"{meth}: pipelined decode disagrees with oracle")
        sequential = sum(
            run_batch_over_pool(plan, a[k], b[k], traces[k], seed=9)
            .metrics.completion_time
            for k in range(K)
        )
        pm = res.metrics
        out[meth] = {
            "makespan": round(pm.makespan, 4),
            "sequential_completion": round(sequential, 4),
            "pipeline_speedup": round(sequential / pm.makespan, 4),
            "occupancy": round(pm.occupancy, 4),
            "phase1_overlap": round(pm.phase1_overlap, 4),
            "products": pm.products,
            "wire_bytes_total": pm.trace.total_bytes,
            "oracle_validated": True,
        }
    out["polydot_over_age_makespan"] = round(
        out["polydot"]["makespan"] / out["age"]["makespan"], 4
    )
    return out


# Auto-planner scenarios: replays per scenario, products per replay,
# and the planner's knobs (estimator window, exploration ratio).
ADAPTIVE_BATCH = 2
ADAPTIVE_WINDOW = 5
ADAPTIVE_EXPLORE_RATIO = 1.5


def _adaptive_statics(candidates, traces, a, b, want, m, seed) -> dict:
    """Replay every static candidate over the exact traces the planner
    faces — same per-replay seeds (``_replay_seed``), same per-
    construction ``compute_scale`` work factors — so the comparison
    isolates the *decisions*, not the simulation draw.  A static that
    does not fit some replay's pool reports only the replays it could
    run (the planner has no such gap: it switches)."""
    K, batch = a.shape[0], a.shape[1]
    ref = AutoPlanner(candidates, cost_m=m)
    out = {}
    for cand in ref.candidates:
        wf = ref.work_factor(cand)
        times = []
        plans = {}
        for k, trace in enumerate(traces):
            if cand.n_workers > trace.n:
                continue
            cfg = cand.fit_to_pool(trace.n)
            if cfg.n_total not in plans:
                plans[cfg.n_total] = get_plan_for(
                    cfg, BlockShapes(k=m, ma=m, mb=m, s=cfg.s, t=cfg.t)
                )
            res = run_batch_over_pool(
                plans[cfg.n_total], a[k], b[k], trace,
                seed=_replay_seed(seed, k), compute_scale=wf,
            )
            for i in range(batch):
                if not np.array_equal(res.y[i], want[k][i]):
                    raise AssertionError(
                        f"static {cand.label()} replay {k}: decode "
                        f"disagrees with oracle"
                    )
            times.append(res.metrics.completion_time)
        out[cand.label()] = {
            "work_factor": round(wf, 4),
            "feasible_replays": len(times),
            "completion_p50": round(float(np.percentile(times, 50)), 4),
            "completion_mean": round(float(np.mean(times)), 4),
            "fits_all_replays": len(times) == K,
            "oracle_validated": True,
        }
    return out


def _adaptive_scenario(candidates, traces, field, rng, m, seed) -> dict:
    """One adaptive scenario: planner vs every static on shared traces."""
    K = len(traces)
    batch = ADAPTIVE_BATCH
    a = field.random(rng, (K, batch, m, m))
    b = field.random(rng, (K, batch, m, m))
    want = [
        [field.matmul(a[k, i].T, b[k, i]) for i in range(batch)]
        for k in range(K)
    ]
    statics = _adaptive_statics(candidates, traces, a, b, want, m, seed)
    planner = AutoPlanner(
        candidates,
        cost_m=m,
        window=ADAPTIVE_WINDOW,
        explore_ratio=ADAPTIVE_EXPLORE_RATIO,
    )
    run = run_adaptive_over_pool(planner, a, b, traces, seed=seed)
    for k in range(K):
        for i in range(batch):
            if not np.array_equal(run.y[k, i], want[k][i]):
                raise AssertionError(
                    f"adaptive replay {k}: decode disagrees with oracle"
                )
    times = np.array([rm.completion_time for rm in run.replay_metrics])
    adaptive_p50 = float(np.percentile(times, 50))
    full = {
        name: s["completion_p50"]
        for name, s in statics.items()
        if s["fits_all_replays"]
    }
    best = min(full.values())
    worst = max(full.values())
    return {
        "replays": K,
        "batch": batch,
        "pool_sizes": [t.n for t in traces],
        "statics": statics,
        "adaptive": {
            "completion_p50": round(adaptive_p50, 4),
            "completion_mean": round(float(times.mean()), 4),
            "oracle_validated": True,
            **run.planner.summary(),
        },
        # < 1: the planner beats even the best fully-feasible static;
        # the acceptance band tops out at 1.05 (exploration overhead).
        "adaptive_over_best_static_p50": round(adaptive_p50 / best, 4),
        "worst_static_over_adaptive_p50": round(worst / adaptive_p50, 4),
    }


def _adaptive_report(field, m) -> dict:
    """Auto-planner vs static constructions under drifting conditions.

    ``degrading_links``: a fixed pool whose Phase-2 fabric degrades 8x
    — first mid-replay (the scheduler resolves the link matrix at each
    replay's set-announcement time), then permanently.  The candidate
    set spans the real trade-off: age(2,2,3) has the lightest per-worker
    work, age(4,1,3) the shallowest barrier (N=13, threshold 4) at 1.37x
    work — link degradation moves the optimum from the former to the
    latter, and no static candidate is best in both regimes.

    ``elastic_pool``: membership shrinks 40 -> 22 -> 16; at 16 only
    age(4,1,3) still fits, so the planner is *forced* off anything else
    it preferred, while statics that need more workers simply cannot
    serve those replays.
    """
    latency = ShiftedExponential(shift=1.0, scale=0.5)
    network = UniformLinks(HeavyTail(shift=0.2, scale=0.2, alpha=1.6), scale=0.3)

    # -- degrading links over a fixed pool --------------------------------
    cands = [
        PlanConfig("age", 2, 2, 3),
        PlanConfig("polydot", 2, 2, 3),
        PlanConfig("age", 4, 1, 3),
        PlanConfig("age", 4, 2, 3),
    ]
    pool = max(c.n_workers for c in cands) + 3
    K, onset, factor, t_mid = 14, 5, 8.0, 1.6
    traces = []
    for k in range(K):
        tr = sample_trace(pool, latency, seed=4000 + k, network=network)
        if k == onset:
            # Degradation arrives mid-replay: links are still clean when
            # Phase 1 goes out, 8x slower by the Phase-2 exchange.
            tr = TimeVaryingLinks(((t_mid, factor),)).apply(tr)
        elif k > onset:
            tr = TimeVaryingLinks(((0.0, factor),)).apply(tr)
        traces.append(tr)
    rng = np.random.default_rng(40)
    degrading = _adaptive_scenario(cands, traces, field, rng, m, seed=17)
    degrading["onset_replay"] = onset
    degrading["link_factor"] = factor

    # -- elastic pool ------------------------------------------------------
    cands = [
        PlanConfig("age", 2, 2, 3),
        PlanConfig("polydot", 2, 2, 3),
        PlanConfig("age", 4, 1, 3),
    ]
    sizes = [40] * 4 + [22] * 4 + [16] * 4
    master = sample_trace(40, latency, seed=7000, network=network)
    epool = ElasticPool(master, tuple(tuple(range(sz)) for sz in sizes))
    traces = [epool.trace_for(k) for k in range(len(epool))]
    rng = np.random.default_rng(41)
    elastic = _adaptive_scenario(cands, traces, field, rng, m, seed=23)

    return {"degrading_links": degrading, "elastic_pool": elastic}


# Byzantine sweep: configured corruption rates and replays per rate.
BYZANTINE_RATES = (0.0, 0.05, 0.10, 0.15, 0.20, 0.25)
BYZANTINE_RUNS = 6


def _byzantine_report(plans, field, rng, m, pool, n_runs=BYZANTINE_RUNS) -> dict:
    """Detect vs correct corruption handling on byte-identical traces.

    For each configured corruption rate the SAME sampled traces replay
    under both strategies (``decode_mode="detect"`` resolves one extra
    confirming witness; ``"correct"`` resolves the error budget ``e``
    from the configured rate and waits for ``thr + 2e`` responders),
    so the comparison isolates the decode strategy.  Reported per rate:
    p50 completion, mean responder overhead over the bare threshold
    (the worker price of each strategy), detected/corrected counts, and
    decode failures; per method, the lowest rate at which correction's
    p50 completion crosses below detection's.
    """
    a = field.random(rng, (m, m))
    b = field.random(rng, (m, m))
    want = field.matmul(a.T, b)
    latency = ShiftedExponential(shift=1.0, scale=1.0)
    out = {
        "rates": list(BYZANTINE_RATES),
        "strategies": ["detect", "correct"],
        "runs_per_rate": n_runs,
    }
    rows = []
    for meth, plan in plans.items():
        thr = plan.decode_threshold
        per_rate = []
        for rate in BYZANTINE_RATES:
            faults = FaultSpec(corrupt_frac=rate)
            # one trace set per rate, replayed by BOTH strategies (and
            # both methods share the pool-sized prefix, like the
            # scenario section)
            traces = [
                sample_trace(
                    pool, latency, faults, seed=6000 + round(rate * 100) * 31 + i
                )
                for i in range(n_runs)
            ]
            entry = {"corrupt_frac": rate}
            for strategy in ("detect", "correct"):
                results = []
                failures = 0
                for run_i, trace in enumerate(traces):
                    try:
                        res = run_over_pool(
                            plan, a, b, trace, seed=run_i, decode_mode=strategy
                        )
                    except DecodeFailure:
                        failures += 1
                        continue
                    if not np.array_equal(res.y, want):
                        raise AssertionError(
                            f"{meth}/byzantine rate={rate} run {run_i} "
                            f"({strategy}): decode disagrees with oracle"
                        )
                    results.append(res.metrics)
                responses = [observed_run(r).thr_arrived for r in results]
                agg = summarize(results)
                entry[strategy] = {
                    "completion_p50": round(agg.get("completion_p50", float("nan")), 4),
                    "responses_mean": round(float(np.mean(responses)), 2)
                    if responses
                    else None,
                    "worker_overhead_mean": round(
                        float(np.mean(responses)) - thr, 2
                    )
                    if responses
                    else None,
                    "rejected_total": agg.get("rejected_total", 0),
                    "corrected_total": agg.get("corrected_total", 0),
                    "decode_failures": failures,
                    "oracle_validated": True,
                }
            d_p50 = entry["detect"]["completion_p50"]
            c_p50 = entry["correct"]["completion_p50"]
            entry["correct_over_detect_p50"] = (
                round(c_p50 / d_p50, 4) if d_p50 else None
            )
            per_rate.append(entry)
            for strategy in ("detect", "correct"):
                rows.append(
                    {
                        "method": meth,
                        "corrupt_frac": rate,
                        "strategy": strategy,
                        "completion_p50": entry[strategy]["completion_p50"],
                        "worker_overhead_mean": entry[strategy][
                            "worker_overhead_mean"
                        ],
                        "decode_failures": entry[strategy]["decode_failures"],
                    }
                )
            # first configured rate where correction's p50 completion is
            # no worse than detection's (None: detection never crossed)
        crossover = next(
            (
                e["corrupt_frac"]
                for e in per_rate
                if e["corrupt_frac"] > 0
                and e["correct_over_detect_p50"] is not None
                and e["correct_over_detect_p50"] <= 1.0
            ),
            None,
        )
        out[meth] = {
            "decode_threshold": thr,
            "per_rate": per_rate,
            "p50_crossover_rate": crossover,
        }
    write_csv("edge_byzantine", rows)
    return out


def _batched_replay_report(plans, field, rng, m) -> dict:
    """Per-method amortization of the batched replay vs a run loop."""
    a = field.random(rng, (BATCH_REPLAY, m, m))
    b = field.random(rng, (BATCH_REPLAY, m, m))
    want = np.stack([field.matmul(a[i].T, b[i]) for i in range(BATCH_REPLAY)])
    latency = ShiftedExponential(shift=1.0, scale=1.0)
    faults = FaultSpec(straggler_frac=0.2, straggler_slowdown=10.0)
    out = {}
    for meth, plan in plans.items():
        trace = sample_trace(plan.n_total, latency, faults, seed=77)
        res = run_batch_over_pool(plan, a, b, trace, seed=78)
        if not np.array_equal(res.y, want):
            raise AssertionError(f"{meth}: batched replay disagrees with oracle")

        def loop():
            for i in range(BATCH_REPLAY):
                run_over_pool(plan, a[i], b[i], trace, seed=78)

        loop_us = timeit(loop, repeat=3) / BATCH_REPLAY
        batched_us = (
            timeit(lambda: run_batch_over_pool(plan, a, b, trace, seed=78), repeat=3)
            / BATCH_REPLAY
        )
        out[meth] = {
            "batch": BATCH_REPLAY,
            "loop_us_per_product": round(loop_us, 1),
            "batched_us_per_product": round(batched_us, 1),
            "amortization": round(loop_us / batched_us, 2),
            "oracle_validated": True,
        }
    return out


def _sharded_child():
    """Child entry (multi-device host): the batched edge replay with the
    scheduler-driven shard_map Phase 2.  Prints ONE JSON line."""
    import jax
    from jax.sharding import Mesh

    field = Field()
    rng = np.random.default_rng(0)
    m, s, t, z, n_spare = 32, 2, 2, 3, 3
    shapes = BlockShapes(k=m, ma=m, mb=m, s=s, t=t)
    schemes = {meth: C.build_scheme(meth, s, t, z) for meth in METHODS}
    pool = max(sch.n_workers for sch in schemes.values()) + n_spare
    plans = {
        meth: get_plan(schemes[meth], shapes, n_spare=pool - sch.n_workers)
        for meth, sch in schemes.items()
    }
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    a = field.random(rng, (BATCH_REPLAY, m, m))
    b = field.random(rng, (BATCH_REPLAY, m, m))
    want = np.stack([field.matmul(a[i].T, b[i]) for i in range(BATCH_REPLAY)])
    latency = ShiftedExponential(shift=1.0, scale=1.0)
    faults = FaultSpec(straggler_frac=0.2, straggler_slowdown=10.0)
    out = {
        "platform": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "batch": BATCH_REPLAY,
        "mode": "all_to_all",
        "pool_size": pool,
        "methods": {},
    }
    for meth, plan in plans.items():
        trace = sample_trace(pool, latency, faults, seed=88)
        res = run_batch_over_pool(plan, a, b, trace, seed=89, mesh=mesh)
        if not np.array_equal(res.y, want):
            raise AssertionError(f"{meth}: sharded batched replay != oracle")
        us = (
            timeit(
                lambda: run_batch_over_pool(plan, a, b, trace, seed=89, mesh=mesh),
                repeat=3,
            )
            / BATCH_REPLAY
        )
        out["methods"][meth] = {
            "us_per_product": round(us, 1),
            # ONE replay's simulated completion (not a percentile — the
            # scenario percentiles live under "scenarios")
            "completion_time": round(res.metrics.completion_time, 4),
            "phase2_subset_nonprefix": bool(
                not np.array_equal(
                    res.metrics.phase2_ids, np.arange(plan.n_workers)
                )
            ),
        }
    out["validated"] = True
    print(json.dumps(out))


def _sharded_report() -> dict:
    return run_sharded_child("benchmarks.edge_runtime", SHARDED_DEVICES)


def _scenarios(n_spare: int):
    """(name, latency model, FaultSpec, explicit-fault kwargs)."""
    return [
        ("all_fast", Deterministic(1.0), FaultSpec(), {}),
        (
            "stragglers_exp",
            ShiftedExponential(shift=1.0, scale=1.0),
            FaultSpec(straggler_frac=0.2, straggler_slowdown=10.0),
            {},
        ),
        (
            "dropouts",
            ShiftedExponential(shift=1.0, scale=0.5),
            FaultSpec(),
            {"dropout_ids": list(range(n_spare))},
        ),
        (
            "heavy_tail_corrupt",
            HeavyTail(shift=1.0, scale=0.5, alpha=1.5),
            FaultSpec(),
            {"corrupt_ids": [1]},
        ),
    ]


def run(m: int = 32, s: int = 2, t: int = 2, z: int = 3, n_spare: int = 3,
        n_runs: int = 8):
    # Default (s, t, z) = (2, 2, 3): the smallest cell of the validation
    # grid where the schemes' worker counts actually separate (PolyDot 22
    # vs AGE 20), so the completion-time comparison exercises the
    # paper's worker-advantage claim rather than a tie.
    #
    # Both schemes share ONE physical pool — the edge setting is a fixed
    # set of devices, not a per-scheme provisioning budget.  Pool size =
    # (largest scheme's n_workers) + n_spare; the scheme that needs
    # fewer workers banks the difference as extra straggler slack, which
    # is exactly how the paper's worker-count advantage becomes a
    # completion-time advantage under load.
    #
    # TRACE=1 turns the observability layer on for the whole run and
    # writes a Perfetto-loadable sidecar (BENCH_edge.trace.json) next to
    # the report.  The report itself is byte-identical either way: the
    # tracer only *reads* already-decided timestamps, and the sidecar is
    # a separate file that bench_diff ignores.
    tracing = bool(os.environ.get("TRACE"))
    if tracing:
        TRACER.clear()
        TRACER.enable()
    field = Field()
    rng = np.random.default_rng(0)
    shapes = BlockShapes(k=m, ma=m, mb=m, s=s, t=t)
    schemes = {meth: C.build_scheme(meth, s, t, z) for meth in METHODS}
    pool = max(sch.n_workers for sch in schemes.values()) + n_spare
    plans = {
        meth: get_plan(schemes[meth], shapes, n_spare=pool - sch.n_workers)
        for meth, sch in schemes.items()
    }
    min_spare = min(p.n_spare for p in plans.values())
    a = field.random(rng, (m, m))
    b = field.random(rng, (m, m))
    want = field.matmul(a.T, b)

    scenarios = {}
    rows = []
    for name, latency, faults, explicit in _scenarios(min_spare):
        per_method = {}
        for meth, plan in plans.items():
            results = []
            wall_us = []
            for run_i in range(n_runs):
                # One trace per (scenario, seed) for the shared pool:
                # both schemes replay byte-identical worker behaviour.
                trace = sample_trace(pool, latency, faults, seed=1000 + run_i)
                if explicit:
                    trace = trace.with_faults(**explicit)
                w0 = time.perf_counter()
                res = run_over_pool(plan, a, b, trace, seed=run_i)
                wall_us.append((time.perf_counter() - w0) * 1e6)
                if not np.array_equal(res.y, want):
                    raise AssertionError(
                        f"{meth}/{name} run {run_i}: decode from subset "
                        f"{res.metrics.responder_ids} disagrees with oracle"
                    )
                results.append(res.metrics)
            agg = summarize(results)
            agg["n_workers"] = plans[meth].n_workers
            agg["n_total"] = plans[meth].n_total
            agg["decode_threshold"] = plans[meth].decode_threshold
            agg["wall_us_mean"] = round(float(np.mean(wall_us)), 1)
            agg["oracle_validated"] = True
            per_method[meth] = agg
            rows.append(
                {
                    "scenario": name,
                    "method": meth,
                    "n_workers": agg["n_workers"],
                    "n_total": agg["n_total"],
                    "completion_p50": round(agg["completion_p50"], 4),
                    "completion_p95": round(agg["completion_p95"], 4),
                    "effective_workers": round(agg["effective_workers_mean"], 2),
                    "wire_bytes_mean": agg["wire_bytes_mean"],
                }
            )
        per_method["polydot_over_age_p50"] = round(
            per_method["polydot"]["completion_p50"]
            / per_method["age"]["completion_p50"],
            4,
        )
        scenarios[name] = per_method

    csv_path = write_csv("edge_runtime", rows)
    report = {
        "bench": "edge_runtime",
        "config": {
            "m": m, "s": s, "t": t, "z": z, "n_runs": n_runs,
            "pool_size": pool,
            "n_spare": {meth: p.n_spare for meth, p in plans.items()},
            "dropouts_injected": min_spare,
            "worker_advantage_age_vs_polydot": plans["polydot"].n_workers
            - plans["age"].n_workers,
        },
        "scenarios": scenarios,
        "per_link": _per_link_report(plans, field, rng, m, pool, n_runs=n_runs),
        "pipelined": _pipeline_report(plans, field, rng, m, pool),
        "adaptive": _adaptive_report(field, m),
        "byzantine": _byzantine_report(plans, field, rng, m, pool),
        "batched_replay": _batched_replay_report(plans, field, rng, m),
        "sharded_batched": _sharded_report(),
        "subset_cache": subset_cache_info(),
    }
    json_path = os.path.join(repo_root(), JSON_NAME)
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    if tracing:
        trace_path = os.path.join(
            repo_root(), JSON_NAME.replace(".json", ".trace.json")
        )
        write_chrome(trace_path, TRACER, metrics=snapshot())
        print(f"trace: {trace_path} ({len(TRACER.events)} events)")

    ratio = scenarios["stragglers_exp"]["polydot_over_age_p50"]
    return [
        {
            "name": "edge_runtime",
            "us_per_call": scenarios["all_fast"]["age"]["wall_us_mean"],
            "derived": f"csv={csv_path} json={json_path} "
            f"N_polydot={plans['polydot'].n_workers} "
            f"N_age={plans['age'].n_workers} "
            f"straggler_p50_ratio_polydot/age={ratio} all_validated=True",
        }
    ]


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
