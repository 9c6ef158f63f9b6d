"""Edge worker-pool runtime: event-driven scheduling, fastest-subset
decode, fault injection, and metrics/trace accounting.

Fast tier-1 coverage: small scheme, deterministic or crafted latency,
fixed seeds — every run validated against the host oracle."""
import numpy as np
import pytest

from repro.core import constructions as C
from repro.core.gf import Field
from repro.core.planner import BlockShapes, make_plan
from repro.runtime import (
    DecodeFailure,
    Deterministic,
    FaultSpec,
    HeavyTail,
    ShiftedExponential,
    run_batch_over_pool,
    run_over_pool,
    sample_trace,
    summarize,
)


@pytest.fixture(scope="module")
def setup():
    field = Field()
    sch = C.build_scheme("age", 2, 2, 2)
    shapes = BlockShapes(k=8, ma=8, mb=4, s=2, t=2)
    plan = make_plan(sch, shapes, n_spare=3, seed=1)
    rng = np.random.default_rng(0)
    a = field.random(rng, (8, 8))
    b = field.random(rng, (8, 4))
    return plan, a, b, field.matmul(a.T, b)


def test_all_fast_smoke(setup):
    """Deterministic pool: correct decode, fully known timeline."""
    plan, a, b, want = setup
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=2)
    run = run_over_pool(plan, a, b, trace, seed=3)
    assert np.array_equal(run.y, want)
    m = run.metrics
    # share (0.1) + compute (1.0) + d2d (0.1) + uplink (0.1), all equal
    assert m.completion_time == pytest.approx(1.3)
    assert m.phase2_set_time == pytest.approx(1.1)
    assert m.responder_ids.size == plan.decode_threshold
    assert m.phase2_ids.size == plan.n_workers
    assert m.n_dropped == 0 and m.rejected_ids.size == 0
    # bytes view consistent with the element counts
    assert m.trace.total_bytes == m.trace.total * plan.field.elem_bytes
    # phase 1 provisions every worker, spares included
    sh = plan.shapes
    per_worker = sh.blk_a[0] * sh.blk_a[1] + sh.blk_b[0] * sh.blk_b[1]
    assert m.trace.phase1_source_to_worker == plan.n_total * per_worker


def test_stragglers_excluded_from_phase2(setup):
    """Slowed workers must not gate the Phase-2 barrier."""
    plan, a, b, want = setup
    slow = [0, 5]
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=4).with_faults(
        straggler_ids=slow, straggler_slowdown=100.0
    )
    run = run_over_pool(plan, a, b, trace, seed=5)
    assert np.array_equal(run.y, want)
    assert not set(slow) & set(run.metrics.phase2_ids.tolist())
    # barrier time unaffected by the stragglers
    assert run.metrics.phase2_set_time == pytest.approx(1.1)


def test_dropouts_up_to_spares(setup):
    plan, a, b, want = setup
    drop = list(range(plan.n_spare))
    trace = sample_trace(
        plan.n_total, ShiftedExponential(1.0, 0.3), seed=6
    ).with_faults(dropout_ids=drop)
    run = run_over_pool(plan, a, b, trace, seed=7)
    assert np.array_equal(run.y, want)
    assert run.metrics.n_dropped == plan.n_spare
    used = set(run.metrics.phase2_ids.tolist()) | set(
        run.metrics.responder_ids.tolist()
    )
    assert not set(drop) & used


def test_too_many_dropouts_fail_loudly(setup):
    plan, a, b, _ = setup
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=8).with_faults(
        dropout_ids=list(range(plan.n_spare + 1))
    )
    with pytest.raises(DecodeFailure, match="dropouts"):
        run_over_pool(plan, a, b, trace, seed=9)


def test_crash_after_phase2(setup):
    """Crashers serve the exchange but never respond to the master."""
    plan, a, b, want = setup
    crash = [1, 3]
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=10).with_faults(
        crash_ids=crash
    )
    run = run_over_pool(plan, a, b, trace, seed=11)
    assert np.array_equal(run.y, want)
    assert run.metrics.n_crashed == 2
    assert not set(crash) & set(run.metrics.responder_ids.tolist())


def test_corrupt_response_detected(setup):
    """A corrupted fast responder must be kept out of the accepted
    subset via decode-consistency confirmation."""
    plan, a, b, want = setup
    trace = sample_trace(
        plan.n_total, ShiftedExponential(1.0, 0.2), seed=12
    ).with_faults(corrupt_ids=[2])
    run = run_over_pool(plan, a, b, trace, seed=13)  # verify_extras="auto"
    assert np.array_equal(run.y, want)
    assert 2 not in run.metrics.responder_ids
    assert run.metrics.confirmed_by.size >= 1


def test_many_corrupt_fast_responders(setup):
    """Several corrupted workers among the very fastest responders must
    not starve the subset search (colex front + randomized tail)."""
    plan, a, b, want = setup
    # deterministic latency -> workers respond in id order; corrupt the
    # three earliest so every fastest-prefix subset is poisoned
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=40).with_faults(
        corrupt_ids=[0, 1, 2]
    )
    run = run_over_pool(plan, a, b, trace, seed=41)
    assert np.array_equal(run.y, want)
    assert not {0, 1, 2} & set(run.metrics.responder_ids.tolist())


def test_heavy_tail_model_runs(setup):
    plan, a, b, want = setup
    trace = sample_trace(plan.n_total, HeavyTail(1.0, 0.5, 1.5), seed=14)
    run = run_over_pool(plan, a, b, trace, seed=15)
    assert np.array_equal(run.y, want)


def test_trace_prefix_replay():
    """take(n) replays the same per-worker behaviour across pool sizes
    (the identical-traces contract of the scheme comparison)."""
    full = sample_trace(25, ShiftedExponential(1.0, 1.0),
                        FaultSpec(dropout_frac=0.1), seed=16)
    part = full.take(20)
    assert np.array_equal(part.compute_delay, full.compute_delay[:20])
    assert np.array_equal(part.dropout, full.dropout[:20])
    with pytest.raises(ValueError):
        full.take(26)


def test_trace_mismatch_rejected(setup):
    plan, a, b, _ = setup
    trace = sample_trace(plan.n_total - 1, Deterministic(1.0), seed=17)
    with pytest.raises(ValueError, match="provisions"):
        run_over_pool(plan, a, b, trace, seed=18)


def test_fault_flags_disjoint():
    trace = sample_trace(
        200,
        Deterministic(1.0),
        FaultSpec(dropout_frac=0.3, crash_after_phase2_frac=0.3,
                  corrupt_frac=0.3),
        seed=19,
    )
    assert not (trace.dropout & trace.crash_after_phase2).any()
    assert not (trace.dropout & trace.corrupt).any()
    assert not (trace.crash_after_phase2 & trace.corrupt).any()


def test_sharded_phase2_worker_subset(setup):
    """run_phase2_sharded serves an arbitrary sender subset (the hook
    the runtime needs to drive the real shard_map exchange)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import protocol as proto
    from repro.core.distributed import run_phase2_sharded

    plan, a, b, want = setup
    field = Field()
    rng = np.random.default_rng(30)
    fa = proto.share_a(plan, a, rng)
    fb = proto.share_b(plan, b, rng)
    ids = np.array([i for i in range(plan.n_total) if i not in (0, 2)])
    ids = ids[: plan.n_workers]
    blk = plan.shapes.blk_y
    noise = field.random(rng, (plan.n_workers, plan.scheme.z) + blk)
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    i_evals = run_phase2_sharded(plan, fa, fb, noise, mesh, worker_ids=ids)
    y = proto.reconstruct(
        plan, i_evals, worker_ids=np.arange(2, 2 + plan.decode_threshold)
    )
    assert np.array_equal(y, want)


def test_run_trace_matches_pool_trace_with_spares(setup):
    """Corollary-12 accounting: on a no-fault deterministic trace with
    n_spare > 0, ``protocol.run``'s Trace must equal the scheduler's —
    spares receive Phase-2 I(alpha_n) too (Phase 3 may decode from any
    provisioned worker), so both count n_workers * (n_total - 1)
    receivers, not n_workers * (n_workers - 1)."""
    plan, a, b, _ = setup
    assert plan.n_spare > 0
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=33)
    pool_tr = run_over_pool(plan, a, b, trace, seed=34).metrics.trace
    from repro.core import protocol as proto

    _, run_tr = proto.run(plan, a, b, seed=0)
    assert run_tr.phase1_source_to_worker == pool_tr.phase1_source_to_worker
    assert run_tr.phase2_worker_to_worker == pool_tr.phase2_worker_to_worker
    assert run_tr.phase3_worker_to_master == pool_tr.phase3_worker_to_master
    assert run_tr.total_bytes == pool_tr.total_bytes
    # and the explicit formula, so a regression is loud
    sh = plan.shapes
    blk_y = (sh.ma // plan.scheme.t) * (sh.mb // plan.scheme.t)
    assert (
        run_tr.phase2_worker_to_worker
        == plan.n_workers * (plan.n_total - 1) * blk_y
    )


# ----------------------------------------------------------------------
# batched replay (run_batch_over_pool)
# ----------------------------------------------------------------------
def _batch_operands(plan, batch, seed=0):
    field = Field()
    rng = np.random.default_rng(seed)
    sh = plan.shapes
    a = field.random(rng, (batch, sh.k, sh.ma))
    b = field.random(rng, (batch, sh.k, sh.mb))
    want = np.stack([field.matmul(a[i].T, b[i]) for i in range(batch)])
    return a, b, want


def test_batch_over_pool_matches_oracle_and_timeline(setup):
    """One replay serves the whole batch: every product decodes to the
    oracle, the timeline equals the per-product run's, and the
    aggregate comm trace is batch x the per-product trace."""
    plan, a1, b1, _ = setup
    batch = 4
    a, b, want = _batch_operands(plan, batch, seed=21)
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=22)
    res = run_batch_over_pool(plan, a, b, trace, seed=23)
    assert np.array_equal(res.y, want)
    assert res.metrics.batch == batch
    assert len(res.per_product) == batch
    single = run_over_pool(plan, a1, b1, trace, seed=23)
    assert res.metrics.completion_time == pytest.approx(
        single.metrics.completion_time
    )
    assert np.array_equal(res.metrics.phase2_ids, single.metrics.phase2_ids)
    assert res.metrics.trace.total == batch * res.per_product[0].trace.total
    assert res.per_product[0].trace.total == single.metrics.trace.total


def test_batch_over_pool_faults(setup):
    """Stragglers, dropouts, and a corrupt responder behave identically
    under the batched replay (faults are per-worker, not per-product)."""
    plan, _, _, _ = setup
    a, b, want = _batch_operands(plan, 3, seed=24)
    drop = list(range(plan.n_spare))
    trace = sample_trace(
        plan.n_total, ShiftedExponential(1.0, 0.3), seed=25
    ).with_faults(dropout_ids=drop, corrupt_ids=[plan.n_spare])
    res = run_batch_over_pool(plan, a, b, trace, seed=26)
    assert np.array_equal(res.y, want)
    assert res.metrics.n_dropped == plan.n_spare
    assert plan.n_spare not in res.metrics.responder_ids
    used = set(res.metrics.phase2_ids.tolist()) | set(
        res.metrics.responder_ids.tolist()
    )
    assert not set(drop) & used
    # loud failure past the provisioned tolerance, same as the scalar path
    bad = sample_trace(plan.n_total, Deterministic(1.0), seed=27).with_faults(
        dropout_ids=list(range(plan.n_spare + 1))
    )
    with pytest.raises(DecodeFailure, match="dropouts"):
        run_batch_over_pool(plan, a, b, bad, seed=28)


def test_batch_over_pool_sharded_mesh(setup):
    """mesh= routes the batched replay's Phase 2 through the shard_map
    exchange, driven by the scheduler's fastest subset."""
    import jax
    from jax.sharding import Mesh

    plan, _, _, _ = setup
    a, b, want = _batch_operands(plan, 3, seed=29)
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=30).with_faults(
        straggler_ids=[1], straggler_slowdown=50.0
    )
    for mode in ("all_to_all", "psum", "psum_scatter"):
        res = run_batch_over_pool(plan, a, b, trace, seed=31, mesh=mesh, mode=mode)
        assert np.array_equal(res.y, want), mode
        assert 1 not in res.metrics.phase2_ids


def test_batch_over_pool_2d_promotion(setup):
    plan, a, b, want = setup
    trace = sample_trace(plan.n_total, Deterministic(1.0), seed=32)
    res = run_batch_over_pool(plan, a, b, trace, seed=33)
    assert res.y.shape == (1,) + want.shape
    assert np.array_equal(res.y[0], want)
    assert res.metrics.batch == 1


# ----------------------------------------------------------------------
# with_faults id validation
# ----------------------------------------------------------------------
def test_with_faults_empty_lists_noop():
    trace = sample_trace(10, Deterministic(1.0), seed=35)
    same = trace.with_faults()
    assert not same.dropout.any() and not same.corrupt.any()
    assert np.array_equal(same.compute_delay, trace.compute_delay)


@pytest.mark.parametrize(
    "kwargs",
    [
        {"dropout_ids": [-1]},
        {"crash_ids": [10]},
        {"corrupt_ids": [3, 3]},
        {"straggler_ids": [0, -2]},
    ],
)
def test_with_faults_rejects_bad_ids(kwargs):
    """Out-of-range / duplicate ids must fail loudly — numpy fancy
    indexing would silently wrap the negatives onto real workers."""
    trace = sample_trace(10, Deterministic(1.0), seed=36)
    with pytest.raises(ValueError, match="indices|duplicate"):
        trace.with_faults(**kwargs)


def test_summarize(setup):
    plan, a, b, _ = setup
    runs = []
    for seed in range(3):
        trace = sample_trace(plan.n_total, ShiftedExponential(1.0, 0.5),
                             seed=20 + seed)
        runs.append(run_over_pool(plan, a, b, trace, seed=seed).metrics)
    agg = summarize(runs)
    assert agg["runs"] == 3
    assert agg["completion_p50"] <= agg["completion_p95"] <= agg["completion_max"]
    assert 1 <= agg["decode_subsets_distinct"] <= 3
    assert agg["n_provisioned"] == plan.n_total
    assert summarize([]) == {"runs": 0}


# ----------------------------------------------------------------------
# Phase 3 on the device: which replays decode there, and that it changes
# nothing but where the product runs
# ----------------------------------------------------------------------
def _decode_counts():
    from repro.obs import REGISTRY

    return (
        REGISTRY.counter("runtime.device_decodes").value,
        REGISTRY.counter("runtime.host_decodes").value,
    )


def _force_host_decode(monkeypatch):
    """Hand the event loop host copies of the I-evaluations, as the mesh
    path does: every replay then decodes on the host."""
    import repro.runtime.pipeline as pipeline

    real_closure = pipeline._batched_compute_closure

    def closure(*args, **kw):
        compute = real_closure(*args, **kw)
        return lambda ids: np.asarray(compute(ids))

    monkeypatch.setattr(pipeline, "_batched_compute_closure", closure)


def _replays(entry, plan, a1, b1, traces):
    """(ys, metrics) of each replay of ``traces`` through one entry point."""
    from repro.runtime import PipelineSession

    if entry == "run_over_pool":
        runs = [run_over_pool(plan, a1, b1, tr, seed=41 + i) for i, tr in enumerate(traces)]
        return [r.y for r in runs], [r.metrics for r in runs]
    a, b, _ = _batch_operands(plan, 3, seed=42)
    if entry == "run_batch_over_pool":
        runs = [
            run_batch_over_pool(plan, a, b, tr, seed=43 + i) for i, tr in enumerate(traces)
        ]
        return [r.y for r in runs], [r.metrics for r in runs]
    session = PipelineSession(plan, seed=44)
    reps = [session.append(a, b, tr) for tr in traces]
    return [r.y for r in reps], [r.metrics for r in reps]


@pytest.mark.parametrize("entry", ["run_over_pool", "run_batch_over_pool", "session"])
def test_device_and_host_decode_give_identical_runs(setup, entry, monkeypatch):
    """A fault-free detect replay decodes on the device; the host decode
    of the same replay gives the same Y and the same RunMetrics (times,
    ids, confirmations, accounting) bit for bit, over stragglers, a
    dropout and non-prefix responder subsets."""
    import dataclasses

    from repro.runtime.metrics import RunMetrics

    plan, a1, b1, _ = setup
    traces = [
        sample_trace(plan.n_total, ShiftedExponential(1.0, 0.3), seed=45 + i)
        .with_faults(dropout_ids=[i], straggler_ids=[plan.n_total - 1 - i])
        for i in range(3)
    ]
    before = _decode_counts()
    ys_dev, ms_dev = _replays(entry, plan, a1, b1, traces)
    mid = _decode_counts()
    assert (mid[0] - before[0], mid[1] - before[1]) == (3, 0)
    _force_host_decode(monkeypatch)
    ys_host, ms_host = _replays(entry, plan, a1, b1, traces)
    after = _decode_counts()
    assert (after[0] - mid[0], after[1] - mid[1]) == (0, 3)
    assert any(
        not np.array_equal(m.responder_ids, np.arange(plan.decode_threshold))
        for m in ms_dev
    )
    for y_dev, y_host in zip(ys_dev, ys_host):
        assert y_dev.dtype == y_host.dtype and np.array_equal(y_dev, y_host)
    for m_dev, m_host in zip(ms_dev, ms_host):
        for f in dataclasses.fields(RunMetrics):
            x, y = getattr(m_dev, f.name), getattr(m_host, f.name)
            if isinstance(x, np.ndarray):
                assert x.dtype == y.dtype and np.array_equal(x, y), f.name
            else:
                assert x == y, f.name


@pytest.mark.parametrize(
    "case", ["verify_extras", "correct", "hybrid_escalated", "corrupt_live", "mesh"]
)
def test_host_decodes_what_the_device_path_cannot(setup, case):
    """Verification, Berlekamp-Welch (also a hybrid pool after it
    escalates), a trace with a corrupt live worker, and the mesh path's
    host array all decode on the host: the fetch copies every worker's
    I-evaluations, no device decode runs, and Y is the oracle's."""
    import dataclasses

    import jax
    from jax.sharding import Mesh

    from repro import obs
    from repro.runtime import HybridState

    plan, _, _, _ = setup
    a, b, want = _batch_operands(plan, 2, seed=50)
    trace = sample_trace(plan.n_total, ShiftedExponential(1.0, 0.3), seed=51)
    kw = {}
    if case == "verify_extras":
        kw = dict(verify_extras=1)
    elif case == "correct":
        kw = dict(decode_mode="correct", error_budget=1)
    elif case == "hybrid_escalated":
        kw = dict(decode_mode="hybrid", hybrid_state=HybridState(escalated=True))
    elif case == "mesh":
        kw = dict(mesh=Mesh(np.array(jax.devices()), ("workers",)))
    elif case == "corrupt_live":
        # corrupt, but outside the decode subset and with no fault model:
        # "auto" asks no confirmation, yet the host draws its garbage
        clean = run_batch_over_pool(plan, a, b, trace, seed=52)
        bad = next(
            w for w in range(plan.n_total) if w not in clean.metrics.responder_ids
        )
        corrupt = np.zeros(plan.n_total, bool)
        corrupt[bad] = True
        trace = dataclasses.replace(trace, corrupt=corrupt)
    obs.TRACER.clear()
    obs.enable()
    before = _decode_counts()
    try:
        res = run_batch_over_pool(plan, a, b, trace, seed=52, **kw)
    finally:
        obs.disable()
    after = _decode_counts()
    walls = [e for e in obs.TRACER.events if e["clock"] == "wall" and e["kind"] == "span"]
    obs.TRACER.clear()
    assert np.array_equal(res.y, want)
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
    (fetch,) = [e for e in walls if e["name"] == "runtime.fetch"]
    assert fetch["attrs"]["decode"] == "host"
    sh = plan.shapes
    assert fetch["attrs"]["bytes"] >= plan.n_total * 2 * sh.blk_y[0] * sh.blk_y[1] * 4
    assert not [e for e in walls if e["name"] == "protocol.phase3.device_decode"]
    if case == "corrupt_live":
        assert np.array_equal(res.metrics.responder_ids, clean.metrics.responder_ids)
