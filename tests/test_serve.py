"""Serving tier: request lifecycle, continuous batching, SLO/admission
semantics, and the served path under every fault kind.

The engine is a pure function of (requests, traces, seed): every test
below runs on deterministic traces and asserts exact censuses — decode
values against the field oracle, deadline misses by count, shed reasons
by name, replay folding by replay count.  The session/pipeline
regression pins the refactor: ``PipelineSession`` appends must replay
byte-identically to the historical ``run_pipeline_over_pool``.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.constructions import PlanConfig
from repro.core.gf import Field
from repro.core.layers import choose_scales
from repro.core.planner import BlockShapes, get_plan_for
from repro.obs import TRACER
from repro.runtime import (
    Deterministic,
    PipelineSession,
    ShiftedExponential,
    run_batch_over_pool,
    run_pipeline_over_pool,
    sample_trace,
)
from repro.serve import DONE, SHED, ServingEngine

FIELD = Field()
CFG = PlanConfig("age", 2, 2, 1)
POOL = CFG.n_workers + 2
K_DIM, OUT, ROWS = 16, 8, 4


def _traces(n, pool=POOL, seed0=100, latency=None, net_scale=0.3):
    latency = latency or ShiftedExponential(shift=0.1, scale=0.5)
    return [
        sample_trace(pool, latency, seed=seed0 + i, net_scale=net_scale)
        for i in range(n)
    ]


def _engine(traces=None, **kw):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(K_DIM, OUT))
    eng = ServingEngine(
        w,
        traces if traces is not None else _traces(16),
        kw.pop("config", CFG),
        field=FIELD,
        seed=0,
        validate=True,
        **kw,
    )
    return eng, w, rng


def _exact_y(x, w):
    """The engine's fixed-point answer, from first principles."""
    s = choose_scales(
        K_DIM, float(np.abs(x).max() + 1e-9), float(np.abs(w).max() + 1e-9),
        FIELD.p,
    )
    yq = FIELD.matmul(FIELD.encode(x.T, s).T, FIELD.encode(w, s))
    return FIELD.decode(yq, s * s)


# ----------------------------------------------------------------------
# request lifecycle and decode exactness
# ----------------------------------------------------------------------
def test_served_requests_decode_exactly():
    """Every served request's y equals the fixed-point oracle computed
    outside the engine — per-request scales survive the batch fold."""
    eng, w, rng = _engine()
    xs = [rng.normal(size=(ROWS, K_DIM)) * mag for mag in (0.1, 1.0, 30.0)]
    reqs = [eng.submit(x, 0.2 * i) for i, x in enumerate(xs)]
    rep = eng.run()
    assert all(r.state == DONE for r in reqs)
    for x, r in zip(xs, reqs):
        assert np.array_equal(r.y, _exact_y(x, w))
        assert r.completion > r.launch >= r.arrival
    s = rep.summary()
    assert s["served"] == 3 and s["shed"] == 0
    assert s["p99_latency"] >= s["p95_latency"] >= s["p50_latency"] > 0


def test_submit_validation():
    eng, w, rng = _engine()
    with pytest.raises(ValueError, match="rows"):
        eng.submit(rng.normal(size=(3, K_DIM)), 0.0)  # t=2 does not divide 3
    eng.submit(rng.normal(size=(ROWS, K_DIM)), 0.0)
    with pytest.raises(ValueError, match="rows"):
        eng.submit(rng.normal(size=(ROWS + 2, K_DIM)), 0.0)  # != first
    with pytest.raises(ValueError, match="k="):
        eng.submit(rng.normal(size=(ROWS, K_DIM + 1)), 0.0)
    with pytest.raises(ValueError, match="mode"):
        ServingEngine(w, _traces(1), CFG, mode="batchy")
    with pytest.raises(ValueError, match="pipe_depth"):
        ServingEngine(w, _traces(1), CFG, pipe_depth=1)


# ----------------------------------------------------------------------
# SLO accounting: exact deadline-miss census on deterministic traces
# ----------------------------------------------------------------------
def test_exact_deadline_census_on_deterministic_trace():
    """Two identical engines: the first learns the (deterministic)
    completion time, the second gets deadlines straddling it — the miss
    census must split exactly there, with no shedding (no estimator
    history on the first launch: admission is optimistic)."""
    det = _traces(4, latency=Deterministic(1.0), net_scale=0.1)
    probe, _, rng = _engine(traces=det)
    x = rng.normal(size=(ROWS, K_DIM))
    c = probe.submit(x, 0.0)
    probe.run()
    completion = c.completion
    assert completion > 0

    eng, _, _ = _engine(traces=det)
    hit = eng.submit(x, 0.0, deadline=completion + 0.5)
    miss = eng.submit(x, 0.0, deadline=completion - 0.5)
    exact = eng.submit(x, 0.0, deadline=completion)  # boundary: met
    rep = eng.run()
    # all three rode the same replay, same deterministic completion
    assert {r.completion for r in (hit, miss, exact)} == {completion}
    assert hit.met_deadline and exact.met_deadline
    assert not miss.met_deadline
    assert rep.summary()["deadline_misses"] == 1
    assert rep.summary()["served"] == 3


def test_admission_sheds_hopeless_deadlines():
    """A burst against a tight SLO: once the estimator has one
    observation, requests whose deadline the prediction rules out are
    shed with reason 'deadline' before any launch is wasted on them."""
    eng, _, rng = _engine(slo=2.0)
    reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)), 0.05 * i)
            for i in range(12)]
    rep = eng.run()
    shed = [r for r in reqs if r.state == SHED]
    assert shed and all(r.shed_reason == "deadline" for r in shed)
    assert all(r.y is None and math.isnan(r.completion) for r in shed)
    served = [r for r in reqs if r.state == DONE]
    assert served  # the first wave launches before any prediction exists
    assert rep.summary()["shed"] == len(shed)


def test_drained_queue_leaves_no_orphans():
    """After run(), every submitted request is terminal (done or shed)
    and the internal queue is empty — nothing in flight, nothing lost."""
    eng, _, rng = _engine(slo=2.5)
    reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)), 0.1 * i)
            for i in range(10)]
    rep = eng.run()
    assert eng._queue == []
    assert all(r.state in (DONE, SHED) for r in reqs)
    s = rep.summary()
    assert s["served"] + s["shed"] == s["requests"] == 10


def test_pool_shrink_sheds_remaining_queue():
    """When the trace source shrinks below the construction's worker
    count, nothing the engine launches can complete: the remaining
    queue is shed with reason 'pool', earlier requests stay served."""
    big = sample_trace(POOL, ShiftedExponential(0.1, 0.5), seed=7,
                       net_scale=0.3)
    small = big.take(CFG.n_workers - 2)
    eng, _, rng = _engine(traces=[big, big] + [small] * 20)
    reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)), 3.0 * i)
            for i in range(8)]
    eng.run()
    served = [r for r in reqs if r.state == DONE]
    shed = [r for r in reqs if r.state == SHED]
    assert served and shed
    assert all(r.shed_reason == "pool" for r in shed)
    # served requests all predate the shrink
    assert max(r.arrival for r in served) < min(r.arrival for r in shed)


def test_degraded_estimates_halve_admission_cap(monkeypatch):
    """When pool-health estimates disagree (degraded), the admission
    cap halves: the same 4-request wave folds into one replay normally
    but two replays under degradation (deferred, not shed)."""
    det = _traces(8, latency=Deterministic(1.0), net_scale=0.1)
    base, _, rng = _engine(traces=det, max_batch=4)
    xs = [rng.normal(size=(ROWS, K_DIM)) for _ in range(4)]
    for x in xs:
        base.submit(x, 0.0)
    assert base.run().summary()["replays"] == 1

    eng, _, _ = _engine(traces=det, max_batch=4)
    monkeypatch.setattr(eng, "_predicted_service", lambda: (0.5, True))
    reqs = [eng.submit(x, 0.0) for x in xs]
    rep = eng.run()
    assert all(r.state == DONE for r in reqs)  # deferred != shed
    assert rep.summary()["replays"] == 2


# ----------------------------------------------------------------------
# continuous vs boundary batching
# ----------------------------------------------------------------------
def test_continuous_beats_boundary_p95_on_identical_stream():
    """Same requests, same traces, same seed: admitting into in-flight
    replays must cut tail latency without losing a single request."""
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(24, ROWS, K_DIM))
    arrivals = np.cumsum(rng.exponential(1.4, 24))
    stats = {}
    for mode in ("continuous", "boundary"):
        eng, _, _ = _engine(traces=_traces(32), mode=mode)
        for x, t in zip(xs, arrivals):
            eng.submit(x, float(t))
        stats[mode] = eng.run().summary()
    assert stats["continuous"]["served"] == stats["boundary"]["served"] == 24
    assert (
        stats["continuous"]["p95_latency"]
        < stats["boundary"]["p95_latency"]
    )
    assert (
        stats["continuous"]["throughput"]
        >= 0.99 * stats["boundary"]["throughput"]
    )


def test_ready_at_boundary_vs_continuous():
    """ready_at(1) waits for the pipeline to drain; ready_at(2) only
    needs the master uplink free — strictly earlier while a replay is
    still in its Phase-2/3 window."""
    plan = get_plan_for(
        PlanConfig("age", 2, 2, 1, n_spare=2),
        BlockShapes(k=8, ma=4, mb=4, s=2, t=2),
        field=FIELD,
    )
    sess = PipelineSession(plan, seed=0, base_time=1.5)
    assert sess.ready_at(1) == sess.ready_at(2) == 1.5
    rng = np.random.default_rng(0)
    a = FIELD.random(rng, (1, 8, 4))
    b = FIELD.random(rng, (1, 8, 4))
    trace = _traces(1, pool=plan.n_total)[0]
    rep = sess.append(a, b, trace, not_before=2.0)
    assert rep.start >= 2.0
    assert sess.ready_at(1) == rep.completion
    assert sess.ready_at(2) < rep.completion  # uplink frees mid-flight
    with pytest.raises(ValueError, match="pipe_depth"):
        sess.ready_at(0)


def test_session_matches_run_pipeline_over_pool():
    """Refactor regression: K appends on a fresh session replay
    byte-identically to the one-shot pipeline entry point."""
    plan = get_plan_for(
        PlanConfig("age", 2, 2, 1, n_spare=2),
        BlockShapes(k=8, ma=4, mb=4, s=2, t=2),
        field=FIELD,
    )
    K, batch = 3, 2
    rng = np.random.default_rng(5)
    a = FIELD.random(rng, (K, batch, 8, 4))
    b = FIELD.random(rng, (K, batch, 8, 4))
    traces = _traces(K, pool=plan.n_total, seed0=50)
    ref = run_pipeline_over_pool(plan, a, b, traces, seed=9)
    sess = PipelineSession(plan, seed=9)
    reps = [sess.append(a[k], b[k], traces[k]) for k in range(K)]
    run = sess.result()
    assert np.array_equal(run.y, ref.y)
    assert run.metrics.makespan == ref.metrics.makespan
    assert run.metrics.occupancy == ref.metrics.occupancy
    for rm, rm_ref in zip(run.replay_metrics, ref.replay_metrics):
        assert rm.completion_time == rm_ref.completion_time
    assert [r.completion for r in reps] == [
        m.completion_time for m in ref.replay_metrics
    ]


# ----------------------------------------------------------------------
# hybrid Byzantine posture through the engine
# ----------------------------------------------------------------------
def test_engine_hybrid_escalates_and_corrects():
    """A persistently corrupt fastest worker: the first replay rejects
    it on the detect path, later replays run Berlekamp-Welch — and
    validate=True proves every decode against the oracle either way."""
    cfg = PlanConfig("age", 2, 2, 2)
    pool = cfg.n_workers + 6
    trace = sample_trace(pool, Deterministic(1.0), seed=2)
    trace = dataclasses.replace(
        trace, uplink_delay=0.1 + 0.01 * np.arange(pool)
    )
    trace = trace.with_faults(corrupt_ids=[0])
    eng, w, rng = _engine(
        traces=[trace], config=cfg, decode_mode="hybrid", verify_extras=2
    )
    reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)), 8.0 * i)
            for i in range(3)]
    rep = eng.run()
    assert all(r.state == DONE for r in reqs)
    assert rep.summary()["replays"] >= 2
    state = eng._session.hybrid_state
    assert state is not None and state.escalated
    # first replay runs the detect path (rejects, corrects nothing);
    # post-escalation replays BW-correct the corrupt worker instead.
    assert eng._obs[0].n_corrected == 0
    assert any(o.n_corrected for o in eng._obs[1:])
    for r in reqs:
        assert np.array_equal(r.y, _exact_y(r.x, w))


# ----------------------------------------------------------------------
# the served path under every fault kind
# ----------------------------------------------------------------------
FAULT_CASES = {
    # case: (placement on the trace, the engine's decode knobs)
    "dropouts": (dict(dropout_ids=[0, 1, 2, 3]), dict(decode_mode="detect")),
    "crashes": (dict(crash_ids=[0, 1]), dict(decode_mode="detect")),
    "stragglers": (dict(straggler_ids=[0, 1]), dict(decode_mode="detect")),
    "corrupt-detect": (
        dict(corrupt_ids=[0]), dict(decode_mode="detect", verify_extras=1)
    ),
    "corrupt-correct": (
        dict(corrupt_ids=[0]), dict(decode_mode="correct", error_budget=1)
    ),
}


@pytest.mark.parametrize("case", sorted(FAULT_CASES))
def test_engine_serves_every_fault_kind(case):
    """One fault kind placed on a deterministic trace, served through
    the engine: every request decodes to the fixed-point oracle, the
    fault lands where it was placed, and the replay's subsets and times
    are those ``run_batch_over_pool`` reports for the same trace and
    seed."""
    faults, knobs = FAULT_CASES[case]
    pool = CFG.n_workers + 4  # dropouts take every spare
    trace = sample_trace(pool, Deterministic(1.0), seed=2)
    trace = dataclasses.replace(
        trace, uplink_delay=0.1 + 0.01 * np.arange(pool)
    ).with_faults(**faults)
    eng, w, rng = _engine(traces=[trace], admission=False, **knobs)
    reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)) * mag, 0.0)
            for mag in (0.1, 10.0)]
    assert eng.run().replays == 1
    for r in reqs:
        assert r.state == DONE
        assert np.array_equal(r.y, _exact_y(r.x, w))
    served = eng.pipeline_result().replay_metrics[0]

    plan = get_plan_for(
        CFG.fit_to_pool(pool),
        BlockShapes(k=K_DIM, ma=ROWS, mb=OUT, s=CFG.s, t=CFG.t),
        field=FIELD,
    )
    a = np.stack([FIELD.encode(r.x.T, r.scale) for r in reqs])
    b = np.stack([FIELD.encode(w, r.scale) for r in reqs])
    ref = run_batch_over_pool(plan, a, b, trace, seed=0, **knobs).metrics
    for f in ("phase2_ids", "responder_ids", "confirmed_by", "rejected_ids",
              "corrected_workers"):
        assert np.array_equal(getattr(served, f), getattr(ref, f)), f
    assert served.completion_time == ref.completion_time

    placed = np.concatenate([np.asarray(v) for v in faults.values()])
    if case == "stragglers":
        assert not np.isin(placed, served.phase2_ids).any()
    elif case == "corrupt-detect":
        assert served.rejected_ids.tolist() == [0]
    elif case == "corrupt-correct":
        assert served.corrected_workers.tolist() == [0]
    else:  # dropped or crashed workers never answer
        assert not np.isin(placed, served.responder_ids).any()
        assert served.n_dropped + served.n_crashed == placed.size


# ----------------------------------------------------------------------
# observability: request lanes in the trace
# ----------------------------------------------------------------------
def test_serve_spans_link_queue_to_replay():
    """Each served request contributes a serve.queue and a serve.service
    sim span on its own ("request", rid) lane, service bounds matching
    the replay it rode; shed requests contribute a serve.shed instant."""
    TRACER.clear()
    TRACER.enable()
    try:
        eng, _, rng = _engine(slo=2.0)
        reqs = [eng.submit(rng.normal(size=(ROWS, K_DIM)), 0.05 * i)
                for i in range(8)]
        eng.run()
    finally:
        TRACER.disable()
    sim = TRACER.sim_events()
    TRACER.clear()
    by_name = {}
    for e in sim:
        by_name.setdefault(e["name"], []).append(e)
    served = [r for r in reqs if r.state == DONE]
    shed = [r for r in reqs if r.state == SHED]
    assert len(by_name.get("serve.service", [])) == len(served)
    assert len(by_name.get("serve.queue", [])) == len(served)
    assert len(by_name.get("serve.shed", [])) == len(shed)
    replays = {e["attrs"]["replay"] for e in by_name.get("replay", [])} or None
    for r in served:
        svc = next(
            e for e in by_name["serve.service"]
            if e["track"] == ("request", r.rid)
        )
        assert svc["t0"] == r.launch and svc["t1"] == r.completion
        q = next(
            e for e in by_name["serve.queue"]
            if e["track"] == ("request", r.rid)
        )
        assert q["t0"] == r.arrival and q["t1"] == r.launch
        assert svc["attrs"]["replay"] == r.replay


def test_traced_run_splits_each_replay_into_named_host_spans(monkeypatch):
    """One traced run() of two replays: per replay one each of the
    engine's admit/encode/decode spans under serve.run, and of the
    runtime.replay; the byte counts are the arrays' own sizes.  W's
    residues already live on the device, so runtime.upload's bytes (what
    crossed from host to device) are A's alone.  The fault-free pool's
    decode runs on the device under protocol.phase3.device_decode, so
    runtime.fetch's bytes (what crossed back) are Y's t^2 coefficient
    rows, not every worker's I-evaluations."""
    import jax

    import repro.runtime.pipeline as pipeline
    from repro.core import protocol as proto

    seen = {"encode": [], "upload": [], "i_evals": [], "fetch": [], "rows": [],
            "b_on_device": []}
    real_append = pipeline.PipelineSession.append
    real_prep = proto._prep_batched_operands
    real_closure = pipeline._batched_compute_closure
    real_decode = proto.decode_y_coeffs

    def append(self, a, b, *args, **kw):
        seen["encode"].append(a.nbytes + b.nbytes)
        return real_append(self, a, b, *args, **kw)

    def prep(plan, a, b):
        seen["b_on_device"].append(isinstance(b, jax.Array))
        a_j, b_j = real_prep(plan, a, b)
        seen["upload"].append(a_j.nbytes)
        return a_j, b_j

    def closure(*args, **kw):
        compute = real_closure(*args, **kw)

        def recorded(ids):
            out = compute(ids)
            seen["i_evals"].append(out.nbytes)
            return out

        return recorded

    def decode(plan, i_evals, ids, *args, **kw):
        out = real_decode(plan, i_evals, ids, *args, **kw)
        seen["fetch"].append(out.nbytes)
        seen["rows"].append((plan.scheme.t ** 2, plan.n_total))
        return out

    monkeypatch.setattr(pipeline.PipelineSession, "append", append)
    monkeypatch.setattr(proto, "_prep_batched_operands", prep)
    monkeypatch.setattr(pipeline, "_batched_compute_closure", closure)
    monkeypatch.setattr(proto, "decode_y_coeffs", decode)
    TRACER.clear()
    TRACER.enable()
    try:
        eng, _, rng = _engine(max_batch=2)
        for i in range(4):
            eng.submit(rng.normal(size=(ROWS, K_DIM)), 0.0)
        rep = eng.run()
    finally:
        TRACER.disable()
    walls = [e for e in TRACER.events if e["clock"] == "wall" and e["kind"] == "span"]
    TRACER.clear()
    assert rep.replays == 2
    by_name = {}
    for e in walls:
        by_name.setdefault(e["name"], []).append(e)
    (run,) = by_name["serve.run"]
    replays = by_name["runtime.replay"]
    assert [e["attrs"]["replay"] for e in replays] == [0, 1]
    assert all(e["parent"] == run["id"] for e in replays)
    for name in ("serve.admit", "serve.encode", "serve.decode"):
        assert [e["attrs"]["replay"] for e in by_name[name]] == [0, 1], name
        assert all(e["parent"] == run["id"] for e in by_name[name]), name
    for name in (
        "runtime.upload", "protocol.phase2", "protocol.phase3.device_decode",
        "runtime.device_wait", "runtime.fetch",
    ):
        spans = by_name[name]
        assert [e["attrs"]["replay"] for e in spans] == [0, 1], name
        assert [e["parent"] for e in spans] == [e["id"] for e in replays], name
    assert [e["attrs"]["bytes"] for e in by_name["serve.encode"]] == seen["encode"]
    assert [e["attrs"]["bytes"] for e in by_name["runtime.upload"]] == seen["upload"]
    # the device decodes: what crosses back is Y's t^2 coefficient rows,
    # not every worker's I-evaluations, and no host search runs
    assert [e["attrs"]["bytes"] for e in by_name["runtime.fetch"]] == seen["fetch"]
    assert [e["attrs"]["decode"] for e in by_name["runtime.fetch"]] == ["device"] * 2
    assert [f * n_total for f, (_, n_total) in zip(seen["fetch"], seen["rows"])] == [
        i * t2 for i, (t2, _) in zip(seen["i_evals"], seen["rows"])
    ]
    assert "protocol.phase3.subset_search" not in by_name
    assert all(b > 0 for b in seen["encode"] + seen["upload"] + seen["fetch"])
    assert seen["b_on_device"] == [True, True]
    assert all(u < e for u, e in zip(seen["upload"], seen["encode"]))
    assert [e["attrs"]["requests"] for e in by_name["serve.encode"]] == [2, 2]


# ----------------------------------------------------------------------
# W's residues resident on the device
# ----------------------------------------------------------------------
def _host_stack(monkeypatch):
    """Build every replay's B operand on the host instead, as int64 field
    values: the runtime then reduces and uploads all of it."""
    import repro.serve.engine as engine_mod

    monkeypatch.setattr(
        engine_mod, "_stack",
        lambda ws: np.stack([np.asarray(w, np.int64) for w in ws]),
    )


def _x(rng, mag):
    """Request rows whose largest magnitude is exactly ``mag``, so the
    request's fixed-point scale depends on ``mag`` alone."""
    x = rng.uniform(-mag, mag, size=(ROWS, K_DIM))
    x[0, 0] = mag
    return x


def _encode_spans(submit, **kw):
    """Run one engine with tracing on; (requests, serve.encode attrs)."""
    TRACER.clear()
    TRACER.enable()
    try:
        eng, w, rng = _engine(**kw)
        reqs = submit(eng, rng)
        eng.run()
    finally:
        TRACER.disable()
    attrs = [
        e["attrs"] for e in TRACER.events
        if e["clock"] == "wall" and e["kind"] == "span" and e["name"] == "serve.encode"
    ]
    TRACER.clear()
    return eng, w, reqs, attrs


@pytest.mark.parametrize(
    "mags, max_batch",
    [
        ((1.0,) * 8, 4),  # two full batches, one scale
        ((1.0,) * 6, 4),  # a full batch, then a partial one
        ((0.01, 1.0, 30.0, 1.0, 0.01, 300.0), 8),  # one batch, four scales
    ],
    ids=["full", "partial", "mixed-scales"],
)
def test_resident_w_decodes_bit_identically_to_host_stack(monkeypatch, mags, max_batch):
    """At one seed, the engine's device-resident W gives the same decoded
    Y, bit for bit, as B stacked on the host and uploaded every replay;
    each distinct scale is uploaded once, and the counters say so."""

    def submit(eng, rng):
        return [eng.submit(_x(rng, m), 0.0) for m in mags]

    eng, w, reqs, attrs = _encode_spans(submit, max_batch=max_batch)
    with monkeypatch.context() as m:
        _host_stack(m)
        _, _, host_reqs, _ = _encode_spans(submit, max_batch=max_batch)
    assert all(r.state == DONE for r in reqs + host_reqs)
    for r, h in zip(reqs, host_reqs):
        assert r.replay == h.replay
        assert np.array_equal(r.y, h.y)
        assert np.array_equal(r.y, _exact_y(r.x, w))
    scales = {
        choose_scales(K_DIM, float(np.abs(r.x).max() + 1e-9),
                      float(np.abs(w).max() + 1e-9), FIELD.p)
        for r in reqs
    }
    assert sorted(eng._w_dev) == sorted(("w", s) for s in scales)
    assert len(scales) == (4 if len(set(mags)) > 1 else 1)
    w_bytes = K_DIM * OUT * 4  # W's int32 residues at one scale
    assert sum(a["requests"] for a in attrs) == len(mags)
    assert sum(a["requests"] - a["w_hits"] for a in attrs) == len(scales)
    assert sum(a["w_bytes"] for a in attrs) == len(scales) * w_bytes


def test_w_residues_cross_to_the_device_once_per_scale():
    """w_bytes > 0 only on the replay that first meets a scale: later
    replays, and later run() waves, at that scale are all hits."""
    TRACER.clear()
    TRACER.enable()
    try:
        eng, w, rng = _engine(max_batch=2)
        for mag in (1.0, 1.0, 100.0):  # a new scale on the third wave
            for _ in range(4):
                eng.submit(_x(rng, mag), 0.0)
            eng.run()
    finally:
        TRACER.disable()
    attrs = [
        e["attrs"] for e in TRACER.events
        if e["clock"] == "wall" and e["kind"] == "span" and e["name"] == "serve.encode"
    ]
    TRACER.clear()
    w_bytes = K_DIM * OUT * 4
    assert [a["requests"] for a in attrs] == [2] * 6
    assert [a["w_bytes"] for a in attrs] == [w_bytes, 0, 0, 0, w_bytes, 0]
    assert [a["w_hits"] for a in attrs] == [1, 2, 2, 2, 1, 2]
    assert len(eng._w_dev) == 2


def test_prep_operands_same_for_host_and_device_input():
    """_prep_batched_operands gives the same int32 operands for a numpy
    array and a jax.Array holding the same integers, negatives and
    values >= p included; a device input is reduced on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core import protocol as proto

    plan = get_plan_for(
        PlanConfig("age", 2, 2, 1), BlockShapes(k=8, ma=4, mb=4, s=2, t=2),
        field=FIELD,
    )
    rng = np.random.default_rng(3)
    p = FIELD.p
    a = rng.integers(-3 * p, 3 * p, size=(2, 8, 4))
    b = rng.integers(-3 * p, 3 * p, size=(2, 8, 4))
    b[0, 0, :4] = [-1, p, -p, 2 * p + 5]
    a_h, b_h = proto._prep_batched_operands(plan, a, b)
    a_d, b_d = proto._prep_batched_operands(
        plan, jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)
    )
    for host, dev, want in ((a_h, a_d, a % p), (b_h, b_d, b % p)):
        assert isinstance(dev, jax.Array)
        assert host.dtype == dev.dtype == jnp.int32
        assert np.array_equal(np.asarray(host), want)
        assert np.array_equal(np.asarray(dev), want)
    # 2D inputs promote to batch 1 on either path
    a2, b2 = proto._prep_batched_operands(plan, jnp.asarray(a[0], jnp.int32), b[0])
    assert a2.shape == (1, 8, 4) and b2.shape == (1, 8, 4)
    assert np.array_equal(np.asarray(a2[0]), a[0] % p)
