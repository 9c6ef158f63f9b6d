"""Phase-3 subset decodability (Theorem 6 / eq. 21 mechanics).

The runtime decodes from whatever ``decode_threshold``-sized responder
subset is fastest, so decode must succeed from *every* such subset of
the provisioned pool — not just the primary prefix — for spare counts
0, 1, 2 across PolyDot-CMPC and AGE-CMPC, and must fail loudly below
the threshold.  Runs one protocol execution per scheme and sweeps
subsets of the recorded I(alpha_n); exhaustive when the subset count is
small, a deterministic sample (always including the prefix and the
tail) otherwise.
"""
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback: deterministic example grid
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import constructions as C
from repro.core import planner
from repro.core import protocol as proto
from repro.core.gf import Field
from repro.core.planner import BlockShapes, make_plan

EXHAUSTIVE_CAP = 300  # max subsets to sweep per (scheme, n_spare) case

SCHEMES = [
    ("polydot", 2, 1, 1),  # small thresholds keep the sweep exhaustive
    ("polydot", 1, 2, 1),
    ("age", 2, 1, 1),
    ("age", 1, 2, 1),
    ("age", 2, 2, 2),
]


def _subsets(n_total: int, thr: int, seed: int):
    """All thr-subsets of range(n_total), or a deterministic sample that
    always includes the primary prefix and the all-spares tail."""
    total = 1
    for i in range(thr):
        total = total * (n_total - i) // (i + 1)
    if total <= EXHAUSTIVE_CAP:
        yield from itertools.combinations(range(n_total), thr)
        return
    rng = np.random.default_rng(seed)
    yield tuple(range(thr))  # prefix fast path
    yield tuple(range(n_total - thr, n_total))  # slowest-tail subset
    for _ in range(EXHAUSTIVE_CAP - 2):
        yield tuple(np.sort(rng.choice(n_total, size=thr, replace=False)))


def _one_execution(method, s, t, z, n_spare, seed):
    field = Field()
    rng = np.random.default_rng(seed)
    sch = C.build_scheme(method, s, t, z)
    shapes = BlockShapes(k=s * 2, ma=t * 2, mb=t * 2, s=s, t=t)
    plan = make_plan(sch, shapes, n_spare=n_spare, seed=seed)
    a = field.random(rng, (shapes.k, shapes.ma))
    b = field.random(rng, (shapes.k, shapes.mb))
    fa = proto.share_a(plan, a, rng)
    fb = proto.share_b(plan, b, rng)
    h = proto.worker_multiply(plan, fa, fb)
    i_evals = proto.degree_reduce(plan, h, rng)
    return plan, i_evals, field.matmul(a.T, b)


@settings(max_examples=10, deadline=None)
@given(
    case=st.sampled_from(SCHEMES),
    n_spare=st.integers(0, 2),
    seed=st.integers(0, 1000),
)
def test_decode_from_every_threshold_subset(case, n_spare, seed):
    method, s, t, z = case
    plan, i_evals, want = _one_execution(method, s, t, z, n_spare, seed)
    thr = plan.decode_threshold
    for ids in _subsets(plan.n_total, thr, seed):
        y = proto.reconstruct(plan, i_evals, worker_ids=np.array(ids))
        assert np.array_equal(y, want), (method, s, t, z, n_spare, ids)


@settings(max_examples=6, deadline=None)
@given(
    case=st.sampled_from(SCHEMES),
    n_spare=st.integers(0, 2),
    short=st.integers(1, 3),
)
def test_below_threshold_fails_loudly(case, n_spare, short):
    method, s, t, z = case
    sch = C.build_scheme(method, s, t, z)
    shapes = BlockShapes(k=s * 2, ma=t * 2, mb=t * 2, s=s, t=t)
    plan = make_plan(sch, shapes, n_spare=n_spare, seed=0)
    n_ids = max(0, plan.decode_threshold - short)
    with pytest.raises(ValueError):
        plan.decode_matrix(np.arange(n_ids))
    with pytest.raises(ValueError):
        proto.reconstruct(
            plan,
            np.zeros((plan.n_total, 2, 2), np.int64),
            worker_ids=np.arange(n_ids),
        )


def test_subset_matrices_cached():
    """Repeated subset decodes hit the plan's subset cache, and the
    prefix fast paths bypass it entirely."""
    planner.subset_cache_clear()
    plan, i_evals, want = _one_execution("age", 2, 2, 2, 2, 7)
    thr = plan.decode_threshold
    ids = np.arange(2, 2 + thr)
    y1 = proto.reconstruct(plan, i_evals, worker_ids=ids)
    info1 = planner.subset_cache_info()
    y2 = proto.reconstruct(plan, i_evals, worker_ids=ids)
    info2 = planner.subset_cache_info()
    assert np.array_equal(y1, want) and np.array_equal(y2, want)
    assert info1["misses"] == 1 and info2["hits"] == info1["hits"] + 1
    # prefix decode does not touch the cache
    proto.reconstruct(plan, i_evals, worker_ids=np.arange(thr))
    assert planner.subset_cache_info()["misses"] == info2["misses"]
    # phase-2 prefix likewise returns the precomputed matrix
    assert plan.phase2_matrix_cached(np.arange(plan.n_workers)) is plan.mix


def test_decode_check_matrix_cached():
    """The master's consistency-check Vandermonde is built once per plan
    (it used to be rebuilt inside every ``run_over_pool`` replay) and
    matches the direct construction."""
    plan, i_evals, want = _one_execution("age", 2, 2, 2, 2, 11)
    v1 = plan.decode_check_matrix()
    v2 = plan.decode_check_matrix()
    assert v1 is v2  # memoized on the plan, not rebuilt
    direct = plan.field.vandermonde(plan.alphas, range(plan.decode_threshold))
    assert np.array_equal(v1, direct)
    assert v1.shape == (plan.n_total, plan.decode_threshold)
    # it predicts every worker's I(alpha_n) from the true coefficients
    thr = plan.decode_threshold
    flat = np.asarray(i_evals).reshape(plan.n_total, -1)
    coeffs = plan.field.matmul(plan.decode_w, flat[:thr])
    assert np.array_equal(plan.field.matmul(v1, coeffs), flat)


def _batched_i_evals(method, s, t, z, n_spare, batch, seed):
    """A batched replay's device-resident I-evaluations, as the runtime
    folds them ([n_total, batch * bry, bcy]), and each product's Y."""
    import jax

    from repro.runtime.pipeline import _batched_compute_closure

    field = Field()
    rng = np.random.default_rng(seed)
    sch = C.build_scheme(method, s, t, z)
    shapes = BlockShapes(k=s * 2, ma=t * 2, mb=t * 3, s=s, t=t)
    plan = make_plan(sch, shapes, n_spare=n_spare, seed=seed)
    a = field.random(rng, (batch, shapes.k, shapes.ma))
    b = field.random(rng, (batch, shapes.k, shapes.mb))
    a_j, b_j = proto._prep_batched_operands(plan, a, b)
    fa, fb = proto.share_batched(plan, a_j, b_j, jax.random.PRNGKey(seed))
    compute = _batched_compute_closure(
        plan, fa, fb, rng, batch, None, "workers", "all_to_all", "auto"
    )
    i_dev = compute(np.arange(plan.n_workers))
    want = np.stack([field.matmul(a[i].T, b[i]) for i in range(batch)])
    return plan, i_dev, want


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("n_spare", [0, 1, 2])
@pytest.mark.parametrize("case", [("age", 2, 2, 2), ("polydot", 2, 2, 1)])
def test_device_decode_matches_host_decode(case, n_spare, batch):
    """The device decode of a responder subset gives the host
    ``Field.matmul`` decode's first t^2 coefficient rows bit for bit, and
    Y unfolds from them alone — for the prefix, the slowest tail and
    subsets that are neither."""
    from repro.runtime.pipeline import _unfold_batched_y

    plan, i_dev, want = _batched_i_evals(*case, n_spare, batch, seed=5 + n_spare)
    thr, t2 = plan.decode_threshold, plan.scheme.t ** 2
    flat = np.asarray(i_dev).reshape(plan.n_total, -1)
    rng = np.random.default_rng(n_spare)
    subsets = [np.arange(thr), np.arange(plan.n_total - thr, plan.n_total)] + [
        np.sort(rng.choice(plan.n_total, size=thr, replace=False)) for _ in range(3)
    ]
    for ids in subsets:
        host = plan.field.matmul(plan.decode_matrix_cached(ids), flat[ids])
        dev = np.asarray(proto.decode_y_coeffs(plan, i_dev, ids))
        assert dev.dtype == np.int32 and dev.shape == (t2, flat.shape[1])
        assert np.array_equal(dev, host[:t2]), ids
        y = _unfold_batched_y(plan, dev.astype(np.int64), batch)
        assert np.array_equal(y, want), ids
        assert np.array_equal(y, _unfold_batched_y(plan, host, batch)), ids


def test_device_decode_rows_cached_per_subset():
    """A subset's device ids and decode rows are uploaded once, in the
    plan's bounded subset cache beside its host decode matrix."""
    plan, i_dev, _ = _batched_i_evals("age", 2, 2, 2, 2, 1, seed=9)
    ids = np.arange(2, 2 + plan.decode_threshold)
    proto.decode_y_coeffs(plan, i_dev, ids)
    cache = plan.__dict__["_subset_cache"]
    key = ("dec_dev", tuple(int(i) for i in ids))
    ids_dev, rows_dev = cache[key]
    assert ("dec", key[1]) in cache
    info = planner.subset_cache_info()
    proto.decode_y_coeffs(plan, i_dev, ids)
    assert planner.subset_cache_info()["misses"] == info["misses"]
    assert cache[key][0] is ids_dev and cache[key][1] is rows_dev
    assert rows_dev.shape == (plan.scheme.t ** 2, plan.decode_threshold)
