"""Sharded batched engine, single-device semantics.

``run_batched_sharded`` must decode bit-identically to ``run_batched``
and the host oracle for every exchange mode, arbitrary Phase-2 sender
subsets, and batched worker-leading operands — here on a 1-device mesh
(the collective degenerates but the shard_map path, padding, subset mix
matrices, and batch folding are all exercised); the multi-device
versions run in subprocesses in ``test_distributed.py``.

Also the int32 safety-bound regression: ``run_phase2_sharded`` used to
assert ``n_total * n_workers < 2**31 // p``, which spuriously rejects
pools past ~180 workers at p = 65521 even though the ``_mod_sum``
accumulation only needs ``npad * p < 2**31`` (padded pool size).
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import constructions as C
from repro.core import protocol as proto
from repro.core.distributed import run_phase2_sharded
from repro.core.gf import Field
from repro.core.planner import BlockShapes, make_plan

MODES = ("all_to_all", "psum", "psum_scatter")


@pytest.fixture(scope="module")
def setup():
    field = Field()
    rng = np.random.default_rng(0)
    sch = C.build_scheme("age", 2, 2, 2)
    shapes = BlockShapes(k=8, ma=12, mb=4, s=2, t=2)
    plan = make_plan(sch, shapes, n_spare=3, seed=1)
    batch = 3
    a = field.random(rng, (batch, 8, 12))
    b = field.random(rng, (batch, 8, 4))
    want = np.stack([field.matmul(a[i].T, b[i]) for i in range(batch)])
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    return plan, a, b, want, mesh


@pytest.mark.parametrize("mode", MODES)
def test_run_batched_sharded_equals_run_batched(setup, mode):
    plan, a, b, want, mesh = setup
    y_ref, tr_ref = proto.run_batched(plan, a, b, seed=2)
    y, tr = proto.run_batched_sharded(plan, a, b, mesh, mode=mode, seed=2)
    assert np.array_equal(y, y_ref)
    assert np.array_equal(y, want)
    # identical Corollary-12 accounting for identical batch sizes
    assert tr.total == tr_ref.total
    assert tr.phase2_worker_to_worker == tr_ref.phase2_worker_to_worker


@pytest.mark.parametrize("mode", MODES)
def test_run_batched_sharded_worker_subset(setup, mode):
    """A non-trivial Phase-2 sender subset plus a shifted Phase-3
    responder subset must still decode exactly (the scheduler's
    straggler path through the shard_map exchange)."""
    plan, a, b, want, mesh = setup
    ids2 = np.array([i for i in range(plan.n_total) if i not in (0, 2)])
    ids2 = ids2[: plan.n_workers]
    ids3 = np.arange(2, 2 + plan.decode_threshold)
    y, _ = proto.run_batched_sharded(
        plan, a, b, mesh, mode=mode, seed=4, phase2_ids=ids2, phase3_ids=ids3
    )
    assert np.array_equal(y, want)


def test_phase2_sharded_batched_matches_unbatched(setup):
    """The batch fold must reproduce per-product unbatched exchanges
    when fed identical shares and noise."""
    plan, a, b, want, mesh = setup
    field = Field()
    rng = np.random.default_rng(9)
    batch = a.shape[0]
    blk = plan.shapes.blk_y
    fa = np.stack([np.asarray(proto.share_a(plan, a[i], rng)) for i in range(batch)])
    fb = np.stack([np.asarray(proto.share_b(plan, b[i], rng)) for i in range(batch)])
    noise = field.random(rng, (batch, plan.n_workers, plan.scheme.z) + blk)
    i_batched = run_phase2_sharded(plan, fa, fb, noise, mesh)
    assert i_batched.shape == (batch, plan.n_total) + blk
    for i in range(batch):
        i_one = run_phase2_sharded(plan, fa[i], fb[i], noise[i], mesh)
        assert np.array_equal(i_batched[i], i_one), i
        assert np.array_equal(proto.reconstruct(plan, i_one), want[i])


def test_large_pool_passes_int32_bound():
    """Regression: a ~230-worker PolyDot pool is int32-safe (npad * p ~
    1.5e7 << 2**31) but the old ``n_total * n_workers < 2**31 // p``
    formula rejected it (230 * 228 = 52440 > 32775)."""
    field = Field()
    rng = np.random.default_rng(3)
    sch = C.build_scheme("polydot", 5, 5, 3)
    assert sch.n_workers >= 180  # the regime the old assert blocked
    shapes = BlockShapes(k=5, ma=5, mb=5, s=5, t=5)
    plan = make_plan(sch, shapes, n_spare=2, seed=0)
    # the old formula must reject this pool, the real bound must not
    assert plan.n_total * plan.n_workers >= (1 << 31) // field.p
    assert plan.n_total * field.p < (1 << 31)

    a = field.random(rng, (5, 5))
    b = field.random(rng, (5, 5))
    fa = proto.share_a(plan, a, rng)
    fb = proto.share_b(plan, b, rng)
    noise = field.random(
        rng, (plan.n_workers, plan.scheme.z) + plan.shapes.blk_y
    )
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    i_evals = run_phase2_sharded(plan, fa, fb, noise, mesh)
    assert np.array_equal(proto.reconstruct(plan, i_evals), field.matmul(a.T, b))


def test_phase2_program_compiled_once_per_shape(setup):
    """Replays of one shape reuse the jitted exchange: the shard_map
    program is cached per (mesh, mode, shape), not rebuilt per call."""
    from repro.core import distributed

    plan, a, b, want, mesh = setup
    field = Field()
    rng = np.random.default_rng(21)
    fa = np.stack([np.asarray(proto.share_a(plan, a[i], rng)) for i in range(3)])
    fb = np.stack([np.asarray(proto.share_b(plan, b[i], rng)) for i in range(3)])
    noise = field.random(rng, (3, plan.n_workers, plan.scheme.z) + plan.shapes.blk_y)
    run_phase2_sharded(plan, fa, fb, noise, mesh, mode="psum_scatter")
    programs = distributed._phase2_program.cache_info().currsize
    for _ in range(2):
        i_evals = run_phase2_sharded(plan, fa, fb, noise, mesh, mode="psum_scatter")
    assert distributed._phase2_program.cache_info().currsize == programs
    first = distributed._phase2_program(
        mesh, "workers", "psum_scatter", "auto", plan.field.p, plan.scheme.z,
        3, *plan.shapes.blk_y,
    )
    assert first._cache_size() == 1  # traced and compiled once
    for i in range(3):
        assert np.array_equal(proto.reconstruct(plan, i_evals[i]), want[i])
