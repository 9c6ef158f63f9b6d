"""Cross-backend equivalence for the GF(p) matmul layer.

Five implementations must agree bit-exactly: both Pallas kernels
(f32-limb and native-int32, interpret mode on CPU), the portable
f32limb and int32 paths, and the host ``Field.matmul`` oracle — swept
over non-tile-multiple shapes, batched/broadcast operand layouts, and
adversarial dense-high-limb inputs that sit on the lazy-reduction
bounds.  Also pins the single-launch contract: batched ``mod_matmul``
lowers to ONE ``pallas_call`` whose grid carries the batch axis.

(The randomized extension of this fixed grid — random shapes, primes,
and distributions — lives in ``test_kernel_fuzz.py``.)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gf import CHUNK_K, LAZY_K, Field, mod_matmul_f32
from repro.kernels.modmatmul import mod_matmul, modmatmul_ref
from repro.kernels.modmatmul.ops import padded_shape, padding_waste, pick_tiles

P = 65521


def _oracle(a, b, p=P):
    """Broadcasting host oracle built on Field.matmul."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    batch = np.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = np.broadcast_to(a, batch + a.shape[-2:])
    b = np.broadcast_to(b, batch + b.shape[-2:])
    af = a.reshape((-1,) + a.shape[-2:])
    bf = b.reshape((-1,) + b.shape[-2:])
    out = np.stack([modmatmul_ref(af[i], bf[i], p) for i in range(af.shape[0])])
    return out.reshape(batch + out.shape[-2:])


BACKENDS = ("f32limb", "int32", "pallas", "pallas_int32")


def _all_backends(a, b, **kw):
    """{backend: result} over every backend (Pallas in interpret mode)."""
    out = {}
    for backend in BACKENDS:
        if backend.startswith("pallas"):
            kw.setdefault("interpret", True)
        out[backend] = np.asarray(mod_matmul(a, b, backend=backend, **kw))
    return out


def _assert_all_equal(want, got_by_backend, ctx=None):
    for backend, got in got_by_backend.items():
        assert np.array_equal(want, got), (backend, ctx)


# non-tile-multiple shapes: every dim off the 8/128/256 alignment grid
SHAPES = [(1, 1, 1), (3, 5, 2), (9, 33, 11), (130, 257, 70), (17, 129, 200)]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_2d_all_backends(m, k, n):
    rng = np.random.default_rng(m * 7919 + k * 31 + n)
    a = rng.integers(0, P, (m, k)).astype(np.int32)
    b = rng.integers(0, P, (k, n)).astype(np.int32)
    want = modmatmul_ref(a, b, P)
    _assert_all_equal(want, _all_backends(a, b, p=P), (m, k, n))


BATCH_CASES = [
    ((4, 9, 33), (4, 33, 11)),       # both batched
    ((9, 33), (4, 33, 11)),          # 2D constant LHS, batched RHS
    ((4, 9, 33), (33, 11)),          # batched LHS, 2D constant RHS
    ((1, 5, 17), (3, 17, 7)),        # unit-batch broadcast
    ((2, 1, 5, 17), (1, 3, 17, 7)),  # multi-dim batch broadcast
    ((3, 9, 300), (3, 300, 11)),     # deep-K batched (scan path on f32limb)
    ((9, 300), (3, 300, 11)),        # deep-K constant LHS
]


@pytest.mark.parametrize("sa,sb", BATCH_CASES)
def test_batched_layouts_all_backends(sa, sb):
    rng = np.random.default_rng(sum(sa) * 131 + sum(sb))
    a = rng.integers(0, P, sa).astype(np.int32)
    b = rng.integers(0, P, sb).astype(np.int32)
    want = _oracle(a, b)
    _assert_all_equal(want, _all_backends(a, b, p=P), (sa, sb))


@pytest.mark.parametrize("p", [251, 4093, 40961, 65519, 65521])
def test_batched_primes(p):
    rng = np.random.default_rng(p)
    a = rng.integers(0, p, (3, 12, 37)).astype(np.int32)
    b = rng.integers(0, p, (3, 37, 9)).astype(np.int32)
    want = _oracle(a, b, p)
    _assert_all_equal(want, _all_backends(a, b, p=p), p)


# ----------------------------------------------------------------------
# lazy-reduction bound regression: dense high limbs at boundary depths
# ----------------------------------------------------------------------
# Values >= P-241 have hi limb 255; depths 127/128/129 bracket the
# LAZY_K cutoff just under the raw-cross-dot-sum exactness limit
# (2*d*255**2 < 2**24 holds through d = 129, fails at 130), and
# 255/256/257 straddle the raw-low-limb fold bound
# 3*(p-1) + d*255**2 < 2**24 and the CHUNK_K chunking boundary.
ADVERSARIAL_K = [LAZY_K - 1, LAZY_K, LAZY_K + 1, 255, CHUNK_K, CHUNK_K + 1]


@pytest.mark.parametrize("k", ADVERSARIAL_K)
def test_dense_high_limb_bounds(k):
    rng = np.random.default_rng(k)
    a = rng.integers(P - 241, P, (2, 8, k)).astype(np.int32)
    b = rng.integers(P - 241, P, (2, k, 8)).astype(np.int32)
    want = _oracle(a, b)
    _assert_all_equal(want, _all_backends(a, b, p=P), k)


def test_all_maximal_elements():
    """Every element p-1: worst case for every accumulation bound."""
    for k in (LAZY_K, 255, CHUNK_K, CHUNK_K + 1):
        a = np.full((2, 4, k), P - 1, np.int32)
        b = np.full((2, k, 4), P - 1, np.int32)
        want = _oracle(a, b)
        _assert_all_equal(want, _all_backends(a, b, p=P), k)


# ----------------------------------------------------------------------
# single-launch + tile-adaptivity contracts
# ----------------------------------------------------------------------
def _collect_eqns(jaxpr, name, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            out.append(eqn)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _collect_eqns(sub, name, out)
    return out


def _grid_of(eqn):
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None)
    if grid is None:
        grid = eqn.params.get("grid")
    return tuple(grid)


def test_batched_single_pallas_launch():
    """[B, M, K] @ [B, K, N] lowers to ONE pallas_call with the batch on
    the leading grid axis (no vmap-of-2D launches)."""
    a = jnp.zeros((4, 16, 32), jnp.int32)
    b = jnp.zeros((4, 32, 8), jnp.int32)

    def f(x, y):
        return mod_matmul(x, y, p=P, backend="pallas", interpret=True)

    jaxpr = jax.make_jaxpr(f)(a, b)
    calls = _collect_eqns(jaxpr.jaxpr, "pallas_call", [])
    assert len(calls) == 1, f"expected one pallas_call, got {len(calls)}"
    grid = _grid_of(calls[0])
    assert len(grid) == 4, grid  # (batch, m, n, k)
    assert grid[0] == 4, grid
    # interpret-mode output stays bit-exact against the host oracle
    rng = np.random.default_rng(0)
    av = rng.integers(0, P, a.shape).astype(np.int32)
    bv = rng.integers(0, P, b.shape).astype(np.int32)
    assert np.array_equal(np.asarray(f(av, bv)), _oracle(av, bv))


def test_constant_lhs_not_broadcast_in_launch():
    """A 2D constant LHS against a batched RHS stays 2D inside the one
    pallas_call: its block index map is batch-invariant, so no [B, ...]
    copy of the constant is materialized."""
    a = jnp.zeros((8, 32), jnp.int32)
    b = jnp.zeros((5, 32, 8), jnp.int32)

    def f(x, y):
        return mod_matmul(x, y, p=P, backend="pallas", interpret=True)

    jaxpr = jax.make_jaxpr(f)(a, b)
    calls = _collect_eqns(jaxpr.jaxpr, "pallas_call", [])
    assert len(calls) == 1
    assert len(_grid_of(calls[0])) == 4
    # the kernel's first operand keeps rank 2 (shared across the batch axis)
    a_inval = calls[0].invars[0].aval
    assert a_inval.ndim == 2, a_inval


def test_pick_tiles_alignment_and_adaptivity():
    for m, k, n in [(1, 1, 1), (10, 6, 1024), (32, 32, 32), (300, 700, 513),
                    (21, 6, 3317760), (8, 1152, 2880)]:
        bm, bn, bk = pick_tiles(m, k, n)
        # each tile is the whole dim (no TPU alignment needed, nothing
        # padded) or an aligned tile: sublane 8, lane 128, K 128/256
        assert bm == m or bm % 8 == 0
        assert bn == n or bn % 128 == 0
        assert bk == k or bk in (128, 256)
        assert bk <= 256  # exact f32 limb accumulation
        # adaptive tiles never waste more than the fixed 128/128/256 tiling
        assert padding_waste(m, k, n, (bm, bn, bk)) <= padding_waste(
            m, k, n, (128, 128, 256)
        ) + 1e-12
    # the protocol's small blocks: the lane dim keeps a 128 floor, but
    # adaptive tiles still cut the total padded MAC count by >4x vs the
    # fixed 128/128/256 tiling
    def macs(m, k, n, tiles):
        mp, kp, np_ = padded_shape(m, k, n, tiles)
        return mp * kp * np_

    assert macs(17, 6, 1024, pick_tiles(17, 6, 1024)) * 4 < macs(
        17, 6, 1024, (128, 128, 256)
    )
    # short dims are never padded: the share phase's K = 6 stays 6
    assert padded_shape(21, 6, 3317760, pick_tiles(21, 6, 3317760)) == (
        21, 6, 3317760
    )
    # a deep K takes a depth that divides it (1152 = 9 * 128)
    assert pick_tiles(8, 1152, 2880)[2] == 128


def test_explicit_tiles_still_win():
    rng = np.random.default_rng(7)
    a = rng.integers(0, P, (3, 20, 40)).astype(np.int32)
    b = rng.integers(0, P, (3, 40, 10)).astype(np.int32)
    want = _oracle(a, b)
    got = np.asarray(
        mod_matmul(a, b, p=P, backend="pallas", interpret=True, bm=8, bn=128, bk=128)
    )
    assert np.array_equal(want, got)


def test_f32limb_matches_field_matmul_oracle_large():
    f = Field(P)
    rng = np.random.default_rng(11)
    a = rng.integers(0, P, (65, 517)).astype(np.int32)
    b = rng.integers(0, P, (517, 43)).astype(np.int32)
    assert np.array_equal(f.matmul(a, b), np.asarray(mod_matmul_f32(a, b, P)))
