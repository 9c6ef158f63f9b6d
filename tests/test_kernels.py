"""Pallas modmatmul kernel vs the numpy oracle (interpret mode executes
the kernel body on CPU), swept over shapes, primes and block sizes."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback: deterministic example grid
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.gf import Field
from repro.kernels.modmatmul import mod_matmul, modmatmul_jnp_ref, modmatmul_ref
from repro.kernels.modmatmul.ops import polyeval

SHAPES = [(1, 1, 1), (4, 7, 5), (128, 256, 128), (130, 300, 70), (200, 513, 33),
          (256, 256, 256), (17, 1024, 9)]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_pallas_vs_oracle(m, k, n):
    rng = np.random.default_rng(m * 1000 + k + n)
    p = 65521
    a = rng.integers(0, p, (m, k)).astype(np.int32)
    b = rng.integers(0, p, (k, n)).astype(np.int32)
    want = modmatmul_ref(a, b, p)
    got = np.asarray(mod_matmul(a, b, p=p, backend="pallas", interpret=True))
    assert np.array_equal(want, got)


@pytest.mark.parametrize("p", [251, 4093, 7919, 40961, 65519, 65521])
def test_pallas_primes(p):
    rng = np.random.default_rng(p)
    a = rng.integers(0, p, (64, 300)).astype(np.int32)
    b = rng.integers(0, p, (300, 32)).astype(np.int32)
    want = modmatmul_ref(a, b, p)
    got = np.asarray(mod_matmul(a, b, p=p, backend="pallas", interpret=True))
    assert np.array_equal(want, got)


@pytest.mark.parametrize("blocks", [(128, 128, 256), (128, 128, 128), (256, 128, 64)])
def test_pallas_block_shapes(blocks):
    bm, bn, bk = blocks
    rng = np.random.default_rng(bm + bn + bk)
    p = 65521
    a = rng.integers(0, p, (100, 200)).astype(np.int32)
    b = rng.integers(0, p, (200, 50)).astype(np.int32)
    got = np.asarray(
        mod_matmul(a, b, p=p, backend="pallas", interpret=True, bm=bm, bn=bn, bk=bk)
    )
    assert np.array_equal(modmatmul_ref(a, b, p), got)


def test_batched():
    rng = np.random.default_rng(5)
    p = 65521
    a = rng.integers(0, p, (3, 32, 64)).astype(np.int32)
    b = rng.integers(0, p, (3, 64, 16)).astype(np.int32)
    want = np.stack([modmatmul_ref(a[i], b[i], p) for i in range(3)])
    got = np.asarray(mod_matmul(a, b, p=p, backend="pallas", interpret=True))
    assert np.array_equal(want, got)
    got_f = np.asarray(mod_matmul(a, b, p=p, backend="f32limb"))
    assert np.array_equal(want, got_f)


def test_jnp_ref_matches_oracle():
    rng = np.random.default_rng(6)
    p = 65521
    a = rng.integers(0, p, (37, 290)).astype(np.int32)
    b = rng.integers(0, p, (290, 21)).astype(np.int32)
    assert np.array_equal(modmatmul_ref(a, b, p), np.asarray(modmatmul_jnp_ref(a, b, p)))


def test_polyeval():
    rng = np.random.default_rng(7)
    f = Field()
    coeffs = f.random(rng, (5, 4, 3))
    alphas = rng.choice(f.p - 1, size=6, replace=False) + 1
    powers = [0, 2, 3, 7, 11]
    v = f.vandermonde(alphas, powers)
    got = np.asarray(polyeval(v.astype(np.int32), coeffs.astype(np.int32), p=f.p))
    want = np.zeros((6, 4, 3), np.int64)
    for n in range(6):
        for j, u in enumerate(powers):
            want[n] = (want[n] + coeffs[j] * f.pow(alphas[n], u)) % f.p
    assert np.array_equal(want, got)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(1, 64), k=st.integers(1, 300), n=st.integers(1, 48),
    seed=st.integers(0, 10_000),
)
def test_pallas_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    p = 65521
    a = rng.integers(0, p, (m, k)).astype(np.int32)
    b = rng.integers(0, p, (k, n)).astype(np.int32)
    got = np.asarray(mod_matmul(a, b, p=p, backend="pallas", interpret=True))
    assert np.array_equal(modmatmul_ref(a, b, p), got)


def test_autotune_skips_and_counts_refused_tilings():
    """A tiling the kernel refuses is skipped and counted; the valid
    candidate wins and is pinned."""
    from repro.kernels.modmatmul import ops
    from repro.obs.metrics import REGISTRY

    refused = REGISTRY.counter("kernels.autotune_refused")
    before = refused.value
    best = ops.autotune_tiles(
        16, 32, 128, backend="pallas", candidates=[(16, 128, 512), (16, 128, 32)],
        repeats=1, interpret=True,
    )
    assert best == (16, 128, 32)
    assert refused.value == before + 1  # bk = 512 breaks exact f32 sums
    assert ops.pick_tiles(16, 32, 128) == best
    ops._AUTOTUNE_CACHE.pop(("pallas", 16, 32, 128))
