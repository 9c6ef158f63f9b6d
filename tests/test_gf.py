"""Field arithmetic: host oracle + device limb paths."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback: deterministic example grid
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.gf import Field, P_DEFAULT, mod_matmul_f32

PRIMES = [251, 4093, 7919, 40961, 65519, 65521]


@pytest.fixture(scope="module")
def f():
    return Field()


def test_inverse(f):
    rng = np.random.default_rng(0)
    for _ in range(50):
        a = int(rng.integers(1, f.p))
        assert (a * f.inv(a)) % f.p == 1


def test_solve_roundtrip(f):
    rng = np.random.default_rng(1)
    a = f.random(rng, (8, 8))
    x = f.random(rng, (8, 3))
    b = f.matmul(a, x)
    got = f.solve(a, b)
    assert np.array_equal(got, x)


def test_inv_matrix(f):
    rng = np.random.default_rng(2)
    a = f.random(rng, (10, 10))
    inv = f.inv_matrix(a)
    assert np.array_equal(f.matmul(a, inv), np.eye(10, dtype=np.int64))


def test_vandermonde_invertible(f):
    rng = np.random.default_rng(3)
    pts = rng.choice(f.p - 1, size=12, replace=False) + 1
    v = f.vandermonde(pts, range(12))
    f.inv_matrix(v)  # must not raise


@pytest.mark.parametrize("p", PRIMES)
def test_limb_matmul_all_primes(p):
    rng = np.random.default_rng(p)
    f = Field(p)
    a = rng.integers(0, p, (37, 300)).astype(np.int32)
    b = rng.integers(0, p, (300, 23)).astype(np.int32)
    want = f.matmul(a, b)
    got = np.asarray(mod_matmul_f32(a, b, p))
    assert np.array_equal(want, got)


@settings(max_examples=30, deadline=None)
@given(
    m=st.integers(1, 40),
    k=st.integers(1, 600),
    n=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_limb_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    f = Field()
    a = rng.integers(0, f.p, (m, k)).astype(np.int32)
    b = rng.integers(0, f.p, (k, n)).astype(np.int32)
    assert np.array_equal(f.matmul(a, b), np.asarray(mod_matmul_f32(a, b, f.p)))


def test_encode_decode_roundtrip(f):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(16, 16))
    q = f.encode(x, 256)
    back = f.decode(q, 256)
    assert np.abs(back - x).max() <= 1.0 / 256


def test_encode_overflow_raises(f):
    with pytest.raises(OverflowError):
        f.encode(np.array([1e6]), 256)


def test_matmul_rejects_non_2d_rhs(f):
    """A batched (or 1D) right-hand side is refused loudly: the chunk
    loop would slice its batch axis as the contraction."""
    a = np.ones((2, 3), np.int64)
    with pytest.raises(ValueError, match="2D right-hand side"):
        f.matmul(a, np.ones((4, 3, 5), np.int64))
    with pytest.raises(ValueError, match="2D right-hand side"):
        f.matmul(a, np.ones(3, np.int64))
    assert np.array_equal(f.matmul(a, np.ones((3, 5), np.int64)), np.full((2, 5), 3))
