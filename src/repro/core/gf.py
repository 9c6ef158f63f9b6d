"""Finite-field GF(p) arithmetic for coded MPC.

Two execution paths:

* **Host path** (numpy ``int64``): exact reference arithmetic used for
  protocol planning (Vandermonde inverses, Lagrange coefficients) and as
  the test oracle.  ``p`` may be any prime < 2**31.

* **Device path** (jnp ``float32`` limbs): TPU-native modular matmul.
  The MXU is a floating-point systolic array, so instead of porting an
  integer GPU algorithm we decompose field elements ``a = a_hi*256 +
  a_lo`` into 8-bit limbs, accumulate limb products exactly in f32
  (products < 2**16; <=256 accumulands keeps partial sums < 2**24, the
  f32 exact-integer bound) and reduce mod p after every 256-deep chunk.
  This requires ``p < 2**16``; the default prime is 65521 (the largest
  16-bit prime).

The device path is also implemented as a Pallas TPU kernel in
``repro.kernels.modmatmul``; the jnp version here is the portable
fallback (identical math, usable inside shard_map/vmap everywhere).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Largest 16-bit prime: elements fit in two 8-bit limbs exactly, enabling
# exact f32 accumulation on the MXU with 256-deep inner chunks.
P_DEFAULT = 65521

# Inner-dimension chunk depth for exact f32 limb accumulation:
# 255*255*256 = 16_646_400 < 2**24.
CHUNK_K = 256

# Lazy-reduction depth bound for *pure-f32* pipelines (the Pallas
# kernel): the two cross-limb dots may be summed raw before a single
# reduction iff 2 * depth * 255**2 < 2**24, i.e. depth <= 129.  At
# depth <= 128 the final recombination may also fold the raw low-limb
# dot and the running accumulator into one reduction:
# 3*(p-1) + 128*255**2 = 8_519_760 < 2**24 for any p < 2**16.
LAZY_K = 128

LIMB = 256  # limb base

# Contraction-depth bound for the native-integer (uint32 accumulator)
# matmul path.  Raw per-chunk limb dots are summed across chunks in
# uint32 *without* intermediate reductions; the binding constraint is
# the summed cross-limb dot: each CHUNK_K-deep chunk contributes at
# most 2 * 256 * 255**2 = 33_292_800, and 129 chunks stay under 2**32
# (129 * 33_292_800 = 4_294_771_200) while 130 would wrap.  The
# same-depth hi/lo dots are a factor ~4 below their bound.
INT32_ACC_CHUNKS = 129
INT32_ACC_K = INT32_ACC_CHUNKS * CHUNK_K  # 33024


@dataclasses.dataclass(frozen=True)
class Field:
    """A prime field GF(p)."""

    p: int = P_DEFAULT

    def __post_init__(self):
        if self.p < 3:
            raise ValueError("p must be an odd prime")

    @property
    def elem_bytes(self) -> int:
        """Wire width of one field element (bytes-level Trace views)."""
        return (self.p.bit_length() + 7) // 8

    # ------------------------------------------------------------------
    # host (numpy int64) reference arithmetic
    # ------------------------------------------------------------------
    def asarray(self, x) -> np.ndarray:
        return np.asarray(x, dtype=np.int64) % self.p

    def add(self, a, b):
        return (np.asarray(a, np.int64) + np.asarray(b, np.int64)) % self.p

    def sub(self, a, b):
        return (np.asarray(a, np.int64) - np.asarray(b, np.int64)) % self.p

    def mul(self, a, b):
        return (np.asarray(a, np.int64) * np.asarray(b, np.int64)) % self.p

    def matmul(self, a, b) -> np.ndarray:
        """Exact (mod p) matmul on the host; chunked to avoid int64 overflow.

        ``a`` is [..., K] and ``b`` must be 2D [K, N]: the chunk loop
        slices ``b``'s leading axis as K, so a batched ``b`` would be
        contracted wrongly (loop over the batch instead).
        """
        a = self.asarray(a)
        b = self.asarray(b)
        if b.ndim != 2:
            raise ValueError(
                f"Field.matmul needs a 2D right-hand side [K, N], got shape "
                f"{b.shape}; loop over a batched operand instead"
            )
        k = a.shape[-1]
        # (p-1)^2 * chunk must stay < 2**63; p < 2**31 -> chunk >= 2 always ok.
        chunk = max(1, int((2**62) // (int(self.p - 1) ** 2)))
        out = np.zeros(a.shape[:-1] + b.shape[1:], dtype=np.int64)
        for s in range(0, k, chunk):
            out = (out + a[..., s : s + chunk] @ b[s : s + chunk]) % self.p
        return out

    def pow(self, a, e: int):
        a = int(a) % self.p
        return pow(a, int(e), self.p)

    def inv(self, a):
        a = int(a) % self.p
        if a == 0:
            raise ZeroDivisionError("0 has no inverse in GF(p)")
        return pow(a, self.p - 2, self.p)

    def neg(self, a):
        return (-np.asarray(a, np.int64)) % self.p

    def random(self, rng: np.random.Generator, shape) -> np.ndarray:
        return rng.integers(0, self.p, size=shape, dtype=np.int64)

    # ------------------------------------------------------------------
    # structured host helpers
    # ------------------------------------------------------------------
    def _pow_table(self, base: np.ndarray, exps: np.ndarray) -> np.ndarray:
        """T[n, j] = base[n] ** exps[j] (mod p) by column-wise repeated
        squaring: one vectorized squaring pass per exponent bit instead
        of a scalar ``pow`` per element.  exps must be non-negative."""
        out = np.ones((base.size, exps.size), np.int64)
        sq = base % self.p
        e = exps.astype(np.int64).copy()
        while e.any():
            mask = (e & 1).astype(bool)
            if mask.any():
                # (p-1)**2 < 2**62 for p < 2**31: int64-exact.
                out[:, mask] = (out[:, mask] * sq[:, None]) % self.p
            e >>= 1
            sq = (sq * sq) % self.p
        return out

    def vandermonde(self, points, powers) -> np.ndarray:
        """V[n, j] = points[n] ** powers[j]  (mod p)."""
        points = np.atleast_1d(np.asarray(points, np.int64)) % self.p
        exps = np.asarray([int(u) for u in powers], np.int64)
        out = np.ones((points.size, exps.size), np.int64)
        if exps.size == 0:
            return out
        pos = exps >= 0
        if pos.any():
            out[:, pos] = self._pow_table(points, exps[pos])
        if (~pos).any():
            if np.any(points == 0):
                raise ZeroDivisionError("0 has no inverse in GF(p)")
            inv_pts = self._pow_table(points, np.array([self.p - 2]))[:, 0]
            out[:, ~pos] = self._pow_table(inv_pts, -exps[~pos])
        return out

    def solve(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Solve a @ x = b (mod p) by Gauss-Jordan elimination."""
        a = self.asarray(a).copy()
        b = self.asarray(b).copy()
        n = a.shape[0]
        if a.shape[1] != n:
            raise ValueError("square system required")
        if b.ndim == 1:
            b = b[:, None]
            squeeze = True
        else:
            squeeze = False
        for col in range(n):
            piv = None
            for r in range(col, n):
                if a[r, col] != 0:
                    piv = r
                    break
            if piv is None:
                raise ZeroDivisionError("singular matrix mod p")
            if piv != col:
                a[[col, piv]] = a[[piv, col]]
                b[[col, piv]] = b[[piv, col]]
            inv = self.inv(a[col, col])
            a[col] = (a[col] * inv) % self.p
            b[col] = (b[col] * inv) % self.p
            for r in range(n):
                if r != col and a[r, col] != 0:
                    f = a[r, col]
                    a[r] = (a[r] - f * a[col]) % self.p
                    b[r] = (b[r] - f * b[col]) % self.p
        x = b % self.p
        return x[:, 0] if squeeze else x

    def inv_matrix(self, a: np.ndarray) -> np.ndarray:
        return self.solve(a, np.eye(a.shape[0], dtype=np.int64))

    def solve_any(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """One solution of a @ x = b (mod p) for a general [m, n] system.

        Unlike :meth:`solve`, ``a`` may be rectangular or rank-deficient:
        Gauss-Jordan runs column by column, free variables are pinned to
        zero, and a zero row of the reduced ``a`` with a nonzero reduced
        ``b`` raises ``ValueError`` (inconsistent system).  This is what
        the Berlekamp-Welch decoder needs — its key system is
        deliberately overdetermined (``thr + 2e`` unknowns, more
        equations) and singular whenever fewer than ``e`` errors actually
        occurred, where *any* particular solution is a valid decode.
        """
        a = self.asarray(a).copy()
        b = self.asarray(b).copy()
        m, n = a.shape
        if b.ndim == 1:
            b = b[:, None]
            squeeze = True
        else:
            squeeze = False
        if b.shape[0] != m:
            raise ValueError(f"rhs has {b.shape[0]} rows, lhs has {m}")
        pivots = []
        row = 0
        for col in range(n):
            if row >= m:
                break
            piv = None
            for r in range(row, m):
                if a[r, col] != 0:
                    piv = r
                    break
            if piv is None:
                continue  # free column
            if piv != row:
                a[[row, piv]] = a[[piv, row]]
                b[[row, piv]] = b[[piv, row]]
            inv = self.inv(a[row, col])
            a[row] = (a[row] * inv) % self.p
            b[row] = (b[row] * inv) % self.p
            for r in range(m):
                if r != row and a[r, col] != 0:
                    f = a[r, col]
                    a[r] = (a[r] - f * a[row]) % self.p
                    b[r] = (b[r] - f * b[row]) % self.p
            pivots.append(col)
            row += 1
        if row < m and np.any(b[row:] != 0):
            raise ValueError("inconsistent linear system mod p")
        x = np.zeros((n, b.shape[1]), np.int64)
        if pivots:
            x[np.asarray(pivots)] = b[: len(pivots)]
        return x[:, 0] if squeeze else x

    def poly_divmod(
        self, num: np.ndarray, den: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Polynomial division mod p on ascending coefficient vectors.

        Returns (quotient, remainder) with ``num = quotient * den +
        remainder`` and ``deg(remainder) < deg(den)``.  ``den`` need not
        be monic (its leading coefficient is inverted once).
        """
        num = self.asarray(num).copy()
        den = self.asarray(den)
        d = int(den.size) - 1
        while d > 0 and den[d] == 0:
            d -= 1
        if den[d] == 0:
            raise ZeroDivisionError("division by the zero polynomial")
        lead_inv = self.inv(den[d])
        n = int(num.size) - 1
        if n < d:
            return np.zeros(1, np.int64), num
        quo = np.zeros(n - d + 1, np.int64)
        for k in range(n - d, -1, -1):
            c = (num[k + d] * lead_inv) % self.p
            if c:
                quo[k] = c
                num[k : k + d + 1] = (num[k : k + d + 1] - c * den[: d + 1]) % self.p
        rem = num[:d] if d > 0 else np.zeros(1, np.int64)
        return quo, rem

    def poly_eval(self, coeffs: np.ndarray, xs: np.ndarray) -> np.ndarray:
        """Evaluate an ascending-coefficient polynomial at points xs
        (Horner, vectorized over the points)."""
        coeffs = self.asarray(coeffs)
        xs = self.asarray(xs)
        out = np.zeros_like(xs)
        for c in coeffs[::-1]:
            out = (out * xs + c) % self.p
        return out

    # ------------------------------------------------------------------
    # fixed-point quantisation (real <-> field)
    # ------------------------------------------------------------------
    def encode(self, x: np.ndarray, scale: int) -> np.ndarray:
        """Quantise reals into the field with a centered lift."""
        q = np.rint(np.asarray(x, np.float64) * scale).astype(np.int64)
        half = (self.p - 1) // 2
        if np.any(np.abs(q) > half):
            raise OverflowError("value out of field range at this scale")
        return q % self.p

    def decode(self, x: np.ndarray, scale: int) -> np.ndarray:
        """Centered lift back to signed reals."""
        x = self.asarray(x)
        half = (self.p - 1) // 2
        signed = np.where(x > half, x - self.p, x)
        return signed.astype(np.float64) / scale


# ----------------------------------------------------------------------
# jnp device path: exact f32 limb arithmetic (p < 2**16)
# ----------------------------------------------------------------------
def _check_limb_prime(p: int):
    if p >= 1 << 16:
        raise ValueError("f32 limb path requires p < 2**16")


def _limb_split(x: jnp.ndarray):
    hi = jnp.floor(x / LIMB)
    return hi, x - hi * LIMB


def _limb_dot_u32(dot, a_hi, a_lo, b_hi, b_lo, p: int, acc=None) -> jnp.ndarray:
    """One <=256-deep limb-decomposed contraction, reduced mod p.

    The four limb dots run on the matrix unit in f32 (each accumulates
    <= 256 products of 8-bit limbs, staying below 2**24 — exact in f32);
    the f32 -> uint32 handoff is therefore exact, and all recombination
    happens lazily in uint32 where the headroom is 2**32 instead of
    2**24.  Per-dot reductions disappear entirely: the cross dots are
    summed raw (< 2**25), the low-limb dot and the running accumulator
    fold into the final reduction, and the recombination constants are
    applied with a *static* overflow check that pre-reduces only when
    bound * c could actually exceed uint32 range.

    ``dot`` is any f32 contraction of depth <= CHUNK_K (a closure over
    ``lax.dot_general`` dimension numbers, so the same code serves 2D,
    batched, and one-sided-constant operand layouts).  ``acc`` is an
    optional uint32 accumulator in [0, p).  Returns uint32 in [0, p).
    """
    pu = jnp.uint32(p)
    f_hihi = int((LIMB * LIMB) % p)  # 2**16 mod p
    f_mid = int(LIMB % p)  # 2**8 mod p
    hh = dot(a_hi, b_hi).astype(jnp.uint32)  # < 2**24
    mid = dot(a_hi, b_lo).astype(jnp.uint32) + dot(a_lo, b_hi).astype(jnp.uint32)
    ll = dot(a_lo, b_lo).astype(jnp.uint32)  # < 2**24

    def mulc(x, c, xmax):
        # x * c mod p for x <= xmax; pre-reduce x only when the raw
        # product could overflow uint32 (static check — c, xmax are
        # Python ints).
        if c == 0:
            return jnp.zeros_like(x)
        if xmax * c >= 1 << 32:
            x = x % pu
        return (x * jnp.uint32(c)) % pu

    tile = mulc(hh, f_hihi, (1 << 24) - 1) + mulc(mid, f_mid, (1 << 25) - 1) + ll
    # tile < 2*p + 2**24 < 2**25; adding acc (< p) stays far below 2**32.
    if acc is not None:
        tile = tile + acc
    return tile % pu


def _contract_dnums(a_ndim: int, b_ndim: int, n_batch: int):
    """dot_general dimension numbers for [..., M, K] @ [..., K, N].

    Returns (contract_dims, batch_dims, a_kaxis, b_kaxis, move_m) where
    ``move_m`` flags the 2D-LHS/batched-RHS layout whose raw output is
    [M, *batch, N] and needs the M axis moved back before returning.
    """
    if b_ndim == 2:
        # [..., M, K] @ [K, N] -> [..., M, N]
        return ((a_ndim - 1,), (0,)), ((), ()), a_ndim - 1, 0, False
    if a_ndim == 2:
        # [M, K] @ [*batch, K, N] -> [M, *batch, N]: the constant LHS is
        # contracted (and limb-split) ONCE instead of being broadcast
        # per batch element.
        return ((1,), (b_ndim - 2,)), ((), ()), 1, b_ndim - 2, True
    batch = tuple(range(n_batch))
    return (
        ((n_batch + 1,), (n_batch,)),
        (batch, batch),
        n_batch + 1,
        n_batch,
        False,
    )


@partial(jax.jit, static_argnames=("p",))
def mod_matmul_f32(a: jnp.ndarray, b: jnp.ndarray, p: int = P_DEFAULT) -> jnp.ndarray:
    """Exact GF(p) matmul via 8-bit limb decomposition in f32.

    a: [..., M, K] @ b: [..., K, N] (int32 in [0, p)) with numpy-style
    broadcasting over the leading batch dims; either side may be a 2D
    constant matrix, which is contracted via ``dot_general`` without
    materializing per-batch copies (and limb-split exactly once).
    Returns int32 [..., M, N] = a @ b mod p.

    Contractions of depth <= CHUNK_K take a no-padding single-dot fast
    path (any accumulation <= 256 deep is exact in f32); deeper ones are
    zero-padded to a CHUNK_K multiple and reduced once per chunk under a
    scan.  The protocol's per-worker block products are typically far
    shallower than CHUNK_K, where padding would waste ~CHUNK_K/K of the
    FLOPs.
    """
    _check_limb_prime(p)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim < 2 or b.ndim < 2:
        raise ValueError(f"operands must be at least 2D, got {a.shape} {b.shape}")
    if a.ndim > 2 and b.ndim > 2:
        batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
        a = jnp.broadcast_to(a, batch + a.shape[-2:])
        b = jnp.broadcast_to(b, batch + b.shape[-2:])
        n_batch = len(batch)
    else:
        n_batch = 0
    contract, batch_dims, ka, kb, move_m = _contract_dnums(a.ndim, b.ndim, n_batch)
    dnums = (contract, batch_dims)

    def dot(x, y):
        return jax.lax.dot_general(x, y, dnums, preferred_element_type=jnp.float32)

    def finish(out_u32):
        out = out_u32.astype(jnp.int32)
        return jnp.moveaxis(out, 0, -2) if move_m else out

    k = a.shape[ka]
    if k <= CHUNK_K:
        a_hi, a_lo = _limb_split(a.astype(jnp.float32))
        b_hi, b_lo = _limb_split(b.astype(jnp.float32))
        return finish(_limb_dot_u32(dot, a_hi, a_lo, b_hi, b_lo, p))

    pad = (-k) % CHUNK_K
    if pad:
        wa = [(0, 0)] * a.ndim
        wa[ka] = (0, pad)
        wb = [(0, 0)] * b.ndim
        wb[kb] = (0, pad)
        a = jnp.pad(a, wa)
        b = jnp.pad(b, wb)
        k += pad
    nchunk = k // CHUNK_K

    a_hi, a_lo = _limb_split(a.astype(jnp.float32))
    b_hi, b_lo = _limb_split(b.astype(jnp.float32))

    def chunked(x, axis):
        # Split the contraction axis into (nchunk, CHUNK_K) and move the
        # chunk count to the front as the scan axis; the CHUNK_K slice
        # stays at ``axis`` so the same dnums apply inside the scan.
        x = x.reshape(x.shape[:axis] + (nchunk, CHUNK_K) + x.shape[axis + 1 :])
        return jnp.moveaxis(x, axis, 0)

    xs = (
        chunked(a_hi, ka),
        chunked(a_lo, ka),
        chunked(b_hi, kb),
        chunked(b_lo, kb),
    )
    acc0 = jnp.zeros(jax.eval_shape(dot, a_hi, b_hi).shape, jnp.uint32)

    def body(acc, limbs):
        ah, al, bh, bl = limbs
        return _limb_dot_u32(dot, ah, al, bh, bl, p, acc=acc), None

    acc, _ = jax.lax.scan(body, acc0, xs)
    return finish(acc)


# ----------------------------------------------------------------------
# native-integer path: Barrett reduction in pure uint32
# ----------------------------------------------------------------------
def barrett_reduce_u32(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """x mod p for uint32 x (any value < 2**32), without 64-bit arithmetic.

    Barrett with mu = floor(2**32 / p): the quotient estimate
    q = floor(x * mu / 2**32) satisfies floor(x/p) - q in {0, 1}, so one
    conditional subtract finishes the reduction.  The 64-bit product
    x * mu is never formed — its high word is assembled from four 16-bit
    limb products, each of which fits uint32:

        x*mu = 2**32*xh*mh + 2**16*(xh*ml + xl*mh) + xl*ml
        q    = xh*mh + (u >> 16) + (v >> 16)       (exact; see below)

    with u = xh*ml + (xl*ml >> 16) and v = xl*mh + (u & 0xFFFF) — the
    carries of the middle column folded in 16 bits at a time.  Every op
    lowers to uint32 vector mul/shift/add, so the same code runs in jnp,
    inside Pallas kernel bodies, and on integer-capable accelerators.
    Requires 1 < p < 2**16 (so that q * p also stays in uint32).
    """
    if not 1 < p < (1 << 16):
        raise ValueError(f"barrett_reduce_u32 requires 1 < p < 2**16, got {p}")
    mu = (1 << 32) // p
    mh = jnp.uint32(mu >> 16)
    ml = jnp.uint32(mu & 0xFFFF)
    x = x.astype(jnp.uint32)
    xh = x >> jnp.uint32(16)
    xl = x & jnp.uint32(0xFFFF)
    t = xl * ml
    u = xh * ml + (t >> jnp.uint32(16))
    v = xl * mh + (u & jnp.uint32(0xFFFF))
    q = xh * mh + (u >> jnp.uint32(16)) + (v >> jnp.uint32(16))
    r = x - q * jnp.uint32(p)
    return jnp.where(r >= jnp.uint32(p), r - jnp.uint32(p), r)


def _barrett_recombine(hh, mid, ll, p: int) -> jnp.ndarray:
    """Recombine raw uint32 limb-dot accumulators into [0, p).

    hh/mid/ll are the hi*hi / cross / lo*lo contraction sums (uint32,
    any value — callers enforce the no-wrap depth bounds).  Each is
    Barrett-reduced before the 16-bit recombination constant is applied,
    so every intermediate stays below p * 2**16 < 2**32.
    """
    f_hihi = (1 << 16) % p
    f_mid = LIMB % p

    def mulc(x, c):
        if c == 0:
            return jnp.zeros_like(x)
        return barrett_reduce_u32(barrett_reduce_u32(x, p) * jnp.uint32(c), p)

    out = mulc(hh, f_hihi) + mulc(mid, f_mid) + barrett_reduce_u32(ll, p)
    return barrett_reduce_u32(out, p)  # sum of three residues < 3p


@partial(jax.jit, static_argnames=("p",))
def mod_matmul_int32(a: jnp.ndarray, b: jnp.ndarray, p: int = P_DEFAULT) -> jnp.ndarray:
    """Exact GF(p) matmul on the native-integer tier (uint32 + Barrett).

    Same operand contract as :func:`mod_matmul_f32` (batched / one-sided
    2D layouts, int32 in [0, p)).  The limb dots still run in f32 (on
    CPU/TPU the f32 GEMM is the fast contraction engine), but everything
    *between* chunks moves to uint32:

    * the contraction is split into CHUNK_K-deep chunks batched into ONE
      set of dots (the chunk axis rides ``vmap`` as a batch dimension —
      no ``scan``, no per-chunk reduction),
    * the raw per-chunk partial sums accumulate across chunks in uint32,
      where the headroom is 2**32 instead of f32's 2**24,
    * a single Barrett recombination at the end replaces the per-chunk
      ``%`` of the f32limb path.

    Deep contractions therefore pay O(1) reductions instead of O(K/256),
    which is where this path overtakes ``mod_matmul_f32`` (see
    ``BENCH_protocol.json`` / ``docs/kernel_design.md``).  The no-wrap
    bound is loud, not silent: padded depth beyond ``INT32_ACC_K``
    (= 33024) raises instead of wrapping the accumulator.
    """
    _check_limb_prime(p)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim < 2 or b.ndim < 2:
        raise ValueError(f"operands must be at least 2D, got {a.shape} {b.shape}")
    if a.ndim > 2 and b.ndim > 2:
        batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
        a = jnp.broadcast_to(a, batch + a.shape[-2:])
        b = jnp.broadcast_to(b, batch + b.shape[-2:])
        n_batch = len(batch)
    else:
        n_batch = 0
    contract, batch_dims, ka, kb, move_m = _contract_dnums(a.ndim, b.ndim, n_batch)
    dnums = (contract, batch_dims)

    def dot(x, y):
        return jax.lax.dot_general(x, y, dnums, preferred_element_type=jnp.float32)

    def finish(out_u32):
        out = out_u32.astype(jnp.int32)
        return jnp.moveaxis(out, 0, -2) if move_m else out

    k = a.shape[ka]
    kpad = -(-k // CHUNK_K) * CHUNK_K
    if kpad > INT32_ACC_K:
        raise ValueError(
            f"int32 backend: padded contraction depth {kpad} exceeds the "
            f"uint32 accumulator bound INT32_ACC_K={INT32_ACC_K} "
            f"({INT32_ACC_CHUNKS} raw chunks; deeper sums would wrap "
            f"silently) — split the contraction or use the f32limb backend"
        )
    if k <= CHUNK_K:
        a_hi, a_lo = _limb_split(a.astype(jnp.float32))
        b_hi, b_lo = _limb_split(b.astype(jnp.float32))
        hh = dot(a_hi, b_hi).astype(jnp.uint32)
        mid = dot(a_hi, b_lo).astype(jnp.uint32) + dot(a_lo, b_hi).astype(jnp.uint32)
        ll = dot(a_lo, b_lo).astype(jnp.uint32)
        return finish(_barrett_recombine(hh, mid, ll, p))

    pad = kpad - k
    if pad:
        wa = [(0, 0)] * a.ndim
        wa[ka] = (0, pad)
        wb = [(0, 0)] * b.ndim
        wb[kb] = (0, pad)
        a = jnp.pad(a, wa)
        b = jnp.pad(b, wb)
    nchunk = kpad // CHUNK_K

    a_hi, a_lo = _limb_split(a.astype(jnp.float32))
    b_hi, b_lo = _limb_split(b.astype(jnp.float32))

    def chunked(x, axis):
        # Split the contraction axis into (nchunk, CHUNK_K) with the
        # chunk count leading — the vmapped dot below turns it into one
        # extra *batch* dimension of a single dot_general (the original
        # dnums still apply to each CHUNK_K slice).
        x = x.reshape(x.shape[:axis] + (nchunk, CHUNK_K) + x.shape[axis + 1 :])
        return jnp.moveaxis(x, axis, 0)

    dot_chunks = jax.vmap(dot)
    hh = jnp.sum(dot_chunks(chunked(a_hi, ka), chunked(b_hi, kb)).astype(jnp.uint32), axis=0)
    mid = jnp.sum(
        dot_chunks(chunked(a_hi, ka), chunked(b_lo, kb)).astype(jnp.uint32)
        + dot_chunks(chunked(a_lo, ka), chunked(b_hi, kb)).astype(jnp.uint32),
        axis=0,
    )
    ll = jnp.sum(dot_chunks(chunked(a_lo, ka), chunked(b_lo, kb)).astype(jnp.uint32), axis=0)
    return finish(_barrett_recombine(hh, mid, ll, p))


# ----------------------------------------------------------------------
# counter-based PRNG: threefry2x32 usable inside Pallas kernel bodies
# ----------------------------------------------------------------------
_THREEFRY_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_THREEFRY_PARITY = 0x1BD11BDA


def _rotl32(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0: jnp.ndarray, c1: jnp.ndarray):
    """Threefry-2x32, 20 rounds (the Random123 / JAX PRNG block cipher).

    Implemented from the spec in plain uint32 shifts/adds/xors so the
    SAME function body runs at the jnp level *and* inside Pallas kernel
    tiles — which is what makes fused in-kernel mask generation
    bit-identical to the materialized :func:`field_mask` path.  The
    5 x 4 round structure injects the extended key (k0, k1,
    k0^k1^parity) after every group of four rounds, per the Skein key
    schedule.  Returns the two output words.
    """
    k0 = jnp.uint32(k0)
    k1 = jnp.uint32(k1)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_THREEFRY_PARITY))
    x0 = c0.astype(jnp.uint32) + ks[0]
    x1 = c1.astype(jnp.uint32) + ks[1]
    for g in range(1, 6):
        rots = _THREEFRY_ROT[:4] if g % 2 else _THREEFRY_ROT[4:]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl32(x1, r) ^ x0
        x0 = x0 + ks[g % 3]
        x1 = x1 + ks[(g + 1) % 3] + jnp.uint32(g)
    return x0, x1


@partial(jax.jit, static_argnames=("shape", "p"))
def field_mask(key: jnp.ndarray, shape: tuple, p: int = P_DEFAULT) -> jnp.ndarray:
    """Counter-based uniform GF(p) mask: the materialized reference of
    the fused in-kernel blinding stream.

    Element at row-major flat index i is
    ``threefry2x32(key, (i, 0))[0] mod p`` — a pure function of (key,
    position), so a Pallas tile can generate exactly its own slice from
    program ids without the array ever existing in memory, and this
    helper materializes the identical values for the portable backends
    and the bit-identity tests.  ``key`` is a (2,) uint32 word pair (a
    classic ``jax.random.PRNGKey`` works as-is).  The modulo-p bias
    (~p / 2**32) matches the repo-standard ``jax.random.randint`` draw.
    """
    _check_limb_prime(p)
    total = 1
    for d in shape:
        total *= int(d)
    if total >= 1 << 32:
        raise ValueError(
            f"field_mask counter space exhausted: prod{tuple(shape)} = "
            f"{total} >= 2**32 — counters would wrap and reuse mask values"
        )
    if total == 0:
        return jnp.zeros(shape, jnp.int32)
    key = jnp.asarray(key, jnp.uint32).reshape(-1)
    ctr = jax.lax.iota(jnp.uint32, total)
    x0, _ = threefry2x32(key[0], key[1], ctr, jnp.zeros_like(ctr))
    return barrett_reduce_u32(x0, p).astype(jnp.int32).reshape(shape)


def crt_combine(residues, primes) -> np.ndarray:
    """Chinese-Remainder combination of per-prime residue arrays.

    Garner's algorithm on the host: int64-exact for
    ``prod(primes) < 2**62`` (checked loudly).  Returns int64 in
    [0, prod(primes)).
    """
    primes = [int(q) for q in primes]
    if len(residues) != len(primes):
        raise ValueError("one residue array per prime required")
    prod = 1
    for q in primes:
        prod *= q
    if prod >= 1 << 62:
        raise ValueError(
            f"prod(primes) = {prod} >= 2**62: CRT combination would "
            f"overflow int64 — use fewer/smaller primes"
        )
    x = np.asarray(residues[0], np.int64) % primes[0]
    m = primes[0]
    for r, q in zip(residues[1:], primes[1:]):
        inv = pow(m % q, -1, q)  # raises if the moduli are not coprime
        diff = (np.asarray(r, np.int64) - x) % q
        x = x + (diff * inv % q) * m
        m *= q
    return x


@partial(jax.jit, static_argnames=("p",))
def mod_mul(a: jnp.ndarray, b: jnp.ndarray, p: int = P_DEFAULT) -> jnp.ndarray:
    """Elementwise a*b mod p. Products of 16-bit values fit exactly in uint32."""
    _check_limb_prime(p)
    prod = a.astype(jnp.uint32) * b.astype(jnp.uint32)
    return (prod % jnp.uint32(p)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("p",))
def mod_add(a: jnp.ndarray, b: jnp.ndarray, p: int = P_DEFAULT) -> jnp.ndarray:
    s = a.astype(jnp.uint32) + b.astype(jnp.uint32)
    return (s % jnp.uint32(p)).astype(jnp.int32)


def random_field_device(key, shape, p: int = P_DEFAULT) -> jnp.ndarray:
    """Uniform GF(p) elements drawn on-device with the JAX PRNG.

    Device-resident counterpart of ``Field.random`` (numpy) — used by the
    batched protocol engine so secret/blinding terms never touch the
    host.  Returns int32 in [0, p); traceable under jit.
    """
    return jax.random.randint(key, shape, 0, p, dtype=jnp.int32)


def powers_matrix(points: np.ndarray, powers, p: int = P_DEFAULT) -> np.ndarray:
    """Host-side Vandermonde with arbitrary power support; int64 -> int32-safe."""
    f = Field(p)
    return f.vandermonde(points, powers).astype(np.int64)
