"""Pallas TPU kernel: exact GF(p) matrix multiplication, p < 2**16.

TPU adaptation of the paper's worker hot loop H(alpha_n) =
F_A(alpha_n) * F_B(alpha_n) over a prime field.  GPU implementations of
field matmul use 32/64-bit integer MACs; the TPU MXU is a *floating
point* systolic array, so we re-think the arithmetic instead of porting:

* field elements (< 2**16) are split into two 8-bit limbs,
* limb products (< 2**16) are accumulated on the MXU in f32 — any
  partial sum of <= 256 such products stays below 2**24, the largest
  integer f32 represents exactly,
* the inner (contraction) dimension is therefore tiled at ``bk <= 256``
  and a Barrett-free reduction (x - floor(x/p)*p, exact in f32 for
  x < 2**24) runs once per tile,
* at ``bk <= LAZY_K`` (128) reductions are *lazy*: the two cross-limb
  dots are summed raw before one reduction (2*128*255**2 < 2**24), and
  the raw low-limb dot plus the running accumulator fold into the
  final reduction (3*(p-1) + 128*255**2 < 2**24),
* limb recombination multiplies by (2**16 mod p) and (2**8 mod p) so
  every intermediate stays < 2**24.

Batching: the protocol's worker/batch axis is a *grid* axis — one
``pallas_call`` computes ``[B, M, K] @ [B, K, N]`` with grid
``(B, M/bm, N/bn, K/bk)`` instead of a vmap of padded 2D launches.  An
unbatched operand (e.g. a constant mixing or decode matrix against a
batched stack) keeps its 2D shape and is indexed batch-invariantly, so
it is never broadcast or copied per batch element.

Each tile is either the whole array dim (no alignment needed, nothing
padded) or aligned: M tiles sublane multiples of 8, N/K tiles lane
multiples of 128 — ``ops.pick_tiles`` chooses them from the actual
operand shape.  The accumulator lives in the output VMEM block; the K
grid axis is ``arbitrary`` (sequential) so accumulation is race-free.

Two arithmetic variants share the launch/grid machinery
(``variant="f32" | "int32"``):

* **f32** — the limb schedule above, bound by the 2**24 f32 ceiling
  (``bk <= 256``).
* **int32** — integer limb split (``>> 8``, ``& 255``), limb dots
  accumulated with ``preferred_element_type=int32`` and recombined per
  K step through a pure-uint32 Barrett reduction
  (``gf.barrett_reduce_u32``); the accumulator bound widens to 2**31
  (``bk <= INT32_KERNEL_MAX_BK``), so deep contractions need no
  K-tiling at all.  Interpret mode only: the TPU v5e MXU has no
  int32 x int32 matmul, so a compile raises ``NotImplementedError``.

``modmatmul_masked_pallas`` additionally fuses the protocol's blinding
masks into the tile: a counter-based threefry2x32 stream (matching
``gf.field_mask`` bit-for-bit) is generated from the tile's grid
position and added to the output block on the last K step — the mask
is never materialized in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.gf import (
    LAZY_K,
    P_DEFAULT,
    _barrett_recombine,
    barrett_reduce_u32,
    threefry2x32,
)

LIMB = 256.0

# Per-tile contraction bound for the native-int32 kernel: each raw
# signed-int32 limb dot accumulates bk products of 8-bit limbs, so
# bk * 255**2 must stay below 2**31.
INT32_KERNEL_MAX_BK = (1 << 31) // (255 * 255)  # 33025 -> bk <= 33024 padded

# The int32 variant runs only in interpret mode: Mosaic refuses its
# limb dots on TPU v5e ("Bad lhs/rhs type: 'vector<8x128xi32>'") because
# that MXU has no int32 x int32 matmul.
INT32_REFUSAL = (
    "the pallas_int32 kernel does not compile for TPU: Mosaic has no "
    "int32 x int32 matmul on the MXU; use backend='pallas' (f32 limbs) "
    "on the chip, or interpret=True to validate the int32 arithmetic"
)


def _modf32(x, p):
    # floor(x/p) in f32 can be off by one ulp; correct both directions.
    r = x - jnp.floor(x / p) * p
    r = jnp.where(r < 0, r + p, r)
    return jnp.where(r >= p, r - p, r)


def _mulmod_const(x, c: int, p: int):
    """x * c mod p with x in [0, p) f32, exact for any p < 2**16: split x
    into 8-bit limbs so each product stays below 2**24."""
    pf = float(p)
    c_hi = float((c * 256) % p)
    c_lo = float(c % p)
    x_hi = jnp.floor(x / LIMB)
    x_lo = x - x_hi * LIMB
    return _modf32(_modf32(x_hi * c_hi, pf) + _modf32(x_lo * c_lo, pf), pf)


def _modmatmul_kernel(a_ref, b_ref, o_ref, *, p: int, lazy: bool, k_axis: int):
    """One (bm, bn) output tile; K-axis accumulation across grid axis
    ``k_axis``.  Batched refs carry a leading unit block axis that is
    dropped before the MXU dots."""
    pf = float(p)
    f_hihi = (1 << 16) % p  # 2**16 mod p
    f_mid = (1 << 8) % p  # 2**8 mod p

    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    af = a_ref[...]
    bf = b_ref[...]
    if af.ndim == 3:  # batched block [1, bm, bk]
        af = af[0]
    if bf.ndim == 3:
        bf = bf[0]
    af = af.astype(jnp.float32)
    bf = bf.astype(jnp.float32)
    a_hi = jnp.floor(af / LIMB)
    a_lo = af - a_hi * LIMB
    b_hi = jnp.floor(bf / LIMB)
    b_lo = bf - b_hi * LIMB

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    # Four MXU matmuls per tile; each single dot accumulates <= bk<=256
    # products of 8-bit limbs -> partial sums < 2**24, exact in f32.
    hh = _modf32(dot(a_hi, b_hi), pf)
    if lazy:
        # bk <= 128: the raw cross-dot sum stays < 2**24, so one
        # reduction replaces three; the raw low-limb dot and the
        # accumulator fold into the final reduction below.
        mid = _modf32(dot(a_hi, b_lo) + dot(a_lo, b_hi), pf)
        ll = dot(a_lo, b_lo)
    else:
        # bk up to 256: the raw cross sum can reach ~2**25 and lose the
        # low bit — reduce each dot separately.
        mid = _modf32(
            _modf32(dot(a_hi, b_lo), pf) + _modf32(dot(a_lo, b_hi), pf), pf
        )
        ll = _modf32(dot(a_lo, b_lo), pf)

    tile = _mulmod_const(hh, f_hihi, p) + _mulmod_const(mid, f_mid, p) + ll
    if not lazy:
        tile = _modf32(tile, pf)
    acc = o_ref[...].astype(jnp.float32)
    # lazy: acc + tile < 3*(p-1) + 128*255**2 < 2**24 — still exact.
    o_ref[...] = _modf32(acc + tile.reshape(o_ref.shape), pf).astype(jnp.int32)


def _modmatmul_int32_kernel(a_ref, b_ref, o_ref, *, p: int, k_axis: int):
    """Native-integer tile: int32 limb dots + uint32 Barrett recombination.

    The limb split is integer (``>> 8`` / ``& 255``), the four dots
    accumulate in *signed int32* (exact while bk * 255**2 < 2**31 —
    enforced at launch), and the recombination runs the shared uint32
    Barrett helpers from ``core.gf``.  No f32 anywhere, so there is no
    2**24 exactness ceiling and no 256-deep chunk reductions: one tile
    covers up to ~33k contraction depth with a single recombination.
    Cross-step accumulation needs only a conditional subtract (both
    addends already sit in [0, p)).
    """
    pu = jnp.uint32(p)

    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ai = a_ref[...]
    bi = b_ref[...]
    if ai.ndim == 3:  # batched block [1, bm, bk]
        ai = ai[0]
    if bi.ndim == 3:
        bi = bi[0]
    a_hi = ai >> 8
    a_lo = ai & 255
    b_hi = bi >> 8
    b_lo = bi & 255

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.int32)
    hh = dot(a_hi, b_hi).astype(jnp.uint32)
    # the two cross dots are each < 2**31 before the cast; their uint32
    # sum has a full 2**32 of headroom
    mid = dot(a_hi, b_lo).astype(jnp.uint32) + dot(a_lo, b_hi).astype(jnp.uint32)
    ll = dot(a_lo, b_lo).astype(jnp.uint32)
    tile = _barrett_recombine(hh, mid, ll, p)

    s = o_ref[...].astype(jnp.uint32) + tile.reshape(o_ref.shape)
    o_ref[...] = jnp.where(s >= pu, s - pu, s).astype(jnp.int32)


def _apply_fused_mask(
    o_ref, v_ref, key_ref, *, p: int, z: int, ncols: int, bn: int,
    k_axis: int, nk: int, batched: bool,
):
    """Add ``v @ R`` to the finished output tile, generating R in-tile.

    R is the counter-based threefry stream of ``core.gf.field_mask`` for
    shape [batch, z, ncols]: element (bb, zi, col) has flat counter
    ``(bb*z + zi) * ncols + col``, so each tile derives exactly its own
    mask slice from program ids — the [batch, z, ncols] array is never
    materialized.  Runs only on the *last* K step, after the matmul
    accumulation for this tile has finished.  Columns past ``ncols``
    (N padding) generate garbage that the caller slices off; rows of
    ``v`` past the logical M are zero-padded by the caller.
    """
    pu = jnp.uint32(p)
    # program ids must be read OUTSIDE the pl.when body: inside the cond
    # branch the primitive survives into the jaxpr un-rewritten and has
    # no lowering off-kernel (breaks interpret mode on CPU).
    j = pl.program_id(2 if batched else 1)
    bbu = pl.program_id(0).astype(jnp.uint32) if batched else None

    @pl.when(pl.program_id(k_axis) == nk - 1)
    def _mask():
        k0 = key_ref[0, 0]
        k1 = key_ref[0, 1]
        cols = (
            j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        ).astype(jnp.uint32)
        v = v_ref[...].astype(jnp.uint32)  # [bm, z]
        acc = jnp.zeros((v.shape[0], bn), jnp.uint32)
        for zi in range(z):
            rowu = bbu * jnp.uint32(z) + jnp.uint32(zi) if batched else jnp.uint32(zi)
            ctr = rowu * jnp.uint32(ncols) + cols
            r0, _ = threefry2x32(k0, k1, ctr, jnp.zeros_like(ctr))
            r = barrett_reduce_u32(r0, p)  # [1, bn] mask row
            # v (< p) times r (< p) fits uint32; reduce per term so the
            # accumulator stays <= z*p (z < 2**16 keeps it wrap-free)
            acc = acc + barrett_reduce_u32(v[:, zi : zi + 1] * r, p)
        contrib = barrett_reduce_u32(acc, p)
        s = o_ref[...].astype(jnp.uint32) + contrib.reshape(o_ref.shape)
        o_ref[...] = jnp.where(s >= pu, s - pu, s).astype(jnp.int32)


def _grid_and_specs(a, b, bm: int, bn: int, bk: int):
    """Shared launch geometry: grid, operand/output BlockSpecs, and the
    K grid-axis index for the f32, int32, and fused-mask kernels."""
    a_batched = a.ndim == 3
    b_batched = b.ndim == 3
    m, k = a.shape[-2:]
    k2, n = b.shape[-2:]
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, (bm, bn, bk))
    batch = None
    if a_batched or b_batched:
        batch = a.shape[0] if a_batched else b.shape[0]
        if a_batched and b_batched:
            assert a.shape[0] == b.shape[0], (a.shape, b.shape)

    if batch is None:
        grid = (m // bm, n // bn, k // bk)
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
        o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        out_shape = (m, n)
        k_axis = 2
    else:
        grid = (batch, m // bm, n // bn, k // bk)
        if a_batched:
            a_spec = pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk))
        else:
            a_spec = pl.BlockSpec((bm, bk), lambda bb, i, j, kk: (i, kk))
        if b_batched:
            b_spec = pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j))
        else:
            b_spec = pl.BlockSpec((bk, bn), lambda bb, i, j, kk: (kk, j))
        o_spec = pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j))
        out_shape = (batch, m, n)
        k_axis = 3
    return grid, a_spec, b_spec, o_spec, out_shape, batch, k_axis


def _launch(kernel, grid, in_specs, o_spec, out_shape, interpret, operands):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=list(in_specs),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",)
        ),
        interpret=interpret,
    )(*operands)


def _base_kernel(variant: str, p: int, bk: int, k_axis: int, interpret: bool):
    """The unmasked tile body for a kernel variant ("f32" | "int32")."""
    if variant == "f32":
        if bk > 256:
            raise ValueError("bk must be <= 256 for exact f32 accumulation")
        return functools.partial(
            _modmatmul_kernel, p=p, lazy=bk <= LAZY_K, k_axis=k_axis
        )
    if variant != "int32":
        raise ValueError(f"unknown kernel variant {variant}")
    if not interpret:
        raise NotImplementedError(INT32_REFUSAL)
    if bk * 255 * 255 >= 1 << 31:
        raise ValueError(
            f"int32 kernel: bk={bk} overflows the signed-int32 limb-dot "
            f"accumulator (needs bk * 255**2 < 2**31, i.e. bk <= "
            f"{INT32_KERNEL_MAX_BK - 1}) — it would wrap silently"
        )
    return functools.partial(_modmatmul_int32_kernel, p=p, k_axis=k_axis)


@functools.partial(
    jax.jit, static_argnames=("p", "bm", "bn", "bk", "interpret", "variant")
)
def modmatmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    p: int = P_DEFAULT,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
    variant: str = "f32",
) -> jnp.ndarray:
    """a [B, M, K] or [M, K]  @  b [B, K, N] or [K, N] mod p.

    int32 in [0, p); M/N/K must be multiples of the block sizes
    (ops.py handles padding and tile selection).  Always a *single*
    ``pallas_call``: a batched operand puts B on the leading grid axis;
    a 2D operand is shared across that axis via its index map (no
    broadcast copies).  2D @ 2D keeps the classic 3-axis grid.

    ``variant`` selects the tile arithmetic: ``"f32"`` is the limb-dot
    MXU kernel (bk <= 256), ``"int32"`` the native-integer tier
    (integer limb dots + uint32 Barrett; bk bounded only by the int32
    accumulator, so deep contractions fit in one tile).
    """
    if p >= 1 << 16:
        raise ValueError("kernel requires p < 2**16")
    grid, a_spec, b_spec, o_spec, out_shape, _, k_axis = _grid_and_specs(
        a, b, bm, bn, bk
    )
    kernel = _base_kernel(variant, p, bk, k_axis, interpret)
    return _launch(kernel, grid, [a_spec, b_spec], o_spec, out_shape, interpret, (a, b))


def modmatmul_int32_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    p: int = P_DEFAULT,
    bm: int = 128,
    bn: int = 128,
    bk: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Convenience alias: the native-int32 variant of the Pallas kernel."""
    return modmatmul_pallas(
        a, b, p=p, bm=bm, bn=bn, bk=bk, interpret=interpret, variant="int32"
    )


@functools.partial(
    jax.jit,
    static_argnames=("p", "ncols", "bm", "bn", "bk", "interpret", "variant"),
)
def modmatmul_masked_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    v: jnp.ndarray,
    key: jnp.ndarray,
    p: int = P_DEFAULT,
    ncols: int = 0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = False,
    variant: str = "f32",
) -> jnp.ndarray:
    """Fused blinding: ``a @ b + v @ R(key)  (mod p)`` in one kernel.

    ``v`` is a 2D [M, z] constant (the secret/blinding Vandermonde
    columns, zero-padded rows past the logical M) and R is the
    counter-based threefry mask of ``core.gf.field_mask`` for shape
    [batch, z, ncols] — generated *inside* the output tile on the last
    K step, never materialized.  ``ncols`` is the logical (pre-padding)
    N, which anchors the per-column counters; ``key`` is a (2,) uint32
    word pair.  Output matches
    ``mod_matmul(a, b) + v @ field_mask(key, (batch, z, ncols))``
    bit-exactly.
    """
    if p >= 1 << 16:
        raise ValueError("kernel requires p < 2**16")
    grid, a_spec, b_spec, o_spec, out_shape, batch, k_axis = _grid_and_specs(
        a, b, bm, bn, bk
    )
    z = v.shape[-1]
    nbatch = 1 if batch is None else batch
    if nbatch * z * ncols >= 1 << 32:
        raise ValueError(
            f"fused mask counter space exhausted: batch*z*ncols = "
            f"{nbatch * z * ncols} >= 2**32 — counters would wrap and "
            f"reuse mask values"
        )
    batched = batch is not None
    if batched:
        v_spec = pl.BlockSpec((bm, z), lambda bb, i, j, kk: (i, 0))
        key_spec = pl.BlockSpec((1, 2), lambda bb, i, j, kk: (0, 0))
    else:
        v_spec = pl.BlockSpec((bm, z), lambda i, j, kk: (i, 0))
        key_spec = pl.BlockSpec((1, 2), lambda i, j, kk: (0, 0))
    base = _base_kernel(variant, p, bk, k_axis, interpret)
    nk = grid[k_axis]

    def kernel(a_ref, b_ref, v_ref, key_ref, o_ref):
        base(a_ref, b_ref, o_ref)
        _apply_fused_mask(
            o_ref, v_ref, key_ref,
            p=p, z=z, ncols=ncols, bn=bn, k_axis=k_axis, nk=nk, batched=batched,
        )

    key2 = jnp.asarray(key, jnp.uint32).reshape(1, 2)
    return _launch(
        kernel, grid, [a_spec, b_spec, v_spec, key_spec], o_spec, out_shape,
        interpret, (a, b, v, key2),
    )
