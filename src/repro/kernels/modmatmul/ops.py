"""Jitted public wrapper around the GF(p) matmul kernels.

Handles padding to tile multiples, batching, tile selection, and
backend dispatch:

* ``"pallas"``       — the Pallas f32-limb kernel (compiled on TPU,
                        interpret mode elsewhere; interpret executes the
                        kernel body in Python for correctness validation
                        on CPU).  Batched operands lower to ONE
                        ``pallas_call`` with the batch on the leading
                        grid axis — no vmap-of-padded-2D launches — and
                        an unbatched operand is shared across the batch
                        axis by its index map instead of being broadcast.
* ``"pallas_int32"`` — the native-integer Pallas kernel: int32 limb
                        dots + in-tile uint32 Barrett reduction, so one
                        tile covers contraction depths the f32 kernel
                        must chunk at 256.  Interpret mode only: a
                        compile for TPU raises ``NotImplementedError``
                        (the v5e MXU has no int32 x int32 matmul).
* ``"f32limb"``      — portable jnp path with the f32 limb math (native
                        ``dot_general`` batching, see ``core.gf``),
* ``"int32"``        — portable native-integer tier: chunk-batched limb
                        dots feeding a uint32 accumulator with ONE
                        Barrett recombination (``core.gf
                        .mod_matmul_int32``) — the deep-K fast path on
                        CPU, where per-chunk reductions dominate
                        ``f32limb``.
* ``"auto"``         — pallas on TPU backends; elsewhere ``int32`` once
                        the contraction is deeper than one 256 chunk
                        (and within the uint32 accumulator bound),
                        ``f32limb`` otherwise.

Tile sizes adapt to the operand shape *per backend* (``pick_tiles``)
unless pinned explicitly; ``register_tile_chooser`` swaps the policy for
a backend and ``autotune_tiles`` measures candidate tilings on the live
device and pins the winner.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax._src.pallas.mosaic.lowering import LoweringException

from ...core.gf import (
    CHUNK_K,
    INT32_ACC_K,
    P_DEFAULT,
    crt_combine,
    field_mask,
    mod_add,
    mod_matmul_f32,
    mod_matmul_int32,
)
from ...obs.metrics import REGISTRY
from ...obs.tracer import TRACER
from .kernel import (
    INT32_KERNEL_MAX_BK,
    modmatmul_masked_pallas,
    modmatmul_pallas,
)

_PALLAS_VARIANTS = {"pallas": "f32", "pallas_int32": "int32"}

# What lowering or compiling a candidate tiling raises when the tiling,
# the backend or the chip's compiler refuses it (autotune skips these).
_COMPILE_REFUSALS = (
    ValueError,
    NotImplementedError,
    LoweringException,
    jax.errors.JaxRuntimeError,
)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ----------------------------------------------------------------------
# tile selection: per-backend choosers + autotune hooks
# ----------------------------------------------------------------------
def _pick_tiles_f32(m: int, k: int, n: int) -> tuple:
    """Default tiles for the f32-limb kernel.

    A dim that fits one tile is one block of its full size: a block
    equal to the whole array dim needs no TPU (8, 128) alignment, so
    nothing is padded.  Padding a short dim up to the alignment would
    copy the other operand ~alignment/dim times, which at published
    widths does not fit the chip.  Larger dims get aligned tiles
    (sublane 8, lane 128), K preferring a depth that divides it;
    ``bk <= LAZY_K`` enables the kernel's lazy-reduction path.  Caps
    keep the VMEM block footprint (a + b + out) near 1 MiB.
    """
    bm = m if m <= 256 else 128
    bn = n if n <= 512 else 128
    if k <= 256:
        bk = k
    else:
        bk = 128 if k % 256 and not k % 128 else 256
    return bm, bn, bk


def _pick_tiles_int32(m: int, k: int, n: int) -> tuple:
    """Default tiles for the native-int32 kernel: same M/N policy, but
    the K tile is freed from the 2**24 f32 ceiling — deeper bk means
    fewer Barrett recombinations per output tile.  Capped at 2048 to
    keep the int32 operand blocks inside the ~1 MiB VMEM budget."""
    bm, bn, _ = _pick_tiles_f32(m, k, n)
    bk = k if k <= 2048 else 2048
    return bm, bn, bk


_TILE_CHOOSERS = {
    "pallas": _pick_tiles_f32,
    "pallas_int32": _pick_tiles_int32,
}

# (backend, m, k, n) -> tiles pinned by autotune_tiles / register_tile_cache
_AUTOTUNE_CACHE: dict = {}


def register_tile_chooser(backend: str, chooser) -> None:
    """Install a tile-selection policy for one pallas backend.

    ``chooser(m, k, n) -> (bm, bn, bk)``.  The hook point for
    hardware-specific tuning tables (the A100-style per-shape chooser
    pattern); ``autotune_tiles`` uses the measured route instead.
    """
    _TILE_CHOOSERS[backend] = chooser


def pick_tiles(m: int, k: int, n: int, backend: str = "pallas") -> tuple:
    """Choose (bm, bn, bk) from the operand shape, per backend.

    Exact-shape autotune pins (``autotune_tiles``) take precedence over
    the backend's registered chooser.
    """
    pinned = _AUTOTUNE_CACHE.get((backend, m, k, n))
    if pinned is not None:
        return pinned
    return _TILE_CHOOSERS.get(backend, _pick_tiles_f32)(m, k, n)


def autotune_tiles(
    m: int,
    k: int,
    n: int,
    backend: str = "pallas",
    p: int = P_DEFAULT,
    batch: int = 1,
    candidates=None,
    repeats: int = 3,
    interpret: bool | None = None,
) -> tuple:
    """Measure candidate tilings on the live device and pin the winner.

    Runs ``mod_matmul`` with each candidate ``(bm, bn, bk)`` on
    synthetic operands of the given shape (compile excluded, best of
    ``repeats``), stores the fastest in the exact-shape autotune cache,
    and returns it — subsequent ``pick_tiles``/``mod_matmul`` calls for
    that (backend, shape) use the tuned tiles automatically.  Default
    candidates bracket the chooser's pick with neighboring K depths and
    M/N splits.
    """
    if backend not in _PALLAS_VARIANTS:
        raise ValueError(f"autotune_tiles supports pallas backends, got {backend}")
    bm0, bn0, bk0 = _TILE_CHOOSERS.get(backend, _pick_tiles_f32)(m, k, n)
    if candidates is None:
        bks = {bk0, max(128, bk0 // 2), bk0 * 2}
        bk_cap = 256 if backend == "pallas" else INT32_KERNEL_MAX_BK - 1
        candidates = sorted(
            {(bm0, bn0, min(bk, bk_cap)) for bk in bks}
            | {(max(8, bm0 // 2), bn0, bk0), (bm0, max(128, bn0 // 2), bk0)}
        )
    rng_a = jax.random.PRNGKey(0)
    shape_a = (batch, m, k) if batch > 1 else (m, k)
    shape_b = (batch, k, n) if batch > 1 else (k, n)
    a = jax.random.randint(rng_a, shape_a, 0, p, dtype=jnp.int32)
    b = jax.random.randint(jax.random.PRNGKey(1), shape_b, 0, p, dtype=jnp.int32)
    best, best_t = None, float("inf")
    refused = REGISTRY.counter("kernels.autotune_refused")
    for bm, bn, bk in candidates:
        try:
            run = mod_matmul.lower(
                a, b, p=p, backend=backend,
                bm=bm, bn=bn, bk=bk, interpret=interpret,
            ).compile()
        except _COMPILE_REFUSALS:
            # the tiling is invalid for this backend/shape, or the
            # chip's compiler refused it; a failure at run time is not
            # a refusal and propagates
            refused.inc()
            continue
        run(a, b).block_until_ready()
        t = min(_timed(run, a, b) for _ in range(max(1, repeats)))
        if t < best_t:
            best, best_t = (bm, bn, bk), t
    if best is None:
        raise RuntimeError(f"no autotune candidate succeeded for {backend}")
    _AUTOTUNE_CACHE[(backend, m, k, n)] = best
    return best


def _timed(run, *args) -> float:
    t0 = time.perf_counter()
    run(*args).block_until_ready()
    return time.perf_counter() - t0


def padded_shape(m: int, k: int, n: int, tiles: tuple) -> tuple:
    """(M, K, N) after padding each dim up to its tile multiple."""
    bm, bn, bk = tiles
    return _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)


def padding_waste(m: int, k: int, n: int, tiles: tuple) -> float:
    """Fraction of MXU MACs spent on padding for one [M,K]@[K,N] product."""
    mp, kp, np_ = padded_shape(m, k, n, tiles)
    return 1.0 - (m * k * n) / float(mp * kp * np_)


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[-2]) % mult0
    p1 = (-x.shape[-1]) % mult1
    if p0 or p1:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, p0), (0, p1)]
        x = jnp.pad(x, pad)
    return x


def _flatten_batch(x: jnp.ndarray, batch: tuple) -> jnp.ndarray:
    """Collapse leading batch dims to one axis; an operand whose batch
    dims are absent or all 1 stays 2D (shared across the kernel's batch
    grid axis — never materialized per element)."""
    nbatch = 1
    for d in x.shape[:-2]:
        nbatch *= d
    if nbatch == 1:
        return x.reshape(x.shape[-2:])
    if x.shape[:-2] != batch:
        x = jnp.broadcast_to(x, batch + x.shape[-2:])
    return x.reshape((-1,) + x.shape[-2:])


def _resolve_auto(k: int) -> str:
    """The ``"auto"`` policy at one call's (static) contraction depth."""
    if jax.default_backend() == "tpu":
        return "pallas"
    if CHUNK_K < k and _round_up(k, CHUNK_K) <= INT32_ACC_K:
        # deeper than one exact-f32 chunk: the uint32-accumulator path
        # skips the per-chunk reductions the f32limb scan must pay
        return "int32"
    return "f32limb"


@functools.partial(
    jax.jit, static_argnames=("p", "backend", "bm", "bn", "bk", "interpret")
)
def mod_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    p: int = P_DEFAULT,
    backend: str = "auto",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """a [..., M, K] @ b [..., K, N] mod p (int32), batched over leading dims.

    Batch dims of ``a`` and ``b`` must broadcast against each other; one
    side may omit them entirely (e.g. a 2D constant matrix against a
    batched operand) — the unbatched side is contracted in place, never
    broadcast.  Tile sizes default to ``pick_tiles`` of the actual shape
    and backend.
    """
    if backend == "auto":
        backend = _resolve_auto(int(a.shape[-1]))

    # This body runs at trace time (the wrapper is jitted), so each
    # event records one *compilation*'s backend + tile choice — the
    # shape/backend signature, not a per-call sample.
    if backend in ("f32limb", "int32"):
        REGISTRY.counter("kernels.modmatmul_lowerings").inc()
        if TRACER.enabled:
            TRACER.event(
                "modmatmul.lower", backend=backend,
                m=int(a.shape[-2]), k=int(a.shape[-1]), n=int(b.shape[-1]),
            )
        fn = mod_matmul_f32 if backend == "f32limb" else mod_matmul_int32
        return fn(a, b, p)

    variant = _PALLAS_VARIANTS.get(backend)
    if variant is None:
        raise ValueError(f"unknown backend {backend}")

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    m, k = a.shape[-2:]
    n = b.shape[-1]
    tm, tn, tk = pick_tiles(m, k, n, backend=backend)
    bm = bm or tm
    bn = bn or tn
    bk = bk or tk
    REGISTRY.counter("kernels.modmatmul_lowerings").inc()
    if TRACER.enabled:
        TRACER.event(
            "modmatmul.lower", backend=backend,
            m=int(m), k=int(k), n=int(n),
            bm=int(bm), bn=int(bn), bk=int(bk), interpret=bool(interpret),
        )
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)

    call = functools.partial(
        modmatmul_pallas, p=p, bm=bm, bn=bn, bk=bk, interpret=interpret,
        variant=variant,
    )
    if a.ndim == 2 and b.ndim == 2:
        out = call(ap, bp)
    else:
        batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
        out = call(_flatten_batch(ap, batch), _flatten_batch(bp, batch))
        out = out.reshape(batch + (ap.shape[-2], bp.shape[-1]))
    return out[..., :m, :n]


@functools.partial(
    jax.jit, static_argnames=("p", "backend", "bm", "bn", "bk", "interpret")
)
def mod_matmul_masked(
    a: jnp.ndarray,
    b: jnp.ndarray,
    v: jnp.ndarray,
    key: jnp.ndarray,
    p: int = P_DEFAULT,
    backend: str = "auto",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``a @ b + v @ R(key)  (mod p)`` — blinding fused into the matmul.

    ``v`` is a 2D [M, z] constant (secret/blinding Vandermonde columns);
    R is the deterministic counter-based mask
    ``field_mask(key, batch + (z, N), p)`` where ``batch`` is the
    broadcast batch of ``a`` and ``b`` and N is the logical output
    width.  On the pallas backends R is generated *inside* the matmul
    tile (threefry on program-id-derived counters — the mask array never
    exists); the portable backends compute the identical values via
    ``field_mask`` inside the same jit.  All backends are bit-identical
    for a given ``key``.
    """
    if backend == "auto":
        backend = _resolve_auto(int(a.shape[-1]))
    m, k = a.shape[-2:]
    n = b.shape[-1]
    z = v.shape[-1]
    if v.ndim != 2 or v.shape[0] != m:
        raise ValueError(f"v must be [M={m}, z], got {v.shape}")
    if a.ndim == 2 and b.ndim == 2:
        batch = ()
    else:
        batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])

    variant = _PALLAS_VARIANTS.get(backend)
    if variant is None:
        # portable route: mask materializes only as a jit-internal value
        mm = mod_matmul(a, b, p=p, backend=backend)
        mask = field_mask(key, tuple(batch) + (z, n), p)
        return mod_add(mm, mod_matmul(v, mask, p=p, backend=backend), p)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tm, tn, tk = pick_tiles(m, k, n, backend=backend)
    bm = bm or tm
    bn = bn or tn
    bk = bk or tk
    REGISTRY.counter("kernels.modmatmul_lowerings").inc()
    if TRACER.enabled:
        TRACER.event(
            "modmatmul.lower", backend=backend, fused_mask=True,
            m=int(m), k=int(k), n=int(n),
            bm=int(bm), bn=int(bn), bk=int(bk), interpret=bool(interpret),
        )
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    vp = _pad_to(v, bm, 1)  # zero rows past M contribute nothing
    call = functools.partial(
        modmatmul_masked_pallas, p=p, ncols=int(n), bm=bm, bn=bn, bk=bk,
        interpret=interpret, variant=variant,
    )
    if not batch:
        out = call(ap, bp, vp, key)
    else:
        out = call(_flatten_batch(ap, batch), _flatten_batch(bp, batch), vp, key)
        out = out.reshape(tuple(batch) + (ap.shape[-2], bp.shape[-1]))
    return out[..., :m, :n]


def mod_matmul_crt(
    a,
    b,
    primes: tuple = (65521, 65519),
    backend: str = "auto",
    **kw,
):
    """Wide-range exact matmul via CRT over several 16-bit primes.

    Computes a @ b mod prod(primes): one residue matmul per prime on the
    selected backend, combined on the host with Garner's algorithm.
    Operands may be any integers (numpy int64 welcome — they are reduced
    per prime); the result is int64 in [0, prod(primes)), exact whenever
    the true product fits the combined modulus.  This is the dynamic-
    range escape hatch: depth/magnitude that would overflow a single
    16-bit field costs one extra residue pass instead of deeper limbs.
    """
    import numpy as np

    primes = tuple(int(q) for q in primes)
    if len(set(primes)) != len(primes):
        raise ValueError(f"CRT primes must be distinct, got {primes}")
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    residues = [
        np.asarray(
            mod_matmul(
                jnp.asarray((a % q).astype(np.int32)),
                jnp.asarray((b % q).astype(np.int32)),
                p=q, backend=backend, **kw,
            ),
            np.int64,
        )
        for q in primes
    ]
    return crt_combine(residues, primes)


def polyeval(
    vander: jnp.ndarray, coeffs: jnp.ndarray, p: int = P_DEFAULT, **kw
) -> jnp.ndarray:
    """Evaluate matrix-coefficient polynomials at many points.

    vander: [N, K] powers matrix (alpha_n ** power_k mod p)
    coeffs: [..., K, R, C] stacked matrix coefficients (leading batch
            dims allowed: the same points evaluate every batch element)
    returns [..., N, R, C]: F(alpha_n) = sum_k vander[n, k] * coeffs[k].
    """
    *batch, k, r, c = coeffs.shape
    flat = mod_matmul(vander, coeffs.reshape(tuple(batch) + (k, r * c)), p=p, **kw)
    return flat.reshape(tuple(batch) + (vander.shape[0], r, c))


def polyeval_masked(
    vander: jnp.ndarray,
    coeffs: jnp.ndarray,
    vsecret: jnp.ndarray,
    key: jnp.ndarray,
    p: int = P_DEFAULT,
    **kw,
) -> jnp.ndarray:
    """``polyeval`` with the z secret coefficients fused into the kernel.

    Evaluates F(alpha_n) = V @ coeffs + Vsecret @ R(key) where
    ``vsecret`` holds the Vandermonde columns of the secret powers and R
    is the counter-based mask playing the secret coefficient draws —
    generated in-tile on the pallas backends, so the secrets never exist
    as an array.  ``coeffs`` must carry zeros at the secret rows.
    """
    *batch, k, r, c = coeffs.shape
    flat = mod_matmul_masked(
        vander, coeffs.reshape(tuple(batch) + (k, r * c)), vsecret, key, p=p, **kw
    )
    return flat.reshape(tuple(batch) + (vander.shape[0], r, c))
