"""High-level privacy-preserving compute API.

``secure_matmul`` runs one Y = A^T B under CMPC between two logical
sources, with fixed-point quantisation into GF(p) and centered-lift
decode; ``secure_matmul_batched`` and ``secure_matmul_crt`` run a batch
of products (over one prime, or over several combined by CRT).
``PrivateLinear`` wraps a weight matrix as "source 2" so that
activations from "source 1" are multiplied without either worker (or
the master) learning the operands — the paper's edge-inference setting
with the transformer stack of this framework as the surrounding model.
Every call here runs synchronously on an idealized pool; batching
requests over a simulated edge pool is the serving tier's
(``repro.serve``).

Overflow discipline: an inner product of length k with operands bounded
by ``a_max``/``w_max`` needs  k * (a_max*scale_a) * (w_max*scale_w)
< (p-1)/2.  ``choose_scales`` picks the largest power-of-two scales
satisfying that bound; with p = 65521 this caps precision, so
``PrivateLinear`` also supports column-blocked accumulation (split the
inner dim, run multiple protocol instances, sum the decoded reals) —
precision then scales with the number of blocks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from .constructions import Scheme, build_scheme
from .gf import Field
from .planner import BlockShapes, CMPCPlan, get_plan
from . import protocol


def choose_scales(k: int, a_max: float, w_max: float, p: int) -> int:
    """Largest power-of-two scale S such that k*(a_max*S)*(w_max*S) fits."""
    half = (p - 1) // 2
    s = 1
    while k * (a_max * 2 * s) * (w_max * 2 * s) < half:
        s *= 2
    return s


@dataclasses.dataclass
class SecureMatmulResult:
    y: np.ndarray
    trace: protocol.Trace
    plan: CMPCPlan


def secure_matmul(
    a: np.ndarray,
    b: np.ndarray,
    method: str = "age",
    s: int = 2,
    t: int = 2,
    z: int = 1,
    field: Optional[Field] = None,
    scale: Optional[int] = None,
    n_spare: int = 0,
    seed: int = 0,
) -> SecureMatmulResult:
    """Privacy-preserving Y = A^T B over the reals.

    a: [k, ma] held by source 1;  b: [k, mb] held by source 2.
    """
    field = field or Field()
    k, ma = a.shape
    k2, mb = b.shape
    if k != k2:
        raise ValueError("inner dimensions disagree")
    if scale is None:
        scale = choose_scales(k, float(np.abs(a).max() + 1e-9), float(np.abs(b).max() + 1e-9), field.p)
    scheme = build_scheme(method, s, t, z)
    shapes = BlockShapes(k=k, ma=ma, mb=mb, s=s, t=t)
    plan = get_plan(scheme, shapes, field=field, n_spare=n_spare, seed=seed)
    aq = field.encode(a, scale)
    bq = field.encode(b, scale)
    yq, trace = protocol.run(plan, aq, bq, seed=seed + 1)
    y = field.decode(yq, scale * scale)
    return SecureMatmulResult(y=y, trace=trace, plan=plan)


def secure_matmul_batched(
    a: np.ndarray,
    b: np.ndarray,
    method: str = "age",
    s: int = 2,
    t: int = 2,
    z: int = 1,
    field: Optional[Field] = None,
    scale: Optional[int] = None,
    n_spare: int = 0,
    seed: int = 0,
    backend: str = "auto",
) -> SecureMatmulResult:
    """Privacy-preserving Y[i] = A[i]^T B[i] for a batch of products.

    a: [batch, k, ma];  b: [batch, k, mb] or [k, mb] (a single B — e.g.
    one weight matrix against a batch of activations — is broadcast).
    One plan (from the process-wide plan cache) serves every product;
    all three phases run device-resident via ``protocol.run_batched``,
    amortizing plan setup and jit compilation across the batch.
    """
    field = field or Field()
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.ndim != 3:
        raise ValueError(f"a must be [batch, k, ma], got {a.shape}")
    if b.ndim == 2:
        b = np.broadcast_to(b, (a.shape[0],) + b.shape)
    batch, k, ma = a.shape
    if b.shape[:2] != (batch, k):
        raise ValueError(f"batch/inner dims disagree: {a.shape} vs {b.shape}")
    mb = b.shape[2]
    if scale is None:
        scale = choose_scales(
            k, float(np.abs(a).max() + 1e-9), float(np.abs(b).max() + 1e-9), field.p
        )
    scheme = build_scheme(method, s, t, z)
    shapes = BlockShapes(k=k, ma=ma, mb=mb, s=s, t=t)
    plan = get_plan(scheme, shapes, field=field, n_spare=n_spare, seed=seed)
    aq = field.encode(a, scale)
    bq = field.encode(b, scale)
    yq, trace = protocol.run_batched(plan, aq, bq, seed=seed + 1, backend=backend)
    y = field.decode(yq, scale * scale)
    return SecureMatmulResult(y=y, trace=trace, plan=plan)


def secure_matmul_crt(
    a: np.ndarray,
    b: np.ndarray,
    method: str = "age",
    s: int = 2,
    t: int = 2,
    z: int = 1,
    primes: tuple = (65521, 65519),
    scale: Optional[int] = None,
    seed: int = 0,
    n_spare: int = 0,
    backend: str = "auto",
    fused_masks: bool = False,
) -> SecureMatmulResult:
    """CRT multi-prime CMPC (beyond-paper): run the protocol once per
    16-bit prime and combine residues with the Chinese Remainder
    Theorem.  The effective modulus P = prod(primes) ~ 2**32 for the
    default pair gives fixed-point headroom a single 16-bit field
    cannot, at one extra protocol pass per extra prime.

    Routed through ``protocol.run_batched_crt``, so every residue pass
    is the batched device-resident pipeline: ``a``/``b`` may be 2D
    ([k, ma]/[k, mb], promoted to batch 1, returning a 2D ``y``) or
    batched 3D, ``backend`` selects the kernel tier per residue, and
    ``fused_masks`` generates secrets/blinding in-kernel.  Residue plans
    come from the process-wide plan cache (one per prime field).
    """
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    batched = a.ndim == 3
    if not batched:
        a = a[None]
        b = b[None]
    _, k, ma = a.shape
    mb = b.shape[-1]
    pbig = 1
    for p in primes:
        pbig *= int(p)
    if scale is None:
        half = (pbig - 1) // 2
        a_max = float(np.abs(a).max() + 1e-9)
        w_max = float(np.abs(b).max() + 1e-9)
        scale = 1
        while k * (a_max * 2 * scale) * (w_max * 2 * scale) < half:
            scale *= 2
    scheme = build_scheme(method, s, t, z)
    shapes = BlockShapes(k=k, ma=ma, mb=mb, s=s, t=t)
    plans = [
        get_plan(
            scheme, shapes, field=Field(int(p)), n_spare=n_spare,
            seed=seed + 17 * i,
        )
        for i, p in enumerate(primes)
    ]

    aq_signed = np.rint(a * scale).astype(np.int64)
    bq_signed = np.rint(b * scale).astype(np.int64)
    combined, trace = protocol.run_batched_crt(
        plans, aq_signed, bq_signed, seed=seed + 31,
        backend=backend, fused_masks=fused_masks,
    )
    # centered lift from [0, P) to (-P/2, P/2], then undo the scaling
    half = pbig // 2
    signed = np.where(combined > half, combined - pbig, combined)
    y = signed.astype(np.float64) / (scale * scale)
    if not batched:
        y = y[0]
    return SecureMatmulResult(y=y, trace=trace, plan=plans[0])


class PrivateLinear:
    """y = x @ W via CMPC, W private to the layer owner.

    The plan is built once per (k, out, s, t, z) signature and reused
    across calls; the inner dimension may be split into ``blocks``
    independent protocol instances (one ``protocol.run`` each) for extra
    fixed-point headroom.
    """

    def __init__(
        self,
        w: np.ndarray,
        method: str = "age",
        s: int = 2,
        t: int = 2,
        z: int = 1,
        blocks: int = 1,
        field: Optional[Field] = None,
        seed: int = 0,
    ):
        self.w = np.asarray(w, np.float64)
        self.method, self.s, self.t, self.z = method, s, t, z
        self.blocks = blocks
        self.field = field or Field()
        self.seed = seed
        # the scheme depends only on ctor args: build it once, not per call
        self._scheme = build_scheme(method, s, t, z)
        k = self.w.shape[0]
        if k % blocks:
            raise ValueError("blocks must divide the inner dimension")

    def _plan(self, batch: int, kblk: int) -> CMPCPlan:
        # Delegates to the process-wide plan cache (planner.get_plan):
        # every PrivateLinear with the same protocol signature shares one
        # plan's Vandermonde/mixing constants.
        shapes = BlockShapes(k=kblk, ma=batch, mb=self.w.shape[1], s=self.s, t=self.t)
        return get_plan(self._scheme, shapes, field=self.field, seed=self.seed)

    def __call__(self, x: np.ndarray) -> np.ndarray:
        """x: [batch, k] activations (source 1).  Returns [batch, out]."""
        x = np.asarray(x, np.float64)
        batch, k = x.shape
        kblk = k // self.blocks
        out = np.zeros((batch, self.w.shape[1]))
        for bi in range(self.blocks):
            sl = slice(bi * kblk, (bi + 1) * kblk)
            xa = x[:, sl].T  # [kblk, batch] == "A"
            wb = self.w[sl]  # [kblk, out]  == "B"
            scale = choose_scales(
                kblk,
                float(np.abs(xa).max() + 1e-9),
                float(np.abs(wb).max() + 1e-9),
                self.field.p,
            )
            plan = self._plan(batch, kblk)
            aq = self.field.encode(xa, scale)
            bq = self.field.encode(wb, scale)
            yq, _ = protocol.run(plan, aq, bq, seed=self.seed + bi)
            out += self.field.decode(yq, scale * scale)
        return out
