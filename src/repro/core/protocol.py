"""The three-phase CMPC protocol engine.

Faithful execution of Algorithm 3 (AGE-CMPC) / Section IV-A
(PolyDot-CMPC) over GF(p):

Phase 1  sources evaluate F_A(alpha_n), F_B(alpha_n) and send one share
         pair to each worker,
Phase 2  every worker computes H(alpha_n) = F_A(alpha_n) F_B(alpha_n),
         forms G_n(x) (eq. 19) and exchanges evaluations; each worker
         sums the received values into I(alpha_n) (eq. 20),
Phase 3  the master reconstructs I(x) from any t^2 + z responses and
         reads Y = A^T B off the first t^2 coefficients (eq. 21).

This module operates on *stacked worker arrays* (leading axis = worker)
so the same code runs single-host (vmapped) or sharded over a mesh axis
via ``repro.core.distributed``.  All modular compute routes through the
``modmatmul`` kernel ops so the TPU path uses the Pallas kernel.

Three execution paths:

* ``run``          — per-product reference: host-side block stacking and
                     Phase-3 decode in numpy (the test oracle),
* ``run_batched_sharded`` — the batched pipeline with the *distributed*
                     Phase 2: the degree-reduction exchange is the
                     ``shard_map`` collective of ``core.distributed``
                     (``all_to_all`` / ``psum`` / ``psum_scatter``),
                     with Phases 1 and 3 on the same jitted kernels,
* ``run_batched``  — batched, fully-jitted, device-resident pipeline:
                     share evaluation, worker multiply, degree reduction
                     and decode execute inside one jitted computation
                     over a whole batch of products.  Block scatter /
                     gather and the decode assembly are index-based
                     ``jnp`` ops built once per plan (``DevicePlan``,
                     cached on the plan); secrets and blinding terms are
                     drawn on-device from the JAX PRNG.  Amortizes plan
                     setup, dispatch, and compilation across the batch —
                     see ``benchmarks/protocol_batch.py``.

A ``Trace`` records the byte movement of each phase, matching the
communication-overhead accounting of Corollary 12.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.modmatmul.ops import (
    mod_matmul,
    mod_matmul_masked,
    polyeval,
    polyeval_masked,
)
from ..obs.tracer import TRACER
from .gf import Field, crt_combine, random_field_device
from .planner import BlockShapes, CMPCPlan


@dataclasses.dataclass
class Trace:
    """Scalar-movement accounting, in field elements.

    Phase-1 counts cover every *provisioned* worker (primaries and
    spares alike — spares receive shares up front so they can step in),
    matching Corollary 12's accounting at N = n_total.  Phase-2 counts
    are spare-inclusive on the *receive* side for the same reason: each
    of the ``n_workers`` senders reaches the other ``n_total - 1``
    provisioned workers, because Phase 3 may decode from any of them.
    ``elem_bytes`` (the field's wire width, ``Field.elem_bytes``)
    converts the element counts into the bytes-level view used by the
    runtime metrics.
    """

    phase1_source_to_worker: int = 0
    phase2_worker_to_worker: int = 0
    phase3_worker_to_master: int = 0
    elem_bytes: int = 2  # width of one GF(p) element on the wire

    def __add__(self, other: "Trace") -> "Trace":
        """Phase-wise sum — aggregate accounting across replays (the
        pipelined runtime sums one Trace per in-flight replay)."""
        if not isinstance(other, Trace):
            return NotImplemented
        if self.elem_bytes != other.elem_bytes:
            raise ValueError(
                f"cannot sum traces with different wire widths "
                f"({self.elem_bytes} vs {other.elem_bytes} bytes)"
            )
        return Trace(
            phase1_source_to_worker=self.phase1_source_to_worker
            + other.phase1_source_to_worker,
            phase2_worker_to_worker=self.phase2_worker_to_worker
            + other.phase2_worker_to_worker,
            phase3_worker_to_master=self.phase3_worker_to_master
            + other.phase3_worker_to_master,
            elem_bytes=self.elem_bytes,
        )

    @property
    def total(self) -> int:
        return (
            self.phase1_source_to_worker
            + self.phase2_worker_to_worker
            + self.phase3_worker_to_master
        )

    @property
    def phase1_bytes(self) -> int:
        return self.phase1_source_to_worker * self.elem_bytes

    @property
    def phase2_bytes(self) -> int:
        return self.phase2_worker_to_worker * self.elem_bytes

    @property
    def phase3_bytes(self) -> int:
        return self.phase3_worker_to_master * self.elem_bytes

    @property
    def total_bytes(self) -> int:
        return self.total * self.elem_bytes


# ----------------------------------------------------------------------
# Phase 1 — sources share data with workers
# ----------------------------------------------------------------------
# The coefficient stacks are built directly in int32 with one reshape /
# transpose block scatter (the host mirror of ``_run_batched_jit``'s
# index-based scatter) and ONE bulk int32 PRNG draw for all z secret
# coefficients — replacing the per-block dict loop, the per-power int64
# draws, and the int64 -> int32 conversion pass over the whole stack
# that used to dominate the ``run()`` share path on CPU.


def _share_stack(
    blocks: np.ndarray,
    n_coeff: int,
    data_pos: np.ndarray,
    secret_pos: np.ndarray,
    p: int,
    rng: np.random.Generator,
) -> np.ndarray:
    """Scatter data blocks + fresh secrets into an int32 coeff stack."""
    stack = np.zeros((n_coeff,) + blocks.shape[1:], np.int32)
    stack[data_pos] = blocks
    stack[secret_pos] = rng.integers(
        0, p, size=(secret_pos.size,) + blocks.shape[1:], dtype=np.int32
    )
    return stack


def share_a(plan: CMPCPlan, a: np.ndarray, rng: np.random.Generator) -> jnp.ndarray:
    """Source 1: F_A(alpha_n) for every provisioned worker.

    Returns int32 [n_total, ma/t, k/s].
    """
    sh = plan.shapes
    s, t = plan.scheme.s, plan.scheme.t
    br, bc = sh.blk_a
    dp = device_plan(plan)  # constants uploaded once per plan, not per call
    with TRACER.span("protocol.phase1.share_a"):
        at = np.ascontiguousarray(np.asarray(a, np.int64).T)  # [ma, k]
        blocks = (
            at.reshape(t, br, s, bc).transpose(0, 2, 1, 3).reshape(t * s, br, bc)
        ).astype(np.int32)
        stack = _share_stack(
            blocks, len(plan.scheme.fa_powers), dp.a_pos_h, dp.sa_pos_h,
            plan.field.p, rng,
        )
        # the numpy stack goes straight into the jitted kernel: an eager
        # jnp.asarray here costs more than the kernel's own conversion
        return polyeval(dp.va, stack, p=plan.field.p)


def share_b(plan: CMPCPlan, b: np.ndarray, rng: np.random.Generator) -> jnp.ndarray:
    sh = plan.shapes
    s, t = plan.scheme.s, plan.scheme.t
    br, bc = sh.blk_b
    dp = device_plan(plan)
    with TRACER.span("protocol.phase1.share_b"):
        bm = np.asarray(b, np.int64)
        blocks = (
            bm.reshape(s, br, t, bc).transpose(0, 2, 1, 3).reshape(s * t, br, bc)
        ).astype(np.int32)
        stack = _share_stack(
            blocks, len(plan.scheme.fb_powers), dp.b_pos_h, dp.sb_pos_h,
            plan.field.p, rng,
        )
        return polyeval(dp.vb, stack, p=plan.field.p)


# ----------------------------------------------------------------------
# Phase 2 — workers compute and communicate
# ----------------------------------------------------------------------
def worker_multiply(plan: CMPCPlan, fa: jnp.ndarray, fb: jnp.ndarray) -> jnp.ndarray:
    """H(alpha_n) = F_A(alpha_n) @ F_B(alpha_n), batched over workers."""
    with TRACER.span("protocol.phase2.worker_multiply"):
        return mod_matmul(fa, fb, p=plan.field.p)


def degree_reduce(
    plan: CMPCPlan,
    h: jnp.ndarray,
    rng: np.random.Generator,
    worker_ids: Optional[Sequence[int]] = None,
) -> jnp.ndarray:
    """Dense (single-host) simulation of the Phase-2 exchange.

    Every worker n forms G_n(x) (eq. 19) and evaluates it at every other
    worker's alpha; the receivers sum into I(alpha_{n'}) (eq. 20).  Here
    that is two modular matmuls:

      I[n'] = sum_n mix[n, n'] * H[n]  +  sum_w (sum_n R_w^(n)) vnoise[n', w]

    ``worker_ids`` selects which n_workers (of n_total provisioned)
    serve Phase 2 — straggler mitigation; default = the primary set.
    Returns I evaluations for *all* provisioned workers [n_total, ...].
    """
    p = plan.field.p
    n = plan.n_workers
    dp = device_plan(plan)
    with TRACER.span("protocol.phase2.degree_reduce"):
        ids, mix_t = _phase2_selection(plan, worker_ids)
        blk = h.shape[-2:]
        h_sel = h[jnp.asarray(ids)]
        h_flat = h_sel.reshape(n, -1)
        i_flat = mod_matmul(mix_t, h_flat, p=p)  # [n_total, blk]
        # Workers' blinding terms R_w^{(n)}: each of the n Phase-2
        # workers contributes z random matrices; only their sum enters
        # I(x).
        r = plan.field.random(rng, (n, plan.scheme.z) + blk)
        r_sum = np.sum(r, axis=0) % p  # [z, blk]
        noise_flat = mod_matmul(
            dp.vnoise,
            jnp.asarray(r_sum.reshape(plan.scheme.z, -1).astype(np.int32)),
            p=p,
        )
        i_evals = _add_mod_i32(i_flat, noise_flat, p)
        return i_evals.reshape((plan.n_total,) + blk)


# ----------------------------------------------------------------------
# worker-subset selection (shared by run / run_batched / the runtime)
# ----------------------------------------------------------------------
def _phase2_selection(
    plan: CMPCPlan, worker_ids: Optional[Sequence[int]]
) -> Tuple[np.ndarray, jnp.ndarray]:
    """(sender ids, device mix.T) for a Phase-2 worker subset.

    ``None`` is the primary-prefix fast path: the pre-transposed device
    constant from ``device_plan``.  Any explicit subset routes through
    the plan's cached subset matrices.
    """
    if worker_ids is None:
        return np.arange(plan.n_workers), device_plan(plan).mix_t
    ids = np.asarray(worker_ids)
    mix = plan.phase2_matrix_cached(ids)
    return ids, jnp.asarray((mix.T % plan.field.p).astype(np.int32))


def _decode_selection(
    plan: CMPCPlan, worker_ids: Optional[Sequence[int]]
) -> Tuple[np.ndarray, np.ndarray]:
    """(responder ids, decode matrix) for a Phase-3 responder subset."""
    if worker_ids is None:
        return np.arange(plan.decode_threshold), plan.decode_w
    ids = np.asarray(worker_ids)
    return ids, plan.decode_matrix_cached(ids)


def assemble_y(plan: CMPCPlan, coeffs: np.ndarray) -> np.ndarray:
    """Lay the first t^2 coefficients of I(x) out as Y (eq. 21).

    coeffs: [>= t^2, blk_flat]; coefficient g = i + t*l is output block
    (row i, col l).  Vectorized transpose — no per-block Python loop.
    """
    t = plan.scheme.t
    br, bc = plan.shapes.blk_y
    blocks = np.asarray(coeffs)[: t * t].reshape(t, t, br, bc)  # [l, i, ., .]
    return blocks.transpose(1, 2, 0, 3).reshape(plan.shapes.ma, plan.shapes.mb)


# ----------------------------------------------------------------------
# Phase 3 — master reconstructs Y = A^T B
# ----------------------------------------------------------------------
def reconstruct(
    plan: CMPCPlan,
    i_evals: jnp.ndarray,
    worker_ids: Optional[Sequence[int]] = None,
) -> np.ndarray:
    """Interpolate I(x) from t^2 + z responses and assemble Y.

    ``worker_ids`` is the responder subset (any ``decode_threshold``
    indices into the provisioned pool); the default is the primary
    prefix, whose decode matrix is precomputed on the plan.
    """
    thr = plan.decode_threshold
    with TRACER.span("protocol.phase3.reconstruct"):
        ids, w = _decode_selection(plan, worker_ids)
        sel = np.asarray(i_evals)[ids].reshape(thr, -1)
        coeffs = plan.field.matmul(w, sel)  # [thr, blk_flat]
        return assemble_y(plan, coeffs)


def reconstruct_corrected(
    plan: CMPCPlan,
    i_evals: jnp.ndarray,
    worker_ids: Sequence[int],
    e: int,
    rng: Optional[np.random.Generator] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Byzantine-tolerant reconstruction: decode Y from ``thr + 2e``
    responses of which up to ``e`` may be arbitrarily corrupted.

    The error-correcting counterpart of :func:`reconstruct` —
    Berlekamp-Welch over the responder subset instead of plain
    interpolation (see :mod:`repro.core.bw_decode`).  Returns
    ``(y, corrected_ids)`` where ``corrected_ids`` names the responders
    identified as corrupt; raises
    :class:`~repro.core.bw_decode.BWDecodeError` past the budget.
    """
    from .bw_decode import bw_decode_evals  # deferred: keeps import light

    evals = np.asarray(i_evals)
    coeffs, corrected = bw_decode_evals(
        plan, evals.reshape(evals.shape[0], -1), np.asarray(worker_ids), e,
        rng=rng,
    )
    return assemble_y(plan, coeffs), corrected


def reconstruct_coded_only(
    plan: CMPCPlan, h: jnp.ndarray, worker_ids: Optional[Sequence[int]] = None
) -> np.ndarray:
    """Coded-computation decode (no Phase 2): interpolate H(x) directly.

    Used for validating decodability of the underlying AGE/PolyDot codes
    (Theorem 6); the master learns garbage coefficients, so this mode
    does NOT provide master-side privacy.
    """
    n = plan.n_workers
    ids = np.arange(n) if worker_ids is None else np.asarray(worker_ids)
    if ids.size != n:
        raise ValueError(f"coded decode needs exactly {n} evaluations")
    v = plan.field.vandermonde(plan.alphas[ids], plan.scheme.h_powers)
    vinv = plan.field.inv_matrix(v)
    sel = np.asarray(h)[ids].reshape(n, -1)
    coeffs = plan.field.matmul(vinv, sel)
    t = plan.scheme.t
    br, bc = plan.shapes.blk_y
    y = np.zeros((plan.shapes.ma, plan.shapes.mb), np.int64)
    for i in range(t):
        for l in range(t):
            blkc = coeffs[plan.important_idx[i, l]].reshape(br, bc)
            y[i * br : (i + 1) * br, l * bc : (l + 1) * bc] = blkc
    return y


# ----------------------------------------------------------------------
# batched device-resident engine
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """Device-resident constants of one CMPCPlan.

    Everything the jitted batched pipeline needs, shipped once as int32:
    share Vandermondes, the Phase-2 mixing matrix (pre-transposed), the
    blinding Vandermonde, the Phase-3 decode matrix, and the index maps
    that replace the host-side Python loops of ``_block_stack_a/_b`` and
    ``reconstruct`` with gather/scatter ``jnp`` ops built once per plan.
    """

    va: jnp.ndarray  # [n_total, |P(F_A)|]
    vb: jnp.ndarray  # [n_total, |P(F_B)|]
    mix_t: jnp.ndarray  # [n_total, n_workers]  (plan.mix.T mod p)
    vnoise: jnp.ndarray  # [n_total, z]
    decode_w: jnp.ndarray  # [thr, thr]
    a_pos: jnp.ndarray  # [t*s] block (i,j) -> row of the F_A coeff stack
    sa_pos: jnp.ndarray  # [z]   secret power -> row of the F_A stack
    b_pos: jnp.ndarray  # [s*t] block (k,l) -> row of the F_B coeff stack
    sb_pos: jnp.ndarray  # [z]
    ids2: jnp.ndarray  # [n_workers] default Phase-2 worker set
    ids3: jnp.ndarray  # [thr] default Phase-3 responder set
    # host copies of the scatter maps for the numpy share path of ``run``
    a_pos_h: np.ndarray = None
    sa_pos_h: np.ndarray = None
    b_pos_h: np.ndarray = None
    sb_pos_h: np.ndarray = None


def _positions(all_powers, powers) -> np.ndarray:
    pos = {u: idx for idx, u in enumerate(all_powers)}
    return np.array([pos[u] for u in powers], np.int32)


def device_plan(plan: CMPCPlan) -> DevicePlan:
    """Build (and cache on the plan) the device-resident constants."""
    cached = plan.__dict__.get("_device_plan")
    if cached is not None:
        return cached
    sch = plan.scheme
    p = plan.field.p
    amap = sch.coded.a_power_map()
    bmap = sch.coded.b_power_map()
    a_pos = np.zeros(sch.t * sch.s, np.int32)
    fa_index = {u: idx for idx, u in enumerate(sch.fa_powers)}
    for (i, j), u in amap.items():
        a_pos[i * sch.s + j] = fa_index[u]
    b_pos = np.zeros(sch.s * sch.t, np.int32)
    fb_index = {u: idx for idx, u in enumerate(sch.fb_powers)}
    for (k, l), u in bmap.items():
        b_pos[k * sch.t + l] = fb_index[u]
    dp = DevicePlan(
        va=jnp.asarray((plan.va % p).astype(np.int32)),
        vb=jnp.asarray((plan.vb % p).astype(np.int32)),
        mix_t=jnp.asarray((plan.mix.T % p).astype(np.int32)),
        vnoise=jnp.asarray((plan.vnoise % p).astype(np.int32)),
        decode_w=jnp.asarray((plan.decode_w % p).astype(np.int32)),
        a_pos=jnp.asarray(a_pos),
        sa_pos=jnp.asarray(_positions(sch.fa_powers, sch.sa)),
        b_pos=jnp.asarray(b_pos),
        sb_pos=jnp.asarray(_positions(sch.fb_powers, sch.sb)),
        ids2=jnp.arange(plan.n_workers, dtype=jnp.int32),
        ids3=jnp.arange(plan.decode_threshold, dtype=jnp.int32),
        a_pos_h=a_pos,
        sa_pos_h=_positions(sch.fa_powers, sch.sa),
        b_pos_h=b_pos,
        sb_pos_h=_positions(sch.fb_powers, sch.sb),
    )
    object.__setattr__(plan, "_device_plan", dp)
    return dp


def _key_words(key: jnp.ndarray) -> jnp.ndarray:
    """A JAX PRNG key as the (2,) uint32 word pair the counter-based
    mask stream (``gf.field_mask`` / the fused kernels) consumes.
    Accepts classic raw ``uint32[2]`` keys and new-style typed keys."""
    if hasattr(key, "dtype") and key.dtype == jnp.uint32:
        return key.reshape(-1)
    return jax.random.key_data(key).reshape(-1).astype(jnp.uint32)


@functools.partial(
    jax.jit,
    static_argnames=("p", "s", "t", "z", "na", "nb", "backend", "fused_masks"),
)
def _share_batched_jit(
    a: jnp.ndarray,
    b: jnp.ndarray,
    key: jnp.ndarray,
    va: jnp.ndarray,
    vb: jnp.ndarray,
    a_pos: jnp.ndarray,
    sa_pos: jnp.ndarray,
    b_pos: jnp.ndarray,
    sb_pos: jnp.ndarray,
    *,
    p: int,
    s: int,
    t: int,
    z: int,
    na: int,
    nb: int,
    backend: str,
    fused_masks: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Phase 1 for a batch of products, on device.

    a: [batch, k, ma], b: [batch, k, mb] int32 in [0, p).  Returns
    (F_A(alpha_n), F_B(alpha_n)) stacked [batch, n_total, ., .] — the
    index-based block scatter replaces _block_stack_a/_b.

    ``fused_masks`` switches the z secret coefficients from materialized
    PRNG draws scattered into the stack to the counter-based threefry
    stream fused into the share-evaluation kernel (``polyeval_masked``):
    the secret rows stay zero and the Vandermonde columns of the secret
    powers multiply in-tile mask values instead.  Decode correctness is
    draw-independent (secrets occupy non-important coefficients), so
    both routes yield bit-identical Y.
    """
    batch, k, ma = a.shape
    mb = b.shape[-1]
    bra, bca = ma // t, k // s  # F_A coefficient block
    brb, bcb = k // s, mb // t  # F_B coefficient block
    k1, k2 = jax.random.split(key, 2)

    at = jnp.swapaxes(a, -1, -2)  # [batch, ma, k]
    a_blocks = (
        at.reshape(batch, t, bra, s, bca)
        .transpose(0, 1, 3, 2, 4)
        .reshape(batch, t * s, bra, bca)
    )
    stack_a = jnp.zeros((batch, na, bra, bca), jnp.int32)
    stack_a = stack_a.at[:, a_pos].set(a_blocks)
    b_blocks = (
        b.reshape(batch, s, brb, t, bcb)
        .transpose(0, 1, 3, 2, 4)
        .reshape(batch, s * t, brb, bcb)
    )
    stack_b = jnp.zeros((batch, nb, brb, bcb), jnp.int32)
    stack_b = stack_b.at[:, b_pos].set(b_blocks)
    if fused_masks:
        # secret coefficients never materialize: V[:, secret] @ R(key)
        # is generated inside the matmul tile on the pallas backends
        fa = polyeval_masked(
            va, stack_a, jnp.take(va, sa_pos, axis=1), _key_words(k1),
            p=p, backend=backend,
        )
        fb = polyeval_masked(
            vb, stack_b, jnp.take(vb, sb_pos, axis=1), _key_words(k2),
            p=p, backend=backend,
        )
        return fa, fb
    stack_a = stack_a.at[:, sa_pos].set(random_field_device(k1, (batch, z, bra, bca), p))
    stack_b = stack_b.at[:, sb_pos].set(random_field_device(k2, (batch, z, brb, bcb), p))
    fa = polyeval(va, stack_a, p=p, backend=backend)  # [batch, n_total, bra, bca]
    fb = polyeval(vb, stack_b, p=p, backend=backend)
    return fa, fb


def share_batched(
    plan: CMPCPlan,
    a: jnp.ndarray,
    b: jnp.ndarray,
    key,
    backend: str = "auto",
    fused_masks: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sources evaluate a whole batch of share pairs in one jitted call.

    a: [batch, k, ma], b: [batch, k, mb] int32 in [0, p); ``key`` is a
    JAX PRNG key (secrets are drawn on device — or generated inside the
    share kernel when ``fused_masks``).  Entry point for the sharded
    batched engine and the batched edge runtime.
    """
    dp = device_plan(plan)
    with TRACER.span(
        "protocol.phase1.share_batched", batch=int(a.shape[0]), backend=backend
    ):
        return _share_batched_jit(
            a, b, key, dp.va, dp.vb, dp.a_pos, dp.sa_pos, dp.b_pos, dp.sb_pos,
            p=plan.field.p,
            s=plan.scheme.s,
            t=plan.scheme.t,
            z=plan.scheme.z,
            na=len(plan.scheme.fa_powers),
            nb=len(plan.scheme.fb_powers),
            backend=backend,
            fused_masks=fused_masks,
        )


@functools.partial(jax.jit, static_argnames=("p", "t", "backend"))
def _decode_batched_jit(
    i_evals: jnp.ndarray,
    decode_w: jnp.ndarray,
    ids3: jnp.ndarray,
    *,
    p: int,
    t: int,
    backend: str,
) -> jnp.ndarray:
    """Phase 3 on device: mod_matmul with the int32 decode matrix, then
    an index-based block gather replaces the ``reconstruct`` loops.

    i_evals: [batch, n_total, bry, bcy]; returns y [batch, ma, mb].
    """
    batch, _, bry, bcy = i_evals.shape
    sel = jnp.take(i_evals, ids3, axis=1).reshape(batch, ids3.shape[0], bry * bcy)
    coeffs = mod_matmul(decode_w, sel, p=p, backend=backend)
    # coefficient g = i + t*l of I(x) is output block (row i, col l)
    y_blocks = coeffs[:, : t * t].reshape(batch, t, t, bry, bcy)  # [b, l, i, ., .]
    return y_blocks.transpose(0, 2, 3, 1, 4).reshape(batch, t * bry, t * bcy)


@functools.partial(jax.jit, static_argnames=("p", "backend"))
def _decode_subset_jit(
    i_evals: jnp.ndarray,
    decode_rows: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    p: int,
    backend: str,
) -> jnp.ndarray:
    """Phase 3 on device for one responder subset: the given rows of its
    decode matrix times the subset's I-evaluations.

    i_evals: [n_total, ...]; returns [rows, payload] int32, the payload
    flattened in the order of ``i_evals.reshape(n_total, -1)``.
    """
    sel = jnp.take(i_evals.reshape(i_evals.shape[0], -1), ids, axis=0)
    return mod_matmul(decode_rows, sel, p=p, backend=backend)


def decode_y_coeffs(
    plan: CMPCPlan, i_evals: jnp.ndarray, worker_ids: Sequence[int],
    backend: str = "auto",
) -> jnp.ndarray:
    """Launch the decode of Y's coefficients from a responder subset.

    ``i_evals`` is the device-resident ``[n_total, ...]`` I-evaluations,
    ``worker_ids`` ``decode_threshold`` sorted responder ids.  Returns the
    first t^2 coefficients of I(x) — Y's blocks, the rest is blinding — as
    an int32 ``[t^2, payload]`` device array, bit-identical to the same
    rows of ``field.matmul(decode_matrix_cached(ids), evals[ids])``.  The
    subset's device ids and int32 decode rows are cached on the plan next
    to its host decode matrix, so a recurring subset uploads nothing.
    """
    p = plan.field.p
    t2 = plan.scheme.t ** 2

    def build(ids: np.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        rows = plan.decode_matrix_cached(ids)[:t2] % p
        return jnp.asarray(ids.astype(np.int32)), jnp.asarray(rows.astype(np.int32))

    ids_dev, rows_dev = plan._subset_cached("dec_dev", np.asarray(worker_ids), build)
    return _decode_subset_jit(i_evals, rows_dev, ids_dev, p=p, backend=backend)


@functools.partial(
    jax.jit,
    static_argnames=(
        "p", "s", "t", "z", "n_workers", "na", "nb", "backend", "fused_masks",
    ),
)
def _run_batched_jit(
    a: jnp.ndarray,
    b: jnp.ndarray,
    key: jnp.ndarray,
    va: jnp.ndarray,
    vb: jnp.ndarray,
    mix_t: jnp.ndarray,
    vnoise: jnp.ndarray,
    decode_w: jnp.ndarray,
    a_pos: jnp.ndarray,
    sa_pos: jnp.ndarray,
    b_pos: jnp.ndarray,
    sb_pos: jnp.ndarray,
    ids2: jnp.ndarray,
    ids3: jnp.ndarray,
    *,
    p: int,
    s: int,
    t: int,
    z: int,
    n_workers: int,
    na: int,
    nb: int,
    backend: str,
    fused_masks: bool = False,
) -> jnp.ndarray:
    """All three protocol phases for a batch of products, on device.

    a: [batch, k, ma], b: [batch, k, mb] int32 in [0, p).
    Returns y: [batch, ma, mb] int32 with y = A^T B mod p per element.
    """
    batch, k, ma = a.shape
    mb = b.shape[-1]
    kshare, k3 = jax.random.split(key, 2)

    # Phase 1 — shared with the sharded engine (inlined under this jit).
    fa, fb = _share_batched_jit(
        a, b, kshare, va, vb, a_pos, sa_pos, b_pos, sb_pos,
        p=p, s=s, t=t, z=z, na=na, nb=nb, backend=backend,
        fused_masks=fused_masks,
    )

    # Phase 2 — worker multiply + dense degree-reduction exchange.
    h = mod_matmul(fa, fb, p=p, backend=backend)  # [batch, n_total, bra, bcb]
    bry, bcy = ma // t, mb // t
    blk_flat = bry * bcy
    h_flat = jnp.take(h, ids2, axis=1).reshape(batch, n_workers, blk_flat)
    # Each Phase-2 worker contributes z blinding matrices R_w^{(n)}, but
    # only their sum over workers enters I(x) — and a sum of i.i.d.
    # uniforms mod p is itself uniform, so the dense single-host
    # simulation draws the summed term directly (n_workers x less PRNG
    # volume; the reference ``degree_reduce`` keeps per-worker draws).
    if fused_masks:
        # summed blinding generated inside the mixing matmul's tiles:
        # I = mix.T @ H + Vnoise @ R(k3), masks never materialized
        i_evals = mod_matmul_masked(
            mix_t, h_flat, vnoise, _key_words(k3), p=p, backend=backend
        )
    else:
        i_flat = mod_matmul(mix_t, h_flat, p=p, backend=backend)  # [b, n_total, .]
        r_sum = random_field_device(k3, (batch, z, blk_flat), p)
        noise = mod_matmul(vnoise, r_sum, p=p, backend=backend)
        i_evals = (
            (i_flat.astype(jnp.uint32) + noise.astype(jnp.uint32)) % jnp.uint32(p)
        ).astype(jnp.int32)

    # Phase 3 — shared with the sharded engine.
    return _decode_batched_jit(
        i_evals.reshape(batch, -1, bry, bcy), decode_w, ids3,
        p=p, t=t, backend=backend,
    )


@functools.partial(jax.jit, static_argnums=1)
def _mod_i32(x: jnp.ndarray, p: int) -> jnp.ndarray:
    # p is static: on a TPU v5e, at the served B operand's size
    # ([8, 2304, 5760] int32), a remainder by a constant compiles in
    # about 1 s and runs in 2.1 ms; by a traced divisor, 35 s and 7.9 ms
    return jnp.mod(x, p).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=2)
def _add_mod_i32(x: jnp.ndarray, y: jnp.ndarray, p: int) -> jnp.ndarray:
    # p static, as in _mod_i32: compiled ahead of time for a v5e at the
    # degree reduction's shapes, the sum's remainder by a traced divisor
    # took 17-27 s per shape, by a constant 0.6 s
    return ((x.astype(jnp.uint32) + y.astype(jnp.uint32)) % jnp.uint32(p)).astype(
        jnp.int32
    )


def _field_i32(x, p: int) -> jnp.ndarray:
    """``x mod p`` as an int32 device array.  A ``jax.Array`` is reduced
    where it lives, on the device; anything else is reduced on the host
    and copied to the device."""
    if isinstance(x, jax.Array):
        return _mod_i32(x, p)
    return jnp.asarray(np.asarray(x) % p, jnp.int32)


def _prep_batched_operands(
    plan: CMPCPlan, a, b
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Validate and promote operands (host or device arrays) to int32
    [batch, k, m] device arrays."""
    a = _field_i32(a, plan.field.p)
    b = _field_i32(b, plan.field.p)
    if a.ndim == 2:
        a = a[None]
    if b.ndim == 2:
        b = b[None]
    if a.ndim != 3 or b.ndim != 3 or a.shape[0] != b.shape[0]:
        raise ValueError(f"expected [batch, k, m] operands, got {a.shape} {b.shape}")
    sh = plan.shapes
    if a.shape[1:] != (sh.k, sh.ma) or b.shape[1:] != (sh.k, sh.mb):
        raise ValueError(
            f"operands {a.shape[1:]}/{b.shape[1:]} disagree with plan "
            f"shapes ({sh.k}, {sh.ma})/({sh.k}, {sh.mb})"
        )
    return a, b


def batch_trace(
    plan: CMPCPlan,
    batch: int = 1,
    n_receivers: Optional[int] = None,
    n_responses: Optional[int] = None,
) -> Trace:
    """Corollary-12 communication accounting for ``batch`` products.

    Phase 1 provisions every worker (spares included); Phase 2's
    receivers likewise span all ``n_total`` provisioned workers — spares
    must receive I(alpha_n) too, since Phase 3 decodes from any of them
    (each of the ``n_workers`` senders reaches the other n_total - 1).
    The edge runtime overrides ``n_receivers`` with the *live* pool
    (dropouts receive nothing) and ``n_responses`` with the responses
    actually arrived at acceptance; the defaults are the idealized
    full-pool / threshold counts of the protocol paths.
    """
    sh = plan.shapes
    t = plan.scheme.t
    blk_y = (sh.ma // t) * (sh.mb // t)
    if n_receivers is None:
        n_receivers = plan.n_total
    if n_responses is None:
        n_responses = plan.decode_threshold
    return Trace(
        phase1_source_to_worker=batch
        * plan.n_total
        * (sh.blk_a[0] * sh.blk_a[1] + sh.blk_b[0] * sh.blk_b[1]),
        phase2_worker_to_worker=batch * plan.n_workers * (n_receivers - 1) * blk_y,
        phase3_worker_to_master=batch * n_responses * blk_y,
        elem_bytes=plan.field.elem_bytes,
    )


def _phase3_device_selection(
    plan: CMPCPlan, phase3_ids: Optional[Sequence[int]]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(device ids3, device decode matrix) for a responder subset."""
    dp = device_plan(plan)
    if phase3_ids is None:
        return dp.ids3, dp.decode_w
    ids3_h, decode_w_h = _decode_selection(plan, phase3_ids)
    return (
        jnp.asarray(ids3_h.astype(np.int32)),
        jnp.asarray((decode_w_h % plan.field.p).astype(np.int32)),
    )


def run_batched(
    plan: CMPCPlan,
    a: np.ndarray,
    b: np.ndarray,
    seed: int = 0,
    phase2_ids: Optional[Sequence[int]] = None,
    phase3_ids: Optional[Sequence[int]] = None,
    backend: str = "auto",
    fused_masks: bool = False,
) -> Tuple[np.ndarray, Trace]:
    """Batched protocol: Y[i] = A[i]^T B[i] mod p for a batch of products.

    a: [batch, k, ma], b: [batch, k, mb] (a single 2D operand pair is
    promoted to batch 1).  The whole pipeline — share evaluation, worker
    multiply, degree reduction and Phase-3 decode — runs inside one
    jitted, device-resident computation; plan constants are shipped once
    via ``device_plan`` and shared across calls and batch elements.
    Per-example secret shares and blinding terms come from the JAX PRNG
    (folded from ``seed``), so results are reproducible per seed but the
    randomness differs from the numpy path of ``run``.

    ``fused_masks`` generates the Phase-1 secret coefficients and the
    Phase-2 summed blinding term inside the matmul kernels (counter-based
    threefry streams) instead of materializing them; Y is unaffected —
    decode exactness holds for any draw — so fused and unfused runs
    agree bit-for-bit.

    Returns (y [batch, ma, mb] int64, Trace for the whole batch).
    """
    a, b = _prep_batched_operands(plan, a, b)
    dp = device_plan(plan)
    p = plan.field.p
    if phase2_ids is None:
        ids2 = dp.ids2
        mix_t = dp.mix_t
    else:
        ids2_h, mix_t = _phase2_selection(plan, phase2_ids)
        ids2 = jnp.asarray(ids2_h.astype(np.int32))
    ids3, decode_w = _phase3_device_selection(plan, phase3_ids)

    # All three phases execute inside one jit, so the span covers the
    # whole dispatch (phase split is only visible on the sharded path).
    with TRACER.span(
        "protocol.run_batched", batch=int(a.shape[0]), backend=backend
    ):
        y = _run_batched_jit(
            a,
            b,
            jax.random.PRNGKey(seed),
            dp.va,
            dp.vb,
            mix_t,
            dp.vnoise,
            decode_w,
            dp.a_pos,
            dp.sa_pos,
            dp.b_pos,
            dp.sb_pos,
            ids2,
            ids3,
            p=p,
            s=plan.scheme.s,
            t=plan.scheme.t,
            z=plan.scheme.z,
            n_workers=plan.n_workers,
            na=len(plan.scheme.fa_powers),
            nb=len(plan.scheme.fb_powers),
            backend=backend,
            fused_masks=fused_masks,
        )
    return np.asarray(y, np.int64), batch_trace(plan, int(a.shape[0]))


def _sum_traces(traces: Sequence[Trace]) -> Trace:
    """Aggregate per-residue traces whose wire widths may differ (CRT
    primes of different byte widths): element counts sum, the combined
    width is the widest residue's (an upper bound on the byte view)."""
    out = Trace(elem_bytes=max(t.elem_bytes for t in traces))
    for t in traces:
        out.phase1_source_to_worker += t.phase1_source_to_worker
        out.phase2_worker_to_worker += t.phase2_worker_to_worker
        out.phase3_worker_to_master += t.phase3_worker_to_master
    return out


def run_batched_crt(
    plans: Sequence[CMPCPlan],
    a: np.ndarray,
    b: np.ndarray,
    seed: int = 0,
    phase2_ids: Optional[Sequence[int]] = None,
    phase3_ids: Optional[Sequence[int]] = None,
    backend: str = "auto",
    fused_masks: bool = False,
) -> Tuple[np.ndarray, Trace]:
    """CRT multi-prime batched protocol: Y mod prod(p_i) from one
    ``run_batched`` per residue plan.

    ``plans`` hold the same scheme/shapes over *distinct* prime fields
    (one plan per CRT residue); operands are arbitrary int64 (reduced
    per field inside ``run_batched``), and the residue outputs combine
    on the host via Garner's algorithm into int64 in [0, prod(p_i)).
    This widens dynamic range without deeper limb arithmetic: fixed-point
    headroom scales with the prime product at one extra protocol pass
    per extra prime.  The returned Trace sums all residue passes.
    """
    primes = [plan.field.p for plan in plans]
    if len(set(primes)) != len(primes):
        raise ValueError(f"CRT plans must use distinct primes, got {primes}")
    residues, traces = [], []
    with TRACER.span("protocol.run_batched_crt", primes=len(primes)):
        for i, plan in enumerate(plans):
            y, tr = run_batched(
                plan, a, b, seed=seed + 31 * i,
                phase2_ids=phase2_ids, phase3_ids=phase3_ids,
                backend=backend, fused_masks=fused_masks,
            )
            residues.append(y)
            traces.append(tr)
    return crt_combine(residues, primes), _sum_traces(traces)


def run_batched_sharded(
    plan: CMPCPlan,
    a: np.ndarray,
    b: np.ndarray,
    mesh,
    axis: str = "workers",
    mode: str = "all_to_all",
    seed: int = 0,
    phase2_ids: Optional[Sequence[int]] = None,
    phase3_ids: Optional[Sequence[int]] = None,
    backend: str = "auto",
) -> Tuple[np.ndarray, Trace]:
    """Batched protocol with the *distributed* Phase 2 on a device mesh.

    Same contract as ``run_batched``, but the degree-reduction exchange
    is the ``shard_map`` collective of
    ``repro.core.distributed.run_phase2_sharded`` (``mode`` selects
    ``all_to_all`` / ``psum`` / ``psum_scatter``): workers live as
    shards on the ``axis`` mesh axis, each shard multiplies its own
    shares, and the whole batch rides one collective.  Phases 1 and 3
    are the same jitted device kernels as ``run_batched``
    (``_share_batched_jit`` / ``_decode_batched_jit``).

    ``phase2_ids`` is the Phase-2 sender subset (e.g. the fastest
    ``n_workers`` picked by the edge scheduler) and routes through the
    plan's cached subset mix matrices; ``phase3_ids`` is the responder
    subset for the decode.  Unlike ``run_batched``'s summed-blinding
    shortcut, the exchange keeps faithful *per-worker* blinding draws
    R_w^{(n)} — they are sharded with their workers.

    Returns (y [batch, ma, mb] int64, Trace for the whole batch).
    """
    from .distributed import run_phase2_sharded  # local: avoid cycle

    a, b = _prep_batched_operands(plan, a, b)
    p = plan.field.p
    batch = int(a.shape[0])
    kshare, knoise = jax.random.split(jax.random.PRNGKey(seed), 2)
    with TRACER.span(
        "protocol.run_batched_sharded", batch=batch, mode=mode, backend=backend
    ):
        fa, fb = share_batched(plan, a, b, kshare, backend=backend)

        n = plan.n_workers
        blk_y = plan.shapes.blk_y
        noise = np.asarray(
            random_field_device(knoise, (batch, n, plan.scheme.z) + blk_y, p)
        )
        with TRACER.span("protocol.phase2.sharded_exchange", mode=mode):
            i_evals = run_phase2_sharded(
                plan,
                fa,
                fb,
                noise,
                mesh,
                axis=axis,
                mode=mode,
                matmul_backend=backend,
                worker_ids=None if phase2_ids is None else np.asarray(phase2_ids),
            )  # [batch, n_total, bry, bcy]

        ids3, decode_w = _phase3_device_selection(plan, phase3_ids)
        with TRACER.span("protocol.phase3.decode_batched"):
            y = _decode_batched_jit(
                jnp.asarray(i_evals), decode_w, ids3,
                p=p, t=plan.scheme.t, backend=backend,
            )
    return np.asarray(y, np.int64), batch_trace(plan, batch)


# ----------------------------------------------------------------------
# end-to-end simulation
# ----------------------------------------------------------------------
def run(
    plan: CMPCPlan,
    a: np.ndarray,
    b: np.ndarray,
    seed: int = 0,
    phase2_ids: Optional[Sequence[int]] = None,
    phase3_ids: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, Trace]:
    """Full protocol: returns (Y = A^T B mod p, communication trace)."""
    rng = np.random.default_rng(seed)
    with TRACER.span("protocol.run"):
        fa = share_a(plan, a, rng)
        fb = share_b(plan, b, rng)
        h = worker_multiply(plan, fa, fb)
        i_evals = degree_reduce(plan, h, rng, worker_ids=phase2_ids)
        y = reconstruct(plan, i_evals, worker_ids=phase3_ids)
    return y, batch_trace(plan, 1)
