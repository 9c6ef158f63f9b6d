"""Distributed CMPC: workers mapped onto a mesh axis via shard_map.

TPU-native adaptation of the paper's edge-worker topology (DESIGN.md
"hardware adaptation"):

* the N protocol workers become shards along a ``workers`` mesh axis
  (padded to a multiple of the axis size; pad workers send zero),
* Phase 2's pairwise exchange — worker n sends G_n(alpha_{n'}) to every
  n' (N(N-1) point-to-point messages on D2D links in the paper) — maps
  onto ONE collective:

    - ``all_to_all``     faithful transposition of the (sender,
                          receiver) axes; bytes on the wire match the
                          paper's zeta = N(N-1) m^2/t^2 accounting,
    - ``psum``           all-reduce of the receiver-indexed partial
                          sums; simple but replicates I(x) everywhere,
    - ``psum_scatter``   reduce-scatter: each device ends with exactly
                          its receivers' I(alpha) — the beyond-paper
                          optimization (see EXPERIMENTS.md §Perf): the
                          exchanged volume drops from O(N^2 m^2/t^2) to
                          O(N m^2/t^2) because the sum into I(x) is
                          *linear* and can be fused into the collective.

The exchange is batched: a whole batch of products rides one collective
by folding the batch axis into each worker's flattened block payload
(the exchange is elementwise over the payload, so the collective shape
is the only thing that grows).  ``protocol.run_batched_sharded`` and
the edge runtime's ``run_batch_over_pool`` enter through this path.

Integer safety: all lane values are < p < 2**16 and ``_mod_sum``
accumulates at most ``npad`` (the pool padded to the axis size) int32
partial values before reducing mod p, so the requirement is
``npad * p < 2**31`` — independent of ``n_workers``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.modmatmul.ops import mod_matmul
from .planner import CMPCPlan


@functools.partial(jax.jit, static_argnames=("npad",))
def _worker_major(x: jnp.ndarray, npad: int) -> jnp.ndarray:
    """[batch, n_total, ...] -> [npad, batch, ...] on the shares' own
    device: the worker axis leads and pad workers hold zeros."""
    x = jnp.moveaxis(x, 1, 0)
    widths = [(0, npad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


def run_phase2_sharded(
    plan: CMPCPlan,
    fa: jnp.ndarray,
    fb: jnp.ndarray,
    noise: np.ndarray,
    mesh: Mesh,
    axis: str = "workers",
    mode: str = "all_to_all",
    matmul_backend: str = "auto",
    return_compiled: bool = False,
    worker_ids: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Workers compute H and run the G-exchange on a device mesh.

    fa: [n_total, br, bk] shares, fb: [n_total, bk, bc]; noise:
    [n_workers, z, br, bc] per-worker blinding matrices R_w^{(n)}.
    Batched: fa [batch, n_total, br, bk], fb [batch, n_total, bk, bc],
    noise [batch, n_workers, z, br, bc] — the batch folds into each
    worker's flat payload, so the whole batch rides ONE collective.
    Returns I(alpha_n) for all (unpadded) provisioned workers:
    [n_total, br, bc], or [batch, n_total, br, bc] for batched inputs.

    ``worker_ids`` selects which ``n_workers`` of the provisioned pool
    serve as Phase-2 senders (straggler mitigation — e.g. the fastest
    subset picked by ``repro.runtime``); ``noise`` rows follow the same
    order.  Non-senders are receive-only (zero mix rows), matching the
    pad workers.  Default is the primary prefix; explicit subsets reuse
    the plan's cached subset mix matrices.

    ``matmul_backend`` threads through to the kernel layer
    (``auto``/``pallas``/``f32limb``): the per-shard worker multiply is
    a batched mod_matmul, so on TPU it lowers to one Pallas launch per
    shard with the local worker count on the batch grid axis.

    The shares stay on the device: they are laid out worker-major where
    they were computed and then sent shard by shard to the mesh.  The
    jitted exchange program is cached per (mesh, mode, shapes), so
    replays of one shape compile once.
    """
    p = plan.field.p
    d = mesh.shape[axis]
    n_total = plan.n_total
    # _mod_sum accumulates <= npad int32 values < p before reducing, so
    # the bound is npad * p (padded pool size; n_workers plays no role).
    npad = n_total + ((-n_total) % d)
    assert npad * p < (1 << 31), "int32 reduction bound: npad * p < 2**31"

    if worker_ids is None:
        ids = np.arange(plan.n_workers)
        mix = plan.mix
    else:
        ids = np.asarray(worker_ids)
        mix = plan.phase2_matrix_cached(ids)

    noise_np = np.asarray(noise)
    batched = fa.ndim == 4
    if not batched:
        fa = fa[None]
        fb = fb[None]
        noise_np = noise_np[None]
    batch = fa.shape[0]

    # Pad workers are receive-only (zero mix rows / zero noise).
    mix_rows = np.zeros((npad, npad), np.int64)
    mix_rows[ids, :n_total] = mix  # [senders, receivers]
    vnz = np.zeros((npad, plan.scheme.z), np.int64)
    vnz[:n_total] = plan.vnoise
    # noise rows follow ids order; layout [npad, z, batch, br, bc] so the
    # local reshape (nloc, z, payload) flattens batch into the payload.
    noise_w = np.moveaxis(noise_np, 0, 2)  # [n_workers, z, batch, br, bc]
    noise_p = np.zeros((npad,) + noise_w.shape[1:], np.int32)
    noise_p[ids] = noise_w

    sharded = NamedSharding(mesh, P(axis))
    args = (
        jax.device_put(_worker_major(jnp.asarray(fa), npad), sharded),
        jax.device_put(_worker_major(jnp.asarray(fb), npad), sharded),
        jax.device_put(mix_rows.astype(np.int32), sharded),
        jax.device_put(noise_p, sharded),
        jax.device_put(vnz.astype(np.int32), NamedSharding(mesh, P())),
    )
    br = fa.shape[2]
    bc = fb.shape[3]
    program = _phase2_program(
        mesh, axis, mode, matmul_backend, p, plan.scheme.z, batch, br, bc
    )
    if return_compiled:
        return program.lower(*args).compile()
    i_evals = np.asarray(program(*args))
    i_evals = np.moveaxis(i_evals[:n_total], 0, 1)  # [batch, n_total, br, bc]
    return i_evals if batched else i_evals[0]


@functools.lru_cache(maxsize=32)
def _phase2_program(
    mesh: Mesh, axis: str, mode: str, matmul_backend: str,
    p: int, z: int, batch: int, br: int, bc: int,
):
    """The jitted shard_map exchange for one (mesh, mode, shape)."""
    if mode not in ("all_to_all", "psum", "psum_scatter"):
        raise ValueError(f"unknown mode {mode}")
    d = mesh.shape[axis]
    blk = batch * br * bc  # per-worker flat payload (whole batch)

    def local(fa_l, fb_l, mix_l, noise_l, vn):
        npad = vn.shape[0]
        # Phase 2a: every local worker multiplies its shares (the batch
        # is just another leading dim of the batched mod_matmul).
        h_l = mod_matmul(fa_l, fb_l, p=p, backend=matmul_backend)  # [nloc, batch, br, bc]
        nloc = h_l.shape[0]
        h_flat = h_l.reshape(nloc, blk)
        # Phase 2b: local workers' G evaluated at every receiver:
        # contrib[nl, r, :] = mix[nl, r] * H[nl] + sum_w R[nl, w] * vn[r, w]
        contrib = (
            mix_l[:, :, None].astype(jnp.uint32) * h_flat[:, None, :].astype(jnp.uint32)
        ) % jnp.uint32(p)
        # Per-worker blinding: noise_eval[nl, r] = sum_w R[nl, w] vn[r, w],
        # accumulated mod p each step (uint32-safe for any z).
        nz = noise_l.reshape(nloc, z, blk)

        def nmix(acc, w):
            term = (
                vn[:, w][None, :, None].astype(jnp.uint32)
                * nz[:, w, :][:, None, :].astype(jnp.uint32)
            ) % jnp.uint32(p)
            return (acc + term) % jnp.uint32(p), None

        acc0 = jnp.zeros((nloc, npad, blk), jnp.uint32)
        noise_eval, _ = jax.lax.scan(nmix, acc0, jnp.arange(z))
        contrib = ((contrib + noise_eval) % jnp.uint32(p)).astype(jnp.int32)

        if mode == "all_to_all":
            # [nloc, npad, blk] -> exchange receiver chunks -> [npad, nloc_r, blk]
            exch = jax.lax.all_to_all(
                contrib, axis, split_axis=1, concat_axis=0, tiled=True
            )
            i_local = _mod_sum(exch, p)  # [nloc_r, blk]
        elif mode == "psum":
            part = _mod_sum(contrib, p)  # [npad, blk] local partial
            i_all = jax.lax.psum(part, axis) % p
            idx = jax.lax.axis_index(axis)
            nloc_r = npad // d
            i_local = jax.lax.dynamic_slice_in_dim(i_all, idx * nloc_r, nloc_r, 0)
        else:  # psum_scatter
            part = _mod_sum(contrib, p)  # [npad, blk]
            i_local = jax.lax.psum_scatter(part, axis, scatter_dimension=0, tiled=True) % p
        return i_local.astype(jnp.int32).reshape(-1, batch, br, bc)

    spec = P(axis)
    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, P()),
            out_specs=spec,
            check_vma=False,
        )
    )


def _mod_sum(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """Sum over axis 0 with int32 accumulation (safe: npad * p < 2**31)."""
    return (jnp.sum(x.astype(jnp.int32), axis=0) % p).astype(jnp.int32)
