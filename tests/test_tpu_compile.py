"""Ahead-of-time compiles of the served path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, so these tests catch
what interpret mode cannot (Mosaic refusals, unaligned blocks, programs
that do not fit the chip's 16 GiB of HBM) at no chip time.  Nothing
runs; results and times come only from ``chip_smoke.py`` on the chip.

Shapes are those of ``chip_smoke.py``: MiniCPM-2B's MLP up-projection
(k = d_model = 2304, out = d_ff = 5760) served by ``ServingEngine``
under AGE(s=2, t=2, z=2) on a pool of ``n_workers + 4``, 16 rows per
request, ``max_batch = 8``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import protocol as proto
from repro.core.constructions import PlanConfig
from repro.core.planner import BlockShapes, get_plan_for
from repro.kernels.modmatmul import ops

HBM_BYTES = 16 * 2**30  # one v5e chip
P = 65521
BATCH = 8  # ServingEngine's default max_batch
ROWS = 16


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plan():
    cfg = get_config("minicpm-2b")
    plan_cfg = PlanConfig("age", 2, 2, 2)
    plan_cfg = plan_cfg.fit_to_pool(plan_cfg.n_workers + 4)
    shapes = BlockShapes(k=cfg.d_model, ma=ROWS, mb=cfg.d_ff, s=2, t=2)
    return get_plan_for(plan_cfg, shapes)


def _sites(plan) -> dict:
    """name -> (a shape, b shape) of each matmul on the served path."""
    nt, nw, thr = plan.n_total, plan.n_workers, plan.decode_threshold
    na, nb = len(plan.scheme.fa_powers), len(plan.scheme.fb_powers)
    (bra, bca), (brb, bcb) = plan.shapes.blk_a, plan.shapes.blk_b
    bry, bcy = plan.shapes.blk_y
    return {
        "polyeval_a": ((nt, na), (BATCH, na, bra * bca)),
        "polyeval_b": ((nt, nb), (BATCH, nb, brb * bcb)),
        "worker_multiply": ((BATCH, nt, bra, bca), (BATCH, nt, brb, bcb)),
        # the runtime folds the batch into the payload before the mix
        "phase2_mix": ((nt, nw), (nw, BATCH * bry * bcy)),
        "decode": ((thr, thr), (BATCH, thr, bry * bcy)),
        # the runtime's decode of Y's t^2 coefficient rows on the device
        "device_decode": ((plan.scheme.t ** 2, thr), (thr, BATCH * bry * bcy)),
    }


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (
        ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    )


def _compile_matmul(sa, sb, sharding, **kw):
    def f(a, b):
        return ops.mod_matmul(a, b, p=P, interpret=False, **kw)

    return jax.jit(f).lower(_spec(sa, sharding), _spec(sb, sharding)).compile()


@pytest.mark.parametrize(
    "site",
    ["polyeval_a", "polyeval_b", "worker_multiply", "phase2_mix", "decode", "device_decode"],
)
def test_matmul_site_compiles_for_v5e(site, plan, one_chip):
    sa, sb = _sites(plan)[site]
    compiled = _compile_matmul(sa, sb, one_chip, backend="pallas")
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


# DeepSeek-V3's MoE layer at its published widths (hidden 7168, expert
# width 2048) under the same scheme: the worker products and the Phase-2
# mix of its largest replays, 8 products of a 128-row bucket (the held
# experts) and one of 2048 rows (the shared expert's down product).
MOE_SITES = {
    "gate_up_multiply": ((8, 21, 64, 3584), (8, 21, 3584, 1024)),
    "down_multiply": ((8, 21, 64, 1024), (8, 21, 1024, 3584)),
    "shared_down_mix": ((21, 17), (17, 1024 * 3584)),
    "shared_down_decode": ((4, 6), (6, 1024 * 3584)),
}


@pytest.mark.parametrize("site", sorted(MOE_SITES))
def test_moe_matmul_site_compiles_for_v5e(site, one_chip):
    sa, sb = MOE_SITES[site]
    compiled = _compile_matmul(sa, sb, one_chip, backend="pallas")
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_device_decode_program_fits_v5e(one_chip, monkeypatch):
    """The runtime's whole decode program (gather of the responders'
    rows, then the product) at its largest MoE launch: the shared
    expert's down product, 21 workers' I-evaluations of 1024 x 3584."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    compiled = proto._decode_subset_jit.lower(
        _spec((21, 1024, 3584), one_chip), _spec((4, 6), one_chip),
        _spec((6,), one_chip), p=P, backend="auto",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_share_program_fits_v5e(plan, one_chip, monkeypatch):
    """Phase 1 at full width is the largest program on the path.  With
    the share stack's K = 6 padded to 128 it needed ~19 GB."""
    # the compile runs here on the CPU backend; steer "auto" to what it
    # resolves to on the chip
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    dp = proto.device_plan(plan)
    sch, sh = plan.scheme, plan.shapes

    def spec_of(x):
        return _spec(x.shape, one_chip, x.dtype)

    args = (
        _spec((BATCH, sh.k, sh.ma), one_chip),
        _spec((BATCH, sh.k, sh.mb), one_chip),
        _spec((2,), one_chip, jnp.uint32),
        *(spec_of(x) for x in (dp.va, dp.vb, dp.a_pos, dp.sa_pos, dp.b_pos, dp.sb_pos)),
    )
    compiled = proto._share_batched_jit.lower(
        *args, p=P, s=sch.s, t=sch.t, z=sch.z,
        na=len(sch.fa_powers), nb=len(sch.fb_powers), backend="auto",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_fused_mask_kernel_compiles_for_v5e(plan, one_chip):
    """The fused-mask kernel at the largest share evaluation."""
    (sv, sc) = _sites(plan)["polyeval_b"]
    z = plan.scheme.z

    def f(v, c, vs, key):
        return ops.mod_matmul_masked(
            v, c, vs, key, p=P, backend="pallas", interpret=False
        )

    compiled = jax.jit(f).lower(
        _spec(sv, one_chip), _spec(sc, one_chip), _spec((sv[0], z), one_chip),
        _spec((2,), one_chip, jnp.uint32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_pallas_int32_refused_for_tpu(one_chip):
    """Mosaic has no int32 x int32 matmul on the v5e MXU: the int32
    kernel says so instead of failing inside the compiler."""
    with pytest.raises(NotImplementedError, match="does not compile for TPU"):
        _compile_matmul((8, 256), (256, 128), one_chip, backend="pallas_int32")
    # interpret mode stays available for validating its arithmetic
    a = np.arange(8 * 256, dtype=np.int32).reshape(8, 256) % P
    b = np.ones((256, 128), np.int32)
    got = ops.mod_matmul(a, b, p=P, backend="pallas_int32", interpret=True)
    assert np.array_equal(np.asarray(got)[:, 0], a.astype(np.int64).sum(1) % P)
