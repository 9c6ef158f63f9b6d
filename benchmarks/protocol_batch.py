"""Batched vs looped protocol execution: per-product wall time,
per-phase breakdown, and kernel padding-waste accounting.

The paper accounts computation overhead *per multiplication*; this
benchmark measures how much of the Python/host overhead of ``run`` the
batched device-resident engine (``run_batched``) amortizes away.  For
each batch size it reports the per-product latency of

* ``loop``    — a Python loop of per-sample ``protocol.run`` calls,
* ``batched`` — one ``protocol.run_batched`` call over the whole batch,

plus the resulting speedup and the speedup against the recorded PR-1
baseline of the batched engine itself (fixed-tile kernels, vmapped
padded-2D launches, per-worker PRNG blinding draws).

Besides the CSV under results/bench/, the run emits machine-readable
``BENCH_protocol.json`` at the repo root (``make bench-json``) so later
PRs can track the perf trajectory:

* ``batches``        — the table above,
* ``phases_us``      — wall time of each protocol phase (reference
                        path, batch of 1): share / multiply / reduce /
                        decode,
* ``padding_waste``  — per hot-matmul-shape fraction of MXU MACs spent
                        on padding under the fixed legacy 128/128/256
                        tiling vs the shape-adaptive ``pick_tiles``,
* ``sharded_batched``— the batched engine with the *distributed*
                        Phase 2 (``run_batched_sharded``): per exchange
                        mode, per-product latency on a forced
                        multi-device host mesh, validated bit-identical
                        against ``run_batched``.  Runs in a subprocess
                        so ``--xla_force_host_platform_device_count``
                        cannot perturb the main single-device numbers.
* ``int_backends``   — the native-integer kernel tier: deep-K
                        ``mod_matmul`` sweep (f32limb vs the int32
                        uint32-accumulator path, bit-validated per
                        shape), the dual-prime CRT protocol route, and
                        fused in-kernel blinding vs materialized masks
                        through ``run_batched`` — all on CPU, where the
                        int32 tier is the ``auto`` pick for deep
                        contractions.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from repro.core import constructions as C
from repro.core import protocol as proto
from repro.core.gf import Field
from repro.core.planner import BlockShapes, get_plan
from repro.kernels.modmatmul.ops import padding_waste, pick_tiles

from .common import repo_root, run_sharded_child, timeit, write_csv

BATCHES = (1, 8, 16, 32)

# Batched-engine per-product latency of the PR-1 revision (fixed
# 128/128/256 tiles, vmap-of-padded-2D kernel launches, broadcast
# constant matrices, per-worker blinding draws), measured on this
# benchmark's default config (m=64, age, s=t=z=2, CPU f32limb backend)
# before the batched/tile-adaptive kernel layer landed.  Kept as the
# reference point for the perf trajectory.
PR1_BASELINE_US = {1: 6995.5, 8: 3285.1, 16: 3033.8, 32: 3851.4}

FIXED_TILES = (128, 128, 256)  # the legacy hardcoded tiling

JSON_NAME = "BENCH_protocol.json"

# Sharded-batched scenario: forced host device count for the child mesh
# and the batch that rides each collective.
SHARDED_DEVICES = 8
SHARDED_BATCH = 16
SHARDED_MODES = ("all_to_all", "psum", "psum_scatter")


def _sharded_child():
    """Child entry (multi-device host): validate + time run_batched_sharded.

    Prints ONE JSON line; the parent embeds it under ``sharded_batched``.
    """
    import jax
    from jax.sharding import Mesh

    field = Field()
    rng = np.random.default_rng(0)
    m, s, t, z = 64, 2, 2, 2
    sch = C.build_scheme("age", s, t, z)
    shapes = BlockShapes(k=m, ma=m, mb=m, s=s, t=t)
    plan = get_plan(sch, shapes, n_spare=2)
    mesh = Mesh(np.array(jax.devices()), ("workers",))
    a = field.random(rng, (SHARDED_BATCH, m, m))
    b = field.random(rng, (SHARDED_BATCH, m, m))
    want, _ = proto.run_batched(plan, a, b, seed=0)
    # a non-prefix sender subset exercises the cached subset mix path
    ids2 = np.arange(1, 1 + plan.n_workers)
    dense_us = (
        timeit(lambda: np.asarray(proto.run_batched(plan, a, b, seed=0)[0]), repeat=3)
        / SHARDED_BATCH
    )
    out = {
        "platform": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "batch": SHARDED_BATCH,
        "n_workers": plan.n_workers,
        "n_spare": plan.n_spare,
        "batched_dense_us_per_product": round(dense_us, 1),
        "modes": {},
    }
    for mode in SHARDED_MODES:
        y, _ = proto.run_batched_sharded(
            plan, a, b, mesh, mode=mode, seed=0, phase2_ids=ids2
        )
        if not np.array_equal(y, want):
            raise AssertionError(f"sharded mode {mode} disagrees with run_batched")
        us = (
            timeit(
                lambda: np.asarray(
                    proto.run_batched_sharded(plan, a, b, mesh, mode=mode, seed=0)[0]
                ),
                repeat=3,
            )
            / SHARDED_BATCH
        )
        out["modes"][mode] = {"us_per_product": round(us, 1)}
    out["validated"] = True
    print(json.dumps(out))


def _sharded_report() -> dict:
    """Run the sharded scenario in a forced-multi-device subprocess."""
    return run_sharded_child("benchmarks.protocol_batch", SHARDED_DEVICES)


def _phase_times(plan, a, b) -> dict:
    """Wall time (us) of each reference-path phase for one product."""
    rng = np.random.default_rng(7)
    fa = proto.share_a(plan, a, rng)
    fb = proto.share_b(plan, b, rng)
    h = proto.worker_multiply(plan, fa, fb)
    i_evals = proto.degree_reduce(plan, h, rng)
    rng2 = np.random.default_rng(7)
    return {
        "share": round(
            timeit(lambda: np.asarray(proto.share_a(plan, a, rng2)), repeat=3), 1
        ),
        "multiply": round(
            timeit(lambda: np.asarray(proto.worker_multiply(plan, fa, fb)), repeat=3), 1
        ),
        "reduce": round(
            timeit(
                lambda: np.asarray(proto.degree_reduce(plan, h, np.random.default_rng(7))),
                repeat=3,
            ),
            1,
        ),
        "decode": round(timeit(lambda: proto.reconstruct(plan, i_evals), repeat=3), 1),
    }


def _padding_report(plan) -> list:
    """Padding-waste ratios of the protocol's hot matmul shapes under
    the legacy fixed tiling vs the adaptive one."""
    sh = plan.shapes
    t = plan.scheme.t
    na = len(plan.scheme.fa_powers)
    bra, bca = sh.blk_a
    brb, bcb = sh.blk_b
    blk_flat = (sh.ma // t) * (sh.mb // t)
    sites = [
        ("phase1_polyeval_a", plan.n_total, na, bra * bca),
        ("phase2_worker_multiply", bra, bca, bcb),
        ("phase2_mix", plan.n_total, plan.n_workers, blk_flat),
        ("phase3_decode", plan.decode_threshold, plan.decode_threshold, blk_flat),
    ]
    out = []
    for name, m, k, n in sites:
        adaptive = pick_tiles(m, k, n)
        out.append(
            {
                "site": name,
                "shape_mkn": [m, k, n],
                "tiles_adaptive": list(adaptive),
                "waste_fixed": round(padding_waste(m, k, n, FIXED_TILES), 4),
                "waste_adaptive": round(padding_waste(m, k, n, adaptive), 4),
            }
        )
    return out


# Deep-K sweep for the int32 tier: [DEEPK_BATCH, 128, K] @ [DEEPK_BATCH,
# K, 128] products, K straddling the single-chunk boundary (256) and
# going deep enough that per-chunk f32 reductions dominate.
DEEPK_BATCH = 4
DEEPK_SWEEP = (256, 512, 1024, 2048, 4096)


def _int_backends_report(plan, field, rng) -> dict:
    """Timings + validation for the native-integer tier (CPU)."""
    import jax.numpy as jnp

    from repro.core.gf import P_DEFAULT
    from repro.kernels.modmatmul.ops import mod_matmul

    kernel_rows = []
    for k in DEEPK_SWEEP:
        a = jnp.asarray(field.random(rng, (DEEPK_BATCH, 128, k)), jnp.int32)
        b = jnp.asarray(field.random(rng, (DEEPK_BATCH, k, 128)), jnp.int32)
        y_f = np.asarray(mod_matmul(a, b, p=P_DEFAULT, backend="f32limb"))
        y_i = np.asarray(mod_matmul(a, b, p=P_DEFAULT, backend="int32"))
        if not np.array_equal(y_f, y_i):
            raise AssertionError(f"int32 disagrees with f32limb at K={k}")
        f32_us = timeit(
            lambda: np.asarray(mod_matmul(a, b, p=P_DEFAULT, backend="f32limb")),
            repeat=5,
        )
        i32_us = timeit(
            lambda: np.asarray(mod_matmul(a, b, p=P_DEFAULT, backend="int32")),
            repeat=5,
        )
        kernel_rows.append(
            {
                "k": k,
                "batch": DEEPK_BATCH,
                "f32limb_us": round(f32_us, 1),
                "int32_us": round(i32_us, 1),
                "speedup": round(f32_us / i32_us, 2),
                "validated": True,
            }
        )

    # dual-prime CRT protocol route vs one single-prime pass
    m = plan.shapes.ma
    batch = 8
    a = field.random(rng, (batch, m, m))
    b = field.random(rng, (batch, m, m))
    single_us = (
        timeit(lambda: np.asarray(proto.run_batched(plan, a, b, seed=0)[0]), repeat=3)
        / batch
    )
    crt_plans = [
        get_plan(plan.scheme, plan.shapes, field=Field(q), seed=17 * i)
        for i, q in enumerate((65521, 65519))
    ]
    want = np.einsum("bki,bkj->bij", a, b) % (65521 * 65519)
    y_crt, _ = proto.run_batched_crt(crt_plans, a, b, seed=0)
    if not np.array_equal(y_crt, want):
        raise AssertionError("CRT protocol route disagrees with the oracle")
    crt_us = (
        timeit(
            lambda: np.asarray(proto.run_batched_crt(crt_plans, a, b, seed=0)[0]),
            repeat=3,
        )
        / batch
    )

    # fused in-kernel blinding vs materialized masks (bit-identical Y)
    y0, _ = proto.run_batched(plan, a, b, seed=0, fused_masks=False)
    y1, _ = proto.run_batched(plan, a, b, seed=0, fused_masks=True)
    if not np.array_equal(y0, y1):
        raise AssertionError("fused-mask run_batched disagrees with unfused")
    fused_us = (
        timeit(
            lambda: np.asarray(
                proto.run_batched(plan, a, b, seed=0, fused_masks=True)[0]
            ),
            repeat=3,
        )
        / batch
    )

    deep = [r for r in kernel_rows if r["k"] >= 256]
    return {
        "deep_k_matmul": kernel_rows,
        "int32_beats_f32limb_deep_k": any(r["speedup"] > 1.0 for r in deep),
        "crt": {
            "primes": [65521, 65519],
            "batch": batch,
            "single_prime_us_per_product": round(single_us, 1),
            "crt_us_per_product": round(crt_us, 1),
            "validated": True,
        },
        "fused_masks": {
            "batch": batch,
            "unfused_us_per_product": round(single_us, 1),
            "fused_us_per_product": round(fused_us, 1),
            "bit_identical": True,
        },
    }


def run():
    field = Field()
    rng = np.random.default_rng(0)
    m, s, t, z = 64, 2, 2, 2
    sch = C.build_scheme("age", s, t, z)
    shapes = BlockShapes(k=m, ma=m, mb=m, s=s, t=t)
    plan = get_plan(sch, shapes)

    rows = []
    best = None
    for batch in BATCHES:
        a = field.random(rng, (batch, m, m))
        b = field.random(rng, (batch, m, m))

        def loop():
            for i in range(batch):
                proto.run(plan, a[i], b[i], seed=i)

        def batched():
            y, _ = proto.run_batched(plan, a, b, seed=0)
            np.asarray(y)

        loop_us = timeit(loop, repeat=3) / batch
        # the batched call is cheap enough to repeat more: the median
        # over 7 keeps one-off scheduler hiccups out of the committed
        # BENCH_protocol.json trajectory
        batched_us = timeit(batched, repeat=7, warmup=2) / batch
        speedup = loop_us / batched_us
        base = PR1_BASELINE_US.get(batch)
        rows.append(
            {
                "batch": batch,
                "m": m,
                "n_workers": plan.n_workers,
                "loop_us_per_product": round(loop_us, 1),
                "batched_us_per_product": round(batched_us, 1),
                "speedup": round(speedup, 2),
                "pr1_baseline_us": base,
                "speedup_vs_pr1": round(base / batched_us, 2) if base else None,
            }
        )
        best = rows[-1]
    path = write_csv("protocol_batch", rows)

    a1 = field.random(rng, (m, m))
    b1 = field.random(rng, (m, m))
    report = {
        "bench": "protocol_batch",
        "config": {
            "m": m,
            "method": "age",
            "s": s,
            "t": t,
            "z": z,
            "n_workers": plan.n_workers,
            "n_total": plan.n_total,
        },
        "batches": rows,
        "phases_us": _phase_times(plan, a1, b1),
        "padding_waste": _padding_report(plan),
        "sharded_batched": _sharded_report(),
        "int_backends": _int_backends_report(plan, field, rng),
    }
    json_path = os.path.join(repo_root(), JSON_NAME)
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    return [
        {
            "name": "protocol_batch",
            "us_per_call": best["batched_us_per_product"],
            "derived": f"csv={path} json={json_path} batch={best['batch']} "
            f"speedup_vs_loop={best['speedup']}x "
            f"speedup_vs_pr1={best['speedup_vs_pr1']}x",
        }
    ]


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
