"""Beyond-paper optimization: Phase-2 exchange collective choice.

The paper's Phase 2 has every worker send G_n(alpha_{n'}) to every
other worker — zeta = N(N-1) m^2/t^2 scalars on the wire (Corollary
12).  Because I(x) = sum_n G_n(x) is *linear*, the exchange can be a
reduce-scatter: the sum is computed inside the collective, so the wire
volume drops to O(N m^2/t^2).

This benchmark compiles the shard_map Phase-2 program in all three
modes on an 8-device worker mesh and counts wire bytes from the HLO.
Run in a CPU subprocess (``JAX_PLATFORMS=cpu``) so the parent keeps
1 device and never shares an accelerator with the child:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.cmpc_comm
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = """
import numpy as np, jax, json
from jax.sharding import Mesh
from repro.core import constructions as C, protocol as proto
from repro.core.planner import BlockShapes, make_plan
from repro.core.distributed import run_phase2_sharded
from repro.core.gf import Field
from repro.launch.hlo_cost import analyze

f = Field(); rng = np.random.default_rng(7)
mesh = Mesh(np.array(jax.devices()), ("workers",))
sch = C.build_scheme("age", 2, 2, 4)
m = 256
shapes = BlockShapes(k=m, ma=m, mb=m, s=2, t=2)
plan = make_plan(sch, shapes, n_spare=7, seed=1)
A = f.random(rng, (m, m)); B = f.random(rng, (m, m))
fa = proto.share_a(plan, A, rng); fb = proto.share_b(plan, B, rng)
noise = f.random(rng, (plan.n_workers, plan.scheme.z, m//2, m//2))
want = f.matmul(A.T, B)

out = {"platform": jax.devices()[0].platform,
       "n_workers": plan.n_workers, "n_total": plan.n_total,
       "paper_zeta_scalars": plan.n_workers*(plan.n_workers-1)*(m//2)*(m//2)}
for mode in ("all_to_all", "psum", "psum_scatter"):
    compiled = run_phase2_sharded(plan, fa, fb, noise, mesh, mode=mode,
                                  return_compiled=True)
    cost = analyze(compiled.as_text())
    i_evals = run_phase2_sharded(plan, fa, fb, noise, mesh, mode=mode)
    ok = bool(np.array_equal(proto.reconstruct(plan, i_evals), want))
    out[mode] = {"collective_bytes_per_device": cost.collectives,
                 "correct": ok}
print(json.dumps(out))
"""


def run():
    res = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True, text=True, timeout=580,
        # a CPU rehearsal: the child never reaches for the accelerator
        # its parent may hold
        env=dict(
            os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            JAX_PLATFORMS="cpu",
            PYTHONPATH="src",
        ),
    )
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    data = json.loads(res.stdout.strip().splitlines()[-1])

    def total(mode):
        return sum(data[mode]["collective_bytes_per_device"].values())

    a2a, ps, rs = total("all_to_all"), total("psum"), total("psum_scatter")
    from .common import write_csv

    rows = [
        {"mode": m, "wire_bytes_per_device": total(m), "correct": data[m]["correct"]}
        for m in ("all_to_all", "psum", "psum_scatter")
    ]
    path = write_csv("cmpc_comm_modes", rows)
    return [
        {
            "name": "cmpc_phase2_collectives",
            "us_per_call": 0,
            "derived": (
                f"csv={path} platform={data['platform']} "
                f"N={data['n_workers']} all_to_all={a2a} psum={ps} "
                f"reduce_scatter={rs} saving={a2a / max(rs, 1):.1f}x all_correct="
                f"{all(data[m]['correct'] for m in ('all_to_all','psum','psum_scatter'))}"
            ),
        }
    ]
