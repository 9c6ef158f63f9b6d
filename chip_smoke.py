#!/usr/bin/env python3
"""Chip smoke test: the private-serving path on a TPU at MiniCPM-2B MLP width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # Phase 2 as shard_map over 4 chips

Drives ``repro.serve.ServingEngine`` through its public API, which runs
``PipelineSession`` -> the jitted protocol phases -> ``modmatmul``.  The
private weight is MiniCPM-2B's MLP up-projection, ``[d_model, d_ff]`` =
``[2304, 5760]`` (random, from ``--seed``), served under AGE(s=2, t=2,
z=2) on a simulated pool of ``n_workers + 4`` edge workers.  Sixteen
requests of 16 activation rows arrive at t=0 and fold into two replays
of ``max_batch = 8``, so the second replay reuses the first one's
compiled programs.

Checks, any failure exits non-zero without the final ``ok`` line:

* every request's decoded field ``Y`` equals the host oracle (the
  engine's ``validate=True``);
* its float result equals, to float32 rounding, the ``jax.numpy``
  float32 product of the fixed-point-rounded operands at the scales
  ``choose_scales`` picked (at k = 2304 the quantisation itself is
  coarse, so the unquantised ``x @ w`` is not the reference);
* every ``modmatmul.lower`` event on the path is the compiled Pallas
  kernel (``backend="pallas"``, ``interpret=False``);
* JAX's first device is a TPU.  There is no CPU branch.

``--four-chips`` runs only the sharded path and what it is compared
with: the same requests with Phase 2 as the ``shard_map`` exchange on a
4-chip ``workers`` mesh, once per exchange mode (``all_to_all``,
``psum``, ``psum_scatter``), each compared bit-exactly with the
one-chip run's decoded ``Y`` and with the oracle.

Times printed here are smoke numbers, not benchmark metrics: the wall
time of the first replay (compile included) and of the later replays.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "minicpm-2b"
N_REQUESTS = 16
ROWS = 16
MAX_BATCH = 8
N_TRACES = 8
EXCHANGE_MODES = ("all_to_all", "psum", "psum_scatter")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase of the smoke produced a wrong or unexpected result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileLog:
    """Host times of JAX's executable builds and persistent-cache hits."""

    def __init__(self, jax_monitoring):
        self.builds: list = []
        self.hits: list = []
        jax_monitoring.register_event_duration_secs_listener(self._on_duration)
        jax_monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.builds.append(time.perf_counter())

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits.append(time.perf_counter())

    def count(self, t0: float, t1: float) -> tuple:
        """(executables built or loaded, of them cache hits) in [t0, t1]."""
        return (
            sum(t0 <= t <= t1 for t in self.builds),
            sum(t0 <= t <= t1 for t in self.hits),
        )


def make_workload(seed: int):
    """Weights, requests and edge-pool traces, all from ``seed``."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.constructions import PlanConfig
    from repro.runtime.pool import ShiftedExponential, sample_trace

    cfg = get_config(ARCH)
    k, out = cfg.d_model, cfg.d_ff
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, out)) / np.sqrt(k)
    xs = rng.normal(size=(N_REQUESTS, ROWS, k))
    plan_cfg = PlanConfig("age", 2, 2, 2)
    pool = plan_cfg.n_workers + 4
    traces = [
        sample_trace(pool, ShiftedExponential(0.1, 0.5), seed=seed + 9000 + i,
                     net_scale=0.3)
        for i in range(N_TRACES)
    ]
    return w, xs, traces, plan_cfg


def float_reference(w, xs, p: int):
    """Per request: the jax.numpy float32 product of the operands rounded
    to the fixed-point grid at the scale ``choose_scales`` picks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.layers import choose_scales

    k = w.shape[0]
    w_max = float(np.abs(w).max() + 1e-9)
    matmul = jax.jit(
        lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    )
    w_grid: dict = {}
    refs = []
    for x in xs:
        s = choose_scales(k, float(np.abs(x).max() + 1e-9), w_max, p)
        if s not in w_grid:
            w_grid[s] = jnp.asarray(np.rint(w * s) / s, jnp.float32)
        xq = jnp.asarray(np.rint(x * s) / s, jnp.float32)
        refs.append(np.asarray(matmul(xq, w_grid[s]), np.float64))
    return refs


def serve(w, xs, traces, plan_cfg, log: CompileLog, **engine_kw) -> dict:
    """One engine, all requests at t=0, one drain; returns what it saw."""
    from repro.obs.tracer import TRACER
    from repro.serve import DONE, ServingEngine

    TRACER.clear()
    TRACER.enable()
    try:
        engine = ServingEngine(
            w, traces, plan_cfg, max_batch=MAX_BATCH, validate=True, seed=0,
            **engine_kw,
        )
        requests = [engine.submit(x, 0.0) for x in xs]
        report = engine.run()
    finally:
        TRACER.disable()
    events = TRACER.events
    check(all(r.state == DONE for r in requests),
          f"requests not served: {[r.state for r in requests]}")
    # jit traces once per shape in a process, so a later run of the same
    # shapes may lower nothing; main() checks that some run did
    lowerings = [e["attrs"] for e in events if e["name"] == "modmatmul.lower"]
    for attrs in lowerings:
        check(attrs.get("backend") == "pallas" and attrs.get("interpret") is False,
              f"modmatmul lowered off the compiled Pallas kernel: {attrs}")
    replays = sorted(
        (e for e in events if e["name"] == "runtime.replay"),
        key=lambda e: e["attrs"]["replay"],
    )
    check(len(replays) == report.replays, "one runtime.replay span per replay")
    return {
        "ys": [r.y for r in requests],
        "replays": report.replays,
        "replay_s": [e["t1"] - e["t0"] for e in replays],
        "compiles": [log.count(e["t0"], e["t1"]) for e in replays],
        "lowerings": len(lowerings),
    }


def check_floats(run: dict, refs: list) -> float:
    """Largest |Y - reference| relative to the reference's magnitude."""
    import numpy as np

    worst = 0.0
    for i, (y, ref) in enumerate(zip(run["ys"], refs)):
        scale = max(1.0, float(np.abs(ref).max()))
        err = float(np.abs(y - ref).max()) / scale
        check(err <= float(np.finfo(np.float32).eps),
              f"request {i}: float result off the float32 reference by {err}")
        worst = max(worst, err)
    return worst


def print_run(label: str, run: dict) -> None:
    times = run["replay_s"]
    print(f"[{label}] chip smoke, not a benchmark: replays={run['replays']} "
          f"first_replay_s={times[0]} (compile included) "
          f"later_replays_s={times[1:]} "
          f"executables_per_replay={[c[0] for c in run['compiles']]} "
          f"cache_hits_per_replay={[c[1] for c in run['compiles']]} "
          f"modmatmul_lowerings={run['lowerings']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip shard_map Phase 2 and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device: platform=tpu kind={kind} count={len(devices)} "
          f"compile_cache={cache_dir}")

    log = CompileLog(jax.monitoring)
    w, xs, traces, plan_cfg = make_workload(args.seed)
    print(f"workload: {ARCH} MLP up-projection w={list(w.shape)} "
          f"requests={N_REQUESTS}x{ROWS} rows {plan_cfg.label()} "
          f"pool={traces[0].n} max_batch={MAX_BATCH}")

    t0 = time.perf_counter()
    one = serve(w, xs, traces, plan_cfg, log)
    print_run("one chip", one)
    check(one["replays"] >= 2, "need two replays of one shape")
    refs = float_reference(w, xs, 65521)
    worst = check_floats(one, refs)
    print(f"[one chip] oracle: field Y exact for {N_REQUESTS} requests; "
          f"float Y vs float32 reference: max rel err {worst}")

    sharded = []
    if args.four_chips:
        mesh = Mesh(np.array(devices[:4]), ("workers",))
        for mode in EXCHANGE_MODES:
            run = serve(w, xs, traces, plan_cfg, log, mesh=mesh,
                        exchange_mode=mode)
            sharded.append(run)
            print_run(f"4 chips {mode}", run)
            for i, (y4, y1) in enumerate(zip(run["ys"], one["ys"])):
                check(np.array_equal(y4, y1),
                      f"{mode}: request {i} differs from the one-chip run")
            check_floats(run, refs)
            print(f"[4 chips {mode}] Y bit-exact vs one chip; oracle exact")

    lowered = one["lowerings"] + sum(r["lowerings"] for r in sharded)
    check(lowered > 0, "no modmatmul lowering was recorded")
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:n_chips]
    )
    print(f"peak_bytes_in_use={peak} total_s={time.perf_counter() - t0}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": "tpu", "kind": kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
