"""Shared benchmark utilities: timing, CSV emission, and the
forced-multi-device subprocess harness for sharded scenarios."""
from __future__ import annotations

import csv
import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict, List

RESULTS_DIR = os.environ.get("REPRO_BENCH_DIR", "results/bench")


def repo_root() -> str:
    """Repo root (where the committed BENCH_*.json snapshots live)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timeit(fn: Callable, repeat: int = 5, warmup: int = 1) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def run_sharded_child(module: str, devices: int, timeout: int = 900) -> Dict:
    """Run ``python -m <module> --sharded-child`` on a forced
    multi-device host and parse its one-line JSON report.

    A subprocess on purpose: ``--xla_force_host_platform_device_count``
    must be set before JAX initializes, and forcing a device split in
    the parent would perturb its single-device benchmark numbers.

    The child is a CPU rehearsal and runs with ``JAX_PLATFORMS=cpu``
    whatever the parent has: a parent that has touched JAX holds the
    accelerator, and a child reaching for it would fail or hang.  Its
    report carries ``platform`` so nobody reads it as a device number.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # append (not overwrite): any operator-supplied XLA_FLAGS must apply
    # to the child too, or its numbers aren't comparable to the parent's
    flags = f"--xla_force_host_platform_device_count={devices}"
    env["XLA_FLAGS"] = (
        env["XLA_FLAGS"] + " " + flags if env.get("XLA_FLAGS") else flags
    )
    env["PYTHONPATH"] = "src" + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    res = subprocess.run(
        [sys.executable, "-m", module, "--sharded-child"],
        capture_output=True,
        text=True,
        env=env,
        cwd=repo_root(),
        timeout=timeout,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"{module} sharded child failed:\n{res.stdout}\n{res.stderr}"
        )
    return json.loads(res.stdout.strip().splitlines()[-1])


def write_csv(name: str, rows: List[Dict]) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.csv")
    if rows:
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
    return path
