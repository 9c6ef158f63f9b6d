#!/usr/bin/env python3
"""On-chip benchmark of the private-serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine:
``ServingEngine`` -> ``PipelineSession`` -> the jitted protocol phases ->
the Pallas ``modmatmul`` kernel.  The cell names a configuration
(``bench/configs/``), a traffic mix (``bench/traffic/``) and, through the
metric lists, the readers in ``bench/metrics/``; the configuration names
its layer (``bench/layers/``).  Each is found by name.

With ``--trace 0`` the last stdout line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a profiler trace
of the window.  Every run compares each decoded ``Y`` with the plain
reference of its layer and prints the numbers compared, with their
limits, as the last lines of stderr and under ``compared``.

Exits 3 with no result line when JAX finds no TPU or too few chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
# A fixed directory inside the checkout: the path is part of the cache's
# key, and nothing is shared with another checkout.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_cell(spec: dict, name: str, bench_dir: str, layers_dir: Optional[str] = None):
    """Resolve cell ``name`` of a ``BENCHMARK.json`` dict into a ``Cell``.

    The configuration's ``layer`` (``projection`` where it names none) is
    the module of that name in ``layers_dir``, ``<bench_dir>/layers`` by
    default.
    """
    from bench import layers
    from bench.harness import Cell, load_metric
    from bench.traffic import Traffic

    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    wl = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(ROOT, configs[wl["config"]]["file"])) as f:
        config = json.load(f)
    layer = layers.load(config.get("layer", layers.DEFAULT),
                        layers_dir or os.path.join(bench_dir, "layers"))
    dep = layer.from_dict(config)
    with open(os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json")) as f:
        traffic = Traffic.from_dict(json.load(f))

    e2e_here = {
        m["name"] for m in spec["end_to_end"]
        if name in m.get("workloads", [name])
    }

    def reported(m: dict, here: set) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return m.get("moves") in here if "moves" in m else True

    metrics_dir = os.path.join(bench_dir, "metrics")
    e2e = [load_metric(m["name"], m["unit"], metrics_dir)
           for m in spec["end_to_end"] if m["name"] in e2e_here]
    per_layer = [load_metric(m["name"], m["unit"], metrics_dir)
                 for m in spec["per_layer"] if reported(m, e2e_here)]
    return Cell(name, int(wl["chips"]), dep, traffic, e2e, per_layer, layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import setup_compile_cache

    from bench.harness import run

    with open(BENCHMARK_JSON) as f:
        spec = json.load(f)
    cell = load_cell(spec, args.workload, os.path.join(ROOT, "bench"))
    setup_compile_cache()
    return run(cell, args.seed, args.seconds, bool(args.trace), T_START,
               os.path.join(TRACE_DIR, args.workload))


if __name__ == "__main__":
    sys.exit(main())
