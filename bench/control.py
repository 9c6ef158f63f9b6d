#!/usr/bin/env python3
"""Readings of the control: the reference put in the program's place, one
precision step down (float32 over the field), at a cell's own size.

    python3 bench/control.py --workload up.age.closed16 --requests 100 --seeds 11 12 13

For each seed it makes the cell's weights and the first ``--requests``
requests a run with that seed sends, computes them with
``reference.control`` on the chip and prints how many elements differ
from the exact reference.  The benchmark's own runs never call this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from bench import reference
    from bench.run import BENCHMARK_JSON, load_cell
    from bench.workload import Activations, make_weights

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    with open(BENCHMARK_JSON) as f:
        cell = load_cell(json.load(f), args.workload, os.path.join(ROOT, "bench"))
    dep = cell.deployment
    for seed in args.seeds:
        w = make_weights(dep, seed)
        acts = Activations(seed, cell.traffic.rows, dep.k)
        xs = [acts.next() for _ in range(args.requests)]
        bad = reference.mismatches(reference.control(xs, w, dep.p),
                                   reference.reference(xs, w, dep.p))
        total = args.requests * cell.traffic.rows * dep.out
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mismatched_elements": sum(bad), "elements": total,
                          "requests_with_a_mismatch": sum(1 for b in bad if b)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
