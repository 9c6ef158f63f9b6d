#!/usr/bin/env python3
"""Readings of the control: the reference put in the program's place, one
precision step down (the layer's ``control``; float32 over the field for a
projection), at a cell's own size.

    python3 bench/control.py --workload up.age.closed16 --requests 100 --seeds 11 12 13

For each seed it makes the cell's weights and the first ``--requests``
requests a run with that seed sends, computes them with the layer's
``control`` on the chip and prints how many elements differ from the
layer's exact reference.  The benchmark's own runs never call this.
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
    from bench.workload import Activations

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    with open(BENCHMARK_JSON) as f:
        cell = load_cell(json.load(f), args.workload, os.path.join(ROOT, "bench"))
    layer, dep = cell.layer, cell.deployment
    for seed in args.seeds:
        weights = layer.make_weights(dep, seed)
        acts = Activations(seed, cell.traffic.rows, dep.in_width)
        xs = [acts.next() for _ in range(args.requests)]
        refs = layer.reference(dep, weights, xs)
        bad = reference.mismatches(layer.control(dep, weights, xs), refs)
        total = sum(int(r.size) for r in refs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mismatched_elements": sum(bad), "elements": total,
                          "requests_with_a_mismatch": sum(1 for b in bad if b)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
