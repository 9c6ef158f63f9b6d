"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--only fig2,fig3,...]

Prints ``name,us_per_call,derived`` CSV; per-table data lands under
results/bench/*.csv.
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()

    from . import (
        cmpc_comm,
        edge_runtime,
        example1,
        fig2,
        fig3,
        fig4,
        protocol_batch,
        protocol_scaling,
        roofline,
        serve_load,
    )

    modules = {
        "example1": example1,
        "fig2": fig2,
        "fig3": fig3,
        "fig4": fig4,
        "protocol_scaling": protocol_scaling,
        "protocol_batch": protocol_batch,
        "cmpc_comm": cmpc_comm,
        "edge_runtime": edge_runtime,
        "roofline": roofline,
        "serve_load": serve_load,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failed = 0
    for name, mod in modules.items():
        try:
            for row in mod.run():
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']},{derived}")
        except Exception as e:  # keep the harness running
            failed += 1
            print(f"{name},ERROR,{e!r}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"{failed} benchmarks failed")


if __name__ == "__main__":
    main()
