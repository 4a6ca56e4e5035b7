"""Benchmark harness entry point: one module per paper table/figure plus
kernel-level and DSE-throughput benchmarks.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]

Each line of output is ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI-sized)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset: table1,fig1,fig6,fig7,"
                         "kernels,ext,dse,coexplore,explore,cellstack,"
                         "service,fleet")
    args = ap.parse_args()

    from repro import runtime
    runtime.enable_compile_cache()

    from benchmarks import (bench_cellstack, bench_coexplore, bench_dse,
                            bench_explore, bench_extensions, bench_fig1,
                            bench_fig6, bench_fig7, bench_fleet,
                            bench_kernels, bench_service, bench_table1)
    suites = {
        "table1": bench_table1.run,
        "fig1": bench_fig1.run,
        "fig6": bench_fig6.run,
        "fig7": bench_fig7.run,
        "kernels": bench_kernels.run,
        "ext": bench_extensions.run,
        "dse": bench_dse.run,
        "coexplore": bench_coexplore.run,
        "explore": bench_explore.run,
        "cellstack": bench_cellstack.run,
        "service": bench_service.run,
        "fleet": bench_fleet.run,
    }
    selected = [s.strip() for s in args.only.split(",") if s.strip()] or \
        list(suites)
    failures = 0
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        try:
            suites[name](quick=args.quick)
            print(f"{name}/TOTAL,{(time.time()-t0)*1e6:.0f},ok")
        except Exception:                                    # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name}/TOTAL,0,FAILED")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
