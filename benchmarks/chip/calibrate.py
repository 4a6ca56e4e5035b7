"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip:

    python3 benchmarks/chip/calibrate.py --workload <cell> --seed <n> \\
        [--cells 12] [--highest 0] [--control 3] [--faults 3] [--out FILE]

* program: ``--cells`` cells resolved through the cell's own route at its
  own size, each read back and set beside the reference (the lower
  readings: the largest of these that sound runs give);
* program at ``highest``: ``--highest`` more cells resolved the same way
  under ``jax.default_matmul_precision("highest")`` and set beside the
  reference at ``highest``: a witness that the two agree in semantics
  wherever the matmuls round alike;
* control: the reference in bfloat16 put in the program's place, on
  ``--control`` cell seeds (the upper readings);
* faults: the reference put in the program's place with half of each
  batch left out, on ``--faults`` cell seeds.

Each reading is one JSON line on standard output (and in ``--out``), with
the loss gap of each of the first ten steps beside the numbers; the last
line gives, per number, the largest program readings and the smallest
control and fault readings.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import catalog  # noqa: E402
import harness  # noqa: E402
import plainref  # noqa: E402
import verdict  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells", type=int, default=12)
    ap.add_argument("--highest", type=int, default=0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec = catalog.Catalog().cell(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    try:
        harness.device_info(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro import runtime
    from repro.core import workloads
    runtime.enable_compile_cache()
    wl = harness.build_workload(config, traffic)
    assignment = {"num_steps": config["num_steps"],
                  "population": config["population"]}
    seeds = harness.cell_seeds(args.seed)
    out = open(args.out, "w") if args.out else None
    rows = []

    def emit(kind, seed, numbers, seconds, gaps):
        row = {"kind": kind, "seed": seed, "seconds": seconds, **numbers,
               "loss_gaps": gaps}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            print(line, file=out, flush=True)

    stated = config.get("matmul_precision", "default")

    def reference(seed, **kw):
        kw.setdefault("precision", stated)
        return plainref.train_cell(config["workload"], config["num_steps"],
                                   config["population"], seed, **kw)

    def compare(kind, seed, published, seconds, precision=stated):
        t0 = time.perf_counter()
        ref = reference(seed, precision=precision)
        seconds["reference"] = time.perf_counter() - t0
        emit(kind, seed, verdict.readings(published, ref), seconds,
             verdict.loss_gaps(published, ref,
                               min(10, config["workload"]["train_steps"])))

    import jax
    with tempfile.TemporaryDirectory(prefix="chipbench-cal-") as root, \
            harness.Losses().kept() as losses:
        route = harness.Route(wl, assignment, traffic,
                              workloads.TraceCache(root))
        for kind, n, precision in (("program", args.cells, None),
                                   ("program_highest", args.highest,
                                    "highest")):
            for _ in range(n):
                s = next(seeds)
                t0 = time.perf_counter()
                with (jax.default_matmul_precision(precision) if precision
                      else contextlib.nullcontext()):
                    if route.resolve(s):
                        raise RuntimeError(f"cell {s} failed")
                seconds = {"program": time.perf_counter() - t0}
                compare(kind, s, verdict.read_back(
                    route.cache, wl, assignment, s, losses.by_seed), seconds,
                    precision or stated)
    for kind, n, kw in (("control", args.control, {"dtype": "bfloat16"}),
                        ("half_batch", args.faults,
                         {"keep_rows": config["workload"]["batch_size"] // 2})):
        for _ in range(n):
            s = next(seeds)
            t0 = time.perf_counter()
            planted = reference(s, **kw)
            compare(kind, s, planted, {"planted": time.perf_counter() - t0})
    numbers = [k for k in rows[0]
               if k not in ("kind", "seed", "seconds", "loss_gaps")]
    summary = {}
    for kind in ("program", "program_highest"):
        got = [r for r in rows if r["kind"] == kind]
        if got:
            summary[kind] = {k: max(r[k] for r in got) for k in numbers}
    for kind in ("control", "half_batch"):
        got = [r for r in rows if r["kind"] == kind]
        if got:
            summary[kind] = {k: min(r[k] for r in got) for k in numbers}
    line = json.dumps({"summary": summary})
    print(line, flush=True)
    if out:
        print(line, file=out)
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
