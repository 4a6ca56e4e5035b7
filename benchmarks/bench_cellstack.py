"""Stacked-cell training benchmark: the ISSUE-8 headline number.

A same-signature grid of model cells (one training recipe, different
seeds — the shape every seed-replicated DSE sweep and every ``datasets``
axis of same-topology variants produces) trains two ways:

* **farm** — the pre-stacking path: per-cell jobs sharded over spawned
  worker processes (``cellfarm.resolve_cells(stack=False)``).  Every
  worker pays a fresh interpreter + JAX import, and every cell a fresh
  jit compile.  On a TPU there is no pool — this process holds the chip —
  so the same call resolves the cells one by one in-process
  (``farm_mode`` in the BENCH line says which ran).
* **stacked** — one ``jit(vmap(train_step))`` batch in-process
  (``cellstack.resolve_stacked``): one compile for the whole stack, the
  cell axis folded into the block-skip kernels' M dimension.

Both paths publish through the content-addressed ``TraceCache`` and the
stacked cells are asserted to be *cache hits for a later solo resolve* —
the bit-exactness contract that makes the comparison honest.  The BENCH
line reports ``cells_per_second`` for the stacked path (tracked by
``tools/bench_diff.py`` as higher-is-better), the farm path's figure, the
``stack_speedup`` ratio, and the stack's jit ``compile_seconds``
separately.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time

import jax

from benchmarks.common import emit_json
from repro.core import snn, workloads
from repro.distributed import cellfarm, cellstack


def _workload(quick: bool) -> workloads.Workload:
    base = workloads.get("mnist-mlp")
    return dataclasses.replace(
        base, name="bench-cellstack-mlp",
        layers=(snn.Dense(24 if quick else 48),),
        pcr=2, n_train=256, n_test=128,
        train_steps=20 if quick else 60, trace_samples=32)


def run(quick: bool = False):
    wl = _workload(quick)
    n_cells = 4 if quick else 8            # acceptance floor: >= 4 cells
    assignment = {"num_steps": 2, "population": 1.0}
    jobs = [cellfarm.CellJob(workload=wl, assignment=assignment, seed=s)
            for s in range(n_cells)]
    sigs = {cellstack.stack_signature(j) for j in jobs}
    assert len(sigs) == 1, f"grid must share one stack signature, got {sigs}"

    with tempfile.TemporaryDirectory() as root:
        # (a) per-cell process farm on the same machine
        t0 = time.perf_counter()
        farmed = cellfarm.resolve_cells(jobs, f"{root}/farm", workers=2,
                                        stack=False)
        farm_dt = time.perf_counter() - t0
        assert all(o.trained for o in farmed)
        cellfarm.shutdown_pool()           # don't leak workers past the bench

        # (b) one vmapped stack, in-process
        cache = workloads.TraceCache(root=f"{root}/stack")
        stats: dict = {}
        t0 = time.perf_counter()
        outcomes = cellstack.resolve_stacked(jobs, cache.root, cache=cache,
                                             stats=stats)
        stack_dt = time.perf_counter() - t0
        assert all(o.trained for o in outcomes)

        # the honesty check: every stacked cell is a later solo-recipe hit
        for job in jobs:
            art = cache.resolve(job.workload, job.assignment, seed=job.seed)
            assert art.cache_hit, "stacked cell missed on solo resolve"

        speedup = farm_dt / max(stack_dt, 1e-9)
        emit_json("cellstack/grid",
                  cells=n_cells,
                  farm_mode=("pool" if jax.default_backend() == "cpu"
                             else "in-process"),
                  farm_seconds=round(farm_dt, 3),
                  farm_cells_per_second=round(n_cells / max(farm_dt, 1e-9),
                                              3),
                  stacked_seconds=round(stack_dt, 3),
                  cells_per_second=round(n_cells / max(stack_dt, 1e-9), 3),
                  compile_seconds=round(stats.get("compile_seconds", 0.0),
                                        3),
                  stack_speedup=round(speedup, 3))
        if speedup <= 1.0:
            raise AssertionError(
                f"stacked training must beat the per-cell farm on a "
                f"same-signature grid: speedup {speedup:.3f} <= 1")


if __name__ == "__main__":
    run()
