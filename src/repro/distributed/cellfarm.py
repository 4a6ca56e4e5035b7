"""Parallel model-cell training: shard pending cells across processes.

The co-exploration trace cache (``repro.core.workloads.cache``) is
content-addressed and publishes atomically, so concurrent trainers of the
same cell race benignly and trainers of *different* cells never interact —
which makes farming the cell list across worker processes safe without any
coordination beyond a shared cache root.  This module is that driver: give
it the pending ``(workload, assignment)`` jobs and a cache root, and it
shards them over a spawned-process pool; afterwards every farmed cell
resolves as a cache hit in the parent.

Pool discipline: the pool exists on the CPU backend only.  A chip belongs
to one process, and the parent that resolves cells already holds it, so on
a TPU every job resolves in-process (with ``stack=True``: every group
stacks) and a failed stack raises.  On the CPU, workers are spawned (not
forked — JAX is not fork-safe once initialized), the pool size is
explicitly capped at ``min(jobs, cpu_count, MAX_POOL_WORKERS)`` so a
100-cell grid never spawns 100 interpreters, the pool is REUSED across
calls within one process (``Study`` steps in one ``explore()`` run share
the already-imported workers; ``atexit`` tears it down), and job
submission is chunked so each worker unpickles one slab instead of one
job at a time.

``stack=True`` prefers *stacked* training over process farming: jobs are
grouped by ``cellstack.stack_signature`` and every group that can amortize
a compile (≥2 cells — or every group, when too few workers make farming
moot) trains in-process as one ``jit(vmap(train_step))`` batch
(``repro.distributed.cellstack``); only leftover singletons hit the pool.

``Study``/``dse.explore(..., workers=N, stack=...)`` and ``dse.coexplore``
are the front ends (ROADMAP "parallel cell farming" / "device-parallel
training of stacked cells").

Fault containment: ``resolve_cells`` never raises on a bad cell — worker
exceptions return as failed ``CellOutcome``\\ s, a hard pool crash tears
down and rebuilds the pool, and both are retried up to ``MAX_RETRIES``
rounds (the ``distributed.fault_tolerance`` restart idiom) before the
failure ships in ``CellOutcome.error`` for the caller to fall back on —
required by the multi-tenant service loop (``repro.serve.dse_service``),
where one tenant's bad cell must not kill another tenant's study.
"""
from __future__ import annotations

import atexit
import dataclasses
import logging
import multiprocessing
import os
from typing import Optional, Sequence, Union

import jax

from repro.core.workloads.cache import TraceCache, cell_key
from repro.core.workloads.registry import Workload

log = logging.getLogger(__name__)

#: hard cap on spawned workers — each is a full interpreter + JAX runtime,
#: so "one per job" stops paying off long before the CPU count on big hosts
MAX_POOL_WORKERS = int(os.environ.get("REPRO_CELLFARM_MAX_WORKERS", "8"))

#: bounded-retry budget for failed cells (the ``fault_tolerance``
#: supervisor's restart idiom): a crashed worker or a raising job is
#: retried this many extra rounds before its outcome ships with ``error``
#: set — it never raises through the caller (``Study._farm_chunk``)
MAX_RETRIES = int(os.environ.get("REPRO_CELLFARM_MAX_RETRIES", "2"))

_pool = None
_pool_size = 0


@dataclasses.dataclass(frozen=True)
class CellJob:
    """One cell to train-or-load: everything a worker needs, picklable."""
    workload: Workload
    assignment: dict               # {"num_steps": T, "population": p}
    seed: int = 0
    quant_bits: tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class CellOutcome:
    key: str                       # content address in the shared cache
    trained: bool                  # True = this worker trained it (a miss)
    #: set when the cell could not be resolved after ``MAX_RETRIES`` retry
    #: rounds — the cache holds nothing for it and nothing was charged;
    #: callers fall back to in-process resolution (or skip)
    error: Optional[str] = None


def _job_key(job: CellJob) -> str:
    norm = {"num_steps": int(job.assignment["num_steps"]),
            "population": float(job.assignment.get("population", 1.0))}
    return cell_key(job.workload, norm, job.seed)


def _resolve_job(args: tuple[CellJob, str]) -> CellOutcome:
    """Worker entry point: resolve one cell against the shared cache root.
    Module-level so the spawn pickler can import it by reference.  Any
    job-level failure is *returned* as a failed outcome, never raised — a
    worker must not poison the whole slab it was mapped."""
    job, root = args
    try:
        cache = TraceCache(root=root)
        art = cache.resolve(job.workload, job.assignment, seed=job.seed,
                            quant_bits=job.quant_bits)
        return CellOutcome(key=art.key, trained=not art.cache_hit)
    except KeyboardInterrupt:
        raise
    except BaseException as e:                           # noqa: BLE001
        return CellOutcome(key=_job_key(job), trained=False,
                           error=f"{type(e).__name__}: {e}")


def _holds_chip() -> bool:
    """True off the CPU backend: this process holds the accelerator, and a
    spawned child that imports JAX could not get it."""
    return jax.default_backend() != "cpu"


def _worker_count(n_jobs: int, workers: Optional[int]) -> int:
    """Effective pool size: explicit request, else one per job — both
    capped at the CPU count and the module-level ``MAX_POOL_WORKERS``; 1
    (in-process, no pool) when this process holds the chip."""
    if _holds_chip():
        return 1
    return min(workers if workers is not None else n_jobs,
               n_jobs, multiprocessing.cpu_count(), MAX_POOL_WORKERS)


def _get_pool(workers: int):
    """The shared spawn pool, rebuilt only when the requested size changes
    — repeated ``resolve_cells`` calls (Study steps, prefetch rounds)
    reuse the already-imported workers instead of paying a fresh
    interpreter + JAX import per call."""
    global _pool, _pool_size
    if _pool is not None and _pool_size != workers:
        shutdown_pool()
    if _pool is None:
        ctx = multiprocessing.get_context("spawn")   # JAX is not fork-safe
        _pool = ctx.Pool(processes=workers)
        _pool_size = workers
    return _pool


def shutdown_pool() -> None:
    """Tear down the shared worker pool (idempotent; re-created lazily)."""
    global _pool, _pool_size
    if _pool is not None:
        _pool.terminate()
        _pool.join()
        _pool = None
        _pool_size = 0


atexit.register(shutdown_pool)


def _farm_attempt(args: Sequence[tuple[CellJob, str]],
                  workers: Optional[int]) -> list[CellOutcome]:
    """One farming round.  Job-level failures come back as failed outcomes
    from ``_resolve_job``; a *pool*-level crash (a worker process died hard
    enough to break the map) marks every in-flight job failed and tears the
    poisoned pool down, so the next attempt gets a fresh one."""
    args = list(args)
    n = _worker_count(len(args), workers)
    if n <= 1 or len(args) == 1:
        return [_resolve_job(a) for a in args]
    # chunked submission: one slab per worker, not one pickle round-trip
    # per job
    chunksize = max(1, (len(args) + n - 1) // n)
    try:
        return _get_pool(n).map(_resolve_job, args, chunksize=chunksize)
    except Exception as e:                               # noqa: BLE001
        shutdown_pool()
        err = f"worker pool crashed: {type(e).__name__}: {e}"
        log.warning("%s (%d cell(s) in flight)", err, len(args))
        return [CellOutcome(key=_job_key(job), trained=False, error=err)
                for job, _ in args]


def resolve_cells(jobs: Sequence[CellJob], root: str,
                  workers: Union[int, str, None] = None,
                  stack: bool = False,
                  max_stack: Optional[int] = None,
                  retries: Optional[int] = None) -> list[CellOutcome]:
    """Resolve ``jobs`` into the cache at ``root``; returns one outcome per
    job, in job order.  ``workers`` bounds the process pool (default: one
    per job, capped at the CPU count and ``MAX_POOL_WORKERS``).

    ``workers="cluster"`` farms across *hosts* instead of processes: jobs
    spool to ``<root>/queue/`` and any ``fleet.FleetWorker`` enrolled on
    the shared root claims them by lease (``repro.distributed.fleet``).
    The call blocks on lease/publish progress and falls back to in-process
    training for cells the fleet makes no progress on, so it completes
    even with zero live workers.  Failed outcomes ship with
    ``CellOutcome.error`` exactly like the process farm (the fleet path
    has its own reclaim/retry machinery, so the local retry loop does not
    re-enter it); ``stack`` does not apply — slab formation is each
    worker's own affair.

    ``stack=True`` routes same-signature groups through the in-process
    vmapped stack trainer first (``cellstack.resolve_stacked``): with a
    usable pool (≥2 effective workers) only ≥2-cell groups stack and
    singletons still farm in parallel; without one, everything stacks
    in-process (a C=1 stack is just the solo loop, minus the spawn).

    This function **never raises on a bad cell**: a crashed worker, a
    poisoned pool, or a job that errors is retried up to ``retries``
    (default ``MAX_RETRIES``) extra rounds — the ``fault_tolerance``
    restart idiom — and then returned with ``CellOutcome.error`` set, so
    one bad cell cannot kill a study or a service loop.  On the CPU a
    failed stack group degrades to farming before counting as a retry; on
    a chip it raises.

    The parent's own ``TraceCache`` counters are untouched — count
    ``trained`` outcomes for miss accounting."""
    jobs = list(jobs)
    if not jobs:
        return []
    if workers == "cluster":
        from repro.distributed import fleet   # lazy: fleet imports us
        return fleet.resolve_cluster(jobs, root)
    if isinstance(workers, str):
        raise ValueError(f"workers must be an int or 'cluster', "
                         f"got {workers!r}")
    retries = MAX_RETRIES if retries is None else int(retries)
    outcomes: list[Optional[CellOutcome]] = [None] * len(jobs)

    if stack:
        from repro.distributed import cellstack   # lazy: cellstack imports us
        groups = cellstack.group_jobs(jobs)
        if _worker_count(len(jobs), workers) >= 2:
            stacked_idx = sorted(i for idxs in groups.values()
                                 if len(idxs) >= 2 for i in idxs)
        else:
            stacked_idx = list(range(len(jobs)))
        if stacked_idx:
            kw = {} if max_stack is None else {"max_stack": max_stack}
            try:
                got = cellstack.resolve_stacked(
                    [jobs[i] for i in stacked_idx], root, **kw)
            except Exception as e:                       # noqa: BLE001
                # on the CPU a failed in-process stack is not fatal: the
                # cells fall through to the farm/serial path below
                # untouched.  On the chip it raises — a cell must train by
                # the route that was asked for.
                if _holds_chip():
                    raise
                log.warning("stacked training failed (%s: %s); falling "
                            "back to farming %d cell(s)",
                            type(e).__name__, e, len(stacked_idx))
            else:
                for i, out in zip(stacked_idx, got):
                    outcomes[i] = out

    pending = [i for i in range(len(jobs)) if outcomes[i] is None]
    attempt = 0
    while pending:
        got = _farm_attempt([(jobs[i], root) for i in pending], workers)
        for i, out in zip(pending, got):
            outcomes[i] = out
        pending = [i for i in pending if outcomes[i].error is not None]
        if not pending:
            break
        attempt += 1
        if attempt > retries:
            log.warning("giving up on %d cell(s) after %d retry round(s): "
                        "%s", len(pending), retries,
                        [outcomes[i].error for i in pending[:3]])
            break
        log.warning("retrying %d failed cell(s), round %d/%d",
                    len(pending), attempt, retries)
    return outcomes
