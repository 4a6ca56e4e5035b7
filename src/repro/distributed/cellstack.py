"""Stacked-cell training: vmap same-shape cells into one device batch.

The DSE's expensive leg is training model cells, and ``cellfarm`` trains
each cell in its own spawned process — a fresh interpreter, a fresh JAX
import, and a fresh jit compile per cell.  But many pending cells are the
*same compiled program*: identical topology shapes and ``num_steps``
(so params, spike trains, and the BPTT ``lax.scan`` length all stack),
differing only in seed or dataset shard.  This module groups such jobs by
**stack signature**, stacks their params/optimizer state/RNG keys along a
leading cell axis, and trains the whole stack with one
``jit(vmap(train_step))`` loop: the cell axis folds into the M dimension of
the block-skip ``spike_gemm``/fused kernels (Pallas batching tiles
``(C·B·T)`` rows instead of ``(B·T)``), and each cell's ``block_flags``
derive from its own spike rows, so per-cell sparsity skipping survives
stacking intact.

Bit-exactness contract (DESIGN.md §14): every published cell must be a
cache hit for a later *solo*-trained recipe, traces bit-identical.  Three
rules make that hold:

* **Init stays host-side and per-cell** (``train_snn.init_cell`` then
  ``jnp.stack``): ``jax.random.normal`` under ``vmap`` draws different
  bits than the solo call — the one leg of the loop that is NOT
  vmap-exact.  Everything downstream (matmuls, ``rate_encode``, key
  splits, value_and_grad, adam) is.
* **Key chains replicate the solo driver exactly**: per-cell training keys
  split *inside* the jitted step (``jax.vmap(jax.random.split)``);
  evaluation (seed 1234) and trace-dump (seed 7) keys are seed-independent
  constants in ``train_snn`` and therefore *shared* across the stack
  (``in_axes=None``).
* **Data batching stays host-side and per-cell**: one
  ``synthetic.batches(..., seed=job.seed)`` iterator per cell, stacked
  per step — the same numpy permutation stream the solo loop consumes.

When the host exposes multiple devices and the cell count divides them,
the cell axis shards over a 1-D ``"cells"`` mesh using the config-driven
rules idiom from ``distributed/sharding.py`` (here the rule table collapses
to one rule — every stacked leaf leads with the cell axis); single-device
CPU is the fallback.  Cells are independent, so partitioning the vmapped
program over the cell axis needs no collectives.

Results unstack and publish per cell through the content-addressed
``TraceCache`` (``TraceCache.publish``), so stacking is invisible to every
consumer: cache keys never mention the stack (a cell's artifact must not
depend on which batch it happened to train in), and ``Study``/``explore``
only see ordinary hits afterwards.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import optim
from repro.core import snn, train_snn
from repro.core.workloads.cache import CellArtifact, TraceCache
from repro.data import synthetic
from repro.distributed.cellfarm import CellJob, CellOutcome
from repro.distributed.sharding import to_named

#: cells per training slab: bounds device memory (C× params + batches) and
#: keeps compile shapes reusable across slabs of one big group
MAX_STACK = 16

#: evaluation batch size / key seeds — must mirror train_snn.evaluate /
#: dump_traces defaults exactly (the bit-exactness contract)
_EVAL_BATCH = 256
_EVAL_SEED = 1234
_TRACE_SEED = 7


# ---------------------------------------------------------------------------
# Stack signatures
# ---------------------------------------------------------------------------

def stack_signature(job: CellJob) -> str:
    """Hash of everything the stacked program *shares* across cells.

    Two jobs with equal signatures compile to the same jitted stack step
    and may train together: the built topology (layer types, shapes,
    LIF parameters — the whole ``SNNConfig`` minus its display name), the
    encoding, the training recipe baked into the compiled step
    (``train_steps``/``batch_size``/``lr``), the test-set geometry the
    stacked evaluate/trace legs iterate (``n_test``/``trace_samples``),
    and the resolved matmul backend.  Deliberately EXCLUDED: workload
    name, ``seed``, ``data_seed``, ``noise``, ``n_train`` — per-cell
    degrees of freedom (seed / dataset shard) that live in host-side
    iterators, never in the compiled program.  mnist-mlp and fmnist-mlp
    cells at the same (T, population) therefore stack.
    """
    T = int(job.assignment["num_steps"])
    pop = float(job.assignment.get("population", 1.0))
    wl = job.workload
    cfg = wl.build(T, pop)
    payload = {
        "cfg": dataclasses.asdict(dataclasses.replace(cfg, name="")),
        "layer_types": [type(l).__name__ for l in cfg.layers],
        "encoding": wl.encoding,
        "n_test": wl.n_test,
        "train_steps": wl.train_steps,
        "batch_size": wl.batch_size,
        "lr": wl.lr,
        "trace_samples": wl.trace_samples,
        "backend": snn.resolve_matmul_backend(wl.matmul_backend),
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def group_jobs(jobs: Sequence[CellJob]) -> dict[str, list[int]]:
    """Job indices grouped by stack signature, order-preserving."""
    groups: dict[str, list[int]] = {}
    for i, job in enumerate(jobs):
        groups.setdefault(stack_signature(job), []).append(i)
    return groups


# ---------------------------------------------------------------------------
# Cell-axis sharding (the sharding.py rules idiom, one rule)
# ---------------------------------------------------------------------------

def stack_mesh(n_cells: int) -> Optional[Mesh]:
    """A 1-D ``"cells"`` mesh over every local device, when the stack
    divides evenly; ``None`` falls back to single-device placement."""
    devices = jax.devices()
    if len(devices) > 1 and n_cells % len(devices) == 0:
        return Mesh(np.array(devices), ("cells",))
    return None


def cell_specs(tree):
    """Spec rule table for stacked-cell state: every leaf leads with the
    cell axis, so the single rule shards dim 0 over ``"cells"`` and
    replicates the rest (``P`` entries beyond rank are implicit-None)."""
    return jax.tree.map(lambda _: P("cells"), tree)


def _shard(tree, mesh: Optional[Mesh]):
    if mesh is None:
        return tree
    return jax.device_put(tree, to_named(cell_specs(tree), mesh))


# ---------------------------------------------------------------------------
# Stacked training
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _train_slab(jobs: Sequence[CellJob],
                stats: Optional[dict] = None) -> list[tuple]:
    """Train one slab of same-signature jobs as a single vmapped stack.
    Returns per-job ``(params numpy, counts, accuracy)`` tuples in job
    order.  ``stats`` (optional) accumulates ``compile_seconds`` (first,
    compiling stack-step call) and ``train_seconds``, and records the
    number of ``devices`` the stacked params live on."""
    job0 = jobs[0]
    wl0 = job0.workload
    T = int(job0.assignment["num_steps"])
    pop = float(job0.assignment.get("population", 1.0))
    cfg = wl0.build(T, pop)
    backend = snn.resolve_matmul_backend(wl0.matmul_backend)
    tx = optim.adam(wl0.lr)
    C = len(jobs)

    datas = [j.workload.make_data(int(j.assignment["num_steps"]))
             for j in jobs]
    # per-cell host-side init — the one non-vmap-exact leg (module docstring)
    inits = [train_snn.init_cell(cfg, tx, j.seed) for j in jobs]
    mesh = stack_mesh(C)
    params = _shard(_stack([i[0] for i in inits]), mesh)
    opt_state = _shard(_stack([i[1] for i in inits]), mesh)
    keys = _shard(jnp.stack([i[2] for i in inits]), mesh)

    step_fn = train_snn.make_train_step(cfg, tx, backend)

    @jax.jit
    def stack_step(params, opt_state, keys, x, y):
        split = jax.vmap(jax.random.split)(keys)
        next_keys, subs = split[:, 0], split[:, 1]
        params, opt_state, loss = jax.vmap(step_fn)(
            params, opt_state, subs, x, y)
        return params, opt_state, next_keys, loss

    iters = [synthetic.batches(d.x_train, d.y_train, wl0.batch_size,
                               seed=j.seed, epochs=10_000)
             for d, j in zip(datas, jobs)]
    t0 = time.perf_counter()
    compile_seconds = None
    for _ in range(wl0.train_steps):
        batches = [next(it) for it in iters]
        x = _shard(jnp.asarray(np.stack([b[0] for b in batches])), mesh)
        y = _shard(jnp.asarray(np.stack([b[1] for b in batches])), mesh)
        params, opt_state, keys, loss = stack_step(
            params, opt_state, keys, x, y)
        if compile_seconds is None:
            jax.block_until_ready(loss)
            compile_seconds = time.perf_counter() - t0
    jax.block_until_ready(params)
    if stats is not None:
        stats["compile_seconds"] = (stats.get("compile_seconds", 0.0)
                                    + (compile_seconds or 0.0))
        stats["train_seconds"] = (stats.get("train_seconds", 0.0)
                                  + time.perf_counter() - t0)
        stats["cells"] = stats.get("cells", 0) + C
        stats["devices"] = len(jax.tree.leaves(params)[0].sharding.device_set)

    accuracy = _evaluate_stack(cfg, backend, params, datas, mesh)
    counts = _trace_stack(cfg, backend, params, datas, wl0.trace_samples,
                          mesh)

    host_params = jax.tree.map(np.asarray, params)
    out = []
    for c in range(C):
        out.append((jax.tree.map(lambda t: t[c], host_params),
                    [np.asarray(layer[c], np.float32) for layer in counts],
                    float(accuracy[c])))
    return out


def _evaluate_stack(cfg, backend, params, datas, mesh) -> np.ndarray:
    """Per-cell test accuracy, replicating ``train_snn.evaluate`` bit for
    bit: same batch size, same seed-independent key chain — shared across
    cells (``in_axes=None``) because the solo chain never involves the
    cell's seed."""
    xs = np.stack([d.x_test for d in datas])
    ys = np.stack([d.y_test for d in datas])
    predict = jax.jit(jax.vmap(
        lambda p, k, x: train_snn._predict(cfg, backend, p, k, x),
        in_axes=(0, None, 0)))
    n = xs.shape[1]
    correct = np.zeros(len(datas), np.int64)
    key = jax.random.key(_EVAL_SEED)
    for i in range(0, n, _EVAL_BATCH):
        key, sub = jax.random.split(key)
        xb = _shard(jnp.asarray(xs[:, i:i + _EVAL_BATCH]), mesh)
        pred = np.asarray(predict(params, sub, xb))
        correct += (pred == ys[:, i:i + _EVAL_BATCH]).sum(axis=1)
    return correct / max(n, 1)


def _trace_stack(cfg, backend, params, datas, trace_samples: int,
                 mesh) -> list[np.ndarray]:
    """Per-cell spike traces, replicating ``train_snn.dump_traces``: shared
    seed-7 encode key, first ``trace_samples`` test samples per cell.
    Returns one (C, T, S) array per spiking layer."""
    key = jax.random.key(_TRACE_SEED)
    xs = _shard(jnp.asarray(
        np.stack([d.x_test[:trace_samples] for d in datas])), mesh)
    counts_fn = jax.jit(jax.vmap(
        lambda p, x: snn.spike_counts_per_layer(
            cfg, p, train_snn._encode_input(key, x, cfg.num_steps),
            matmul_backend=backend)))
    return [np.asarray(c) for c in counts_fn(params, xs)]


# ---------------------------------------------------------------------------
# Front end
# ---------------------------------------------------------------------------

def resolve_stacked(jobs: Sequence[CellJob], root: str,
                    cache: Optional[TraceCache] = None,
                    max_stack: int = MAX_STACK,
                    stats: Optional[dict] = None) -> list[CellOutcome]:
    """Resolve ``jobs`` against the cache at ``root``, training pending
    cells as vmapped same-signature stacks (in slabs of ``max_stack``).
    Jobs need not share a signature — they are grouped internally, and a
    singleton group still trains in-process as a C=1 stack (bit-exact, no
    process spawn).  Returns one outcome per job, in job order; already-
    published cells resolve as hits exactly like the process farm."""
    cache = cache if cache is not None else TraceCache(root=root)
    outcomes: list[Optional[CellOutcome]] = [None] * len(jobs)
    for _sig, idxs in group_jobs(jobs).items():
        pending = []
        for i in idxs:
            job = jobs[i]
            if cache.contains(job.workload, job.assignment, seed=job.seed):
                art = cache.resolve(job.workload, job.assignment,
                                    seed=job.seed,
                                    quant_bits=job.quant_bits)
                outcomes[i] = CellOutcome(key=art.key, trained=False)
            else:
                pending.append(i)
        for s in range(0, len(pending), max_stack):
            slab = pending[s:s + max_stack]
            results = _train_slab([jobs[i] for i in slab], stats=stats)
            for i, (params, counts, acc) in zip(slab, results):
                job = jobs[i]
                art = cache.publish(job.workload, job.assignment,
                                    seed=job.seed, params=params,
                                    counts=counts, accuracy=acc,
                                    quant_bits=job.quant_bits)
                outcomes[i] = CellOutcome(key=art.key,
                                          trained=not art.cache_hit)
    return outcomes


__all__ = ["MAX_STACK", "CellArtifact", "cell_specs", "group_jobs",
           "resolve_stacked", "stack_mesh", "stack_signature"]
