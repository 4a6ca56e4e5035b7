"""Smoke run of the cell-training path on a TPU.

    python chip_smoke.py             # one chip: kernels, mlp, conv, study
    python chip_smoke.py --chips 4   # one 8-cell stacked slab: 4-chip mesh
                                     # against one chip, and nothing else

It drives the path a DSE study takes to its model cells, through the entry
points a user calls — ``TraceCache.resolve`` for one cell,
``dse.explore(stack=True)`` for a study — at the registered workloads'
largest population (2.0), with the Pallas kernels compiled for the chip:

* kernels — the block-skip GEMM and conv kernels, forward and VJP, at the
  registered layer shapes against the jnp reference on grid-valued
  operands, where every summation order is exact: they must be equal;
* mlp — ``mnist-mlp`` at T=25 (784-256-256-40, batch 64, 150 steps) on
  every matmul backend;
* conv — ``dvs-conv`` at T=16 (32x32x2 events, 16 and 32 channels) on the
  jnp and block-skip kernel backends;
* study — ``explore`` over mnist-mlp T in {15, 25} x population in
  {1.0, 2.0}, stacked in-process, ending in a frontier.

Each cell line gives wall and XLA compile seconds (a compile served by the
persistent cache counts its load), the loss of the initial and
the trained params on the first training batch, test accuracy, and the
largest difference of the per-layer spike traces from the jnp backend's.
A phase fails on a non-finite value, accuracy at chance, a kernel backend
whose step holds no compiled kernel (``tpu_custom_call``), a kernel that
disagrees with its reference, or a study cell that failed or trained by a
fallback route.  The last line is one JSON object naming the device.  With
no TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import optim, runtime  # noqa: E402
from repro.core import dse, snn, train_snn, workloads  # noqa: E402
from repro.distributed import cellfarm, cellstack  # noqa: E402
from repro.kernels import ops  # noqa: E402

POPULATION = 2.0
_compile = {"seconds": 0.0}


def _on_duration(event: str, duration: float, **_):
    # XLA compiles only: trace and lower events nest (an inner jit's trace
    # runs inside its caller's), so summing them would count twice
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["seconds"] += duration


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def timed(out: dict):
    """Wall seconds and XLA compile seconds of the block, into ``out``."""
    c0, t0 = _compile["seconds"], time.perf_counter()
    yield out
    out["wall_seconds"] = time.perf_counter() - t0
    out["compile_seconds"] = _compile["seconds"] - c0


def device_check(chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    print(f"jax {jax.__version__}; devices {devices}")
    print(f"platform {d.platform}; device_kind {d.device_kind}; "
          f"count {len(devices)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform {d.platform!r}; "
                 f"this smoke runs on a TPU only")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# kernels: exact agreement with the reference on grid operands
# ---------------------------------------------------------------------------

def _grid(key, shape, scale=64):
    """Values on a 1/256 grid in (-scale/256, scale/256): exact in bf16 and
    in every f32 summation order at these sizes."""
    return jax.random.randint(key, shape, -scale, scale).astype(
        jnp.float32) / 256.0


def _sparse_spikes(key, shape, rate=0.2):
    """{0,1} spikes with the leading half of the rows silent, so whole
    tiles are skipped."""
    s = jax.random.bernoulli(key, rate, shape).astype(jnp.float32)
    return s.at[:shape[0] // 2].set(0.0)


def _oracle(label: str, kernel, dense, s, w, g, key) -> None:
    """``kernel`` (forward and VJP) must equal ``dense`` on grid operands.
    Then, on f32 weights off the grid, print how far the kernel and the
    default-precision ``dense`` each lie from ``dense`` at HIGHEST."""
    out, vjp = jax.vjp(kernel, s, w)
    ref, ref_vjp = jax.vjp(dense, s, w)
    for got, want, what in zip((out,) + vjp(g), (ref,) + ref_vjp(g),
                               ("out", "dS", "dW")):
        check(bool(jnp.array_equal(got, want)), f"{label} {what} != jnp")
    wf = jax.random.normal(key, w.shape) / math.sqrt(w.size / w.shape[-1])
    hi = dense(s, wf, precision=jax.lax.Precision.HIGHEST)
    d_kernel = float(jnp.abs(kernel(s, wf) - hi).max())
    d_default = float(jnp.abs(dense(s, wf) - hi).max())
    print(f"kernels: {label} fwd+vjp equal to jnp on grid operands; "
          f"f32 weights: max|kernel-highest|={d_kernel} "
          f"max|jnp_default-highest|={d_default}")


def phase_kernels() -> None:
    blocks = snn.KERNEL_BLOCKS
    mlp = workloads.get("mnist-mlp").build(25, POPULATION)
    conv = workloads.get("dvs-conv").build(16, POPULATION)
    keys = iter(jax.random.split(jax.random.key(0), 64))

    # Dense: spike_gemm_train at each registered mnist-mlp layer
    k_in = math.prod(mlp.input_shape)
    for spec in mlp.layers:
        _oracle(f"spike_gemm 64x{k_in}x{spec.features}",
                lambda s, w: ops.spike_gemm_train(s, w, **blocks),
                lambda s, w, precision=None: jnp.dot(s, w,
                                                     precision=precision),
                _sparse_spikes(next(keys), (64, k_in)),
                _grid(next(keys), (k_in, spec.features)),
                _grid(next(keys), (64, spec.features)), next(keys))
        k_in = spec.features

    # Conv: spike_conv_train at each registered dvs-conv conv layer
    shape = conv.input_shape
    for spec, nxt in zip(conv.layers, snn.output_shapes(conv)):
        if isinstance(spec, snn.Conv):
            h, w_, c = shape

            def kernel(s, w, spec=spec):
                return ops.spike_conv_train(s, w, stride=spec.stride,
                                            padding=spec.padding, **blocks)

            def dense(s, w, precision=None, spec=spec):
                return jax.lax.conv_general_dilated(
                    s, w, (spec.stride, spec.stride), spec.padding,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=precision)

            _oracle(f"spike_conv 64x{h}x{w_}x{c}->{nxt}", kernel, dense,
                    _sparse_spikes(next(keys), (64, h, w_, c)),
                    _grid(next(keys), (spec.kernel, spec.kernel, c,
                                       spec.features)),
                    _grid(next(keys), (64,) + nxt), next(keys))
        shape = nxt


# ---------------------------------------------------------------------------
# cells through TraceCache.resolve
# ---------------------------------------------------------------------------

def resolve_cell(name: str, T: int, backend: str, ref_counts=None) -> list:
    """Train one registered cell through ``TraceCache.resolve`` on a fresh
    root; returns its per-layer trace counts."""
    wl = dataclasses.replace(workloads.get(name), matmul_backend=backend)
    cfg = wl.build(T, POPULATION)
    data = wl.make_data(T)
    x0 = jnp.asarray(data.x_train[:wl.batch_size])
    y0 = jnp.asarray(data.y_train[:wl.batch_size])
    tx = optim.adam(wl.lr)
    params0, opt_state0, key0 = train_snn.init_cell(cfg, tx, 0)
    if backend != "jnp":
        step = jax.jit(train_snn.make_train_step(cfg, tx, backend))
        text = step.lower(params0, opt_state0, key0, x0, y0).as_text()
        check("tpu_custom_call" in text,
              f"{name} {backend}: the train step holds no compiled kernel")

    res: dict = {}
    with tempfile.TemporaryDirectory() as root, timed(res):
        art = workloads.TraceCache(root=root).resolve(
            wl, {"num_steps": T, "population": POPULATION})
    check(not art.cache_hit, f"{name} {backend}: fresh root gave a hit")

    loss = jax.jit(lambda p: train_snn.loss_fn(
        cfg, p, jax.random.key(0), x0, y0, matmul_backend=backend))
    init_loss, final_loss = float(loss(params0)), float(loss(art.params))
    counts = [np.asarray(c) for c in art.counts]
    S = min(wl.trace_samples, wl.n_test)
    check(all(c.shape == (T, S) and np.isfinite(c).all() for c in counts),
          f"{name} {backend}: traces not finite (T, S) arrays")
    check(math.isfinite(final_loss), f"{name} {backend}: loss {final_loss}")
    chance = 1.0 / wl.num_classes
    check(art.accuracy > 1.5 * chance,
          f"{name} {backend}: accuracy {art.accuracy} at chance ({chance})")
    diff = ("n/a" if ref_counts is None else
            max(float(np.abs(c - r).max()) for c, r in zip(counts,
                                                           ref_counts)))
    print(f"cell {name} T={T} p={POPULATION} backend={backend}: "
          f"wall_seconds={res['wall_seconds']} "
          f"compile_seconds={res['compile_seconds']} "
          f"init_loss={init_loss} final_loss={final_loss} "
          f"accuracy={art.accuracy} "
          f"max_trace_diff_vs_jnp={diff} "
          f"trace_spikes={[float(c.sum()) for c in counts]}")
    return counts


def phase_cells(name: str, T: int, backends) -> None:
    ref = None
    for backend in backends:
        counts = resolve_cell(name, T, backend, ref_counts=ref)
        if backend == "jnp":
            ref = counts


# ---------------------------------------------------------------------------
# study: explore over a T x population grid, stacked in-process
# ---------------------------------------------------------------------------

def phase_study() -> None:
    res: dict = {}
    with tempfile.TemporaryDirectory() as root, timed(res):
        cache = workloads.TraceCache(root=root)
        study = dse.explore(workload="mnist-mlp", num_steps=(15, 25),
                            population=(1.0, POPULATION), max_lhr=4,
                            cache=cache, stack=True, workers=0)
    summary = study.summary
    frontier = study.frontier
    print(f"study: wall_seconds={res['wall_seconds']} "
          f"compile_seconds={res['compile_seconds']} summary={summary}")
    check(summary["cells_resolved"] == 4 and not summary["cells_skipped"],
          f"study resolved {summary['cells_resolved']} of 4 cells")
    # every cell trained by the stack (farmed_misses); none by the serial
    # path that picks up failed outcomes (cache misses)
    check(summary["cache"]["farmed_misses"] == 4,
          f"stack trained {summary['cache']['farmed_misses']} of 4 cells")
    check(summary["cache"]["misses"] == 0,
          f"{summary['cache']['misses']} cell(s) trained by a fallback route")
    check(len(frontier) > 0, "empty frontier")
    for k in study.objectives:
        check(bool(np.isfinite(np.asarray(frontier.columns[k],
                                          np.float64)).all()),
              f"frontier column {k} not finite")


# ---------------------------------------------------------------------------
# four chips: the stacked slab on the "cells" mesh against one chip
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _unsharded():
    """Train slabs on one device: ``stack_mesh`` is the only seam."""
    real = cellstack.stack_mesh
    cellstack.stack_mesh = lambda n_cells: None
    try:
        yield
    finally:
        cellstack.stack_mesh = real


def _slab(jobs, root: str, label: str):
    stats: dict = {}
    res: dict = {}
    with timed(res):
        outs = cellstack.resolve_stacked(jobs, root, stats=stats)
    check(all(o.trained and o.error is None for o in outs),
          f"{label}: a cell did not train")
    cache = workloads.TraceCache(root=root)
    arts = [cache.resolve(j.workload, j.assignment, seed=j.seed)
            for j in jobs]
    check(all(a.cache_hit for a in arts), f"{label}: published cell missed")
    print(f"slab {label}: devices={stats['devices']} "
          f"wall_seconds={res['wall_seconds']} "
          f"compile_seconds={res['compile_seconds']} "
          f"accuracy={[a.accuracy for a in arts]}")
    return stats["devices"], arts


def phase_mesh(chips: int) -> None:
    wl = workloads.get("mnist-mlp")
    asn = {"num_steps": 25, "population": POPULATION}
    jobs = [cellfarm.CellJob(workload=wl, assignment=asn, seed=s)
            for s in range(8)]
    with tempfile.TemporaryDirectory() as root:
        n_mesh, sharded = _slab(jobs, os.path.join(root, "mesh"),
                                f"{chips}-chip mesh")
        with _unsharded():
            n_one, single = _slab(jobs, os.path.join(root, "one"),
                                  "one chip")
    check(n_mesh == chips, f"mesh slab lived on {n_mesh} devices")
    check(n_one == 1, f"unsharded slab lived on {n_one} devices")
    p_diff = max(float(np.abs(np.asarray(a, np.float64)
                              - np.asarray(b, np.float64)).max())
                 for x, y in zip(sharded, single)
                 for a, b in zip(jax.tree.leaves(x.params),
                                 jax.tree.leaves(y.params)))
    t_diff = max(float(np.abs(a - b).max())
                 for x, y in zip(sharded, single)
                 for a, b in zip(x.counts, y.counts))
    acc_equal = [x.accuracy == y.accuracy for x, y in zip(sharded, single)]
    check(math.isfinite(p_diff) and math.isfinite(t_diff),
          "non-finite params or traces")
    print(f"mesh vs one chip: bit_identical={p_diff == 0 and t_diff == 0} "
          f"max_param_diff={p_diff} max_trace_diff={t_diff} "
          f"accuracy_equal={acc_equal}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the stacked slab on a 4-chip mesh "
                         "against one chip")
    args = ap.parse_args()
    runtime.enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    device = device_check(args.chips)
    phases = ([("mesh", lambda: phase_mesh(args.chips))] if args.chips > 1
              else [("kernels", phase_kernels),
                    ("mlp", lambda: phase_cells(
                        "mnist-mlp", 25, snn.MATMUL_BACKENDS)),
                    ("conv", lambda: phase_cells(
                        "dvs-conv", 16, ("jnp", "spike_gemm"))),
                    ("study", phase_study)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        print(f"phase {name}: ok in {time.perf_counter() - t0} s",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
