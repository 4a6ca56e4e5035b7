"""One run of one cell: set-up, the measured window, the traced slice, the
check of what the window produced, and the result line.

The window drives the program's own cell-resolution path,
``TraceCache.resolve``, one cell at a time.  Each cell is trained from a
fresh seed drawn from the run's seed, evaluated, traced and published to a
``TraceCache`` rooted in a temporary directory, so every cell is a miss.
Set-up resolves one cell of the same shapes from a seed of its own, which
compiles and loads every program the window runs.  The window then runs
whole cells until ``seconds`` have passed and ends when the last one is
published.

The per-step training losses of each cell, which ``train_snn.train``
returns and ``TraceCache`` drops after training, are kept by cell seed
(``Losses``) for the check.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import sys
import tempfile
import time
import traceback

import numpy as np

import catalog as catalog_mod
import devtrace
import verdict
import work

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the benchmark's own host span around each call into the program
RESOLVE_SPAN = "bench.resolve"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print("chipbench:", *parts, file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}; the "
                     f"benchmark runs on a TPU only")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def build_workload(config: dict, traffic: dict):
    """The configuration as the program's ``Workload``, with the traffic's
    matmul backend."""
    from repro.core import snn, workloads
    wl = config["workload"]
    layers = []
    for spec in wl["layers"]:
        if spec[0] == "dense":
            layers.append(snn.Dense(spec[1]))
        elif spec[0] == "conv":
            layers.append(snn.Conv(spec[1], spec[2], spec[3], spec[4]))
        else:
            layers.append(snn.MaxPool(spec[1]))
    return workloads.Workload(
        name=config["name"], dataset=wl["dataset"],
        input_shape=tuple(wl["input_shape"]), layers=tuple(layers),
        num_classes=wl["num_classes"], pcr=wl["pcr"],
        encoding=wl["encoding"], num_steps_choices=(config["num_steps"],),
        population_choices=(config["population"],), n_train=wl["n_train"],
        n_test=wl["n_test"], data_seed=wl["data_seed"], noise=wl["noise"],
        train_steps=wl["train_steps"], batch_size=wl["batch_size"],
        lr=wl["lr"], trace_samples=wl["trace_samples"],
        matmul_backend=traffic["backend"])


@dataclasses.dataclass
class Route:
    """Resolves one cell through the program's entry; returns 1 where it
    failed, else 0."""
    workload: object
    assignment: dict
    traffic: dict
    cache: object

    def resolve(self, seed: int) -> int:
        if self.traffic["route"] != "solo":
            raise ValueError(f"unknown route {self.traffic['route']!r}")
        art = self.cache.resolve(self.workload, self.assignment, seed=seed,
                                 quant_bits=())
        return int(art.cache_hit)


class Losses:
    """The per-step losses of every cell trained while ``kept`` is open, by
    cell seed: ``TrainResult.train_loss`` of ``train_snn.train``, the
    trainer that ``TraceCache.resolve`` calls, which returns them and whose
    caller drops them.  The trainer runs unchanged; its result is read on
    the way out."""

    def __init__(self):
        self.by_seed: dict[int, list[float]] = {}

    @contextlib.contextmanager
    def kept(self):
        from repro.core import train_snn
        train = train_snn.train

        def keep(*args, **kwargs):
            res = train(*args, **kwargs)
            self.by_seed[int(kwargs.get("seed", 0))] = list(res.train_loss)
            return res

        train_snn.train = keep
        try:
            yield self
        finally:
            train_snn.train = train


class Compiles:
    """XLA compile seconds (a compile served by the persistent cache counts
    its load), split into set-up and window."""

    def __init__(self):
        self.seconds = {"setup": 0.0, "window": 0.0, "check": 0.0}
        self.phase = "setup"

    def __call__(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.seconds[self.phase] += duration


def cell_seeds(seed: int):
    """Fresh cell seeds drawn from the run's seed: set-up's first, then the
    window's, the same sequence for the same seed."""
    rng = np.random.default_rng(int(seed))
    while True:
        yield int(rng.integers(0, 2 ** 31 - 1))


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, cat: catalog_mod.Catalog = None,
        require_tpu: bool = True, trace_dir: str = None) -> dict:
    """One run of the cell; returns the result object."""
    cat = cat or catalog_mod.Catalog()
    spec = cat.cell(workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    device = device_info(cell["chips"], require_tpu)
    peak = cat.peaks(device["kind"]) if require_tpu else None

    import jax
    from repro import runtime
    if require_tpu:
        runtime.enable_compile_cache()
        # keep every program set-up compiles: JAX keeps only those over 1 s
        # by default, and the train step each cell re-jits compiles in less,
        # so a window cell would compile it or load it by the cache's history
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(spec, seed, seconds, trace, t_start, cat, device, peak,
                    compiles, trace_dir)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(spec, seed, seconds, trace, t_start, cat, device, peak, compiles,
         trace_dir) -> dict:
    import jax
    from repro.core import workloads
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]

    wl = build_workload(config, traffic)
    assignment = {"num_steps": config["num_steps"],
                  "population": config["population"]}
    seeds = cell_seeds(seed)
    with contextlib.ExitStack() as stack:
        root = stack.enter_context(tempfile.TemporaryDirectory(
            prefix="chipbench-cells-"))
        route = Route(wl, assignment, traffic, workloads.TraceCache(root))
        losses = stack.enter_context(Losses().kept())

        if route.resolve(next(seeds)):
            raise RuntimeError("set-up: the warm-up cell failed")
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s, compile {compiles.seconds['setup']:.3f}"
            f" s")

        compiles.phase = "window"
        done, attempted, failed, published, cell_s = 0, 0, 0, [], []
        traced = None
        if trace:
            tdir = trace_dir or stack.enter_context(
                tempfile.TemporaryDirectory(prefix="chipbench-trace-"))
        t0 = time.perf_counter()
        while True:
            cell_seed = next(seeds)
            tracing = trace and traced is None and done > 0
            if tracing:
                jax.profiler.start_trace(tdir)
                u0 = time.perf_counter()
            attempted += 1
            c0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(RESOLVE_SPAN):
                    bad = route.resolve(cell_seed)
            except Exception:                          # noqa: BLE001
                traceback.print_exc(file=sys.stderr)
                bad = 1
            if tracing:
                jax.profiler.stop_trace()
                traced = time.perf_counter() - u0
                traced_cells = 1 - bad
            cell_s.append(time.perf_counter() - c0)
            failed += bad
            done += 1 - bad
            if not bad:
                published.append(cell_seed)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds and (not trace or traced is not None):
                break
        compiles.phase = "check"
        mem = memory_peak_bytes(cell["chips"])
        log(f"window {elapsed:.3f} s, {done} cells, compile "
            f"{compiles.seconds['window']:.3f} s, memory peak {mem}")
        log("cell seconds " + " ".join(f"{c:.3f}" for c in cell_s))

        trace_path = None
        if trace:
            paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            trace_path = max(paths, key=os.path.getmtime) if paths else None

        # the rate the window ran at outside the traced cell, whose start,
        # stop and dump of the profiler slow it
        untraced = (elapsed - traced, done - traced_cells) if trace else (
            elapsed, done)
        record = {
            "cells": done, "window_s": elapsed, "chips": cell["chips"],
            "untraced_s": untraced[0], "untraced_cells": untraced[1],
            "compile_s": compiles.seconds["window"],
            "train_steps": config["workload"]["train_steps"],
            "step_flops": work.train_step_flops(
                config["workload"], config["num_steps"],
                config["population"]),
            "peak": peak, "config": config, "traffic": traffic,
            "trace": None,
        }
        if trace_path:
            tr = devtrace.load(trace_path)
            # the traced slice is the traced cell's resolve span
            spans = devtrace.host_spans(tr, {RESOLVE_SPAN})
            record["trace"] = devtrace.reduce(
                tr, cell["chips"],
                window_ns=spans[0][1:] if len(spans) == 1 else None,
                span_names=(RESOLVE_SPAN,))
            record["trace"]["slice_s"] = traced

        rng = np.random.default_rng([int(seed), 1])
        sample = sorted(rng.choice(len(published),
                                   size=min(traffic["check_cells"],
                                            len(published)),
                                   replace=False)) if published else []
        checks = verdict.check_cells(
            route.cache, wl, assignment, config,
            [published[i] for i in sample], losses.by_seed)

    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            value = cat.metric(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"cells_per_min": done * 60.0 / elapsed, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = mem
    if trace and record["trace"]:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    result = {"correct": checks["correct"] and failed == 0 and done > 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and record["trace"]:
        result["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"]}
    result["checks"] = checks["numbers"]
    return result
