"""Reduction of a JAX profiler trace to the numbers the metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
data: planes, their lines, and events ``[name, start_ns, duration_ns,
stats]``.  ``reduce`` works on that data only, so the tests check it on a
small recorded trace kept as JSON.

Device planes are named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per operation the chip ran, named by its HLO
text (``%spike_conv.22 = f32[65536,128]{...} custom-call(...)``), a loop
holding the ops of its body; ``XLA Modules`` holds one event per program
execution, named ``<program>(<id>)``.  Busy time is the union of the op
intervals of one chip, averaged over the chips used.

    python3 benchmarks/chip/devtrace.py <trace.xplane.pb> <out.json> [N]

writes the plain data of a trace for reading it by hand: per line its event
count, total seconds, the names that took most time, and its first ``N``
events (default 200), which is also how a slice is kept as test data.
"""
from __future__ import annotations

import json
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: stats kept from device events: what a reader needs and nothing bulky
KEEP_STATS = ("hlo_category", "hlo_module", "long_name", "tf_op",
              "flops", "bytes_accessed")


def _plain(value):
    return value if isinstance(value, (int, float, str)) else str(value)


def load(path: str, all_stats: bool = False) -> dict:
    """The trace at ``path`` as plain data: device events with the stats in
    ``KEEP_STATS`` (every stat with ``all_stats``), host events without."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                stats = {}
                if device:
                    stats = {k: _plain(v) for k, v in dict(e.stats).items()
                             if all_stats or k in KEEP_STATS}
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union_ns(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for start, stop in sorted(intervals):
        if stop <= end:
            continue
        total += stop - max(start, end)
        end = stop
    return total


def _gaps(intervals, lo, hi):
    """Idle intervals of [lo, hi] not covered by any of ``intervals``."""
    gaps, end = [], lo
    for start, stop in sorted(intervals):
        if start > end:
            gaps.append((end, start))
        end = max(end, stop)
    if hi > end:
        gaps.append((end, hi))
    return gaps


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list[dict]:
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def program_name(event_name: str) -> str:
    """``jit_train_step(42)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


#: ops that hold other ops' intervals; left out of the ranking of ops
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%spike_gemm_bwd_dw.34 = f32[...] custom-call(...)`` ->
    ``spike_gemm_bwd_dw``."""
    m = re.match(r"%([\w\-]+?)(?:\.\d+)? = ", event_name)
    return m.group(1) if m else program_name(event_name)


def host_line(trace: dict, names) -> list:
    """The events of the host line that holds a span named in ``names``:
    the thread that called into the program, its Python frames
    (``$cache.py:202 resolve``) among them."""
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if any(e[0] in names for e in line["events"]):
                return line["events"]
    return []


def host_spans(trace: dict, names) -> list[tuple[str, float, float]]:
    """Host events whose name is in ``names``: (name, start_ns, end_ns)."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name in names:
                    out.append((name, start, start + dur))
    return out


def reduce(trace: dict, chips: int, window_ns: tuple[float, float] = None,
           span_names=(), top: int = 10) -> dict:
    """Busy and window seconds (mean over the first ``chips`` chips),
    program executions (of all those chips), the device
    ops that took most time by op name (seconds per chip, loops left out),
    and the longest idle gaps of the first chip, each named by the
    innermost event, on the host line that holds a span in ``span_names``,
    that its midpoint falls in.

    ``window_ns`` bounds the traced slice on the trace's clock; without it
    the slice runs from the first to the last device event.
    """
    planes = device_planes(trace)[:chips]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    ops_by_chip = [_line(p, OPS_LINE) for p in planes]
    if window_ns is None:
        starts = [e[1] for ops in ops_by_chip for e in ops]
        ends = [e[1] + e[2] for ops in ops_by_chip for e in ops]
        if not starts:
            raise ValueError("the trace holds no device op")
        window_ns = (min(starts), max(ends))
    lo, hi = window_ns
    busy, programs, op_time = [], {}, {}
    for plane, ops in zip(planes, ops_by_chip):
        busy.append(_union_ns([(max(s, lo), min(s + d, hi))
                               for _, s, d, _ in ops if s < hi and s + d > lo]))
        for name, start, dur, stats in _line(plane, MODULES_LINE):
            entry = programs.setdefault(program_name(name), [0.0, 0])
            entry[0] += dur * 1e-9
            entry[1] += 1
        for event in ops:
            name = op_name(event[0])
            if name not in CONTAINERS:
                op_time[name] = op_time.get(name, 0.0) + event[2] * 1e-9
    gaps = sorted(_gaps([(s, s + d) for _, s, d, _ in ops_by_chip[0]],
                        lo, hi), key=lambda g: g[0] - g[1])[:top]
    host = host_line(trace, set(span_names))
    names, starts, ends = (np.array([e[0] for e in host], object),
                           np.array([e[1] for e in host]),
                           np.array([e[1] + e[2] for e in host]))
    named = []
    for start, stop in gaps:
        mid = 0.5 * (start + stop)
        inside = np.flatnonzero((starts <= mid) & (ends >= mid))
        name = (names[inside[np.argmin(ends[inside] - starts[inside])]]
                if inside.size else "outside any span")
        named.append([str(name), (stop - start) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "programs": programs,
        "device_ops": sorted(([n, t / len(planes)]
                              for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": named,
    }


def summarize(trace: dict, events: int = 200, top: int = 30) -> dict:
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            by_name = {}
            for name, _, dur, _ in line["events"]:
                entry = by_name.setdefault(name, [name, 0.0, 0])
                entry[1] += dur * 1e-9
                entry[2] += 1
            lines.append({
                "name": line["name"], "count": len(line["events"]),
                "total_s": sum(e[2] for e in line["events"]) * 1e-9,
                "top": sorted(by_name.values(), key=lambda e: -e[1])[:top],
                "events": line["events"][:events]})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def main(argv):
    if len(argv) not in (3, 4):
        sys.exit("usage: devtrace.py <trace.xplane.pb> <out.json> [N]")
    events = int(argv[3]) if len(argv) == 4 else 200
    with open(argv[2], "w") as f:
        json.dump(summarize(load(argv[1], all_stats=True), events), f)


if __name__ == "__main__":
    main(sys.argv)
