"""The profiler trace and its reduction to busy, idle and kernel
seconds.

The traced run wraps its first round in ``bench.traced`` and each
analysis and reset in ``bench.*`` annotations
(``jax.profiler.TraceAnnotation``, recorded on the host plane).  The
reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- busy: the union of the device's op intervals inside ``bench.traced``,
  averaged over the chips;
- idle gaps: the holes in that union, each named by the innermost
  ``bench.*`` annotation open at its midpoint;
- module seconds: the summed device durations of each XLA module.
"""

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.traced"
PREFIX = "bench."
TOP = 10

Interval = Tuple[int, int, str]  # start ns, end ns, name


def load(path: str) -> dict:
    """The events the reduction needs, as plain lists."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
            devices.append({"plane": plane.name, "ops": lines[OPS_LINE],
                            "modules": lines[MODULES_LINE]})
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name.startswith(PREFIX)]
    return {"devices": devices, "host": host}


def _union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for start, end, _ in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _holder(host: List[Interval], moment: float) -> str:
    open_now = [h for h in host
                if h[0] <= moment < h[1] and h[2] != WINDOW]
    return max(open_now)[2] if open_now else "no annotation"


def reduce(events: dict) -> dict:
    devices, host = events["devices"], events["host"]
    windows = [h for h in host if h[2] == WINDOW]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    else:
        spans = [iv for d in devices for iv in d["ops"]] + host
        lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    busy, gaps = [], []
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    for device in devices:
        union = _union(device["ops"], lo, hi)
        busy.append(sum(e - s for s, e in union))
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for start, end in zip(edges[::2], edges[1::2]):
            if end > start:
                gaps.append((end - start,
                             _holder(host, (start + end) / 2)))
        for start, end, name in device["ops"]:
            if lo <= start < hi:
                # "%while.20 = (s8[32,4097]...) while(...)": the op's name
                ops[name.split(" = ")[0]] += (end - start) / len(devices)
        for start, end, name in device["modules"]:
            if lo <= start < hi:
                modules[re.sub(r"\(\d+\)$", "", name)] += (
                    (end - start) / len(devices))
    count = max(1, len(devices))
    window_ns = hi - lo
    busy_ns = sum(busy) / count
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1 - busy_ns / window_ns,
        "chips": len(devices),
        "module_s": {name: ns / 1e9 for name, ns in modules.items()},
        "top_ops": [[name, ns / 1e9] for name, ns in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, ns / 1e9] for ns, name in
                      sorted(gaps, reverse=True)[:TOP]],
    }


class Profiler:
    """``jax.profiler`` over the traced round; the trace is written to
    a directory under ``TMPDIR`` and removed once it is reduced."""

    def __init__(self):
        self.directory = None

    def annotate(self, name: str):
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    def start(self) -> None:
        import jax

        self.directory = tempfile.mkdtemp(prefix="bench-trace-")
        # no Python tracer: it records every call of the pure-Python VM,
        # which made a traced analysis 2.5 times as long (PR 22)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> dict:
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.directory, "**",
                                           "*.xplane.pb"), recursive=True)
            return reduce(load(max(paths, key=os.path.getmtime)))
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
