"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

From the trace it takes, per device plane (``/device:TPU:<n>``):

  busy_s        the union of the intervals in which an XLA op ran,
  window_s      the traced window: the span of the benchmark's own host
                annotations (``bench.*``), which open and close it,
  kernels       device seconds per executable, from the plane's
                ``XLA Modules`` line, keyed by the module name without its
                ``(<id>)`` suffix (``jit_cascade_flat``, ...),
  op_time       device seconds per XLA op, named ``<module>:<op>``
                (``jit_cascade_flat:%while.40``),
  gaps          the idle gaps between busy intervals inside the window,
                each labelled by the innermost ``bench.*`` host annotation
                open at its midpoint (``idle`` when none is).

Host annotations are read from every line of the ``/host:CPU`` plane;
their times share the device planes' clock in the trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


class _Labeller:
    """The innermost (latest-opened) annotation covering a time."""

    def __init__(self, annotations: list[tuple[int, int, str]]):
        self.ann = sorted(annotations)
        self.starts = [a for a, _, _ in self.ann]
        self.longest = max((b - a for a, b, _ in self.ann), default=0)

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] >= t - self.longest:
            if self.ann[i][1] > t:
                return self.ann[i][2]
            i -= 1
        return "idle"


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines``; each
    line has ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    annotations: list[tuple[int, int, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        a = int(ev.start_ns)
                        annotations.append(
                            (a, a + int(ev.duration_ns), ev.name))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            kernels: dict = defaultdict(float)
            modules = []
            for ev in lines.get("XLA Modules", []):
                name = MODULE_ID.sub("", ev.name)
                kernels[name] += ev.duration_ns * 1e-9
                a = int(ev.start_ns)
                modules.append((a, a + int(ev.duration_ns), name))
            modules.sort()
            starts = [a for a, _, _ in modules]
            ops, op_time = [], defaultdict(float)
            for ev in lines.get("XLA Ops", []):
                a = int(ev.start_ns)
                ops.append((a, a + int(ev.duration_ns)))
                i = bisect.bisect_right(starts, a) - 1
                mod = modules[i][2] if i >= 0 and a < modules[i][1] else "?"
                op_time[f"{mod}:{ev.name.split(' = ', 1)[0]}"] += \
                    ev.duration_ns * 1e-9
            devices.append({"plane": plane.name, "ops": ops,
                            "kernels": dict(kernels),
                            "op_time": dict(op_time)})
    if not annotations:
        raise ValueError("the trace holds no bench.* host annotation")
    lo = min(a for a, _, _ in annotations)
    hi = max(b for _, b, _ in annotations)
    label = _Labeller(annotations)
    out = []
    for d in devices:
        busy = _union(_clip(d["ops"], lo, hi))
        gaps = []
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((a - prev, label.at((a + prev) // 2)))
            prev = max(prev, b)
        out.append({"plane": d["plane"],
                    "busy_s": sum(b - a for a, b in busy) * 1e-9,
                    "kernels": d["kernels"], "op_time": d["op_time"],
                    "gaps": [(s * 1e-9, name) for s, name in gaps]})
    return {"window_s": (hi - lo) * 1e-9, "devices": out}


def reduce_file(path: str) -> dict:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)


def summarize(red: dict, top: int = 10) -> dict:
    """Chip-averaged busy time, per-kernel device time summed over chips,
    and the ``breakdown`` of the result line."""
    devs = red["devices"]
    if not devs:
        return {"window_s": red["window_s"], "busy_s": None,
                "kernels": {}, "breakdown": None}
    kernels: dict = defaultdict(float)
    ops: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    for d in devs:
        for k, s in d["kernels"].items():
            kernels[k] += s
        for k, s in d["op_time"].items():
            ops[k] += s
        for s, name in d["gaps"]:
            gaps[name] += s
    n = len(devs)
    busy = sum(d["busy_s"] for d in devs) / n
    by = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gp = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": red["window_s"], "busy_s": busy,
            "chips": n, "kernels": dict(kernels),
            "breakdown": {"device_ops": [[k, s / n] for k, s in by],
                          "idle_gaps": [[k, s / n] for k, s in gp]}}
