"""The idle account: which program span held each idle device second.

Reads the same ``.xplane.pb`` as ``devtrace.py``, whose window and busy
intervals it shares: the window is the span of the benchmark's own
``bench.*`` host annotations, and a device is busy where an XLA op runs
on its plane.  Every idle instant of the window is charged to a class:

  1. if some shard lines have a program span open, the instant is split
     equally among them, each share going to the class of that line's
     innermost program span: ``kernel.*`` -> ``dispatch``,
     ``registry.*`` -> ``registry``, any other -> ``shard_host``;
  2. otherwise, if the driver line's innermost program span is
     ``plan.*`` or ``engine.*``, to ``planner``;
  3. otherwise to ``none``.

A host line (one thread) is a shard line if it holds a ``shard.plan``
span and a driver line if it holds an ``engine.submit`` span.  Program
spans are the ``repro.obs`` spans that a recording ``Tracer`` mirrors
into the profiler's trace: dot-namespaced lower-case names other than
``bench.*``.  With several device planes the account is taken per
plane and averaged, as ``devtrace`` averages busy time; with none (a
run on the CPU) the whole window is idle.

    python chipbench/idle_account.py <trace dir>

prints the account of the trace under ``<trace dir>`` (as ``run.py
--trace 1 --trace-dir`` leaves it), with ``devtrace``'s busy time and
window beside it, as one JSON line.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

import devtrace

CLASSES = ("shard_host", "registry", "dispatch", "planner", "none")
PROGRAM = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+")


def _program(name: str) -> bool:
    return bool(PROGRAM.fullmatch(name)) and not name.startswith("bench.")


def _shard_class(name: str) -> str:
    if name.startswith("kernel."):
        return "dispatch"
    if name.startswith("registry."):
        return "registry"
    return "shard_host"


def _host_lines(planes):
    """``(shard line?, driver line?, spans)`` per host line that holds
    program spans; spans sorted outer-first, as ``(start, end, name)``."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if _program(ev.name):
                    a = int(ev.start_ns)
                    spans.append((a, a + int(ev.duration_ns), ev.name))
            if spans:
                names = {n for _, _, n in spans}
                spans.sort(key=lambda s: (s[0], -s[1]))
                out.append(("shard.plan" in names, "engine.submit" in names,
                            spans))
    return out


def _charge(lines, busy, lo: int, hi: int):
    """Seconds per class and per innermost span over the idle instants
    of ``[lo, hi)`` outside the disjoint ``busy`` intervals."""
    bounds = []
    for li, (_, _, spans) in enumerate(lines):
        for k, (a, b, _) in enumerate(spans):
            if b > lo and a < hi:
                bounds.append((max(a, lo), 1, li, k))
                bounds.append((min(b, hi), 0, li, k))
    for a, b in busy:
        bounds.append((a, 2, -1, 1))
        bounds.append((b, 2, -1, -1))
    bounds.append((hi, 2, -1, 0))
    bounds.sort()
    shard = [li for li, (s, _, _) in enumerate(lines) if s]
    driver = [li for li, (_, d, _) in enumerate(lines) if d]
    # Open spans per line, innermost last: (start, index, name).
    stacks: list[list] = [[] for _ in lines]
    classes = dict.fromkeys(CLASSES, 0.0)
    by_span: dict = defaultdict(float)
    in_busy = 0
    prev = lo
    for t, kind, li, k in bounds:
        if t > prev and not in_busy:
            dt = (t - prev) * 1e-9
            held = [stacks[i][-1] for i in shard if stacks[i]]
            if held:
                for _, _, name in held:
                    classes[_shard_class(name)] += dt / len(held)
                    by_span[name] += dt / len(held)
            else:
                name = max((stacks[i][-1] for i in driver if stacks[i]),
                           default=(0, 0, ""))[2]
                if name.startswith(("plan.", "engine.")):
                    classes["planner"] += dt
                    by_span[name] += dt
                else:
                    classes["none"] += dt
        prev = max(prev, t)
        if kind == 2:
            in_busy += k
        elif kind == 1:
            a, _, name = lines[li][2][k]
            stacks[li].append((a, k, name))
        else:
            st = stacks[li]
            for j in range(len(st) - 1, -1, -1):
                if st[j][1] == k:
                    del st[j]
                    break
    return classes, by_span


def account(planes) -> dict:
    """The idle account of a trace's window: ``window_s``, ``busy_s``,
    ``chips``, ``classes`` (idle seconds per class) and ``spans`` (idle
    seconds per innermost span name, most first), averaged over the
    device planes."""
    planes = list(planes)
    annotations = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        a = int(ev.start_ns)
                        annotations.append((a, a + int(ev.duration_ns)))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            by_name = {line.name: line for line in plane.lines}
            ops = by_name["XLA Ops"].events if "XLA Ops" in by_name else []
            devices.append([(int(e.start_ns),
                             int(e.start_ns) + int(e.duration_ns))
                            for e in ops])
    if not annotations:
        raise ValueError("the trace holds no bench.* host annotation")
    lo = min(a for a, _ in annotations)
    hi = max(b for _, b in annotations)
    lines = _host_lines(planes)
    n = max(len(devices), 1)
    classes = dict.fromkeys(CLASSES, 0.0)
    spans: dict = defaultdict(float)
    busy_s = 0.0
    for ops in devices or [[]]:
        busy = devtrace._union(devtrace._clip(ops, lo, hi))
        busy_s += sum(b - a for a, b in busy) * 1e-9 / n
        c, s = _charge(lines, busy, lo, hi)
        for k, v in c.items():
            classes[k] += v / n
        for k, v in s.items():
            spans[k] += v / n
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_s,
            "chips": len(devices), "classes": classes,
            "spans": sorted(spans.items(), key=lambda kv: -kv[1])}


def shares(acc: dict) -> dict:
    """The per-layer readings of an account, in % of the window."""
    w = acc["window_s"]
    c = acc["classes"]
    return {"idle_shard_host_share": 100.0 * c["shard_host"] / w,
            "idle_planner_share": 100.0 * c["planner"] / w,
            "idle_dispatch_share": 100.0 * c["dispatch"] / w}


def main(argv=None) -> int:
    import jax
    (log_dir,) = argv if argv is not None else sys.argv[1:]
    planes = list(jax.profiler.ProfileData.from_file(
        devtrace.find_xplane(log_dir)).planes)
    summary = devtrace.summarize(devtrace.reduce_planes(planes))
    acc = account(planes)
    acc["spans"] = acc["spans"][:5]
    print(json.dumps({"account": acc, "shares": shares(acc),
                      "devtrace": {k: summary[k]
                                   for k in ("window_s", "busy_s")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
