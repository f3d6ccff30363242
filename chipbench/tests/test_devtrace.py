"""Self-check of the trace reduction on a recorded trace.

``data/cascade_slice.xplane.pb`` is a 60 ms slice of a traced window of
``fig9-lookup-typed`` on one TPU v5e: the ``/device:TPU:0`` plane's
events and the benchmark's ``bench.*`` host annotations that lie wholly
inside the slice.  The test recomputes busy time, kernel time and idle
time by a second, plain method and pins the numbers read once by hand.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cascade_slice.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    import jax
    return list(jax.profiler.ProfileData.from_file(DATA).planes)


def _events(planes, plane_name, line_name):
    for p in planes:
        if p.name == plane_name:
            for line in p.lines:
                if line.name == line_name:
                    return [(int(e.start_ns), int(e.duration_ns), e.name)
                            for e in line.events]
    return []


def test_busy_idle_and_kernel_time(planes):
    red = devtrace.reduce_planes(planes)
    (dev,) = red["devices"]
    ann = [e for p in planes if p.name == "/host:CPU" for line in p.lines
           for e in ((int(e.start_ns), int(e.duration_ns), e.name)
                     for e in line.events) if e[2].startswith("bench.")]
    lo = min(a for a, _, _ in ann)
    hi = max(a + d for a, d, _ in ann)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9, abs=1e-12)

    # Busy by coverage counting over the sorted op boundaries.
    ops = _events(planes, "/device:TPU:0", "XLA Ops")
    edges = sorted([(max(a, lo), 1) for a, d, _ in ops]
                   + [(min(a + d, hi), -1) for a, d, _ in ops])
    t = np.array([e[0] for e in edges], np.int64)
    depth = np.cumsum([e[1] for e in edges])
    busy_ns = int(np.sum(np.diff(t)[depth[:-1] > 0]))
    assert dev["busy_s"] == pytest.approx(busy_ns * 1e-9, abs=1e-12)

    idle = sum(s for s, _ in dev["gaps"])
    assert idle + dev["busy_s"] == pytest.approx(red["window_s"],
                                                 abs=1e-12)

    mods = _events(planes, "/device:TPU:0", "XLA Modules")
    assert set(dev["kernels"]) == {"jit_cascade_flat"}
    assert dev["kernels"]["jit_cascade_flat"] == pytest.approx(
        sum(d for _, d, _ in mods) * 1e-9, abs=1e-12)

    # Read once from this file.
    assert len(mods) == 9
    assert red["window_s"] == pytest.approx(0.05425861, abs=1e-9)
    assert dev["busy_s"] == pytest.approx(0.007804656, abs=1e-9)
    assert dev["kernels"]["jit_cascade_flat"] == pytest.approx(
        0.007843343, abs=1e-9)


def test_breakdown(planes):
    s = devtrace.summarize(devtrace.reduce_planes(planes))
    ops = dict(s["breakdown"]["device_ops"])
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert len(ops) == 10 and all(k.startswith("jit_cascade_flat:%")
                                  for k in ops)
    assert set(gaps) == {"bench.collect", "bench.generate", "bench.cascade",
                         "bench.submit"}
    assert gaps["bench.collect"] == pytest.approx(0.027839433, abs=1e-9)
    assert s["busy_s"] + sum(gaps.values()) == pytest.approx(
        s["window_s"], abs=1e-12)
