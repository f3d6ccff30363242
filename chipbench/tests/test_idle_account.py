"""The idle account (``idle_account.py``): its rules on synthetic
planes, its agreement with ``devtrace`` on the recorded slice, and its
sum over a traced run of a cell at test size on the CPU."""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

import devtrace
import idle_account
from common import load_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cascade_slice.xplane.pb")
US = 1e-6


def _line(name, *events):
    return NS(name=name, events=[NS(name=n, start_ns=a * 1000,
                                    duration_ns=(b - a) * 1000)
                                 for n, a, b in events])


def _planes(*busy):
    """A window of 1,000 us; a driver line and two shard lines; one TPU
    plane per entry of ``busy`` (its op intervals, in us)."""
    host = NS(name="/host:CPU", lines=[
        _line("python", ("bench.window", 0, 1000),
              ("engine.submit", 0, 300), ("plan.compile", 0, 250),
              ("engine.collect", 700, 800), ("serve.decode", 850, 900)),
        _line("python", ("shard.plan", 200, 600),
              ("kernel.cascade", 300, 400), ("bench.cascade", 300, 400),
              ("PjitFunction(cascade_flat)", 310, 390)),
        _line("python", ("shard.plan", 350, 500),
              ("registry.pack", 350, 450)),
    ])
    devs = [NS(name=f"/device:TPU:{i}",
               lines=[_line("XLA Ops", *[("op", a, b) for a, b in ops])])
            for i, ops in enumerate(busy)]
    return [host] + devs


def test_rules_and_their_order():
    acc = idle_account.account(_planes([(100, 200)]))
    want = {"planner": 200, "shard_host": 275, "dispatch": 75,
            "registry": 50, "none": 300}
    assert acc["chips"] == 1
    assert acc["window_s"] == pytest.approx(1000 * US)
    assert acc["busy_s"] == pytest.approx(100 * US)
    for k, v in want.items():
        assert acc["classes"][k] == pytest.approx(v * US, abs=1e-12), k
    spans = dict(acc["spans"])
    assert spans["plan.compile"] == pytest.approx(100 * US)
    assert spans["engine.collect"] == pytest.approx(100 * US)
    assert spans["kernel.cascade"] == pytest.approx(75 * US)
    assert "serve.decode" not in spans and "bench.cascade" not in spans
    s = idle_account.shares(acc)
    assert s["idle_shard_host_share"] == pytest.approx(27.5)
    assert s["idle_planner_share"] == pytest.approx(20.0)
    assert s["idle_dispatch_share"] == pytest.approx(7.5)


def test_chips_are_averaged():
    one = idle_account.account(_planes([(100, 200)]))
    two = idle_account.account(_planes([(100, 200)], [(0, 1000)]))
    assert two["chips"] == 2
    assert two["busy_s"] == pytest.approx((100 + 1000) / 2 * US)
    for k, v in one["classes"].items():
        assert two["classes"][k] == pytest.approx(v / 2, abs=1e-12), k


def test_recorded_slice_charges_none_and_shares_busy_time():
    import jax
    planes = list(jax.profiler.ProfileData.from_file(DATA).planes)
    red = devtrace.reduce_planes(planes)
    acc = idle_account.account(planes)
    assert acc["busy_s"] == red["devices"][0]["busy_s"]
    assert acc["window_s"] == red["window_s"]
    idle = acc["window_s"] - acc["busy_s"]
    assert acc["classes"]["none"] == pytest.approx(idle, abs=1e-12)
    assert sum(acc["classes"].values()) == pytest.approx(idle, abs=1e-12)


def test_traced_run_on_the_cpu_sums_to_the_idle_window(tmp_path):
    """A traced run of ``fig9-lookup-typed`` at test size: the program's
    spans reach the trace, and the classes sum to the window (the CPU
    run has no device plane, so all of it is idle) within 1 us."""
    import jax
    import run
    bench, cell, config, traffic = load_cell("fig9-lookup-typed", True)
    out = run.run_cell(bench, cell, config, traffic, seed=2**31 + 11,
                       seconds=1.0, trace=True, devices=jax.devices()[:1],
                       trace_dir=str(tmp_path))
    assert out["correct"]
    assert "plan_wait_ms_per_req" in out["metrics"]
    planes = list(jax.profiler.ProfileData.from_file(
        devtrace.find_xplane(str(tmp_path))).planes)
    acc = idle_account.account(planes)
    assert acc["chips"] == 0 and acc["busy_s"] == 0.0
    assert sum(acc["classes"].values()) == pytest.approx(
        acc["window_s"] - acc["busy_s"], abs=1e-6)
    assert acc["classes"]["shard_host"] > 0
    assert acc["classes"]["planner"] > 0
    names = dict(acc["spans"])
    assert {"shard.plan", "plan.compile"} <= set(names)
