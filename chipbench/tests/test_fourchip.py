"""The four-chip cell, ``fig9-lookup-4chip``, at a test size on four
host devices: one shard homed on each, as on the four-chip host.

A sound run comes out correct; the control ``lag`` and the fault
``shard_left_out`` (shard 0's answers, those of the first chip, left
out) do not.  The readers of ``pack_mb_per_chip`` and
``cascade_ms_per_chip_s`` are checked on hand-built runs, including a
run of a program without the gauge or the span's ``device`` field.
"""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from common import load_cell
from fakes import ControlEngine, install_fault
from repro.launch.mesh import ensure_host_devices

# Before JAX's backends start: the CPU's four host devices stand in for
# the host's four chips.
ensure_host_devices(4)

import jax  # noqa: E402

CELL = "fig9-lookup-4chip"
SEED = 2**31 + 16

needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four devices")


def run_four(seed: int, seconds: float, engine=None) -> dict:
    """One harness run of the cell at ``common.SMALL``'s size, with the
    configuration's four home devices kept; ``engine``, if given, is
    built in the program's place."""
    import run
    import store
    bench, cell, config, traffic = load_cell(CELL, small=True)
    config["store"]["devices"] = 4
    orig = store.build_engine
    built = []

    def build(spec):
        eng = engine() if engine is not None else orig(spec)
        built.append(eng)
        return eng
    store.build_engine = build
    try:
        out = run.run_cell(bench, cell, config, traffic, seed=seed,
                           seconds=seconds, trace=False,
                           devices=jax.devices()[:4])
    finally:
        store.build_engine = orig
    out["device_maps"] = [e.device_map() for e in built]
    return out


@needs_four
def test_sound_run_is_correct_with_one_shard_per_device():
    out = run_four(SEED, 1.0)
    assert out["correct"] and out["failed"] == 0
    assert out["compared"] == {
        "wrong_answers": {"value": 0, "limit": 0},
        "window_compiles": {"value": 0, "limit": 0}}
    for homes in out["device_maps"]:
        assert len(set(homes.values())) == 4


@needs_four
def test_control_lag_is_not_correct():
    out = run_four(SEED, 1.0, engine=lambda: ControlEngine("lag"))
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0


@needs_four
def test_shard_left_out_is_not_correct(monkeypatch):
    install_fault(monkeypatch, "shard_left_out")
    out = run_four(SEED, 0.5)
    assert not out["correct"]
    assert out["failed"] > 0


def _reader(name):
    import run
    return run.load_module("layer_metrics", name)


def test_pack_mb_per_chip_reads_the_fullest_device():
    read = _reader("pack_mb_per_chip").read
    run = NS(counters1={"pack_bytes_by_device": {
        "tpu:0": 41_000_000, "tpu:1": 43_500_000, "tpu:2": 0}})
    assert read(run) == pytest.approx(43.5)
    # A program without the gauge, or with no pack built yet.
    assert read(NS(counters1={"cascade_queries": 5})) is None
    assert read(NS(counters1={"pack_bytes_by_device": {}})) is None


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


def test_cascade_ms_per_chip_s_takes_the_busiest_device():
    read = _reader("cascade_ms_per_chip_s").read
    spans = [
        # tpu:0: overlapping calls of two shards and one past the window
        # end: union [1.0, 1.5] + [9.8, 10.0] = 0.7 s.
        _span("kernel.cascade", 1.0, 1.3, device="tpu:0"),
        _span("kernel.cascade", 1.2, 1.5, device="tpu:0"),
        _span("kernel.cascade", 9.8, 10.4, device="tpu:0"),
        # tpu:1: one call that opened before the window: 0.4 s in it.
        _span("kernel.cascade", -0.1, 0.4, device="tpu:1"),
        # Other spans on the device do not count.
        _span("registry.pack", 2.0, 8.0, device="tpu:1", bytes=1),
    ]
    run = NS(spans=spans, w0=0.0, w1=10.0, window_s=10.0)
    assert read(run) == pytest.approx(1e3 * 0.7 / 10.0)
    # One device ("host": every shard on the default device).
    run.spans = [_span("kernel.cascade", 2.0, 3.0, device="host"),
                 _span("kernel.cascade", 2.5, 4.0, device="host")]
    assert read(run) == pytest.approx(1e3 * 2.0 / 10.0)
    # A program whose spans name no device, or no cascade at all.
    run.spans = [_span("kernel.cascade", 2.0, 3.0, n=4)]
    assert read(run) is None
    run.spans = []
    assert read(run) is None
