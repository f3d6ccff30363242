"""The comparison that decides ``correct`` passes sound runs and fails
the control and each fault the cells can have.

Run with ``python -m pytest chipbench/tests`` (on the CPU:
``JAX_PLATFORMS=cpu``).  The store is cut to a test size (``SMALL`` in
``common.py``); the traffic is each cell's own.
"""

from __future__ import annotations

import pytest

from common import run_once
from fakes import ControlEngine, install_fault

CELLS = ["fig9-lookup-typed", "fig9-balanced-mixed", "ycsb-a-zipf"]
SEED = 2**31 + 11


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_once(cell, SEED, 1.0)
    assert out["correct"] and out["failed"] == 0
    assert out["compared"] == {
        "wrong_answers": {"value": 0, "limit": 0},
        "window_compiles": {"value": 0, "limit": 0}}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_control_lag_is_not_correct(cell):
    out = run_once(cell, SEED, 1.0, engine=lambda: ControlEngine("lag"))
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell", ["fig9-lookup-typed",
                                  "fig9-balanced-mixed"])
def test_control_no_rdel_is_not_correct(cell):
    out = run_once(cell, SEED, 1.0,
                   engine=lambda: ControlEngine("no_rdel"))
    assert not out["correct"]


@pytest.mark.parametrize("fault", ["writes_dropped", "half_batch",
                                   "answer_altered", "shard_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    install_fault(monkeypatch, fault)
    out = run_once(cell, SEED, 0.5)
    assert not out["correct"]
    assert out["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_compile_in_window_is_not_correct(cell, monkeypatch):
    install_fault(monkeypatch, "window_compile")
    out = run_once(cell, SEED, 0.5)
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert out["compared"]["window_compiles"]["value"] > 0
