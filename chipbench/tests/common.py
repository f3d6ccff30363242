"""Shared set-up of the correctness checks: a cell's files, optionally
cut to a size a test run on the CPU can hold, and one harness run."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# A test-sized store: the cell's own traffic and settings over fewer
# keys and, where the configuration has them, fewer range deletes in the
# preload's history, all shards on the default device.
SMALL = {"preload_keys": 40_000, "key_region_bits": 19, "devices": 0}
SMALL_HISTORY = 512


def load_cell(name: str, small: bool):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    with open(os.path.join(BENCH_DIR, "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if small:
        store = config["store"]
        store.update(SMALL)
        if store.get("preload_range_deletes"):
            store["preload_range_deletes"] = SMALL_HISTORY
        traffic["warmup_requests"] = 4
    return bench, cell, config, traffic


def run_once(name: str, seed: int, seconds: float, small: bool = True,
             engine=None):
    """One run of the harness after its look for a chip; ``engine``, if
    given, is built in the program's place."""
    import jax
    import run
    import store
    bench, cell, config, traffic = load_cell(name, small)
    orig = store.build_engine
    if engine is not None:
        store.build_engine = lambda _store: engine()
    try:
        return run.run_cell(bench, cell, config, traffic, seed=seed,
                            seconds=seconds, trace=False,
                            devices=jax.devices()[:1])
    finally:
        store.build_engine = orig
