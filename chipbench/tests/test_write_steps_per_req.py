"""``write_steps_per_req`` over one traced ``lookup-typed`` WriteBatch
of the cell's own generator and store settings, at test size on the
CPU: about 297 write steps a shard when each plan step is applied on
its own, two or three a shard when the executor fuses the write run."""

from __future__ import annotations

import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from common import load_cell
from generator import OP_PUT, OP_RANGE_DELETE, Request, TrafficGen

SEED = 2**31 + 29


def _writebatch(traffic, store):
    gen = TrafficGen(traffic, store, SEED)
    while True:
        req = gen.next_request()
        if req.cls == "writebatch":
            return gen, req


def _loaded(gen, n):
    """A request of ``n`` updates of preloaded keys, so the memtable
    holds some entries when the WriteBatch arrives."""
    z = np.zeros(n, np.uint64)
    return Request("load", np.full(n, OP_PUT, np.uint8), gen.keys[:n],
                   gen.vals[:n], z, z)


@pytest.mark.parametrize("fused", [False, True])
def test_write_steps_per_writebatch(fused):
    import run
    from repro import obs
    from store import as_batch, build_engine
    _, _, config, traffic = load_cell("fig9-lookup-typed", small=True)
    store = config["store"]
    gen, req = _writebatch(traffic, store)
    eng = build_engine(store)
    eng.submit(as_batch(_loaded(gen, 4096))).get_results()
    if not fused:
        for sh in eng.shards:
            sh._fuses = lambda steps: False
    with obs.enabled() as tr:
        w0 = time.perf_counter()
        eng.submit(as_batch(req)).get_results()
        w1 = time.perf_counter()
    plans = eng.planner.plan(as_batch(req)).shard_plans
    eng.close()
    metric = run.load_module("layer_metrics", "write_steps_per_req")
    got = metric.read(NS(spans=tr.events(), w0=w0, w1=w1,
                         requests=[NS(cls=req.cls)]))
    shards = int(store["shards"])
    assert np.count_nonzero(req.kinds == OP_RANGE_DELETE) == 205
    if fused:
        assert 2 * shards <= got <= 3 * shards
    else:
        # Range deletes reach every hash shard; with the updates between
        # them each shard's plan alternates ~150 runs of each kind.
        assert got == sum(len(sp.steps) for sp in plans)
        assert 250 * shards <= got <= 340 * shards

