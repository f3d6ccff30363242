"""``mem_ms_per_req`` over a traced WriteBatch and MultiGet of the
``ycsb-a-zipf`` cell's own generator and store settings, at test size
on the CPU: it sums the memtable probe and merge spans of the window,
clipped to it, per request; without ``lsm.mem.merge`` spans it counts
the probe alone; with no memtable span or no request it reads None."""

from __future__ import annotations

import time
from types import SimpleNamespace as NS

import pytest

from common import load_cell
from generator import TrafficGen

SEED = 2**31 + 31


def _first(gen, cls):
    while True:
        req = gen.next_request()
        if req.cls == cls:
            return req


def _traced_requests():
    """Spans, window and requests of one WriteBatch, then one
    MultiGet, against a test-size store."""
    from repro import obs
    from store import as_batch, build_engine
    _, _, config, traffic = load_cell("ycsb-a-zipf", small=True)
    store = config["store"]
    gen = TrafficGen(traffic, store, SEED)
    reqs = [_first(gen, "writebatch"), _first(gen, "multiget")]
    eng = build_engine(store)
    with obs.enabled() as tr:
        w0 = time.perf_counter()
        for req in reqs:
            eng.submit(as_batch(req)).get_results()
        w1 = time.perf_counter()
    eng.close()
    return tr.events(), w0, w1, [NS(cls=r.cls) for r in reqs]


@pytest.fixture(scope="module")
def traced():
    return _traced_requests()


def _read(spans, w0, w1, requests):
    import run
    metric = run.load_module("layer_metrics", "mem_ms_per_req")
    return metric.read(NS(spans=spans, w0=w0, w1=w1, requests=requests))


def _ms(spans, name):
    return sum(e["t1"] - e["t0"] for e in spans if e["name"] == name) * 1e3


def test_sums_probe_and_merge_spans(traced):
    spans, w0, w1, requests = traced
    merges = [e for e in spans if e["name"] == "lsm.mem.merge"]
    # The updates reach every shard's memtable; each chunk reports its
    # rows in and the run's rows after.
    assert merges and all(0 < e["attrs"]["n"] and
                          0 <= e["attrs"]["size"] for e in merges)
    assert sum(e["attrs"]["n"] for e in merges) == 2048
    probe, merge = _ms(spans, "lsm.get.mem"), _ms(spans, "lsm.mem.merge")
    assert probe > 0 and merge > 0
    got = _read(spans, w0, w1, requests)
    assert got == pytest.approx((probe + merge) / len(requests))


def test_probe_alone_without_merge_spans(traced):
    """What the reader finds in a program whose memtable writes carry
    no span: the probe's time alone."""
    spans, w0, w1, requests = traced
    probe_only = [e for e in spans if e["name"] != "lsm.mem.merge"]
    got = _read(probe_only, w0, w1, requests)
    assert got == pytest.approx(_ms(spans, "lsm.get.mem") / len(requests))


def test_clipped_to_window_and_none_without_spans():
    span = {"name": "lsm.get.mem", "t0": 0.5, "t1": 2.0, "attrs": {}}
    req = [NS(cls="multiget")]
    assert _read([span], 1.0, 3.0, req) == pytest.approx(1000.0)
    assert _read([], 1.0, 3.0, req) is None
    assert _read([span], 1.0, 3.0, []) is None
