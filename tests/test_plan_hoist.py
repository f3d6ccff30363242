"""The planner's dependency-aware read hoisting against a reference.

``reference_shard_plan`` is the per-segment formulation of the hoisting
rule: it walks a shard's op stream one same-kind segment at a time and
tests each read segment against every write since the open read slot
began.  ``Planner`` assigns slots from each read's last touching write
in one pass per shard; both must compile every batch to field-for-field
identical ``ShardPlan``s, with the same slot and conflict counts.
"""

import numpy as np
import pytest

from repro import obs
from repro.engine import (OP_DELETE, OP_GET, OP_PUT, OP_RANGE_DELETE,
                          OP_RANGE_SCAN, OpBatch, Planner, ShardRouter)
from repro.engine.plan import _POINT_KINDS, PlanStep, ShardPlan


def _read_conflicts(batch, slot, gets, scans):
    """Which of a read segment's ops overlap the slot's writes: a get if
    a write range covers its key or a written key equals it; a scan if a
    write range overlaps [lo, hi) or a written key falls inside it."""
    wlo = np.concatenate(slot["wlo"]) if slot["wlo"] else None
    wk = np.concatenate(slot["wkeys"]) if slot["wkeys"] else None
    g_conf = np.zeros(len(gets), dtype=bool)
    if len(gets):
        keys = batch.keys[gets]
        if wlo is not None:
            whi = np.concatenate(slot["whi"])
            g_conf |= ((keys[:, None] >= wlo[None, :]) &
                       (keys[:, None] < whi[None, :])).any(axis=1)
        if wk is not None:
            g_conf |= np.isin(keys, wk)
    if scans is None:
        return g_conf, None
    _, alos, ahis = scans
    s_conf = np.zeros(len(alos), dtype=bool)
    if wlo is not None:
        whi = np.concatenate(slot["whi"])
        s_conf |= ((alos[:, None] < whi[None, :]) &
                   (ahis[:, None] > wlo[None, :])).any(axis=1)
    if wk is not None:
        s_conf |= ((wk[None, :] >= alos[:, None]) &
                   (wk[None, :] < ahis[:, None])).any(axis=1)
    return g_conf, s_conf


def reference_shard_plan(s, batch, oidx, slo, shi):
    """One open read slot; each read segment hoists into it unless it
    overlaps a write accumulated since the slot opened, in which case
    the conflicting reads open a fresh slot after those writes."""
    sp = ShardPlan(shard=s)
    if len(oidx) == 0:
        return sp, 0, 0
    k = batch.kinds[oidx]
    wr = (k != OP_GET) & (k != OP_RANGE_SCAN)
    brk = (wr[1:] != wr[:-1]) | (wr[1:] & (k[1:] != k[:-1]))
    bounds = np.concatenate([[0], np.flatnonzero(brk) + 1, [len(k)]])
    items: list = []  # PlanStep (writes) | dict (open read slots)
    slot = None
    conflicts = 0

    def open_slot():
        s_ = {"gets": [], "scans": [], "wlo": [], "whi": [], "wkeys": []}
        items.append(s_)
        return s_

    for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        kind = int(k[a])
        idx = oidx[a:b]
        if wr[a]:
            if kind in _POINT_KINDS:
                items.append(PlanStep(
                    kind=kind, idx=idx, keys=batch.keys[idx],
                    vals=batch.vals[idx] if kind == OP_PUT else None))
                if slot is not None:
                    slot["wkeys"].append(batch.keys[idx])
            else:
                items.append(PlanStep(kind=kind, idx=idx, los=slo[a:b],
                                      his=shi[a:b]))
                if slot is not None:
                    slot["wlo"].append(slo[a:b])
                    slot["whi"].append(shi[a:b])
            continue
        if slot is None:
            slot = open_slot()
        gets = idx[k[a:b] == OP_GET]
        sm = k[a:b] == OP_RANGE_SCAN
        scans = (idx[sm], slo[a:b][sm], shi[a:b][sm]) if sm.any() else None
        g_conf, s_conf = _read_conflicts(batch, slot, gets, scans)
        if len(gets):
            slot["gets"].append(gets[~g_conf])
        if scans is not None:
            slot["scans"].append(tuple(x[~s_conf] for x in scans))
        n_conf = int(g_conf.sum()) + (0 if s_conf is None
                                      else int(s_conf.sum()))
        if n_conf:
            conflicts += n_conf
            slot = open_slot()
            if g_conf.any():
                slot["gets"].append(gets[g_conf])
            if s_conf is not None and s_conf.any():
                slot["scans"].append(tuple(x[s_conf] for x in scans))

    n_slots = 0
    for item in items:
        if isinstance(item, PlanStep):
            sp.steps.append(item)
            continue
        n_slots += 1
        gids = [g for g in item["gets"] if len(g)]
        if gids:
            gid = np.concatenate(gids)
            sp.steps.append(PlanStep(kind=OP_GET, idx=gid,
                                     keys=batch.keys[gid]))
        sids = [t for t in item["scans"] if len(t[0])]
        if sids:
            sp.steps.append(PlanStep(
                kind=OP_RANGE_SCAN,
                idx=np.concatenate([t[0] for t in sids]),
                los=np.concatenate([t[1] for t in sids]),
                his=np.concatenate([t[2] for t in sids])))
    return sp, n_slots, conflicts


class ReferencePlanner(Planner):
    """Routes like ``Planner``; plans each shard with the reference."""

    def _shard_plan(self, s, batch, oidx, slo, shi):
        return reference_shard_plan(s, batch, oidx, slo, shi)


def assert_same_plans(got, want):
    assert len(got.shard_plans) == len(want.shard_plans)
    for g, w in zip(got.shard_plans, want.shard_plans):
        assert (g.shard, g.seq) == (w.shard, w.seq)
        assert len(g.steps) == len(w.steps), g.shard
        for i, (a, b) in enumerate(zip(g.steps, w.steps)):
            assert a.kind == b.kind, (g.shard, i)
            for f in ("idx", "keys", "vals", "los", "his"):
                x, y = getattr(a, f), getattr(b, f)
                if y is None:
                    assert x is None, (g.shard, i, f)
                else:
                    assert x.dtype == y.dtype, (g.shard, i, f)
                    np.testing.assert_array_equal(x, y, err_msg=str(
                        (g.shard, i, f)))


def plan_both(router, batch):
    """(plan, slots, conflicts) from the planner and the reference."""
    got = Planner(router)._plan(batch, 0)
    want = ReferencePlanner(router)._plan(batch, 0)
    assert_same_plans(got[0], want[0])
    assert got[1:] == want[1:]
    return want


def random_batch(rng, n, universe, max_len):
    """A shuffled stream of all five op kinds over [0, universe)."""
    kinds = rng.choice(
        [OP_PUT, OP_DELETE, OP_GET, OP_RANGE_DELETE, OP_RANGE_SCAN], n,
        p=[0.3, 0.1, 0.35, 0.1, 0.15]).astype(np.uint8)
    keys = rng.integers(0, universe, n, dtype=np.uint64)
    vals = rng.integers(1, 1 << 40, n, dtype=np.uint64)
    los = rng.integers(0, universe - 1, n, dtype=np.uint64)
    his = np.minimum(los + rng.integers(1, max_len + 1, n, dtype=np.uint64),
                     np.uint64(universe))
    rng_op = kinds >= OP_RANGE_DELETE
    pt = ~rng_op
    return OpBatch(kinds, keys=np.where(pt, keys, 0),
                   vals=np.where(kinds == OP_PUT, vals, 0),
                   los=np.where(rng_op, los, 0), his=np.where(rng_op, his, 0))


# sparse: the fig9 key space, where hoisting almost never breaks; dense:
# 64 keys, so gets hit written keys and ranges and slots close often.
DENSITIES = {"sparse": (1 << 25, 128), "dense": (64, 8)}


@pytest.mark.parametrize("density", sorted(DENSITIES))
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("partition", ["hash", "range"])
def test_plans_match_reference(partition, shards, density):
    universe, max_len = DENSITIES[density]
    router = ShardRouter(shards, partition=partition, universe=universe)
    rng = np.random.default_rng([shards, universe, int(partition == "hash")])
    slots = conflicts = 0
    for n in (1, 7, 60, 600, 2000):
        _, s, c = plan_both(router, random_batch(rng, n, universe, max_len))
        slots, conflicts = slots + s, conflicts + c
    if density == "dense":
        # The streams exercise the slot chase, not just one slot.
        assert conflicts > 0 and slots > 5 * shards


EDGE_CASES = {
    # Shard 1 of 2 under range partitioning receives nothing.
    "empty_shard": ([("put", 1, 1), ("get", 2), ("range_delete", 0, 5),
                     ("get", 3)], 2, "range", 100),
    "reads_only": ([("get", 5), ("range_scan", 0, 9), ("get", 5),
                    ("range_scan", 3, 4)], 2, "hash", 100),
    "writes_only": ([("put", 5, 1), ("delete", 5), ("range_delete", 0, 9),
                     ("put", 6, 2), ("put", 7, 3)], 2, "hash", 100),
    # The scan before put 30 hoists; the scan after it holds 30 and must
    # open a slot after the put, as must the get of 30.
    "scan_holds_later_written_key": (
        [("get", 5), ("range_scan", 20, 40), ("put", 30, 1),
         ("range_scan", 20, 40), ("get", 30), ("get", 31)], 1, "hash", 100),
    # range_delete [40, 60) is clipped to [50, 60) on shard 1: get 55 is
    # covered there, get 45 (shard 0, clipped [40, 50)) is covered too,
    # get 60 sits just past the edge and hoists.
    "get_covered_at_clipped_edge": (
        [("get", 1), ("get", 51), ("range_delete", 40, 60), ("get", 55),
         ("get", 45), ("get", 60), ("get", 2)], 2, "range", 100),
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_edge_plans_match_reference(case):
    ops, shards, partition, universe = EDGE_CASES[case]
    router = ShardRouter(shards, partition=partition, universe=universe)
    plan, _, _ = plan_both(router, OpBatch.from_ops(ops))
    steps = [[(st.kind, st.idx.tolist()) for st in sp.steps]
             for sp in plan.shard_plans]
    if case == "empty_shard":
        assert steps[1] == []
    elif case == "scan_holds_later_written_key":
        assert steps[0] == [(OP_GET, [0, 5]), (OP_RANGE_SCAN, [1]),
                            (OP_PUT, [2]), (OP_GET, [4]),
                            (OP_RANGE_SCAN, [3])]
    elif case == "get_covered_at_clipped_edge":
        assert steps == [
            [(OP_GET, [0, 6]), (OP_RANGE_DELETE, [2]), (OP_GET, [4])],
            [(OP_GET, [1, 5]), (OP_RANGE_DELETE, [2]), (OP_GET, [3])]]


def test_plan_compile_records_slots_and_conflicts():
    """One shard, one conflict: get 7 after delete 7 closes the first
    slot, so two slots are emitted and one read closed a slot."""
    router = ShardRouter(1, partition="hash", universe=100)
    batch = OpBatch.from_ops([
        ("get", 7), ("put", 3, 1), ("get", 9), ("delete", 7), ("get", 7),
        ("get", 4)])
    with obs.enabled() as tr:
        plan = Planner(router).plan(batch)
    (ev,) = [e for e in tr.events() if e["name"] == "plan.compile"]
    assert ev["attrs"]["read_slots"] == 2
    assert ev["attrs"]["read_conflicts"] == 1
    assert [(st.kind, st.idx.tolist()) for st in plan.shard_plans[0].steps] \
        == [(OP_GET, [0, 2, 5]), (OP_PUT, [1]), (OP_DELETE, [3]),
            (OP_GET, [4])]
