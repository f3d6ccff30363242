"""Background delete-aware compaction scheduler (``lsm/scheduler.py``).

The contract under test is BYTE-IDENTITY: because background jobs run
only at deterministic drain points (plan start, ``Engine.drain``,
seal backpressure, recovery replay), an engine with the scheduler on
must produce the same read results, the same I/O ledger, the same
flush points and level shapes, and the same recovered-from-WAL state
as the inline engine — for ANY sequence of blocking engine calls,
across all 5 range-delete strategies and 1/2/4 shards.

On top of identity: backpressure stalls are counted, the proactive
tombstone-density trigger actually reclaims GLORAN garbage, the
merge-rank compaction routing is bit-exact with the host path, the
vectorized presorted flush build equals the legacy lexsort build, and
the scheduler/per-level metrics surface through ``engine.stats()``.
"""

from __future__ import annotations

import numpy as np
import pytest

try:  # optional dev dependency: property tests only run when present
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core.eve import RAEConfig
from repro.core.gloran import GloranConfig
from repro.core.lsm_drtree import LSMDRTreeConfig
from repro.durable import recover
from repro.engine import Engine, EngineConfig
from repro.lsm.format import LSMConfig
from repro.lsm.sstable import build_sstable
from repro.lsm.tree import STRATEGIES, LSMTree

UNIVERSE = 1 << 16


def small_lsm():
    # Tiny capacities so short workloads cross flush/compaction points.
    return LSMConfig(buffer_capacity=32, size_ratio=4, key_size=16,
                     value_size=16, key_universe=UNIVERSE)


def small_gloran():
    return GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=16, size_ratio=4,
                              key_size=16),
        eve=RAEConfig(capacity=64, key_universe=UNIVERSE))


def make_engine(*, strategy="gloran", shards=2, scheduler=False,
                **cfg_kw):
    cfg_kw.setdefault("pipeline", False)
    cfg = EngineConfig(devices=0, scheduler=scheduler, **cfg_kw)
    return Engine(shards, strategy=strategy, lsm_config=small_lsm(),
                  gloran_config=small_gloran(), config=cfg)


def mixed_ops(seed, n_rounds=6, batch=48):
    """Deterministic op script: ("put"|"del"|"rdel"|"flush"|"get"|"scan")
    tuples, heavy enough to cross several flush + cascade boundaries."""
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_rounds):
        keys = rng.integers(1, UNIVERSE - 1, batch).astype(np.uint64)
        ops.append(("put", keys, keys * np.uint64(2 + i)))
        if i % 2 == 0:
            ops.append(("del", keys[: batch // 4]))
            ops.append(("get", rng.integers(
                1, UNIVERSE - 1, batch).astype(np.uint64)))
        else:
            lo = int(rng.integers(1, UNIVERSE // 2))
            ops.append(("rdel", lo, lo + int(rng.integers(1, 2000))))
            lo = int(rng.integers(1, UNIVERSE - 2))
            ops.append(("scan", lo, lo + 3000))
        if i == n_rounds // 2:
            ops.append(("flush",))
    return ops


def apply_and_compare(a, b, ops):
    """Apply the same script to both engines, asserting every read op
    returns identical results (reads are the mid-stream observation
    points where background state must already coincide with inline)."""
    for op in ops:
        if op[0] == "put":
            a.put_batch(op[1], op[2])
            b.put_batch(op[1], op[2])
        elif op[0] == "del":
            a.delete_batch(op[1])
            b.delete_batch(op[1])
        elif op[0] == "rdel":
            a.range_delete(op[1], op[2])
            b.range_delete(op[1], op[2])
        elif op[0] == "flush":
            a.flush()
            b.flush()
        elif op[0] == "get":
            fa, va = a.get_batch(op[1])
            fb, vb = b.get_batch(op[1])
            np.testing.assert_array_equal(fa, fb)
            np.testing.assert_array_equal(va[fa], vb[fb])
        elif op[0] == "scan":
            ka, va = a.range_scan(op[1], op[2])
            kb, vb = b.range_scan(op[1], op[2])
            np.testing.assert_array_equal(ka, kb)
            np.testing.assert_array_equal(va, vb)


def assert_same_store(a, b, *, io=True):
    """Byte-identical visible state AND structure (and, by default, the
    cumulative simulated-I/O ledger) between two drained engines."""
    probes = np.arange(1, UNIVERSE, 37, dtype=np.uint64)
    fa, va = a.get_batch(probes)
    fb, vb = b.get_batch(probes)
    np.testing.assert_array_equal(fa, fb)
    np.testing.assert_array_equal(va[fa], vb[fb])
    sa = a.range_scan(0, UNIVERSE)
    sb = b.range_scan(0, UNIVERSE)
    np.testing.assert_array_equal(sa[0], sb[0])
    np.testing.assert_array_equal(sa[1], sb[1])
    for sha, shb in zip(a.shards, b.shards):
        ta, tb = sha.tree, shb.tree
        assert ta.stats()["levels"] == tb.stats()["levels"]
        assert ta.seq == tb.seq
        assert ta.num_entries == tb.num_entries
        for la, lb in zip(ta.levels, tb.levels):
            if la is None or lb is None:
                assert (la is None or len(la) == 0) == \
                       (lb is None or len(lb) == 0)
                continue
            np.testing.assert_array_equal(la.keys, lb.keys)
            np.testing.assert_array_equal(la.seqs, lb.seqs)
            np.testing.assert_array_equal(la.vals, lb.vals)
        if io:
            assert ta.io.snapshot() == tb.io.snapshot()


# ------------------------------------------------- tentpole: identity
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_background_matches_inline(strategy, shards):
    inline = make_engine(strategy=strategy, shards=shards,
                         scheduler=False)
    bg = make_engine(strategy=strategy, shards=shards, scheduler=True)
    apply_and_compare(inline, bg, mixed_ops(7, n_rounds=8))
    inline.flush()
    bg.flush()
    assert_same_store(inline, bg)
    # A drained background engine owes nothing.
    for sh in bg.shards:
        c = sh.scheduler.counters()
        assert c["queue_depth"] == 0
        assert c["frozen"] == 0
        assert c["compaction_debt"] == 0
        assert c["flush_jobs"] > 0  # the workload really went background


@pytest.mark.parametrize("max_frozen", [1, 2, 8])
def test_background_matches_inline_seal_limits(max_frozen):
    inline = make_engine(strategy="gloran", shards=1, scheduler=False)
    bg = make_engine(strategy="gloran", shards=1, scheduler=True,
                     max_frozen=max_frozen)
    rng = np.random.default_rng(11)
    # One oversized put batch seals many times inside a single plan:
    # with max_frozen=1 every seal past the first backpressures.
    keys = rng.integers(1, UNIVERSE - 1, 600).astype(np.uint64)
    for eng in (inline, bg):
        eng.put_batch(keys, keys + np.uint64(1))
        eng.range_delete(100, 5000)
        eng.put_batch(keys[:64], keys[:64] + np.uint64(9))
    assert_same_store(inline, bg)
    c = bg.shards[0].scheduler.counters()
    if max_frozen == 1:
        assert c["stall_count"] > 0
        assert bg.stats()["sched"]["stall_count"] == c["stall_count"]
    assert c["max_queue_depth"] >= 1


def test_mid_compaction_close_drains_pending_jobs():
    """Pipelined submits + immediate close: close() must quiesce every
    queued flush/cascade job and leave the same state as the inline
    engine that ran everything serially."""
    inline = make_engine(strategy="lrr", shards=4, scheduler=False)
    bg = make_engine(strategy="lrr", shards=4, scheduler=True,
                     pipeline=True)
    rng = np.random.default_rng(3)
    batches = [rng.integers(1, UNIVERSE - 1, 256).astype(np.uint64)
               for _ in range(6)]
    for i, keys in enumerate(batches):
        inline.put_batch(keys, keys * np.uint64(3 + i))
    inline.range_delete(1000, 9000)
    handles = []
    for i, keys in enumerate(batches):  # fire-and-forget pipelined
        from repro.engine.plan import OpBatch
        handles.append(bg.submit(OpBatch.puts(keys,
                                              keys * np.uint64(3 + i))))
    bg.range_delete(1000, 9000)
    bg.close()  # drains in-flight work AND pending scheduler jobs
    inline.close()
    for sh in bg.shards:
        c = sh.scheduler.counters()
        assert c["queue_depth"] == 0
        assert c["frozen"] == 0
        assert c["compaction_debt"] == 0
    assert_same_store(inline, bg, io=False)  # pipelined wall differs,
    # but the ledger must still agree per shard:
    for sha, shb in zip(inline.shards, bg.shards):
        assert sha.tree.io.snapshot() == shb.tree.io.snapshot()


def test_wal_recovery_background_matches_inline(tmp_path):
    """The WAL written by a background engine recovers to the same
    store as the WAL written by the inline engine (FLUSH frames are
    acked only after the background flush durably published)."""
    dirs = {m: str(tmp_path / m) for m in ("inline", "bg")}
    engines = {
        "inline": make_engine(strategy="gloran", shards=2,
                              scheduler=False, wal_dir=dirs["inline"]),
        "bg": make_engine(strategy="gloran", shards=2, scheduler=True,
                          wal_dir=dirs["bg"]),
    }
    ops = mixed_ops(23, n_rounds=6)
    for eng in engines.values():
        for op in ops:
            if op[0] == "put":
                eng.put_batch(op[1], op[2])
            elif op[0] == "del":
                eng.delete_batch(op[1])
            elif op[0] == "rdel":
                eng.range_delete(op[1], op[2])
            elif op[0] == "flush":
                eng.flush()
        eng.close()
    ra = recover(dirs["inline"])
    rb = recover(dirs["bg"])
    assert_same_store(ra, rb, io=False)
    # Each recovered store also matches its own live original shape.
    assert ra.recovery["frames_replayed"] > 0
    ra.close()
    rb.close()


# ----------------------------------------------- property: any stream
if HAS_HYPOTHESIS:

    @st.composite
    def op_streams(draw):
        n = draw(st.integers(min_value=3, max_value=10))
        ops = []
        for _ in range(n):
            kind = draw(st.sampled_from(
                ["put", "del", "rdel", "flush", "get", "scan"]))
            if kind == "put":
                seed = draw(st.integers(0, 2**16))
                size = draw(st.integers(1, 160))
                rng = np.random.default_rng(seed)
                keys = rng.integers(1, UNIVERSE - 1,
                                    size).astype(np.uint64)
                ops.append(("put", keys, keys + np.uint64(seed % 97)))
            elif kind == "del":
                seed = draw(st.integers(0, 2**16))
                rng = np.random.default_rng(seed)
                ops.append(("del", rng.integers(
                    1, UNIVERSE - 1, draw(st.integers(1, 40))
                ).astype(np.uint64)))
            elif kind == "rdel":
                lo = draw(st.integers(1, UNIVERSE - 3))
                ops.append(("rdel", lo,
                            lo + draw(st.integers(1, 4000))))
            elif kind == "get":
                seed = draw(st.integers(0, 2**16))
                rng = np.random.default_rng(seed)
                ops.append(("get", rng.integers(
                    1, UNIVERSE - 1, 64).astype(np.uint64)))
            elif kind == "scan":
                lo = draw(st.integers(0, UNIVERSE - 2))
                ops.append(("scan", lo, lo + draw(st.integers(1, 5000))))
            else:
                ops.append(("flush",))
        return ops

    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(strategy=st.sampled_from(STRATEGIES),
           shards=st.sampled_from([1, 2, 4]),
           max_frozen=st.sampled_from([1, 4]),
           ops=op_streams())
    def test_background_identity_property(strategy, shards, max_frozen,
                                          ops):
        inline = make_engine(strategy=strategy, shards=shards,
                             scheduler=False)
        bg = make_engine(strategy=strategy, shards=shards,
                         scheduler=True, max_frozen=max_frozen)
        apply_and_compare(inline, bg, ops)
        inline.flush()
        bg.flush()
        assert_same_store(inline, bg)
else:  # pragma: no cover - optional dependency missing

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_background_identity_property():
        pass


# --------------------------------------- proactive tombstone trigger
def test_proactive_trigger_reclaims_gloran_garbage():
    """With ``tombstone_trigger`` set, tombstone-dense levels compact
    proactively: the GC floor advances and the global index sheds
    obsolete range records — without changing any visible result."""
    oracle = make_engine(strategy="gloran", shards=1, scheduler=False)
    plain = make_engine(strategy="gloran", shards=1, scheduler=True)
    eager = make_engine(strategy="gloran", shards=1, scheduler=True,
                        tombstone_trigger=0.05)
    rng = np.random.default_rng(5)
    keys = rng.integers(1, UNIVERSE - 1, 1500).astype(np.uint64)
    for eng in (oracle, plain, eager):
        eng.put_batch(keys, keys * np.uint64(7))
        for j in range(24):  # dense range-delete burst
            lo = 1 + j * (UNIVERSE // 32)
            eng.range_delete(lo, lo + UNIVERSE // 40)
        eng.put_batch(keys[:40], keys[:40] + np.uint64(1))  # plan kick
        eng.drain()
    probes = np.arange(1, UNIVERSE, 23, dtype=np.uint64)
    fo, vo = oracle.get_batch(probes)
    for eng in (plain, eager):
        f, v = eng.get_batch(probes)
        np.testing.assert_array_equal(f, fo)
        np.testing.assert_array_equal(v[f], vo[fo])
    assert eager.shards[0].scheduler.counters()["proactive_jobs"] > 0
    assert plain.shards[0].scheduler.counters()["proactive_jobs"] == 0
    ge, gp = eager.shards[0].tree.gloran, plain.shards[0].tree.gloran
    # Proactive bottom compactions raise the GC floor at least as far
    # as drains alone, and strictly reclaim index records or floor.
    assert ge.gc_floor >= gp.gc_floor
    assert (ge.gc_floor > gp.gc_floor or
            ge.index.num_records <= gp.index.num_records)


# ------------------------------------- merge-rank compaction routing
@pytest.mark.parametrize("strategy", ["gloran", "lrr"])
def test_compaction_merge_rank_parity(strategy):
    """Compaction ordering through the merge-rank kernel is bit-exact
    with the host searchsorted path: same levels, same I/O charges."""
    host = make_engine(strategy=strategy, shards=1,
                       use_merge_kernel=False)
    kern = make_engine(strategy=strategy, shards=1,
                       use_merge_kernel=True, kernel_min_merge=1)
    apply_and_compare(host, kern, mixed_ops(13, n_rounds=8))
    host.flush()
    kern.flush()
    assert_same_store(host, kern)


# --------------------------------------- vectorized presorted builds
def test_build_sstable_presorted_matches_lexsort():
    rng = np.random.default_rng(17)
    cfg = small_lsm()
    n = 700
    keys = rng.integers(1, 400, n).astype(np.uint64)  # many duplicates
    seqs = rng.permutation(np.arange(1, n + 1)).astype(np.uint64)
    types = (rng.integers(0, 2, n)).astype(np.uint8)
    vals = rng.integers(0, 1 << 40, n).astype(np.uint64)
    legacy = build_sstable(keys, seqs, types, vals, cfg, seed=3)
    order = np.lexsort((seqs, keys))  # key-major; presorted contract
    pre = build_sstable(keys[order], seqs[order], types[order],
                        vals[order], cfg, seed=3, presorted=True)
    np.testing.assert_array_equal(legacy.keys, pre.keys)
    np.testing.assert_array_equal(legacy.seqs, pre.seqs)
    np.testing.assert_array_equal(legacy.types, pre.types)
    np.testing.assert_array_equal(legacy.vals, pre.vals)
    np.testing.assert_array_equal(legacy.bloom.words, pre.bloom.words)


def test_vectorized_flush_keeps_last_write():
    tree = LSMTree(small_lsm(), strategy="decomp")
    for k in range(40):
        tree.put(k % 16, k)  # overwrites wrap the memtable
    tree.flush()
    run = next(lvl for lvl in tree.levels if lvl is not None and
               len(lvl))
    assert list(run.keys) == sorted(set(run.keys))
    for k, v in zip(run.keys, run.vals):
        assert tree.get(int(k)) == int(v)


# ------------------------------------------------- metrics surfacing
def test_scheduler_and_per_level_metrics_surface():
    eng = make_engine(strategy="lrr", shards=2, scheduler=True)
    apply_and_compare(eng, eng, [])  # no-op; keep helper honest
    rng = np.random.default_rng(29)
    keys = rng.integers(1, UNIVERSE - 1, 900).astype(np.uint64)
    eng.put_batch(keys, keys)
    eng.range_delete(10, 9000)
    eng.put_batch(keys[:50], keys[:50])
    stt = eng.stats()
    assert stt["sched"]["flush_jobs"] > 0
    assert stt["sched"]["queue_depth"] == 0  # stats() drains first
    m = stt["metrics"]
    assert m.get("sched.flush_jobs", 0) > 0
    assert "lsm.compaction.bytes.L0" in m
    assert any(k.startswith("lsm.rt_density.L") for k in m)
    lsm = stt["lsm"]
    assert any(k.startswith("rt_compaction.bytes.") for k in lsm)
