"""Execution-mode parity: pipelined, device-pinned and scheduled engines
against one serial reference.

The engine's contract across its execution modes is byte-identity: a
pipelined engine — shards pinned over 0 or 4 devices, the background
compaction scheduler off or on — must return the same results AND
charge the same I/O as the serial single-device engine with the same
scheduler setting, for every strategy.  The matrix drives one mixed
put/delete/range-delete/get/scan workload through all 20 cells and
diffs each against that reference.  The satellites cover stats()
idempotency, close() with batches in flight, WAL recovery after a
pipelined device-pinned run, and the retired ``EngineConfig.procs``.
"""

import jax
import numpy as np
import pytest

from repro.core.eve import RAEConfig
from repro.core.gloran import GloranConfig
from repro.core.lsm_drtree import LSMDRTreeConfig
from repro.engine import Engine, EngineConfig, OpBatch
from repro.lsm import LSMConfig
from repro.lsm.tree import STRATEGIES

UNIVERSE = 1 << 20


def small_lsm():
    return LSMConfig(buffer_capacity=64, size_ratio=3, key_size=16,
                     value_size=48, block_size=512,
                     key_universe=UNIVERSE)


def small_gloran():
    return GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=16, size_ratio=3,
                              key_size=16, block_size=512),
        eve=RAEConfig(capacity=64, key_universe=UNIVERSE))


def engine_cfg(*, devices=0, scheduler=False, pipeline=True, **kw):
    return EngineConfig(devices=devices, scheduler=scheduler,
                        pipeline=pipeline, cache_blocks=256,
                        kernel_min_batch=1, kernel_min_areas=1,
                        kernel_min_filter=1, cascade_compiled=True, **kw)


def make_engine(*, strategy="gloran", shards=4, **kw):
    return Engine(shards, strategy=strategy, lsm_config=small_lsm(),
                  gloran_config=small_gloran(), config=engine_cfg(**kw))


def drive(eng, rounds=2, universe=2000, seed=7):
    """Mixed workload with flushes; returns every result surface."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        keys = rng.integers(0, universe, size=220).astype(np.uint64)
        vals = rng.integers(1, 1 << 40, size=220, dtype=np.uint64)
        eng.put_batch(keys, vals)
        eng.delete_batch(keys[:30])
        lo = int(rng.integers(0, universe // 2))
        eng.range_delete_batch([(lo, lo + 400), (lo + 600, lo + 900)])
        probe = rng.integers(0, universe, size=300).astype(np.uint64)
        found, got = eng.get_batch(probe)
        out.append(("get", found, got))
        for k, v in eng.range_scan_batch([(0, universe // 3),
                                          (universe // 4, universe)]):
            out.append(("scan", k, v))
    return out


def assert_same_results(ref, got):
    assert len(ref) == len(got)
    for (tag_a, a1, a2), (tag_b, b1, b2) in zip(ref, got):
        assert tag_a == tag_b
        assert np.array_equal(a1, b1)
        if tag_a == "get":
            assert np.array_equal(a2[a1], b2[b1])  # values where found
        else:
            assert np.array_equal(a2, b2)


_REFS: dict = {}


def reference(strategy, scheduler=False):
    """Serial single-device reference results + IOStats, cached per
    (strategy, scheduler) — the background scheduler runs extra
    compactions at drain points, so its I/O ledger is compared against
    a scheduler-on serial run, not the quiescent one."""
    key = (strategy, scheduler)
    if key not in _REFS:
        eng = make_engine(strategy=strategy, pipeline=False,
                          scheduler=scheduler)
        res = drive(eng)
        _REFS[key] = (res, eng.stats()["io"], eng.num_entries)
        eng.close()
    return _REFS[key]


MATRIX = [(s, d, b) for s in STRATEGIES for d in (0, 4)
          for b in (False, True)]


@pytest.mark.parametrize("strategy,devices,scheduler", MATRIX)
def test_parity_matrix(strategy, devices, scheduler):
    ref_res, ref_io, ref_entries = reference(strategy, scheduler)
    eng = make_engine(strategy=strategy, devices=devices,
                      scheduler=scheduler)
    try:
        res = drive(eng)
        assert_same_results(ref_res, res)
        st = eng.stats()
        assert st["io"] == ref_io
        assert st["entries"] == ref_entries
        assert st["engine"]["pipelined_batches"] > 0
        assert st["devices"]["distinct"] == (
            min(devices, len(jax.devices())) if devices else 1)
    finally:
        eng.close()


def test_stats_idempotent_across_calls(tmp_path):
    """Per-shard ledgers are cumulative snapshots, so stats() twice with
    no work between must diff clean — no double-counted kernel, I/O,
    scheduler or WAL ledgers."""
    eng = make_engine(scheduler=True, wal_dir=str(tmp_path),
                      fsync="never")
    try:
        drive(eng, rounds=1)
        s1 = eng.stats()
        s2 = eng.stats()
        assert s1["wal"]["frames"] > 0
        for key in ("io", "kernels", "entries", "cache", "lsm", "sched",
                    "wal", "executor", "engine", "metrics"):
            assert s1.get(key) == s2.get(key), key
    finally:
        eng.close()


def test_mid_stream_close_drains(tmp_path):
    """close() with pipelined batches in flight must collect them all
    (acked results complete) before tearing the shard pools down."""
    eng = make_engine(wal_dir=str(tmp_path), fsync="never")
    try:
        keys = np.arange(500, dtype=np.uint64)
        eng.put_batch(keys, keys + np.uint64(1))
        pends = [eng.submit(OpBatch.gets(keys)) for _ in range(4)]
    finally:
        eng.close()
    for p in pends:
        found, vals = p.get_results()
        assert found.all()
        assert np.array_equal(vals, keys + np.uint64(1))


def test_wal_recovery_after_pipelined_device_run(tmp_path):
    """A pipelined, 4-device durable run recovers with the same gets and
    scans as the serial single-device reference."""
    from repro.durable import recover
    probe = np.arange(0, 2000, 3, dtype=np.uint64)
    ref = make_engine(pipeline=False)
    ref_res = drive(ref)
    ref_found, ref_vals = ref.get_batch(probe)
    ref_k, ref_v = ref.range_scan(0, UNIVERSE)
    ref.close()

    eng = make_engine(devices=4, wal_dir=str(tmp_path), fsync="never")
    assert_same_results(ref_res, drive(eng))
    eng.close()

    rec = recover(str(tmp_path), config=engine_cfg(devices=4))
    try:
        assert rec.recovery["frames_replayed"] > 0
        assert rec.stats()["devices"]["enabled"]
        found, vals = rec.get_batch(probe)
        k, v = rec.range_scan(0, UNIVERSE)
        assert np.array_equal(ref_found, found)
        assert np.array_equal(ref_vals[found], vals[found])
        assert np.array_equal(ref_k, k)
        assert np.array_equal(ref_v, v)
    finally:
        rec.close()


def test_engineconfig_procs_must_be_zero():
    with pytest.raises(ValueError, match="removed"):
        EngineConfig(procs=2)
    cfg = EngineConfig(procs=0, devices=0, pipeline=False)
    eng = Engine(1, config=cfg)
    try:
        eng.put(5, 55)
        assert eng.get(5) == 55
    finally:
        eng.close()
