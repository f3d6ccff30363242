"""Durability subsystem: WAL, manifest, snapshots, crash recovery.

The centerpiece is the crash-consistency property: truncate the WAL at
an ARBITRARY byte offset (any record boundary or mid-record), recover,
and the store's get/scan results and level shapes must be byte-identical
to a never-crashed reference store built from exactly the surviving
frames — across all 5 range-delete strategies and 1/2/4 shards.
"""

from __future__ import annotations

import glob
import os

import numpy as np
import pytest

try:  # optional dev dependency: property tests only run when present
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core.gloran import GloranConfig
from repro.core.eve import RAEConfig
from repro.core.lsm_drtree import LSMDRTreeConfig
from repro.durable import (FRAME_BATCH, LevelManifest, WalReader,
                           WalWriter, atomic_write_json, keep_last_k,
                           list_versions, load_snapshot, recover,
                           replay_frame, save_snapshot, take_snapshot,
                           wal_has_frames)
from repro.durable.wal import _seg_path, shard_dir
from repro.engine import Engine, EngineConfig
from repro.lsm.format import LSMConfig
from repro.lsm.tree import STRATEGIES

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


def make_engine(tmp, *, shards=2, strategy="gloran", fsync="batch",
                wal=True, segment_bytes=4 << 20):
    cfg = EngineConfig(wal_dir=str(tmp) if wal else None, fsync=fsync,
                       wal_segment_bytes=segment_bytes, devices=0,
                       pipeline=False)
    return Engine(shards, strategy=strategy, lsm_config=small_lsm(),
                  gloran_config=small_gloran(), config=cfg)


def apply_workload(eng, ops):
    """ops: list of ("put", keys, vals) / ("del", keys) /
    ("rdel", lo, hi) / ("flush",) tuples."""
    for op in ops:
        if op[0] == "put":
            eng.put_batch(op[1], op[2])
        elif op[0] == "del":
            eng.delete_batch(op[1])
        elif op[0] == "rdel":
            eng.range_delete(op[1], op[2])
        else:
            eng.flush()


def mixed_ops(seed, n_batches=6, batch=48):
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_batches):
        keys = rng.integers(1, UNIVERSE - 1, batch).astype(np.uint64)
        ops.append(("put", keys, keys * np.uint64(2 + i)))
        if i % 2 == 0:
            ops.append(("del", keys[: batch // 4]))
        if i % 2 == 1:
            lo = int(rng.integers(1, UNIVERSE // 2))
            ops.append(("rdel", lo, lo + int(rng.integers(1, 2000))))
        if i == n_batches // 2:
            ops.append(("flush",))
    return ops


def assert_same_store(a, b):
    """Byte-identical visible state AND structure between two engines."""
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
        assert sha.tree.stats()["levels"] == shb.tree.stats()["levels"]
        assert sha.tree.seq == shb.tree.seq
        assert sha.tree.num_entries == shb.tree.num_entries


# --------------------------------------------------------------- atomic
def test_atomic_versioned_keep_last_k(tmp_path):
    d = str(tmp_path)
    for v in range(1, 6):
        atomic_write_json(os.path.join(d, f"M-{v:08d}.json"), {"v": v},
                          fsync=False)
    assert list_versions(d, "M-", ".json") == [1, 2, 3, 4, 5]
    dropped = keep_last_k(d, "M-", 2, ".json")
    assert dropped == [1, 2, 3]
    assert list_versions(d, "M-", ".json") == [4, 5]
    # tmp siblings and foreign names are ignored
    open(os.path.join(d, "M-00000009.json.tmp"), "w").close()
    open(os.path.join(d, "other.json"), "w").close()
    assert list_versions(d, "M-", ".json") == [4, 5]


# ------------------------------------------------------------------ wal
def test_wal_roundtrip_and_rotation(tmp_path):
    d = str(tmp_path)
    w = WalWriter(d, 0, segment_bytes=512, fsync="batch")
    frames_in = []
    for i in range(10):
        kinds = np.full(8, i % 3, np.uint8)
        keys = np.arange(8, dtype=np.uint64) + i
        w.append(FRAME_BATCH, i, kinds, keys, keys * 2, keys * 3,
                 keys * 4)
        frames_in.append((kinds, keys))
    w.close()
    w.close()  # idempotent
    assert w.segments_rotated > 0
    frames = WalReader(d, 0).read_frames()
    assert len(frames) == 10
    for fr, (kinds, keys) in zip(frames, frames_in):
        np.testing.assert_array_equal(fr.kinds, kinds)
        np.testing.assert_array_equal(fr.keys, keys)
        np.testing.assert_array_equal(fr.vals, keys * 2)
        np.testing.assert_array_equal(fr.los, keys * 3)
        np.testing.assert_array_equal(fr.his, keys * 4)
    assert [fr.plan_seq for fr in frames] == list(range(10))
    assert wal_has_frames(d)


def test_wal_reopen_appends_after_tail(tmp_path):
    d = str(tmp_path)
    w = WalWriter(d, 0, fsync="never")
    w.append(FRAME_BATCH, 0, np.zeros(4, np.uint8),
             np.arange(4, dtype=np.uint64), np.zeros(4, np.uint64),
             np.zeros(4, np.uint64), np.zeros(4, np.uint64))
    w.close()
    w2 = WalWriter(d, 0, fsync="never")
    w2.append(FRAME_BATCH, 1, np.ones(2, np.uint8),
              np.arange(2, dtype=np.uint64), np.zeros(2, np.uint64),
              np.zeros(2, np.uint64), np.zeros(2, np.uint64))
    w2.close()
    frames = WalReader(d, 0).read_frames()
    assert [fr.plan_seq for fr in frames] == [0, 1]
    assert [len(fr) for fr in frames] == [4, 2]


def test_wal_torn_tail_every_offset(tmp_path):
    """Truncating the single segment at EVERY byte offset yields exactly
    the frames whose bytes fully survived — never garbage, never a
    crash."""
    d = str(tmp_path)
    w = WalWriter(d, 0, fsync="never")
    ends = []
    at = 16  # segment header
    for i in range(4):
        at += w.append(FRAME_BATCH, i, np.full(3, 1, np.uint8),
                       np.arange(3, dtype=np.uint64),
                       np.zeros(3, np.uint64), np.zeros(3, np.uint64),
                       np.zeros(3, np.uint64))
        ends.append(at)
    w.close()
    path = _seg_path(shard_dir(d, 0), 0)
    blob = open(path, "rb").read()
    assert len(blob) == ends[-1]
    for cut in range(len(blob) + 1):
        with open(path, "wb") as f:
            f.write(blob[:cut])
        r = WalReader(d, 0)
        frames = r.read_frames()
        expect = sum(1 for e in ends if e <= cut)
        assert len(frames) == expect, f"cut={cut}"
        r.truncate_torn_tail()
        # After truncation the stream is clean and re-appendable.
        assert len(WalReader(d, 0).read_frames()) == expect
    with open(path, "wb") as f:
        f.write(blob)


def test_wal_crc_corruption_stops_reader(tmp_path):
    d = str(tmp_path)
    w = WalWriter(d, 0, fsync="never")
    for i in range(3):
        w.append(FRAME_BATCH, i, np.full(4, 1, np.uint8),
                 np.arange(4, dtype=np.uint64), np.zeros(4, np.uint64),
                 np.zeros(4, np.uint64), np.zeros(4, np.uint64))
    w.close()
    path = _seg_path(shard_dir(d, 0), 0)
    blob = bytearray(open(path, "rb").read())
    blob[-5] ^= 0xFF  # scribble inside the last frame's payload
    open(path, "wb").write(bytes(blob))
    r = WalReader(d, 0)
    assert len(r.read_frames()) == 2
    assert r.torn


# ------------------------------------------------------------- manifest
def test_manifest_versioned_commits_and_fallback(tmp_path):
    d = str(tmp_path)
    m = LevelManifest(d, keep=3, config={"x": 1}, fsync=False)
    v1 = m.commit()
    m.doc["shards"]["0"] = {"levels": []}
    v2 = m.commit()
    assert (v1, v2) == (1, 2)
    loaded = LevelManifest.load(d, fsync=False)
    assert loaded.version == 2
    assert loaded.config == {"x": 1}
    assert loaded.shard_record(0) == {"levels": []}
    # Damage the newest file: load falls back to the previous version.
    newest = sorted(glob.glob(os.path.join(d, "MANIFEST-*.json")))[-1]
    open(newest, "w").write("{not json")
    assert LevelManifest.load(d, fsync=False).version == 1


def test_manifest_records_structure_on_flush(tmp_path):
    eng = make_engine(tmp_path / "w", shards=1)
    keys = np.arange(1, 200, dtype=np.uint64)
    eng.put_batch(keys, keys)
    eng.flush()
    eng.close()
    m = LevelManifest.load(str(tmp_path / "w" / "manifest"))
    rec = m.shard_record(0)
    assert rec is not None and any(lv for lv in rec["levels"])
    assert rec["seq"] == len(keys)
    assert any(e.get("reason") in ("plan", "flush") for e in
               m.doc["edits"])


# ---------------------------------------------------- engine round trip
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_recover_full_log_matches_original(tmp_path, strategy):
    wdir = tmp_path / "wal"
    eng = make_engine(wdir, shards=2, strategy=strategy)
    apply_workload(eng, mixed_ops(seed=7))
    eng.close()
    rec = recover(str(wdir), config=EngineConfig(devices=0,
                                                 pipeline=False))
    assert_same_store(eng, rec)
    rec.close()


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_recover_over_fused_write_runs(tmp_path, shards):
    """WriteBatches whose puts, deletes and range deletes interleave are
    applied as fused write runs live; the WAL replays them step by
    step, and the recovered store equals the live one."""
    from repro.engine import OP_DELETE, OP_PUT, OP_RANGE_DELETE, OpBatch
    wdir = tmp_path / "wal"
    eng = make_engine(wdir, shards=shards)
    rng = np.random.default_rng(17)
    for _ in range(12):
        n = 64
        k = rng.choice(np.array([OP_PUT] * 6 + [OP_DELETE]
                                + [OP_RANGE_DELETE] * 2, np.uint8), n)
        keys = rng.integers(1, 4000, n).astype(np.uint64)
        los = rng.integers(1, 3900, n).astype(np.uint64)
        rd = k == OP_RANGE_DELETE
        z = np.zeros(n, np.uint64)
        eng.submit(OpBatch(k, keys=np.where(rd, z, keys),
                           vals=np.where(k == OP_PUT, keys * np.uint64(3),
                                         z),
                           los=np.where(rd, los, z),
                           his=np.where(rd, los + np.uint64(60), z))
                   ).get_results()
        eng.submit(OpBatch.gets(rng.integers(1, 4000, 100))).get_results()
    assert eng.stats()["executor"]["write_runs_fused"] >= 12
    live = [sh.tree.io.snapshot()["by_tag"] for sh in eng.shards]
    assert all(t.get("index_flush") and t.get("compaction") for t in live)
    eng.close()
    rec = recover(str(wdir), config=EngineConfig(devices=0,
                                                 pipeline=False))
    assert rec.stats()["executor"]["write_runs_fused"] == 0
    assert_same_store(eng, rec)
    for a, b in zip(eng.shards, rec.shards):
        assert a.tree.gloran.gc_floor == b.tree.gloran.gc_floor
        assert [len(l.areas) if l is not None else 0
                for l in a.tree.gloran.index.levels] == \
            [len(l.areas) if l is not None else 0
             for l in b.tree.gloran.index.levels]
    rec.close()


def test_engine_refuses_dirty_wal_dir(tmp_path):
    eng = make_engine(tmp_path, shards=1)
    eng.put_batch(np.arange(1, 10, dtype=np.uint64),
                  np.arange(1, 10, dtype=np.uint64))
    eng.close()
    with pytest.raises(RuntimeError, match="recover"):
        make_engine(tmp_path, shards=1)


def test_engine_context_manager_and_close_idempotent(tmp_path):
    with make_engine(tmp_path, shards=2) as eng:
        eng.put_batch(np.arange(1, 50, dtype=np.uint64),
                      np.arange(1, 50, dtype=np.uint64))
    eng.close()  # second close is a no-op
    assert eng._pools is None
    for sh in eng.shards:
        assert sh.wal._closed


def test_wal_metrics_exposed(tmp_path):
    eng = make_engine(tmp_path, shards=2)
    keys = np.arange(1, 300, dtype=np.uint64)
    eng.put_batch(keys, keys)
    m = eng.stats()["metrics"]
    assert m["wal.bytes"] > 0
    assert m["wal.fsyncs"] > 0
    assert m["wal.frames"] >= 1
    assert m["recovery.wall_s"] == 0.0
    eng.close()
    rec = recover(str(tmp_path), config=EngineConfig(devices=0,
                                                     pipeline=False))
    m2 = rec.stats()["metrics"]
    assert m2["recovery.wall_s"] > 0.0
    assert m2["recovery.frames_replayed"] >= 1
    rec.close()


def test_replay_after_explicit_flush_keeps_level_shapes(tmp_path):
    eng = make_engine(tmp_path, shards=1, strategy="gloran")
    keys = np.arange(1, 40, dtype=np.uint64)  # below buffer capacity
    eng.put_batch(keys[:20], keys[:20])
    eng.flush()  # structure change outside any plan
    eng.put_batch(keys[20:], keys[20:])
    eng.close()
    rec = recover(str(tmp_path), config=EngineConfig(devices=0,
                                                     pipeline=False))
    assert_same_store(eng, rec)
    rec.close()


# ------------------------------------------------------------ snapshots
@pytest.mark.parametrize("strategy", ["gloran", "lrr", "decomp"])
def test_snapshot_tail_restart(tmp_path, strategy):
    eng = make_engine(tmp_path, shards=2, strategy=strategy)
    apply_workload(eng, mixed_ops(seed=11))
    take_snapshot(eng)
    tail_keys = np.arange(30000, 30020, dtype=np.uint64)
    eng.put_batch(tail_keys, tail_keys * 5)
    eng.close()
    rec = recover(str(tmp_path), config=EngineConfig(devices=0,
                                                     pipeline=False))
    assert rec.recovery["snapshot_loaded"] == 1
    # Only the two post-snapshot frames replayed (WAL-tail restart).
    assert rec.recovery["frames_replayed"] <= 4
    assert_same_store(eng, rec)
    rec.close()
    # A second recovery ignores nothing new and still matches.
    rec2 = recover(str(tmp_path), config=EngineConfig(devices=0,
                                                      pipeline=False))
    assert_same_store(eng, rec2)
    rec2.close()


def test_snapshot_ignored_when_ahead_of_wal(tmp_path):
    """A snapshot recorded past the durable prefix (possible under
    fsync='never' + power loss) is discarded; full replay still wins."""
    eng = make_engine(tmp_path, shards=1)
    keys = np.arange(1, 64, dtype=np.uint64)
    eng.put_batch(keys, keys)
    take_snapshot(eng)
    eng.close()
    # Simulate the snapshot's WAL foundation vanishing.
    for seg in glob.glob(str(tmp_path / "shard-000" / "*.wal")):
        os.remove(seg)
    rec = recover(str(tmp_path), config=EngineConfig(devices=0,
                                                     pipeline=False))
    assert rec.recovery["snapshot_loaded"] == 0
    found, _ = rec.get_batch(keys)
    assert not found.any()  # only the (empty) durable prefix survives
    rec.close()


@pytest.mark.parametrize("order", ["saved", "insertion"])
def test_snapshot_memtable_run_roundtrip(tmp_path, order):
    """Save-then-load restores each shard's memtable run exactly; a
    ``mem`` array in the insertion order that snapshots of the dict
    memtable kept (shuffled here) restores to the same run."""
    eng = make_engine(tmp_path, shards=2, wal=False)
    rng = np.random.default_rng(19)
    keys = rng.integers(1, UNIVERSE, size=150).astype(np.uint64)
    eng.put_batch(keys, keys * 3)
    eng.delete_batch(keys[::7])
    eng.drain()
    path = save_snapshot(eng, str(tmp_path / "snaps"))
    if order == "insertion":
        for f in glob.glob(os.path.join(path, "shard-*.npz")):
            with np.load(f) as data:
                arrays = {k: data[k] for k in data.files}
            mem = arrays["mem"]
            assert len(mem) > 1
            arrays["mem"] = mem[rng.permutation(len(mem))]
            np.savez(f, **arrays)
    rec = make_engine(tmp_path, shards=2, wal=False)
    load_snapshot(rec, path)
    for sha, shb in zip(eng.shards, rec.shards):
        for x, y in zip(sha.tree.mem, shb.tree.mem):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)
    assert_same_store(eng, rec)
    eng.close()
    rec.close()


# ----------------------------------------------- crash consistency (HP)
def crash_oracle(frames_per_shard, router):
    """Strategy-independent visible state implied by surviving frames.

    Applied PER SHARD: a shard's ops only ever touch keys it owns, and
    after a crash one shard's stream may hold a range delete another
    shard's truncated stream lost — the survivors must not leak across.
    """
    from repro.engine.plan import (OP_DELETE, OP_PUT, OP_RANGE_DELETE)
    state: dict[int, int] = {}
    for s, frames in frames_per_shard.items():
        shard_state: dict[int, int] = {}
        for fr in frames:
            for i in range(len(fr)):
                k = int(fr.kinds[i])
                if k == OP_PUT:
                    shard_state[int(fr.keys[i])] = int(fr.vals[i])
                elif k == OP_DELETE:
                    shard_state.pop(int(fr.keys[i]), None)
                elif k == OP_RANGE_DELETE:
                    lo, hi = int(fr.los[i]), int(fr.his[i])
                    for kk in [kk for kk in shard_state
                               if lo <= kk < hi]:
                        del shard_state[kk]
        state.update(shard_state)
    return state


def truncate_wal_at(wal_dir, shard, cut):
    """Chop shard 0's stream to its first `cut` bytes (across segments,
    in listing order) — the simulated crash point."""
    sdir = shard_dir(str(wal_dir), shard)
    segs = sorted(glob.glob(os.path.join(sdir, "*.wal")))
    remaining = cut
    for seg in segs:
        size = os.path.getsize(seg)
        if remaining >= size:
            remaining -= size
            continue
        with open(seg, "r+b") as f:
            f.truncate(remaining)
        remaining = 0


def run_crash_case(tmp, strategy, shards, seed, cut_frac):
    """Truncate shard 0's WAL at an arbitrary byte offset; recovery must
    equal a never-crashed reference store built from exactly the
    surviving frames, and match the strategy-independent oracle."""
    wdir = tmp / "wal"
    eng = make_engine(wdir, shards=shards, strategy=strategy,
                      segment_bytes=2048)
    apply_workload(eng, mixed_ops(seed=seed, n_batches=4, batch=32))
    eng.close()

    # Crash: chop shard 0's stream at an arbitrary byte offset.
    sdir = shard_dir(str(wdir), 0)
    total = sum(os.path.getsize(s)
                for s in glob.glob(os.path.join(sdir, "*.wal")))
    truncate_wal_at(wdir, 0, int(cut_frac * total))

    # The durable prefix after the crash.
    surviving = {s: WalReader(str(wdir), s).read_frames()
                 for s in range(shards)}

    rec = recover(str(wdir), config=EngineConfig(devices=0,
                                                 pipeline=False))

    # Reference: a never-crashed store fed exactly the surviving frames.
    ref = make_engine(tmp / "ref", shards=shards, strategy=strategy,
                      wal=False)
    for s in range(shards):
        for fr in surviving[s]:
            replay_frame(ref.shards[s], fr)

    assert_same_store(ref, rec)

    # Oracle cross-check: visible key->val state is exactly what the
    # surviving frames imply, independent of strategy internals.
    oracle = crash_oracle(surviving, rec.router)
    keys = np.array(sorted(oracle), dtype=np.uint64)
    if len(keys):
        found, vals = rec.get_batch(keys)
        assert found.all()
        np.testing.assert_array_equal(
            vals, np.array([oracle[int(k)] for k in keys], np.uint64))
    sk, sv = rec.range_scan(0, UNIVERSE)
    np.testing.assert_array_equal(sk, keys)
    rec.close()
    ref.close()


# Deterministic sweep: the crash-consistency property across all 5
# strategies x shards 1/2/4 at boundary and mid-record cut points —
# always collected, hypothesis or not.
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("cut_frac", [0.33, 0.87])
def test_crash_consistency_sweep(tmp_path, strategy, shards, cut_frac):
    run_crash_case(tmp_path, strategy, shards,
                   seed=hash((strategy, shards)) % 1000, cut_frac=cut_frac)


if HAS_HYPOTHESIS:
    @settings(max_examples=12, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(strategy=st.sampled_from(STRATEGIES),
           shards=st.sampled_from([1, 2, 4]),
           seed=st.integers(0, 2 ** 16),
           cut_frac=st.floats(0.0, 1.0))
    def test_crash_consistency_property(tmp_path_factory, strategy,
                                        shards, seed, cut_frac):
        run_crash_case(tmp_path_factory.mktemp("crash"), strategy,
                       shards, seed, cut_frac)
else:
    @pytest.mark.skip(reason="hypothesis not installed; randomized "
                             "crash property not collected (the "
                             "deterministic sweep above still runs)")
    def test_crash_consistency_property():
        pass
