"""Plan/submit API: typed op batches, planner compilation, pipelined
vs serial result parity, op-stream ordering semantics, and
malformed-batch validation."""

import numpy as np
import pytest

from repro import obs
from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.durable.manifest import structure_fingerprint
from repro.engine import (OP_DELETE, OP_GET, OP_PUT, OP_RANGE_DELETE,
                          OP_RANGE_SCAN, Engine, EngineConfig, OpBatch,
                          Planner, ShardRouter)
from repro.lsm import LSMConfig, STRATEGIES

UNIVERSE = 1 << 20


def small_cfg(**kw):
    d = dict(buffer_capacity=64, size_ratio=3, key_size=16, value_size=48,
             block_size=512, key_universe=UNIVERSE)
    d.update(kw)
    return LSMConfig(**d)


def small_gloran():
    return GloranConfig(index=LSMDRTreeConfig(buffer_capacity=16,
                                              size_ratio=3, key_size=16,
                                              block_size=512),
                        eve=RAEConfig(capacity=64, key_universe=UNIVERSE))


def make_engine(strategy="gloran", num_shards=4, pipeline=None, **cfg_kw):
    g = small_gloran() if strategy == "gloran" else None
    cfg = EngineConfig(pipeline=pipeline, cache_blocks=256,
                       kernel_min_batch=1, kernel_min_areas=1,
                       kernel_min_filter=1, **cfg_kw)
    return Engine(num_shards=num_shards, strategy=strategy,
                  lsm_config=small_cfg(), gloran_config=g, config=cfg)


def mixed_stream(rng, n, universe=2000, max_len=40):
    """A mixed tuple op stream with every kind interleaved."""
    ops = []
    for _ in range(n):
        r = rng.random()
        if r < 0.45:
            ops.append(("put", int(rng.integers(0, universe)),
                        int(rng.integers(1, 1 << 30))))
        elif r < 0.75:
            ops.append(("get", int(rng.integers(0, universe))))
        elif r < 0.83:
            ops.append(("delete", int(rng.integers(0, universe))))
        elif r < 0.92:
            lo = int(rng.integers(0, universe - 2))
            ops.append(("range_delete", lo,
                        lo + int(rng.integers(1, max_len))))
        else:
            lo = int(rng.integers(0, universe - 2))
            ops.append(("range_scan", lo,
                        lo + int(rng.integers(1, 200))))
    return ops


def assert_results_identical(a: list, b: list):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        if isinstance(x, tuple):
            assert isinstance(y, tuple), i
            assert x[0].tobytes() == y[0].tobytes(), i
            assert x[1].tobytes() == y[1].tobytes(), i
        else:
            assert x == y, i


# ----------------------------------------------------------- construction
class TestOpBatchConstruction:
    def test_typed_constructors(self):
        b = OpBatch.gets([1, 2, 3])
        assert len(b) == 3 and b.kind_name == "get"
        assert b.get_ids.tolist() == [0, 1, 2]
        b = OpBatch.puts([1, 2], [10, 20])
        assert b.kind_name == "put" and b.vals.tolist() == [10, 20]
        b = OpBatch.range_scans([(0, 5), (9, 11)])
        assert b.kind_name == "range_scan"
        assert b.scan_ids.tolist() == [0, 1]
        assert OpBatch.deletes([7]).kind_name == "delete"
        assert OpBatch.range_deletes([(1, 2)]).kind_name == "range_delete"

    def test_from_ops_round_trip(self):
        ops = [("put", 1, 10), ("get", 1), ("delete", 2),
               ("range_delete", 0, 5), ("range_scan", 0, 9)]
        b = OpBatch.from_ops(ops)
        assert b.to_ops() == ops
        assert b.kind_name == "mixed"
        assert b.counts() == {"put": 1, "delete": 1, "get": 1,
                              "range_delete": 1, "range_scan": 1}

    def test_concat(self):
        b = OpBatch.concat([OpBatch.gets([1, 2]),
                            OpBatch.range_scans([(0, 4)])])
        assert len(b) == 3 and b.scan_ids.tolist() == [2]
        assert len(OpBatch.concat([])) == 0

    def test_validation_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown op kind"):
            OpBatch.from_ops([("upsert", 1, 2)])
        with pytest.raises(ValueError, match="unknown op kind"):
            OpBatch(np.array([9], np.uint8))

    def test_validation_bad_arity(self):
        with pytest.raises(ValueError, match="arguments"):
            OpBatch.from_ops([("put", 1)])
        with pytest.raises(ValueError, match="arguments"):
            OpBatch.from_ops([("get", 1, 2)])

    def test_validation_empty_range(self):
        with pytest.raises(ValueError, match="empty range"):
            OpBatch.range_deletes([(5, 5)])
        with pytest.raises(ValueError, match="empty range"):
            OpBatch.from_ops([("range_scan", 9, 3)])

    def test_validation_shape_mismatch(self):
        with pytest.raises(ValueError, match="keys vs"):
            OpBatch.puts([1, 2, 3], [1])
        with pytest.raises(ValueError, match="length"):
            OpBatch(np.zeros(3, np.uint8), keys=np.zeros(2, np.uint64))

    def test_malformed_batch_rejected_by_engine(self):
        eng = make_engine(num_shards=2)
        with pytest.raises(ValueError):
            eng.execute([("get",)])
        with pytest.raises(ValueError):
            eng.range_scan_batch([(10, 10)])


# ---------------------------------------------------------------- planner
class TestPlanner:
    @pytest.mark.parametrize("partition", ["hash", "range"])
    def test_plan_covers_every_op_once_per_owner(self, partition):
        router = ShardRouter(4, partition=partition, universe=UNIVERSE)
        planner = Planner(router)
        rng = np.random.default_rng(7)
        batch = OpBatch.from_ops(mixed_stream(rng, 300))
        plan = planner.plan(batch)
        seen_point: list = []
        seen_range: dict = {}
        for sp in plan.shard_plans:
            prev_write = -1
            for step in sp.steps:
                # Within a step op ids ascend (stream order); write
                # steps ascend across each other (reads may hoist).
                assert (np.diff(step.idx) > 0).all()
                if step.kind not in (OP_GET, OP_RANGE_SCAN):
                    assert step.idx.min() > prev_write
                    prev_write = int(step.idx.max())
                for i in step.idx.tolist():
                    if batch.kinds[i] >= OP_RANGE_DELETE:
                        seen_range[i] = seen_range.get(i, 0) + 1
                    else:
                        seen_point.append(i)
        # Every point op executes exactly once, on one shard.
        assert sorted(seen_point) == \
            np.flatnonzero(batch.kinds <= OP_GET).tolist()
        # Every range op visits each owning shard exactly once.
        for i in np.flatnonzero(batch.kinds >= OP_RANGE_DELETE).tolist():
            owners = router.shards_for_range(int(batch.los[i]),
                                             int(batch.his[i]))
            assert seen_range[i] == len(owners), i

    def test_same_kind_runs_are_grouped(self):
        router = ShardRouter(1, partition="hash", universe=UNIVERSE)
        batch = OpBatch.from_ops([
            ("put", 1, 1), ("put", 2, 2), ("get", 1), ("get", 2),
            ("range_delete", 0, 4), ("get", 1), ("put", 3, 3)])
        (sp,) = Planner(router).plan(batch).shard_plans
        assert [(s.kind, len(s)) for s in sp.steps] == [
            (OP_PUT, 2), (OP_GET, 2), (OP_RANGE_DELETE, 1), (OP_GET, 1),
            (OP_PUT, 1)]

    def test_reads_hoist_past_disjoint_writes(self):
        """Reads that cannot observe an intervening write merge into the
        open read slot; reads that overlap it stay behind it."""
        router = ShardRouter(1, partition="hash", universe=UNIVERSE)
        batch = OpBatch.from_ops([
            ("get", 100), ("range_delete", 0, 50), ("get", 200),
            ("range_scan", 60, 90), ("get", 10), ("range_scan", 40, 70)])
        (sp,) = Planner(router).plan(batch).shard_plans
        kinds = [(s.kind, s.idx.tolist()) for s in sp.steps]
        # get 200 hoists next to get 100; scan [60,90) hoists too; get 10
        # and scan [40,70) overlap the delete and execute after it.
        assert kinds == [(OP_GET, [0, 2]), (OP_RANGE_SCAN, [3]),
                         (OP_RANGE_DELETE, [1]), (OP_GET, [4]),
                         (OP_RANGE_SCAN, [5])]

    def test_hoisted_semantics_match_model(self):
        """Hoisting never changes what a read observes."""
        eng = make_engine(num_shards=2)
        res = eng.execute([
            ("put", 1, 10), ("put", 5, 50), ("put", 9, 90),
            ("get", 9),            # pre-delete
            ("range_delete", 0, 6),
            ("get", 9),            # disjoint: hoists, same verdict
            ("get", 5),            # covered: must see the delete
            ("range_scan", 0, 20),
        ])
        assert res[3] == 90 and res[5] == 90 and res[6] is None
        assert res[7][0].tolist() == [9]

    def test_range_partition_clips_per_shard(self):
        router = ShardRouter(4, partition="range", universe=1000)
        batch = OpBatch.range_scans([(200, 760)])
        plan = Planner(router).plan(batch)
        visits = [(sp.shard, int(st.los[0]), int(st.his[0]))
                  for sp in plan.shard_plans for st in sp.steps]
        assert visits == [(0, 200, 250), (1, 250, 500), (2, 500, 750),
                          (3, 750, 760)]

    def test_clip_ranges_matches_scalar_routing(self):
        rng = np.random.default_rng(11)
        router = ShardRouter(5, partition="range", universe=UNIVERSE)
        los = rng.integers(0, UNIVERSE + 5000, 200).astype(np.uint64)
        his = los + rng.integers(1, UNIVERSE // 2, 200).astype(np.uint64)
        rids, shards, clos, chis = router.clip_ranges(los, his)
        got: dict = {}
        for r, s, a, b in zip(rids.tolist(), shards.tolist(),
                              clos.tolist(), chis.tolist()):
            got.setdefault(r, []).append((s, a, b))
        for r in range(200):
            assert got[r] == router.shards_for_range(int(los[r]),
                                                     int(his[r]))


# ------------------------------------------------------ submit semantics
class TestSubmitSemantics:
    def test_interleaved_ordering_through_opbatch(self):
        """put/get/range_delete/range_scan interleavings observe strict
        request order: each op sees exactly the writes before it."""
        eng = make_engine(num_shards=4)
        res = eng.submit(OpBatch.from_ops([
            ("put", 10, 100), ("put", 11, 110), ("get", 10),
            ("range_scan", 0, 20),
            ("range_delete", 0, 11),
            ("get", 10), ("get", 11),
            ("range_scan", 0, 20),
            ("put", 10, 200), ("get", 10),
            ("delete", 11), ("get", 11),
            ("range_scan", 0, 20),
        ])).results()
        assert res[2] == 100
        assert res[3][0].tolist() == [10, 11]
        assert res[3][1].tolist() == [100, 110]
        assert res[5] is None and res[6] == 110
        assert res[7][0].tolist() == [11]
        assert res[9] == 200 and res[11] is None
        assert res[12][0].tolist() == [10]
        assert res[12][1].tolist() == [200]

    def test_typed_accessors(self):
        eng = make_engine(num_shards=2)
        eng.put_batch(np.arange(100, dtype=np.uint64),
                      np.arange(100, dtype=np.uint64) * np.uint64(3))
        pending = eng.submit(OpBatch.gets(np.arange(50, dtype=np.uint64)))
        found, vals = pending.get_results()
        assert found.all()
        np.testing.assert_array_equal(
            vals, np.arange(50, dtype=np.uint64) * np.uint64(3))
        pending = eng.submit(OpBatch.range_scans([(0, 10), (90, 200)]))
        (k0, v0), (k1, v1) = pending.scan_results()
        assert k0.tolist() == list(range(10))
        assert k1.tolist() == list(range(90, 100))
        # wait() is idempotent; accessors can be re-read.
        pending.wait().wait()
        assert pending.scan_results()[0][0].tolist() == list(range(10))

    def test_submit_overlaps_with_planning(self):
        """Pipelined submit returns a live handle; several batches can
        be in flight and collect in any order with correct results."""
        eng = make_engine(num_shards=4, pipeline=True)
        keys = np.arange(2000, dtype=np.uint64)
        eng.put_batch(keys, keys + np.uint64(5))
        eng.flush()
        pendings = [eng.submit(OpBatch.gets(keys[i::4]))
                    for i in range(4)]
        for i, p in reversed(list(enumerate(pendings))):
            found, vals = p.get_results()
            assert found.all()
            np.testing.assert_array_equal(vals, keys[i::4] + np.uint64(5))
        assert all(p.done() for p in pendings)
        eng.drain()

    def test_write_read_order_across_inflight_batches(self):
        """A later submit must observe an earlier in-flight submit's
        writes (per-shard FIFO)."""
        eng = make_engine(num_shards=4, pipeline=True)
        keys = np.arange(500, dtype=np.uint64)
        p1 = eng.submit(OpBatch.puts(keys, keys + np.uint64(1)))
        p2 = eng.submit(OpBatch.range_deletes([(100, 300)]))
        p3 = eng.submit(OpBatch.gets(keys))
        found, vals = p3.get_results()
        live = (keys < 100) | (keys >= 300)
        np.testing.assert_array_equal(found, live)
        np.testing.assert_array_equal(vals[found], keys[live] + np.uint64(1))
        p1.wait(), p2.wait()

    def test_shard_wall_and_stall_stats(self):
        eng = make_engine(num_shards=4, pipeline=True)
        keys = np.arange(3000, dtype=np.uint64)
        eng.put_batch(keys, keys)
        eng.flush()
        eng.get_batch(keys)
        snap = eng.stats()["engine"]
        assert snap["pipelined_batches"] > 0
        assert len(snap["shard_wall_seconds"]) == 4
        assert len(snap["shard_stall_seconds"]) == 4
        assert all(v >= 0 for v in snap["shard_stall_seconds"].values())

    def test_serial_engine_records_serial_batches(self):
        eng = make_engine(num_shards=2, pipeline=False)
        eng.put_batch(np.arange(10, dtype=np.uint64),
                      np.arange(10, dtype=np.uint64))
        snap = eng.stats()["engine"]
        assert snap["serial_batches"] > 0
        assert snap["pipelined_batches"] == 0

    def test_serial_submit_dropped_handle_still_lands_in_stats(self):
        """A serial submit collects inline: even if the caller drops
        the PendingBatch, the ops are recorded."""
        eng = make_engine(num_shards=2, pipeline=False)
        eng.submit(OpBatch.puts(np.arange(20, dtype=np.uint64),
                                np.arange(20, dtype=np.uint64)))
        snap = eng.stats()["engine"]
        assert snap["ops"].get("put") == 20
        assert snap["serial_batches"] == 1

    def test_pipeline_env_default(self, monkeypatch):
        monkeypatch.setenv("REPRO_ENGINE_PIPELINE", "0")
        assert not make_engine(num_shards=2).pipeline_default
        monkeypatch.setenv("REPRO_ENGINE_PIPELINE", "1")
        assert make_engine(num_shards=2).pipeline_default
        # Explicit config wins over the environment.
        assert not make_engine(num_shards=2,
                               pipeline=False).pipeline_default


# ----------------------------------------------------- pipelined parity
class TestPipelinedParity:
    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("num_shards", [1, 2, 4])
    def test_pipelined_identical_to_serial(self, strategy, num_shards):
        """``submit(pipeline=True)`` returns byte-identical results to
        the serial path for mixed op streams, for every strategy and
        shard count."""
        rng = np.random.default_rng(61)
        stream = mixed_stream(rng, 260)
        engines = [make_engine(strategy=strategy, num_shards=num_shards,
                               pipeline=pl) for pl in (False, True)]
        # Several submits so pipelined batches genuinely overlap.
        for i in range(0, len(stream), 65):
            batch_ops = stream[i:i + 65]
            res = [eng.submit(OpBatch.from_ops(batch_ops)).results()
                   for eng in engines]
            assert_results_identical(res[0], res[1])
        probe = rng.integers(0, 2100, size=400).astype(np.uint64)
        f0, v0 = engines[0].get_batch(probe)
        f1, v1 = engines[1].get_batch(probe)
        assert f0.tobytes() == f1.tobytes()
        assert v0[f0].tobytes() == v1[f1].tobytes()

    @pytest.mark.parametrize("partition", ["hash", "range"])
    def test_parity_across_partitions_with_flush(self, partition):
        rng = np.random.default_rng(67)
        engines = [make_engine(num_shards=4, pipeline=pl,
                               partition=partition)
                   for pl in (False, True)]
        for round_ in range(3):
            stream = mixed_stream(rng, 150, universe=UNIVERSE)
            batch = OpBatch.from_ops(stream)
            res = [eng.submit(batch).results() for eng in engines]
            assert_results_identical(res[0], res[1])
            for eng in engines:
                eng.flush()


# -------------------------------------------------------- fused write runs
def interleaved_writes(rng, n, universe=2000, max_len=40, reads=0.0):
    """A batch of puts, point deletes and range deletes (and, with
    ``reads``, that share of gets) in shuffled order, so each shard's
    plan alternates short write steps of every kind."""
    kinds = rng.choice(np.array([OP_PUT] * 10 + [OP_DELETE]
                                + [OP_RANGE_DELETE] * 2, np.uint8), n)
    kinds[rng.random(n) < reads] = OP_GET
    keys = rng.integers(0, universe, n).astype(np.uint64)
    los = rng.integers(0, universe - max_len, n).astype(np.uint64)
    his = los + rng.integers(1, max_len, n).astype(np.uint64)
    rd = kinds == OP_RANGE_DELETE
    z = np.zeros(n, np.uint64)
    return OpBatch(kinds, keys=np.where(rd, z, keys),
                   vals=np.where(kinds == OP_PUT, keys * np.uint64(7) + 1, z),
                   los=np.where(rd, los, z), his=np.where(rd, his, z))


def fusion_engine(strategy, shards, scheduler):
    """Tiny memtable, index buffer and EVE filters, so memtable flushes,
    bottom compactions, index flushes and GC land inside write runs."""
    g = GloranConfig(index=LSMDRTreeConfig(buffer_capacity=8, size_ratio=3,
                                           key_size=16, block_size=512),
                     eve=RAEConfig(capacity=16, key_universe=UNIVERSE))
    return Engine(num_shards=shards, strategy=strategy,
                  lsm_config=small_cfg(buffer_capacity=24),
                  gloran_config=g if strategy == "gloran" else None,
                  config=EngineConfig(pipeline=False, devices=0,
                                      scheduler=scheduler))


def shard_state(sh) -> dict:
    """Everything a write run can leave behind on one shard."""
    t = sh.tree
    cols = ("keys", "seqs", "types", "vals")
    state = {
        "seq": t.seq, "io": t.io.snapshot(),
        "mem": [x.tobytes() for x in t.mem],
        "levels": [None if lvl is None else
                   [getattr(lvl, c).tobytes() for c in cols]
                   for lvl in t.levels],
        "frozen": [[getattr(f, c).tobytes() for c in cols]
                   for f in t.frozen],
        "index_epoch": structure_fingerprint(t)[1]}
    g = t.gloran
    if g is not None:
        state["gc_floor"] = g.gc_floor
        state["eve"] = [(r.count, r.min_seq, r.max_seq,
                         r.bloom.words.tobytes()) for r in g.eve.chain]
        state["index"] = [None if lvl is None else
                          [getattr(lvl.areas, c).tobytes()
                           for c in ("lo", "hi", "smin", "smax")]
                          for lvl in g.index.levels]
        state["staged"] = g.index.buffer.extract_all().lo.tobytes()
    return state


def _drive_fusion(eng, seed):
    """Interleaved WriteBatches, each followed by a MultiGet, and every
    fourth round a mixed request with hoisted reads; per request its
    answers and whether each shard's level structure moved."""
    rng = np.random.default_rng(seed)
    out = []
    for r in range(16):
        batches = [interleaved_writes(rng, 64),
                   OpBatch.gets(rng.integers(0, 2000, 150))]
        if r % 4 == 3:
            batches.append(interleaved_writes(rng, 96, reads=0.3))
        for b in batches:
            fp0 = [structure_fingerprint(sh.tree) for sh in eng.shards]
            found, vals = eng.submit(b).get_results()
            moved = [structure_fingerprint(sh.tree) != f
                     for sh, f in zip(eng.shards, fp0)]
            out.append((found.tobytes(), vals[found].tobytes(), moved))
    eng.drain()
    return out


@pytest.mark.parametrize("scheduler", [False, True])
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("strategy", ["gloran", "lrr", "decomp"])
def test_fused_write_runs_match_step_by_step(strategy, shards, scheduler):
    """A fused write run leaves answers, I/O ledgers, level shapes and
    contents, seqs, the GC floor and the EVE chain exactly as applying
    its plan steps one by one does; only GLORAN trees fuse."""
    fused = fusion_engine(strategy, shards, scheduler)
    ref = fusion_engine(strategy, shards, scheduler)
    for sh in ref.shards:
        sh._fuses = lambda run: False
    with obs.enabled() as tr:
        got = _drive_fusion(fused, seed=100 + shards)
    want = _drive_fusion(ref, seed=100 + shards)
    assert got == want
    for a, b in zip(fused.shards, ref.shards):
        assert shard_state(a) == shard_state(b)
    n_fused = fused.stats()["executor"]["write_runs_fused"]
    assert ref.stats()["executor"]["write_runs_fused"] == 0
    if strategy != "gloran":
        assert n_fused == 0
        return
    assert n_fused > 0
    assert all(sh.tree.gloran.gc_floor > 0 for sh in fused.shards)
    # Memtable flushes (seals with the scheduler on) and index flushes
    # happened inside fused runs, not only between them.
    evs = tr.events()
    runs = [e for e in evs if e["name"] == "shard.write_run"]
    for name in ("lsm.seal" if scheduler else "lsm.flush",
                 "gloran.index_flush"):
        assert any(r["tid"] == e["tid"] and r["t0"] <= e["t0"]
                   and e["t1"] <= r["t1"]
                   for e in evs if e["name"] == name for r in runs), name
