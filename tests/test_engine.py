"""Sharded batched query engine: routing, caching, kernel filter stage,
and end-to-end equivalence with the scalar LSM-tree read path."""

import numpy as np
import pytest

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.engine import BlockCache, Engine, EngineConfig, ShardRouter
from repro.lsm import LSMConfig, LSMTree, STRATEGIES

UNIVERSE = 1 << 20


def small_cfg(**kw):
    d = dict(buffer_capacity=64, size_ratio=3, key_size=16, value_size=48,
             block_size=512, key_universe=UNIVERSE)
    d.update(kw)
    return LSMConfig(**d)


def small_gloran(index_buffer=16):
    return GloranConfig(index=LSMDRTreeConfig(buffer_capacity=index_buffer,
                                              size_ratio=3, key_size=16,
                                              block_size=512),
                        eve=RAEConfig(capacity=64, key_universe=UNIVERSE))


def kernel_cfg(**kw):
    d = dict(cache_blocks=512, kernel_min_batch=1, kernel_min_areas=1,
             kernel_min_filter=1)
    d.update(kw)
    return EngineConfig(**d)


class Model:
    def __init__(self):
        self.d = {}

    def apply(self, op):
        if op[0] == "put":
            self.d[op[1]] = op[2]
        elif op[0] == "del":
            self.d.pop(op[1], None)
        else:
            for k in [k for k in self.d if op[1] <= k < op[2]]:
                del self.d[k]

    def get(self, k):
        return self.d.get(k)


def make_ops(rng, n, universe=2000, rdel_ratio=0.06, max_len=100):
    ops = []
    for _ in range(n):
        r = rng.random()
        if r < rdel_ratio:
            lo = int(rng.integers(0, universe - 2))
            ops.append(("rdel", lo, lo + int(rng.integers(1, max_len))))
        elif r < rdel_ratio + 0.05:
            ops.append(("del", int(rng.integers(0, universe))))
        else:
            ops.append(("put", int(rng.integers(0, universe)),
                        int(rng.integers(1, 1 << 30))))
    return ops


def drive(engine, model, ops):
    for op in ops:
        if op[0] == "put":
            engine.put(op[1], op[2])
        elif op[0] == "del":
            engine.delete(op[1])
        else:
            engine.range_delete(op[1], op[2])
        model.apply(op)


# ------------------------------------------------------------- routing
class TestRouter:
    @pytest.mark.parametrize("partition", ["hash", "range"])
    def test_every_key_owns_one_shard(self, partition):
        r = ShardRouter(4, partition=partition, universe=UNIVERSE)
        keys = np.random.default_rng(0).integers(
            0, UNIVERSE, size=2000).astype(np.uint64)
        sid = r.shard_of(keys)
        assert sid.min() >= 0 and sid.max() < 4
        # split covers every request index exactly once
        idxs = np.concatenate(r.split(keys))
        assert sorted(idxs.tolist()) == list(range(len(keys)))

    def test_hash_spreads_uniformly(self):
        r = ShardRouter(8, partition="hash", universe=UNIVERSE)
        keys = np.arange(80_000, dtype=np.uint64)  # adversarially dense
        counts = np.bincount(r.shard_of(keys), minlength=8)
        assert counts.min() > 0.8 * counts.mean()

    def test_range_clips_range_ops(self):
        r = ShardRouter(4, partition="range", universe=1000)
        parts = r.shards_for_range(200, 760)
        assert parts == [(0, 200, 250), (1, 250, 500), (2, 500, 750),
                         (3, 750, 760)]

    def test_range_partition_out_of_universe_keys(self):
        """shard_of clamps keys >= universe into the last shard; range
        ops must reach them there (the last slab is unbounded above)."""
        r = ShardRouter(4, partition="range", universe=1000)
        assert r.shards_for_range(4000, 6000) == [(3, 4000, 6000)]
        eng = Engine(num_shards=4, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=EngineConfig(partition="range"))
        key = UNIVERSE + 123
        eng.put(key, 7)
        assert eng.get(key) == 7
        eng.range_delete(UNIVERSE, UNIVERSE + 1000)
        assert eng.get(key) is None

    @pytest.mark.parametrize("partition", ["hash", "range"])
    def test_round_trip_request_order(self, partition):
        """Batched results come back in request order across shards."""
        eng = Engine(num_shards=4, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=EngineConfig(partition=partition))
        keys = np.random.default_rng(1).permutation(
            np.arange(3000, dtype=np.uint64))
        vals = keys * np.uint64(7) + np.uint64(13)
        eng.put_batch(keys, vals)
        probe = np.random.default_rng(2).permutation(keys)[:1200]
        found, got = eng.get_batch(probe)
        assert found.all()
        np.testing.assert_array_equal(got,
                                      probe * np.uint64(7) + np.uint64(13))

    def test_execute_mixed_ops_in_order(self):
        eng = Engine(num_shards=4, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran())
        res = eng.execute([
            ("put", 10, 100), ("put", 11, 110), ("get", 10),
            ("range_delete", 0, 11), ("get", 10), ("get", 11),
            ("put", 10, 200), ("get", 10), ("delete", 11), ("get", 11),
        ])
        assert res == [None, None, 100, None, None, 110, None, 200,
                       None, None]


# -------------------------------------------------------------- caching
class TestBlockCache:
    def test_lru_hit_miss_accounting(self):
        c = BlockCache(capacity_blocks=2)
        hit = c.probe_many(1, np.array([0, 1, 0]))
        assert hit.tolist() == [False, False, True]
        assert (c.hits, c.misses) == (1, 2)
        # The duplicate hit made block 0 most-recent, so admitting block 2
        # evicts block 1 (the LRU entry).
        c.probe_many(1, np.array([2]))
        assert c.probe_many(1, np.array([0]))[0]  # still resident
        assert not c.probe_many(1, np.array([1]))[0]  # evicted

    def test_disabled_cache_never_hits(self):
        c = BlockCache(0)
        assert not c.probe_many(1, np.array([0, 0, 0])).any()
        assert c.hits == 0

    def test_engine_repeated_lookups_skip_io(self):
        """Read-through cache: the second identical lookup batch charges
        (almost) no data-block I/O."""
        eng = Engine(num_shards=2, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=kernel_cfg())
        keys = np.arange(0, 3000, dtype=np.uint64)
        eng.put_batch(keys, keys + np.uint64(1))
        eng.flush()
        probe = keys[::3]
        r0 = eng.io_reads
        eng.get_batch(probe)
        cold = eng.io_reads - r0
        r0 = eng.io_reads
        eng.get_batch(probe)
        warm = eng.io_reads - r0
        assert warm < cold
        snap = eng.cache_snapshot()
        assert snap["hits"] > 0
        assert snap["hit_rate"] > 0.4


# ------------------------------------------------------- kernel filters
class TestKernelPath:
    def test_interval_and_bloom_kernels_are_hit(self):
        """Batched lookups on a DR-tree level execute through the Pallas
        interval kernel (and SSTable filters through the bloom kernel).
        The fused cascade (which supersedes both with one launch, see
        tests/test_cascade.py) is pinned off: this covers the per-level
        fallback path."""
        eng = Engine(num_shards=2, strategy="gloran",
                     lsm_config=small_cfg(),
                     gloran_config=small_gloran(index_buffer=8),
                     config=kernel_cfg(use_cascade_kernel=False))
        rng = np.random.default_rng(3)
        model = Model()
        drive(eng, model, make_ops(rng, 1500, rdel_ratio=0.15))
        eng.flush()
        probe = rng.integers(0, 2100, size=600).astype(np.uint64)
        found, vals = eng.get_batch(probe)
        kc = eng.kernel_counters
        assert kc.interval_calls > 0 and kc.interval_queries > 0
        assert kc.bloom_calls > 0 and kc.bloom_queries > 0
        for j, k in enumerate(probe.tolist()):
            want = model.get(k)
            assert bool(found[j]) == (want is not None), k
            if want is not None:
                assert vals[j] == want

    def test_kernel_gating_thresholds(self):
        """Small batches stay on the numpy filters (no kernel launches)."""
        eng = Engine(num_shards=1, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=EngineConfig(kernel_min_batch=4096))
        keys = np.arange(500, dtype=np.uint64)
        eng.put_batch(keys, keys)
        eng.range_delete(0, 100)
        eng.flush()
        eng.get_batch(keys)
        kc = eng.kernel_counters
        assert kc.interval_calls == 0 and kc.bloom_calls == 0


# --------------------------------------------------------- equivalence
class TestEquivalence:
    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_engine_matches_scalar_tree(self, strategy):
        """The Pallas-backed batched read path returns exactly what the
        scalar ``LSMTree.get`` path returns, for every strategy, under a
        randomized put/delete/range-delete workload."""
        rng = np.random.default_rng(17)
        ops = make_ops(rng, 1200, rdel_ratio=0.08)
        g = small_gloran() if strategy == "gloran" else None
        eng = Engine(num_shards=4, strategy=strategy,
                     lsm_config=small_cfg(), gloran_config=g,
                     config=kernel_cfg())
        tree = LSMTree(small_cfg(), strategy=strategy, gloran_config=g)
        model = Model()
        drive(eng, model, ops)
        for op in ops:
            if op[0] == "put":
                tree.put(op[1], op[2])
            elif op[0] == "del":
                tree.delete(op[1])
            else:
                tree.range_delete(op[1], op[2])
        probe = rng.integers(0, 2100, size=800).astype(np.uint64)
        found, vals = eng.get_batch(probe)
        for j, k in enumerate(probe.tolist()):
            scalar = tree.get(k)
            batched = int(vals[j]) if found[j] else None
            assert batched == scalar == model.get(k), (strategy, k)

    @pytest.mark.parametrize("partition", ["hash", "range"])
    def test_range_scan_matches_scalar(self, partition):
        rng = np.random.default_rng(23)
        ops = make_ops(rng, 900, rdel_ratio=0.08)
        eng = Engine(num_shards=3, strategy="gloran",
                     lsm_config=small_cfg(),
                     gloran_config=small_gloran(),
                     config=EngineConfig(partition=partition))
        model = Model()
        drive(eng, model, ops)
        for _ in range(10):
            lo = int(rng.integers(0, 1900))
            hi = lo + int(rng.integers(1, 300))
            ks, vs = eng.range_scan(lo, hi)
            got = sorted(zip(ks.tolist(), vs.tolist()))
            want = sorted((k, v) for k, v in model.d.items()
                          if lo <= k < hi)
            assert got == want, (partition, lo, hi)

    def test_sharded_registry_equivalent_to_unsharded(self):
        from repro.runtime import SessionRegistry
        regs = [SessionRegistry(strategy="gloran", num_shards=s,
                                engine_config=kernel_cfg() if s > 1
                                else None)
                for s in (1, 4)]
        for reg in regs:
            for sid in range(800):
                reg.register(sid, np.arange(4), np.arange(4) + sid)
            for lo in range(0, 600, 50):
                reg.expire_range(lo, lo + 30)
            reg.flush()
        sids = np.repeat(np.arange(800, dtype=np.uint64), 2)
        pages = np.tile(np.arange(2, dtype=np.uint64), 800)
        f1, v1 = regs[0].lookup(sids, pages)
        f4, v4 = regs[1].lookup(sids, pages)
        np.testing.assert_array_equal(f1, f4)
        np.testing.assert_array_equal(v1[f1], v4[f4])


# ------------------------------------------------------ fused write runs
def _writebatch(rng, n, kinds, universe=2000):
    """A WriteBatch of ``n`` ops drawn from ``kinds`` in shuffled order."""
    from repro.engine import OP_PUT, OP_RANGE_DELETE, OpBatch
    k = rng.choice(np.array(kinds, np.uint8), n)
    keys = rng.integers(0, universe, n).astype(np.uint64)
    los = rng.integers(0, universe - 50, n).astype(np.uint64)
    rd = k == OP_RANGE_DELETE
    z = np.zeros(n, np.uint64)
    return OpBatch(k, keys=np.where(rd, z, keys),
                   vals=np.where(k == OP_PUT, keys + np.uint64(1), z),
                   los=np.where(rd, los, z),
                   his=np.where(rd, los + np.uint64(50), z))


def _fusion_engine(strategy, pipeline=False):
    return Engine(num_shards=2, strategy=strategy,
                  lsm_config=small_cfg(buffer_capacity=4096),
                  gloran_config=small_gloran(index_buffer=4096)
                  if strategy == "gloran" else None,
                  config=EngineConfig(pipeline=pipeline,
                                      devices=0))


class TestWriteRunFusion:
    @pytest.mark.parametrize("strategy,kinds,fuses", [
        ("gloran", "put+range_delete", True),
        ("gloran", "put+delete+range_delete", True),
        ("gloran", "delete+range_delete", True),
        ("gloran", "put", False),
        ("gloran", "range_delete", False),
        ("gloran", "put+delete", False),
        ("lrr", "put+delete+range_delete", False),
        ("decomp", "put+delete+range_delete", False),
        ("lookup_delete", "put+range_delete", False),
        ("scan_delete", "put+range_delete", False),
    ])
    def test_engagement_rule(self, strategy, kinds, fuses):
        """Only GLORAN write runs that mix range deletes with memtable
        writes fuse, and the counters say how many runs and steps."""
        from repro.engine import KIND_CODES
        codes = [KIND_CODES[k] for k in kinds.split("+")]
        eng = _fusion_engine(strategy)
        rng = np.random.default_rng(3)
        want_runs = want_steps = 0
        for _ in range(3):
            batch = _writebatch(rng, 120, codes)
            for sp in eng.planner.plan(batch).shard_plans:
                if len({s.kind for s in sp.steps}) > 1 and fuses:
                    want_runs += 1
                    want_steps += len(sp.steps)
            eng.submit(batch).get_results()
        ex = eng.stats()["executor"]
        assert ex == {"write_runs_fused": want_runs,
                      "write_steps_fused": want_steps}
        assert (want_runs > 0) == fuses
        assert eng.stats()["metrics"]["executor.write_runs_fused"] == \
            want_runs

    def test_write_run_span_holds_one_call_of_each_kind(self):
        """A fused run opens ``shard.write_run`` with its step and op
        counts, and inside it one ``shard.range_delete`` and one
        ``shard.put`` span; the EVE insert stays under the former."""
        from repro import obs
        from repro.engine import OP_PUT, OP_RANGE_DELETE
        eng = _fusion_engine("gloran", pipeline=True)
        batch = _writebatch(np.random.default_rng(5), 200,
                            [OP_PUT, OP_PUT, OP_RANGE_DELETE])
        plans = eng.planner.plan(batch).shard_plans
        with obs.enabled() as tr:
            eng.submit(batch).get_results()
        evs = tr.events()
        runs = [e for e in evs if e["name"] == "shard.write_run"]
        assert len(runs) == 2
        for sp in plans:
            run = next(e for e in runs if e["attrs"]["shard"] == sp.shard)
            assert run["attrs"]["steps"] == len(sp.steps) > 2
            assert run["attrs"]["puts"] == sum(
                len(s) for s in sp.steps if s.kind == OP_PUT)
            assert run["attrs"]["range_deletes"] == sum(
                len(s) for s in sp.steps if s.kind == OP_RANGE_DELETE)
            inside = [e for e in evs if e["tid"] == run["tid"]
                      and run["t0"] <= e["t0"] and e["t1"] <= run["t1"]
                      and e["name"].startswith("shard.")
                      and e is not run]
            assert sorted(e["name"] for e in inside) == \
                ["shard.put", "shard.range_delete"]
            rdel = next(e for e in inside
                        if e["name"] == "shard.range_delete")
            assert rdel["attrs"]["n"] == run["attrs"]["range_deletes"]
            eve = [e for e in evs if e["name"] == "gloran.eve"
                   and e["tid"] == run["tid"]]
            assert len(eve) == 1
            assert rdel["t0"] <= eve[0]["t0"] and eve[0]["t1"] <= rdel["t1"]

    def test_timed_io_sleeps_once_per_fused_run(self, monkeypatch):
        """In timed-I/O mode a fused run waits once, for the I/O its
        steps charged together: the same total as step by step."""
        from repro.engine import OP_DELETE, OP_PUT, OP_RANGE_DELETE
        from repro.engine import executor as executor_mod
        slept = {}

        def run(fused):
            calls = []
            monkeypatch.setattr(executor_mod.time, "sleep", calls.append)
            eng = Engine(num_shards=2, strategy="gloran",
                         lsm_config=small_cfg(buffer_capacity=24),
                         gloran_config=small_gloran(index_buffer=8),
                         config=EngineConfig(pipeline=False,
                                             devices=0, io_wait_s=1e-6))
            if not fused:
                for sh in eng.shards:
                    sh._fuses = lambda steps: False
            rng = np.random.default_rng(9)
            for _ in range(6):
                eng.submit(_writebatch(rng, 80, [OP_PUT, OP_PUT, OP_DELETE,
                                                 OP_RANGE_DELETE])
                           ).get_results()
            slept[fused] = calls
            return eng.stats()["executor"]["write_runs_fused"]

        assert run(True) == 12 and run(False) == 0
        assert len(slept[True]) <= 12 < len(slept[False])
        assert sum(slept[True]) == pytest.approx(sum(slept[False]))
        assert sum(slept[True]) > 0
