"""Fused lookup-cascade parity suite.

The cascade replaces the engine read path's per-level kernel launches
(one bloom launch per SSTable level + one interval launch per DR-tree
level, each re-uploading filter state) with ONE launch over persistent
device arrays.  These tests pin the contract that makes that swap
invisible:

  * results AND simulated I/O charges are bit-identical cascade-on vs
    cascade-off, across all 5 range-delete strategies x shard counts,
    in both dispatch modes (interpret-mode Pallas and the jit'd XLA
    fallback CPU CI compiles);
  * exactly one cascade launch per ``get_batch`` regardless of how many
    levels the tree has (the whole point of the fusion);
  * compaction/flush invalidation: a stale device pack must never serve
    a post-compaction lookup;
  * the kernel agrees with an independent numpy oracle on random packed
    states;
  * the vectorized memtable/put/delete batch paths keep flush points
    and results identical to the historical per-record loops.
"""

import numpy as np
import pytest

try:  # optional dev dependency: property tests only run when present
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

import jax.numpy as jnp

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.core.eve import BloomBits, fold64to32
from repro.engine import Engine, EngineConfig
from repro.kernels.cascade.ops import CascadeState, cascade_lookup
from repro.kernels.cascade.ref import cascade_np
from repro.lsm import LSMConfig, LSMTree, STRATEGIES
from repro.lsm.scheduler import CompactionScheduler

UNIVERSE = 1 << 20
MODES = ("interpret", "compiled")


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


def engine_cfg(*, cascade: bool, mode: str = "compiled", **kw):
    d = dict(cache_blocks=512, kernel_min_batch=1, kernel_min_areas=1,
             kernel_min_filter=1, use_cascade_kernel=cascade,
             cascade_compiled=(mode == "compiled"))
    d.update(kw)
    return EngineConfig(**d)


def drive(store, rng, rounds=5, universe=2000):
    """A mixed put/delete/range-delete workload with plenty of flushes."""
    for _ in range(rounds):
        keys = rng.integers(0, universe, size=220).astype(np.uint64)
        store.put_batch(keys, keys * np.uint64(3) + np.uint64(1))
        store.delete_batch(rng.integers(0, universe, size=30)
                           .astype(np.uint64))
        for _ in range(6):
            lo = int(rng.integers(0, universe - 80))
            store.range_delete(lo, lo + int(rng.integers(1, 64)))


def build_engine(strategy, shards, cascade, mode, seed=42):
    g = small_gloran() if strategy == "gloran" else None
    eng = Engine(num_shards=shards, strategy=strategy,
                 lsm_config=small_cfg(), gloran_config=g,
                 config=engine_cfg(cascade=cascade, mode=mode))
    drive(eng, np.random.default_rng(seed))
    return eng


def io_snapshots(eng):
    return [sh.tree.io.snapshot() for sh in eng.shards]


# ---------------------------------------------------------------- parity
class TestEngineParity:
    """Cascade-on must be indistinguishable from cascade-off in results
    and in every I/O ledger entry."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("shards", (1, 2, 4))
    def test_results_and_io_identical(self, strategy, shards):
        rng = np.random.default_rng(9)
        probe = rng.integers(0, 2100, size=700).astype(np.uint64)
        on = build_engine(strategy, shards, True, "compiled")
        off = build_engine(strategy, shards, False, "compiled")
        f1, v1 = on.get_batch(probe)
        f0, v0 = off.get_batch(probe)
        np.testing.assert_array_equal(f1, f0)
        np.testing.assert_array_equal(v1[f1], v0[f0])
        assert io_snapshots(on) == io_snapshots(off), strategy
        assert on.kernel_counters.cascade_calls > 0

    @pytest.mark.parametrize("mode", MODES)
    def test_dispatch_modes_agree(self, mode):
        """Interpret-mode Pallas and the compiled XLA fallback both
        reproduce the per-level path exactly (gloran, the richest
        stack: bloom + fence + GLORAN interval columns)."""
        rng = np.random.default_rng(3)
        probe = rng.integers(0, 2100, size=500).astype(np.uint64)
        on = build_engine("gloran", 2, True, mode)
        off = build_engine("gloran", 2, False, mode)
        f1, v1 = on.get_batch(probe)
        f0, v0 = off.get_batch(probe)
        np.testing.assert_array_equal(f1, f0)
        np.testing.assert_array_equal(v1[f1], v0[f0])
        assert io_snapshots(on) == io_snapshots(off)

    def test_memtable_overlay_parity(self):
        """Unflushed memtable entries (wins over levels, tombstones,
        validity of memtable-resolved seqs) ride through the cascade."""
        probe = np.arange(0, 600, dtype=np.uint64)
        engines = []
        for cascade in (True, False):
            eng = build_engine("gloran", 1, cascade, "compiled")
            eng.put_batch(np.arange(100, 200, dtype=np.uint64),
                          np.full(100, 7, np.uint64))
            eng.delete_batch(np.arange(150, 170, dtype=np.uint64))
            engines.append(eng)
        (f1, v1), (f0, v0) = (e.get_batch(probe) for e in engines)
        np.testing.assert_array_equal(f1, f0)
        np.testing.assert_array_equal(v1[f1], v0[f0])
        assert io_snapshots(engines[0]) == io_snapshots(engines[1])


if HAS_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000),
           nprobe=st.integers(1, 400),
           strategy=st.sampled_from(("gloran", "lrr")))
    def test_hypothesis_workload_parity(seed, nprobe, strategy):
        """Random workloads: found/vals/IO charges identical on/off."""
        rng = np.random.default_rng(seed)
        probe = rng.integers(0, 2100, size=nprobe).astype(np.uint64)
        on = build_engine(strategy, 2, True, "compiled", seed=seed)
        off = build_engine(strategy, 2, False, "compiled", seed=seed)
        f1, v1 = on.get_batch(probe)
        f0, v0 = off.get_batch(probe)
        np.testing.assert_array_equal(f1, f0)
        np.testing.assert_array_equal(v1[f1], v0[f0])
        assert io_snapshots(on) == io_snapshots(off)


# ------------------------------------------------------ launch counting
class TestLaunchFusion:
    def test_one_launch_per_get_batch_any_level_count(self):
        """The counter contract: one bloom-cascade launch per
        ``get_batch`` per shard, no matter how many levels exist, and
        zero per-level bloom/interval launches alongside it."""
        eng = Engine(num_shards=1, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=engine_cfg(cascade=True))
        rng = np.random.default_rng(1)
        drive(eng, rng, rounds=8)  # size-ratio-3 tree: several levels
        tree = eng.shards[0].tree
        levels = sum(1 for l in tree.levels if l is not None and len(l))
        assert levels >= 2, "workload must build a multi-level tree"
        probe = rng.integers(0, 2100, size=512).astype(np.uint64)
        for i in range(3):
            k0 = eng.kernel_counters
            eng.get_batch(probe)
            k1 = eng.kernel_counters
            assert k1.cascade_calls - k0.cascade_calls == 1, i
            assert k1.bloom_calls == k0.bloom_calls
            assert k1.interval_calls == k0.interval_calls
        assert eng.kernel_counters.cascade_queries >= 3 * 512

    def test_gating_declines_small_batches(self):
        eng = Engine(num_shards=1, strategy="gloran",
                     lsm_config=small_cfg(), gloran_config=small_gloran(),
                     config=engine_cfg(cascade=True, kernel_min_batch=4096))
        keys = np.arange(600, dtype=np.uint64)
        eng.put_batch(keys, keys)
        eng.flush()
        eng.get_batch(keys)
        assert eng.kernel_counters.cascade_calls == 0

    def test_steady_state_uploads_nothing(self):
        """Repeat lookups on an unchanged tree re-use the device pack:
        the upload ledger must not move."""
        eng = build_engine("gloran", 1, True, "compiled")
        probe = np.arange(0, 512, dtype=np.uint64)
        eng.get_batch(probe)
        up0 = eng.kernel_counters.upload_bytes
        packs0 = eng.kernel_counters.cascade_packs
        for _ in range(4):
            eng.get_batch(probe)
        assert eng.kernel_counters.upload_bytes == up0
        assert eng.kernel_counters.cascade_packs == packs0


# --------------------------------------------------------- invalidation
class TestInvalidation:
    def test_compaction_invalidates_device_pack(self):
        """Stale device arrays must never serve a post-compaction
        lookup: after writes/flushes/range deletes move the level set
        and the index epoch, the cascade answers from fresh state."""
        eng = build_engine("gloran", 1, True, "compiled")
        eng.get_batch(np.arange(0, 512, dtype=np.uint64))  # pack v1
        packs0 = eng.kernel_counters.cascade_packs
        keys = np.arange(3000, 3400, dtype=np.uint64)
        eng.put_batch(keys, keys + np.uint64(5))
        eng.range_delete(3000, 3100)
        eng.flush()
        probe = np.arange(2990, 3200, dtype=np.uint64)
        found, vals = eng.get_batch(probe)
        want = (probe >= 3100) & (probe < 3400)
        np.testing.assert_array_equal(found, want)
        np.testing.assert_array_equal(vals[found],
                                      probe[found] + np.uint64(5))
        assert eng.kernel_counters.cascade_packs > packs0

    def test_post_mutation_parity_stays_exact(self):
        """Interleaved lookups and mutations: every lookup round stays
        bit-identical (results + I/O) with the cascade-off twin."""
        rng = np.random.default_rng(77)
        engines = [build_engine("gloran", 2, c, "compiled", seed=77)
                   for c in (True, False)]
        for r in range(4):
            probe = rng.integers(0, 2400, size=300).astype(np.uint64)
            (f1, v1), (f0, v0) = (e.get_batch(probe) for e in engines)
            np.testing.assert_array_equal(f1, f0)
            np.testing.assert_array_equal(v1[f1], v0[f0])
            assert io_snapshots(engines[0]) == io_snapshots(engines[1]), r
            mut = np.random.default_rng(100 + r)
            for e in engines:
                drive(e, np.random.default_rng(100 + r), rounds=1)
            del mut


# ------------------------------------------------------- kernel oracle
def _pow2(n):
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def random_pack(rng, n_levels, n_gl):
    """A random packed cascade state + its host-side mirror."""
    lk, ls, koff, kcnt, wds, woff, mb, sds = [], [], [], [], [], [], [], []
    at = wat = 0
    for l in range(n_levels):
        n = int(rng.integers(1, 400))
        keys = np.unique(rng.integers(0, 1 << 18, size=n)
                         .astype(np.uint64))
        n = len(keys)
        seqs = rng.integers(1, 1 << 18, size=n).astype(np.uint64)
        bb = BloomBits(max(64, n * 10), 6, seed=l + 3)
        bb.insert(keys)
        p = _pow2(n)
        lk.append(np.concatenate([keys.astype(np.uint32),
                                  np.full(p - n, 0xFFFFFFFF, np.uint32)]))
        ls.append(np.concatenate([seqs.astype(np.uint32),
                                  np.zeros(p - n, np.uint32)]))
        koff.append(at)
        kcnt.append(n)
        at += p
        wp = _pow2(len(bb.words))
        wds.append(np.concatenate([bb.words,
                                   np.zeros(wp - len(bb.words),
                                            np.uint32)]))
        woff.append(wat)
        wat += wp
        mb.append(bb.m_bits)
        sds.append(bb.seeds)
    glo = [[], [], [], []]
    goff, gcnt = [], []
    gat = 0
    for g in range(n_gl):
        n = int(rng.integers(0, 150))
        starts = np.sort(rng.choice(
            np.arange(0, 1 << 18, 5, dtype=np.uint64),
            size=n, replace=False)) if n else np.zeros(0, np.uint64)
        ends = starts + rng.integers(1, 5, size=n).astype(np.uint64) \
            if n else starts
        if n > 1:
            ends[:-1] = np.minimum(ends[:-1], starts[1:])
        p = max(64, _pow2(n))
        glo[0].append(np.concatenate(
            [starts.astype(np.uint32),
             np.full(p - n, 0xFFFFFFFF, np.uint32)]))
        glo[1].append(np.concatenate(
            [ends.astype(np.uint32),
             np.full(p - n, 0xFFFFFFFF, np.uint32)]))
        glo[2].append(np.zeros(p, np.uint32))
        glo[3].append(np.concatenate(
            [rng.integers(1, 1 << 18, size=n).astype(np.uint32),
             np.zeros(p - n, np.uint32)]))
        goff.append(gat)
        gcnt.append(n)
        gat += p
    import math
    host = dict(
        lkeys=np.concatenate(lk), lseqs=np.concatenate(ls),
        key_off=np.array(koff, np.int32),
        key_cnt=np.array(kcnt, np.int32),
        words=np.concatenate(wds), word_off=np.array(woff, np.int32),
        mbits=np.array(mb, np.uint32), seeds=np.stack(sds),
        glo_lo=(np.concatenate(glo[0]) if n_gl
                else np.zeros(1, np.uint32)),
        glo_hi=(np.concatenate(glo[1]) if n_gl
                else np.zeros(1, np.uint32)),
        glo_smin=(np.concatenate(glo[2]) if n_gl
                  else np.zeros(1, np.uint32)),
        glo_smax=(np.concatenate(glo[3]) if n_gl
                  else np.zeros(1, np.uint32)),
        gl_off=np.array(goff, np.int32), gl_cnt=np.array(gcnt, np.int32))
    state = CascadeState(
        **{k: jnp.asarray(v) for k, v in host.items()},
        L=n_levels, H=6, G=n_gl,
        steps_keys=int(math.ceil(math.log2(
            max(p.shape[0] for p in lk) + 1))) + 1,
        steps_gl=int(math.ceil(math.log2(
            (max(p.shape[0] for p in glo[0]) if n_gl else 1) + 1))) + 1,
        key_pad=tuple(p.shape[0] for p in lk),
        word_pad=tuple(p.shape[0] for p in wds),
        gl_pad=tuple(p.shape[0] for p in glo[0]))
    return state, host


class TestKernelOracle:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("n_levels,n_gl", [(1, 0), (2, 1), (4, 3)])
    def test_matches_numpy_oracle(self, mode, n_levels, n_gl):
        rng = np.random.default_rng(n_levels * 10 + n_gl)
        state, host = random_pack(rng, n_levels, n_gl)
        n = 333
        q = rng.integers(0, 1 << 18, size=n).astype(np.uint64)
        qh = fold64to32(q)
        qs = rng.integers(0, 1 << 18, size=n).astype(np.uint32)
        qr = (rng.random(n) < 0.2).astype(np.int32)
        bm, hm, gm, pos = cascade_np(q.astype(np.uint32), qh, qs, qr,
                                     **host)
        maybe, hit, gl, p2 = cascade_lookup(
            q.astype(np.uint32), qh, qs, qr, state,
            compiled=(mode == "compiled"), interpret=True)
        lbits = 1 << np.arange(n_levels)
        np.testing.assert_array_equal(
            (maybe * lbits).sum(1).astype(np.int32), bm)
        np.testing.assert_array_equal(
            (hit * lbits).sum(1).astype(np.int32), hm)
        if n_gl:
            gbits = 1 << np.arange(n_gl)
            np.testing.assert_array_equal(
                (gl * gbits).sum(1).astype(np.int32), gm)
        np.testing.assert_array_equal(p2, pos.T)


# ----------------------------------------- vectorized write/probe paths
def run_rows(run) -> dict:
    """A memtable run as ``key -> (seq, type, val)``."""
    return {k: (s, t, v) for k, s, t, v in
            zip(*(x.tolist() for x in run))}


class LoopTree(LSMTree):
    """The reference memtable: per-record writes into a test-local
    ``dict`` (``ref``), flushed when it holds ``buffer_capacity``
    entries.  ``mem`` is rebuilt from ``ref`` after every write call
    and before every flush, so reads, seals and flushes see what a
    dict memtable held."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.ref = {}

    def _sync(self):
        keys = sorted(self.ref)
        rows = np.array([self.ref[k] for k in keys],
                        dtype=np.uint64).reshape(-1, 3)
        self.mem = (np.array(keys, dtype=np.uint64), rows[:, 0],
                    rows[:, 1].astype(np.uint8), rows[:, 2])

    def _mem_insert_batch(self, keys, seqs, typ, vals):
        vals = np.zeros(len(keys), np.uint64) if vals is None else vals
        for k, s, v in zip(np.asarray(keys).tolist(),
                           np.asarray(seqs).tolist(),
                           np.asarray(vals).tolist()):
            self.ref[k] = (s, typ, v)
            if len(self.ref) >= self.config.buffer_capacity:
                self.flush()
        self._sync()

    def flush(self):
        self._sync()
        super().flush()
        self.ref = {}


def tree_pair(strategy="gloran", scheduler=False, **cfg):
    a = LSMTree(small_cfg(**cfg), strategy=strategy,
                gloran_config=small_gloran())
    b = LoopTree(small_cfg(**cfg), strategy=strategy,
                 gloran_config=small_gloran())
    if scheduler:
        for t in (a, b):
            t.scheduler = CompactionScheduler(t, max_frozen=2)
    return a, b


def assert_same_state(a, b):
    """Memtable contents, flush points, level shapes and I/O agree."""
    assert a.seq == b.seq
    assert run_rows(a.mem) == b.ref
    for x, y in zip(a.mem, b.mem):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)
    cols = ("keys", "seqs", "types", "vals")
    assert [None if l is None else [getattr(l, c).tobytes() for c in cols]
            for l in a.levels] == \
        [None if l is None else [getattr(l, c).tobytes() for c in cols]
         for l in b.levels]
    assert [[getattr(f, c).tobytes() for c in cols] for f in a.frozen] == \
        [[getattr(f, c).tobytes() for c in cols] for f in b.frozen]
    assert a.io.snapshot() == b.io.snapshot()


def assert_same_answers(a, b, probe):
    fa, va = a.get_batch(probe)
    fb, vb = b.get_batch(probe)
    np.testing.assert_array_equal(fa, fb)
    np.testing.assert_array_equal(va[fa], vb[fb])
    for j, k in enumerate(probe.tolist()):
        want = b.get(k)
        assert a.get(k) == want, k
        assert (int(va[j]) if fa[j] else None) == want, k
    assert a.io.snapshot() == b.io.snapshot()


def both(a, b, fn):
    fn(a)
    fn(b)
    assert_same_state(a, b)


class TestVectorizedWrites:
    @pytest.mark.parametrize("seed", (0, 1, 2))
    def test_flush_points_and_state_identical(self, seed):
        """Chunked run merges == per-record dict inserts: same memtable,
        flush points, level shapes, I/O charges, and lookup answers
        (duplicates inside a batch keep last-wins order)."""
        rng = np.random.default_rng(seed)
        a, b = tree_pair()
        for _ in range(6):
            keys = rng.integers(0, 500, size=150).astype(np.uint64)
            vals = rng.integers(0, 1 << 30, size=150).astype(np.uint64)
            both(a, b, lambda t: t.put_batch(keys, vals))
            dels = rng.integers(0, 500, size=40).astype(np.uint64)
            both(a, b, lambda t: t.delete_batch(dels))
        assert_same_answers(a, b,
                            rng.integers(0, 600, size=400).astype(np.uint64))

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("scheduler", (False, True))
    def test_dict_oracle_every_strategy(self, strategy, scheduler):
        """Random write calls of every size (in-batch duplicates,
        tombstones, scalar puts and deletes, range deletes), inline or
        sealing into a compaction scheduler drained at random points,
        leave the run memtable where the dict memtable leaves it."""
        rng = np.random.default_rng(STRATEGIES.index(strategy))
        a, b = tree_pair(strategy, scheduler)
        for _ in range(40):
            n = int(rng.choice([1, 7, 63, 64, 65, 200]))
            keys = rng.integers(0, 300, size=n).astype(np.uint64)
            op = rng.integers(0, 5)
            if op == 0:
                both(a, b, lambda t: t.put_batch(keys, keys * np.uint64(3)))
            elif op == 1:
                both(a, b, lambda t: t.delete_batch(keys))
            elif op == 2:
                k = int(keys[0])
                both(a, b, lambda t: (t.put(k, k + 1), t.delete(k + 1)))
            elif op == 3:
                lo = int(keys[0])
                both(a, b, lambda t: t.range_delete(lo, lo + 9))
            elif scheduler:
                both(a, b, lambda t: t.scheduler.run_due())
            else:
                both(a, b, lambda t: t.flush())
        assert_same_answers(a, b, np.arange(0, 320, dtype=np.uint64))

    @pytest.mark.parametrize("fill", ("exact", "dups", "over"))
    def test_fill_ends_at_capacity(self, fill):
        """A batch whose distinct keys end exactly at capacity flushes
        once, at its end; duplicates that keep the count one below do
        not flush; one key past capacity starts the next memtable."""
        a, b = tree_pair(buffer_capacity=64)
        both(a, b, lambda t: t.put_batch(
            np.arange(10, dtype=np.uint64), np.arange(10, dtype=np.uint64)))
        keys = np.arange(10, 64, dtype=np.uint64)  # 54 new: exactly full
        if fill == "dups":
            keys = np.concatenate([keys[:-1], keys[:5]])
        elif fill == "over":
            keys = np.concatenate([keys, [np.uint64(999)]])
        both(a, b, lambda t: t.put_batch(keys, keys + np.uint64(7)))
        flushed = [len(l) for l in a.levels if l is not None]
        assert len(a.mem[0]) == {"exact": 0, "dups": 63, "over": 1}[fill]
        assert flushed == ([] if fill == "dups" else [64])

    def test_reserved_seqs_last_arrival_wins(self):
        """Under ``reserved_seqs`` the seqs may fall as the records
        arrive: among a chunk's duplicates the later record still
        wins, as ``dict.update`` ordered it, not the higher seq; and
        ``seq`` reads the reserved last one at every flush."""
        a, b = tree_pair(buffer_capacity=16)
        rng = np.random.default_rng(5)
        last = 0
        for _ in range(12):
            n = int(rng.integers(1, 40))
            keys = rng.integers(0, 24, size=n).astype(np.uint64)
            seqs = (last + rng.permutation(n) + 1).astype(np.uint64)
            last += n

            def write(t):
                with t.reserved_seqs(seqs, last):
                    t.put_batch(keys, keys + np.uint64(1))
            both(a, b, write)
        assert_same_answers(a, b, np.arange(0, 30, dtype=np.uint64))

    def test_memtable_probe_matches_scalar_get(self):
        """The sorted-run memtable stage answers exactly what the
        scalar ``get`` answers, tombstones included."""
        t = LSMTree(small_cfg(buffer_capacity=1 << 30), strategy="gloran",
                    gloran_config=small_gloran())
        rng = np.random.default_rng(8)
        keys = rng.integers(0, 300, size=200).astype(np.uint64)
        t.put_batch(keys, keys + np.uint64(1))
        t.delete_batch(rng.integers(0, 300, size=50).astype(np.uint64))
        assert len(t.mem[0])  # everything still buffered
        probe = np.arange(0, 320, dtype=np.uint64)
        f, v = t.get_batch(probe)
        for j, k in enumerate(probe.tolist()):
            want = t.get(k)
            got = int(v[j]) if f[j] else None
            assert got == want, k
