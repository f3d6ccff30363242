"""Multi-device shard-placement parity suite.

PR 7 gives each engine shard its own XLA device: registry packs are
committed per-device, kernel dispatches pin their launches, and the
pipelined shard workers stop serializing on the default device.  These
tests pin the contract that makes that placement invisible:

  * the device matrix — every range-delete strategy x shard count x
    device count returns byte-identical results AND exact IOStats
    snapshots vs the single-device fallback (``devices=0``, the ungated
    legacy path);
  * the per-device ``upload_bytes`` ledger: packs upload once per
    device in steady state (never once per batch), split across exactly
    the devices the shards were homed on;
  * concurrency — interleaved ``submit()`` streams of mixed OpBatches
    are deterministic across pipeline on/off x devices on/off (per-shard
    FIFO is the only ordering contract, and it is enough);
  * invalidation — a flush/compaction (index-epoch bump) mid-stream
    rebuilds the per-device packs on EVERY device, not just device 0.

The suite needs multiple host-platform devices; tests/conftest.py
forces 4 before jax initializes (cells needing more than the host has
skip).
"""

import os

import numpy as np
import pytest

try:  # optional dev dependency: property tests only run when present
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

import jax

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.engine import Engine, EngineConfig, OpBatch
from repro.launch.mesh import (ensure_host_devices,
                               forced_host_device_count, shard_devices)
from repro.lsm import LSMConfig, STRATEGIES

UNIVERSE = 1 << 20
N_DEVICES = len(jax.devices())


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


def engine_cfg(*, devices, pipeline=None, **kw):
    d = dict(cache_blocks=512, kernel_min_batch=1, kernel_min_areas=1,
             kernel_min_filter=1, cascade_compiled=True, devices=devices,
             pipeline=pipeline)
    d.update(kw)
    return EngineConfig(**d)


def drive(store, rng, rounds=4, universe=2000):
    """A mixed put/delete/range-delete workload with plenty of flushes."""
    for _ in range(rounds):
        keys = rng.integers(0, universe, size=220).astype(np.uint64)
        store.put_batch(keys, keys * np.uint64(3) + np.uint64(1))
        store.delete_batch(rng.integers(0, universe, size=30)
                           .astype(np.uint64))
        for _ in range(5):
            lo = int(rng.integers(0, universe - 80))
            store.range_delete(lo, lo + int(rng.integers(1, 64)))


def build_engine(strategy, shards, devices, seed=42, pipeline=None):
    g = small_gloran() if strategy == "gloran" else None
    eng = Engine(num_shards=shards, strategy=strategy,
                 lsm_config=small_cfg(), gloran_config=g,
                 config=engine_cfg(devices=devices, pipeline=pipeline))
    drive(eng, np.random.default_rng(seed))
    return eng


def io_snapshots(eng):
    return [sh.tree.io.snapshot() for sh in eng.shards]


# --------------------------------------------------------- mesh helpers
class TestMeshHelpers:
    def test_forced_count_parses_xla_flags(self, monkeypatch):
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--foo=1 --xla_force_host_platform_device_count=7 --bar=2")
        assert forced_host_device_count() == 7

    def test_ensure_respects_existing_force(self, monkeypatch):
        """The dryrun-vs-engine contract: whoever forced a count first
        wins; ensure never overwrites XLA_FLAGS (the PR-7 fix for
        dryrun's unconditional 512 overwrite)."""
        flags = "--xla_force_host_platform_device_count=7"
        monkeypatch.setenv("XLA_FLAGS", flags)
        assert ensure_host_devices(512) == 7
        assert os.environ["XLA_FLAGS"] == flags

    def test_ensure_after_backend_init_reports_reality(self, monkeypatch):
        """Backends are initialized in this process (conftest forced 4
        devices), so without a forced flag ensure cannot change the
        count — it must report the live one and leave flags alone."""
        monkeypatch.setenv("XLA_FLAGS", "--some_other_flag=1")
        assert ensure_host_devices(64) == N_DEVICES
        assert os.environ["XLA_FLAGS"] == "--some_other_flag=1"

    def test_shard_devices_round_robin_with_limit(self):
        devs = shard_devices(6, limit=2)
        assert len(devs) == 6
        assert len({d.id for d in devs}) == min(2, N_DEVICES)
        assert devs[0].id == devs[2].id == devs[4].id
        one = shard_devices(4, limit=1)
        assert {d.id for d in one} == {jax.devices()[0].id}


# -------------------------------------------------- device-matrix parity
class TestDeviceMatrixParity:
    """Results, I/O snapshots, and scan output must be byte-identical
    across device counts 1/2/4 vs the single-device fallback."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("shards", (1, 2, 4))
    def test_results_and_io_identical(self, strategy, shards):
        rng = np.random.default_rng(9)
        probe = rng.integers(0, 2100, size=600).astype(np.uint64)
        scan = [(0, 700), (900, 1600)]
        base = build_engine(strategy, shards, devices=0)
        io_drive = io_snapshots(base)  # post-drive, pre-probe charges
        f0, v0 = base.get_batch(probe)
        s0 = base.range_scan_batch(scan)
        assert base.devices is None  # the ungated fallback path
        for devcount in (1, 2, 4):
            if devcount > N_DEVICES:
                pytest.skip(f"host has {N_DEVICES} XLA devices")
            eng = build_engine(strategy, shards, devices=devcount)
            assert io_snapshots(eng) == io_drive, (devcount, "drive io")
            f1, v1 = eng.get_batch(probe)
            s1 = eng.range_scan_batch(scan)
            np.testing.assert_array_equal(f1, f0)
            np.testing.assert_array_equal(v1[f1], v0[f0])
            for (ka, va), (kb, vb) in zip(s1, s0):
                np.testing.assert_array_equal(ka, kb)
                np.testing.assert_array_equal(va, vb)
            assert io_snapshots(eng) == io_snapshots(base), devcount
            dv = eng.stats()["devices"]
            assert dv["enabled"]
            assert dv["distinct"] == min(devcount, shards)

    def test_device_map_round_robin(self):
        eng = Engine(num_shards=4, strategy="gloran",
                     lsm_config=small_cfg(),
                     gloran_config=small_gloran(),
                     config=engine_cfg(devices=2))
        assert eng.device_map() == {0: "cpu:0", 1: "cpu:1",
                                    2: "cpu:0", 3: "cpu:1"}


# ------------------------------------------------------ upload ledger
class TestPerDeviceLedger:
    def test_steady_state_uploads_once_per_device(self):
        """4 shards homed on 4 devices: the pack ledger lands once on
        each device and repeat batches move NOTHING — uploads are per
        device, never per batch."""
        if N_DEVICES < 4:
            pytest.skip(f"host has {N_DEVICES} XLA devices")
        eng = build_engine("gloran", 4, devices=4)
        probe = np.arange(0, 1024, dtype=np.uint64)
        eng.get_batch(probe)
        led0 = eng.kernel_counters.snapshot()["upload_bytes_by_device"]
        assert set(led0) == {f"cpu:{i}" for i in range(4)}
        assert all(v > 0 for v in led0.values())
        for _ in range(4):
            eng.get_batch(probe)
        led1 = eng.kernel_counters.snapshot()["upload_bytes_by_device"]
        assert led1 == led0
        assert sum(led1.values()) == eng.kernel_counters.upload_bytes

    def test_fallback_ledger_lands_on_host(self):
        eng = build_engine("gloran", 2, devices=0)
        eng.get_batch(np.arange(0, 512, dtype=np.uint64))
        led = eng.kernel_counters.snapshot()["upload_bytes_by_device"]
        assert set(led) == {"host"}
        assert led["host"] == eng.kernel_counters.upload_bytes


# --------------------------------------------------------- invalidation
class TestEpochInvalidation:
    def test_epoch_bump_invalidates_packs_on_every_device(self):
        """A mid-stream flush/compaction (index-epoch bump) must rebuild
        the per-device packs on EVERY device, not just device 0 — and
        the post-bump answers must still match the single-device twin
        exactly."""
        if N_DEVICES < 4:
            pytest.skip(f"host has {N_DEVICES} XLA devices")
        eng = build_engine("gloran", 4, devices=4, seed=7)
        twin = build_engine("gloran", 4, devices=0, seed=7)
        probe = np.arange(0, 2048, dtype=np.uint64)
        for e in (eng, twin):
            e.get_batch(probe)  # pack v1 on every shard's device
        packs0 = [sh.kernels.cascade_packs for sh in eng.shards]
        assert all(p >= 1 for p in packs0), "every shard must have packed"
        led0 = eng.kernel_counters.snapshot()["upload_bytes_by_device"]
        # Mid-stream epoch bump on every shard: broadcast range deletes
        # (hash partition) + writes, then flush.
        keys = np.arange(3000, 3800, dtype=np.uint64)
        for e in (eng, twin):
            e.put_batch(keys, keys + np.uint64(5))
            e.range_delete(3000, 3200)
            e.flush()
            e.range_delete(100, 400)  # staged post-flush state too
        f1, v1 = eng.get_batch(probe)
        f0, v0 = twin.get_batch(probe)
        np.testing.assert_array_equal(f1, f0)
        np.testing.assert_array_equal(v1[f1], v0[f0])
        assert io_snapshots(eng) == io_snapshots(twin)
        packs1 = [sh.kernels.cascade_packs for sh in eng.shards]
        assert all(b > a for a, b in zip(packs0, packs1)), \
            (packs0, packs1)
        led1 = eng.kernel_counters.snapshot()["upload_bytes_by_device"]
        assert all(led1[d] > led0[d] for d in led0), (led0, led1)


# ---------------------------------------------------------- concurrency
def op_stream(rng, n_ops, universe=2400):
    """One bursty mixed op stream (puts/gets/deletes/range ops)."""
    ops = []
    while len(ops) < n_ops:
        kind = int(rng.integers(0, 5))
        burst = min(int(rng.integers(1, 24)), n_ops - len(ops))
        if kind == 0:
            for k in rng.integers(0, universe, size=burst).tolist():
                ops.append(("put", k, k * 3 + 1))
        elif kind == 1:
            for k in rng.integers(0, universe, size=burst).tolist():
                ops.append(("get", k))
        elif kind == 2:
            for k in rng.integers(0, universe, size=burst).tolist():
                ops.append(("delete", k))
        elif kind == 3:
            for lo in rng.integers(0, universe - 70, size=burst).tolist():
                ops.append(("range_delete", lo, lo + 40))
        else:
            for lo in rng.integers(0, universe - 300,
                                   size=burst).tolist():
                ops.append(("range_scan", lo, lo + 220))
    return ops


def canon(results):
    """Hashable form of a results list (scan arrays -> bytes)."""
    out = []
    for r in results:
        if isinstance(r, tuple):
            out.append((r[0].tobytes(), r[1].tobytes()))
        else:
            out.append(r)
    return out


def run_interleaved(pipeline, devices, seed, n_batches=6, n_ops=160):
    """Submit a stream of mixed OpBatches ahead of collection and
    return every batch's results + the final I/O snapshots."""
    eng = build_engine("gloran", 4, devices=devices, seed=seed,
                       pipeline=pipeline)
    rng = np.random.default_rng(seed + 1)
    handles = [eng.submit(OpBatch.from_ops(op_stream(rng, n_ops)))
               for _ in range(n_batches)]
    results = [canon(h.results()) for h in handles]
    eng.drain()
    return results, io_snapshots(eng)


class TestConcurrentSubmission:
    @pytest.mark.parametrize("seed", (3, 11))
    def test_interleaved_submits_deterministic_across_modes(self, seed):
        """Pipeline on/off x devices on/off: identical per-batch results
        and I/O under submit-ahead interleaving — per-shard FIFO plus
        deterministic merge-back is the whole ordering contract."""
        configs = [(False, 0), (True, 0), (False, None), (True, None)]
        outs = [run_interleaved(pl, dv, seed) for pl, dv in configs]
        for (res, io), cfg in zip(outs[1:], configs[1:]):
            assert res == outs[0][0], cfg
            assert io == outs[0][1], cfg

    def test_pipelined_devices_fifo_under_jitter(self):
        """Many small batches racing through the shard pools with
        devices on: every collected batch matches the serial twin's
        answer batch-for-batch (thread scheduling cannot reorder a
        shard's work)."""
        a, io_a = run_interleaved(True, None, seed=23, n_batches=10,
                                  n_ops=96)
        b, io_b = run_interleaved(False, 0, seed=23, n_batches=10,
                                  n_ops=96)
        assert a == b
        assert io_a == io_b


if HAS_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 10_000), n_batches=st.integers(2, 6),
           n_ops=st.integers(40, 200))
    def test_hypothesis_interleaved_mixed_batches(seed, n_batches, n_ops):
        """Random interleaved OpBatch streams across shards: pipeline
        on/off x devices on/off all agree, results and I/O."""
        outs = [run_interleaved(pl, dv, seed, n_batches=n_batches,
                                n_ops=n_ops)
                for pl, dv in ((False, 0), (True, 0), (True, None))]
        for res, io in outs[1:]:
            assert res == outs[0][0]
            assert io == outs[0][1]
