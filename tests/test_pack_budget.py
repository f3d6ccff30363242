"""The cascade's pack budget follows the form that runs and the device
that holds the pack.

The Pallas form holds a launch's whole pack in VMEM and keeps the
``MAX_PACK_*`` limits; the XLA form reads its operands from HBM and may
take a share of its home device's memory.  An engine with one shard per
device admits a pack past the VMEM key cap under the XLA form, answers
every lookup as a ``dict`` replay does, and reports each device's
resident pack bytes.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.engine import Engine, EngineConfig, OpBatch
from repro.kernels.cascade import ops
from repro.kernels.dispatch import PALLAS, XLA
from repro.lsm import LSMConfig

UNIVERSE = 1 << 32
SLAB = UNIVERSE // 4  # a range-partitioned shard's share of the keys


class _Device:
    """A device as ``pack_budget`` sees it: its ``memory_stats()``."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_pallas_form_keeps_the_vmem_limits():
    for dev in (None, _Device({"bytes_limit": 16 << 30})):
        b = ops.pack_budget(PALLAS, dev)
        assert (b.keys, b.words, b.areas, b.bytes) == (
            ops.MAX_PACK_KEYS, ops.MAX_PACK_WORDS, ops.MAX_PACK_AREAS,
            ops.MAX_PACK_BYTES)


@pytest.mark.parametrize("limit", [16 << 30, 3 << 30])
def test_xla_form_takes_a_share_of_device_memory(limit):
    b = ops.pack_budget(XLA, _Device({"bytes_limit": limit,
                                      "bytes_in_use": 1 << 20}))
    assert b.bytes == int(limit * ops.HBM_PACK_SHARE)
    assert (b.keys, b.words, b.areas) == (b.bytes // 8, b.bytes // 4,
                                          b.bytes // 16)
    # The one-chip deployment's largest pack (a 2^22-slot level beside
    # the rest: ~4.8M key slots, ~43 MB) fits a 16 GiB chip's share.
    if limit == 16 << 30:
        assert b.keys > 4_800_000 and b.bytes > 43 << 20


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
def test_xla_form_without_a_device_limit_takes_the_host_ceiling(stats):
    b = ops.pack_budget(XLA, _Device(stats))
    assert b.bytes == ops.HOST_PACK_BYTES
    assert b.keys == ops.HOST_PACK_BYTES // 8
    # This backend's own default device, as the legacy path uses it.
    if jax.default_backend() == "cpu":
        assert ops.pack_budget(XLA).bytes == ops.HOST_PACK_BYTES


def test_state_nbytes_is_pack_bytes_of_its_pads():
    st = SimpleNamespace(key_pad=(1 << 20, 64), word_pad=(1 << 16, 8),
                         gl_pad=(64,))
    assert ops.CascadeState.nbytes.fget(st) == ops.pack_bytes(
        (1 << 20) + 64, (1 << 16) + 8, 64)


def _four_device_engine(buffer_capacity):
    return Engine(num_shards=4, strategy="gloran",
                  lsm_config=LSMConfig(buffer_capacity=buffer_capacity,
                                       size_ratio=10, key_size=16,
                                       value_size=48, block_size=4096,
                                       key_universe=UNIVERSE),
                  gloran_config=GloranConfig(
                      index=LSMDRTreeConfig(buffer_capacity=64,
                                            size_ratio=4, key_size=16,
                                            block_size=512),
                      eve=RAEConfig(capacity=4096, key_universe=UNIVERSE)),
                  config=EngineConfig(partition="range", devices=4,
                                      kernel_min_batch=1))


def _rdels(rng, n, lo, width, span=128):
    los = lo + rng.integers(0, width - span, size=n)
    return [(int(a), int(a) + span) for a in los]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_pack_past_the_vmem_key_cap_is_admitted_per_device():
    """Shard 0 holds ~560k keys: its pack pads past 2^20 key slots, the
    Pallas form's cap.  Under the XLA form each shard's pack is built on
    its own device, every lookup takes the cascade, and every answer is
    the ``dict`` replay's."""
    eng = _four_device_engine(1 << 14)
    homes = eng.device_map()
    assert len(set(homes.values())) == 4
    rng = np.random.default_rng(16)
    model: dict = {}
    loads = [rng.choice(1 << 29, size=560_000, replace=False)] + \
        [s * SLAB + rng.choice(1 << 20, size=40_000, replace=False)
         for s in (1, 2, 3)]
    for keys in loads:
        keys = keys.astype(np.uint64)
        vals = keys * np.uint64(3) + np.uint64(1)
        for i in range(0, len(keys), 1 << 16):
            eng.submit(OpBatch.puts(keys[i:i + (1 << 16)],
                                    vals[i:i + (1 << 16)]))
        model.update(zip(keys.tolist(), vals.tolist()))
    issued = 0
    for rnd in range(3):
        # Range deletes on every shard (enough to flush GLORAN levels),
        # then updates, then lookups of loaded keys and of misses.
        rd = [r for s in range(4)
              for r in _rdels(rng, 80, s * SLAB, 1 << 20 if s else 1 << 29)]
        eng.submit(OpBatch.range_deletes(rd))
        for lo, hi in rd:
            for k in range(lo, hi):
                model.pop(k, None)
        upd = np.concatenate([rng.choice(k, size=500) for k in loads]
                             ).astype(np.uint64)
        uval = upd + np.uint64(rnd + 7)
        eng.submit(OpBatch.puts(upd, uval))
        model.update(zip(upd.tolist(), uval.tolist()))
        probe = np.concatenate(
            [rng.choice(k, size=2048) for k in loads]
            + [rng.integers(0, UNIVERSE, size=1024)]).astype(np.uint64)
        found, got = eng.submit(OpBatch.gets(probe)).get_results()
        issued += len(probe)
        want = [model.get(k) for k in probe.tolist()]
        assert found.tolist() == [w is not None for w in want]
        assert got[found].tolist() == [w for w in want if w is not None]

    snap = eng.kernel_counters.snapshot()
    assert snap["pack_declined_keys"] == snap["pack_declined_bytes"] == 0
    assert snap["cascade_queries"] == issued
    big = eng.shards[0].registry.view(eng.shards[0].tree, XLA)
    assert sum(big.state.key_pad) > ops.MAX_PACK_KEYS
    assert big.state.G > 0
    # The gauge: one entry per home device, each its shard's pack.
    by_dev = snap["pack_bytes_by_device"]
    assert sorted(by_dev) == sorted(homes.values())
    for s, sh in enumerate(eng.shards):
        assert by_dev[homes[s]] == sh.registry.view(sh.tree).state.nbytes
    assert eng.stats()["kernels"]["pack_bytes_by_device"] == by_dev
    # The Pallas form would hold that pack in VMEM: declined, by keys.
    assert eng.shards[0].registry.view(eng.shards[0].tree, PALLAS) is None
    assert eng.shards[0].kernels.pack_declined_keys == 1
    eng.close()


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_pack_and_cascade_spans_name_their_device():
    """``registry.pack`` spans carry the home device and the built pack's
    bytes, ``kernel.cascade`` spans the device the launch ran on; with
    shards sharing a device the gauge sums them."""
    eng = _four_device_engine(256)
    homes = eng.device_map()
    rng = np.random.default_rng(3)
    keys = np.concatenate([s * SLAB + rng.choice(1 << 16, size=3000,
                                                 replace=False)
                           for s in range(4)]).astype(np.uint64)
    eng.submit(OpBatch.puts(keys, keys))
    with obs.enabled() as tr:
        eng.submit(OpBatch.range_deletes(
            [r for s in range(4) for r in _rdels(rng, 70, s * SLAB, 1 << 16)]))
        eng.submit(OpBatch.gets(keys)).get_results()
        eng.drain()
    evs = tr.events()
    packs = [e for e in evs if e["name"] == "registry.pack"]
    calls = [e for e in evs if e["name"] == "kernel.cascade"]
    assert {e["attrs"]["device"] for e in packs} == set(homes.values())
    assert {e["attrs"]["device"] for e in calls} == set(homes.values())
    last = {}
    for e in sorted(packs, key=lambda e: e["t1"]):
        assert e["attrs"]["bytes"] > 0
        last[e["attrs"]["device"]] = e["attrs"]["bytes"]
    assert last == eng.kernel_counters.snapshot()["pack_bytes_by_device"]
    eng.close()

    # Two shards on one device: the gauge is their sum.
    one = Engine(num_shards=2, strategy="gloran",
                 lsm_config=LSMConfig(buffer_capacity=256,
                                      key_universe=UNIVERSE),
                 config=EngineConfig(partition="range", devices=0,
                                     kernel_min_batch=1))
    k2 = np.concatenate([rng.choice(1 << 16, size=2000, replace=False),
                         (UNIVERSE // 2) + rng.choice(1 << 16, size=2000,
                                                      replace=False)]
                        ).astype(np.uint64)
    one.submit(OpBatch.puts(k2, k2))
    one.submit(OpBatch.gets(k2)).get_results()
    one.drain()
    got = one.kernel_counters.snapshot()["pack_bytes_by_device"]
    assert got == {"host": sum(sh.registry.view(sh.tree).state.nbytes
                               for sh in one.shards)}
    one.close()
