"""The store's kernels as a TPU v5e would run them, checked without a chip.

The compile tests hand the TPU compiler, for a described (not attached)
``v5e:2x2`` topology, the exact jitted form each ops module dispatches on
a TPU backend, at real widths: query tiles of 8192 and a cascade pack of
about 1M key slots.  Whatever the chip's compiler would refuse, these
refuse.  The dispatch tests run on the CPU with the backend reported as
``"tpu"``, and pin that every ops module then takes its chip form and
refuses to interpret.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.core.eve import BloomBits, fold64to32
from repro.engine import Engine, EngineConfig, OpBatch
from repro.engine.plan import OP_GET, OP_PUT, OP_RANGE_DELETE
from repro.kernels import dispatch
from repro.kernels.bloom import ops as bloom_ops
from repro.kernels.cascade import ops as cascade_ops
from repro.kernels.interval import ops as interval_ops
from repro.kernels.merge import ops as merge_ops
from repro.kernels.merge.ref import merge_ranks_np
from repro.lsm import LSMConfig

QUERIES = 8192
# Four LSM levels and three GLORAN DR-tree levels, pow2-padded as the
# registry packs them: 1,179,648 key slots.
KEY_PAD = (1 << 17, 1 << 18, 1 << 18, 1 << 19)
WORD_PAD = (1 << 14, 1 << 15, 1 << 15, 1 << 16)
GL_PAD = (1 << 12, 1 << 13, 1 << 14)
H = 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an abstract argument placed on one chip.

    The persistent compilation cache is off while this module's compiles
    run: an entry written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype=jnp.uint32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, **static):
    return fn.lower(*args, **static).compile()


def _compile_cascade(shape, key_pad, word_pad, gl_pad):
    L, G = len(key_pad), len(gl_pad)
    K, W, A = sum(key_pad), sum(word_pad), sum(gl_pad)
    i32 = jnp.int32
    q = shape((QUERIES,))
    _compile(cascade_ops._cascade_xla, q, q, q, shape((QUERIES,), i32),
             shape((K,)), shape((K,)), shape((L,), i32), shape((L,), i32),
             shape((W,)), shape((L,), i32), shape((L,)), shape((L, H)),
             shape((A,)), shape((A,)), shape((A,)), shape((A,)),
             shape((G,), i32), shape((G,), i32), L=L, H=H, G=G,
             key_pad=key_pad, word_pad=word_pad, gl_pad=gl_pad)


def test_cascade_compiles_for_v5e(shape):
    _compile_cascade(shape, KEY_PAD, WORD_PAD, GL_PAD)


def test_cascade_compiles_for_v5e_past_the_vmem_caps(shape):
    """One shard of 2,621,440 keys, as each chip of the four-chip
    deployment holds: a 2^22-slot level beside two smaller ones, ~4.8M
    key slots and ~43 MB, past the Pallas form's VMEM limits."""
    key_pad = (1 << 16, 1 << 19, 1 << 22)
    assert sum(key_pad) > cascade_ops.MAX_PACK_KEYS
    _compile_cascade(shape, key_pad, (1 << 12, 1 << 15, 1 << 20),
                     (1 << 13,))


def test_bloom_compiles_for_v5e(shape):
    _compile(bloom_ops._bloom_xla, shape((QUERIES,)), shape((1 << 20,)),
             m_bits=1 << 25, seeds=(1, 2, 3))


def test_interval_compiles_for_v5e(shape):
    col = shape((1 << 16,))
    _compile(interval_ops._interval_xla, shape((QUERIES,)),
             shape((QUERIES,)), col, col, col, col)


def test_merge_compiles_for_v5e(shape):
    _compile(merge_ops._merge_ranks_xla, shape((1 << 20,)),
             shape((1 << 16,)))


# ------------------------------------------- dispatch on a TPU backend
@pytest.fixture
def tpu_backend(monkeypatch):
    """Report the backend as a TPU and make every Pallas entry raise, so
    a dispatch that still picked the Pallas form would fail loudly."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def refused(*_, **__):
        raise AssertionError("a Pallas kernel was dispatched on a TPU")
    for mod, name in ((cascade_ops, "cascade_pallas"),
                      (bloom_ops, "bloom_probe_pallas"),
                      (interval_ops, "interval_query_pallas"),
                      (merge_ops, "merge_rank_pallas")):
        monkeypatch.setattr(mod, name, refused)


def test_tpu_backend_picks_xla_forms(tpu_backend):
    assert dispatch.default_forms() == {
        "cascade": "xla", "bloom": "xla", "interval": "xla", "merge": "xla"}
    rng = np.random.default_rng(0)

    bb = BloomBits(1 << 14, 6)
    keys = rng.integers(0, 1 << 62, 1000, dtype=np.uint64)
    bb.insert(keys)
    probes = np.concatenate([keys[:500], rng.integers(
        0, 1 << 62, 500, dtype=np.uint64)])
    got = np.asarray(bloom_ops.bloom_probe(
        fold64to32(probes), bb.words, m_bits=bb.m_bits,
        seeds=tuple(int(s) for s in bb.seeds)))
    np.testing.assert_array_equal(got, bb.might_contain(probes))

    lo = np.arange(0, 64 * 100, 100, dtype=np.uint32)
    hi, smin, smax = lo + 50, lo % 7, lo % 7 + 20
    qk = rng.integers(0, 6500, 700).astype(np.uint32)
    qs = rng.integers(0, 30, 700).astype(np.uint32)
    got = np.asarray(interval_ops.interval_query(qk, qs, lo, hi, smin,
                                                 smax))
    i = np.searchsorted(lo, qk, side="right") - 1
    want = (i >= 0) & (qk < hi[i]) & (smin[i] <= qs) & (qs < smax[i])
    np.testing.assert_array_equal(got, want)

    ka = np.sort(rng.integers(0, 500, 1500)).astype(np.uint32)
    kb = np.sort(np.append(rng.integers(0, 500, 700),
                           0xFFFFFFFF)).astype(np.uint32)
    for x, y in ((ka, kb), (kb, ka)):
        pa, pb = merge_ops.merge_ranks(x, y)
        wa, wb = merge_ranks_np(x, y)
        np.testing.assert_array_equal(pa, wa)
        np.testing.assert_array_equal(pb, wb)


def _call(kernel, **ask):
    """One small dispatch of ``kernel`` through its public entry."""
    k = np.arange(256, dtype=np.uint32)
    if kernel == "cascade":
        state = SimpleNamespace(L=1, G=0, H=1, key_sizes=(256,),
                                gl_sizes=())
        return cascade_ops.cascade_lookup(k, k, k, k > 0, state, **ask)
    if kernel == "bloom":
        return bloom_ops.bloom_probe(k, np.zeros(64, np.uint32),
                                     m_bits=2048, seeds=(1,), **ask)
    if kernel == "interval":
        col = np.zeros(64, np.uint32)
        return interval_ops.interval_query(k, k, col, col, col, col, **ask)
    return merge_ops.merge_ranks(k, k, **ask)


@pytest.mark.parametrize("kernel,ask", [
    (kernel, ask) for kernel in ("cascade", "bloom", "interval", "merge")
    for ask in (dict(interpret=True), dict(interpret=False))
] + [("cascade", dict(compiled=False)), ("merge", dict(compiled=False))])
def test_tpu_backend_refuses_pallas_requests(tpu_backend, kernel, ask):
    with pytest.raises(ValueError, match="TPU backend"):
        _call(kernel, **ask)


# ----------------------------------------- the engine on a TPU backend
def _store(offset: int, **kernels) -> Engine:
    """A 2-shard GLORAN engine whose every kernel gate opens at size 1;
    keys live at ``offset`` and up (2^62: past the u32 gates)."""
    universe = 1 << 63 if offset else 1 << 20
    lsm = LSMConfig(buffer_capacity=64, size_ratio=3, key_size=16,
                    value_size=48, block_size=512, key_universe=universe)
    gloran = GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=8, size_ratio=3, key_size=16,
                              block_size=512),
        eve=RAEConfig(capacity=64, key_universe=universe))
    return Engine(num_shards=2, strategy="gloran", lsm_config=lsm,
                  gloran_config=gloran,
                  config=EngineConfig(cache_blocks=512, kernel_min_batch=1,
                                      kernel_min_areas=1,
                                      kernel_min_filter=1,
                                      kernel_min_merge=1,
                                      **kernels))


def _traffic(eng: Engine, offset: int) -> list:
    """Mixed get/put/range-delete batches, then range scans; every
    answer in submit order."""
    rng = np.random.default_rng(5)
    base = np.uint64(offset)
    out = []
    for _ in range(6):
        kinds = rng.choice(np.array([OP_GET, OP_PUT, OP_RANGE_DELETE],
                                    np.uint8), size=600, p=(0.4, 0.5, 0.1))
        lo = base + rng.integers(0, 3900, 600, dtype=np.uint64)
        hi = lo + rng.integers(1, 100, 600, dtype=np.uint64)
        rd = kinds == OP_RANGE_DELETE
        out.append(eng.submit(OpBatch(
            kinds, keys=base + rng.integers(0, 4000, 600, dtype=np.uint64),
            vals=rng.integers(1, 1 << 62, 600, dtype=np.uint64),
            los=np.where(rd, lo, base), his=np.where(rd, hi, base)))
            .get_results())
    lo = base + rng.integers(0, 3500, 40, dtype=np.uint64)
    out += eng.submit(OpBatch.range_scans(
        zip(lo.tolist(), (lo + 500).tolist()))).scan_results()
    return out


@pytest.mark.parametrize("offset,kernels,ran", [
    (1 << 62, dict(use_cascade_kernel=False), ("bloom",)),
    (0, dict(use_cascade_kernel=False), ("bloom", "interval", "merge")),
    (0, {}, ("cascade", "merge")),
], ids=["u64-bloom", "u32-per-level", "u32-cascade"])
def test_tpu_backend_engine_matches_host_path(tpu_backend, offset, kernels,
                                              ran):
    """``Engine.submit`` on a TPU backend drives each chip form through
    the executor (query padding, per-shard device placement, registry-
    resident filter words) and answers exactly as the host path does,
    with the same per-shard IOStats."""
    chip = _store(offset, **kernels)
    host = _store(offset, use_cascade_kernel=False, use_bloom_kernel=False,
                  use_interval_kernel=False, use_merge_kernel=False)
    got, want = _traffic(chip, offset), _traffic(host, offset)
    kc = chip.kernel_counters
    for kernel in ran:
        assert getattr(kc, f"{kernel}_calls") > 0, kernel
    assert host.kernel_counters.snapshot()["upload_bytes"] == 0
    for (a, b), (x, y) in zip(got, want):
        np.testing.assert_array_equal(a, x)
        np.testing.assert_array_equal(b, y)
    assert [sh.tree.io.snapshot() for sh in chip.shards] == \
        [sh.tree.io.snapshot() for sh in host.shards]
