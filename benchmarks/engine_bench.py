"""Sharded batched query engine benchmark -> BENCH_engine.json.

Sweeps shards x batch size x range-delete ratio and compares the
engine's batched lookup path (fused cascade kernel over device-resident
filter state, with the Bloom + interval per-level stage as fallback,
block cache) against the seed's per-key ``LSMTree.get`` Python loop on
the same data and probe distribution.  Probes are drawn from the
inserted key population (serving-style: schedulers look up sessions
that exist), so the GLORAN validity stage — where the interval kernel
runs — sees real candidate batches.

A second sweep (``cascade_sweep``) isolates the cascade itself: lookup
throughput vs range-delete ratio (0/1/5/20%), fused-cascade vs the
per-level kernel path on identical data, reporting kernel launches and
host->device upload bytes per lookup.  Its acceptance figure gates
cascade >= 1.5x over the per-level path at batch >= 4096.

    PYTHONPATH=src python benchmarks/engine_bench.py

Env:
    REPRO_ENGINE_BENCH_SMOKE=1   ~10 s subset (scripts/check.sh)
    REPRO_BENCH_SCALE=full       ~10x workload
    REPRO_BENCH_OUT=path.json    output path (default BENCH_engine.json)

Kernel launches run in Pallas interpret mode on CPU containers; their
per-launch overhead is real there and amortizes only over large
candidate batches — exactly what the engine's ``kernel_min_batch``
gating encodes.  Rows with ``fused_filters=False`` isolate that cost.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
from repro.engine import Engine, EngineConfig
from repro.lsm import LSMConfig, LSMTree

SMOKE = os.environ.get("REPRO_ENGINE_BENCH_SMOKE") == "1"
SCALE = 10 if os.environ.get("REPRO_BENCH_SCALE") == "full" else 1
OUT = os.environ.get("REPRO_BENCH_OUT", "BENCH_engine.json")

UNIVERSE = 1 << 22
RANGE_LEN = 128

if SMOKE:
    PRELOAD = 30_000
    SHARDS = (1, 4)
    BATCHES = (1024,)
    RATIOS = (0.1,)
    ROUNDS = 3
else:
    PRELOAD = 100_000 * SCALE
    SHARDS = (1, 2, 4, 8)
    BATCHES = (256, 1024, 4096)
    RATIOS = (0.0, 0.05, 0.2)
    ROUNDS = 5


def lsm_cfg() -> LSMConfig:
    return LSMConfig(buffer_capacity=4096, key_size=16, value_size=48,
                     key_universe=UNIVERSE)


def gloran_cfg() -> GloranConfig:
    # Small index write buffer so range-delete churn actually reaches the
    # on-disk DR-tree levels that the interval kernel serves.
    return GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=512, size_ratio=10,
                              key_size=16),
        eve=RAEConfig(capacity=100_000, key_universe=UNIVERSE))


def engine_cfg(fused: bool = True, cascade: bool = True) -> EngineConfig:
    return EngineConfig(cache_blocks=16384,
                        use_bloom_kernel=fused, use_interval_kernel=fused,
                        use_cascade_kernel=fused and cascade,
                        kernel_min_batch=128, kernel_min_areas=64,
                        kernel_min_filter=4096)


def preload(store, keys: np.ndarray, n_rdel: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    for i in range(0, len(keys), 8192):
        kk = keys[i:i + 8192]
        store.put_batch(kk, kk + np.uint64(1))
    for _ in range(n_rdel):
        lo = int(rng.integers(0, UNIVERSE - RANGE_LEN - 1))
        store.range_delete(lo, lo + RANGE_LEN)


def probe_batches(keys: np.ndarray, batch: int, rounds: int,
                  seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return keys[rng.integers(0, len(keys), size=(rounds + 1, batch))]


def bench_scalar(tree: LSMTree, keys: np.ndarray, batch: int,
                 ratio: float) -> float:
    """The seed path: one ``tree.get`` Python call per key."""
    probes = probe_batches(keys, batch, ROUNDS, seed=99)
    for k in probes[0].tolist():
        tree.get(k)  # warm
    t0 = time.perf_counter()
    for p in probes[1:]:
        for k in p.tolist():
            tree.get(k)
    dt = time.perf_counter() - t0
    return ROUNDS * batch / dt


def bench_engine(eng: Engine, keys: np.ndarray, batch: int,
                 rounds: int | None = None) -> dict:
    rounds = ROUNDS if rounds is None else rounds
    probes = probe_batches(keys, batch, rounds, seed=99)
    eng.get_batch(probes[0])  # warm caches + jit
    eng.reset_stats()  # per-measurement latency window (counters cumulate)
    r0, k0 = eng.io_reads, eng.kernel_counters
    c0 = eng.cache_snapshot()
    t0 = time.perf_counter()
    for p in probes[1:]:
        eng.get_batch(p)
    dt = time.perf_counter() - t0
    k1 = eng.kernel_counters
    c1 = eng.cache_snapshot()
    # Deltas only: the engine (and its cache) persists across rows, so
    # lifetime counters would cross-contaminate batch-size measurements.
    hits = c1["hits"] - c0["hits"]
    misses = c1["misses"] - c0["misses"]
    n = rounds * batch
    launches = ((k1.cascade_calls - k0.cascade_calls)
                + (k1.bloom_calls - k0.bloom_calls)
                + (k1.interval_calls - k0.interval_calls))
    hist = eng.stats_.latency.get("get")
    lat = hist.snapshot() if hist is not None else {}
    return {
        "latency_us": {q: lat.get(q)
                       for q in ("p50_us", "p95_us", "p99_us")},
        "ops_per_sec": n / dt,
        "io_reads_per_lookup": (eng.io_reads - r0) / n,
        "cache_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
        "interval_kernel_calls": k1.interval_calls - k0.interval_calls,
        "interval_kernel_queries": k1.interval_queries - k0.interval_queries,
        "bloom_kernel_calls": k1.bloom_calls - k0.bloom_calls,
        "cascade_kernel_calls": k1.cascade_calls - k0.cascade_calls,
        "kernel_launches_per_lookup": launches / n,
        "upload_bytes_per_lookup": (k1.upload_bytes - k0.upload_bytes) / n,
    }


def cascade_sweep() -> list[dict]:
    """Lookup throughput vs range-delete ratio: fused cascade vs the
    per-level kernel path, on identical data and probe streams.

    One shard, block cache off: the sweep isolates the kernel-dispatch
    structure itself.  The tree uses a small buffer / size ratio so the
    data spreads over several SSTable levels (the steady serving shape
    — a leveled LSM mid-compaction, not one fully-compacted run), the
    range deletes land after the last bottom compaction so their
    records are live in the global index, and every level clears the
    per-level gating thresholds: the per-level path launches one bloom
    kernel per SSTable level plus one interval kernel per DR-tree level
    per ``get_batch``, each re-touching filter state, while the cascade
    launches ONCE over the registry's persistent device state.
    Launches and upload bytes per lookup are steady-state deltas over
    the measured rounds (packs uploaded at warmup).
    """
    ratios = (0.2,) if SMOKE else (0.0, 0.01, 0.05, 0.20)
    batches = (4096,) if SMOKE else (1024, 4096)
    lsm = LSMConfig(buffer_capacity=512, size_ratio=4, key_size=16,
                    value_size=48, key_universe=UNIVERSE)
    rng = np.random.default_rng(13)
    rows = []
    for ratio in ratios:
        keys = rng.integers(0, UNIVERSE, size=PRELOAD).astype(np.uint64)
        n_rdel = int(PRELOAD * ratio / 4)
        engines = {}
        for name, cascade in (("cascade", True), ("per_level", False)):
            cfg = EngineConfig(cache_blocks=0, use_bloom_kernel=True,
                               use_interval_kernel=True,
                               use_cascade_kernel=cascade,
                               kernel_min_batch=128, kernel_min_areas=64,
                               kernel_min_filter=512)
            eng = Engine(num_shards=1, strategy="gloran",
                         lsm_config=lsm, gloran_config=gloran_cfg(),
                         config=cfg)
            preload(eng, keys, n_rdel, seed=5)
            engines[name] = eng
        for batch in batches:
            row = {"rdel_ratio": ratio, "batch": batch}
            # Long windows + interleaved best-of-3: these are single-
            # process wall measurements on shared hardware whose
            # throughput drifts over seconds, so the two paths are
            # measured alternately (each repetition samples the same
            # machine epoch for both) and each keeps its best rep —
            # otherwise a sustained slow period landing on one side
            # dominates the speedup ratio.
            best: dict = {}
            for _ in range(3):
                for name, eng in engines.items():
                    m = bench_engine(eng, keys, batch,
                                     rounds=6 if SMOKE else 20)
                    if name not in best or \
                            m["ops_per_sec"] > best[name]["ops_per_sec"]:
                        best[name] = m
            for name, m in best.items():
                row[f"{name}_ops_per_sec"] = round(m["ops_per_sec"], 1)
                row[f"{name}_launches_per_lookup"] = round(
                    m["kernel_launches_per_lookup"], 6)
                row[f"{name}_upload_bytes_per_lookup"] = round(
                    m["upload_bytes_per_lookup"], 4)
                row[f"{name}_io_reads_per_lookup"] = round(
                    m["io_reads_per_lookup"], 4)
            row["cascade_speedup_vs_per_level"] = round(
                row["cascade_ops_per_sec"] / row["per_level_ops_per_sec"],
                2)
            rows.append(row)
            print(f"# cascade sweep ratio={ratio} batch={batch}: "
                  f"{row['cascade_ops_per_sec']:,.0f} vs "
                  f"{row['per_level_ops_per_sec']:,.0f} ops/s "
                  f"({row['cascade_speedup_vs_per_level']}x), launches/"
                  f"lookup {row['cascade_launches_per_lookup']:.5f} vs "
                  f"{row['per_level_launches_per_lookup']:.5f}",
                  flush=True)
    return rows


def run() -> dict:
    rng = np.random.default_rng(7)
    rows = []
    scalar_baselines = {}
    for ratio in RATIOS:
        keys = rng.integers(0, UNIVERSE, size=PRELOAD).astype(np.uint64)
        # Delete count scales with ratio x entries so the global index
        # actually cascades through its on-disk levels under churn.
        n_rdel = int(PRELOAD * ratio / 4)
        tree = LSMTree(lsm_cfg(), "gloran", gloran_cfg())
        preload(tree, keys, n_rdel, seed=5)
        base = bench_scalar(tree, keys, max(BATCHES), ratio)
        scalar_baselines[str(ratio)] = round(base, 1)
        print(f"# scalar per-key loop  ratio={ratio}: {base:,.0f} ops/s",
              flush=True)
        variants = [(s, True) for s in SHARDS]
        variants += [(4, False)] if 4 in SHARDS and not SMOKE else []
        for shards, fused in variants:
            eng = Engine(num_shards=shards, strategy="gloran",
                         lsm_config=lsm_cfg(), gloran_config=gloran_cfg(),
                         config=engine_cfg(fused))
            preload(eng, keys, n_rdel, seed=5)
            for batch in BATCHES:
                m = bench_engine(eng, keys, batch)
                row = {
                    "shards": shards,
                    "batch": batch,
                    "rdel_ratio": ratio,
                    "fused_filters": fused,
                    "engine_ops_per_sec": round(m["ops_per_sec"], 1),
                    "scalar_ops_per_sec": round(base, 1),
                    "speedup_vs_per_key_loop": round(
                        m["ops_per_sec"] / base, 2),
                    "io_reads_per_lookup": round(
                        m["io_reads_per_lookup"], 4),
                    "cache_hit_rate": round(m["cache_hit_rate"], 4),
                    "interval_kernel_calls": m["interval_kernel_calls"],
                    "interval_kernel_queries": m["interval_kernel_queries"],
                    "bloom_kernel_calls": m["bloom_kernel_calls"],
                    "cascade_kernel_calls": m["cascade_kernel_calls"],
                    "get_batch_latency_us": m["latency_us"],
                }
                rows.append(row)
                print(f"# engine x{shards} batch={batch} ratio={ratio} "
                      f"fused={fused}: {m['ops_per_sec']:,.0f} ops/s "
                      f"({row['speedup_vs_per_key_loop']}x), "
                      f"ik={m['interval_kernel_calls']} "
                      f"bk={m['bloom_kernel_calls']} "
                      f"ck={m['cascade_kernel_calls']} "
                      f"cache={m['cache_hit_rate']:.2f}", flush=True)
    sweep = cascade_sweep()
    target = [r for r in rows
              if r["shards"] == 4 and r["batch"] >= 1024
              and r["fused_filters"]]
    result = {
        "config": {
            "preload_entries": PRELOAD,
            "universe": UNIVERSE,
            "range_delete_len": RANGE_LEN,
            "rounds": ROUNDS,
            "strategy": "gloran",
            "smoke": SMOKE,
            "probe_distribution": "drawn from inserted keys",
        },
        "scalar_per_key_ops_per_sec": scalar_baselines,
        "rows": rows,
        "cascade_sweep": sweep,
        "acceptance": {
            "min_speedup_4shard_batch_ge_1024": min(
                (r["speedup_vs_per_key_loop"] for r in target),
                default=None),
            "max_speedup_4shard_batch_ge_1024": max(
                (r["speedup_vs_per_key_loop"] for r in target),
                default=None),
            "cascade_min_speedup_vs_perlevel_batch_ge_4096": min(
                (r["cascade_speedup_vs_per_level"] for r in sweep
                 if r["batch"] >= 4096), default=None),
        },
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(f"# wrote {OUT}: min 4-shard/batch>=1024 speedup = "
          f"{result['acceptance']['min_speedup_4shard_batch_ge_1024']}x, "
          f"cascade vs per-level @>=4096 = "
          f"{result['acceptance']['cascade_min_speedup_vs_perlevel_batch_ge_4096']}x",
          flush=True)
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
