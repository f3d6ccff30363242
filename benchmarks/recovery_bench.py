"""Cold-start recovery benchmark -> the ``recovery`` section of
BENCH_engine.json.

Measures what a restart costs as a function of WAL-tail length: build a
durable store, close it, ``recover()`` from the directory, and time the
wall — once per tail length, with and without a snapshot covering the
prefix.  Every recovered store is verified against the original
(probe gets + full scan + level shapes) before its row is published.

    PYTHONPATH=src python benchmarks/recovery_bench.py

Env:
    REPRO_RECOVERY_BENCH_SMOKE=1  small tails (scripts/check.sh)
    REPRO_BENCH_OUT=path.json     output path (default BENCH_engine.json,
                                  merged: other sections are preserved)
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro.launch.mesh import ensure_host_devices

ensure_host_devices(4)

from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig  # noqa: E402
from repro.durable import recover, take_snapshot  # noqa: E402
from repro.engine import Engine, EngineConfig  # noqa: E402
from repro.lsm import LSMConfig  # noqa: E402

SMOKE = os.environ.get("REPRO_RECOVERY_BENCH_SMOKE") == "1"
OUT = os.environ.get("REPRO_BENCH_OUT", "BENCH_engine.json")

UNIVERSE = 1 << 22
BATCH = 4096
SHARDS = 2
TAIL_BATCHES = (4, 16) if SMOKE else (4, 16, 64)


def cfgs():
    lsm = LSMConfig(buffer_capacity=4096, key_size=16, value_size=48,
                    key_universe=UNIVERSE)
    glo = GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=512, size_ratio=10,
                              key_size=16),
        eve=RAEConfig(capacity=100_000, key_universe=UNIVERSE))
    return lsm, glo


def build_store(wal_dir: str, n_batches: int, *,
                snapshot_at: int | None = None) -> Engine:
    lsm, glo = cfgs()
    cfg = EngineConfig(partition="range", pipeline=False, devices=0,
                       wal_dir=wal_dir, fsync="rotate")
    eng = Engine(SHARDS, strategy="gloran", lsm_config=lsm,
                 gloran_config=glo, config=cfg)
    rng = np.random.default_rng(23)
    for i in range(n_batches):
        keys = rng.integers(0, UNIVERSE, size=BATCH).astype(np.uint64)
        eng.put_batch(keys, keys + np.uint64(1))
        if i % 4 == 3:
            lo = int(rng.integers(0, UNIVERSE - 2048))
            eng.range_delete(lo, lo + 2048)
        if snapshot_at is not None and i == snapshot_at:
            take_snapshot(eng)
    return eng


def verify(a: Engine, b: Engine) -> None:
    probes = np.random.default_rng(9).integers(
        0, UNIVERSE, size=4096).astype(np.uint64)
    fa, va = a.get_batch(probes)
    fb, vb = b.get_batch(probes)
    assert np.array_equal(fa, fb) and np.array_equal(va[fa], vb[fb])
    sa = a.range_scan(0, UNIVERSE // 64)
    sb = b.range_scan(0, UNIVERSE // 64)
    assert np.array_equal(sa[0], sb[0]) and np.array_equal(sa[1], sb[1])
    for sha, shb in zip(a.shards, b.shards):
        assert sha.tree.stats()["levels"] == shb.tree.stats()["levels"]


def bench_row(n_batches: int, *, with_snapshot: bool) -> dict:
    tmp = tempfile.mkdtemp(prefix="repro-recovery-")
    try:
        snap_at = (n_batches * 3) // 4 if with_snapshot else None
        eng = build_store(tmp, n_batches, snapshot_at=snap_at)
        entries = eng.num_entries
        eng.close()
        wal_bytes = sum(
            os.path.getsize(os.path.join(root, f))
            for root, _, files in os.walk(tmp) for f in files
            if f.endswith(".wal"))
        t0 = time.perf_counter()
        rec = recover(tmp, config=EngineConfig(devices=0, pipeline=False))
        wall = time.perf_counter() - t0
        verify(eng, rec)
        row = {
            "tail_batches": n_batches,
            "entries": entries,
            "snapshot": with_snapshot,
            "wal_bytes": wal_bytes,
            "frames_replayed": rec.recovery["frames_replayed"],
            "snapshot_loaded": rec.recovery["snapshot_loaded"],
            "recovery_wall_s": round(wall, 4),
            "replay_frames_per_sec": round(
                rec.recovery["frames_replayed"] / wall) if wall else None,
        }
        rec.close()
        return row
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run() -> dict:
    rows = []
    for n in TAIL_BATCHES:
        for with_snapshot in (False, True):
            row = bench_row(n, with_snapshot=with_snapshot)
            rows.append(row)
            print(f"# recovery x{n:3d} batches "
                  f"(snapshot={'y' if with_snapshot else 'n'}): "
                  f"{row['recovery_wall_s']}s, "
                  f"{row['frames_replayed']} frames replayed, "
                  f"{row['wal_bytes'] / 1e6:.1f} MB WAL", flush=True)
    section = {
        "config": {"shards": SHARDS, "batch": BATCH,
                   "fsync": "rotate", "smoke": SMOKE},
        "rows": rows,
        # Cold-start scaling: recovery wall vs WAL-tail length, and the
        # snapshot fast path's effect on the same store.
        "max_recovery_wall_s": max(r["recovery_wall_s"] for r in rows),
        "verified": True,
    }
    doc = {}
    if os.path.exists(OUT):
        try:
            with open(OUT) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = {}
    doc["recovery"] = section
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# wrote {OUT}: recovery section, "
          f"max wall {section['max_recovery_wall_s']}s", flush=True)
    return section


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
