"""Mixed-workload benchmark (fig9 scale) -> BENCH_mixed.json.

The paper's headline scenario: point gets, range scans, and range
deletes arriving interleaved in one op stream (§6, fig9).  Every batch
goes through the typed plan/submit API — ``OpBatch`` construction,
``Planner`` compilation, ``Engine.submit`` — sweeping the get/scan/
range-delete mix ratio, the shard count, and pipelined vs serial shard
execution, with a submit-ahead window of 2 so planning batch n+1
overlaps executing batch n (the serve-loop pattern).

    PYTHONPATH=src python benchmarks/mixed_bench.py

Env:
    REPRO_MIXED_BENCH_SMOKE=1   ~20 s subset (scripts/check.sh)
    REPRO_BENCH_SCALE=full      ~4x workload
    REPRO_BENCH_OUT=path.json   output path (default BENCH_mixed.json)
    REPRO_TRACE_OUT=trace.json  also run one traced 4-shard pipelined
                                pass and export it as Chrome trace-event
                                JSON (load in Perfetto / chrome://tracing)
    REPRO_TRACE_ONLY=1          skip the benchmark sweeps, only export
                                the trace (fast CI artifact mode)

Throughput is reported two ways, extending this repo's existing
device-grounded convention (``WorkloadResult.modeled_ops_per_sec``:
the simulator *counts* block I/Os instead of sleeping on them, so raw
wall-clock alone under-charges I/O):

  wall      raw host wall-clock for both execution modes, measured with
            interleaved repetitions and every kernel shape pre-warmed.
            Python's GIL serializes the simulator's host compute, so on
            a small CI box the pipelined wall number mostly reflects
            thread scheduling, not the architecture — it is published
            for exactly that transparency.
  modeled   the architecture projection the per-shard wall/stall
            ledgers exist to make observable.  Both sides derive from
            the SAME serial run (identical plans, identical per-shard
            work):

              serial    = measured wall + (fleet-total I/Os) * T_IO
                          (one thread executes every shard plan and
                          issues every I/O in sequence)
              pipelined = max over shards of (that shard's busy seconds
                          + its I/Os * T_IO), plus the measured
                          non-overlapped coordination time (plan +
                          merge-back: serial wall minus the shards'
                          busy sum)
                          (each shard runs on its own executor and
                          drives its own I/O queue; the critical path
                          is the busiest shard)

            T_IO = 20us, a 4 KB NVMe random read — the paper's
            hardware, same constant as ``repro.baselines``.

Two acceptance figures:

  modeled   the modeled mixed-batch speedup, pipelined vs serial,
            geomean across mixes at the maximum shard count.
  wall      ``wall_speedup``: MEASURED wall, pipelined multi-device vs
            the serial single-device path, in timed-I/O mode
            (``EngineConfig.io_wait_s = T_IO``: each shard worker
            sleeps out the block I/Os its plan steps charge, so wall
            time includes the store's device waits and those waits
            overlap across shard workers exactly as concurrent NVMe
            queues would).  Each shard is pinned to its own XLA device
            (``shard_devices``; the bench forces
            ``--xla_force_host_platform_device_count`` up front), so
            kernel dispatch compute also overlaps.  This is the gated
            number — the model stays as the projection, the wall clock
            is the proof.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.launch.mesh import ensure_host_devices

SMOKE = os.environ.get("REPRO_MIXED_BENCH_SMOKE") == "1"
# Per-shard XLA devices need host-platform devices forced BEFORE jax's
# backends initialize (first engine build); an XLA_FLAGS count already
# forced by the environment (e.g. CI) is respected.
ensure_host_devices(4)

from repro.core import (GloranConfig, LSMDRTreeConfig, RAEConfig, RTree,  # noqa: E402
                        StagingBuffer, disjointize)
from repro.engine import Engine, EngineConfig, OpBatch  # noqa: E402
from repro.lsm import LSMConfig  # noqa: E402
SCALE = 4 if os.environ.get("REPRO_BENCH_SCALE") == "full" else 1
OUT = os.environ.get("REPRO_BENCH_OUT", "BENCH_mixed.json")
TRACE_OUT = os.environ.get("REPRO_TRACE_OUT", "")
TRACE_ONLY = os.environ.get("REPRO_TRACE_ONLY") == "1"

UNIVERSE = 1 << 22
SCAN_ENTRIES = 256  # target live entries per scan (span = entries/density)
RDEL_LEN = 512  # keyspace span of one range delete (a session-block expiry)
GET_HIT_FRAC = 0.85  # gets probing live keys (serving-registry pattern)
BURST = 64  # mean same-kind arrival burst length
DEPTH = 2  # submit-ahead window (plan n+1 while n executes)
T_IO = 20e-6  # seconds per counted block I/O (4 KB NVMe random read,
#               same device grounding as repro.baselines.WorkloadResult)

# (get, range_scan, range_delete) op fractions per mix.
MIXES = {
    "read_mostly": (0.94, 0.04, 0.02),
    "scan_heavy": (0.65, 0.30, 0.05),
    "delete_heavy": (0.85, 0.05, 0.10),
    "rdel_dominant": (0.25, 0.05, 0.70),
}

if SMOKE:
    PRELOAD = 48_000
    N_RDEL = 300
    SHARDS = (4,)
    BATCH = 8192
    ROUNDS = 1
    REPS = 2
    MIX_KEYS = ("read_mostly", "rdel_dominant")
    N_BUF = 6_000
else:
    PRELOAD = 120_000 * SCALE
    N_RDEL = 1200 * SCALE
    SHARDS = (1, 2, 4)
    BATCH = 8192
    ROUNDS = 2
    REPS = 3
    MIX_KEYS = tuple(MIXES)
    N_BUF = 24_000 * SCALE


def lsm_cfg() -> LSMConfig:
    return LSMConfig(buffer_capacity=4096, key_size=16, value_size=48,
                     key_universe=UNIVERSE)


def gloran_cfg() -> GloranConfig:
    return GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=512, size_ratio=10,
                              key_size=16),
        eve=RAEConfig(capacity=100_000, key_universe=UNIVERSE))


def engine_cfg(pipeline: bool, devices: int | None = None) -> EngineConfig:
    # Kernel-heavy gating (the TPU-deployment stand-in, as in
    # engine_bench's fused-filter rows): every SSTable filter and
    # DR-tree level probe runs through the Pallas kernels, so the
    # pipeline's win — overlapping per-shard kernel launches instead of
    # queueing them behind one Python thread — is what gets measured.
    # The block cache stays off: its per-block host loop is serial
    # Python, which engine_bench measures separately.  ``devices`` is
    # passed explicitly (not left to REPRO_ENGINE_DEVICES) so the
    # serial baseline is always the single-device path and the
    # pipelined side always pins per-shard devices, whatever the env.
    # scheduler is pinned off: the legacy sweep rows measure the
    # pipelined-vs-serial architecture and must not drift across the CI
    # REPRO_ENGINE_BG_COMPACT matrix cells; the background scheduler
    # has its own dedicated section (``bench_bg_scheduler``).
    return EngineConfig(partition="range", pipeline=pipeline,
                        cache_blocks=0, kernel_min_batch=32,
                        kernel_min_areas=32, kernel_min_filter=512,
                        devices=devices, scheduler=False)


def preload_keys() -> np.ndarray:
    return np.random.default_rng(5).integers(
        0, UNIVERSE, size=PRELOAD).astype(np.uint64)


def make_engine(shards: int, pipeline: bool,
                devices: int | None = None) -> Engine:
    eng = Engine(num_shards=shards, strategy="gloran",
                 lsm_config=lsm_cfg(), gloran_config=gloran_cfg(),
                 config=engine_cfg(pipeline, devices))
    keys = preload_keys()
    for i in range(0, len(keys), 8192):
        kk = keys[i:i + 8192]
        eng.put_batch(kk, kk + np.uint64(1))
    rng = np.random.default_rng(6)
    rdels = rng.integers(0, UNIVERSE - RDEL_LEN - 1, size=N_RDEL)
    eng.range_delete_batch([(int(lo), int(lo) + RDEL_LEN)
                            for lo in rdels])
    eng.flush()
    return eng


def mixed_batches(mix: tuple, rounds: int, seed: int) -> list[OpBatch]:
    """One interleaved OpBatch per round (+1 warm), same for every
    engine configuration (seeded).

    Kinds arrive in bursts (geometric, mean ``BURST``) — the serving
    tier's arrival pattern: a scheduler tick issues a run of page
    lookups, a scan job a run of scans, the expiry reaper a run of range
    deletes.  Expected op fractions still match ``mix`` (every burst has
    the same mean length).  Gets probe live keys with probability
    ``GET_HIT_FRAC`` (a registry looks up sessions it registered); scan
    spans are sized to cover ~``SCAN_ENTRIES`` live entries.
    """
    rng = np.random.default_rng(seed)
    probs = np.asarray(mix, dtype=float)
    live = preload_keys()
    scan_len = SCAN_ENTRIES * UNIVERSE // PRELOAD
    out = []
    for _ in range(rounds + 1):
        ops: list[tuple] = []
        while len(ops) < BATCH:
            kind = int(rng.choice(3, p=probs))
            burst = min(int(rng.geometric(1.0 / BURST)),
                        BATCH - len(ops))
            if kind == 0:
                hot = rng.random(burst) < GET_HIT_FRAC
                keys = np.where(hot, live[rng.integers(0, len(live),
                                                       size=burst)],
                                rng.integers(0, UNIVERSE, size=burst)
                                .astype(np.uint64))
                for k in keys.tolist():
                    ops.append(("get", int(k)))
            elif kind == 1:
                for lo in rng.integers(0, UNIVERSE - scan_len - 1,
                                       size=burst).tolist():
                    ops.append(("range_scan", lo, lo + scan_len))
            else:
                for lo in rng.integers(0, UNIVERSE - RDEL_LEN - 1,
                                       size=burst).tolist():
                    ops.append(("range_delete", lo, lo + RDEL_LEN))
        out.append(OpBatch.from_ops(ops))
    return out


def run_batches(eng: Engine, batches: list[OpBatch]) -> float:
    """Submit with a depth-``DEPTH`` in-flight window; returns seconds."""
    t0 = time.perf_counter()
    inflight = []
    for b in batches:
        inflight.append(eng.submit(b))
        if len(inflight) >= DEPTH:
            inflight.pop(0).wait()
    for p in inflight:
        p.wait()
    return time.perf_counter() - t0


def shard_io(eng: Engine) -> list[int]:
    return [sh.io_reads + sh.io_writes for sh in eng.shards]


def _shard_busy(eng: Engine) -> list[float]:
    return [eng.stats_.shard_wall.get(s, 0.0)
            for s in range(eng.num_shards)]


def _measure(eng: Engine, batches: list[OpBatch]):
    """One measured rep; (wall s, per-shard I/Os, per-shard busy s)."""
    io0, b0 = shard_io(eng), _shard_busy(eng)
    dt = run_batches(eng, batches)
    ios = [b - a for a, b in zip(io0, shard_io(eng))]
    busy = [b - a for a, b in zip(b0, _shard_busy(eng))]
    return dt, ios, busy


def bench_cell(mix_name: str, shards: int) -> tuple[dict, dict]:
    """One (mix, shard-count) cell: serial + pipelined rows.

    The two engines are built identically and the measurement reps
    alternate serial/pipelined on the same per-rep batches, so bursty
    host interference (shared CI cores) hits both sides alike; the
    reported speedup is the median per-rep ratio.
    """
    # The serial engine IS the single-device baseline (devices=0, the
    # ungated fallback path); the pipelined engine pins one XLA device
    # per shard.  Both are explicit so the env can't change what this
    # cell compares.
    engines = {False: make_engine(shards, False, devices=0),
               True: make_engine(shards, True, devices=shards)}
    # Twice REPS measured rounds: the first half serves the modeled
    # rows, the second half the timed-I/O wall_speedup reps.
    all_batches = mixed_batches(MIXES[mix_name], ROUNDS * REPS * 2,
                                seed=71)
    # Pre-warm every kernel shape the measured batches will launch on a
    # throwaway engine: jit compilation is process-global and one-time,
    # so neither measured side may pay it (whichever ran first would
    # otherwise foot the whole compile bill and look slower).
    scratch = make_engine(shards, True, devices=shards)
    for b in all_batches:
        scratch.submit(b).wait()
    del scratch
    for eng in engines.values():
        eng.submit(all_batches[0]).wait()  # warm caches + state
    n = ROUNDS * BATCH
    walls: dict = {False: [], True: []}
    m_serial: list[float] = []
    m_piped: list[float] = []
    cell_ios = None
    for rep in range(REPS):
        rep_batches = all_batches[1 + rep * ROUNDS:
                                  1 + (rep + 1) * ROUNDS]
        for pl in (False, True):
            dt, ios, busy = _measure(engines[pl], rep_batches)
            walls[pl].append(dt)
            if pl:
                continue
            # Architecture projection from the serial run's per-shard
            # ledgers (identical plans either way; see module
            # docstring): serial serializes all busy time and all I/O
            # on one thread; pipelined's critical path is the busiest
            # shard plus the non-overlapped plan/merge coordination.
            cell_ios = ios if cell_ios is None else \
                [a + b for a, b in zip(cell_ios, ios)]
            coord = max(dt - sum(busy), 0.0)
            m_serial.append(dt + sum(ios) * T_IO)
            m_piped.append(
                max(b + i * T_IO for b, i in zip(busy, ios)) + coord)
    modeled = {False: m_serial, True: m_piped}
    rows = {}
    for pl in (False, True):
        eng = engines[pl]
        snap = eng.stats()["engine"]
        stall = sum(snap["shard_stall_seconds"].values())
        wall = sum(snap["shard_wall_seconds"].values())
        rows[pl] = {
            "mix": mix_name,
            "shards": shards,
            "pipeline": pl,
            "wall_ops_per_sec": round(REPS * n / sum(walls[pl]), 1),
            "modeled_ops_per_sec": round(REPS * n / sum(modeled[pl]), 1),
            "io_per_op": round(sum(cell_ios) / (REPS * n), 3),
            "max_shard_io_frac": round(max(cell_ios) /
                                       max(sum(cell_ios), 1), 3),
            "shard_stall_frac": round(stall / max(wall + stall, 1e-12),
                                      3),
            # Engine-side batch-latency tails per op class (whole engine
            # lifetime: preload + warm + measured reps) and per-shard
            # plan-execution p99 — the EngineStats histograms the PR's
            # observability layer keeps regardless of tracing.
            "batch_latency_us": {
                op: {q: h[q] for q in ("p50_us", "p95_us", "p99_us")}
                for op, h in snap["latency"].items()},
            "shard_p99_us": {s: h["p99_us"]
                             for s, h in snap["shard_latency"].items()},
        }
    rows[True]["speedup_vs_serial_modeled"] = round(float(np.median(
        [s / p for s, p in zip(m_serial, m_piped)])), 2)
    rows[True]["speedup_vs_serial_wall"] = round(float(np.median(
        [s / p for s, p in zip(walls[False], walls[True])])), 2)
    for pl in (False, True):
        rows[pl]["devices"] = engines[pl].stats()["devices"]["distinct"]
    # -------- measured-wall gate: timed-I/O mode (see module docstring).
    # Same engines (both sides executed identical batches, so their tree
    # states are identical), now sleeping out every charged block I/O.
    # The serial single-device side pays its I/O sequentially; the
    # pipelined per-device side overlaps shard waits and shard kernel
    # compute — THE wall-clock win the model has been projecting, now
    # measured.  Rows at <2 shards carry wall_speedup=None (no overlap
    # to measure).
    wall_speedup = None
    timed: dict = {False: [], True: []}
    if shards >= 2:
        for eng in engines.values():
            eng.config.io_wait_s = T_IO
        for rep in range(REPS):
            rep_batches = all_batches[1 + (REPS + rep) * ROUNDS:
                                      1 + (REPS + rep + 1) * ROUNDS]
            for pl in (False, True):
                dt, _, _ = _measure(engines[pl], rep_batches)
                timed[pl].append(dt)
        wall_speedup = round(float(np.median(
            [s / p for s, p in zip(timed[False], timed[True])])), 2)
        rows[True]["wall_timed"] = {
            "serial_single_device_s": round(sum(timed[False]), 4),
            "pipelined_multi_device_s": round(sum(timed[True]), 4),
            "io_wait_s_per_block": T_IO,
        }
    rows[True]["wall_speedup"] = wall_speedup
    return rows[False], rows[True]


def bench_buffer_insert() -> dict:
    """Delete-path staging microbench: before/after buffer-insert wall.

    The same range-delete record stream runs through the historical
    R-tree write buffer (per-record Python descent + disjointize on
    flush — PR 3's hot spot in delete-heavy mixes) and through the
    columnar ``StagingBuffer`` (burst-sized vectorized appends + the
    incrementally merged ``drain_disjoint``), with identical flush
    points (every ``buffer_capacity`` records).  Both walls include the
    flush-time disjointize, so the ratio is the end-to-end buffer
    absorption speedup the refactor delivers.
    """
    cap = gloran_cfg().index.buffer_capacity
    rng = np.random.default_rng(12)
    los = rng.integers(0, UNIVERSE - RDEL_LEN - 1,
                       size=N_BUF).astype(np.uint64)
    his = los + np.uint64(RDEL_LEN)
    smins = np.zeros(N_BUF, dtype=np.uint64)
    seqs = np.arange(1, N_BUF + 1, dtype=np.uint64)

    t0 = time.perf_counter()
    rt = RTree()
    for lo, hi, s in zip(los.tolist(), his.tolist(), seqs.tolist()):
        rt.insert(lo, hi, 0, s)
        if rt.size >= cap:
            disjointize(rt.extract_all())
            rt.clear()
    rtree_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    sb = StagingBuffer(cap)
    for a0 in range(0, N_BUF, BURST):  # engine plan-step-sized arrivals
        a1 = min(N_BUF, a0 + BURST)
        at = a0
        while at < a1:
            take = min(max(cap - sb.size, 1), a1 - at)
            sb.insert_batch(los[at:at + take], his[at:at + take],
                            smins[at:at + take], seqs[at:at + take])
            at += take
            if sb.size >= cap:
                sb.drain_disjoint()
                sb.clear()
    staging_s = time.perf_counter() - t1
    out = {
        "records": N_BUF,
        "arrival_burst": BURST,
        "buffer_capacity": cap,
        "rtree_buffer_seconds": round(rtree_s, 4),
        "staging_buffer_seconds": round(staging_s, 4),
        "speedup": round(rtree_s / staging_s, 2),
    }
    print(f"# buffer insert x{N_BUF}: rtree {rtree_s:.3f}s -> staging "
          f"{staging_s:.3f}s ({out['speedup']}x)", flush=True)
    return out


def bench_wal_overhead() -> dict:
    """Durability cost: put-heavy mixed throughput, WAL on vs off.

    The same deterministic batch stream (puts with periodic range
    deletes, fresh store both sides so flush points match exactly) runs
    through a no-WAL engine and a WAL engine with group commit +
    ``fsync="batch"`` — the strongest policy, one fsync per submitted
    shard plan.  Interleaved reps, median-of-medians ratio; the
    acceptance gate holds it under 1.25x.
    """
    import shutil
    import tempfile

    rng = np.random.default_rng(17)
    # No smoke reduction: the stream must be long enough to amortize
    # per-stream fixed costs (first-segment creation, warmup batches),
    # or the ratio measures those instead of the steady-state fsync
    # cost.  Full size is ~2 s — cheap enough for check.sh.
    n_batches = 12
    batches = []
    for i in range(n_batches):
        keys = rng.integers(0, UNIVERSE, size=BATCH).astype(np.uint64)
        batches.append(OpBatch.puts(keys, keys + np.uint64(1)))
        if i % 3 == 2:
            lo = int(rng.integers(0, UNIVERSE - RDEL_LEN - 1))
            batches.append(OpBatch.range_deletes([(lo, lo + RDEL_LEN)]))

    def one_pass(wal_dir: str | None) -> tuple[float, dict | None]:
        cfg = EngineConfig(partition="range", pipeline=False, devices=0,
                           wal_dir=wal_dir, fsync="batch",
                           scheduler=False)
        eng = Engine(num_shards=2, strategy="gloran",
                     lsm_config=lsm_cfg(), gloran_config=gloran_cfg(),
                     config=cfg)
        t0 = time.perf_counter()
        for b in batches:
            eng.submit(b, pipeline=False).wait()
        wall = time.perf_counter() - t0
        wal = (eng.stats().get("wal") if wal_dir is not None else None)
        eng.close()
        return wall, wal

    walls: dict = {"none": [], "wal": []}
    wal_counters = None
    reps = max(REPS, 3)
    for _ in range(reps):
        for mode in ("none", "wal"):
            tmp = (tempfile.mkdtemp(prefix="repro-walbench-")
                   if mode == "wal" else None)
            try:
                wall, counters = one_pass(tmp)
            finally:
                if tmp is not None:
                    shutil.rmtree(tmp, ignore_errors=True)
            walls[mode].append(wall)
            if counters is not None:
                wal_counters = counters
    nw = float(np.median(walls["none"]))
    ww = float(np.median(walls["wal"]))
    n_ops = sum(len(b) for b in batches)
    out = {
        "ops": n_ops,
        "reps": reps,
        "fsync": "batch",
        "nowal_wall_seconds": round(nw, 4),
        "wal_wall_seconds": round(ww, 4),
        "nowal_ops_per_sec": round(n_ops / nw),
        "wal_ops_per_sec": round(n_ops / ww),
        "overhead_ratio": round(ww / nw, 3),
        "wal_bytes": wal_counters["bytes"],
        "wal_fsyncs": wal_counters["fsyncs"],
        "wal_frames": wal_counters["frames"],
    }
    print(f"# wal overhead: {nw:.3f}s -> {ww:.3f}s "
          f"({out['overhead_ratio']}x, {out['wal_fsyncs']} fsyncs, "
          f"{out['wal_bytes'] / 1e6:.1f} MB logged)", flush=True)
    return out


def bench_flush_materialize() -> dict:
    """Memtable->run materialization: dict row-tuple loop vs the run.

    The memtable used to be a ``key -> (seq, type, val)`` dict that a
    flush materialized with a Python row-tuple comprehension
    (``np.array([(k, s, t, v) ...])``); it is now a key-sorted columnar
    run that the flush hands to ``build_sstable(presorted=True)`` as it
    stands.  This micro-bench times both over the same 10^5 puts and
    checks they produce identical runs.
    """
    from repro.lsm.sstable import build_sstable
    from repro.lsm.tree import LSMTree

    n = 100_000
    rng = np.random.default_rng(41)
    keys = rng.permutation(
        rng.integers(0, UNIVERSE, size=n).astype(np.uint64))
    tree = LSMTree(LSMConfig(buffer_capacity=n + 1, key_size=16,
                             value_size=48, key_universe=UNIVERSE),
                   strategy="decomp")
    tree.put_batch(keys, keys + np.uint64(1))
    cfg = tree.config
    mem = {k: (s, 0, k + 1)
           for s, k in enumerate(keys.tolist(), start=1)}

    def legacy():
        items = np.array([(k, s, t, v)
                          for k, (s, t, v) in mem.items()],
                         dtype=np.uint64)
        return build_sstable(items[:, 0], items[:, 1],
                             items[:, 2].astype(np.uint8), items[:, 3],
                             cfg)

    def columnar():
        return build_sstable(*tree.mem, cfg, presorted=True)

    reps = max(REPS, 3)
    walls = {"legacy": [], "columnar": []}
    runs = {}
    for _ in range(reps):
        for name, fn in (("legacy", legacy), ("columnar", columnar)):
            t0 = time.perf_counter()
            runs[name] = fn()
            walls[name].append(time.perf_counter() - t0)
    np.testing.assert_array_equal(runs["legacy"].keys,
                                  runs["columnar"].keys)
    np.testing.assert_array_equal(runs["legacy"].vals,
                                  runs["columnar"].vals)
    lw = float(np.median(walls["legacy"]))
    cw = float(np.median(walls["columnar"]))
    out = {
        "entries": n,
        "reps": reps,
        "legacy_wall_seconds": round(lw, 4),
        "columnar_wall_seconds": round(cw, 4),
        "speedup": round(lw / cw, 2),
    }
    print(f"# flush materialize x{n}: rows {lw:.3f}s -> columnar "
          f"{cw:.3f}s ({out['speedup']}x)", flush=True)
    return out


def bench_bg_scheduler() -> dict:
    """Background compaction: put tail latency + steady-state uploads.

    A session-expiry stream (each round puts a fresh key window, range-
    deletes the previous one, then reads) runs against two otherwise
    identical engines: inline flushes (``scheduler=False``) vs the
    background scheduler with the Lethe-style tombstone-density trigger.
    Batches submit serially (depth 1) so each batch's wall is exactly
    what it carries:

      inline  the put batch that fills the memtable pays the flush +
              L0 merge (and any cascade) on its own wall — the p99 put
              tail IS the compaction.
      bg      the same put batch only seals the memtable; the flush job
              runs at the next plan's drain point (the read batch),
              and tombstone-dense levels compact proactively, purging
              range-deleted entries at the bottom.

    Reported: per-batch put p99 from the engine latency histograms
    (``stats()["engine"]["latency"]["put"]``) over the measured window,
    and the same window's host->device ``upload_bytes`` delta — the
    proactive purge keeps levels and the GLORAN index small, so the
    read path's device re-packs move fewer bytes at steady state.
    """
    warm, rounds = (1, 5) if SMOKE else (2, 12)
    span_w = 1 << 14  # key window per round; fully expired next round

    def round_batches(r: int, rng) -> list[OpBatch]:
        base = (r * span_w) % (UNIVERSE - 2 * span_w)
        keys = base + rng.choice(span_w, size=4096,
                                 replace=False).astype(np.uint64)
        prev = (base - span_w) % (UNIVERSE - 2 * span_w)
        step = span_w // 32
        rdels = [(int(prev + j * step), int(prev + (j + 1) * step))
                 for j in range(32)]
        reads = base + rng.integers(0, span_w,
                                    size=2048).astype(np.uint64)
        return [OpBatch.puts(keys[:2048], keys[:2048] + np.uint64(1)),
                OpBatch.puts(keys[2048:], keys[2048:] + np.uint64(1)),
                OpBatch.gets(reads),
                OpBatch.range_deletes(rdels)]

    def one_side(background: bool) -> dict:
        cfg = EngineConfig(partition="range", pipeline=False, devices=0,
                           kernel_min_batch=32, kernel_min_areas=32,
                           kernel_min_filter=512,
                           scheduler=background, max_frozen=4,
                           tombstone_trigger=0.1 if background
                           else None)
        eng = Engine(num_shards=1, strategy="gloran",
                     lsm_config=lsm_cfg(), gloran_config=gloran_cfg(),
                     config=cfg)
        rng = np.random.default_rng(53)
        for r in range(warm):
            for b in round_batches(r, rng):
                eng.submit(b, pipeline=False).wait()
        eng.reset_stats()
        up0 = eng.kernel_counters.upload_bytes
        for r in range(warm, warm + rounds):
            for b in round_batches(r, rng):
                eng.submit(b, pipeline=False).wait()
        snap = eng.stats()
        put = snap["engine"]["latency"]["put"]
        out = {
            "p99_put_us": put["p99_us"],
            "p50_put_us": put["p50_us"],
            "upload_bytes": eng.kernel_counters.upload_bytes - up0,
            "entries": eng.num_entries,
        }
        if background:
            out["sched"] = snap["sched"]
        eng.close()
        return out

    inline = one_side(False)
    bg = one_side(True)
    out = {
        "rounds": rounds,
        "puts_per_round": 4096,
        "inline_p99_put_us": inline["p99_put_us"],
        "bg_p99_put_us": bg["p99_put_us"],
        "p99_put_improvement": round(
            inline["p99_put_us"] / max(bg["p99_put_us"], 1e-9), 2),
        "inline_p50_put_us": inline["p50_put_us"],
        "bg_p50_put_us": bg["p50_put_us"],
        "inline_upload_bytes": inline["upload_bytes"],
        "bg_upload_bytes": bg["upload_bytes"],
        "upload_bytes_ratio": round(
            bg["upload_bytes"] / max(inline["upload_bytes"], 1), 3),
        "inline_entries": inline["entries"],
        "bg_entries": bg["entries"],
        "sched": bg["sched"],
    }
    print(f"# bg scheduler: put p99 {inline['p99_put_us']:.0f}us -> "
          f"{bg['p99_put_us']:.0f}us ({out['p99_put_improvement']}x), "
          f"uploads {inline['upload_bytes'] / 1e6:.1f}MB -> "
          f"{bg['upload_bytes'] / 1e6:.1f}MB "
          f"(ratio {out['upload_bytes_ratio']}), "
          f"{out['sched']['proactive_jobs']} proactive jobs", flush=True)
    return out


def export_trace(path: str, shards: int = 4) -> dict:
    """One traced {shards}-shard pipelined mixed pass -> Chrome trace.

    The exported JSON loads in Perfetto / chrome://tracing: one track
    per shard worker thread (submit -> plan.compile -> shard.plan ->
    per-step shard.* -> kernel.* spans, engine.collect on the caller
    track).  Also prints the ``analysis.report`` trace digest."""
    from repro import obs
    from repro.analysis.report import trace_report

    eng = make_engine(shards, True)
    batches = mixed_batches(MIXES["scan_heavy"], 4, seed=91)
    eng.submit(batches[0]).wait()  # warm jit outside the trace
    with obs.enabled() as tr:
        run_batches(eng, batches[1:])
        eng.drain()
        tr.export_chrome(path)
        rep = trace_report(tr.chrome_events())
    print(f"# wrote {path}: {len(tr.events())} spans over "
          f"{len(batches) - 1} batches x{shards} shards; wall "
          f"{rep['wall_us']:.0f}us, perfect-overlap bound "
          f"{rep['modeled_us']:.0f}us, stall shares "
          + " ".join(f"s{s}:{r['stall_share']:.0%}"
                     for s, r in rep["shards"].items()), flush=True)
    return rep


def run() -> dict:
    if TRACE_OUT and TRACE_ONLY:
        export_trace(TRACE_OUT)
        return {}
    rows = []
    for mix_name in MIX_KEYS:
        for shards in SHARDS:
            serial, piped = bench_cell(mix_name, shards)
            rows += [serial, piped]
            print(f"# {mix_name:12s} x{shards}: serial "
                  f"{serial['modeled_ops_per_sec']:,.0f} modeled ops/s, "
                  f"pipelined {piped['modeled_ops_per_sec']:,.0f} "
                  f"({piped['speedup_vs_serial_modeled']}x modeled, "
                  f"{piped['speedup_vs_serial_wall']}x wall, "
                  f"{piped['wall_speedup']}x timed wall on "
                  f"{piped['devices']} devices), stall "
                  f"{piped['shard_stall_frac']:.0%}", flush=True)
    max_s = max(SHARDS)
    target = [r for r in rows if r["shards"] == max_s
              and r.get("speedup_vs_serial_modeled")]
    geo = float(np.exp(np.mean(np.log(
        [r["speedup_vs_serial_modeled"] for r in target])))) \
        if target else None
    timed_rows = [r for r in rows if r["shards"] >= 2
                  and r.get("wall_speedup") is not None]
    buf = bench_buffer_insert()
    wal = bench_wal_overhead()
    flm = bench_flush_materialize()
    bg = bench_bg_scheduler()
    result = {
        "config": {
            "preload_entries": PRELOAD,
            "preload_range_deletes": N_RDEL,
            "universe": UNIVERSE,
            "batch": BATCH,
            "rounds": ROUNDS,
            "reps": REPS,
            "scan_entries": SCAN_ENTRIES,
            "rdel_len": RDEL_LEN,
            "get_hit_frac": GET_HIT_FRAC,
            "submit_depth": DEPTH,
            "buffer_insert_records": N_BUF,
            "mixes": {k: MIXES[k] for k in MIX_KEYS},
            "t_io_seconds": T_IO,
            "strategy": "gloran",
            "partition": "range",
            "smoke": SMOKE,
        },
        "rows": rows,
        "buffer_insert": buf,
        "wal": wal,
        "flush_materialize": flm,
        "bg_scheduler": bg,
        "acceptance": {
            # Background compaction gates (scripts/check.sh): the put
            # p99 under the delete-heavy session-expiry stream must be
            # >= 2x better with the scheduler on (puts stop carrying
            # flush/compaction), and the measured window must move
            # FEWER host->device bytes (proactive tombstone-density
            # compaction purges dead entries, so device re-packs
            # shrink at steady state).
            "bg_p99_put_improvement": bg["p99_put_improvement"],
            "bg_upload_bytes_ratio": bg["upload_bytes_ratio"],
            # Durability gate: put-heavy throughput with group-commit
            # WAL (fsync per submitted batch) within 1.25x of no-WAL.
            "wal_overhead": wal["overhead_ratio"],
            # Delete-path refactor: columnar staging buffer vs the
            # per-record R-tree write buffer, same stream + flush points.
            "staging_buffer_insert_speedup": buf["speedup"],
            # Headline: modeled mixed-batch throughput, pipelined vs
            # serial, across the mixes at the max shard count (geomean;
            # per-mix and wall numbers are all in ``rows``).
            "geomean_pipeline_speedup_max_shards": round(geo, 2)
            if geo else None,
            "min_pipeline_speedup_max_shards": min(
                (r["speedup_vs_serial_modeled"] for r in target),
                default=None),
            "max_pipeline_speedup_max_shards": max(
                (r["speedup_vs_serial_modeled"] for r in target),
                default=None),
            "min_pipeline_speedup_max_shards_wall": min(
                (r["speedup_vs_serial_wall"] for r in target),
                default=None),
            # THE wall-clock gate (scripts/check.sh): measured wall in
            # timed-I/O mode, pipelined per-shard-device engines vs the
            # serial single-device path, worst mix at >= 2 shards.
            "min_wall_speedup_ge2_shards": min(
                (r["wall_speedup"] for r in timed_rows), default=None),
            "wall_speedup_max_shards": {
                r["mix"]: r["wall_speedup"] for r in timed_rows
                if r["shards"] == max_s},
        },
    }
    if TRACE_OUT:
        export_trace(TRACE_OUT)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(f"# wrote {OUT}: geomean {max_s}-shard modeled pipeline "
          f"speedup = "
          f"{result['acceptance']['geomean_pipeline_speedup_max_shards']}"
          f"x, min timed wall speedup (>=2 shards) = "
          f"{result['acceptance']['min_wall_speedup_ge2_shards']}x",
          flush=True)
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
