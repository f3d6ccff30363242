#!/usr/bin/env python
"""Smoke run of the GLORAN store's main path on a TPU.

Loads a 4-shard, hash-partitioned store and drives the paper's balanced
mix (Fig. 9 of arXiv 2511.06061: 50% lookups, 49% updates, 1% range
deletes of 128 keys, strategy ``gloran``) through ``Engine.submit`` in
batches of 4096 ops, then one batch of range scans.  Every answer is
checked against a plain ``dict`` model built from the same seed.  The
run fails unless the lookup cascade, the merge-rank kernel and packed
GLORAN DR-tree levels all reached the device.  The timings it prints are
smoke timings, not benchmark numbers.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one shard per chip vs all on one

With ``--chips 4`` the script runs only the per-shard device homing
check: the same workload on ``devices=4`` and on ``devices=1``, whose
results and I/O ledgers must be identical.

Exits non-zero, printing no result, when JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

SHARDS = 4
UNIVERSE = 1 << 32      # u32 keys: the device paths take the batch
RDEL_LEN = 128
MIX = (0.50, 0.49, 0.01)  # lookups, updates, range deletes (Fig. 9)
PRELOAD_CHUNK = 1 << 16
# The one-chip benchmark deployment's load (2,621,440 keys).  The chip
# runs the cascade's XLA form, whose packs may take a share of the
# chip's HBM (``pack_budget``), far past this load's ~40 MB of packs.
PRELOAD_KEYS = 5 << 19
BATCHES = 48
BATCH = 4096
SCANS = 512
SCAN_LEN = 4096


class CompileLog:
    """Backend compiles (or persistent-cache loads) seen by JAX."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.count, "seconds": self.seconds,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


def make_workload(seed: int) -> dict:
    """Preload columns, mixed op batches and scan ranges, all from
    ``seed``."""
    from repro.engine.plan import OP_GET, OP_PUT, OP_RANGE_DELETE, OpBatch
    rng = np.random.default_rng(seed)
    # Keys are uniform over [0, region), the power of two above 12 x
    # PRELOAD_KEYS: about Fig. 9's density (150k preloaded keys over 2^21).
    region = 1 << (12 * PRELOAD_KEYS).bit_length()
    keys = rng.integers(0, region, PRELOAD_KEYS, dtype=np.uint64)
    vals = rng.integers(0, 1 << 63, PRELOAD_KEYS, dtype=np.uint64)
    batches = []
    for _ in range(BATCHES):
        kinds = rng.choice(np.array([OP_GET, OP_PUT, OP_RANGE_DELETE],
                                    np.uint8), size=BATCH, p=MIX)
        los = rng.integers(0, region - RDEL_LEN, BATCH, dtype=np.uint64)
        rd = kinds == OP_RANGE_DELETE
        batches.append(OpBatch(
            kinds, keys=rng.integers(0, region, BATCH, dtype=np.uint64),
            vals=rng.integers(0, 1 << 63, BATCH, dtype=np.uint64),
            los=np.where(rd, los, 0), his=np.where(rd, los + RDEL_LEN, 0)))
    lo = rng.integers(0, region - SCAN_LEN, SCANS, dtype=np.uint64)
    scans = OpBatch.range_scans(zip(lo.tolist(), (lo + SCAN_LEN).tolist()))
    return {"keys": keys, "vals": vals, "batches": batches, "scans": scans,
            "region": region}


def apply_model(model: dict, batch) -> tuple[np.ndarray, np.ndarray]:
    """Apply one mixed batch to the dict model in request order; the
    expected (found, vals) of its gets."""
    from repro.engine.plan import OP_GET, OP_PUT
    found, vals = [], []
    for kind, k, v, lo, hi in zip(batch.kinds.tolist(), batch.keys.tolist(),
                                  batch.vals.tolist(), batch.los.tolist(),
                                  batch.his.tolist()):
        if kind == OP_GET:
            got = model.get(k)
            found.append(got is not None)
            vals.append(0 if got is None else got)
        elif kind == OP_PUT:
            model[k] = v
        else:
            for x in range(lo, hi):
                model.pop(x, None)
    return np.array(found, bool), np.array(vals, np.uint64)


def store_configs():
    from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
    from repro.lsm import LSMConfig
    # Fig. 9's tree (baselines.workload.make_tree defaults) with the
    # engine bench's 512-record index buffer, so range deletes reach
    # on-disk DR-tree levels within the run.
    lsm = LSMConfig(buffer_capacity=4096, size_ratio=10, key_size=256,
                    value_size=768, block_size=4096, key_universe=UNIVERSE)
    gloran = GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=512, size_ratio=10,
                              key_size=256, block_size=4096),
        eve=RAEConfig(capacity=100_000, bits_per_record=10,
                      key_universe=UNIVERSE))
    return lsm, gloran


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def drive(wl: dict, devices: int) -> dict:
    """Run the whole workload on one engine; check every answer against
    the dict model; return results, ledgers and walls."""
    from repro.engine import Engine, EngineConfig, OpBatch
    lsm, gloran = store_configs()
    eng = Engine(num_shards=SHARDS, strategy="gloran", lsm_config=lsm,
                 gloran_config=gloran,
                 config=EngineConfig(partition="hash", devices=devices))
    walls = {}
    model: dict = {}
    t0 = time.perf_counter()
    keys, vals = wl["keys"], wl["vals"]
    for i in range(0, len(keys), PRELOAD_CHUNK):
        eng.submit(OpBatch.puts(keys[i:i + PRELOAD_CHUNK],
                                vals[i:i + PRELOAD_CHUNK]))
    model.update(zip(keys.tolist(), vals.tolist()))
    eng.drain()
    walls["preload_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    gets = []
    n_gets = 0
    for b, batch in enumerate(wl["batches"]):
        pending = eng.submit(batch)
        want_found, want_vals = apply_model(model, batch)  # overlaps
        found, got = pending.get_results()
        if not np.array_equal(found, want_found) or not np.array_equal(
                got[found], want_vals[want_found]):
            bad = int(np.flatnonzero((found != want_found)
                                     | (found & (got != want_vals)))[0])
            fail(f"batch {b}: get #{bad} returned "
                 f"found={bool(found[bad])} val={int(got[bad])}, the model "
                 f"found={bool(want_found[bad])} val={int(want_vals[bad])}")
        gets.append((found, got))
        n_gets += len(found)
    walls["mixed_batches_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    scans = eng.submit(wl["scans"]).scan_results()
    walls["scan_batch_s"] = time.perf_counter() - t0
    live = np.fromiter(sorted(model), np.uint64, len(model))
    for i, (lo, hi, (sk, sv)) in enumerate(zip(wl["scans"].los,
                                               wl["scans"].his, scans)):
        a, z = np.searchsorted(live, [lo, hi])
        want = live[a:z]
        if not np.array_equal(sk, want) or \
                sv.tolist() != [model[k] for k in want.tolist()]:
            fail(f"scan {i} [{lo}, {hi}): {len(sk)} entries, the model "
                 f"{len(want)}")

    kern = eng.kernel_counters.snapshot()
    io = [sh.stats_full()["io"] for sh in eng.shards]
    packs = []
    for sh in eng.shards:
        view = sh.registry.view(sh.tree)
        if view is None:
            packs.append(None)
            continue
        st = view.state
        packs.append({"levels": st.L, "gloran_levels": st.G,
                      "bytes": sum(int(a.nbytes) for a in (
                          st.lkeys, st.lseqs, st.words, st.glo_lo,
                          st.glo_hi, st.glo_smin, st.glo_smax))})
    device_map = eng.device_map()
    entries = eng.num_entries
    eng.close()
    return {"gets": gets, "scans": scans, "n_gets": n_gets,
            "n_scan_entries": int(sum(len(k) for k, _ in scans)),
            "kernels": kern, "io": io, "packs": packs,
            "device_map": device_map, "entries": entries, "walls": walls}


def check_device_path(out: dict) -> None:
    kern = out["kernels"]
    for name in ("cascade_calls", "merge_calls"):
        if kern[name] <= 0:
            fail(f"{name} = {kern[name]}: that kernel never ran")
    for s, p in enumerate(out["packs"]):
        if p is None:
            fail(f"shard {s}: no cascade pack (declined by the registry)")
        if p["gloran_levels"] <= 0:
            fail(f"shard {s}: no GLORAN DR-tree level in the device pack")


def report(label: str, out: dict) -> None:
    print(f"[{label}] shard->device {out['device_map']}")
    print(f"[{label}] entries {out['entries']}, gets checked "
          f"{out['n_gets']}, scan entries checked {out['n_scan_entries']}")
    print(f"[{label}] resident cascade packs per shard: {out['packs']}")
    print(f"[{label}] kernel counters: {json.dumps(out['kernels'])}")
    print(f"[{label}] phase walls (smoke timings, not benchmark numbers): "
          f"{json.dumps(out['walls'])}")


def compare_homing(wl: dict) -> None:
    """The same workload with one shard per chip and with every shard
    pinned to chip 0: results and per-shard IOStats must be identical."""
    homed = drive(wl, devices=4)
    report("devices=4", homed)
    pinned = drive(wl, devices=1)
    report("devices=1", pinned)
    check_device_path(homed)
    check_device_path(pinned)
    if len(set(homed["device_map"].values())) != 4:
        fail(f"devices=4 did not home the shards on four chips: "
             f"{homed['device_map']}")
    for what in ("gets", "scans"):
        for i, (a, b) in enumerate(zip(homed[what], pinned[what])):
            if not (np.array_equal(a[0], b[0])
                    and np.array_equal(a[1], b[1])):
                fail(f"devices=4 and devices=1 differ in {what} #{i}")
    if homed["io"] != pinned["io"]:
        fail("devices=4 and devices=1 charged different IOStats")
    print("devices=4 vs devices=1: results identical, per-shard IOStats "
          "identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.kernels.cascade.ops import pack_budget
    from repro.kernels.dispatch import XLA, default_forms
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    compiles = CompileLog(jax)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}")
    print(f"kernel forms: {default_forms()}")
    print(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    wl = make_workload(args.seed)
    print(f"workload: {SHARDS} hash shards, {PRELOAD_KEYS} preload keys over "
          f"[0, {wl['region']}), universe {UNIVERSE}, {BATCHES} x "
          f"{BATCH} ops at lookup/update/range-delete {MIX}, "
          f"{SCANS} scans of {SCAN_LEN}; pack limit "
          f"{pack_budget(XLA, devs[0]).bytes} B; generated in "
          f"{time.perf_counter() - t0:.3f} s")

    if args.chips == 1:
        out = drive(wl, devices=0)  # every shard on the default chip
        report("1 chip", out)
        check_device_path(out)
    else:
        compare_homing(wl)
    print(f"compile (smoke timing, not a benchmark number): "
          f"{json.dumps(compiles.snapshot())}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
