#!/usr/bin/env python
"""Kill-and-recover check: SIGKILL a live writer mid-stream, recover,
verify the durable prefix — the CI form of the crash-consistency
property against a REAL process death (no simulated truncation).

Parent/child protocol:

  child   opens a durable engine (``fsync="batch"``) on a shared WAL
          directory and streams seeded mixed batches (puts, point
          deletes, range deletes, periodic flushes).  After each
          acknowledged batch it appends one line — ``<batch_index>`` —
          to ``acked.log`` (write + flush + fsync), the parent's record
          of what durability was promised.
  parent  waits until a few batches are acked, then SIGKILLs the child
          (no shutdown path runs), recovers the store from the WAL
          directory, regenerates the same seeded stream, and verifies:
          every *acked* batch's effects are present — gets return
          exactly the oracle state of the acked prefix; a possibly
          half-acked trailing batch is allowed to be present or absent
          atomically per shard plan (frames are atomic units).

Exit 0 on success.  Run:  PYTHONPATH=src python scripts/kill_and_recover.py
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

UNIVERSE = 1 << 20
BATCH = 512
N_BATCHES = 200
SHARDS = 2
SEED = 31


def make_batches():
    """The deterministic workload both processes derive independently."""
    rng = np.random.default_rng(SEED)
    out = []
    for i in range(N_BATCHES):
        keys = rng.integers(1, UNIVERSE - 1, BATCH).astype(np.uint64)
        vals = keys * np.uint64(2 + (i % 7))
        dels = keys[: BATCH // 8]
        lo = int(rng.integers(1, UNIVERSE // 2))
        rd = (lo, lo + int(rng.integers(64, 4096)))
        out.append((keys, vals, dels, rd, i % 5 == 4))
    return out


def engine_config(wal_dir):
    from repro.engine import EngineConfig
    return EngineConfig(partition="hash", pipeline=False, devices=0,
                        wal_dir=wal_dir, fsync="batch")


def build_engine(wal_dir):
    from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
    from repro.engine import Engine
    from repro.lsm import LSMConfig
    lsm = LSMConfig(buffer_capacity=1024, key_size=16, value_size=16,
                    key_universe=UNIVERSE)
    glo = GloranConfig(
        index=LSMDRTreeConfig(buffer_capacity=128, key_size=16),
        eve=RAEConfig(capacity=4096, key_universe=UNIVERSE))
    return Engine(SHARDS, strategy="gloran", lsm_config=lsm,
                  gloran_config=glo, config=engine_config(wal_dir))


def child_main(wal_dir: str) -> None:
    eng = build_engine(wal_dir)
    ack = open(os.path.join(wal_dir, "acked.log"), "w")
    for i, (keys, vals, dels, rd, do_flush) in enumerate(make_batches()):
        eng.put_batch(keys, vals)
        eng.delete_batch(dels)
        eng.range_delete(*rd)
        if do_flush:
            eng.flush()
        ack.write(f"{i}\n")
        ack.flush()
        os.fsync(ack.fileno())
    # Never reached under the parent's SIGKILL; harmless standalone.
    eng.close()


def oracle_state(n_acked: int) -> list[dict]:
    """The post-crash envelope: visible key->val states the store may
    legally serve.  [0] is the fully-acked prefix; [1..3] apply the
    in-flight batch's sub-ops (puts, then point deletes, then the range
    delete) — each lands as its own per-shard WAL frame, so any prefix
    of them can be durable on a given shard."""
    state: dict = {}
    for keys, vals, dels, (lo, hi), _ in make_batches()[:n_acked]:
        for k, v in zip(keys.tolist(), vals.tolist()):
            state[k] = v
        for k in dels.tolist():
            state.pop(k, None)
        for k in [k for k in state if lo <= k < hi]:
            del state[k]
    envelope = [state]
    if n_acked < N_BATCHES:
        keys, vals, dels, (lo, hi), _ = make_batches()[n_acked]
        s1 = dict(state)
        for k, v in zip(keys.tolist(), vals.tolist()):
            s1[k] = v
        s2 = dict(s1)
        for k in dels.tolist():
            s2.pop(k, None)
        s3 = {k: v for k, v in s2.items() if not lo <= k < hi}
        envelope += [s1, s2, s3]
    return envelope


def parent_main() -> int:
    wal_dir = tempfile.mkdtemp(prefix="repro-killrec-")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", wal_dir],
        env={**os.environ, "PYTHONPATH": os.environ.get("PYTHONPATH",
                                                        "src")})
    ack_path = os.path.join(wal_dir, "acked.log")
    deadline = time.time() + 180
    target = 8
    try:
        while time.time() < deadline:
            if child.poll() is not None:
                print("child exited before the kill — workload too "
                      "small for this host; treating as failure")
                return 1
            try:
                n = sum(1 for _ in open(ack_path))
            except OSError:
                n = 0
            if n >= target:
                break
            time.sleep(0.05)
        else:
            print("timeout waiting for acked batches")
            return 1
        child.send_signal(signal.SIGKILL)
        child.wait()
    finally:
        if child.poll() is None:
            child.kill()
    acked = [int(x) for x in open(ack_path).read().split()]
    n_acked = max(acked) + 1 if acked else 0
    print(f"killed mid-stream after {n_acked} acked batches")

    # The parent's first import of the store (and so of JAX) comes only
    # now, with the child dead: a TPU belongs to one process at a time.
    from repro.durable import recover
    from repro.engine import EngineConfig
    rec = recover(wal_dir, config=EngineConfig(devices=0, pipeline=False))
    print(f"recovered: {rec.recovery}")

    # Acked-prefix state must be FULLY present.  The batch after the
    # last ack may be partially durable (each of its sub-ops is its own
    # per-shard atomic frame), so every key's served state must match
    # SOME stage of the envelope — never a value from nowhere, never a
    # lost acked write.
    envelope = oracle_state(n_acked)
    want = envelope[0]
    keys = np.array(sorted(want), dtype=np.uint64)
    found, vals = rec.get_batch(keys)
    bad = 0
    for k, f, v in zip(keys.tolist(), found.tolist(), vals.tolist()):
        ok = any((f and st.get(k) == v) or (not f and k not in st)
                 for st in envelope)
        if not ok:
            bad += 1
            if bad <= 5:
                print(f"MISMATCH key={k} found={f} val={v} "
                      f"want={want[k]} envelope="
                      f"{[st.get(k) for st in envelope]}")
    if bad:
        print(f"FAIL: {bad} acked keys lost or corrupted")
        return 1
    m = rec.stats()["metrics"]
    assert m["recovery.wall_s"] > 0.0 and m["wal.bytes"] > 0
    rec.close()
    print(f"OK: all {len(keys)} acked keys verified "
          f"(recovery {m['recovery.wall_s']:.3f}s, "
          f"{int(m['recovery.frames_replayed'])} frames)")
    import shutil
    shutil.rmtree(wal_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child_main(sys.argv[2])
    else:
        sys.exit(parent_main())
