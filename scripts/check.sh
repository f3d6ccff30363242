#!/usr/bin/env bash
# Local verification: tier-1 tests + a ~10 s engine benchmark smoke so
# batched-lookup throughput drift is caught before it lands.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Per-run pytest timeout when the plugin is available (CI installs
# pytest-timeout): a deadlocked shard/device worker fails fast instead
# of hanging the job.  Local environments without the plugin run plain.
PYTEST_TIMEOUT=""
if python -c "import pytest_timeout" 2>/dev/null; then
    PYTEST_TIMEOUT="--timeout=900 --timeout-method=thread"
fi
python -m pytest -x -q ${PYTEST_TIMEOUT}

REPRO_ENGINE_BENCH_SMOKE=1 REPRO_BENCH_OUT=/tmp/BENCH_engine_smoke.json \
    python benchmarks/engine_bench.py

python - <<'EOF'
import json
d = json.load(open("/tmp/BENCH_engine_smoke.json"))
s = d["acceptance"]["min_speedup_4shard_batch_ge_1024"]
assert s is not None and s >= 2.0, \
    f"engine speedup regressed: {s}x < 2x vs per-key loop"
print(f"check OK: 4-shard batched lookups {s}x vs per-key loop")
c = d["acceptance"]["cascade_min_speedup_vs_perlevel_batch_ge_4096"]
assert c is not None and c >= 1.5, \
    f"fused cascade regressed: {c}x < 1.5x vs per-level kernel path"
print(f"check OK: fused lookup cascade {c}x vs per-level kernels "
      f"at batch >= 4096")
EOF

REPRO_RANGE_BENCH_SMOKE=1 REPRO_BENCH_OUT=/tmp/BENCH_range_smoke.json \
    python benchmarks/range_bench.py

python - <<'EOF'
import json
d = json.load(open("/tmp/BENCH_range_smoke.json"))
s = d["acceptance"]["best_speedup_any_shards"]
assert s is not None and s >= 2.0, \
    f"batched range-scan speedup regressed: {s}x < 2x vs per-call loop"
m = d["acceptance"]["min_speedup_single_shard"]
assert m is not None and m >= 1.4, \
    f"single-shard batched scans regressed: {m}x < 1.4x vs per-call loop"
print(f"check OK: batched range scans best {s}x / 1-shard min {m}x "
      f"vs per-call loop")
EOF

REPRO_MIXED_BENCH_SMOKE=1 REPRO_BENCH_OUT=/tmp/BENCH_mixed_smoke.json \
    python benchmarks/mixed_bench.py

python - <<'EOF'
import json
d = json.load(open("/tmp/BENCH_mixed_smoke.json"))
s = d["acceptance"]["geomean_pipeline_speedup_max_shards"]
assert s is not None and s >= 1.5, \
    f"pipelined mixed-batch speedup regressed: {s}x < 1.5x vs serial"
print(f"check OK: pipelined mixed batches {s}x (modeled) vs serial")
# The tentpole gate: MEASURED wall in timed-I/O mode, pipelined
# per-shard-device engines vs the serial single-device path — the
# model's projected overlap must show up on the clock.
w = d["acceptance"]["min_wall_speedup_ge2_shards"]
assert w is not None and w >= 1.3, \
    f"measured wall speedup regressed: {w}x < 1.3x at >=2 shards"
print(f"check OK: measured timed-I/O wall speedup {w}x (>=2 shards, "
      f"per-shard devices) vs serial single-device")
# Delete-heavy smoke row (range-delete-dominant mix) runs above; the
# staging-buffer gate pins the columnar delete path's absorption win.
b = d["acceptance"]["staging_buffer_insert_speedup"]
assert b is not None and b >= 2.0, \
    f"staging-buffer insert speedup regressed: {b}x < 2x vs R-tree buffer"
print(f"check OK: columnar staging buffer inserts {b}x vs R-tree buffer")
mixes = {r["mix"] for r in d["rows"]}
assert "rdel_dominant" in mixes, "delete-heavy smoke row missing"
lat = next(r["batch_latency_us"] for r in d["rows"] if r["pipeline"])
assert lat and all({"p50_us", "p95_us", "p99_us"} <= set(h)
                   for h in lat.values()), \
    "engine.stats latency percentiles missing from mixed-bench rows"
# Durability gate: group-commit WAL (fsync="batch") must keep a
# put-heavy stream within 1.25x of the no-WAL wall.
w = d["acceptance"]["wal_overhead"]
assert w is not None and w <= 1.25, \
    f"WAL overhead regressed: {w}x > 1.25x vs no-WAL put-heavy stream"
print(f"check OK: group-commit WAL overhead {w}x <= 1.25x")
# Background compaction gates: put p99 under the delete-heavy
# session-expiry stream must be >= 2x better with the scheduler on
# (puts seal instead of carrying flush/compaction), and the measured
# window must move fewer host->device bytes (proactive tombstone-
# density compaction purges dead entries, shrinking device re-packs).
p = d["acceptance"]["bg_p99_put_improvement"]
assert p is not None and p >= 2.0, \
    f"background-scheduler put p99 win regressed: {p}x < 2x vs inline"
print(f"check OK: background scheduler put p99 {p}x better than inline")
u = d["acceptance"]["bg_upload_bytes_ratio"]
assert u is not None and u < 1.0, \
    f"background-scheduler upload bytes not lower: ratio {u} >= 1.0"
print(f"check OK: background steady-state upload bytes ratio {u} < 1.0")
EOF

# Durability: cold-start recovery smoke.  Each row round-trips a store
# through close -> recover() and verifies gets/scans/level shapes
# against the original; the snapshot rows additionally exercise
# take_snapshot + WAL-tail-only replay.
REPRO_RECOVERY_BENCH_SMOKE=1 REPRO_BENCH_OUT=/tmp/BENCH_engine_smoke.json \
    python benchmarks/recovery_bench.py

python - <<'EOF'
import json
d = json.load(open("/tmp/BENCH_engine_smoke.json"))
r = d["recovery"]
assert r["verified"], "recovery rows were not verified against originals"
snap = [x for x in r["rows"] if x["snapshot"]]
assert snap and all(x["snapshot_loaded"] for x in snap), \
    "snapshot fast path did not engage on the snapshot rows"
print(f"check OK: recovery verified on {len(r['rows'])} rows, "
      f"max wall {r['max_recovery_wall_s']}s, snapshot fast path used")
EOF

# Durability: real SIGKILL mid-stream, then recover + verify the acked
# prefix against the seeded oracle envelope.
python scripts/kill_and_recover.py

REPRO_OBS_BENCH_SMOKE=1 REPRO_BENCH_OUT=/tmp/BENCH_obs_smoke.json \
    python benchmarks/obs_overhead.py

python - <<'EOF'
import json
d = json.load(open("/tmp/BENCH_obs_smoke.json"))
a = d["acceptance"]
off = a["disabled_projected_overhead_frac"]
assert off <= 0.02, \
    f"disabled tracer overhead too high: {off:.2%} of batch wall > 2%"
print(f"check OK: disabled tracer costs {off:.3%} of batch wall "
      f"({d['spans_per_batch']} spans x {d['null_span_cost_ns']}ns)")
on = a["enabled_wall_ratio"]
assert on <= 1.10, \
    f"enabled tracer overhead too high: {on}x wall ratio > 1.10x"
print(f"check OK: enabled tracer wall ratio {on}x <= 1.10x")
EOF
