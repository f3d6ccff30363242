"""Run one benchmark cell of the GLORAN store once, on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--trace-dir DIR]

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``).  The run:

1. fails, printing no result, unless JAX finds a TPU with at least the
   cell's chips;
2. turns on JAX's persistent compile cache (``<checkout>/.jax_cache``,
   or ``$JAX_COMPILATION_CACHE_DIR``);
3. builds the store through ``Engine`` and preloads it through
   ``Engine.submit``, in chunks of puts, then the configuration's
   history of range deletes;
4. rehearses: drives a first store, built alike, through the traffic
   file's ``warmup_requests`` and then, in the same closed loop, for
   ``REHEARSAL_MARGIN`` times ``--seconds`` of time outside compiles, so
   it reaches past anything the window can reach and every padded shape
   the window will meet has compiled; frees it, builds and preloads the
   measured store, and warms that up on the same request stream's first
   ``warmup_requests``;
5. measures a closed loop for ``--seconds``: one driver thread keeps the
   traffic's ``depth`` requests in flight on ``Engine.submit`` and times
   each from submit until its results are collected;
6. frees the store, then compares every lookup answer of the run with
   the plain reference (``reference.py``), replayed in submit order;
7. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device``, ``breakdown`` (traced runs) and, last,
   ``compared``: each number compared with its limit.  ``correct``
   needs no wrong answer and no compile inside the window.

``--trace 0`` reports the cell's end-to-end metrics (``end_to_end/``);
``--trace 1`` records the program's ``repro.obs`` spans and a
``jax.profiler`` trace of the window and reports the cell's per-layer
metrics (``layer_metrics/``).  ``--trace-dir`` keeps that trace there
instead of in a temporary directory.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from reference import DictStore, count_wrong  # noqa: E402
from generator import TrafficGen  # noqa: E402

# The rehearsal runs this many times the window's length, not counting
# time spent compiling.  It skips repeated lookup-only requests (see
# ``_Rehearsal``), so at the window's speed it goes at least as far
# into the request stream; the margin covers run-to-run spread.
REHEARSAL_MARGIN = 1.5


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer metrics."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def closed_loop(eng, gen, depth: int, more, out: list, annotate, on_done):
    """Keep ``depth`` requests in flight until ``more()`` turns false,
    then drain.  Appends ``(request, found, vals, t_submit, t_done)``."""
    from store import as_batch
    inflight: deque = deque()
    while True:
        while len(inflight) < depth and more():
            with annotate("bench.generate"):
                req = gen.next_request()
                batch = as_batch(req)
            with annotate("bench.submit"):
                t0 = time.perf_counter()
                pending = eng.submit(batch)
            inflight.append((req, pending, t0))
        if not inflight:
            return
        req, pending, t0 = inflight.popleft()
        with annotate("bench.collect"):
            found, vals = pending.get_results()
        out.append((req, found, vals, t0, time.perf_counter()))
        on_done()


class _Rehearsal:
    """The request stream of the rehearsal: the generator's first
    ``n_warm`` requests, then more for ``seconds`` of time outside
    compiles, less each lookup-only request that follows another."""

    def __init__(self, gen, n_warm: int, seconds: float, compiles):
        self.gen = gen
        self.n_warm = n_warm
        self.seconds = seconds
        self.compiles = compiles
        self.t0 = None
        self.prev_read_only = False

    def more(self) -> bool:
        if self.gen.issued < self.n_warm:
            return True
        now = time.time()
        if self.t0 is None:
            self.t0 = now
        return (now - self.t0) - self.compiles.busy(self.t0, now) \
            < self.seconds

    def next_request(self):
        while True:
            req = self.gen.next_request()
            read_only = req.n_lookups == len(req.kinds)
            skip = read_only and self.prev_read_only and self.more()
            self.prev_read_only = read_only
            if not skip:
                return req


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, devices: list,
             trace_dir: str | None = None, t_start: float = T_START,
             peaks: dict | None = None) -> dict:
    """Everything after the look for a chip; returns the result line."""
    import jax
    from compile_log import CompileLog
    from repro.engine import OpBatch
    from store import KernelRecorder, as_batch, build_engine
    store = config["store"]
    compiles = CompileLog(jax)
    phase = {"jax_init_s": time.perf_counter() - t_start}

    def preloaded():
        gen = TrafficGen(traffic, store, seed)
        eng = build_engine(store)
        chunk = int(store["preload_chunk"])
        for i in range(0, len(gen.keys), chunk):
            eng.submit(OpBatch.puts(gen.keys[i:i + chunk],
                                    gen.vals[i:i + chunk]))
        for req in gen.history:
            eng.submit(as_batch(req))
        eng.drain()
        return gen, eng

    depth = int(traffic["depth"])
    n_warm = int(traffic["warmup_requests"])

    # Rehearsal: every padded shape the program will need is fixed by
    # the seeded request stream, so a first store driven through the
    # warm-up and on, for longer than the window, compiles them all (or
    # loads them from the persistent cache).  It is then freed, and a
    # second store, built alike from the same seed, is measured.  Reads
    # leave the store's structure as it is, so of each run of lookup-only
    # requests only the first is sent: the others would meet the shapes
    # it met.
    t = time.perf_counter()
    gen, eng = preloaded()
    rehearsal = _Rehearsal(gen, n_warm, REHEARSAL_MARGIN * seconds,
                           compiles)
    closed_loop(eng, rehearsal, depth, rehearsal.more, [],
                contextlib.nullcontext, lambda: None)
    n_rehearsed = gen.issued
    eng.close()
    del eng, gen, rehearsal
    gc.collect()
    phase["rehearsal_s"] = time.perf_counter() - t
    c_rehearsal = compiles.snapshot()

    t = time.perf_counter()
    gen, eng = preloaded()
    phase["preload_s"] = time.perf_counter() - t

    done: list = []
    compile_at: list = []

    def note_compiles():
        c = compiles.snapshot()["compiles"]
        if c != note_compiles.last:
            compile_at.append((len(done) - 1, c - note_compiles.last))
            note_compiles.last = c
    note_compiles.last = compiles.snapshot()["compiles"]

    t = time.perf_counter()
    closed_loop(eng, gen, depth, lambda: gen.issued < n_warm, done,
                contextlib.nullcontext, note_compiles)
    phase["warmup_s"] = time.perf_counter() - t
    n_warm_done = len(done)
    c_warm = compiles.snapshot()

    annotate = contextlib.nullcontext
    spans_tracer = recorder = prof_dir = None
    if trace:
        from repro.obs.tracer import Tracer, set_tracer
        spans_tracer = Tracer()
        set_tracer(spans_tracer)
        recorder = KernelRecorder(jax)
        annotate = jax.profiler.TraceAnnotation
        prof_dir = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    counters0 = eng.kernel_counters.snapshot()

    w0 = time.perf_counter()
    setup_s = w0 - t_start
    deadline = w0 + seconds
    with annotate("bench.window"):
        closed_loop(eng, gen, depth, lambda: time.perf_counter() < deadline,
                    done, annotate, note_compiles)
    w1 = time.perf_counter()
    c_end = compiles.snapshot()
    counters1 = eng.kernel_counters.snapshot()

    dev_summary = None
    spans: list = []
    calls: list = []
    if trace:
        jax.profiler.stop_trace()
        from repro.obs.tracer import NULL_TRACER, set_tracer
        set_tracer(NULL_TRACER)
        recorder.restore()
        spans = [e for e in spans_tracer.events()
                 if e["t1"] > w0 and e["t0"] < w1]
        calls = [c for c in recorder.calls if w0 <= c["t0"] < w1]
        for k in ("cascade", "merge"):
            first = next((c for c in calls if c["kernel"] == k), None)
            if first is not None:
                log(f"first {k} call of the window: {json.dumps(first)}")
        import devtrace
        t = time.perf_counter()
        dev_summary = devtrace.summarize(devtrace.reduce_file(
            devtrace.find_xplane(prof_dir)))
        log(f"trace reduced in {time.perf_counter() - t:.3f} s")
        if trace_dir is None:
            shutil.rmtree(prof_dir, ignore_errors=True)

    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    entries = eng.num_entries
    device_map = eng.device_map()
    eng.close()
    del eng

    window = done[n_warm_done:]
    run = SimpleNamespace(
        setup_s=setup_s, w0=w0, w1=w1, window_s=w1 - w0, seconds=seconds,
        requests=[SimpleNamespace(cls=r.cls, n_ops=len(r.kinds),
                                  n_lookups=r.n_lookups, t0=a, t1=b)
                  for r, _, _, a, b in window],
        shards=int(store["shards"]), spans=spans, kernel_calls=calls,
        counters0=counters0, counters1=counters1, device=dev_summary,
        peaks=peaks, kernel_bytes=lambda name: load_module("kernel_bytes",
                                                           name))
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = load_module("layer_metrics" if trace else "end_to_end",
                            m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The reference, after the store is freed: replay the preload and
    # every request in submit order, and compare each lookup answer.
    t = time.perf_counter()
    ref = DictStore(gen.keys, gen.vals)
    for req in gen.history:
        ref.apply(req)
    wrong = 0
    failed = 0
    for i, (req, found, vals, _, _) in enumerate(done):
        want_found, want_vals = ref.apply(req)
        bad = count_wrong(found, vals, want_found, want_vals)
        wrong += bad
        if i >= n_warm_done and bad:
            failed += 1
    ref_s = time.perf_counter() - t

    in_window = c_end["compiles"] - c_warm["compiles"]
    log(f"phases {json.dumps(phase)}")
    log(f"setup_s {setup_s:.3f}; window {w1 - w0:.3f} s, {len(window)} "
        f"requests, {sum(len(r.kinds) for r, *_ in window)} ops; warm-up "
        f"{n_warm_done} requests; reference {ref_s:.3f} s")
    by_cls: dict = {}
    for r, _, _, a, b in window:
        by_cls.setdefault(r.cls, []).append(b - a)
    for name, lat in sorted(by_cls.items()):
        log(f"class {name}: {len(lat)} requests, latency ms p50 "
            f"{np.percentile(lat, 50) * 1e3:.3f} p95 "
            f"{np.percentile(lat, 95) * 1e3:.3f} max {max(lat) * 1e3:.3f}")
    log(f"compiles: rehearsal {json.dumps(c_rehearsal)}; after it, warm-up "
        f"{c_warm['compiles'] - c_rehearsal['compiles']}, window "
        f"{in_window} (at request index, count: {compile_at}); rehearsal "
        f"reached request {n_rehearsed}, the window "
        f"{n_warm_done + len(window)}")
    if in_window:
        log("compiled in the window: " + json.dumps(
            {k: v - c_warm["by_name"].get(k, 0)
             for k, v in c_end["by_name"].items()
             if v != c_warm["by_name"].get(k, 0)}))
    log("kernel counters in window: " + json.dumps(
        {k: counters1[k] - counters0[k] for k in counters1
         if isinstance(counters1[k], int)}))
    log(f"entries {entries}; shard->device {device_map}")
    if dev_summary is not None:
        log(f"device kernels (s): {json.dumps(dev_summary['kernels'])}")

    device0 = devices[0]
    device = {"platform": device0.platform, "kind": device0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": wrong == 0 and in_window == 0,
           "attempted": len(window),
           "failed": failed, "metrics": metrics, "device": device}
    if dev_summary is not None:
        device["busy_s"] = dev_summary["busy_s"]
        device["window_s"] = dev_summary["window_s"]
        if dev_summary["breakdown"] is not None:
            out["breakdown"] = dev_summary["breakdown"]
    out["compared"] = {"wrong_answers": {"value": wrong, "limit": 0},
                       "window_compiles": {"value": in_window, "limit": 0}}
    log(f"compared: wrong_answers {wrong} (limit 0) over "
        f"{sum(r.n_lookups for r, *_ in done)} lookups of "
        f"{len(done)} requests")
    log(f"compared: window_compiles {in_window} (limit 0)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        log(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    cell = cells[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    # The compile cache lives in the checkout, at a fixed path, and the
    # program's cache helper takes it from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU found (JAX's first device is {devs[0].platform!r})")
        return 1
    if len(devs) < int(cell["chips"]):
        log(f"cell {cell['name']} needs {cell['chips']} chips, JAX found "
            f"{len(devs)}")
        return 1
    peaks = load_json(HERE, "peaks.json")
    if devs[0].device_kind not in peaks:
        log(f"no peaks for device kind {devs[0].device_kind!r} in "
            "peaks.json")
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    out = run_cell(bench, cell, config, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devs[:int(cell["chips"])],
                   trace_dir=args.trace_dir,
                   peaks=peaks[devs[0].device_kind])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
