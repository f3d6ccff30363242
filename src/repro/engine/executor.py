"""Per-shard batched execution with the fused Pallas filter stage.

``ShardExecutor`` owns one ``LSMTree`` and drives its canonical batched
read path (``LSMTree.get_batch``) with four hooks swapped in:

  cascade_fn   THE preferred read path: one fused launch of the
               ``repro.kernels.cascade`` kernel answers every level's
               Bloom + fence questions and the GLORAN per-level interval
               verdicts from persistent device state (the shard's
               ``DeviceFilterRegistry`` — uploaded once per SSTable /
               index epoch, invalidated on compaction).  Gated by
               ``kernel_min_batch`` and u32 eligibility; when it
               declines, the per-level hooks below serve the lookup
               instead, with identical results and I/O charges,
  bloom_fn     SSTable filter probes through the ``repro.kernels.bloom``
               Pallas kernel (bit-exact with ``BloomBits.might_contain``)
               once the sub-batch and filter are big enough to pay for a
               launch,
  cache        data-block reads charged through the shard's read-through
               ``BlockCache`` so hot blocks stop costing I/O,
  validity_fn  GLORAN validity probing where each LSM-DRtree level is
               queried with one ``interval_query`` Pallas launch instead
               of a per-key ``covers`` descent — the disjoint level
               arrays are clamped into u32 working space (exact for
               u32-range queries) and padded to power-of-two tiles so
               jit re-traces stay bounded by O(log) distinct shapes,
               not one per compaction,
  rank_fn      scan merge-back positions through the
               ``repro.kernels.merge`` merge-rank kernel (bit-exact with
               the host searchsorted pair) once a two-way round's runs
               are big enough to pay for a launch.

Range-delete plan steps stay columnar end-to-end: the step's clipped
``los``/``his`` arrays flow untouched through
``LSMTree.range_delete_arrays`` into the GLORAN staging buffer's
vectorized batch append.

The control flow stays single-sourced in ``LSMTree`` / ``GloranIndex`` /
``LSMDRTree``; hooks only replace HOW a verdict is computed, never what
is charged for it — except the block cache, whose whole point is
skipping charges for resident blocks.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from itertools import groupby

import numpy as np

from ..core.eve import fold64to32
from ..kernels.bloom.ops import bloom_probe
from ..kernels.cascade.ops import cascade_lookup
from ..kernels.dispatch import kernel_form
from ..kernels.interval.ops import interval_query
from ..kernels.merge.ops import merge_ranks
from ..lsm.tree import CascadeVerdict, LSMTree
from ..obs import span
from .cache import BlockCache
from .plan import (KIND_NAMES, OP_DELETE, OP_GET, OP_PUT, OP_RANGE_DELETE,
                   OP_RANGE_SCAN, ShardPlan)
# _U32_LIMIT / _next_pow2 are shared with the registry: both kernel
# paths must gate and pad identically for cascade parity to hold.
from .registry import DeviceFilterRegistry, _next_pow2, _U32_LIMIT
from .stats import KernelCounters
# Submodule import (not the package) keeps the engine <-> durable import
# graph acyclic; durable.manifest depends only on durable.atomic.
from ..durable.manifest import structure_fingerprint
from ..durable.wal import FRAME_BATCH

_QUERY_TILE = 1024  # block_rows(8) x LANES(128): one grid row
_WRITE_KINDS = (OP_PUT, OP_DELETE, OP_RANGE_DELETE)


@dataclass
class EngineConfig:
    """Knobs of the batched execution layer (not the LSM itself)."""

    partition: str = "hash"  # "hash" | "range" key partitioning
    pipeline: bool | None = None  # concurrent shard plans; None = env
    cache_blocks: int = 0  # per-shard block cache capacity; 0 = off
    use_bloom_kernel: bool = True
    use_interval_kernel: bool = True
    use_merge_kernel: bool = True
    use_cascade_kernel: bool = True  # fused all-levels lookup cascade
    cascade_compiled: bool | None = None  # None/True = XLA form
    kernel_min_batch: int = 256  # sub-batch size worth a kernel launch
    kernel_min_areas: int = 64  # DR-tree level size worth a launch
    kernel_min_filter: int = 512  # SSTable entries worth a launch
    kernel_min_merge: int = 1024  # total keys in a 2-way merge round
    # None = the backend picks each kernel's form (kernels.dispatch);
    # any explicit value is refused on a TPU backend.
    interpret: bool | None = None
    # Per-shard XLA devices: None = env (REPRO_ENGINE_DEVICES; unset =
    # auto: use up to num_shards of the available devices, or fall back
    # to the single-device path on 1-device hosts); 0 = forced off (the
    # ungated legacy path); N = pin shards round-robin over the first
    # min(N, available) devices.
    devices: int | None = None
    # Timed-I/O mode: seconds a shard worker sleeps per simulated I/O
    # block its plan step charged (0.0 = off, the default — I/O stays
    # count-only).  With it on, measured wall includes the store's
    # modeled device waits, and those waits OVERLAP across pipelined
    # shard workers (sleep releases the GIL) exactly as concurrent NVMe
    # queues would — the wall-clock benchmark mode.
    io_wait_s: float = 0.0
    # Durability: a WAL directory turns on per-shard write-ahead logging
    # plus the level manifest (see ``repro.durable``).  Batches are
    # acknowledged only after their write ops are appended (and, under
    # the "batch" policy, fsynced).  ``fsync`` is one of "batch" |
    # "rotate" | "never" (see ``durable.wal.FSYNC_POLICIES``).
    wal_dir: str | None = None
    fsync: str = "batch"
    wal_segment_bytes: int = 4 << 20
    # Background delete-aware compaction scheduling (lsm/scheduler.py):
    # None = env (REPRO_ENGINE_BG_COMPACT; unset/0 = off — the inline
    # flush path, byte-identical to the scheduler-less engine).  With it
    # on, a full memtable seals into an immutable snapshot and flush +
    # cascade run as background jobs at the deterministic drain points,
    # so put batches stop carrying compaction on their wall clock.
    scheduler: bool | None = None
    # Soft limit on sealed-but-unflushed memtables per shard; sealing
    # past it backpressures (runs due jobs on the sealing thread,
    # counted as a stall).
    max_frozen: int = 4
    # Lethe-style proactive compaction trigger: a level whose estimated
    # range-tombstone density reaches this fraction is compacted down
    # ahead of overflow (None = capacity-driven only, the parity
    # default — proactive compaction intentionally diverges from the
    # inline level shapes to reclaim GLORAN garbage early).
    tombstone_trigger: float | None = None
    # Shards always execute in this process: a TPU belongs to one
    # process.  The field remains only for callers that still pass
    # ``procs=0``; any other value is refused.
    procs: int = 0

    def __post_init__(self):
        if self.procs != 0:
            raise ValueError(
                f"procs={self.procs}: worker-process shard execution was "
                "removed; shards run in the engine's process (procs=0)")


class ShardExecutor:
    def __init__(self, tree: LSMTree, config: EngineConfig | None = None,
                 device=None):
        self.tree = tree
        self.config = config or EngineConfig()
        # The shard's home XLA device (None = default-device legacy
        # path).  Every kernel dispatch below passes it through, and the
        # registry commits its persistent packs to it, so this shard's
        # device compute — during which jax releases the GIL — runs
        # concurrently with other shards' instead of serializing on
        # device 0.
        self.device = device
        self.cache = BlockCache(self.config.cache_blocks)
        self.kernels = KernelCounters()
        # Device-resident packed filter state for the fused cascade AND
        # the per-level kernel fallback (per-SSTable pieces + GLORAN
        # interval views, structurally invalidated).
        self.registry = DeviceFilterRegistry(self.kernels, device=device)
        # Durability attachments (None = volatile shard; see
        # ``Engine._attach_durability`` / ``repro.durable``).  The WAL
        # writer is single-appender by construction: all appends happen
        # on this shard's worker thread (or the engine thread after a
        # drain), the existing per-shard FIFO.
        self.wal = None
        self.manifest = None
        self.shard_id = 0
        # Background compaction scheduler (None = inline flush path).
        self.scheduler = None
        # Fused write runs (``_write_run``) and the plan steps they held.
        self.write_runs_fused = 0
        self.write_steps_fused = 0
        # Compactions route their two-run merge through the gated
        # merge-rank kernel closure (bit-exact with the host
        # searchsorted pair — same hook the scan tournament uses).
        tree.compaction_rank_fn = self._rank_fn()

    def attach_durability(self, wal, manifest, shard_id: int) -> None:
        self.wal = wal
        self.manifest = manifest
        self.shard_id = int(shard_id)

    def attach_scheduler(self, scheduler) -> None:
        """Enable background mode: the tree seals instead of flushing
        inline, and this executor drains the job queue at every plan
        start / explicit flush (the deterministic points that keep
        results byte-identical to the inline path)."""
        self.scheduler = scheduler
        self.tree.scheduler = scheduler
        self.tree.io.enable_locking()

    def run_scheduler(self, reason: str = "sched") -> None:
        """Drain due background jobs, committing a manifest edit if the
        level structure moved (jobs mutate structure outside any plan,
        exactly like an explicit flush)."""
        if self.scheduler is None or not self.scheduler.has_work():
            return
        fp0 = (structure_fingerprint(self.tree)
               if self.manifest is not None else None)
        self.scheduler.run_due()
        self._maybe_record_structure(fp0, reason)

    def _log_plan(self, sp: ShardPlan) -> None:
        """Group commit: ONE WAL frame holding every write op of this
        shard plan (reads are not logged — replay re-derives any reads
        embedded in delete strategies from the rebuilt state).  Under
        the "batch" fsync policy the frame is durable before any step
        executes, so acknowledgement (which follows ``run_plan``)
        implies durability."""
        kinds, keys, vals, los, his = [], [], [], [], []
        for step in sp.steps:
            if step.kind not in (OP_PUT, OP_DELETE, OP_RANGE_DELETE):
                continue
            if step.kind == OP_RANGE_DELETE:
                n = len(step.los)
                z = np.zeros(n, np.uint64)
                keys.append(z)
                vals.append(z)
                los.append(step.los)
                his.append(step.his)
            else:
                n = len(step.keys)
                z = np.zeros(n, np.uint64)
                keys.append(step.keys)
                vals.append(step.vals if step.kind == OP_PUT else z)
                los.append(z)
                his.append(z)
            kinds.append(np.full(n, step.kind, np.uint8))
        if not kinds:
            return
        self.wal.append(FRAME_BATCH, sp.seq, np.concatenate(kinds),
                        np.concatenate(keys), np.concatenate(vals),
                        np.concatenate(los), np.concatenate(his))

    def _maybe_record_structure(self, fp0, reason: str) -> None:
        """Commit a manifest edit iff the durable structure moved."""
        if self.manifest is None:
            return
        if structure_fingerprint(self.tree) != fp0:
            self.manifest.record_structure(self.shard_id, self.tree,
                                           reason=reason)

    # ----------------------------------------------------------- writes
    def put_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Insert a batch of (key, val) pairs into the shard's tree."""
        self.tree.put_batch(keys, vals)

    def delete_batch(self, keys: np.ndarray) -> None:
        """Point-delete a batch of keys (one tombstone each)."""
        self.tree.delete_batch(keys)

    def range_delete(self, lo: int, hi: int) -> None:
        """Delete [lo, hi) via the tree's configured strategy."""
        self.tree.range_delete(lo, hi)

    def range_delete_batch(self, ranges) -> None:
        """Apply a batch of [lo, hi) range deletes in request order
        (GLORAN absorbs the batch in one index/estimator call)."""
        self.tree.range_delete_batch(ranges)

    def range_delete_arrays(self, los: np.ndarray, his: np.ndarray) -> None:
        """Columnar batch range delete: the plan step's clipped bound
        arrays go straight into the tree (no tuple round trip)."""
        self.tree.range_delete_arrays(los, his)

    def flush(self) -> None:
        """Flush the shard's memtable (and LRR buffer) to level 0.

        Durable shards first log a FLUSH marker — the flush mutates
        level structure outside any plan, and replay must flush at the
        same point for level shapes to come back byte-identical — and
        commit a manifest edit if the level stack moved."""
        if self.wal is not None:
            self.wal.append_flush()
        fp0 = (structure_fingerprint(self.tree)
               if self.manifest is not None else None)
        self.tree.flush()
        if self.scheduler is not None:
            # Explicit flush is synchronous: the FLUSH frame above acks
            # only after the background flush durably publishes.
            self.scheduler.drain()
        self._maybe_record_structure(fp0, "flush")

    # ------------------------------------------------ uniform surface
    # The engine aggregates shards through these accessors only.
    @property
    def io_reads(self) -> int:
        return self.tree.io.reads

    @property
    def io_writes(self) -> int:
        return self.tree.io.writes

    @property
    def num_entries(self) -> int:
        return self.tree.num_entries

    def cache_snapshot(self) -> dict:
        return self.cache.snapshot()

    def stats_full(self) -> dict:
        """Every per-shard ledger ``engine.stats()`` rolls up, in one
        JSON-able document."""
        from ..lsm.scheduler import level_rt_density
        tree = self.tree
        return {
            "io": tree.io.snapshot(),
            "entries": int(tree.num_entries),
            "kernels": self.kernels.snapshot(),
            "cache": self.cache.snapshot(),
            "staging": (tree.gloran.buffer_snapshot()
                        if tree.gloran is not None else None),
            "sched": (self.scheduler.counters()
                      if self.scheduler is not None else None),
            "wal": self.wal.counters() if self.wal is not None else None,
            "executor": {"write_runs_fused": self.write_runs_fused,
                         "write_steps_fused": self.write_steps_fused},
            "lsm": {
                "compaction_bytes": {int(i): int(b) for i, b in
                                     tree.compaction_bytes.items()},
                "rt_compaction_bytes": {int(i): int(b) for i, b in
                                        tree.rt_compaction_bytes.items()},
                "rt_density": {i: round(level_rt_density(tree, i), 4)
                               for i in range(len(tree.levels))},
                "num_levels": len(tree.levels),
            },
        }

    def close(self) -> None:
        if self.wal is not None:
            self.wal.close()

    # ------------------------------------------------------- typed plans
    def run_plan(self, sp: ShardPlan) -> tuple[list, float]:
        """Execute one compiled ``ShardPlan`` in request order.

        Each ``PlanStep`` is one vectorized sub-batch on this shard's
        batched paths.  Returns ``(payloads, wall_seconds)`` where
        payloads carry the result-bearing steps — ``(OP_GET, idx, found,
        vals)`` and ``(OP_RANGE_SCAN, idx, [(keys, vals), ...])`` — for
        the engine's deterministic merge-back; ``wall_seconds`` is this
        shard's busy time (the pipeline's per-shard wall/stall metric).
        Thread-safe across shards: every touched structure (tree, cache,
        counters, I/O ledger) is shard-local.
        """
        t0 = time.perf_counter()
        payloads: list = []
        with span("shard.plan", shard=sp.shard, batch=sp.seq,
                  steps=len(sp.steps), n_ops=sp.n_ops,
                  device="host" if self.device is None else
                  f"{self.device.platform}:{self.device.id}"):
            if self.wal is not None:
                with span("shard.wal_append", shard=sp.shard,
                          batch=sp.seq):
                    self._log_plan(sp)
            # Background jobs drain BEFORE the plan's steps: every plan
            # starts from the fully-caught-up state the inline path
            # would have reached, which is what keeps cross-plan
            # results, level shapes, and I/O ledgers byte-identical
            # with the scheduler on.
            self.run_scheduler()
            fp0 = (structure_fingerprint(self.tree)
                   if self.manifest is not None else None)
            # Maximal runs of write steps with no read between them, and
            # the read steps between those runs.
            for writes, run in groupby(
                    sp.steps, key=lambda s: s.kind in _WRITE_KINDS):
                run = list(run)
                if writes and self._fuses(run):
                    self._write_run(sp, run)
                else:
                    for step in run:
                        self._run_step(sp, step, payloads)
            self._maybe_record_structure(fp0, "plan")
        return payloads, time.perf_counter() - t0

    def _run_step(self, sp: ShardPlan, step, payloads: list) -> None:
        """Apply one plan step on its own."""
        with span("shard." + KIND_NAMES[step.kind], n=len(step),
                  shard=sp.shard, batch=sp.seq):
            io0 = self.tree.io.total
            if step.kind == OP_PUT:
                self.put_batch(step.keys, step.vals)
            elif step.kind == OP_DELETE:
                self.delete_batch(step.keys)
            elif step.kind == OP_GET:
                found, vals = self.get_batch(step.keys)
                payloads.append((OP_GET, step.idx, found, vals))
            elif step.kind == OP_RANGE_SCAN:
                res = self.range_scan_batch(
                    list(zip(step.los.tolist(), step.his.tolist())))
                payloads.append((OP_RANGE_SCAN, step.idx, res))
            else:  # OP_RANGE_DELETE (bounds clipped per shard)
                self.range_delete_arrays(step.los, step.his)
            self._io_wait(io0)

    def _io_wait(self, io0: int) -> None:
        """Timed-I/O mode: serve the blocks charged since ``io0`` as a
        real wait.  Charges are untouched (the ledger stays
        bit-identical); only wall time grows, and it overlaps across
        shard workers — sleep releases the GIL."""
        io_wait = self.config.io_wait_s
        if io_wait > 0.0:
            dio = self.tree.io.total - io0
            if dio:
                time.sleep(dio * io_wait)

    # ------------------------------------------------ fused write runs
    def _fuses(self, run: list) -> bool:
        """Whether a run of write steps is applied fused: it mixes range
        deletes with memtable writes on a GLORAN tree, whose range
        deletes never enter the memtable.  Every other run (single-kind,
        or range deletes that are memtable writes or point ops under the
        other strategies) is applied step by step."""
        kinds = {step.kind for step in run}
        return (self.tree.strategy == "gloran" and OP_RANGE_DELETE in kinds
                and (OP_PUT in kinds or OP_DELETE in kinds))

    def _write_run(self, sp: ShardPlan, run: list) -> None:
        """Apply a run of interleaved memtable writes and GLORAN range
        deletes as one index call and one memtable call per same-kind
        stretch, with exactly the state step-by-step application gives.

        Under GLORAN the memtable's flush points depend only on the
        memtable writes and the index's only on the range deletes; the
        two meet where a memtable flush's bottom compaction probes the
        index and sets its GC floor.  So the run is cut into segments
        that end with the step during which the memtable may fill, each
        applied range deletes first, then memtable writes, every op at
        the seq its in-order application would have drawn: at every
        flush the index holds exactly the range deletes issued before
        it, and ``tree.seq`` reads what the in-order put batch had set.
        """
        tree = self.tree
        io0 = tree.io.total
        with span("shard.write_run", shard=sp.shard, batch=sp.seq,
                  steps=len(run),
                  puts=sum(len(s) for s in run if s.kind == OP_PUT),
                  range_deletes=sum(len(s) for s in run
                                    if s.kind == OP_RANGE_DELETE)):
            at = 0
            while at < len(run):
                room = tree.config.buffer_capacity - len(tree.mem[0])
                end, w = at, 0
                while end < len(run):
                    step = run[end]
                    end += 1
                    if step.kind != OP_RANGE_DELETE:
                        w += len(step)
                        if w >= room:
                            break  # the memtable may fill in this step
                self._write_segment(sp, run[at:end])
                at = end
        self.write_runs_fused += 1
        self.write_steps_fused += len(run)
        self._io_wait(io0)

    def _write_segment(self, sp: ShardPlan, seg: list) -> None:
        """One segment of a fused write run: its range deletes in one
        call, then its memtable writes in one call per same-kind
        stretch, at the seqs reserved for them in request order."""
        at = self.tree.seq
        own = []  # the seqs each step would draw in request order
        for step in seg:
            own.append(np.arange(at + 1, at + len(step) + 1,
                                 dtype=np.uint64))
            at += len(step)
        rds = [i for i, s in enumerate(seg) if s.kind == OP_RANGE_DELETE]
        mems = [i for i, s in enumerate(seg) if s.kind != OP_RANGE_DELETE]
        with self.tree.reserved_seqs(
                np.concatenate([own[i] for i in rds + mems]), at):
            if rds:
                n = sum(len(seg[i]) for i in rds)
                with span("shard.range_delete", n=n, shard=sp.shard,
                          batch=sp.seq):
                    self.range_delete_arrays(
                        np.concatenate([seg[i].los for i in rds]),
                        np.concatenate([seg[i].his for i in rds]))
            for kind, group in groupby((seg[i] for i in mems),
                                       key=lambda s: s.kind):
                group = list(group)
                keys = np.concatenate([s.keys for s in group])
                with span("shard." + KIND_NAMES[kind], n=len(keys),
                          shard=sp.shard, batch=sp.seq):
                    if kind == OP_PUT:
                        self.put_batch(
                            keys, np.concatenate([s.vals for s in group]))
                    else:
                        self.delete_batch(keys)

    # ------------------------------------------------------------ reads
    def _validity_fn(self):
        """The GLORAN validity hook: batched ``is_deleted`` verdicts with
        per-level probes routed through the interval Pallas kernel (when
        gating admits a launch).  None for non-GLORAN strategies."""
        t = self.tree
        if t.strategy == "gloran" and t.gloran is not None:
            return lambda k, s: t.gloran.is_deleted_batch(
                k, s, query_fn=self._query_drtree_level)
        return None

    def get_batch(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Batched point lookups; (found, vals), order = request order.

        The fused cascade hook answers the whole filter stack in one
        launch when its gates admit the batch; the per-level bloom /
        interval hooks are the ungated fallback for the same call."""
        self.cache.op_class = "get"
        return self.tree.get_batch(
            np.asarray(keys, dtype=np.uint64),
            cache=self.cache if self.cache.enabled else None,
            bloom_fn=self._bloom_maybe,
            validity_fn=self._validity_fn(),
            cascade_fn=self._cascade)

    # --------------------------------------------------- cascade kernel
    def _cascade(self, keys: np.ndarray, resolved: np.ndarray,
                 seqs: np.ndarray) -> CascadeVerdict | None:
        """One fused launch for a lookup batch, or None to decline.

        Gates: the batch must be worth a launch (``kernel_min_batch``),
        the tree's packed view must exist (non-empty levels, u32-exact
        keys/seqs, within the pack budget of the cascade's form on this
        shard's device — see ``DeviceFilterRegistry``), and the query
        keys plus any memtable-resolved seqs must fit u32 working space.
        A declined launch falls back to the per-level path with identical
        results.
        """
        cfg = self.config
        if not cfg.use_cascade_kernel or len(keys) < cfg.kernel_min_batch:
            return None
        form = kernel_form("cascade", interpret=cfg.interpret,
                           compiled=cfg.cascade_compiled)
        view = self.registry.view(self.tree, form)
        if view is None:
            return None
        if int(keys.max()) >= _U32_LIMIT:
            return None
        if resolved.any() and int(seqs[resolved].max()) >= _U32_LIMIT:
            return None
        maybe, hit, gl_cov, pos = cascade_lookup(
            keys.astype(np.uint32), fold64to32(keys),
            seqs.astype(np.uint32), resolved, view.state,
            interpret=cfg.interpret, compiled=cfg.cascade_compiled,
            device=self.device)
        self.kernels.cascade_calls += 1
        self.kernels.cascade_queries += len(keys)
        return CascadeVerdict(slots=view.slots, maybe=maybe, hit=hit,
                              pos=pos,
                              gl_cov=gl_cov if view.has_gloran else None)

    def range_scan(self, lo: int, hi: int):
        """One range scan; (keys, vals) of the live entries in [lo, hi)."""
        return self.range_scan_batch([(lo, hi)])[0]

    def range_scan_batch(self, ranges) -> list:
        """Batched range scans through the tree's one-pass batch path,
        with GLORAN validity filtering on the kernel hook, merge-back
        positions on the merge-rank kernel hook, and slice charges
        absorbed by the shard's block cache; one (keys, vals) pair per
        requested [lo, hi), in request order."""
        self.cache.op_class = "range_scan"
        return self.tree.range_scan_batch(
            ranges, validity_fn=self._validity_fn(),
            cache=self.cache if self.cache.enabled else None,
            rank_fn=self._rank_fn())

    # ----------------------------------------------------- merge kernel
    def _rank_fn(self):
        """The sorted-view merge hook: two-way merge-round output
        positions through the ``merge_ranks`` kernel when the
        round is big enough to pay for a launch and both runs fit u32
        working space; declines (None -> host searchsorted) otherwise.
        """
        cfg = self.config
        if not cfg.use_merge_kernel:
            return None

        def rank(ka: np.ndarray, kb: np.ndarray):
            n = len(ka) + len(kb)
            if (n < cfg.kernel_min_merge or not len(ka) or not len(kb)
                    or int(ka[-1]) >= _U32_LIMIT
                    or int(kb[-1]) >= _U32_LIMIT):
                return None
            # Both runs padded to powers of two so compiled shapes stay
            # O(log) distinct across compactions; the pad sits above
            # every admitted key, so neither rank counts it.
            a = np.full(_next_pow2(len(ka)), _U32_LIMIT, dtype=np.uint32)
            b = np.full(_next_pow2(len(kb)), _U32_LIMIT, dtype=np.uint32)
            a[:len(ka)] = ka
            b[:len(kb)] = kb
            pa, pb = merge_ranks(a, b, interpret=cfg.interpret,
                                 device=self.device, na=len(ka),
                                 nb=len(kb))
            self.kernels.merge_calls += 1
            self.kernels.merge_keys += n
            return pa[:len(ka)], pb[:len(kb)]

        return rank

    # --------------------------------------------------- filter kernels
    def _bloom_maybe(self, lvl, keys: np.ndarray) -> np.ndarray:
        """SSTable filter verdicts; Pallas-launched when worth it.

        Filter words go to the kernel as the registry's device-resident
        copy (uploaded once per run uid), so the ungated per-level path
        stops re-uploading the filter on every probe."""
        cfg = self.config
        bb = lvl.bloom
        if (cfg.use_bloom_kernel and len(keys) >= cfg.kernel_min_batch
                and len(lvl) >= cfg.kernel_min_filter):
            n = len(keys)
            m = max(_QUERY_TILE, _next_pow2(n))
            k32 = np.zeros(m, dtype=np.uint32)
            k32[:n] = fold64to32(keys)
            out = np.asarray(bloom_probe(
                k32, self.registry.bloom_words(lvl), m_bits=bb.m_bits,
                seeds=tuple(int(s) for s in bb.seeds),
                interpret=cfg.interpret, device=self.device))
            self.kernels.bloom_calls += 1
            self.kernels.bloom_queries += n
            return out[:n]
        return bb.might_contain(keys)

    def _query_drtree_level(self, lvl, keys: np.ndarray, seqs: np.ndarray,
                            io) -> np.ndarray:
        """Point-stab one DR-tree level; Pallas-launched when worth it."""
        cfg = self.config
        if (cfg.use_interval_kernel
                and len(lvl) >= cfg.kernel_min_areas
                and len(keys) >= cfg.kernel_min_batch
                and int(keys.max()) < _U32_LIMIT
                and int(seqs.max()) < _U32_LIMIT):
            return self._interval_kernel_query(lvl, keys, seqs, io)
        return lvl.query_batch(keys, seqs, io=io)

    def _interval_kernel_query(self, lvl, keys: np.ndarray,
                               seqs: np.ndarray, io) -> np.ndarray:
        """One Pallas launch over a disjoint level; same I/O as a probe."""
        lo32, hi32, smin32, smax32 = self._level_u32(lvl)
        io.read_blocks(lvl.probe_cost() * len(keys), tag="drtree_probe")
        n = len(keys)
        m = max(_QUERY_TILE, _next_pow2(n))
        kq = np.zeros(m, dtype=np.uint32)
        sq = np.zeros(m, dtype=np.uint32)
        kq[:n] = keys.astype(np.uint32)
        sq[:n] = seqs.astype(np.uint32)
        out = np.asarray(interval_query(kq, sq, lo32, hi32, smin32, smax32,
                                        interpret=self.config.interpret,
                                        device=self.device))
        self.kernels.interval_calls += 1
        self.kernels.interval_queries += n
        return out[:n]

    def _level_u32(self, lvl):
        """Clamped, padded u32 view of an immutable DR-tree level —
        the registry's device-resident piece (``clamp_level_u32``, the
        single source of the u32 transform), shared with the cascade's
        packed GLORAN view: one upload and one device copy serve both
        kernel paths, and the interval ops layer passes the pre-uploaded
        ``jax.Array`` columns through untouched."""
        live = [l for l in getattr(self.tree.gloran.index, "levels", [])
                if l is not None]
        return self.registry.gl_columns(lvl, live)
