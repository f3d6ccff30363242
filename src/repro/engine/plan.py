"""Typed op batches and the planner that compiles them per shard.

The engine's public surface is **plan -> submit -> collect**:

  ``OpBatch``    a typed, columnar batch of mixed operations — structured
                 arrays for kind/key/val/lo/hi, validated at construction
                 (replaces the ad-hoc ``("get", k)`` tuple convention),
  ``Planner``    compiles an ``OpBatch`` against a ``ShardRouter`` into
                 one ``ShardPlan`` per shard: point ops are routed
                 vectorized, range ops are clipped to the owning slabs,
                 and consecutive same-kind ops bound for the same shard
                 are grouped into one vectorized ``PlanStep``,
  ``Plan``       the compiled batch: per-shard plans plus the merge-back
                 bookkeeping (which op ids are scans, how many ops).

Plans are pure data — compiling one mutates nothing — so planning batch
n+1 can overlap executing batch n (see ``engine.pending``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import count

import numpy as np

from ..obs import span
from .router import ShardRouter

# Op kind codes (stable: these are the OpBatch column encoding).
OP_PUT = 0
OP_DELETE = 1
OP_GET = 2
OP_RANGE_DELETE = 3
OP_RANGE_SCAN = 4

KIND_NAMES = ("put", "delete", "get", "range_delete", "range_scan")
KIND_CODES = {name: code for code, name in enumerate(KIND_NAMES)}
_POINT_KINDS = (OP_PUT, OP_DELETE, OP_GET)
# Tuple arity per kind for the ``from_ops`` migration shim.
_ARITY = {OP_PUT: 3, OP_DELETE: 2, OP_GET: 2,
          OP_RANGE_DELETE: 3, OP_RANGE_SCAN: 3}


def _u64(x, n: int | None = None) -> np.ndarray:
    if x is None:
        return np.zeros(0 if n is None else n, dtype=np.uint64)
    return np.asarray(x, dtype=np.uint64)


class OpBatch:
    """A typed, columnar batch of mixed engine operations.

    Struct-of-arrays: ``kinds`` (uint8 op codes), ``keys``/``vals``
    (uint64, point ops), ``los``/``his`` (uint64, range ops).  Unused
    columns hold zeros.  Construction validates shape, kind codes, and
    range bounds once — executors and planners then trust the arrays
    and never re-inspect per-op tuples.

    Build one with the typed constructors (``OpBatch.gets(keys)``,
    ``OpBatch.puts(keys, vals)``, ``OpBatch.range_scans(ranges)``, ...),
    the mixed-stream shim ``OpBatch.from_ops([("put", k, v), ...])``, or
    directly from columns.  Batches are immutable by convention; results
    of ``Engine.submit`` align with op order (op id = row index).
    """

    __slots__ = ("kinds", "keys", "vals", "los", "his")

    def __init__(self, kinds, keys=None, vals=None, los=None, his=None):
        kinds = np.asarray(kinds, dtype=np.uint8)
        n = len(kinds)
        self.kinds = kinds
        self.keys = _u64(keys, n)
        self.vals = _u64(vals, n)
        self.los = _u64(los, n)
        self.his = _u64(his, n)
        self._validate()

    def _validate(self) -> None:
        n = len(self.kinds)
        for name in ("keys", "vals", "los", "his"):
            col = getattr(self, name)
            if col.ndim != 1 or len(col) != n:
                raise ValueError(
                    f"OpBatch.{name}: expected 1-D length {n}, "
                    f"got shape {col.shape}")
        if n and int(self.kinds.max()) > OP_RANGE_SCAN:
            bad = int(np.flatnonzero(self.kinds > OP_RANGE_SCAN)[0])
            raise ValueError(
                f"OpBatch: unknown op kind code {self.kinds[bad]} "
                f"at op {bad}")
        rng = self.kinds >= OP_RANGE_DELETE
        if rng.any():
            empty = rng & (self.los >= self.his)
            if empty.any():
                bad = int(np.flatnonzero(empty)[0])
                raise ValueError(
                    f"OpBatch: empty range [{self.los[bad]}, "
                    f"{self.his[bad]}) at op {bad} "
                    f"({KIND_NAMES[self.kinds[bad]]})")

    # ------------------------------------------------------ constructors
    @classmethod
    def puts(cls, keys, vals) -> "OpBatch":
        keys, vals = _u64(keys), _u64(vals)
        if len(keys) != len(vals):
            raise ValueError(
                f"OpBatch.puts: {len(keys)} keys vs {len(vals)} vals")
        return cls(np.full(len(keys), OP_PUT, np.uint8), keys=keys,
                   vals=vals)

    @classmethod
    def deletes(cls, keys) -> "OpBatch":
        keys = _u64(keys)
        return cls(np.full(len(keys), OP_DELETE, np.uint8), keys=keys)

    @classmethod
    def gets(cls, keys) -> "OpBatch":
        keys = _u64(keys)
        return cls(np.full(len(keys), OP_GET, np.uint8), keys=keys)

    @classmethod
    def _ranges(cls, code: int, ranges) -> "OpBatch":
        ranges = list(ranges)
        los = _u64([r[0] for r in ranges])
        his = _u64([r[1] for r in ranges])
        return cls(np.full(len(ranges), code, np.uint8), los=los, his=his)

    @classmethod
    def range_deletes(cls, ranges) -> "OpBatch":
        return cls._ranges(OP_RANGE_DELETE, ranges)

    @classmethod
    def range_scans(cls, ranges) -> "OpBatch":
        return cls._ranges(OP_RANGE_SCAN, ranges)

    @classmethod
    def from_ops(cls, ops) -> "OpBatch":
        """Migration shim from the legacy tuple stream:
        ``("put", k, v) | ("delete", k) | ("get", k) |
        ("range_delete", lo, hi) | ("range_scan", lo, hi)``."""
        n = len(ops)
        kinds = np.zeros(n, dtype=np.uint8)
        keys = np.zeros(n, dtype=np.uint64)
        vals = np.zeros(n, dtype=np.uint64)
        los = np.zeros(n, dtype=np.uint64)
        his = np.zeros(n, dtype=np.uint64)
        for i, op in enumerate(ops):
            code = KIND_CODES.get(op[0])
            if code is None:
                raise ValueError(f"unknown op kind: {op[0]!r} at op {i}")
            if len(op) != _ARITY[code]:
                raise ValueError(
                    f"op {i}: {op[0]!r} takes {_ARITY[code] - 1} "
                    f"arguments, got {len(op) - 1}")
            kinds[i] = code
            if code in _POINT_KINDS:
                keys[i] = op[1]
                if code == OP_PUT:
                    vals[i] = op[2]
            else:
                los[i], his[i] = op[1], op[2]
        return cls(kinds, keys=keys, vals=vals, los=los, his=his)

    @classmethod
    def concat(cls, batches) -> "OpBatch":
        batches = list(batches)
        if not batches:
            return cls(np.zeros(0, np.uint8))
        return cls(np.concatenate([b.kinds for b in batches]),
                   keys=np.concatenate([b.keys for b in batches]),
                   vals=np.concatenate([b.vals for b in batches]),
                   los=np.concatenate([b.los for b in batches]),
                   his=np.concatenate([b.his for b in batches]))

    # ------------------------------------------------------------- views
    def __len__(self) -> int:
        return len(self.kinds)

    @property
    def scan_ids(self) -> np.ndarray:
        """Op ids of the range scans (merge-back slots)."""
        return np.flatnonzero(self.kinds == OP_RANGE_SCAN)

    @property
    def get_ids(self) -> np.ndarray:
        """Op ids of the point gets."""
        return np.flatnonzero(self.kinds == OP_GET)

    @property
    def kind_name(self) -> str:
        """The op class: a kind name if homogeneous, else ``"mixed"``."""
        if len(self.kinds) == 0:
            return "mixed"
        k0 = int(self.kinds[0])
        if (self.kinds == k0).all():
            return KIND_NAMES[k0]
        return "mixed"

    def counts(self) -> dict:
        c = np.bincount(self.kinds, minlength=len(KIND_NAMES))
        return {name: int(c[code]) for code, name in enumerate(KIND_NAMES)
                if c[code]}

    def to_ops(self) -> list[tuple]:
        """Back to the legacy tuple stream (tests / debugging)."""
        out = []
        for i, code in enumerate(self.kinds.tolist()):
            if code == OP_PUT:
                out.append(("put", int(self.keys[i]), int(self.vals[i])))
            elif code in (OP_DELETE, OP_GET):
                out.append((KIND_NAMES[code], int(self.keys[i])))
            else:
                out.append((KIND_NAMES[code], int(self.los[i]),
                            int(self.his[i])))
        return out

    def __repr__(self) -> str:
        return f"OpBatch(n={len(self)}, {self.counts()})"


@dataclass
class PlanStep:
    """One same-kind vectorized sub-batch bound for one shard.

    ``idx`` holds the op ids (rows of the source ``OpBatch``) this step
    serves, ascending — per-shard arrival order is request order.  Point
    steps carry ``keys`` (and ``vals`` for puts); range steps carry the
    per-shard *clipped* ``los``/``his``.
    """

    kind: int
    idx: np.ndarray
    keys: np.ndarray | None = None
    vals: np.ndarray | None = None
    los: np.ndarray | None = None
    his: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.idx)


@dataclass
class ShardPlan:
    """Everything one shard executes for a batch, in request order."""

    shard: int
    steps: list[PlanStep] = field(default_factory=list)
    seq: int = -1  # owning Plan's batch number (trace correlation)

    @property
    def n_ops(self) -> int:
        return sum(len(s) for s in self.steps)

    def __bool__(self) -> bool:
        return bool(self.steps)


@dataclass
class Plan:
    """A compiled ``OpBatch``: per-shard plans + merge-back bookkeeping."""

    batch: OpBatch
    shard_plans: list[ShardPlan]
    seq: int = -1  # planner-assigned batch number (trace correlation)

    @property
    def n_ops(self) -> int:
        return len(self.batch)

    @property
    def scan_ids(self) -> np.ndarray:
        return self.batch.scan_ids


class Planner:
    """Compiles ``OpBatch``es into per-shard ``ShardPlan``s.

    Routing is columnar: one vectorized ``shard_of`` call covers every
    point op, one vectorized ``clip_ranges`` call covers every range op
    (clipping each [lo, hi) to the slabs it overlaps under range
    partitioning, broadcasting under hash).  Per shard, the op stream is
    ordered by op id and split into maximal same-kind runs — each run
    becomes one ``PlanStep``, so a shard executes exactly the vectorized
    sub-batches the old ``Engine.execute`` loop built per-op in Python.
    """

    def __init__(self, router: ShardRouter):
        self.router = router
        self._seq = count()

    def plan(self, batch: OpBatch) -> Plan:
        seq = next(self._seq)
        with span("plan.compile", kind=batch.kind_name,
                  n_ops=len(batch), batch=seq) as sp:
            plan, slots, conflicts = self._plan(batch, seq)
            sp.set(read_slots=slots, read_conflicts=conflicts)
            return plan

    def _plan(self, batch: OpBatch, seq: int) -> tuple[Plan, int, int]:
        ns = self.router.num_shards
        kinds = batch.kinds
        point_ids = np.flatnonzero(kinds <= OP_GET)
        range_ids = np.flatnonzero(kinds >= OP_RANGE_DELETE)

        # Per-shard op ids (points) — split() is stable, ids ascend.
        if len(point_ids):
            psplit = self.router.split(batch.keys[point_ids])
        else:
            psplit = [np.zeros(0, np.int64)] * ns

        # Per-shard clipped visits (ranges), vectorized across the batch.
        rids, rshards, clos, chis = self.router.clip_ranges(
            batch.los[range_ids], batch.his[range_ids])

        plans = []
        slots = conflicts = 0
        for s in range(ns):
            oidx = point_ids[psplit[s]]
            slo = shi = None
            vm = rshards == s
            if vm.any():
                v_ids = range_ids[rids[vm]]
                oidx = np.concatenate([oidx, v_ids])
                slo = np.concatenate(
                    [np.zeros(len(oidx) - len(v_ids), np.uint64),
                     clos[vm]])
                shi = np.concatenate(
                    [np.zeros(len(oidx) - len(v_ids), np.uint64),
                     chis[vm]])
                order = np.argsort(oidx, kind="stable")
                oidx, slo, shi = oidx[order], slo[order], shi[order]
            sp, n_slots, n_conflicts = self._shard_plan(s, batch, oidx,
                                                        slo, shi)
            sp.seq = seq
            plans.append(sp)
            slots += n_slots
            conflicts += n_conflicts
        return Plan(batch=batch, shard_plans=plans, seq=seq), slots, conflicts

    def _shard_plan(self, s: int, batch: OpBatch, oidx: np.ndarray,
                    slo, shi) -> tuple[ShardPlan, int, int]:
        """Split one shard's ordered op-id stream into vectorized steps;
        returns the plan, its read slots, and the reads that closed one.

        Writes split on every kind change (their relative order is the
        semantics).  Reads are scheduled dependency-aware: a get or a
        range scan commutes with every other read, and it commutes with
        an intervening *write* as long as the write does not touch its
        key(s) — a range delete over a cold slab cannot change what a
        hot get observes.  Number the stream's same-kind segments; for
        each read, ``last`` is the last write segment before its own
        that touches it.  A *read slot* opens at the first read segment
        and holds every read whose ``last`` precedes the slot; the first
        read with a later ``last`` closes it, and the next slot opens at
        that read's segment.  Each slot executes where it opened — one
        batched-get step, then one batched-scan step, both in op-id
        order — so mixed streams compile to a few large read sub-batches
        while every read still observes exactly the writes its results
        depend on.
        """
        sp = ShardPlan(shard=s)
        n = len(oidx)
        if n == 0:
            return sp, 0, 0
        k = batch.kinds[oidx]
        wr = (k != OP_GET) & (k != OP_RANGE_SCAN)
        brk = (wr[1:] != wr[:-1]) | (wr[1:] & (k[1:] != k[:-1]))
        starts = np.concatenate([[0], np.flatnonzero(brk) + 1])
        keys = batch.keys[oidx]
        vals = batch.vals[oidx]

        rpos = np.flatnonzero(~wr)  # reads, in op-id order
        opens: list = []  # the segment each read slot opens at
        conflicts = 0
        if len(rpos) == n:
            opens = [0]  # reads only: one slot, nothing to test
            groups = [rpos]
        elif len(rpos):
            seg = np.concatenate([[0], np.cumsum(brk)])
            rseg = seg[rpos]
            last = self._last_writes(k, seg, keys, slo, shi, rpos, rseg)
            # Slot chase: a read conflicts with the slot opened at segment
            # o iff last > o, so the next slot opens at the first segment
            # holding such a read — a suffix minimum over reads by last.
            by_last = np.argsort(last, kind="stable")
            last_sorted = last[by_last]
            first_seg = np.minimum.accumulate(rseg[by_last][::-1])[::-1]
            opens = [int(rseg[0])]
            while True:
                i = int(np.searchsorted(last_sorted, opens[-1], "right"))
                if i == len(last_sorted):
                    break
                opens.append(int(first_seg[i]))
            # Each read joins the newest slot opened at or before its
            # segment, unless that slot opened at its own segment and
            # the read does not conflict with the slot before.
            op_arr = np.asarray(opens)
            slot = np.searchsorted(op_arr, rseg, "right") - 1
            at_open = (slot > 0) & (op_arr[slot] == rseg)
            back = at_open & (last <= op_arr[np.maximum(slot - 1, 0)])
            slot -= back
            conflicts = int((at_open & ~back).sum())
            by_slot = np.argsort(slot, kind="stable")
            cuts = np.searchsorted(slot[by_slot], np.arange(1, len(opens)))
            groups = np.split(rpos[by_slot], cuts)

        def emit_slot(j: int) -> None:
            in_slot = groups[j]
            rk = k[in_slot]
            gpos = in_slot[rk == OP_GET]
            if len(gpos):
                sp.steps.append(PlanStep(kind=OP_GET, idx=oidx[gpos],
                                         keys=keys[gpos]))
            spos = in_slot[rk == OP_RANGE_SCAN]
            if len(spos):
                sp.steps.append(PlanStep(kind=OP_RANGE_SCAN,
                                         idx=oidx[spos], los=slo[spos],
                                         his=shi[spos]))

        # Visit the write segments and the segments slots open at.
        visit = wr[starts]
        visit[opens] = True
        g = np.flatnonzero(visit)
        ends = np.append(starts[1:], n)
        slot_at = {o: j for j, o in enumerate(opens)}
        for at, a, b, kind in zip(g.tolist(), starts[g].tolist(),
                                  ends[g].tolist(), k[starts[g]].tolist()):
            if kind == OP_RANGE_DELETE:
                sp.steps.append(PlanStep(kind=kind, idx=oidx[a:b],
                                         los=slo[a:b], his=shi[a:b]))
            elif kind == OP_PUT or kind == OP_DELETE:
                sp.steps.append(PlanStep(
                    kind=kind, idx=oidx[a:b], keys=keys[a:b],
                    vals=vals[a:b] if kind == OP_PUT else None))
            else:
                emit_slot(slot_at[at])
        return sp, len(opens), conflicts

    @staticmethod
    def _last_writes(k, seg, keys, slo, shi, rpos, rseg) -> np.ndarray:
        """For each read, the last write segment before its own that
        touches it, or -1.  A get is touched by a write range covering
        its key or a written key equal to it; a scan by a write range
        overlapping [lo, hi) or a written key inside it."""
        last = np.full(len(rpos), -1, np.int64)
        pw = np.flatnonzero((k == OP_PUT) | (k == OP_DELETE))
        rw = np.flatnonzero(k == OP_RANGE_DELETE)
        if len(pw) + len(rw) == 0:
            return last
        is_get = k[rpos] == OP_GET
        gi = np.flatnonzero(is_get)
        if len(pw) and len(gi):
            # Gets against point writes: sort by (key, position) and
            # carry the last write within each key group.
            pos = np.concatenate([pw, rpos[gi]])
            order = np.lexsort((pos, keys[pos]))
            wmark = np.where(order < len(pw), np.arange(len(pos)), -1)
            prev = np.maximum.accumulate(wmark)
            sk = keys[pos[order]]
            at = np.flatnonzero(order >= len(pw))
            m = prev[at]
            hit = (m >= 0) & (sk[np.maximum(m, 0)] == sk[at])
            last[gi[order[at[hit]] - len(pw)]] = seg[pos[order[m[hit]]]]
        # Interval tests as inclusive [lo, hi] bounds: a get is [key, key],
        # a scan or range write [lo, hi - 1], a point write [key, key].
        if len(rw) and len(gi):
            gk = keys[rpos[gi]]
            last[gi] = np.maximum(last[gi], _last_overlap(
                gk, gk, rseg[gi], slo[rw], shi[rw] - 1, seg[rw]))
        si = np.flatnonzero(~is_get)
        if len(si):  # scans: slo/shi exist, so rw may index them
            sp_ = rpos[si]
            last[si] = _last_overlap(
                slo[sp_], shi[sp_] - 1, rseg[si],
                np.concatenate([keys[pw], slo[rw]]),
                np.concatenate([keys[pw], shi[rw] - 1]),
                seg[np.concatenate([pw, rw])])
        return last


# Cells of one reads x writes overlap block: bounds the planner's memory
# on very large mixed batches.
_OVERLAP_BLOCK = 1 << 20


def _last_overlap(rlo, rhi, rseg, wlo, whi, wseg) -> np.ndarray:
    """Per read interval [rlo, rhi], the largest ``wseg`` among the write
    intervals [wlo, whi] overlapping it with ``wseg`` below the read's
    ``rseg``, or -1 (all bounds inclusive)."""
    out = np.full(len(rlo), -1, np.int64)
    if len(wlo) == 0:
        return out
    # Writes along the first axis: the max reduces across whole rows.
    wlo, whi, wseg = wlo[:, None], whi[:, None], wseg[:, None]
    cols = max(1, _OVERLAP_BLOCK // len(wlo))
    for a in range(0, len(rlo), cols):
        b = a + cols
        hit = ((rlo[a:b] <= whi) & (wlo <= rhi[a:b]) & (wseg < rseg[a:b]))
        out[a:b] = np.where(hit, wseg, -1).max(axis=0)
    return out
