"""Stand-ins for the store in the correctness checks.

``ControlEngine`` is the control: the plain reference put in the
program's place, with one stated guarantee broken.

  lag            a lookup does not see the writes of the request just
                 before it: keys that request wrote answer their value
                 from before it (acknowledged writes not yet visible),
  no_rdel        range deletes are dropped (covered keys stay visible).

``install_fault`` breaks the real engine underneath a run, one fault at
a time:

  writes_dropped   every update is acknowledged and never applied: the
                   store's state never changes,
  half_batch       only the first half of each request's lookups is
                   answered; the rest come back not found,
  answer_altered   the first found value of each shard's lookup batch
                   is altered where the shard produces it,
  shard_left_out   shard 0's lookup answers are left out of the gather
                   of shard results (the exchange with the chip that
                   homes it): its keys come back not found,
  window_compile   every shard lookup batch compiles a program of a new
                   shape, as a store whose shapes the rehearsal did not
                   reach would inside the window.
"""

from __future__ import annotations

import numpy as np

from generator import OP_GET, OP_PUT


class _Counters:
    def snapshot(self) -> dict:
        return {"cascade_queries": 0}


class _Pending:
    def __init__(self, found, vals):
        self._out = (found, vals)

    def get_results(self):
        return self._out


class ControlEngine:
    def __init__(self, mode: str):
        assert mode in ("lag", "no_rdel")
        self.mode = mode
        self.d: dict = {}
        self.prev_undo: dict = {}
        self.shards: list = []
        self.kernel_counters = _Counters()
        self.num_entries = 0

    def submit(self, batch):
        d, undo = self.d, {}
        found, vals = [], []
        for kind, k, v, lo, hi in zip(batch.kinds.tolist(),
                                      batch.keys.tolist(),
                                      batch.vals.tolist(),
                                      batch.los.tolist(),
                                      batch.his.tolist()):
            if kind == OP_GET:
                if (self.mode == "lag" and k in self.prev_undo
                        and k not in undo):
                    g = self.prev_undo[k]
                else:
                    g = d.get(k)
                found.append(g is not None)
                vals.append(0 if g is None else g)
            elif kind == OP_PUT:
                undo.setdefault(k, d.get(k))
                d[k] = v
            elif self.mode != "no_rdel":
                for x in range(lo, hi):
                    if x in d:
                        undo.setdefault(x, d.pop(x))
        self.prev_undo = undo
        return _Pending(np.array(found, bool), np.array(vals, np.uint64))

    def drain(self):
        pass

    def device_map(self):
        return {}

    def close(self):
        pass


def install_fault(monkeypatch, fault: str) -> None:
    from repro.engine import executor, pending
    if fault == "writes_dropped":
        monkeypatch.setattr(executor.ShardExecutor, "put_batch",
                            lambda self, keys, vals: None)
    elif fault == "half_batch":
        orig = pending.PendingBatch.get_results

        def half(self):
            found, vals = orig(self)
            found, vals = found.copy(), vals.copy()
            found[len(found) // 2:] = False
            vals[len(vals) // 2:] = 0
            return found, vals
        monkeypatch.setattr(pending.PendingBatch, "get_results", half)
    elif fault == "answer_altered":
        orig = executor.ShardExecutor.get_batch

        def altered(self, keys):
            found, vals = orig(self, keys)
            vals = vals.copy()
            hit = np.flatnonzero(found)
            if len(hit):
                vals[hit[0]] ^= np.uint64(1)
            return found, vals
        monkeypatch.setattr(executor.ShardExecutor, "get_batch", altered)
    elif fault == "shard_left_out":
        orig = executor.ShardExecutor.run_plan

        def left_out(self, sp):
            steps, wall = orig(self, sp)
            if sp.shard == 0:
                steps = [p for p in steps if p[0] != OP_GET]
            return steps, wall
        monkeypatch.setattr(executor.ShardExecutor, "run_plan", left_out)
    elif fault == "window_compile":
        import itertools

        import jax
        orig = executor.ShardExecutor.get_batch
        sizes = itertools.count(1)

        def compiling(self, keys):
            jax.jit(lambda x: x + 1)(np.zeros(next(sizes), np.float32))
            return orig(self, keys)
        monkeypatch.setattr(executor.ShardExecutor, "get_batch", compiling)
    else:
        raise ValueError(fault)
