"""The system under test, built from a configuration file.

``build_engine`` turns a configuration's ``store`` section into an
``Engine`` (strategy, shards, placement, the LSM tree and the GLORAN
index).  ``KernelRecorder`` wraps the executor's calls into the cascade
and merge kernels in a traced run: it opens a ``bench.<kernel>`` host
annotation around each call, so the device trace shows which host call
a kernel ran under, and records the call's real sizes for
``kernel_bytes``.
"""

from __future__ import annotations

import time

import numpy as np


def build_engine(store: dict):
    from repro.core import GloranConfig, LSMDRTreeConfig, RAEConfig
    from repro.engine import Engine, EngineConfig
    from repro.lsm import LSMConfig
    universe = 1 << int(store["key_universe_bits"])
    lsm = LSMConfig(key_universe=universe, **store["lsm"])
    gl = store.get("gloran")
    gloran = None if gl is None else GloranConfig(
        index=LSMDRTreeConfig(**gl["index"]),
        eve=RAEConfig(key_universe=universe, **gl["eve"]))
    return Engine(num_shards=int(store["shards"]),
                  strategy=store["strategy"], lsm_config=lsm,
                  gloran_config=gloran,
                  config=EngineConfig(partition=store["partition"],
                                      devices=int(store["devices"]),
                                      procs=0))


def as_batch(req):
    from repro.engine import OpBatch
    return OpBatch(req.kinds, keys=req.keys, vals=req.vals, los=req.los,
                   his=req.his)


class KernelRecorder:
    """Wraps ``cascade_lookup`` and ``merge_ranks`` where the shard
    executor calls them; ``restore`` puts the originals back."""

    def __init__(self, jax):
        from repro.engine import executor
        self._mod = executor
        self._annotate = jax.profiler.TraceAnnotation
        self._orig = {"cascade_lookup": executor.cascade_lookup,
                      "merge_ranks": executor.merge_ranks}
        self._sizes: dict[int, tuple] = {}
        self.calls: list[dict] = []
        executor.cascade_lookup = self._cascade
        executor.merge_ranks = self._merge

    def restore(self) -> None:
        for name, fn in self._orig.items():
            setattr(self._mod, name, fn)

    def _level_sizes(self, state) -> tuple:
        hit = self._sizes.get(id(state))
        if hit is None or hit[0] is not state:
            hit = (state, np.asarray(state.key_cnt).tolist(),
                   np.asarray(state.gl_cnt).tolist())
            self._sizes[id(state)] = hit
        return hit[1], hit[2]

    def _cascade(self, qkey32, qhash32, qseq32, qres, state, **kw):
        key_cnt, gl_cnt = self._level_sizes(state)
        with self._annotate("bench.cascade"):
            t0 = time.perf_counter()
            out = self._orig["cascade_lookup"](qkey32, qhash32, qseq32,
                                               qres, state, **kw)
            t1 = time.perf_counter()
        self.calls.append({"kernel": "cascade", "t0": t0, "t1": t1,
                           "n": len(qkey32), "key_cnt": key_cnt,
                           "gl_cnt": gl_cnt, "hashes": state.H})
        return out

    def _merge(self, ka, kb, **kw):
        # The executor pads both runs with 0xFFFFFFFF above every real
        # key; the real lengths are the counts below that sentinel.
        na = int(np.searchsorted(ka, 0xFFFFFFFF))
        nb = int(np.searchsorted(kb, 0xFFFFFFFF))
        with self._annotate("bench.merge"):
            t0 = time.perf_counter()
            out = self._orig["merge_ranks"](ka, kb, **kw)
            t1 = time.perf_counter()
        self.calls.append({"kernel": "merge", "t0": t0, "t1": t1,
                           "na": na, "nb": nb})
        return out
