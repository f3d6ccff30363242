"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (``traffic/<name>.json``) names request classes, each with
its op count, the exact op counts of each kind in a request, and where
its keys come from.  Requests come in blocks: a block holds ``per_block``
requests of each class, in an order shuffled by the seed, so every seed
sends the same set of sizes in another order.  Within a request the op
kinds are shuffled too.

Key sources, all drawn from the run's seed:

  loaded_uniform   uniform over the preloaded keys,
  loaded_zipf      YCSB's scrambled zipfian over the preloaded keys:
                   ranks from YCSB's ``ZipfianGenerator`` (Gray et al.,
                   "Quickly generating billion-record synthetic
                   databases", SIGMOD 1994) over all records, mapped to
                   keys through a permutation drawn with the records,
  region_uniform   uniform over the key region, so most keys miss.

The preloaded keys come from the run's seed, or, where the configuration
gives a ``record_seed``, from that: the same records in every run.  A
configuration's ``preload_range_deletes`` adds a history after the
load: that many range deletes of ``preload_range_len`` keys, drawn from
the run's seed, in one request (``history``).

Range deletes start uniformly in the region and cover ``range_len``
keys, ``[lo, lo + range_len)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# Op kind codes of the engine's OpBatch columns (repro.engine.plan).
OP_PUT = 0
OP_GET = 2
OP_RANGE_DELETE = 3
KIND_CODES = {"lookup": OP_GET, "update": OP_PUT,
              "range_delete": OP_RANGE_DELETE}


@dataclass
class Request:
    cls: str                 # request class name from the traffic file
    kinds: np.ndarray        # (n,) u8 op codes
    keys: np.ndarray         # (n,) u64; 0 for range deletes
    vals: np.ndarray         # (n,) u64; 0 except for updates
    los: np.ndarray          # (n,) u64; 0 except for range deletes
    his: np.ndarray

    @property
    def n_lookups(self) -> int:
        return int(np.count_nonzero(self.kinds == OP_GET))


class Zipfian:
    """YCSB's ``ZipfianGenerator`` over items 0..n-1 with constant
    ``theta``: item 0 is the most popular."""

    def __init__(self, n: int, theta: float):
        ranks = np.arange(1, n + 1, dtype=np.float64)
        self.n = n
        self.theta = theta
        self.zetan = float(np.sum(ranks ** -theta))
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (
            1.0 - zeta2 / self.zetan)
        self.half_pow = 1.0 + 0.5 ** theta

    def sample(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        far = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        out = np.where(uz < 1.0, 0,
                       np.where(uz < self.half_pow, 1, far.astype(np.int64)))
        return np.minimum(out, self.n - 1).astype(np.int64)


class TrafficGen:
    """Preload columns and an endless, seeded request stream."""

    def __init__(self, traffic: dict, store: dict, seed: int):
        self.traffic = traffic
        self.rng = np.random.default_rng(seed)
        self.region = 1 << int(store["key_region_bits"])
        # A configuration with a ``record_seed`` loads the same records in
        # every run, as YCSB's load phase does; the run's seed then draws
        # the values and the operations.
        rec = (self.rng if "record_seed" not in store
               else np.random.default_rng(int(store["record_seed"])))
        n = int(store["preload_keys"])
        self.keys = rec.choice(self.region, n,
                               replace=False).astype(np.uint64)
        self.vals = self.rng.integers(0, 1 << 63, n, dtype=np.uint64)
        self.history: list[Request] = []
        n_hist = int(store.get("preload_range_deletes", 0))
        if n_hist:
            self.history.append(self._range_deletes(
                "history", n_hist, int(store["preload_range_len"])))
        self.classes = traffic["requests"]
        self._zipf = None
        if any("loaded_zipf" in c["keys"].values() for c in self.classes):
            self._zipf = Zipfian(n, float(traffic["zipf_theta"]))
            self._scramble = rec.permutation(n)
        self._block: list[dict] = []
        self.issued = 0

    def _draw_keys(self, source: str, n: int) -> np.ndarray:
        if source == "loaded_uniform":
            return self.keys[self.rng.integers(0, len(self.keys), n)]
        if source == "loaded_zipf":
            rank = self._zipf.sample(self.rng.random(n))
            return self.keys[self._scramble[rank]]
        if source == "region_uniform":
            return self.rng.integers(0, self.region, n, dtype=np.uint64)
        raise ValueError(f"unknown key source {source!r}")

    def _range_deletes(self, cls: str, n: int, span: int) -> Request:
        lo = self.rng.integers(0, self.region - span, n, dtype=np.uint64)
        zero = np.zeros(n, np.uint64)
        return Request(cls, np.full(n, OP_RANGE_DELETE, np.uint8), zero,
                       zero, lo, lo + np.uint64(span))

    def next_request(self) -> Request:
        if not self._block:
            block = [c for c in self.classes
                     for _ in range(int(c["per_block"]))]
            self._block = [block[i]
                           for i in self.rng.permutation(len(block))]
        c = self._block.pop()
        self.issued += 1
        counts = {kind: int(cnt) for kind, cnt in c["ops"].items()}
        n = sum(counts.values())
        kinds = np.concatenate([np.full(cnt, KIND_CODES[kind], np.uint8)
                                for kind, cnt in counts.items()])
        kinds = kinds[self.rng.permutation(n)]
        keys = np.zeros(n, np.uint64)
        vals = np.zeros(n, np.uint64)
        los = np.zeros(n, np.uint64)
        his = np.zeros(n, np.uint64)
        for kind, code in (("lookup", OP_GET), ("update", OP_PUT)):
            at = kinds == code
            if at.any():
                keys[at] = self._draw_keys(c["keys"][kind], int(at.sum()))
        at = kinds == OP_PUT
        vals[at] = self.rng.integers(0, 1 << 63, int(at.sum()),
                                     dtype=np.uint64)
        at = kinds == OP_RANGE_DELETE
        if at.any():
            span = int(c["range_len"])
            lo = self.rng.integers(0, self.region - span, int(at.sum()),
                                   dtype=np.uint64)
            los[at] = lo
            his[at] = lo + np.uint64(span)
        return Request(c["name"], kinds, keys, vals, los, his)
