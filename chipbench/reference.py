"""The plain reference of the store's semantics: a Python ``dict``.

It imports nothing of the program.  Ops apply in request order and, in
a request, in op order: an update sets the key's value, a range delete
``[lo, hi)`` removes every key in it, and a lookup returns the value the
key holds at that point, or not-found.  That is the configurations'
stated guarantee: every acknowledged write is visible to every later op
in submit order, and a range delete hides every covered key written
before it.
"""

from __future__ import annotations

import numpy as np

from generator import OP_GET, OP_PUT


class DictStore:
    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.d = dict(zip(keys.tolist(), vals.tolist()))

    def apply(self, req) -> tuple[np.ndarray, np.ndarray]:
        """Apply one request; the (found, value) of each lookup, in op
        order."""
        d = self.d
        kinds = req.kinds
        if not (kinds == OP_GET).all():
            return self._apply_ops(req)
        got = [d.get(k) for k in req.keys.tolist()]
        found = np.array([g is not None for g in got], bool)
        vals = np.array([0 if g is None else g for g in got], np.uint64)
        return found, vals

    def _apply_ops(self, req):
        d = self.d
        found, vals = [], []
        for kind, k, v, lo, hi in zip(req.kinds.tolist(), req.keys.tolist(),
                                      req.vals.tolist(), req.los.tolist(),
                                      req.his.tolist()):
            if kind == OP_GET:
                g = d.get(k)
                found.append(g is not None)
                vals.append(0 if g is None else g)
            elif kind == OP_PUT:
                d[k] = v
            else:
                for x in range(lo, hi):
                    d.pop(x, None)
        return np.array(found, bool), np.array(vals, np.uint64)


def count_wrong(found, vals, want_found, want_vals) -> int:
    """Lookups whose found flag or value differs from the reference."""
    bad = (found != want_found) | (want_found & (vals != want_vals))
    return int(np.count_nonzero(bad))
