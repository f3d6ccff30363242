"""Bytes the merge-rank kernel's algorithm needs for one call.

Two sorted u32 runs of true lengths na and nb (padding not counted).
Each key of one run finds its rank in the other by a binary search of
ceil(log2 n_other) 4-B probes; each key is read once as input (4 B) and
its merged position written once as output (4 B).
"""

from __future__ import annotations

import math


def _probes(n: int) -> int:
    return math.ceil(math.log2(n)) if n > 1 else 0


def call_bytes(call: dict) -> int:
    na, nb = call["na"], call["nb"]
    return 4 * (na * _probes(nb) + nb * _probes(na)) + 8 * (na + nb)
