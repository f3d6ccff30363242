"""Bytes the lookup cascade's algorithm needs for one call.

Counted so that they read the same work whatever implements it, and only
for real queries, never padding.  Per query:

  each SSTable level l     H Bloom-word reads of 4 B, and a binary search
                           of ceil(log2 K_l) probes of key + seq (8 B)
                           over the level's true size K_l,
  each GLORAN level g      a binary search of ceil(log2 A_g) probes of an
                           interval (lo, hi, smin, smax: 16 B) over the
                           level's true area count A_g,
  inputs                   key, Bloom hash, resolved seq and flag: 16 B,
  outputs                  Bloom, hit and GLORAN bit masks (4 B each) and
                           one 4-B position per SSTable level.
"""

from __future__ import annotations

import math


def _probes(n: int) -> int:
    return math.ceil(math.log2(n)) if n > 1 else 0


def bytes_per_query(key_cnt, gl_cnt, hashes: int) -> int:
    levels = sum(4 * hashes + 8 * _probes(int(k)) for k in key_cnt)
    gloran = sum(16 * _probes(int(a)) for a in gl_cnt)
    return levels + gloran + 16 + 12 + 4 * len(key_cnt)


def call_bytes(call: dict) -> int:
    return call["n"] * bytes_per_query(call["key_cnt"], call["gl_cnt"],
                                       call["hashes"])
