"""Public dispatch for the merge-rank kernel.

Two forms of the same ranks (``kernels.dispatch`` picks one): the jit'd
XLA searchsorted pair, the only form on a TPU backend and
``compiled=True`` elsewhere; and off the chip by default the Pallas
kernel in interpret mode, which pads the query side to (rows x 128)
tiles and chunks VMEM-oversized resident runs (contiguous sorted slices
— per-chunk counts add).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import span
from ..dispatch import XLA, kernel_form
from .kernel import LANES, merge_rank_pallas
from .ref import merge_ranks_ref

# 4 B x 1 Mi = 4 MB resident run per call keeps run + tiles under VMEM.
MAX_KEYS_PER_CALL = 1 << 20


_merge_ranks_xla = jax.jit(merge_ranks_ref)


def _as_dev(arr, device, dtype=jnp.uint32):
    """Upload one operand, committed to ``device`` when one is given.

    Merge rounds have no persistent device-resident state (both runs
    arrive as host numpy every call), so unlike the filter kernels the
    merge path strictly needs explicit placement to run per shard —
    uncommitted uploads would all land on the default device."""
    if device is not None:
        return jax.device_put(np.asarray(arr, np.uint32), device)
    return jnp.asarray(arr, dtype)


def _rank(queries: np.ndarray, arr: np.ndarray, *, leq: bool,
          block_rows: int, interpret: bool, device) -> np.ndarray:
    """Counts of ``arr`` elements preceding each query (chunk-summed)."""
    q32 = _as_dev(queries, device)
    n = q32.shape[0]
    tile = block_rows * LANES
    n_pad = -n % tile
    q = jnp.pad(q32, (0, n_pad)).reshape(-1, LANES)
    total = jnp.zeros(q.shape, dtype=jnp.int32)
    m = arr.shape[0]
    for a0 in range(0, m, MAX_KEYS_PER_CALL):
        a1 = min(m, a0 + MAX_KEYS_PER_CALL)
        total = total + merge_rank_pallas(
            q, _as_dev(arr[a0:a1], device), leq=leq,
            block_rows=block_rows, interpret=interpret)
    return np.asarray(total).reshape(-1)[:n]


def merge_ranks(ka: np.ndarray, kb: np.ndarray, *, block_rows: int = 8,
                interpret: bool | None = None,
                compiled: bool | None = None, device=None,
                na: int | None = None, nb: int | None = None):
    """Merged-output positions of two key-sorted uint32 runs.

    Returns ``(pa, pb)`` int64 numpy arrays: ``pa[i]`` is the slot of
    ``ka[i]`` in the merged order, ``pb`` likewise; ties across runs
    place a-entries first — bit-exact with the host searchsorted pair in
    ``lsm.merge.merge_two`` (duplicates within and across runs allowed).

    ``compiled=True`` routes through the jit'd XLA path instead of the
    Pallas kernel; ``None`` picks from the backend (XLA on a TPU, the
    interpreted Pallas kernel elsewhere).  ``device`` commits both runs
    to one XLA device so the launch runs there (per-shard placement).
    ``na``/``nb`` name the real run lengths when the caller has padded
    the runs; they only label the ``kernel.merge`` span.
    """
    ka = np.asarray(ka)
    kb = np.asarray(kb)
    with span("kernel.merge", na=len(ka) if na is None else na,
              nb=len(kb) if nb is None else nb):
        return _merge_ranks(ka, kb, block_rows=block_rows,
                            interpret=interpret, compiled=compiled,
                            device=device)


def _merge_ranks(ka, kb, *, block_rows, interpret, compiled, device):
    na, nb = len(ka), len(kb)
    if kernel_form("merge", interpret=interpret,
                   compiled=compiled) == XLA:
        pa, pb = _merge_ranks_xla(_as_dev(ka, device), _as_dev(kb, device))
        return (np.asarray(pa).astype(np.int64),
                np.asarray(pb).astype(np.int64))
    interpret = True if interpret is None else interpret
    ra = _rank(ka, kb, leq=False, block_rows=block_rows,
               interpret=interpret, device=device)
    rb = _rank(kb, ka, leq=True, block_rows=block_rows,
               interpret=interpret, device=device)
    return (np.arange(na, dtype=np.int64) + ra,
            np.arange(nb, dtype=np.int64) + rb)
