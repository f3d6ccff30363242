"""Public dispatch for the fused lookup-cascade kernel.

``CascadeState`` is the device-resident packed filter state built once
per tree shape by the engine's ``DeviceFilterRegistry`` (per-level key/
seq/bloom-word arrays pow2-padded and concatenated, the GLORAN disjoint
interval view likewise) — uploads happen at pack time, NOT per lookup.
``cascade_lookup`` pads the query stream to (rows x 128) tiles and runs
either the jit'd pure-XLA form of the math (the default, and the only
form on a TPU backend — see ``kernels.dispatch``) or, with
``compiled=False`` off the chip, the Pallas kernel in interpret mode.

Pack budget (``pack_budget``): the registry declines a pack past the
budget of the form that will run it and the device that will hold it,
and the per-level kernels serve that tree instead.  The Pallas form
holds its whole pack in VMEM, so it keeps the ``MAX_PACK_*`` limits.
The XLA form reads its operands from HBM: a pack may take a fixed share
of its home device's memory (``bytes_limit`` of ``memory_stats()``),
or ``HOST_PACK_BYTES`` where the device reports no limit (the CPU).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import span
from ..dispatch import PALLAS, XLA, kernel_form
from .kernel import LANES, cascade_pallas
from .ref import cascade_flat


def pack_bytes(key_slots: int, word_slots: int, area_slots: int) -> int:
    """Resident operand bytes of a pack: u32 keys+seqs, u32 words, and
    four u32 interval columns (one budget formula for gate + docs)."""
    return 8 * key_slots + 4 * word_slots + 16 * area_slots


# VMEM limits of the Pallas form, which holds a launch's whole pack in
# VMEM.
MAX_PACK_KEYS = 1 << 20  # u32 keys+seqs: 8 MB resident
MAX_PACK_WORDS = 1 << 20  # 4 MB of packed filter words
MAX_PACK_AREAS = 1 << 20  # 4 arrays x 4 B x 1 Mi = 16 MB / 4
# Joint ceiling on one launch's resident operand bytes: the per-
# dimension limits alone could admit ~28 MB combined, past the ~16 MB
# VMEM of most TPU generations; the registry declines any pack whose
# keys+seqs (8 B/slot) + words (4 B) + interval columns (16 B/area)
# exceed this, so the sum stays under VMEM with tile/output headroom.
MAX_PACK_BYTES = 12 << 20

# The XLA form's share of its home device's memory, per pack.  While a
# pack is rebuilt its pieces, its concatenation and the view it replaces
# are resident together (three copies), and up to four shards may share
# one device: twelve sixteenths, with the rest left to the kernels'
# working set and the program's other arrays.
HBM_PACK_SHARE = 1 / 16
# The XLA form's budget on a device that reports no memory limit.
HOST_PACK_BYTES = 1 << 30


@dataclass(frozen=True)
class PackBudget:
    """Largest admissible pack: slots of each operand and total bytes
    (by ``pack_bytes``)."""

    keys: int
    words: int
    areas: int
    bytes: int


def pack_budget(form: str, device=None) -> PackBudget:
    """The pack budget of a cascade that runs in ``form`` on ``device``
    (None: JAX's default device).

    The Pallas form keeps the VMEM limits.  The XLA form may take
    ``HBM_PACK_SHARE`` of the device's ``bytes_limit``, or
    ``HOST_PACK_BYTES`` where ``memory_stats()`` reports none; each
    operand alone may then fill the whole budget."""
    if form == PALLAS:
        return PackBudget(MAX_PACK_KEYS, MAX_PACK_WORDS, MAX_PACK_AREAS,
                          MAX_PACK_BYTES)
    dev = device if device is not None else jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    total = HOST_PACK_BYTES if not limit else int(limit * HBM_PACK_SHARE)
    return PackBudget(keys=total // 8, words=total // 4, areas=total // 16,
                      bytes=total)


@dataclass
class CascadeState:
    """Packed device arrays + static dims of one cascade-eligible tree.

    Built by the registry; every array is a ``jax.Array`` already on
    device, so a lookup uploads only its (rows x 128) query tiles."""

    lkeys: jax.Array      # (K,) u32 concat per-level keys (pow2-padded)
    lseqs: jax.Array      # (K,) u32 matching entry seqs
    key_off: jax.Array    # (L,) i32 segment offsets
    key_cnt: jax.Array    # (L,) i32 true (unpadded) level sizes
    words: jax.Array      # (W,) u32 concat bloom words (pow2-padded)
    word_off: jax.Array   # (L,) i32
    mbits: jax.Array      # (L,) u32 per-level filter bit counts
    seeds: jax.Array      # (L, H) u32 per-level hash seeds
    glo_lo: jax.Array     # (A,) u32 GLORAN disjoint view (clamped u32)
    glo_hi: jax.Array
    glo_smin: jax.Array
    glo_smax: jax.Array
    gl_off: jax.Array     # (G,) i32
    gl_cnt: jax.Array     # (G,) i32
    L: int
    H: int
    G: int
    steps_keys: int       # fixed binary-search depth (Pallas form)
    steps_gl: int
    key_pad: tuple        # static pow2 per-level padded sizes (XLA form)
    word_pad: tuple
    gl_pad: tuple
    # Host copies of key_cnt / gl_cnt, kept by the registry when it
    # packs, so a launch's span carries them without a device read.
    key_sizes: tuple = ()
    gl_sizes: tuple = ()

    @property
    def nbytes(self) -> int:
        """Resident operand bytes, by ``pack_bytes``."""
        return pack_bytes(sum(self.key_pad), sum(self.word_pad),
                          sum(self.gl_pad))


def device_key(device) -> str:
    """``platform:id`` of a home device, or ``"host"`` for None (the
    default device): the name spans and per-device counters use."""
    return "host" if device is None else f"{device.platform}:{device.id}"


_cascade_xla = jax.jit(cascade_flat, static_argnames=(
    "L", "H", "G", "key_pad", "word_pad", "gl_pad"))


def cascade_lookup(qkey32, qhash32, qseq32, qres, state: CascadeState, *,
                   block_rows: int = 8, interpret: bool | None = None,
                   compiled: bool | None = None, device=None):
    """One fused launch for a batch of point lookups.

    qkey32: (n,) uint32 exact keys (u32-gated by the caller); qhash32:
    (n,) uint32 ``fold64to32`` bloom inputs; qseq32/qres: (n,) seqs and
    resolved flags of entries already answered by the memtable stage.

    ``compiled=None`` runs the jit'd XLA form; ``compiled=False`` runs
    the Pallas kernel, which only interprets (off the chip) — on a TPU
    backend it raises (``kernels.dispatch``).  ``device`` commits the
    query tiles to one XLA device so the launch runs there (the state
    arrays are committed by the registry; committed operands pin
    placement) — per-shard device execution without a per-call transfer
    of the packed state.

    Returns numpy ``(maybe, hit, gl_cov, pos)``: (n, L) bool Bloom and
    exact-match verdicts per level, (n, G) bool GLORAN per-level
    coverage of (key, resolved seq), and (n, L) int64 level-local
    candidate positions.
    """
    with span("kernel.cascade", n=len(qkey32), levels=state.L,
              gl_levels=state.G, key_cnt=state.key_sizes,
              gl_cnt=state.gl_sizes, hashes=state.H,
              device=device_key(device)):
        return _cascade_lookup(qkey32, qhash32, qseq32, qres, state,
                               block_rows=block_rows, interpret=interpret,
                               compiled=compiled, device=device)


def _cascade_lookup(qkey32, qhash32, qseq32, qres, state, *,
                    block_rows, interpret, compiled, device):
    form = kernel_form("cascade", interpret=interpret, compiled=compiled)
    n = len(qkey32)
    tile = block_rows * LANES
    m = _next_pow2_mult(n, tile)
    qk = np.zeros(m, np.uint32)
    qh = np.zeros(m, np.uint32)
    qs = np.zeros(m, np.uint32)
    qr = np.zeros(m, np.int32)
    qk[:n] = qkey32
    qh[:n] = qhash32
    qs[:n] = qseq32
    qr[:n] = np.asarray(qres, bool)[:n]
    if device is not None:
        qk, qh, qs, qr = (jax.device_put(q, device)
                          for q in (qk, qh, qs, qr))
    st = state
    if form == XLA:
        bloom, hit, gl, pos = _cascade_xla(
            qk, qh, qs, qr, st.lkeys, st.lseqs, st.key_off, st.key_cnt,
            st.words, st.word_off, st.mbits, st.seeds, st.glo_lo,
            st.glo_hi, st.glo_smin, st.glo_smax, st.gl_off, st.gl_cnt,
            L=st.L, H=st.H, G=st.G, key_pad=st.key_pad,
            word_pad=st.word_pad, gl_pad=st.gl_pad)
        bloom = np.asarray(bloom)
        hit = np.asarray(hit)
        gl = np.asarray(gl)
        pos = np.asarray(pos).reshape(st.L, m)
    else:
        r = m // LANES
        one = jnp.zeros(1, jnp.int32)
        # Pallas rejects zero-length block operands; with G=0 the gl
        # stage is compiled out, so placeholders are never read.
        gl_off = st.gl_off if st.G else one
        gl_cnt = st.gl_cnt if st.G else one
        bloom, hit, gl, pos = cascade_pallas(
            qk.reshape(r, LANES), qh.reshape(r, LANES),
            qs.reshape(r, LANES), qr.reshape(r, LANES),
            st.lkeys, st.lseqs, st.key_off, st.key_cnt, st.words,
            st.word_off, st.mbits, st.seeds, st.glo_lo, st.glo_hi,
            st.glo_smin, st.glo_smax, gl_off, gl_cnt,
            L=st.L, H=st.H, G=st.G, steps_keys=st.steps_keys,
            steps_gl=st.steps_gl, block_rows=block_rows,
            interpret=True if interpret is None else interpret)
        bloom = np.asarray(bloom).reshape(-1)
        hit = np.asarray(hit).reshape(-1)
        gl = np.asarray(gl).reshape(-1)
        pos = np.asarray(pos).reshape(st.L, m)
    lbits = np.arange(st.L, dtype=np.int32)
    maybe = ((bloom[:n, None] >> lbits) & 1).astype(bool)
    hitm = ((hit[:n, None] >> lbits) & 1).astype(bool)
    if st.G:
        gbits = np.arange(st.G, dtype=np.int32)
        gl_cov = ((gl[:n, None] >> gbits) & 1).astype(bool)
    else:
        gl_cov = np.zeros((n, 0), bool)
    return maybe, hitm, gl_cov, pos[:, :n].T.astype(np.int64)


def _next_pow2_mult(n: int, tile: int) -> int:
    """Smallest pow2 multiple of ``tile`` >= n (bounds distinct compiled
    query shapes to O(log max-batch))."""
    m = tile
    while m < n:
        m <<= 1
    return m
