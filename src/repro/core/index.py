"""Block index construction — the TPU-native ParIS/MESSI index (DESIGN.md §4).

The pointer-based iSAX tree of the paper becomes a two-level flat structure:

  level 1: fixed-capacity *blocks* (= leaves), formed by sorting series by
           their bit-interleaved iSAX word (the breadth-first tree order) and
           cutting the sorted sequence every ``capacity`` series;
  level 2: per-block *envelopes* (= leaf iSAX summaries): segment-wise
           [min lo, max hi] over the member series' symbol regions.

Because the envelope contains every member's region, the envelope MINDIST is
<= every member's MINDIST <= the true distance: the no-false-dismissal
guarantee of the iSAX tree carries over unchanged (property-tested).

The raw series are physically permuted into block order so refinement reads
contiguous HBM, and the per-series bounds are stored planar (w on sublanes,
series on lanes) for the Pallas lower-bound kernel.

Everything here is jit-compatible so the distributed builder can run it
inside shard_map — that is the paper's "every worker builds its own subtrees
independently, no synchronization" property, obtained by construction.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax
from repro.kernels import ops

RAW_PAD = 1.0e4   # pad-series point value: squared distance >> any real one


class HostRawBlocks:
    """Host-side raw blocks of an index opened out-of-core (DESIGN.md §5).

    Wraps the (B, C, n) raw section of a persisted index — normally an
    ``np.memmap`` over the index file — so the streaming search
    (storage/ooc_search.py) can fetch one block at a time while only the
    summaries/envelopes live on device.  Rides in the ``BlockIndex``
    treedef as static metadata, so it uses default identity hash/eq: the
    contents never reach a trace, only ``fetch`` results do, as operands.
    """

    def __init__(self, blocks, path: str | None = None):
        self.blocks = blocks
        self.path = path

    @property
    def dtype(self) -> np.dtype:
        """On-disk dtype of the raw series (I/O accounting derives
        itemsize from this, not from an assumed float32)."""
        return np.dtype(self.blocks.dtype)

    @property
    def n_blocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def block_nbytes(self) -> int:
        """Bytes of one (C, n) raw block as stored on disk."""
        _, c, n = self.blocks.shape
        return c * n * self.dtype.itemsize

    def fetch(self, block_id: int) -> np.ndarray:
        """Read one (C, n) block into a fresh host array (the disk I/O).

        Called from the block cache's background reader thread
        (storage/cache.py) as well as the driver: read-only memmap
        slicing plus a fresh-array copy, so concurrent calls are safe.
        """
        return np.ascontiguousarray(self.blocks[block_id])


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["raw", "slo", "shi", "elo", "ehi", "ids"],
    meta_fields=["n", "w", "card", "capacity", "n_real", "host_raw"],
)
@dataclasses.dataclass
class BlockIndex:
    """The in-memory index (one shard of it, in the distributed setting)."""
    raw: jax.Array   # (B, C, n) f32   z-normed series, block order, padded
    slo: jax.Array   # (B, w, C) f32   per-series region lower bounds
    shi: jax.Array   # (B, w, C) f32   per-series region upper bounds
    elo: jax.Array   # (w, B)  f32     block envelope lower bounds (planar)
    ehi: jax.Array   # (w, B)  f32     block envelope upper bounds (planar)
    ids: jax.Array   # (B, C) int32    original series ids (-1 = padding)
    n: int           # series length
    w: int
    card: int
    capacity: int
    n_real: int      # number of non-padding series
    # Out-of-core hook: set by storage.open_index, which leaves ``raw`` as a
    # zero-width (B, 0, n) placeholder and keeps the real blocks on disk.
    # The device search paths refuse such an index (engine/frontier prepare);
    # storage.ooc_search streams blocks through HostRawBlocks.fetch instead.
    host_raw: HostRawBlocks | None = None

    @property
    def n_blocks(self) -> int:
        return self.raw.shape[0]

    @property
    def device_resident(self) -> bool:
        """True when the raw series are on device (the in-memory paths)."""
        return self.raw.shape[1] == self.capacity


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["raw", "lo", "hi", "ids"],
    meta_fields=["n", "w", "card", "n_real"],
)
@dataclasses.dataclass
class FlatIndex:
    """ParIS view: the SAX-array scan needs no blocks, just planar bounds."""
    raw: jax.Array   # (Np, n) f32
    lo: jax.Array    # (w, Np) f32
    hi: jax.Array    # (w, Np) f32
    ids: jax.Array   # (Np,) int32
    n: int
    w: int
    card: int
    n_real: int


def block_layout(n_series: int, capacity: int) -> tuple[int, int, int]:
    """-> (cap, n_blocks, n_padded): the one definition of how N series cut
    into fixed-capacity blocks.  Shared by ``assemble_blocks`` and the
    out-of-core build pipeline (storage/pipeline/driver.py), so the two
    builders cannot disagree on padding and stay byte-compatible."""
    cap = min(capacity, n_series)
    n_padded = n_series + (-n_series) % cap
    return cap, n_padded // cap, n_padded


@functools.partial(jax.jit,
                   static_argnames=("w", "card", "capacity", "normalize"))
def build(raw: jax.Array, *, w: int = isax.W, card: int = isax.CARD,
          capacity: int = 512, normalize: bool = True,
          ids: jax.Array | None = None) -> BlockIndex:
    """Build the block index from raw series (N, n).

    Jitted: one compiled program per shape, so its peak device memory is
    what the compiled build's ``memory_analysis()`` reports (the raw
    input stays alive; the caller owns it).  Also traceable inside
    shard_map (the distributed builder)."""
    n_series, n = raw.shape
    if ids is None:
        ids = jnp.arange(n_series, dtype=jnp.int32)

    xn = isax.znorm(raw) if normalize else raw.astype(jnp.float32)
    _, sax = ops.summarize(xn, w=w, card=card, normalize=False)
    bounds = isax.bounds_from_sax(sax, card)                  # (N, w, 2)

    order = isax.sort_order(sax, w)
    return assemble_blocks(xn[order], bounds[order], ids[order],
                           n=n, w=w, card=card, capacity=capacity)


ops.register_dispatch_cache(build)


def block_envelopes(slo, shi, ids_b, xp=jnp):
    """Per-block envelopes from per-series bounds. -> (elo, ehi), (w, B).

    slo/shi (B, w, C), ids_b (B, C).  pad members are identified by id < 0,
    NOT by sentinel values: a REAL series in the top (or bottom) symbol
    region legitimately carries a +/-SENTINEL edge, and excluding it would
    shrink the envelope below a member's region — a false-dismissal bug
    (caught by the hypothesis envelope-containment property).  Blocks that
    are pure padding get a sentinel envelope (never selected).

    ``xp`` is the array namespace: jnp for the jit-compatible builders
    here, np for the out-of-core builder (storage/ooc_build.py) — one
    definition of the envelope rules for both.
    """
    real = (ids_b >= 0)[:, None, :]                           # (B, 1, C)
    elo = xp.min(xp.where(real, slo, isax.SENTINEL), axis=2).T     # (w, B)
    ehi = xp.max(xp.where(real, shi, -isax.SENTINEL), axis=2).T    # (w, B)
    any_real = xp.any(ids_b >= 0, axis=1)                     # (B,)
    elo = xp.where(any_real[None, :], elo, isax.SENTINEL)
    ehi = xp.where(any_real[None, :], ehi, isax.SENTINEL)
    return elo, ehi


def assemble_blocks(xn: jax.Array, bounds: jax.Array, ids: jax.Array, *,
                    n: int, w: int, card: int, capacity: int) -> BlockIndex:
    """Cut iSAX-sorted series into fixed-capacity blocks (+ envelopes).

    Inputs are already in sorted (tree) order; this is the IndexConstruction
    stage shared by the one-shot and the incremental (ParIS+) builders.
    """
    n_series = xn.shape[0]
    cap, b, n_padded = block_layout(n_series, capacity)
    pad = n_padded - n_series
    if pad:
        xn = jnp.concatenate(
            [xn, jnp.full((pad, n), RAW_PAD, jnp.float32)], axis=0)
        bounds = jnp.concatenate(
            [bounds, jnp.full((pad, w, 2), isax.SENTINEL, jnp.float32)], axis=0)
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)], axis=0)

    raw_b = xn.reshape(b, cap, n)
    bounds_b = bounds.reshape(b, cap, w, 2)
    slo = jnp.transpose(bounds_b[..., 0], (0, 2, 1))          # (B, w, C)
    shi = jnp.transpose(bounds_b[..., 1], (0, 2, 1))
    elo, ehi = block_envelopes(slo, shi, ids.reshape(b, cap))

    return BlockIndex(raw=raw_b, slo=slo, shi=shi, elo=elo, ehi=ehi,
                      ids=ids.reshape(b, cap), n=n, w=w, card=card,
                      capacity=cap, n_real=n_series)


def flat_view(index: BlockIndex) -> FlatIndex:
    """Reinterpret the block index as a ParIS-style flat SAX array."""
    if not index.device_resident:
        raise ValueError("flat_view needs device-resident raw series; this "
                         "index was opened out-of-core (storage.open_index)")
    b, c, n = index.raw.shape
    w = index.w
    lo = jnp.transpose(index.slo, (1, 0, 2)).reshape(w, b * c)
    hi = jnp.transpose(index.shi, (1, 0, 2)).reshape(w, b * c)
    return FlatIndex(raw=index.raw.reshape(b * c, n), lo=lo, hi=hi,
                     ids=index.ids.reshape(b * c), n=index.n, w=w,
                     card=index.card, n_real=index.n_real)


def build_flat(raw: jax.Array, *, w: int = isax.W, card: int = isax.CARD,
               normalize: bool = True) -> FlatIndex:
    """Build only the ParIS flat SAX array (no sort, as in the paper)."""
    n_series, n = raw.shape
    xn = isax.znorm(raw) if normalize else raw.astype(jnp.float32)
    _, sax = ops.summarize(xn, w=w, card=card, normalize=False)
    bounds = isax.bounds_from_sax(sax, card)                  # (N, w, 2)
    return FlatIndex(raw=xn, lo=bounds[..., 0].T, hi=bounds[..., 1].T,
                     ids=jnp.arange(n_series, dtype=jnp.int32),
                     n=n, w=w, card=card, n_real=n_series)
