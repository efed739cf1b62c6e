"""Shared top-k frontier: the k-NN generalization of the BSF (DESIGN.md §4a).

ParIS+ and MESSI answer exact k-NN queries: every worker maintains a
k-element best-so-far priority structure and prunes against the k-th best
distance.  This module is that structure, TPU-native: a fixed-size,
per-query, always-sorted (distance, id) table that lives inside jit'd
loops as a plain pytree.  All four search paths (MESSI query-major /
block-major, ParIS flat scan, UCR brute force) and the distributed
two-round protocol carry a ``Frontier`` instead of a scalar BSF.

Invariants (property-tested in tests/test_topk.py):
  * rows are sorted ascending by (distance, id) — ties break toward the
    smaller id, matching a ``jax.lax.top_k`` brute-force oracle over an
    id-ordered distance matrix;
  * ids are unique per row; empty slots are (INF, -1);
  * ``threshold()`` (the k-th best distance) only ever decreases under
    ``insert``/``merge``, so pruning with ``lb >= threshold()`` keeps the
    no-false-dismissal guarantee for every k: a candidate can only be
    skipped once k strictly better answers are already held.

``QuerySetup`` owns the query-side preparation that used to be
copy-pasted across the search paths: z-normalization, PAA, the stage-A
approximate seeding (best-envelope block refinement) and the work-stats
initialization.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import isax
from repro.kernels import ops

INF = jnp.float32(jnp.finfo(jnp.float32).max)
_PAD_ID_KEY = jnp.int32(jnp.iinfo(jnp.int32).max)   # sort key for id < 0


class SearchStats(NamedTuple):
    """Work counters, per query — the quantities behind the paper's Fig. 9/12."""
    blocks_visited: jax.Array    # envelopes that survived pruning & were refined
    series_refined: jax.Array    # real-distance computations performed
    lb_series: jax.Array         # per-series lower bounds computed
    iters: jax.Array             # while_loop trips (scalar, shared)


def stats_init(qn: int) -> SearchStats:
    # three separate zeros buffers, NOT one shared array: the counters
    # ride inside engine.PreparedSearch, which engine.run donates —
    # aliased leaves would be the same buffer donated twice
    return SearchStats(blocks_visited=jnp.zeros((qn,), jnp.int32),
                       series_refined=jnp.zeros((qn,), jnp.int32),
                       lb_series=jnp.zeros((qn,), jnp.int32),
                       iters=jnp.zeros((), jnp.int32))


class Frontier(NamedTuple):
    """Per-query top-k result set. dists/ids (Q, K), ascending by (dist, id)."""
    dists: jax.Array   # (Q, K) f32 squared (or any monotone) distances
    ids: jax.Array     # (Q, K) int32 original series ids; -1 = empty slot

    @property
    def k(self) -> int:
        return self.dists.shape[-1]

    def threshold(self) -> jax.Array:
        """(Q,) k-th best distance — the pruning bound. INF until full."""
        return self.dists[..., -1]

    def insert(self, d: jax.Array, ids: jax.Array) -> "Frontier":
        return insert_batch(self, d, ids)

    def insert_topk(self, d: jax.Array, ids: jax.Array) -> "Frontier":
        return insert_topk(self, d, ids)

    def merge(self, other: "Frontier") -> "Frontier":
        return merge(self, other)


def init(qn: int, k: int) -> Frontier:
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    return Frontier(dists=jnp.full((qn, k), INF, jnp.float32),
                    ids=jnp.full((qn, k), -1, jnp.int32))


def _topk_by_dist_id(d: jax.Array, ids: jax.Array, k: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Ascending (distance, id)-lexicographic top-k along the last axis.

    The id tiebreak makes the result deterministic and equal to
    ``lax.top_k`` over an id-ordered distance row; ids < 0 sort last
    among equal distances.
    """
    key_id = jnp.where(ids >= 0, ids, _PAD_ID_KEY)
    order = jnp.lexsort((key_id, d), axis=-1)[..., :k]
    return (jnp.take_along_axis(d, order, axis=-1),
            jnp.take_along_axis(ids, order, axis=-1))


def insert_batch(f: Frontier, d: jax.Array, ids: jax.Array, *,
                 assume_unique: bool = False) -> Frontier:
    """Fold a batch of candidates (Q, M) into the frontier. Pure, O(K+M) sort.

    Candidates with id < 0 are ignored.  A candidate whose id is already
    held (re-visits: the stage-A block re-scanned by the main loop) keeps
    one slot, at the MIN of both distances — recomputing the same pair
    under a different gather shape can differ in the last ulps, and the
    scalar-BSF code took the min, so this preserves its k=1 output
    exactly.  Within one batch ids must be distinct — true for every
    caller, since blocks/chunks/shards partition the series.

    ``assume_unique=True`` skips the O(Q*M*K) duplicate mask for callers
    whose candidates provably cannot collide with held ids (the UCR scan:
    globally unique ids, each seen once; the shard merge: disjoint
    shards into an empty frontier).
    """
    d = jnp.where(ids >= 0, d.astype(jnp.float32), INF)
    if not assume_unique:
        same = (ids[..., :, None] == f.ids[..., None, :]) \
            & (ids[..., :, None] >= 0)                       # (Q, M, K)
        held = jnp.min(jnp.where(same, d[..., :, None], INF), axis=-2)
        f = f._replace(dists=jnp.minimum(f.dists, held))
        d = jnp.where(jnp.any(same, axis=-1), INF, d)
    all_d = jnp.concatenate([f.dists, d], axis=-1)
    all_i = jnp.concatenate([f.ids, ids], axis=-1)
    nd, ni = _topk_by_dist_id(all_d, all_i, f.k)
    return Frontier(dists=nd, ids=jnp.where(nd < INF, ni, -1))


def insert_topk(f: Frontier, d: jax.Array, ids: jax.Array) -> Frontier:
    """Fold PRE-SELECTED candidates (Q, k'), k' <= K, into the frontier.

    The fast path behind ``ops.block_topk`` / ``ops.fused_panel_topk``:
    the kernel already reduced the (Q, C) panel to its (dist, id)-lex
    top-k, so the merge sorts K + k' <= 2K elements instead of K + C.

    Exactness: inserting only the (dist, id)-lex top-k of a batch (ids
    distinct within the batch) is bit-identical to inserting the whole
    batch.  Any unselected candidate has >= k candidates strictly
    (dist, id)-before it in the SAME batch, each of which lands in the
    result or loses only to something even better — so the unselected
    candidate could never reach the table; and its duplicate-min side
    effect on a held id is dominated the same way (the held entry it
    would lower is itself lex-before it).  Hence every block-major site
    keeps PR-4/PR-5 golden parity by construction.
    """
    if d.shape[-1] > f.k:
        raise ValueError(
            f"insert_topk expects pre-selected candidates: got "
            f"{d.shape[-1]} > k={f.k}; use insert_batch for full panels")
    return insert_batch(f, d, ids)


def merge(fa: Frontier, fb: Frontier) -> Frontier:
    """Merge two frontiers (e.g. per-shard results) into one top-k."""
    return insert_batch(fa, fb.dists, fb.ids)


def result_dists(f: Frontier) -> jax.Array:
    """(Q, K) sqrt'd distances for a SearchResult; empty slots stay INF."""
    return jnp.where(f.ids >= 0, jnp.sqrt(f.dists), INF)


def bound(f: Frontier, initial_threshold: jax.Array | None = None
          ) -> jax.Array:
    """(Q,) pruning bound: k-th best so far, tightened by a seeded
    threshold (the distributed protocol's round-1 global reduce)."""
    t = f.threshold()
    if initial_threshold is not None:
        t = jnp.minimum(t, initial_threshold)
    return t


def all_gather_merge(f: Frontier, axis_names) -> Frontier:
    """Inside shard_map: merge every shard's frontier into the global top-k.

    One (D, Q, K) all-gather + one local sort per shard — communication
    independent of dataset size (the round-2 exchange of DESIGN.md §6).
    """
    gd = jax.lax.all_gather(f.dists, axis_names)   # (D, Q, K)
    gi = jax.lax.all_gather(f.ids, axis_names)
    qn, k = f.dists.shape
    return insert_batch(init(qn, k),
                        jnp.moveaxis(gd, 0, 1).reshape(qn, -1),
                        jnp.moveaxis(gi, 0, 1).reshape(qn, -1),
                        assume_unique=True)        # shards are disjoint


def query_block_l2(q: jax.Array, blocks: jax.Array) -> jax.Array:
    """Per-query distances to its own gathered block(s).

    q (Q, n); blocks (Q, ..., C, n) -> (Q, ..., C) squared distances, using
    the same expanded form as the MXU kernel (einsum keeps it fused).
    """
    qq = jnp.sum(q * q, axis=-1)                              # (Q,)
    xx = jnp.sum(blocks * blocks, axis=-1)                    # (Q, ..., C)
    # HIGHEST for the same reason as kernels/batch_l2.py: a single bf16
    # MXU pass would err by ~1e-1, enough to reorder near neighbours
    cross = jnp.einsum("qn,q...n->q...", q, blocks,
                       precision=jax.lax.Precision.HIGHEST)
    extra = xx.ndim - 1
    qq = qq.reshape(qq.shape + (1,) * extra)
    return jnp.maximum(qq + xx - 2.0 * cross, 0.0)


def approximate(index, q: jax.Array, q_paa: jax.Array, k: int = 1
                ) -> tuple[Frontier, jax.Array]:
    """Stage A: seed a frontier from each query's best-envelope block.

    Returns (frontier, block_lb (Q, B)).  One lower-bound kernel pass over
    all block envelopes, then one batched L2 against the argmin block —
    the paper's "search the tree for the query's leaf, compute real
    distances in it, store the minimum in the BSF", generalized to k.
    """
    block_lb = ops.lb_scan_planar(q_paa, index.elo, index.ehi, n=index.n)
    b0 = jnp.argmin(block_lb, axis=1)                         # (Q,)
    blocks = index.raw[b0]                                    # (Q, C, n)
    d = query_block_l2(q, blocks)                             # (Q, C)
    f = init(q.shape[0], k).insert(d, index.ids[b0])
    return f, block_lb


class QuerySetup(NamedTuple):
    """Shared query-side prep for every search path."""
    q: jax.Array                 # (Q, n) prepared (z-normed / cast) queries
    q_paa: jax.Array | None      # (Q, w) PAA, when an index is involved
    frontier: Frontier           # stage-A-seeded (or empty) top-k frontier
    block_lb: jax.Array | None   # (Q, B) stage-A envelope lower bounds
    stats: SearchStats


def prepare(queries: jax.Array, k: int, *, index=None, w: int | None = None,
            normalize: bool = True) -> QuerySetup:
    """z-norm/PAA + stage-A seeding + stats init.

    ``index``: a BlockIndex enables stage-A approximate seeding.  ``w``:
    compute PAA without an index (ParIS flat scan without a block view).
    """
    q = (isax.znorm(queries) if normalize else queries).astype(jnp.float32)
    qn = q.shape[0]
    q_paa = block_lb = None
    if index is not None and not index.device_resident:
        raise ValueError(
            "index raw series are not device-resident (opened out-of-core "
            "via storage.open_index); use repro.storage.ooc_search, or "
            "storage.load_index for the in-memory paths")
    if index is not None:
        q_paa = isax.paa(q, index.w)
        front, block_lb = approximate(index, q, q_paa, k)
    else:
        if w is not None:
            q_paa = isax.paa(q, w)
        front = init(qn, k)
    return QuerySetup(q=q, q_paa=q_paa, frontier=front, block_lb=block_lb,
                      stats=stats_init(qn))
