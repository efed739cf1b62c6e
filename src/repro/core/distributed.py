"""Multi-device / multi-pod index build and query answering (DESIGN.md §6).

The paper's worker threads become mesh devices.  Every device is symmetric
(as every core is in the paper): the dataset is range-sharded over ALL mesh
axes flattened, each device builds its own BlockIndex shard completely
independently (the paper's "workers process distinct subtrees ... no need for
synchronization"), and query answering is the two-round shared-frontier
protocol (the k-NN generalization of the paper's shared BSF), wrapped
around an arbitrary ``engine.QueryPlan`` — any metric, either ordered
schedule, either backend:

  round 1: every shard seeds its approximate top-k frontier (stage A) ->
           pmin all-reduce of the k-th-best distance (one scalar per
           query).  The min over shards of the local k-th best upper
           bounds the GLOBAL k-th-NN distance (any one shard already
           holds k candidates at least that good), so it is a valid
           shared pruning threshold for every shard.
  round 2: every shard runs the exact ordered-pruning search seeded with
           that global threshold (so pruning is as tight as the paper's
           shared-memory BSF reads), producing its local top-k frontier;
           an all-gather + frontier merge (core/frontier.py) then yields
           the identical global top-k on every shard.

``search_sharded`` runs the protocol inside one shard_map over
device-resident shards; ``search_sharded_ooc`` runs the SAME two rounds
at the host level over out-of-core shards (one ``storage.SearchSession``
per shard — the paper's multi-node on-disk deployment), with the pmin
becoming an np.minimum reduce between the stage-A pass and the walks.

Total communication per query batch: one (Q,) scalar all-reduce + one
(Q, K) frontier all-gather — independent of dataset size, which is what
makes this design runnable at 1000+ nodes.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.index as index_lib
from repro.core import engine
from repro.core import frontier as frontier_lib
from repro.core.frontier import Frontier
from repro.core.index import BlockIndex
from repro.core.search import SearchResult, SearchStats


def _all_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def index_pspecs(mesh: Mesh, like: BlockIndex | None = None,
                 **meta: Any) -> BlockIndex:
    """PartitionSpecs for each BlockIndex field (shard over all axes).

    shard_map spec pytrees must carry the same static metadata as the real
    index, so pass either ``like`` (an existing index) or explicit meta.
    """
    ax = _all_axes(mesh)
    if like is not None:
        meta = dict(n=like.n, w=like.w, card=like.card,
                    capacity=like.capacity, n_real=like.n_real)
    return BlockIndex(
        raw=P(ax), slo=P(ax), shi=P(ax),
        elo=P(None, ax), ehi=P(None, ax), ids=P(ax), **meta)


def build_sharded(raw: jax.Array, mesh: Mesh, *, w: int = 16, card: int = 256,
                  capacity: int = 512, normalize: bool = True) -> BlockIndex:
    """Build one index shard per device from globally-sharded raw data.

    raw (N, n) with N divisible by the device count.  Each shard's series
    keep their GLOBAL ids so query answers are mesh-shape-independent.
    """
    ax = _all_axes(mesh)
    n_series, n = raw.shape
    n_dev = mesh.size
    if n_series % n_dev:
        raise ValueError(f"N={n_series} must divide device count {n_dev}")
    shard_n = n_series // n_dev
    cap = min(capacity, shard_n)
    ids = jnp.arange(n_series, dtype=jnp.int32)

    def _build(local_raw, local_ids):
        return index_lib.build(local_raw, w=w, card=card, capacity=capacity,
                               normalize=normalize, ids=local_ids)

    out_specs = index_pspecs(mesh, n=n, w=w, card=card, capacity=cap,
                             n_real=shard_n)
    # check_vma off: the Pallas summarize kernel's outputs carry no
    # varying-axes annotation, which the check would reject on the chip
    fn = jax.shard_map(_build, mesh=mesh, in_specs=(P(ax), P(ax)),
                       out_specs=out_specs, check_vma=False)
    return fn(raw, ids)


def _merge_shards(res, ax) -> tuple[jax.Array, jax.Array]:
    """All-gather per-shard (Q, K) results and merge into the global top-k.

    Merging happens in the sqrt-distance domain (monotone, so the
    (dist, id) order is unchanged); empty local slots carry id -1 and are
    dropped by the frontier insert.
    """
    f_g = frontier_lib.all_gather_merge(Frontier(res.dist, res.idx), ax)
    return f_g.dists, f_g.ids


def search_sharded(sharded_index: BlockIndex, queries: jax.Array, mesh: Mesh,
                   *, k: int = 1, blocks_per_iter: int = 4,
                   lb_filter: bool = True,
                   deadline_blocks: int | None = None,
                   schedule: str = "block_major",
                   metric=None) -> SearchResult:
    """Exact global k-NN over all shards. queries (Q, n) replicated.

    The two-round protocol wrapped around an ``engine.QueryPlan``:
    ``schedule`` picks "block_major" (optimized batched schedule, the
    production default) or "query_major" (the paper-faithful priority-
    queue order, kept as the measured baseline); ``metric`` overrides
    the metric axis (default z-normed ``ED`` — pass ``engine.Cosine()``
    for a sharded vector index built with ``normalize=False``).
    """
    ax = _all_axes(mesh)
    specs = index_pspecs(mesh, like=sharded_index)
    m = engine.ED(lb_filter=lb_filter) if metric is None else metric
    plan = engine.QueryPlan(metric=m, schedule=schedule, k=k,
                            blocks_per_iter=blocks_per_iter,
                            deadline_blocks=deadline_blocks)

    def _search(local_index, q):
        # round 1: local approximate top-k -> global k-th-best all-reduce
        prep = engine.prepare(m, local_index, q, k)
        thr_g = jax.lax.pmin(prep.front.threshold(), ax)
        # round 2: resume from the round-1 prepared state, seeded with
        # the global threshold — query prep, block ranking, and stage A
        # are reused, not recomputed (previously this leaned on XLA CSE
        # to dedup the second engine.prepare inside the shard_map trace)
        res = engine.run(local_index, q, plan, initial_threshold=thr_g,
                         prepared=prep)
        # merge: all-gather the (Q, K) shard frontiers -> global top-k
        dist_g, idx_g = _merge_shards(res, ax)
        stats = SearchStats(
            blocks_visited=jax.lax.psum(res.stats.blocks_visited, ax),
            series_refined=jax.lax.psum(res.stats.series_refined, ax),
            lb_series=jax.lax.psum(res.stats.lb_series, ax),
            iters=jax.lax.pmax(res.stats.iters, ax),
        )
        return SearchResult(dist=dist_g, idx=idx_g, stats=stats)

    out = SearchResult(
        dist=P(None), idx=P(None),
        stats=SearchStats(blocks_visited=P(None), series_refined=P(None),
                          lb_series=P(None), iters=P()))
    fn = jax.shard_map(_search, mesh=mesh, in_specs=(specs, P(None)),
                       out_specs=out, check_vma=False)
    return fn(sharded_index, queries)


def search_sharded_ooc(sessions: Sequence, queries: jax.Array, *,
                       k: int = 1, lb_filter: bool = True,
                       normalize_queries: bool = True, metric=None,
                       pipeline_depth: int | None = None,
                       group_blocks: int | None = None):
    """Distributed OUT-OF-CORE exact k-NN: the same two-round protocol,
    host-level, over per-shard ``storage.SearchSession``s.

    Each session wraps one shard's on-disk index (disjoint series,
    global ids — e.g. built per shard with ``core.build(..., ids=...)``
    and persisted).  Round 1 runs stage A on every shard (fetching only
    best-envelope blocks) and min-reduces the k-th-best thresholds;
    round 2 RESUMES each shard from its round-1 prepared state
    (``storage.PreparedRound``), seeded with the global bound: the
    cached block-major walk skips query prep, block ranking, and every
    stage-A block — no block is fetched or refined twice per protocol
    run — while pruning as tightly as the shared-memory BSF would
    allow; finally the per-shard frontiers merge into the global top-k.

    Returns an ``OocSearchResult`` whose stats/io are summed over
    shards; round 1's stage-A disk reads are billed into each shard's
    round-2 IOStats (the prepared state carries them), so
    ``io.blocks_fetched`` is the protocol's FULL disk cost, directly
    comparable to running the shards blind.  -> global exact top-k,
    identical to a single out-of-core search over the union of the
    shards.  (``stats.iters`` stays 0: the cached walk does not count
    while_loop trips.)

    ``pipeline_depth``/``group_blocks`` forward to every shard's stage-A
    chain and round-2 walk (``engine.run_cached``'s pipeline knobs;
    None = each session's own default).  Answers are bit-identical at
    every setting — only speculative I/O and sync cadence change.
    """
    import numpy as np

    from repro.storage.ooc_search import IOStats, OocSearchResult

    if not sessions:
        raise ValueError("search_sharded_ooc needs at least one session")
    kw = dict(k=k, lb_filter=lb_filter, normalize_queries=normalize_queries,
              metric=metric, pipeline_depth=pipeline_depth,
              group_blocks=group_blocks)
    # round 1: per-shard stage-A prepared states -> host pmin of thresholds
    preps = [s.approximate_threshold(queries, **kw) for s in sessions]
    thr_g = jnp.asarray(np.minimum.reduce([p.threshold for p in preps]))
    # round 2: per-shard walks resumed from round 1, seeded with the bound
    results = [s.search(queries, initial_threshold=thr_g, prepared=p, **kw)
               for s, p in zip(sessions, preps)]
    # merge: per-shard frontiers (sqrt domain, disjoint ids) -> global top-k
    front = Frontier(results[0].dist, results[0].idx)
    for r in results[1:]:
        front = frontier_lib.merge(front, Frontier(r.dist, r.idx))
    stats = SearchStats(
        blocks_visited=functools.reduce(
            jnp.add, [r.stats.blocks_visited for r in results]),
        series_refined=functools.reduce(
            jnp.add, [r.stats.series_refined for r in results]),
        lb_series=functools.reduce(
            jnp.add, [r.stats.lb_series for r in results]),
        iters=functools.reduce(
            jnp.maximum, [r.stats.iters for r in results]),
    )
    io = IOStats(
        bytes_read=sum(r.io.bytes_read for r in results),
        bytes_scan=sum(r.io.bytes_scan for r in results),
        blocks_fetched=sum(r.io.blocks_fetched for r in results),
        blocks_total=sum(r.io.blocks_total for r in results),
        cache_hits=sum(r.io.cache_hits for r in results),
        blocks_refined=sum(r.io.blocks_refined for r in results),
    )
    return OocSearchResult(dist=front.dists, idx=front.ids,
                           stats=stats, io=io)


def search_sharded_scan(raw: jax.Array, queries: jax.Array, mesh: Mesh,
                        *, k: int = 1, chunk: int = 4096) -> SearchResult:
    """Distributed UCR-Suite-p brute force (baseline + oracle), same merge."""
    from repro.core import ucr
    ax = _all_axes(mesh)
    n_series = raw.shape[0]
    ids = jnp.arange(n_series, dtype=jnp.int32)

    def _scan(local_raw, local_ids, q):
        res = ucr.search_scan(local_raw, q, k=k,
                              chunk=min(chunk, local_raw.shape[0]),
                              ids=local_ids)
        return _merge_shards(res, ax)

    fn = jax.shard_map(_scan, mesh=mesh, in_specs=(P(ax), P(ax), P(None)),
                       out_specs=(P(None), P(None)), check_vma=False)
    dist, idx = fn(raw, ids, queries)
    qn = queries.shape[0]
    stats = SearchStats(
        blocks_visited=jnp.zeros((qn,), jnp.int32),
        series_refined=jnp.full((qn,), n_series, jnp.int32),
        lb_series=jnp.zeros((qn,), jnp.int32),
        iters=jnp.zeros((), jnp.int32))
    return SearchResult(dist=dist, idx=idx, stats=stats)
