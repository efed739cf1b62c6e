"""MESSI-style exact k-NN query answering (DESIGN.md §4).

Both schedules now live in `core/engine.py` — this module is the
Euclidean face of the engine, kept as the stable public API.  Paper
mapping (details in the engine docstrings):

  Stage A  "search the tree for the query's leaf, compute real distances
           in it, store the minimum in BSF"       -> `engine.prepare`
           (best-envelope block argmin + one batched L2 against it).
  Stage C  "surviving leaves go into priority queues ordered by lower
           bound; workers pop, stop a queue when its head's LB >= BSF"
                                                  -> the `query_major`
           schedule (per-query LB-argsorted blocks + lax.while_loop);
           `block_major` is the beyond-paper batched order (each block
           once, suffix-min stopping table — see EXPERIMENTS.md §Perf).
  k-NN BSF "the BSF array holds the k best-so-far answers; pruning uses
           the k-th best distance"                -> the shared top-k
           Frontier (core/frontier.py): pruning against
           ``frontier.threshold()`` can never discard a true k-NN
           member (no false dismissals, any k).
  per-series lower-bound filtering inside a leaf  -> ED(lb_filter=True)
           masks refinement to series whose own MINDIST < threshold
           (the stats expose the paper's "MESSI performs fewer real
           distance calculations" claim).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
from jax.profiler import TraceAnnotation

from repro.core import engine
from repro.core.engine import ED, QueryPlan
from repro.core.frontier import Frontier, INF, SearchStats  # re-exported
from repro.core.index import BlockIndex


class SearchResult(NamedTuple):
    dist: jax.Array              # (Q, K) exact k-NN distances, ascending
    idx: jax.Array               # (Q, K) original ids; -1 = fewer than K real
    stats: SearchStats

    @property
    def nn_dist(self) -> jax.Array:
        """(Q,) nearest-neighbour distance (the k=1 column)."""
        return self.dist[..., 0]

    @property
    def nn_idx(self) -> jax.Array:
        """(Q,) nearest-neighbour id (the k=1 column)."""
        return self.idx[..., 0]


def refine_panel(q: jax.Array, q_paa: jax.Array, front: Frontier,
                 stats: SearchStats, block: jax.Array, ids_b: jax.Array,
                 lo: jax.Array | None, hi: jax.Array | None,
                 active: jax.Array, thr: jax.Array, *, n: int, w: int,
                 lb_filter: bool) -> tuple[Frontier, SearchStats]:
    """Back-compat shim: the ED specialization of ``engine.panel_refine``."""
    qs = engine.QueryState(q=q, aux=(q_paa,))
    return engine.panel_refine(ED(lb_filter=lb_filter), qs, front, stats,
                               block, ids_b, lo, hi, active, thr, n=n, w=w)


def search(index: BlockIndex, queries: jax.Array, *, k: int = 1,
           blocks_per_iter: int = 4, lb_filter: bool = True,
           initial_threshold: jax.Array | None = None,
           deadline_blocks: int | None = None,
           normalize_queries: bool = True) -> SearchResult:
    """Exact k-NN for a batch of queries (Q, n) against one index shard.

    ``initial_threshold`` tightens the pruning bound (squared distance) —
    the distributed path passes the globally-reduced k-th-best approximate
    distance here (paper's shared-BSF variable); it never appears in the
    result, which always holds this shard's own top-k.
    ``deadline_blocks`` caps refined blocks per query (straggler mitigation /
    anytime answers; None = exact).
    ``normalize_queries=False`` is the generic-vector path (core/vector.py):
    the index was built with normalize=False and queries arrive prepared.
    The call to ``engine.run`` (the host's enqueue of the program) is the
    profiler span ``engine.dispatch``.
    """
    plan = QueryPlan(metric=ED(normalize=normalize_queries,
                               lb_filter=lb_filter),
                     schedule="query_major", k=k,
                     blocks_per_iter=blocks_per_iter,
                     deadline_blocks=deadline_blocks)
    with TraceAnnotation("engine.dispatch"):
        return engine.run(index, queries, plan, initial_threshold)


def search_block_major(index: BlockIndex, queries: jax.Array, *, k: int = 1,
                       lb_filter: bool = True,
                       initial_threshold: jax.Array | None = None,
                       deadline_blocks: int | None = None,
                       normalize_queries: bool = True) -> SearchResult:
    """Exact k-NN with the BLOCK-major schedule (beyond-paper optimization).

    Blocks are visited ONCE each, in ascending min-over-queries lower-bound
    order; every visit is one contiguous ``dynamic_slice`` plus one (Q, C)
    MXU panel against all still-active queries, with the suffix-min table
    supplying the exact per-query stopping rule (measured rationale in
    EXPERIMENTS.md §Perf; schedule internals in core/engine.py).
    """
    plan = QueryPlan(metric=ED(normalize=normalize_queries,
                               lb_filter=lb_filter),
                     schedule="block_major", k=k,
                     deadline_blocks=deadline_blocks)
    return engine.run(index, queries, plan, initial_threshold)
