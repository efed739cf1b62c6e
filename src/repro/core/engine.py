"""One query engine: metric x schedule x backend (DESIGN.md §4).

ParIS/ParIS+ (on-disk) and MESSI (in-memory) are one algorithmic
skeleton — rank candidates by a lower bound, seed a best-so-far top-k,
refine survivors under the tightening k-th-best bound — specialized to
where the raw data lives and how workers coordinate.  This module is
that skeleton, written once, with each axis pluggable:

  * **metric** — what "distance" and "lower bound" mean.  A ``Metric``
    supplies query preparation, the block-envelope lower bound, the
    per-series lower bound and the exact distance; concrete adapters:
    ``ED`` (z-normed Euclidean, the paper's core), ``DTW(r)`` (Sakoe-
    Chiba band, the paper's §V extension over the UNCHANGED index) and
    ``Cosine`` (unit-norm embeddings, the paper's §V vector claim).
  * **schedule** — the traversal order and stopping rule.
    ``query_major`` (paper-faithful per-query priority order),
    ``block_major`` (each block once, min-over-queries order with a
    suffix-min stopping table) and ``flat`` (the ParIS whole-SAX-array
    scan with chunked refinement).
  * **backend** — where raw series live.  Device-resident indexes run
    fully jitted (``run`` / ``run_flat``); indexes opened out-of-core
    run the same block-major walk at the host level, every fetch and
    speculative prefetch driven through a callback into a
    ``storage.BlockCache`` (``run_cached``, used by
    ``storage.SearchSession``).

The public drivers (``core.search``, ``core.dtw``, ``core.vector``,
``core.paris``, ``storage.SearchSession``) are thin wrappers that
construct plans; the distributed two-round protocol
(``core.distributed``) wraps ANY plan, with round 1's work captured in
a resumable ``PreparedSearch`` (``prepare`` / ``run_cached_stage_a``)
that round 2 (``run`` / ``run_cached``) resumes instead of recomputing.  Every ``Metric.distances``
call lives in this module: the two pruned refine loops
(``panel_refine``, shared by both block-major backends, and the
query-major refine inside ``_query_major``) ask the metric for their
whole LB + distance + select step (``panel_topk``, ``gathered_topk``),
which ED runs as one DESIGN.md §8 fused kernel each; stage-A seeding
(``prepare`` / ``_cached_stage_a``) and the flat chunk refine
(``run_flat``) also call it and need the same swap to fuse end to end.

Exactness: a schedule only skips work whose metric lower bound is >= the
frontier's k-th-best distance, and every metric's bounds satisfy
``block_lb <= series_lb <= distance``, so no true k-NN member is ever
dismissed — for any metric, schedule, backend, or k.
"""
# repro: sync-trace — every device->host transfer in this module must
# carry a '# sync' (deliberate) or '# host' (host-data, no transfer)
# annotation; `python -m repro.analysis` enforces it (DESIGN.md §10)
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import frontier as frontier_lib
from repro.core import isax
from repro.core.frontier import Frontier, INF, SearchStats, query_block_l2
from repro.core.index import BlockIndex, FlatIndex, RAW_PAD
from repro.kernels import ops, ref

_bound = frontier_lib.bound

SCHEDULES = ("query_major", "block_major", "flat")


class QueryState(NamedTuple):
    """Metric-prepared queries: ``q`` plus metric-owned aux arrays
    (ED/Cosine: the PAA; DTW: the Keogh envelope and its PAA)."""
    q: jax.Array
    aux: tuple


# ---------------------------------------------------------------------------
# metric adapters
# ---------------------------------------------------------------------------

def prep_vectors(v: jax.Array, unit_norm: bool = True) -> jax.Array:
    """Embedding preparation for the Cosine metric (was core/vector.py).

    Unit-normalization makes Euclidean top-k == cosine top-k; the
    sqrt(d) rescale keeps per-dim values ~N(0,1)-sized so the iSAX
    breakpoints (standard-normal quantiles) stay discriminative.  A
    global scale preserves the NN ordering exactly.
    """
    v = v.astype(jnp.float32)
    if unit_norm:
        v = v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-8)
        v = v * jnp.sqrt(jnp.float32(v.shape[-1]))
    return v


def query_envelope(q: jax.Array, r: int) -> tuple[jax.Array, jax.Array]:
    """Keogh envelope: U_i = max(q[i-r:i+r+1]), L_i = min(...). q (..., n)."""
    n = q.shape[-1]
    pads = [(0, 0)] * (q.ndim - 1) + [(r, r)]
    qu = jnp.pad(q, pads, constant_values=-jnp.inf)
    ql = jnp.pad(q, pads, constant_values=jnp.inf)
    iu = jnp.arange(n)[:, None] + jnp.arange(2 * r + 1)[None, :]
    u = jnp.max(qu[..., iu], axis=-1)
    l = jnp.min(ql[..., iu], axis=-1)
    return u, l


def lb_keogh(q_env: tuple[jax.Array, jax.Array], x: jax.Array) -> jax.Array:
    """LB_Keogh(Q, x)^2 for raw candidates. u,l (Q, n); x (N, n) -> (Q, N)."""
    u, l = q_env
    above = jnp.maximum(x[None] - u[:, None], 0.0)
    below = jnp.maximum(l[:, None] - x[None], 0.0)
    d = above + below   # at most one of the two is nonzero per element
    return jnp.sum(d * d, axis=-1)


def interval_planar_lb(u_paa: jax.Array, l_paa: jax.Array, lo: jax.Array,
                       hi: jax.Array, *, n: int) -> jax.Array:
    """Squared MINDIST of interval [l_paa, u_paa] to regions [lo, hi].

    Per segment: max(0, lo - u, l - hi) — zero when they overlap —
    which lower-bounds LB_Keogh_PAA and hence DTW against any series in
    the region.  Implemented with the existing planar kernel by
    querying u against (lo, +S) and l against (-S, hi) and summing the
    pieces.  lo/hi (w, M): M may be blocks (envelopes) or individual
    series (the flat schedule).
    """
    big = isax.SENTINEL
    w, m = lo.shape
    above = ops.lb_scan_planar(u_paa, lo,
                               jnp.full((w, m), big, jnp.float32), n=n)
    below = ops.lb_scan_planar(l_paa, jnp.full((w, m), -big, jnp.float32),
                               hi, n=n)
    return above + below


def dtw_band(a: jax.Array, b: jax.Array, r: int) -> jax.Array:
    """Exact squared-DTW with band r. a (..., n) vs b (..., n), broadcast.

    The anti-diagonal DP now lives in ``kernels/ref.py`` (it is the
    oracle for the Pallas wavefront kernel); this stays the generic
    arbitrary-rank entry point.  Panel-shaped refine callers go through
    ``ops.dtw_panel``, which dispatches to the kernel by mode.
    """
    return ref.dtw_band_ref(a, b, r)


@dataclasses.dataclass(frozen=True)
class ED:
    """Z-normalized Euclidean distance — the paper's core metric.

    ``lb_filter`` toggles the per-series MINDIST filter inside a
    surviving block (the paper's "MESSI performs fewer real distance
    calculations" mechanism); ``normalize=False`` is the prepared-vector
    path (queries arrive already cast/scaled).
    """
    normalize: bool = True
    lb_filter: bool = True

    @property
    def filters(self) -> bool:
        return self.lb_filter

    # per-series filtering reads the stored iSAX region bounds
    needs_bounds = True

    def prep_queries(self, queries: jax.Array, *, w: int) -> QueryState:
        q = (isax.znorm(queries) if self.normalize
             else queries).astype(jnp.float32)
        return QueryState(q=q, aux=(isax.paa(q, w),))

    def block_lb(self, qs: QueryState, lo: jax.Array, hi: jax.Array, *,
                 n: int) -> jax.Array:
        """MINDIST of each query to planar (w, M) region bounds -> (Q, M).

        M may be blocks (envelopes) or individual series (the flat
        schedule) — the bound is the same formula either way.
        """
        return ops.lb_scan_planar(qs.aux[0], lo, hi, n=n)

    def distances(self, qs: QueryState, block: jax.Array) -> jax.Array:
        if block.ndim == 2:            # shared (C, n) panel: one MXU pass
            return ops.batch_l2(qs.q, block)
        return query_block_l2(qs.q, block)   # per-query gather (Q, ..., C, n)

    def panel_topk(self, qs: QueryState, block: jax.Array, ids_b: jax.Array,
                   lo, hi, active: jax.Array, thr: jax.Array, k: int, *,
                   n: int, w: int) -> tuple[jax.Array, jax.Array, jax.Array]:
        """LB-filter + distance + (dist, id)-lex top-k over one (C, n)
        panel -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,)).

        With the MINDIST filter on, the whole pipeline is ONE fused
        kernel (``ops.fused_panel_topk``); the per-query ``active`` mask
        folds into the threshold as -inf (``lb < -inf`` is never true)."""
        if self.lb_filter:
            return ops.fused_panel_topk(
                qs.q, qs.aux[0], block, lo, hi, ids_b,
                jnp.where(active, thr, -jnp.inf), k=k, n=n)
        live = active[:, None] & (ids_b >= 0)[None, :]
        d = jnp.where(live, self.distances(qs, block), INF)
        sd, si = ops.block_topk(d, jnp.where(live, ids_b[None, :], -1), k)
        return sd, si, jnp.sum(live, axis=1, dtype=jnp.int32)

    def gathered_topk(self, qs: QueryState, index: BlockIndex,
                      idxs: jax.Array, active: jax.Array, thr: jax.Array,
                      front: Frontier
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """The query-major refine: each query against its own (Q, K)
        blocks ``idxs`` -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,)),
        the candidates for ``front``.

        With the MINDIST filter on, ONE kernel reads each active block
        in place (``ops.gathered_panel_topk``), selecting only within
        the frontier's k-th; without it, the gather."""
        if self.lb_filter:
            return ops.gathered_panel_topk(
                qs.q, qs.aux[0], index.raw, index.slo, index.shi, index.ids,
                idxs, active, thr, front.threshold(), k=front.k, n=index.n)
        return gathered_refine(self, qs, index, idxs, active, thr, front.k)

    def finalize_stats(self, stats: SearchStats, capacity: int
                       ) -> SearchStats:
        """Counter semantics are already right for ED: ``series_refined``
        counts filter survivors (the panel is masked before insert)."""
        return stats


@dataclasses.dataclass(frozen=True)
class Cosine(ED):
    """Cosine similarity over embeddings, served as Euclidean top-k.

    ``prep_vectors`` maps both corpus (at build) and queries (here) onto
    the sqrt(d)-scaled unit sphere, where d^2 = dim * (2 - 2 cos) is
    monotone in cosine — so the exact ED frontier IS the exact cosine
    top-k, descending (``vector.cosine_scores`` inverts the map).
    """
    normalize: bool = False     # never z-norm embeddings
    unit_norm: bool = True

    def prep_queries(self, queries: jax.Array, *, w: int) -> QueryState:
        q = prep_vectors(queries, self.unit_norm)
        return QueryState(q=q, aux=(isax.paa(q, w),))


@dataclasses.dataclass(frozen=True)
class DTW:
    """Sakoe-Chiba-band DTW over the UNCHANGED Euclidean index (paper §V).

    The block lower bound widens the query to its Keogh envelope and
    takes the interval-to-region MINDIST, which lower-bounds
    LB_Keogh_PAA and hence DTW — no-false-dismissal carries over.  The
    per-series filter is LB_Keogh on the raw values (tighter than PAA);
    it reads the fetched block itself, so it needs no stored bounds.
    """
    r: int

    filters = True
    needs_bounds = False

    def prep_queries(self, queries: jax.Array, *, w: int) -> QueryState:
        q = isax.znorm(queries).astype(jnp.float32)
        u, l = query_envelope(q, self.r)
        return QueryState(q=q, aux=(u, l, isax.paa(u, w), isax.paa(l, w)))

    def block_lb(self, qs: QueryState, lo: jax.Array, hi: jax.Array, *,
                 n: int) -> jax.Array:
        """Interval [l_paa, u_paa] to region [lo, hi] MINDIST -> (Q, M)."""
        return interval_planar_lb(qs.aux[2], qs.aux[3], lo, hi, n=n)

    def series_lb(self, qs: QueryState, block: jax.Array, lo, hi, *,
                  n: int, w: int) -> jax.Array:
        u, l = qs.aux[0], qs.aux[1]
        if block.ndim == 2:                               # panel (C, n)
            return lb_keogh((u, l), block)                # (Q, C)
        above = jnp.maximum(block - u[:, None, None, :], 0.0)
        below = jnp.maximum(l[:, None, None, :] - block, 0.0)
        dd = above + below
        return jnp.sum(dd * dd, axis=-1)                  # (Q, K, C)

    def distances(self, qs: QueryState, block: jax.Array) -> jax.Array:
        if block.ndim <= 3:            # (C, n) panel or (Q, C, n) stage A
            return ops.dtw_panel(qs.q, block, r=self.r)
        qn, kb, c, n = block.shape                              # (Q,K,C,n)
        return ops.dtw_panel(qs.q, block.reshape(qn, kb * c, n),
                             r=self.r).reshape(qn, kb, c)

    def panel_topk(self, qs: QueryState, block: jax.Array, ids_b: jax.Array,
                   lo, hi, active: jax.Array, thr: jax.Array, k: int, *,
                   n: int, w: int) -> tuple[jax.Array, jax.Array, jax.Array]:
        """LB_Keogh filter + banded-DP panel + top-k select.  The filter
        reads the raw block (no stored bounds), so the LB stays a
        separate pass; the select is still the block_topk kernel."""
        s_lb = self.series_lb(qs, block, lo, hi, n=n, w=w)      # (Q, C)
        live = (s_lb < thr[:, None]) & active[:, None] & (ids_b >= 0)[None, :]
        d = jnp.where(live, self.distances(qs, block), INF)
        sd, si = ops.block_topk(d, jnp.where(live, ids_b[None, :], -1), k)
        return sd, si, jnp.sum(live, axis=1, dtype=jnp.int32)

    def gathered_topk(self, qs: QueryState, index: BlockIndex,
                      idxs: jax.Array, active: jax.Array, thr: jax.Array,
                      front: Frontier
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """The query-major refine by gathering: LB_Keogh and the banded
        DP both read the raw values (``ops.dtw_panel``)."""
        return gathered_refine(self, qs, index, idxs, active, thr, front.k)

    def finalize_stats(self, stats: SearchStats, capacity: int
                       ) -> SearchStats:
        """DTW's historical convention, now uniform across backends:
        every visited block costs a full panel of LB_Keogh bounds AND a
        full panel of banded-DP distances (the DP runs for all
        candidates, then masks), so ``series_refined == lb_series ==
        blocks_visited * capacity`` — the filter-survivor count the
        generic refine accumulated would claim pruning savings the DP
        never realizes."""
        v = stats.blocks_visited
        return SearchStats(blocks_visited=v, series_refined=v * capacity,
                           lb_series=v * capacity,
                           iters=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# prepared round-1 state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PreparedSearch:
    """Round-1 state as a first-class resumable object (DESIGN.md §6).

    Everything the paper's approximate phase produces — metric-prepared
    queries, the block lower-bound matrix, the stage-A-seeded frontier,
    and the work stats accrued so far — plus, on the cached backend, the
    ids of the blocks stage A already fetched and refined.  Produced by
    ``prepare`` (device) / ``run_cached_stage_a`` (cached); accepted by
    ``run`` / ``run_cached`` so the two-round distributed protocol's
    second round skips query prep, block ranking, and every
    already-refined block instead of recomputing round 1.

    The frontier is a strictly-tighter seed, not a different answer:
    resuming from it is bit-identical to re-running round 1 under the
    seeded bound (candidates the global bound would have masked all have
    ``lb >= threshold`` and so can never displace a reported slot).

    Registered as a pytree with ``refined`` static, so it threads
    through jitted device code (``run`` donates it — round 2 reuses the
    round-1 frontier buffers instead of holding both alive).
    """
    qs: QueryState
    front: Frontier
    block_lb: jax.Array            # (Q, B) metric block lower bounds
    stats: SearchStats             # work already accrued (stage A)
    refined: frozenset = frozenset()   # block ids stage A refined (cached)

    @property
    def k(self) -> int:
        return self.front.k


jax.tree_util.register_dataclass(
    PreparedSearch,
    data_fields=("qs", "front", "block_lb", "stats"),
    meta_fields=("refined",))


def _check_prepared(prepared: PreparedSearch, plan: QueryPlan,
                    n_blocks: int, qn: int) -> None:
    if prepared.k != plan.k:
        raise ValueError(f"prepared state holds a k={prepared.k} frontier "
                         f"but the plan asks k={plan.k}; round 2 must reuse "
                         "the round-1 plan")
    if prepared.block_lb.shape[-1] != n_blocks:
        raise ValueError(
            f"prepared block_lb ranks {prepared.block_lb.shape[-1]} blocks "
            f"but this index has {n_blocks}; the prepared state belongs to "
            "a different index")
    if prepared.block_lb.shape[0] != qn:
        raise ValueError(
            f"prepared state was built for {prepared.block_lb.shape[0]} "
            f"queries but {qn} were passed; round 2 must reuse the round-1 "
            "query batch (only the shape is checkable here — binding the "
            "CONTENT is the caller's job, as storage.SearchSession does "
            "via its query fingerprint)")


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """One cell of the metric x schedule matrix, plus its tuning knobs.

    Hashable (static under jit): the pruning-threshold seed — a traced
    (Q,) array in the distributed protocol — is an argument of
    ``run``/``run_flat``/``run_cached``, never part of the plan.  The
    backend axis is picked by which runner the plan is handed to.
    """
    metric: object = ED()
    schedule: str = "block_major"
    k: int = 1
    blocks_per_iter: int = 4        # query_major refine width
    deadline_blocks: int | None = None   # anytime cap; None = exact
    chunk: int = 4096               # flat-schedule refinement chunk

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(f"schedule must be one of {SCHEDULES}, "
                             f"got {self.schedule!r}")
        if self.deadline_blocks is not None and self.deadline_blocks < 1:
            # fail at plan construction, not deep inside a walk: every
            # backend clamps the deadline against n_blocks, and a <= 0
            # deadline would silently clamp to an empty walk — an
            # approximate answer the caller never asked for
            raise ValueError(
                f"deadline_blocks must be >= 1 (or None for an exact "
                f"search), got {self.deadline_blocks}")


def _require_device_resident(index: BlockIndex) -> None:
    if not index.device_resident:
        raise ValueError(
            "index raw series are not device-resident (opened out-of-core "
            "via storage.open_index); use engine.run_cached through a "
            "storage.SearchSession (or storage.ooc_search), or "
            "storage.load_index for the in-memory backends")


def prepare(metric, index: BlockIndex, queries: jax.Array, k: int
            ) -> PreparedSearch:
    """Metric prep + block ranking + stage-A seeding (device backend).

    The paper's approximate phase, metric-generic: one block-LB kernel
    pass ranks every envelope, then each query's best block is refined
    exactly and seeds the top-k frontier.  Returns a ``PreparedSearch``
    the distributed protocol threads into ``run`` as round-2 state
    (``refined`` stays empty: the device walk keeps revisiting stage-A
    blocks — a resident panel costs no I/O, and the frontier insert
    dedups by id — so skipping them would change last-ulp min-of-both
    distances and break bit-stability with the non-protocol paths).
    """
    _require_device_resident(index)
    qs = metric.prep_queries(queries, w=index.w)
    qn = qs.q.shape[0]
    block_lb = metric.block_lb(qs, index.elo, index.ehi, n=index.n)
    b0 = jnp.argmin(block_lb, axis=1)                         # (Q,)
    ids0 = index.ids[b0]                                      # (Q, C)
    d0 = metric.distances(qs, index.raw[b0])                  # (Q, C)
    # pad lanes (id < 0) hold RAW_PAD series with FINITE huge distances —
    # mask to INF before the select (block_topk's masking contract)
    sd, si = ops.block_topk(jnp.where(ids0 >= 0, d0, INF), ids0, k)
    front = frontier_lib.init(qn, k).insert_topk(sd, si)
    return PreparedSearch(qs=qs, front=front, block_lb=block_lb,
                          stats=frontier_lib.stats_init(qn))


def panel_refine(metric, qs: QueryState, front: Frontier, stats: SearchStats,
                 block: jax.Array, ids_b: jax.Array,
                 lo: jax.Array | None, hi: jax.Array | None,
                 active: jax.Array, thr: jax.Array, *,
                 n: int, w: int) -> tuple[Frontier, SearchStats]:
    """Refine one (C, n) raw block panel against every query at once.

    The per-block unit of work shared by the block-major schedule on
    both backends (device while_loop and the cached host walk): the
    metric's ``panel_topk`` pipeline — per-series lower-bound filtering,
    distances, and the (dist, id)-lex top-k select, fused into one
    kernel where the metric allows — then an ``insert_topk`` merge
    (2k-wide, not K + C) and the work-stat updates.  ``active`` (Q,)
    masks queries whose block lower bound beat ``thr``; ``lo``/``hi``
    are the block's (w, C) per-series bounds (None when the metric
    filters off the raw values, or not at all).
    """
    c = block.shape[0]
    sd, si, nlive = metric.panel_topk(qs, block, ids_b, lo, hi, active,
                                      thr, front.k, n=n, w=w)
    front = front.insert_topk(sd, si)
    stats = SearchStats(
        blocks_visited=stats.blocks_visited + active.astype(jnp.int32),
        series_refined=stats.series_refined + nlive,
        lb_series=stats.lb_series
        + (active.astype(jnp.int32) * c if metric.filters else 0),
        iters=stats.iters,
    )
    return front, stats


# ---------------------------------------------------------------------------
# device backend: the two ordered schedules + the flat scan
# ---------------------------------------------------------------------------

def gathered_refine(metric, qs: QueryState, index: BlockIndex,
                    idxs: jax.Array, active: jax.Array, thr: jax.Array,
                    k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Query-major refine by gathering each query's K blocks into a
    (Q, K, C, n) copy: the path of metrics whose filter reads the raw
    values (DTW) or that do not filter (ED without its LB filter)."""
    qn = qs.q.shape[0]
    blocks = index.raw[idxs]                                    # (Q,K,C,n)
    ids = index.ids[idxs]                                       # (Q,K,C)
    if metric.filters:
        s_lb = metric.series_lb(qs, blocks, None, None,
                                n=index.n, w=index.w)           # (Q,K,C)
        s_act = (s_lb < thr[:, None, None]) & active[..., None]
    else:
        s_act = jnp.broadcast_to(active[..., None], ids.shape)
    d = metric.distances(qs, blocks)                            # (Q,K,C)
    live = s_act & (ids >= 0)
    # blocks partition the series and idxs rows are distinct, so ids are
    # unique per row: block_topk's subset-exactness holds
    sd, si = ops.block_topk(jnp.where(live, d, INF).reshape(qn, -1),
                            jnp.where(live, ids, -1).reshape(qn, -1), k)
    return sd, si, jnp.sum(live, axis=(1, 2), dtype=jnp.int32)


def _query_major(metric, index: BlockIndex, qs: QueryState, front: Frontier,
                 block_lb: jax.Array, stats0: SearchStats, *,
                 blocks_per_iter: int, deadline_blocks: int | None,
                 initial_threshold) -> tuple[Frontier, SearchStats]:
    """Paper-faithful order: each query refines ITS next-best blocks.

    Per-query LB-argsorted schedule + lax.while_loop refining the next
    ``blocks_per_iter`` blocks per trip; exits when every query's next
    block LB >= its pruning bound.  Ordered traversal + that stopping
    rule ARE the paper's priority-queue semantics; the heap itself is an
    artifact of MIMD threads.  Each trip's refine is the metric's
    ``gathered_topk``: for ED one kernel that reads every active block
    in place (``ops.gathered_panel_topk``), else ``gathered_refine``.
    """
    b, c, _ = index.raw.shape
    kb = min(blocks_per_iter, b)

    order = jnp.argsort(block_lb, axis=1)                     # (Q, B)
    max_ptr = b if deadline_blocks is None else min(b, deadline_blocks)

    def next_lb(ptr):
        # Invariant: ``cond`` evaluates this even when ptr >= max_ptr —
        # jnp.logical_and does not short-circuit — so after the final body
        # trip ptr can reach up to b + kb - 1.  The clamp keeps the slice
        # start in-bounds explicitly (the clamped value is discarded:
        # ptr < max_ptr is already False) instead of leaning on
        # dynamic_slice's implicit start clamping.
        safe = jnp.minimum(ptr, b - 1)
        nxt = jax.lax.dynamic_slice_in_dim(order, safe, 1, axis=1)  # (Q,1)
        return jnp.take_along_axis(block_lb, nxt, axis=1)[:, 0]     # (Q,)

    def cond(state):
        ptr, f, _ = state
        return jnp.logical_and(ptr < max_ptr,
                               jnp.any(next_lb(ptr)
                                       < _bound(f, initial_threshold)))

    def body(state):
        ptr, f, st = state
        thr = _bound(f, initial_threshold)
        idxs = jax.lax.dynamic_slice_in_dim(order, ptr, kb, axis=1)  # (Q,K)
        lbs = jnp.take_along_axis(block_lb, idxs, axis=1)            # (Q,K)
        active = lbs < thr[:, None]                                  # (Q,K)

        def refine(carry):
            f_i, st_i = carry
            sd, si, nlive = metric.gathered_topk(qs, index, idxs, active,
                                                 thr, f_i)
            f_n = f_i.insert_topk(sd, si)
            nact = jnp.sum(active, axis=1, dtype=jnp.int32)
            st_n = SearchStats(
                blocks_visited=st_i.blocks_visited + nact,
                series_refined=st_i.series_refined + nlive,
                lb_series=st_i.lb_series
                + (nact * c if metric.filters else 0),
                iters=st_i.iters,
            )
            return f_n, st_n

        f_n, st_n = jax.lax.cond(
            jnp.any(active), refine, lambda cr: cr, (f, st))
        st_n = st_n._replace(iters=st_n.iters + 1)
        return ptr + kb, f_n, st_n

    ptr0 = jnp.zeros((), jnp.int32)
    _, front, stats = jax.lax.while_loop(cond, body, (ptr0, front, stats0))
    return front, stats


def block_major_schedule(block_lb, xp=jnp):
    """Shared block-major schedule: visit order + suffix-min stop table.

    Blocks ascend by min-over-queries lower bound; the suffix min over
    the scheduled LB matrix gives the exact stopping rule (when
    suffix[ptr, q] >= threshold[q] nothing later can improve q's top-k).
    ``xp`` is jnp on the device backend, np on the cached host walk —
    one definition of the schedule for both.
    """
    if xp is jnp:
        order = xp.argsort(xp.min(block_lb, axis=0))          # (B,)
        sched_lb = block_lb[:, order]                         # (Q, B)
        suffix = jax.lax.cummin(sched_lb[:, ::-1], axis=1)[:, ::-1]
    else:
        order = np.argsort(block_lb.min(axis=0), kind="stable")
        sched_lb = block_lb[:, order]
        suffix = np.minimum.accumulate(sched_lb[:, ::-1], axis=1)[:, ::-1]
    return order, sched_lb, suffix


def _block_major(metric, index: BlockIndex, qs: QueryState, front: Frontier,
                 block_lb: jax.Array, stats0: SearchStats, *,
                 deadline_blocks: int | None, initial_threshold
                 ) -> tuple[Frontier, SearchStats]:
    """Beyond-paper batched order: every block visited at most once.

    Each visit is one contiguous ``dynamic_slice`` (no gather) plus one
    (Q, C) panel against all still-active queries; the suffix-min table
    supplies the same no-false-dismissal stopping rule (see EXPERIMENTS.md
    §Perf for why this wins on batch hardware).
    """
    b, c, n = index.raw.shape

    order, _, suffix = block_major_schedule(block_lb)
    max_ptr = b if deadline_blocks is None else min(b, deadline_blocks)

    def cond(state):
        ptr, f, _ = state
        # same invariant as ``next_lb`` in the query-major schedule:
        # logical_and does not short-circuit, so this slice is evaluated
        # at ptr == max_ptr after the final trip — clamp explicitly (the
        # value is discarded)
        safe = jnp.minimum(ptr, b - 1)
        live = jax.lax.dynamic_slice_in_dim(suffix, safe, 1, axis=1)[:, 0]
        return jnp.logical_and(ptr < max_ptr,
                               jnp.any(live < _bound(f, initial_threshold)))

    def body(state):
        ptr, f, st = state
        thr = _bound(f, initial_threshold)
        b_id = order[ptr]
        lbs = jax.lax.dynamic_slice_in_dim(block_lb, b_id, 1, axis=1)[:, 0]
        active = lbs < thr                                    # (Q,)

        def refine(cr):
            f_i, st_i = cr
            block = jax.lax.dynamic_index_in_dim(index.raw, b_id, 0,
                                                 keepdims=False)   # (C, n)
            ids_b = jax.lax.dynamic_index_in_dim(index.ids, b_id, 0,
                                                 keepdims=False)   # (C,)
            lo = hi = None
            if metric.filters and metric.needs_bounds:
                lo = jax.lax.dynamic_index_in_dim(index.slo, b_id, 0,
                                                  keepdims=False)  # (w, C)
                hi = jax.lax.dynamic_index_in_dim(index.shi, b_id, 0,
                                                  keepdims=False)
            return panel_refine(metric, qs, f_i, st_i, block, ids_b, lo, hi,
                                active, thr, n=n, w=index.w)

        f_n, st_n = jax.lax.cond(
            jnp.any(active), refine, lambda cr: cr, (f, st))
        st_n = st_n._replace(iters=st_n.iters + 1)
        return ptr + 1, f_n, st_n

    ptr0 = jnp.zeros((), jnp.int32)
    _, front, stats = jax.lax.while_loop(cond, body, (ptr0, front, stats0))
    return front, stats


@functools.partial(jax.jit, static_argnames=("plan",),
                   donate_argnames=("prepared",))
def run(index: BlockIndex, queries: jax.Array, plan: QueryPlan,
        initial_threshold: jax.Array | None = None,
        prepared: PreparedSearch | None = None):
    """Execute a plan against a device-resident index. -> SearchResult.

    ``initial_threshold`` tightens the pruning bound (squared distance)
    — the distributed protocol passes the globally-reduced k-th-best
    here (the paper's shared-BSF variable); it never appears in the
    result, which always holds this index's own top-k.

    ``prepared`` resumes from a round-1 ``PreparedSearch`` (same metric,
    index, queries, and k — ``prepare`` produces it) instead of paying
    for query prep, block ranking, and stage A again; it is donated, so
    the caller must treat it as consumed.
    """
    from repro.core.search import SearchResult   # thin wrapper layer
    if plan.schedule == "flat":
        raise ValueError("the flat schedule scans a FlatIndex — use "
                         "engine.run_flat (or paris.search_flat)")
    if prepared is None:
        prepared = prepare(plan.metric, index, queries, plan.k)
    else:
        _check_prepared(prepared, plan, index.n_blocks, queries.shape[0])
    qs, front, block_lb, stats0 = (prepared.qs, prepared.front,
                                   prepared.block_lb, prepared.stats)
    if plan.schedule == "query_major":
        front, stats = _query_major(
            plan.metric, index, qs, front, block_lb, stats0,
            blocks_per_iter=plan.blocks_per_iter,
            deadline_blocks=plan.deadline_blocks,
            initial_threshold=initial_threshold)
    else:
        front, stats = _block_major(
            plan.metric, index, qs, front, block_lb, stats0,
            deadline_blocks=plan.deadline_blocks,
            initial_threshold=initial_threshold)
    stats = plan.metric.finalize_stats(stats, index.capacity)
    return SearchResult(dist=frontier_lib.result_dists(front),
                        idx=front.ids, stats=stats)


@functools.partial(jax.jit, static_argnames=("plan",))
def run_flat(index: FlatIndex, queries: jax.Array, plan: QueryPlan,
             block_index: BlockIndex | None = None,
             initial_threshold: jax.Array | None = None):
    """The ParIS schedule: one planar LB pass over EVERY series, then
    chunked candidate refinement with the running frontier.

    ``block_index`` (optional) enables stage-A seeding from the block
    view; without it the scan starts from an empty frontier (the first
    chunk is then refined in full, which seeds it).  Metric-generic: the
    per-series planar bound is the same ``Metric.block_lb`` formula
    evaluated on per-series (not per-block) region bounds.

    ``plan.deadline_blocks`` (anytime, in CHUNK units — the flat
    schedule's block analogue) caps the number of chunks refined: the LB
    pass still covers every series, but once the cap is hit later
    chunks' candidates are skipped, exactly like a deadline-cut
    block-major walk defers its unvisited blocks.
    """
    from repro.core.search import SearchResult
    metric = plan.metric
    npad, n = index.raw.shape
    if block_index is not None:
        prep = prepare(metric, block_index, queries, plan.k)
        qs, front = prep.qs, prep.front
    else:
        qs = metric.prep_queries(queries, w=index.w)
        front = frontier_lib.init(qs.q.shape[0], plan.k)
    q = qs.q
    qn = q.shape[0]
    c = min(plan.chunk, npad)
    pad = (-npad) % c

    lo, hi, raw, ids = index.lo, index.hi, index.raw, index.ids
    if pad:
        lo = jnp.concatenate([lo, jnp.full((index.w, pad), isax.SENTINEL)], 1)
        hi = jnp.concatenate([hi, jnp.full((index.w, pad), isax.SENTINEL)], 1)
        raw = jnp.concatenate(
            [raw, jnp.full((pad, n), RAW_PAD, jnp.float32)], 0)
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)], 0)

    # phase 2 — the flat LB scan over the ENTIRE SAX array (one kernel pass)
    lb = metric.block_lb(qs, lo, hi, n=n)                     # (Q, Np+pad)

    # phase 3 — chunked candidate refinement with the running frontier
    nchunks = raw.shape[0] // c
    raw_c = raw.reshape(nchunks, c, n)
    ids_c = ids.reshape(nchunks, c)
    lb_c = lb.reshape(qn, nchunks, c)

    deadline = plan.deadline_blocks      # static: None leaves the exact
                                         # scan's traced graph unchanged

    def step(carry, inp):
        front, refined, nref = carry
        raw_k, ids_k, lb_k = inp                              # (C,n),(C,),(Q,C)
        thr = _bound(front, initial_threshold)
        act = (lb_k < thr[:, None]) & (ids_k[None, :] >= 0)
        do = jnp.any(act)
        if deadline is not None:
            do = jnp.logical_and(do, nref < deadline)

        def refine(cr):
            front_j, refined_j = cr
            d = jnp.where(act, metric.distances(qs, raw_k), INF)  # (Q, C)
            sd, si = ops.block_topk(d, jnp.where(act, ids_k[None, :], -1),
                                    front_j.k)
            front_n = front_j.insert_topk(sd, si)
            return (front_n,
                    refined_j + jnp.sum(act, axis=1, dtype=jnp.int32))

        front, refined = jax.lax.cond(do, refine, lambda cr: cr,
                                      (front, refined))
        return (front, refined, nref + do.astype(jnp.int32)), None

    (front, refined, _), _ = jax.lax.scan(
        step, (front, jnp.zeros((qn,), jnp.int32), jnp.zeros((), jnp.int32)),
        (raw_c, ids_c, jnp.moveaxis(lb_c, 1, 0)))

    stats = SearchStats(
        blocks_visited=jnp.full((qn,), nchunks, jnp.int32),
        series_refined=refined,
        lb_series=jnp.full((qn,), index.n_real, jnp.int32),   # whole array
        iters=jnp.asarray(nchunks, jnp.int32),
    )
    return SearchResult(dist=frontier_lib.result_dists(front),
                        idx=front.ids, stats=stats)


# ---------------------------------------------------------------------------
# cached backend: the same block-major walk, host-driven through callbacks
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "n", "w"))
def _cached_refine_step(metric, qs, front, stats, block, ids_b, lo, hi, lbs,
                        initial_threshold, *, n: int, w: int):
    """One fetched block against all queries — the device side of the walk."""
    thr = _bound(front, initial_threshold)
    active = lbs < thr
    return panel_refine(metric, qs, front, stats, block, ids_b, lo, hi,
                        active, thr, n=n, w=w)


@functools.partial(jax.jit, static_argnames=("metric", "n", "w"))
def _cached_refine_group(metric, qs, front, stats, blocks, ids_g, lo_g, hi_g,
                         lbs_g, initial_threshold, *, n: int, w: int):
    """G stacked blocks against all queries in ONE dispatch.

    ``blocks`` is the (G, C, n) stack of a group of consecutive surviving
    schedule slots; a ``lax.scan`` runs the same per-block body as
    ``_cached_refine_step`` over the group with the frontier as carry, so
    every block's active mask is computed against the threshold AFTER all
    earlier blocks in the group — exactly the threshold the serial walk
    would have shown it.  The host only picked the group under a stale
    (one-group-old) threshold; staleness can admit a block whose queries
    are all dead by its turn, and such a block contributes nothing: its
    active mask is all-False, so the frontier insert and every stat
    counter are no-ops.  Hence dist/idx AND stats are bit-identical to
    dispatching the group one block at a time.

    ``lo_g``/``hi_g`` are (G, w, C) stacked per-series bounds or None
    (metrics that filter off raw values, or not at all) — the None case
    traces a separate program, mirroring the single-block step.  One
    compile per distinct group length; partial final groups reuse the
    single-block step when they shrink to one block.
    """
    def body(carry, xs):
        f, st = carry
        if lo_g is None:
            block, ids_b, lbs = xs
            lo = hi = None
        else:
            block, ids_b, lo, hi, lbs = xs
        thr = _bound(f, initial_threshold)
        active = lbs < thr
        return panel_refine(metric, qs, f, st, block, ids_b, lo, hi,
                            active, thr, n=n, w=w), None
    xs = ((blocks, ids_g, lbs_g) if lo_g is None
          else (blocks, ids_g, lo_g, hi_g, lbs_g))
    (front, stats), _ = jax.lax.scan(body, (front, stats), xs)
    return front, stats


def cached_setup(index: BlockIndex, queries: jax.Array, plan: QueryPlan
                 ) -> PreparedSearch:
    """Query prep + block ranking for an index whose raw lives off-device.

    Only summaries/envelopes are touched (they are device-resident on an
    opened index); the frontier starts EMPTY — stage A needs raw blocks,
    which the walk fetches through its callback.
    """
    metric = plan.metric
    qs = metric.prep_queries(queries, w=index.w)
    qn = qs.q.shape[0]
    block_lb = metric.block_lb(qs, index.elo, index.ehi, n=index.n)
    return PreparedSearch(qs=qs, front=frontier_lib.init(qn, plan.k),
                          block_lb=block_lb,
                          stats=frontier_lib.stats_init(qn))


def _check_pipeline_knobs(pipeline_depth: int, group_blocks: int) -> None:
    if pipeline_depth < 1 or group_blocks < 1:
        raise ValueError(
            f"pipeline_depth and group_blocks must be >= 1 (1, 1 is the "
            f"serial walk), got ({pipeline_depth}, {group_blocks})")


class _GroupDispatcher:
    """Host side of the pipelined refine: stack a group, dispatch once.

    Shared by stage A and the walk.  A one-block group goes through
    ``_cached_refine_step`` — byte-for-byte today's serial dispatch, so
    (D=1, G=1) walks reuse the existing jit cache and stay bit-identical
    including stats; larger groups stack to (G, C, n) and run the
    ``lax.scan`` group kernel in a single dispatch (one host->device
    round trip, one threshold sync for the whole group).
    """

    def __init__(self, index: BlockIndex, plan: QueryPlan, block_lb,
                 fetch, initial_threshold):
        self.index = index
        self.metric = plan.metric
        self.block_lb = block_lb                 # (Q, B) device
        self.fetch = fetch
        self.thr0 = initial_threshold
        self.needs = plan.metric.filters and plan.metric.needs_bounds
        self.dispatches = 0

    def __call__(self, qs, front, stats, gids: list[int]):
        index, needs = self.index, self.needs
        self.dispatches += 1
        with TraceAnnotation("walk.dispatch"):
            if len(gids) == 1:
                b = gids[0]
                lo = index.slo[b] if needs else None
                hi = index.shi[b] if needs else None
                return _cached_refine_step(
                    self.metric, qs, front, stats, self.fetch(b),
                    index.ids[b], lo, hi, self.block_lb[:, b], self.thr0,
                    n=index.n, w=index.w)
            blocks = jnp.stack([self.fetch(b) for b in gids])    # (G, C, n)
            gi = jnp.asarray(np.asarray(gids, dtype=np.int32))   # host ids
            lo_g = index.slo[gi] if needs else None              # (G, w, C)
            hi_g = index.shi[gi] if needs else None
            return _cached_refine_group(
                self.metric, qs, front, stats, blocks, index.ids[gi],
                lo_g, hi_g, jnp.transpose(self.block_lb[:, gi]),  # (G, Q)
                self.thr0, n=index.n, w=index.w)


def _cached_stage_a(index, plan, prep: PreparedSearch, block_lb_h,
                    fetch, speculate, initial_threshold, *,
                    pipeline_depth: int = 1, group_blocks: int = 1,
                    telemetry: dict | None = None) -> PreparedSearch:
    """Stage A on the cached backend: each query's best-envelope block
    seeds the frontier — a pure fetch/refine chain, so it gets the full
    pipeline treatment: the next ``pipeline_depth`` blocks are always in
    flight behind the reader pool, and up to ``group_blocks`` blocks ride
    one batched dispatch.  Returns the state with the refined block ids
    recorded, so a resumed walk never fetches or refines them again."""
    qs, front, stats = prep.qs, prep.front, prep.stats
    dispatch = _GroupDispatcher(index, plan, prep.block_lb, fetch,
                                initial_threshold)
    stage_a = [int(b) for b in np.unique(np.argmin(block_lb_h, axis=1))]
    i = 0
    with TraceAnnotation("walk.stage_a"):
        while i < len(stage_a):
            gids = stage_a[i:i + group_blocks]
            for b in gids:                     # group reads first, in order
                speculate(b)
            nxt = i + len(gids)
            for b in stage_a[nxt:nxt + pipeline_depth]:  # depth-D lookahead
                speculate(b)
            front, stats = dispatch(qs, front, stats, gids)
            i = nxt
    if telemetry is not None:
        telemetry["stage_a_blocks"] = len(stage_a)
        telemetry["stage_a_dispatches"] = dispatch.dispatches
    return dataclasses.replace(
        prep, front=front, stats=stats,
        refined=prep.refined | frozenset(stage_a))


def run_cached(index: BlockIndex, queries: jax.Array, plan: QueryPlan, *,
               fetch: Callable[[int], jax.Array],
               speculate: Callable[[int], None] = lambda b: None,
               initial_threshold: jax.Array | None = None,
               prepared: PreparedSearch | None = None,
               pipeline_depth: int = 1, group_blocks: int = 1,
               telemetry: dict | None = None
               ) -> tuple[Frontier, SearchStats, PreparedSearch]:
    """The §5 host-level walk: the block-major schedule driven through a
    fetch callback (``storage.BlockCache`` in production), as a
    depth-D, group-G pipeline that degenerates to the serial walk at
    (D=1, G=1).

    Same schedule, same stopping rule, same ``panel_refine`` as the
    device block-major backend — only the block transport differs:
    ``fetch(b)`` must return the (C, n) device block (blocking only if a
    disk read is needed), ``speculate(b)`` starts a background read.

    ``pipeline_depth`` (D) is how many surviving schedule slots beyond
    the current group are speculated per iteration — D reads in flight
    behind the cache's reader pool instead of one.  ``group_blocks``
    (G) batches up to G consecutive surviving blocks (under the current
    host threshold) into ONE jitted dispatch (``_cached_refine_group``),
    and the walk syncs the threshold once per GROUP instead of once per
    block.  Both are threshold-speculative and exact by construction:
    the host threshold only decides which blocks are dispatched, it is
    stale by at most one group, and a stale bound only *weakens* host
    pruning — a block admitted stale meets the up-to-date device-side
    threshold inside the dispatch (the group scan carries the frontier),
    so it refines exactly what the serial walk would have refined (often
    nothing), and dist/idx/stats land bit-identical for any (D, G);
    only I/O (extra speculated-then-pruned fetches) can differ.

    ``telemetry`` (optional dict) is filled with host-side walk counters
    — ``syncs`` (host<->device threshold round trips), ``dispatches``,
    ``walk_blocks`` — so callers can verify the amortization
    (syncs ~= refined_blocks / G + 1).

    The host walk records profiler spans (``jax.profiler
    .TraceAnnotation``; each costs about a microsecond with the profiler
    off): ``walk.prep`` (query prep, block ranking and its landing on
    the host), ``walk.stage_a``, ``walk.schedule``, ``walk.scan`` (each
    survivor scan), ``walk.dispatch`` (each group's slices, fetch and
    enqueue) and ``walk.sync`` (each threshold pull, as many as
    ``syncs``).

    Returns ``(frontier, stats, state)``: the local frontier, the
    finalized work stats, and the walk's end state as a resumable
    ``PreparedSearch`` (pre-finalize stats; ``refined`` holds every
    block this run — and the run it resumed — actually refined).  I/O
    accounting belongs to the callback owner (the session).

    ``plan.deadline_blocks`` caps the blocks the walk refines AFTER
    stage A (the paper's approximate phase always completes, so an
    anytime answer is never worse than MESSI's approximate one); when
    the cap fires the returned frontier is the anytime answer and the
    returned state is its exact-resume continuation —
    ``serve.certify`` derives the certified error bound from it, and
    feeding it back through ``prepared`` upgrades to the exact answer
    bit-identically (same schedule order, same thresholds at every
    refine) while refining only the deferred blocks.

    ``prepared`` resumes from a ``PreparedSearch`` (produced by
    ``run_cached_stage_a`` — or a deadline-cut ``run_cached`` — for the
    same metric, index, queries, and k): query prep, block ranking, and
    stage A are skipped, and the walk never fetches or refines a block
    in ``prepared.refined`` again.
    """
    if plan.schedule != "block_major":
        raise ValueError("the cached backend walks the block-major "
                         f"schedule; got {plan.schedule!r}")
    _check_pipeline_knobs(pipeline_depth, group_blocks)
    n_blocks = index.n_blocks
    if prepared is None:
        with TraceAnnotation("walk.prep"):
            prep = cached_setup(index, queries, plan)
            lb_h = np.asarray(prep.block_lb)                 # sync: 1/batch
        prep = _cached_stage_a(index, plan, prep, lb_h,
                               fetch, speculate, initial_threshold,
                               pipeline_depth=pipeline_depth,
                               group_blocks=group_blocks,
                               telemetry=telemetry)
    else:
        _check_prepared(prepared, plan, n_blocks, queries.shape[0])
        prep = prepared
    qs, front, block_lb, stats = (prep.qs, prep.front, prep.block_lb,
                                  prep.stats)
    done = prep.refined
    budget = plan.deadline_blocks        # refines left; None = unbounded
    with TraceAnnotation("walk.schedule"):
        # one sync per batch: the host copy drives block ordering and the
        # suffix-min stop table; the walk itself then syncs once per GROUP
        # (the '# sync' sites below), which is the pipeline's amortization
        block_lb_h = np.asarray(block_lb)                        # sync
        dispatch = _GroupDispatcher(index, plan, block_lb, fetch,
                                    initial_threshold)
        # -- block-major walk over the surviving schedule -------------
        order, sched_lb, _ = block_major_schedule(block_lb_h, xp=np)
        # slot_done[s]: schedule slot s already refined (stage A / a
        # resumed run) or consumed by this walk — the survivor scan
        # masks it out
        slot_done = (np.isin(order, np.fromiter(done, np.int64, len(done)))
                     if done else np.zeros(n_blocks, dtype=bool))

    walked: list[int] = []               # blocks THIS walk refined
    n_syncs = 1
    with TraceAnnotation("walk.sync"):
        thr_h = np.asarray(_bound(front, initial_threshold))          # sync
    ptr = 0
    while ptr < n_blocks:
        if budget is not None and len(walked) >= budget:
            break                       # deadline: answer is anytime now
        # vectorized survivor scan — one numpy op per threshold sync
        # replaces the per-slot Python pending() loop: a slot survives
        # if unconsumed and any query's scheduled LB beats the bound.
        # (No survivors <=> the suffix-min stopping rule fires: suffix
        # minima over pruned slots cannot beat thr either.)
        with TraceAnnotation("walk.scan"):
            live = np.flatnonzero(~slot_done[ptr:] & np.any(
                sched_lb[:, ptr:] < thr_h[:, None], axis=0)) + ptr
        if live.size == 0:
            break                       # nothing later helps any query
        g = (group_blocks if budget is None
             else min(group_blocks, budget - len(walked)))
        take = live[:g]                 # this group's schedule slots
        gids = [int(order[s]) for s in take]
        for b in gids[1:]:
            # group members behind the head start reading now, so the
            # reader pool overlays them with the head's blocking fetch
            speculate(b)
        front, stats = dispatch(qs, front, stats, gids)           # async
        walked += gids
        slot_done[take] = True
        # depth-D threshold-speculative lookahead: the next D surviving
        # slots under the (now one group stale) bound start reading
        # while the device refines and the sync below waits.  The bound
        # only tightens, so a speculated slot pruned before its turn
        # just stays cached under its id for a later query/batch (a
        # deadline-cut walk leaves it warm for its own continuation).
        for s in live[g:g + pipeline_depth]:
            speculate(int(order[s]))
        with TraceAnnotation("walk.sync"):
            thr_h = np.asarray(_bound(front, initial_threshold))  # sync
        n_syncs += 1
        # slots in [ptr, take[-1]] not taken were pruned under a bound
        # that only tightened since — jump straight past the group
        ptr = int(take[-1]) + 1
    if telemetry is not None:
        telemetry.update(syncs=n_syncs, dispatches=dispatch.dispatches,
                         walk_blocks=len(walked))
    state = dataclasses.replace(prep, front=front, stats=stats,
                                refined=done | frozenset(walked))
    return front, plan.metric.finalize_stats(stats, index.capacity), state


def run_cached_stage_a(index: BlockIndex, queries: jax.Array,
                       plan: QueryPlan, *,
                       fetch: Callable[[int], jax.Array],
                       speculate: Callable[[int], None] = lambda b: None,
                       pipeline_depth: int = 1, group_blocks: int = 1
                       ) -> PreparedSearch:
    """Stage A only, on the cached backend: the approximate top-k after
    refining each query's best-envelope block.  The distributed
    out-of-core protocol min-reduces ``front.threshold()`` across shards
    (round 1), then threads the returned ``PreparedSearch`` back into
    ``run_cached`` so round 2 resumes instead of repeating stage A.
    ``pipeline_depth``/``group_blocks`` pipeline the stage-A chain the
    same way they pipeline the walk (see ``run_cached``)."""
    _check_pipeline_knobs(pipeline_depth, group_blocks)
    with TraceAnnotation("walk.prep"):
        prep = cached_setup(index, queries, plan)
        lb_h = np.asarray(prep.block_lb)                     # sync: 1/round
    return _cached_stage_a(index, plan, prep, lb_h,
                           fetch, speculate, None,
                           pipeline_depth=pipeline_depth,
                           group_blocks=group_blocks)


# the dispatch mode is read at trace time inside these jitted entry
# points — ops.set_mode / ops.kernel_mode clears them on mode changes
ops.register_dispatch_cache(run)
ops.register_dispatch_cache(run_flat)
ops.register_dispatch_cache(_cached_refine_step)
ops.register_dispatch_cache(_cached_refine_group)
