"""Golden parity matrix for the query engine (core/engine.py).

The engine-backed public wrappers must be BIT-identical — dist, idx,
AND stats — to the frozen pre-refactor drivers (tests/_legacy_drivers.py)
on every previously existing metric x schedule x backend cell, for
k in {1, 5, 32} (including k > n_real padding).  The three matrix cells
the engine newly unlocks check exactness against their oracle paths:

  * out-of-core DTW        vs in-memory ``search_dtw``
  * distributed out-of-core vs single-device out-of-core (and the scan)
  * session-served cosine   vs ``vector.search_vectors``
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _legacy_drivers as legacy
import repro.core as core
from repro import storage
from repro.core import distributed, dtw as D, engine, vector
from repro.core.paris import search_flat, search_paris
from repro.core.search import search_block_major
from repro.core.ucr import search_scan
from repro.data import random_walk

KS = (1, 5, 32)
R = 4    # DTW band


def _bitwise(got, want, stats=True, expanded=True):
    """ids (and stats) bit for bit; distances too, except those of the
    expanded form (ED, cosine: ``expanded=True``) where the Pallas kernels
    run (interpret/pallas modes): the fused ED kernel sums its dots in its
    own order, so those are held to the expanded form's worst-case f32
    bound instead (DESIGN.md §8).  DTW distances stay bit for bit."""
    from repro.kernels import ops
    assert np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
    if not (expanded and ops._use_pallas()[0]):
        assert np.array_equal(np.asarray(got.dist), np.asarray(want.dist))
    else:
        n = 128                     # the longest fixture series
        u = 2.0 ** -24
        gamma = n * u / (1 - n * u)
        g2 = np.asarray(got.dist, np.float64) ** 2
        w2 = np.asarray(want.dist, np.float64) ** 2
        assert np.all(np.abs(g2 - w2)
                      <= (4 * gamma + 8 * u) * 2 * n + 4 * u * w2)
    if stats:
        for g, w in zip(got.stats, want.stats):
            assert np.array_equal(np.asarray(g), np.asarray(w))


def _exact(got, want):
    """Exactness for cross-backend cells: identical neighbour sets; the
    distances may differ in final ulps between the panel and gathered
    distance kernels."""
    assert np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
    np.testing.assert_allclose(np.asarray(got.dist), np.asarray(want.dist),
                               rtol=1e-6, atol=1e-6)


@pytest.fixture(scope="module")
def data():
    raw = random_walk(1024, 128, seed=13)
    rng = np.random.default_rng(29)
    qs = jnp.asarray(raw[rng.choice(1024, 6, replace=False)]
                     + 0.1 * rng.standard_normal((6, 128))
                     .astype(np.float32))
    return raw, qs


@pytest.fixture(scope="module")
def idx(data):
    raw, _ = data
    return core.build(jnp.asarray(raw), capacity=64)


@pytest.fixture(scope="module")
def tiny():
    """20 real series: k=32 exercises the (INF, -1) padding rows."""
    raw = random_walk(20, 64, seed=5)
    qs = jnp.asarray(raw[:3] * 1.01)
    return core.build(jnp.asarray(raw), capacity=8), qs


@pytest.fixture(scope="module")
def opened(data, tmp_path_factory):
    raw, _ = data
    path = tmp_path_factory.mktemp("engine") / "full.dsix"
    storage.save_index(core.build(jnp.asarray(raw), capacity=64), path)
    return storage.open_index(path)


# ---------------------------------------------------------------------------
# previously existing cells: bit-identical to the frozen drivers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", KS)
def test_parity_ed_query_major(idx, data, k):
    _, qs = data
    _bitwise(core.search(idx, qs, k=k), legacy.search(idx, qs, k=k))


@pytest.mark.parametrize("k", KS)
def test_parity_ed_block_major(idx, data, k):
    _, qs = data
    _bitwise(search_block_major(idx, qs, k=k),
             legacy.search_block_major(idx, qs, k=k))


@pytest.mark.parametrize("k", KS)
def test_parity_ed_flat(idx, data, k):
    _, qs = data
    _bitwise(search_paris(idx, qs, k=k, chunk=256),
             legacy.search_paris(idx, qs, k=k, chunk=256))


def test_parity_ed_flat_standalone(data):
    """ParIS without a block index: empty-frontier start, no stage A."""
    raw, qs = data
    fidx = core.build_flat(jnp.asarray(raw))
    _bitwise(search_flat(fidx, qs, k=5, chunk=200),
             legacy.search_flat(fidx, qs, k=5, chunk=200))


def test_parity_ed_knob_sweep(idx, data):
    """The tuning knobs trace distinct graphs — pin each variant."""
    _, qs = data
    thr = jnp.asarray(core.search(idx, qs, k=1).dist[:, 0]) ** 2 + 1e-3
    for kw in (dict(lb_filter=False), dict(deadline_blocks=3),
               dict(blocks_per_iter=2), dict(initial_threshold=thr)):
        _bitwise(core.search(idx, qs, k=5, **kw),
                 legacy.search(idx, qs, k=5, **kw))
    for kw in (dict(lb_filter=False), dict(deadline_blocks=3),
               dict(initial_threshold=thr)):
        _bitwise(search_block_major(idx, qs, k=5, **kw),
                 legacy.search_block_major(idx, qs, k=5, **kw))


@pytest.mark.parametrize("k", KS)
def test_parity_dtw_query_major(data, k):
    raw, qs = data
    idx = core.build(jnp.asarray(raw[:512]), capacity=64)
    _bitwise(D.search_dtw(idx, qs, r=R, k=k),
             legacy.search_dtw(idx, qs, r=R, k=k), expanded=False)


@pytest.mark.parametrize("k", KS)
def test_parity_cosine_device(data, k):
    """search_vectors == the legacy ED driver on prepped embeddings."""
    rng = np.random.default_rng(3)
    embs = jnp.asarray(rng.standard_normal((1024, 64)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))
    vidx = vector.build_vector_index(embs, capacity=64)
    _bitwise(vector.search_vectors(vidx, qs, k=k),
             legacy.search(vidx, vector.prep_vectors(qs), k=k,
                           normalize_queries=False))


@pytest.mark.parametrize("k", (1, 32))
def test_parity_padding_k_gt_n_real(tiny, k):
    """k > n_real: the padding rows (INF dist, id -1) match bit-for-bit."""
    tidx, qs = tiny
    _bitwise(core.search(tidx, qs, k=k), legacy.search(tidx, qs, k=k))
    _bitwise(search_block_major(tidx, qs, k=k),
             legacy.search_block_major(tidx, qs, k=k))
    _bitwise(search_paris(tidx, qs, k=k, chunk=8),
             legacy.search_paris(tidx, qs, k=k, chunk=8))
    _bitwise(D.search_dtw(tidx, qs, r=R, k=k),
             legacy.search_dtw(tidx, qs, r=R, k=k), expanded=False)
    if k > 20:
        got = core.search(tidx, qs, k=k)
        assert np.all(np.asarray(got.idx)[:, 20:] == -1)


@pytest.mark.parametrize("k", KS)
def test_parity_ed_cached_backend(data, opened, k):
    """The cached walk answers exactly what the scan answers — the
    pre-refactor session contract (storage tests pin the I/O side)."""
    raw, qs = data
    got = storage.ooc_search(opened, qs, k=k)
    want = search_scan(jnp.asarray(raw), qs, k=k)
    _bitwise(got, want, stats=False)


# ---------------------------------------------------------------------------
# query-major refine: the gathered kernel (interpret) against its oracle
# (ref) through engine.run, Q = 16, K = blocks_per_iter = 4, k = 10
# ---------------------------------------------------------------------------

def _query_major_case(metric, variant):
    """1,500 series of length 128 in 12 blocks of 128 (the kernel takes
    lane-aligned blocks), the last one part padding."""
    rng = np.random.default_rng(41)
    if isinstance(metric, engine.Cosine):
        raw = rng.standard_normal((1500, 128)).astype(np.float32)
        index = vector.build_vector_index(jnp.asarray(raw), capacity=128)
        qs = jnp.asarray(raw[rng.choice(1500, 16, replace=False)] + 0.05)
    else:
        raw = random_walk(1500, 128, seed=43)
        index = core.build(jnp.asarray(raw), capacity=128)
        qs = jnp.asarray(raw[rng.choice(1500, 16, replace=False)]
                         + 0.1 * rng.standard_normal((16, 128))
                         .astype(np.float32))
    kw = {}
    if variant == "deadline":
        kw["deadline_blocks"] = 6
    plan = engine.QueryPlan(metric=metric, schedule="query_major", k=10,
                            blocks_per_iter=4, **kw)
    thr = None
    if variant == "threshold":
        exact = engine.run(index, qs, engine.QueryPlan(
            metric=metric, schedule="query_major", k=1))
        thr = jnp.asarray(exact.dist[:, 0]) ** 2 * 1.5 + 1e-3
    return index, qs, plan, thr


@pytest.mark.parametrize("variant", ["exact", "deadline", "threshold"])
@pytest.mark.parametrize("metric", [engine.ED(), engine.Cosine()],
                         ids=["ed", "cosine"])
def test_query_major_kernel_vs_oracle(metric, variant):
    """Ids, blocks visited, series refined and trips identical; distances
    within the expanded form's f32 bound."""
    from repro.kernels import ops
    index, qs, plan, thr = _query_major_case(metric, variant)
    with ops.kernel_mode("ref"):
        want = engine.run(index, qs, plan, thr)
    with ops.kernel_mode("interpret"):
        got = engine.run(index, qs, plan, thr)
    assert np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
    for g, w in zip(got.stats, want.stats):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    n = index.n                     # ||q||^2 = ||x||^2 = n for both
    u = 2.0 ** -24
    gamma = n * u / (1 - n * u)
    g2 = np.asarray(got.dist, np.float64) ** 2
    w2 = np.asarray(want.dist, np.float64) ** 2
    assert np.all(np.abs(g2 - w2) <= (4 * gamma + 8 * u) * 2 * n + 4 * u * w2)
    assert int(want.stats.iters) > 1


def test_query_major_dtw_unchanged_by_mode(data):
    """DTW keeps the gathered path: bit for bit across modes."""
    from repro.kernels import ops
    raw, qs = data
    index = core.build(jnp.asarray(raw[:512]), capacity=64)
    with ops.kernel_mode("ref"):
        want = D.search_dtw(index, qs, r=R, k=10, blocks_per_iter=4)
    with ops.kernel_mode("interpret"):
        got = D.search_dtw(index, qs, r=R, k=10, blocks_per_iter=4)
    _bitwise(got, want, expanded=False)


# ---------------------------------------------------------------------------
# new cells: exactness against their oracle paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", KS)
def test_new_cell_ooc_dtw(data, opened, k):
    """DTW metric x cached backend == in-memory search_dtw."""
    raw, qs = data
    mem = D.search_dtw(core.build(jnp.asarray(raw), capacity=64),
                       qs, r=R, k=k)
    ooc = storage.ooc_search(opened, qs, k=k, metric=engine.DTW(r=R))
    _exact(ooc, mem)
    # each block is read at most once (DTW envelope bounds can be loose
    # enough on random walks that no block is pruned outright at k=1)
    assert ooc.io.blocks_fetched <= ooc.io.blocks_total
    assert ooc.io.bytes_read <= ooc.io.bytes_scan


@pytest.mark.parametrize("k", KS)
def test_new_cell_session_cosine(tmp_path, k):
    """Cosine metric x cached backend == device search_vectors."""
    rng = np.random.default_rng(3)
    embs = jnp.asarray(rng.standard_normal((1024, 64)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))
    vidx = vector.build_vector_index(embs, capacity=64)
    path = tmp_path / "vec.dsix"
    storage.save_index(vidx, path)
    dev = vector.search_vectors(vidx, qs, k=k)
    with storage.SearchSession(storage.open_index(path),
                               cache_blocks=8) as sess:
        ses = sess.search(qs, k=k, metric=engine.Cosine())
    _exact(ses, dev)


def _shard_sessions(raw, tmp_path, n_shards=2, cache_blocks=8):
    n = len(raw) // n_shards
    sessions = []
    for s in range(n_shards):
        ids = jnp.arange(s * n, (s + 1) * n, dtype=jnp.int32)
        sidx = core.build(jnp.asarray(raw[s * n:(s + 1) * n]),
                          capacity=64, ids=ids)
        path = tmp_path / f"shard{s}.dsix"
        storage.save_index(sidx, path)
        sessions.append(storage.SearchSession(
            storage.open_index(path), cache_blocks=cache_blocks))
    return sessions


@pytest.mark.parametrize("k", KS)
def test_new_cell_distributed_ooc(data, opened, tmp_path, k):
    """Two-round protocol over per-shard sessions == single-device ooc
    (and the scan oracle) — disjoint shards, global ids."""
    raw, qs = data
    sessions = _shard_sessions(raw, tmp_path)
    try:
        got = distributed.search_sharded_ooc(sessions, qs, k=k)
    finally:
        for s in sessions:
            s.close()
    single = storage.ooc_search(opened, qs, k=k)
    _exact(got, single)
    _exact(got, search_scan(jnp.asarray(raw), qs, k=k))


def test_distributed_ooc_threshold_tightens_reads(data, tmp_path):
    """Round 1's global pmin bound must not cost MORE disk than running
    the shards blind — the reason the protocol exists."""
    raw, qs = data
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    seeded = _shard_sessions(raw, tmp_path / "a")
    blind = _shard_sessions(raw, tmp_path / "b")
    try:
        res = distributed.search_sharded_ooc(seeded, qs, k=5)
        blind_reads = sum(s.search(qs, k=5).io.blocks_fetched
                          for s in blind)
    finally:
        for s in seeded + blind:
            s.close()
    assert res.io.blocks_fetched <= blind_reads
    assert res.io.cache_hits >= 0 and res.io.blocks_total > 0


# ---------------------------------------------------------------------------
# plan/axis validation
# ---------------------------------------------------------------------------

def test_plan_rejects_unknown_schedule():
    with pytest.raises(ValueError, match="schedule"):
        engine.QueryPlan(schedule="priority_queue")


def test_run_rejects_flat_plan(idx, data):
    _, qs = data
    with pytest.raises(ValueError, match="run_flat"):
        engine.run(idx, qs, engine.QueryPlan(schedule="flat"))


def test_run_cached_requires_block_major(opened, data):
    _, qs = data
    with pytest.raises(ValueError, match="block-major"):
        engine.run_cached(opened, qs,
                          engine.QueryPlan(schedule="query_major"),
                          fetch=lambda b: None)


def test_run_refuses_out_of_core_index(opened, data):
    _, qs = data
    with pytest.raises(ValueError, match="out-of-core"):
        engine.run(opened, qs, engine.QueryPlan())


def test_plan_rejects_nonpositive_deadline():
    for bad in (0, -3):
        with pytest.raises(ValueError, match="deadline_blocks"):
            engine.QueryPlan(deadline_blocks=bad)


def test_run_cached_deadline_cuts_then_resumes_exact(opened, data):
    """A deadline-cut walk returns a resumable state whose continuation
    lands bit-identically on the exact answer (frontier AND cumulative
    stats), refining only the deferred blocks."""
    _, qs = data

    def fetch(b):
        return jax.device_put(opened.host_raw.fetch(b))

    plan = engine.QueryPlan(schedule="block_major", k=5)
    cut_plan = engine.QueryPlan(schedule="block_major", k=5,
                                deadline_blocks=2)
    front, _, state = engine.run_cached(opened, qs, cut_plan, fetch=fetch)
    ref_front, ref_stats, ref_state = engine.run_cached(opened, qs, plan,
                                                        fetch=fetch)
    assert state.refined < ref_state.refined     # strictly fewer blocks
    got_front, got_stats, _ = engine.run_cached(opened, qs, plan,
                                                fetch=fetch, prepared=state)
    assert np.array_equal(np.asarray(got_front.dists),
                          np.asarray(ref_front.dists))
    assert np.array_equal(np.asarray(got_front.ids),
                          np.asarray(ref_front.ids))
    assert np.array_equal(np.asarray(got_stats.blocks_visited),
                          np.asarray(ref_stats.blocks_visited))
    assert np.array_equal(np.asarray(got_stats.series_refined),
                          np.asarray(ref_stats.series_refined))
