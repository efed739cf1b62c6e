"""Profiler spans of the served paths: the host walk and the block cache
(``engine.run_cached`` through ``storage.SearchSession``) and the
in-memory dispatch (``core.search``).

Each search runs under ``jax.profiler.start_trace`` and the trace is
read back with ``jax.profiler.ProfileData``: every span appears, the
span counts equal the program's own counters (``last_telemetry``,
``IOStats``), and the same search with the profiler off answers the
same, bit for bit.
"""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core as core
from repro import storage
from repro.core.index import HostRawBlocks
from repro.data import random_walk

N, LEN, CAP = 2000, 128, 64
WALK_SPANS = ("walk.prep", "walk.stage_a", "walk.schedule", "walk.scan",
              "walk.dispatch", "cache.wait", "walk.sync", "walk.settle",
              "cache.read_file", "cache.upload")


@pytest.fixture(scope="module")
def dataset():
    raw = random_walk(N, LEN, seed=43)
    rng = np.random.default_rng(17)
    qs = jnp.asarray(raw[rng.choice(N, 4, replace=False)]
                     + 0.05 * rng.standard_normal((4, LEN))
                     .astype(np.float32))
    return raw, qs


@pytest.fixture(scope="module")
def opened(dataset, tmp_path_factory):
    raw, _ = dataset
    path = tmp_path_factory.mktemp("spans") / "rw.dsix"
    storage.save_index(core.build(jnp.asarray(raw), capacity=CAP), path)
    return storage.open_index(path)


@pytest.fixture
def slow_reads(monkeypatch):
    """Every block read takes a few ms longer, so a fetch that finds its
    block still in flight blocks on it (a ``cache.wait``) on any host."""
    fetch = HostRawBlocks.fetch

    def slow(self, b):
        time.sleep(0.003)
        return fetch(self, b)

    monkeypatch.setattr(HostRawBlocks, "fetch", slow)


def _span_counts(trace_dir) -> collections.Counter:
    xplanes = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert len(xplanes) == 1
    return collections.Counter(
        ev.name for plane in ProfileData.from_file(str(xplanes[0])).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)


def _traced(trace_dir, search):
    jax.profiler.start_trace(str(trace_dir))
    try:
        res = search()
        jax.block_until_ready((res.dist, res.idx))
    finally:
        jax.profiler.stop_trace()
    return res, _span_counts(trace_dir)


def _same(got, want):
    assert np.array_equal(np.asarray(got.dist), np.asarray(want.dist))
    assert np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
    for a, b in zip(got.stats, want.stats):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("d,g", [(1, 1), (2, 4)])
def test_walk_spans_match_counters(tmp_path, dataset, opened, slow_reads,
                                   d, g):
    _, qs = dataset

    def search(sess):
        return sess.search(qs, k=3, pipeline_depth=d, group_blocks=g)

    with storage.SearchSession(opened, cache_blocks=8) as sess:
        res, n = _traced(tmp_path, lambda: search(sess))
        tel = sess.last_telemetry
    with storage.SearchSession(opened, cache_blocks=8) as sess:
        plain = search(sess)
        assert sess.last_telemetry == tel

    assert all(n[name] > 0 for name in WALK_SPANS), n
    assert n["walk.sync"] == tel["syncs"]
    assert n["walk.dispatch"] == tel["dispatches"] + tel["stage_a_dispatches"]
    assert n["cache.read_file"] == n["cache.upload"] == res.io.blocks_fetched
    assert tel["dispatches"] <= n["walk.scan"] <= tel["dispatches"] + 1
    for once in ("walk.prep", "walk.stage_a", "walk.schedule", "walk.settle"):
        assert n[once] == 1, once
    assert n["engine.dispatch"] == 0
    assert set(tel) == {"syncs", "dispatches", "walk_blocks",
                        "stage_a_blocks", "stage_a_dispatches"}
    _same(res, plain)
    assert res.io == plain.io


def test_in_memory_dispatch_span(tmp_path, dataset):
    raw, qs = dataset
    index = core.build(jnp.asarray(raw), capacity=CAP)
    plain = core.search(index, qs, k=3)
    res, n = _traced(tmp_path, lambda: core.search(index, qs, k=3))
    assert n["engine.dispatch"] == 1
    assert not any(n[name] for name in WALK_SPANS)
    _same(res, plain)
