#!/usr/bin/env python3
"""Smoke run of exact k-NN search on a TPU, end to end, checked against a
float64 scan.

    python chip_smoke.py               # one chip: in-memory + on-disk
    python chip_smoke.py --four-chips  # four chips: the sharded protocol

One process, on the chip(s) it finds.  All data is the paper's Synthetic
collection (random walks, series length 256) generated from ``--seed``.

Phases on one chip:

* **in memory (MESSI)** — ``core.build`` over ``MEM_SERIES`` series held in
  HBM, then one batch of ``N_QUERIES`` random-walk queries through
  ``core.search`` at k=1 and k=10;
* **on disk (ParIS+)** — the first ``DISK_SERIES`` series written as a
  headerless f32 file, indexed by ``storage.run_pipeline``, opened with
  ``storage.open_index`` and served by a ``SearchSession`` whose block
  cache holds at most 1/8 of the blocks: one batch through ``search``,
  one through ``submit``/``drain``;
* **DTW** — ``dtw.search_dtw`` (band r = n/10) over an index of the
  first ``DTW_SERIES`` series, which runs the banded-DTW kernel.

With ``--four-chips`` only the sharded protocol runs:
``distributed.build_sharded`` + ``search_sharded`` over the on-disk
phase's ``DISK_SERIES`` series on a 4-device mesh.

Every answer is checked against ``oracle_knn`` (``oracle_dtw`` for
DTW), a plain NumPy float64 scan that uses nothing of ``repro``.  Any
failure exits non-zero.  The earlier output lines are smoke timings
(wall clock; JAX's own compile events split out where marked), not
metrics; the last line is the JSON device record.
Without a TPU the script exits 2 and prints no record.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_LEN = 256            # the paper's Synthetic series length
# 2^22 series = 4 GiB of raw f32.  Compiled for a v5e, ``core.build``
# over them needs 12.5 GiB (4.0 argument + 4.5 output + 4.0 temp) of the
# 16 GiB HBM, so 2^22 and not 2^21; ``phase_in_memory`` checks the
# compiled build against the chip's ``bytes_limit`` before it runs.
MEM_SERIES = 1 << 22
DISK_SERIES = 1 << 21  # 2 GiB series file; also the four-chip collection
DTW_SERIES = 1 << 13   # the DTW phase's slice (its oracle is an O(N n r) DP)
N_QUERIES = 16
K = 10
CAPACITY = 512         # series per block (leaf)
CACHE_FRACTION = 8     # the session caches at most 1/8 of the blocks
DATA_DIR = ROOT / ".smoke_data"

# f32 unit roundoff.  The system z-normalizes in f32 and takes squared
# distances in the expanded form ||q||^2 + ||x||^2 - 2 q.x, each dot a
# length-n f32 sum (MXU at Precision.HIGHEST on the chip).  Such a sum
# errs by at most gamma_n = n u / (1 - n u) times the sum of |terms|,
# and |q.x| <= (||q||^2 + ||x||^2) / 2, so the computed squared distance
# lies within (2 gamma_n + 4u)(||q||^2 + ||x||^2) of the exact one (the
# 4u covers the two additions).  Z-normed series have ||.||^2 = n.
U = 2.0 ** -24


def dist_tol_sq(n: int) -> float:
    """Tolerance on a squared distance against the float64 oracle: twice
    the expanded form's worst-case f32 error, the second half covering
    the f32 z-normalization (its effect is ~2 d ||dx|| with ||dx|| of
    order sqrt(n) u times the series' scale, far below the first).  At
    n=256: 3.2e-2.  One bf16 MXU pass (the TPU's default f32 precision)
    errs by ~1e-1 at n=256, so this bound still catches a matmul that
    lost its precision setting."""
    gamma = n * U / (1 - n * U)
    return 2 * (2 * gamma + 4 * U) * (2 * n)


def dtw_tol_sq(n: int, d2: np.ndarray) -> np.ndarray:
    """Tolerance on a squared DTW distance ``d2`` against the float64
    oracle.  DTW sums at most 2n-1 non-negative terms (q_i - x_j)^2 along
    its path, each an f32 subtraction and square (3u), so every path's
    f32 cost lies within gamma_{2n+2} of its exact cost, relatively, and
    so does the minimum over paths.  Half of ``dist_tol_sq`` is added
    for the f32 z-normalization, as for ED."""
    g = (2 * n + 2) * U / (1 - (2 * n + 2) * U)
    return g * d2 + dist_tol_sq(n) / 2


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the float64 oracle (NumPy only)
# ---------------------------------------------------------------------------

def znorm64(x: np.ndarray) -> np.ndarray:
    """Z-normalize rows in float64 (population std, floor 1e-8)."""
    x = np.asarray(x, np.float64)
    mu = x.mean(axis=-1, keepdims=True)
    sd = x.std(axis=-1, keepdims=True)
    return (x - mu) / np.maximum(sd, 1e-8)


def oracle_knn(raw: np.ndarray, queries: np.ndarray, k: int,
               chunk: int = 1 << 16) -> tuple[np.ndarray, np.ndarray]:
    """Exact k-NN by a full float64 scan -> (squared dists, ids), (Q, k),
    ascending by (distance, id)."""
    q = znorm64(queries)
    qq = np.sum(q * q, axis=1)
    best_d = np.full((len(q), 0), np.inf)
    best_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(raw), chunk):
        x = znorm64(raw[s:s + chunk])
        d = qq[:, None] + np.sum(x * x, axis=1)[None, :] - 2.0 * (q @ x.T)
        kk = min(k, d.shape[1])
        part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
        cand_d = np.concatenate(
            [best_d, np.take_along_axis(d, part, axis=1)], axis=1)
        cand_i = np.concatenate([best_i, part + s], axis=1)
        order = np.lexsort((cand_i, cand_d), axis=1)[:, :k]
        best_d = np.take_along_axis(cand_d, order, axis=1)
        best_i = np.take_along_axis(cand_i, order, axis=1)
    return best_d, best_i


def oracle_dtw(raw: np.ndarray, queries: np.ndarray, r: int
               ) -> np.ndarray:
    """Squared DTW with Sakoe-Chiba band r (|i - j| <= r) of every query
    against every series, in float64 -> (Q, N).  Row-by-row DP; row i
    keeps only its band, cell (i, j) at offset j - i + r."""
    q, x = znorm64(queries), znorm64(raw)
    n, width = x.shape[1], 2 * r + 1
    prev = np.full((width, len(q), len(x)), np.inf)
    cur = np.empty_like(prev)
    for i in range(n):
        cur.fill(np.inf)
        for b in range(width):
            j = i + b - r
            if not 0 <= j < n:
                continue
            cost = (q[:, i, None] - x[None, :, j]) ** 2
            if i == 0 and j == 0:
                cur[b] = cost
                continue
            best = prev[b]                           # (i-1, j-1)
            if b + 1 < width:
                best = np.minimum(best, prev[b + 1])  # (i-1, j)
            if b > 0:
                best = np.minimum(best, cur[b - 1])   # (i, j-1)
            cur[b] = cost + best
        prev, cur = cur, prev
    return prev[r]


def top_k(d2: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(Q, N) distances -> (squared dists, ids), (Q, k), ascending by
    (distance, id)."""
    order = np.lexsort((np.broadcast_to(np.arange(d2.shape[1]), d2.shape),
                        d2), axis=1)[:, :k]
    return np.take_along_axis(d2, order, axis=1), order


def check_knn(name: str, raw: np.ndarray, queries: np.ndarray,
              dist: np.ndarray, idx: np.ndarray, oracle: tuple,
              k: int, *, all_d2: np.ndarray | None = None,
              r: int | None = None) -> bool:
    """Hold one answer to the oracle; print each mismatch; -> passed.

    Per query: the k ids are real and distinct; each reported distance
    agrees with the float64 distance of its own id within
    ``dist_tol_sq`` (``dtw_tol_sq`` when ``r`` is given, the distances
    then read from ``all_d2``, the oracle's (Q, N) DTW matrix); and at
    every rank the id is the oracle's, unless the two float64 distances
    tie within twice that tolerance (the most by which two f32
    distances can be reordered).
    """
    n = raw.shape[1]
    or_d, or_i = oracle[0][:, :k], oracle[1][:, :k]
    q64 = znorm64(queries)
    ok = True
    for qi in range(len(queries)):
        ids = np.asarray(idx[qi, :k], np.int64)
        if np.any(ids < 0) or len(set(ids.tolist())) != k:
            say(f"MISMATCH {name} q{qi}: ids {ids.tolist()} not {k} "
                "distinct real series")
            ok = False
            continue
        if r is None:
            true = np.sum((znorm64(raw[ids]) - q64[qi]) ** 2, axis=1)
            tol = np.full(k, dist_tol_sq(n)) + 4 * U * true
        else:
            true = all_d2[qi, ids]
            tol = dtw_tol_sq(n, true)
        rep = np.asarray(dist[qi, :k], np.float64) ** 2
        for j in range(k):
            if abs(rep[j] - true[j]) > tol[j]:
                say(f"MISMATCH {name} q{qi} rank {j}: reported d^2 "
                    f"{rep[j]!r} vs float64 {true[j]!r} for id {ids[j]}")
                ok = False
            if (ids[j] != or_i[qi, j]
                    and abs(true[j] - or_d[qi, j]) > 2 * tol[j]):
                say(f"MISMATCH {name} q{qi} rank {j}: id {ids[j]} "
                    f"(d^2 {true[j]!r}) where the oracle has {or_i[qi, j]} "
                    f"(d^2 {or_d[qi, j]!r})")
                ok = False
    return ok


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def make_data(n_series: int, n_queries: int, seed: int, n: int = N_LEN
              ) -> tuple[np.ndarray, list[np.ndarray]]:
    """-> (collection (N, n), three query batches (Q, n)), all random
    walks: the collection from ``seed``, each batch from its own seed."""
    from repro.data import random_walk
    raw = random_walk(n_series, n, seed=seed)
    batches = [random_walk(n_queries, n, seed=seed + 1 + i)
               for i in range(3)]
    return raw, batches


def _memory_stats() -> dict:
    import jax
    return jax.devices()[0].memory_stats() or {}


def _peak_bytes() -> int | None:
    return _memory_stats().get("peak_bytes_in_use")


_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """JAX's own compile events while the block runs: ``lower_s`` the
    seconds spent tracing and lowering to MLIR, ``programs`` the number
    of XLA programs compiled or loaded from the persistent cache,
    ``cache_hits`` how many of them were loaded, ``compile_s`` the
    seconds that took.  The rest of a call's wall time is the run."""

    def __enter__(self):
        import jax
        self.programs, self.cache_hits = 0, 0
        self.lower_s, self.compile_s = 0.0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_dur(self, event, secs, **_):
        if event in _LOWER:
            self.lower_s += secs
        elif event == _BACKEND_COMPILE:
            self.programs += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)

    def record(self, timings: dict, key: str) -> None:
        timings[f"{key}_lower_s"] = self.lower_s
        timings[f"{key}_compile_s"] = self.compile_s
        timings[f"{key}_programs"] = self.programs
        timings[f"{key}_cache_hits"] = self.cache_hits


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_in_memory(raw: np.ndarray, queries: np.ndarray, *,
                    ks=(1, K), capacity: int = CAPACITY) -> dict:
    """MESSI: build in HBM, then ``core.search`` at each k.
    -> {"results": {k: (dist, idx)}, "timings": {...}, ...}."""
    import jax
    import jax.numpy as jnp

    from repro import core

    t = {}
    t0 = time.perf_counter()
    x = jax.device_put(raw)
    x.block_until_ready()
    t["transfer_s"] = time.perf_counter() - t0

    # compiled ahead of the call only to read its memory analysis; the
    # call below is the users' ``core.build`` and reuses this program
    t0 = time.perf_counter()
    compiled = core.build.lower(x, capacity=capacity).compile()
    t["build_compile_s"] = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    limit = _memory_stats().get("bytes_limit")
    if limit is not None and need > limit:
        raise RuntimeError(f"the compiled build needs {need} B of device "
                           f"memory, the device has {limit} B")
    t0 = time.perf_counter()
    with CompileLog() as log:
        index = core.build(x, capacity=capacity)
        index.raw.block_until_ready()
    t["build_run_s"] = time.perf_counter() - t0
    log.record(t, "build_run")
    del x
    if not index.device_resident or index.n_real != len(raw):
        raise RuntimeError("in-memory build did not leave every series "
                           "resident on the device")

    q = jnp.asarray(queries)
    results, blocks_visited = {}, {}
    for k in ks:
        for label in ("first_call", "second_call"):
            t0 = time.perf_counter()
            with CompileLog() as log:
                res = core.search(index, q, k=k)
                res.dist.block_until_ready()
            t[f"search_k{k}_{label}_s"] = time.perf_counter() - t0
            log.record(t, f"search_k{k}_{label}")
        results[k] = (np.asarray(res.dist), np.asarray(res.idx))
        blocks_visited[k] = int(np.sum(np.asarray(res.stats.blocks_visited)))
    out = {"results": results, "timings": t, "n_blocks": index.n_blocks,
           "blocks_visited": blocks_visited, "peak_bytes": _peak_bytes(),
           "build_memory": {
               "argument": mem.argument_size_in_bytes,
               "output": mem.output_size_in_bytes,
               "temp": mem.temp_size_in_bytes,
               "alias": mem.alias_size_in_bytes,
               "bytes_limit": limit}}
    del index, res
    gc.collect()
    return out


def phase_on_disk(raw: np.ndarray, batch_search: np.ndarray,
                  batch_submit: np.ndarray, work_dir: Path, *, k: int = K,
                  capacity: int = CAPACITY,
                  cache_fraction: int = CACHE_FRACTION,
                  workers: int = 4) -> dict:
    """ParIS+: series file -> staged build -> out-of-core session.
    -> {"results": {"search": (dist, idx), "submit": (dist, idx)}, ...}."""
    import jax.numpy as jnp

    from repro import storage

    t = {}
    work_dir.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    store = storage.SeriesStore.write(work_dir / "series.f32", raw)
    t["write_series_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    path, report = storage.run_pipeline(store, work_dir / "index.dsix",
                                        capacity=capacity, workers=workers)
    t["pipeline_build_s"] = time.perf_counter() - t0
    index = storage.open_index(path)
    cache_blocks = max(2, index.n_blocks // cache_fraction)
    out = {"n_blocks": index.n_blocks, "cache_blocks": cache_blocks,
           "results": {}, "io": {}}
    with storage.SearchSession(index, cache_blocks=cache_blocks) as sess:
        t0 = time.perf_counter()
        res = sess.search(jnp.asarray(batch_search), k=k)
        res.dist.block_until_ready()
        t["search_first_call_s"] = time.perf_counter() - t0
        out["results"]["search"] = (np.asarray(res.dist),
                                    np.asarray(res.idx))
        out["io"]["search"] = res.io._asdict()

        t0 = time.perf_counter()
        ticket = sess.submit(jnp.asarray(batch_submit), k=k)
        sess.drain()
        res = ticket.result()
        res.dist.block_until_ready()
        t["submit_drain_first_call_s"] = time.perf_counter() - t0
        out["results"]["submit"] = (np.asarray(res.dist),
                                    np.asarray(res.idx))
        out["io"]["submit"] = res.io._asdict()
    out["timings"] = t
    out["peak_bytes"] = _peak_bytes()
    return out


def phase_dtw(raw: np.ndarray, queries: np.ndarray, *, k: int = K,
              r: int | None = None, capacity: int = CAPACITY) -> dict:
    """Exact DTW k-NN (``dtw.search_dtw``, band r, default n/10) over an
    in-memory index of ``raw``.  -> {"results": (dist, idx), "r": r, ...}."""
    import jax.numpy as jnp

    from repro import core
    from repro.core import dtw

    r = raw.shape[1] // 10 if r is None else r
    t = {}
    index = core.build(jnp.asarray(raw), capacity=capacity)
    q = jnp.asarray(queries)
    for label in ("first_call", "second_call"):
        t0 = time.perf_counter()
        with CompileLog() as log:
            res = dtw.search_dtw(index, q, r=r, k=k)
            res.dist.block_until_ready()
        t[f"search_{label}_s"] = time.perf_counter() - t0
        log.record(t, f"search_{label}")
    return {"results": (np.asarray(res.dist), np.asarray(res.idx)),
            "r": r, "timings": t, "n_blocks": index.n_blocks,
            "blocks_visited": int(np.sum(np.asarray(
                res.stats.blocks_visited)))}


def phase_four_chips(raw: np.ndarray, queries: np.ndarray, *, k: int = K,
                     capacity: int = CAPACITY, n_dev: int = 4) -> dict:
    """The sharded two-round protocol on an ``n_dev``-device mesh.
    Checks that every device holds its own shard of the index."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed

    devices = jax.devices()[:n_dev]
    if len(devices) != n_dev:
        raise RuntimeError(f"need {n_dev} devices, found {len(devices)}")
    mesh = jax.make_mesh((n_dev,), ("data",), devices=devices)
    t = {}
    t0 = time.perf_counter()
    x = jax.device_put(raw, NamedSharding(mesh, P("data")))
    x.block_until_ready()
    t["transfer_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with CompileLog() as log:
        sidx = distributed.build_sharded(x, mesh, capacity=capacity)
        sidx.raw.block_until_ready()
    t["build_call_s"] = time.perf_counter() - t0
    log.record(t, "build_call")
    del x

    # each device must hold its own contiguous range of series ids
    per = len(raw) // n_dev
    shards = sorted(sidx.ids.addressable_shards, key=lambda s: s.index[0].start)
    held = {s.device for s in shards}
    if len(shards) != n_dev or held != set(devices):
        raise RuntimeError(f"index ids live on {held}, not one shard on "
                           f"each of {devices}")
    for i, s in enumerate(shards):
        ids = np.asarray(s.data)
        real = ids[ids >= 0]
        if (len(real) != per or real.min() != i * per
                or real.max() != (i + 1) * per - 1):
            raise RuntimeError(f"shard {i} on {s.device} holds ids "
                               f"[{real.min()}, {real.max()}], not its own "
                               f"range [{i * per}, {(i + 1) * per})")
    raw_devices = {s.device for s in sidx.raw.addressable_shards}
    if raw_devices != set(devices):
        raise RuntimeError(f"raw series live on {raw_devices}")

    q = jnp.asarray(queries)
    for label in ("first_call", "second_call"):
        t0 = time.perf_counter()
        with CompileLog() as log:
            res = distributed.search_sharded(sidx, q, mesh, k=k)
            res.dist.block_until_ready()
        t[f"search_{label}_s"] = time.perf_counter() - t0
        log.record(t, f"search_{label}")
    return {"results": (np.asarray(res.dist), np.asarray(res.idx)),
            "timings": t, "shards": [(str(s.device), s.data.shape)
                                     for s in sidx.raw.addressable_shards]}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _report(phase: str, out: dict) -> None:
    for key, val in out.get("timings", {}).items():
        say(f"smoke timing (not a metric) {phase}.{key} = {val!r}")
    for key in ("n_blocks", "cache_blocks", "blocks_visited", "r", "io",
                "build_memory", "peak_bytes", "shards"):
        if key in out:
            say(f"{phase}.{key} = {out[key]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded protocol on a 4-chip mesh")
    args = ap.parse_args(argv)

    try:
        from repro.kernels import ops
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    if ops._use_pallas() != (True, False):
        print(f"chip_smoke: kernel dispatch mode {ops.get_mode()!r} would "
              "not run the compiled Pallas kernels", file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    say(f"device: {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{cache_dir}, seed {args.seed}")
    if K > CAPACITY:
        # ops falls back to the jnp oracle when k exceeds the candidates
        # of one block; the smoke must run the kernels themselves
        print("chip_smoke: K > CAPACITY would take the oracle fallback",
              file=sys.stderr)
        return 2

    ok = True
    if args.four_chips:
        t0 = time.perf_counter()
        raw, batches = make_data(DISK_SERIES, N_QUERIES, args.seed)
        say(f"data: {raw.shape} series in "
            f"{time.perf_counter() - t0:.1f}s")
        out = phase_four_chips(raw, batches[0], n_dev=n_chips)
        _report("four_chips", out)
        oracle = oracle_knn(raw, batches[0], K)
        ok &= check_knn("four_chips k=10", raw, batches[0],
                        *out["results"], oracle, K)
    else:
        t0 = time.perf_counter()
        raw, batches = make_data(MEM_SERIES, N_QUERIES, args.seed)
        say(f"data: {raw.shape} series in "
            f"{time.perf_counter() - t0:.1f}s")
        mem = phase_in_memory(raw, batches[0])
        _report("in_memory", mem)
        t0 = time.perf_counter()
        oracle = oracle_knn(raw, batches[0], K)
        say(f"oracle (float64, host) in_memory {time.perf_counter() - t0:.1f}s")
        for k, (d, i) in mem["results"].items():
            ok &= check_knn(f"in_memory k={k}", raw, batches[0], d, i,
                            oracle, k)
        del mem

        disk_raw = raw[:DISK_SERIES]
        try:
            disk = phase_on_disk(disk_raw, batches[1], batches[2], DATA_DIR)
        finally:
            shutil.rmtree(DATA_DIR, ignore_errors=True)
        _report("on_disk", disk)
        t0 = time.perf_counter()
        oracle = oracle_knn(disk_raw, np.concatenate(batches[1:]), K)
        say(f"oracle (float64, host) on_disk {time.perf_counter() - t0:.1f}s")
        for j, name in enumerate(("search", "submit")):
            d, i = disk["results"][name]
            part = tuple(a[j * N_QUERIES:(j + 1) * N_QUERIES] for a in oracle)
            ok &= check_knn(f"on_disk {name} k={K}", disk_raw,
                            batches[1 + j], d, i, part, K)
        del disk

        dtw_raw = raw[:DTW_SERIES]
        out = phase_dtw(dtw_raw, batches[0])
        _report("dtw", out)
        t0 = time.perf_counter()
        all_d2 = oracle_dtw(dtw_raw, batches[0], out["r"])
        say(f"oracle (float64, host) dtw {time.perf_counter() - t0:.1f}s")
        ok &= check_knn(f"dtw r={out['r']} k={K}", dtw_raw, batches[0],
                        *out["results"], top_k(all_d2, K), K,
                        all_d2=all_d2, r=out["r"])
    if not ok:
        print("chip_smoke: answers disagree with the float64 oracle",
              file=sys.stderr)
        return 1
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
