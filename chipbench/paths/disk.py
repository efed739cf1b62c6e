"""Served path of an index on disk (ParIS+).

Set-up writes the collection chunk by chunk as a headerless float32
series file, warms the build's programs on a small file of the same
chunk shapes, then times the users' build: ``storage.run_pipeline``
into a fresh directory and ``storage.open_index``.  Requests go to one
``storage.SearchSession`` whose block cache holds 1/``cache_fraction``
of the blocks; its walk knobs stay at the program's defaults.
"""
from __future__ import annotations

import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen


class Served:
    def __init__(self, ctx):
        from repro import storage
        cfg = ctx.cfg
        self.k = ctx.traffic["k"]
        self.work = ctx.workdir / "disk"
        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        series = self.work / "series.f32"
        with open(series, "wb") as f:
            for part in gen.collection_chunks(cfg, ctx.seed):
                f.write(np.asarray(part).tobytes())
        store = storage.SeriesStore(path=series, length=cfg["length"])
        kw = dict(w=cfg["w"], card=cfg["card"], capacity=cfg["capacity"],
                  workers=cfg["pipeline_workers"])
        # the build's programs, compiled or loaded on a file whose chunks
        # and shards have the timed build's shapes
        warm_rows = min(store.n_series, cfg["pipeline_workers"] << 14)
        warm = storage.SeriesStore.write(self.work / "warm.f32",
                                         store.read(0, warm_rows))
        storage.run_pipeline(warm, self.work / "warm" / "index.dsix", **kw)
        shutil.rmtree(self.work / "warm")
        t0 = time.perf_counter()
        path, _ = storage.run_pipeline(store, self.work / "idx" / "index.dsix",
                                       **kw)
        index = storage.open_index(path)
        jax.block_until_ready(index)
        self.build_s = time.perf_counter() - t0
        self.session = storage.SearchSession(
            index, cache_blocks=max(2, index.n_blocks // cfg["cache_fraction"]))
        rows_of = lambda ids: store.memmap()[np.asarray(ids)]
        self.pool = ctx.make_pool(rows_of)
        self.warm = ctx.make_warmup(rows_of)

    def search(self, q: np.ndarray):
        res = self.session.search(jnp.asarray(q), k=self.k)
        jax.block_until_ready((res.dist, res.idx))
        return res

    def pull(self, res) -> tuple[np.ndarray, np.ndarray, dict]:
        d, i, visited = jax.device_get(
            (res.dist, res.idx, res.stats.blocks_visited))
        return d, i, {"blocks_visited": int(np.sum(visited)),
                      "disk_blocks_read": int(res.io.blocks_fetched)}

    def close(self) -> None:
        self.session.close()
        shutil.rmtree(self.work, ignore_errors=True)
