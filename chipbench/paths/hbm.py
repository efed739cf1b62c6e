"""Served path of an index held in HBM (MESSI).

Set-up makes the collection on the device, compiles (or loads) the
build program, then times the users' build call, ``core.build``.  Each
request is one ``core.search`` call over that index.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import gen


class Served:
    def __init__(self, ctx):
        from repro import core
        self._search = core.search
        cfg = ctx.cfg
        self.k = ctx.traffic["k"]
        x = gen.collection(cfg, ctx.seed)
        kw = dict(w=cfg["w"], card=cfg["card"], capacity=cfg["capacity"])
        core.build.lower(x, **kw).compile()
        t0 = time.perf_counter()
        self.index = core.build(x, **kw)
        jax.block_until_ready(self.index)
        self.build_s = time.perf_counter() - t0
        rows_of = lambda ids: jnp.take(x, jnp.asarray(ids), axis=0)
        self.pool = ctx.make_pool(rows_of)
        self.warm = ctx.make_warmup(rows_of)

    def search(self, q: np.ndarray):
        res = self._search(self.index, jnp.asarray(q), k=self.k)
        return jax.block_until_ready(res)

    def pull(self, res) -> tuple[np.ndarray, np.ndarray, dict]:
        d, i, visited = jax.device_get(
            (res.dist, res.idx, res.stats.blocks_visited))
        return d, i, {"blocks_visited": int(np.sum(visited))}

    def close(self) -> None:
        self.index = None
