"""Fresh random walks, not members of the collection: the papers'
Synthetic query workload."""
from __future__ import annotations

import functools

import jax

import parts


@functools.partial(jax.jit, static_argnames=("count", "length"))
def _walks(k, *, count, length):
    return parts.load("datasets", "random_walk").walks(k, count, length)


def make(cfg: dict, traffic: dict, k: jax.Array, count: int, rows_of
         ) -> jax.Array:
    return _walks(k, count=count, length=cfg["length"])
