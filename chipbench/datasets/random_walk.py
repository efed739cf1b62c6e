"""The papers' Synthetic collection: random walks, cumulative sums of
N(0, 1) steps, as ``repro.data.random_walk`` defines them.  The
benchmark's own copy, so that no change to the program can move the
yardstick."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def walks(k: jax.Array, n_series: int, length: int) -> jax.Array:
    """(n_series, length) float32 random walks."""
    return jnp.cumsum(jax.random.normal(k, (n_series, length), jnp.float32),
                      axis=1)


@functools.partial(jax.jit, static_argnames=("rows", "length"))
def chunk(k: jax.Array, i: jax.Array, *, rows: int, length: int
          ) -> jax.Array:
    """Chunk ``i`` of the collection whose key is ``k``."""
    return walks(jax.random.fold_in(k, i), rows, length)
