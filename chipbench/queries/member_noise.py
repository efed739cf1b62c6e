"""A collection member drawn uniformly, z-normalised, plus Gaussian
noise of standard deviation ``sigma``: the controlled-hardness query
method of Zoumpatianos et al., VLDB J 2018."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _znorm(x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-8)


@functools.partial(jax.jit, static_argnames=("n_series", "count"))
def _member_ids(k: jax.Array, *, n_series: int, count: int) -> jax.Array:
    return jax.random.randint(k, (count,), 0, n_series)


@jax.jit
def _noisy(rows: jax.Array, k: jax.Array, sigma: jax.Array) -> jax.Array:
    z = _znorm(rows)
    return z + sigma * jax.random.normal(k, z.shape, jnp.float32)


def make(cfg: dict, traffic: dict, k: jax.Array, count: int, rows_of
         ) -> jax.Array:
    """``rows_of(ids)`` returns the collection's rows ``ids``."""
    k_ids, k_noise = jax.random.split(k)
    ids = _member_ids(k_ids, n_series=cfg["n_series"], count=count)
    return _noisy(jnp.asarray(rows_of(np.asarray(ids))), k_noise,
                  jnp.float32(traffic["sigma"]))
