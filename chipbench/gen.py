"""The run's data, made on the device from ``--seed``.

Every array comes from a ``jax.random`` key derived from the seed, so
the same seed gives the same collection and the same queries on every
run, and another seed another collection and other queries.  The
generators are found by name: the configuration's ``dataset`` in
``datasets/<name>.py`` (``chunk(key, i, rows=, length=)``), the
traffic's ``queries`` in ``queries/<name>.py`` (``make(cfg, traffic,
key, count, rows_of)``).

A collection is made in chunks of ``CHUNK`` series by one compiled
program: the in-memory path places the chunks into one device buffer,
the on-disk path writes them to its series file, and the reference
remakes the same chunks after the window, bit for bit, as one buffer or
as row-contiguous shards over a cell's chips (``collection_shards``).
No chunk is larger than 128 MiB, so generating a collection adds little
to the device's peak memory beyond the collection itself.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import parts

CHUNK = 1 << 17
# sub-streams of the seed's key
_COLLECTION, _QUERIES, _WARMUP = range(3)


def key(seed: int, stream: int) -> jax.Array:
    """The key of ``stream`` under ``seed``, any whole number below
    2**64 (both 32-bit halves count)."""
    base = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(base, seed >> 32), stream)


@functools.partial(jax.jit, donate_argnums=0)
def _place(buf: jax.Array, part: jax.Array, start: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(buf, part, start, axis=0)


def chunk_rows(cfg: dict) -> int:
    """Series in each chunk of the configuration's collection."""
    n_series = cfg["n_series"]
    rows = min(CHUNK, n_series)
    if n_series % rows:
        raise ValueError(f"{n_series} series do not cut into chunks of "
                         f"{rows}")
    return rows


def collection_chunks(cfg: dict, seed: int, chunks: range | None = None,
                      device: jax.Device | None = None
                      ) -> Iterator[jax.Array]:
    """The configuration's collection, chunk by chunk, on the device:
    the chunks numbered ``chunks`` (all by default), made on ``device``
    (the default device by default)."""
    dataset = parts.load("datasets", cfg["dataset"])
    rows = chunk_rows(cfg)
    if chunks is None:
        chunks = range(cfg["n_series"] // rows)
    put = ((lambda a: a) if device is None
           else functools.partial(jax.device_put, device=device))
    k = put(key(seed, _COLLECTION))
    for i in chunks:
        yield dataset.chunk(k, put(jnp.int32(i)), rows=rows,
                            length=cfg["length"])


def collection(cfg: dict, seed: int) -> jax.Array:
    """The whole collection (N, n) float32 on the device."""
    buf = jnp.zeros((cfg["n_series"], cfg["length"]), jnp.float32)
    start = 0
    for part in collection_chunks(cfg, seed):
        buf = _place(buf, part, jnp.int32(start))
        start += part.shape[0]
    return buf


def collection_shards(cfg: dict, seed: int, devices: Sequence[jax.Device]
                      ) -> list[jax.Array]:
    """The collection as ``d = len(devices)`` row-contiguous shards,
    shard ``j`` on ``devices[j]`` with rows ``[j N/d, (j+1) N/d)``: the
    layout that a ``P(axis)`` sharding over those devices gives.  Each
    shard is made on its own device from the chunks of
    ``collection_chunks``; a chunk that spans shards is made on each of
    their devices and cut.  The devices make one chunk each at a time,
    so a device holds its shard and one chunk.  Concatenated, the shards
    are ``collection`` bit for bit; on one device the one shard is
    ``collection`` itself."""
    if len(devices) == 1:
        with jax.default_device(devices[0]):
            return [collection(cfg, seed)]
    n_series, length = cfg["n_series"], cfg["length"]
    if n_series % len(devices):
        raise ValueError(f"{n_series} series do not cut into "
                         f"{len(devices)} shards")
    per, rows = n_series // len(devices), chunk_rows(cfg)
    shards, streams = [], []
    for j, dev in enumerate(devices):
        # filled on the device itself: jnp.zeros(device=) fills on the
        # default device and copies, a second shard's bytes there
        with jax.default_device(dev):
            shards.append(jnp.zeros((per, length), jnp.float32))
        chunks = range(j * per // rows, -(-(j + 1) * per // rows))
        streams.append(zip(chunks, collection_chunks(cfg, seed, chunks,
                                                     dev)))
    for step in zip(*streams):
        for j, (i, part) in enumerate(step):
            a, b = max(j * per, i * rows), min((j + 1) * per, (i + 1) * rows)
            if b - a < rows:
                part = part[a - i * rows:b - i * rows]
            shards[j] = _place(shards[j], part, jnp.int32(a - j * per))
        jax.block_until_ready(shards)
    return shards


def queries(cfg: dict, traffic: dict, seed: int, count: int,
            rows_of: Callable[[np.ndarray], jax.Array] | None = None, *,
            warmup: bool = False) -> np.ndarray:
    """(count, length) float32 queries on the host, where a client holds
    them.  ``rows_of(ids)`` returns collection rows, for generators that
    draw members.  ``warmup`` draws from a stream of its own, so warming
    up never sends a query of the window."""
    kind = parts.load("queries", traffic["queries"])
    k = key(seed, _WARMUP if warmup else _QUERIES)
    return np.asarray(kind.make(cfg, traffic, k, count, rows_of))
