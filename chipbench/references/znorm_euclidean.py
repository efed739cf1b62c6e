"""The plain reference for ``"reference": "znorm_euclidean"``
configurations, and its control.

Imports nothing of the program.  Exact k-NN under z-normalised
Euclidean distance, the semantics such a configuration states, by a
full scan of the benchmark's own collection (remade from the seed after
the window, so nothing the program built is read).  The collection is a
list of row-contiguous shards, each on its own device (one shard where
the cell runs on one chip); a series' id is its row in the shards'
concatenation.

1. a device scan (``scan_topm``) in float32 at ``Precision.HIGHEST``
   ranks every series of each shard for every distinct query of the
   window, on that shard's device, and keeps the best ``k + MARGIN`` of
   the shards' merged lists as candidates;
2. on the host, every candidate and every reported id is z-normalised
   and measured again in float64 in the direct form sum((q - x)^2), and
   the candidates' float64 order gives the oracle's top k.  The oracle
   is conclusive for a query when its k-th float64 distance lies below
   the last candidate's float32 distance minus ``scan_tol``: no series
   outside the candidates can then be closer; a query where that fails
   is scanned again with more candidates (``Oracle``).

``compare`` holds each answer of the window to the oracle and returns
the numbers that ``correct`` is decided on, each with its limit:

* ``bad_answers`` — answers whose k ids are not k distinct real ids;
* ``rank_misses`` — ranks whose id differs from the oracle's where the
  two float64 distances do not tie within twice the gap limit;
* ``gap_max`` — the widest gap between a reported squared distance and
  the float64 squared distance of its own id;
* ``gap_rms`` — the root mean square of those gaps over all answers.

The control (``control_answers``) is this reference put in the
program's place at the next precision below the configuration's
(float32 at ``Precision.HIGH``, three bf16 passes): its answers must
come out not correct.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

MARGIN = 16            # candidates kept beyond k
U = 2.0 ** -24         # float32 unit roundoff


def scan_tol(n: int) -> float:
    """Worst-case error of a float32 expanded-form squared distance
    between z-normalised series (||q||^2 = ||x||^2 = n): each length-n
    dot errs by at most gamma_n times the sum of |terms| <= n, and the
    z-normalisation by as much again.  3.2e-2 at n = 256."""
    gamma = n * U / (1 - n * U)
    return 2 * (2 * gamma + 4 * U) * (2 * n)


def _znorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, 1e-8)


def _merge(bd, bi, nd, ni, m):
    d = jnp.concatenate([bd, nd], axis=1)
    i = jnp.concatenate([bi, ni], axis=1)
    d, i = jax.lax.sort((d, i), dimension=1, num_keys=2)
    return d[:, :m], i[:, :m]


def _dot_high(a, b):
    """a @ b.T in three bf16 passes (hi.hi + hi.lo + lo.hi, float32
    accumulation): ``Precision.HIGH`` spelled out, so that it computes
    the same on every backend."""
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    (ah, al), (bh, bl) = split(a), split(b)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return dot(ah, bh.T) + dot(ah, bl.T) + dot(al, bh.T)


def _dot(a, b, precision):
    if precision == "high":
        return _dot_high(a, b)
    return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "precision", "chunk"))
def _scan_tile(x, q, *, m: int, precision: str, chunk: int):
    qz = _znorm(q)
    qq = jnp.sum(qz * qz, axis=-1)

    def body(carry, start):
        bd, bi = carry
        xz = _znorm(jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=0))
        xx = jnp.sum(xz * xz, axis=-1)
        d = qq[:, None] + xx[None, :] - 2.0 * _dot(qz, xz, precision)
        d = jnp.maximum(d, 0.0)
        nd, ni = jax.lax.top_k(-d, m)
        return _merge(bd, bi, -nd, ni + start, m), None

    init = (jnp.full((q.shape[0], m), jnp.inf, jnp.float32),
            jnp.full((q.shape[0], m), -1, jnp.int32))
    starts = jnp.arange(0, x.shape[0], chunk, dtype=jnp.int32)
    (bd, bi), _ = jax.lax.scan(body, init, starts)
    return bd, bi


def _starts(x: Sequence[jax.Array]) -> np.ndarray:
    """The first id of each shard, and the number of series at the end."""
    return np.cumsum([0] + [s.shape[0] for s in x])


def scan_topm(x: Sequence[jax.Array], q: np.ndarray, m: int, *,
              precision: str = "highest", tile: int = 256,
              chunk: int = 1 << 17) -> tuple[np.ndarray, np.ndarray]:
    """Top-m of every query by a float32 scan of the whole collection,
    held as the shards ``x`` ((N_j, n) each) -> (squared distances,
    ids), (Q, m), ascending by (distance, id); m <= N.  Each shard is
    scanned on its own device, all shards of a tile dispatched before
    any is read, and keeps its best min(m, N_j) with ids offset by its
    first row; the shards' lists merge by (distance, id), and the top-m
    of the union is the merged top-m of the shards.  Queries run in
    tiles of ``tile``, each shard in chunks of ``chunk`` series; one
    program for all tiles of a shard.  ``precision`` is "highest"
    (float32 at ``Precision.HIGHEST``) or "high" (three bf16 passes,
    ``_dot_high``)."""
    starts = _starts(x)
    chunks = [min(chunk, s.shape[0]) for s in x]
    for s, c in zip(x, chunks):
        if s.shape[0] % c:
            raise ValueError(f"{s.shape[0]} series do not cut into chunks "
                             f"of {c}")
    tile = min(tile, len(q))
    out_d, out_i = [], []
    for s in range(0, len(q), tile):
        part = q[s:s + tile]
        pad = tile - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[:1], pad, 0)])
        part = jnp.asarray(part)
        found = [_scan_tile(xs, part, m=min(m, xs.shape[0]),
                            precision=precision, chunk=c)
                 for xs, c in zip(x, chunks)]
        d = np.concatenate([np.asarray(d) for d, _ in found], axis=1)
        i = np.concatenate([np.asarray(i) + start
                            for (_, i), start in zip(found, starts)], axis=1)
        if len(x) > 1:     # one shard's list is in that order already
            order = np.lexsort((i, d), axis=1)[:, :m]
            d = np.take_along_axis(d, order, 1)
            i = np.take_along_axis(i, order, 1)
        out_d.append(d[:tile - pad])
        out_i.append(i[:tile - pad])
    return np.concatenate(out_d), np.concatenate(out_i)


def znorm64(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    mu = a.mean(axis=-1, keepdims=True)
    sd = a.std(axis=-1, keepdims=True)
    return (a - mu) / np.maximum(sd, 1e-8)


def take(x: Sequence[jax.Array], ids: np.ndarray, block: int = 1 << 16
         ) -> np.ndarray:
    """The series ``ids`` (R,), each in [0, N), on the host (R, n)
    float32: each from the shard that holds it, in blocks of ``block``
    ids."""
    starts = _starts(x)
    ids = np.asarray(ids)
    owner = np.searchsorted(starts, ids, side="right") - 1
    rows = np.zeros((len(ids), x[0].shape[1]), np.float32)
    for j, (xs, start) in enumerate(zip(x, starts)):
        at = np.flatnonzero(owner == j)
        local = (ids[at] - start).astype(np.int32)
        for s in range(0, len(at), block):
            rows[at[s:s + block]] = np.asarray(
                jnp.take(xs, jnp.asarray(local[s:s + block]), axis=0))
    return rows


def exact_d2(x: Sequence[jax.Array], q: np.ndarray, ids: np.ndarray
             ) -> np.ndarray:
    """float64 squared distances of each query row ``q`` (R, n) to the
    series ``ids`` (R, j) of the sharded collection ``x``, in the direct
    form; ids outside [0, N) give NaN."""
    n_series = _starts(x)[-1]
    flat = ids.reshape(-1)
    ok = (flat >= 0) & (flat < n_series)
    rows = take(x, np.where(ok, flat, 0))
    qz = np.repeat(znorm64(q), ids.shape[1], axis=0)
    d = np.sum((znorm64(rows) - qz) ** 2, axis=1)
    return np.where(ok, d, np.nan).reshape(ids.shape)


class Oracle:
    """Exact k-NN of the distinct queries ``q`` (U, n) over the sharded
    collection ``x``.  A query whose candidates cannot rule out a closer
    series outside them is scanned again with eight times the
    candidates; each shard's scan keeps at most all of its series
    (``scan_topm``), so the candidates can grow to the whole
    collection."""

    def __init__(self, x: Sequence[jax.Array], q: np.ndarray, k: int):
        n_series = _starts(x)[-1]
        self.ids = np.zeros((len(q), k), np.int64)
        self.d2 = np.zeros((len(q), k))
        todo, m = np.arange(len(q)), k + MARGIN
        while todo.size:
            m = min(m, n_series)
            d32, cand = scan_topm(x, q[todo], m)
            d64 = exact_d2(x, q[todo], cand)
            order = np.lexsort((cand, d64), axis=1)
            self.ids[todo] = np.take_along_axis(cand, order, 1)[:, :k]
            self.d2[todo] = np.take_along_axis(d64, order, 1)[:, :k]
            if m == n_series:
                break
            open_ = ~(self.d2[todo, -1] < d32[:, -1] - scan_tol(q.shape[1]))
            todo, m = todo[open_], 8 * m


def compare(x: Sequence[jax.Array], pool: np.ndarray, rows: np.ndarray,
            dist: np.ndarray, idx: np.ndarray, k: int,
            limits: dict) -> dict:
    """Hold every answer to the oracle over the sharded collection
    ``x``.  ``rows`` (A,) are the pool rows the answers were asked for;
    ``dist``/``idx`` (A, k) the answers (distances, not squared).
    -> {name: {"value", "limit"}}, ``correct`` iff every value is within
    its limit."""
    uniq, inv = np.unique(rows, return_inverse=True)
    oracle = Oracle(x, pool[uniq], k)
    n_series = _starts(x)[-1]
    idx = np.asarray(idx, np.int64)
    distinct = np.array([len(set(r.tolist())) == k for r in idx], bool)
    valid = distinct & np.all((idx >= 0) & (idx < n_series), axis=1)
    true = exact_d2(x, pool[rows], idx)
    rep = np.asarray(dist, np.float64) ** 2
    gap = np.abs(rep - true)[valid]
    tie = 2 * limits["gap_max"]
    or_ids, or_d2 = oracle.ids[inv], oracle.d2[inv]
    miss = (idx != or_ids) & ~(np.abs(true - or_d2) <= tie)
    values = {
        "bad_answers": int((~valid).sum()),
        "rank_misses": int(miss[valid].sum()),
        "gap_max": float(gap.max()) if gap.size else 0.0,
        "gap_rms": float(np.sqrt(np.mean(gap ** 2))) if gap.size else 0.0,
    }
    return {name: {"value": v, "limit": limits.get(name, 0)}
            for name, v in values.items()}


def control_answers(x: Sequence[jax.Array], pool: np.ndarray,
                    rows: np.ndarray, k: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """The control: the reference's scan in the program's place at
    ``Precision.HIGH`` (three bf16 passes, the step below the
    configuration's float32 at HIGHEST) -> (dist, idx) (A, k)."""
    uniq, inv = np.unique(rows, return_inverse=True)
    d2, ids = scan_topm(x, pool[uniq], k, precision="high")
    return np.sqrt(d2)[inv].astype(np.float32), ids[inv]
