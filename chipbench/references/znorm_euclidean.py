"""The plain reference for ``"reference": "znorm_euclidean"``
configurations, and its control.

Imports nothing of the program.  Exact k-NN under z-normalised
Euclidean distance, the semantics such a configuration states, by a
full scan of the benchmark's own collection (remade from the seed after
the window, so nothing the program built is read):

1. a device scan (``scan_topm``) in float32 at ``Precision.HIGHEST``
   ranks every series for every distinct query of the window and keeps
   the best ``k + MARGIN`` as candidates;
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


def scan_topm(x: jax.Array, q: np.ndarray, m: int, *,
              precision: str = "highest", tile: int = 256,
              chunk: int = 1 << 17) -> tuple[np.ndarray, np.ndarray]:
    """Top-m of every query by a float32 scan of the whole collection
    ``x`` (N, n) on the device -> (squared distances, ids), (Q, m),
    ascending by (distance, id).  Queries run in tiles of ``tile``, the
    collection in chunks of ``chunk`` series; one program for all tiles.
    ``precision`` is "highest" (float32 at ``Precision.HIGHEST``) or
    "high" (three bf16 passes, ``_dot_high``)."""
    n_series = x.shape[0]
    chunk = min(chunk, n_series)
    if n_series % chunk:
        raise ValueError(f"{n_series} series do not cut into chunks of "
                         f"{chunk}")
    tile = min(tile, len(q))
    out_d, out_i = [], []
    for s in range(0, len(q), tile):
        part = q[s:s + tile]
        pad = tile - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[:1], pad, 0)])
        d, i = _scan_tile(x, jnp.asarray(part), m=m, precision=precision,
                          chunk=chunk)
        out_d.append(np.asarray(d)[:tile - pad])
        out_i.append(np.asarray(i)[:tile - pad])
    return np.concatenate(out_d), np.concatenate(out_i)


def znorm64(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    mu = a.mean(axis=-1, keepdims=True)
    sd = a.std(axis=-1, keepdims=True)
    return (a - mu) / np.maximum(sd, 1e-8)


def exact_d2(x: jax.Array, q: np.ndarray, ids: np.ndarray,
             block: int = 1 << 16) -> np.ndarray:
    """float64 squared distances of each query row ``q`` (R, n) to the
    series ``ids`` (R, j) of ``x``, in the direct form; ids outside
    [0, N) give NaN."""
    n_series = x.shape[0]
    flat = ids.reshape(-1)
    ok = (flat >= 0) & (flat < n_series)
    safe = np.where(ok, flat, 0).astype(np.int32)
    rows = np.concatenate([
        np.asarray(jnp.take(x, jnp.asarray(safe[s:s + block]), axis=0))
        for s in range(0, len(safe), block)]) if len(safe) else \
        np.zeros((0, x.shape[1]), np.float32)
    qz = np.repeat(znorm64(q), ids.shape[1], axis=0)
    d = np.sum((znorm64(rows) - qz) ** 2, axis=1)
    return np.where(ok, d, np.nan).reshape(ids.shape)


class Oracle:
    """Exact k-NN of the distinct queries ``q`` (U, n) over ``x``.  A
    query whose candidates cannot rule out a closer series outside them
    is scanned again with eight times the candidates."""

    def __init__(self, x: jax.Array, q: np.ndarray, k: int):
        n_series = x.shape[0]
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


def compare(x: jax.Array, pool: np.ndarray, rows: np.ndarray,
            dist: np.ndarray, idx: np.ndarray, k: int,
            limits: dict) -> dict:
    """Hold every answer to the oracle.  ``rows`` (A,) are the pool rows
    the answers were asked for; ``dist``/``idx`` (A, k) the answers
    (distances, not squared).  -> {name: {"value", "limit"}}, ``correct``
    iff every value is within its limit."""
    uniq, inv = np.unique(rows, return_inverse=True)
    oracle = Oracle(x, pool[uniq], k)
    n_series = x.shape[0]
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


def control_answers(x: jax.Array, pool: np.ndarray, rows: np.ndarray,
                    k: int) -> tuple[np.ndarray, np.ndarray]:
    """The control: the reference's scan in the program's place at
    ``Precision.HIGH`` (three bf16 passes, the step below the
    configuration's float32 at HIGHEST) -> (dist, idx) (A, k)."""
    uniq, inv = np.unique(rows, return_inverse=True)
    d2, ids = scan_topm(x, pool[uniq], k, precision="high")
    return np.sqrt(d2)[inv].astype(np.float32), ids[inv]
