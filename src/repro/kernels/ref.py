"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret mode on
CPU, shape/dtype sweeps in tests/test_kernels_*.py) and the fallback
implementation on platforms without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import isax

INF = jnp.float32(jnp.finfo(jnp.float32).max)
_PAD_ID_KEY = jnp.int32(jnp.iinfo(jnp.int32).max)   # sort key for id < 0


def paa_sax_ref(x: jax.Array, w: int, card: int) -> tuple[jax.Array, jax.Array]:
    """(N, n) f32 -> PAA (N, w) f32, symbols (N, w) int32. Input already z-normed."""
    p = isax.paa(x, w)
    return p, isax.sax_from_paa(p, card)


def isax_summarize_ref(x: jax.Array, *, w: int, card: int,
                       normalize: bool = True
                       ) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels/isax_summarize.py: optional z-norm + PAA/SAX."""
    xx = isax.znorm(x) if normalize else x
    return paa_sax_ref(xx, w, card)


def lb_block_ref(q_paa: jax.Array, env: jax.Array, n: int) -> jax.Array:
    """Block-envelope lower bounds. q_paa (Q, w), env (B, w, 2) -> (Q, B) f32 (squared)."""
    return isax.mindist_paa_bounds_sq(q_paa[:, None, :], env[None], n)


def lb_series_ref(q_paa: jax.Array, bounds: jax.Array, n: int) -> jax.Array:
    """Per-series lower bounds. q_paa (Q, w), bounds (N, w, 2) -> (Q, N) f32 (squared)."""
    return isax.mindist_paa_bounds_sq(q_paa[:, None, :], bounds[None], n)


def lb_scan_ref(q_paa: jax.Array, lo: jax.Array, hi: jax.Array, *,
                n: int) -> jax.Array:
    """Oracle for kernels/lb_scan.py: planar MINDIST lower bounds.

    q_paa (Q, w); lo/hi (w, N) -> (Q, N) squared bounds with the n/w
    scale factor (``n`` is the raw series length).
    """
    w = q_paa.shape[1]
    qe = q_paa[:, :, None]
    d = jnp.maximum(jnp.maximum(lo[None] - qe, qe - hi[None]), 0.0)
    return (float(n) / float(w)) * jnp.sum(d * d, axis=1)


def batch_l2_ref(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared Euclidean distances. q (Q, n), x (N, n) -> (Q, N) f32.

    Uses the expanded form ||q||^2 + ||x||^2 - 2 q.x (MXU-friendly, matches the
    kernel) with a clamp at zero for numerical safety.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qq = jnp.sum(q * q, axis=-1, keepdims=True)          # (Q, 1)
    xx = jnp.sum(x * x, axis=-1)[None, :]                # (1, N)
    # HIGHEST: at the TPU's default f32 precision (one bf16 pass) the
    # cross term errs by ~1e-1 at n=256, far above the k-NN gaps
    cross = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qq + xx - 2.0 * cross, 0.0)


def batch_l2_exact_ref(q: jax.Array, x: jax.Array) -> jax.Array:
    """Direct-subtraction oracle (most accurate; O(Q*N*n) memory)."""
    d = q[:, None, :] - x[None, :, :]
    return jnp.sum(d * d, axis=-1)


def topk_by_dist_id(d: jax.Array, ids: jax.Array, k: int
                    ) -> tuple[jax.Array, jax.Array]:
    """Ascending (distance, id)-lexicographic top-k along the last axis.

    Mirrors ``core.frontier._topk_by_dist_id`` (duplicated here because
    ``frontier`` imports ``ops`` imports this module): ids < 0 sort last
    among equal distances and come back normalized to -1.  When k exceeds
    the candidate count the result is padded with (INF, -1) rows.
    """
    m = d.shape[-1]
    if k > m:
        pad = k - m
        d = jnp.concatenate(
            [d, jnp.full(d.shape[:-1] + (pad,), INF, d.dtype)], axis=-1)
        ids = jnp.concatenate(
            [ids, jnp.full(ids.shape[:-1] + (pad,), -1, ids.dtype)], axis=-1)
    key = jnp.where(ids >= 0, ids, _PAD_ID_KEY)
    order = jnp.lexsort((key, d), axis=-1)[..., :k]
    sd = jnp.take_along_axis(d, order, axis=-1)
    si = jnp.take_along_axis(ids, order, axis=-1)
    return sd, jnp.where(si >= 0, si, -1)


def block_topk_ref(d: jax.Array, ids: jax.Array, k: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels/block_topk.py. d (Q, C) f32, ids (Q, C) int32.

    Contract (the engine's masking discipline): within a row ids >= 0 are
    distinct, and every lane with id < 0 carries d == INF — pad lanes are
    interchangeable, so the kernel may collapse duplicates among them.
    """
    return topk_by_dist_id(d, ids, k)


def fused_panel_topk_ref(q: jax.Array, q_paa: jax.Array, block: jax.Array,
                         lo: jax.Array, hi: jax.Array, ids: jax.Array,
                         thr: jax.Array, *, k: int, n: int
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for kernels/fused_refine.py: the unfused composition the
    engine's ED ``panel_refine`` ran before fusion.

    q (Q, n), q_paa (Q, w), block (C, n), lo/hi (w, C) planar bounds,
    ids (C,) int32, thr (Q,) effective pruning bound (callers fold the
    per-query active mask in as -inf).  Returns the (dist, id)-lex top-k
    of the live lanes — dead lanes are (INF, -1) — plus the per-query
    live-lane count (the ``series_refined`` stat).
    """
    w = q_paa.shape[-1]
    qe = q_paa[:, :, None]                                    # (Q, w, 1)
    dd = jnp.maximum(jnp.maximum(lo[None] - qe, qe - hi[None]), 0.0)
    lb = (n / w) * jnp.sum(dd * dd, axis=1)                   # (Q, C)
    live = (lb < thr[:, None]) & (ids >= 0)[None, :]
    d = jnp.where(live, batch_l2_ref(q, block), INF)
    idm = jnp.where(live, ids[None, :], -1)
    sd, si = topk_by_dist_id(d, idm, k)
    return sd, si, jnp.sum(live, axis=1, dtype=jnp.int32)


def gathered_panel_topk_ref(q: jax.Array, q_paa: jax.Array, raw: jax.Array,
                            slo: jax.Array, shi: jax.Array, ids: jax.Array,
                            idxs: jax.Array, active: jax.Array,
                            thr: jax.Array, bound: jax.Array, *, k: int,
                            n: int
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for kernels/fused_refine.py's gathered entry: the query-major
    refine the engine ran before fusion, which gathers each query's K
    blocks into a (Q, K, C, n) copy.

    q (Q, n), q_paa (Q, w); raw (B, C, n), slo/shi (B, w, C), ids (B, C)
    the whole index; idxs (Q, K) block ids, active (Q, K) bool, thr (Q,)
    pruning bound, bound (Q,) the largest distance selected.  Returns the
    (dist, id)-lex top-k of each query's live lanes within ``bound`` over
    its K blocks — the rest are (INF, -1) — and the live count.  With
    ``bound`` the frontier's k-th, inserting the result leaves the same
    frontier as inserting every live lane: no lane above the k-th can
    enter it or lower a held one.  The distance is
    ``core.frontier.query_block_l2``'s expanded form, repeated here
    because ``frontier`` imports this module.
    """
    qn, w = q_paa.shape
    blocks = raw[idxs]                                        # (Q, K, C, n)
    idg = ids[idxs]                                           # (Q, K, C)
    qe = q_paa[:, None, :, None]                              # (Q, 1, w, 1)
    dd = jnp.maximum(jnp.maximum(slo[idxs] - qe, qe - shi[idxs]), 0.0)
    s_lb = (n / w) * jnp.sum(dd * dd, axis=2)                 # (Q, K, C)
    live = (s_lb < thr[:, None, None]) & active[..., None] & (idg >= 0)
    qq = jnp.sum(q * q, axis=-1)[:, None, None]               # (Q, 1, 1)
    xx = jnp.sum(blocks * blocks, axis=-1)                    # (Q, K, C)
    cross = jnp.einsum("qn,q...n->q...", q, blocks,
                       precision=jax.lax.Precision.HIGHEST)
    d = jnp.maximum(qq + xx - 2.0 * cross, 0.0)
    keep = live & (d <= bound[:, None, None])
    sd, si = topk_by_dist_id(jnp.where(keep, d, INF).reshape(qn, -1),
                             jnp.where(keep, idg, -1).reshape(qn, -1), k)
    return sd, si, jnp.sum(live, axis=(1, 2), dtype=jnp.int32)


def dtw_band_ref(a: jax.Array, b: jax.Array, r: int) -> jax.Array:
    """Exact squared-DTW with band r. a (..., n) vs b (..., n), broadcast.

    Anti-diagonal DP: diag k holds cells (i, j) with i+j == k; each
    diagonal depends only on the previous two, so the whole diagonal
    updates in one vector op.  Cells outside the band are +INF.  The
    Pallas wavefront kernel (kernels/dtw_band.py) mirrors these ops
    EXACTLY — both are pure elementwise arithmetic with no reductions,
    so the two agree bit-for-bit (locked in tests/test_kernels.py).
    """
    a, b = jnp.broadcast_arrays(a, b)
    n = a.shape[-1]
    i_idx = jnp.arange(n)

    def diag_cost(k):
        # cell (i, k-i) for i in [0, n)
        j = k - i_idx
        valid = (j >= 0) & (j < n) & (jnp.abs(i_idx - j) <= r)
        jc = jnp.clip(j, 0, n - 1)
        c = (a[..., i_idx] - jnp.take(b, jc, axis=-1)) ** 2
        return jnp.where(valid, c, INF)

    # dp diagonals indexed by i (row); shifting aligns (i-1, j), (i, j-1),
    # (i-1, j-1)
    def shift_down(d):  # d[i] -> d[i-1]
        return jnp.concatenate([jnp.full(d.shape[:-1] + (1,), INF),
                                d[..., :-1]], axis=-1)

    def body(carry, k):
        prev, prev2 = carry   # diag k-1, diag k-2 (indexed by i)
        c = diag_cost(k)
        best = jnp.minimum(jnp.minimum(prev, shift_down(prev)),
                           shift_down(prev2))
        cur = c + jnp.where(k == 0, 0.0, best)
        cur = jnp.minimum(cur, INF)   # keep +INF cells from overflowing
        return (cur, prev), None

    init_shape = a.shape[:-1] + (n,)
    prev = jnp.full(init_shape, INF)
    prev2 = jnp.full(init_shape, INF)
    (last, second), _ = jax.lax.scan(body, (prev, prev2),
                                     jnp.arange(2 * n - 1))
    return last[..., n - 1]   # cell (n-1, n-1) lives on diag 2n-2 at i=n-1


def dtw_band_panel_ref(q: jax.Array, x: jax.Array, *, r: int
                       ) -> jax.Array:
    """Oracle for kernels/dtw_band.py's panel entry: q (Q, n) against
    a shared panel x (C, n) -> (Q, C), or gathered x (Q, M, n) ->
    (Q, M), by broadcasting into dtw_band_ref."""
    if x.ndim == 2:
        return dtw_band_ref(q[:, None, :], x[None, :, :], r)
    return dtw_band_ref(q[:, None, :], x, r)


def ssm_scan_ref(xc, dt, bm, cm, a_log):
    """Sequential oracle for kernels/ssm_scan.py (same math as
    models/mamba's recurrence with b = dt * xc * B)."""
    f32 = jnp.float32
    xc, dt, bm, cm = (t.astype(f32) for t in (xc, dt, bm, cm))
    a = jnp.exp(dt[..., None] * a_log.astype(f32)[None, None])   # (B,S,D,N)
    b = (dt * xc)[..., None] * bm[:, :, None, :]

    def step(h, inp):
        at, bt, ct = inp
        h = at * h + bt
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    bsz, s, d = xc.shape
    h0 = jnp.zeros((bsz, d, bm.shape[-1]), f32)
    _, y = jax.lax.scan(step, h0, (a.swapaxes(0, 1), b.swapaxes(0, 1),
                                   cm.swapaxes(0, 1)))
    return y.swapaxes(0, 1)
