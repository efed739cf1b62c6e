"""Pallas TPU kernel: fused lower-bound + distance + top-k select.

One pass over a raw (C, n) block tile does everything the engine's ED
``panel_refine`` used to do in three XLA ops with (Q, C) HBM
intermediates between them:

  1. per-series MINDIST lower bound from the planar (w, TC) region
     bounds (VPU, same arithmetic as kernels/lb_scan.py);
  2. the live mask ``(lb < thr) & (id >= 0)`` — a tile with no live lane
     skips the distance matmul entirely (``pl.when``), the kernel-level
     form of the paper's "fewer real distance calculations";
  3. the expanded-form ||q||^2 + ||x||^2 - 2 q.x distances on the MXU
     (same tiling rules as kernels/batch_l2.py — see below);
  4. (dist, id)-lexicographic top-k select of the live lanes
     (kernels/block_topk.py), accumulated across C tiles through the
     revisited (Q, k) output block.

Only (Q, k) candidates and the (Q,) live-lane count ever reach HBM; the
(Q, C) lower-bound and distance panels never materialize.

Oracle contract: selection is integer-exact and feeding the frontier a
top-k subset provably preserves the final top-k
(``Frontier.insert_topk``), so ids and live counts match the oracle
exactly.  Distances match within the worst-case f32 error of the
expanded form, (4 gamma_n + 8u)(||q||^2 + ||x||^2) on squared
distances (DESIGN.md §8) — not bit-for-bit: the compiler picks a dot's
summation order by its shapes and fusion context, so two evaluations of
the same expanded form may differ in the last bits.  The default tile
sizes still mirror kernels/batch_l2.py (tq = min(128, max(8, Q)),
tc = min(256, max(128, C))).

Dead lanes come back as (INF, -1) — exactly what the engine's unfused
path inserted — and callers fold the per-query active mask into ``thr``
as -inf rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_topk import INF, _PAD_ID_KEY, select_topk

_NEG_INF = jnp.float32(-jnp.inf)


def _kernel(q_ref, qp_ref, thr_ref, x_ref, lo_ref, hi_ref, id_ref,
            out_d_ref, out_i_ref, out_n_ref, *, k: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full(out_d_ref.shape, INF, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)
        out_n_ref[...] = jnp.zeros(out_n_ref.shape, jnp.int32)

    qp = qp_ref[...]                                        # (TQ, w)
    lo = lo_ref[...]                                        # (w, TC)
    hi = hi_ref[...]                                        # (w, TC)
    ids = id_ref[...]                                       # (1, TC)
    thr = thr_ref[...]                                      # (TQ, 1)

    qe = qp[:, :, None]                                     # (TQ, w, 1)
    dd = jnp.maximum(jnp.maximum(lo[None] - qe, qe - hi[None]), 0.0)
    lb = scale * jnp.sum(dd * dd, axis=1)                   # (TQ, TC)
    live = (lb < thr) & (ids >= 0)                          # (TQ, TC)
    out_n_ref[...] += jnp.sum(live, axis=1, dtype=jnp.int32)[:, None]

    @pl.when(jnp.any(live))
    def _refine():
        q = q_ref[...].astype(jnp.float32)                  # (TQ, n)
        x = x_ref[...].astype(jnp.float32)                  # (TC, n)
        qq = jnp.sum(q * q, axis=-1, keepdims=True)         # (TQ, 1)
        xx = jnp.sum(x * x, axis=-1)[None, :]               # (1, TC)
        # HIGHEST: as in batch_l2, one bf16 pass would err by ~1e-1
        cross = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)             # (TQ, TC) on MXU
        d = jnp.maximum(qq + xx - 2.0 * cross, 0.0)
        d = jnp.where(live, d, INF)
        key = jnp.broadcast_to(jnp.where(live, ids, _PAD_ID_KEY), d.shape)
        td, ti = select_topk(d, key, k)
        rd = jnp.concatenate([out_d_ref[...], td], axis=-1)     # (TQ, 2k)
        ri = jnp.concatenate([out_i_ref[...], ti], axis=-1)
        md, mi = select_topk(rd, jnp.where(ri >= 0, ri, _PAD_ID_KEY), k)
        out_d_ref[...] = md
        out_i_ref[...] = mi


@functools.partial(jax.jit, static_argnames=("k", "n", "tile_q", "tile_c",
                                             "interpret"))
def fused_panel_topk(q: jax.Array, q_paa: jax.Array, block: jax.Array,
                     lo: jax.Array, hi: jax.Array, ids: jax.Array,
                     thr: jax.Array, *, k: int, n: int, tile_q: int = 128,
                     tile_c: int = 256, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q (Q, n); q_paa (Q, w); block (C, n); lo/hi (w, C) planar bounds;
    ids (C,) int32; thr (Q,) effective bound (-inf disables a query).
    -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,) int32)."""
    qn, w = q_paa.shape
    c = block.shape[0]
    # batch_l2's tiling rules
    tq = min(tile_q, max(8, qn))
    tc = min(tile_c, max(128, c))

    qpad = (-qn) % tq
    if qpad:
        q = jnp.concatenate([q, jnp.zeros((qpad, n), q.dtype)], 0)
        q_paa = jnp.concatenate([q_paa, jnp.zeros((qpad, w), q_paa.dtype)], 0)
        thr = jnp.concatenate([thr, jnp.full((qpad,), _NEG_INF)], 0)
    cpad = (-c) % tc
    if cpad:
        block = jnp.concatenate(
            [block, jnp.zeros((cpad, n), block.dtype)], 0)
        lo = jnp.concatenate([lo, jnp.zeros((w, cpad), lo.dtype)], 1)
        hi = jnp.concatenate([hi, jnp.zeros((w, cpad), hi.dtype)], 1)
        ids = jnp.concatenate([ids, jnp.full((cpad,), -1, jnp.int32)], 0)

    grid = (q.shape[0] // tq, block.shape[0] // tc)
    out_d, out_i, out_n = pl.pallas_call(
        functools.partial(_kernel, k=k, scale=float(n) / float(w)),  # host
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, n), lambda i, j: (i, 0)),     # q
            pl.BlockSpec((tq, w), lambda i, j: (i, 0)),     # q_paa
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),     # thr
            pl.BlockSpec((tc, n), lambda i, j: (j, 0)),     # block
            pl.BlockSpec((w, tc), lambda i, j: (0, j)),     # lo
            pl.BlockSpec((w, tc), lambda i, j: (0, j)),     # hi
            pl.BlockSpec((1, tc), lambda i, j: (0, j)),     # ids
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.int32),
            jax.ShapeDtypeStruct((q.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(q, q_paa, thr[:, None], block, lo, hi, ids[None, :])
    return out_d[:qn], out_i[:qn], out_n[:qn, 0]
