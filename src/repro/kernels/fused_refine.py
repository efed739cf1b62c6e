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

``gathered_panel_topk`` is the same step for the query-major refine,
where each query has its own K blocks: the block ids scalar-prefetched,
one program loops over the active (query, block) pairs and copies each
block straight from the index, so no (Q, K, C, n) gathered copy exists
and an inactive pair starts no copy and costs no grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ---------------------------------------------------------------------------
# gathered refine: each query against ITS OWN next K blocks (query-major)
# ---------------------------------------------------------------------------

_DEPTH = 3      # block copies in flight: the one computed on and two ahead


def _gathered_kernel(idx_ref, act_ref, q_ref, qp_ref, thr_ref, bound_ref,
                     raw_hbm, lo_hbm, hi_hbm, id_hbm, tail_hbm, out_d_ref,
                     out_i_ref,
                     out_n_ref, x_buf, lo_buf, hi_buf, id_buf, sem, *,
                     k: int, kb: int, scale: float, n_full: int):
    total = idx_ref.shape[0]                                # Q * K pairs
    out_d_ref[...] = jnp.full(out_d_ref.shape, INF, jnp.float32)
    out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)
    out_n_ref[...] = jnp.zeros(out_n_ref.shape, jnp.int32)

    # A copy from HBM moves whole 8-row tiles of the (B, C) id table: a
    # block's ids come in the tile that holds its row, and the rows past
    # the last whole tile (n_full) from ``tail``, those rows padded to 8.
    def copies(s, slot):
        b = idx_ref[s]
        blocks = [pltpu.make_async_copy(src.at[b], dst.at[slot],
                                        sem.at[r, slot])
                  for r, (src, dst) in enumerate(((raw_hbm, x_buf),
                                                  (lo_hbm, lo_buf),
                                                  (hi_hbm, hi_buf)))]
        tail = pltpu.make_async_copy(tail_hbm, id_buf.at[slot],
                                     sem.at[3, slot])
        tile = None
        if n_full:
            row0 = pl.multiple_of(jnp.minimum(b // 8 * 8, n_full - 8), 8)
            tile = pltpu.make_async_copy(id_hbm.at[pl.ds(row0, 8)],
                                         id_buf.at[slot], sem.at[3, slot])
        return blocks, tile, tail, b >= n_full

    def start(s, slot):
        @pl.when(s < total)
        def _():
            blocks, tile, tail, in_tail = copies(s, slot)
            for c in blocks:
                c.start()
            if tile is not None:
                pl.when(jnp.logical_not(in_tail))(tile.start)
            pl.when(in_tail)(tail.start)

    def next_active(s):
        """The first active pair at or after s, or total."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < total, act_ref[jnp.minimum(t, total - 1)] == 0),
            lambda t: t + 1, s)

    # the m-th active pair lands in slot m % _DEPTH
    s0 = ahead = next_active(0)
    start(s0, 0)
    for slot in range(1, _DEPTH - 1):
        ahead = next_active(ahead + 1)
        start(ahead, slot)

    def step(carry):
        s, slot, ahead = carry
        ahead = next_active(ahead + 1)
        start(ahead, (slot + _DEPTH - 1) % _DEPTH)
        blocks, _, tail, _ = copies(s, slot)
        for c in blocks + [tail]:       # a wait needs only the shapes
            c.wait()
        i, b = s // kb, idx_ref[s]
        qe = qp_ref[i]                                      # (w, 1)
        ids = id_buf[slot, pl.ds(b % 8, 1), :]              # (1, C)
        dd = jnp.maximum(jnp.maximum(lo_buf[slot] - qe, qe - hi_buf[slot]),
                         0.0)
        lb = scale * jnp.sum(dd * dd, axis=0, keepdims=True)   # (1, C)
        live = (lb < thr_ref[i]) & (ids >= 0)               # (1, C)
        out_n_ref[i] = out_n_ref[i] + jnp.sum(live, axis=1, dtype=jnp.int32,
                                              keepdims=True)

        @pl.when(jnp.any(live))
        def _refine():
            q = q_ref[i]                                    # (1, n)
            x = x_buf[slot]                                 # (C, n)
            qq = jnp.sum(q * q, axis=-1, keepdims=True)     # (1, 1)
            # ||x||^2 - 2 q.x as one f32 sum on the VPU: a one-row dot
            # would take the MXU six passes over the block at HIGHEST.
            # Summed down the transpose, it lands on lanes, as lb does.
            xr = jnp.sum((x * (x - 2.0 * q)).T, axis=0, keepdims=True)
            d = jnp.maximum(qq + xr, 0.0)                   # (1, C)
            keep = live & (d <= bound_ref[i])
            d = jnp.where(keep, d, INF)

            # a block none of whose kept distances reaches the running
            # k-th cannot change the (dist, id)-lex top-k: skip the select
            @pl.when(jnp.any(keep & (d <= out_d_ref[i][:, k - 1:k])))
            def _select():
                td, ti = select_topk(d, jnp.where(keep, ids, _PAD_ID_KEY), k)
                rd = jnp.concatenate([out_d_ref[i], td], axis=-1)
                ri = jnp.concatenate([out_i_ref[i], ti], axis=-1)
                md, mi = select_topk(rd, jnp.where(ri >= 0, ri, _PAD_ID_KEY),
                                     k)
                out_d_ref[i] = md
                out_i_ref[i] = mi

        return next_active(s + 1), (slot + 1) % _DEPTH, ahead

    jax.lax.while_loop(lambda c: c[0] < total, step,
                       (s0, jnp.int32(0), ahead))


@functools.partial(jax.jit, static_argnames=("k", "n", "interpret"))
def gathered_panel_topk(q: jax.Array, q_paa: jax.Array, raw: jax.Array,
                        slo: jax.Array, shi: jax.Array, ids: jax.Array,
                        idxs: jax.Array, active: jax.Array, thr: jax.Array,
                        bound: jax.Array, *, k: int, n: int,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q (Q, n); q_paa (Q, w); the index's raw (B, C, n), slo/shi
    (B, w, C) and ids (B, C) in place; idxs (Q, K) block ids and active
    (Q, K) bool per query; thr (Q,) pruning bound; bound (Q,) the largest
    distance selected (the frontier's k-th: a candidate above it cannot
    enter the frontier, so most blocks skip the select).  One program
    loops over the
    active (query, block) pairs in order, copying each block straight
    from the index with ``_DEPTH`` copies in flight, so no (Q, K, C, n)
    copy exists and an inactive pair costs a scalar test.
    -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,) int32), the lex top-k
    of the live lanes within ``bound`` and the live count over all K
    blocks of each query."""
    qn, w = q_paa.shape
    kb = idxs.shape[1]
    nb, c, _ = raw.shape
    n_full = nb - nb % 8
    tail = jnp.concatenate(
        [ids[n_full:], jnp.full((8 - nb % 8, c), -1, jnp.int32)])

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, f, a: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_d, out_i, out_n = pl.pallas_call(
        functools.partial(_gathered_kernel, k=k, kb=kb,
                          scale=float(n) / float(w),        # host
                          n_full=n_full),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole((qn, 1, n)), whole((qn, w, 1)),
                      whole((qn, 1, 1)), whole((qn, 1, 1))] + [in_hbm] * 5,
            out_specs=[whole((qn, 1, k)), whole((qn, 1, k)),
                       whole((qn, 1, 1))],
            scratch_shapes=[pltpu.VMEM((_DEPTH, c, n), jnp.float32),
                            pltpu.VMEM((_DEPTH, w, c), jnp.float32),
                            pltpu.VMEM((_DEPTH, w, c), jnp.float32),
                            pltpu.VMEM((_DEPTH, 8, c), jnp.int32),
                            pltpu.SemaphoreType.DMA((4, _DEPTH))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((qn, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((qn, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(idxs.reshape(-1).astype(jnp.int32),
      active.reshape(-1).astype(jnp.int32),
      q.astype(jnp.float32)[:, None, :], q_paa[:, :, None],
      thr.reshape(qn, 1, 1), bound.reshape(qn, 1, 1), raw, slo, shi, ids,
      tail)
    return out_d[:, 0], out_i[:, 0], out_n[:, 0, 0]
