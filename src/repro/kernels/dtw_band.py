"""Pallas TPU kernel: banded-DTW anti-diagonal wavefront over a panel.

``engine.dtw_band`` computes exact squared DTW with a Sakoe-Chiba band as
a ``lax.scan`` over anti-diagonals — VPU-shaped math, but XLA-compiled
with (Q, M, n) broadcast intermediates.  This kernel runs the same
wavefront on-chip: one grid cell handles one query against a (TM,) tile
of candidate series, keeping the two rolling diagonals (n, TM) in
registers/VMEM and writing only the (1, TM) corner costs to HBM.

Layout: candidates arrive transposed, (n, TM) per tile (points on
sublanes, series on lanes).  Diagonal k needs ``b[i] = x[k-i]`` for i in
[0, n): that is diagonal k-1's column rolled down one sublane with row
``x[k]`` entering at i = 0, so each step is one roll and one single-row
load — no in-kernel gather, and no dynamic slice at an offset Mosaic
cannot prove 8-aligned.  The query arrives as (Q, n, 1), so its block
(1, n, 1) spans the array's last two dimensions; the output is
(Q, 1, Mp) with (1, 1, TM) blocks for the same reason.

Bit-compatibility: every op here (subtract, square, where, minimum, add)
is elementwise — no reductions, no dot — and the op ORDER mirrors
``ref.dtw_band_ref`` exactly, so kernel and oracle agree bit-for-bit
regardless of tiling (locked by np.array_equal in tests/test_kernels.py).

Supports both engine forms: a shared (C, n) panel (every query scans the
same block) and a gathered (Q, M, n) panel (query-major refine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Python scalar, not a jnp value: the kernel closes over it, and
# pallas_call rejects captured traced constants
INF = float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, xt_ref, out_ref, *, n: int, r: int):
    a = q_ref[0]                                    # (n, 1) query column
    tm = xt_ref.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, tm), 0)

    def shift_down(d, fill):                        # d[i] -> d[i-1]
        return jnp.where(i == 0, fill, pltpu.roll(d, 1, 0))

    def body(kk, carry):
        prev, prev2, b = carry                      # diag k-1, k-2 (by i)
        # b[i] = x[k-i]: the previous diagonal's b shifted down a row, with
        # x[k] entering at row 0 (clamped past the end: those cells are
        # outside the matrix and masked below)
        row = xt_ref[0, pl.ds(jnp.minimum(kk, n - 1), 1), :]     # (1, tm)
        b = shift_down(b, row)
        jj = kk - i
        valid = (jj >= 0) & (jj < n) & (jnp.abs(i - jj) <= r)
        c = jnp.where(valid, (a - b) ** 2, INF)
        best = jnp.minimum(jnp.minimum(prev, shift_down(prev, INF)),
                           shift_down(prev2, INF))
        cur = c + jnp.where(kk == 0, 0.0, best)
        cur = jnp.minimum(cur, INF)                 # keep +INF from overflow
        return cur, prev, b

    # loop carries must not be splatted constants: Mosaic gives a splat a
    # replicated layout that the loop body cannot yield back.  So the +INF
    # diagonals are a select over the loaded block (i < 0 never holds), and
    # b starts as the block itself (rows not yet shifted in are masked)
    blk = xt_ref[0]                                 # (n, tm)
    init = jnp.where(i < 0, blk, INF)
    last, _, _ = jax.lax.fori_loop(0, 2 * n - 1, body, (init, init, blk))
    out_ref[0] = last[n - 1:n, :]                   # cell (n-1, n-1)


@functools.partial(jax.jit,
                   static_argnames=("r", "tile_m", "interpret"))
def dtw_band_panel(q: jax.Array, x: jax.Array, *, r: int, tile_m: int = 256,
                   interpret: bool = False) -> jax.Array:
    """Banded squared-DTW panel. q (Q, n) f32; x either (C, n) — shared
    panel, every query vs every series -> (Q, C) — or (Q, M, n) — gathered
    panel, query i vs its own M series -> (Q, M)."""
    qn, n = q.shape
    shared = x.ndim == 2
    m = x.shape[-2]
    tm = min(tile_m, max(128, m))
    mpad = (-m) % tm
    if mpad:
        pad_shape = x.shape[:-2] + (mpad, n)
        x = jnp.concatenate([x, jnp.zeros(pad_shape, x.dtype)], axis=-2)
    mp = x.shape[-2]

    xt = jnp.swapaxes(x, -1, -2).astype(jnp.float32)    # (..., n, Mp)
    if shared:
        xt = xt[None]                                    # (1, n, Mp)
        x_map = lambda qi, j: (0, 0, j)
    else:
        x_map = lambda qi, j: (qi, 0, j)

    qt = q.astype(jnp.float32)[:, :, None]               # (Q, n, 1)
    grid = (qn, mp // tm)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, 1), lambda qi, j: (qi, 0, 0)),
            pl.BlockSpec((1, n, tm), x_map),
        ],
        out_specs=pl.BlockSpec((1, 1, tm), lambda qi, j: (qi, 0, j)),
        out_shape=jax.ShapeDtypeStruct((qn, 1, mp), jnp.float32),
        interpret=interpret,
    )(qt, xt)
    return out[:, 0, :m]
