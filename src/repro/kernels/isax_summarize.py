"""Pallas TPU kernel: fused z-norm + PAA + iSAX symbol quantization.

This is Stage 1/2 of the paper's pipeline (IndexBulkLoading workers computing
iSAX summarizations with SIMD) mapped onto the TPU: one grid step summarizes a
tile of series resident in VMEM, producing PAA values and symbols in one pass
over the raw data (the raw tile is read exactly once from HBM).

Layout notes (TPU):
  * the series tile is (TN, n): lane dimension = series points, 128-aligned
    for typical n (128/256/...);
  * PAA is one MXU matmul against a (w, n) 0/1 segment-indicator matrix,
    contracting the points: (w, n) x (TN, n)^T -> (w, TN), then a divide by
    the segment length.  The result is TRANSPOSED — segments on sublanes,
    series on lanes — so the symbol pass below works on dense vregs; the
    wrapper transposes the (w, N) outputs back.  A (TN, n) -> (TN, w, n/w)
    reshape would be the obvious form, but Mosaic cannot lay that shape
    cast out;
  * quantization = sum over breakpoints of (paa >= bp), unrolled over the
    card-1 breakpoints as compile-time constants: integer-exact, no gather,
    the same count ``isax.sax_from_paa`` takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(x_ref, seg_ref, paa_ref, sax_ref, *, seg_len: int,
            normalize: bool, bps: tuple[float, ...]):
    x = x_ref[...].astype(jnp.float32)          # (TN, n)
    if normalize:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(x * x, axis=-1, keepdims=True) - mu * mu
        x = (x - mu) / jnp.maximum(jnp.sqrt(jnp.maximum(var, 0.0)), 1e-8)
    # HIGHEST: the MXU's default f32 pass rounds operands to bf16, which
    # moves PAA values by ~1e-3 and flips symbols near breakpoints
    p = jax.lax.dot_general(
        seg_ref[...], x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) / seg_len     # (w, TN)
    s = jnp.zeros(p.shape, jnp.int32)
    for bp in bps:
        s = s + (p >= bp).astype(jnp.int32)
    paa_ref[...] = p
    sax_ref[...] = s


@functools.partial(jax.jit, static_argnames=("w", "card", "normalize", "tile_n", "interpret"))
def isax_summarize(x: jax.Array, *, w: int = 16, card: int = 256,
                   normalize: bool = True, tile_n: int = 256,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(N, n) raw series -> (PAA (N, w) f32, symbols (N, w) int32).

    N is padded to a tile multiple internally; callers receive unpadded
    results.
    """
    from repro.core import isax as _isax

    n_series, n = x.shape
    if n % w:
        raise ValueError(f"series length {n} not divisible by w={w}")
    tile = min(tile_n, max(8, n_series))
    pad = (-n_series) % tile
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, n), x.dtype)], axis=0)
    npad = x.shape[0]

    seg_len = n // w
    seg = jnp.asarray(np.arange(n)[None, :] // seg_len
                      == np.arange(w)[:, None], jnp.float32)   # (w, n)
    bps = tuple(float(b) for b in _isax.breakpoints(card))   # host

    grid = (npad // tile,)
    paa_t, sax_t = pl.pallas_call(
        functools.partial(_kernel, seg_len=seg_len, normalize=normalize,
                          bps=bps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
            pl.BlockSpec((w, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((w, tile), lambda i: (0, i)),
            pl.BlockSpec((w, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w, npad), jnp.float32),
            jax.ShapeDtypeStruct((w, npad), jnp.int32),
        ],
        interpret=interpret,
    )(x, seg)
    return paa_t[:, :n_series].T, sax_t[:, :n_series].T
