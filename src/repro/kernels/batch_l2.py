"""Pallas TPU kernel: batched squared-Euclidean distances on the MXU.

The paper's SIMD "real distance calculation" phase.  On TPU the right
formulation is the expanded form

    ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x

because the cross term is a (TQ, n) x (n, TN) matmul that runs on the MXU at
full throughput, while the norms are cheap VPU row reductions computed in the
same VMEM residency.  Per grid step we stream one (TN, n) tile of raw series
from HBM exactly once — the kernel is HBM-bandwidth-bound at small Q and
MXU-bound for large query batches, matching the roofline analysis in
EXPERIMENTS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, x_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (TQ, n)
    x = x_ref[...].astype(jnp.float32)          # (TN, n)
    qq = jnp.sum(q * q, axis=-1, keepdims=True)             # (TQ, 1)
    xx = jnp.sum(x * x, axis=-1)[None, :]                   # (1, TN)
    # HIGHEST: one bf16 MXU pass (the f32 default) errs by ~1e-1 on the
    # cross term at n=256, far above the k-NN distance gaps
    cross = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # (TQ, TN) on MXU
    out_ref[...] = jnp.maximum(qq + xx - 2.0 * cross, 0.0)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def batch_l2(q: jax.Array, x: jax.Array, *, tile_q: int = 128,
             tile_n: int = 256, interpret: bool = False) -> jax.Array:
    """q (Q, n), x (N, n) -> (Q, N) squared Euclidean distances, f32."""
    q_count, n = q.shape
    n_items = x.shape[0]
    tq = min(tile_q, max(8, q_count))
    tn = min(tile_n, max(128, n_items))

    qpad = (-q_count) % tq
    if qpad:
        q = jnp.concatenate([q, jnp.zeros((qpad, n), q.dtype)], axis=0)
    npad = (-n_items) % tn
    if npad:
        x = jnp.concatenate([x, jnp.zeros((npad, n), x.dtype)], axis=0)

    grid = (q.shape[0] // tq, x.shape[0] // tn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, n), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, n), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], x.shape[0]), jnp.float32),
        interpret=interpret,
    )(q, x)
    return out[:q_count, :n_items]
