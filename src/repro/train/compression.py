"""Gradient compression: int8 quantization with error feedback.

In the implicit-DP (pjit) path XLA owns the gradient all-reduce, so
compression is exposed as an *explicit-DP* alternative: per-shard grads are
quantized to int8 (per-leaf absmax scale), exchanged with an ``all_gather``
over the data axes (int8 on the wire — 4x fewer bytes than f32), and
dequant-summed locally.  The quantization residual feeds back into the next
step's gradient (error feedback), which is what keeps convergence intact —
``tests/test_train.py`` checks a quadratic converges with compression on.

Honesty note (DESIGN.md §6): a production int8 *all-reduce* needs
reduction-over-int8 support in the collective itself; XLA reduces in the
operand dtype, and int8 sums overflow.  all_gather+local-sum keeps int8 on
the wire at the cost of O(N) receive buffers — the right trade for the
gradient sizes here; both variants' collective bytes are visible in the
dry-run HLO.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def quantize8(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-leaf absmax int8 quantization. Returns (q, scale)."""
    s = jnp.max(jnp.abs(g)) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(g / s), -127, 127).astype(jnp.int8)
    return q, s


def dequant8(q: jax.Array, s: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * s


def compress_with_feedback(g: jax.Array, err: jax.Array
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize (g + err); the new residual is returned as next-step err."""
    gc = g.astype(jnp.float32) + err
    q, s = quantize8(gc)
    return q, s, gc - dequant8(q, s)


def compress_allreduce(g: jax.Array, e: jax.Array, ax, n: int
                       ) -> tuple[jax.Array, jax.Array]:
    """int8 mean-reduce of one per-shard gradient leaf.

    For use INSIDE a shard_map whose data axes are ``ax`` (each shard holds
    its own local gradient).  Returns (mean grad, new error state)."""
    q, s, new_e = compress_with_feedback(g, e)
    qs = jax.lax.all_gather(q, ax)                       # int8 on the wire
    ss = jax.lax.all_gather(s, ax)
    tot = jnp.tensordot(ss, qs.astype(jnp.float32), axes=((0,), (0,)))
    return tot / n, new_e


def ddp_allreduce_int8(grads: Any, err: Any, mesh: Mesh,
                       data_axes: tuple[str, ...]) -> tuple[Any, Any]:
    """Explicit-DP mean of per-shard grads with int8 wire format.

    ``grads``/``err``: pytrees whose leaves carry a leading per-shard dim
    (n_shards, *shape), sharded over the data axes.  Returns (mean gradient,
    replicated; new per-shard error state, same layout as input).
    """
    ax = data_axes if len(data_axes) > 1 else data_axes[0]
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]

    def body(g, e):
        return compress_allreduce(g[0], e[0], ax, n)

    def all_leaves(gs, es):
        out = jax.tree.map(body, gs, es)
        leaf = lambda x: isinstance(x, tuple)
        return (jax.tree.map(lambda o: o[0], out, is_leaf=leaf),
                jax.tree.map(lambda o: o[1][None], out, is_leaf=leaf))

    fn = jax.shard_map(all_leaves, mesh=mesh,
                       in_specs=(P(ax), P(ax)), out_specs=(P(), P(ax)),
                       check_vma=False)
    return fn(grads, err)


def init_error_state(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
