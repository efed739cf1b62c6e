"""Attention: chunked online-softmax (flash-style in pure JAX) for training
and prefill, plus KV-cache decode (full cache and ring-buffer SWA cache).

Design (DESIGN.md §7):
  * training/prefill never materialize (S, S) scores: an outer ``lax.scan``
    over query chunks and an inner scan over KV chunks carry the running
    (max, denominator, accumulator) triple — block memory is
    (B, KV, G, Cq, Ck);
  * GQA is computed grouped — queries reshaped to (B, S, KV, G, hd) so KV is
    never repeated in memory;
  * ``swa`` attention slices a static-width KV window per query chunk
    (``window + Cq`` wide) instead of sweeping all KV chunks: cost is
    O(S·W) not O(S²), which is what makes the 500k cells affordable;
  * the baseline "full" path sweeps the whole rectangle with a causal mask
    (2× the useful FLOPs).  ``triangular=True`` switches to a block-
    triangular schedule (skips fully-masked KV chunks per query chunk) — a
    §Perf optimization measured in EXPERIMENTS.md;
  * decode attends one new token against the cache, chunk-scanned, with a
    position mask; SWA decode uses a ring buffer of width ``window``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


NEG = -1.0e30


def _block_attn(q, k, v, mask, sm_scale):
    """One online-softmax block.

    q (B, Cq, KV, G, hd); k, v (B, Ck, KV, hd); mask (B or 1, KV or 1, G or 1,
    Cq, Ck) bool. Returns (scores_max (..., Cq), exp_sum, weighted_v) with
    leading dims (B, KV, G).
    """
    s = jnp.einsum("bqkgh,bckh->bkgqc", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask, s, NEG)
    m = jnp.max(s, axis=-1)                                   # (B,KV,G,Cq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                   # (B,KV,G,Cq)
    o = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


# Flash-style backward: recompute each block's scores instead of saving
# them.  Without this, the (Cq, Ck|span)-sized score/prob tensors become
# per-iteration residuals of the inner attention scans and get STACKED over
# the trip count — measured as ~60% of hymba train_4k's HBM bytes
# (EXPERIMENTS.md §Perf iteration 3).  The block inputs (q/k/v tiles) are
# loop-slices of already-saved tensors, so the only cost is ~1 extra block
# forward inside the backward pass.
_block_attn_ckpt = jax.checkpoint(_block_attn)


def _merge(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool = True, window: int = 0, chunk: int = 512,
           q_offset: jax.Array | int = 0, sm_scale: float | None = None,
           triangular: bool = False) -> jax.Array:
    """Chunked attention.  q (B, Sq, H, hd); k, v (B, Sk, KVH, hd).

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill
    continuation / cross-attn alignment).  ``window > 0`` = sliding-window
    (causal implied).  Returns (B, Sq, H, hd), q.dtype.
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    def _div_chunk(s, want):
        c = min(want, s)
        while s % c:
            c -= 1
        return c

    cq = _div_chunk(sq, chunk)
    ck = _div_chunk(sk, chunk)
    assert sq % cq == 0 and sk % ck == 0, (sq, cq, sk, ck)
    nq, nk = sq // cq, sk // ck
    qg = q.reshape(b, sq, kvh, g, hd)

    if window:
        return _attend_swa(qg, k, v, window=window, cq=cq,
                           q_offset=q_offset, scale=scale
                           ).reshape(b, sq, h, hd)
    if causal and triangular and nq > 1:
        return _attend_triangular(qg, k, v, cq=cq, ck=ck,
                                  q_offset=q_offset, scale=scale
                                  ).reshape(b, sq, h, hd)

    def q_step(_, iq):
        qi = jax.lax.dynamic_slice_in_dim(qg, iq * cq, cq, axis=1)
        qpos = q_offset + iq * cq + jnp.arange(cq)

        def kv_step(carry, ik):
            m0, l0, o0 = carry
            ki = jax.lax.dynamic_slice_in_dim(k, ik * ck, ck, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, ik * ck, ck, axis=1)
            kpos = ik * ck + jnp.arange(ck)
            if causal:
                mask = (qpos[:, None] >= kpos[None, :])[None, None, None]
            else:
                mask = jnp.ones((1, 1, 1, cq, ck), bool)
            m2, l2, o2 = _block_attn_ckpt(qi, ki, vi, mask, scale)
            return _merge(m0, l0, o0, m2, l2, o2), None

        init = (jnp.full((b, kvh, g, cq), NEG, jnp.float32),
                jnp.zeros((b, kvh, g, cq), jnp.float32),
                jnp.zeros((b, kvh, g, cq, hd), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = o / jnp.maximum(l[..., None], 1e-30)            # (B,KV,G,Cq,hd)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))      # (nq,B,KV,G,Cq,hd)
    out = jnp.moveaxis(outs, 0, 1)                            # (B,nq,KV,G,Cq,hd)
    out = jnp.moveaxis(out, 4, 2)                             # (B,nq,Cq,KV,G,hd)
    return out.reshape(b, sq, h, hd)


def _attend_triangular(qg, k, v, *, cq: int, ck: int, q_offset, scale):
    """Block-triangular causal schedule (§Perf optimization).

    The baseline sweeps the full nq×nk rectangle and masks; here we scan the
    *static list of causally-live block pairs* (i, j) with j·ck < (i+1)·cq +
    q_offset, accumulating per-query-chunk online-softmax state at slice i.
    HLO FLOPs drop to ~the triangle (~2× for square self-attention) at the
    price of a serialized pair scan — batch/head parallelism is untouched.
    Requires a static q_offset.
    """
    b, sq, kvh, g, hd = qg.shape
    sk = k.shape[1]
    nq, nk = sq // cq, sk // ck
    off = int(q_offset)
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if j * ck < off + (i + 1) * cq]
    i_idx = jnp.asarray([p[0] for p in pairs], jnp.int32)
    j_idx = jnp.asarray([p[1] for p in pairs], jnp.int32)

    def pair_step(carry, ij):
        m_all, l_all, o_all = carry                 # (nq, B, KV, G, Cq[, hd])
        i, j = ij
        qi = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1)
        ki = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=1)
        qpos = off + i * cq + jnp.arange(cq)
        kpos = j * ck + jnp.arange(ck)
        mask = (qpos[:, None] >= kpos[None, :])[None, None, None]
        m2, l2, o2 = _block_attn_ckpt(qi, ki, vi, mask, scale)
        m0 = jax.lax.dynamic_index_in_dim(m_all, i, 0, keepdims=False)
        l0 = jax.lax.dynamic_index_in_dim(l_all, i, 0, keepdims=False)
        o0 = jax.lax.dynamic_index_in_dim(o_all, i, 0, keepdims=False)
        m, l, o = _merge(m0, l0, o0, m2, l2, o2)
        upd = lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, i, 0)
        return (upd(m_all, m), upd(l_all, l), upd(o_all, o)), None

    init = (jnp.full((nq, b, kvh, g, cq), NEG, jnp.float32),
            jnp.zeros((nq, b, kvh, g, cq), jnp.float32),
            jnp.zeros((nq, b, kvh, g, cq, hd), jnp.float32))
    (m, l, o), _ = jax.lax.scan(pair_step, init, (i_idx, j_idx))
    out = o / jnp.maximum(l[..., None], 1e-30)      # (nq,B,KV,G,Cq,hd)
    out = jnp.moveaxis(out, 0, 1)                   # (B,nq,KV,G,Cq,hd)
    out = jnp.moveaxis(out, 4, 2)                   # (B,nq,Cq,KV,G,hd)
    return out.astype(qg.dtype).reshape(b, sq, kvh * g, hd)


def _attend_swa(qg, k, v, *, window: int, cq: int, q_offset, scale):
    """Sliding-window attention: per query chunk, slice a static KV window.

    Window slice width is ``window + cq`` rounded so cost is O(S·W).
    """
    b, sq, kvh, g, hd = qg.shape
    sk = k.shape[1]
    nq = sq // cq
    span = min(window + cq, sk)

    def q_step(_, iq):
        qi = jax.lax.dynamic_slice_in_dim(qg, iq * cq, cq, axis=1)
        qpos = q_offset + iq * cq + jnp.arange(cq)            # (Cq,)
        # earliest key any query in this chunk may see
        start = jnp.clip(q_offset + iq * cq - window + 1, 0, sk - span)
        ki = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kpos = start + jnp.arange(span)
        mask = ((qpos[:, None] >= kpos[None, :])
                & (qpos[:, None] - kpos[None, :] < window))[None, None, None]
        m, l, o = _block_attn_ckpt(qi, ki, vi, mask, scale)
        out = o / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(qg.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1)
    out = jnp.moveaxis(out, 4, 2)
    return out.reshape(b, sq, kvh * g, hd)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  pos: jax.Array, *, window: int = 0, chunk: int = 1024,
                  sm_scale: float | None = None) -> jax.Array:
    """One-token decode. q (B, 1, H, hd); caches (B, S, KVH, hd).

    ``pos`` (scalar or (B,)): index of the NEW token (keys at indices > pos
    are masked).  For ``window > 0`` the cache is a ring buffer of width
    ``window`` written at ``pos % window`` — masking handles wrap-around.
    Chunk-scanned flash-decoding style (partials merged by LSE), so the
    (B, S) score row is never materialized for 500k caches.
    """
    b, _, h, hd = q.shape
    sk, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    ck = min(chunk, sk)
    nk = sk // ck
    qg = q.reshape(b, 1, kvh, g, hd)
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))

    def kv_step(carry, ik):
        m0, l0, o0 = carry
        ki = jax.lax.dynamic_slice_in_dim(k_cache, ik * ck, ck, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v_cache, ik * ck, ck, axis=1)
        slot = ik * ck + jnp.arange(ck)                       # (Ck,)
        if window:
            # ring buffer: slot s holds absolute position p iff
            # p % window == s and pos - window < p <= pos
            age = (pos[:, None] - slot[None, :]) % window      # (B, Ck)
            abs_pos = pos[:, None] - age
            valid = (abs_pos >= 0) & (abs_pos <= pos[:, None])
        else:
            valid = slot[None, :] <= pos[:, None]
        mask = valid[:, None, None, None, :]                  # (B,1,1,1,Ck)
        m2, l2, o2 = _block_attn(qg, ki, vi, mask, scale)
        return _merge(m0, l0, o0, m2, l2, o2), None

    init = (jnp.full((b, kvh, g, 1), NEG, jnp.float32),
            jnp.zeros((b, kvh, g, 1), jnp.float32),
            jnp.zeros((b, kvh, g, 1, hd), jnp.float32))
    (m, l, o), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
    out = o / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 3, 1)                             # (B,1,KV,G,hd)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def decode_attend_seqsharded(q: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, pos: jax.Array, *,
                             mesh, axes: tuple[str, ...],
                             b_axes: tuple[str, ...] = (),
                             chunk: int = 1024,
                             sm_scale: float | None = None):
    """Flash-decoding over a KV cache sequence-sharded on ``axes``.

    Two users: long-context cells (batch=1, sequence over the DATA axes)
    and GQA decode where kv_heads doesn't divide the model axis (sequence
    over the MODEL axis — head_dim sharding makes GSPMD all-gather the
    cache; replication blows HBM; see EXPERIMENTS.md §Perf iteration 2).

    The whole cache transaction lives inside one shard_map: the owning
    shard does a masked write of the new token's K/V into its local chunk,
    every shard computes a partial online-softmax over its chunk (positions
    offset by the shard index), and partials merge with one max/sum
    reduction (B x KVH x G scalars — the same tiny collective footprint as
    the index's BSF protocol).  Returns (out, new_k_cache, new_v_cache).
    """
    from jax.sharding import PartitionSpec as P
    b, _, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    ax = axes if len(axes) > 1 else axes[0]
    bp = (b_axes if len(b_axes) > 1 else b_axes[0]) if b_axes else None

    def body(qf, knf, vnf, kf, vf, posf):
        bl = qf.shape[0]
        sloc = kf.shape[1]
        idx = jax.lax.axis_index(ax)
        base = idx * sloc
        posb = jnp.broadcast_to(jnp.asarray(posf), (bl,))
        # masked write of the new token into the owning shard's chunk
        local = jnp.clip(posb - base, 0, sloc - 1)            # (B,)
        mine = (posb >= base) & (posb < base + sloc)          # (B,)

        def write(cache, new):
            def one(c, n, s, m):
                cur = jax.lax.dynamic_slice_in_dim(c, s, 1, axis=0)
                upd = jnp.where(m, n.astype(c.dtype), cur)
                return jax.lax.dynamic_update_slice_in_dim(c, upd, s, axis=0)
            return jax.vmap(one)(cache, new, local, mine)

        kf = write(kf, knf)
        vf = write(vf, vnf)

        qg = qf.reshape(bl, 1, kvh, g, hd)
        ck = min(chunk, sloc)
        nk = sloc // ck

        def kv_step(carry, ik):
            m0, l0, o0 = carry
            ki = jax.lax.dynamic_slice_in_dim(kf, ik * ck, ck, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(vf, ik * ck, ck, axis=1)
            slot = base + ik * ck + jnp.arange(ck)
            valid = slot[None, :] <= posb[:, None]
            mask = valid[:, None, None, None, :]
            m2, l2, o2 = _block_attn(qg, ki, vi, mask, scale)
            return _merge(m0, l0, o0, m2, l2, o2), None

        init = (jnp.full((bl, kvh, g, 1), NEG, jnp.float32),
                jnp.zeros((bl, kvh, g, 1), jnp.float32),
                jnp.zeros((bl, kvh, g, 1, hd), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        # cross-shard LSE merge
        mg = jax.lax.pmax(m, ax)
        a = jnp.exp(m - mg)
        lg = jax.lax.psum(l * a, ax)
        og = jax.lax.psum(o * a[..., None], ax)
        out = og / jnp.maximum(lg[..., None], 1e-30)
        out = jnp.moveaxis(out, 3, 1)
        return out.reshape(bl, 1, h, hd).astype(qf.dtype), kf, vf

    cache_spec = P(bp, ax, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bp, None, None, None), P(bp, None, None, None),
                  P(bp, None, None, None), cache_spec, cache_spec, P()),
        out_specs=(P(bp, None, None, None), cache_spec, cache_spec),
        check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache, jnp.asarray(pos))


def cache_update(k_cache, v_cache, k_new, v_new, pos, *, window: int = 0):
    """Write one new token's K/V at position ``pos`` (ring slot if SWA)."""
    b = k_new.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
    slot = pos % window if window else pos

    def write(cache, new):
        def one(c, n, s):
            return jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
        return jax.vmap(one)(cache, new, slot)

    return write(k_cache, k_new), write(v_cache, v_new)
