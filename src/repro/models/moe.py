"""Token-choice top-k Mixture-of-Experts with sort-based dispatch.

Two execution paths sharing the same math:

  * local (mesh=None)  — every device sees all experts; used by the CPU
    smoke tests and as the reference the EP path is validated against;
  * EP (mesh given)    — experts sharded over the "model" axis inside a
    manual ``shard_map``: each device routes its local tokens, packs a
    fixed-capacity per-expert buffer, exchanges it with one
    ``all_to_all`` (the GShard dispatch), runs its local experts, and
    reverses the exchange for the combine.  No one-hot dispatch einsums —
    dispatch is a sort + scatter, so HLO FLOPs stay ~= the useful expert
    FLOPs (this is what keeps MODEL_FLOPS/HLO_FLOPs honest in §Roofline).

Capacity: per (source device, expert) C = ceil(T*k/E * cf) rounded up to a
multiple of 8; overflowing assignments are dropped (token keeps its other
experts' contributions — standard dropping semantics), counted in aux stats.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import common


class MoEAux(NamedTuple):
    load_balance: jax.Array     # Switch-style aux loss (scalar)
    router_z: jax.Array         # router z-loss (scalar)
    dropped_frac: jax.Array     # fraction of assignments dropped (scalar)


def capacity(n_tokens: int, n_experts: int, top_k: int, cf: float) -> int:
    c = int(-(-n_tokens * top_k * cf // n_experts))   # ceil
    return max(8, -(-c // 8) * 8)                     # round up to 8


def route(x: jax.Array, w_router: jax.Array, top_k: int
          ) -> tuple[jax.Array, jax.Array, MoEAux]:
    """x (T, d) -> (weights (T, K), expert ids (T, K), aux losses)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    w, ids = jax.lax.top_k(probs, top_k)                       # (T, K)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    e = probs.shape[-1]
    # Switch load-balance loss: E * sum_e f_e * p_e
    sel = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)      # top-1 choice
    lb = e * jnp.sum(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return w, ids, MoEAux(lb, z, jnp.zeros((), jnp.float32))


def _dispatch_indices(ids: jax.Array, n_experts: int, cap: int
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-based slot assignment.

    ids (T, K) -> (token_of_assignment (A,), slot (A,), kept (A,)) where
    ``slot`` indexes a (E*cap) buffer (== E*cap means dropped) and A = T*K.
    Assignments are ranked within their expert by (token, k) order — the
    deterministic analogue of the paper's Fetch&Inc work claiming.
    """
    t, k = ids.shape
    a = t * k
    eids = ids.reshape(a)
    tok = jnp.arange(a, dtype=jnp.int32) // k
    order = jnp.argsort(eids, stable=True)                     # group by expert
    es = eids[order]
    # rank within expert group = position - group start
    counts = jnp.bincount(eids, length=n_experts)              # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(a, dtype=jnp.int32) - starts[es].astype(jnp.int32)
    pos = jnp.zeros((a,), jnp.int32).at[order].set(pos_sorted)  # unsort
    kept = pos < cap
    slot = jnp.where(kept, eids * cap + pos, n_experts * cap)
    return tok, slot, kept


def _expert_ffn(buf: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                act) -> jax.Array:
    """buf (E, C, d); wg/wu (E, d, f); wd (E, f, d) -> (E, C, d)."""
    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_ffn_local(x: jax.Array, params: dict, *, top_k: int,
                  capacity_factor: float, act) -> tuple[jax.Array, MoEAux]:
    """All experts local.  x (T, d) -> (T, d)."""
    t, d = x.shape
    e = params["wg"].shape[0]
    cap = capacity(t, e, top_k, capacity_factor)
    w, ids, aux = route(x, params["router"], top_k)
    tok, slot, kept = _dispatch_indices(ids, e, cap)

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(x[tok])
    out_e = _expert_ffn(buf[:-1].reshape(e, cap, d),
                        params["wg"], params["wu"], params["wd"], act)
    out_e = jnp.concatenate([out_e.reshape(e * cap, d),
                             jnp.zeros((1, d), x.dtype)])      # dropped row
    contrib = out_e[slot] * w.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(
        jnp.where(kept[:, None], contrib, 0))
    dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
    return y, aux._replace(dropped_frac=dropped)


def moe_ffn_ep(x: jax.Array, params: dict, *, top_k: int,
               capacity_factor: float, act, mesh: Mesh,
               data_axes: tuple[str, ...], model_axis: str = "model"
               ) -> tuple[jax.Array, MoEAux]:
    """Expert-parallel MoE: experts sharded over ``model_axis``.

    x (B, S, d) is sharded over the data axes on B and REPLICATED over the
    model axis (the standard TP activation layout), so dispatch needs no
    all_to_all at all: every peer already holds every token, slices the
    per-expert buffers of ITS OWN experts locally, and the combine is one
    psum over the model axis (the same bytes as a TP FFN all-reduce).  The
    routing computation is replicated across model peers — redundant
    arithmetic, zero communication; the paper's "every worker does the same
    cheap bookkeeping, no synchronization" trade made on silicon.
    """
    e = params["wg"].shape[0]
    m = mesh.shape[model_axis]
    assert e % m == 0, (e, m)
    el = e // m

    def body(xl, router, wg, wu, wd):
        b, s, d = xl.shape
        t = b * s
        xt = xl.reshape(t, d)
        cap = capacity(t, e, top_k, capacity_factor)
        w, ids, aux = route(xt, router, top_k)
        tok, slot, kept = _dispatch_indices(ids, e, cap)

        buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].set(xt[tok])
        p = jax.lax.axis_index(model_axis)
        mine = jax.lax.dynamic_slice_in_dim(
            buf[:-1].reshape(e, cap, d), p * el, el, axis=0)    # (El, cap, d)
        out_e = _expert_ffn(mine, wg, wu, wd, act)              # (El, cap, d)
        out_full = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((e * cap, d), xt.dtype),
            out_e.reshape(el * cap, d), p * el * cap, axis=0)
        out_full = jnp.concatenate([out_full, jnp.zeros((1, d), xt.dtype)])
        contrib = out_full[slot] * w.reshape(-1)[:, None].astype(xt.dtype)
        y = jnp.zeros((t, d), xt.dtype).at[tok].add(
            jnp.where(kept[:, None], contrib, 0))
        y = jax.lax.psum(y, model_axis)                         # combine
        dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
        aux = MoEAux(jax.lax.pmean(aux.load_balance, data_axes),
                     jax.lax.pmean(aux.router_z, data_axes),
                     jax.lax.pmean(dropped, data_axes))
        return y.reshape(b, s, d), aux

    dp = data_axes if len(data_axes) > 1 else data_axes[0]
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(dp, None, None),
                   MoEAux(P(), P(), P())),
        check_vma=False)
    y, aux = fn(x, params["router"], params["wg"], params["wu"], params["wd"])
    return y, aux


def moe_ffn(x: jax.Array, params: dict, *, top_k: int, capacity_factor: float,
            act, mesh: Mesh | None = None,
            data_axes: tuple[str, ...] = ()) -> tuple[jax.Array, MoEAux]:
    """Dispatcher: (B, S, d) -> (B, S, d) plus aux losses."""
    if mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1 \
            and params["wg"].shape[0] % mesh.shape["model"] == 0:
        return moe_ffn_ep(x, params, top_k=top_k,
                          capacity_factor=capacity_factor, act=act,
                          mesh=mesh, data_axes=data_axes)
    b, s, d = x.shape
    y, aux = moe_ffn_local(x.reshape(b * s, d), params, top_k=top_k,
                           capacity_factor=capacity_factor, act=act)
    return y.reshape(b, s, d), aux


def param_specs(cfg) -> dict:
    """ParamSpec tree for one MoE FFN layer stack (leading 'layers' dim)."""
    L, d, f, e = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts
    S = common.ParamSpec
    return {
        "router": S((L, d, e), ("layers", "embed", "experts_r"), scale=0.1),
        "wg": S((L, e, d, f), ("layers", "experts", "ff_in", "ff")),
        "wu": S((L, e, d, f), ("layers", "experts", "ff_in", "ff")),
        "wd": S((L, e, f, d), ("layers", "experts", "ff", "embed_out")),
    }
