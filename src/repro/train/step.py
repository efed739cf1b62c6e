"""Train / eval steps: microbatched grad accumulation, f32 accumulators,
NaN-step skipping (fault tolerance — a bad batch never corrupts the params),
and an LR schedule computed inside the step (no host round-trip).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.train import optimizer as opt_lib


def lr_schedule(step, *, base_lr: float = 3e-4, warmup: int = 100,
                total: int = 10_000, min_frac: float = 0.1):
    """Linear warmup + cosine decay, all in jnp (usable inside jit)."""
    t = step.astype(jnp.float32) + 1.0      # first update gets lr > 0
    warm = t / jnp.maximum(warmup, 1)
    prog = jnp.clip((t - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
    cos = min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return base_lr * jnp.where(t < warmup, warm, cos)


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def _all_finite(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    fin = jnp.ones((), jnp.bool_)
    for l in leaves:
        fin = jnp.logical_and(fin, jnp.all(jnp.isfinite(l)))
    return fin


def make_train_step(cfg, *, mesh=None, data_axes: tuple[str, ...] = (),
                    base_lr: float = 3e-4, total_steps: int = 10_000,
                    warmup: int = 100, triangular: bool = False,
                    microbatch: int | None = None) -> Callable:
    """Build the jit-able train step for one architecture config.

    Signature: (params, opt_state, batch) -> (params, opt_state, metrics).
    Gradients are accumulated in f32 across ``cfg.microbatch`` microbatches
    (a ``lax.scan``, so HLO size is constant in the count); non-finite
    grads skip the update and bump ``metrics["skipped"]``.  A ``mesh``
    needs Auto axes (``repro.launch.mesh.make_mesh``): the model places
    activations with sharding constraints.
    """
    mb = microbatch if microbatch is not None else max(1, cfg.microbatch)
    kind = cfg.optimizer

    def loss_for(params, batch):
        return transformer.loss_fn(params, batch, cfg, mesh=mesh,
                                   data_axes=data_axes,
                                   triangular=triangular)

    def train_step(params, opt_state, batch):
        if mb > 1:
            split = jax.tree.map(
                lambda a: a.reshape(mb, a.shape[0] // mb, *a.shape[1:]),
                batch)

            def acc(carry, mb_batch):
                g_acc, l_acc = carry
                (l, met), g = jax.value_and_grad(loss_for, has_aux=True)(
                    params, mb_batch)
                g = jax.tree.map(lambda x, y: x + y.astype(jnp.float32),
                                 g_acc, g)
                return (g, l_acc + l), met

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss), mets = jax.lax.scan(
                acc, (g0, jnp.zeros(())), split)
            grads = _tree_scale(grads, 1.0 / mb)
            loss = loss / mb
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), mets)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for, has_aux=True)(params, batch)

        lr = lr_schedule(opt_state.step, base_lr=base_lr, warmup=warmup,
                         total=total_steps)
        new_params, new_state = opt_lib.opt_update(
            kind, grads, opt_state, params, lr=lr)

        # fault tolerance: skip non-finite updates wholesale
        ok = jnp.logical_and(_all_finite(grads), jnp.isfinite(loss))
        pick = lambda n, o: jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), n, o)
        new_params = pick(new_params, params)
        new_state = jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), new_state,
            opt_state._replace(step=opt_state.step + 1))

        gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                             for g in jax.tree.leaves(grads)))
        metrics = dict(metrics)
        metrics.update(loss=loss, lr=lr, grad_norm=gnorm,
                       skipped=(~ok).astype(jnp.int32))
        return new_params, new_state, metrics

    return train_step


def make_eval_step(cfg, *, mesh=None, data_axes: tuple[str, ...] = ()
                   ) -> Callable:
    def eval_step(params, batch):
        loss, metrics = transformer.loss_fn(params, batch, cfg, mesh=mesh,
                                            data_axes=data_axes)
        return metrics
    return eval_step


def make_serve_step(cfg, *, mesh=None, data_axes: tuple[str, ...] = (),
                    kv_shard: tuple | None = None) -> Callable:
    """One-token decode step (the thing the decode_* shape cells lower)."""
    def serve_step(params, tokens, pos, cache):
        return transformer.decode_step(params, tokens, pos, cache, cfg,
                                       mesh=mesh, data_axes=data_axes,
                                       kv_shard=kv_shard)
    return serve_step


def make_prefill_step(cfg, *, mesh=None, data_axes: tuple[str, ...] = ()
                      ) -> Callable:
    def prefill_step(params, batch, cache):
        return transformer.prefill(params, batch, cache, cfg, mesh=mesh,
                                   data_axes=data_axes)
    return prefill_step
