"""Sharded AdamW (+ cosine schedule, global-norm clipping).

Optimizer moments are sharded exactly like the parameters (the rule table
puts them on the FSDP axes), which is what makes ZeRO-style training of
the 405B config possible: params+moments are distributed over all chips.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros_like(p)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def state_axes(param_axes_tree):
    """Sharding axes for the optimizer state given the param axes tree."""
    return AdamWState(step=(), mu=param_axes_tree, nu=param_axes_tree)


def state_shapes(param_shapes_tree):
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=param_shapes_tree,
        nu=param_shapes_tree,
    )


def schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def global_norm(tree):
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def init_shards(shards) -> AdamWState:
    """Optimizer state over FSDP flat shard buckets: mu/nu are lists
    shaped like the shard stacks (ZeRO — each rank holds moments only
    for the block it owns)."""
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=[jnp.zeros_like(s) for s in shards],
        nu=[jnp.zeros_like(s) for s in shards],
    )


def apply_shards(cfg: AdamWConfig, state: AdamWState, shards, grad_shards,
                 *, axis: str | None = None, grad_scale: float = 1.0):
    """One AdamW step over flat shard buckets (the ZeRO step: each rank
    updates only the parameter block it owns).

    ``shards``/``grad_shards`` are lists of same-shaped local shard
    arrays (under ``shard_map`` each rank sees its own ``[1, W/n]``
    row).  AdamW is elementwise, so flat-bucket math equals per-leaf
    math given the same clip scale and schedule; the one cross-rank
    quantity is the global grad norm, assembled from local
    sum-of-squares with a ``psum`` over ``axis`` (pass None when the
    stacks are resident unsharded).  ``grad_scale`` folds the
    data-parallel mean into the step (reduce-scatter delivers sums).
    Zero-padded bucket tails stay zero: grad 0 keeps mu/nu 0 and weight
    decay multiplies a zero param.

    Returns ``(new_shards, new_state, metrics)``.
    """
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32) * grad_scale))
             for g in grad_shards)
    if axis is not None:
        sq = jax.lax.psum(sq, axis)
    gnorm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    step = state.step + 1
    lr = schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * grad_scale * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) \
            + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    out = [upd(p, g, m, v) for p, g, m, v in
           zip(shards, grad_shards, state.mu, state.nu)]
    new_shards = [o[0] for o in out]
    new_state = AdamWState(step, [o[1] for o in out], [o[2] for o in out])
    return new_shards, new_state, {"grad_norm": gnorm, "lr": lr}


@jax.named_scope("adamw")
def apply(cfg: AdamWConfig, state: AdamWState, params, grads):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    step = state.step + 1
    lr = schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.mu)
    flat_v = jax.tree.leaves(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_p, AdamWState(step, new_m, new_v), {"grad_norm": gnorm, "lr": lr}
