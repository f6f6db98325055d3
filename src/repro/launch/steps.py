"""The step builder behind the trainer and the lowering tests.

``build_cell`` returns, for one (arch × shape × mesh) cell, the step
function plus the abstract inputs and shardings — everything
``.lower()`` needs, with zero device allocation.  The launcher's
``Trainer`` and the benchmark's training driver jit its ``train`` step;
the ``prefill`` and ``decode`` steps (``registry.forward`` and the
monolithic ``registry.decode_step``) are lowered only by
``tests/test_lowering.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSpec, input_specs
from repro.models import registry
from repro.sharding import merged_rules, axis_rules, resolve_spec, spec_tree
from repro.train import optimizer as opt


BATCH_AXES = {
    "tokens": ("batch", "act_seq"),
    "labels": ("batch", "act_seq"),
    "encoder_embeds": ("batch", "frames", "act_embed"),
    "vision_embeds": ("batch", "frames", "act_embed"),
    "pos": ("batch",),
}


@dataclasses.dataclass
class Cell:
    cfg: ModelConfig
    shape: ShapeSpec
    mesh: jax.sharding.Mesh
    step_fn: Callable
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any

    def lower(self):
        jitted = jax.jit(self.step_fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings)
        with compat.set_mesh(self.mesh):
            return jitted.lower(*self.abstract_args)


def _shardings_for(tree_axes, tree_shapes, mesh):
    specs = spec_tree(tree_axes, tree_shapes, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(batch_specs, mesh):
    out = {}
    for k, v in batch_specs.items():
        out[k] = NamedSharding(mesh, resolve_spec(BATCH_AXES[k], v.shape, mesh))
    return out


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
               opt_cfg: opt.AdamWConfig | None = None,
               *, microbatches: int = 1,
               cast_params_bf16: bool = False) -> Cell:
    """Build one (arch × shape × mesh) cell.

    Training options (both default off; the launcher's
    ``--microbatches`` and ``--cast-bf16``):
    * microbatches      — gradient accumulation; activation memory and
                          logits buffers shrink ×m.
    * cast_params_bf16  — cast f32 master params to bf16 *before* the
                          model consumes them, so FSDP all-gathers move
                          half the bytes.
    """
    rules = merged_rules(cfg.sharding_overrides)
    with axis_rules(rules):
        p_shapes = registry.param_shapes(cfg)
        p_axes = registry.param_axes(cfg)
        p_shard = _shardings_for(p_axes, p_shapes, mesh)
        b_specs = input_specs(cfg, shape)
        b_shard = batch_shardings(b_specs, mesh)

        if shape.kind == "train":
            ocfg = opt_cfg or opt.AdamWConfig()
            o_shapes = opt.state_shapes(p_shapes)
            o_shard = opt.AdamWState(
                step=NamedSharding(mesh, P()),
                mu=p_shard, nu=p_shard)

            def train_step(params, opt_state, batch):
                with axis_rules(rules):
                    cparams = params
                    if cast_params_bf16:
                        cdt = jnp.dtype(cfg.dtype)
                        cparams = jax.tree.map(
                            lambda p: p.astype(cdt)
                            if p.dtype == jnp.float32 and p.ndim > 1 else p,
                            params)
                    if microbatches > 1:
                        def split(x):
                            return x.reshape((microbatches,
                                              x.shape[0] // microbatches)
                                             + x.shape[1:])
                        mb = jax.tree.map(split, batch)
                        vg = jax.value_and_grad(registry.loss_fn, has_aux=True)

                        def body(carry, b):
                            acc_l, acc_g = carry
                            (l, _), g = vg(cparams, cfg, b)
                            return (acc_l + l,
                                    jax.tree.map(jnp.add, acc_g, g)), None

                        zg = jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32), cparams)
                        (loss, grads), _ = jax.lax.scan(
                            body, (jnp.zeros(()), zg), mb)
                        inv = 1.0 / microbatches
                        loss = loss * inv
                        grads = jax.tree.map(lambda g: g * inv, grads)
                        metrics = {"nll": loss, "aux": jnp.zeros(())}
                    else:
                        (loss, metrics), grads = jax.value_and_grad(
                            registry.loss_fn, has_aux=True)(cparams, cfg, batch)
                    grads = jax.tree.map(lambda g, p: g.astype(jnp.float32),
                                         grads, cparams)
                    params, opt_state, om = opt.apply(ocfg, opt_state, params, grads)
                    metrics = dict(metrics, loss=loss, **om)
                    return params, opt_state, metrics

            return Cell(
                cfg, shape, mesh, train_step,
                (p_shapes, o_shapes, b_specs),
                (p_shard, o_shard, b_shard),
                (p_shard, o_shard, None),
            )

        if shape.kind == "prefill":
            def prefill_step(params, batch):
                with axis_rules(rules):
                    logits, _ = registry.forward(params, cfg, batch)
                    return logits

            return Cell(
                cfg, shape, mesh, prefill_step,
                (p_shapes, b_specs),
                (p_shard, b_shard),
                None,
            )

        if shape.kind == "decode":
            c_shapes = registry.cache_shapes(cfg, shape.global_batch, shape.seq_len)
            c_axes = registry.cache_axes(cfg, shape.global_batch, shape.seq_len)
            c_shard = _shardings_for(c_axes, c_shapes, mesh)

            def serve_step(params, cache, batch):
                with axis_rules(rules):
                    logits, cache = registry.decode_step(
                        params, cfg, cache, batch["tokens"], batch["pos"])
                    return logits, cache

            return Cell(
                cfg, shape, mesh, serve_step,
                (p_shapes, c_shapes, b_specs),
                (p_shard, c_shard, b_shard),
                (None, c_shard),
            )

    raise ValueError(shape.kind)
