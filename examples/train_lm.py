"""End-to-end training driver with checkpoint/restart.

Any assigned architecture is selectable; ``--scale tiny|small|full``
shrinks the config for CPU demonstration (full configs target TPU pods
via ``repro.launch.train``).  Demonstrates: engine-driven prefetch +
async checkpointing, fault-tolerant restart (rerun the same command — it
resumes from the last committed step), straggler stats.

    PYTHONPATH=src python examples/train_lm.py --arch smollm-360m \
        --scale tiny --steps 30
"""
import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_configs
from repro.configs.scales import SCALES
from repro.core import ProgressEngine
from repro.data.pipeline import PrefetchPipeline, SyntheticLM
from repro.models import registry
from repro.train import optimizer as opt_mod
from repro.train.train_loop import Trainer, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list_configs())
    ap.add_argument("--scale", default="tiny", choices=list(SCALES))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    overrides = dict(SCALES[args.scale])
    cfg = get_config(args.arch)
    if overrides:
        if cfg.moe:
            overrides["moe"] = cfg.moe.__class__(
                num_experts=4, top_k=2, expert_d_ff=overrides["d_ff"] // 2,
                group_size=64)
            overrides["d_ff"] = overrides["d_ff"] // 2
        if cfg.ssm:
            overrides["ssm"] = cfg.ssm.__class__(
                d_state=16, expand=2, head_dim=16, chunk_size=16)
        if cfg.shared_attn_every:
            overrides.update(num_layers=5, shared_attn_every=2,
                             shared_attn_lora_rank=8)
        if cfg.is_encoder_decoder:
            overrides.update(num_encoder_layers=2, encoder_frames=16,
                             max_position_embeddings=256)
        cfg = cfg.with_overrides(**overrides)

    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={args.arch} scale={args.scale} params={n / 1e6:.1f}M")

    ocfg = opt_mod.AdamWConfig(lr=args.lr, warmup_steps=5,
                               total_steps=max(args.steps, 10))
    opt_state = opt_mod.init(params)

    def make_batch(b):
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.is_encoder_decoder:
            batch["encoder_embeds"] = jnp.ones(
                (batch["tokens"].shape[0], cfg.encoder_frames, cfg.d_model),
                jnp.bfloat16)
        return batch

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: registry.loss_fn(p, cfg, batch), has_aux=True)(params)
        params, opt_state, om = opt_mod.apply(ocfg, opt_state, params, grads)
        return params, opt_state, dict(loss=loss, **om)

    eng = ProgressEngine()
    src = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=11)
    pipe = PrefetchPipeline(map(make_batch, iter(src)), eng, depth=3)

    trainer = Trainer(
        step_fn, params, opt_state, pipe,
        TrainLoopConfig(total_steps=args.steps, checkpoint_every=10,
                        checkpoint_dir=os.path.join(args.ckpt_dir, args.arch),
                        log_every=5, resume=True),
        engine=eng,
        hooks=[lambda s, m: print(
            f"step {s:4d} loss={m['loss']:.4f} gnorm={m['grad_norm']:.2f} "
            f"lr={m['lr']:.2e} {m['step_time_s'] * 1e3:.0f}ms")])
    if trainer.ckpt.latest_step() is not None:
        print(f"resuming from committed step {trainer.ckpt.latest_step()}")
    log = trainer.run()
    pipe.close()
    print(f"done: loss {log[0]['loss']:.4f} -> {log[-1]['loss']:.4f}; "
          f"stragglers flagged: {dict(trainer.straggler.flagged)}")


if __name__ == "__main__":
    main()
