"""Production training launcher.

On a TPU host the script trains on the chips JAX sees (``--scale full``
is the published configuration); ``--devices N`` is a CPU rehearsal on N
forced host devices, e.g.:

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --devices 8 --mesh 4x2 --scale tiny --steps 20

All async subsystems (data prefetch, checkpointing, monitors) run on the
one collated progress engine (see DESIGN.md).
"""
import argparse
import dataclasses
import os
import sys


@dataclasses.dataclass
class TrainRun:
    """What a training run reports: the trainer's logged metrics, the
    final parameters (FSDP: the shard buckets), and how many of their
    leaves the run changed."""
    log: list
    params: object
    moved: int
    leaves: int


def _fingerprint(tree):
    """Per-leaf (sum, sum of |x|) on the host: cheap evidence that an
    update touched every leaf."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def sums(t):
        return jnp.stack([
            jnp.stack([jnp.sum(l.astype(jnp.float32)),
                       jnp.sum(jnp.abs(l.astype(jnp.float32)))])
            for l in jax.tree.leaves(t)])

    return np.asarray(jax.jit(sums)(tree))


def _moved(before, after) -> tuple[int, int]:
    import numpy as np
    return int(np.sum(np.any(before != after, axis=1))), len(before)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU rehearsal on N forced host devices (selects "
                         "the CPU platform)")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 -> (data=4, model=2)")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "small", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--cast-bf16", action="store_true")
    ap.add_argument("--collective-backend", default="native",
                    choices=["native", "user"],
                    help="native: gradient reduction inside the jitted "
                         "step (GSPMD); user: nonblocking user-space "
                         "collectives on the progress engine")
    ap.add_argument("--collective-chunks", type=int, default=4,
                    help="chunk pipelining factor for --collective-backend "
                         "user")
    ap.add_argument("--collective-algorithm", default="ring",
                    help="user-backend allreduce schedule "
                         "(ring/bidir/recursive_doubling/halving_doubling)")
    ap.add_argument("--collective-round-batch", type=int, default=0,
                    help="rounds fused per jitted dispatch in the user "
                         "backend (0 = auto from bucket size)")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-style FSDP over the mesh's data axis: "
                         "params + optimizer state sharded into flat "
                         "per-dtype buckets, grads reduce-scattered "
                         "(half the wire bytes of allreduce), full "
                         "params prefetched per step via persistent "
                         "all-gathers chained off compute futures; "
                         "works with both collective backends (native "
                         "uses in-program all_gather/psum_scatter) and "
                         "lifts the user backend's model-dim-1 limit")
    ap.add_argument("--fsdp-bucket-bytes", type=int, default=1 << 22,
                    help="flat-bucket size for --fsdp (smaller = more "
                         "buckets = more prefetch-chain links)")
    ap.add_argument("--pipeline", default="none",
                    choices=["none", "gpipe", "1f1b"],
                    help="pipeline-parallel backend: gpipe = the "
                         "monolithic lax.scan reference; 1f1b = the "
                         "event-driven continuation-DAG schedule on the "
                         "progress engine (per-stage streams, persistent "
                         "user-space p2p handoffs), composed with the "
                         "engine grad reducer over the data axis")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="pipeline stages (0 = the mesh's second dim); "
                         "with --pipeline the mesh is (data x stage) and "
                         "--microbatches sets M per step")
    ap.add_argument("--elastic", action="store_true",
                    help="membership-aware fault tolerance (user backend "
                         "only): a shared MembershipEpoch ties the "
                         "watchdog/heartbeat to the reducer's persistent "
                         "collectives; on invalidation the trainer "
                         "remeshes onto the survivors and retries the "
                         "step's batch")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.0,
                    help="enable a HeartbeatMonitor with this peer "
                         "timeout in seconds (0 = off; implies --elastic "
                         "epoch wiring)")
    ap.add_argument("--chaos-kill", type=int, default=0,
                    help="simulate the death of N devices at the first "
                         "logged step >= --chaos-kill-step (requires "
                         "--elastic)")
    ap.add_argument("--chaos-kill-step", type=int, default=10)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.pipeline != "none":
        return _run_pipeline(args)
    result = run(args)
    fmt = "{:.6f}" if args.fsdp else "{:.4f}"
    if result.log:
        print(f"final loss {fmt.format(result.log[-1]['loss'])}")
        print(f"params moved: {result.moved}/{result.leaves} leaves")
    else:
        # resume found a checkpoint at/past --steps: nothing left to run
        ckpt = os.path.join(args.ckpt_dir,
                            args.arch + ("-fsdp" if args.fsdp else ""))
        print(f"nothing to do: resumed past step {args.steps - 1} "
              f"(rm -r {ckpt} to restart)")
    return 0


def run(args: argparse.Namespace) -> TrainRun:
    """Train as the (parsed) flags say, on the devices JAX sees."""
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.configs.scales import scaled_config
    from repro.configs.shapes import ShapeSpec
    from repro.core import ProgressEngine
    from repro.data.pipeline import PrefetchPipeline, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell
    from repro.models import registry
    from repro.train import optimizer as opt_mod
    from repro.train.train_loop import Trainer, TrainLoopConfig

    n_dev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (n_dev, 1)
    mesh = make_mesh(shape, ("data", "model"))
    print(f"devices={n_dev} mesh={dict(mesh.shape)}")

    cfg = scaled_config(args.arch, args.scale)

    shape_spec = ShapeSpec("train", seq_len=args.seq,
                           global_batch=args.global_batch, kind="train")
    ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=5,
                               total_steps=max(args.steps, 10))

    user_backend = args.collective_backend == "user"
    if args.fsdp:
        if args.microbatches > 1 or args.cast_bf16:
            raise SystemExit("--fsdp does not compose with "
                             "--microbatches/--cast-bf16 yet")
        return _run_fsdp(args, cfg, ocfg, mesh)
    if user_backend:
        if dict(mesh.shape).get("model", 1) != 1:
            raise SystemExit("--collective-backend user on a 2-D mesh "
                             "requires --fsdp (ZeRO sharding over the "
                             "data axis); without it use model dim 1")
        if args.microbatches > 1:
            raise SystemExit("--collective-backend user does not compose "
                             "with --microbatches yet")

    cell = build_cell(cfg, shape_spec, mesh, opt_cfg=ocfg,
                      microbatches=args.microbatches,
                      cast_params_bf16=args.cast_bf16)
    jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)

    with compat.set_mesh(mesh):
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt_mod.init(params)
        # place onto the cell's shardings (FSDP/TP distribution)
        params = jax.device_put(params, cell.in_shardings[0])
        opt_state = jax.device_put(opt_state, cell.in_shardings[1])
        b_shardings = cell.in_shardings[2]
    before = _fingerprint(params)

    eng = ProgressEngine()
    src = SyntheticLM(cfg.vocab_size, args.seq, args.global_batch, seed=5)

    def to_batch(b):
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.is_encoder_decoder:
            batch["encoder_embeds"] = jnp.ones(
                (args.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.bfloat16)
        return batch

    pipe = PrefetchPipeline(map(to_batch, iter(src)), eng, depth=3)

    def step_fn(params, opt_state, batch):
        batch = {k: jax.device_put(v, b_shardings[k]) for k, v in batch.items()}
        return jitted(params, opt_state, batch)

    elastic_on = args.elastic or args.heartbeat_timeout > 0 \
        or args.chaos_kill > 0
    if elastic_on and not user_backend:
        raise SystemExit("--elastic/--chaos-kill/--heartbeat-timeout "
                         "require --collective-backend user (the epoch "
                         "invalidates user-space persistent collectives)")

    from repro.collectives.nonblocking import CollectiveSpec
    spec = CollectiveSpec(backend=args.collective_backend,
                          algorithm=args.collective_algorithm,
                          chunks=args.collective_chunks,
                          round_batch=args.collective_round_batch or None)

    split, reducer, epoch, remesh_fn = None, None, None, None
    if user_backend:
        # Split step: shard_map-local grads (stacked per device) + an
        # engine-driven bucketed allreduce + a jitted apply.  Traced
        # OUTSIDE the mesh context so in-model shard hints no-op inside
        # the manual shard_map region.
        from jax.sharding import PartitionSpec as P
        from repro.collectives.overlap import EngineGradReducer
        from repro.train.train_loop import UserCollectiveStep

        def local_grad(params, batch):
            cparams = params
            if args.cast_bf16:
                # mirror build_cell's cast_params_bf16: bf16 forward,
                # f32 master params and gradients
                cdt = jnp.dtype(cfg.dtype)
                cparams = jax.tree.map(
                    lambda p: p.astype(cdt)
                    if p.dtype == jnp.float32 and p.ndim > 1 else p, params)
            (loss, mets), g = jax.value_and_grad(
                registry.loss_fn, has_aux=True)(cparams, cfg, batch)
            stacked = jax.tree.map(
                lambda v: v[None].astype(jnp.float32), g)
            mets = dict(mets, loss=loss)
            return jax.tree.map(lambda v: v[None], mets), stacked

        def make_grad_fn(mesh_):
            return jax.jit(compat.shard_map(
                local_grad, mesh=mesh_, in_specs=(P(), P("data")),
                out_specs=P("data")))

        @jax.jit
        def apply_fn(params, opt_state, grads, stacked_mets):
            params, opt_state, om = opt_mod.apply(ocfg, opt_state,
                                                  params, grads)
            mets = {k: jnp.mean(v) for k, v in stacked_mets.items()}
            return params, opt_state, dict(mets, **om)

        if elastic_on:
            from repro.collectives.nonblocking import MembershipEpoch
            epoch = MembershipEpoch()

        reducer = EngineGradReducer(mesh, "data", engine=eng, spec=spec,
                                    mean=True, epoch=epoch)
        split = UserCollectiveStep(make_grad_fn(mesh), apply_fn, reducer,
                                   spec=spec)

        if elastic_on:
            from jax.sharding import NamedSharding

            from repro.distributed import elastic

            def remesh_fn(exc, params, opt_state):
                # survivors' mesh: pure data-parallel (model dim stays 1)
                survivors = getattr(exc, "survivors", None) \
                    or len(jax.devices())
                new_mesh = elastic.remesh(survivors, prefer_model=1)
                print(f"remesh: {getattr(exc, 'survivors', '?')} "
                      f"survivor(s) -> mesh {dict(new_mesh.shape)}")
                reducer.remesh(new_mesh, "data")
                params = jax.device_put(
                    params, NamedSharding(new_mesh, P()))
                opt_state = jax.device_put(
                    opt_state, NamedSharding(new_mesh, P()))
                return (UserCollectiveStep(make_grad_fn(new_mesh),
                                           apply_fn, reducer, spec=spec),
                        params, opt_state)

        print(f"collective backend: user "
              f"({reducer.algorithm}, chunks={args.collective_chunks}, "
              f"round_batch={args.collective_round_batch or 'auto'}, "
              f"persistent schedules per bucket)")

    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, checkpoint_every=10,
        checkpoint_dir=os.path.join(args.ckpt_dir, args.arch),
        log_every=5, collective_spec=spec)
    hooks = [lambda s, m: print(
        f"step {s:4d} loss={m['loss']:.4f} "
        f"{m['step_time_s'] * 1e3:.0f}ms", flush=True)]
    if args.heartbeat_timeout > 0:
        from repro.distributed.fault_tolerance import HeartbeatMonitor
        hb = HeartbeatMonitor(
            eng, [f"rank{i}" for i in range(len(jax.devices()))],
            timeout=args.heartbeat_timeout, epoch=epoch)
        hooks.append(lambda s, m: [hb.beat(p) for p in hb.alive])
    if args.chaos_kill > 0:
        killed = []

        def chaos_hook(s, m):
            if s >= args.chaos_kill_step and not killed:
                killed.append(s)
                survivors = max(1, len(jax.devices()) - args.chaos_kill)
                print(f"chaos: killing {args.chaos_kill} device(s) at "
                      f"step {s} -> {survivors} survivors")
                epoch.invalidate(survivors=survivors,
                                 reason=f"--chaos-kill {args.chaos_kill}")
        hooks.append(chaos_hook)
    trainer = Trainer(
        step_fn, params, opt_state, pipe, loop_cfg,
        engine=eng, split_step=split, epoch=epoch, remesh_fn=remesh_fn,
        hooks=hooks)
    if user_backend:
        log = trainer.run()
    else:
        with compat.set_mesh(mesh):
            log = trainer.run()
    pipe.close()
    if reducer is not None:
        reducer.close()
    return TrainRun(log, trainer.params,
                    *_moved(before, _fingerprint(trainer.params)))


def build_fsdp_programs(cfg, ocfg, mesh, layout, *, axis="data"):
    """The three jitted FSDP step programs over ``mesh``'s data axis.

    Shared verbatim by the user and native backends — the *only*
    difference between the two paths is who moves the bytes (persistent
    engine handles vs the in-program ``all_gather``/``psum_scatter``
    pair in ``ag_fn``/``rs_fn``), so a loss-trajectory comparison
    measures exactly the collectives.

    * ``grad_fn(gathered_flats, batch)`` — unflattens the full flat
      buckets ``[n, W]`` in-program, runs loss+grad, reflattens to
      stacked grad buckets ``[n, W]``;
    * ``apply_fn(shards, opt_state, grad_shards, stacked_mets)`` — the
      sharded AdamW step (each rank updates only its block; grad norm
      via cross-data psum of shard sum-of-squares);
    * ``ag_fn(shards)`` / ``rs_fn(flat_grads)`` — the native
      collectives, as standalone programs mirroring the engine handles.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.models import registry
    from repro.train import optimizer as opt_mod

    n = layout.n
    B = layout.num_buckets

    def local_grad(flats, batch):
        params = layout.unflatten([f[0] for f in flats])
        (loss, mets), g = jax.value_and_grad(
            registry.loss_fn, has_aux=True)(params, cfg, batch)
        gleaves = [l.astype(jnp.float32) for l in jax.tree.leaves(g)]
        flat_g = [layout.flatten_bucket(gleaves, b)[None] for b in range(B)]
        mets = dict(mets, loss=loss)
        return jax.tree.map(lambda v: v[None], mets), flat_g

    grad_fn = jax.jit(compat.shard_map(
        local_grad, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis)))

    state_spec = opt_mod.AdamWState(step=P(), mu=P(axis), nu=P(axis))

    def local_apply(shards, opt_state, gshards, smets):
        state = opt_mod.AdamWState(opt_state.step,
                                   [m[0] for m in opt_state.mu],
                                   [v[0] for v in opt_state.nu])
        new_sh, new_state, om = opt_mod.apply_shards(
            ocfg, state, [s[0] for s in shards], [g[0] for g in gshards],
            axis=axis, grad_scale=1.0 / n)
        mets = {k: jax.lax.pmean(v[0], axis) for k, v in smets.items()}
        return ([s[None] for s in new_sh],
                opt_mod.AdamWState(new_state.step,
                                   [m[None] for m in new_state.mu],
                                   [v[None] for v in new_state.nu]),
                dict(mets, **om))

    apply_fn = jax.jit(compat.shard_map(
        local_apply, mesh=mesh,
        in_specs=(P(axis), state_spec, P(axis), P(axis)),
        out_specs=(P(axis), state_spec, P())))

    def local_ag(shards):
        return [jax.lax.all_gather(s[0], axis, tiled=True)[None]
                for s in shards]

    ag_fn = jax.jit(compat.shard_map(
        local_ag, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis)))

    def local_rs(flat_grads):
        return [jax.lax.psum_scatter(g[0], axis, scatter_dimension=0,
                                     tiled=True)[None]
                for g in flat_grads]

    rs_fn = jax.jit(compat.shard_map(
        local_rs, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis)))

    return grad_fn, apply_fn, ag_fn, rs_fn


def _run_fsdp(args, cfg, ocfg, mesh):
    """ZeRO-style FSDP rehearsal over the mesh's data axis.

    Params and AdamW moments live as flat per-dtype bucket shards
    ``[n, W/n]`` (rank ``r`` owns row ``r``); every step all-gathers the
    full flat buckets for the forward/backward and reduce-scatters the
    grad buckets so each rank receives only the block it will apply —
    half the wire bytes of the allreduce path.  ``--collective-backend
    user`` moves both through persistent engine handles, with the next
    step's gathers chained as continuations off the optimizer's compute
    futures; ``native`` runs the same step programs with in-program
    ``all_gather``/``psum_scatter``.  Other mesh axes (``model``)
    replicate, so the same step runs unchanged on (4,1) and (2,2).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.collectives.nonblocking import CollectiveSpec, MembershipEpoch
    from repro.collectives.overlap import FsdpLayout, FsdpReducer
    from repro.core import ProgressEngine
    from repro.data.pipeline import PrefetchPipeline, SyntheticLM
    from repro.models import registry
    from repro.train import optimizer as opt_mod
    from repro.train.train_loop import FsdpStep, Trainer, TrainLoopConfig

    axis = "data"
    user_backend = args.collective_backend == "user"
    spec = CollectiveSpec(backend=args.collective_backend,
                          algorithm=args.collective_algorithm,
                          chunks=args.collective_chunks,
                          round_batch=args.collective_round_batch or None)
    eng = ProgressEngine()

    elastic_on = args.elastic or args.heartbeat_timeout > 0 \
        or args.chaos_kill > 0
    if elastic_on and not user_backend:
        raise SystemExit("--elastic/--chaos-kill/--heartbeat-timeout "
                         "require --collective-backend user")
    epoch = MembershipEpoch() if elastic_on else None

    with compat.set_mesh(mesh):
        params = registry.init_params(cfg, jax.random.PRNGKey(0))

    def shard_state(mesh_, params_tree, mu_tree=None, nu_tree=None,
                    step=None):
        n = dict(mesh_.shape)[axis]
        layout = FsdpLayout(params_tree, n, args.fsdp_bucket_bytes)
        sharding = NamedSharding(mesh_, P(axis))
        shards = layout.shard_params(params_tree, mesh_, axis)
        if mu_tree is None:
            mu = [jax.device_put(jnp.zeros_like(s), sharding)
                  for s in shards]
            nu = [jax.device_put(jnp.zeros_like(s), sharding)
                  for s in shards]
            step = jnp.zeros((), jnp.int32)
        else:
            mu = layout.shard_params(mu_tree, mesh_, axis)
            nu = layout.shard_params(nu_tree, mesh_, axis)
        return layout, shards, opt_mod.AdamWState(step, mu, nu)

    layout, shards, opt_state = shard_state(mesh, params)
    before = _fingerprint(shards)
    print(f"fsdp: {layout.num_buckets} bucket(s), shard widths "
          f"{[w // layout.n for w in layout.widths]} over {axis}="
          f"{layout.n} ({args.collective_backend} backend)")
    grad_fn, apply_fn, ag_fn, rs_fn = build_fsdp_programs(
        cfg, ocfg, mesh, layout, axis=axis)

    reducer, split, step_fn, remesh_fn = None, None, None, None
    if user_backend:
        reducer = FsdpReducer(mesh, axis, engine=eng, spec=spec,
                              bucket_bytes=args.fsdp_bucket_bytes,
                              epoch=epoch)
        split = FsdpStep(grad_fn, apply_fn, reducer, spec=spec)
    else:
        def step_fn(shards, opt_state, batch):
            flats = ag_fn(shards)
            smets, flat_grads = grad_fn(flats, batch)
            gshards = rs_fn(flat_grads)
            return apply_fn(shards, opt_state, gshards, smets)

    if user_backend and elastic_on:
        from repro.distributed import elastic

        model_dim = dict(mesh.shape).get("model", 1)

        def remesh_fn(exc, shards_, opt_state_):
            nonlocal layout
            survivors = getattr(exc, "survivors", None) \
                or len(jax.devices())
            new_mesh = elastic.remesh(survivors, prefer_model=model_dim)
            print(f"remesh: {getattr(exc, 'survivors', '?')} survivor(s) "
                  f"-> mesh {dict(new_mesh.shape)}")
            # shard widths depend on the data-axis size: gather the old
            # shards on host, rebuild the layout + programs for the new
            # mesh, re-shard params AND moments (step counter carries)
            params_tree = layout.unshard_params(shards_)
            mu_tree = layout.unshard_params(opt_state_.mu)
            nu_tree = layout.unshard_params(opt_state_.nu)
            reducer.remesh(new_mesh, axis)
            layout, new_shards, new_state = shard_state(
                new_mesh, params_tree, mu_tree, nu_tree, opt_state_.step)
            g2, a2, _, _ = build_fsdp_programs(cfg, ocfg, new_mesh,
                                               layout, axis=axis)
            return (FsdpStep(g2, a2, reducer, spec=spec),
                    new_shards, new_state)

    src = SyntheticLM(cfg.vocab_size, args.seq, args.global_batch, seed=5)

    def to_batch(b):
        return {k: jnp.asarray(v) for k, v in b.items()}

    pipe = PrefetchPipeline(map(to_batch, iter(src)), eng, depth=3)

    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, checkpoint_every=max(args.steps, 10),
        checkpoint_dir=os.path.join(args.ckpt_dir, args.arch + "-fsdp"),
        log_every=5, collective_spec=spec)
    hooks = [lambda s, m: print(
        f"step {s:4d} loss={m['loss']:.6f} "
        f"{m['step_time_s'] * 1e3:.0f}ms", flush=True)]
    if args.heartbeat_timeout > 0:
        from repro.distributed.fault_tolerance import monitor_mesh
        hb = monitor_mesh(eng, mesh, axis, timeout=args.heartbeat_timeout,
                          epoch=epoch)
        hooks.append(lambda s, m: [hb.beat(p) for p in hb.alive])
    if args.chaos_kill > 0:
        killed = []

        def chaos_hook(s, m):
            if s >= args.chaos_kill_step and not killed:
                killed.append(s)
                survivors = max(1, len(jax.devices()) - args.chaos_kill)
                print(f"chaos: killing {args.chaos_kill} device(s) at "
                      f"step {s} -> {survivors} survivors")
                epoch.invalidate(survivors=survivors,
                                 reason=f"--chaos-kill {args.chaos_kill}")
        hooks.append(chaos_hook)

    trainer = Trainer(step_fn, shards, opt_state, pipe, loop_cfg,
                      engine=eng, split_step=split, epoch=epoch,
                      remesh_fn=remesh_fn, hooks=hooks)
    log = trainer.run()
    pipe.close()
    if reducer is not None:
        print(f"prefetch overlap: {reducer.prefetch_overlap:.3f} "
              f"({reducer.gathers} chained gathers)")
        reducer.close()
    return TrainRun(log, trainer.params,
                    *_moved(before, _fingerprint(trainer.params)))


def _run_pipeline(args):
    """Pipeline-parallel rehearsal: a residual-MLP stage stack trained
    against a fixed linear teacher, on a (data x stage) mesh.

    * ``--pipeline gpipe``: the monolithic ``lax.scan`` reference —
      forward AND backward differentiate through the tick scan inside
      one jitted step (data dim must be 1).
    * ``--pipeline 1f1b``: one event-driven :class:`PipelineSchedule`
      per data row (per-stage executor-owned streams, persistent p2p
      handoffs), composed with the existing ``EngineGradReducer`` over
      the data axis of the 2-D mesh — the split-step
      ``UserCollectiveStep`` path, exactly as for plain data-parallel.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.collectives.overlap import EngineGradReducer
    from repro.core import ProgressEngine, ProgressExecutor
    from repro.data.pipeline import PrefetchPipeline
    from repro.distributed import pipeline as pl
    from repro.train import optimizer as opt_mod
    from repro.train.train_loop import (Trainer, TrainLoopConfig,
                                        UserCollectiveStep)

    n_dev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        S0 = args.pipeline_stages or n_dev
        shape = (max(n_dev // S0, 1), S0)
    D, S = shape
    if args.pipeline_stages and args.pipeline_stages != S:
        raise SystemExit(f"--pipeline-stages {args.pipeline_stages} "
                         f"contradicts --mesh {args.mesh} (stage dim {S})")
    if D * S > n_dev:
        raise SystemExit(f"mesh {D}x{S} needs {D * S} devices, have {n_dev}")
    if args.pipeline == "gpipe" and D != 1:
        raise SystemExit("--pipeline gpipe differentiates through one "
                         "scan; use a 1xS mesh (data dim 1)")
    mesh = Mesh(np.array(jax.devices()[:D * S]).reshape(D, S),
                ("data", "stage"))
    M = max(args.microbatches, 1)
    d_model, d_hidden, mb = 16, 32, max(args.global_batch, 1)
    print(f"pipeline={args.pipeline} mesh={dict(mesh.shape)} "
          f"microbatches={M} "
          f"bubble={pl.bubble_fraction(S, M, args.pipeline):.3f} "
          f"peak_act={pl.peak_activation_microbatches(S, M, args.pipeline)}")

    def stage_fn(p, x):
        return x + jnp.tanh(x @ p["w1"]) @ p["w2"]

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "w1": jax.random.normal(k1, (S, d_model, d_hidden)) * 0.1,
        "w2": jax.random.normal(k2, (S, d_hidden, d_model)) * 0.1,
    }
    ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=5,
                               total_steps=max(args.steps, 10))
    opt_state = opt_mod.init(params)

    eng = ProgressEngine()
    ex = ProgressExecutor(eng, num_workers=2).start()
    eng.attach_executor(ex)

    rng = np.random.default_rng(7)
    teacher = (rng.standard_normal((d_model, d_model))
               .astype(np.float32) * 0.3)

    def gen():
        while True:
            xs = rng.standard_normal((D, M, mb, d_model)).astype(np.float32)
            yield {"xs": jnp.asarray(xs), "ts": jnp.asarray(xs @ teacher)}

    pipe = PrefetchPipeline(gen(), eng, depth=3)

    @jax.jit
    def apply_fn(params, opt_state, grads, stacked_mets):
        params, opt_state, om = opt_mod.apply(ocfg, opt_state,
                                              params, grads)
        mets = {k: jnp.mean(v) for k, v in stacked_mets.items()}
        return params, opt_state, dict(mets, **om)

    from repro.collectives.nonblocking import CollectiveSpec
    pspec = CollectiveSpec(
        backend="user" if args.pipeline == "1f1b" else "native",
        algorithm=args.collective_algorithm,
        chunks=args.collective_chunks,
        round_batch=args.collective_round_batch or None)
    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, checkpoint_every=10,
        checkpoint_dir=os.path.join(args.ckpt_dir,
                                    f"pipeline-{args.pipeline}"),
        log_every=5, collective_spec=pspec, pipeline=args.pipeline)
    hooks = [lambda s, m: print(
        f"step {s:4d} loss={m['loss']:.4f} "
        f"{m['step_time_s'] * 1e3:.0f}ms", flush=True)]

    rows, reducer = [], None
    if args.pipeline == "gpipe":
        gmesh = Mesh(mesh.devices[0], ("stage",))
        params = jax.device_put(params, NamedSharding(gmesh, P("stage")))
        gp = pl.gpipe(stage_fn, gmesh, "stage", S)

        def gp_loss(p, xs, ts):
            ys = gp(p, xs)
            per = jnp.stack([loss_fn(ys[m], ts[m]) for m in range(M)])
            return jnp.mean(per)

        @jax.jit
        def step_fn(p, o, batch):
            loss, g = jax.value_and_grad(gp_loss)(
                p, batch["xs"][0], batch["ts"][0])
            p, o, om = opt_mod.apply(ocfg, o, p, g)
            return p, o, dict(loss=loss, **om)

        trainer = Trainer(step_fn, params, opt_state, pipe, loop_cfg,
                          engine=eng, hooks=hooks)
    else:
        params = jax.device_put(params, NamedSharding(mesh, P("stage")))
        for r in range(D):
            rmesh = Mesh(mesh.devices[r], ("stage",))
            rows.append(pl.PipelineSchedule(
                stage_fn, rmesh, "stage", S, loss_fn=loss_fn,
                engine=eng, executor=ex, name=f"pipe{r}"))
        sharding2d = NamedSharding(mesh, P("data", "stage"))

        def stack_rows(*row_leaves):
            # row r's [S, w...] leaf is one single-device [1, w...] shard
            # per stage — reassemble all D*S of them into one global
            # [D, S, w...] array for the data-axis reduction (zero-copy)
            shards = [sh[None]
                      for leaf in row_leaves
                      for sh in pl.PipelineSchedule._by_stage(leaf)]
            shape = (D, S) + tuple(row_leaves[0].shape[1:])
            return jax.make_array_from_single_device_arrays(
                shape, sharding2d, shards)

        def grad_fn(params, batch):
            # launch every row's DAG before waiting on any: the rows'
            # stage streams progress concurrently under the executor
            reqs = [rows[r].istep(params, batch["xs"][r], batch["ts"][r])
                    for r in range(D)]
            outs = [rows[r]._wait(reqs[r], timeout=600) for r in range(D)]
            # each row's loss scalar lives on that row's last-stage
            # device; hop through host for the [D] metrics stack
            losses = jnp.asarray(np.stack(
                [np.asarray(o[0]) for o in outs]))
            grads = jax.tree.map(stack_rows, *[o[1] for o in outs])
            return {"loss": losses}, grads

        reducer = EngineGradReducer(mesh, "data", engine=eng, spec=pspec,
                                    mean=True)
        split = UserCollectiveStep(grad_fn, apply_fn, reducer, spec=pspec)
        trainer = Trainer(None, params, opt_state, pipe, loop_cfg,
                          engine=eng, split_step=split, hooks=hooks)

    log = trainer.run()
    pipe.close()
    for r in rows:
        r.close()
    if reducer is not None:
        reducer.close()
    ex.shutdown(drain=True, timeout=600)
    if log:
        print(f"final loss {log[-1]['loss']:.4f}")
        if rows:
            st = rows[0].stats()
            print(f"pipe0 stats: hops={st['hop_starts']} "
                  f"p2p_completions={st['p2p_stream_completions']} "
                  f"blocking_waits={st['blocking_waits']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
