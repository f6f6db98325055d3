"""Production serving launcher: continuous batching on the progress engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --scale tiny --requests 8 --slots 4

``--scale full`` serves the published configuration (random weights) on
the chip JAX sees.  The run exits 1 if any request failed or fell short
of its tokens, or a decode error or dropped task was recorded.

Model-axis-sharded decode (vocab-parallel unembed) with the per-step
logits all-gather either in-program (native) or as persistent user-space
collectives on the serve-collective stream:

    PYTHONPATH=src python -m repro.launch.serve --devices 2 \
        --model-shards 2 --collective-backend user

Continuous batching on a paged KV cache (length-bucketed admission,
chunked prefill interleaved with decode, preemption under block
pressure) is the only cache layout — the fixed-slot path is retired:

    PYTHONPATH=src python -m repro.launch.serve \
        --slots 12 --kv-block-size 16 --kv-blocks 65 --requests 64
"""
import argparse
import dataclasses
import os
import sys

import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "small", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(2, 7),
                    metavar=("MIN", "MAX"),
                    help="prompt lengths are drawn uniformly from "
                         "[MIN, MAX]")
    ap.add_argument("--cache-mode", default="paged",
                    choices=["slots", "paged"],
                    help="KV cache layout; 'paged' (the only mode) is a "
                         "paged block pool with continuous batching "
                         "(backlog admission, chunked prefill, preemption)."
                         "  'slots' is retired and errors with a migration "
                         "hint.")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="positions per KV block (paged mode)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="total pool blocks incl. the reserved scratch "
                         "block (0 = slots*ceil(max_seq/block)+1, i.e. "
                         "the fixed-slot capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt positions each mid-prefill lane is fed "
                         "by one fused prefill call, run between decode "
                         "steps")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU rehearsal on N forced host devices (selects "
                         "the CPU platform)")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="shard decode over a 'model' mesh axis of this "
                         "size (0 = unsharded)")
    ap.add_argument("--collective-backend", default="native",
                    choices=["native", "user"],   # -> one CollectiveSpec
                    help="per-step logits all-gather: native in-program "
                         "lax.all_gather, or persistent user-space "
                         "allgather on the serve-collective stream")
    ap.add_argument("--collective-chunks", type=int, default=1,
                    help="chunk pipelining factor for the user backend")
    ap.add_argument("--collective-round-batch", type=int, default=0,
                    help="rounds fused per dispatch in the user backend "
                         "(0 = auto from payload size)")
    ap.add_argument("--progress-workers", type=int, default=0,
                    help="N background progress threads (0 = caller-driven)")
    ap.add_argument("--continuation-policy", default="deferred",
                    choices=["inline", "deferred"],
                    help="completion callbacks run inline on the progress "
                         "thread, or deferred to a bounded owner drain")
    ap.add_argument("--continuation-max-drain", type=int, default=64,
                    help="max continuations executed per drain (deferred "
                         "policy backpressure bound)")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.0,
                    help="enable a HeartbeatMonitor subsystem with this "
                         "peer timeout in seconds (0 = off); a dead peer "
                         "invalidates the membership epoch and the server "
                         "drains, remeshes and re-admits")
    ap.add_argument("--watchdog-limit", type=float, default=0.0,
                    help="enable a StepWatchdog subsystem with this "
                         "wall-clock step limit in seconds (0 = off)")
    ap.add_argument("--chaos-kill", type=int, default=0,
                    help="simulate the death of N devices after half the "
                         "requests finish (invalidates the membership "
                         "epoch) and report the recovery")
    ap.add_argument("--stats", action="store_true",
                    help="print progress statistics after serving")
    return ap.parse_args(argv)


@dataclasses.dataclass
class Server:
    """What ``build`` assembles: the model, the serve engine and the
    progress machinery around it."""
    cfg: object
    srv: object
    engine: object
    executor: object = None
    epoch: object = None
    heartbeat: object = None


def build(args: argparse.Namespace) -> Server:
    """The configured model (random weights from PRNGKey(0)) behind a
    ServeEngine, as the flags say."""
    import jax

    from repro.collectives.nonblocking import CollectiveSpec
    from repro.configs.scales import scaled_config
    from repro.core import ProgressEngine, ProgressExecutor
    from repro.models import registry
    from repro.serve.engine import ServeEngine

    if args.cache_mode == "slots":
        raise SystemExit(
            "--cache-mode slots was retired: the paged pool serves the "
            "same bytes at block granularity.  Drop the flag, or mimic "
            "fixed lanes with --kv-block-size B --kv-blocks "
            "(slots*max_seq//B + 1).")

    spec = CollectiveSpec(backend=args.collective_backend,
                          chunks=args.collective_chunks,
                          round_batch=args.collective_round_batch or None)
    cfg = scaled_config(args.arch, args.scale)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    eng = ProgressEngine()
    executor = None
    if args.progress_workers > 0:
        executor = ProgressExecutor(
            eng, args.progress_workers,
            continuation_max_drain=args.continuation_max_drain)
    mesh = None
    if args.model_shards > 0:
        from repro.launch.mesh import make_mesh
        if args.model_shards > len(jax.devices()):
            raise SystemExit(f"--model-shards {args.model_shards} > "
                             f"{len(jax.devices())} devices (use --devices)")
        mesh = make_mesh((args.model_shards,), ("model",))
    elif args.collective_backend == "user":
        raise SystemExit("--collective-backend user requires --model-shards "
                         ">= 1 (the user backend is the sharded decode's "
                         "logits all-gather)")
    # fault tolerance: one membership epoch shared by the monitors and
    # the serve engine's persistent collectives — a dead peer or a hung
    # step fails in-flight starts retryably, and the engine drains,
    # remeshes onto the survivors, and re-admits from the backlog
    epoch = None
    heartbeat = None
    if args.heartbeat_timeout > 0 or args.watchdog_limit > 0 \
            or args.chaos_kill > 0:
        from repro.collectives.nonblocking import MembershipEpoch
        from repro.distributed.fault_tolerance import (HeartbeatMonitor,
                                                       StepWatchdog)
        epoch = MembershipEpoch()
        if args.heartbeat_timeout > 0:
            heartbeat = HeartbeatMonitor(
                eng, [f"rank{i}" for i in range(len(jax.devices()))],
                timeout=args.heartbeat_timeout, epoch=epoch)
        if args.watchdog_limit > 0:
            StepWatchdog(eng, limit=args.watchdog_limit, epoch=epoch)
    srv = ServeEngine(cfg, params, eng, batch_slots=args.slots,
                      max_seq=args.max_seq, executor=executor,
                      continuation_policy=args.continuation_policy,
                      continuation_max_drain=args.continuation_max_drain,
                      mesh=mesh, collective_spec=spec,
                      kv_block_size=args.kv_block_size,
                      kv_blocks=args.kv_blocks or None,
                      prefill_chunk=args.prefill_chunk,
                      epoch=epoch)
    if executor is not None:
        executor.start()
    return Server(cfg, srv, eng, executor, epoch, heartbeat)


def random_prompts(cfg, n: int, lengths, seed: int = 1) -> list[np.ndarray]:
    """``n`` prompts of uniform random tokens, lengths uniform in
    ``lengths`` = (min, max) inclusive."""
    rng = np.random.RandomState(seed)
    lo, hi = lengths
    return [rng.randint(1, cfg.vocab_size - 1,
                        size=rng.randint(lo, hi + 1)).astype(np.int32)
            for _ in range(n)]


def serve(server: Server, prompts, max_new: int, tag: str = "req",
          timeout: float = 600) -> list:
    """Submit one request per prompt and serve until idle; returns the
    requests in submit order."""
    from repro.serve.engine import GenRequest
    reqs = [GenRequest(f"{tag}{i}", p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        server.srv.submit(r)
    server.srv.run_until_idle(timeout=timeout)
    return reqs


def shortfalls(server: Server, reqs) -> list[str]:
    """What went wrong while serving ``reqs``: recorded failures, and
    requests that did not complete with all their tokens."""
    out = [f"{type(e).__name__}: {e}" for e in server.srv.failures()]
    for r in reqs:
        if not r.done_req.is_complete or r.done_req.failed \
                or len(r.out_tokens) != r.max_new_tokens:
            out.append(f"{r.request_id}: {len(r.out_tokens)}/"
                       f"{r.max_new_tokens} tokens, exception="
                       f"{r.done_req.exception!r}")
    return out


def close(server: Server) -> None:
    server.srv.close(timeout=60)
    if server.executor is not None:
        server.executor.shutdown(drain=True, timeout=60)


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    from repro.core import stats as stats_mod

    server = build(args)
    srv, eng, executor, epoch = (server.srv, server.engine, server.executor,
                                 server.epoch)
    prompts = random_prompts(server.cfg, args.requests, args.prompt_len)
    if args.chaos_kill > 0:
        import time as _time
        half = max(1, args.requests // 2)
        reqs = serve(server, prompts[:half], args.max_new)
        survivors = max(1, len(jax.devices()) - args.chaos_kill)
        t_kill = _time.monotonic()
        epoch.invalidate(survivors=survivors,
                         reason=f"--chaos-kill {args.chaos_kill}")
        reqs += serve(server, prompts[half:], args.max_new, tag="late")
        t_rec = (_time.monotonic() - t_kill) * 1e3
        print(f"chaos: killed {args.chaos_kill} device(s) -> {survivors} "
              f"survivors; remeshes={srv.remeshes}, second half served "
              f"in {t_rec:.1f} ms")
    else:
        reqs = serve(server, prompts, args.max_new)
    if server.heartbeat is not None:
        for peer in server.heartbeat.alive:
            server.heartbeat.beat(peer)
    snap = stats_mod.collect(eng, executor)   # before close drops the queue
    lat = srv.latency_snapshot()              # before close, too
    sched = srv.scheduler_snapshot()
    problems = shortfalls(server, reqs)       # before close frees streams
    close(server)

    gen = sum(len(r.out_tokens) for r in reqs)
    mode = (f"{args.progress_workers} progress workers"
            if args.progress_workers > 0 else "caller-driven progress")
    shard = (f"model-shards={args.model_shards} "
             f"backend={args.collective_backend}, "
             if args.model_shards > 0 else "")
    print(f"served {len(reqs)} requests, {gen} tokens in {srv.steps} fused "
          f"decode steps (batching factor {gen / max(srv.steps, 1):.2f}x) "
          f"[{shard}{mode}]")
    # null-safe latency report: requests that failed before their first
    # token are counted, not subtracted from everyone else's TTFT
    print(lat.format())
    if sched is not None:
        print(sched.format())
    if args.stats:
        print(stats_mod.format_stats(snap))
    for p in problems:
        print(f"FAILED {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
