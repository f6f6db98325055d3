"""Bring-up check on a TPU: serving, training and the Pallas kernels at
published widths, through the entry points a user calls.

    python chip_smoke.py                # one chip: serve, train, kernels
    python chip_smoke.py --four-chips   # four chips: FSDP training and
                                        # sharded serving, native vs user

Everything runs in this one process (a chip belongs to one process).
Weights are random, from a fixed seed.  Each phase prints its set-up
(compile) seconds and what it checked; any failed check exits non-zero.
The last line of standard output is one JSON object naming the device.
On anything but a TPU the script exits non-zero at once.
"""
import argparse
import faulthandler
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
SCRATCH = os.path.join(ROOT, ".smoke")        # checkpoints; removed after
# a phase that stalls (a compile that never ends, a collective that never
# completes) shows up as every thread's stack on stderr, once per period
STALL_DUMP_S = 300


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def _ckpt_dir(name: str) -> str:
    path = os.path.join(SCRATCH, name)
    shutil.rmtree(path, ignore_errors=True)  # fresh: resume must not skip
    return path


def _on_all_devices(tree, devices) -> bool:
    import jax
    want = set(devices)
    return all(leaf.sharding.device_set == want
               for leaf in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

SERVE_REQUESTS, SERVE_PROMPT_LEN, SERVE_MAX_NEW = 8, (8, 64), 16


def serve_streams(argv, *, rounds: int = 1):
    """Build the server from launcher flags, serve the same random prompts
    ``rounds`` times, and check every round completes cleanly.  Returns
    (server, prompts, per-round token streams); the server is open."""
    from repro.launch import serve as S
    args = S.parse_args(argv)
    t0 = time.monotonic()
    server = S.build(args)
    warm = S.serve(server, S.random_prompts(server.cfg, 1, (2, 2), seed=0),
                   max_new=2, tag="warmup")
    print(f"  setup: {time.monotonic() - t0:.1f} s building the server "
          f"(weights, compiles) and serving one warm-up request")
    check(not S.shortfalls(server, warm), "warm-up request served")
    prompts = S.random_prompts(server.cfg, args.requests, args.prompt_len)
    streams = []
    for k in range(rounds):
        t0 = time.monotonic()
        reqs = S.serve(server, prompts, args.max_new, tag=f"round{k}-")
        problems = S.shortfalls(server, reqs)
        for p in problems:
            print(f"    {p}")
        check(not problems,
              f"round {k}: {len(reqs)} requests, prompts "
              f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
              f"each completed with {args.max_new} tokens; no decode "
              f"errors, no dropped tasks ({time.monotonic() - t0:.1f} s)")
        streams.append([list(r.out_tokens) for r in reqs])
    return server, prompts, streams


def decode_vs_forward(server, prompt, generated):
    """Feed prompt + generated tokens one at a time through the engine's
    paged decode program (``registry.decode_step_paged``, jitted by the
    ServeEngine) on a fresh pool, and compare every position's logits
    with ``registry.forward`` over the whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import registry
    from repro.serve.kvcache import PagedKVCache

    cfg, srv = server.cfg, server.srv
    seq = np.concatenate([prompt, generated[:-1]]).astype(np.int32)
    n = len(seq)
    pool = PagedKVCache(cfg, srv.batch_slots, srv.max_seq,
                        block_size=srv.slots.block_size,
                        num_blocks=srv.slots.num_blocks)
    lane = pool.assign("check", seq_len=n)
    cache = pool.cache
    rows = []
    for t in range(n):
        lane.pos = t
        assert pool.ensure(lane.index, t)
        toks = np.zeros((srv.batch_slots, 1), np.int32)
        toks[lane.index, 0] = seq[t]
        fed = np.zeros((srv.batch_slots,), bool)
        fed[lane.index] = True
        logits, cache = srv._jit_decode(
            srv.params, cache, jnp.asarray(toks), pool.positions(),
            pool.block_tables(), jnp.asarray(fed))
        rows.append(logits[lane.index, 0])
    got = np.asarray(jnp.stack(rows), np.float32)                # [n, V]
    want = np.asarray(jax.jit(
        lambda p, t: registry.forward(p, cfg, {"tokens": t})[0])(
            srv.params, jnp.asarray(seq)[None])[0], np.float32)  # [n, V]
    err = float(np.max(np.abs(got - want)))
    rel = err / float(np.max(np.abs(want)))
    # Both paths compute in bfloat16 (cfg.dtype) with float32 softmax and
    # norms, but round and reduce in different orders: decode attends one
    # query against the paged cache, forward runs the chunked causal
    # kernel over the sequence, and each of 24 layers rounds its
    # activations to bf16 (relative step 2**-8).  A few such roundings
    # compound to a few 1e-2 of the logit scale; a wrong cache position,
    # mask or RoPE offset moves the logits by O(1) of that scale.
    tol = 5e-2
    check(rel <= tol, f"paged decode logits vs registry.forward over {n} "
          f"positions: max |diff| / max |ref| = {rel:.3e} <= {tol}")
    # greedy choices: wherever forward's top-2 margin exceeds twice the
    # largest gap between the two paths, no rounding can flip the choice,
    # so the served token must be forward's argmax
    top2 = np.sort(want, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    p0 = len(prompt) - 1
    decided = [t for t in range(p0, n) if margin[t] > 2 * err]
    agree = all(int(np.argmax(want[t])) == int(generated[t - p0])
                for t in decided)
    check(agree, f"served greedy tokens match forward's argmax at the "
          f"{len(decided)}/{n - p0} positions with a clear margin")


def phase_serve(arch="qwen2-0.5b", scale="full"):
    from repro.launch import serve as S
    print(f"[serve] {arch} scale={scale} through ServeEngine (paged pool)",
          flush=True)
    argv = ["--arch", arch, "--scale", scale,
            "--requests", str(SERVE_REQUESTS), "--slots", "8",
            "--max-new", str(SERVE_MAX_NEW), "--max-seq", "128",
            "--prompt-len", *map(str, SERVE_PROMPT_LEN)]
    server, prompts, (first, second) = serve_streams(argv, rounds=2)
    check(first == second, "the same requests served twice give "
          "identical token streams")
    decode_vs_forward(server, prompts[0], first[0])
    S.close(server)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train(argv, name):
    import numpy as np
    from repro.launch import train as T
    args = T.parse_args(argv + ["--ckpt-dir", _ckpt_dir(name)])
    result = T.run(args)
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    losses = [m["loss"] for m in result.log]
    print(f"  setup: {result.log[0]['step_time_s']:.1f} s for step 0 "
          f"(compile included)")
    check(len(result.log) >= 2 and bool(np.all(np.isfinite(losses))),
          f"{args.steps} steps, logged losses finite: {losses}")
    check(result.moved == result.leaves,
          f"every parameter leaf updated ({result.moved}/{result.leaves})")
    return result


def phase_train(arch="smollm-360m", scale="full", batch=8, seq=1024):
    import jax
    print(f"[train] {arch} scale={scale}, batch {batch} x seq {seq}, "
          f"native backend, mesh 1x1", flush=True)
    result = train(["--arch", arch, "--scale", scale, "--mesh", "1x1",
                    "--steps", "4", "--global-batch", str(batch),
                    "--seq", str(seq)], "train")
    check(_on_all_devices(result.params, jax.devices()[:1]),
          "parameters live on the chip")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels():
    """Each Pallas entry point once, compiled, at the widths of the
    model configs, against the pure-jnp oracle in ``kernels/ref.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.decode_attention import flash_decode
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm_bwd, rmsnorm_fwd
    from repro.kernels.ssd_scan import ssd_chunk
    from repro.models.mamba import dims

    print("[kernels] Pallas entry points compiled for the chip vs "
          "kernels/ref.py (oracle at float32 'highest' matmul precision)",
          flush=True)
    qwen, mamba = get_config("qwen2-0.5b"), get_config("mamba2-1.3b")
    H, KVH, hd = qwen.num_heads, qwen.num_kv_heads, qwen.resolved_head_dim()
    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(shape, dtype=bf):
        return jax.random.normal(next(ks), shape, dtype)

    def compare(name, fn, oracle, args, tol):
        t0 = time.monotonic()
        compiled = jax.jit(fn).lower(*args).compile()
        setup = time.monotonic() - t0
        got = jax.tree.leaves(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(oracle)(*args))
        err = 0.0
        ok = True
        for g, w in zip(got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            ok &= bool(np.allclose(g, w, atol=tol, rtol=tol))
            err = max(err, float(np.max(np.abs(g - w))))
        check(ok, f"{name}: compiled in {setup:.1f} s, max |diff| "
              f"{err:.2e}, allclose atol=rtol={tol}")

    # bf16 operands, f32 accumulation: one bf16 rounding of an operand or
    # of the probabilities is a relative 2**-8 step, so the same 2e-2 the
    # interpret-mode tests use for bf16 (3e-2 for the SSD states, which sum
    # a whole chunk)
    q, k, v = (normal((1, 2048, H, hd)), normal((1, 2048, KVH, hd)),
               normal((1, 2048, KVH, hd)))
    compare("flash_attention [1,2048,14,64] vs kv [1,2048,2,64]",
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True),
            (q, k, v), 2e-2)
    lengths = jnp.asarray([1, 17, 500, 1024, 1500, 2000, 2047, 2048],
                          jnp.int32)
    dq, kc, vc = (normal((8, H, hd)), normal((8, 2048, KVH, hd)),
                  normal((8, 2048, KVH, hd)))
    compare("flash_decode [8,14,64] vs a 2048-position cache",
            flash_decode, ref.decode_attention_ref,
            (dq, kc, vc, lengths), 2e-2)
    _, nh, hp, ds = dims(mamba)
    Q = mamba.ssm.chunk_size
    dt = (jax.nn.softplus(normal((4, Q, nh), jnp.float32)) * 0.1)
    a_log = jax.random.uniform(next(ks), (nh,), minval=0.0, maxval=2.0)
    compare(f"ssd_chunk Q={Q} nh={nh} hp={hp} ds={ds}", ssd_chunk,
            ref.ssd_chunk_ref,
            (normal((4, Q, nh, hp)), normal((4, Q, ds)), normal((4, Q, ds)),
             dt.astype(bf), a_log), 3e-2)
    D = qwen.d_model
    x, g = normal((4096, D)), normal((4096, D))
    s = normal((D,), jnp.float32) + 1.0
    compare(f"rmsnorm_fwd [4096,{D}]", rmsnorm_fwd, ref.rmsnorm_ref,
            (x, s), 2e-2)

    def bwd_oracle(x, s, g):
        dx, dsc = jax.vjp(ref.rmsnorm_ref, x.astype(jnp.float32), s)[1](
            g.astype(jnp.float32))
        return dx, dsc

    compare(f"rmsnorm_bwd [4096,{D}] (dscale summed over row blocks)",
            lambda x, s, g: (lambda dx, p: (dx, p.sum(0)))(
                *rmsnorm_bwd(x, s, g)),
            bwd_oracle, (x, s, g), 2e-2)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four_chips(n=4, scale="full", seq=1024):
    import jax
    import numpy as np

    devices = jax.devices()
    print(f"[fsdp] smollm-360m {scale}, --fsdp on a {n}x1 mesh, native vs "
          f"user backend, same seed", flush=True)
    base = ["--arch", "smollm-360m", "--scale", scale, "--mesh", f"{n}x1",
            "--fsdp", "--steps", "6", "--global-batch", "8",
            "--seq", str(seq)]
    losses = {}
    for backend in ("native", "user"):
        print(f" backend={backend}", flush=True)
        result = train(base + ["--collective-backend", backend],
                       f"fsdp-{backend}")
        check(_on_all_devices(result.params, devices),
              f"every parameter shard bucket spans the {n} devices")
        losses[backend] = [m["loss"] for m in result.log]
    diff = float(np.max(np.abs(np.subtract(losses["native"],
                                           losses["user"]))))
    bitwise = losses["native"] == losses["user"]
    print(f"  native losses {losses['native']}\n"
          f"  user   losses {losses['user']}")
    # both backends run the same jitted grad/apply programs and differ only
    # in who moves the bytes; XLA's reduce-scatter may sum the four shards
    # in another order than the user-space schedule, a float32
    # reassociation of ~1e-7 relative per step on a loss of ~11
    tol = 1e-4
    check(diff <= tol, f"FSDP losses native vs user: max |diff| = "
          f"{diff:.3e} ({'bitwise identical' if bitwise else 'not bitwise'}"
          f", tolerance {tol})")

    from repro.launch import serve as S
    print(f"[sharded serve] qwen2-0.5b {scale}, --model-shards {n}, native "
          f"vs user all-gather of the vocab-parallel logits", flush=True)
    streams = {}
    for backend in ("native", "user"):
        print(f" backend={backend}", flush=True)
        argv = ["--arch", "qwen2-0.5b", "--scale", scale,
                "--requests", str(SERVE_REQUESTS), "--slots", "8",
                "--max-new", str(SERVE_MAX_NEW), "--max-seq", "128",
                "--prompt-len", *map(str, SERVE_PROMPT_LEN),
                "--model-shards", str(n), "--collective-backend", backend]
        server, _, (streams[backend],) = serve_streams(argv)
        srv = server.srv
        check(_on_all_devices(srv.params, devices)
              and _on_all_devices(srv.slots.cache, devices),
              f"weights and KV pool are replicated over the {n} devices")
        check(srv.mesh.devices.size == n
              and set(srv.mesh.devices.flat) == set(devices),
              f"the model mesh spans the {n} devices")
        S.close(server)
    check(streams["native"] == streams["user"],
          "sharded serving: native and user all-gather give identical "
          "token streams")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (FSDP training and "
                         "sharded serving, native vs user collectives)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}", flush=True)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_serve, phase_train, phase_kernels])
    t_all = time.monotonic()
    faulthandler.dump_traceback_later(STALL_DUMP_S, repeat=True)
    try:
        for phase in phases:
            t0 = time.monotonic()
            phase()
            print(f"  phase done in {time.monotonic() - t0:.1f} s", flush=True)
    except CheckFailed:
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
        shutil.rmtree(SCRATCH, ignore_errors=True)
    print(f"all phases passed in {time.monotonic() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
