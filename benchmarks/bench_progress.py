"""Paper Figs 7–12 (+ continuation-delivery rows) progress-engine
microbenchmarks, and the serve-decode latency family (fig-14-style:
user-space serve collectives vs the native-sharded and unsharded decode
paths, in a forced-multi-device child process)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import threading
import time

from benchmarks._util import LatencyStats, make_dummy_task, row, run_pending_tasks
from repro.core import (DEFERRED, DONE, INLINE, NOPROGRESS, CompletionWatcher,
                        ContinuationQueue, ProgressEngine, ProgressExecutor,
                        Request, TaskQueue)


def fig7_latency_vs_pending():
    """Latency overhead as #independent pending tasks grows (paper: <0.5µs
    below 32 tasks, then linear growth)."""
    rows = []
    for n in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024):
        eng = ProgressEngine()
        stats = run_pending_tasks(eng, n, duration_s=0.002, repeats=3)
        rows.append(row(f"fig7_pending_{n}", stats.mean(),
                        f"p99={stats.p99():.1f}us"))
    return rows


def fig8_poll_overhead():
    """Latency vs per-poll busy delay; 10 concurrent tasks (paper Fig 8)."""
    rows = []
    for delay_us in (0, 1, 5, 10, 50, 100):
        eng = ProgressEngine()
        stats = run_pending_tasks(eng, 10, duration_s=0.002,
                                  poll_delay_s=delay_us * 1e-6, repeats=3)
        rows.append(row(f"fig8_polldelay_{delay_us}us", stats.mean(), ""))
    return rows


def fig9_thread_contention():
    """k threads all progressing the SAME (default) stream — the
    MPI_THREAD_MULTIPLE pathology (paper Fig 9)."""
    rows = []
    for k in (1, 2, 4, 8):
        eng = ProgressEngine()
        stats = LatencyStats()
        counter = {"n": 10 * k}
        for _ in range(10 * k):
            eng.async_start(make_dummy_task(0.002, stats, counter))
        stop = threading.Event()

        def spin():
            while not stop.is_set() and counter["n"] > 0:
                eng.progress()

        threads = [threading.Thread(target=spin) for _ in range(k)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        while counter["n"] > 0 and time.perf_counter() - t0 < 30:
            time.sleep(0.0002)
        stop.set()
        for t in threads:
            t.join()
        rows.append(row(f"fig9_threads_shared_{k}", stats.mean(), ""))
    return rows


def fig9_executor_scaling():
    """ProgressExecutor scaling: 1/2/4 workers × 8 streams of dummy tasks
    (the §4.4 fix, productised): per-stream serial contexts let added
    workers reduce progress latency instead of fighting one lock, and the
    executor's stats prove zero cross-stream contention."""
    rows = []
    n_streams, tasks_per_stream = 8, 10
    for workers in (1, 2, 4):
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, workers)
        streams = [ex.stream(f"s{i}") for i in range(n_streams)]
        stats = LatencyStats()
        counters = []
        for s in streams:
            c = {"n": tasks_per_stream}
            counters.append(c)
            for _ in range(tasks_per_stream):
                # per-poll busy delay makes worker parallelism observable
                eng.async_start(make_dummy_task(0.002, stats, c,
                                                poll_delay_s=5e-6), None, s)
        ex.start()
        t0 = time.perf_counter()
        while any(c["n"] > 0 for c in counters):
            time.sleep(0.0002)
            if time.perf_counter() - t0 > 30:
                raise TimeoutError
        ex.shutdown(drain=True, timeout=30)
        wstats = ex.worker_stats()
        contention = sum(s.contention for s in streams)
        rows.append(row(f"fig9_executor_w{workers}_s{n_streams}", stats.mean(),
                        f"steals={sum(w.steals for w in wstats)} "
                        f"contention={contention}"))
    return rows


def fig10_task_class():
    """All tasks behind ONE TaskQueue poll hook, completing in order at
    staggered intervals (paper Listing 1.4): latency flat vs count,
    because each progress call inspects only the queue head."""
    rows = []
    interval = 100e-6
    for n in (1, 8, 64, 512, 2048):
        eng = ProgressEngine()
        q = TaskQueue(eng)
        stats = LatencyStats()
        base = time.perf_counter() + 0.001
        done = {"n": n}

        def mk(i):
            deadline = base + i * interval

            def ready():
                return time.perf_counter() >= deadline

            def on_complete():
                stats.add(time.perf_counter() - deadline)
                done["n"] -= 1
            return ready, on_complete

        for i in range(n):
            r, c = mk(i)
            q.submit(r, c)
        t0 = time.perf_counter()
        while done["n"] > 0:
            eng.progress()
            if time.perf_counter() - t0 > 30:
                raise TimeoutError
        # only the head is checked per sweep: latency independent of n
        rows.append(row(f"fig10_taskclass_{n}", stats.mean(), ""))
    return rows


def fig11_streams():
    """k threads, each with its OWN stream: no contention (paper Fig 11)."""
    rows = []
    for k in (1, 2, 4, 8):
        eng = ProgressEngine()
        stats = LatencyStats()
        errors = []

        def worker():
            try:
                s = eng.stream()
                counter = {"n": 10}
                for _ in range(10):
                    eng.async_start(make_dummy_task(0.002, stats, counter),
                                    None, s)
                t0 = time.perf_counter()
                while counter["n"] > 0:
                    eng.progress(s)
                    if time.perf_counter() - t0 > 30:
                        raise TimeoutError
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        rows.append(row(f"fig11_streams_{k}", stats.mean(), ""))
    return rows


def fig12_request_query():
    """Overhead of the completion-event query loop vs #pending requests
    (paper Fig 12: negligible below ~256)."""
    rows = []
    for n in (1, 16, 64, 256, 1024):
        eng = ProgressEngine()
        w = CompletionWatcher(eng)
        reqs = [Request() for _ in range(n)]
        fired = []
        for r in reqs:
            w.watch(r, lambda rr: fired.append(1))
        # measure pure sweep cost with nothing complete
        t0 = time.perf_counter()
        iters = 200
        for _ in range(iters):
            eng.progress()
        sweep_us = (time.perf_counter() - t0) / iters * 1e6
        for r in reqs:
            r.complete()
        eng.progress()
        assert len(fired) == n
        rows.append(row(f"fig12_query_{n}", sweep_us, "per-progress sweep"))
    return rows


def fig13_continuation_vs_waitset():
    """Completion-delivery latency, callback vs wait-set (the serve-decode
    pattern): N staggered "decode steps" complete on a worker-progressed
    stream; measure deadline → consumer-observes-completion.

    * waitset  — the consumer thread loops ``wait_any`` over the
      outstanding requests and removes each winner (pull).
    * cont_inline   — continuations run ON the progress worker the moment
      the sweep observes completion (push, lowest latency).
    * cont_deferred — continuations queue and the consumer thread drains
      (push + owner-thread execution, the backpressure-bounded mode).
    """
    rows = []
    n, duration = 64, 0.002
    for mode in ("waitset", "cont_inline", "cont_deferred"):
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, 1, steal=False)
        s = ex.stream("decode")
        stats = LatencyStats()
        deadlines = {}
        reqs = []
        for i in range(n):
            r = Request(tag=f"step{i}")
            deadlines[id(r)] = time.perf_counter() + duration * (1 + i % 8)
            reqs.append(r)

        def mk(r):
            def poll(thing):
                if time.perf_counter() >= deadlines[id(r)]:
                    r.complete()
                    return DONE
                return NOPROGRESS
            return poll

        observed = {"n": 0}

        def on_complete(r):
            stats.add(time.perf_counter() - deadlines[id(r)])
            observed["n"] += 1

        q = None
        if mode != "waitset":
            policy = INLINE if mode == "cont_inline" else DEFERRED
            q = ContinuationQueue(eng, s, policy=policy, name=mode)
            for r in reqs:
                q.attach(r, on_complete)
        for r in reqs:
            eng.async_start(mk(r), None, s)
        with ex:
            t0 = time.perf_counter()
            if mode == "waitset":
                outstanding = list(reqs)
                while outstanding:
                    _, winner = eng.wait_any(outstanding, timeout=30)
                    on_complete(winner)
                    outstanding.remove(winner)
            else:
                while observed["n"] < n:
                    if q.policy == DEFERRED:
                        q.drain(8)          # bounded owner drain
                    time.sleep(20e-6)
                    if time.perf_counter() - t0 > 30:
                        raise TimeoutError
            ex.drain(timeout=30)
        rows.append(row(f"fig13_{mode}_{n}", stats.mean(),
                        f"p99={stats.p99():.1f}us"))
    return rows


_SERVE_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import time
import jax, numpy as np
from repro import compat
from repro.configs import get_config
from repro.core import ProgressEngine
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine

cfg = get_config("qwen2-0.5b").with_overrides(
    num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
    num_kv_heads=2, head_dim=16, remat_policy="none")
params = registry.init_params(cfg, jax.random.PRNGKey(0))
mesh = compat.make_mesh((2,), ("model",))

def serve_once(mesh, backend, max_new=16, n_req=4):
    eng = ProgressEngine()
    srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=128,
                      mesh=mesh, collective_backend=backend)
    # warm THIS engine's programs before timing (a fresh ServeEngine
    # means fresh jit closures: the user gather compiles at
    # construction, but decode — and the native gather — compile on
    # first use, and an unwarmed first step would bill XLA compiles to
    # the timed window, skewing the native-vs-user comparison)
    warm = GenRequest("warm", np.array([1, 2], np.int32), max_new_tokens=2)
    srv.submit(warm)
    srv.run_until_idle(timeout=600)
    warm_steps = srv.steps
    reqs = [GenRequest(f"r{i}", np.array([i + 1, i + 2], np.int32),
                       max_new_tokens=max_new) for i in range(n_req)]
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    srv.run_until_idle(timeout=600)
    wall = time.perf_counter() - t0
    steps = srv.steps - warm_steps
    toks = sum(len(r.out_tokens) for r in reqs)
    lat = srv.latency_snapshot()
    srv.close(timeout=60)
    return wall / max(steps, 1) * 1e6, toks, lat

rows = {}
for name, m, backend in (("unsharded", None, "native"),
                         ("native_m2", mesh, "native"),
                         ("user_m2", mesh, "user")):
    us, toks, lat = serve_once(m, backend)
    rows[name] = us
    print(f"serve_decode_{name},{us:.3f},per fused decode step; "
          f"{toks} tokens, ttft_p50={lat.ttft_ms_p50:.1f}ms")
print(f"serve_gain_user_vs_native_m2,{rows['native_m2'] / rows['user_m2']:.3f},"
      f"user {rows['user_m2']:.0f}us vs native in-program gather "
      f"{rows['native_m2']:.0f}us per step")
"""


_SERVE_CB_SNIPPET = """
import time
import jax, numpy as np
from repro.configs import get_config
from repro.core import ProgressEngine
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine

cfg = get_config("qwen2-0.5b").with_overrides(
    num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
    num_kv_heads=2, head_dim=16, remat_policy="none")
params = registry.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
N, MAX_SEQ = 64, 64
prompts = [rng.randint(1, 255, size=rng.randint(2, 17)).astype(np.int32)
           for _ in range(N)]
gaps = rng.exponential(0.002, size=N)        # Poisson arrivals, ~500 req/s

def trace(**kw):
    eng = ProgressEngine()
    srv = ServeEngine(cfg, params, eng, max_seq=MAX_SEQ, **kw)
    warm = GenRequest("warm", np.array([1, 2], np.int32), max_new_tokens=2)
    srv.submit(warm)
    srv.run_until_idle(timeout=600)          # compile outside the trace
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    due = 0.0
    for i, r in enumerate(reqs):
        due += gaps[i]
        while time.perf_counter() - t0 < due:
            eng.progress()
        srv.submit(r)
    srv.run_until_idle(timeout=600)
    lat = srv.latency_snapshot()
    sched = srv.scheduler_snapshot()
    srv.close(timeout=60)
    return [list(r.out_tokens) for r in reqs], lat, sched

# 4-lane baseline FIRST: if the wide sweep dies, these rows are
# salvaged by the parent (see serve_continuous_batching).  Same paged
# pool as the wide run (32+1 blocks of 8) capped at 4 decode lanes —
# the shape the retired fixed-slot engine used to serve
lane_toks, lane_lat, _ = trace(batch_slots=4, kv_block_size=8,
                               kv_blocks=33)
print(f"serve_cb_ttft_lane4,{lane_lat.ttft_ms_p50 * 1e3:.3f},"
      f"p50 TTFT; concurrency cap 4 lanes, p99 latency "
      f"{lane_lat.latency_ms_p99:.1f}ms")
print(f"serve_cb_p99_lane4,{lane_lat.latency_ms_p99 * 1e3:.3f},"
      f"p99 request latency at a 4-lane cap")

# wide: SAME cache bytes but 12 decode lanes — block granularity is
# what buys the concurrency, and per-stream tokens must not change
paged_toks, paged_lat, sched = trace(
    batch_slots=12, kv_block_size=8, kv_blocks=33)
assert paged_toks == lane_toks, "wide-pool trace diverged from 4-lane"
print(f"serve_cb_ttft_paged,{paged_lat.ttft_ms_p50 * 1e3:.3f},"
      f"p50 TTFT; peak {sched.peak_resident} resident on the same "
      f"bytes, {sched.preemptions} preemptions")
print(f"serve_cb_p99_paged,{paged_lat.latency_ms_p99 * 1e3:.3f},"
      f"p99 request latency, paged pool (32 blocks of 8)")
print(f"cb_gain_concurrency,{sched.peak_resident / 4:.3f},"
      f"peak resident {sched.peak_resident} vs the 4-lane cap at "
      f"equal cache bytes (ratio row: untracked by the trend gate)")
"""


_RECOVERY_SNIPPET = """
import time
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.collectives.nonblocking import MembershipEpoch
from repro.core import ProgressEngine
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine

cfg = get_config("qwen2-0.5b").with_overrides(
    num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
    num_kv_heads=2, head_dim=16, remat_policy="none")
params = registry.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
prompts = [rng.randint(1, 255, size=rng.randint(2, 9)).astype(np.int32)
           for _ in range(8)]

def recover(**kw):
    # invalidate mid-decode; time invalidate -> drained, remeshed,
    # re-admitted and idle (the full membership-change recovery path,
    # including the rebuilt decode program's compile)
    eng = ProgressEngine()
    epoch = MembershipEpoch()
    srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=64,
                      epoch=epoch, **kw)
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    while sum(len(r.out_tokens) for r in reqs) < 8 \\
            and time.perf_counter() - t0 < 300:
        eng.progress()
    t0 = time.perf_counter()
    epoch.invalidate(survivors=1, reason="bench")
    srv.run_until_idle(timeout=600)
    dt = time.perf_counter() - t0
    lat = srv.latency_snapshot()
    assert lat.failed == 0 and srv.remeshes == 1, (lat.failed, srv.remeshes)
    srv.close(timeout=60)
    return dt

# serve row FIRST so a trainer-section crash still salvages it
dt = recover(kv_block_size=8)
print(f"recovery_serve_paged,{dt * 1e6:.0f},invalidate -> drained+"
      f"remeshed+re-admitted+idle with per-lane KV checkpoint/restore "
      f"migration, 8 reqs, paged pool")

# trainer: remesh-and-retry step (catches MembershipError, rebuilds the
# split step on the survivors, retries the same batch)
from jax.sharding import PartitionSpec as P, NamedSharding
from repro import compat
from repro.collectives.overlap import EngineGradReducer
from repro.data.pipeline import SyntheticLM
from repro.distributed import elastic
from repro.train import optimizer as opt_mod
from repro.train.train_loop import Trainer, TrainLoopConfig, \\
    UserCollectiveStep

tcfg = get_config("smollm-360m").with_overrides(
    num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
    num_kv_heads=2, head_dim=16, remat_policy="none")
src = SyntheticLM(tcfg.vocab_size, 16, 4, seed=1)
it = iter(src)
batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
           for _ in range(8)]

class ListPipe:
    def __init__(self, bs):
        self.bs = list(bs)
    def next_batch(self):
        return self.bs.pop(0)
    def close(self):
        pass

ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=8)

def local_grad(p, batch):
    (loss, mets), g = jax.value_and_grad(
        registry.loss_fn, has_aux=True)(p, tcfg, batch)
    return (jax.tree.map(lambda v: v[None], dict(mets, loss=loss)),
            jax.tree.map(lambda v: v[None].astype(jnp.float32), g))

def make_grad_fn(mesh_):
    return jax.jit(compat.shard_map(local_grad, mesh=mesh_,
                                    in_specs=(P(), P("data")),
                                    out_specs=P("data")))

@jax.jit
def apply_fn(p, o, g, sm):
    p, o, om = opt_mod.apply(ocfg, o, p, g)
    return p, o, dict({k: jnp.mean(v) for k, v in sm.items()}, **om)

eng = ProgressEngine()
mesh = elastic.remesh(1, prefer_model=1)
epoch = MembershipEpoch()
red = EngineGradReducer(mesh, "data", engine=eng, chunks=2, mean=True,
                        epoch=epoch)
split = UserCollectiveStep(make_grad_fn(mesh), apply_fn, red)

def remesh_fn(exc, p, o):
    new_mesh = elastic.remesh(exc.survivors, prefer_model=1)
    red.remesh(new_mesh, "data")
    p = jax.device_put(p, NamedSharding(new_mesh, P()))
    o = jax.device_put(o, NamedSharding(new_mesh, P()))
    return UserCollectiveStep(make_grad_fn(new_mesh), apply_fn, red), p, o

step_times, fired = {}, []

def hook(s, m):
    step_times[s] = m["step_time_s"]
    if s == 3 and not fired:
        fired.append(s)
        epoch.invalidate(survivors=1, reason="bench")

params_t = registry.init_params(tcfg, jax.random.PRNGKey(0))
tr = Trainer(None, params_t, opt_mod.init(params_t), ListPipe(batches),
             TrainLoopConfig(total_steps=8, checkpoint_every=10**6,
                             checkpoint_dir="/tmp/bench_recovery_ckpt",
                             log_every=1, resume=False,
                             collective_backend="user"),
             engine=eng, split_step=split, epoch=epoch,
             remesh_fn=remesh_fn, hooks=[hook])
tr.run()
red.close()
assert tr.recoveries == 1, tr.recoveries
warm = min(step_times[s] for s in step_times if s not in (0, 4))
print(f"recovery_train_step,{step_times[4] * 1e6:.0f},remesh+retry "
      f"step wall time (warm step {warm * 1e6:.0f}us)")
"""


_FSDP_SNIPPET = """
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.collectives.nonblocking import CollectiveSpec
from repro.collectives.overlap import FsdpLayout, FsdpReducer
from repro.core import ProgressEngine
from repro.data.pipeline import SyntheticLM
from repro.launch.train import build_fsdp_programs
from repro.models import registry
from repro.train import optimizer as opt_mod
from repro.train.train_loop import FsdpStep, Trainer, TrainLoopConfig

cfg = get_config("smollm-360m").with_overrides(
    num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
    num_kv_heads=2, head_dim=16, remat_policy="none")
STEPS = 12
ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=STEPS)
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
axis, n = "data", 2

src = SyntheticLM(cfg.vocab_size, 16, 4, seed=7)
it = iter(src)
batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
           for _ in range(STEPS)]

def timed(fn, reps=3):
    fn()                                   # warmup / compile
    t0 = time.monotonic()
    for _ in range(reps):
        fn()
    return (time.monotonic() - t0) / reps

# unsharded baseline FIRST: a crash in the FSDP sweep must still
# salvage this row (same discipline as the serve families)
params = registry.init_params(cfg, jax.random.PRNGKey(0))

@jax.jit
def base_step(p, o, batch):
    (loss, mets), g = jax.value_and_grad(
        registry.loss_fn, has_aux=True)(p, cfg, batch)
    p, o, om = opt_mod.apply(ocfg, o, p, g)
    return p, o, loss

t_base = timed(lambda: jax.block_until_ready(
    base_step(params, opt_mod.init(params), batches[0])))
print(f"fsdp_unsharded_step,{t_base * 1e6:.0f},replicated jitted "
      f"grad+AdamW baseline, no sharding (2x2-device child)",
      flush=True)

# shared FSDP scaffolding: flat per-dtype bucket shards [n, W/n] over
# the data axis; the SAME jitted grad/apply programs serve both
# backends, only the byte movement differs
layout = FsdpLayout(params, n, 1 << 22)
sharding = NamedSharding(mesh, P(axis))

def fresh_state():
    shards = layout.shard_params(params, mesh, axis)
    return shards, opt_mod.AdamWState(
        jnp.zeros((), jnp.int32),
        [jax.device_put(jnp.zeros_like(s), sharding) for s in shards],
        [jax.device_put(jnp.zeros_like(s), sharding) for s in shards])

grad_fn, apply_fn, ag_fn, rs_fn = build_fsdp_programs(
    cfg, ocfg, mesh, layout, axis=axis)

def native_step(sh, st, batch):
    flats = ag_fn(sh)
    smets, flat_grads = grad_fn(flats, batch)
    gshards = rs_fn(flat_grads)
    return apply_fn(sh, st, gshards, smets)

sh_n, st_n = fresh_state()
t_native = timed(lambda: jax.block_until_ready(
    native_step(sh_n, st_n, batches[0])))
print(f"fsdp_native_step,{t_native * 1e6:.0f},in-program "
      f"all_gather/psum_scatter FSDP step, data={n} model=2",
      flush=True)

# user backend: persistent engine handles, next step's gathers chained
# off the optimizer's compute futures (measured via the Trainer so the
# cross-step prefetch chain is real)
class ListPipe:
    def __init__(self, bs):
        self.bs = list(bs)
    def next_batch(self):
        return self.bs.pop(0)
    def close(self):
        pass

eng = ProgressEngine()
spec = CollectiveSpec(backend="user", chunks=2)
reducer = FsdpReducer(mesh, axis, engine=eng, spec=spec,
                      bucket_bytes=1 << 22)
split = FsdpStep(grad_fn, apply_fn, reducer, spec=spec)
step_times = {}
sh_u, st_u = fresh_state()
tr = Trainer(None, sh_u, st_u, ListPipe(batches),
             TrainLoopConfig(total_steps=STEPS, checkpoint_every=10**6,
                             checkpoint_dir="/tmp/bench_fsdp_ckpt",
                             log_every=1, resume=False,
                             collective_spec=spec),
             engine=eng, split_step=split,
             hooks=[lambda s, m: step_times.__setitem__(
                 s, m["step_time_s"])])
tr.run()
overlap, gathers = reducer.prefetch_overlap, reducer.gathers
reducer.close()
warm = sorted(step_times[s] for s in step_times if s > 0)
t_user = warm[len(warm) // 2]
print(f"fsdp_user_step,{t_user * 1e6:.0f},persistent engine "
      f"reduce-scatter/all-gather FSDP step, median of "
      f"{len(warm)} warm steps", flush=True)
assert overlap > 0.0, overlap
print(f"fsdp_prefetch_overlap,{overlap:.3f},fraction of the gather "
      f"window hidden behind compute ({gathers} chained gathers; "
      f"HIGHER is better — a drop shows as 'improved' in the gate)",
      flush=True)
"""


def _cpu_child_env() -> dict:
    """Environment of a child that forces host devices: a CPU rehearsal by
    construction, so it never reaches for an accelerator that this
    process may already hold."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def fsdp_training():
    """ZeRO-style FSDP step family (fsdp_* rows, 2x2 host devices in a
    child): the replicated unsharded baseline, the native in-program
    all_gather/psum_scatter step, the user-backend step on persistent
    engine handles, and the measured prefetch-overlap fraction of the
    continuation-chained gathers.  Baseline prints before the FSDP
    sweep so a crash in the new path still salvages it (same
    discipline as serve_collectives)."""
    env = _cpu_child_env()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_FSDP_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    rows = [l for l in stdout.splitlines() if l.startswith("fsdp_")]
    if rc != 0:
        rows.append(f"fsdp,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


_DEBUG_OVERHEAD_SNIPPET = """
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp

from repro import compat
from repro.collectives import nonblocking as NB
from repro.core import ProgressEngine, debug


def step_time(reps=50):
    # fresh stack per measurement: make_lock picks plain Lock vs
    # OrderedLock at construction, so the debug run must build its own
    mesh = compat.make_mesh((4,), ("x",))
    eng = ProgressEngine()
    coll = NB.UserCollectives(eng)
    x = jnp.ones((4, 4096), jnp.float32)
    h = coll.allreduce_init(x, mesh, "x")
    for _ in range(5):
        h.start(x).wait(timeout=120)            # warm: compiled + cached
    t0 = time.monotonic()
    for _ in range(reps):
        h.start(x).wait(timeout=120)
    us = (time.monotonic() - t0) / reps * 1e6
    h.close()
    coll.close()
    return us


off = step_time()
prev = debug.set_debug(True)
on = step_time()
debug.set_debug(prev)
tax = (on - off) / off * 100.0
print(f"debug_overhead_off,{off:.2f},warmed persistent allreduce step")
print(f"debug_overhead_on,{on:.2f},REPRO_DEBUG tax {tax:+.1f}% (target <5)")
"""


def debug_overhead():
    """REPRO_DEBUG=1 tax on a warmed persistent-allreduce step
    (debug_overhead_* rows, 4 host devices in a child): same step timed
    with the checkers dormant and armed — the lifecycle hooks and
    ordered locks must stay under the ~5%% budget that makes running
    tier-1 under REPRO_DEBUG=1 in CI viable."""
    env = _cpu_child_env()
    env.pop("REPRO_DEBUG", None)      # the child toggles it itself
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_DEBUG_OVERHEAD_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    rows = [l for l in stdout.splitlines() if l.startswith("debug_overhead")]
    if rc != 0:
        rows.append(f"debug_overhead,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


_PIPELINE_SNIPPET = """
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ProgressEngine, ProgressExecutor
from repro.distributed import pipeline as pl

S, M, d, h, mb = 4, 8, 32, 64, 8
mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))

def stage_fn(p, x):
    return x + jnp.tanh(x @ p["w1"]) @ p["w2"]

def loss_fn(y, t):
    return jnp.mean((y - t) ** 2)

k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
params = {"w1": jax.random.normal(k1, (S, d, h)) * 0.1,
          "w2": jax.random.normal(k2, (S, h, d)) * 0.1}
xs = jax.random.normal(k3, (M, mb, d))
ts = jax.random.normal(k4, (M, mb, d))

def timed(fn, reps=3):
    fn()                                   # warmup / compile
    t0 = time.monotonic()
    for _ in range(reps):
        fn()
    return (time.monotonic() - t0) / reps

# baseline rows FIRST: a crash in the DAG sweep must still salvage them
def seq_step(params, xs, ts):
    scale = jnp.float32(1.0 / M)
    acc = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for m in range(M):
        def head(p, x=xs[m], t=ts[m]):
            y = x
            for s in range(S):
                y = stage_fn(jax.tree.map(lambda a: a[s], p), y)
            return loss_fn(y, t)
        lm, pull = jax.vjp(head, params)
        acc = jax.tree.map(jnp.add, acc, pull(scale)[0])
        losses.append(lm)
    return sum(losses) * scale, acc

seq_jit = jax.jit(seq_step)
t_seq = timed(lambda: jax.block_until_ready(seq_jit(params, xs, ts)))
print(f"pipeline_seq_step,{t_seq * 1e6:.0f},single-device jitted "
      f"microbatch-accumulation baseline (S={S},M={M})", flush=True)

gmesh = mesh
pparams = jax.device_put(params, NamedSharding(gmesh, P("stage")))
gp = pl.gpipe(stage_fn, gmesh, "stage", S)

def gp_loss(p, xs, ts):
    ys = gp(p, xs)
    return jnp.mean(jnp.stack([loss_fn(ys[m], ts[m]) for m in range(M)]))

gp_jit = jax.jit(jax.value_and_grad(gp_loss))
t_gp = timed(lambda: jax.block_until_ready(gp_jit(pparams, xs, ts)))
print(f"pipeline_gpipe_step,{t_gp * 1e6:.0f},monolithic lax.scan "
      f"fwd+bwd reference (S={S},M={M})", flush=True)

engine = ProgressEngine()
ex = ProgressExecutor(engine, num_workers=2).start()
engine.attach_executor(ex)
sched = pl.PipelineSchedule(stage_fn, mesh, "stage", S, loss_fn=loss_fn,
                            engine=engine, executor=ex)
t_1f1b = timed(lambda: sched.step(params, xs, ts, timeout=600))
print(f"pipeline_1f1b_step,{t_1f1b * 1e6:.0f},event-driven continuation-"
      f"DAG step, persistent p2p handoffs (S={S},M={M})", flush=True)

# measured bubble, two ways.  Tick-based: idle slots of the DAG the run
# actually executed (cells retired per stage vs the realized tick span)
# — schedule-correctness, exact on any host.  Wall-based: per-stage
# stream idle from the cell spans — only meaningful with >= S cores
# (this container timeshares one), so it is reported, not asserted.
tm = sched.last_step_timing
assert tm is not None, "no step timing recorded"
cells = sum(tm["cells"])
tick_bubble = 1.0 - cells / (S * tm["grid_ticks"])
analytic = pl.bubble_fraction(S, M, "1f1b")
wall_bubble = tm.get("bubble", float("nan"))
idle_us = sum(tm.get("idle_s", [0])) / max(len(tm.get("idle_s", [1])), 1)
print(f"pipeline_1f1b_bubble,{idle_us * 1e6:.0f},measured={tick_bubble:.4f}"
      f" analytic={analytic:.4f} wall={wall_bubble:.3f} (S={S},M={M})",
      flush=True)
st = sched.stats()
assert st["p2p_stream_completions"] > 0, st
assert abs(tick_bubble - analytic) <= 0.02, (tick_bubble, analytic)
sched.close()
ex.shutdown(drain=True, timeout=120)
"""


def pipeline_parallelism():
    """Pipeline-parallel step family (pipeline_* rows, 4 host devices
    in a child): sequential microbatch accumulation, the monolithic
    GPipe scan, and the event-driven 1F1B continuation-DAG schedule,
    plus the measured-vs-analytic bubble row.  Baseline rows print
    before the 1F1B sweep so a crash in the new path still salvages
    them (same discipline as serve_collectives)."""
    env = _cpu_child_env()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_PIPELINE_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    rows = [l for l in stdout.splitlines() if l.startswith("pipeline_")]
    if rc != 0:
        rows.append(f"pipeline,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


def recovery():
    """Membership-change recovery path (recovery_* rows, single-device
    child): serve drain/remesh/re-admit to idle on the paged pool
    (including per-lane KV checkpoint/restore migration), and the
    trainer's remesh-and-retry step.  The serve row prints first so a
    crash mid-sweep salvages it (same discipline as the serve
    families)."""
    env = _cpu_child_env()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_RECOVERY_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    rows = [l for l in stdout.splitlines() if l.startswith("recovery_")]
    if rc != 0:
        rows.append(f"recovery,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


def serve_continuous_batching():
    """Continuous-batching arrival trace (serve_cb rows): one Poisson
    trace served by the paged engine capped at 4 decode lanes and by
    the same pool opened wide, at equal cache memory.  The child prints
    the 4-lane rows before starting the wide sweep, so a timeout or
    crash mid-sweep still salvages the baseline rows (same discipline
    as serve_collectives)."""
    env = _cpu_child_env()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_SERVE_CB_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    rows = [l for l in stdout.splitlines()
            if l.startswith(("serve_cb", "cb_gain"))]
    if rc != 0:
        rows.append(f"serve_cb,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


def serve_collectives():
    """Serve-decode latency family (fig-14 style, 2 host devices in a
    child): per-step latency of the fused decode chain — unsharded,
    model-axis-sharded with the native in-program all-gather, and with
    the persistent user-space all-gather on the serve-collective
    stream.  ``serve_gain_*`` holds the user/native ratio (excluded
    from the trend gate by prefix)."""
    env = _cpu_child_env()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_SERVE_SNIPPET)],
            capture_output=True, text=True, timeout=1200, env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1200s"
    # salvage completed rows: a dead sweep must not hide earlier rows
    rows = [l for l in stdout.splitlines() if l.startswith("serve_")]
    if rc != 0:
        rows.append(f"serve_decode,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows


def run():
    rows = []
    rows += fig7_latency_vs_pending()
    rows += fig8_poll_overhead()
    rows += fig9_thread_contention()
    rows += fig9_executor_scaling()
    rows += fig10_task_class()
    rows += fig11_streams()
    rows += fig12_request_query()
    rows += fig13_continuation_vs_waitset()
    rows += serve_collectives()
    rows += serve_continuous_batching()
    rows += pipeline_parallelism()
    rows += fsdp_training()
    rows += recovery()
    rows += debug_overhead()
    return rows
