"""Paper Fig 13/14: user-level allreduce vs the native collective.

Runs in a subprocess with 8 host devices (the main process stays
single-device).  Fig 13: wall time of a jitted single-int allreduce,
native ``psum`` vs the user-level schedules — the paper's result is
that the specialized user-level implementation is competitive (it
beats MPICH's Iallreduce in the paper thanks to context shortcuts).
Fig 14: the *nonblocking* engine-driven ``iallreduce`` (chunk-pipelined
round schedules, see ``collectives/nonblocking.py``) vs native ``psum``
at 128KB / 4MB / 64MB / 256MB, two ways:

* **one-shot per-round** (``round_batch=1``, the PR-3 baseline rows —
  names unchanged so the CI trend report tracks them): every round of
  every chunk is its own dispatch + engine round trip;
* **persistent + round batching** (``allreduce_init``/``start`` with the
  auto batch factor): the plan and fused round programs are built once,
  each ``start`` re-binds the payload.  Small payloads collapse to 1–2
  dispatches (with multi-chunk payloads stacked through one program);
  large payloads keep per-round dispatch for chunk pipelining.

``fig14_persistent_gain_*`` rows record the per-config speedup of the
persistent path over the one-shot per-round baseline — the small-payload
amortization win the trend gate must never lose.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.collectives import schedules as S

mesh = compat.make_mesh((8,), ("x",))
x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)   # one scalar per rank

def native(v):
    return jax.lax.psum(v, "x")

fns = {"native_psum": native}
fns.update({k: (lambda f: lambda v: f(v, "x"))(f) for k, f in S.ALGORITHMS.items()})

for name, fn in fns.items():
    jitted = jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    out = jitted(x); out.block_until_ready()          # compile
    iters = 300
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(x)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / iters * 1e6
    print(f"fig13_allreduce_1int_{name},{us:.3f},8 host devices")

# ---- Fig 14: nonblocking engine-driven iallreduce vs native, by size ----
from repro.core import ProgressEngine
from repro.collectives import nonblocking as NB

eng = ProgressEngine()
coll = NB.UserCollectives(eng)
native_jit = jax.jit(compat.shard_map(native, mesh=mesh, in_specs=P("x"),
                                      out_specs=P("x")))

def timed(issue, iters):
    out = issue()                             # compile / warm everything
    t0 = time.perf_counter()
    for _ in range(iters):
        out = issue()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6

# payload rows: 128KB + 4MB (latency regime: persistent + round batching
# collapse each start to 1-2 dispatches), 64MB, 256MB (bandwidth regime:
# per-round dispatch keeps chunks pipelining; recursive doubling with
# 2-way chunk pipelining lands within ~1.4x of the native psum).
for D, iters in ((4096, 30), (131072, 20), (2097152, 8), (8388608, 4)):
    xs = jnp.ones((8, D), jnp.float32)
    nbytes = xs.size * 4
    out = native_jit(xs); out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = native_jit(xs)
    out.block_until_ready()
    nat_us = (time.perf_counter() - t0) / iters * 1e6
    print(f"fig14_native_psum_{nbytes}B,{nat_us:.3f},"
          f"bw={nbytes / nat_us / 1e3:.2f}GB/s")
    for alg in ("ring", "recursive_doubling"):
        for K in (1, 2, 4):
            # one-shot, one dispatch per round: the PR-3 baseline row
            # (same name across PRs — the trend report tracks it)
            us = timed(lambda: coll.iallreduce(
                xs, mesh, "x", algorithm=alg, chunks=K,
                round_batch=1).wait(timeout=600), iters)
            print(f"fig14_user_iallreduce_{nbytes}B_{alg}_c{K},{us:.3f},"
                  f"bw={nbytes / us / 1e3:.2f}GB/s vs native "
                  f"x{us / nat_us:.2f}")
            # persistent handle + auto round batching: *_init once,
            # start() per iteration re-binds the payload
            h = coll.allreduce_init(xs, mesh, "x", algorithm=alg, chunks=K)
            pus = timed(lambda: h.start(xs).wait(timeout=600), iters)
            print(f"fig14_user_iallreduce_persistent_{nbytes}B_{alg}_c{K},"
                  f"{pus:.3f},rb={h.round_batch} "
                  f"bw={nbytes / pus / 1e3:.2f}GB/s vs native "
                  f"x{pus / nat_us:.2f}")
            # value field IS the speedup ratio (trend.py excludes these
            # rows from the latency gate by prefix)
            print(f"fig14_persistent_gain_{nbytes}B_{alg}_c{K},"
                  f"{us / pus:.3f},persistent {pus:.1f}us vs one-shot "
                  f"per-round {us:.1f}us")
            h.close()
coll.close()
"""


def run():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"     # host devices: never the accelerator
    try:
        proc = subprocess.run([sys.executable, "-c", textwrap.dedent(SNIPPET)],
                              capture_output=True, text=True, timeout=1500,
                              env=env)
        stdout, rc, err = proc.stdout, proc.returncode, proc.stderr or ""
    except subprocess.TimeoutExpired as e:
        stdout, rc, err = e.stdout or "", -1, "timeout after 1500s"
    # salvage whatever rows completed: a slow/dead fig14 sweep must not
    # throw away the fig13 rows already printed before it
    rows = [l for l in stdout.splitlines() if l.startswith("fig1")]
    if rc != 0:
        rows.append(f"fig13_14_allreduce,nan,FAILED(rc={rc}): {err[-200:]}")
    return rows
