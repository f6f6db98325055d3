"""Persistent collective schedules + round batching.

Equivalence suites run in multi-device subprocesses (1/2/4 devices):
persistent rebind (same handle, successive distinct payloads) and
round-batched vs unbatched outputs use integer-valued payloads so float
sums are exact and results can be asserted *bit-identical* to the native
op.  Handle lifecycle — one outstanding start, failure-then-restart,
cancel, close — runs in-process against fake host-callable plans.
"""
import random
import types

import pytest

from tests._multidevice import run_with_devices

from repro.core import ProgressEngine  # noqa: E402
from repro.core.request import CancelledError  # noqa: E402
from repro.collectives import nonblocking as NB  # noqa: E402
from repro.collectives import schedules as S  # noqa: E402


# ---------------------------------------------------------------------------
# Equivalence vs native (subprocess, 1/2/4 devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_persistent_rebind_bitidentical(n_devices):
    """MPI *_init/Start: one handle, three successive distinct payloads,
    each bit-identical to the native psum (integer-valued payloads make
    the float sums exact, so equality is exact equality)."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from jax.sharding import PartitionSpec as P
        from repro.core import ProgressEngine
        from repro.collectives import nonblocking as NB
        from repro.collectives import schedules as S
        n = {n_devices}
        mesh = compat.make_mesh((n,), ("x",))
        eng = ProgressEngine()
        coll = NB.UserCollectives(eng)
        native = jax.jit(compat.shard_map(lambda v: jax.lax.psum(v, "x"),
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        for alg in S.ALGORITHMS:
            h = coll.allreduce_init(
                jax.ShapeDtypeStruct((n * 2, 33), jnp.float32), mesh, "x",
                algorithm=alg, chunks=2)
            for seed in (1, 2, 3):
                x = jax.random.randint(jax.random.PRNGKey(seed),
                                       (n * 2, 33), -8, 8).astype(jnp.float32)
                out = h.start(x).wait(timeout=120)
                np.testing.assert_array_equal(
                    np.asarray(out), np.asarray(native(x)),
                    err_msg=f"{{alg}} seed={{seed}}")
            assert h.starts == 3
            h.close()
        assert coll.failed == 0
        coll.close()
        print("REBIND_OK")
    """, n_devices=n_devices)
    assert "REBIND_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_round_batched_equals_unbatched(n_devices):
    """Round fusion is plain composition: batched (incl. the stacked
    multi-chunk small-payload path) and unbatched issues produce
    bit-identical outputs for every algorithm, and the collectives
    beyond allreduce survive batching too."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from jax.sharding import PartitionSpec as P
        from repro.core import ProgressEngine
        from repro.collectives import nonblocking as NB
        from repro.collectives import schedules as S
        n = {n_devices}
        mesh = compat.make_mesh((n,), ("x",))
        eng = ProgressEngine()
        coll = NB.UserCollectives(eng)
        x = jax.random.randint(jax.random.PRNGKey(0), (n * 2, 3, 40),
                               -8, 8).astype(jnp.float32)
        for alg in S.ALGORITHMS:
            for K in (1, 3):
                ref = coll.iallreduce(x, mesh, "x", algorithm=alg,
                                      chunks=K, round_batch=1).wait(timeout=120)
                for rb in (2, 100, None):       # partial, full, auto
                    got = coll.iallreduce(x, mesh, "x", algorithm=alg,
                                          chunks=K,
                                          round_batch=rb).wait(timeout=120)
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(ref),
                        err_msg=f"{{alg}} K={{K}} rb={{rb}}")
        y = jax.random.randint(jax.random.PRNGKey(1), (n * 2, n * 4),
                               -8, 8).astype(jnp.float32)
        for op, kw in (("ireduce_scatter", {{}}), ("iallgather", {{}})):
            ref = getattr(coll, op)(y, mesh, "x", chunks=2,
                                    round_batch=1).wait(timeout=120)
            got = getattr(coll, op)(y, mesh, "x", chunks=2,
                                    round_batch=100).wait(timeout=120)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=op)
        z = jax.random.randint(jax.random.PRNGKey(2), (n * n, 6),
                               -8, 8).astype(jnp.float32)
        ref = coll.ialltoall(z, mesh, "x", chunks=2,
                             round_batch=1).wait(timeout=120)
        got = coll.ialltoall(z, mesh, "x", chunks=2,
                             round_batch=100).wait(timeout=120)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        coll.close()
        print("BATCH_EQ_OK")
    """, n_devices=n_devices)
    assert "BATCH_EQ_OK" in out


def test_grad_reducer_caches_persistent_handles():
    """EngineGradReducer: one persistent schedule per grad bucket,
    re-started across steps instead of rebuilt — and the reduction still
    equals the plain cross-device mean every step."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.core import ProgressEngine
        from repro.collectives.overlap import EngineGradReducer
        n = 4
        mesh = compat.make_mesh((n,), ("data",))
        eng = ProgressEngine()
        red = EngineGradReducer(mesh, "data", engine=eng, chunks=3,
                                bucket_bytes=64, mean=True)
        for step in range(3):
            grads = {
                "w": jax.random.normal(jax.random.PRNGKey(step), (n, 8, 16)),
                "b": jax.random.normal(jax.random.PRNGKey(step + 10), (n, 16)),
            }
            out = red.iallreduce_tree(grads).wait(timeout=120)
            for k, g in grads.items():
                np.testing.assert_allclose(np.asarray(out[k]),
                                           np.asarray(g).mean(0),
                                           atol=1e-5, err_msg=f"{k}@{step}")
        handles = list(red._persistent.values())
        assert len(handles) >= 2                 # one per bucket
        assert all(h.starts == 3 for h in handles), \
            [h.starts for h in handles]
        red.close()
        assert all(h._closed for h in handles)
        print("REDUCER_PERSISTENT_OK")
    """, n_devices=4)
    assert "REDUCER_PERSISTENT_OK" in out


# ---------------------------------------------------------------------------
# Handle lifecycle (in-process, fake host-callable plans)
# ---------------------------------------------------------------------------

def host_schedule(fns):
    """A compiled-view schedule of plain host callables (floats instead
    of arrays: jax_future treats objects without .is_ready() as ready)
    wrapped so PersistentCollective can 'compile' it at any batch."""
    sched = NB._Schedule(tuple(fns))
    return types.SimpleNamespace(num_rounds=len(fns),
                                 compiled=lambda b: sched)


def fake_plan(schedules, split=None, join=None):
    return NB._Plan("allreduce", "ring", None, None, None, None,
                    schedules, split or (lambda x: [x]),
                    join or NB._first, 0, 1)


def make_handle(fns, **plan_kw):
    eng = ProgressEngine()
    coll = NB.UserCollectives(eng)
    plan = fake_plan([host_schedule(fns)], **plan_kw)
    return coll, NB.PersistentCollective(coll, plan, warmup=False)


class TestPersistentLifecycle:
    def test_start_wait_start(self):
        coll, h = make_handle([lambda v: v + 1, lambda v: v * 10])
        assert h.start(1.0).wait(timeout=5) == 20.0
        assert h.start(2.0).wait(timeout=5) == 30.0
        assert h.starts == 2
        coll.close()

    def test_second_start_while_active_raises(self):
        coll, h = make_handle([lambda v: v])
        req = h.start(1.0)
        with pytest.raises(RuntimeError, match="active start"):
            h.start(2.0)
        req.wait(timeout=5)
        h.start(3.0).wait(timeout=5)         # complete -> restartable
        coll.close()

    def test_failure_then_restart_same_handle(self):
        def stage(v):
            if v < 0:
                raise RuntimeError("negative payload boom")
            return v + 1

        coll, h = make_handle([stage])
        bad = h.start(-1.0)
        assert bad.failed
        with pytest.raises(RuntimeError, match="negative payload boom"):
            bad.value()
        good = h.start(5.0)                  # failed start is restartable
        assert good.wait(timeout=5) == 6.0
        assert coll.failed == 1 and coll.completed == 1
        coll.close()

    def test_cancel_then_restart(self):
        gate = {"open": False}
        blocker = types.SimpleNamespace(is_ready=lambda: gate["open"])
        # payload 1.0 stalls on the gated blocker; later payloads flow
        coll, h = make_handle([lambda v: blocker if v == 1.0 else v,
                               lambda v: v])
        req = h.start(1.0)
        assert not req.is_complete
        h.cancel()
        assert req.cancelled and req.failed
        with pytest.raises(CancelledError):
            req.wait(timeout=5)
        assert coll.cancelled == 1 and coll.in_flight == 0
        # cancelled start is restartable; cancel when idle is a no-op
        h.cancel()
        req2 = h.start(2.0)
        gate["open"] = True                  # also unwedges the old task
        assert req2.wait(timeout=5) == 2.0
        coll.close()

    def test_cancel_after_complete_is_noop(self):
        coll, h = make_handle([lambda v: v])
        req = h.start(1.0)
        assert req.wait(timeout=5) == 1.0
        req.cancel()
        assert not req.cancelled and req.value() == 1.0
        assert coll.cancelled == 0
        coll.close()

    def test_closed_handle_rejects_start(self):
        coll, h = make_handle([lambda v: v])
        h.close()
        with pytest.raises(RuntimeError, match="closed"):
            h.start(1.0)
        coll.close()

    def test_shape_dtype_validation(self):
        import jax.numpy as jnp
        from repro import compat
        mesh = compat.make_mesh((1,), ("x",))
        eng = ProgressEngine()
        coll = NB.UserCollectives(eng)
        h = coll.allreduce_init(jnp.zeros((2, 4), jnp.float32), mesh, "x")
        with pytest.raises(ValueError, match="shape"):
            h.start(jnp.zeros((2, 5), jnp.float32))
        with pytest.raises(ValueError, match="dtype"):
            h.start(jnp.zeros((2, 4), jnp.int32))
        out = h.start(jnp.ones((2, 4), jnp.float32)).wait(timeout=30)
        assert out.shape == (2, 4)
        coll.close()


# ---------------------------------------------------------------------------
# Round batching mechanics (in-process)
# ---------------------------------------------------------------------------

class TestRoundBatching:
    def test_auto_round_batch_breakpoints(self):
        R = 15
        # latency regime: one dispatch
        assert S.auto_round_batch(128 << 10, R) == R
        assert S.auto_round_batch(S.ROUND_BATCH_SMALL_BYTES, R) == R
        # middle: two dispatches
        mid = S.auto_round_batch(S.ROUND_BATCH_SMALL_BYTES + 1, R)
        assert mid == -(-R // 2)
        assert S.auto_round_batch(S.ROUND_BATCH_LARGE_BYTES, R) == mid
        # bandwidth regime: per-round pipelining
        assert S.auto_round_batch(S.ROUND_BATCH_LARGE_BYTES + 1, R) == 1
        # degenerate schedules never batch
        assert S.auto_round_batch(1, 1) == 1
        assert S.auto_round_batch(1, 0) == 1

    def test_fuse_rounds_is_composition(self):
        fns = [lambda v: v + 1, lambda v: v * 3, lambda v: v - 2]
        assert S.fuse_rounds(fns)(4) == ((4 + 1) * 3) - 2
        f = S.fuse_rounds([fns[0]])
        assert f is fns[0]                   # single round: no wrapper
        with pytest.raises(ValueError):
            S.fuse_rounds([])

    def test_compiled_groups_and_caches(self):
        import jax.numpy as jnp
        from repro import compat
        mesh = compat.make_mesh((1,), ("x",))
        stages = [NB._RoundStage(lambda v, i=i: v + i, donate=i > 0)
                  for i in range(5)]
        rs = NB._RoundSchedule(mesh, "x", stages)
        assert rs.compiled(2).num_rounds == 3        # 2+2+1
        assert rs.compiled(5).num_rounds == 1
        assert rs.compiled(99).num_rounds == 1       # clamped to len
        assert rs.compiled(1).num_rounds == 5
        assert rs.compiled(2) is rs.compiled(2)      # cached per batch
        x = jnp.ones((1, 3))
        for b in (1, 2, 5):
            out = x
            for prog in rs.compiled(b).stages:
                out = prog(out)
            assert float(out[0, 0]) == 1 + 0 + 1 + 2 + 3 + 4

    def test_plan_round_batch_resolution(self):
        import jax.numpy as jnp
        from repro import compat
        # explicit beats auto; auto resolves from payload size
        assert NB._resolve_round_batch(3, 1 << 30, 15) == 3
        assert NB._resolve_round_batch(None, 128 << 10, 15) == 15
        assert NB._resolve_round_batch(0, 1 << 30, 15) == 1
        # n == 1: degenerate empty schedule pins the batch to 1
        mesh = compat.make_mesh((1,), ("x",))
        eng = ProgressEngine()
        coll = NB.UserCollectives(eng)
        h = coll.allreduce_init(jnp.zeros((2, 8), jnp.float32), mesh, "x",
                                round_batch=3, warmup=False)
        assert h.round_batch == 1
        coll.close()


# ---------------------------------------------------------------------------
# Exactly-once under random drains, with batching in play
# ---------------------------------------------------------------------------

def test_persistent_restart_random_drains():
    """A persistent handle restarted many times under random progress/
    drain interleavings executes every (fused) stage exactly once per
    start."""
    from repro.core import DEFERRED
    eng = ProgressEngine()
    coll = NB.UserCollectives(eng, policy=DEFERRED)
    counts = []

    def stage(s):
        def fn(v):
            counts[-1][s] += 1
            return v + 1
        return fn

    plan = fake_plan([host_schedule([stage(0), stage(1), stage(2)])])
    h = NB.PersistentCollective(coll, plan, warmup=False)
    rng = random.Random(7)
    for trial in range(20):
        counts.append([0, 0, 0])
        req = h.start(float(trial))
        steps = 0
        while not req.is_complete and steps < 10_000:
            op = rng.randrange(3)
            if op == 0:
                eng.progress(coll.stream)
            elif op == 1:
                coll.queue.drain(max_items=rng.randrange(1, 3))
            else:
                eng.progress(coll.stream)
                coll.queue.drain()
            steps += 1
        assert req.value() == trial + 3.0
    assert counts == [[1, 1, 1]] * 20
    coll.close()
