"""Serve-side persistent collectives + executor-driven starts (+ the
overlap/serve correctness fixes that ride along).

Sharded-serve equivalence runs in multi-device subprocesses (1/2/4
devices); executor-driven start mechanics, latency bookkeeping and
bucketing fixes run in-process.
"""
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collectives import nonblocking as NB
from repro.collectives.overlap import bucket_tree
from repro.configs import get_config
from repro.core import ProgressEngine, ProgressExecutor
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine
from tests._multidevice import run_with_devices
from tests.conftest import reduce_cfg


# ---------------------------------------------------------------------------
# Sharded serve: user backend token streams == native-sharded (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_sharded_serve_user_matches_native(n_devices):
    """Acceptance: decode with --collective-backend user on a model axis
    produces token streams identical to the native-sharded path (both
    consume the same partial-logits program; only the gather differs)."""
    out = run_with_devices(f"""
        import jax, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.core import ProgressEngine
        from repro.models import registry
        from repro.serve.engine import GenRequest, ServeEngine

        n = {n_devices}
        cfg = get_config('qwen2-0.5b').with_overrides(
            num_layers=2, d_model=32, d_ff=64, vocab_size=64, num_heads=4,
            num_kv_heads=2, head_dim=16, remat_policy='none')
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        mesh = compat.make_mesh((n,), ('model',))

        def serve(backend, mesh):
            eng = ProgressEngine()
            srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=64,
                              mesh=mesh, collective_backend=backend,
                              collective_chunks=2)
            reqs = [GenRequest(f'r{{i}}', np.array([i + 1, i + 2], np.int32),
                               max_new_tokens=4) for i in range(6)]
            dones = [srv.submit(r) for r in reqs]
            srv.run_until_idle(timeout=300)
            toks = [d.value() for d in dones]
            assert srv._ag_handle is None or srv._ag_handle.starts == srv.steps
            srv.close(timeout=60)
            return toks

        native = serve('native', mesh)
        user = serve('user', mesh)
        assert native == user, (native, user)
        assert all(len(t) == 4 for t in user)
        if n > 1:    # vocab not divisible by the model axis: eager error
            bad = cfg.with_overrides(vocab_size=63)
            try:
                ServeEngine(bad, registry.init_params(bad,
                            jax.random.PRNGKey(0)), ProgressEngine(),
                            batch_slots=2, max_seq=32, mesh=mesh)
                raise AssertionError('divisibility not validated')
            except ValueError as e:
                assert 'divisible' in str(e)
        print('SHARDED_SERVE_EQUIV_OK')
    """, n_devices=n_devices)
    assert "SHARDED_SERVE_EQUIV_OK" in out


def test_sharded_serve_on_executor_matches_caller_driven():
    """Executor-adopted serve-collective stream (executor-driven gather
    starts) produces the same tokens as the caller-driven bridge."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.core import ProgressEngine, ProgressExecutor
        from repro.models import registry
        from repro.serve.engine import GenRequest, ServeEngine

        cfg = get_config('qwen2-0.5b').with_overrides(
            num_layers=2, d_model=32, d_ff=64, vocab_size=64, num_heads=4,
            num_kv_heads=2, head_dim=16, remat_policy='none')
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        mesh = compat.make_mesh((2,), ('model',))

        def serve(workers, start=True):
            eng = ProgressEngine()
            ex = None
            if workers:
                ex = ProgressExecutor(eng, workers, steal=False)
                if start:
                    ex.start()
            srv = ServeEngine(cfg, params, eng, batch_slots=2, max_seq=64,
                              mesh=mesh, collective_backend='user',
                              executor=ex)
            r = GenRequest('a', np.array([5, 6], np.int32), max_new_tokens=4)
            d = srv.submit(r)
            srv.run_until_idle(timeout=300)
            srv.close(timeout=60)
            if ex is not None and ex.running:
                ex.shutdown(drain=True, timeout=60)
            return d.value()

        assert serve(0) == serve(2)
        # regression: executor attached but never started must degrade
        # to inline progress of ALL serve streams (incl. the collective
        # stream driving the gather rounds), not hang to TimeoutError
        assert serve(2, start=False) == serve(0)
        print('EXEC_SERVE_EQUIV_OK')
    """, n_devices=2)
    assert "EXEC_SERVE_EQUIV_OK" in out


def test_sharded_serve_rejects_bad_configs(rng):
    # (vocab divisibility needs a >1 model axis — validated in the
    # 2/4-device subprocess above)
    from repro import compat
    mesh = compat.make_mesh((1,), ("model",))
    cfg = reduce_cfg(get_config("qwen2-0.5b"))
    params = registry.init_params(cfg, rng)
    with pytest.raises(ValueError, match="axis"):
        ServeEngine(cfg, params, ProgressEngine(), batch_slots=2,
                    max_seq=32, mesh=mesh, model_axis="nope")
    with pytest.raises(ValueError, match="backend"):
        ServeEngine(cfg, params, ProgressEngine(), batch_slots=2,
                    max_seq=32, collective_backend="bogus")


# ---------------------------------------------------------------------------
# Executor-driven persistent starts (in-process, fake host plans)
# ---------------------------------------------------------------------------

def host_schedule(fns):
    sched = NB._Schedule(tuple(fns))
    return types.SimpleNamespace(num_rounds=len(fns),
                                 compiled=lambda b: sched)


def fake_plan(schedules, split=None, join=None):
    return NB._Plan("allreduce", "ring", None, None, None, None,
                    schedules, split or (lambda x: [x]),
                    join or NB._first, 0, 1)


class TestExecutorDrivenStart:
    def test_start_dispatches_on_worker_not_caller(self):
        """Acceptance: start() on an executor-adopted stream returns
        without dispatching round 0 on the calling thread — the worker
        that owns the collective stream issues it."""
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, 1, steal=False)
        coll = NB.UserCollectives(eng, executor=ex)
        h = NB.PersistentCollective(
            coll, fake_plan([host_schedule([lambda v: v + 1,
                                            lambda v: v * 10])]),
            warmup=False)
        ex.start()
        try:
            main = threading.get_ident()
            req = h.start(2.0)
            assert req.wait(timeout=30) == 30.0
            assert req.issue_thread is not None
            assert req.issue_thread != main
            assert req.issue_thread in ex.worker_thread_idents()
        finally:
            ex.shutdown(drain=True, timeout=30)
            coll.close()

    def test_start_falls_back_to_caller_thread(self):
        """No running executor: round 0 dispatches on the start() caller
        (and an executor constructed but never started does not defer)."""
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, 1, steal=False)     # never started
        coll = NB.UserCollectives(eng, executor=ex)
        h = NB.PersistentCollective(
            coll, fake_plan([host_schedule([lambda v: v + 1])]),
            warmup=False)
        req = h.start(1.0)
        assert req.issue_thread == threading.get_ident()
        assert req.wait(timeout=30) == 2.0
        coll.close()

    def test_deferred_split_failure_fails_request(self):
        """A split that raises inside the worker-issued launch fails the
        request (observable via wait), never the worker thread."""
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, 1, steal=False)
        coll = NB.UserCollectives(eng, executor=ex)

        def bad_split(x):
            raise RuntimeError("split boom")

        h = NB.PersistentCollective(
            coll, fake_plan([host_schedule([lambda v: v])],
                            split=bad_split),
            warmup=False)
        ex.start()
        try:
            req = h.start(1.0)
            with pytest.raises(RuntimeError, match="split boom"):
                req.wait(timeout=30)
            assert req.failed
            # handle restartable after the failed deferred start
            h.plan.split = lambda x: [x]
            assert h.start(3.0).wait(timeout=30) == 3.0
        finally:
            ex.shutdown(drain=True, timeout=30)
            coll.close()

    def test_executor_shutdown_between_start_and_wait(self):
        """The issue task survives executor shutdown: wait() falls back
        to inline progress and still completes the collective."""
        eng = ProgressEngine()
        ex = ProgressExecutor(eng, 1, steal=False)
        coll = NB.UserCollectives(eng, executor=ex)
        h = NB.PersistentCollective(
            coll, fake_plan([host_schedule([lambda v: v * 2])]),
            warmup=False)
        ex.start()
        req = h.start(4.0)
        ex.shutdown(drain=False, timeout=30)   # workers gone, task queued
        assert req.wait(timeout=30) == 8.0
        coll.close()


# ---------------------------------------------------------------------------
# Serve latency fields (TTFT exactly once; finished_at on both paths)
# ---------------------------------------------------------------------------

class CountingGenRequest(GenRequest):
    """Counts first_token_at stamps (None -> value transitions)."""

    def __setattr__(self, key, value):
        if key == "first_token_at" and value is not None:
            object.__setattr__(self, "_ttft_stamps",
                               getattr(self, "_ttft_stamps", 0) + 1)
        object.__setattr__(self, key, value)


@pytest.fixture
def served(rng):
    cfg = reduce_cfg(get_config("qwen2-0.5b"),
                     num_layers=2, d_model=32, d_ff=64, vocab_size=64)
    params = registry.init_params(cfg, rng)
    eng = ProgressEngine()
    srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=64)
    return srv, eng


class TestServeLatencyFields:
    def test_ttft_stamped_exactly_once_on_success(self, served):
        srv, eng = served
        req = CountingGenRequest("r0", np.array([1, 2], np.int32),
                                 max_new_tokens=5)
        srv.submit(req)
        srv.run_until_idle(timeout=240)
        assert req._ttft_stamps == 1           # 5 steps, ONE stamp
        assert req.first_token_at is not None
        assert req.finished_at is not None
        assert req.finished_at >= req.first_token_at >= req.submitted_at
        snap = srv.latency_snapshot()
        assert snap.submitted == 1 and snap.completed == 1
        assert snap.failed == 0 and snap.no_first_token == 0
        assert snap.ttft_ms_mean is not None
        assert snap.latency_ms_mean >= snap.ttft_ms_mean

    def test_failed_before_first_token_null_propagates(self, served):
        """A request whose decode fails before producing any token keeps
        first_token_at=None, gets finished_at, and the snapshot counts
        it instead of faking a TTFT."""
        srv, eng = served
        req = GenRequest("r0", np.array([1], np.int32), max_new_tokens=2)
        with srv._lock:
            slot = srv.slots.assign(req.request_id)
            req.slot_index = slot.index
            req.next_input = 1
            srv._active[slot.index] = req

        def broken(*a, **k):
            raise RuntimeError("device lost")

        srv._jit_decode = broken
        srv._schedule_decode()
        t0 = time.monotonic()
        while not req.done_req.is_complete:
            eng.progress()
            assert time.monotonic() - t0 < 30
        assert req.done_req.failed
        assert req.first_token_at is None      # null-propagated, not faked
        assert req.finished_at is not None     # failure path stamps finish
        snap = srv.latency_snapshot()
        assert snap.failed == 1 and snap.no_first_token == 1
        assert snap.ttft_ms_mean is None       # nothing to aggregate
        assert snap.latency_ms_mean is not None

    def test_prefill_failure_records_and_frees_slots(self, served):
        """Prefill raising fails the admitted batch with finished_at set
        and slots released — and later arrivals still serve."""
        srv, eng = served
        real = srv._jit_decode
        srv._jit_decode = lambda *a: (_ for _ in ()).throw(
            RuntimeError("prefill boom"))
        bad = GenRequest("bad", np.array([1, 2, 3], np.int32),
                         max_new_tokens=2)
        done = srv.submit(bad)
        t0 = time.monotonic()
        while not done.is_complete:
            eng.progress()
            assert time.monotonic() - t0 < 30
        assert done.failed and "prefill boom" in str(done.exception)
        assert bad.finished_at is not None and bad.first_token_at is None
        assert len(srv.slots.free_slots()) == 4
        assert not srv._prefill_active
        srv._jit_decode = real
        good = srv.submit(GenRequest("good", np.array([1], np.int32),
                                     max_new_tokens=2))
        srv.run_until_idle(timeout=120)
        assert good.is_complete and len(good.value()) == 2
        snap = srv.latency_snapshot()
        assert snap.failed == 1 and snap.completed == 1

    def test_submit_not_blocked_by_prefill_lock(self, served):
        """The serve lock is free while prefill stages its cache: a
        submit() during prefill returns promptly instead of waiting for
        the whole token-by-token prompt loop."""
        srv, eng = served
        in_prefill = threading.Event()
        release = threading.Event()
        real = srv._jit_decode

        def slow_decode(*a, **k):
            in_prefill.set()
            assert release.wait(timeout=30)
            return real(*a, **k)

        srv._jit_decode = slow_decode
        first = srv.submit(GenRequest("a", np.array([1, 2, 3], np.int32),
                                      max_new_tokens=1))
        runner = threading.Thread(target=lambda: srv.run_until_idle(240))
        runner.start()
        try:
            assert in_prefill.wait(timeout=30)
            t0 = time.monotonic()
            srv.submit(GenRequest("b", np.array([4], np.int32),
                                  max_new_tokens=1))
            submit_s = time.monotonic() - t0
            assert submit_s < 1.0, f"submit blocked {submit_s:.1f}s on prefill"
            assert srv._prefill_active          # prefill really was running
        finally:
            release.set()
            runner.join(timeout=240)
        assert first.is_complete


# ---------------------------------------------------------------------------
# Mixed-dtype bucketing (overlap.allreduce_tree / bucket_tree)
# ---------------------------------------------------------------------------

class TestBucketTree:
    def test_buckets_are_single_dtype(self):
        tree = {"a": jnp.ones((4,), jnp.float32),
                "b": jnp.ones((4,), jnp.bfloat16),
                "c": jnp.ones((4,), jnp.float32),
                "d": jnp.ones((4,), jnp.bfloat16)}
        leaves = jax.tree.leaves(tree)
        buckets = bucket_tree(tree, bucket_bytes=1 << 20)
        assert sorted(i for b in buckets for i in b) == list(range(4))
        for b in buckets:
            dts = {jnp.dtype(leaves[i].dtype) for i in b}
            assert len(dts) == 1, f"mixed-dtype bucket {b}: {dts}"

    def test_size_limit_still_respected_per_dtype(self):
        tree = [jnp.ones((1024,), jnp.float32) for _ in range(4)]
        buckets = bucket_tree(tree, bucket_bytes=4096)
        assert len(buckets) == 4               # each leaf hits the cap

    def test_non_array_leaf_rejected_eagerly(self):
        with pytest.raises(TypeError, match="leaf 1 is float"):
            bucket_tree([jnp.ones((2,)), 3.14, jnp.ones((2,))])


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_mixed_dtype_allreduce_tree_matches_psum(n_devices):
    """Bucketed user-schedule allreduce_tree on a mixed f32/bf16 tree:
    per-leaf dtype preserved (no silent upcast) and values match the
    per-leaf native psum within the leaf dtype's tolerance."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from jax.sharding import PartitionSpec as P
        from repro.collectives.overlap import allreduce_tree

        n = {n_devices}
        mesh = compat.make_mesh((n,), ("x",))
        key = jax.random.PRNGKey(0)
        tree = {{
            "w32": jax.random.normal(key, (n, 3, 8), jnp.float32),
            "w16": jax.random.normal(key, (n, 2, 5)).astype(jnp.bfloat16),
            "b32": jax.random.normal(key, (n, 7), jnp.float32),
            "b16": jax.random.normal(key, (n, 4)).astype(jnp.bfloat16),
        }}

        def reduced(algorithm):
            fn = lambda t: allreduce_tree(t, "x", algorithm)
            return jax.jit(compat.shard_map(
                fn, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(tree)

        native = reduced("psum")
        for alg in ("ring", "recursive_doubling"):
            user = reduced(alg)
            for k in tree:
                nat, usr = native[k], user[k]
                assert usr.dtype == tree[k].dtype, (k, usr.dtype)
                tol = 1e-5 if usr.dtype == jnp.float32 else 0.05
                np.testing.assert_allclose(
                    np.asarray(usr, np.float32), np.asarray(nat, np.float32),
                    atol=tol, rtol=tol, err_msg=f"{{alg}}/{{k}}")
        print("MIXED_DTYPE_TREE_OK")
    """, n_devices=n_devices)
    assert "MIXED_DTYPE_TREE_OK" in out
