"""Membership-aware persistent collectives + engine-wired fault tolerance.

Four tiers:

* epoch/handle level — ``MembershipEpoch.invalidate`` fails an in-flight
  persistent start exactly once with a retryable ``MembershipError``,
  marks the handle stale until ``rebuild``, and notifies listeners only
  after the handles are failed;
* monitor level — ``HeartbeatMonitor`` survives a concurrent
  ``beat()``/``_poll()`` hammer, ``StepWatchdog`` is one-shot per arm
  (disarm-before-callbacks), and the elastic planners reject impossible
  survivor counts loudly;
* model level — the fixed-slot decode path honours the ``fed`` mask,
  so batched prefill cannot advance the recurrent state of SSM lanes it
  did not feed (the latent bug the paged path already guarded against);
* chaos level (slow) — kill devices mid-decode, mid-prefill and
  mid-gather: the serve engine drains, checkpoints resident lanes,
  remeshes onto the survivors and re-admits, and every token stream is
  bit-identical to an undisturbed run; the trainer's post-failure loss
  trajectory is bit-identical to a from-checkpoint restart on the same
  surviving mesh.
"""
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collectives import nonblocking as NB
from repro.configs import get_config
from repro.core import ProgressEngine
from repro.distributed import elastic
from repro.distributed.fault_tolerance import (
    HeartbeatMonitor, StepWatchdog, StragglerDetector)
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine
from conftest import reduce_cfg
from tests._multidevice import run_with_devices


# ---------------------------------------------------------------------------
# Epoch / handle lifecycle
# ---------------------------------------------------------------------------

def _one_device_handle(epoch=None, **kw):
    from repro import compat
    mesh = compat.make_mesh((1,), ("x",))
    eng = ProgressEngine()
    coll = NB.UserCollectives(eng)
    h = coll.allreduce_init(jnp.zeros((2, 4), jnp.float32), mesh, "x",
                            epoch=epoch, warmup=False, **kw)
    return mesh, coll, h


class TestMembershipEpoch:
    def test_stale_handle_raises_until_rebuild(self):
        epoch = NB.MembershipEpoch(n_devices=1)
        mesh, coll, h = _one_device_handle(epoch)
        out = h.start(jnp.ones((2, 4), jnp.float32)).wait(timeout=30)
        assert float(jnp.sum(out)) == 8.0
        exc = epoch.invalidate(survivors=1, reason="unit test")
        assert exc.survivors == 1 and exc.version == 1
        assert h.stale
        with pytest.raises(NB.MembershipError) as ei:
            h.start(jnp.ones((2, 4), jnp.float32))
        assert ei.value.survivors == 1 and ei.value.version == 1
        h.rebuild(mesh)
        assert not h.stale and h.rebuilds == 1
        out = h.start(jnp.ones((2, 4), jnp.float32)).wait(timeout=30)
        assert float(jnp.sum(out)) == 8.0
        coll.close()

    def test_invalidate_fails_inflight_start_exactly_once(self):
        """The in-flight start is failed retryably; a second invalidation
        does not double-fail the (already complete) request."""
        from tests.test_persistent_collectives import make_handle
        gate = {"open": False}
        blocker = types.SimpleNamespace(is_ready=lambda: gate["open"])
        coll, h = make_handle([lambda v: blocker, lambda v: v])
        epoch = NB.MembershipEpoch(n_devices=4)
        epoch.register(h)
        h.epoch = epoch
        h._epoch_version = epoch.version
        req = h.start(1.0)
        assert not req.is_complete
        epoch.invalidate(survivors=3, reason="peer died")
        assert req.is_complete and req.failed
        with pytest.raises(NB.MembershipError) as ei:
            req.value()
        assert ei.value.survivors == 3
        failed_before = coll.failed
        epoch.invalidate(survivors=2)
        assert coll.failed == failed_before      # no double-fail
        gate["open"] = True                      # abandoned round retires
        coll.close()

    def test_listeners_run_after_handles_failed(self):
        from tests.test_persistent_collectives import make_handle
        gate = {"open": False}
        blocker = types.SimpleNamespace(is_ready=lambda: gate["open"])
        coll, h = make_handle([lambda v: blocker, lambda v: v])
        epoch = NB.MembershipEpoch(n_devices=2)
        epoch.register(h)
        h.epoch = epoch
        h._epoch_version = epoch.version
        seen = []
        epoch.subscribe(lambda ep, exc: seen.append(
            (ep.version, exc.survivors, h.active.is_complete)))
        req = h.start(1.0)
        assert not req.is_complete
        epoch.invalidate(survivors=1)
        # the listener observed the handle's start already failed
        assert seen == [(1, 1, True)]
        gate["open"] = True
        coll.close()

    def test_epoch_tracks_survivor_count(self):
        epoch = NB.MembershipEpoch(n_devices=8)
        assert epoch.n_devices == 8 and epoch.version == 0
        epoch.invalidate(survivors=5)
        epoch.invalidate(survivors=3)
        assert epoch.n_devices == 3 and epoch.version == 2
        assert epoch.invalidations == 2


# ---------------------------------------------------------------------------
# Monitors
# ---------------------------------------------------------------------------

class TestHeartbeatRace:
    def test_concurrent_beat_and_poll(self):
        """Hammer beat() from worker threads while _poll sweeps with an
        advancing clock right at the timeout edge: no deadlock, no
        permanently-lost peer (the final beat always revives)."""
        eng = ProgressEngine()
        clock = {"t": 0.0}
        lock = threading.Lock()

        def now():
            with lock:
                return clock["t"]

        hb = HeartbeatMonitor(eng, ["p0", "p1"], timeout=1.0, clock=now)
        stop = threading.Event()

        def beater():
            while not stop.is_set():
                hb.beat("p0")

        threads = [threading.Thread(target=beater) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                with lock:
                    clock["t"] += 0.6       # p1 dies; p0 is kept alive
                eng.progress()
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert "p1" in hb.failed
        hb.beat("p0")
        assert "p0" in hb.alive

    def test_dead_peer_invalidates_epoch_with_device_count(self):
        eng = ProgressEngine()
        clock = {"t": 0.0}
        epoch = NB.MembershipEpoch(n_devices=8)
        hb = HeartbeatMonitor(eng, [f"h{i}" for i in range(4)], timeout=5.0,
                              clock=lambda: clock["t"], epoch=epoch,
                              devices_per_peer=2)
        clock["t"] = 3.0
        for i in range(3):
            hb.beat(f"h{i}")                # h3 silent
        clock["t"] = 6.0
        eng.progress()
        assert epoch.version == 1
        assert epoch.n_devices == 6         # 3 peers x 2 devices


class TestWatchdogOneShot:
    def test_disarm_after_fire_no_refire(self):
        eng = ProgressEngine()
        clock = {"t": 0.0}
        epoch = NB.MembershipEpoch(n_devices=4)
        wd = StepWatchdog(eng, limit=10.0, clock=lambda: clock["t"],
                          epoch=epoch)
        wd.arm()
        clock["t"] = 11.0
        eng.progress()
        assert wd.fired == 1
        # a hung step keeps the membership: survivors == current devices
        assert epoch.version == 1 and epoch.n_devices == 4
        # further sweeps without re-arm must NOT refire
        clock["t"] = 1000.0
        eng.progress()
        eng.progress()
        assert wd.fired == 1 and epoch.version == 1
        wd.arm()
        clock["t"] = 2000.0
        eng.progress()
        assert wd.fired == 2 and epoch.version == 2

    def test_handler_progressing_engine_does_not_refire(self):
        """on_hang may itself progress the engine (restart machinery):
        the disarm-before-callback ordering keeps firing one-shot."""
        eng = ProgressEngine()
        clock = {"t": 0.0}
        wd = StepWatchdog(eng, limit=5.0, clock=lambda: clock["t"],
                          on_hang=lambda: eng.progress())
        wd.arm()
        clock["t"] = 6.0
        eng.progress()
        assert wd.fired == 1


class TestElasticValidation:
    def test_largest_pof2_rejects_zero(self):
        with pytest.raises(ValueError, match="n >= 1"):
            elastic.largest_pof2(0)

    def test_plan_mesh_rejects_total_loss(self):
        with pytest.raises(ValueError, match="at least 1"):
            elastic.plan_mesh(0)
        with pytest.raises(ValueError, match="at least 1"):
            elastic.plan_mesh(-3)

    def test_remesh_rejects_total_loss(self):
        with pytest.raises(ValueError, match="at least 1"):
            elastic.remesh(0)


class TestStragglerBounds:
    def test_history_and_flagged_bounded(self):
        d = StragglerDetector(threshold=1.5, history_maxlen=8)
        for i in range(100):
            d.record(f"src{i}", 1.0 if i < 5 else 10.0 + i)
        assert len(d.history) <= 8
        assert len(d.flagged) <= 8

    def test_flagged_evicts_least_recent(self):
        d = StragglerDetector(threshold=1.5, history_maxlen=2)
        for _ in range(5):
            d.record("ok", 1.0)
        d.record("a", 10.0)
        d.record("b", 10.0)
        d.record("c", 10.0)
        assert set(d.flagged) == {"b", "c"}   # "a" evicted (LRU)


# ---------------------------------------------------------------------------
# Model level: fed mask on the fixed-slot decode path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-1.2b"])
def test_fed_mask_freezes_slot_ssm_state(arch):
    """The latent fixed-slot bug: a batched call feeding only some lanes
    must not advance the recurrent state of the others.  Mirrors the
    paged-path guard (test_continuous_batching) on the SLOT cache."""
    cfg = reduce_cfg(get_config(arch), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    cache = registry.init_cache(cfg, 2, 16)
    # advance both lanes once so the state is non-trivial
    toks = jnp.asarray([[5], [6]], jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    _, cache = registry.decode_step(params, cfg, cache, toks, pos)
    # now feed ONLY lane 0; lane 1 sees a garbage token
    fed = jnp.asarray([True, False])
    _, new_cache = registry.decode_step(params, cfg, cache,
                                        jnp.asarray([[7], [9]], jnp.int32),
                                        pos + 1, fed)
    flat_old = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat_new = jax.tree_util.tree_flatten_with_path(new_cache)[0]
    checked = 0
    for (path, old), (_, new) in zip(flat_old, flat_new):
        # mamba's slot cache IS the state tree; hybrid nests it under
        # ssm/tail_ssm next to attention KV (which is position-safe and
        # legitimately written for unfed lanes)
        if cfg.family != "ssm" and "ssm" not in jax.tree_util.keystr(path):
            continue
        checked += 1
        assert float(jnp.max(jnp.abs(new[:, 1] - old[:, 1]))) == 0.0
        assert float(jnp.max(jnp.abs(new[:, 0] - old[:, 0]))) > 0.0
    assert checked > 0


def test_reset_cache_lane_zeroes_recycled_slot():
    cfg = reduce_cfg(get_config("mamba2-1.3b"), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    cache = registry.init_cache(cfg, 2, 16)
    toks = jnp.asarray([[5], [6]], jnp.int32)
    _, cache = registry.decode_step(params, cfg, cache, toks,
                                    jnp.zeros((2,), jnp.int32))
    cache = registry.reset_cache_lane(cfg, cache, 1)
    for leaf in jax.tree_util.tree_leaves(cache):
        assert float(jnp.max(jnp.abs(leaf[:, 1]))) == 0.0
        assert float(jnp.max(jnp.abs(leaf[:, 0]))) > 0.0


def _serve_streams(cfg, params, prompts, max_new, *, staggered=False, **kw):
    eng = ProgressEngine()
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 32)
    srv = ServeEngine(cfg, params, eng, **kw)
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    if staggered:
        # submit the second request only once the first is mid-decode, so
        # its prefill interleaves with the first lane's decode steps
        srv.submit(reqs[0])
        t0 = time.monotonic()
        while len(reqs[0].out_tokens) < 2 and time.monotonic() - t0 < 120:
            eng.progress()
        assert len(reqs[0].out_tokens) >= 2
        for r in reqs[1:]:
            srv.submit(r)
    else:
        for r in reqs:
            srv.submit(r)
    srv.run_until_idle(timeout=300)
    lat = srv.latency_snapshot()
    srv.close(timeout=60)
    return [list(r.out_tokens) for r in reqs], lat


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-1.2b"])
def test_slot_engine_interleaved_prefill_regression(arch):
    """Serve-level regression for the fed-mask fix: prefilling request B
    while request A decodes must leave A's stream bit-identical to A
    served in isolation (SSM state frozen for non-fed lanes)."""
    cfg = reduce_cfg(get_config(arch), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=n).astype(np.int32)
               for n in (5, 9)]
    ref = [_serve_streams(cfg, params, [p], 6)[0][0] for p in prompts]
    got, lat = _serve_streams(cfg, params, prompts, 6, staggered=True)
    assert got == ref
    assert lat.completed == 2 and lat.failed == 0


# ---------------------------------------------------------------------------
# KV lane checkpoint/restore (the migration primitive)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "arch,kv_dtype",
    [("qwen2-0.5b", "bf16"), ("qwen2-0.5b", "int8"),
     ("mamba2-1.3b", "bf16"), ("zamba2-1.2b", "bf16")],
    ids=["qwen2-0.5b", "qwen2-0.5b-int8kv", "mamba2-1.3b", "zamba2-1.2b"])
def test_kv_lane_checkpoint_restore_roundtrip(arch, kv_dtype):
    from repro.serve.kvcache import PagedKVCache
    cfg = reduce_cfg(get_config(arch), dtype="float32",
                     kv_cache_dtype=kv_dtype)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    pool = PagedKVCache(cfg, lanes=2, max_seq=32, block_size=4)
    lane = pool.assign("req", seq_len=1)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 1,
                              cfg.vocab_size)
    pos = jnp.zeros((2,), jnp.int32)
    fed = jnp.asarray([True, False])
    # feed 6 tokens into lane 0, growing its table as we go
    for t in range(6):
        assert pool.ensure(lane.index, t)
        tables = jnp.asarray(pool.block_tables())
        _, pool.cache = registry.decode_step_paged(
            params, cfg, pool.cache, toks, pos + t, tables, fed)
        lane.pos = t + 1
    ckpt = pool.checkpoint_lane(lane.index)
    assert ckpt["pos"] == 6
    # every pooled leaf, [layers, num_blocks, block_size, KVH*width], is
    # snapshotted as the lane's 2 blocks
    pooled = [leaf for leaf in jax.tree_util.tree_leaves(pool.cache)
              if leaf.shape[1:3] == (pool.num_blocks, 4)]
    assert len(ckpt["blocks"]) == len(pooled)
    for leaf, snap in zip(pooled, ckpt["blocks"].values()):
        assert snap.shape == (leaf.shape[0], 2) + leaf.shape[2:]
    # restore into a FRESH pool (different block layout is fine: the
    # snapshot is logical positions, the table maps them to new blocks)
    pool2 = PagedKVCache(cfg, lanes=2, max_seq=32, block_size=4)
    pool2.assign("other", seq_len=3)        # shift the block layout
    lane2 = pool2.assign("req", seq_len=7)
    pool2.cache = pool2.restore_lane(pool2.cache, lane2.index, ckpt)
    assert pool2.slots[lane2.index].pos == 6
    ckpt2 = pool2.checkpoint_lane(lane2.index)
    assert ckpt2["pos"] == ckpt["pos"]
    for key in ckpt["blocks"]:
        np.testing.assert_array_equal(ckpt2["blocks"][key],
                                      ckpt["blocks"][key])
    for key in ckpt["state"]:
        np.testing.assert_array_equal(ckpt2["state"][key],
                                      ckpt["state"][key])


# ---------------------------------------------------------------------------
# Chaos (slow): kill devices mid-flight; everything recovers, streams exact
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = reduce_cfg(get_config("qwen2-0.5b"), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _chaos_serve(cfg, params, prompts, max_new, *, kill_after_tokens,
                 watchdog=False, **kw):
    """Serve with a shared epoch; invalidate once `kill_after_tokens`
    tokens are out (0 = mid-prefill).  Returns (streams, lat, srv)."""
    eng = ProgressEngine()
    epoch = NB.MembershipEpoch()
    srv = ServeEngine(cfg, params, eng, batch_slots=3, max_seq=48,
                      epoch=epoch, **kw)
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    t0 = time.monotonic()
    while sum(len(r.out_tokens) for r in reqs) < kill_after_tokens \
            and time.monotonic() - t0 < 180:
        eng.progress()
    if watchdog:
        clock = {"t": 0.0}
        wd = StepWatchdog(eng, limit=10.0, clock=lambda: clock["t"],
                          epoch=epoch)
        wd.arm()
        clock["t"] = 11.0
        eng.progress()                       # fires -> epoch invalidated
        assert wd.fired == 1
    else:
        epoch.invalidate(survivors=1, reason="chaos: simulated device loss")
    srv.run_until_idle(timeout=300)
    lat = srv.latency_snapshot()
    streams = [list(r.out_tokens) for r in reqs]
    remeshes = srv.remeshes
    srv.close(timeout=60)
    return streams, lat, remeshes


@pytest.mark.slow
class TestChaosServe:
    def test_kill_mid_decode_slots(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, cfg.vocab_size - 1,
                               size=rng.randint(2, 8)).astype(np.int32)
                   for _ in range(6)]
        ref, _ = _serve_streams(cfg, params, prompts, 8, batch_slots=3,
                                max_seq=48)
        got, lat, remeshes = _chaos_serve(cfg, params, prompts, 8,
                                          kill_after_tokens=4)
        assert got == ref                       # replay is bit-exact
        assert lat.completed == 6 and lat.failed == 0
        assert remeshes == 1

    def test_kill_mid_decode_paged_with_kv_migration(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(4)
        prompts = [rng.randint(1, cfg.vocab_size - 1,
                               size=rng.randint(4, 12)).astype(np.int32)
                   for _ in range(8)]
        kw = dict(cache_mode="paged", kv_block_size=4)
        ref, _ = _serve_streams(cfg, params, prompts, 8, batch_slots=3,
                                max_seq=48, **kw)
        got, lat, remeshes = _chaos_serve(cfg, params, prompts, 8,
                                          kill_after_tokens=5, **kw)
        assert got == ref
        assert lat.completed == 8 and lat.failed == 0
        assert remeshes == 1

    def test_kill_mid_prefill_paged(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, cfg.vocab_size - 1,
                               size=rng.randint(8, 16)).astype(np.int32)
                   for _ in range(6)]
        kw = dict(cache_mode="paged", kv_block_size=4, prefill_chunk=2)
        ref, _ = _serve_streams(cfg, params, prompts, 6, batch_slots=3,
                                max_seq=48, **kw)
        # kill before ANY token is out: prefills are in flight
        got, lat, remeshes = _chaos_serve(cfg, params, prompts, 6,
                                          kill_after_tokens=0, **kw)
        assert got == ref
        assert lat.completed == 6 and lat.failed == 0
        assert remeshes == 1

    def test_watchdog_fired_restart(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(6)
        prompts = [rng.randint(1, cfg.vocab_size - 1,
                               size=rng.randint(2, 8)).astype(np.int32)
                   for _ in range(4)]
        ref, _ = _serve_streams(cfg, params, prompts, 6, batch_slots=3,
                                max_seq=48)
        got, lat, remeshes = _chaos_serve(cfg, params, prompts, 6,
                                          kill_after_tokens=2,
                                          watchdog=True)
        assert got == ref
        assert lat.completed == 4 and lat.failed == 0
        assert remeshes == 1


@pytest.mark.slow
def test_chaos_kill_mid_gather_sharded():
    """Sharded decode on the user backend: killing a device mid-flight
    fails the persistent allgather retryably; the engine rebuilds on the
    single survivor (unsharded fallback) and streams stay exact."""
    out = run_with_devices("""
        import time
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core import ProgressEngine
        from repro.collectives import nonblocking as NB
        from repro.launch.mesh import make_mesh
        from repro.models import registry
        from repro.serve.engine import GenRequest, ServeEngine

        cfg = get_config("qwen2-0.5b").with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256,
            num_heads=4, num_kv_heads=2, head_dim=16,
            remat_policy="none", dtype="float32")
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(2)
        prompts = [rng.randint(1, cfg.vocab_size - 1,
                               size=rng.randint(2, 8)).astype(np.int32)
                   for _ in range(4)]

        def serve(epoch=None, kill_at=None):
            eng = ProgressEngine()
            mesh = make_mesh((2,), ("model",))
            srv = ServeEngine(cfg, params, eng, batch_slots=2, max_seq=32,
                              mesh=mesh, collective_backend="user",
                              epoch=epoch)
            reqs = [GenRequest(f"r{i}", p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            if kill_at is not None:
                t0 = time.monotonic()
                while sum(len(r.out_tokens) for r in reqs) < kill_at \\
                        and time.monotonic() - t0 < 180:
                    eng.progress()
                epoch.invalidate(survivors=1, reason="chaos")
            srv.run_until_idle(timeout=300)
            lat = srv.latency_snapshot()
            streams = [list(r.out_tokens) for r in reqs]
            rm = srv.remeshes
            srv.close(timeout=60)
            return streams, lat, rm

        ref, _, _ = serve()
        epoch = NB.MembershipEpoch()
        got, lat, remeshes = serve(epoch=epoch, kill_at=3)
        assert got == ref, (got, ref)
        assert lat.completed == 4 and lat.failed == 0
        assert remeshes == 1
        print("SHARDED_CHAOS_OK")
    """, n_devices=2)
    assert "SHARDED_CHAOS_OK" in out


@pytest.mark.slow
def test_train_chaos_trajectory_matches_restart_bitforbit():
    """Kill 2 of 4 devices mid-run: the elastic trainer remeshes and
    retries the failed step's batch, so the loss trajectory from the
    failure on is bit-identical to stopping, checkpointing, and
    restarting on the 2 survivors."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.configs import get_config
        from repro.core import ProgressEngine
        from repro.collectives.nonblocking import MembershipEpoch
        from repro.collectives.overlap import EngineGradReducer
        from repro.data.pipeline import SyntheticLM
        from repro.distributed import elastic
        from repro.models import registry
        from repro.train import optimizer as opt_mod
        from repro.train.train_loop import (Trainer, TrainLoopConfig,
                                            UserCollectiveStep)

        cfg = get_config("smollm-360m").with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256,
            num_heads=4, num_kv_heads=2, head_dim=16,
            remat_policy="none")
        STEPS, KILL = 10, 5
        src = SyntheticLM(cfg.vocab_size, 16, 8, seed=3)
        it = iter(src)
        batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
                   for _ in range(STEPS)]

        class ListPipe:
            def __init__(self, bs):
                self.bs = list(bs)
            def next_batch(self):
                return self.bs.pop(0)
            def close(self):
                pass

        ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=2,
                                   total_steps=STEPS)

        def local_grad(params, batch):
            (loss, mets), g = jax.value_and_grad(
                registry.loss_fn, has_aux=True)(params, cfg, batch)
            stacked = jax.tree.map(
                lambda v: v[None].astype(jnp.float32), g)
            return jax.tree.map(lambda v: v[None],
                                dict(mets, loss=loss)), stacked

        def make_grad_fn(mesh_):
            return jax.jit(compat.shard_map(
                local_grad, mesh=mesh_, in_specs=(P(), P("data")),
                out_specs=P("data")))

        @jax.jit
        def apply_fn(params, opt_state, grads, sm):
            params, opt_state, om = opt_mod.apply(ocfg, opt_state,
                                                  params, grads)
            mets = {k: jnp.mean(v) for k, v in sm.items()}
            return params, opt_state, dict(mets, **om)

        def loop_cfg(n, d):
            return TrainLoopConfig(
                total_steps=n, checkpoint_every=10**6,
                checkpoint_dir=f"/tmp/elastic_bitident/{d}",
                log_every=1, resume=False, collective_backend="user")

        def fresh_state():
            params = registry.init_params(cfg, jax.random.PRNGKey(0))
            return params, opt_mod.init(params)

        # --- elastic run: invalidate after step KILL-1 completes ------
        eng = ProgressEngine()
        mesh4 = elastic.remesh(4, prefer_model=1)
        epoch = MembershipEpoch()
        red = EngineGradReducer(mesh4, "data", engine=eng, chunks=2,
                                mean=True, epoch=epoch)
        split = UserCollectiveStep(make_grad_fn(mesh4), apply_fn, red)

        def remesh_fn(exc, params, opt_state):
            new_mesh = elastic.remesh(exc.survivors, prefer_model=1)
            red.remesh(new_mesh, "data")
            params = jax.device_put(params, NamedSharding(new_mesh, P()))
            opt_state = jax.device_put(opt_state,
                                       NamedSharding(new_mesh, P()))
            return (UserCollectiveStep(make_grad_fn(new_mesh), apply_fn,
                                       red), params, opt_state)

        losses, fired = [], []

        def hook(s, m):
            losses.append(m["loss"])
            if s == KILL - 1 and not fired:
                fired.append(s)
                epoch.invalidate(survivors=2, reason="chaos")

        params, opt_state = fresh_state()
        tr = Trainer(None, params, opt_state, ListPipe(batches),
                     loop_cfg(STEPS, "a"), engine=eng, split_step=split,
                     epoch=epoch, remesh_fn=remesh_fn, hooks=[hook])
        tr.run()
        red.close()
        assert tr.recoveries == 1, tr.recoveries
        assert len(losses) == STEPS

        # --- reference: run KILL steps on 4, restart rest on 2 --------
        ref = []
        engA = ProgressEngine()
        redA = EngineGradReducer(mesh4, "data", engine=engA, chunks=2,
                                 mean=True)
        splitA = UserCollectiveStep(make_grad_fn(mesh4), apply_fn, redA)
        params, opt_state = fresh_state()
        trA = Trainer(None, params, opt_state, ListPipe(batches[:KILL]),
                      loop_cfg(KILL, "b1"), engine=engA, split_step=splitA,
                      hooks=[lambda s, m: ref.append(m["loss"])])
        trA.run()
        redA.close()
        mesh2 = elastic.remesh(2, prefer_model=1)
        engB = ProgressEngine()
        redB = EngineGradReducer(mesh2, "data", engine=engB, chunks=2,
                                 mean=True)
        splitB = UserCollectiveStep(make_grad_fn(mesh2), apply_fn, redB)
        p2 = jax.device_put(trA.params, NamedSharding(mesh2, P()))
        o2 = jax.device_put(trA.opt_state, NamedSharding(mesh2, P()))
        trB = Trainer(None, p2, o2, ListPipe(batches[KILL:]),
                      loop_cfg(STEPS - KILL, "b2"), engine=engB,
                      split_step=splitB,
                      hooks=[lambda s, m: ref.append(m["loss"])])
        trB.run()
        redB.close()

        assert len(ref) == STEPS
        for i, (a, b) in enumerate(zip(losses, ref)):
            assert a == b, (i, a, b)       # bit-for-bit, incl. post-kill
        print("TRAIN_BITIDENT_OK")
    """, n_devices=4, timeout=600)
    assert "TRAIN_BITIDENT_OK" in out


# ---------------------------------------------------------------------------
# 2-D mesh FSDP membership: in-flight starts fail once, remesh replans
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_fsdp_invalidate_mid_reduce_scatter_2d_mesh():
    """On a (2,2) data x model mesh, invalidating the epoch while a
    persistent FSDP reduce-scatter is in flight fails that start exactly
    once with a retryable MembershipError; ``remesh`` onto the surviving
    (2,1) mesh replans the handles (fresh schedules for the new mesh,
    same data axis) and the reducer computes exact sums again."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.collectives import nonblocking as NB
        from repro.collectives.overlap import FsdpReducer
        from repro.core import ProgressEngine

        eng = ProgressEngine()
        epoch = NB.MembershipEpoch(n_devices=4)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        spec = NB.CollectiveSpec(backend="user", chunks=2)
        red = FsdpReducer(mesh, "data", engine=eng, spec=spec,
                          epoch=epoch)

        g = jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8)
        r = red.ireduce_scatter([g])
        assert not r.is_complete
        epoch.invalidate(survivors=2, reason="chaos")
        failed_after = red.coll.failed
        assert failed_after >= 1
        try:
            r.wait(timeout=30)
            raise AssertionError("expected MembershipError")
        except NB.MembershipError as e:
            assert e.survivors == 2 and e.version == 1
        # exactly once: a second invalidation does not double-fail
        epoch.invalidate(survivors=2)
        assert red.coll.failed == failed_after

        # survivors' mesh drops the model axis; the data axis (and so
        # the shard widths) survives, handles replan lazily
        mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                     ("data", "model"))
        red.remesh(mesh2)
        assert red.remeshes == 1 and red.axis_size == 2
        out = red.ireduce_scatter([g]).wait(timeout=60)
        ref = np.asarray(g[0] + g[1]).reshape(2, 4)
        assert np.array_equal(np.asarray(out[0]), ref), out
        sh = jnp.arange(2 * 4, dtype=jnp.int32).reshape(2, 4)
        full = red.gather([sh], timeout=60)
        assert np.array_equal(np.asarray(full[0]),
                              np.asarray(sh).reshape(1, 8).repeat(2, 0))
        red.close()
        print("FSDP_RS_EPOCH_OK")
    """, n_devices=4)
    assert "FSDP_RS_EPOCH_OK" in out


@pytest.mark.multidevice
def test_fsdp_invalidate_mid_prefetch_gather_2d_mesh():
    """The other in-flight shape: a continuation-chained prefetch
    all-gather killed mid-start on a (2,2) mesh fails exactly once and
    surfaces the MembershipError from FsdpGather.wait."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.collectives import nonblocking as NB
        from repro.collectives.overlap import FsdpReducer
        from repro.core import ProgressEngine

        eng = ProgressEngine()
        epoch = NB.MembershipEpoch(n_devices=4)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        red = FsdpReducer(mesh, "data", engine=eng,
                          spec=NB.CollectiveSpec(backend="user"),
                          epoch=epoch)
        sh = jnp.arange(2 * 4, dtype=jnp.int32).reshape(2, 4)
        gather = red.igather([sh])
        epoch.invalidate(survivors=2, reason="chaos")
        failed_after = red.coll.failed
        assert failed_after >= 1
        try:
            gather.wait(timeout=30)
            raise AssertionError("expected MembershipError")
        except NB.MembershipError as e:
            assert e.survivors == 2
        epoch.invalidate(survivors=2)
        assert red.coll.failed == failed_after     # no double-fail
        red.close()
        print("FSDP_AG_EPOCH_OK")
    """, n_devices=4)
    assert "FSDP_AG_EPOCH_OK" in out
