"""Continuous batching on the paged KV cache.

Correctness story, in three tiers:

* model level — ``decode_step_paged`` is BIT-identical to the monolithic
  ``decode_step`` for every family (the paged gather view reduces over
  the same positions once the causal mask zeroes the rest);
* engine level — the ``ServeEngine`` (chunked prefill interleaved with
  decode, admission from a length-bucketed backlog, preemption under
  block pressure) produces token streams invariant to the pool shape: a
  deliberately tight pool matches a roomy preemption-free one, because
  greedy decode is per-lane deterministic and replay rebuilds exactly
  the prompt + generated prefix;
* trace level (slow) — a Poisson arrival trace with hundreds of mixed
  length requests through a deliberately tight block pool: every request
  completes, streams match the roomy-pool reference, preemptions stay
  bounded, and the backlog drains exactly when blocks free.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProgressEngine
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine, _BucketBacklog
from conftest import reduce_cfg
from tests._multidevice import run_with_devices


@pytest.fixture(scope="module")
def tiny():
    cfg = reduce_cfg(get_config("qwen2-0.5b"), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mixed_prompts(n, vocab, lo=2, hi=12, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab - 1,
                        size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _serve(cfg, params, prompts, max_new, *, batch_slots=4, max_seq=32,
           submit_gap=None, **kw):
    eng = ProgressEngine()
    srv = ServeEngine(cfg, params, eng, batch_slots=batch_slots,
                      max_seq=max_seq, **kw)
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    if submit_gap is None:
        for r in reqs:
            srv.submit(r)
    else:
        t0 = time.perf_counter()
        due = 0.0
        for i, r in enumerate(reqs):
            due += submit_gap[i]
            while time.perf_counter() - t0 < due:
                eng.progress()
            srv.submit(r)
    srv.run_until_idle(timeout=300)
    lat = srv.latency_snapshot()
    sched = srv.scheduler_snapshot()
    srv.close(timeout=60)
    return [list(r.out_tokens) for r in reqs], lat, sched, reqs


# ---------------------------------------------------------------------------
# Model level: paged decode == monolithic decode, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "arch,kv_dtype",
    [("qwen2-0.5b", "bf16"), ("qwen2-0.5b", "int8"),
     ("mamba2-1.3b", "bf16"), ("zamba2-1.2b", "bf16")],
    ids=["qwen2-0.5b", "qwen2-0.5b-int8kv", "mamba2-1.3b", "zamba2-1.2b"])
def test_paged_decode_matches_monolithic_decode(arch, kv_dtype):
    cfg = reduce_cfg(get_config(arch), dtype="float32",
                     kv_cache_dtype=kv_dtype)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B, S, bs = 3, 16, 4
    max_blocks = S // bs
    cache = registry.init_cache(cfg, B, S)
    pcache = registry.init_paged_cache(cfg, B, 1 + B * max_blocks, bs)
    tables = np.zeros((B, max_blocks), np.int32)
    for i in range(B):
        tables[i] = 1 + i * max_blocks + np.arange(max_blocks)
    tables = jnp.asarray(tables)
    fed = jnp.ones((B,), bool)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0,
                              cfg.vocab_size)
    pos = jnp.zeros((B,), jnp.int32)
    for t in range(8):
        cur = toks[:, t:t + 1]
        lg, cache = registry.decode_step(params, cfg, cache, cur, pos)
        lgp, pcache = registry.decode_step_paged(params, cfg, pcache, cur,
                                                 pos, tables, fed)
        assert float(jnp.max(jnp.abs(lg - lgp))) == 0.0, (arch, t)
        pos = pos + 1


def test_fed_mask_freezes_ssm_state():
    """A fused paged call must not advance the recurrent state of lanes
    it did not feed — the prerequisite for interleaving one lane's
    prefill with another's decode in SSM/hybrid families."""
    cfg = reduce_cfg(get_config("mamba2-1.3b"), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B = 2
    cache = registry.init_paged_cache(cfg, B, 2, 4)
    tables = jnp.zeros((B, 4), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    toks = jnp.asarray([[7], [9]], jnp.int32)
    # feed only lane 0; lane 1 sees a garbage token
    fed = jnp.asarray([True, False])
    _, new_cache = registry.decode_step_paged(params, cfg, cache, toks,
                                              pos, tables, fed)
    for old, new in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(new_cache)):
        # lane 1 state frozen exactly; lane 0 advanced
        assert float(jnp.max(jnp.abs(new[:, 1] - old[:, 1]))) == 0.0
        assert float(jnp.max(jnp.abs(new[:, 0] - old[:, 0]))) > 0.0


def _random_tree(tree, key):
    """Every leaf of ``tree`` filled with random values of its dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for leaf, k in zip(leaves, jax.random.split(key, len(leaves))):
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128,
                                          jnp.int32).astype(jnp.int8))
        else:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.mark.parametrize(
    "arch,kv_dtype",
    [("qwen2-0.5b", "bf16"), ("qwen2-0.5b", "int8"),
     ("mamba2-1.3b", "bf16"), ("zamba2-1.2b", "bf16")],
    ids=["qwen2-0.5b", "qwen2-0.5b-int8kv", "mamba2-1.3b", "zamba2-1.2b"])
def test_paged_chunk_matches_one_token_calls(arch, kv_dtype):
    """One [B, C] paged call equals C one-token calls: the logits of each
    lane's last fed column and the pool at the fed positions agree (to
    float32 rounding where attention batches the chunk's rows, exactly
    where a family scans its columns), and nothing a lane is not fed is
    written: a lane fed nothing keeps its blocks and state bit for bit,
    and a lane near capacity does not clamp its unfed columns into its
    last live block."""
    cfg = reduce_cfg(get_config(arch), dtype="float32",
                     kv_cache_dtype=kv_dtype)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    B, C, bs, max_blocks = 5, 4, 4, 4               # lane capacity 16
    num_blocks = 1 + B * max_blocks
    tables = jnp.asarray(1 + np.arange(B * max_blocks).reshape(
        B, max_blocks), jnp.int32)
    cache = _random_tree(registry.init_paged_cache(cfg, B, num_blocks, bs),
                         jax.random.PRNGKey(1))
    pos = np.array([3, 5, 2, 6, 14], np.int32)      # lane 4: 2 of 4 fit
    counts = np.array([0, 1, 3, C, 2], np.int32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, C), 0,
                              cfg.vocab_size)
    # both sides jitted, as the engine serves them
    step = jax.jit(lambda c, t, p, f: registry.decode_step_paged(
        params, cfg, c, t, p, tables, f))
    lg, chunk = step(cache, toks, jnp.asarray(pos), jnp.asarray(counts))
    assert lg.shape == (B, 1, cfg.vocab_size)
    ref, ref_lg = cache, np.zeros(lg.shape, np.float32)
    for t in range(C):
        out, ref = step(ref, toks[:, t:t + 1], jnp.asarray(pos + t),
                        jnp.asarray(counts > t))
        last = counts - 1 == t
        ref_lg[last] = np.asarray(out)[last]
    exact = not registry.paged_has_blocks(cfg) or cfg.family == "hybrid"
    fed_lanes = counts > 0
    gap = np.abs(np.asarray(lg)[fed_lanes] - ref_lg[fed_lanes]).max()
    if exact:
        assert gap == 0.0, gap
    else:
        assert gap <= 1e-4 * np.abs(ref_lg).max(), gap
    # the (block, offset) of every fed position
    written = np.zeros((num_blocks, bs), bool)
    for b in range(B):
        for p in range(pos[b], pos[b] + counts[b]):
            written[int(tables[b, p // bs]), p % bs] = True
    written[0] = True                               # scratch block
    for path, c0 in jax.tree_util.tree_flatten_with_path(cache)[0]:
        key = jax.tree_util.keystr(path)
        c0 = np.asarray(c0)
        got = np.asarray(_leaf(chunk, path))
        want = np.asarray(_leaf(ref, path))
        if c0.ndim >= 3 and c0.shape[1:3] == (num_blocks, bs):
            # pooled: untouched where nothing was fed, bit for bit
            assert (got[:, ~written] == c0[:, ~written]).all(), key
            live = written.copy()
            live[0] = False
            if exact:
                assert (got[:, live] == want[:, live]).all(), key
            elif c0.dtype == np.int8:               # a rounding step apart
                diff = got[:, live].astype(int) - want[:, live]
                assert np.abs(diff).max() <= 1, key
            else:
                np.testing.assert_allclose(got[:, live], want[:, live],
                                           rtol=1e-4, atol=1e-4 *
                                           np.abs(want[:, live]).max(),
                                           err_msg=key)
        else:
            # lane-indexed recurrent state: the unfed lane is frozen
            assert (got[:, 0] == c0[:, 0]).all(), key
            assert (got == want).all(), key


def _leaf(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree


# ---------------------------------------------------------------------------
# Engine level: token streams are invariant to the pool shape
# ---------------------------------------------------------------------------

class TestPagedEngineEquivalence:
    def test_streams_match_roomy_pool(self, tiny):
        """Default (roomy, preemption-free) pool vs small blocks: the
        same streams, because block granularity is invisible to greedy
        decode."""
        cfg, params = tiny
        prompts = _mixed_prompts(10, cfg.vocab_size)
        ref, _, _, _ = _serve(cfg, params, prompts, 5)
        got, lat, sched, _ = _serve(cfg, params, prompts, 5,
                                    kv_block_size=8)
        assert got == ref
        assert lat.completed == 10 and lat.failed == 0
        assert sched.admitted >= 10 and sched.prefill_calls > 0

    def test_streams_match_under_preemption(self, tiny):
        """A pool too small for the working set forces evictions; replay
        rebuilds prompt + generated prefix, so streams are unchanged and
        preemption is invisible in the output."""
        cfg, params = tiny
        prompts = _mixed_prompts(12, cfg.vocab_size)
        ref, _, _, _ = _serve(cfg, params, prompts, 12)
        got, lat, sched, reqs = _serve(
            cfg, params, prompts, 12,
            kv_block_size=4, kv_blocks=11, prefill_chunk=4)
        assert got == ref
        assert lat.completed == 12 and lat.failed == 0
        assert sched.preemptions > 0          # pressure actually happened
        assert lat.preempted > 0
        # bounded: the oldest-resident-protected policy cannot thrash —
        # each eviction re-queues a request younger than some survivor
        assert sched.preemptions < 12 * 12
        assert all(r.preemptions < 12 for r in reqs)

    def test_wide_lanes_beat_lane_cap_at_equal_bytes(self, tiny):
        """The continuous-batching claim in miniature: on a pool worth
        2 lanes x 32 positions (16 blocks of 4), opening 8 lanes
        sustains more than 2 residents — block granularity means short
        requests stop paying max_seq."""
        cfg, params = tiny
        prompts = _mixed_prompts(16, cfg.vocab_size, lo=2, hi=8)
        got, lat, sched, _ = _serve(
            cfg, params, prompts, 4, batch_slots=8,
            kv_block_size=4, kv_blocks=17)
        assert lat.completed == 16 and lat.failed == 0
        assert sched.peak_resident > 2

    def test_queue_time_reported(self, tiny):
        cfg, params = tiny
        prompts = _mixed_prompts(8, cfg.vocab_size)
        _, lat, _, _ = _serve(cfg, params, prompts, 4, batch_slots=2,
                              kv_block_size=8)
        # 8 requests through 2 lanes: later arrivals waited measurably
        assert lat.queued_ms_mean is not None
        assert lat.queued_ms_p99 >= lat.queued_ms_p50 >= 0.0


@pytest.mark.parametrize("lengths,chunk", [
    ([9], 4), ([2, 5, 12], 4), ([17], 8), ([1, 1], 8), ([6, 3], 1)])
def test_prompt_prefills_in_one_call_per_chunk(tiny, lengths, chunk):
    """Prompts admitted together are prefilled in ceil((L-1)/C) calls
    for the longest L, each call feeding every mid-prefill lane up to C
    positions; ``prefill_tokens`` counts every prompt position but the
    last (the first decode step's input), and the streams are those of
    prefill one position per call."""
    cfg, params = tiny
    rng = np.random.RandomState(len(lengths) * 100 + chunk)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=n).astype(np.int32)
               for n in lengths]
    got, lat, sched, _ = _serve(cfg, params, prompts, 3,
                                prefill_chunk=chunk)
    assert lat.completed == len(prompts) and sched.preemptions == 0
    assert sched.prefill_calls == -(-(max(lengths) - 1) // chunk)
    assert sched.prefill_tokens == sum(n - 1 for n in lengths)
    ref, _, _, _ = _serve(cfg, params, prompts, 3, prefill_chunk=1)
    assert got == ref


class TestBacklogAndBlocks:
    def test_backlog_drains_exactly_when_blocks_free(self, tiny):
        """A request that does not fit the free pool stays backlogged —
        and is admitted on the step where a resident releases enough
        blocks, not before, not never."""
        cfg, params = tiny
        eng = ProgressEngine()
        srv = ServeEngine(cfg, params, eng, batch_slots=2, max_seq=32,
                          kv_block_size=4,
                          kv_blocks=9)           # 8 usable = one max_seq
        # resident consumes 6 of 8 blocks (prompt 21 -> ceil(21/4) = 6)
        big = GenRequest("big", np.arange(1, 22, dtype=np.int32),
                         max_new_tokens=2)
        srv.submit(big)
        srv.run_until_idle(timeout=120)
        assert len(big.out_tokens) == 2
        # now occupy 6 blocks with a long-runner, then submit one that
        # needs 3: it must wait in the backlog
        r1 = GenRequest("r1", np.arange(1, 22, dtype=np.int32),
                        max_new_tokens=8)
        d1 = srv.submit(r1)
        r2 = GenRequest("r2", np.arange(1, 10, dtype=np.int32),
                        max_new_tokens=2)
        d2 = srv.submit(r2)
        t0 = time.monotonic()
        while not d2.is_complete:
            eng.progress()
            assert time.monotonic() - t0 < 120
        # r2 could only have been admitted after r1 finished and freed
        # its blocks (6 + 3 > 8): its queue time spans r1's decode
        assert d1.is_complete
        assert r2.queued_s > 0
        srv.run_until_idle(timeout=60)
        assert srv.slots.allocator.free_count == 8   # all returned
        srv.close(timeout=60)

    def test_oldest_resident_never_preempted(self, tiny):
        cfg, params = tiny
        prompts = _mixed_prompts(10, cfg.vocab_size, lo=6, hi=12, seed=3)
        _, lat, sched, reqs = _serve(
            cfg, params, prompts, 10, batch_slots=4,
            kv_block_size=4, kv_blocks=11, prefill_chunk=4)
        assert lat.completed == 10
        assert sched.preemptions > 0
        # request 0 is the oldest from submission to completion: the
        # policy protects it for its whole residency
        assert reqs[0].preemptions == 0

    def test_bucket_backlog_orders_by_seq_and_length(self):
        bb = _BucketBacklog()

        def req(seq, n):
            r = GenRequest(f"q{seq}", np.arange(n, dtype=np.int32))
            r.seq = seq
            r.replay = r.prompt
            return r

        bb.push(req(3, 4))
        bb.push(req(1, 5))       # same bucket (len 4..7): ahead of seq 3
        bb.push(req(2, 40))      # different bucket
        assert len(bb) == 3
        # fits-everything: oldest bucket first, FIFO within
        popped = []
        while len(bb):
            r, lane = bb.pop_fitting(lambda r: "lane")
            popped.append(r.seq)
        assert popped == [1, 2, 3]
        # head-of-line bypass: bucket heads that do not fit are skipped
        bb.push(req(1, 40))
        bb.push(req(2, 4))
        r, _ = bb.pop_fitting(
            lambda r: "lane" if len(r.replay) < 10 else None)
        assert r.seq == 2


# ---------------------------------------------------------------------------
# Chaos: failures under the paged engine leak nothing
# ---------------------------------------------------------------------------

class TestPagedChaos:
    def _engine(self, tiny, **kw):
        cfg, params = tiny
        eng = ProgressEngine()
        srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=32,
                          kv_block_size=4, **kw)
        return srv, eng

    def test_prefill_chunk_failure_frees_blocks(self, tiny):
        """Kill the fused call of the second prefill chunk: every
        mid-prefill request is failed exactly once, all blocks and lanes
        return to the free lists, and the engine still serves
        afterwards."""
        srv, eng = self._engine(tiny)
        usable = srv.slots.allocator.usable_blocks
        real = srv._jit_decode
        calls = {"n": 0}

        def boom(*a):
            calls["n"] += 1
            if calls["n"] >= 2:                  # second chunk, not first
                raise RuntimeError("prefill chunk boom")
            return real(*a)

        srv._jit_decode = boom
        # 10 prefill positions each: two chunks of prefill_chunk = 8
        reqs = [GenRequest(f"c{i}", np.arange(1, 12, dtype=np.int32),
                           max_new_tokens=2) for i in range(3)]
        dones = [srv.submit(r) for r in reqs]
        t0 = time.monotonic()
        while not all(d.is_complete for d in dones):
            eng.progress()
            assert time.monotonic() - t0 < 60
        assert all(d.failed for d in dones)
        # failed exactly once: one terminal transition per request
        snap = srv.latency_snapshot()
        assert snap.failed == 3 and snap.completed == 0
        assert snap.no_first_token == 3
        assert snap.ttft_ms_mean is None         # null-propagated
        assert srv.slots.allocator.free_count == usable
        assert srv.slots.free_count == 4
        assert not srv.slots.allocator.owners()
        srv._jit_decode = real
        ok = srv.submit(GenRequest("ok", np.array([1, 2], np.int32),
                                   max_new_tokens=2))
        srv.run_until_idle(timeout=60)
        assert ok.is_complete and len(ok.value()) == 2
        srv.close(timeout=60)

    def test_decode_dispatch_failure_frees_blocks(self, tiny):
        """Kill the decode dispatch: the step's failure continuation
        fails every decoding request once and releases lanes + blocks;
        TTFT stays null for requests that never produced a token."""
        srv, eng = self._engine(tiny)
        usable = srv.slots.allocator.usable_blocks
        real = srv._jit_decode
        state = {"armed": False}

        def boom(*a):
            # arm after prefill: single-token prompts skip prefill, so
            # the first call IS the decode dispatch
            if state["armed"]:
                raise RuntimeError("decode dispatch boom")
            return real(*a)

        srv._jit_decode = boom
        state["armed"] = True
        reqs = [GenRequest(f"d{i}", np.array([i + 1], np.int32),
                           max_new_tokens=4) for i in range(2)]
        dones = [srv.submit(r) for r in reqs]
        t0 = time.monotonic()
        while not all(d.is_complete for d in dones):
            eng.progress()
            assert time.monotonic() - t0 < 60
        assert all(d.failed for d in dones)
        snap = srv.latency_snapshot()
        assert snap.failed == 2
        assert snap.no_first_token == 2 and snap.ttft_ms_mean is None
        assert srv.slots.allocator.free_count == usable
        assert srv.slots.free_count == 4
        state["armed"] = False
        srv._jit_decode = real
        ok = srv.submit(GenRequest("ok", np.array([3], np.int32),
                                   max_new_tokens=2))
        srv.run_until_idle(timeout=60)
        assert ok.is_complete and len(ok.value()) == 2
        srv.close(timeout=60)

    def test_step_harvest_failure_frees_blocks(self, tiny):
        """A step killed AFTER dispatch (async device error surfacing at
        materialisation) takes the same failure path: no leaked blocks,
        TTFT null-propagated for tokenless requests."""
        srv, eng = self._engine(tiny)
        usable = srv.slots.allocator.usable_blocks
        real = srv._next_ids
        srv._next_ids = lambda logits: (_ for _ in ()).throw(
            RuntimeError("harvest boom"))
        r = GenRequest("h", np.array([5], np.int32), max_new_tokens=4)
        done = srv.submit(r)
        t0 = time.monotonic()
        while not done.is_complete:
            eng.progress()
            assert time.monotonic() - t0 < 60
        assert done.failed and "harvest boom" in str(done.exception)
        assert r.first_token_at is None
        assert srv.slots.allocator.free_count == usable
        srv._next_ids = real
        srv.close(timeout=60)


# ---------------------------------------------------------------------------
# Trace level (slow): Poisson arrival stress harness
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_arrival_trace_stress(tiny):
    """Hundreds of mixed-length requests through a tight paged pool:
    every request completes, token streams are bit-identical to the
    roomy preemption-free pool on the same trace, preemptions happen
    and stay bounded, and nothing leaks."""
    cfg, params = tiny
    N = 500
    rng = np.random.RandomState(42)
    prompts = [rng.randint(1, cfg.vocab_size - 1,
                           size=rng.randint(1, 20)).astype(np.int32)
               for _ in range(N)]
    gaps = rng.exponential(0.001, size=N)     # ~1k req/s offered
    ref, ref_lat, _, _ = _serve(cfg, params, prompts, 4, batch_slots=8,
                                max_seq=32, submit_gap=list(gaps))
    assert ref_lat.completed == N
    got, lat, sched, reqs = _serve(
        cfg, params, prompts, 4, batch_slots=8, max_seq=32,
        kv_block_size=4, kv_blocks=25,
        prefill_chunk=4, submit_gap=list(gaps))
    assert got == ref
    assert lat.completed == N and lat.failed == 0
    assert sched.preemptions > 0              # the pool was actually tight
    assert sched.preemptions < 4 * N          # bounded, no thrash
    assert max(r.preemptions for r in reqs) < 20
    assert lat.queued_ms_p99 is not None


@pytest.mark.slow
@pytest.mark.multidevice
@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_arrival_trace_sharded(n_devices):
    """The paged scheduler under model-axis-sharded decode: same trace,
    tight pool streams identical to the roomy sharded engine."""
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
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, 63, size=rng.randint(1, 10)).astype(np.int32)
                   for _ in range(40)]

        def serve(**kw):
            eng = ProgressEngine()
            srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=32,
                              mesh=mesh, **kw)
            reqs = [GenRequest(f'r{{i}}', p, max_new_tokens=4)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            srv.run_until_idle(timeout=300)
            lat = srv.latency_snapshot()
            srv.close(timeout=60)
            return [list(r.out_tokens) for r in reqs], lat

        ref, _ = serve()
        got, lat = serve(kv_block_size=4, kv_blocks=17,
                         prefill_chunk=4)
        assert got == ref, 'tight sharded pool diverged from roomy'
        assert lat.completed == 40 and lat.failed == 0
        print('PAGED_SHARDED_TRACE_OK')
    """, n_devices=n_devices)
    assert "PAGED_SHARDED_TRACE_OK" in out


@pytest.mark.slow
def test_trace_ssm_concurrency_consistent():
    """SSM/hybrid families: concurrent continuous batching produces the
    same streams as serial (one-resident-at-a-time) service — the fed
    mask and lane reset isolate recurrent state across interleavings.
    (This is why the retired fixed-slot engine could not serve as a
    reference: its prefill leaked garbage tokens into other lanes'
    SSM states by construction.)"""
    for arch in ("mamba2-1.3b", "zamba2-1.2b"):
        cfg = reduce_cfg(get_config(arch), dtype="float32")
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        prompts = _mixed_prompts(6, cfg.vocab_size, seed=5)
        kw = dict(kv_block_size=8)
        serial = []
        eng = ProgressEngine()
        srv = ServeEngine(cfg, params, eng, batch_slots=4, max_seq=32, **kw)
        for i, p in enumerate(prompts):       # one resident at a time
            r = GenRequest(f"s{i}", p, max_new_tokens=4)
            srv.submit(r)
            srv.run_until_idle(timeout=120)
            serial.append(list(r.out_tokens))
        srv.close(timeout=60)
        got, lat, _, _ = _serve(cfg, params, prompts, 4, batch_slots=4,
                                max_seq=32, **kw)
        assert got == serial, arch
        assert lat.completed == 6
