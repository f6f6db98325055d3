"""Event-driven continuous-batching server on the progress engine.

Serving is the dynamic side of the paper's story: requests arrive at
arbitrary times (the "unexpected message queue" of MPI has no SPMD
analogue — this layer is it).  Since the continuation layer
(repro.core.continuations) landed, the whole request lifecycle is
completion-driven — there is no polling loop anywhere in this file:

* ``submit``            — the *arrival event* schedules a one-shot
  admission task on the admit stream (none is scheduled while idle);
* admission / prefill   — admits arrivals into free paged-KV lanes
  (blocks + lane claimed atomically) and runs one chunk of batched
  prefill, then schedules the first decode step;
* decode                — one fused decode step for ALL active slots
  (continuous batching) is dispatched and its device completion watched
  by a one-shot readiness task (``Array.is_ready``, never blocked on)
  that completes a per-step ``Request``;
* detokenize            — a continuation attached to the step request:
  extracts tokens, finishes requests (their ``done_req`` completes,
  firing any client continuations), and *chains the next decode step* —
  each stage's completion schedules the next;
* slot-free event       — finishing requests re-schedules admission, so
  a backlog drains exactly when capacity appears.

Between requests every serve stream is empty: no perpetual task spins,
no idle polling — the paper's event-driven integration claim (§4.6).

The continuation execution policy is a knob (``continuation_policy``):
``INLINE`` runs detokenize on the progress thread that observed decode
completion; ``DEFERRED`` (default) queues it and the owner drains with
``continuation_max_drain`` as bounded backpressure.  With a
``ProgressExecutor`` the serve streams are adopted by its workers and a
deferred queue is drained by them between polls; without one, a cheap
subsystem bridges streams + continuation drain into every
``engine.progress()`` call, so the classic ``while: engine.progress()``
loop still serves traffic.

**Model-axis sharding + serve-side collectives.**  With a ``mesh`` the
decode step is tensor-parallel on the output projection: one shared
``shard_map`` program runs ``decode_hidden`` and each model-axis rank's
vocab-slice unembed (``unembed_partial``), producing the per-rank
partial logits ``[n, B, V/n]`` plus the updated cache.  The full logits
are then the rank-order all-gather of that activation, two ways:

* ``collective_backend="native"`` — a second jitted ``shard_map``
  program with an in-program ``lax.all_gather`` (the GSPMD baseline);
* ``collective_backend="user"``  — a **persistent user-space
  all-gather** (``allgather_init``/``start``) on a dedicated
  serve-collective stream.  Decode's shapes are fixed, so the plan and
  fused round programs compile exactly once at engine construction;
  every step is a ``start(partial)`` re-bind whose completion feeds the
  existing detokenize continuation.  The gather rounds are driven by
  the progress engine while the host stays free for admission/prefill
  of concurrent arrivals — and with an executor the ``start`` itself is
  executor-driven (the worker owning the collective stream dispatches
  round 0, the decode chain pays an enqueue).

Both sharded paths consume bit-identical partial logits from the same
program, so their greedy token streams are identical — the serve-side
analogue of the fig-14 user-vs-native comparison.
"""
from __future__ import annotations

import collections
import dataclasses
import statistics
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import DEFERRED, DONE, NOPROGRESS, ProgressEngine, Request
from repro.core import debug
from repro.core.continuations import POLICIES, ContinuationQueue
from repro.core.executor import ProgressExecutor
from repro.core.stats import SchedulerStats
from repro.collectives.nonblocking import (CollectiveSpec,
                                           MembershipError,
                                           spec_from_legacy)
from repro.models import registry
from repro.serve.kvcache import PagedKVCache


@dataclasses.dataclass
class GenRequest:
    request_id: str
    prompt: np.ndarray               # [prompt_len] int32
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done_req: Request = dataclasses.field(default_factory=Request)
    slot_index: int = -1
    next_input: int = 0            # next token to feed the fused decode
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    # stamped exactly once, by the detokenize continuation of the first
    # decode step that produced a token for this request; stays None for
    # requests that fail before their first token (TTFT must not count
    # them — see ServeLatencyStats.no_first_token)
    first_token_at: float | None = None
    finished_at: float | None = None
    # -- continuous-batching bookkeeping (paged cache mode) ----------------
    # replay = prompt + generated prefix: what prefill must rebuild in the
    # KV cache.  Set at first admission; recomputed at preemption so a
    # re-admitted request resumes its exact token stream (greedy decode is
    # per-lane deterministic — same replay ⇒ same continuation).
    replay: Optional[np.ndarray] = None
    prefill_pos: int = 0           # replay tokens already fed this residency
    preemptions: int = 0           # times evicted under block pressure
    seq: int = 0                   # submit order; the scheduler never
    #                                preempts the oldest resident
    queued_s: float = 0.0          # total backlog wait across (re)admissions
    last_enqueued_at: float = 0.0
    # membership change: host-side snapshot of the lane's KV prefix +
    # per-lane state (PagedKVCache.checkpoint_lane), carried through the
    # backlog so re-admission on the rebuilt mesh restores instead of
    # replaying the whole prefix; None = replay from tokens
    kv_ckpt: Optional[dict] = None


def _serve_program(cfg):
    """The unsharded serving program: one fused paged call that prefill
    (tokens ``[B, prefill_chunk]``) and decode (``[B, 1]``) both
    dispatch.  Jitted from a named function, so that the device trace
    names both shapes' modules ``jit_serve_call``.  ``cache`` is the
    block pool, leaves ``[layers, num_blocks, block_size, KVH*hd]``
    (``serve/kvcache.py``); it is not donated, so the pre-call pool
    stays valid for a failed step or prefill chunk."""
    def serve_call(params, cache, tokens, pos, tables, fed):
        return registry.decode_step_paged(params, cfg, cache, tokens, pos,
                                          tables, fed)
    return jax.jit(serve_call)


class _BucketBacklog:
    """Length-bucketed FIFO backlog (power-of-two length buckets).

    Admission drains buckets in order of their oldest member, so requests
    of similar length are admitted together (their prefills retire
    together and lanes churn less — the classic bucket-by-length batching
    idiom), while one bucket's over-long head cannot starve the others:
    ``pop_fitting`` falls through to the next bucket when a head does not
    fit the free pool.  Within a bucket order is by submit ``seq``, so a
    preempted request re-enters ahead of younger arrivals and is retried
    first once blocks free up.
    """

    def __init__(self):
        self._buckets: dict[int, collections.deque] = {}

    @staticmethod
    def bucket_of(length: int) -> int:
        return max(1, int(length)).bit_length()

    def push(self, req: GenRequest) -> None:
        dq = self._buckets.setdefault(self.bucket_of(len(req.replay)),
                                      collections.deque())
        if not dq or req.seq >= dq[-1].seq:
            dq.append(req)
        elif req.seq <= dq[0].seq:
            dq.appendleft(req)
        else:                       # rare: mid-deque re-admission
            items = sorted([*dq, req], key=lambda r: r.seq)
            dq.clear()
            dq.extend(items)

    def pop_fitting(self, fits):
        """First (oldest-bucket-first) request for which ``fits(req)``
        returns a lane; ``(None, None)`` when nothing fits."""
        order = sorted((dq for dq in self._buckets.values() if dq),
                       key=lambda dq: dq[0].seq)
        for dq in order:
            lane = fits(dq[0])
            if lane is not None:
                return dq.popleft(), lane
        return None, None

    def drain(self) -> list:
        out = []
        for dq in self._buckets.values():
            out.extend(dq)
            dq.clear()
        out.sort(key=lambda r: r.seq)
        return out

    def __len__(self) -> int:
        return sum(len(dq) for dq in self._buckets.values())


def _quantiles(samples_ms: list[float]) -> tuple[float, float, float]:
    mean = statistics.fmean(samples_ms)
    s = sorted(samples_ms)
    p50 = s[len(s) // 2]
    p99 = s[min(int(0.99 * len(s)), len(s) - 1)]
    return mean, p50, p99


@dataclasses.dataclass
class ServeLatencyStats:
    """Request-latency snapshot (``ServeEngine.latency_snapshot``).

    TTFT aggregates cover only requests that produced a first token;
    ``no_first_token`` counts the ones that finished (failed) without —
    they are excluded from TTFT rather than silently dropped from the
    ledger.  Latency aggregates cover every finished request.  Queue-time
    aggregates cover time spent waiting in the backlog (summed across
    re-admissions for preempted requests); ``preempted``/``preemptions``
    count requests evicted under block pressure and total evictions."""
    submitted: int = 0
    completed: int = 0
    failed: int = 0
    no_first_token: int = 0          # finished without a first token
    preempted: int = 0               # finished requests evicted >= once
    preemptions: int = 0             # total evictions over those requests
    ttft_ms_mean: float | None = None
    ttft_ms_p50: float | None = None
    ttft_ms_p99: float | None = None
    latency_ms_mean: float | None = None
    latency_ms_p50: float | None = None
    latency_ms_p99: float | None = None
    queued_ms_mean: float | None = None
    queued_ms_p50: float | None = None
    queued_ms_p99: float | None = None

    def format(self) -> str:
        def f(v):
            return f"{v:.1f}" if v is not None else "n/a"
        return (f"requests: {self.submitted} submitted, "
                f"{self.completed} completed, {self.failed} failed "
                f"({self.no_first_token} without first token, "
                f"{self.preempted} preempted {self.preemptions}x); "
                f"TTFT ms mean/p50/p99 {f(self.ttft_ms_mean)}/"
                f"{f(self.ttft_ms_p50)}/{f(self.ttft_ms_p99)}; "
                f"latency ms mean/p50/p99 {f(self.latency_ms_mean)}/"
                f"{f(self.latency_ms_p50)}/{f(self.latency_ms_p99)}; "
                f"queued ms mean/p50/p99 {f(self.queued_ms_mean)}/"
                f"{f(self.queued_ms_p50)}/{f(self.queued_ms_p99)}")


class ServeEngine:
    def __init__(self, cfg, params, engine: ProgressEngine,
                 batch_slots: int = 8, max_seq: int = 512,
                 greedy: bool = True,
                 executor: Optional[ProgressExecutor] = None,
                 continuation_policy: str = DEFERRED,
                 continuation_max_drain: int = 64,
                 mesh=None, model_axis: str = "model",
                 collective_spec: CollectiveSpec | None = None,
                 collective_backend: str | None = None,
                 collective_chunks: int | None = None,
                 collective_round_batch: int | None = None,
                 cache_mode: str = "paged",
                 kv_block_size: int = 16,
                 kv_blocks: int | None = None,
                 prefill_chunk: int = 8,
                 epoch=None):
        if continuation_policy not in POLICIES:
            raise ValueError(f"continuation_policy must be one of {POLICIES}")
        spec = spec_from_legacy(collective_spec, surface="ServeEngine",
                                backend=collective_backend,
                                chunks=collective_chunks,
                                round_batch=collective_round_batch)
        if spec.user and mesh is None:
            # silently serving the plain native path while the operator
            # believes they exercised user-space collectives is worse
            # than an eager error
            raise ValueError("collective backend 'user' requires a mesh "
                             "(model-axis-sharded decode)")
        if cache_mode == "slots":
            raise ValueError(
                "cache_mode='slots' was retired: the fixed-slot cache is "
                "gone (paged is strictly more capable — same bytes, block "
                "granularity).  Drop the kwarg, or size the pool with "
                "kv_block_size/kv_blocks to mimic fixed lanes "
                "(kv_blocks = batch_slots * max_seq // kv_block_size + 1)")
        if cache_mode != "paged":
            raise ValueError("cache_mode must be 'paged'")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        if mesh is not None:
            # the sharded decode replicates the weights over the mesh:
            # place them there once, not on every step's call
            params = jax.device_put(
                params, jax.sharding.NamedSharding(mesh, P()))
        self.cfg = cfg
        self.params = params
        self.engine = engine
        self.executor = executor
        self.mesh = mesh
        self.model_axis = model_axis
        self.collective_spec = spec
        self.collective_backend = spec.backend   # read-compat mirror
        self._sharded = mesh is not None
        self.paged = True                        # retained for callers
        self.slots = PagedKVCache(cfg, batch_slots, max_seq,
                                  block_size=kv_block_size,
                                  num_blocks=kv_blocks, mesh=mesh)
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        # retained for elastic rebuilds (_rebuild_for_survivors)
        self._kv_block_size = kv_block_size
        self._kv_blocks = kv_blocks
        self._arrivals: collections.deque[GenRequest] = collections.deque()
        self._active: dict[int, GenRequest] = {}
        # paged continuous batching: requests waiting for blocks/lanes,
        # and lanes whose prompt replay is mid-prefill (chunked — prefill
        # interleaves with decode steps instead of blocking them)
        self._backlog = _BucketBacklog()
        self._prefilling: dict[int, GenRequest] = {}
        self._seq = 0                  # submit-order stamp (preemption policy)
        self.sched = SchedulerStats()
        # one lock serialises admission/prefill against detokenize: the
        # stages may run on different executor workers, but KV cache and
        # slot state are shared.  Prefill itself runs OUTSIDE the lock
        # (staged cache, published atomically) so submit() and the
        # detokenize path never block behind a prefill chunk.
        self._lock = debug.make_lock("ServeEngine._lock")
        self._decode_inflight = None
        self._current_step = None      # the step whose continuation owns state
        self._admit_scheduled = False
        self._prefill_active = False
        self._stopping = False
        self._closed = False
        # membership (fault tolerance): the epoch's invalidation listener
        # only RECORDS the change — it may run inside whatever subsystem
        # poll fired the invalidation (often an executor worker), where a
        # drain/rebuild would self-deadlock.  The heavy work happens on
        # the admit path (_apply_membership_change).
        self.epoch = epoch
        self._membership_exc = None
        self._remeshing = False
        self.remeshes = 0
        # finished-request ledger for latency_snapshot (bounded: a
        # long-lived server must not grow per-request records forever)
        self._submitted = 0
        self._finished: collections.deque[tuple] = collections.deque(
            maxlen=4096)
        if self._sharded:
            self._build_sharded_decode()
        else:
            self.coll = None
            self._ag_handle = None
            self._jit_gather = None
            self._jit_decode = _serve_program(cfg)
        self.admit_stream = engine.stream("serve-admit")
        self.decode_stream = engine.stream("serve-decode")
        # decode completions are delivered through this queue; its
        # detection task lives on the decode stream so INLINE runs
        # detokenize right where completion was observed
        self.continuations = ContinuationQueue(
            engine, self.decode_stream, policy=continuation_policy,
            name="serve-cont")
        self.continuation_max_drain = continuation_max_drain
        self._queue_adopted = False
        # streams the caller-driven bridge must poll: the serve pair
        # plus (user backend) the collective stream — without an
        # executor nobody else progresses the all-gather rounds, and
        # with one that is NOT running the run_until_idle fallback
        # drives these same streams inline (a running executor never
        # routes through _poll_streams, so there is no contention)
        self._bridge_streams = [self.admit_stream, self.decode_stream]
        if self.coll is not None:
            self._bridge_streams.append(self.coll.stream)
        if executor is not None:
            executor.adopt(self.admit_stream)
            executor.adopt(self.decode_stream)
            if continuation_policy == DEFERRED:
                executor.adopt_queue(self.continuations)
                self._queue_adopted = True
            self._sub = None
        else:
            # no executor: bridge the serve streams (and the continuation
            # drain) into every engine.progress() call so single-threaded
            # callers still serve
            self._sub = engine.register_subsystem(
                "serve-streams", self._poll_streams, cheap=True, priority=4)
        self.steps = 0
        self._calls = 0                # serving-program dispatches so far
        # bounded: transient device failures on a long-lived server must
        # not accumulate exception objects forever
        self.decode_errors: collections.deque[BaseException] = \
            collections.deque(maxlen=256)
        if epoch is not None:
            epoch.subscribe(self._on_epoch_invalidate)

    # -- sharded decode construction --------------------------------------
    def _build_sharded_decode(self) -> None:
        """Compile the model-axis decode pair: ONE shared partial-logits
        program (hidden + per-rank vocab-slice unembed) and the gather —
        in-program ``all_gather`` (native) or a persistent user-space
        ``allgather_init`` handle on a dedicated serve-collective stream
        (built and warmed once: decode shapes are fixed)."""
        cfg, mesh, axis = self.cfg, self.mesh, self.model_axis
        from repro.collectives import nonblocking as NB
        if axis not in dict(mesh.shape):
            raise ValueError(f"mesh has no axis {axis!r}: {dict(mesh.shape)}")
        n = dict(mesh.shape)[axis]
        V = cfg.vocab_size
        if V % n:
            raise ValueError(
                f"sharded serving needs vocab_size ({V}) divisible by the "
                f"{axis!r} axis size ({n})")
        vloc = V // n
        self._model_shards = n
        if not hasattr(registry.module_for(cfg), "decode_hidden_paged"):
            raise ValueError(
                f"sharded serving not supported for family {cfg.family!r}")

        def local_step(params, cache, toks, pos, tables, fed):
            hid, new_cache = registry.decode_hidden_paged(
                params, cfg, cache, toks, pos, tables, fed)
            r = jax.lax.axis_index(axis)
            # [B, 1, vloc] -> [1, B, vloc]: leading dim carries the
            # rank (the user-collective payload layout)
            part = registry.unembed_partial(params, cfg, hid,
                                            r * vloc, vloc)
            return part[:, 0][None], new_cache

        self._jit_decode = jax.jit(compat.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P()),
            out_specs=(P(axis), P())))

        def local_gather(part):                  # local [1, B, vloc]
            return jax.lax.all_gather(part, axis, axis=2, tiled=True)

        if not self.collective_spec.user:
            self._jit_gather = jax.jit(compat.shard_map(
                local_gather, mesh=mesh, in_specs=P(axis),
                out_specs=P(axis)))              # global [n, B, V]
            self.coll = None
            self._ag_handle = None
        else:
            self._jit_gather = None
            self.coll = NB.UserCollectives(self.engine,
                                           executor=self.executor,
                                           name="serve-coll",
                                           epoch=self.epoch)
            self._ag_handle = self.coll.allgather_init(
                jax.ShapeDtypeStruct((n, self.batch_slots, vloc),
                                     jnp.float32),
                mesh, axis, spec=self.collective_spec, warmup=True)

    # -- client API -------------------------------------------------------
    def submit(self, request: GenRequest) -> Request:
        with self._lock:
            if self._stopping:
                raise RuntimeError("serve engine is stopping")
            request.seq = self._seq
            self._seq += 1
            request.last_enqueued_at = time.monotonic()
            self._arrivals.append(request)
            self._submitted += 1
            waiting = len(self._arrivals) + len(self._backlog)
            self.sched.peak_backlog = max(self.sched.peak_backlog, waiting)
        self._schedule_admit()               # the arrival event
        return request.done_req

    # -- caller-driven bridge ---------------------------------------------
    def _poll_streams(self) -> bool:
        made = 0
        for s in self._bridge_streams:
            try:
                made += s._poll_once()
            except Exception:
                # the broken task is already dropped and recorded on
                # s.task_errors; the bridge must NOT let the exception
                # escape, or the engine's isolation would unregister it
                # and silently halt all serving
                pass
        made += self.continuations.drain(self.continuation_max_drain)
        coll = self.coll                 # close() nulls the attr concurrently
        if coll is not None:
            made += coll.queue.drain(self.continuation_max_drain)
        return made > 0

    # -- admission (event-scheduled, one-shot) ------------------------------
    def _schedule_admit(self) -> None:
        with self._lock:
            pending = (self._arrivals or self._backlog or self._prefilling
                       or self._membership_exc is not None)
            if self._admit_scheduled or not pending:
                return
            self._admit_scheduled = True
        self.engine.async_start(self._admit_task, None, self.admit_stream)

    def _admit_task(self, thing) -> str:
        with self._lock:
            self._admit_scheduled = False
        self._admit()
        self._schedule_decode()
        return DONE                          # one-shot: nothing left to poll

    def _admit(self) -> bool:
        """Admission + one prefill chunk (staged cache writes outside
        the lock, published atomically).

        A pending membership change is applied first — nothing may be
        admitted onto the old mesh.  The unlocked reads are benign: the
        flag is set under the lock, and an invalidation racing past the
        check is caught by the decode gate and the next admit pass."""
        if self._membership_exc is not None:
            self._apply_membership_change()
            if self._membership_exc is not None:
                return False         # in-flight work must drain first
        return self._admit_paged()

    def _admit_paged(self) -> bool:
        """Continuous-batching admission: drain arrivals into the
        length-bucketed backlog, admit whatever fits the free lanes AND
        free blocks (lane + prefill blocks claimed atomically), then run
        ONE chunk of batched prefill — one fused call feeding EVERY
        mid-prefill lane up to ``prefill_chunk`` next replay tokens.
        Long prompts therefore interleave with decode steps instead of
        blocking them: the caller (admit task / detokenize continuation)
        re-schedules until every replay is rebuilt.

        Runs the chunk on a STAGED cache outside the lock (no decode
        step is in flight and ``_prefill_active`` excludes concurrent
        admissions)."""
        with self._lock:
            if self._decode_inflight is not None or self._prefill_active:
                return False
            with jax.profiler.TraceAnnotation("serve.admit") as span:
                admitted = self._admit_pass_locked()
                span.set_metadata(admitted=len(admitted))
            if not self._prefilling:
                return False
            self.sched.peak_resident = max(
                self.sched.peak_resident,
                len(self._active) + len(self._prefilling))
            self._prefill_active = True
            cache = self.slots.cache
        try:
            for req in admitted:
                idx = req.slot_index
                # recycled lane: zero per-lane recurrent state (SSM) so
                # the previous occupant cannot leak into this request
                cache = self.slots.reset_lane(cache, idx)
                if req.kv_ckpt is not None:
                    # migrated lane (membership change): restore the KV
                    # prefix + per-lane state checkpointed off the old
                    # mesh instead of replaying the whole prefix
                    cache = self.slots.restore_lane(cache, idx, req.kv_ckpt)
                    req.prefill_pos = len(req.replay) - 1
                    req.kv_ckpt = None
            cache, completed = self._prefill_chunk(cache)
        except BaseException as exc:  # noqa: BLE001
            # chunk failure: the staged cache is NOT published, so every
            # mid-prefill replay is lost — fail those requests exactly
            # once, return their lanes and blocks to the free lists
            self.decode_errors.append(exc)
            with self._lock:
                self._prefill_active = False
                for idx, req in list(self._prefilling.items()):
                    self._prefilling.pop(idx)
                    self.slots.release(self.slots.slots[idx])
                    req.finished_at = time.monotonic()
                    self._record_locked(req, failed=True)
                    req.done_req.fail(exc)
            self._schedule_admit()           # backlog remainder, if any
            return False
        with self._lock:
            self._prefill_active = False
            self.slots.cache = cache
            for idx in completed:
                self._active[idx] = self._prefilling.pop(idx)
        return True

    def _admit_pass_locked(self) -> list:
        """Drain arrivals into the backlog and claim a lane (and its
        prefill blocks) for every request that fits; caller holds
        ``self._lock``.  Returns the requests admitted."""
        now = time.monotonic()
        while self._arrivals:
            req = self._arrivals.popleft()
            if req.replay is None:
                req.replay = np.asarray(req.prompt, np.int32)
            self._backlog.push(req)

        def fits(req):
            return self.slots.assign(req.request_id,
                                     seq_len=len(req.replay))

        admitted = []
        while self.slots.free_count:
            req, lane = self._backlog.pop_fitting(fits)
            if req is None:
                break
            req.slot_index = lane.index
            req.prefill_pos = 0
            req.queued_s += now - req.last_enqueued_at
            self._prefilling[lane.index] = req
            admitted.append(req)
            self.sched.admitted += 1
        return admitted

    def _prefill_chunk(self, cache):
        """One fused paged call of ``[batch_slots, prefill_chunk]``
        tokens over the staged cache: each mid-prefill lane is fed its
        next ``min(prefill_chunk, remaining)`` replay positions, the
        other lanes none.  Every chunk pads to the same shape, so prefill
        and decode (``[batch_slots, 1]``) are the serving program's only
        two shapes.  Logits are discarded (and in sharded mode no gather
        is started) — prefill only needs the KV side effect.  Columns a
        lane is not fed write the scratch block and leave its SSM state
        frozen (see models/transformer.py).  Returns the staged cache and
        the lanes whose replay completed."""
        feeding = [(idx, req) for idx, req in self._prefilling.items()
                   if req.prefill_pos < len(req.replay) - 1]
        if feeding:
            C = self.prefill_chunk
            toks = np.zeros((self.batch_slots, C), np.int32)
            fed = np.zeros((self.batch_slots,), np.int32)
            for idx, req in feeding:
                n = min(C, len(req.replay) - 1 - req.prefill_pos)
                toks[idx, :n] = req.replay[req.prefill_pos:
                                           req.prefill_pos + n]
                fed[idx] = n
            tokens = int(fed.sum())
            self._calls += 1
            with jax.profiler.TraceAnnotation("serve.prefill",
                                              call=self._calls,
                                              lanes=len(feeding),
                                              tokens=tokens):
                _, cache = self._jit_decode(
                    self.params, cache, jnp.asarray(toks),
                    self.slots.positions(), self.slots.block_tables(),
                    jnp.asarray(fed))
            for idx, req in feeding:
                req.prefill_pos += int(fed[idx])
                self.slots.slots[idx].pos += int(fed[idx])
            self.sched.prefill_calls += 1
            self.sched.prefill_tokens += tokens
        completed = []
        for idx, req in self._prefilling.items():
            if req.prefill_pos >= len(req.replay) - 1:
                req.next_input = int(req.replay[-1])
                completed.append(idx)
        return cache, completed

    # -- fused decode (continuation-chained steps) ---------------------------
    def _schedule_decode(self) -> None:
        with self._lock:
            # defer while a prefill is staging: a step launched off the
            # pre-prefill cache would have its continuation overwrite the
            # published prompt KV.  The admitting thread always calls
            # _schedule_decode after publishing, so nothing starves.
            busy = (self._decode_inflight is not None
                    or self._prefill_active)
            # membership pending: nothing launches on the old mesh — the
            # admit path applies the change first.  With a step still in
            # flight its own continuation funnels there; re-scheduling
            # here too would spin the admit stream against it.
            blocked = self._membership_exc is not None
            launched = not busy and not blocked and bool(self._active)
            if launched:
                step, agreq, cache = self._launch_decode_locked()
            # paged: prompts may still be mid-replay with no lane decoding
            # yet — keep the prefill chain alive (the admit task runs the
            # next chunk; _admit_scheduled bounds this to one outstanding
            # task)
            reschedule = (not busy and not blocked
                          and not self._active and bool(self._prefilling))
        if launched:
            self._attach_step(step, agreq, cache)
        elif reschedule or (blocked and not busy):
            self._schedule_admit()

    def _launch_decode_locked(self):
        """Dispatch one fused decode step; caller holds ``self._lock``.
        Returns ``(step, agreq, cache)``.

        Unsharded / native-sharded: completion is watched by a one-shot
        readiness task on the decode stream that completes ``step`` —
        the only place the device is polled.  User backend: the step's
        partial logits are re-bound into the persistent all-gather
        (``start``), and ``agreq``'s completion (bridged by a
        continuation) completes ``step`` with the gathered logits — the
        engine drives the gather rounds while the device runs.

        Dispatch failure fails the request instead of wedging the stream
        (the failure continuation cleans up).  The caller attaches the
        continuations AFTER releasing the lock: an already-failed step
        fires inline immediately, and that must not happen while the
        serve lock is held.
        """
        step = Request(tag="decode-step")
        self._current_step = step
        self._calls += 1
        try:
            with jax.profiler.TraceAnnotation("serve.decode",
                                              call=self._calls,
                                              step=self.steps + 1) as span:
                self._ensure_capacity_locked()
                lanes = len(self._active)
                span.set_metadata(lanes=lanes)
                toks = np.zeros((self.batch_slots, 1), np.int32)
                for idx, req in self._active.items():
                    toks[idx, 0] = req.next_input
                pos = self.slots.positions()
                fed = np.zeros((self.batch_slots,), bool)
                for idx in self._active:
                    fed[idx] = True
                out, cache = self._jit_decode(
                    self.params, self.slots.cache, jnp.asarray(toks), pos,
                    self.slots.block_tables(), jnp.asarray(fed))
                if self._jit_gather is not None:     # native-sharded gather
                    out = self._jit_gather(out)
                agreq = None
                if self._ag_handle is not None:      # user-space gather
                    agreq = self._ag_handle.start(out)
            self.sched.decode_lanes += lanes
        except BaseException as exc:  # noqa: BLE001
            step.fail(exc)
            return step, None, None
        self._decode_inflight = (out, cache)
        if agreq is None:
            def ready_poll(thing, out=out, cache=cache, step=step) -> str:
                if not out.is_ready():       # device still busy — no block
                    return NOPROGRESS
                step.complete((out, cache))
                return DONE

            self.engine.async_start(ready_poll, None, self.decode_stream)
        return step, agreq, cache

    # -- block pressure: preemption / re-admission (paged mode) -------------
    def _ensure_capacity_locked(self) -> None:
        """Grow every decoding lane's block table to cover its next write
        position, preempting victims under block pressure.  Caller holds
        ``self._lock``.

        Policy: the oldest resident (smallest submit ``seq``, across
        decoding AND prefilling lanes) is never preempted, so it always
        runs to completion — every preemption strictly reduces the set of
        requests younger than it, which bounds total preemptions for a
        finite workload (no livelock).  Victims are evicted
        youngest-first; a lane may evict itself (it re-enters the backlog
        ahead of younger arrivals and is retried once blocks free)."""
        for idx in sorted(self._active, key=lambda i: self._active[i].seq):
            while idx in self._active:
                if self.slots.ensure(idx, self.slots.slots[idx].pos):
                    break
                victim = self._pick_victim_locked()
                if victim is None:
                    # sole resident: PagedKVCache guarantees the pool
                    # holds one max_seq request, so ensure cannot fail
                    raise RuntimeError(
                        "block pool exhausted with no preemptible victim")
                self._preempt_locked(victim)

    def _pick_victim_locked(self) -> Optional[int]:
        """Lane of the youngest resident, never the oldest; ``None`` when
        fewer than two requests are resident."""
        residents = {**self._prefilling, **self._active}
        if len(residents) < 2:
            return None
        return max(residents, key=lambda i: residents[i].seq)

    def _preempt_locked(self, idx: int) -> None:
        """Evict one resident lane: return its blocks to the free list
        and re-queue the request with its generated prefix folded into
        ``replay``.  Greedy decode is per-lane deterministic, so the
        rebuilt KV continues the exact same token stream — preemption is
        invisible in the output."""
        req = self._active.pop(idx, None)
        if req is None:
            req = self._prefilling.pop(idx)
        self.slots.release(self.slots.slots[idx])
        req.preemptions += 1
        self.sched.preemptions += 1
        req.replay = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.out_tokens, np.int32)])
        req.prefill_pos = 0
        req.slot_index = -1
        req.last_enqueued_at = time.monotonic()
        self._backlog.push(req)

    def _attach_step(self, step: Request, agreq=None, cache=None) -> None:
        if agreq is not None:
            # bridge the persistent all-gather into the step request:
            # detokenize (below) stays identical across backends
            self.continuations.attach(
                agreq,
                lambda rq, step=step, cache=cache:
                    step.complete((rq.value(), cache)),
                on_error=lambda rq, step=step: step.fail(
                    rq.exception
                    or RuntimeError("serve all-gather failed")))
        self.continuations.attach(step, self._on_step_done,
                                  on_error=self._on_step_failed)

    def _next_ids(self, logits) -> np.ndarray:
        """Greedy ids [B] from the step output: unsharded logits are
        [B, 1, V]; sharded (gathered) logits are [n, B, V] with every
        row the full vocab in rank order — row 0 is the whole answer."""
        if self._sharded:
            return np.asarray(jnp.argmax(logits[0], axis=-1))
        return np.asarray(jnp.argmax(logits[:, -1], axis=-1))

    def _on_step_done(self, step: Request) -> None:
        """Detokenize stage (a continuation): harvest the fused step,
        finish/complete requests, and chain the next decode step."""
        n = self.steps + 1                     # the step this harvests
        with jax.profiler.TraceAnnotation("serve.harvest", step=n):
            logits, cache = step.value()
            try:
                # materialize OUTSIDE the lock: this is where async device
                # errors surface (not at dispatch) — a raise here must take
                # the failure path, not wedge the server with _active full
                # and no task on any stream
                with jax.profiler.TraceAnnotation("serve.sample", step=n):
                    next_ids = self._next_ids(logits)
            except BaseException as exc:  # noqa: BLE001
                self._fail_step(step, exc)
                return
            freed = False
            with self._lock:
                if self._current_step is not step:
                    return                     # stale: a newer step owns state
                self._current_step = None
                self._decode_inflight = None
                self.slots.cache = cache
                self.steps += 1
                finished = []
                for idx, req in list(self._active.items()):
                    tok = int(next_ids[idx])
                    if req.first_token_at is None:
                        # TTFT stamp: exactly once, on the first token
                        req.first_token_at = time.monotonic()
                    req.out_tokens.append(tok)
                    req.next_input = tok
                    self.slots.slots[idx].pos += 1
                    if (len(req.out_tokens) >= req.max_new_tokens
                            or self.slots.slots[idx].pos >= self.max_seq - 1):
                        finished.append(idx)
                for idx in finished:
                    req = self._active.pop(idx)
                    req.finished_at = time.monotonic()
                    self.slots.release(self.slots.slots[idx])
                    self._record_locked(req, failed=False)
                    req.done_req.complete(req.out_tokens)
                    freed = True
            # admit between steps: arrivals that landed while this step was
            # in flight (their admission was deferred — prefill and an
            # in-flight step must not both write slots.cache) join the batch
            # before the next launch.  Prefill stages outside the lock, so
            # releasing it first keeps submit() responsive during admission.
            self._admit()
            self._schedule_decode()            # chain the next step
        if freed:
            self._schedule_admit()             # the slot-free event

    def _on_step_failed(self, step: Request) -> None:
        """Failure continuation: a decode step that failed fails every
        in-flight request with the step's exception (propagated through
        ``Request.exception``) and frees their slots."""
        self._fail_step(step, step.exception)

    def _fail_step(self, step: Request, exc: BaseException) -> None:
        self.decode_errors.append(exc)
        with self._lock:
            if self._current_step is not step:
                # stale failure (a newer healthy step was launched before
                # this continuation drained): the requests already belong
                # to that step — touching state here would clobber it
                return
            self._current_step = None
            self._decode_inflight = None
            if (isinstance(exc, MembershipError)
                    or self._membership_exc is not None):
                # membership change killed the STEP, not the requests:
                # the failed step never published its cache, so every
                # resident lane is still pre-step-consistent — checkpoint
                # + requeue them for re-admission on the rebuilt mesh
                # instead of failing them (no in-flight request is lost)
                if self._membership_exc is None:
                    self._membership_exc = exc
                self._requeue_residents_locked()
            else:
                for idx, req in list(self._active.items()):
                    self._active.pop(idx)
                    # first_token_at stays as-is: a request that failed
                    # before its first token keeps None (null-propagated
                    # — counted by the snapshot, never faked into TTFT)
                    req.finished_at = time.monotonic()
                    self.slots.release(self.slots.slots[idx])
                    self._record_locked(req, failed=True)
                    req.done_req.fail(exc)
        self._schedule_admit()

    # -- membership changes (elastic fault tolerance) -----------------------
    def _on_epoch_invalidate(self, epoch, exc) -> None:
        """Epoch listener — runs inside whatever subsystem poll fired the
        invalidation (often an executor worker), so it only records the
        change and pokes the admit path; draining or rebuilding here
        could deadlock the worker against its own stream."""
        with self._lock:
            if self._closed:
                return
            self._membership_exc = exc
        self._schedule_admit()

    def _requeue_residents_locked(self) -> int:
        """Move every resident request (decoding or mid-prefill) back to
        the queue for re-admission on the rebuilt mesh.  Decoding paged
        lanes checkpoint their KV prefix + per-lane state to host memory
        (block-table walk) so restore skips the replay; mid-prefill lanes
        just replay.  Caller holds ``self._lock``; any in-flight step was
        failed WITHOUT publishing, so lane state is pre-step-consistent
        and ``replay = prompt + out_tokens`` resumes the exact stream."""
        now = time.monotonic()
        moved = []
        for idx, req in list(self._active.items()):
            self._active.pop(idx)
            lane = self.slots.slots[idx]
            if lane.pos > 0:
                try:
                    req.kv_ckpt = self.slots.checkpoint_lane(idx)
                except Exception as ckpt_exc:   # fall back to full replay
                    self.decode_errors.append(ckpt_exc)
                    req.kv_ckpt = None
            self.slots.release(lane)
            moved.append(req)
        for idx, req in list(self._prefilling.items()):
            self._prefilling.pop(idx)
            req.kv_ckpt = None                  # partial prefix: replay
            self.slots.release(self.slots.slots[idx])
            moved.append(req)
        for req in moved:
            req.replay = np.concatenate([
                np.asarray(req.prompt, np.int32),
                np.asarray(req.out_tokens, np.int32)])
            req.prefill_pos = 0
            req.slot_index = -1
            req.last_enqueued_at = now
        for req in moved:
            self._backlog.push(req)
        return len(moved)

    def _apply_membership_change(self) -> None:
        """Drain + rebuild after an epoch invalidation (admit path, no
        lock held).  Residents are checkpointed and requeued under the
        lock; the rebuild — survivors' mesh, fresh KV pool, recompiled
        decode/gather programs, new persistent all-gather — runs outside
        it.  Bails while a step or prefill is in flight: their
        completion/failure continuations funnel back here."""
        with self._lock:
            exc = self._membership_exc
            if exc is None or self._remeshing:
                return
            if self._decode_inflight is not None or self._prefill_active:
                return
            self._remeshing = True
            moved = self._requeue_residents_locked()
        try:
            self._rebuild_for_survivors(exc)
        except BaseException:
            # keep _membership_exc set: the next admit pass retries the
            # rebuild (residents are already requeued — idempotent)
            with self._lock:
                self._remeshing = False
            raise
        with self._lock:
            self._remeshing = False
            if self._membership_exc is exc:     # a FRESH invalidation
                self._membership_exc = None     # during rebuild stays
            self.remeshes += 1
        if moved:
            self._schedule_admit()

    def _rebuild_for_survivors(self, exc) -> None:
        """Rebuild every mesh-dependent piece on the survivors: the mesh
        (model axis shrunk to what survives — capped by the old degree
        and the vocab divisibility rule), KV pool, decode and gather
        programs, and the persistent all-gather handle.  Nothing resident
        survives in device memory: requeued requests carry their prefix
        as a host checkpoint or as replay tokens."""
        from repro.distributed import elastic
        from repro.launch.mesh import make_mesh
        old_handle, old_coll = self._ag_handle, self.coll
        self._ag_handle = None
        self.coll = None
        # stop bridging the old collective stream BEFORE draining it
        self._bridge_streams = [self.admit_stream, self.decode_stream]
        if old_handle is not None:
            old_handle.close()
        if old_coll is not None:
            old_coll.close()
        if self._sharded:
            survivors = getattr(exc, "survivors", None)
            if survivors is None:
                survivors = self._model_shards
            # plan_mesh validates survivors >= 1 and keeps the model
            # degree when it still fits; vocab divisibility caps it below
            shape, _axes = elastic.plan_mesh(
                survivors, prefer_model=self._model_shards)
            m = shape[1]
            while m > 1 and self.cfg.vocab_size % m:
                m //= 2
            if m > 1:
                self.mesh = make_mesh((m,), (self.model_axis,))
            else:
                # a lone survivor serves unsharded — there is nothing
                # left to gather
                self.mesh = None
                self._sharded = False
        self.slots = PagedKVCache(self.cfg, self.batch_slots,
                                  self.max_seq,
                                  block_size=self._kv_block_size,
                                  num_blocks=self._kv_blocks,
                                  mesh=self.mesh)
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params, jax.sharding.NamedSharding(self.mesh, P()))
        else:
            self.params = jax.device_put(self.params, jax.devices()[0])
        if self._sharded:
            self._build_sharded_decode()
            if self.coll is not None:
                self._bridge_streams = [self.admit_stream,
                                        self.decode_stream, self.coll.stream]
        else:
            self._jit_gather = None
            self._jit_decode = _serve_program(self.cfg)

    def failures(self) -> list[BaseException]:
        """Every failure recorded while serving: failed prefill chunks and
        decode steps (``decode_errors``) and tasks dropped from the serve
        streams.  Call before ``close``, which hands the streams back."""
        out = list(self.decode_errors)
        for stream in self._bridge_streams:
            out.extend(stream.task_errors)
        return out

    # -- latency accounting ------------------------------------------------
    def _record_locked(self, req: GenRequest, failed: bool) -> None:
        """Append one finished request to the ledger (caller holds the
        serve lock — or owns the request exclusively, as prefill does)."""
        self._finished.append((req.submitted_at, req.first_token_at,
                               req.finished_at, failed, req.queued_s,
                               req.preemptions))

    def latency_snapshot(self) -> ServeLatencyStats:
        """TTFT / completion-latency aggregates over the (bounded) ledger
        of finished requests.  Requests that failed before producing a
        first token are counted (``no_first_token``) and excluded from
        the TTFT aggregates instead of silently skewing them."""
        with self._lock:
            records = list(self._finished)
            submitted = self._submitted
        snap = ServeLatencyStats(submitted=submitted)
        ttfts, lats, queued = [], [], []
        for sub, first, fin, failed, q_s, npre in records:
            if failed:
                snap.failed += 1
            else:
                snap.completed += 1
            if first is None:
                snap.no_first_token += 1
            else:
                ttfts.append((first - sub) * 1e3)
            if fin is not None:
                lats.append((fin - sub) * 1e3)
            queued.append(q_s * 1e3)
            if npre:
                snap.preempted += 1
                snap.preemptions += npre
        if ttfts:
            (snap.ttft_ms_mean, snap.ttft_ms_p50,
             snap.ttft_ms_p99) = _quantiles(ttfts)
        if lats:
            (snap.latency_ms_mean, snap.latency_ms_p50,
             snap.latency_ms_p99) = _quantiles(lats)
        if queued:
            (snap.queued_ms_mean, snap.queued_ms_p50,
             snap.queued_ms_p99) = _quantiles(queued)
        return snap

    def scheduler_snapshot(self) -> SchedulerStats:
        """Copy of the continuous-batching scheduler counters."""
        with self._lock:
            return dataclasses.replace(self.sched)

    # -- lifecycle ------------------------------------------------------------
    @property
    def idle(self) -> bool:
        with self._lock:
            busy = (self._active or self._arrivals or self._prefill_active
                    or self._prefilling or len(self._backlog)
                    or self._decode_inflight is not None
                    or self._membership_exc is not None)
        return not busy and self.continuations.ready == 0

    def run_until_idle(self, timeout: float = 120.0) -> None:
        """Serve until the backlog empties.  With an executor the worker
        threads do the progressing and this thread just waits; without one
        it is the classic caller-driven progress loop."""
        t0 = time.monotonic()
        while not self.idle:
            if self.executor is not None and self.executor.running:
                time.sleep(0.0005)
            elif self._sub is not None:
                # bridge polls the streams; pace out when nothing moved
                # (waiting on the device must not burn the core)
                if self.engine.progress() == 0:
                    time.sleep(50e-6)
            else:
                # executor attached but not running (never started, or
                # already shut down): drive the adopted streams inline so
                # waiting can never silently hang
                made = self._poll_streams()
                subs = self.engine.poll_subsystems()
                if not made and not subs:
                    time.sleep(50e-6)       # device wait: don't burn a core
            if time.monotonic() - t0 > timeout:
                raise TimeoutError("serve engine did not drain")

    def stop(self) -> None:
        """Begin shutdown: reject new submissions.  Already-submitted
        work keeps flowing (the event chain runs the backlog down); once
        it finishes no tasks remain, so drains terminate."""
        with self._lock:
            self._stopping = True

    def close(self, timeout: float = 60.0) -> None:
        """Stop, serve the backlog, then deterministically drain: both
        serve streams empty and every pending continuation executed
        (Listing 1.2 finalize, extended to the continuation layer).
        Idempotent: a second close (finally blocks, racing shutdown
        paths) is a no-op."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self.stop()
        self.run_until_idle(timeout=timeout)
        if self.executor is not None and self.executor.running:
            self.executor.drain(timeout)
        else:
            self.engine.drain(self.admit_stream, timeout=timeout)
            self.engine.drain(self.decode_stream, timeout=timeout)
        self.continuations.drain()             # anything still ready
        if self._queue_adopted:
            self.executor.release_queue(self.continuations)
            self._queue_adopted = False
        self.continuations.close()
        if self._ag_handle is not None:
            self._ag_handle.close()
            self._ag_handle = None
        if self.coll is not None:
            # drains the serve-collective stream and hands it back
            self.coll.close(timeout=timeout)
            self.coll = None
            self._bridge_streams = [self.admit_stream, self.decode_stream]
        if self._sub is not None:
            self.engine.unregister_subsystem(self._sub)
            self._sub = None
        # hand the (drained) streams back to the engine: a process that
        # builds ServeEngines repeatedly must not grow the stream list
        for stream in (self.admit_stream, self.decode_stream):
            if self.executor is not None and self.executor.owns(stream):
                self.executor.release(stream)
            if not stream.pending:
                self.engine.free_stream(stream)
