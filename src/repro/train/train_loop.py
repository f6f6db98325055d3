"""Training loop — every async subsystem hangs off ONE progress engine.

The loop body is the paper's Figure 4(b) pattern, deliberately:

    dispatch step N+1 (nonblocking: jit returns immediately)
    ── while the device runs ──
    engine.progress():  data prefetch fills, checkpoint stages advance,
                        heartbeats/watchdog checked, metrics flush
    block on step N's loss only when needed (jax_future completion)

``jax_future`` + ``Request.is_complete`` replace blocking
``block_until_ready`` calls, so the host never idles inside a wait loop
while there is progress to be made — the computation/communication
overlap story, at the host level.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core import ProgressEngine, ProgressExecutor, global_engine, \
    jax_future
from repro.collectives.nonblocking import CollectiveSpec, MembershipError, \
    spec_from_legacy
from repro.core.request import Request
from repro.distributed.fault_tolerance import StepWatchdog, StragglerDetector
from repro.train import optimizer as opt_mod
from repro.train.checkpoint import AsyncCheckpointer


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    watchdog_limit_s: float = 600.0
    resume: bool = True
    # >0: that many background progress workers drive prefetch/checkpoint/
    # watchdog tasks (§4.4); 0: the overlap window self-progresses as before
    progress_workers: int = 0
    # gradient-reduction configuration: ONE CollectiveSpec covers
    # backend ("native" keeps the reduction inside the jitted step,
    # "user" runs nonblocking user-space collectives on the progress
    # engine — requires a split step, see ``UserCollectiveStep``/
    # ``FsdpStep``), algorithm, chunk count, and round batching.  The
    # collective_* fields below are the deprecated spelling: still
    # accepted for one release (a DeprecationWarning fires once), still
    # readable afterwards (mirrored from the resolved spec).
    collective_spec: "CollectiveSpec | None" = None
    collective_backend: "str | None" = None
    collective_algorithm: "str | None" = None
    collective_chunks: "int | None" = None
    collective_round_batch: "int | None" = None
    # pipeline-parallel schedule this loop runs under ("none", "gpipe",
    # "1f1b") — a record field like collective_spec.backend: the
    # launcher carries the machinery (PipelineSchedule per data row),
    # the config is what logs/stats report
    pipeline: str = "none"

    _DEFAULT_SPEC = CollectiveSpec(backend="native", algorithm="ring",
                                   chunks=4, round_batch=0)

    def __post_init__(self):
        spec = self.collective_spec
        legacy = (("backend", self.collective_backend),
                  ("algorithm", self.collective_algorithm),
                  ("chunks", self.collective_chunks),
                  ("round_batch", self.collective_round_batch))
        if spec is not None:
            # mirrored legacy fields (from a previous resolve, or a
            # dataclasses.replace round-trip) must agree with the spec;
            # a *conflicting* explicit legacy kwarg is a config bug
            for name, val in legacy:
                if val is not None and val != getattr(spec, name):
                    raise ValueError(
                        f"TrainLoopConfig: collective_spec.{name}="
                        f"{getattr(spec, name)!r} conflicts with legacy "
                        f"collective_{name}={val!r}; pass one, not both")
        else:
            spec = spec_from_legacy(
                None, surface="TrainLoopConfig",
                backend=self.collective_backend,
                algorithm=self.collective_algorithm,
                chunks=self.collective_chunks,
                round_batch=self.collective_round_batch,
                default=self._DEFAULT_SPEC)
        self.collective_spec = spec
        self.collective_backend = spec.backend
        self.collective_algorithm = spec.algorithm
        self.collective_chunks = spec.chunks
        self.collective_round_batch = spec.round_batch


@dataclasses.dataclass
class UserCollectiveStep:
    """Split train step for the engine-driven collective backend.

    ``grad_fn(params, batch) -> (stacked_metrics, stacked_grads)`` —
    per-device losses/metrics and gradients stacked on a leading
    axis-size dim (``shard_map`` local grads); ``reducer`` (an
    ``EngineGradReducer``) allreduces the grads on the collective
    stream while the engine also progresses prefetch/checkpoint tasks;
    ``apply_fn(params, opt_state, grads, stacked_metrics) -> (params,
    opt_state, metrics)`` finishes the step.  ``spec`` (a
    :class:`~repro.collectives.nonblocking.CollectiveSpec`) records the
    reduction configuration the reducer was built with — the same
    config object every other surface takes."""
    grad_fn: Callable
    apply_fn: Callable
    reducer: Any
    spec: "CollectiveSpec | None" = None

    def __post_init__(self):
        if self.spec is not None and not isinstance(self.spec,
                                                    CollectiveSpec):
            raise TypeError(
                f"spec must be a CollectiveSpec, got "
                f"{type(self.spec).__name__} (legacy kwargs belong on "
                f"TrainLoopConfig)")


@dataclasses.dataclass
class FsdpStep:
    """Split train step for ZeRO-style FSDP on the user backend.

    Parameters live as *flat shard stacks* (``FsdpLayout.shard_params``
    — one ``[n, W/n]`` array per bucket, rank ``r`` owning row ``r``):

    * ``grad_fn(gathered_flats, batch) -> (stacked_metrics,
      flat_grads)`` — takes the all-gathered full flat buckets
      ``[n, W]``, unflattens *inside* the jitted program, and returns
      per-device metrics plus stacked flat grad buckets ``[n, W]``;
    * ``reducer`` (an :class:`~repro.collectives.overlap.FsdpReducer`)
      reduce-scatters the grad buckets — each rank receives only its
      own block — and prefetches the next step's params via
      continuation-chained persistent all-gathers;
    * ``apply_fn(shards, opt_state, grad_shards, stacked_metrics) ->
      (shards, opt_state, metrics)`` — the sharded optimizer step.

    ``spec`` as in :class:`UserCollectiveStep`."""
    grad_fn: Callable
    apply_fn: Callable
    reducer: Any
    spec: "CollectiveSpec | None" = None

    def __post_init__(self):
        if self.spec is not None and not isinstance(self.spec,
                                                    CollectiveSpec):
            raise TypeError(
                f"spec must be a CollectiveSpec, got "
                f"{type(self.spec).__name__} (legacy kwargs belong on "
                f"TrainLoopConfig)")


class Trainer:
    def __init__(self, step_fn: Callable, params, opt_state,
                 pipeline, cfg: TrainLoopConfig,
                 engine: Optional[ProgressEngine] = None,
                 hooks: list[Callable[[int, dict], None]] | None = None,
                 split_step: "UserCollectiveStep | None" = None,
                 epoch=None,
                 remesh_fn: Callable | None = None):
        # epoch: a collectives MembershipEpoch shared with the reducer's
        # persistent handles.  The watchdog invalidates it when a step
        # hangs, so the in-flight reduction fails retryably instead of
        # deadlocking the loop.  remesh_fn(exc, params, opt_state) ->
        # (split_step, params, opt_state) rebuilds the split step on the
        # survivors' mesh (new shard_map programs, re-placed state, a
        # remeshed reducer); with it set, a MembershipError surfacing
        # from grad dispatch or the reduction wait is recovered
        # *within the same step*: rebuild, then retry the step's batch.
        # keep the config's collective_backend and the split_step argument
        # consistent: the config is the record (stats/logs), the split_step
        # carries the machinery — they must agree or the caller gets the
        # wrong backend silently
        if split_step is not None and cfg.collective_backend != "user":
            cfg = dataclasses.replace(
                cfg,
                collective_spec=dataclasses.replace(cfg.collective_spec,
                                                    backend="user"),
                collective_backend="user")
        elif split_step is None and cfg.collective_backend == "user":
            raise ValueError(
                "collective_backend='user' requires a split_step "
                "(UserCollectiveStep or FsdpStep with "
                "grad_fn/apply_fn/reducer)")
        self.step_fn = step_fn
        self.split_step = split_step
        self.params = params
        self.opt_state = opt_state
        self.pipeline = pipeline
        self.cfg = cfg
        self.engine = engine or global_engine()
        self.hooks = hooks or []
        self.ckpt = AsyncCheckpointer(cfg.checkpoint_dir, self.engine)
        self.straggler = StragglerDetector()
        self.epoch = epoch
        self.remesh_fn = remesh_fn
        self.watchdog = StepWatchdog(self.engine, cfg.watchdog_limit_s,
                                     on_hang=self._on_hang, epoch=epoch)
        self.start_step = 0
        self.recoveries = 0
        self.metrics_log: list[dict] = []
        self._pending_ckpt: Request | None = None
        self._pending_gather = None     # FsdpStep: chained param prefetch
        self._hung = False

    # ------------------------------------------------------------------
    def _on_hang(self):
        self._hung = True

    def _reduced_grads(self, batch):
        """Split-step grad dispatch + engine-driven bucketed reduction."""
        stacked_metrics, grads = self.split_step.grad_fn(self.params, batch)
        reduction = self.split_step.reducer.iallreduce_tree(grads)
        return stacked_metrics, \
            reduction.wait(timeout=self.cfg.watchdog_limit_s)

    def _split_step_once(self, batch):
        """One split-backend step; sets params/opt_state, returns metrics.
        Raises MembershipError retryably (params not yet updated)."""
        limit = self.cfg.watchdog_limit_s
        if isinstance(self.split_step, FsdpStep):
            ss = self.split_step
            if self._pending_gather is None:
                # cold start (or post-remesh): no prefetch in flight —
                # issue the continuation-chained gather and wait it here
                self._pending_gather = ss.reducer.igather(self.params)
            flats = self._pending_gather.wait(timeout=limit)
            self._pending_gather = None
            stacked_metrics, flat_grads = ss.grad_fn(flats, batch)
            grad_shards = ss.reducer.ireduce_scatter(flat_grads) \
                .wait(timeout=limit)
            self.params, self.opt_state, metrics = ss.apply_fn(
                self.params, self.opt_state, grad_shards, stacked_metrics)
            # prefetch the next step's full params NOW: each bucket's
            # persistent all-gather start is chained off that bucket's
            # compute future, so gather rounds progress on the collective
            # stream behind the optimizer tail, the metrics wait, host
            # logging and the next batch fetch (§4.6 continuations)
            self._pending_gather = ss.reducer.igather(
                self.params, after=[ss.reducer.future(s)
                                    for s in self.params])
            return metrics
        stacked_metrics, grads = self._reduced_grads(batch)
        self.params, self.opt_state, metrics = self.split_step.apply_fn(
            self.params, self.opt_state, grads, stacked_metrics)
        return metrics

    def maybe_resume(self):
        if not self.cfg.resume:
            return
        latest = self.ckpt.latest_step()
        if latest is not None:
            state = self.ckpt.restore(latest, {"params": self.params,
                                               "opt_state": self.opt_state})
            self.params = state["params"]
            self.opt_state = state["opt_state"]
            self.start_step = latest + 1

    # ------------------------------------------------------------------
    def run(self) -> list[dict]:
        executor = None
        if self.cfg.progress_workers > 0:
            # background progress (§4.4): workers own the default stream's
            # async tasks (prefetch fills, checkpoint stages, futures) plus
            # the subsystem hooks; the overlap window below then *waits*
            # (engine.wait yields to the executor) instead of polling
            executor = ProgressExecutor(self.engine,
                                        self.cfg.progress_workers)
            executor.adopt(self.engine.default_stream)
            executor.start()
        try:
            return self._run_loop()
        finally:
            if executor is not None:
                executor.shutdown(drain=True, timeout=600)

    def _run_loop(self) -> list[dict]:
        self.maybe_resume()
        for step in range(self.start_step, self.cfg.total_steps):
            with jax.profiler.StepTraceAnnotation("train.step",
                                                  step_num=step):
                self._run_step(step)
        # finalize: drain pending checkpoint I/O (paper Listing 1.2 note:
        # finalize spins progress until all async tasks complete)
        if self._pending_ckpt is not None:
            self.engine.wait(self._pending_ckpt, timeout=600)
        return self.metrics_log

    def _run_step(self, step: int) -> None:
        """One iteration of the loop: fetch, dispatch, wait, log.  Each
        part is a profiler span (``train.*``) on the device trace's
        clock."""
        with jax.profiler.TraceAnnotation("train.batch", step=step):
            batch = self.pipeline.next_batch()     # warm path: no block
        t0 = time.monotonic()
        self.watchdog.arm()
        with jax.profiler.TraceAnnotation("train.dispatch", step=step):
            if self.split_step is not None:
                # engine-driven collective backend: dispatch local grads,
                # issue the nonblocking bucketed allreduce, and let the
                # engine overlap the reduction with prefetch/checkpoint
                # progress (and the tail of backward, still in flight)
                try:
                    metrics = self._split_step_once(batch)
                except MembershipError as exc:
                    if self.remesh_fn is None:
                        raise
                    # membership changed mid-step (dead peer or hung
                    # collective): rebuild the split step on survivors
                    # and retry THIS step's batch.  Params were not yet
                    # updated, so the retried step computes exactly what
                    # a from-checkpoint restart at this step would.  An
                    # in-flight FSDP prefetch died with the old epoch —
                    # drop it; the retry re-gathers on the new mesh.
                    self._pending_gather = None
                    self.split_step, self.params, self.opt_state = \
                        self.remesh_fn(exc, self.params, self.opt_state)
                    self.recoveries += 1
                    self._hung = False
                    self.watchdog.arm()
                    metrics = self._split_step_once(batch)
            else:
                # nonblocking dispatch — jit returns before device finishes
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch)
        loss_req = jax_future(self.engine, metrics)

        # overlap window: drive collated progress until device done
        # (with progress workers attached, wait yields to them instead)
        with jax.profiler.TraceAnnotation("train.wait", step=step):
            self.engine.wait(loss_req)
        self.watchdog.disarm()
        dur = time.monotonic() - t0
        self.straggler.record("self", dur)

        if (step + 1) % self.cfg.checkpoint_every == 0 \
                or step == self.cfg.total_steps - 1:
            # async save: stages progress inside future loop iterations
            self._pending_ckpt = self.ckpt.save_async(
                step, {"params": self.params, "opt_state": self.opt_state})

        if step % self.cfg.log_every == 0 or step == self.cfg.total_steps - 1:
            with jax.profiler.TraceAnnotation("train.log", step=step):
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                m["step"] = step
                m["step_time_s"] = dur
                self.metrics_log.append(m)
                for hook in self.hooks:
                    hook(step, m)
        if self._hung:
            raise RuntimeError("watchdog: step exceeded wall-clock limit")
