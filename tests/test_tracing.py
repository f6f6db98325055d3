"""Profiler spans inside the serving engine and the trainer.

The engine and the trainer mark their boundaries with
``jax.profiler.TraceAnnotation`` spans, which land in the profiler's own
trace beside the device's events.  These tests run a tiny ``ServeEngine``
and a 3-step ``Trainer`` under ``jax.profiler.start_trace`` and read the
spans back from the written ``.xplane.pb``: every span is there with the
arguments that tie it to its cause, the spans agree with the engine's
counters, and with the profiler off the same run serves the same tokens.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProgressEngine
from repro.data.pipeline import PrefetchPipeline, SyntheticLM
from repro.models import registry
from repro.serve.engine import GenRequest, ServeEngine
from repro.train import optimizer as opt_mod
from repro.train.train_loop import Trainer, TrainLoopConfig
from conftest import reduce_cfg

TRAIN_STEPS = 3


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    args: dict


def _read_spans(trace_dir) -> list[Span]:
    """The host spans of the newest trace under ``trace_dir`` whose name
    starts with ``serve.`` or ``train.``, in order of start."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "train.")):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return sorted(out, key=lambda s: s.start)


def _prompts(n, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab - 1, size=rng.randint(2, 12)).astype(
        np.int32) for _ in range(n)]


def _serve_once(srv, prompts):
    reqs = [GenRequest(f"r{i}", p, max_new_tokens=3 + i % 4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_idle(timeout=300)
    assert not srv.failures()
    return [list(r.out_tokens) for r in reqs]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One engine serves the same requests twice, the profiler off and
    then on.  Returns what the traced pass produced and read."""
    cfg = reduce_cfg(get_config("qwen2-0.5b"), dtype="float32")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    srv = ServeEngine(cfg, params, ProgressEngine(), batch_slots=4,
                      max_seq=32, prefill_chunk=4)
    prompts = _prompts(7, cfg.vocab_size)
    plain = _serve_once(srv, prompts)
    steps0, lanes0 = srv.steps, srv.sched.decode_lanes
    prefills0, tokens0 = srv.sched.prefill_calls, srv.sched.prefill_tokens
    trace_dir = tmp_path_factory.mktemp("serve_trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        traced = _serve_once(srv, prompts)
    finally:
        jax.profiler.stop_trace()
    out = dict(prompts=prompts, plain=plain, traced=traced,
               spans=_read_spans(trace_dir),
               steps=srv.steps - steps0,
               decode_lanes=srv.sched.decode_lanes - lanes0,
               prefill_calls=srv.sched.prefill_calls - prefills0,
               prefill_tokens=srv.sched.prefill_tokens - tokens0,
               sched=srv.scheduler_snapshot())
    srv.close(timeout=60)
    return out


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A 3-step trainer run under the profiler; returns its spans."""
    cfg = reduce_cfg(get_config("smollm-360m"), num_layers=2, d_model=32,
                     d_ff=64, vocab_size=64)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = opt_mod.AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=50)

    @jax.jit
    def step_fn(params, opt_state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        (loss, _), grads = jax.value_and_grad(
            lambda p: registry.loss_fn(p, cfg, batch), has_aux=True)(params)
        params, opt_state, om = opt_mod.apply(ocfg, opt_state, params, grads)
        return params, opt_state, dict(loss=loss, **om)

    eng = ProgressEngine()
    pipe = PrefetchPipeline(SyntheticLM(64, 16, 4, seed=3), eng, depth=2)
    ckpt = tmp_path_factory.mktemp("ckpt")
    loop = TrainLoopConfig(total_steps=TRAIN_STEPS, checkpoint_every=1000,
                           checkpoint_dir=str(ckpt), log_every=1,
                           resume=False)
    tr = Trainer(step_fn, params, opt_mod.init(params), pipe, loop,
                 engine=eng)
    trace_dir = tmp_path_factory.mktemp("train_trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        log = tr.run()
    finally:
        jax.profiler.stop_trace()
        pipe.close()
    assert len(log) == TRAIN_STEPS
    return _read_spans(trace_dir)


def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.mark.parametrize("name,args", [
    ("serve.admit", {"admitted"}),
    ("serve.prefill", {"call", "lanes", "tokens"}),
    ("serve.decode", {"call", "step", "lanes"}),
    ("serve.harvest", {"step"}),
    ("serve.sample", {"step"}),
    ("train.step", {"step_num"}),
    ("train.batch", {"step"}),
    ("train.dispatch", {"step"}),
    ("train.wait", {"step"}),
    ("train.log", {"step"}),
])
def test_span_appears_with_args(served, trained, name, args):
    spans = _named(served["spans"] if name.startswith("serve.")
                   else trained, name)
    assert spans, f"no {name} span in the trace"
    for s in spans:
        assert args <= set(s.args), (name, s.args)
        assert s.end >= s.start


def _sample_nests_in_harvest(r):
    harvests = _named(r["spans"], "serve.harvest")
    for s in _named(r["spans"], "serve.sample"):
        assert any(h.start <= s.start and s.end <= h.end
                   and h.args["step"] == s.args["step"] for h in harvests)
    assert len(_named(r["spans"], "serve.sample")) == len(harvests)


def _calls_dense_and_increasing(r):
    calls = [s.args["call"] for s in r["spans"]
             if s.name in ("serve.prefill", "serve.decode")]
    assert calls and calls == list(range(calls[0], calls[0] + len(calls)))
    assert calls[0] > 1        # numbered across the engine's lifetime


def _decode_spans_count_steps(r):
    decodes = _named(r["spans"], "serve.decode")
    assert len(decodes) == r["steps"] > 0
    steps = [s.args["step"] for s in decodes]
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    assert steps == [s.args["step"] for s in _named(r["spans"],
                                                    "serve.harvest")]


def _decode_lanes_are_tokens(r):
    emitted = sum(len(t) for t in r["traced"])
    assert r["decode_lanes"] == emitted
    assert sum(s.args["lanes"] for s in _named(r["spans"],
                                               "serve.decode")) == emitted
    assert f"{r['sched'].decode_lanes} decode lanes" in r["sched"].format()


def _prefill_spans_count_calls(r):
    prefills = _named(r["spans"], "serve.prefill")
    # with no preemption every prompt but its last token is prefilled,
    # and its last is the first decode step's input
    assert r["sched"].preemptions == 0
    assert len(prefills) == r["prefill_calls"] > 0
    assert sum(s.args["tokens"] for s in prefills) == \
        r["prefill_tokens"] == sum(len(p) - 1 for p in r["prompts"])
    assert f"({r['sched'].prefill_tokens} tokens)" in r["sched"].format()
    assert sum(s.args["admitted"] for s in _named(
        r["spans"], "serve.admit")) == len(r["prompts"])


def _profiler_off_serves_same_tokens(r):
    assert r["plain"] == r["traced"]
    assert all(r["plain"])


@pytest.mark.parametrize("check", [
    _sample_nests_in_harvest, _calls_dense_and_increasing,
    _decode_spans_count_steps, _decode_lanes_are_tokens,
    _prefill_spans_count_calls, _profiler_off_serves_same_tokens,
], ids=lambda f: f.__name__.strip("_"))
def test_serving_spans_agree_with_engine(served, check):
    check(served)


@pytest.mark.parametrize("name", ["train.batch", "train.dispatch",
                                  "train.wait", "train.log"])
def test_train_spans_nest_in_their_step(trained, name):
    steps = _named(trained, "train.step")
    assert [s.args["step_num"] for s in steps] == list(range(TRAIN_STEPS))
    inner = _named(trained, name)
    assert [s.args["step"] for s in inner] == list(range(TRAIN_STEPS))
    for s in inner:
        outer = steps[s.args["step"]]
        assert outer.start <= s.start and s.end <= outer.end
