"""Serving cells (mix ``"driver": "serving"``): the program's
``ServeEngine`` on its paged pool, driven caller-side
(``engine.progress()`` in this thread, as the launcher does by default),
fed by an open loop (requests sent when due) or a closed loop (each
client sends its next request when the last completes), with the
requests that the mix's generator makes.

Every output token is stamped with the host clock after the
``progress()`` call that appended it; a request's time to first token
runs from when it was due, not from when it was submitted.  After the
window the program's state is freed and the plain reference scores a
sample of the served tokens (see ``check``).
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench.harness import program, traffic
from chipbench.harness.spec import generator, reference

SLEEP_S = 50e-6          # pause after a progress() call that did nothing


class _Requests:
    """What the harness saw of each request, beside the program's own
    ``GenRequest``."""

    def __init__(self):
        self.all = []            # (record, GenRequest) in submit order
        self.inflight = []

    def add(self, rec, g):
        self.all.append((rec, g))
        self.inflight.append((rec, g))

    def stamp(self, now: float) -> list:
        """Stamp new tokens; return the records that finished."""
        done, still = [], []
        for rec, g in self.inflight:
            n = len(g.out_tokens)
            if len(rec["token_s"]) < n:
                rec["token_s"].extend([now] * (n - len(rec["token_s"])))
            if g.done_req.is_complete:
                rec["done_s"] = now
                rec["failed"] = bool(g.done_req.failed)
                rec["queued_s"] = g.queued_s
                done.append(rec)
            else:
                still.append((rec, g))
        self.inflight = still
        return done

    def fed(self) -> dict:
        """Positions each request has fed through the model so far."""
        out = {}
        for rec, g in self.all:
            if g.out_tokens:
                out[rec["id"]] = len(g.prompt) - 1 + len(g.out_tokens)
            else:
                out[rec["id"]] = g.prefill_pos if g.slot_index >= 0 else 0
        return out


def _snapshot(srv, reqs: _Requests, now: float) -> dict:
    fed = reqs.fed()
    return {"t": now, "steps": srv.steps,
            "prefill_calls": srv.sched.prefill_calls,
            "preemptions": srv.sched.preemptions, "fed": fed,
            "tokens": sum(len(g.out_tokens) for _, g in reqs.all)}


def _stretch(a: dict, b: dict) -> dict:
    """Work between two snapshots: program calls, positions fed, the sum
    over those positions of the keys each attends to, tokens emitted."""
    positions = context = 0
    for rid, hi in b["fed"].items():
        lo = a["fed"].get(rid, 0)
        positions += hi - lo
        context += (hi * (hi + 1) - lo * (lo + 1)) // 2
    return {"seconds": b["t"] - a["t"],
            "decode_steps": b["steps"] - a["steps"],
            "calls": (b["steps"] - a["steps"]
                      + b["prefill_calls"] - a["prefill_calls"]),
            "positions": positions, "context_sum": context,
            "decode_tokens": b["tokens"] - a["tokens"],
            "preemptions": b["preemptions"] - a["preemptions"]}


def build(cell):
    """The served model with weights from the seed, warmed up on one
    short request.  Returns (srv, engine)."""
    import jax
    from repro.core import ProgressEngine
    from repro.serve.engine import GenRequest, ServeEngine

    c, dep = cell.cfg, cell.cfg["deployment"]["serve"]
    mc = program.model_config(c)
    init = jax.jit(lambda k: program.program_tree(
        c, reference(c).init_weights(c, k)))
    key = jax.random.PRNGKey(traffic.device_seed(cell.seed))
    program.check_layout(mc, jax.eval_shape(init, key))
    params = jax.block_until_ready(init(key))
    eng = ProgressEngine()
    srv = ServeEngine(mc, params, eng, batch_slots=dep["lanes"],
                      max_seq=dep["max_seq"],
                      kv_block_size=dep["kv_block_size"],
                      prefill_chunk=dep["prefill_chunk"])
    if cell.tamper is not None:
        cell.tamper(srv)
    # the one program shape the window uses ([lanes, 1] tokens), and the
    # host-side argmax, compile here
    warm = GenRequest("warmup", np.ones(3, np.int32), max_new_tokens=2)
    srv.submit(warm)
    srv.run_until_idle(timeout=1200)
    if srv.failures() or len(warm.out_tokens) != 2:
        raise RuntimeError(f"warm-up request failed: {srv.failures()}")
    return srv, eng


def run(cell, tracer) -> dict:
    """Serve the cell's traffic; returns the run record."""
    from repro.serve.engine import GenRequest

    srv, eng = build(cell)
    mix, vocab = cell.mix, cell.cfg["vocab_size"]
    closed = mix["loop"] == "closed"
    reqs = _Requests()
    rec_run = {"setup_s": time.monotonic() - cell.t_start,
               "lateness_s": [], "step_s": []}

    def submit(i, item, due, now):
        g = GenRequest(f"r{i}", item["prompt"], max_new_tokens=item["max_new"])
        srv.submit(g)
        rec = {"id": i, "due_s": due, "submit_s": now, "token_s": [],
               "done_s": None, "failed": False, "queued_s": None,
               "prompt_len": len(item["prompt"]), "max_new": item["max_new"]}
        reqs.add(rec, g)
        rec_run["lateness_s"].append(now - due)

    seconds = cell.seconds
    trace_off = seconds - mix["trace_seconds"] if cell.trace else None
    t0 = time.monotonic()
    clock = lambda: time.monotonic() - t0          # noqa: E731
    queue = generator(mix).requests(mix, cell.seed, vocab, seconds)
    if closed:
        for i in range(mix["clients"]):
            submit(i, queue[i], 0.0, 0.0)
        nxt, w0 = mix["clients"], None
        first = [rec for rec, _ in reqs.all]
    else:
        nxt, w0 = 0, 0.0
    snaps, last_steps = {}, srv.steps
    if not closed:
        snaps["start"] = _snapshot(srv, reqs, 0.0)
        compiles = tracer.compiles
    deadline = seconds + mix.get("drain_limit_s", 0.0)
    span = tracer.span
    while True:
        now = clock()
        if not closed and nxt < len(queue) and queue[nxt]["due_s"] <= now:
            with span("bench.submit"):
                while nxt < len(queue) and queue[nxt]["due_s"] <= now:
                    submit(nxt, queue[nxt], queue[nxt]["due_s"], clock())
                    nxt += 1
        with span("bench.progress"):
            made = eng.progress()
        if not made:
            with span("bench.sleep"):
                time.sleep(SLEEP_S)
        if srv.steps != last_steps:
            last_steps = srv.steps
            now = clock()
            rec_run["step_s"].append(now)
            with span("bench.stamp"):
                for _ in reqs.stamp(now):
                    if not closed or "end" in snaps:
                        continue
                    if nxt >= len(queue):
                        raise RuntimeError("closed-loop pool exhausted")
                    submit(nxt, queue[nxt], now, now)
                    nxt += 1
            if closed and w0 is None and all(r["token_s"] for r in first):
                w0 = now
                snaps["start"] = _snapshot(srv, reqs, now)
                compiles = tracer.compiles
        now = clock()
        if (trace_off is not None and w0 is not None
                and now >= w0 + trace_off and "trace" not in snaps):
            tracer.start()
            snaps["trace"] = _snapshot(srv, reqs, now)
        if w0 is not None and now >= w0 + seconds and "end" not in snaps:
            snaps["end"] = _snapshot(srv, reqs, now)
            rec_run["window_compiles"] = tracer.compiles - compiles
            if "trace" in snaps:
                tracer.stop()
            if closed:
                break
        if ("end" in snaps and nxt >= len(queue)
                and (not reqs.inflight or now > deadline)):
            break
    rec_run["window_s"] = snaps["end"]["t"] - snaps["start"]["t"]
    rec_run["window"] = _stretch(snaps["start"], snaps["end"])
    if "trace" in snaps:
        rec_run["traced"] = _stretch(snaps["trace"], snaps["end"])
    rec_run["window_start_s"], rec_run["window_end_s"] = (
        snaps["start"]["t"], snaps["end"]["t"])
    rec_run["requests"] = [rec for rec, _ in reqs.all]
    rec_run["closed"] = closed
    failures = srv.failures()
    # what the check reads: prompts and served tokens of every request
    # that has tokens and did not fail.  A closed loop stops at the close
    # of its window, so most of its requests are still answering: their
    # served tokens so far are scored like a finished answer's.
    served = [(rec, np.asarray(g.prompt), list(g.out_tokens))
              for rec, g in reqs.all if g.out_tokens and not rec["failed"]]
    unfinished = 0 if closed else sum(1 for rec, _ in reqs.all
                                      if rec["done_s"] is None)
    rec_run["memory_peak_bytes"] = tracer.memory_peak()
    del srv, eng, reqs
    gc.collect()
    rec_run["check"], rec_run["check_items"] = check(
        cell, served, unfinished, len(failures))
    return rec_run


def attempted(run: dict) -> int:
    """Requests sent."""
    return len(run["requests"])


def failed(run: dict) -> int:
    """Requests the engine failed; in an open loop also those that never
    finished.  A closed loop's requests still answering when its window
    closes are cut by the window, not failed."""
    return sum(1 for r in run["requests"]
               if r["failed"] or (not run["closed"] and r["done_s"] is None))


def report(run: dict) -> dict:
    late = sorted(run["lateness_s"])
    w = run["window"]
    return {"requests": len(run["requests"]),
            "finished": sum(1 for r in run["requests"]
                            if r["done_s"] is not None),
            "ramp_s": run["window_start_s"],
            "generator_late_max_s": late[-1] if late else None,
            "decode_call_share": (w["decode_steps"] / w["calls"]
                                  if w["calls"] else None)}


def sample(cell, served: list) -> list:
    """The requests the check scores: the one with the most positions
    and ``check_requests - 1`` more drawn from the seed."""
    if not served:
        return []
    k = min(cell.mix["check_requests"], len(served))
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i][1]) + len(served[i][2])))
    rest = order[1:]
    rng = traffic.rng_for(cell.seed, "check")
    pick = [order[0]] + list(rng.choice(rest, size=k - 1, replace=False)
                             if k > 1 else [])
    return [served[i] for i in pick]


def gaps(cell, items: list, quant: str | None = None) -> np.ndarray:
    """For each item (prompt, served tokens), at each served position, how
    far the reference's logit of the chosen token lies below its best.
    The chosen token is the served one, or with ``quant`` the one the
    reference computed at that precision puts first."""
    import jax
    import jax.numpy as jnp
    c, ref = cell.cfg, reference(cell.cfg)
    L = cell.cfg["deployment"]["serve"]["max_seq"]

    @jax.jit
    def score(w, seq, nxt):
        lg = ref.logits(c, w, seq[None])[0]
        pick = nxt if quant is None else jnp.argmax(
            ref.logits(c, w, seq[None], quant)[0], axis=-1)
        best = jnp.max(lg, axis=-1)
        return best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    key = jax.random.PRNGKey(traffic.device_seed(cell.seed))
    w = jax.jit(lambda k: ref.init_weights(c, k))(key)
    out = []
    for prompt, served in items:
        full = np.concatenate([prompt, np.asarray(served, np.int32)])
        seq = np.zeros(L, np.int32)
        nxt = np.zeros(L, np.int32)
        seq[:len(full) - 1] = full[:-1]
        nxt[:len(full) - 1] = full[1:]
        g = np.asarray(score(w, jnp.asarray(seq), jnp.asarray(nxt)))
        out.append(g[len(prompt) - 1:len(full) - 1])
    return np.concatenate(out) if out else np.zeros(0)


def check(cell, served: list, unfinished: int, failures: int):
    """Numbers compared, each ``[value, limit]``: the widest logit gap of
    the sampled served tokens, requests of an open loop that never
    finished, and failures the engine recorded.  Also returns the scored
    (prompt, tokens)."""
    items = [(p, s) for _, p, s in sample(cell, served)]
    g = gaps(cell, items)
    widest = float(g.max()) if g.size else None
    print(f"check: {len(items)} requests, {g.size} served tokens scored "
          f"against the reference", file=sys.stderr)
    return ({"logit_gap": [widest, cell.cfg["limits"]["serve_logit_gap"]],
             "unfinished": [unfinished, 0], "engine_failures": [failures, 0]},
            items)
