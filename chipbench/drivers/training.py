"""Training cells on one program (mix ``"driver": "training"``): the
program's ``Trainer`` over the step that ``launch/steps.build_cell``
builds, as ``launch/train.run`` assembles it, fed the batches that the
mix's generator draws from the seed.  A run over other programs (the
FSDP split step, say) is a driver of its own.

Set-up builds one trainer and drives it through its first
``check_steps`` steps (the first compiles); the window hands that same
trainer on.  A hook that the trainer calls after every step reads what
the check needs and ends each phase.  The trainer saves no checkpoint:
its save interval lies past every step a run makes.

After the window the program's state is freed and the plain reference
repeats the first steps from the same weights and batches (``check``).
"""
from __future__ import annotations

import gc
import itertools
import os
import time

import numpy as np

from chipbench.harness import program, traffic
from chipbench.harness.spec import BENCH_DIR, generator, reference

NEVER = 1 << 40


class _PhaseDone(Exception):
    """Raised from the trainer's hook to end a phase."""


def run(cell, tracer) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.configs.shapes import ShapeSpec
    from repro.core import ProgressEngine
    from repro.data.pipeline import PrefetchPipeline
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell
    from repro.train import optimizer as opt_mod
    from repro.train.train_loop import Trainer, TrainLoopConfig

    c, job = cell.cfg, cell.mix
    B, S, V = job["global_batch"], job["seq_len"], c["vocab_size"]
    mc = program.model_config(c)
    mesh = make_mesh(tuple(job["mesh"]), ("data", "model"))
    ocfg = opt_mod.AdamWConfig(**job["optimizer"])
    cellp = build_cell(mc, ShapeSpec("train", seq_len=S, global_batch=B,
                                     kind="train"), mesh, opt_cfg=ocfg)
    jitted = jax.jit(cellp.step_fn, in_shardings=cellp.in_shardings,
                     out_shardings=cellp.out_shardings)
    key = jax.random.PRNGKey(traffic.device_seed(cell.seed))
    ref = reference(c)
    init = jax.jit(lambda k: program.program_tree(c, ref.init_weights(c, k)),
                   out_shardings=cellp.in_shardings[0])
    program.check_layout(mc, jax.eval_shape(init, key))
    with compat.set_mesh(mesh):
        params = init(key)
        opt_state = jax.device_put(opt_mod.init(params),
                                   cellp.in_shardings[1])
    b_shard = cellp.in_shardings[2]

    eng = ProgressEngine()
    to_batch = lambda b: {k: jnp.asarray(v) for k, v in b.items()}  # noqa
    pipe = PrefetchPipeline(map(to_batch, generator(job).batches(
        job, cell.seed, V)), eng, depth=3)

    def step_fn(params, opt_state, batch):
        batch = {k: jax.device_put(v, b_shard[k]) for k, v in batch.items()}
        return jitted(params, opt_state, batch)

    if cell.tamper is not None:
        step_fn = cell.tamper(step_fn)
    b1 = ocfg.b1
    grad_norms = jax.jit(lambda mu: program.leaf_norms(
        c, jax.tree.map(lambda m: m / (1 - b1), mu)))
    change_norms = jax.jit(lambda p, k: program.leaf_norms(c, jax.tree.map(
        jnp.subtract, p, program.program_tree(c, ref.init_weights(c, k)))))

    state = {"phase": "check", "losses": [], "step_s": [], "bad": 0}
    n_check, n_trace = job["check_steps"], job["trace_steps"]

    def hook(step, m):
        now = time.monotonic()
        if state["phase"] == "check":
            state["losses"].append(m["loss"])
            if step == 0:
                state["grad"] = {k: float(v) for k, v in
                                 grad_norms(trainer.opt_state.mu).items()}
            if step == n_check - 1:
                state["change"] = {k: float(v) for k, v in
                                   change_norms(trainer.params, key).items()}
                raise _PhaseDone
        elif state["phase"] == "window":
            state["step_s"].append(now)
            state["bad"] += not np.isfinite(m["loss"])
            if now - state["w0"] >= cell.seconds:
                raise _PhaseDone
        elif state["phase"] == "trace":
            state["traced"] += 1
            if state["traced"] == n_trace:
                raise _PhaseDone

    loop_cfg = TrainLoopConfig(
        total_steps=NEVER, checkpoint_every=NEVER, log_every=1,
        resume=False, checkpoint_dir=os.path.join(BENCH_DIR, "out", "ckpt"))
    trainer = Trainer(step_fn, params, opt_state, pipe, loop_cfg,
                      engine=eng, hooks=[hook])
    del params, opt_state

    def phase(name: str, first_step: int):
        state["phase"] = name
        trainer.start_step = first_step
        try:
            trainer.run()
        except _PhaseDone:
            return
        raise RuntimeError(f"trainer stopped before the {name} phase ended")

    phase("check", 0)
    rec = {"setup_s": time.monotonic() - cell.t_start}
    compiles = tracer.compiles
    state["w0"] = time.monotonic()
    phase("window", n_check)
    steps = len(state["step_s"])
    rec["window_compiles"] = tracer.compiles - compiles
    rec["window_s"] = state["step_s"][-1] - state["w0"]
    rec["window"] = {"steps": steps, "tokens": steps * B * S,
                     "seconds": rec["window_s"], "failed_steps": state["bad"]}
    rec["step_s"] = [state["w0"]] + state["step_s"]
    if cell.trace:
        state["traced"] = 0
        t = time.monotonic()
        tracer.start()
        phase("trace", n_check + steps)
        tracer.stop()
        rec["traced"] = {"steps": n_trace, "tokens": n_trace * B * S,
                         "seconds": time.monotonic() - t}
    rec["memory_peak_bytes"] = tracer.memory_peak()
    program_side = {"loss": state["losses"][:n_check],
                    "grad": state["grad"], "change": state["change"]}
    pipe.close()
    del trainer, jitted, pipe
    gc.collect()
    rec["check"] = check(cell, program_side)
    return rec


def reference_steps(cell, rows_of_batch=None, quant=None) -> dict:
    """The plain reference over the run's first ``check_steps`` batches:
    each step's loss, per-leaf norms of the first clipped gradient, and
    of the weights' change after the last step.  ``rows_of_batch`` keeps
    only the first that many rows of each batch (a fault to plant)."""
    import jax
    import jax.numpy as jnp
    c, job = cell.cfg, cell.mix
    ref = reference(c)
    o = job["optimizer"]
    rows = job["reference_rows"]
    key = jax.random.PRNGKey(traffic.device_seed(cell.seed))
    w0 = jax.jit(lambda k: ref.init_weights(c, k))(key)
    lg = jax.jit(lambda w, t, l: ref.loss_and_grad(
        c, w, t, l, rows, quant))
    # the moments and the gradient are donated: the update fits beside
    # the first weights, which the change is measured from
    step = jax.jit(lambda s, w, m, v, g: ref.adamw_step(o, s, w, m, v, g),
                   donate_argnums=(2, 3, 4))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in t.items()})
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    out = {"loss": []}
    batches = generator(job).batches(job, cell.seed, c["vocab_size"])
    for s, b in enumerate(itertools.islice(batches, job["check_steps"]), 1):
        t, lab = b["tokens"], b["labels"]
        if rows_of_batch is not None:
            t, lab = t[:rows_of_batch], lab[:rows_of_batch]
        loss, g = lg(w, jnp.asarray(t), jnp.asarray(lab))
        w, m, v, g = step(jnp.asarray(s), w, m, v, g)
        out["loss"].append(float(loss))
        if s == 1:
            out["grad"] = {k: float(x) for k, x in norms(g).items()}
    out["change"] = {k: float(x) for k, x in norms(
        jax.tree.map(jnp.subtract, w, w0)).items()}
    return out


def attempted(run: dict) -> int:
    """Steps completed in the window."""
    return run["window"]["steps"]


def failed(run: dict) -> int:
    """Steps in the window whose loss was not finite."""
    return run["window"]["failed_steps"]


def report(run: dict) -> dict:
    """Step intervals in the window: a run that counts fewer steps shows
    whether one step stalled or all ran slower."""
    t = run["step_s"]
    d = sorted((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return {"step_ms_median": d[len(d) // 2], "step_ms_max": d[-1],
            "steps_over_1_5x_median": sum(x > 1.5 * d[len(d) // 2]
                                          for x in d)}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's and the reference's
    readings.  Leaves whose reference gradient is under a thousandth of
    the median leaf's move by round-off alone and are left out."""
    med_g = float(np.median(list(ref["grad"].values())))
    live = [k for k, g in ref["grad"].items() if g >= 1e-3 * med_g]

    def worst(a: dict, b: dict) -> float:
        med = float(np.median([b[k] for k in live]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in live)

    return {"loss_gap": max(abs(x - y) for x, y in
                            zip(prog["loss"], ref["loss"])),
            "grad_norm_gap": worst(prog["grad"], ref["grad"]),
            "change_norm_gap": worst(prog["change"], ref["change"])}


def check(cell, prog: dict) -> dict:
    lim = cell.cfg["limits"]
    got = gaps(prog, reference_steps(cell))
    return {k: [v, lim[f"train_{k}"]] for k, v in got.items()}
