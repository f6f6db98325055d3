"""Whole runs of tiny cells on the CPU (the look for a chip skipped):
a sound run is correct; a run whose timed path is broken underneath is
not; and the float8 control, put through the run's own comparison with
the committed limits, is not correct either."""
import pytest

from chipbench import control
from chipbench.run import run_cell
from conftest import TINY_OPEN, tiny_cell

SERVE = "qwen2-0.5b.decode"
TRAIN = "smollm-360m.train"


def _correct(workload, tamper=None, mix=None):
    bench, cell = tiny_cell(workload, tamper=tamper, mix=mix)
    result, _ = run_cell(bench, cell, on_chip=False)
    return result["correct"]


@pytest.mark.parametrize("workload,mix", [(SERVE, None), (SERVE, TINY_OPEN),
                                          (TRAIN, None)])
def test_sound_run_is_correct(workload, mix):
    assert _correct(workload, mix=mix)


def alter_tokens(srv):
    """Every token changed where it is produced."""
    produce = srv._next_ids
    srv._next_ids = lambda logits: (produce(logits) + 1) % srv.cfg.vocab_size


def state_unchanged(step_fn):
    """A step that computes its metrics and returns its state unchanged."""
    def step(params, opt_state, batch):
        return (params, opt_state) + tuple(step_fn(params, opt_state,
                                                   batch)[2:])
    return step


def half_batch(step_fn):
    """Half of each batch left out, the mean taken over the rest."""
    def step(params, opt_state, batch):
        return step_fn(params, opt_state,
                       {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return step


@pytest.mark.parametrize("mix", [None, TINY_OPEN])
def test_altered_tokens_are_not_correct(mix):
    assert not _correct(SERVE, alter_tokens, mix)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_training_faults_are_not_correct(fault):
    assert not _correct(TRAIN, fault)


def test_serve_control_is_not_correct():
    bench, cell = tiny_cell(SERVE)
    r = control.serve_readings(bench, cell, on_chip=False)
    assert r["correct"] and not r["control_correct"]
    assert r["fp8"] > r["program"]


def test_train_control_and_fault_are_not_correct():
    _, cell = tiny_cell(TRAIN)
    r = control.train_readings(cell)
    assert not r["fp8_correct"] and not r["half_batch_correct"]
