"""Median device idle time between consecutive training steps
(``jit_train_step`` executions) in the traced stretch: what the trainer's
host loop (notice, metrics read, hooks, batch fetch, dispatch) costs the
device per step.  Reading it also prints the training cell's idle-gap
table by program span (``harness/program_trace.py``)."""
from chipbench.harness import program_trace


def read(run):
    t = program_trace.for_run(run)
    return None if t is None else t["train_turnaround_ms"]
