"""Median device time of one prefill call of the serving program
(``jit_serve_call``) in the traced stretch: every call that the pairing
rule of ``harness/program_trace.py`` does not pair with a decode step's
harvest."""
from chipbench.harness import program_trace


def read(run):
    t = program_trace.for_run(run)
    return None if t is None else t["serving"]["prefill_call_ms"]
