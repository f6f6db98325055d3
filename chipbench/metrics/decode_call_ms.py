"""Median device time of one decode call of the serving program
(``jit_serve_call``) in the traced stretch.  Decode calls are told from
prefill calls, which run the same program, by the pairing rule of
``harness/program_trace.py``."""
from chipbench.harness import program_trace


def read(run):
    t = program_trace.for_run(run)
    return None if t is None else t["serving"]["decode_call_ms"]
