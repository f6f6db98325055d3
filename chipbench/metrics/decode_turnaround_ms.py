"""Median, over the decode calls in the traced stretch, of the device's
idle time from the end of the call's execution to the start of the next
serving call's: what the host's serialisation of steps (notice, argmax
read, admission, dispatch) costs the device per decode step."""
from chipbench.harness import program_trace


def read(run):
    t = program_trace.for_run(run)
    return None if t is None else t["serving"]["decode_turnaround_ms"]
