"""Progress latency: median, over the decode calls in the traced
stretch, of the time from the end of the call's execution on the device
to the start of its ``serve.harvest`` span, where the progress engine has
noticed the step and runs its continuation."""
from chipbench.harness import program_trace


def read(run):
    t = program_trace.for_run(run)
    return None if t is None else t["serving"]["completion_notice_ms"]
