"""Median host-clock interval between the completions of consecutive
decode steps inside the window."""
import statistics


def read(run):
    lo, hi = run["window_start_s"], run["window_end_s"]
    t = [s for s in run["step_s"] if lo <= s <= hi]
    d = [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    return statistics.median(d) if d else None
