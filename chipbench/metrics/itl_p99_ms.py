"""99th percentile of the gaps between consecutive output tokens of a
request, over all requests: every gap of an open loop's requests, and
the gaps that lie inside the window of a closed loop."""
from chipbench.harness.traffic import percentile


def read(run):
    lo, hi = run["window_start_s"], run["window_end_s"]
    gaps = []
    for r in run["requests"]:
        t = r["token_s"]
        for a, b in zip(t, t[1:]):
            if not run["closed"] or (a >= lo and b <= hi):
                gaps.append((b - a) * 1e3)
    return percentile(gaps, 99) if gaps else None
