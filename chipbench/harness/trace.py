"""From a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and returns
plain lists; ``reduce`` does the arithmetic on them, so that tests can
feed it small hand-made traces.

* busy: the union of the intervals in which an operation ran on a
  device, inside the traced stretch (the host span ``bench.traced``);
* top device operations by summed duration;
* idle gaps: each stretch between busy intervals is charged to the
  innermost benchmark host span (``bench.*``) that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os

TRACED_SPAN = "bench.traced"


def load(trace_dir: str):
    """(device_ops, host_spans) from the newest trace under ``trace_dir``:
    ``device_ops`` maps each device plane's name to its operations
    ``[(name, start_ns, end_ns)]``; ``host_spans`` lists the host events
    whose name starts with ``bench.``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("bench."))
    return device_ops, host_spans


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _covering(spans, starts, t) -> str:
    """Name of the latest-starting span that covers time ``t``."""
    k = bisect.bisect_right(starts, t)
    for s, e, n in reversed(spans[max(0, k - 64):k]):
        if e >= t:
            return n
    return "outside bench spans"


def reduce(device_ops: dict, host_spans: list, top: int = 10) -> dict:
    """Seconds busy (averaged over devices), the traced stretch's length,
    and the ``top`` operations and idle gaps by seconds."""
    traced = [(s, e) for n, s, e in host_spans if n == TRACED_SPAN]
    if not traced:
        raise ValueError(f"no {TRACED_SPAN} span in the trace")
    lo, hi = min(s for s, _ in traced), max(e for _, e in traced)
    if not device_ops:
        raise ValueError("no device plane with XLA Ops in the trace")
    busy_ns, by_op, gaps = 0.0, {}, {}
    spans = sorted(((s, e, n) for n, s, e in host_spans
                    if n != TRACED_SPAN), key=lambda t: t[0])
    starts = [s for s, _, _ in spans]
    for ops in device_ops.values():
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        busy = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += _length(busy)
        for n, s, e in ops:
            # an XLA op's event name is its whole HLO line: keep the name
            short = n.split(" = ")[0]
            by_op[short] = by_op.get(short, 0.0) + (min(e, hi) - max(s, lo))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            name = _covering(spans, starts, (g0 + g1) / 2)
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0)
    n_dev = len(device_ops)
    rank = lambda d: sorted(([k, v / n_dev / 1e9] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / n_dev / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps)}
