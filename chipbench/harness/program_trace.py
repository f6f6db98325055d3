"""The program's own spans beside the device's executions, on the
profiler's one clock.

The serving engine and the trainer mark their boundaries with
``jax.profiler.TraceAnnotation`` spans (``serve.*``, ``train.*``), and the
serving program is jitted as ``serve_call``, so that the device trace
names its module ``jit_serve_call``.  ``load`` reads the newest
``.xplane.pb`` under a trace directory (``run.py`` traces into
``chipbench/out/trace``) into plain lists; the functions below it do the
arithmetic, so that tests can feed them small hand-made traces:

* ``serving``: the pairing rule.  A decode step is never dispatched while
  another serving call is in flight, and nothing is dispatched while a
  step is in flight, so of the ``jit_serve_call`` executions the one that
  ends last before a ``serve.harvest`` span starts is that step's decode
  call; every other one is a prefill call.  From the pairs: device time
  per call, progress latency (end of a decode call to the start of its
  harvest) and turnaround (end of a decode call to the start of the next
  call).
* ``idle_gaps``: the traced stretch's idle device time, each gap charged
  to the innermost span covering its midpoint, a program span where one
  covers it and else the harness's (``bench.*``).
* ``scope_time``: device time by the op's top-level ``jax.named_scope``,
  from the scope path XLA keeps for each op (``tf_op``); time that a
  nested op accounts for is its own, not its parent's.

``for_run`` does all of it once per traced run, keeps it in the run record
for the metrics that read it, and prints the two tables to standard
error.  Where the program has no such spans or module (an older program)
the metrics read ``None`` and the tables still print.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import sys

from chipbench.harness import trace as tr

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "trace")
PROGRAM_PREFIXES = ("serve.", "train.")
SERVE_MODULE = "jit_serve_call"
TRAIN_MODULE = "jit_train_step"
OUTSIDE = "outside every span"
NO_SCOPE = "no scope"
# path components of a tf_op that JAX adds itself and no named_scope makes
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint",
              "rematted_computation", "remat", "scan", "shard_map",
              "custom_jvp_call", "custom_vjp_call", "pjit"}
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")
_IDENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*$")


def newest(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str = TRACE_DIR) -> dict:
    """From the newest trace under ``trace_dir``:

    * ``spans``: ``[(name, start_ns, end_ns, args)]`` of the host events
      named ``serve.*``, ``train.*`` or ``bench.*``, by start;
    * ``executions``: for each device plane, ``[(module, start_ns,
      end_ns)]`` from its ``XLA Modules`` line, the module's name without
      the ``(<program id>)`` that the chip appends;
    * ``ops``: for each device plane, ``[(op, start_ns, end_ns)]`` from
      its ``XLA Ops`` line;
    * ``op_paths``: each op's scope path (``tf_op``), by op name.
    """
    import jax

    with open(newest(trace_dir), "rb") as f:
        buf = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(buf)
    spans, executions, ops = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    executions[plane.name] = [
                        (module_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     {k: v for k, v in e.stats})
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIXES + ("bench.",)))
    return {"spans": sorted(spans, key=lambda s: s[1]),
            "executions": executions, "ops": ops, "op_paths": _op_paths(buf)}


def module_name(event_name: str) -> str:
    """``jit_serve_call(1545...)`` -> ``jit_serve_call``."""
    return re.sub(r"\(\d+\)$", "", event_name)


# -- the scope path of each op -------------------------------------------------
# ``ProfileData`` gives an event's own stats but not those of its metadata,
# which is where the chip keeps ``tf_op``.  The few fields needed are read
# from the protobuf's wire format (XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
# .str_value = 5).

def _varint(buf: bytes, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of the message in ``buf[lo:hi]``; the value of a
    length-delimited field is its ``(start, end)`` in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 1:
            v, i = (i, i + 8), i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf, entry):
    """The value message's extent of a map entry (``value = 2``)."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return (entry[1], entry[1])


def _op_paths(buf: bytes) -> dict:
    """``{op name: tf_op}`` over the device planes of a serialized
    XSpace."""
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events.append(_map_value(buf, v))
            elif g == 5:
                meta = dict(_fields(buf, *_map_value(buf, v)))
                if 2 in meta:
                    stat_names[meta.get(1, 0)] = _text(buf, meta[2])
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for ev in events:
            op = path = None
            for g, v in _fields(buf, *ev):
                if g == 2:
                    op = _text(buf, v)
                elif g == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1, 0) in tf_op and 5 in stat:
                        path = _text(buf, stat[5])
            if op is not None and path is not None:
                out[op] = path
    return out


def top_scope(path: str) -> str:
    """The outermost ``jax.named_scope`` in an op's scope path:
    ``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
    attention/dot_general`` -> ``attention``.  Programs (``jit(...)``),
    the structure JAX adds, transformation wrappers and einsum specs are
    passed over, and the last component (the primitive) is not a scope."""
    for part in path.split("/")[:-1]:
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if (part and not part.startswith(("jit(", "pjit("))
                and part not in _STRUCTURE and _IDENT.match(part)):
            return part
    return NO_SCOPE


# -- arithmetic on plain lists -------------------------------------------------

def traced_stretch(spans) -> tuple:
    traced = [(s, e) for n, s, e, _ in spans if n == tr.TRACED_SPAN]
    if not traced:
        raise ValueError(f"no {tr.TRACED_SPAN} span in the trace")
    return min(s for s, _ in traced), max(e for _, e in traced)


def _median_ms(values_ns):
    return statistics.median(values_ns) / 1e6 if values_ns else None


def serving(executions, spans, module: str = SERVE_MODULE) -> dict:
    """The pairing rule over one device's executions inside the traced
    stretch; counts, medians in ms, and the calls' summed device
    seconds."""
    lo, hi = traced_stretch(spans)
    calls = sorted((s, e) for n, s, e in executions
                   if n == module and s >= lo and e <= hi)
    ends = [e for _, e in calls]
    harvests = sorted(s for n, s, _, _ in spans if n == "serve.harvest")
    decode, last = {}, -1            # call index -> its harvest's start
    for h in harvests:
        i = bisect.bisect_left(ends, h) - 1
        if i > last:
            decode[i], last = h, i
    dec = sorted(decode)
    pre = [c for i, c in enumerate(calls) if i not in decode]
    return {
        "decode_calls": len(dec), "prefill_calls": len(pre),
        "calls_s": sum(e - s for s, e in calls) / 1e9,
        "decode_call_ms": _median_ms([calls[i][1] - calls[i][0]
                                      for i in dec]),
        "prefill_call_ms": _median_ms([e - s for s, e in pre]),
        "completion_notice_ms": _median_ms([decode[i] - calls[i][1]
                                            for i in dec]),
        "decode_turnaround_ms": _median_ms([
            calls[i + 1][0] - calls[i][1] for i in dec
            if i + 1 < len(calls)]),
    }


def turnaround_ms(executions, spans, module: str = TRAIN_MODULE):
    """Median device idle between consecutive executions of ``module``
    inside the traced stretch, in ms."""
    lo, hi = traced_stretch(spans)
    calls = sorted((s, e) for n, s, e in executions
                   if n == module and s >= lo and e <= hi)
    return _median_ms([b[0] - a[1] for a, b in zip(calls, calls[1:])])


def idle_gaps(ops, spans) -> list:
    """``[[span name, seconds]]`` of the traced stretch's idle device
    time (averaged over devices), each gap charged to the innermost span
    covering its midpoint: a program span, else a harness span."""
    lo, hi = traced_stretch(spans)
    inner = sorted(((s, e, n) for n, s, e, _ in spans
                    if n != tr.TRACED_SPAN), key=lambda t: t[0])
    starts = [s for s, _, _ in inner]
    gaps = {}
    for device_ops in ops.values():
        busy = tr.union(tr._clip([(s, e) for _, s, e in device_ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = tr._covering(inner, starts, (g0 + g1) / 2)
                name = OUTSIDE if name == "outside bench spans" else name
                gaps[name] = gaps.get(name, 0.0) + (g1 - g0)
    n = max(len(ops), 1)
    return sorted(([k, v / n / 1e9] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])


def scope_time(ops, op_paths, spans) -> list:
    """``[[scope, seconds]]`` of device time inside the traced stretch by
    top-level named scope (averaged over devices).  Ops nest (a loop's
    body ops lie inside the loop's event), so each op counts its own
    time: its interval less the ops nested in it."""
    lo, hi = traced_stretch(spans)
    by_scope = {}
    for device_ops in ops.values():
        evs = sorted(((max(s, lo), min(e, hi), n) for n, s, e in device_ops
                      if e > lo and s < hi), key=lambda t: (t[0], -t[1]))
        own = [e - s for s, e, _ in evs]
        stack = []                   # indices of the open ancestors
        for i, (s, e, _) in enumerate(evs):
            while stack and evs[stack[-1]][1] <= s:
                stack.pop()
            if stack:
                own[stack[-1]] -= e - s
            stack.append(i)
        for (s, e, name), t in zip(evs, own):
            scope = top_scope(op_paths.get(name, ""))
            by_scope[scope] = by_scope.get(scope, 0.0) + t
    n = max(len(ops), 1)
    return sorted(([k, v / n / 1e9] for k, v in by_scope.items()),
                  key=lambda kv: -kv[1])


def _table(title: str, rows: list, window_s: float) -> str:
    lines = [title]
    for name, secs in rows:
        share = 100.0 * secs / window_s if window_s > 0 else 0.0
        lines.append(f"  {name:<28} {secs:.6f} s  {share:.3f}% of the "
                     f"stretch")
    return "\n".join(lines)


def for_run(run: dict, trace_dir: str = TRACE_DIR):
    """The program trace of a traced run: read once, kept in the run
    record, its tables printed to standard error.  ``None`` when the run
    was not traced."""
    if not run.get("trace"):
        return None
    if "program_trace" not in run:
        t = load(trace_dir)
        spans = t["spans"]
        lo, hi = traced_stretch(spans)
        devices = sorted(t["executions"])
        execs = t["executions"][devices[0]] if devices else []
        out = {"serving": serving(execs, spans),
               "train_turnaround_ms": turnaround_ms(execs, spans),
               "idle_gaps": idle_gaps(t["ops"], spans),
               "scope_time": scope_time(t["ops"], t["op_paths"], spans)}
        window = (hi - lo) / 1e9
        print(_table("program trace: idle device time by innermost span",
                     out["idle_gaps"], window), file=sys.stderr)
        print(_table("program trace: device time by top-level named scope",
                     out["scope_time"], window), file=sys.stderr)
        srv, busy = out["serving"], run["trace"]["busy_s"]
        seen = run.get("traced", {})
        if srv["decode_calls"] + srv["prefill_calls"]:
            print(f"program trace: {srv['decode_calls']} decode calls paired "
                  f"(traced.decode_steps {seen.get('decode_steps')}), "
                  f"{srv['decode_calls'] + srv['prefill_calls']} calls in "
                  f"all (traced.calls {seen.get('calls')}), their device "
                  f"time {100.0 * srv['calls_s'] / busy:.2f}% of busy_s; "
                  f"{srv}; traced stretch {seen}", file=sys.stderr)
        run["program_trace"] = out
    return run["program_trace"]
