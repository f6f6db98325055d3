"""The program-trace readers on hand-made traces: the pairing rule of
serving calls, progress latency and turnaround, idle gaps charged to the
innermost program span, device time by named scope, and the scope paths
read from a serialized trace; then a real CPU trace of a tiny serving
run read through ``program_trace.load``."""
import numpy as np
import pytest

from chipbench.harness import program_trace as pt
from chipbench.harness import spec

MS = 1_000_000                       # ns


def _traced(lo, hi):
    return ("bench.traced", lo * MS, hi * MS, {})


def _span(name, s, e, **args):
    return (name, s * MS, e * MS, args)


def _call(s, e, module=pt.SERVE_MODULE):
    return (module, s * MS, e * MS)


# -- the pairing rule ------------------------------------------------------------

def test_pairing_with_prefill_before_and_after_decode():
    # a chunk of two prefill calls queued ahead of decode step 7; its
    # harvest dispatches a prefill call and step 8 behind it
    execs = [_call(0, 10), _call(10, 20), _call(20, 30),
             _call(30.5, 31, "jit__argmax"),
             _call(34, 44), _call(44, 54), _call(60, 61, "jit__argmax")]
    spans = [_traced(0, 70),
             _span("serve.harvest", 32, 36, step=7),
             _span("serve.harvest", 57, 62, step=8)]
    r = pt.serving(execs, spans)
    assert (r["decode_calls"], r["prefill_calls"]) == (2, 3)
    assert r["decode_call_ms"] == pytest.approx(10.0)
    assert r["prefill_call_ms"] == pytest.approx(10.0)
    assert r["calls_s"] == pytest.approx(50e-3)


def test_trace_that_starts_in_a_queued_chunk():
    # the stretch opens while a prefill call runs: that call is cut off,
    # the rest of its chunk and the decode call behind it are whole; the
    # first harvest is of a step whose call ran before the stretch
    execs = [_call(-5, 8), _call(8, 18), _call(18, 28), _call(28, 38)]
    spans = [_traced(0, 60),
             _span("serve.harvest", 1, 2, step=3),
             _span("serve.harvest", 40, 45, step=4)]
    r = pt.serving(execs, spans)
    assert (r["decode_calls"], r["prefill_calls"]) == (1, 2)
    assert r["completion_notice_ms"] == pytest.approx(2.0)


def test_notice_and_turnaround_arithmetic():
    execs = [_call(0, 10), _call(13, 23), _call(30, 40), _call(41, 51),
             _call(55, 65)]
    # decode calls end at 10, 40 and 65; harvests start 1, 3 and 0.5 ms
    # later; the next calls start 3 and 1 ms after the first two
    spans = [_traced(0, 80), _span("serve.harvest", 11, 12, step=1),
             _span("serve.harvest", 43, 44, step=2),
             _span("serve.harvest", 65.5, 66, step=3)]
    r = pt.serving(execs, spans)
    assert r["decode_calls"] == 3 and r["prefill_calls"] == 2
    assert r["completion_notice_ms"] == pytest.approx(1.0)
    assert r["decode_turnaround_ms"] == pytest.approx(2.0)


def test_harvest_paired_once():
    # two harvests with no call between them: the second pairs nothing
    execs = [_call(0, 10), _call(20, 30)]
    spans = [_traced(0, 40), _span("serve.harvest", 11, 12, step=1),
             _span("serve.harvest", 14, 15, step=2)]
    r = pt.serving(execs, spans)
    assert (r["decode_calls"], r["prefill_calls"]) == (1, 1)


def test_no_program_spans_reads_none():
    r = pt.serving([_call(0, 10, "jit__lambda_")], [_traced(0, 20)])
    assert r["decode_calls"] == 0 and r["decode_call_ms"] is None
    assert r["completion_notice_ms"] is None
    assert pt.turnaround_ms([], [_traced(0, 20)]) is None


def test_train_turnaround():
    execs = [_call(0, 100, pt.TRAIN_MODULE), _call(110, 210, pt.TRAIN_MODULE),
             _call(214, 314, pt.TRAIN_MODULE)]
    assert pt.turnaround_ms(execs, [_traced(0, 400)]) == pytest.approx(7.0)


# -- idle gaps and scopes --------------------------------------------------------

def test_idle_gaps_charged_to_innermost_program_span():
    ops = {"/device:TPU:0": [("fusion.1", 0, 10 * MS),
                             ("fusion.2", 14 * MS, 30 * MS),
                             ("fusion.3", 33 * MS, 40 * MS),
                             ("fusion.4", 45 * MS, 50 * MS)]}
    spans = [_traced(0, 50),
             _span("bench.progress", 9, 15),
             _span("serve.harvest", 10, 15, step=1),
             _span("serve.sample", 10, 13, step=1),   # gap [10, 14) mid 12
             _span("bench.sleep", 30, 34),            # gap [30, 33)
             ]                                        # gap [40, 45): none
    gaps = dict(pt.idle_gaps(ops, spans))
    assert gaps["serve.sample"] == pytest.approx(4e-3)
    assert gaps["bench.sleep"] == pytest.approx(3e-3)
    assert gaps[pt.OUTSIDE] == pytest.approx(5e-3)
    assert "serve.harvest" not in gaps and "bench.progress" not in gaps


@pytest.mark.parametrize("path,scope", [
    ("jit(serve_call)/while/body/closed_call/paged_view/gather:",
     "paged_view"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attention/bsd,dhk->bshk/dot_general:", "attention"),
    ("jit(train_step)/transpose(jvp(logits))/bsd,vd->bsv/dot_general:",
     "logits"),
    ("jit(train_step)/adamw/mul:", "adamw"),
    ("jit(serve_call)/while/body/dynamic_slice:", pt.NO_SCOPE),
    ("jit(serve_call)/jit(_take)/gather:", pt.NO_SCOPE),
    ("", pt.NO_SCOPE),
])
def test_top_scope(path, scope):
    assert pt.top_scope(path) == scope


def test_scope_time_counts_each_ops_own_time():
    ops = {"/device:TPU:0": [("%while.1 = ...", 0, 100 * MS),
                             ("%fusion.1 = ...", 10 * MS, 40 * MS),
                             ("%fusion.2 = ...", 50 * MS, 90 * MS),
                             ("%copy.3 = ...", 100 * MS, 120 * MS)]}
    paths = {"%while.1 = ...": "jit(serve_call)/while:",
             "%fusion.1 = ...": "jit(serve_call)/while/body/attention/dot:",
             "%fusion.2 = ...": "jit(serve_call)/while/body/mlp/dot:"}
    got = dict(pt.scope_time(ops, paths, [_traced(0, 110)]))
    assert got["attention"] == pytest.approx(30e-3)
    assert got["mlp"] == pytest.approx(40e-3)
    # the loop's own 30 ms (less its nested ops), and the copy up to 110
    assert got[pt.NO_SCOPE] == pytest.approx(30e-3 + 10e-3)


# -- the serialized trace --------------------------------------------------------

def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_op_paths_from_the_wire_format():
    stat_meta = _field(1, 7) + _field(2, "tf_op")
    other_meta = _field(1, 8) + _field(2, "flops")
    event_meta = (_field(1, 3) + _field(2, "%fusion.9 = bf16[2] fusion()")
                  + _field(5, _field(1, 8) + _field(3, 99))
                  + _field(5, _field(1, 7) + _field(5, "jit(f)/mlp/dot:")))
    device = (_field(1, 1) + _field(2, "/device:TPU:0")
              + _field(3, _field(2, "XLA Ops"))
              + _field(4, _field(1, 3) + _field(2, event_meta))
              + _field(5, _field(1, 7) + _field(2, stat_meta))
              + _field(5, _field(1, 8) + _field(2, other_meta)))
    host = (_field(2, "/host:CPU")
            + _field(4, _field(1, 1) + _field(2, _field(2, "serve.decode")
                                              + _field(5, _field(1, 7)))))
    space = _field(1, host) + _field(1, device) + _field(4, "host0")
    assert pt._op_paths(space) == {
        "%fusion.9 = bf16[2] fusion()": "jit(f)/mlp/dot:"}


def test_module_name_drops_program_id():
    assert pt.module_name("jit_serve_call(15459770517925678411)") == \
        "jit_serve_call"
    assert pt.module_name("jit_train_step") == "jit_train_step"


def test_readers_without_a_trace_read_none():
    for name in ("decode_call_ms", "prefill_call_ms", "completion_notice_ms",
                 "decode_turnaround_ms", "train_turnaround_ms"):
        assert spec.reader(name)({"trace": None}) is None


# -- a real trace -------------------------------------------------------------------

def test_load_reads_a_cpu_serving_trace(tmp_path):
    import jax
    from repro.configs import get_config
    from repro.core import ProgressEngine
    from repro.models import registry
    from repro.serve.engine import GenRequest, ServeEngine
    cfg = get_config("qwen2-0.5b").with_overrides(
        num_layers=2, d_model=64, d_ff=128, vocab_size=256, num_heads=4,
        num_kv_heads=2, head_dim=16, dtype="float32")
    srv = ServeEngine(cfg, registry.init_params(cfg, jax.random.PRNGKey(0)),
                      ProgressEngine(), batch_slots=2, max_seq=32)
    reqs = [GenRequest(f"r{i}", np.arange(1, 4 + i, dtype=np.int32),
                       max_new_tokens=3) for i in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            for r in reqs:
                srv.submit(r)
            srv.run_until_idle(timeout=120)
    finally:
        jax.profiler.stop_trace()
    srv.close(timeout=60)
    t = pt.load(str(tmp_path))
    names = [n for n, _, _, _ in t["spans"]]
    assert "bench.traced" in names
    for want in ("serve.admit", "serve.prefill", "serve.decode",
                 "serve.harvest", "serve.sample"):
        assert want in names
    decodes = [a for n, _, _, a in t["spans"] if n == "serve.decode"]
    assert len(decodes) == 3 and all(a["lanes"] == 2 for a in decodes)
    # the CPU has no device plane: no executions, and the readers say so
    assert t["executions"] == {} and t["ops"] == {}
    r = pt.serving([], t["spans"])
    assert r["decode_calls"] == 0 and r["decode_call_ms"] is None
