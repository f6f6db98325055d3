"""The benchmark's own arithmetic on the CPU: traffic, trace reduction,
the float32 reference against the program, and the counts of operations
and bytes against hand counts."""
import numpy as np
import pytest

from chipbench.harness import spec, traffic
from chipbench.harness import trace as tr

DECODE = spec.traffic("decode")
TRAIN = spec.traffic("train_8x1024")
REQUESTS = spec.generator(DECODE)
OPEN = dict(DECODE, loop="open", rate_per_s=2.0)


# -- traffic -----------------------------------------------------------------

def test_every_mix_names_its_driver_and_generator():
    for w in spec.benchmark()["workloads"]:
        mix = spec.traffic(w["traffic"])
        assert hasattr(spec.driver(mix), "run")
        assert spec.generator(mix) is not None


def test_open_loop_same_seed_same_requests():
    a = REQUESTS.requests(OPEN, 2**31 + 11, 1000, 50.0)
    b = REQUESTS.requests(OPEN, 2**31 + 11, 1000, 50.0)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


def test_seeds_share_the_work_and_its_order():
    a = REQUESTS.requests(OPEN, 1, 1000, 50.0)
    b = REQUESTS.requests(OPEN, 2, 1000, 50.0)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])
    lens = [len(r["prompt"]) for r in a]
    assert lens != sorted(lens)
    assert len(a) == 100
    due = [r["due_s"] for r in a]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 50.0


@pytest.mark.parametrize("key", ["prompt_len", "output_len"])
def test_length_quantiles(key):
    d = DECODE[key]
    x = REQUESTS.lengths(d, 2001)
    assert x.min() >= d["min"] and x.max() <= d["max"]
    assert abs(np.median(x) - d["median"]) <= 1
    # the lognormal's quartiles: median * exp(+-0.6745 sigma), if unclipped
    for q, z in ((25, -0.6745), (75, 0.6745)):
        want = np.clip(d["median"] * np.exp(z * d["sigma"]), d["min"], d["max"])
        assert abs(np.percentile(x, q) - want) <= 0.01 * want + 1


def test_closed_loop_and_batches_deterministic():
    a = REQUESTS.requests(DECODE, 5, 100, 50.0)
    b = REQUESTS.requests(DECODE, 5, 100, 50.0)
    assert len(a) == DECODE["pool"] and "due_s" not in a[0]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    mix = dict(TRAIN, global_batch=8, seq_len=16)
    gen = spec.generator(TRAIN)
    g1, g2 = gen.batches(mix, 9, 50), gen.batches(mix, 9, 50)
    b1, b2 = next(g1), next(g2)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert np.array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    rows = np.concatenate([b1["tokens"], next(g1)["tokens"]])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_shared_reader_by_name():
    assert spec.reader("idle_share.train") is not None
    assert spec.reader("idle_share.decode")({"trace": {
        "window_s": 2.0, "busy_s": 1.5}}) == pytest.approx(25.0)


def test_percentile_nearest_rank():
    assert traffic.percentile(range(1, 101), 90) == 90
    assert traffic.percentile([5.0], 99) == 5.0


# -- trace reduction ---------------------------------------------------------

def _span(name, s, e):
    return (name, s, e)


def test_reduce_busy_and_gaps():
    spans = [_span("bench.traced", 0, 100), _span("bench.progress", 10, 40),
             _span("bench.sleep", 60, 80)]
    ops = {"/device:TPU:0": [
        ("fusion.1", 0, 10), ("fusion.2", 5, 20),       # overlap -> [0, 20)
        ("%fusion.3 = f32[8] fusion(...)", 50, 70), ("fusion.4", 55, 60),
        ("fusion.5", 90, 120)]}                          # clipped to 100
    r = tr.reduce(ops, spans)
    assert r["busy_s"] == pytest.approx(50e-9)   # 20 + 20 + 10
    assert r["window_s"] == pytest.approx(100e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.progress"] == pytest.approx(30e-9)   # [20, 50)
    assert gaps["bench.sleep"] == pytest.approx(20e-9)      # [70, 90)
    assert dict(r["device_ops"])["%fusion.3"] == pytest.approx(20e-9)
    assert dict(r["device_ops"])["fusion.5"] == pytest.approx(10e-9)


def test_reduce_averages_devices():
    spans = [_span("bench.traced", 0, 10)]
    ops = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": [("a", 0, 5)]}
    assert tr.reduce(ops, spans)["busy_s"] == pytest.approx(7.5e-9)


def test_load_reads_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.TRACED_SPAN):
        with jax.profiler.TraceAnnotation("bench.progress"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = tr.load(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert {tr.TRACED_SPAN, "bench.progress"} <= names
    assert all(e >= s for _, s, e in spans)


# -- the plain reference -------------------------------------------------------

SMALL_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   vocab_size=512)


def _tiny(name):
    return dict(spec.config(name), **SMALL_MODEL, compute_dtype="float32")


@pytest.mark.parametrize("name", ["qwen2-0.5b", "smollm-360m"])
def test_reference_matches_program_forward(name):
    import jax
    from chipbench.harness import program
    from repro.models import registry
    c = _tiny(name)
    ref = spec.reference(c)
    mc = program.model_config(c)
    w = ref.init_weights(c, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                c["vocab_size"])
    want = ref.logits(c, w, tokens)
    with jax.default_matmul_precision("highest"):
        got, _ = registry.forward(program.program_tree(c, w), mc,
                                  {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_reference_fp8_control_departs():
    import jax
    c = _tiny("qwen2-0.5b")
    ref = spec.reference(c)
    w = ref.init_weights(c, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, c["vocab_size"])
    gap = float(np.max(np.abs(np.asarray(ref.logits(c, w, t))
                              - np.asarray(ref.logits(c, w, t, "fp8")))))
    assert gap > 1e-2


def test_reference_loss_and_grad_blocks():
    import jax
    import jax.numpy as jnp
    c = _tiny("smollm-360m")
    ref = spec.reference(c)
    w = ref.init_weights(c, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, c["vocab_size"])
    l1, g1 = ref.loss_and_grad(c, w, t[:, :-1], t[:, 1:], rows=1)
    l4, g4 = ref.loss_and_grad(c, w, t[:, :-1], t[:, 1:], rows=4)
    assert float(l1) == pytest.approx(float(l4), rel=1e-5)
    assert float(l4) == pytest.approx(float(ref.loss(c, w, t[:, :-1],
                                                     t[:, 1:])), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)
    assert float(ref.lr_at({"lr": 1.0, "warmup_steps": 5, "total_steps": 10,
                            "min_lr_ratio": 0.1}, jnp.asarray(1))) == \
        pytest.approx(0.2)


# -- counts of operations and bytes, against hand counts ----------------------

def test_counts_by_hand():
    q, s = spec.config("qwen2-0.5b"), spec.config("smollm-360m")
    ref = spec.reference(q)
    # qwen2-0.5b: embed 151936*896; per layer q 896*896+896, k and v
    # 896*128+128 each, o 896*896, mlp 3*896*4864, two norms 896
    layer = 802816 + 896 + 2 * (114688 + 128) + 802816 + 13074432 + 1792
    assert ref.param_count(q) == 24 * layer + 151936 * 896 + 896 == 494032768
    assert ref.param_count(s) == 361821120
    assert ref.kv_bytes_per_position(q) == 2 * 24 * 2 * 64 * 2 == 12288
    # smollm-360m, S = 1024: 6 * (32 * 9830400 + 49152 * 960) + 12*32*960*1024
    assert ref.train_flops_per_token(s, 1024) == \
        6 * (32 * 9830400 + 47185920) + 377487360 == 2548039680
    # one qwen2 token at context 100, logits used: 2 * weights it passes
    # (24 layers of 14909440 matmul weights, and the 896 x 151936 head)
    # plus 4 * 14 heads * 64 * 100 keys per layer
    assert ref.forward_flops(q, 100) == \
        2 * 24 * 14909440 + 2 * 896 * 151936 + 4 * 24 * 14 * 64 * 100
