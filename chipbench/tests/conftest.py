"""Tiny cells for the CPU: the real configuration and mix files with their
sizes cut, so that a whole run (set-up, window, check) takes seconds.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# wide enough that the float8 control's logits depart from the
# reference's by as much as at full size
TINY_MODEL = dict(hidden_size=512, intermediate_size=1024, num_hidden_layers=2,
                  num_attention_heads=8, num_key_value_heads=2,
                  vocab_size=2048)
TINY_SERVE = {"lanes": 4, "max_seq": 80, "kv_block_size": 16,
              "prefill_chunk": 8}
TINY_LEN = {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 4,
            "max": 16}
TINY_MIX = {
    "decode": dict(clients=4, pool=4096, prompt_len=TINY_LEN,
                   output_len=dict(TINY_LEN, median=24, min=16, max=48),
                   check_requests=8, trace_seconds=0.5),
    "train_8x1024": dict(global_batch=2, seq_len=16, reference_rows=1),
}
# an open loop through the same driver and generator (no cell has one yet)
TINY_OPEN = dict(loop="open", rate_per_s=6.0, drain_limit_s=30,
                 prompt_len=TINY_LEN,
                 output_len=dict(TINY_LEN, median=4, min=2, max=8),
                 check_requests=3, trace_seconds=0.5)


def tiny_cell(workload: str, seed: int = 3, seconds: float = 1.0,
              trace: bool = False, tamper=None, mix: dict | None = None):
    """The cell at tiny sizes; ``mix`` overrides its mix's parameters
    further."""
    import time

    from chipbench.harness import spec
    from chipbench.run import Cell
    bench = spec.benchmark()
    w = spec.workload(bench, workload)
    cfg = dict(spec.config(w["config"]), **TINY_MODEL)
    if "serve" in cfg["deployment"]:
        cfg["deployment"] = dict(cfg["deployment"], serve=TINY_SERVE)
    mix = {**spec.traffic(w["traffic"]), **TINY_MIX[w["traffic"]],
           **(mix or {})}
    return bench, Cell(w["name"], w["chips"], cfg, mix, seed, seconds, trace,
                       time.monotonic(), tamper)
