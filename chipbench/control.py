"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip (not part of a benchmark run):

    python3 chipbench/control.py --workload qwen2-0.5b.decode \
        --seeds 11 12 13 --seconds 50

Serving: a run of the cell (its window at ``--seconds``), then, on the
same prompts and served tokens, the control: the reference computed with
float8_e4m3fn operands in the program's place, read by the gap of the
token it puts first.

Training: per seed, the reference put in the program's place and
compared with the float32 reference as a run compares the program: at
float8 (the control), and with half of each batch left out (a fault).

Each control's numbers go through the run's own comparison with the
committed limits; ``control_correct`` has to come out false.  One JSON
line per seed.  Like a run, it refuses anything but a TPU.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench import run as bench_run  # noqa: E402
from chipbench.drivers import serving, training  # noqa: E402
from chipbench.harness import spec  # noqa: E402


def serve_readings(bench, cell, on_chip=True) -> dict:
    result, run = bench_run.run_cell(bench, cell, on_chip)
    fp8 = serving.gaps(cell, run["check_items"], quant="fp8")
    control = {k: list(v) for k, v in run["check"].items()}
    control["logit_gap"][0] = float(fp8.max())
    return {"correct": result["correct"],
            "program": result["checks"]["logit_gap"]["value"],
            "fp8": control["logit_gap"][0], "tokens": int(fp8.size),
            "control_correct": bench_run.judge(control)}


def train_readings(cell) -> dict:
    lim = cell.cfg["limits"]
    ref = training.reference_steps(cell)
    out = {}
    for name, kw in (("fp8", {"quant": "fp8"}),
                     ("half_batch", {"rows_of_batch":
                                     cell.mix["global_batch"] // 2})):
        got = training.gaps(training.reference_steps(cell, **kw), ref)
        out[name] = got
        out[f"{name}_correct"] = bench_run.judge(
            {k: [v, lim[f"train_{k}"]] for k, v in got.items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    jax = bench_run._setup_jax(True)
    if jax.devices()[0].platform != "tpu":
        print(f"control: needs a TPU, found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    from chipbench.harness import program
    program.import_program()
    for seed in args.seeds:
        cell = bench_run.Cell(w["name"], w["chips"], spec.config(w["config"]),
                              spec.traffic(w["traffic"]), seed, args.seconds,
                              False, time.monotonic())
        if cell.mix["driver"] == "training":
            out = train_readings(cell)
        else:
            out = serve_readings(bench, cell)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
