"""One run of one benchmark cell on the accelerator this process sees.

    python3 chipbench/run.py --workload qwen2-0.5b.decode --seed 7 \
        --seconds 50 --trace 0

The cell, its configuration, its traffic mix, the driver and the
generator that the mix names, and its metrics are found by name
(``chipbench/harness/spec.py``).  Set-up (weights from the seed, compilation,
warm-up) is timed as ``setup_s``; then the window runs for ``--seconds``;
then the program's state is freed and the output is checked against the
plain reference.  ``--trace 1`` traces part of the window and reports the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared beside their limits.  On
anything but a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time
import traceback
from typing import Callable, Optional

T_START = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.harness import spec  # noqa: E402

OUT_DIR = os.path.join(BENCH_DIR, "out")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    # tests replace part of the timed path with a broken one
    tamper: Optional[Callable] = None


class Tracer:
    """Profiler on/off around a stretch of the window, host spans inside
    it, and the device memory peak."""

    def __init__(self, trace_dir: str, chips: int):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.dir = trace_dir
        self.chips = chips
        self.active = False
        self._ann = None
        self.compiles = 0          # backend compilations so far

        def count(event, _secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(count)

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        from chipbench.harness.trace import TRACED_SPAN
        self._ann = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._ann.__enter__()
        self.active = True

    def stop(self):
        import jax
        self._ann.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def memory_peak(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        return int(max(peaks))


def _setup_jax(cache: bool):
    import jax
    if not cache:
        return jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(bench: dict, cell: Cell, on_chip: bool = True):
    """Set up, run and check one cell; returns (result object, run
    record).  ``on_chip`` false (tests) skips the look for a chip, the
    compile cache and the table of peaks."""
    jax = _setup_jax(on_chip)
    devices = jax.devices()
    if on_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"needs a TPU, found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise SystemExit(f"needs {cell.chips} chips, found "
                             f"{len(devices)}")
    from chipbench.harness import program
    from chipbench.harness import trace as tr
    from chipbench.harness.peaks import peaks_for
    program.import_program()
    if on_chip:
        from repro.launch.compile_cache import enable_compile_cache
        if enable_compile_cache() != CACHE_DIR:
            raise RuntimeError("the program keeps its compile cache "
                               "elsewhere")

    tracer = Tracer(os.path.join(OUT_DIR, "trace"), cell.chips)
    driver = spec.driver(cell.mix)
    run = driver.run(cell, tracer)
    run.update(cfg=cell.cfg, mix=cell.mix, ref=spec.reference(cell.cfg),
               chips=cell.chips, workload=cell.name,
               peaks=(peaks_for(devices[0].device_kind)
                      if on_chip else None))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {}
    if cell.trace:
        ops, spans = tr.load(tracer.dir)
        run["trace"] = tr.reduce(ops, spans)
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
        print(f"trace: device planes {sorted(ops)}", file=sys.stderr)
    metrics = {}
    for m in spec.metrics_for(bench, cell.name, cell.trace):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = run["check"]
    # what else a reader of the run needs, on standard error
    info = {"setup_s": run["setup_s"], "window_s": run["window_s"],
            "compiles_in_window": run["window_compiles"],
            "memory_peak_bytes": run["memory_peak_bytes"],
            "window": run["window"], **driver.report(run)}
    print(f"run: {json.dumps(info)}", file=sys.stderr)
    return {"correct": judge(checks), "attempted": driver.attempted(run),
            "failed": driver.failed(run), "metrics": metrics,
            "device": device, **result,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}, run


def judge(checks: dict) -> bool:
    """``correct``: every number compared is finite and within its limit."""
    return all(v is not None and math.isfinite(v) and v <= lim
               for v, lim in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec.benchmark()
        w = spec.workload(bench, args.workload)
        cell = Cell(w["name"], w["chips"], spec.config(w["config"]),
                    spec.traffic(w["traffic"]), args.seed, args.seconds,
                    bool(args.trace), T_START)
        result, _ = run_cell(bench, cell)
    except SystemExit as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except Exception:   # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        return 1
    print(json.dumps(result))
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
