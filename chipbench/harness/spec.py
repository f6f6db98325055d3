"""Finds every piece of a cell by name: ``BENCHMARK.json`` at the root of
the checkout, ``chipbench/configs/<config>.json``,
``chipbench/traffic/<traffic>.json``, the driver and the generator that
the mix names (``chipbench/drivers/<driver>.py``,
``chipbench/generators/<generator>.py``), ``chipbench/metrics/<metric>.py``
and the reference that the configuration names
(``chipbench/references/<reference>.py``).  Adding a cell, a
configuration, a mix, a kind of run, an architecture or a metric is
adding files and entries."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def reference(c: dict):
    """The plain reference module a configuration names."""
    return importlib.import_module(f"chipbench.references.{c['reference']}")


def driver(mix: dict):
    """The module that runs a mix's kind of cell: ``run(cell, tracer)``,
    ``attempted(run)``, ``failed(run)``, ``report(run)``."""
    return importlib.import_module(f"chipbench.drivers.{mix['driver']}")


def generator(mix: dict):
    """The module that turns a mix's parameters into its work."""
    return importlib.import_module(f"chipbench.generators.{mix['generator']}")


def metrics_for(bench: dict, workload_name: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer
    metrics (``trace`` true): those that list the cell, or list no cells
    and move an end-to-end metric that the cell reports (the contract
    lets a later entry leave out its list of cells)."""
    e2e = [m for m in bench["end_to_end"]
           if workload_name in m.get("workloads", [workload_name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(metric_name: str):
    """``read(run) -> float | None`` of ``metrics/<metric_name>.py``, or
    where there is no such file, of the reader its name splits from:
    ``idle_share.train`` is read by ``metrics/idle_share.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric_name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, "metrics",
                            f"{metric_name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
