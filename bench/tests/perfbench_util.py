"""Helpers of the benchmark's tests: load bench/ files by path, and
build a cell at a size a CPU test can hold."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# the program first; the benchmark's own modules (compare, work,
# reference) after everything else, so they shadow nothing
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))
if BENCH not in sys.path:
    sys.path.append(BENCH)


def load(rel: str, name: str | None = None):
    """Import bench/<rel> as a module of its own."""
    name = name or "perfbench_" + rel.replace("/", "_").replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def run_module():
    """bench/run.py, with the trace reduction it loads under its own
    name."""
    load("trace.py", "bench_trace")
    return load("run.py", "perfbench_run")


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cells() -> dict:
    """{cell: (config, traffic, chips)} of every workload in
    BENCHMARK.json."""
    return {w["name"]: (w["config"], w["traffic"], int(w["chips"]))
            for w in benchmark()["workloads"]}


def entry_kind(workload: str) -> str:
    """The entry driver named by a cell's traffic file."""
    with open(os.path.join(BENCH, "traffic",
                           cells()[workload][1] + ".json")) as fh:
        return json.load(fh)["entry"]


def small_cell(workload: str, **traffic_overrides):
    """(cell, config, traffic) of a cell cut to a CPU test's size: few
    requests, a small population, a small sample."""
    run = run_module()
    config_name, traffic_name, chips = cells()[workload]
    config = run.load_json(os.path.join(BENCH, "configs",
                                        config_name + ".json"))
    traffic = run.load_json(os.path.join(BENCH, "traffic",
                                         traffic_name + ".json"))
    cell = {"name": workload, "config": config_name,
            "traffic": traffic_name, "chips": chips}
    if "n_requests" in traffic:
        traffic["n_requests"] = 128
    if "n_streams" in traffic:
        traffic["n_streams"] = 8
    if "population" in config:
        config["population"].update(n_modules=3, n_cells=2)
        config["grid_step_ns"] = 2.5
    chk = traffic["check"]
    for k in ("sample_streams", "sample_modules"):
        if k in chk:
            chk[k] = 2
    traffic.update(traffic_overrides)
    return cell, config, traffic
