"""The chip benchmark finds each cell's files, and each metric's
reader, by the name BENCHMARK.json gives; an unknown name fails."""

import os
import re

import pytest

from perfbench_util import BENCH, ROOT, benchmark, run_module

run = run_module()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    bench = benchmark()
    for w in bench["workloads"]:
        cell, config, traffic = run.find_cell(bench, w["name"])
        assert config["name"] == cell["config"]
        entry = run.entry_module(traffic["entry"])
        assert hasattr(entry, "Cell")
        assert traffic["check"]["limits"]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for name, rel in files.items():
        assert rel == f"bench/configs/{name}.json"
        assert os.path.isfile(os.path.join(ROOT, rel))


def test_unknown_names_fail():
    bench = benchmark()
    with pytest.raises(run.BenchError):
        run.find_cell(bench, "no-such-cell")
    with pytest.raises(run.BenchError):
        run.entry_module("no_such_entry")
    with pytest.raises(run.BenchError):
        run.reader("no_such_metric")
    broken = dict(bench, workloads=[dict(bench["workloads"][0],
                                         config="no-such-config")])
    with pytest.raises(FileNotFoundError):
        run.find_cell(broken, broken["workloads"][0]["name"])


def test_every_metric_has_a_reader_and_a_cell():
    bench = benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(run.reader(m["name"]).value)
            assert NAME.match(m["name"])
            assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        # each per-layer metric moves one end-to-end metric that every
        # one of its cells reports
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def test_every_cell_reports_setup_and_another_metric():
    bench = benchmark()
    for w in bench["workloads"]:
        e2e = [m["name"] for m in run.metrics_for(bench, "end_to_end",
                                                  w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_for(bench, "per_layer", w["name"])


def test_contract_shapes():
    bench = benchmark()
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for k in ("end_to_end", "per_layer")
              for m in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert os.path.isdir(BENCH)
