"""The per-layer metrics read from the program's spans: a `--trace 1`
run of each cell at a small size reports every span metric of the
cell, and the replay cells' four add up to the traced call; a reader
gives nothing for a partial record or a program without spans."""

import json
import sys

import pytest

from perfbench_util import benchmark, cells, run_module, small_cell

run = run_module()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
REPLAY = ("prep_ms_per_call.replay", "dispatch_ms_per_call.replay",
          "fetch_ms_per_call.replay", "controller_ms_per_call.replay")
PROFILE = ("margin_fetch_s_per_profile", "margin_reduce_s_per_profile",
           "controller_s_per_profile")


def span_metrics(workload: str) -> list[str]:
    return [m["name"] for m in run.metrics_for(benchmark(), "per_layer",
                                               workload)
            if m["source"] == "program_span"]


def _traced_run(monkeypatch, capsys, tmp_path, workload):
    """One `--trace 1` run of `workload` at a small size, the device
    check skipped; returns (result line, the trace reduction)."""
    from repro.core import spans
    small = small_cell(workload)
    monkeypatch.setattr(run, "enable_compile_cache", lambda jax: "off")
    monkeypatch.setattr(run, "find_cell", lambda bench, name: small)
    monkeypatch.setattr(run, "device_info", lambda jax, chips: dict(CPU))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    trace = sys.modules["bench_trace"]
    reduce, reduced = trace.reduce, []

    def keep(events, **kw):
        reduced.append(reduce(events, **kw))
        return reduced[-1]
    monkeypatch.setattr(trace, "reduce", keep)
    spans.clear()
    assert run.main(["--workload", workload, "--seed", "4000000009",
                     "--seconds", "0.05", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line, reduced[-1]


@pytest.mark.parametrize("workload", list(cells()))
def test_traced_run_reports_span_metrics(monkeypatch, capsys, tmp_path,
                                         workload):
    names = span_metrics(workload)
    assert names, workload
    line, red = _traced_run(monkeypatch, capsys, tmp_path, workload)
    assert line["correct"] is True, line["checks"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in names:
        assert name in got, (name, sorted(got))
        assert 0.0 < got[name] < float("inf"), (name, got[name])
    call_s = sum(red["call_s"]) / red["calls"]
    if set(REPLAY) <= set(names):
        total = sum(got[n] for n in REPLAY) * 1e-3
        assert total == pytest.approx(call_s, rel=0.05)
    if set(PROFILE) <= set(names):
        total = sum(got[n] for n in PROFILE)
        assert total == pytest.approx(call_s, rel=0.05)


def _ctx(calls, trace=True):
    return {"calls": calls, "trace": {"calls": calls} if trace else None}


@pytest.mark.parametrize("name", sorted({n for w in cells()
                                         for n in span_metrics(w)}))
def test_reader_needs_one_root_per_call(monkeypatch, name):
    from repro.core import spans
    monkeypatch.setattr(spans, "summary", lambda: {
        "roots": 2, "spans": {n: {"n": 2, "total_s": 0.5, "self_s": 0.25,
                                  "bytes": 1e9}
                              for n in ("sim.prep", "sim.dispatch",
                                        "sim.fetch",
                                        "aldram.evaluate_system",
                                        "aldram.evaluate_dynamic",
                                        "aldram.profile", "margin.fetch",
                                        "margin.reduce")}})
    value = run.reader(name).value
    assert value(_ctx(2)) is not None and value(_ctx(2)) > 0.0
    assert value(_ctx(3)) is None
    assert value(_ctx(2, trace=False)) is None
    # a program without spans (the module cannot be imported)
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert value(_ctx(2)) is None
