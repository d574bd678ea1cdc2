"""Host spans of the campaign entry points (`repro.core.spans`).

  * outside a profiler trace a span records nothing and opens no
    `TraceAnnotation`;
  * under a trace, each entry call is one root whose stages carry the
    documented span names, and the self times add up to the root;
  * the `margin.fetch` bytes are the pass envelopes of both profile
    campaigns, counted from their shapes, and its `evals` the margins
    the benchmark's work count credits a profile with;
  * results and dispatch counts are the same with tracing on and off;
  * the spans land in the written trace on a `/host:` plane, nested in
    the caller's annotation.
"""

import glob

import jax
import numpy as np
import pytest

from repro.core import perf_model, spans
from repro.core import timing as T
from repro.core.aldram import ALDRAMController
from repro.core.dram_sim import OPEN_FCFS, Policy
from repro.core.sim_engine import SimEngine
from repro.core.thermal import ThermalConfig, diurnal, steady

NAMES = {
    "evaluate_system": {"aldram.evaluate_system", "sim.prep",
                        "sim.dispatch", "sim.fetch"},
    "evaluate_dynamic": {"aldram.evaluate_dynamic", "sim.prep",
                         "sim.dispatch", "sim.fetch"},
    "profile": {"aldram.profile", "margin.fetch", "margin.reduce"},
}


def _bench_module(name: str):
    """bench/<name>.py, the chip benchmark's own module, by path."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def controller(small_pop):
    ctrl = ALDRAMController(temp_bins=(55.0, 85.0), per_bank=True)
    ctrl.profile(small_pop)
    return ctrl


def _entry(kind, ctrl, pop):
    """(call, counters): one small entry call of `kind` and the
    dispatch counters it moves."""
    eng = SimEngine()
    if kind == "evaluate_system":
        def call():
            out = ctrl.evaluate_system(
                pop, n=96, seed=3, engine=eng,
                policies=(OPEN_FCFS, Policy(reorder_window=8)))
            r = out["result"]
            return {"mean": r.mean_latency_ns, "p99": r.p99_latency_ns,
                    "total": r.total_ns, "speedups": out["speedups"]}
    elif kind == "evaluate_dynamic":
        def call():
            out = ctrl.evaluate_dynamic(
                pop, scenarios=(steady(45.0), diurnal(40.0, 80.0,
                                                      period_ns=2e4)),
                config=ThermalConfig(), n=96, seed=3, engine=eng,
                fused=True)
            r = out["result"]
            return {"mean": r.mean_latency_ns, "total": r.total_ns,
                    "temp_max": r.temp_max,
                    "worst_bin": np.asarray(out["worst_bin"]),
                    "lat": out["mean_latency_ns"]}
    else:
        prof_ctrl = ALDRAMController(ctrl.profiler, temp_bins=(55.0, 85.0),
                                     per_bank=True)

        def call():
            t = prof_ctrl.profile(pop)
            return {"params": t.params, "module": t.params_module,
                    "trefi_r": t.safe_trefi_read,
                    "trefi_w": t.safe_trefi_write}

    def counters():
        return (eng.dispatch_count, ctrl.profiler.engine.dispatch_count,
                perf_model.synth_dispatch_count)
    return call, counters


def _traced(tmp_path, fn):
    """Run `fn` under a profiler trace (no Python tracer), inside an
    outer annotation; returns (its result, the .xplane.pb path)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.clear()
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("test.call"):
            out = fn()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    return out, path


def test_no_trace_records_nothing(monkeypatch, controller, small_pop):
    made = []

    class Spy:
        def __init__(self, *a, **k):
            made.append(a)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Spy)
    spans.clear()
    call, _ = _entry("evaluate_system", controller, small_pop)
    call()
    with spans.span("x", bytes=3) as s:
        s.count(rows=2)
    assert made == []
    assert spans.summary() == {"roots": 0, "spans": {}}


def test_no_trace_outside_profiler(controller, small_pop):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    spans.clear()
    _entry("profile", controller, small_pop)[0]()
    assert spans.summary() == {"roots": 0, "spans": {}}


@pytest.mark.parametrize("kind", list(NAMES))
def test_one_root_and_self_times_add_up(tmp_path, controller, small_pop,
                                        kind):
    call, counters = _entry(kind, controller, small_pop)
    plain = call()                                       # compiles
    c0 = counters()
    plain = call()
    c1 = counters()
    traced, _ = _traced(tmp_path, call)
    c2 = counters()
    summ = spans.summary()
    # the same results and launches with the trace on as off
    assert traced.keys() == plain.keys()
    for k in plain:
        np.testing.assert_array_equal(np.asarray(traced[k]),
                                      np.asarray(plain[k]), err_msg=k)
    assert np.subtract(c2, c1).tolist() == np.subtract(c1, c0).tolist()
    # one root, the documented stages, self times adding up to it
    assert summ["roots"] == 1
    got = summ["spans"]
    assert set(got) == NAMES[kind]
    root = got["aldram." + kind]
    assert root["n"] == 1
    assert sum(v["self_s"] for v in got.values()) == pytest.approx(
        root["total_s"], abs=1e-6)
    assert all(v["self_s"] >= 0.0 and v["total_s"] <= root["total_s"]
               for v in got.values())


def test_margin_fetch_bytes_are_both_grids(tmp_path, controller, small_pop):
    """Only the pass envelopes cross to the host, one byte a boolean:
    the refresh campaign's [modules, chips, banks, grid] for each test,
    the timing campaign's [modules, banks, bins, combos] for each."""
    call, _ = _entry("profile", controller, small_pop)
    call()
    _traced(tmp_path, call)
    fetch = spans.summary()["spans"]["margin.fetch"]
    prof = controller.profiler
    m, ch, bk, kc = small_pop.cells.shape[:4]
    bins = 2
    combos = [len(prof.combo_grid(op)) for op in ("read", "write")]
    refresh = 2 * m * ch * bk * len(T.refresh_grid())
    timing = m * bk * bins * sum(combos)
    assert fetch["bytes"] == refresh + timing
    assert fetch["n"] == 2
    work = _bench_module("work")
    assert fetch["evals"] == work.margin_evals(
        m * ch * bk * kc, len(T.refresh_grid()), bins, combos)


def test_counts_of_the_replay_spans(tmp_path, controller, small_pop):
    call, _ = _entry("evaluate_system", controller, small_pop)
    call()
    _traced(tmp_path, call)
    got = spans.summary()["spans"]
    streams = 2 * len(perf_model.WORKLOADS)
    # the batch split and the engine's packing: 70 streams x 96 each
    assert got["sim.prep"]["n"] == 2
    assert got["sim.prep"]["streams"] == 2 * streams
    assert got["sim.prep"]["requests"] == 2 * streams * 96
    assert got["aldram.evaluate_system"]["rows"] == 3
    assert got["aldram.evaluate_system"]["policies"] == 2
    # the synthesis launch and the replay launch
    assert got["sim.dispatch"]["n"] == 2


def test_counts_of_the_kv_replay_spans(tmp_path, controller, small_pop):
    """A YCSB campaign's `sim.prep` counts its ranks, bank groups and
    operations."""
    from repro.core.dram_sim import KVSpec
    kv = KVSpec(n=96, n_streams=2, seed=3, n_records=1 << 12)

    def call():
        out = controller.evaluate_system(
            small_pop, traffic=kv, std=T.DDR4_2400,
            rank_timings=T.DDR4_2400_RANK, engine=SimEngine(),
            policies=(OPEN_FCFS, Policy(page="closed")))
        return out["result"].constraint_stall_ns

    call()
    _traced(tmp_path, call)
    prep = spans.summary()["spans"]["sim.prep"]
    assert prep["n"] == 1
    assert (prep["streams"], prep["requests"]) == (2, 2 * 96)
    assert (prep["ranks"], prep["bank_groups"]) == (2, 4)
    # each operation is 16 or 18 lines: 96 lines hold 6 of them
    assert prep["ops"] == kv.op_count() == 2 * 6
    assert spans.summary()["spans"]["aldram.evaluate_system"]["rows"] == 3


@pytest.mark.parametrize("kind", list(NAMES))
def test_spans_in_the_written_trace(tmp_path, controller, small_pop, kind):
    from jax.profiler import ProfileData
    call, _ = _entry(kind, controller, small_pop)
    call()
    _, path = _traced(tmp_path, call)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in NAMES[kind] | {"test.call"}:
                    events.setdefault(e.name, []).append(
                        (plane.name, e.start_ns, e.end_ns, dict(e.stats)))
    assert set(events) == NAMES[kind] | {"test.call"}
    (outer,) = events.pop("test.call")
    (root,) = events["aldram." + kind]
    for name, evs in events.items():
        for plane, s, e, _ in evs:
            assert plane.startswith("/host:"), (name, plane)
            assert outer[1] <= root[1] <= s <= e <= root[2] <= outer[2]
    if kind == "profile":
        assert root[3]["modules"] == small_pop.n_modules
        assert sum(ev[3]["bytes"] for ev in events["margin.fetch"]) == \
            spans.summary()["spans"]["margin.fetch"]["bytes"]
