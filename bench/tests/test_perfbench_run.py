"""bench/run.py end to end on the CPU: it refuses to run without a TPU;
with the look for a chip skipped, a run at a small size reports
`correct` true, and false when the timed path is broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench_util import (BENCH, ROOT, cells, entry_kind, run_module,
                            small_cell)

run = run_module()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cli(cwd, workload="fig4-static-1ch"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000007", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_to_run_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _drive(monkeypatch, capsys, workload, seconds=0.05, **overrides):
    """One run of `workload` at a small size, the device check skipped;
    returns the result line."""
    small = small_cell(workload, **overrides)
    monkeypatch.setattr(run, "enable_compile_cache", lambda jax: "off")
    monkeypatch.setattr(run, "find_cell", lambda bench, name: small)
    monkeypatch.setattr(run, "device_info", lambda jax, chips: dict(CPU))
    assert run.main(["--workload", workload, "--seed", "4000000009",
                     "--seconds", str(seconds), "--trace", "0"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("correct ")
    return line


@pytest.mark.parametrize("workload", list(cells()))
def test_sound_run_is_correct(monkeypatch, capsys, workload):
    line = _drive(monkeypatch, capsys, workload)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert line["device"]["platform"] == "cpu"


# ---------------------------------------------------------------- faults
def _stats_half(monkeypatch):
    """Half of the requests left out: mean and p99 over the first half
    of each stream."""
    from repro.core import sim_engine
    orig = sim_engine._device_stats

    def half(lat, valid, k):
        n = lat.shape[-1] // 2
        return orig(lat[..., :n], valid[..., :n], min(k, n))
    monkeypatch.setattr(sim_engine, "_device_stats", half)


def _answer_altered(monkeypatch):
    """Each request's completion 0.01 ns late where it is produced."""
    from repro.core import dram_sim
    orig = dram_sim.service_math

    def late(*a):
        out = list(orig(*a))
        out[4] = out[4] + 0.01
        out[5] = out[5] + 0.01
        return tuple(out)
    monkeypatch.setattr(dram_sim, "service_math", late)


def _state_unchanged(monkeypatch):
    """The replay step returns its bank state unchanged."""
    from repro.core import dram_sim
    orig = dram_sim.service_math

    def stale(t, gate, open_b, act_b, wrd_b, rdy_b, *rest):
        out = list(orig(t, gate, open_b, act_b, wrd_b, rdy_b, *rest))
        out[0], out[1], out[2], out[3] = open_b, act_b, wrd_b, rdy_b
        return tuple(out)
    monkeypatch.setattr(dram_sim, "service_math", stale)


def _margins_shifted(monkeypatch):
    """Every margin the kernel produces 0.05 too high."""
    from repro.core.sweep import MarginEngine
    orig = MarginEngine.margins

    def high(self, *a, **k):
        r, w = orig(self, *a, **k)
        return r + 0.05, w + 0.05
    monkeypatch.setattr(MarginEngine, "margins", high)


def _cells_halved(monkeypatch):
    """Half of the cells of every module left out of the envelope."""
    from repro.core.variation import Population
    orig = Population.flat_cells

    def half(self):
        c = self.cells
        keep = c[:, :, :, : max(1, c.shape[3] // 2)]
        reps = c.shape[3] // keep.shape[3]
        return orig(Population(np.concatenate([keep] * reps, axis=3)))
    monkeypatch.setattr(Population, "flat_cells", half)


# the faults each entry kind's timed path can have; every cell of
# BENCHMARK.json is driven with those of its entry
REPLAY_FAULTS = [_stats_half, _answer_altered, _state_unchanged]
FAULTS_BY_ENTRY = {
    "replay_static": REPLAY_FAULTS,
    "thermal_bracket": REPLAY_FAULTS,
    "profile": [_margins_shifted, _cells_halved],
}
FAULTS = [(w, f) for w in cells()
          for f in FAULTS_BY_ENTRY.get(entry_kind(w), [])]


@pytest.fixture
def fresh_programs():
    """Programs traced with a fault planted must not outlive the test."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__[1:]}" for w, f in FAULTS])
def test_broken_timed_path_is_not_correct(monkeypatch, capsys, workload,
                                          fault, fresh_programs):
    fault(monkeypatch)
    line = _drive(monkeypatch, capsys, workload)
    assert line["correct"] is False, line["checks"]
