"""The check of `ycsb-a-ddr4-2r-1ch` sees the mechanism and the skew: at
a size a CPU test holds, the sound program passes it, and each of these
fails it — the plain reference computed in bfloat16, and the program
with a planted fault: tCCD_L treated as tCCD_S, tRRD_L treated as
tRRD_S, uniform keys in place of Zipf.

tFAW is not among them: in this traffic no rank ever has five ACTs
within tFAW (a record's 16 lines sit in one row of 2 banks, so an FCFS
stream activates at most 2 banks per 16 requests), and the program
with tFAW ignored gives every compared number bit for bit; the last
test records that.  The hand-built traces of tests/test_bank_groups.py
show tFAW in the program and in the reference."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_util import run_module, small_cell

run = run_module()
WORKLOAD = "ycsb-a-ddr4-2r-1ch"
SEED = 5000000011
FIELDS = ("trrd_s", "trrd_l", "tfaw", "tccd_s", "tccd_l")


@pytest.fixture
def fresh_programs():
    """Programs traced with a fault planted must not outlive the test."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def _cell():
    cell_def, config, traffic = small_cell(WORKLOAD, n_requests=1024)
    traffic["check"]["sample_streams"] = 4
    return run.entry_module(traffic["entry"]).Cell(
        config, traffic, SEED, int(cell_def["chips"]))


def _number(cell, ref=None):
    (name, limit), = cell.traffic["check"]["limits"].items()
    out = cell.select(cell.call())
    return cell.number(out, cell.reference() if ref is None else ref), limit


def _rank_timing(monkeypatch, **swap):
    """The program replays with some rank timings replaced: each named
    one takes the value of the field named, or the number given."""
    from repro.core import dram_sim
    orig = dram_sim.service_math

    def planted(*a):
        if len(a) == 15:                  # the rank and bank-group path
            state, timings = a[14]
            t = dict(zip(FIELDS, timings))
            for k, v in swap.items():
                t[k] = t[v] if isinstance(v, str) else v
            a = a[:14] + ((state, tuple(t[k] for k in FIELDS)),)
        return orig(*a)
    monkeypatch.setattr(dram_sim, "service_math", planted)


def _uniform_keys(monkeypatch):
    """Every record rank equally likely: a uniform inverse-CDF table."""
    from repro.core import dram_sim

    def uniform(n_items, theta):
        return np.floor(np.arange(1, n_items, dtype=np.float64) / n_items
                        * 2.0 ** 32).astype(np.uint32)
    monkeypatch.setattr(dram_sim, "zipf_cdf_u32", uniform)


FAULTS = {
    "tccd_l_as_tccd_s": lambda mp: _rank_timing(mp, tccd_l="tccd_s"),
    "trrd_l_as_trrd_s": lambda mp: _rank_timing(mp, trrd_l="trrd_s"),
    "uniform_keys": _uniform_keys,
}


def test_sound_program_passes():
    value, limit = _number(_cell())
    assert value <= limit, value


def test_bfloat16_control_fails():
    cell = _cell()
    ref = cell.reference(np.float32)
    value = cell.number(cell.reference(jnp.bfloat16), ref)
    assert value > 1e-5, value


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_fault_fails(monkeypatch, fresh_programs, fault):
    FAULTS[fault](monkeypatch)
    value, limit = _number(_cell())
    assert value > limit, (fault, value)


def test_tfaw_never_binds_in_this_traffic(monkeypatch, fresh_programs):
    cell = _cell()
    sound = cell.call()
    _rank_timing(monkeypatch, tfaw=0.0)
    import jax
    jax.clear_caches()
    ignored = _cell().call()
    for k in sound:
        np.testing.assert_array_equal(ignored[k], sound[k])
