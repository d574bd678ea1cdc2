"""Fault-injection contracts (ISSUE 9; `repro.core.faults`).

Three brackets:
  * ZERO-FAULT PARITY — `SimSpec(faults=FaultSpec.none())` is
    bit-identical to a spec with NO fault axis on every backend
    (static scan/pallas_interpret and adaptive
    scan/pallas_interpret, device and host stats), up to the trailing
    F=1 axis.  The no-fault spec compiles the EXACT unfaulted code
    path — the same static-branch pinning as the C*R==1 channel case.
  * FAULTED BEHAVIOR — the fault axis rides the grid: inert lane 0
    reproduces the unfaulted numbers bit-for-bit, error lanes count
    detected/silent errors, the watchdog trips/degrades/probes with
    the EXACT detected-error bound, and every backend agrees
    bit-for-bit on latencies, bins and counters (temps agree to float
    reduction noise, like the existing backend contract).
  * FLEET TELEMETRY — `FleetSpec(faults=...)` injects faults into the
    serving replay and the in-scan detected errors drive the
    error-driven guardband policy (tighten spy).
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import faults
from repro.core import timing as T
from repro.core.dram_sim import OPEN_FCFS, Policy, Trace
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import ThermalSpec, diurnal, steady

N = 160


def mk_trace(n, seed):
    r = np.random.default_rng(seed)
    arr = np.cumsum(r.uniform(2.0, 14.0, n)).astype(np.float32)
    return Trace(arr, r.integers(0, 8, n).astype(np.int32),
                 r.integers(0, 64, n).astype(np.int32),
                 (r.uniform(size=n) < 0.3))


TRACES = (mk_trace(N, 1), mk_trace(N - 25, 2))
# three static rows, JEDEC (slowest) LAST per the faulted convention
ROWS = np.stack([T.TimingParams(trcd=13.75 - 1.5 * i, tras=35.0 - 4 * i,
                                twr=15.0 - 1.5 * i,
                                trp=13.75 - 1.5 * i).as_row()
                 for i in range(3)])[::-1].copy()
POLS = (OPEN_FCFS, Policy(page="closed"), Policy(reorder_window=8))

ACTIVE = faults.FaultSpec(scenarios=(
    faults.FaultScenario(name="none"),
    faults.FaultScenario(name="err", err_scale=0.8, err_free_red=0.0,
                         detect_frac=0.9, retry_ns=60.0),
    faults.FaultScenario(name="wd", err_scale=0.8, err_free_red=0.0,
                         detect_frac=1.0, wd_err_n=4, wd_probe=16,
                         wd_recover_n=2),
), seed=3)

THERMAL = ThermalSpec(scenarios=(steady(48.0), diurnal(45.0, 80.0, 2e3)),
                      temp_bins=(55.0, 70.0, 85.0))
TABS = np.stack([ROWS[0], ROWS[1], ROWS[2], ROWS[2]])[None]   # [1, 4, 6]
SENS = faults.FaultSpec(scenarios=(
    faults.FaultScenario(name="none"),
    faults.FaultScenario(name="stuck", stuck_c=30.0, stuck_from_ns=100.0,
                         err_scale=0.5, err_bin_c=0.02, err_free_red=0.0),
    faults.FaultScenario(name="noisy_wd", noise_c=6.0, err_scale=0.5,
                         err_free_red=0.0, wd_err_n=3, wd_sense_n=4,
                         wd_jump_c=8.0, wd_probe=12, wd_recover_n=2),
), seed=7)

STATIC_BACKENDS = ("scan", "pallas_interpret")
ADAPTIVE_BACKENDS = ("scan", "pallas_interpret")


def static_spec(fspec=None, collect=()):
    return SimSpec(traces=TRACES, timings=ROWS, policies=POLS,
                   faults=fspec, collect=collect)


def adaptive_spec(fspec=None, collect=("latencies", "temps", "bins")):
    return SimSpec(traces=TRACES, timings=TABS, policies=POLS[:2],
                   thermal=THERMAL, faults=fspec, collect=collect)


@pytest.fixture(scope="module")
def static_res():
    """{backend: (plain, none, faulted)} static results, one compile
    each for the whole module."""
    out = {}
    for be in STATIC_BACKENDS:
        eng = SimEngine(backend=be)
        out[be] = (eng.run(static_spec()),
                   eng.run(static_spec(faults.FaultSpec.none())),
                   eng.run(static_spec(ACTIVE, collect=("latencies",))))
    return out


@pytest.fixture(scope="module")
def adaptive_res():
    out = {}
    for be in ADAPTIVE_BACKENDS:
        eng = SimEngine(backend=be)
        out[be] = (eng.run(adaptive_spec()),
                   eng.run(adaptive_spec(faults.FaultSpec.none())),
                   eng.run(adaptive_spec(SENS)))
    return out


class TestZeroFaultParity:
    @pytest.mark.parametrize("be", STATIC_BACKENDS)
    def test_static_none_bit_identical(self, static_res, be):
        r0, rn, _ = static_res[be]
        assert r0.fault_counters is None
        assert rn.mean_latency_ns.shape == r0.mean_latency_ns.shape + (1,)
        assert np.array_equal(rn.mean_latency_ns[..., 0],
                              r0.mean_latency_ns)
        assert np.array_equal(rn.p99_latency_ns[..., 0], r0.p99_latency_ns)
        assert np.array_equal(rn.total_ns[..., 0], r0.total_ns)
        assert rn.fault_counters.shape == (r0.total_ns.shape
                                           + (1, faults.N_COUNTERS))
        assert rn.fault_counters.sum() == 0

    @pytest.mark.parametrize("be", ADAPTIVE_BACKENDS)
    def test_adaptive_none_bit_identical(self, adaptive_res, be):
        r0, rn, _ = adaptive_res[be]
        assert r0.fault_counters is None
        assert rn.mean_latency_ns.shape == r0.mean_latency_ns.shape + (1,)
        assert np.array_equal(rn.mean_latency_ns[..., 0],
                              r0.mean_latency_ns)
        assert np.array_equal(rn.temps[..., 0, :], r0.temps)
        assert np.array_equal(rn.bank_heat[..., 0, :], r0.bank_heat)
        assert np.array_equal(rn.temp_max[..., 0], r0.temp_max)
        assert np.array_equal(rn.bin_switches[..., 0], r0.bin_switches)
        assert rn.fault_counters.sum() == 0

    def test_host_stats_none_bit_identical(self):
        eng = SimEngine(backend="scan", stats="host", reorder="host")
        r0 = eng.run(static_spec())
        rn = eng.run(static_spec(faults.FaultSpec.none()))
        assert np.array_equal(rn.mean_latency_ns[..., 0],
                              r0.mean_latency_ns)
        assert np.array_equal(rn.p99_latency_ns[..., 0], r0.p99_latency_ns)
        assert rn.fault_counters.sum() == 0

    def test_none_spec_is_flagged_inert(self):
        assert faults.FaultSpec.none().is_none
        assert not ACTIVE.is_none
        assert not static_spec(faults.FaultSpec.none()).fault_on
        assert static_spec(ACTIVE).fault_on


class TestFaultedStatic:
    def test_shapes_and_inert_lane(self, static_res):
        r0, _, rf = static_res["scan"]
        t, p, s = r0.mean_latency_ns.shape
        assert rf.mean_latency_ns.shape == (t, p, s, len(ACTIVE))
        assert rf.fault_counters.shape == (t, p, s, len(ACTIVE),
                                           faults.N_COUNTERS)
        # lane 0 is inert: bit-identical to the unfaulted campaign
        assert np.array_equal(rf.mean_latency_ns[..., 0],
                              r0.mean_latency_ns)
        assert rf.fault_counters[..., 0, :].sum() == 0

    def test_error_lane_counts_and_prices(self, static_res):
        r0, _, rf = static_res["scan"]
        assert rf.detected_errors[..., 1].sum() > 0
        assert rf.silent_errors[..., 1].sum() > 0
        # detected retries are priced: the error lane is slower than
        # the inert lane on the reduced (non-JEDEC) rows
        assert (rf.total_ns[:, :, :-1, 1]
                > rf.total_ns[:, :, :-1, 0]).all()

    def test_watchdog_lane(self, static_res):
        _, _, rf = static_res["scan"]
        det = rf.detected_errors[..., 2]
        bound = 4 * (rf.wd_trips[..., 2] + 1) + rf.wd_probes[..., 2]
        assert (det <= bound).all()
        assert rf.degraded_requests[..., 2].sum() > 0
        assert rf.wd_trips[..., 2].sum() > 0
        # detect_frac=1.0: the watchdog lane never corrupts silently
        assert rf.silent_errors[..., 2].sum() == 0

    @pytest.mark.parametrize("be", ("pallas_interpret",))
    def test_cross_backend_bit_exact(self, static_res, be):
        a, b = static_res["scan"][2], static_res[be][2]
        assert np.array_equal(a.fault_counters, b.fault_counters)
        assert np.array_equal(a.latencies, b.latencies)
        assert np.array_equal(a.mean_latency_ns, b.mean_latency_ns)

    def test_host_stats_match_device(self, static_res):
        eng = SimEngine(backend="scan", stats="host", reorder="host")
        rh = eng.run(static_spec(ACTIVE, collect=("latencies",)))
        a = static_res["scan"][2]
        assert np.array_equal(rh.fault_counters, a.fault_counters)
        assert np.array_equal(rh.latencies, a.latencies)


class TestFaultedAdaptive:
    def test_shapes_and_inert_lane(self, adaptive_res):
        r0, _, rf = adaptive_res["scan"]
        assert rf.mean_latency_ns.shape == (r0.mean_latency_ns.shape
                                            + (len(SENS),))
        assert np.array_equal(rf.mean_latency_ns[..., 0],
                              r0.mean_latency_ns)
        assert rf.fault_counters[..., 0, :].sum() == 0

    def test_sensor_fault_causes_errors(self, adaptive_res):
        _, _, rf = adaptive_res["scan"]
        # the stuck-cold sensor mis-bins at hot temperatures -> errors
        assert (rf.detected_errors[..., 1].sum()
                + rf.silent_errors[..., 1].sum()) > 0

    def test_watchdog_bound(self, adaptive_res):
        _, _, rf = adaptive_res["scan"]
        det = rf.detected_errors[..., 2]
        bound = 3 * (rf.wd_trips[..., 2] + 1) + rf.wd_probes[..., 2]
        assert (det <= bound).all()

    def test_cross_backend_bit_exact(self, adaptive_res):
        a = adaptive_res["scan"][2]
        b = adaptive_res["pallas_interpret"][2]
        assert np.array_equal(a.fault_counters, b.fault_counters)
        assert np.array_equal(a.latencies, b.latencies)
        assert np.array_equal(a.bins, b.bins)
        assert np.array_equal(a.temp_max, b.temp_max)
        assert np.array_equal(a.bin_switches, b.bin_switches)
        # float temperature reductions agree to reduction noise only
        assert np.allclose(a.temps, b.temps, atol=1e-4)


class TestDispatchAndValidation:
    def test_faulted_campaign_is_one_dispatch(self):
        eng = SimEngine(backend="scan")
        eng.run(static_spec(ACTIVE))
        assert eng.dispatch_count == 1
        eng.run(adaptive_spec(SENS))
        assert eng.dispatch_count == 2

    def test_per_bank_static_faults_unsupported(self):
        rows_b = np.repeat(ROWS[:, None, :], 8, axis=1)   # [S, B, 6]
        with pytest.raises(AssertionError, match="per-bank"):
            SimSpec(traces=TRACES, timings=rows_b, policies=POLS,
                    faults=ACTIVE)

    def test_run_bracket_rejects_faults(self):
        eng = SimEngine(backend="scan")
        with pytest.raises(AssertionError, match="bracket"):
            eng.run_bracket(adaptive_spec(SENS), ROWS[-1])

    def test_faults_type_checked(self):
        with pytest.raises(AssertionError):
            SimSpec(traces=TRACES, timings=ROWS, policies=POLS,
                    faults="not-a-faultspec")


class TestFleetTelemetry:
    def test_detected_errors_drive_tightening(self, monkeypatch):
        from repro.core import guardband
        from repro.core.calibration import CALIBRATED_VARIATION
        from repro.core.variation import sample_population
        from repro.fleet.recal import FleetEngine, FleetSpec

        cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=4,
                                  n_cells=3)
        pop = sample_population(jax.random.PRNGKey(7), cfg)
        fa = faults.FaultSpec(scenarios=(
            faults.FaultScenario(name="err", err_scale=1.0,
                                 err_free_red=0.0, detect_frac=0.9,
                                 retry_ns=60.0),), seed=5)
        spec = FleetSpec(policy="error", n_epochs=4, workload_rows=(0,),
                         n_requests=256, seed=0, faults=fa)
        eng = FleetEngine(pop, spec, var_cfg=cfg)

        calls = []
        orig = guardband.tighten_rows

        def spy(rows, mask=None, **kw):
            calls.append(None if mask is None else mask.copy())
            return orig(rows, mask=mask, **kw)

        monkeypatch.setattr(guardband, "tighten_rows", spy)
        res = eng.run()

        assert res.served_detected.sum() > 0
        assert res.replay_dispatches == spec.n_epochs
        # in-scan telemetry reached the guardband policy
        assert len(calls) > 0
        assert res.tighten_steps.sum() > 0
        s = res.summary()
        assert s["total_served_detected"] == res.served_detected.sum()
        assert s["total_served_silent"] == res.served_silent.sum()

    def test_no_faults_no_served_counters(self):
        from repro.core.calibration import CALIBRATED_VARIATION
        from repro.core.variation import sample_population
        from repro.fleet.recal import FleetEngine, FleetSpec

        cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=4,
                                  n_cells=3)
        pop = sample_population(jax.random.PRNGKey(7), cfg)
        spec = FleetSpec(policy="error", n_epochs=2, workload_rows=(0,),
                         n_requests=256, seed=0)
        res = FleetEngine(pop, spec, var_cfg=cfg).run()
        assert res.served_detected.sum() == 0
        assert res.served_silent.sum() == 0
