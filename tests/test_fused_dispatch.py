"""Fused synth->replay dispatch, exact FR-FCFS buffer shrink and the
padding-suffix invariant: the fast-path contracts.

  * a `SynthSpec` trace axis makes synthesis part of the ONE replay
    dispatch, bit-identical to materializing the batch first
    (threefry determinism);
  * `run_bracket` fuses adaptive replay + on-device worst-bin
    round-up + static bracket into the same launch, matching the
    two-dispatch host formulation;
  * `_eff_window` shrinks the FR-FCFS pending buffer to its exact
    slack-horizon bound without changing the permutation;
  * interior-invalid masks are rejected loudly everywhere a replay
    layout would silently desynchronize.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim, perf_model
from repro.core.dram_sim import OPEN_FCFS, Policy, SynthSpec, Trace
from repro.core.sim_engine import SimEngine, SimSpec, _eff_window
from repro.core.thermal import (ThermalConfig, ThermalSpec, diurnal,
                                steady)
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, stack_timing
from repro.kernels.replay import ops as replay_ops


def _small_synth(n=64, workloads=3):
    offs, rhs, wfs, ias = perf_model._pool_knobs()
    return SynthSpec(n=n, offsets=offs[:workloads],
                     row_hits=rhs[:workloads],
                     write_fracs=wfs[:workloads],
                     inter_arrivals=ias[:workloads])


class TestSynthFusion:
    def test_synth_spec_materializes_trace_batch(self):
        """The declarative pool == the materialized pool, bit for bit
        (threefry: same fold offsets -> same streams)."""
        tb = perf_model.trace_batch(n=64, seed=0)
        mat = perf_model.synth_spec(n=64, seed=0).materialize()
        assert len(mat) == np.asarray(tb.arrival).shape[0]
        for i, tr in enumerate(mat):
            for a, b, name in zip(tr, tb, Trace._fields):
                assert np.array_equal(np.asarray(a),
                                      np.asarray(b)[i]), (i, name)

    def test_fused_run_bit_identical_one_dispatch(self):
        synth = _small_synth()
        mat = synth.materialize()
        rows = stack_timing([DDR3_1600, ALDRAM_55C_EVAL])
        policies = (OPEN_FCFS, Policy(reorder_window=8))
        kw = dict(timings=rows, policies=policies)
        eng = SimEngine()
        res_m = eng.run(SimSpec(traces=mat, **kw))
        s0 = perf_model.synth_dispatch_count
        d0 = eng.dispatch_count
        res_f = eng.run(SimSpec(traces=synth, **kw))
        assert eng.dispatch_count - d0 == 1
        assert perf_model.synth_dispatch_count == s0, \
            "fused run must not launch a separate synthesis"
        assert np.array_equal(res_f.mean_latency_ns, res_m.mean_latency_ns)
        assert np.array_equal(res_f.p99_latency_ns, res_m.p99_latency_ns)
        assert np.array_equal(res_f.total_ns, res_m.total_ns)

    def test_fused_adaptive_matches_materialized(self):
        synth = _small_synth()
        mat = synth.materialize()
        tab = np.stack([ALDRAM_55C_EVAL.as_row(),
                        DDR3_1600.as_row()])[None]
        tspec = ThermalSpec(
            scenarios=(steady(48.0), diurnal(40.0, 90.0,
                                             period_ns=2.0e4)),
            temp_bins=(55.0,),
            config=ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4))
        kw = dict(timings=tab, policies=(Policy(reorder_window=4),),
                  thermal=tspec)
        eng = SimEngine()
        res_m = eng.run(SimSpec(traces=mat, **kw))
        res_f = eng.run(SimSpec(traces=synth, **kw))
        for f in ("mean_latency_ns", "total_ns", "temp_max",
                  "temp_mean", "bin_switches", "bank_heat"):
            assert np.array_equal(getattr(res_f, f),
                                  getattr(res_m, f)), f

    def test_synth_dispatch_scope(self):
        synth = _small_synth(n=32)
        with perf_model.synth_dispatch_scope() as outer:
            synth.materialize()              # first call -> 1 dispatch
            synth.materialize()              # cached -> free
            with perf_model.synth_dispatch_scope(reset=True) as inner:
                _small_synth(n=16).materialize()
            assert inner.count == 1
        assert outer.count == 1              # inner was reset
        assert inner.count == 1              # frozen at scope exit


class TestRunBracket:
    def test_matches_two_dispatch_formulation(self):
        synth = _small_synth()
        tab = np.stack([ALDRAM_55C_EVAL.as_row(), DDR3_1600.as_row()])
        bins = (55.0,)
        cfg = ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4)
        scns = (steady(48.0), diurnal(40.0, 90.0, period_ns=2.0e4))
        tspec = ThermalSpec(scenarios=scns, temp_bins=bins, config=cfg)
        policies = (Policy(reorder_window=4),)
        base = DDR3_1600.as_row()
        spec = SimSpec(traces=synth, timings=tab[None],
                       policies=policies, thermal=tspec)
        eng = SimEngine()
        d0 = eng.dispatch_count
        br = eng.run_bracket(spec, base_row=base)
        assert eng.dispatch_count - d0 == 1

        # reference formulation: adaptive run, host round-up, static run
        res_a = SimEngine().run(spec)
        assert np.array_equal(br["adaptive"]["mean"],
                              res_a.mean_latency_ns)
        peak = res_a.temp_max[:, :, 0, :].max(axis=(0, 1))
        np.testing.assert_allclose(br["temp_peak"], peak, rtol=1e-6)
        worst = np.searchsorted(np.asarray(bins, np.float32),
                                peak + cfg.hyst_c, side="left")
        assert np.array_equal(br["worst_bin"], worst)
        rows = np.concatenate([base[None], tab[worst]], axis=0)
        res_s = SimEngine().run(SimSpec(traces=synth, timings=rows,
                                        policies=policies))
        assert np.array_equal(br["static"]["mean"],
                              res_s.mean_latency_ns)

    def test_evaluate_adaptive_fused_parity_and_dispatches(self):
        tab = np.stack([ALDRAM_55C_EVAL.as_row(), DDR3_1600.as_row()])
        kw = dict(bins=(55.0,),
                  scenarios=(steady(48.0),
                             diurnal(40.0, 90.0, period_ns=2.0e4)),
                  config=ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4),
                  n=64, policies=(Policy(reorder_window=4),))
        runs = {}
        for fused in (False, True):
            eng = SimEngine()
            with perf_model.synth_dispatch_scope() as scope:
                res = perf_model.evaluate_adaptive(tab, fused=fused,
                                                   engine=eng, **kw)
            runs[fused] = (res, eng.dispatch_count, scope.count)
        res_d, replays_d, synths_d = runs[False]
        res_f, replays_f, synths_f = runs[True]
        assert (replays_d, synths_d) == (2, 1)
        assert (replays_f, synths_f) == (1, 0)
        assert np.array_equal(res_f["worst_bin"], res_d["worst_bin"])
        for pd_f, pd_d in zip(res_f["per_policy"], res_d["per_policy"]):
            for name in pd_f:
                for key in ("adaptive_gmean", "static_worst_gmean",
                            "oracle_gmean"):
                    np.testing.assert_allclose(pd_f[name][key],
                                               pd_d[name][key],
                                               rtol=1e-6,
                                               err_msg=(name, key))


class TestEffWindow:
    def test_exact_shrink_preserves_permutation(self):
        tr = dram_sim.synth_trace(jax.random.PRNGKey(7), 200,
                                  row_hit=0.6)
        arr = np.asarray(tr.arrival)
        valid = np.ones(200, bool)
        window, slack, cap = 32, 30.0, 16.0
        eff = _eff_window(arr[None], valid[None], window, slack)
        assert 1 <= eff < window, eff      # the bound actually bites

        def perm(buf):
            return np.asarray(dram_sim.frfcfs_perm(
                jnp.asarray(arr), tr.bank, tr.row, jnp.asarray(valid),
                jnp.float32(window), jnp.float32(slack),
                jnp.float32(cap), buf))

        assert np.array_equal(perm(eff), perm(window))

    def test_decreasing_arrivals_fall_back_to_nominal(self):
        arr = np.array([[5.0, 3.0, 8.0]], np.float32)
        valid = np.ones((1, 3), bool)
        assert _eff_window(arr, valid, 16, 30.0) == 16


class TestPrefixInvariant:
    def _holey(self):
        arr = np.zeros((1, 8), np.float32)
        ib = np.zeros((1, 8), np.int32)
        valid = np.ones((1, 8), bool)
        valid[0, 3] = False                  # interior hole
        return arr, ib, valid

    def test_check_prefix_valid_rejects_interior_invalid(self):
        _, _, valid = self._holey()
        with pytest.raises(ValueError, match="prefix"):
            dram_sim.check_prefix_valid(valid, "test")
        # prefix-true masks (including all-False padding rows) pass
        ok = np.zeros((2, 8), bool)
        ok[0, :5] = True
        dram_sim.check_prefix_valid(ok, "test")

    def test_replay_grid_rejects_interior_invalid(self):
        arr, ib, valid = self._holey()
        a3 = jnp.asarray(np.broadcast_to(arr[:, None], (1, 1, 8)))
        i3 = jnp.asarray(np.broadcast_to(ib[:, None], (1, 1, 8)))
        rows = stack_timing([DDR3_1600])
        with pytest.raises(ValueError, match="prefix"):
            replay_ops.replay_grid(a3, i3, i3, i3.astype(bool),
                                   jnp.asarray(valid),
                                   jnp.asarray(rows),
                                   jnp.zeros((1,), bool))
