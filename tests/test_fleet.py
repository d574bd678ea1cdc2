"""Fleet recalibration service: drift model, ECC observation, and the
closed-loop FleetEngine (tentpole of the fleet subsystem — see
`repro.fleet`)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.calibration import CALIBRATED_VARIATION
from repro.core.variation import FIELD_WEAK_SIGNS, sample_population
from repro.fleet.drift import DriftConfig, DriftModel
from repro.fleet.monitor import ECCConfig, ErrorMonitor, ecc_events
from repro.fleet.recal import FleetEngine, FleetSpec, frontier, run_policies


def tiny_cfg(n_modules=4, n_cells=3):
    return dataclasses.replace(CALIBRATED_VARIATION,
                               n_modules=n_modules, n_cells=n_cells)


def tiny_pop(n_modules=4, n_cells=3, seed=7):
    return sample_population(jax.random.PRNGKey(seed),
                             tiny_cfg(n_modules, n_cells))


class TestDrift:
    def test_aging_is_monotone_toward_weak_side(self):
        cfg = tiny_cfg()
        pop = tiny_pop()
        dm = DriftModel(pop, DriftConfig(vrt_prob=0.0), var_cfg=cfg)
        st = dm.init_state()
        prev = dm.cells(st)
        for _ in range(3):
            st = dm.advance(st, days=5.0)
            cur = dm.cells(st)
            assert (st.aged >= 0).all()
            # every field moves toward its weak side, never back
            d = FIELD_WEAK_SIGNS * (np.log(cur.astype(np.float64))
                                    - np.log(prev.astype(np.float64)))
            assert (d >= -1e-6).all()
            prev = cur

    def test_tail_cells_drift_fastest(self):
        """The guardband-setting tail ages fastest: mean drift rate
        must increase with the weakness score (design-induced
        variation follow-up)."""
        from repro.core.variation import weakness_score
        cfg = tiny_cfg(8, 16)
        pop = tiny_pop(8, 16)
        dm = DriftModel(pop, var_cfg=cfg, seed=0)
        score = np.asarray(weakness_score(np.asarray(pop.cells,
                                                     np.float64), cfg))
        rate = dm.rates.mean(axis=-1).ravel()
        s = score.ravel()
        weak = rate[s > np.quantile(s, 0.9)]
        strong = rate[s < np.quantile(s, 0.1)]
        assert weak.mean() > strong.mean() * 1.5

    def test_vrt_toggles_and_recovers(self):
        cfg = tiny_cfg()
        pop = tiny_pop()
        dm = DriftModel(pop, DriftConfig(vrt_prob=1.0, vrt_recover=1.0,
                                         vrt_drop=0.5), var_cfg=cfg)
        st = dm.advance(dm.init_state())
        assert st.vrt.all()
        base = dm.base[..., 2] * np.exp(-st.aged[..., 2])
        np.testing.assert_allclose(dm.cells(st)[..., 2], base * 0.5,
                                   rtol=1e-5)
        st2 = dm.advance(st)
        assert not st2.vrt.any()        # recover probability 1

    def test_temp_accelerates_aging_one_sided(self):
        dm = DriftModel(tiny_pop(), var_cfg=tiny_cfg())
        ref = dm.cfg.ref_temp_c
        assert dm.temp_factor(ref) == 1.0
        assert dm.temp_factor(ref - 20.0) == 1.0     # no sub-ref credit
        assert dm.temp_factor(ref + 10.0) > 1.0

    def test_population_roundtrip_shape(self):
        pop = tiny_pop()
        dm = DriftModel(pop, var_cfg=tiny_cfg())
        dpop = dm.population(dm.advance(dm.init_state()))
        assert dpop.cells.shape == pop.cells.shape


class TestECC:
    def test_uncorrectable_gated_exactly_zero_below_two(self):
        corr, unc = ecc_events(np.array([0, 1, 2, 5]))
        assert corr[0] == 0.0
        assert corr[1] > 0.0 and corr[2] > 0.0
        # EXACT zero for f < 2 — the zero-uncorrectable guarantee is an
        # integer-count gate, not a float tolerance
        assert unc[0] == 0.0 and unc[1] == 0.0
        assert unc[2] > 0.0 and unc[3] > unc[2]

    def test_rejects_float_counts(self):
        with pytest.raises(AssertionError):
            ecc_events(np.array([1.0, 2.0]))

    def test_rejects_negative_counts(self):
        with pytest.raises(ValueError, match="non-negative"):
            ecc_events(np.array([1, -1, 2]))

    def test_event_penalty_units_contract(self):
        """`event_penalty_ns` takes EVENT COUNTS over one period of
        `accesses` served accesses and returns ns PER ACCESS — the
        number that adds directly onto a mean request latency."""
        from repro.fleet.monitor import event_penalty_ns
        cfg = ECCConfig(corr_penalty_ns=2.0e3, unc_penalty_ns=5.0e6,
                        accesses_per_epoch=1.0e5)
        pen = event_penalty_ns(np.array([10.0]), np.array([2.0]), cfg)
        # (10 * 2e3 + 2 * 5e6) ns over 1e5 accesses
        assert pen[0] == pytest.approx((10 * 2e3 + 2 * 5e6) / 1e5)
        # explicit accesses override scales the denominator, nothing else
        pen2 = event_penalty_ns(np.array([10.0]), np.array([2.0]), cfg,
                                accesses=2.0e5)
        assert pen2[0] == pytest.approx(pen[0] / 2.0)

    def test_monitor_probe_clean_on_undrifted_population(self):
        """The deployed table was profiled on this population, so the
        scrub of the UNDRIFTED cells under the deployed rows must be
        error-free — the zero-error invariant at day 0."""
        from repro.core.aldram import ALDRAMController
        pop = tiny_pop()
        ctrl = ALDRAMController(per_bank=True)
        eng = FleetEngine(pop, FleetSpec(n_epochs=1),
                          var_cfg=tiny_cfg())
        table = eng.controller.profile(pop)
        rows, idx = eng._rows_from_table(table)
        assert idx is None          # per-bank fleet: dense row state
        pr = ErrorMonitor(engine=eng.controller.engine).probe(
            pop, rows[:, 0], float(table.temp_bins[0]))
        assert pr.clean
        assert pr.worst_margin.min() > 0.0
        assert pr.fail_counts.shape == (pop.n_modules, pop.n_banks)


class TestTableLineage:
    def test_patch_bumps_version_and_rollback_restores(self):
        pop = tiny_pop()
        eng = FleetEngine(pop, FleetSpec(n_epochs=1), var_cfg=tiny_cfg())
        t0 = eng.controller.profile(pop)
        assert t0.version == 0
        t1 = t0.patch(safe_trefi_read=t0.safe_trefi_read * 0.5)
        assert t1.version == 1 and t1.parent is t0
        t2 = t1.patch(safe_trefi_read=t1.safe_trefi_read * 0.5)
        assert t2.version == 2
        assert t2.rollback() is t1 and t2.rollback().rollback() is t0
        assert t0.rollback() is t0          # root rolls back to itself
        np.testing.assert_allclose(t2.rollback().rollback().safe_trefi_read,
                                   t0.safe_trefi_read)

    def test_patch_rejects_unknown_fields(self):
        pop = tiny_pop()
        eng = FleetEngine(pop, FleetSpec(n_epochs=1), var_cfg=tiny_cfg())
        t0 = eng.controller.profile(pop)
        with pytest.raises(AssertionError):
            t0.patch(temp_bins=(55.0,))


@pytest.mark.slow
class TestFleetEngine:
    """End-to-end fleet-month smoke (slow: three policies x profile +
    30 probes each)."""

    def setup_method(self):
        self.cfg = tiny_cfg(6, 4)
        self.pop = sample_population(jax.random.PRNGKey(7), self.cfg)
        self.spec = FleetSpec(n_epochs=20, workload_rows=(0,),
                              n_requests=256,
                              module_failures=((8, 2),), seed=0)

    def test_policies_and_frontier(self):
        res = run_policies(self.pop, self.spec, var_cfg=self.cfg)
        fr = frontier(res)
        err = res["error"].summary()
        sta = res["static"].summary()
        # one replay dispatch per serving epoch, every policy
        for r in res.values():
            assert r.replay_dispatches == self.spec.n_epochs
        # zero-error invariant: error-driven serves EXACTLY zero
        # uncorrectable events; static-forever accumulates ECC events
        assert err["total_unc"] == 0.0
        assert sta["total_events"] > 0.0
        assert sta["total_events"] > err["total_events"]
        assert err["eff_reduction"] > sta["eff_reduction"]
        # the error policy actually acted (tighten, recal or relax)
        assert (err["max_tighten_steps"] > 0 or err["n_recals"] > 0)
        assert err["final_version"] > 0
        # heartbeat fault injection: module 2 dies at epoch 8 and is
        # excluded from serving stats from then on
        dead = res["error"].dead_modules
        assert dead[-1] == 1 and dead[:8].max() == 0
        # frontier is anchored on static
        assert fr["policies"]["static"]["errors_avoided"] == 0.0
        assert fr["policies"]["error"]["errors_avoided"] > 0.0

    def test_straggler_fallback_serves_jedec(self):
        """A module whose sampled recalibration trips the straggler
        detector serves JEDEC rows for that epoch."""
        eng = FleetEngine(self.pop,
                          dataclasses.replace(self.spec, policy="periodic"),
                          var_cfg=self.cfg)
        rng = np.random.default_rng(0)
        det = eng._straggler_detector(rng, eng_cluster(eng))
        slow = eng._slow_recals(rng, eng_cluster(eng), det)
        assert slow.shape == (self.pop.n_modules,)
        assert slow.dtype == bool


def eng_cluster(eng):
    from repro.runtime.straggler import ClusterModel
    return ClusterModel(n_nodes=eng.pop.n_modules)


class TestRegionFleet:
    """regions > 1 fleet: the deployed state is the mask-compressed
    unique-row store + shared index map, probes run at (bank, region)
    granularity, tightening acts on unique rows (healing every region
    that shares one), and compression telemetry rides the record."""

    def test_unique_mask_scatters_shared_rows(self):
        idx = np.array([[[0, 0], [1, 2]]], np.int32)     # [1, 2, 2]
        fail = np.zeros((1, 2, 2), bool)
        fail[0, 0, 1] = True          # (bank 0, region 1) shares row 0
        um = FleetEngine._unique_mask(fail, idx, 3)
        assert um.shape == (1, 3)
        assert um[0].tolist() == [True, False, False]
        fail[0, 1, 0] = True          # (bank 1, region 0) -> row 1
        um = FleetEngine._unique_mask(fail, idx, 3)
        assert um[0].tolist() == [True, True, False]

    def test_drift_region_accel_scales_rates(self):
        """`region_accel` multiplies the per-cell rates by the
        row-position factor — same seed, same jitter, so 0.0 is
        bit-exactly the pre-hierarchy trajectory."""
        from repro.core.charge import row_positions
        cfg = tiny_cfg(3, 4)
        pop = tiny_pop(3, 4)
        dm0 = DriftModel(pop, DriftConfig(), var_cfg=cfg, seed=5)
        dm1 = DriftModel(pop, DriftConfig(region_accel=2.0),
                         var_cfg=cfg, seed=5)
        pos = np.asarray(row_positions(4), np.float64)
        np.testing.assert_allclose(
            dm1.rates, dm0.rates * (1.0 + 2.0 * pos)[:, None],
            rtol=1e-12)

    def test_probe_region_axis_consistent_with_dense(self):
        from repro.core.timing import DDR3_1600
        pop = tiny_pop(3, 4)
        m, bk = pop.n_modules, pop.n_banks
        rows3 = np.broadcast_to(DDR3_1600.as_row(),
                                (m, bk, 6)).astype(np.float32).copy()
        mon = ErrorMonitor()
        p3 = mon.probe(pop, rows3, 55.0)
        assert p3.fail_counts.shape == (m, bk)
        # rg=1 region layout is value-identical to the dense probe
        p41 = mon.probe(pop, rows3[:, :, None, :], 55.0)
        assert p41.fail_counts.shape == (m, bk, 1)
        assert np.array_equal(p41.fail_counts[..., 0], p3.fail_counts)
        assert np.array_equal(p41.worst_margin[..., 0],
                              p3.worst_margin)
        # rg=2 with region-constant rows partitions the same cells
        p42 = mon.probe(pop, np.broadcast_to(
            rows3[:, :, None, :], (m, bk, 2, 6)).copy(), 55.0)
        assert p42.fail_counts.shape == (m, bk, 2)
        assert np.array_equal(p42.fail_counts.sum(axis=2),
                              p3.fail_counts)
        assert np.array_equal(p42.worst_margin.min(axis=2),
                              p3.worst_margin)

    @pytest.mark.slow
    def test_region_fleet_closed_loop(self):
        """End-to-end regions=2 error-policy month: one replay
        dispatch per epoch, a per-region deployed table, and the
        compression-ratio telemetry on the served rows."""
        cfg = tiny_cfg(4, 8)
        pop = tiny_pop(4, 8)
        spec = FleetSpec(policy="error", n_epochs=5, n_requests=96,
                         workload_rows=(0,), temp_bins=(55.0, 85.0),
                         regions=2, seed=0)
        eng = FleetEngine(pop, spec, var_cfg=cfg,
                          drift_cfg=DriftConfig(region_accel=3.0))
        res = eng.run()
        assert res.replay_dispatches == spec.n_epochs
        assert res.table.per_region and res.table.regions == 2
        assert res.compression_ratio.shape == (spec.n_epochs,)
        assert ((res.compression_ratio > 0.0)
                & (res.compression_ratio <= 1.0)).all()
        s = res.summary()
        assert 0.0 < s["mean_compression_ratio"] <= 1.0
        assert s["final_compression_ratio"] == res.compression_ratio[-1]
        # the deployed state round-trips: unique store + shared map
        rows, idx = eng._rows_from_table(res.table)
        assert idx is not None and idx.shape == (4, pop.n_banks, 2)
        dense = FleetEngine._dense(rows[:, 0], idx)
        assert dense.shape == (4, pop.n_banks, 2, 6)
