"""AL-DRAM mechanism tests: profiler envelopes, controller tables,
reliability invariant, guardband semantics."""

import dataclasses

import numpy as np
import pytest

from repro.core import timing as T
from repro.core.aldram import ALDRAMController
from repro.core.calibration import CALIBRATED_CONSTANTS
from repro.core.profiler import Profiler


@pytest.fixture(scope="module")
def controller(small_pop):
    ctrl = ALDRAMController(
        Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5),
        temp_bins=(55.0, 70.0, 85.0))
    ctrl.profile(small_pop)
    return ctrl


# make module-scoped fixture see session fixture
@pytest.fixture(scope="module")
def small_pop():
    import jax
    from repro.core.calibration import CALIBRATED_VARIATION
    from repro.core.variation import sample_population
    cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=10, n_cells=6)
    return sample_population(jax.random.PRNGKey(7), cfg)


class TestProfiler:
    def test_refresh_envelope_beats_standard(self, small_pop):
        prof = Profiler(constants=CALIBRATED_CONSTANTS)
        rp = prof.refresh_profile(small_pop, 85.0, "read")
        assert (rp.per_module >= T.STANDARD_TREFI_MS).all(), \
            "every module must sustain the 64 ms standard"

    def test_bank_envelope_at_least_module(self, small_pop):
        prof = Profiler(constants=CALIBRATED_CONSTANTS)
        rp = prof.refresh_profile(small_pop, 85.0, "read")
        assert (rp.per_bank.min(axis=1) >= rp.per_module - 1e-6).all() or \
               np.allclose(rp.per_bank.min(axis=1), rp.per_module), \
            "module envelope is the min over its banks"

    def test_guardband_applied(self, small_pop):
        prof = Profiler(constants=CALIBRATED_CONSTANTS)
        rp = prof.refresh_profile(small_pop, 85.0, "read")
        assert (rp.safe <= rp.per_module - T.REFRESH_STEP_MS + 1e-6).all()

    def test_chosen_combos_pass(self, small_pop):
        prof = Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5)
        rp = prof.refresh_profile(small_pop, 85.0, "read")
        tp = prof.timing_profile(small_pop, 85.0, "read", rp.safe)
        # re-evaluate chosen combos: margins must be non-negative
        from repro.kernels.charge_sim import ops
        import jax.numpy as jnp
        for m in range(small_pop.n_modules):
            r, _ = ops.combo_margins(
                jnp.asarray(small_pop.module(m)),
                jnp.asarray(tp.combos[m:m + 1]), 85.0,
                CALIBRATED_CONSTANTS, impl="ref")
            assert float(np.asarray(r).min()) >= 0.0


class TestController:
    def test_selection_conservative_in_temperature(self, controller):
        """Latency at a hotter bin is never lower (paper Sec. 4)."""
        for m in range(4):
            lat = [controller.select(m, t).read_sum()
                   for t in (40.0, 55.0, 70.0, 85.0)]
            assert all(a <= b + 1e-6 for a, b in zip(lat, lat[1:])), lat

    def test_above_hottest_bin_falls_back_to_jedec(self, controller):
        p = controller.select(0, 90.0)
        assert p.read_sum() == T.DDR3_1600.read_sum()

    def test_all_tables_at_or_below_standard(self, controller):
        tbl = controller.table
        std = np.array([T.DDR3_1600.trcd, T.DDR3_1600.tras,
                        T.DDR3_1600.twr, T.DDR3_1600.trp])
        assert (tbl.params <= std[None, None, :] + 1e-6).all()

    def test_reliability_invariant(self, controller, small_pop):
        """The 33-day zero-error claim: every selected table is
        error-free for its module at its bin's max temperature."""
        assert controller.verify(small_pop)

    def test_verify_chunked_module_groups(self, controller, small_pop,
                                          monkeypatch):
        """Forcing a tiny `max_grid_elems` drives the g < m chunked
        path: several margin dispatches over module groups, same
        verdict as the single-dispatch grid."""
        m, b = controller.table.module_params.shape[:2]
        banks = controller.table.n_banks
        cols = b * (1 + banks)       # envelope + per-bank combo columns
        cpm = int(np.prod(small_pop.cells.shape[1:4]))
        calls = {"n": 0, "rows": []}
        real = controller.engine.margins

        def spy(cells, combos, **kw):
            calls["n"] += 1
            calls["rows"].append((np.asarray(cells).shape[0],
                                  np.asarray(combos).shape[0]))
            return real(cells, combos, **kw)

        monkeypatch.setattr(controller.engine, "margins", spy)
        # small enough that each group is a single module: g == 1
        assert controller.verify(small_pop, max_grid_elems=cpm * cols)
        assert calls["n"] == m, calls
        assert all(r == (cpm, cols) for r in calls["rows"]), calls["rows"]

        calls["n"], calls["rows"] = 0, []
        # the default budget keeps the tested size one dispatch
        assert controller.verify(small_pop)
        assert calls["n"] == 1 and calls["rows"][0] == (m * cpm, m * cols)

    @pytest.mark.parametrize("regions", [1, 2])
    def test_profile_chunked_module_groups(self, small_pop, monkeypatch,
                                           regions):
        """A grid budget below the campaign's size runs the timing
        campaign over module groups — tables and selection views are
        bit-identical to the single dispatch."""
        from repro.core import aldram

        def make():
            return ALDRAMController(
                Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5),
                temp_bins=(55.0, 85.0), regions=regions)

        one = make()
        one.profile(small_pop)
        cpm = int(np.prod(small_pop.cells.shape[1:4]))
        cols = 2 * sum(t.combos.shape[0]
                       for t in one.sweep_result.spec.tests)
        monkeypatch.setattr(aldram, "PROFILE_GRID_ELEMS", 3 * cpm * cols)
        grp = make()
        grp.profile(small_pop)
        # refresh + ceil(10 / 3) module groups
        assert grp.engine.dispatch_count == 1 + 4
        assert np.array_equal(one.table.params, grp.table.params)
        assert np.array_equal(one.table.module_params,
                              grp.table.module_params)
        for f in aldram._MODULE_VIEWS:
            for a, b in zip(getattr(one.sweep_result, f),
                            getattr(grp.sweep_result, f)):
                assert np.array_equal(a, b), f

    def test_reductions_deeper_when_cooler(self, controller):
        r55 = controller.average_reductions(55.0)
        r85 = controller.average_reductions(85.0)
        for k in ("tras", "twr", "trp"):
            assert r55[k] >= r85[k] - 1e-6, (k, r55[k], r85[k])


class TestAdaptiveTable:
    def test_guardbanded_selection(self):
        from repro.core.autotune import AdaptiveTable
        rng = np.random.default_rng(0)
        t = AdaptiveTable((0.5, 1.0), static_worst_case=100.0,
                          quantile=0.99, k_sigma=2.0)
        for _ in range(200):
            t.observe(0, 0.3, rng.normal(10, 1))
        t.fit()
        v = t.select(0, 0.3)
        assert 10 < v < 25, v                       # guardbanded, not worst
        assert t.select(0, 0.9) == 100.0            # unprofiled bin: JEDEC
        assert t.select(1, 0.3) == 100.0            # unprofiled unit
        assert 0.7 < t.savings(0, 0.3) < 0.95
