"""MarginEngine / SweepSpec tests: fused-vs-per-bin equivalence
(bit-for-bit on the ref impl), temperature monotonicity of the pass
envelopes, old-path-vs-new-path controller tables, and the dispatch
count invariant (profiling campaigns cost O(1) kernel launches)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import timing as T
from repro.core.aldram import ALDRAMController
from repro.core.calibration import CALIBRATED_CONSTANTS
from repro.core.profiler import Profiler
from repro.core.sweep import MarginEngine, Op, OpSweep, SweepSpec
from repro.core.variation import Population
from repro.kernels.charge_sim import ops as charge_ops

C = CALIBRATED_CONSTANTS
TEMPS = (55.0, 70.0, 85.0)
GRID_STEP = 2.5


def make_profiler():
    return Profiler(constants=C, grid_step=GRID_STEP, impl="ref")


@pytest.fixture(scope="module")
def campaign(small_pop):
    """One fused read+write, multi-temperature campaign."""
    prof = make_profiler()
    rng = np.random.default_rng(3)
    n = small_pop.n_modules
    trefi_r = (64.0 + 8.0 * rng.integers(0, 10, n)).astype(np.float32)
    trefi_w = (64.0 + 8.0 * rng.integers(0, 8, n)).astype(np.float32)
    spec = SweepSpec(
        temps=TEMPS,
        tests=(OpSweep(Op.READ, prof.combo_grid(Op.READ), trefi_r),
               OpSweep(Op.WRITE, prof.combo_grid(Op.WRITE), trefi_w)))
    return prof, spec, prof.engine.sweep(small_pop, spec)


class TestFusedMatchesPerBin:
    def test_bit_for_bit_vs_per_bin_combo_margins(self, small_pop, campaign):
        """(a) one fused multi-temperature dispatch == per-bin
        `combo_margins` calls, bitwise, on the ref impl."""
        prof, spec, _ = campaign
        fused = prof.engine.campaign_margins(small_pop, spec)
        cpm = int(np.prod(small_pop.cells.shape[1:4]))
        cells = jnp.asarray(small_pop.flat_cells())
        for test, m3 in zip(spec.tests, fused):
            trefi_cells = jnp.asarray(
                np.repeat(test.trefi_per_module(small_pop.n_modules), cpm))
            for ti, temp in enumerate(TEMPS):
                r, w = charge_ops.combo_margins(
                    cells, jnp.asarray(test.combos), temp, C,
                    impl="ref", trefi_cells=trefi_cells)
                ref = np.asarray(r if test.op is Op.READ else w)
                assert np.array_equal(m3[:, ti, :], ref), (test.op, temp)

    def test_shim_paths_match_engine(self, small_pop):
        """refresh_profile / timing_profile shims reproduce the raw
        engine sweep exactly."""
        prof = make_profiler()
        rp_read, rp_write = prof.refresh_campaign(small_pop, 85.0)
        rp_read2 = prof.refresh_profile(small_pop, 85.0, "read")
        for a, b in zip(rp_read, rp_read2):
            assert np.array_equal(a, b)
        tp = prof.timing_profile(small_pop, 55.0, Op.READ, rp_read.safe)
        res = prof.engine.sweep(small_pop, SweepSpec.single(
            Op.READ, prof.combo_grid(Op.READ), (55.0,), rp_read.safe))
        assert np.array_equal(tp.combos, res.chosen[0][:, 0, :])
        assert np.array_equal(tp.pass_per_module, res.ok[0][:, 0, :])


class TestDeviceEnvelopes:
    """The envelopes reduced on the device are, bitwise, the host's
    reduction of the dense `MarginEngine.margins` grids of the same
    dispatch layout (`campaign_margins`)."""

    @pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
    @pytest.mark.parametrize("regions", [1, 2])
    def test_sweep_envelopes_match_host_reduction(self, small_pop, impl,
                                                  regions):
        pop = Population(small_pop.cells[:3, :, :, :4])
        prof = Profiler(constants=C, grid_step=GRID_STEP, impl=impl)
        spec = SweepSpec(
            temps=(55.0, 85.0),
            tests=(OpSweep(Op.READ, prof.combo_grid(Op.READ), 96.0),
                   OpSweep(Op.WRITE, prof.combo_grid(Op.WRITE),
                           np.float32([64.0, 80.0, 72.0]))))
        res = prof.engine.sweep(pop, spec, regions=regions)
        m, ch, bk, kc = pop.cells.shape[:4]
        for k, m3 in enumerate(prof.engine.campaign_margins(pop, spec)):
            okr = (m3.reshape(m, ch, bk, regions, kc // regions, 2, -1)
                   >= 0.0).all(4).all(1)
            assert np.array_equal(res.ok_bank[k], okr.all(2))
            assert np.array_equal(res.ok[k], okr.all(2).all(1))
            if regions > 1:
                assert np.array_equal(res.ok_region[k], okr)
            # the envelopes select: some combos pass, not all
            assert 0 < res.ok[k].sum() < res.ok[k].size

    @pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
    def test_refresh_envelopes_match_host_reduction(self, small_pop, impl):
        pop = Population(small_pop.cells[:3])
        prof = Profiler(constants=C, grid_step=GRID_STEP, impl=impl)
        grid = T.refresh_grid()
        combos = np.repeat(np.asarray(prof.std.as_array())[None], len(grid),
                           axis=0)
        combos[:, 4] = grid
        m, ch, bk, kc = pop.cells.shape[:4]
        dense = prof.engine.margins(pop.flat_cells(), combos, temp_c=85.0)
        host = [(g.reshape(m, ch, bk, kc, -1) >= 0.0).all(3) for g in dense]
        dev = prof.engine.envelopes(
            pop.flat_cells(), combos, cell_shape=(m, ch, bk, kc), axes=(3,),
            blocks=((Op.READ, 0, len(grid)), (Op.WRITE, 0, len(grid))),
            temp_c=85.0).fetch()
        for h, d in zip(host, dev):
            assert d.dtype == np.bool_ and np.array_equal(h, d)
            assert 0 < h.sum() < h.size
        for rp, h in zip(prof.refresh_campaign(pop, 85.0), host):
            for a, b in zip(rp, prof._refresh_envelopes(h, grid)):
                assert np.array_equal(a, b)

    @pytest.mark.parametrize("shift", [0.0, 0.05])
    def test_chip_smoke_margin_parity(self, small_pop, monkeypatch, shift):
        """`chip_smoke.py`'s profile check — a kernel engine's dense
        margins and device envelopes against the reference engine's —
        reads zero flips and equal envelopes for the kernel, and sees a
        kernel whose margins run `shift` high."""
        import importlib.util
        import pathlib
        path = pathlib.Path(__file__).parents[1] / "chip_smoke.py"
        mod = importlib.util.spec_from_file_location("chip_smoke", path)
        smoke = importlib.util.module_from_spec(mod)
        mod.loader.exec_module(smoke)
        pop = Population(small_pop.cells[:3, :, :, :4])
        prof = Profiler(constants=C, grid_step=GRID_STEP,
                        impl="pallas_interpret")
        spec = prof.campaign_spec((55.0, 85.0),
                                  *prof.refresh_campaign(pop, 85.0))
        if shift:
            real = prof.engine.margins
            monkeypatch.setattr(prof.engine, "margins", lambda *a, **k: tuple(
                g + shift for g in real(*a, **k)))
        got = smoke.margin_parity(
            prof.engine, MarginEngine(constants=C, std=prof.std, impl="ref"),
            pop, spec)
        assert got["margin_cells_compared"] == (
            int(np.prod(pop.cells.shape[:4])) * 2
            * sum(t.combos.shape[0] for t in spec.tests))
        if shift:
            assert got["margin_max_abs_diff"] > shift / 2
            assert got["pass_fail_flips"] > 0
            assert got["envelopes_differing"] > 0
        else:
            assert got["margin_max_abs_diff"] < 1e-3
            assert got["pass_fail_flips"] == got["envelopes_differing"] == 0

    def test_sweeps_launch_each_group_ahead_of_its_fetch(self, small_pop,
                                                          monkeypatch):
        """Module groups: each dispatch is launched before the one
        before it is fetched (the device never waits for the host's
        selection), at most two in flight, and each group's result is
        its own `sweep`."""
        from repro.core import sweep as sweep_mod
        prof = make_profiler()
        spec = SweepSpec.single(Op.READ, prof.combo_grid(Op.READ), (85.0,))
        groups = [(Population(small_pop.cells[lo:lo + 3]), spec)
                  for lo in (0, 3, 6)]
        alone = [prof.engine.sweep(p, s) for p, s in groups]
        events = []
        launch, fetch = MarginEngine.envelopes, sweep_mod.Envelopes.fetch

        def spy_launch(self, *a, **k):
            events.append("launch")
            return launch(self, *a, **k)

        def spy_fetch(self):
            events.append("fetch")
            return fetch(self)
        monkeypatch.setattr(MarginEngine, "envelopes", spy_launch)
        monkeypatch.setattr(sweep_mod.Envelopes, "fetch", spy_fetch)
        got = prof.engine.sweeps(groups)
        assert events == ["launch", "launch", "fetch", "launch", "fetch",
                          "fetch"]
        for a, b in zip(alone, got):
            assert np.array_equal(a.ok[0], b.ok[0])
            assert np.array_equal(a.chosen_bank[0], b.chosen_bank[0])


class TestEnvelopeMonotonicity:
    def test_pass_envelope_monotone_in_temperature(self, campaign):
        """(b) a combo passing at a hotter bin also passes at every
        cooler bin: hotter never helps (paper Sec. 1)."""
        _, _, res = campaign
        for ok in res.ok:                      # [modules, temps, combos]
            for ti in range(len(TEMPS) - 1):
                hot_only = ok[:, ti + 1] & ~ok[:, ti]
                assert not hot_only.any()

    def test_passing_counts_shrink_with_temperature(self, campaign):
        _, _, res = campaign
        for ok in res.ok:
            counts = ok.sum(-1)                # [modules, temps]
            assert (np.diff(counts, axis=-1) <= 0).all()

    def test_chosen_latency_monotone_in_temperature(self, campaign):
        _, _, res = campaign
        for sums in res.latency_sum:           # [modules, temps]
            assert (np.diff(sums, axis=-1) >= -1e-6).all()


class TestControllerEquivalence:
    def test_profile_table_matches_per_bin_path(self, small_pop):
        """(c) the fused controller table's MODULE view equals the old
        per-bin, per-op procedure run through the shims (the default
        per-bank profile carries it unchanged)."""
        ctrl = ALDRAMController(make_profiler(), temp_bins=TEMPS)
        tbl = ctrl.profile(small_pop)

        # the pre-redesign path: one timing_profile call per (bin, op)
        prof = make_profiler()
        rp_read, rp_write = prof.refresh_campaign(small_pop, 85.0)
        n = small_pop.n_modules
        expect = np.zeros((n, len(TEMPS), 4), np.float32)
        for bi, temp in enumerate(TEMPS):
            tp_r = prof.timing_profile(small_pop, temp, "read", rp_read.safe)
            tp_w = prof.timing_profile(small_pop, temp, "write",
                                       rp_write.safe)
            expect[:, bi, 0] = np.maximum(tp_r.combos[:, 0],
                                          tp_w.combos[:, 0])
            expect[:, bi, 1] = tp_r.combos[:, 1]
            expect[:, bi, 2] = tp_w.combos[:, 2]
            expect[:, bi, 3] = np.maximum(tp_r.combos[:, 3],
                                          tp_w.combos[:, 3])
        assert tbl.per_bank and tbl.params.ndim == 4
        assert np.array_equal(tbl.module_params, expect)
        assert np.array_equal(tbl.reduce_banks().params, expect)
        assert np.array_equal(tbl.safe_trefi_read, rp_read.safe)
        assert np.array_equal(tbl.safe_trefi_write, rp_write.safe)
        # a per_bank=False controller builds exactly the module table
        tbl_m = ALDRAMController(make_profiler(), temp_bins=TEMPS,
                                 per_bank=False).profile(small_pop)
        assert tbl_m.params.ndim == 3
        assert np.array_equal(tbl_m.params, expect)

    def test_average_reductions_above_hottest_bin(self, small_pop):
        """Satellite: no StopIteration above the hottest profiled bin —
        standard-timing fallback means 0% reductions."""
        ctrl = ALDRAMController(make_profiler(), temp_bins=TEMPS)
        ctrl.profile(small_pop)
        red = ctrl.average_reductions(95.0)
        assert red == {"trcd": 0.0, "tras": 0.0, "twr": 0.0, "trp": 0.0}


class TestDispatchCounts:
    """Acceptance criterion: profile() and verify() over the default
    bins are single batched campaigns — kernel launches do not scale
    with bins, modules, or ops."""

    def _spy(self, monkeypatch):
        calls = []
        real = charge_ops.padded_margin_sweep

        def spy(*args, **kwargs):
            calls.append((args[1].shape[0]))   # n_combos per dispatch
            return real(*args, **kwargs)

        monkeypatch.setattr(charge_ops, "padded_margin_sweep", spy)
        return calls

    def test_profile_is_two_dispatches(self, small_pop, monkeypatch):
        calls = self._spy(monkeypatch)
        ctrl = ALDRAMController(make_profiler())   # default 5 bins
        ctrl.profile(small_pop)
        # one refresh campaign (both ops) + ONE fused timing campaign
        # covering 5 bins x (read + write)
        assert len(calls) == 2, calls
        assert ctrl.engine.dispatch_count == 2

    def test_verify_is_one_dispatch(self, small_pop, monkeypatch):
        ctrl = ALDRAMController(make_profiler())
        ctrl.profile(small_pop)
        calls = self._spy(monkeypatch)
        assert ctrl.verify(small_pop)
        assert len(calls) == 1, calls
        # per-bank verify: (1 envelope + n_banks) combo columns per
        # (module, bin), still one dispatch
        assert calls[0] == (small_pop.n_modules * len(ctrl.temp_bins)
                            * (1 + small_pop.n_banks))

    def test_verify_per_module_table_is_one_dispatch(self, small_pop,
                                                     monkeypatch):
        ctrl = ALDRAMController(make_profiler(), per_bank=False)
        ctrl.profile(small_pop)
        calls = self._spy(monkeypatch)
        assert ctrl.verify(small_pop)
        assert len(calls) == 1, calls
        assert calls[0] == small_pop.n_modules * len(ctrl.temp_bins)

    def test_dispatches_independent_of_bins(self, small_pop, monkeypatch):
        calls = self._spy(monkeypatch)
        ctrl = ALDRAMController(make_profiler(), temp_bins=TEMPS)
        ctrl.profile(small_pop)
        ctrl.verify(small_pop)
        assert len(calls) == 3                      # 2 profile + 1 verify

    def test_profile_values_unchanged_by_fusion(self, small_pop):
        """Same table whether 1 bin or many share the dispatch."""
        one = ALDRAMController(make_profiler(), temp_bins=(70.0,))
        many = ALDRAMController(make_profiler(), temp_bins=TEMPS)
        t1 = one.profile(small_pop)
        tm = many.profile(small_pop)
        assert np.array_equal(t1.params[:, 0], tm.params[:, 1])  # 70C bin


class TestSpecValidation:
    def test_conflicting_trefi_rejected(self, small_pop):
        prof = make_profiler()
        grid = prof.combo_grid(Op.READ)
        spec = SweepSpec(temps=(55.0,),
                         tests=(OpSweep(Op.READ, grid, 64.0),
                                OpSweep(Op.READ, grid, 96.0)))
        with pytest.raises(ValueError):
            prof.engine.sweep(small_pop, spec)

    def test_op_parsing(self):
        assert Op.parse("read") is Op.READ
        assert Op.parse(Op.WRITE) is Op.WRITE
        with pytest.raises(ValueError):
            Op.parse("refresh")

    def test_from_sweep_adaptive_table(self, small_pop):
        """The autotune bridge: sweep results drive guardbanded
        runtime selection with JEDEC fallback semantics."""
        from repro.core.autotune import AdaptiveTable
        prof = make_profiler()
        res = prof.engine.sweep(small_pop, SweepSpec.single(
            Op.READ, prof.combo_grid(Op.READ), TEMPS))
        t = AdaptiveTable.from_sweep(res, Op.READ,
                                     static_worst_case=T.DDR3_1600.read_sum())
        v = t.select(0, 55.0)
        assert 0 < v <= T.DDR3_1600.read_sum()
        assert t.select(0, 99.0) == T.DDR3_1600.read_sum()  # above bins


@pytest.fixture(scope="module")
def small_pop():
    import jax
    from repro.core.calibration import CALIBRATED_VARIATION
    from repro.core.variation import sample_population
    cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=8, n_cells=5)
    return sample_population(jax.random.PRNGKey(11), cfg)
