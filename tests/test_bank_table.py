"""Per-bank timing tables (FLY-DRAM-style spatial variation) +
population-contract tests: per-bank profiling rides the same fused
campaign dispatch, banked replays are parity-tested against the
per-module path across every layout (scalar scan, lane-major scan,
adaptive scan, Pallas kernel), `reduce_banks()` is bit-exact, and the
reorder-cache / stacked-CellParams / refresh-envelope contracts are
pinned down."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim, sim_engine
from repro.core.aldram import ALDRAMController, TimingTable
from repro.core.calibration import (CALIBRATED_CONSTANTS,
                                    CALIBRATED_VARIATION)
from repro.core.charge import CellParams
from repro.core.dram_sim import Trace
from repro.core.profiler import Profiler
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import ThermalConfig, ThermalSpec, steady
from repro.core.timing import (ALDRAM_55C_EVAL, DDR3_1600,
                               STANDARD_TREFI_MS, stack_timing)
from repro.core.variation import sample_population
from repro.kernels.replay import ops as replay_ops

N_BANKS = 8


def synth(seed=0, n=256, **kw):
    return dram_sim.synth_trace(jax.random.PRNGKey(seed), n, **kw)


def bank_rows(s=2, banks=N_BANKS, d=0.05):
    """[S, banks, 6] stack with a distinct row per (lane, bank)."""
    rows = np.empty((s, banks, 6), np.float32)
    for si in range(s):
        for b in range(banks):
            f = 0.6 + d * b + 0.02 * si
            rows[si, b] = DDR3_1600.scaled(f, f, f, f).as_row()
    return rows


@pytest.fixture(scope="module")
def controller(small_pop):
    ctrl = ALDRAMController(
        Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5,
                 impl="ref"),
        temp_bins=(55.0, 70.0, 85.0))
    ctrl.profile(small_pop)
    return ctrl


class TestPopulationContract:
    """Satellite: the stacked-cell trailing dim must match the
    CellParams field count (it is 5, not the 4 the old docstring
    promised), and `unstack` enforces it."""

    def test_cells_trailing_dim_matches_fields(self, small_pop):
        assert len(CellParams._fields) == 5
        assert small_pop.cells.shape[-1] == len(CellParams._fields)
        p = small_pop.params()
        assert np.array_equal(np.asarray(p.stack()),
                              np.asarray(small_pop.cells))

    def test_unstack_rejects_wrong_width(self):
        with pytest.raises(AssertionError):
            CellParams.unstack(jnp.zeros((3, 4)))
        with pytest.raises(AssertionError):
            CellParams.unstack(jnp.zeros((3, 6)))
        CellParams.unstack(jnp.zeros((3, 5)))      # the contract width

    def test_worst_case_reference_width(self):
        from repro.core.variation import worst_case_reference
        assert worst_case_reference().shape[-1] == len(CellParams._fields)


class TestRefreshEnvelopeContainment:
    """Satellite: audit `RefreshProfile` granularities on a population
    with chips != banks, so a transposed reduction cannot hide behind
    the symmetric 8x8 default."""

    @pytest.fixture(scope="class")
    def asym(self):
        cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=4,
                                  n_chips=4, n_banks=8, n_cells=4)
        pop = sample_population(jax.random.PRNGKey(3), cfg)
        prof = Profiler(constants=CALIBRATED_CONSTANTS, impl="ref")
        rp, _ = prof.refresh_campaign(pop, 85.0)
        return pop, rp

    def test_documented_shapes(self, asym):
        pop, rp = asym
        m, ch, bk = pop.cells.shape[:3]
        assert (ch, bk) == (4, 8)
        assert rp.per_module.shape == (m,)
        assert rp.per_chip.shape == (m, ch)
        assert rp.per_bank.shape == (m, bk)

    def test_envelope_containment(self, asym):
        """per_module == per_chip.min == per_bank.min exactly: the
        module envelope is the intersection of either slicing of the
        same cell hierarchy."""
        _, rp = asym
        assert np.array_equal(rp.per_module, rp.per_chip.min(axis=1))
        assert np.array_equal(rp.per_module, rp.per_bank.min(axis=1))
        assert (rp.per_chip >= rp.per_module[:, None]).all()
        assert (rp.per_bank >= rp.per_module[:, None]).all()
        assert (rp.safe <= rp.per_module).all()


class TestReorderCacheDigest:
    """Satellite: the FR-FCFS host-reorder cache keys on CONTENT, so
    mutating a trace's arrays in place yields a fresh permutation."""

    def _trace(self, seed=0, n=160):
        rng = np.random.default_rng(seed)
        return Trace(
            np.cumsum(rng.exponential(8.0, n)).astype(np.float32),
            rng.integers(0, 8, n).astype(np.int32),
            rng.integers(0, 3, n).astype(np.int32),
            (rng.random(n) < 0.3))

    def test_inplace_mutation_gets_fresh_reorder(self):
        t = self._trace()
        r1 = dram_sim.frfcfs_reorder(t, window=8)
        # in-place mutation: same array objects (same id), new contents
        t.row[:] = t.row[::-1].copy()
        t.arrival[:] = t.arrival * np.float32(0.5)
        r2 = dram_sim.frfcfs_reorder(t, window=8)
        order = dram_sim.frfcfs_order(t, 8, 30.0)
        for got, field in zip(r2, t):
            assert np.array_equal(np.asarray(got),
                                  np.asarray(field)[order])
        assert not np.array_equal(np.asarray(r1.row),
                                  np.asarray(r2.row))

    def test_returned_trace_is_frozen(self):
        """The cached entry is shared across hits: mutating a RETURNED
        trace in place must raise, not poison later equal-content
        lookups."""
        r = dram_sim.frfcfs_reorder(self._trace(7), window=4)
        with pytest.raises(ValueError):
            r.arrival[:] = 0.0

    def test_equal_content_hits_cache(self, monkeypatch):
        """Two distinct-but-equal traces share one Python reorder."""
        calls = {"n": 0}
        real = dram_sim.frfcfs_order

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(dram_sim, "frfcfs_order", spy)
        dram_sim.frfcfs_reorder(self._trace(5), window=4)
        dram_sim.frfcfs_reorder(self._trace(5), window=4)
        assert calls["n"] == 1


class TestLookupBinEdges:
    """Satellite: `lookup_many` bin-edge semantics, and their parity
    with the in-scan `searchsorted` selection of `replay_adaptive`."""

    BINS = (45.0, 55.0, 65.0)

    @pytest.fixture(scope="class")
    def table(self):
        # bin-monotone per-module params so safe_stack rows == lookup
        # rows at every bin edge
        base = np.array([[9.0, 24.0, 10.0, 11.0],
                         [10.0, 26.0, 11.0, 12.0],
                         [11.0, 28.0, 12.0, 13.0]], np.float32)
        return TimingTable(self.BINS, base[None, :, :],
                           np.array([64.0]), np.array([64.0]))

    def test_exact_edge_selects_that_bin(self, table):
        for bi, tc in enumerate(self.BINS):
            row = table.lookup_many(0, np.array([tc]))[0]
            assert np.array_equal(row[:4], table.params[0, bi])
        # epsilon above an edge rounds UP to the next bin
        row = table.lookup_many(0, np.array([45.0 + 1e-3]))[0]
        assert np.array_equal(row[:4], table.params[0, 1])

    def test_above_hottest_bin_is_jedec(self, table):
        for tc in (65.0 + 1e-3, 90.0):
            row = table.lookup_many(0, np.array([tc]))[0]
            assert np.array_equal(row, DDR3_1600.as_row())
        # exactly ON the hottest edge still uses the profiled row
        row = table.lookup_many(0, np.array([65.0]))[0]
        assert np.array_equal(row[:4], table.params[0, 2])
        assert row[4] == STANDARD_TREFI_MS and row[5] == DDR3_1600.tcl

    def test_parity_with_in_scan_selection(self, table):
        """At the same sensed temperatures (edges included, plus the
        above-hottest fallback) the adaptive scan selects the same
        row `lookup_many` returns — replayed latencies bit-identical
        to the static replay of the looked-up row."""
        rows, bins = table.safe_stack()
        t = synth(9, 200)
        temps = (44.0, 45.0, 45.1, 55.0, 65.0, 66.0, 90.0)
        tspec = ThermalSpec(scenarios=tuple(steady(tc) for tc in temps),
                            temp_bins=tuple(bins),
                            config=ThermalConfig(c_heat=0.0))
        eng = SimEngine()
        res_a = eng.run(SimSpec(traces=(t,), timings=rows, thermal=tspec,
                                collect=("latencies", "bins")))
        look = table.lookup_many(np.zeros(len(temps), np.int64),
                                 np.array(temps))
        res_s = eng.run(SimSpec(traces=(t,), timings=look,
                                collect=("latencies",)))
        for ci, tc in enumerate(temps):
            bi = int(np.searchsorted(np.asarray(bins), tc, side="left"))
            assert (res_a.bins[0, 0, 0, ci] == bi).all(), tc
            assert np.array_equal(res_a.latencies[0, 0, 0, ci],
                                  res_s.latencies[0, 0, ci]), tc


class TestBankedReplayParity:
    """Tentpole: every replay layout accepts per-bank rows; constant
    rows are bit-identical to the per-module path, and varying rows
    match the vmap-over-banks reference."""

    def test_constant_bank_rows_bit_identical_static(self):
        rows = stack_timing([DDR3_1600, ALDRAM_55C_EVAL])
        rows_b = np.broadcast_to(rows[:, None, :],
                                 (2, N_BANKS, 6)).copy()
        traces = (synth(0, 256), synth(1, 129, row_hit=0.2))
        for eng_kw in ({}, {"stats": "host", "reorder": "host"}):
            eng = SimEngine(**eng_kw)
            rm = eng.run(SimSpec(traces=traces, timings=rows,
                                 collect=("latencies",)))
            rb = eng.run(SimSpec(traces=traces, timings=rows_b,
                                 collect=("latencies",)))
            assert np.array_equal(rm.latencies, rb.latencies)
            assert np.array_equal(rm.total_ns, rb.total_ns)
            assert np.array_equal(rm.mean_latency_ns, rb.mean_latency_ns)
            assert np.array_equal(rm.p99_latency_ns, rb.p99_latency_ns)

    def test_constant_bank_stack_bit_identical_adaptive(self):
        stack = stack_timing([ALDRAM_55C_EVAL,
                              DDR3_1600.scaled(0.9, 0.9, 0.9, 0.9),
                              DDR3_1600])
        stack_b = np.broadcast_to(stack[:, None, :],
                                  (3, N_BANKS, 6)).copy()
        tspec = ThermalSpec(scenarios=(steady(50.0),),
                            temp_bins=(45.0, 55.0),
                            config=ThermalConfig(c_heat=2e-5))
        eng = SimEngine()
        rm = eng.run(SimSpec(traces=(synth(2, 200),), timings=stack,
                             thermal=tspec,
                             collect=("latencies", "bins")))
        rb = eng.run(SimSpec(traces=(synth(2, 200),),
                             timings=stack_b[None], thermal=tspec,
                             collect=("latencies", "bins")))
        assert np.array_equal(rm.latencies, rb.latencies)
        assert np.array_equal(rm.bins, rb.bins)
        assert np.array_equal(rm.bank_heat, rb.bank_heat)
        assert np.array_equal(rm.total_ns, rb.total_ns)

    def test_single_bank_traces_match_vmap_over_banks(self):
        """A trace touching only bank b replays under a varying
        per-bank stack exactly as under row b alone — the
        vmap-over-banks reference of the in-scan gather."""
        rows_b = bank_rows()
        rng = np.random.default_rng(0)
        n, eng = 128, SimEngine()
        for b0 in (0, 3, 7):
            tr = Trace(arrival=jnp.arange(n) * 8.0,
                       bank=jnp.full((n,), b0, jnp.int32),
                       row=jnp.asarray(rng.integers(0, 16, n), jnp.int32),
                       is_write=jnp.asarray(rng.random(n) < 0.3))
            r_bank = eng.run(SimSpec(traces=(tr,), timings=rows_b,
                                     collect=("latencies",)))
            r_mod = eng.run(SimSpec(traces=(tr,),
                                    timings=rows_b[:, b0, :],
                                    collect=("latencies",)))
            assert np.array_equal(r_bank.latencies, r_mod.latencies), b0
            assert np.array_equal(r_bank.total_ns, r_mod.total_ns)

    def test_replay_one_vs_replay_rows_banked(self):
        """The scalar scan and the lane-major scan agree bit-for-bit
        per banked row stack (mixed-bank trace, distinct rows)."""
        rows_b = jnp.asarray(bank_rows())
        tr = synth(1, 96)
        valid = jnp.ones(96, bool)
        lat_rows, tot_rows = dram_sim.replay_rows(
            tr.arrival, tr.bank, tr.row, tr.is_write, valid, rows_b,
            False)
        for s in range(rows_b.shape[0]):
            lat1, tot1 = dram_sim.replay_one(
                tr.arrival, tr.bank, tr.row, tr.is_write, valid,
                rows_b[s], False)
            assert np.array_equal(np.asarray(lat_rows)[s],
                                  np.asarray(lat1)), s
            assert np.asarray(tot_rows)[s] == np.asarray(tot1), s

    def test_pallas_banked_matches_scan_oracle(self):
        rows_b = bank_rows(s=3)
        tr = synth(4, 96)

        def b3(x):
            return jnp.asarray(np.broadcast_to(
                np.asarray(x)[None, None], (1, 2, 96)).copy())

        args = (b3(tr.arrival), b3(tr.bank), b3(tr.row),
                b3(np.asarray(tr.is_write, np.int32)),
                jnp.ones((1, 96), bool), jnp.asarray(rows_b),
                jnp.asarray([False, True]))
        lat_ref, tot_ref = replay_ops.replay_grid(*args, impl="ref")
        lat_pl, tot_pl = replay_ops.replay_grid(
            *args, impl="pallas_interpret", bs=8)
        np.testing.assert_allclose(np.asarray(lat_pl),
                                   np.asarray(lat_ref), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(tot_pl),
                                   np.asarray(tot_ref), rtol=1e-5)

    def test_banked_campaign_is_one_dispatch(self, monkeypatch):
        calls = {"replay": 0}
        real = sim_engine._replay_grid

        def spy(*a, **k):
            calls["replay"] += 1
            return real(*a, **k)

        monkeypatch.setattr(sim_engine, "_replay_grid", spy)
        SimEngine().run(SimSpec(
            traces=(synth(0, 96), synth(1, 64)), timings=bank_rows(),
            policies=(dram_sim.OPEN_FCFS,
                      dram_sim.Policy(reorder_window=4))))
        assert calls["replay"] == 1

    def test_bank_axis_must_match_n_banks(self):
        with pytest.raises(AssertionError):
            SimSpec(traces=(synth(0, 64),), timings=bank_rows(banks=4))
        SimSpec(traces=(synth(0, 64),), timings=bank_rows(banks=4),
                n_banks=4)


class TestBankTable:
    """Tentpole: the profiled per-bank TimingTable and its closures."""

    def test_reduce_banks_bit_exact(self, controller, small_pop):
        tbl = controller.table
        assert tbl.per_bank and tbl.n_banks == small_pop.n_banks
        ctrl_m = ALDRAMController(
            Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5,
                     impl="ref"),
            temp_bins=controller.temp_bins, per_bank=False)
        tbl_m = ctrl_m.profile(small_pop)
        red = tbl.reduce_banks()
        assert not red.per_bank
        assert np.array_equal(red.params, tbl_m.params)
        assert np.array_equal(tbl.module_params, tbl_m.params)

    def test_bank_envelope_contains_module_envelope(self, controller):
        res = controller.sweep_result
        for k in range(len(res.ok)):
            assert np.array_equal(res.ok[k], res.ok_bank[k].all(1))
            # a combo passing the whole module passes every bank
            assert not (res.ok[k][:, None] & ~res.ok_bank[k]).any()
            assert (res.latency_sum_bank[k]
                    <= res.latency_sum[k][:, None, :] + 1e-6).all()

    def test_lookup_many_banks_semantics(self, controller):
        tbl = controller.table
        rng = np.random.default_rng(1)
        mods = rng.integers(0, tbl.params.shape[0], 24)
        banks = rng.integers(0, tbl.n_banks, 24)
        temps = rng.uniform(40.0, 95.0, 24)
        rows = tbl.lookup_many_banks(mods, banks, temps)
        bins = np.asarray(tbl.temp_bins)
        for i in range(24):
            bi = int(np.searchsorted(bins, temps[i], side="left"))
            if bi >= len(bins):
                assert np.array_equal(rows[i], DDR3_1600.as_row())
            else:
                assert np.array_equal(
                    rows[i, :4], tbl.params[mods[i], bi, banks[i]])

    def test_safe_stack_banks_envelope(self, controller):
        rows, bins = controller.table.safe_stack_banks()
        nb, banks = len(controller.temp_bins), controller.table.n_banks
        assert rows.shape == (nb + 1, banks, 6)
        assert np.array_equal(rows[-1],
                              np.broadcast_to(DDR3_1600.as_row(),
                                              (banks, 6)))
        # bin-monotone per bank, and every bank row covers the
        # all-module lookup of its (bin, bank)
        assert (np.diff(rows, axis=0) >= -1e-6).all()
        m = controller.table.params.shape[0]
        mods = np.arange(m)
        for bi, tc in enumerate(controller.temp_bins):
            for b in range(banks):
                lk = controller.table.lookup_many_banks(
                    mods, np.full(m, b), np.full(m, tc)).max(axis=0)
                assert (rows[bi, b] >= lk - 1e-6).all()

    def test_verify_per_bank_invariant(self, controller, small_pop):
        """The zero-error invariant holds per (module, bin, bank)."""
        assert controller.verify(small_pop)

    def test_verify_catches_bad_bank_row(self, controller, small_pop):
        """Corrupting ONE bank's row (an aggressive tRCD cut) must
        flip verify — the bank diagonal is actually checked."""
        tbl = controller.table
        params = tbl.params.copy()
        params[0, 0, 3, 0] = 1.0          # absurd tRCD on one bank
        bad = dataclasses.replace(tbl, params=params)
        controller.table = bad
        try:
            assert not controller.verify(small_pop)
        finally:
            controller.table = tbl

    def test_evaluate_bank_system_one_replay(self, controller,
                                             small_pop, monkeypatch):
        calls = {"replay": 0}
        real = sim_engine._replay_grid

        def spy(*a, **k):
            calls["replay"] += 1
            return real(*a, **k)

        monkeypatch.setattr(sim_engine, "_replay_grid", spy)
        res = controller.evaluate_bank_system(small_pop, n=128)
        assert calls["replay"] == 1
        nt = len(res["temps"])
        assert res["rows"].shape == (1 + 2 * nt,
                                     controller.table.n_banks, 6)
        # per-module envelope rows ride constant across banks
        for si in range(nt):
            assert (res["rows"][1 + si]
                    == res["rows"][1 + si, :1]).all()
        # the FLY-DRAM headline: per-bank mean timing reductions beat
        # the per-module envelope for both tests
        for op, d in res["reductions"].items():
            assert d["bank"] >= d["module"] - 1e-9, (op, d)

    def test_non_default_bank_count_plumbed(self):
        """A population with n_banks != 8 profiles AND evaluates: the
        table's bank count flows through trace synthesis and SimSpec
        (regression — the campaign entry points used to assume 8)."""
        cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=3,
                                  n_chips=2, n_banks=4, n_cells=3)
        pop = sample_population(jax.random.PRNGKey(5), cfg)
        ctrl = ALDRAMController(
            Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5,
                     impl="ref"),
            temp_bins=(55.0, 85.0))
        ctrl.profile(pop)
        assert ctrl.table.n_banks == 4
        assert ctrl.verify(pop)
        res = ctrl.evaluate_bank_system(pop, n=96)
        assert res["rows"].shape == (1 + 2 * 2, 4, 6)
        dyn = ctrl.evaluate_dynamic(pop, n=96, per_bank=True,
                                    scenarios=(steady(50.0),))
        assert dyn["table"].shape == (3, 4, 6)

    def test_sweep_result_drops_margin_grids(self, controller):
        """profile() keeps the selection views but not the
        O(cells x combos) raw margin grids: every array it holds is a
        per-module view."""
        res = controller.sweep_result
        m = controller.table.module_params.shape[0]
        views = [a for f in dataclasses.fields(res)
                 if isinstance(getattr(res, f.name), tuple)
                 for a in getattr(res, f.name) if isinstance(a, np.ndarray)]
        assert views and all(a.shape[0] == m for a in views)
        assert len(res.latency_sum_bank) == len(res.latency_sum) == 2

    def test_dynamic_per_bank_closure(self, controller, small_pop):
        """evaluate_dynamic(per_bank=True) deploys the per-bank stack
        through the same 2-replay-dispatch campaign."""
        res = controller.evaluate_dynamic(small_pop, n=128,
                                          per_bank=True)
        assert res["table"].shape == (len(controller.temp_bins) + 1,
                                      controller.table.n_banks, 6)
        for name, d in res["per_scenario"].items():
            assert d["adaptive_gmean"] >= d["static_worst_gmean"] - 1e-9
