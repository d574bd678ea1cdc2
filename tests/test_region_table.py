"""Subarray-region spatial hierarchy (finer-than-bank timing maps):
`regions=1` bit-identity against the per-bank path on every backend,
region-map gather correctness in-scan, the lossless unique-rows
compressor, `TimingTable.patch` shape/rank validation, the region
controller end-to-end (profile -> levels -> verify -> one-dispatch
system evaluation)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import dram_sim, faults, sim_engine
from repro.core import timing as T
from repro.core.aldram import ALDRAMController, TimingTable
from repro.core.calibration import (CALIBRATED_CONSTANTS,
                                    CALIBRATED_VARIATION)
from repro.core.dram_sim import Trace
from repro.core.profiler import Profiler
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import ThermalConfig, ThermalSpec, steady
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, stack_timing
from repro.core.variation import sample_population
from repro.runtime.compression import (compress_rows, compress_stack,
                                       decompress_rows,
                                       rows_compression_ratio)

N_BANKS = 8
SUB = dram_sim.SUBARRAY_ROWS

ACTIVE = faults.FaultSpec(scenarios=(
    faults.FaultScenario(name="none"),
    faults.FaultScenario(name="err", err_scale=0.8, err_free_red=0.0,
                         detect_frac=0.9, retry_ns=60.0),
), seed=3)


def synth(seed=0, n=256, **kw):
    return dram_sim.synth_trace(jax.random.PRNGKey(seed), n, **kw)


def bank_rows(s=2, banks=N_BANKS, d=0.05):
    rows = np.empty((s, banks, 6), np.float32)
    for si in range(s):
        for b in range(banks):
            f = 0.6 + d * b + 0.02 * si
            rows[si, b] = DDR3_1600.scaled(f, f, f, f).as_row()
    return rows


def region_rows(s=2, banks=N_BANKS, regions=2):
    """[S, banks * regions, 6] all-distinct unique rows + the identity
    map — the finest-possible region store (U == G)."""
    g = banks * regions
    rows = np.empty((s, g, 6), np.float32)
    for si in range(s):
        for u in range(g):
            f = 0.55 + 0.02 * u + 0.015 * si
            rows[si, u] = DDR3_1600.scaled(f, f, f, f).as_row()
    return rows, np.arange(g, dtype=np.int32)


def region_trace(b0, r0, regions=2, seed=0, n=128):
    """A trace whose every request lands in bank `b0`, subarray region
    `r0` (row offsets cover several subarray multiples, so the
    `row % SUBARRAY_ROWS` folding is exercised, not just row < SUB)."""
    rng = np.random.default_rng(seed)
    w = SUB // regions
    off = rng.integers(r0 * w, (r0 + 1) * w, n)
    row = (rng.integers(0, 4, n) * SUB + off).astype(np.int32)
    return Trace(np.cumsum(rng.exponential(8.0, n)).astype(np.float32),
                 np.full(n, b0, np.int32), row,
                 (rng.random(n) < 0.3))


def assert_identical(ra, rb, fields=("total_ns", "mean_latency_ns",
                                     "p99_latency_ns")):
    for f in fields:
        a, b = getattr(ra, f), getattr(rb, f)
        if a is None or b is None:
            assert a is None and b is None, f
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), f


BACKENDS = ("scan", "pallas_interpret")


class TestRegionsOneBitIdentity:
    """Acceptance: `regions=1` (an identity region map over the
    per-bank stack) compiles the EXACT per-bank path — bit-identical
    latencies on every backend, static and adaptive, faults on/off."""

    @pytest.mark.parametrize("be", BACKENDS)
    def test_static_identity_map_every_backend(self, be):
        rows = bank_rows()
        traces = (synth(0, 256), synth(1, 129, row_hit=0.2))
        idmap = np.arange(N_BANKS, dtype=np.int32)
        eng = SimEngine(backend=be)
        rb = eng.run(SimSpec(traces=traces, timings=rows,
                             collect=("latencies",)))
        rr = eng.run(SimSpec(traces=traces, timings=rows,
                             region_map=idmap, collect=("latencies",)))
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)

    def test_static_per_lane_identity_map(self):
        """A 2-dim [S, banks] identity map (one map per timing lane)
        is the same static branch as the shared 1-dim map."""
        rows = bank_rows(s=3)
        idmap = np.broadcast_to(np.arange(N_BANKS, dtype=np.int32),
                                (3, N_BANKS)).copy()
        eng = SimEngine()
        rb = eng.run(SimSpec(traces=(synth(2, 200),), timings=rows,
                             collect=("latencies",)))
        rr = eng.run(SimSpec(traces=(synth(2, 200),), timings=rows,
                             region_map=idmap, collect=("latencies",)))
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)

    def _adaptive_specs(self, fspec=None):
        stack = stack_timing([ALDRAM_55C_EVAL,
                              DDR3_1600.scaled(0.9, 0.9, 0.9, 0.9),
                              DDR3_1600])
        stack_b = np.broadcast_to(stack[:, None, :],
                                  (3, N_BANKS, 6)).copy()[None]
        tspec = ThermalSpec(scenarios=(steady(50.0),),
                            temp_bins=(45.0, 55.0),
                            config=ThermalConfig(c_heat=2e-5))
        kw = dict(traces=(synth(2, 200),), thermal=tspec, faults=fspec,
                  collect=("latencies", "bins"))
        idmap = np.arange(N_BANKS, dtype=np.int32)
        return (SimSpec(timings=stack_b, **kw),
                SimSpec(timings=stack_b, region_map=idmap, **kw))

    @pytest.mark.parametrize("be", BACKENDS)
    def test_adaptive_identity_map(self, be):
        eng = SimEngine(backend=be)
        sb, sr = self._adaptive_specs()
        rb, rr = eng.run(sb), eng.run(sr)
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)
        assert np.array_equal(rb.bins, rr.bins)
        assert np.array_equal(rb.bank_heat, rr.bank_heat)

    @pytest.mark.parametrize("be", BACKENDS)
    def test_adaptive_identity_map_with_faults(self, be):
        eng = SimEngine(backend=be)
        sb, sr = self._adaptive_specs(ACTIVE)
        rb, rr = eng.run(sb), eng.run(sr)
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)
        assert np.array_equal(rb.fault_counters, rr.fault_counters)
        assert rr.detected_errors.sum() > 0        # the axis is live

    def test_adaptive_per_stack_identity_map(self):
        """A [K, G] per-stack map rides the table axis."""
        sb, sr = self._adaptive_specs()
        sr = dataclasses.replace(
            sr, region_map=np.broadcast_to(sr.region_map,
                                           (1, N_BANKS)).copy())
        rb, rr = SimEngine().run(sb), SimEngine().run(sr)
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)

    def test_static_faults_with_region_map_rejected(self):
        """The faulted static replay prices retries against ONE JEDEC
        row — spatial static timings (dense OR compressed) have no
        such row, so the spec refuses the combination up front."""
        rows, idmap = region_rows()
        with pytest.raises(AssertionError):
            SimSpec(traces=(synth(0, 64),), timings=rows,
                    region_map=idmap, faults=ACTIVE)


class TestRegionGather:
    """regions=2: the in-scan (bank, region-of-row) gather through the
    index map picks exactly the mapped unique row."""

    def test_single_region_trace_matches_scalar_row(self):
        rows, idmap = region_rows()
        eng = SimEngine()
        for b0, r0 in ((0, 0), (3, 1), (7, 0)):
            tr = region_trace(b0, r0, seed=b0 + r0)
            rr = eng.run(SimSpec(traces=(tr,), timings=rows,
                                 region_map=idmap,
                                 collect=("latencies",)))
            slot = int(idmap[b0 * 2 + r0])
            rm = eng.run(SimSpec(traces=(tr,), timings=rows[:, slot],
                                 collect=("latencies",)))
            assert np.array_equal(rr.latencies, rm.latencies), (b0, r0)
            assert np.array_equal(rr.total_ns, rm.total_ns)

    def test_bank_constant_map_matches_dense_banked(self):
        """A map whose two regions of every bank share that bank's
        unique row replays bit-identically to the dense per-bank
        stack — region resolution degrades gracefully to per-bank."""
        rows = bank_rows()
        rmap = np.repeat(np.arange(N_BANKS, dtype=np.int32), 2)
        traces = (synth(0, 256), synth(1, 129, row_hit=0.2))
        eng = SimEngine()
        rb = eng.run(SimSpec(traces=traces, timings=rows,
                             collect=("latencies",)))
        rr = eng.run(SimSpec(traces=traces, timings=rows,
                             region_map=rmap, collect=("latencies",)))
        assert_identical(rb, rr)
        assert np.array_equal(rb.latencies, rr.latencies)

    def test_backends_agree_on_region_campaign(self):
        rows, idmap = region_rows(s=3)
        spec = SimSpec(traces=(synth(4, 200), synth(5, 96)),
                       timings=rows, region_map=idmap,
                       policies=(dram_sim.OPEN_FCFS,
                                 dram_sim.Policy(page="closed")))
        ref = SimEngine(backend="scan").run(spec)
        res = SimEngine(backend="pallas_interpret").run(spec)
        for f in ("total_ns", "mean_latency_ns", "p99_latency_ns"):
            np.testing.assert_allclose(
                np.asarray(getattr(res, f)),
                np.asarray(getattr(ref, f)), rtol=1e-5, err_msg=f)

    def test_adaptive_single_region_trace_matches_module_stack(self):
        """The adaptive replay gathers (selected bin, map[bank,
        region]) — a single-(bank, region) trace matches the plain
        per-module replay of that slot's column."""
        g = N_BANKS * 2
        tabs = np.empty((1, 4, g, 6), np.float32)
        for u in range(g):
            f = 0.6 + 0.015 * u
            tabs[0, :3, u] = np.stack(
                [DDR3_1600.scaled(f, f, f, f).as_row(),
                 DDR3_1600.scaled(f + .1, f + .1, f + .1, f + .1).as_row(),
                 DDR3_1600.scaled(f + .2, f + .2, f + .2, f + .2).as_row()])
        tabs[0, 3] = DDR3_1600.as_row()
        tabs[0] = np.maximum.accumulate(tabs[0], axis=0)
        idmap = np.arange(g, dtype=np.int32)
        tspec = ThermalSpec(scenarios=(steady(50.0),),
                            temp_bins=(45.0, 55.0, 65.0),
                            config=ThermalConfig(c_heat=2e-5))
        eng = SimEngine()
        for b0, r0 in ((1, 0), (6, 1)):
            tr = region_trace(b0, r0, seed=10 + b0)
            rr = eng.run(SimSpec(traces=(tr,), timings=tabs,
                                 thermal=tspec, region_map=idmap,
                                 collect=("latencies", "bins")))
            slot = int(idmap[b0 * 2 + r0])
            rm = eng.run(SimSpec(traces=(tr,),
                                 timings=tabs[:, :, slot],
                                 thermal=tspec,
                                 collect=("latencies", "bins")))
            assert np.array_equal(rr.latencies, rm.latencies), (b0, r0)
            assert np.array_equal(rr.bins, rm.bins)


class TestCompression:
    """Satellite: the lossless unique-rows + index-map compressor."""

    def _dense(self, g=12, d=4, distinct=3, lead=(2,), seed=0):
        rng = np.random.default_rng(seed)
        vals = rng.uniform(10.0, 40.0, (distinct, d)).astype(np.float32)
        pick = rng.integers(0, distinct, lead + (g,))
        return vals[pick]

    def test_round_trip_bit_exact(self):
        dense = self._dense(lead=(3, 2))
        store, idx = compress_rows(dense)
        assert store.shape[:2] == (3, 2) and idx.shape == (3, 2, 12)
        assert np.array_equal(decompress_rows(store, idx), dense)
        assert store.shape[-2] <= 3          # at most `distinct` rows

    def test_all_equal_collapses_to_one_row(self):
        dense = np.broadcast_to(np.arange(4, dtype=np.float32),
                                (2, 8, 4)).copy()
        store, idx = compress_rows(dense)
        assert store.shape == (2, 1, 4)
        assert (idx == 0).all()
        assert rows_compression_ratio(store, idx) == 1.0 / 8.0
        assert np.array_equal(decompress_rows(store, idx), dense)

    def test_all_unique_is_u_equals_g(self):
        rng = np.random.default_rng(1)
        dense = rng.uniform(1.0, 9.0, (10, 4)).astype(np.float32)
        store, idx = compress_rows(dense)
        assert store.shape == (10, 4)
        assert rows_compression_ratio(store, idx) == 1.0
        assert np.array_equal(decompress_rows(store, idx), dense)

    def test_min_u_floor_pads_with_last_row(self):
        dense = np.ones((6, 4), np.float32)
        store, idx = compress_rows(dense, min_u=3)
        assert store.shape == (3, 4)
        assert np.array_equal(store, np.ones((3, 4), np.float32))
        assert np.array_equal(decompress_rows(store, idx), dense)

    def test_compress_stack_shared_map(self):
        """One map shared across the stack axis: two slots merge only
        if their rows agree at EVERY stack position."""
        s, g = 3, 6
        dense = np.zeros((s, g, 4), np.float32)
        dense[:, :3] = 1.0                  # slots 0-2 identical columns
        dense[:, 3:] = 2.0
        dense[2, 5] = 7.0                   # slot 5 diverges at stack 2
        store, idx = compress_stack(dense)
        assert idx.shape == (g,)
        assert idx[0] == idx[1] == idx[2]
        assert idx[3] == idx[4] and idx[5] != idx[3]
        assert store.shape[1] == 3          # three distinct columns
        rebuilt = decompress_rows(
            store.transpose(1, 0, 2).reshape(store.shape[1], -1), idx)
        assert np.array_equal(
            rebuilt.reshape(g, s, 4).transpose(1, 0, 2), dense)

    def test_recompression_after_tighten_round_trips(self):
        """Tightening unique rows keeps the layout lossless: the
        re-compressed patched store round-trips bit-exactly, and U can
        only shrink (rows clamp together at the JEDEC anchor)."""
        from repro.core.guardband import tighten_rows
        rng = np.random.default_rng(2)
        store = np.stack([DDR3_1600.scaled(f, f, f, f).as_row()
                          for f in rng.uniform(0.6, 0.9, 5)]
                         ).astype(np.float32)
        idx = rng.integers(0, 5, 16).astype(np.int32)
        mask = np.zeros(5, bool)
        mask[:3] = True
        new_store, at_jedec = tighten_rows(store, mask)
        assert at_jedec.shape == (5,)
        assert (new_store[:3, :4] >= store[:3, :4]).all()
        assert np.array_equal(new_store[3:], store[3:])
        dense = decompress_rows(new_store, idx)
        store2, idx2 = compress_rows(dense)
        assert store2.shape[-2] <= 5
        assert np.array_equal(decompress_rows(store2, idx2), dense)


def tiny_region_table(m=2, nb=2, banks=4, rg=2, u=3, seed=0):
    rng = np.random.default_rng(seed)
    params = rng.uniform(10.0, 30.0, (m, nb, u, 4)).astype(np.float32)
    idx = rng.integers(0, u, (m, nb, banks, rg)).astype(np.int32)
    idx[0, 0, 0, 0] = u - 1                  # the full range is used
    dense = decompress_rows(params, idx.reshape(m, nb, banks * rg)
                            ).reshape(m, nb, banks, rg, 4)
    pb = dense.max(axis=3)
    return TimingTable((55.0, 85.0), params, np.full(m, 64.0),
                       np.full(m, 64.0), params_module=pb.max(axis=2),
                       region_index=idx, params_bank=pb)


class TestPatchValidation:
    """Satellite: `TimingTable.patch` refuses rank/shape changes with
    `ValueError` (the unique-row axis is the ONE legal resize) and the
    lineage survives a rejected patch untouched."""

    def test_u_resize_is_the_legal_patch(self):
        t0 = tiny_region_table()
        grown = np.concatenate([t0.params, t0.params[:, :, -1:]], axis=2)
        t1 = t0.patch(params=grown)
        assert t1.version == 1 and t1.parent is t0
        assert t1.n_unique == t0.n_unique + 1
        # shrink is legal too, as long as the map stays in range
        idx = np.clip(t0.region_index, 0, 0)
        t2 = t0.patch(params=t0.params[:, :, :1], region_index=idx)
        assert t2.n_unique == 1

    def test_rank_change_rejected(self):
        t0 = tiny_region_table()
        with pytest.raises(ValueError, match="rank"):
            t0.patch(params=t0.params[:, :, 0])

    def test_spatial_shape_change_rejected(self):
        t0 = tiny_region_table()
        with pytest.raises(ValueError, match="shape"):
            t0.patch(params_bank=t0.params_bank[:, :, :2])
        with pytest.raises(ValueError, match="shape"):
            t0.patch(region_index=t0.region_index[:, :, :, :1])
        # the module/bin axes of the region store are pinned too
        with pytest.raises(ValueError, match="shape"):
            t0.patch(params=t0.params[:1])

    def test_cannot_introduce_uncarried_field(self):
        t0 = tiny_region_table()
        bank_only = t0.reduce_regions()
        with pytest.raises(ValueError, match="introduce"):
            bank_only.patch(region_index=t0.region_index)

    def test_index_past_store_rejected(self):
        t0 = tiny_region_table()
        bad = t0.region_index.copy()
        bad[0, 0, 0, 0] = t0.n_unique
        with pytest.raises(ValueError, match="unique-row"):
            t0.patch(region_index=bad)
        # shrinking U below the map's reach is the same violation
        with pytest.raises(ValueError, match="unique-row"):
            t0.patch(params=t0.params[:, :, :1])

    def test_rollback_across_violation(self):
        """A rejected patch must not perturb the lineage: the deployed
        version keeps its parent chain and rolls back cleanly."""
        t0 = tiny_region_table()
        t1 = t0.patch(params=t0.params * np.float32(1.01))
        with pytest.raises(ValueError):
            t1.patch(params=t1.params[:, :, 0])
        assert t1.version == 1 and t1.parent is t0
        assert t1.rollback() is t0
        assert t0.rollback() is t0


@pytest.fixture(scope="module")
def region_pop():
    cfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=6,
                              n_cells=8)
    return sample_population(jax.random.PRNGKey(7), cfg)


@pytest.fixture(scope="module")
def region_ctrl(region_pop):
    ctrl = ALDRAMController(
        Profiler(constants=CALIBRATED_CONSTANTS, grid_step=2.5,
                 impl="ref"),
        temp_bins=(55.0, 70.0, 85.0), regions=4)
    ctrl.profile(region_pop)
    return ctrl


@pytest.mark.slow
class TestRegionController:
    """Tentpole: profile -> mask-compressed region table -> resolution
    levels -> per-(module, bin, bank, region) verify -> one-dispatch
    system evaluation."""

    def test_profile_builds_compressed_store(self, region_ctrl,
                                             region_pop):
        tbl = region_ctrl.table
        assert tbl.per_region and tbl.per_bank
        assert tbl.regions == 4 and tbl.n_banks == region_pop.n_banks
        m, nb = tbl.module_params.shape[:2]
        assert tbl.params.shape == (m, nb, tbl.n_unique, 4)
        assert tbl.region_index.shape == (m, nb, tbl.n_banks, 4)
        assert tbl.compression_ratio() < 1.0

    def test_expand_regions_round_trip(self, region_ctrl):
        tbl = region_ctrl.table
        dense = tbl.expand_regions()
        m, nb, banks, rg = tbl.region_index.shape
        assert dense.shape == (m, nb, banks, rg, 4)
        for (mi, bi, bb, rr) in [(0, 0, 0, 0), (1, 2, 3, 2),
                                 (5, 1, 7, 3)]:
            u = tbl.region_index[mi, bi, bb, rr]
            assert np.array_equal(dense[mi, bi, bb, rr],
                                  tbl.params[mi, bi, u])

    def test_region_table_levels(self, region_ctrl):
        t1 = region_ctrl.region_table(1)
        assert not t1.per_region and t1.per_bank
        assert np.array_equal(t1.params, region_ctrl.table.params_bank)
        t2 = region_ctrl.region_table(2)
        assert t2.per_region and t2.regions == 2
        assert t2.compression_ratio() <= 1.0
        assert region_ctrl.region_table(4) is region_ctrl.table
        with pytest.raises(AssertionError):
            region_ctrl.region_table(3)      # must divide R

    def test_lookup_many_regions_semantics(self, region_ctrl):
        tbl = region_ctrl.table
        dense = tbl.expand_regions()
        rng = np.random.default_rng(1)
        mods = rng.integers(0, dense.shape[0], 24)
        banks = rng.integers(0, tbl.n_banks, 24)
        regs = rng.integers(0, tbl.regions, 24)
        temps = rng.uniform(40.0, 95.0, 24)
        rows = tbl.lookup_many_regions(mods, banks, regs, temps)
        bins = np.asarray(tbl.temp_bins)
        for i in range(24):
            bi = int(np.searchsorted(bins, temps[i], side="left"))
            if bi >= len(bins):
                assert np.array_equal(rows[i], DDR3_1600.as_row())
            else:
                assert np.array_equal(
                    rows[i, :4], dense[mods[i], bi, banks[i], regs[i]])

    def test_verify_region_invariant(self, region_ctrl, region_pop):
        assert region_ctrl.verify(region_pop)

    def test_verify_catches_bad_unique_row(self, region_ctrl,
                                           region_pop):
        """Corrupting ONE unique row (absurd tRCD) must flip verify —
        the region diagonal reads through the index map."""
        tbl = region_ctrl.table
        params = tbl.params.copy()
        params[0, 0, 0, 0] = 1.0
        region_ctrl.table = dataclasses.replace(tbl, params=params)
        try:
            assert not region_ctrl.verify(region_pop)
        finally:
            region_ctrl.table = tbl

    def test_region_reductions_monotone(self, region_ctrl):
        """The headline: finer spatial resolution monotonically
        recovers timing reduction (structural on the select-metric
        latency sums — NOT on system gmean speedups)."""
        red = region_ctrl.region_reductions(levels=(2, 4))
        for op, d in red.items():
            assert d["bank"] >= d["module"] - 1e-9, (op, d)
            assert d["region2"] >= d["bank"] - 1e-9, (op, d)
            assert d["region4"] >= d["region2"] - 1e-9, (op, d)

    def test_safe_stack_regions_deployed_form(self, region_ctrl):
        tbl = region_ctrl.table
        rows_u, edges, idx = tbl.safe_stack_regions()
        nb = len(region_ctrl.temp_bins)
        assert rows_u.shape[0] == nb + 1 and rows_u.shape[2] == 6
        assert idx.shape == (tbl.n_banks, tbl.regions)
        assert np.array_equal(edges,
                              np.asarray(region_ctrl.temp_bins,
                                         np.float32))
        # the gathered JEDEC fallback row is JEDEC for every slot
        last = rows_u[-1][idx.reshape(-1)]
        assert np.array_equal(
            last, np.broadcast_to(DDR3_1600.as_row(),
                                  last.shape).astype(np.float32))
        # bin-monotone through the gather, per slot
        gathered = rows_u[:, idx.reshape(-1)]
        assert (np.diff(gathered[:nb], axis=0) >= -1e-6).all()

    def test_evaluate_region_system_one_dispatch(self, region_ctrl,
                                                 region_pop,
                                                 monkeypatch):
        calls = {"replay": 0}
        real = sim_engine._replay_grid

        def spy(*a, **k):
            calls["replay"] += 1
            return real(*a, **k)

        monkeypatch.setattr(sim_engine, "_replay_grid", spy)
        res = region_ctrl.evaluate_region_system(region_pop, n=128,
                                                 levels=(2, 4))
        assert calls["replay"] == 1
        assert set(res["compression_ratio"]) == {2, 4}
        for op, d in res["reductions"].items():
            assert (d["region4"] >= d["region2"] - 1e-9
                    >= d["bank"] - 2e-9 >= d["module"] - 3e-9), (op, d)
        # the compressed timing axis really is smaller than dense
        assert res["rows"].shape[1] <= res["region_map"].shape[0]
        assert res["region_map"].shape == (region_pop.n_banks * 4,)
