"""DRAM latency profiler — the SoftMC/FPGA campaign analogue (Sec. 5).

Given a (simulated) module population, the profiler:

  1. sweeps the refresh interval at standard timings to find the
     maximum error-free interval per bank/chip/module (Fig. 2a, 3a/b),
  2. derives the *safe refresh interval* (max passing − 8 ms guardband),
  3. sweeps all timing-parameter combinations at the safe interval and
     at each temperature, finding each module's error-free envelope
     (Fig. 2b/c, 3c/d),
  4. selects, per module, the acceptable combo (minimum latency sum,
     min-tRCD tie-break) -> per-parameter reductions.

Everything is batched through `repro.core.sweep.MarginEngine`: a
refresh campaign (both ops) is ONE kernel dispatch, and a
multi-temperature timing campaign over both ops is ONE dispatch — the
whole 115-module characterization costs O(1) launches.  The
`refresh_profile` / `timing_profile` methods are thin shims over the
engine kept for single-condition callers; multi-condition campaigns
should build a `SweepSpec` and call `Profiler.engine.sweep` directly.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from repro.core import timing as T
from repro.core.charge import ChargeConstants, DEFAULT_CONSTANTS
from repro.core.spans import span
from repro.core.sweep import (MarginEngine, Op, SweepSpec,
                              param_reductions, select_combos)
from repro.core.variation import Population


class RefreshProfile(NamedTuple):
    """Maximum error-free refresh intervals (ms) at standard timings.

    Granularity convention (audited against the [modules, chips,
    banks, K] cell hierarchy of `variation.Population`):

      per_chip[m, c] — envelope of chip c: the worst BANK (and tail
                       cell) of that chip governs (reduce banks, K).
      per_bank[m, b] — envelope of RANK-level bank b: bank b spans
                       bank b of every chip (chips operate in
                       lockstep), so the worst CHIP at that bank
                       index governs (reduce chips, K).

    The module envelope is the intersection of either slicing:
    `per_module == per_chip.min(1) == per_bank.min(1)` exactly (the
    first grid failure over a union of cells is the min over its
    parts) — pinned by the envelope-containment test in
    tests/test_bank_table.py on a population with chips != banks.
    """

    per_module: np.ndarray        # [modules]
    per_chip: np.ndarray          # [modules, chips]
    per_bank: np.ndarray          # [modules, banks]
    safe: np.ndarray              # [modules] = per_module − guardband


class TimingProfile(NamedTuple):
    """Chosen error-free timing combo per module at one temperature."""

    combos: np.ndarray            # [modules, 5]  (trcd, tras, twr, trp, trefi)
    latency_sum: np.ndarray       # [modules]
    pass_per_module: np.ndarray   # [modules, n_combos] bool


@dataclasses.dataclass(frozen=True)
class Profiler:
    constants: ChargeConstants = DEFAULT_CONSTANTS
    std: T.TimingParams = T.DDR3_1600
    refresh_guardband_ms: float = T.REFRESH_STEP_MS
    impl: str = "auto"
    grid_step: float = T.TIMING_STEP_NS   # coarsen for calibration search
    engine: MarginEngine | None = None    # built from the fields if None

    def __post_init__(self):
        if self.engine is None:
            object.__setattr__(self, "engine", MarginEngine(
                constants=self.constants, std=self.std, impl=self.impl))

    # ---------------------------------------------------------- combo grids
    def combo_grid(self, op: Op | str) -> np.ndarray:
        op = Op.parse(op)
        grid = (T.read_combo_grid if op is Op.READ else T.write_combo_grid)
        return grid(self.std, self.grid_step)

    def campaign_spec(self, temps: tuple[float, ...],
                      rp_read: "RefreshProfile",
                      rp_write: "RefreshProfile") -> SweepSpec:
        """The standard full campaign: read+write combo grids at each
        test's safe refresh interval, across `temps` — the one spec the
        controller, calibration and the figure benchmarks all run."""
        from repro.core.sweep import OpSweep
        return SweepSpec(
            temps=tuple(temps),
            tests=(OpSweep(Op.READ, self.combo_grid(Op.READ), rp_read.safe),
                   OpSweep(Op.WRITE, self.combo_grid(Op.WRITE),
                           rp_write.safe)))

    # ---------------------------------------------------- refresh sweep (2a)
    def refresh_campaign(self, pop: Population, temp: float = 85.0,
                         grid_ms: np.ndarray | None = None
                         ) -> tuple[RefreshProfile, RefreshProfile]:
        """Refresh-interval envelopes for BOTH tests from ONE dispatch
        (the kernel computes read and write margins in the same pass)."""
        grid = grid_ms if grid_ms is not None else T.refresh_grid()
        std_combo = np.asarray(self.std.as_array())
        combos = np.repeat(std_combo[None, :], len(grid), axis=0)
        combos[:, 4] = grid
        # the device reduces each grid over the tail cells, keeping
        # chips and banks: [modules, chips, banks, grid] pass booleans
        m, ch, bk, k = pop.cells.shape[:4]
        g = len(grid)
        per_cellmin = self.engine.envelopes(
            pop.flat_cells(), combos, cell_shape=(m, ch, bk, k), axes=(3,),
            blocks=((Op.READ, 0, g), (Op.WRITE, 0, g)), temp_c=temp).fetch()
        with span("margin.reduce"):
            return tuple(self._refresh_envelopes(ok, grid)
                         for ok in per_cellmin)

    def refresh_profile(self, pop: Population, temp: float, op: Op | str,
                        grid_ms: np.ndarray | None = None) -> RefreshProfile:
        """Single-test shim over `refresh_campaign` (same one dispatch)."""
        rp_read, rp_write = self.refresh_campaign(pop, temp, grid_ms)
        return rp_read if Op.parse(op) is Op.READ else rp_write

    def _refresh_envelopes(self, per_cellmin: np.ndarray,
                           grid: np.ndarray) -> RefreshProfile:
        """Envelopes from the [modules, chips, banks, grid] booleans:
        every tail cell of the (module, chip, bank) passes."""

        def max_passing(mask: np.ndarray) -> np.ndarray:
            # mask: [..., n_grid]; the envelope is monotone (longer
            # refresh interval = more leakage = less safe), so take the
            # last grid value before the first failure.
            any_fail = ~mask
            idx = np.where(any_fail.any(-1), any_fail.argmax(-1), len(grid))
            idx = np.maximum(idx - 1, 0)
            return grid[idx]

        # rank-level bank b = bank b of EVERY chip -> worst chip governs
        per_bank = max_passing(per_cellmin.all(1))              # [m, banks]
        per_chip = max_passing(per_cellmin.all(2))              # [m, chips]
        per_module = max_passing(per_cellmin.all(1).all(1))
        safe = np.maximum(per_module - self.refresh_guardband_ms, grid[0])
        return RefreshProfile(per_module, per_chip, per_bank, safe)

    # ------------------------------------------------- timing sweep (2b/2c)
    def timing_profile(self, pop: Population, temp: float, op: Op | str,
                       safe_trefi_ms: np.ndarray | None = None
                       ) -> TimingProfile:
        """Sweep timing combos for every module at its safe refresh
        interval, in one batched margin-grid evaluation (shim over a
        single-test, single-temperature `SweepSpec`)."""
        op = Op.parse(op)
        spec = SweepSpec.single(op, self.combo_grid(op), (float(temp),),
                                safe_trefi_ms)
        res = self.engine.sweep(pop, spec)
        return TimingProfile(res.chosen[0][:, 0, :],
                             res.latency_sum[0][:, 0],
                             res.ok[0][:, 0, :])

    # ----------------------------------------------------------- reductions
    def reductions(self, prof: TimingProfile, op: Op | str
                   ) -> dict[str, float]:
        """Average per-parameter and latency-sum reductions vs standard."""
        op = Op.parse(op)
        std = self.std
        r = param_reductions(prof.combos, std, allsafe=True)
        base = std.read_sum() if op is Op.READ else std.write_sum()
        r["latency_sum"] = float(1 - (prof.latency_sum / base).mean())
        return r


__all__ = ["Profiler", "RefreshProfile", "TimingProfile", "select_combos"]
