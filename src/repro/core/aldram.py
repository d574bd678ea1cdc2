"""Adaptive-Latency DRAM: the mechanism (paper Sec. 4).

The controller holds one timing table per (module, temperature bin) —
and, by default, per rank-level BANK within it (FLY-DRAM-style
spatial variation: the module envelope is governed by its weakest
bank, so per-bank registers recover the latency the envelope gives
away; `evaluate_bank_system` prices that headline) — built by the
profiler, and at runtime selects the table for the
module's *current* operating temperature — always rounding the
temperature UP to the next profiled bin (conservative).  The paper's
reliability argument is enforced as an invariant: every selected table
must be error-free for the whole module at the bin's maximum
temperature, with the profiling guardband included.

No DRAM-chip or interface changes: this is exactly the multiple-
timing-register scheme the paper proposes for the memory controller.

Profiling is fully batched through `repro.core.sweep.MarginEngine`:
`profile()` is one refresh campaign plus ONE fused
(temperature bins x read/write) timing campaign, and `verify()` is ONE
dispatch over every (module, bin) pair — no per-bin or per-module
Python-loop kernel calls anywhere.  Past a grid-size budget both run
their campaign over module groups instead (the paper-scale timing
campaign is 1.5e9 margin cells, more than one chip holds).
`evaluate_system()` closes the loop on the system side: the profiled
tables feed a batched `repro.core.sim_engine` campaign that produces a
temperature-resolved Fig. 4 in two more dispatches.

`evaluate_dynamic()` goes one step further and exercises the *online*
half of the mechanism: the profiled per-bin table stack
(`TimingTable.safe_stack`, JEDEC fallback row last) rides the replay
dispatch itself, and the controller's bin-switching logic — sensing,
conservative round-up, down-switch hysteresis, above-hottest-bin
JEDEC fallback — runs inside the traced `lax.scan` per request, under
dynamic thermal scenarios (`repro.core.thermal`).

Both system closures inherit the engine's device-resident fast path:
the statistics and thermal diagnostics they consume (mean latencies,
temp_max, bin_switches) reduce in-dispatch and only [grid]-shaped
summaries reach the host — a profile-to-Fig.4 campaign never
materializes O(grid x requests) arrays host-side.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import timing as T
from repro.core.profiler import Profiler
from repro.core.spans import span
from repro.core.sweep import Op, param_reductions
from repro.core.variation import Population

DEFAULT_TEMP_BINS = (45.0, 55.0, 65.0, 75.0, 85.0)

# Margin-grid cells (tail cells x combo columns) per profiling
# dispatch.  The paper-scale timing campaign (115 modules x 1536 cells
# x 8505 columns = 1.5e9 cells) emits two float32 grids of 6.1 GB
# each (12.3 GB as the kernel pads them) that live on the device until
# they are reduced to pass envelopes there — most of a 16 GB chip — so
# `profile` runs larger campaigns in module groups of at most this
# many cells (2 GB of grids per dispatch).
PROFILE_GRID_ELEMS = 1 << 28

# the per-module selection views of a `SweepResult`, which module
# groups concatenate along their leading axis
_MODULE_VIEWS = ("ok", "chosen", "latency_sum", "ok_bank",
                 "chosen_bank", "latency_sum_bank", "ok_region",
                 "chosen_region", "latency_sum_region")


def default_scenarios():
    """The stock dynamic-ambient suite for `evaluate_dynamic` /
    `benchmarks.thermal_bench`: steady (the degenerate near-static
    case), a diurnal ramp spanning several bins, a cooling failure
    stepping into the hot bins mid-trace, and a bursty square wave
    hovering around a bin edge (the hysteresis stress)."""
    from repro.core import thermal
    return (thermal.steady(42.0),
            thermal.diurnal(38.0, 72.0, period_ns=1.2e5),
            thermal.cooling_failure(44.0, 28.0, at_ns=3.0e4),
            thermal.bursty(42.0, 16.0, period_ns=6.0e4, duty=0.5))


@dataclasses.dataclass
class TimingTable:
    """Timing parameters for each temperature bin.

    `params` is either the per-module table ([modules, bins, 4] ->
    (trcd, tras, twr, trp) in ns) or a FLY-DRAM-style per-bank table
    ([modules, bins, banks, 4]): the margin is *spatial*, so keeping
    one register row per rank-level bank recovers the latency a
    module-level envelope gives away to its weakest bank.

    A per-bank table also carries `params_module`, the module-envelope
    table selected on the intersected (all-banks) pass envelope of the
    SAME fused campaign.  `reduce_banks()` returns it as a standalone
    per-module table, bit-identical to what a per-module-only
    `profile()` builds — note this is NOT a per-parameter max over the
    bank rows: each bank's argmin-latency choice trades parameters
    differently, so the elementwise max of bank rows is generally not
    a profiled grid point at all.  The module-level methods
    (`lookup`/`lookup_many`/`safe_stack`) always answer from the
    module envelope, so every pre-bank caller sees identical rows.
    """

    temp_bins: tuple[float, ...]
    # [modules, bins, 4] | [modules, bins, banks, 4] |
    # [modules, bins, U, 4] unique-row store (when `region_index` set)
    params: np.ndarray
    safe_trefi_read: np.ndarray     # [modules] ms
    safe_trefi_write: np.ndarray    # [modules] ms
    # module-envelope table riding a per-bank `params` (None otherwise)
    params_module: np.ndarray | None = None
    # ---- subarray-region spatial level (mask-compressed) ----
    # int32 [modules, bins, banks, regions] -> unique-row axis of
    # `params`: the index map of the compressed region table.  When
    # set, `params` is the [modules, bins, U, 4] unique-row store and
    # `params_bank` carries the per-bank table (selected on the bank
    # envelope of the SAME campaign — NOT derivable from the region
    # rows, for the same reason the module envelope is not the max of
    # the bank rows), so every bank-level answer stays bit-stable.
    region_index: np.ndarray | None = None
    params_bank: np.ndarray | None = None   # [modules, bins, banks, 4]
    # online-update lineage (repro.fleet.recal): every `patch` bumps
    # the version and keeps the previous table for `rollback`
    version: int = 0
    parent: "TimingTable | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        assert self.params.ndim in (3, 4), self.params.shape
        if self.per_region:
            assert self.params.ndim == 4 \
                and self.region_index.ndim == 4 \
                and self.params_bank is not None \
                and self.params_bank.ndim == 4, \
                "a per-region table = unique store + index map + the " \
                "per-bank table of the same campaign"
            assert self.region_index.shape[:2] == self.params.shape[:2] \
                and self.params_bank.shape[:3] \
                == self.region_index.shape[:3], \
                (self.params.shape, self.region_index.shape,
                 self.params_bank.shape)
            assert int(self.region_index.max()) < self.params.shape[2], \
                "region_index out of range of the unique-row store"
        if self.per_bank:
            assert self.params_module is not None \
                and self.params_module.ndim == 3, \
                "a per-bank table carries its module-envelope table"

    @property
    def per_bank(self) -> bool:
        return self.params.ndim == 4

    @property
    def per_region(self) -> bool:
        return self.region_index is not None

    @property
    def regions(self) -> int:
        return self.region_index.shape[3] if self.per_region else 1

    @property
    def n_unique(self) -> int | None:
        """Unique-row count U of the compressed region store."""
        return self.params.shape[2] if self.per_region else None

    @property
    def n_banks(self) -> int | None:
        if self.per_region:
            return self.region_index.shape[2]
        return self.params.shape[2] if self.per_bank else None

    @property
    def bank_params(self) -> np.ndarray | None:
        """The per-bank [modules, bins, banks, 4] view (the table
        itself for a plain per-bank table, the carried bank table for
        a region-compressed one)."""
        if self.per_region:
            return self.params_bank
        return self.params if self.per_bank else None

    @property
    def module_params(self) -> np.ndarray:
        """The per-module [modules, bins, 4] view (the table itself
        when per-module, the carried envelope table when per-bank)."""
        return self.params_module if self.per_bank else self.params

    def reduce_banks(self) -> "TimingTable":
        """Collapse to the per-module table: exactly the table a
        per-module-only profile builds (see class docstring)."""
        if not self.per_bank:
            return self
        return TimingTable(self.temp_bins, self.module_params,
                           self.safe_trefi_read, self.safe_trefi_write)

    def reduce_regions(self) -> "TimingTable":
        """Collapse a region-compressed table to the per-bank table of
        the same campaign: exactly the table a per-bank-only profile
        builds (the carried `params_bank` was selected on the bank
        envelope of the SAME fused dispatch)."""
        if not self.per_region:
            return self
        return TimingTable(self.temp_bins, self.params_bank,
                           self.safe_trefi_read, self.safe_trefi_write,
                           params_module=self.params_module)

    def expand_regions(self) -> np.ndarray:
        """Decompress the region store to the dense
        [modules, bins, banks, regions, 4] table (bit-exact: the store
        is a lossless layout, `runtime.compression.compress_rows`)."""
        assert self.per_region
        from repro.runtime.compression import decompress_rows
        m, nb, banks, regions = self.region_index.shape
        dense = decompress_rows(
            self.params, self.region_index.reshape(m, nb, -1))
        return dense.reshape(m, nb, banks, regions, 4)

    def compression_ratio(self) -> float:
        """Stored unique rows / dense (banks x regions) rows — the
        deployability metric of the region table (< 1.0 means the
        store beats materializing every region row)."""
        assert self.per_region
        return float(self.n_unique) / float(self.n_banks * self.regions)

    # ---------------------------------------------------- online lineage
    def _check_patch(self, name: str, new) -> None:
        """Shape/rank compatibility of one patched field vs THIS
        version (the parent of the patch): a patch that silently
        changes the table's rank or spatial shape mid-lineage would
        desynchronize every consumer holding the lineage — raise
        `ValueError` instead.  The unique-row axis of a region store
        is the one axis allowed to resize (re-compression after drift
        legitimately changes U), provided the index map stays in
        range (checked cross-field after the replace)."""
        cur = getattr(self, name)
        if cur is None:
            raise ValueError(
                f"patch cannot introduce '{name}': version "
                f"{self.version} does not carry it (rank change "
                "mid-lineage)")
        new = np.asarray(new)
        if new.ndim != cur.ndim:
            raise ValueError(
                f"patch '{name}': rank {new.ndim} incompatible with "
                f"parent version {self.version} rank {cur.ndim} "
                f"({new.shape} vs {cur.shape})")
        if name == "params" and self.per_region:
            ok = (new.shape[:2] == cur.shape[:2]
                  and new.shape[3:] == cur.shape[3:])
        else:
            ok = new.shape == cur.shape
        if not ok:
            raise ValueError(
                f"patch '{name}': shape {new.shape} incompatible with "
                f"parent version {self.version} shape {cur.shape}")

    def patch(self, **updates) -> "TimingTable":
        """A new table VERSION with the given field updates (`params`,
        `params_module`, `params_bank`, `region_index`,
        `safe_trefi_read`, `safe_trefi_write`) — the deployment move
        of the fleet recalibration service (`repro.fleet.recal`):
        online guardband tightening, clean-streak relaxation, and
        re-profiling all install their new rows through here, so every
        deployed table knows its lineage.  The patched table's
        `version` is bumped and its `parent` is THIS table; the caller
        must have verified (margin probe or full `verify()`) that the
        patched rows restore the zero-error invariant for the
        population being served before deploying.

        Every update is validated against the parent version's shape
        and rank (`ValueError` on mismatch, see `_check_patch`) — a
        rank- or shape-changing deployment is a new PROFILE, not a
        patch."""
        allowed = {"params", "params_module", "params_bank",
                   "region_index", "safe_trefi_read", "safe_trefi_write"}
        assert set(updates) <= allowed, set(updates) - allowed
        for name, new in updates.items():
            self._check_patch(name, new)
        if self.per_region:
            nxt_params = np.asarray(updates.get("params", self.params))
            nxt_index = np.asarray(
                updates.get("region_index", self.region_index))
            if int(nxt_index.max()) >= nxt_params.shape[2]:
                raise ValueError(
                    "patch: region_index indexes past the unique-row "
                    f"store (max {int(nxt_index.max())} >= "
                    f"U={nxt_params.shape[2]})")
        return dataclasses.replace(self, version=self.version + 1,
                                   parent=self, **updates)

    def rollback(self) -> "TimingTable":
        """The previous deployed version (self if this is the root).
        The escape hatch when a patch turns out to be wrong — e.g. a
        relaxation deployed on a clean streak that the next scrub pass
        proves premature."""
        return self.parent if self.parent is not None else self

    def lookup(self, module: int, temp_c: float) -> T.TimingParams:
        """Conservative selection: smallest profiled bin >= temp; above
        the hottest bin fall back to standard JEDEC timings."""
        return T.TimingParams.from_row(
            self.lookup_many(np.array([module]), np.array([temp_c]))[0])

    def _lookup_rows(self, temps_c: np.ndarray, gather) -> np.ndarray:
        """The ONE conservative-selection core both granularities
        share: `np.searchsorted` picks the smallest profiled bin >=
        temp (rounding UP), queries ABOVE the hottest profiled bin
        fall back to standard JEDEC timings, and the static
        tREFI/tCL columns ride along.  `gather(bin_idx)` returns each
        query's [K, 4] params at its (clamped) bin — the only thing
        that differs between the module and per-bank lookups."""
        bins = np.asarray(self.temp_bins, np.float64)
        bi = np.searchsorted(bins, temps_c, side="left")
        over = bi >= len(bins)
        rows = np.empty((temps_c.shape[0], 6), np.float32)
        rows[:, :4] = np.where(
            over[:, None], np.asarray(T.DDR3_1600.as_row()[:4]),
            gather(np.minimum(bi, len(bins) - 1)))
        rows[:, 4] = T.STANDARD_TREFI_MS
        rows[:, 5] = T.DDR3_1600.tcl
        return rows

    def lookup_many(self, modules: np.ndarray,
                    temps_c: np.ndarray) -> np.ndarray:
        """Vectorised batched selection: pairwise (module, temperature)
        queries -> [K, 6] stacked timing rows (`TimingParams.as_row`
        layout), with `_lookup_rows`' conservative round-up and
        above-hottest-bin JEDEC fallback — the controller never
        extrapolates reduced timings past the temperatures it
        actually verified.  The in-scan adaptive replay
        (`dram_sim.replay_adaptive` over `safe_stack`) applies the
        same two rules per request, plus a down-switch hysteresis
        (see `safe_stack`)."""
        modules, temps_c = np.broadcast_arrays(
            np.atleast_1d(np.asarray(modules, np.int64)),
            np.atleast_1d(np.asarray(temps_c, np.float64)))
        return self._lookup_rows(
            temps_c, lambda bi: self.module_params[modules, bi])

    def lookup_many_banks(self, modules: np.ndarray, banks: np.ndarray,
                          temps_c: np.ndarray) -> np.ndarray:
        """Per-bank variant of `lookup_many`: pairwise (module, bank,
        temperature) queries -> [K, 6] stacked timing rows, through
        the same `_lookup_rows` selection core."""
        assert self.per_bank, "per-module table has no bank axis"
        modules, banks, temps_c = np.broadcast_arrays(
            np.atleast_1d(np.asarray(modules, np.int64)),
            np.atleast_1d(np.asarray(banks, np.int64)),
            np.atleast_1d(np.asarray(temps_c, np.float64)))
        return self._lookup_rows(
            temps_c, lambda bi: self.bank_params[modules, bi, banks])

    def lookup_many_regions(self, modules: np.ndarray, banks: np.ndarray,
                            regions: np.ndarray,
                            temps_c: np.ndarray) -> np.ndarray:
        """Per-(bank, subarray region) variant of `lookup_many`:
        pairwise (module, bank, region, temperature) queries -> [K, 6]
        stacked timing rows through the same `_lookup_rows` selection
        core, gathered through the compressed store's index map."""
        assert self.per_region, "not a region-compressed table"
        modules, banks, regions, temps_c = np.broadcast_arrays(
            np.atleast_1d(np.asarray(modules, np.int64)),
            np.atleast_1d(np.asarray(banks, np.int64)),
            np.atleast_1d(np.asarray(regions, np.int64)),
            np.atleast_1d(np.asarray(temps_c, np.float64)))

        def gather(bi):
            u = self.region_index[modules, bi, banks, regions]
            return self.params[modules, bi, u]

        return self._lookup_rows(temps_c, gather)

    def safe_stack(self) -> tuple[np.ndarray, np.ndarray]:
        """The table stack the ADAPTIVE replay selects over in-scan:
        ([bins + 1, 6] rows, [bins] edges).

        Row b is the all-module-safe row of bin b (max over modules
        per parameter: the slowest module governs a one-register-set
        deployment, paper Sec. 6), additionally forced bin-monotone by
        a running max over bins — a hotter bin never carries a smaller
        parameter than a cooler one, so in-scan bin selection can only
        relax timings as the module cools (monotone rows also make
        "adaptive is never slower than static-worst-case" a structural
        guarantee, not a statistical one).  The LAST row is the JEDEC
        fallback selected above the hottest profiled bin — identical
        semantics to `lookup_many`, and elementwise >= every profiled
        row since profiling only ever reduces below standard.

        Hysteresis rides next to this stack at replay time
        (`thermal.ThermalConfig.hyst_c`): switching UP through these
        rows is immediate — the reliability invariant must hold the
        instant the sensed temperature crosses a bin edge — while
        switching DOWN requires the temperature to fall the hysteresis
        margin below the cooler bin's edge, so a module hovering on an
        edge does not thrash the timing registers.
        """
        return self._stack_rows(
            lambda mods, tc: self.lookup_many(
                mods, np.full(mods.shape[0], tc)).max(axis=0))

    def safe_stack_banks(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-bank variant of `safe_stack`: ([bins + 1, banks, 6]
        rows, [bins] edges) — one all-module-safe row per (bin, bank),
        bin-monotone per bank via the same running max, with the
        JEDEC fallback row last (broadcast across banks).  The
        adaptive replay gathers row (selected bin, request's bank)
        in-scan, so a per-bank deployment rides the identical
        dispatch as the per-module stack."""
        assert self.per_bank
        banks = self.n_banks

        def bin_rows(mods, tc):
            m = mods.shape[0]
            return np.stack([self.lookup_many_banks(
                mods, np.full(m, b), np.full(m, tc)).max(axis=0)
                for b in range(banks)])

        return self._stack_rows(bin_rows)

    def safe_stack_regions(self) -> tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
        """Per-region variant of `safe_stack`, in DEPLOYED compressed
        form: ([bins + 1, U', 6] unique rows, [bins] edges,
        [banks, regions] int32 region map).

        The dense all-module-safe per-(bin, bank, region) stack (same
        running-max bin-monotone construction, JEDEC fallback row last)
        is RE-compressed with one index map shared across bins — the
        in-scan replay gathers row (selected bin, map[bank, region])
        and the map must not vary with the bin — so U' here is the
        unique count over whole (bank, region) timing COLUMNS, not the
        per-bin count the table stores."""
        assert self.per_region
        banks, regions = self.n_banks, self.regions
        from repro.runtime.compression import compress_stack

        def bin_rows(mods, tc):
            m = mods.shape[0]
            out = np.empty((banks, regions, 6), np.float32)
            for b in range(banks):
                for r in range(regions):
                    out[b, r] = self.lookup_many_regions(
                        mods, np.full(m, b), np.full(m, r),
                        np.full(m, tc)).max(axis=0)
            return out

        dense, edges = self._stack_rows(bin_rows)
        rows, idx = compress_stack(
            dense.reshape(dense.shape[0], banks * regions, 6))
        return rows, edges, idx.reshape(banks, regions)

    def _stack_rows(self, bin_rows) -> tuple[np.ndarray, np.ndarray]:
        """The ONE stack-construction core both granularities share:
        `bin_rows(modules, bin_temp)` -> the all-module-safe row(s) of
        that bin ([6] or [banks, 6]); a running max forces the stack
        bin-monotone and the JEDEC fallback row rides last."""
        nb = len(self.temp_bins)
        mods = np.arange(self.params.shape[0])
        first = bin_rows(mods, self.temp_bins[0])
        rows = np.empty((nb + 1,) + first.shape, np.float32)
        rows[0] = first
        for bi, tc in enumerate(self.temp_bins[1:], start=1):
            rows[bi] = bin_rows(mods, tc)
        rows[:nb] = np.maximum.accumulate(rows[:nb], axis=0)
        rows[nb] = T.DDR3_1600.as_row()
        return rows, np.asarray(self.temp_bins, np.float32)


class ALDRAMController:
    """Profile once; select per (module, temperature) at runtime.

    `per_bank=True` (the default) builds a FLY-DRAM-style per-bank
    `TimingTable` from the SAME fused campaign dispatch — the margin
    grid is simply reduced per rank-level bank instead of collapsing
    the whole cell hierarchy — alongside the module-envelope table
    every module-level method keeps answering from."""

    def __init__(self, profiler: Profiler | None = None,
                 temp_bins: tuple[float, ...] = DEFAULT_TEMP_BINS,
                 per_bank: bool = True, regions: int = 1):
        self.profiler = profiler or Profiler()
        self.engine = self.profiler.engine
        self.temp_bins = temp_bins
        self.per_bank = per_bank
        assert regions >= 1 and (regions == 1 or per_bank), \
            "subarray regions refine the per-bank table"
        self.regions = regions
        self.table: TimingTable | None = None
        self.sweep_result = None

    # ------------------------------------------------------------ profile
    def profile(self, pop: Population) -> TimingTable:
        """Build the full (module x bin[, bank]) table from one refresh
        campaign and ONE fused multi-temperature, read+write timing
        campaign — the per-bank axis costs zero extra dispatches."""
        with span("aldram.profile", modules=pop.n_modules):
            prof = self.profiler
            rp_read, rp_write = prof.refresh_campaign(pop, 85.0)
            res = self._sweep(
                pop, prof.campaign_spec(self.temp_bins, rp_read, rp_write))
            # the selection views, for reporting (evaluate_bank_system's
            # reduction statistics, tests)
            self.sweep_result = res
            kr, kw = res.index(Op.READ), res.index(Op.WRITE)

            def combine(cr, cw):
                # one register set must satisfy both tests: take the safer
                # (larger) of the read/write choices per parameter
                p = np.empty(cr.shape[:-1] + (4,), np.float32)
                p[..., 0] = np.maximum(cr[..., 0], cw[..., 0])
                p[..., 1] = cr[..., 1]               # tRAS: read test
                p[..., 2] = cw[..., 2]               # tWR: write test
                p[..., 3] = np.maximum(cr[..., 3], cw[..., 3])
                return p

            params_module = combine(res.chosen[kr], res.chosen[kw])
            if self.regions > 1:
                # [modules, banks, bins, 4] -> [modules, bins, banks, 4]
                params_bank = combine(res.chosen_bank[kr],
                                      res.chosen_bank[kw]
                                      ).transpose(0, 2, 1, 3)
                # [modules, banks, regions, bins, 4]
                # -> [modules, bins, banks * regions, 4], mask-compressed
                # per (module, bin) into the unique-row store + index map
                from repro.runtime.compression import compress_rows
                m = params_module.shape[0]
                dense = combine(res.chosen_region[kr], res.chosen_region[kw]
                                ).transpose(0, 3, 1, 2, 4)
                nb, banks, regions = dense.shape[1:4]
                store, idx = compress_rows(
                    dense.reshape(m, nb, banks * regions, 4))
                self.table = TimingTable(
                    self.temp_bins, store.astype(np.float32),
                    rp_read.safe, rp_write.safe,
                    params_module=params_module,
                    region_index=idx.reshape(m, nb, banks, regions),
                    params_bank=params_bank)
            elif self.per_bank:
                # [modules, banks, bins, 4] -> [modules, bins, banks, 4]
                params_bank = combine(res.chosen_bank[kr],
                                      res.chosen_bank[kw]
                                      ).transpose(0, 2, 1, 3)
                self.table = TimingTable(self.temp_bins, params_bank,
                                         rp_read.safe, rp_write.safe,
                                         params_module=params_module)
            else:
                self.table = TimingTable(self.temp_bins, params_module,
                                         rp_read.safe, rp_write.safe)
            return self.table

    def _sweep(self, pop: Population, spec):
        """`engine.sweep` of the timing campaign — ONE dispatch up to
        `PROFILE_GRID_ELEMS` margin cells, else one per module group,
        each launched before the one before it is fetched.  Every
        selection view is per module (a module's envelope reads only
        its own cells' margins), so the groups' views concatenate to
        exactly the single-dispatch result."""
        m = pop.n_modules
        cpm = int(np.prod(pop.cells.shape[1:4]))
        cols = len(spec.temps) * sum(t.combos.shape[0]
                                     for t in spec.tests)
        g = max(1, PROFILE_GRID_ELEMS // (cpm * cols))
        if g >= m:
            return self.engine.sweep(pop, spec, regions=self.regions)
        groups = []
        for lo in range(0, m, g):
            sl = slice(lo, min(lo + g, m))
            tests = tuple(
                dataclasses.replace(
                    t, trefi_ms=(None if t.trefi_ms is None
                                 else t.trefi_per_module(m)[sl]))
                for t in spec.tests)
            groups.append((Population(pop.cells[sl]),
                           dataclasses.replace(spec, tests=tests)))
        parts = self.engine.sweeps(groups, regions=self.regions)
        views = {f: tuple(np.concatenate([getattr(r, f)[k] for r in parts])
                          for k in range(len(getattr(parts[0], f))))
                 for f in _MODULE_VIEWS}
        return dataclasses.replace(parts[0], spec=spec, **views)

    # ----------------------------------------------- resolution levels
    def region_table(self, level: int) -> TimingTable:
        """The table profiled at a COARSER region resolution, derived
        from the stored campaign views without a new dispatch: the
        level-`level` envelope of a (bank, coarse-region) group is the
        intersection of its fine regions' envelopes (`ok.all` over the
        grouped axis — exact booleans), so re-running `select_combos`
        on the grouped envelope reproduces what a `regions=level`
        profile would have chosen, bit-identically.  `level` must
        divide the profiled region count; `level == 1` returns the
        per-bank table (`reduce_regions`), `level == regions` the
        table itself."""
        assert self.table is not None and self.table.per_region
        res = self.sweep_result
        R = self.regions
        assert 1 <= level <= R and R % level == 0, (level, R)
        if level == R:
            return self.table
        if level == 1:
            return self.table.reduce_regions()
        from repro.core.sweep import select_combos
        from repro.runtime.compression import compress_rows
        m = self.table.module_params.shape[0]
        chosen = {}
        for op in Op:
            k = res.index(op)
            okl = res.ok_region[k].reshape(
                res.ok_region[k].shape[:2] + (level, R // level)
                + res.ok_region[k].shape[3:]).all(3)
            chosen[op], _ = select_combos(
                res.spec.tests[k].combos, okl, op,
                res.spec.op_trefi(op, m), self.profiler.std)

        def combine(cr, cw):
            p = np.empty(cr.shape[:-1] + (4,), np.float32)
            p[..., 0] = np.maximum(cr[..., 0], cw[..., 0])
            p[..., 1] = cr[..., 1]
            p[..., 2] = cw[..., 2]
            p[..., 3] = np.maximum(cr[..., 3], cw[..., 3])
            return p

        dense = combine(chosen[Op.READ], chosen[Op.WRITE]
                        ).transpose(0, 3, 1, 2, 4)
        nb, banks = dense.shape[1:3]
        store, idx = compress_rows(
            dense.reshape(m, nb, banks * level, 4))
        return TimingTable(
            self.temp_bins, store.astype(np.float32),
            self.table.safe_trefi_read, self.table.safe_trefi_write,
            params_module=self.table.params_module,
            region_index=idx.reshape(m, nb, banks, level),
            params_bank=self.table.params_bank)

    # ------------------------------------------------------------- select
    def select(self, module: int, temp_c: float) -> T.TimingParams:
        assert self.table is not None, "profile() first"
        return self.table.lookup(module, temp_c)

    # -------------------------------------------------------------- verify
    def verify(self, pop: Population,
               max_grid_elems: int = 8_000_000) -> bool:
        """The zero-error invariant (the paper's 33-day stress test,
        Sec. 6): for every module and every bin, the selected timings
        must be error-free at the bin's max temperature with the safe
        refresh interval — and for a per-bank table, every
        (module, bin, bank) row must additionally be error-free for
        every cell of ITS rank-level bank (all chips, all tail cells).
        Returns True iff no margin is negative.

        ONE vectorised dispatch: every (module, bin) envelope row —
        and, per-bank, every (module, bin, bank) row — becomes a combo
        column with its bin temperature, the per-module safe refresh
        intervals ride in the per-cell read/write overrides, and the
        module- (and bank-) diagonals of the resulting grid are
        reduced host-side.

        The dense grid pairs every module's cells with every module's
        combos, so only its diagonals are useful; for very large
        populations the check is chunked into module groups that keep
        each dispatch under `max_grid_elems` (still no per-module
        Python-loop kernel calls — group count grows like sqrt of the
        excess, and the small/tested sizes stay a single dispatch).
        """
        assert self.table is not None
        tbl = self.table
        m, b = tbl.module_params.shape[:2]
        ch, bk, kc = pop.cells.shape[1:4]
        cpm = ch * bk * kc                           # cells per module
        banks = tbl.n_banks if tbl.per_bank else 0
        if banks:
            assert banks == bk, (banks, bk)
        rg = tbl.regions if tbl.per_region else 0
        if rg:
            assert kc % rg == 0, (kc, rg)
        # combos per module: b envelope rows, [b, banks] bank rows,
        # and for a region table the [b, banks, regions] region rows
        cols = b * (1 + banks + banks * rg)
        g = max(1, min(m, int((max_grid_elems / (cpm * cols)) ** 0.5)))

        cells = np.asarray(pop.flat_cells()).reshape(m, cpm, -1)
        trefi_r = tbl.safe_trefi_read.astype(np.float32)
        trefi_w = tbl.safe_trefi_write.astype(np.float32)
        temps_bins = np.asarray(tbl.temp_bins, np.float32)
        # per-module column layout: b envelope rows, the [b, banks]
        # bank rows, then the [b, banks * regions] region rows — bin
        # temperatures tile accordingly
        temps_mod = temps_bins
        if banks:
            temps_mod = np.concatenate([temps_mod,
                                        np.repeat(temps_bins, banks)])
        if rg:
            temps_mod = np.concatenate(
                [temps_mod, np.repeat(temps_bins, banks * rg)])
        dense_r = tbl.expand_regions() if rg else None

        for lo in range(0, m, g):
            sl = slice(lo, min(lo + g, m))
            n = sl.stop - sl.start
            combos = np.empty((n * cols, 5), np.float32)
            rows_m = tbl.module_params[sl].reshape(n, b, 4)
            if banks:
                rows_b = tbl.bank_params[sl].reshape(n, b * banks, 4)
                parts = [rows_m, rows_b]
                if rg:
                    parts.append(dense_r[sl].reshape(n, b * banks * rg, 4))
                combos[:, :4] = np.concatenate(
                    parts, axis=1).reshape(n * cols, 4)
            else:
                combos[:, :4] = rows_m.reshape(n * cols, 4)
            combos[:, 4] = T.STANDARD_TREFI_MS       # overridden per cell
            read_m, write_m = self.engine.margins(
                cells[sl].reshape(n * cpm, -1), combos,
                temps_combo=np.tile(temps_mod, n),
                trefi_read=np.repeat(trefi_r[sl], cpm),
                trefi_write=np.repeat(trefi_w[sl], cpm))
            mi = np.arange(n)
            for grid in (read_m, write_m):
                grid = grid.reshape(n, cpm, n, cols)
                # module-diagonal of the envelope block [mods, cpm, b]
                if grid[mi, :, mi, :b].min() < 0.0:
                    return False
                if banks:
                    # bank block: module-diagonal, then pair each cell's
                    # bank with its combo's bank
                    gb = grid[:, :, :, b:b * (1 + banks)].reshape(
                        n, ch, bk, kc, n, b, banks)
                    gb = gb[mi, :, :, :, mi]     # [mods, ch, bk, kc, b, banks]
                    bj = np.arange(banks)
                    if gb[:, :, bj, :, :, bj].min() < 0.0:
                        return False
                if rg:
                    # region block: module-diagonal, then pair each
                    # cell's (bank, row-position group) with its
                    # combo's (bank, region)
                    gr = grid[:, :, :, b * (1 + banks):].reshape(
                        n, ch, bk, rg, kc // rg, n, b, banks, rg)
                    gr = gr[mi, :, :, :, :, mi]
                    # [mods, ch, bk, rg_cell, kc/rg, b, banks, rg_combo]
                    bj = np.arange(banks)[:, None]
                    rj = np.arange(rg)[None, :]
                    if gr[:, :, bj, rj, :, :, bj, rj].min() < 0.0:
                        return False
        return True

    # ------------------------------------------------------ system closure
    def evaluate_system(self, pop: Population,
                        temps: tuple[float, ...] | None = None,
                        n: int = 4096, seed: int = 0,
                        policies=None, engine=None, traffic=None,
                        std: T.TimingParams = T.DDR3_1600,
                        rank_timings: T.RankTimings | None = None,
                        t_burst_ns: float = 5.0) -> dict:
        """Close the loop from profiling to the paper's Fig. 4: replay
        the full workload pool under the timings the profiler actually
        measured, one temperature bin at a time — NOT the paper's
        hard-coded 55C evaluation constants.

        For every requested temperature the controller takes the
        profiled per-(module, bin) `TimingTable` rows (`lookup_many`),
        reduces them to the all-module-safe row (the slowest module
        governs a one-register-set deployment, paper Sec. 6), and
        stacks them with the DDR3 baseline into ONE batched SimEngine
        campaign: 35 workloads x single/multi-core x (1 + n_temps)
        timing rows in 2 traced dispatches.

        Returns per-temperature-bin speedup summaries plus the raw
        latency/speedup grids and the campaign's `SimResult`.

        `traffic` (a `dram_sim.KVSpec`) replaces the workload pool: the
        same rows (`std` the baseline, JEDEC DDR3-1600 by default)
        replay its streams on the geometry its address map describes,
        with `rank_timings` (`SimSpec.rank_timings`) and `t_burst_ns`,
        in one dispatch; the result then carries
        {"temps", "rows", "result", "policies", "source"}.
        """
        from repro.core import dram_sim, perf_model
        with span("aldram.evaluate_system") as s:
            if self.table is None:
                self.profile(pop)
            tbl = self.table
            temps = tuple(temps if temps is not None else tbl.temp_bins)
            policies = policies or (dram_sim.OPEN_FCFS,)
            m = tbl.params.shape[0]
            rows = np.empty((1 + len(temps), 6), np.float32)
            rows[0] = std.as_row()
            mods = np.arange(m)
            for si, tc in enumerate(temps):
                # all-safe row: max over modules per parameter at this bin
                rows[1 + si] = tbl.lookup_many(
                    mods, np.full(m, tc)).max(axis=0)
            # tREFI and tCL are not profiled: the standard's
            rows[1:, 4:] = rows[0, 4:]
            s.count(rows=len(rows), policies=len(policies))
            if traffic is not None:
                from repro.core.sim_engine import SimEngine, SimSpec
                res = (engine or SimEngine()).run(SimSpec(
                    traces=traffic, timings=rows, policies=policies,
                    n_banks=traffic.n_banks, n_ranks=traffic.n_ranks,
                    bank_groups=traffic.bank_groups,
                    rank_timings=rank_timings, t_burst_ns=t_burst_ns))
                return {"temps": temps, "rows": rows, "result": res,
                        "policies": policies, "source": "profiled-table"}

            em = perf_model.evaluate_many(rows, n=n, seed=seed, engine=engine,
                                          policies=policies,
                                          n_banks=pop.n_banks)
            sp = perf_model.cpi_speedups(em["mean_latency_ns"])
            intensive = np.array([w.intensive for w in perf_model.WORKLOADS])
            # summaries for EVERY policy of the campaign; `per_temp` is the
            # first policy's view (the headline the benchmarks report)
            per_policy = []
            for pi in range(len(policies)):
                d = {}
                for si, tc in enumerate(temps):
                    s_multi = sp[1, :, pi, 1 + si]       # multi-core
                    d[float(tc)] = {
                        "multi_intensive_gmean":
                            perf_model.gmean_speedup(s_multi[intensive]),
                        "multi_nonintensive_gmean":
                            perf_model.gmean_speedup(s_multi[~intensive]),
                        "multi_all_gmean": perf_model.gmean_speedup(s_multi),
                        "single_all_gmean":
                            perf_model.gmean_speedup(sp[0, :, pi, 1 + si]),
                    }
                per_policy.append(d)
            return {"temps": temps, "rows": rows, "speedups": sp,
                    "mean_latency_ns": em["mean_latency_ns"],
                    "result": em["result"],
                    "workloads": em["workloads"], "per_temp": per_policy[0],
                    "per_policy": per_policy, "policies": policies,
                    "source": "profiled-table"}

    # -------------------------------------------------- per-bank closure
    def evaluate_bank_system(self, pop: Population,
                             temps: tuple[float, ...] | None = None,
                             n: int = 4096, seed: int = 0,
                             policies=None, engine=None) -> dict:
        """FLY-DRAM's headline, priced on the system side: replay the
        workload pool under the all-module-safe PER-BANK rows of every
        temperature bin, against the per-module envelope rows of the
        same bins — in ONE batched campaign.

        The timing axis is a [1 + 2*T, banks, 6] per-bank stack: the
        JEDEC baseline and the per-module envelope rows ride it
        broadcast constant across banks (which replays bit-identical
        to the per-module path), the per-bank rows vary per bank, and
        the replay gathers each request's row from its bank — so the
        whole comparison is still one synthesis + one replay dispatch.

        Also reports the table-level mean timing reductions (the
        Sec. 5.2 statistic, per test) at both granularities.  The
        per-bank reduction is structurally >= the per-module one:
        every bank envelope contains its module envelope, so each
        bank's chosen latency sum is <= its module's.
        """
        from repro.core import dram_sim, perf_model
        if self.table is None:
            self.profile(pop)
        tbl = self.table
        assert tbl.per_bank, "profile() a per_bank controller first"
        temps = tuple(temps if temps is not None else tbl.temp_bins)
        policies = policies or (dram_sim.OPEN_FCFS,)
        m, banks = tbl.module_params.shape[0], tbl.n_banks
        assert banks == pop.n_banks, (banks, pop.n_banks)
        nt = len(temps)
        rows = np.empty((1 + 2 * nt, banks, 6), np.float32)
        rows[0] = T.DDR3_1600.as_row()[None, :]
        mods = np.arange(m)
        for si, tc in enumerate(temps):
            rows[1 + si] = tbl.lookup_many(
                mods, np.full(m, tc)).max(axis=0)[None, :]
            for bb in range(banks):
                rows[1 + nt + si, bb] = tbl.lookup_many_banks(
                    mods, np.full(m, bb), np.full(m, tc)).max(axis=0)

        em = perf_model.evaluate_many(rows, n=n, seed=seed,
                                      engine=engine, policies=policies,
                                      n_banks=banks)
        sp = perf_model.cpi_speedups(em["mean_latency_ns"])
        intensive = np.array([w.intensive for w in perf_model.WORKLOADS])
        per_temp = {}
        for si, tc in enumerate(temps):
            s_mod = sp[1, :, 0, 1 + si]              # multi-core
            s_bank = sp[1, :, 0, 1 + nt + si]
            per_temp[float(tc)] = {
                "module_all_gmean": perf_model.gmean_speedup(s_mod),
                "bank_all_gmean": perf_model.gmean_speedup(s_bank),
                "module_intensive_gmean":
                    perf_model.gmean_speedup(s_mod[intensive]),
                "bank_intensive_gmean":
                    perf_model.gmean_speedup(s_bank[intensive]),
                "bank_minus_module":
                    perf_model.gmean_speedup(s_bank)
                    - perf_model.gmean_speedup(s_mod),
            }
        # table-level mean timing reductions per granularity
        red = {}
        res_sweep = self.sweep_result
        std = self.profiler.std
        for op in Op:
            k = res_sweep.index(op)
            base = std.read_sum() if op is Op.READ else std.write_sum()
            red[op.value] = {
                "module": float(
                    1 - (res_sweep.latency_sum[k] / base).mean()),
                "bank": float(
                    1 - (res_sweep.latency_sum_bank[k] / base).mean()),
            }
        return {"temps": temps, "rows": rows, "speedups": sp,
                "mean_latency_ns": em["mean_latency_ns"],
                "workloads": em["workloads"], "per_temp": per_temp,
                "reductions": red, "policies": policies,
                "source": "profiled-bank-table"}

    # ------------------------------------------------- per-region closure
    def region_reductions(self, levels: tuple[int, ...] = ()
                          ) -> dict[str, dict[str, float]]:
        """Table-level mean timing reductions (the Sec. 5.2 statistic)
        at every spatial resolution level: module envelope, per-bank,
        and per-(bank, region) at each requested `levels` entry (all
        derived from the ONE stored campaign, no new dispatch).  The
        sequence is structurally monotone — every finer envelope
        contains its coarser group's, so each finer level's mean
        chosen latency sum is <= the coarser one's."""
        from repro.core.sweep import select_combos
        res = self.sweep_result
        assert res is not None, "profile() first"
        R = self.regions
        m = self.table.module_params.shape[0]
        std = self.profiler.std
        out: dict[str, dict[str, float]] = {}
        for op in Op:
            k = res.index(op)
            base = std.read_sum() if op is Op.READ else std.write_sum()
            d = {"module": float(
                     1 - (res.latency_sum[k] / base).mean()),
                 "bank": float(
                     1 - (res.latency_sum_bank[k] / base).mean())}
            for lv in levels:
                assert 1 <= lv <= R and R % lv == 0, (lv, R)
                if lv == R:
                    sums = res.latency_sum_region[k]
                else:
                    okl = res.ok_region[k].reshape(
                        res.ok_region[k].shape[:2] + (lv, R // lv)
                        + res.ok_region[k].shape[3:]).all(3)
                    _, sums = select_combos(
                        res.spec.tests[k].combos, okl, op,
                        res.spec.op_trefi(op, m), std)
                d[f"region{lv}"] = float(1 - (sums / base).mean())
            out[op.value] = d
        return out

    def evaluate_region_system(self, pop: Population,
                               levels: tuple[int, ...] | None = None,
                               temps: tuple[float, ...] | None = None,
                               n: int = 4096, seed: int = 0,
                               policies=None, engine=None) -> dict:
        """The subarray-region headline, priced on the system side:
        replay the workload pool under the all-module-safe rows of
        EVERY spatial resolution level — module envelope, per-bank,
        and per-(bank, region) at each `levels` entry — in ONE batched
        campaign.

        The timing axis rides the dispatch MASK-COMPRESSED: the dense
        [rows, banks, regions, 6] stack (JEDEC baseline + module rows
        + bank rows + one block of region rows per level, coarser
        levels broadcast into the finest layout — exact, since a
        level-l region is a contiguous group of fine regions) is
        collapsed by `compress_stack` to a [rows, U, 6] unique-row
        stack plus ONE [banks * regions] index map, and the replay
        gathers each request's row through the map in-scan.  Still one
        synthesis + one replay dispatch for the whole resolution
        sweep.

        Also reports `region_reductions` (structurally monotone per
        level) and the store's compression ratio per level."""
        from repro.core import dram_sim, perf_model
        from repro.runtime.compression import compress_stack
        if self.table is None:
            self.profile(pop)
        tbl = self.table
        assert tbl.per_region, "profile() a regions>1 controller first"
        R = tbl.regions
        if levels is None:
            levels = tuple(lv for lv in (2, 4, 8)
                           if lv <= R and R % lv == 0)
        temps = tuple(temps if temps is not None else tbl.temp_bins)
        policies = policies or (dram_sim.OPEN_FCFS,)
        m, banks = tbl.module_params.shape[0], tbl.n_banks
        assert banks == pop.n_banks, (banks, pop.n_banks)
        tables = {lv: self.region_table(lv) for lv in levels}
        nt = len(temps)
        nl = len(levels)
        s_rows = 1 + (2 + nl) * nt
        dense = np.empty((s_rows, banks, R, 6), np.float32)
        dense[0] = T.DDR3_1600.as_row()[None, None, :]
        mods = np.arange(m)
        for si, tc in enumerate(temps):
            dense[1 + si] = tbl.lookup_many(
                mods, np.full(m, tc)).max(axis=0)[None, None, :]
            for bb in range(banks):
                dense[1 + nt + si, bb] = tbl.lookup_many_banks(
                    mods, np.full(m, bb), np.full(m, tc)).max(axis=0)
        for li, lv in enumerate(levels):
            t_lv = tables[lv]
            off = 1 + (2 + li) * nt
            for si, tc in enumerate(temps):
                for bb in range(banks):
                    seg = dense[off + si, bb].reshape(lv, R // lv, 6)
                    for j in range(lv):
                        seg[j] = t_lv.lookup_many_regions(
                            mods, np.full(m, bb), np.full(m, j),
                            np.full(m, tc)).max(axis=0)[None, :]
        rows_u, region_map = compress_stack(
            dense.reshape(s_rows, banks * R, 6))

        em = perf_model.evaluate_many(rows_u, n=n, seed=seed,
                                      engine=engine, policies=policies,
                                      n_banks=banks,
                                      region_map=region_map)
        sp = perf_model.cpi_speedups(em["mean_latency_ns"])
        per_temp = {}
        for si, tc in enumerate(temps):
            d = {"module_all_gmean": perf_model.gmean_speedup(
                     sp[1, :, 0, 1 + si]),
                 "bank_all_gmean": perf_model.gmean_speedup(
                     sp[1, :, 0, 1 + nt + si])}
            for li, lv in enumerate(levels):
                d[f"region{lv}_all_gmean"] = perf_model.gmean_speedup(
                    sp[1, :, 0, 1 + (2 + li) * nt + si])
            per_temp[float(tc)] = d
        red = self.region_reductions(levels)
        ratios = {lv: tables[lv].compression_ratio() for lv in levels}
        return {"temps": temps, "levels": levels, "rows": rows_u,
                "region_map": region_map, "speedups": sp,
                "mean_latency_ns": em["mean_latency_ns"],
                "workloads": em["workloads"], "per_temp": per_temp,
                "reductions": red, "compression_ratio": ratios,
                "policies": policies, "source": "profiled-region-table"}

    # ----------------------------------------------------- dynamic closure
    def evaluate_dynamic(self, pop: Population, scenarios=None,
                         config=None, n: int = 4096, seed: int = 0,
                         policies=None, engine=None,
                         per_bank: bool = False,
                         fused: bool = False) -> dict:
        """The paper's actual mechanism, end to end: profile the
        population, stack the per-bin all-module-safe rows
        (`TimingTable.safe_stack`), and replay the workload pool with
        the controller's bin-switching logic running INSIDE the traced
        scan — per-request temperature sensing, conservative round-up,
        hysteresis, JEDEC fallback — under a set of dynamic thermal
        scenarios (`repro.core.thermal`), bracketed by the
        static-worst-case and oracle deployments.

        Unlike `evaluate_system` (one static row per pre-known
        temperature bin), nothing here is pre-reduced: the profiled
        `TimingTable` stack itself rides the dispatch and the replay
        decides per request which row applies.  Still O(1) traced
        dispatches (one synthesis, one adaptive replay, one static
        replay) regardless of how many scenarios or policies ride the
        campaign.  `per_bank=True` deploys the per-bank stack
        (`safe_stack_banks`): the in-scan selection then gathers row
        (bin, request's bank) — same dispatch count.  `fused=True`
        collapses the whole evaluation — synthesis, adaptive replay,
        worst-bin provisioning AND the static bracket — into ONE
        dispatch (`SimEngine.run_bracket`).
        """
        from repro.core import dram_sim, perf_model, thermal
        with span("aldram.evaluate_dynamic") as s:
            if self.table is None:
                self.profile(pop)
            scenarios = tuple(default_scenarios() if scenarios is None
                              else scenarios)
            policies = policies or (dram_sim.OPEN_FCFS,)
            s.count(scenarios=len(scenarios), policies=len(policies))
            rows, bins = (self.table.safe_stack_banks() if per_bank
                          else self.table.safe_stack())
            out = perf_model.evaluate_adaptive(
                rows, bins, scenarios, config=config, n=n, seed=seed,
                engine=engine, policies=policies, n_banks=pop.n_banks,
                fused=fused)
            out["source"] = "profiled-table-dynamic"
            out["policies"] = policies
            return out

    # ----------------------------------------------------------- reporting
    def average_reductions(self, temp_c: float,
                           std: T.TimingParams = T.DDR3_1600) -> dict:
        """Module-envelope Sec. 5.2 statistics (per-bank reductions
        are reported by `evaluate_bank_system`)."""
        assert self.table is not None
        bi = next((i for i, b in enumerate(self.table.temp_bins)
                   if temp_c <= b), None)
        if bi is None:
            # above the hottest profiled bin the controller falls back
            # to standard timings (TimingTable.lookup): 0% reductions
            return {k: 0.0 for k in ("trcd", "tras", "twr", "trp")}
        return param_reductions(self.table.module_params[:, bi, :], std)
