"""Declarative profiling sweeps: the paper's Sec. 5 characterization
campaign (115 modules x all timing combos x multiple temperatures x
read/write tests) as ONE batched kernel dispatch.

The margin kernel is elementwise over a (cells x combos) grid, so every
sweep axis is just a block structure on that grid:

  * temperature bins  -> the per-combo temperature column,
  * read/write op     -> the kernel's two outputs (one pass computes
                         both; a test keeps the one it exercises),
  * per-module safe refresh intervals -> per-cell, per-op tREFI
                         override columns folded into the cell side.

`SweepSpec` declares the campaign, `MarginEngine` compiles it into a
single padded dispatch (Pallas on TPU, jnp oracle on CPU), reduces the
margin grids to pass envelopes on the device, and returns a structured
`SweepResult` with the pass envelopes, the per-module argmin-latency
combo choice (vectorised — no Python loops) and reduction statistics.
Callers that used to issue one `combo_margins` call per (module,
temperature, op) now issue one engine call per campaign.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timing as T
from repro.core.charge import ChargeConstants, DEFAULT_CONSTANTS
from repro.core.spans import span
from repro.core.variation import Population


class Op(enum.Enum):
    """Which DRAM test a sweep exercises (paper Sec. 5.1)."""

    READ = "read"
    WRITE = "write"

    @classmethod
    def parse(cls, v: "Op | str") -> "Op":
        return v if isinstance(v, Op) else Op(str(v).lower())

    @property
    def latency_cols(self) -> tuple[int, ...]:
        """Combo columns of this test's latency sum (Fig. 3c/3d)."""
        return (0, 1, 3) if self is Op.READ else (0, 2, 3)


@dataclasses.dataclass(frozen=True)
class OpSweep:
    """One test of a campaign: an op, its combo grid, and (optionally)
    the per-module safe refresh interval the test runs at."""

    op: Op
    combos: np.ndarray                       # [n_combos, 5]
    trefi_ms: np.ndarray | float | None = None   # [modules], scalar, or None

    def __post_init__(self):
        object.__setattr__(self, "op", Op.parse(self.op))
        object.__setattr__(self, "combos",
                           np.asarray(self.combos, np.float32))

    def trefi_per_module(self, n_modules: int) -> np.ndarray | None:
        if self.trefi_ms is None:
            return None
        t = np.asarray(self.trefi_ms, np.float32)
        if t.ndim == 0:
            t = np.full((n_modules,), float(t), np.float32)
        assert t.shape == (n_modules,), (t.shape, n_modules)
        return t


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A declarative multi-axis profiling campaign.

    tests: the (op, combo grid, safe-tREFI) tuples to evaluate;
    temps:  the temperature bins — every test runs at every bin.

    All READ tests must agree on `trefi_ms` (likewise WRITE): the
    per-op refresh override is a per-cell column shared by every combo
    column of that op in the fused dispatch.
    """

    tests: tuple[OpSweep, ...]
    temps: tuple[float, ...]

    def __post_init__(self):
        object.__setattr__(self, "tests", tuple(self.tests))
        object.__setattr__(self, "temps",
                           tuple(float(t) for t in self.temps))
        assert self.tests and self.temps, "empty sweep"

    @classmethod
    def single(cls, op: Op | str, combos: np.ndarray,
               temps: tuple[float, ...] | float,
               trefi_ms: np.ndarray | float | None = None) -> "SweepSpec":
        temps = (temps,) if isinstance(temps, (int, float)) else tuple(temps)
        return cls(tests=(OpSweep(Op.parse(op), combos, trefi_ms),),
                   temps=temps)

    def op_trefi(self, op: Op, n_modules: int) -> np.ndarray | None:
        """The shared per-module tREFI override of all `op` tests."""
        picked: np.ndarray | None = None
        seen = False
        for t in self.tests:
            if t.op is not op:
                continue
            cur = t.trefi_per_module(n_modules)
            if seen and not _same_trefi(picked, cur):
                raise ValueError(
                    f"all {op.value} tests in one sweep must share trefi_ms")
            picked, seen = cur, True
        return picked


def _same_trefi(a: np.ndarray | None, b: np.ndarray | None) -> bool:
    if a is None or b is None:
        return a is b
    return np.array_equal(a, b)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Structured result of one fused campaign.

    Per test k (aligned with spec.tests):
      ok[k]:          [modules, n_temps, n_combos_k] pass envelope
                      (every cell of the module passes)
      chosen[k]:      [modules, n_temps, 5] minimum-latency passing
                      combo (min latency sum, min tRCD tie-break), with
                      the module's tREFI in column 4
      latency_sum[k]: [modules, n_temps] latency sum of the choice

    Per-bank views of the SAME dispatch (FLY-DRAM-style spatial
    variation: the margin grid is reduced over (chips, tail cells)
    only, keeping the rank-level bank axis — bank b spans bank b of
    every chip, see `variation.Population`):
      ok_bank[k]:          [modules, banks, n_temps, n_combos_k]
      chosen_bank[k]:      [modules, banks, n_temps, 5]
      latency_sum_bank[k]: [modules, banks, n_temps]

    The module envelope is the intersection of its bank envelopes
    (`ok[k] == ok_bank[k].all(1)`, exactly), so every bank's chosen
    latency sum is <= its module's — per-bank registers can only
    recover latency the module-level envelope gives away.

    Per-(bank, subarray region) views of the SAME dispatch when the
    campaign asks for `regions` > 1 (design-induced variation: the
    tail-cell axis is the row-position axis, partitioned into
    `regions` contiguous subarray regions — see `charge.row_positions`):
      ok_region[k]:          [modules, banks, regions, n_temps, n_combos_k]
      chosen_region[k]:      [modules, banks, regions, n_temps, 5]
      latency_sum_region[k]: [modules, banks, regions, n_temps]

    The spatial hierarchy is exact at every level:
    `ok_bank[k] == ok_region[k].all(2)` and
    `ok[k] == ok_region[k].all(2).all(1)` — booleans, not tolerances.

    The raw margin grids are reduced on the device and never copied
    to the host (`MarginEngine.campaign_margins` returns them where a
    caller needs them).
    """

    spec: SweepSpec
    std: T.TimingParams
    ok: tuple[np.ndarray, ...]
    chosen: tuple[np.ndarray, ...]
    latency_sum: tuple[np.ndarray, ...]
    ok_bank: tuple[np.ndarray, ...] = ()
    chosen_bank: tuple[np.ndarray, ...] = ()
    latency_sum_bank: tuple[np.ndarray, ...] = ()
    regions: int = 1
    ok_region: tuple[np.ndarray, ...] = ()
    chosen_region: tuple[np.ndarray, ...] = ()
    latency_sum_region: tuple[np.ndarray, ...] = ()

    @property
    def temps(self) -> tuple[float, ...]:
        return self.spec.temps

    def index(self, op: Op | str) -> int:
        """Index of the first test exercising `op`."""
        op = Op.parse(op)
        for k, t in enumerate(self.spec.tests):
            if t.op is op:
                return k
        raise KeyError(op)

    def reductions(self, op: Op | str) -> tuple[dict[str, float], ...]:
        """Per-temperature average reductions vs standard timings (the
        paper's Sec. 5.2 statistics), one dict per temp bin."""
        k = self.index(op)
        op = Op.parse(op)
        std = self.std
        chosen, sums = self.chosen[k], self.latency_sum[k]
        base = std.read_sum() if op is Op.READ else std.write_sum()
        out = []
        for ti in range(len(self.temps)):
            r = param_reductions(chosen[:, ti, :], std, allsafe=True)
            r["latency_sum"] = float(1 - (sums[:, ti] / base).mean())
            out.append(r)
        return tuple(out)


def param_reductions(params: np.ndarray, std: T.TimingParams,
                     allsafe: bool = False) -> dict[str, float]:
    """Mean fractional timing reductions vs `std` (the paper's Sec. 5.2
    statistic).  params: [..., >=4] rows of (trcd, tras, twr, trp[, ..]).
    With `allsafe`, adds the max-based reductions that are safe for ALL
    modules (Sec. 6 system eval).  Shared by SweepResult, Profiler and
    the controller so the statistic is defined in exactly one place."""
    cols = ("trcd", "tras", "twr", "trp")
    stds = (std.trcd, std.tras, std.twr, std.trp)
    flat = np.asarray(params).reshape(-1, params.shape[-1])
    r = {n: float(1 - (flat[:, i] / s).mean())
         for i, (n, s) in enumerate(zip(cols, stds))}
    if allsafe:
        r.update({f"{n}_allsafe": float(1 - flat[:, i].max() / s)
                  for i, (n, s) in enumerate(zip(cols, stds))})
    return r


def select_combos(combos: np.ndarray, ok: np.ndarray, op: Op | str,
                  trefi_ms: np.ndarray | None = None,
                  std: T.TimingParams = T.DDR3_1600
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Vectorised per-module combo selection (paper Sec. 5.1 step 4):
    among passing combos pick minimum latency sum, min-tRCD tie-break;
    fall back to the slowest combo when nothing passes.

    combos: [C, 5]; ok: [..., C] bool -> (chosen [..., 5], sums [...]).
    Replaces the per-module Python loop with lexsort/take_along_axis.
    """
    op = Op.parse(op)
    lat_sum = combos[:, op.latency_cols].sum(-1)
    order = np.lexsort((combos[:, 0], lat_sum))        # min sum, min tRCD
    ok_ord = np.take_along_axis(ok, np.broadcast_to(order, ok.shape), -1)
    first = ok_ord.argmax(-1)                          # first pass in order
    has = ok_ord.any(-1)
    pick = np.where(has, order[first], int(lat_sum.argmax()))
    chosen = combos[pick].astype(np.float32)           # [..., 5]
    if trefi_ms is None:
        chosen[..., 4] = std.trefi
    else:
        # trefi is per-module: broadcast over any trailing sweep axes
        t = np.asarray(trefi_ms, np.float32)
        chosen[..., 4] = t.reshape(t.shape + (1,) * (pick.ndim - 1))
    return chosen, lat_sum[pick].astype(np.float32)


class Envelopes(NamedTuple):
    """The pass envelopes of one `MarginEngine.envelopes` dispatch,
    still on the device: launched, not waited for."""

    arrays: tuple[jax.Array, ...]
    evals: int                  # margins the dispatch evaluated

    def fetch(self) -> tuple[np.ndarray, ...]:
        """Wait for the dispatch and copy the envelopes to the host."""
        with span("margin.fetch", bytes=sum(a.nbytes for a in self.arrays),
                  evals=self.evals):
            return tuple(jax.device_get(self.arrays))


@functools.partial(jax.jit, static_argnames=("cell_shape", "axes",
                                             "blocks"))
def _pass_envelopes(read_m: jax.Array, write_m: jax.Array, *,
                    cell_shape: tuple[int, ...], axes: tuple[int, ...],
                    blocks: tuple[tuple[int, int, int], ...]
                    ) -> tuple[jax.Array, ...]:
    """Per block (grid 0 read / 1 write, first, end column): every cell
    of the reduced `axes` passes, `(margin >= 0).all(axes)`, with the
    grid's rows viewed as `cell_shape`.  The grids may carry the
    kernel's padding beyond the `prod(cell_shape)` rows and the block's
    columns: the slice fuses into the test, so each float32 grid is
    read once and never copied."""
    n = int(np.prod(cell_shape))
    grids = (read_m, write_m)
    return tuple((grids[g][:n, lo:hi].reshape(cell_shape + (hi - lo,))
                  >= 0.0).all(axes)
                 for g, lo, hi in blocks)


@dataclasses.dataclass
class MarginEngine:
    """Facade that compiles a `SweepSpec` into one kernel dispatch.

    `dispatch_count` increments once per kernel launch — profiling
    campaigns are expected to cost O(1) dispatches regardless of the
    number of temperature bins, modules, or ops (the call-count spy in
    tests/test_sweep.py pins this down).
    """

    constants: ChargeConstants = DEFAULT_CONSTANTS
    std: T.TimingParams = T.DDR3_1600
    impl: str = "auto"
    dispatch_count: int = 0

    # ------------------------------------------------------------ low level
    def margins(self, cells: np.ndarray | jnp.ndarray, combos: np.ndarray,
                temps_combo: np.ndarray | None = None,
                temp_c: float | None = None,
                trefi_read: np.ndarray | None = None,
                trefi_write: np.ndarray | None = None,
                *, on_device: bool = False
                ) -> tuple[np.ndarray, np.ndarray] | tuple[jax.Array,
                                                           jax.Array]:
        """One dispatch: dense (read, write) margin grids [n, m], copied
        to the host.

        Give either `temps_combo` ([m] per-combo temperature) or a
        scalar `temp_c`.  `trefi_read`/`trefi_write`: optional [n]
        per-cell refresh-interval overrides for the two tests.

        `on_device` instead returns the grids as jax arrays left on the
        device, uncopied and padded to the kernel's blocks (the margins
        in the first n rows and m columns), for a program that reduces
        them there (`envelopes`).
        """
        from repro.kernels.charge_sim import ops as charge_ops
        combos = np.asarray(combos, np.float32)
        if temps_combo is None:
            assert temp_c is not None, "need temps_combo or temp_c"
            temps_combo = np.full((combos.shape[0],), float(temp_c),
                                  np.float32)
        self.dispatch_count += 1
        read_m, write_m = charge_ops.padded_margin_sweep(
            jnp.asarray(cells), jnp.asarray(combos),
            jnp.asarray(temps_combo, jnp.float32), self.constants,
            impl=self.impl,
            trefi_read_cells=_as_jnp(trefi_read),
            trefi_write_cells=_as_jnp(trefi_write))
        if on_device:
            return read_m, write_m
        n, m = np.shape(cells)[0], combos.shape[0]
        read_m, write_m = read_m[:n, :m], write_m[:n, :m]
        with span("margin.fetch", bytes=read_m.nbytes + write_m.nbytes,
                  evals=read_m.size + write_m.size):
            return np.asarray(read_m), np.asarray(write_m)

    def envelopes(self, cells: np.ndarray | jnp.ndarray, combos: np.ndarray,
                  cell_shape: tuple[int, ...], axes: tuple[int, ...],
                  blocks: tuple[tuple[Op, int, int], ...],
                  temps_combo: np.ndarray | None = None,
                  temp_c: float | None = None,
                  trefi_read: np.ndarray | None = None,
                  trefi_write: np.ndarray | None = None) -> Envelopes:
        """One dispatch reduced to pass envelopes on the device: per
        block (op, first, end column), `(margin >= 0).all(axes)` of the
        op's grid with its rows viewed as `cell_shape`, a bool array
        [kept cell axes..., end - first].  The grids never leave the
        device; only the envelopes cross, at `fetch()` of the result.
        Arguments otherwise as `margins`."""
        read_m, write_m = self.margins(
            cells, combos, temps_combo=temps_combo, temp_c=temp_c,
            trefi_read=trefi_read, trefi_write=trefi_write, on_device=True)
        arrays = _pass_envelopes(
            read_m, write_m, cell_shape=tuple(cell_shape), axes=tuple(axes),
            blocks=tuple((int(Op.parse(op) is Op.WRITE), lo, hi)
                         for op, lo, hi in blocks))
        return Envelopes(arrays, 2 * int(np.prod(cell_shape))
                         * np.asarray(combos).shape[0])

    # ------------------------------------------------------------ campaign
    def sweep(self, pop: Population, spec: SweepSpec,
              regions: int = 1) -> SweepResult:
        """Run a whole declarative campaign in ONE dispatch.

        Column layout of the fused grid: tests are concatenated, and
        within a test the combo grid is tiled once per temperature bin
        (temp-major), with the bin temperature in the per-combo
        temperature column.  Per-module safe refresh intervals are
        folded into the per-cell, per-op override columns.

        The device reduces each test's grid to its per-(module, bank,
        region) pass envelope; the host builds the bank and module
        envelopes and selects the combos from those booleans.

        `regions` > 1 additionally reduces the SAME margin grid per
        (module, bank, subarray region): the tail-cell axis is the
        row-position axis, split into `regions` contiguous groups
        (cell k -> region k * regions // n_cells), so no extra margin
        evaluation — still ONE dispatch — and the hierarchy is exact
        (`ok == ok_region.all(regions).all(banks)`).
        """
        return self.sweeps([(pop, spec)], regions)[0]

    def sweeps(self, campaigns: list[tuple[Population, SweepSpec]],
               regions: int = 1) -> list[SweepResult]:
        """`sweep` of each (population, spec) pair, one dispatch each.
        Each dispatch is launched before the envelopes of the one
        before it are fetched, so the device runs them back to back
        while the host selects, and the grids of at most two
        dispatches are live on the device at once.  (One at a time,
        the device idles through each selection: the paper-scale
        profile then takes a third longer on a TPU v5e.)"""
        out, pending = [], []
        for pop, spec in campaigns:
            pending.append((spec, *self._launch(pop, spec, regions)))
            if len(pending) == 2:
                out.append(self._select(regions, *pending.pop(0)))
        out.extend(self._select(regions, *p) for p in pending)
        return out

    def campaign_margins(self, pop: Population, spec: SweepSpec
                         ) -> tuple[np.ndarray, ...]:
        """The dense margins that `sweep` reduces on the device, copied
        to the host: per test k, [n_cells, n_temps, n_combos_k] from
        ONE dispatch of the same fused grid — for a caller that checks
        the margins themselves (the kernel against a reference)."""
        cols, blocks, _ = self._fused_columns(pop, spec)
        read_m, write_m = self.margins(pop.flat_cells(), **cols)
        return tuple((read_m if op is Op.READ else write_m)[:, lo:hi]
                     .reshape(-1, len(spec.temps), test.combos.shape[0])
                     for test, (op, lo, hi) in zip(spec.tests, blocks))

    def _fused_columns(self, pop: Population, spec: SweepSpec
                       ) -> tuple[dict, list, dict]:
        """The fused grid of `spec` (see `sweep`): the `margins`
        arguments of its columns, each test's (op, first, end) columns,
        and the per-module refresh intervals per op."""
        cpm = int(np.prod(pop.cells.shape[1:4]))     # cells per module
        n_temps = len(spec.temps)
        temps_arr = np.asarray(spec.temps, np.float32)
        combo_blocks, temp_cols, blocks = [], [], []
        off = 0
        for test in spec.tests:
            base = test.combos                        # [C, 5]
            combo_blocks.append(np.tile(base, (n_temps, 1)))
            temp_cols.append(np.repeat(temps_arr, base.shape[0]))
            blocks.append((test.op, off, off + n_temps * base.shape[0]))
            off = blocks[-1][2]
        trefi_mod = {op: spec.op_trefi(op, pop.n_modules) for op in Op}
        trefi_cells = {op: (None if trefi_mod[op] is None
                            else np.repeat(trefi_mod[op], cpm))
                       for op in Op}
        cols = dict(combos=np.concatenate(combo_blocks, axis=0),
                    temps_combo=np.concatenate(temp_cols, axis=0),
                    trefi_read=trefi_cells[Op.READ],
                    trefi_write=trefi_cells[Op.WRITE])
        return cols, blocks, trefi_mod

    def _launch(self, pop: Population, spec: SweepSpec, regions: int
                ) -> tuple[Envelopes, dict]:
        ch, bk, kc = pop.cells.shape[1:4]
        assert regions >= 1 and kc % regions == 0, \
            f"regions={regions} must divide the {kc} tail cells " \
            f"(contiguous row-position groups)"
        cols, blocks, trefi_mod = self._fused_columns(pop, spec)
        # per-(bank, region) envelope: reduce over chips and the cells
        # WITHIN each region's row-position group
        env = self.envelopes(
            pop.flat_cells(),
            cell_shape=(pop.n_modules, ch, bk, regions, kc // regions),
            axes=(1, 4), blocks=tuple(blocks), **cols)
        return env, trefi_mod

    def _select(self, regions: int, spec: SweepSpec, env: Envelopes,
                trefi_mod: dict) -> SweepResult:
        """The selection views from the per-(bank, region) envelopes."""
        okr = env.fetch()
        with span("margin.reduce"):
            ok, chosen, sums = [], [], []
            ok_b, chosen_b, sums_b = [], [], []
            ok_r, chosen_r, sums_r = [], [], []
            for test, okr_k in zip(spec.tests, okr):
                # [modules, banks, regions, T, C]; the bank envelope is
                # its intersection over regions and the module envelope
                # the intersection over banks — identical booleans to a
                # collapse over the whole cell hierarchy at every level
                okr_k = okr_k.reshape(okr_k.shape[:3] + (
                    len(spec.temps), test.combos.shape[0]))
                okb_k = okr_k.all(2)
                ok_k = okb_k.all(1)
                ch_k, s_k = select_combos(test.combos, ok_k, test.op,
                                          trefi_mod[test.op], self.std)
                chb_k, sb_k = select_combos(test.combos, okb_k, test.op,
                                            trefi_mod[test.op], self.std)
                ok.append(ok_k)
                chosen.append(ch_k)
                sums.append(s_k)
                ok_b.append(okb_k)
                chosen_b.append(chb_k)
                sums_b.append(sb_k)
                if regions > 1:
                    chr_k, sr_k = select_combos(test.combos, okr_k, test.op,
                                                trefi_mod[test.op], self.std)
                    ok_r.append(okr_k)
                    chosen_r.append(chr_k)
                    sums_r.append(sr_k)
        return SweepResult(spec=spec, std=self.std, ok=tuple(ok),
                           chosen=tuple(chosen), latency_sum=tuple(sums),
                           ok_bank=tuple(ok_b),
                           chosen_bank=tuple(chosen_b),
                           latency_sum_bank=tuple(sums_b),
                           regions=regions, ok_region=tuple(ok_r),
                           chosen_region=tuple(chosen_r),
                           latency_sum_region=tuple(sums_r))


def _as_jnp(x: np.ndarray | None) -> jnp.ndarray | None:
    return None if x is None else jnp.asarray(x, jnp.float32)


__all__ = ["Op", "OpSweep", "SweepSpec", "SweepResult", "MarginEngine",
           "Envelopes", "select_combos", "param_reductions"]
