"""The AL-DRAM mechanism as a reusable library: per-unit,
per-condition-bin adaptive parameter tables with a guardband.

This is the TPU-framework transfer of the paper's idea (DESIGN.md §3):
  unit       ~ DRAM module        -> worker node / host / kernel shape-bin
  condition  ~ temperature        -> load / congestion bin
  parameter  ~ tRCD/tRAS/tWR/tRP  -> timeout / prefetch depth / block size
  guardband  ~ one sweep step     -> quantile + k*sigma margin

Used by runtime/straggler.py (adaptive collective timeouts),
runtime/fault.py (heartbeat miss budgets) and data/pipeline.py
(adaptive prefetch depth); `from_sweep` builds one from a profile.
The worst-case STATIC value plays the role of the JEDEC timing:
`select` never returns something less safe than the profiled
guardbanded envelope, and unprofiled bins fall back to the static
worst case — the same conservative semantics as the paper's controller.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class AdaptiveTable:
    """Profile -> table -> guardbanded runtime selection."""

    condition_bins: tuple[float, ...]
    static_worst_case: float
    quantile: float = 0.999
    k_sigma: float = 3.0
    higher_is_safer: bool = True     # timeouts: larger = safer

    def __post_init__(self):
        self._table: dict[tuple[int, int], float] = {}
        self._samples: dict[tuple[int, int], list[float]] = {}

    # ------------------------------------------------------------ profile
    def _bin(self, condition: float) -> int:
        """Smallest profiled bin >= condition; one past the end when the
        condition exceeds every bin (so `select` falls back to the
        static worst case, like the controller above its hottest bin)."""
        for i, b in enumerate(self.condition_bins):
            if condition <= b:
                return i
        return len(self.condition_bins)

    def observe(self, unit: int, condition: float, value: float):
        b = self._bin(condition)
        if b >= len(self.condition_bins):
            # beyond the profiled range `select` always answers with the
            # static worst case; fitting such samples would only build
            # unreachable table entries
            return
        self._samples.setdefault((unit, b), []).append(float(value))

    def fit(self, min_samples: int = 16):
        """Build the guardbanded table from observations.

        `min_samples` is clamped to >= 2: a quantile + k*sigma
        guardband needs a spread, and 0/1 observations have none
        (std degenerates to 0, the "guardband" would be the single
        sample itself).  Bins left unfitted stay out of the table, so
        `select` answers with the static worst case — profiling with
        degenerate data is a no-op, never an unsafe threshold."""
        min_samples = max(int(min_samples), 2)
        for key, vals in self._samples.items():
            if len(vals) < min_samples:
                continue
            v = np.asarray(vals)
            q = float(np.quantile(v, self.quantile))
            guard = q + self.k_sigma * float(v.std())
            if self.higher_is_safer:
                self._table[key] = min(guard, self.static_worst_case)
            else:
                self._table[key] = max(guard, self.static_worst_case)
        return self

    @classmethod
    def from_sweep(cls, result, op, static_worst_case: float
                   ) -> "AdaptiveTable":
        """Build a table directly from a `MarginEngine` campaign: the
        chosen per-module latency sums of a `SweepResult` become the
        per-unit, per-condition-bin entries (condition = temperature
        bin), with the standard-timing latency sum as the static worst
        case.  The profiling guardband is already inside the sweep's
        combo selection, so no extra quantile/sigma margin is applied.
        """
        t = cls(condition_bins=tuple(result.temps),
                static_worst_case=float(static_worst_case),
                higher_is_safer=True)
        sums = result.latency_sum[result.index(op)]    # [units, bins]
        for u in range(sums.shape[0]):
            for b in range(sums.shape[1]):
                t._table[(u, b)] = min(float(sums[u, b]),
                                       t.static_worst_case)
        return t

    # ------------------------------------------------------------- select
    def select(self, unit: int, condition: float) -> float:
        """Conservative: exact bin if profiled, else the next-safer
        profiled bin, else the static worst case (JEDEC fallback)."""
        b = self._bin(condition)
        for bb in range(b, len(self.condition_bins)):
            if (unit, bb) in self._table:
                return self._table[(unit, bb)]
        return self.static_worst_case

    def savings(self, unit: int, condition: float) -> float:
        """Fractional margin recovered vs the static worst case."""
        v = self.select(unit, condition)
        wc = self.static_worst_case
        return (wc - v) / wc if self.higher_is_safer else (v - wc) / wc
