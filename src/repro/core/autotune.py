"""The AL-DRAM mechanism as a reusable library: per-unit,
per-condition-bin adaptive parameter tables with a guardband.

This is the TPU-framework transfer of the paper's idea (DESIGN.md §3):
  unit       ~ DRAM module        -> worker node / host / kernel shape-bin
  condition  ~ temperature        -> load / congestion bin
  parameter  ~ tRCD/tRAS/tWR/tRP  -> timeout / prefetch depth / block size
  guardband  ~ one sweep step     -> quantile + k*sigma margin

Used by runtime/straggler.py (adaptive collective timeouts),
data/pipeline.py (adaptive prefetch depth) and the kernel block-size
tables.  The worst-case STATIC value plays the role of the JEDEC
timing: `select` never returns something less safe than the profiled
guardbanded envelope, and unprofiled bins fall back to the static
worst case — the same conservative semantics as the paper's controller.

`ReplayTuner` turns the same table inward, onto the simulator itself:
the replay-dispatch configuration (`ReplayConfig`: backend core,
Pallas lane-block size, synthesis fusion) is the adaptive parameter,
the campaign's (kind, log2-size) bin is the condition, and the
conservative lax.scan default is the static worst case every
unprofiled bin falls back to.  `SimEngine.autotune` profiles the
candidates and records winners here; `SimEngine(backend="auto")`
consults the table at run time.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

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


# --------------------------------------------------------------------
# Replay-dispatch autotuning (SimEngine backend/tile selection)
# --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    """One replay-dispatch configuration the tuner scores: which
    replay core (`SimEngine.backend` value, "auto" excluded), the
    Pallas lane-block size (None = kernel default BLOCK_ROWS) and
    whether a `SynthSpec` trace axis synthesizes inside the dispatch."""

    backend: str = "scan"
    block_rows: int | None = None
    fuse_synth: bool = True


def replay_unit(adaptive: bool, banked: bool,
                channels: bool = False, regioned: bool = False) -> int:
    """Campaign-kind unit of the tuner table: the replay shapes
    (static/adaptive x per-module/per-bank x single/multi-channel x
    dense/region-compressed) tune independently.  Units 0-3 are the
    historical single-channel kinds (stored tables stay valid); a
    multi-channel campaign (`SimSpec.n_channels * n_ranks > 1` —
    different state footprint and gather pattern) offsets by 4; a
    region-compressed campaign (`SimSpec.region_map` — the extra
    in-scan index-map gather changes the dispatch cost profile)
    offsets by 8."""
    return ((8 if regioned else 0) + (4 if channels else 0)
            + (2 if adaptive else 0) + (1 if banked else 0))


# log2(request count) bin edges: campaigns within a bin share a tuned
# config (dispatch cost is dominated by N; the grid axes just vmap)
REPLAY_SIZE_BINS = (10.0, 12.0, 14.0, 17.0, 24.0)

# candidate 0 is ALWAYS the conservative scan default — it is the
# static worst case unprofiled bins fall back to
# (a 64-lane block does not lower on the TPU once the padded lane axis
# exceeds one block, so the kernel candidates start at 128 lanes)
_CANDIDATES = {
    "tpu": (ReplayConfig("scan"),
            ReplayConfig("pallas", 128),
            ReplayConfig("pallas", 256),
            ReplayConfig("merged"),
            ReplayConfig("merged", fuse_synth=False)),
    # interpret-mode Pallas is a pure-Python step loop — never a
    # performance candidate off-TPU
    "cpu": (ReplayConfig("scan"),
            ReplayConfig("scan", fuse_synth=False),
            ReplayConfig("merged"),
            ReplayConfig("merged", fuse_synth=False)),
}


@dataclasses.dataclass
class ReplayTuner:
    """Profiled (backend, block_rows, fuse_synth) selection per
    (campaign kind, size bin), with `AdaptiveTable` fallback
    semantics: `lookup` on an unprofiled bin answers candidate 0 (the
    scan default), exactly like the timing controller answering JEDEC
    above its hottest profiled bin.

    The table persists as JSON — `path` wins, else the
    REPRO_AUTOTUNE_PATH env var, else
    ~/.cache/repro/replay_tune_<platform>.json; path="" disables the
    disk cache.  Stored entries whose candidate list no longer matches
    (different platform/candidate set) are dropped on load."""

    platform: str = "cpu"
    path: str | None = None
    candidates: tuple[ReplayConfig, ...] = ()

    def __post_init__(self):
        if not self.candidates:
            if self.platform not in _CANDIDATES:
                raise ValueError(
                    f"ReplayTuner has no candidate list for platform "
                    f"{self.platform!r} (known: {sorted(_CANDIDATES)}); "
                    f"pass `candidates` explicitly")
            self.candidates = _CANDIDATES[self.platform]
        self.table = AdaptiveTable(condition_bins=REPLAY_SIZE_BINS,
                                   static_worst_case=0.0,
                                   higher_is_safer=False)
        self.timings: dict[tuple[int, int], list[float]] = {}
        self._load()

    # -------------------------------------------------------- persist
    def _resolve_path(self) -> str | None:
        if self.path == "":
            return None
        if self.path:
            return self.path
        env = os.environ.get("REPRO_AUTOTUNE_PATH")
        if env:
            return env
        return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                            f"replay_tune_{self.platform}.json")

    def _load(self):
        p = self._resolve_path()
        if not p or not os.path.exists(p):
            return
        try:
            with open(p) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return
        if data.get("candidates") != [dataclasses.asdict(c)
                                      for c in self.candidates]:
            return
        for key, idx in data.get("table", {}).items():
            u, b = (int(x) for x in key.split(","))
            if 0 <= int(idx) < len(self.candidates):
                self.table._table[(u, b)] = float(idx)

    def _save(self):
        p = self._resolve_path()
        if not p:
            return
        os.makedirs(os.path.dirname(p), exist_ok=True)
        data = {
            "platform": self.platform,
            "candidates": [dataclasses.asdict(c)
                           for c in self.candidates],
            "table": {f"{u},{b}": int(v) for (u, b), v
                      in self.table._table.items()},
        }
        with open(p, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)

    # --------------------------------------------------------- select
    def _condition(self, n: int) -> float:
        return math.log2(max(int(n), 1))

    def lookup(self, unit: int, n: int) -> ReplayConfig:
        """The profiled config for a campaign of `n` requests —
        candidate 0 (scan default) when the bin is unprofiled."""
        idx = int(self.table.select(unit, self._condition(n)))
        return self.candidates[idx]

    def tune(self, unit: int, n: int, measure
             ) -> tuple[ReplayConfig, list[float]]:
        """Score every candidate with `measure(config) -> seconds`
        (supplied by the engine — the tuner never imports it), record
        the winner's index in the table, persist, and return
        (winning config, per-candidate times)."""
        times = [float(measure(cfg)) for cfg in self.candidates]
        best = int(np.argmin(times))
        b = self.table._bin(self._condition(n))
        if b < len(self.table.condition_bins):
            # beyond the last bin `select` always answers candidate 0,
            # so (like AdaptiveTable.observe) there is nothing to store
            self.table._table[(unit, b)] = float(best)
            self.timings[(unit, b)] = times
            self._save()
        return self.candidates[best], times
