"""Closed-loop thermal model for adaptive-timing replay (paper Sec. 4).

AL-DRAM's defining feature is *online* adaptation: the memory
controller reads the module's current temperature and switches timing
registers on the fly.  This module supplies the temperature side of
that loop as a first-order RC model that runs INSIDE the replay scan
(`repro.core.dram_sim.replay_adaptive`):

  * every access deposits heat on its bank, proportional to the actual
    access energy of `repro.core.power` (a row miss pays the ACT/PRE
    pair plus the row-active window of the *currently selected* tRAS,
    so faster timings literally run cooler — the loop is closed),
  * between requests the per-bank heat decays toward a time-varying
    ambient with time constant `tau_ns`,
  * the module's sensed temperature is the ambient plus the summed
    bank overheat, and the controller re-selects its temperature bin
    from it per request (`searchsorted` over the bin edges, with
    hysteresis — see below).

Ambient scenarios are encoded as closed-form parameter rows so an
arbitrary stack of them vmaps through ONE replay dispatch: a scenario
row is

    [base, amp_sin, period_sin_ns, amp_step, t_step_ns,
     amp_burst, period_burst_ns, duty, hyst_scale]

and `ambient_at(row, t)` evaluates

    base + amp_sin * sin(2*pi*t/period_sin)          (diurnal ramp)
         + amp_step * (t >= t_step)                  (cooling failure)
         + amp_burst * ((t mod period_burst) < duty*period_burst)
                                                     (bursty load)

`hyst_scale` scales the config's hysteresis for this scenario only —
an *oracle* variant of any scenario is `oracle()` (hyst_scale = 0:
instant, thrash-free-by-assumption bin selection), which is how the
benchmarks price the cost of the real controller's hysteresis.

Hysteresis semantics (mirrors `aldram.TimingTable.lookup_many`'s
conservative rounding): switching UP to a hotter bin is immediate —
reliability must never wait — while switching DOWN to a cooler bin
requires the sensed temperature to fall `hyst_c` *below* the cooler
bin's edge, so a module hovering on a bin boundary does not thrash the
timing registers.  Above the hottest profiled bin the selection falls
back to the JEDEC row (the last row of the table stack), exactly like
the static controller.

The thermal diagnostics a campaign reports (temp_max / temp_mean /
bin_switches per grid cell) are reduced INSIDE the replay dispatch on
the engine's default device-stats path; the raw [grid, N] sensed
temperature and selected-bin traces only materialize when a
`sim_engine.SimSpec` opts in via `collect=("temps", "bins")`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.power import PowerParams, energy_terms

# scenario-row columns (see module docstring)
SCN_COLS = 9


@dataclasses.dataclass(frozen=True)
class ThermalConfig:
    """Physical constants of the RC model (one per campaign).

    tau_ns   : RC time constant of the module's heat decay toward
               ambient (DRAM package thermal time constants are
               milliseconds-to-seconds; the default keeps interesting
               dynamics within a few-thousand-request trace).
    c_heat   : degrees C deposited per unit of access energy (the
               energy units of `power.PowerParams`); 0 disables
               activity heating (pure-ambient mode, the degenerate
               constant-temperature case when the ambient is steady).
    hyst_c   : down-switch hysteresis in degrees C (see module
               docstring; scaled per scenario by `hyst_scale`).
    power    : energy decomposition used for the per-access deposit.
    """

    tau_ns: float = 2.0e5
    # equilibrium overheat ~= c_heat * energy_per_access * tau / gap:
    # ~1 C at desktop traffic (20 ns gaps), ~2-8 C for a saturating
    # multi-core stream (4-5 ns gaps) — the range the paper's Fig. 9
    # module-temperature measurements span
    c_heat: float = 2.0e-5
    hyst_c: float = 2.0
    power: PowerParams = dataclasses.field(default_factory=PowerParams)

    def as_row(self) -> np.ndarray:
        """[6] row consumed by the replay scan: (tau_ns, c_heat,
        hyst_c, e_burst, e_act_pre, p_act_standby)."""
        return np.concatenate([
            np.array([self.tau_ns, self.c_heat, self.hyst_c],
                     np.float32), energy_terms(self.power)])


@dataclasses.dataclass(frozen=True)
class ThermalScenario:
    """One ambient/cooling trajectory (a campaign axis cell)."""

    name: str
    base_c: float
    amp_sin: float = 0.0
    period_sin_ns: float = 1.0
    amp_step: float = 0.0
    t_step_ns: float = 0.0
    amp_burst: float = 0.0
    period_burst_ns: float = 1.0
    duty: float = 0.0
    hyst_scale: float = 1.0

    def as_row(self) -> np.ndarray:
        return np.array([self.base_c, self.amp_sin, self.period_sin_ns,
                         self.amp_step, self.t_step_ns, self.amp_burst,
                         self.period_burst_ns, self.duty,
                         self.hyst_scale], np.float32)

    def oracle(self) -> "ThermalScenario":
        """Zero-hysteresis variant: the controller tracks the sensed
        temperature instantly (the upper bound on adaptive gains)."""
        return dataclasses.replace(self, name=self.name + "+oracle",
                                   hyst_scale=0.0)


# ------------------------------------------------------- scenario builders
def steady(temp_c: float, name: str | None = None) -> ThermalScenario:
    """Constant ambient — the degenerate case that must reproduce the
    static replay bit-for-bit (with `c_heat = 0`)."""
    return ThermalScenario(name or f"steady{temp_c:.0f}C", base_c=temp_c)


def diurnal(lo_c: float, hi_c: float, period_ns: float = 4.0e5,
            name: str | None = None) -> ThermalScenario:
    """Sinusoidal ramp between `lo_c` and `hi_c` (day/night or
    enclosure duty-cycling, compressed to trace timescales)."""
    mid, amp = (lo_c + hi_c) / 2.0, (hi_c - lo_c) / 2.0
    return ThermalScenario(name or f"diurnal{lo_c:.0f}-{hi_c:.0f}C",
                           base_c=mid, amp_sin=amp,
                           period_sin_ns=period_ns)


def cooling_failure(base_c: float, jump_c: float,
                    at_ns: float = 2.0e4,
                    name: str | None = None) -> ThermalScenario:
    """Step: a fan/chiller dies at `at_ns` and the ambient jumps by
    `jump_c` for the rest of the trace."""
    return ThermalScenario(name or f"coolfail+{jump_c:.0f}C",
                           base_c=base_c, amp_step=jump_c, t_step_ns=at_ns)


def bursty(base_c: float, amp_c: float, period_ns: float = 1.0e5,
           duty: float = 0.5, name: str | None = None) -> ThermalScenario:
    """Square-wave ambient: hot bursts of `duty` fraction of each
    period (a neighbouring component duty-cycling)."""
    return ThermalScenario(name or f"bursty+{amp_c:.0f}C", base_c=base_c,
                           amp_burst=amp_c, period_burst_ns=period_ns,
                           duty=duty)


def stack_scenarios(scns: Sequence[ThermalScenario]) -> np.ndarray:
    """[C, SCN_COLS] scenario-row matrix for one vmapped campaign."""
    return np.stack([s.as_row() for s in scns], axis=0)


def rate_scenario(kind: str) -> ThermalScenario:
    """Arrival-RATE modulation for multi-tenant traffic
    (`dram_sim.TenantSpec`): the same closed-form scenario encoding
    and `ambient_at` evaluator, with base ~1.0 read as a
    dimensionless rate multiplier instead of a temperature.  "poisson"
    is a flat 1.0 (plain exponential gaps), "diurnal" swings the rate
    0.4x-1.6x sinusoidally, "bursty" square-waves 1.0x-2.5x."""
    if kind == "poisson":
        return steady(1.0, name="poisson-rate")
    if kind == "diurnal":
        return diurnal(0.4, 1.6, name="diurnal-rate")
    if kind == "bursty":
        return bursty(1.0, 1.5, duty=0.3, name="bursty-rate")
    raise ValueError(f"unknown rate scenario {kind!r}")


def ambient_at(scn_row, t):
    """Ambient temperature of a scenario row at time `t` (ns).  Pure
    jnp arithmetic (no control flow) so the scenario axis vmaps."""
    base, a_sin, p_sin, a_step, t_step, a_b, p_b, duty = (
        scn_row[0], scn_row[1], scn_row[2], scn_row[3], scn_row[4],
        scn_row[5], scn_row[6], scn_row[7])
    two_pi = 2.0 * math.pi
    sin_part = a_sin * jnp.sin(two_pi * t / p_sin)
    step_part = a_step * (t >= t_step).astype(jnp.float32)
    burst_part = a_b * ((t % p_b) < duty * p_b).astype(jnp.float32)
    return base + sin_part + step_part + burst_part


def heat_decay(arrival, tau):
    """Per-request RC decay factor of a request stream: exp(-dt / tau)
    over the gap to the previous request in issue order (the first one
    decays from t = 0), [..., N] -> [..., N].

    The adaptive replay cores (the `dram_sim.replay_adaptive` scan and
    the Pallas kernel) take this and `ambient_at` as precomputed
    streams, so the transcendental functions run once, vectorized, in
    the same XLA lowering for both — computed inside the kernel they
    would come from the kernel compiler's own `exp`/`sin`, which need
    not round like XLA's, and one ulp of sensed temperature can move a
    bin edge."""
    prev = jnp.concatenate([jnp.zeros_like(arrival[..., :1]),
                            arrival[..., :-1]], axis=-1)
    dt = jnp.maximum(arrival - prev, 0.0)
    return jnp.exp(-dt / tau)


def overheat_sum(heat):
    """Sum of the per-bank overheat over the leading bank axis, added
    in bank order: the scan ([B]) and the kernel ([B, lanes]) then
    round identically, which a reduction leaves to the backend."""
    total = heat[0]
    for i in range(1, heat.shape[0]):
        total = total + heat[i]
    return total


def ambient_at_host(scn: ThermalScenario, t: float) -> float:
    """Host-side reference of `ambient_at` (used by tests and by the
    static-worst-case bin estimate)."""
    r = scn.as_row().astype(np.float64)
    return float(r[0] + r[1] * np.sin(2.0 * np.pi * t / r[2])
                 + r[3] * (t >= r[4])
                 + r[5] * ((t % r[6]) < r[7] * r[6]))


@dataclasses.dataclass(frozen=True)
class ThermalSpec:
    """The thermal axis of a `sim_engine.SimSpec` campaign: which
    scenarios to replay, the bin edges the in-scan controller selects
    over, and the RC constants.  Attaching one switches the engine to
    the adaptive replay path; the timing axis is then interpreted as a
    stack of TABLES ([K, len(temp_bins)+1, 6], last row = JEDEC
    fallback) instead of single rows."""

    scenarios: tuple[ThermalScenario, ...]
    temp_bins: tuple[float, ...]
    config: ThermalConfig = dataclasses.field(default_factory=ThermalConfig)

    def __post_init__(self):
        object.__setattr__(self, "scenarios", tuple(self.scenarios))
        object.__setattr__(self, "temp_bins", tuple(self.temp_bins))
        assert self.scenarios, "empty thermal axis"
        assert list(self.temp_bins) == sorted(self.temp_bins)

    def pack(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(scenario rows [C, SCN_COLS], bin edges [S], config row)."""
        return (stack_scenarios(self.scenarios),
                np.asarray(self.temp_bins, np.float32),
                self.config.as_row())


__all__ = ["SCN_COLS", "ThermalConfig", "ThermalScenario", "ThermalSpec",
           "steady", "diurnal", "cooling_failure", "bursty",
           "stack_scenarios", "rate_scenario", "ambient_at",
           "ambient_at_host"]
