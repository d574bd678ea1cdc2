"""In-scan fault injection for the adaptive control loop.

AL-DRAM's safety argument trusts two inputs: the sensed module
temperature (which picks the timing bin) and the profiled margins
(which picked the rows).  This module makes both faultable INSIDE the
replay dispatch — no out-of-band probe, no host round trip — so the
serving stack is exercised against the failure modes a real memory
controller must survive:

  * SENSOR faults — stuck-at, additive drift, bounded noise,
    quantization, first-order sensing lag, and dropout (the sensor
    repeats its last reading), all applied to the sensed temperature
    inside `dram_sim.replay_adaptive`'s scan, so mis-binning and its
    consequences (too-aggressive rows at hot temperatures) happen
    in-dispatch.
  * TRANSIENT read errors — a margin-conditioned per-request error
    probability: the further the served row sits below the JEDEC
    timing sum (and the further the TRUE temperature sits above the
    served bin's edge), the likelier a bit flip.  A DETECTED error
    re-issues the request at the JEDEC row — the retry latency plus a
    CAS re-issue is priced into the request latency and `total_ns` —
    while an UNDETECTED one silently corrupts and increments an
    on-device counter.  The per-request uniforms are threefry-derived
    (`fault_uniforms`), positional by ISSUE order, and shared across
    timing lanes (common random numbers), so every backend consumes
    the identical stream bit-for-bit.
  * WATCHDOG — per-module counters carried in the scan state: a
    cumulative detected-error budget and a consecutive
    sensor-implausibility (per-request rate-of-change bound) counter
    trip a STICKY degradation to the JEDEC fallback row.  Recovery is
    hysteretic and probe-based: every `wd_probe`-th degraded request
    is served at the adaptive row as a probe, and only
    `wd_recover_n` consecutive clean probes un-trip.  Because the
    error budget only resets on a probe-confirmed recovery, the
    detected-error count of a watchdog-on replay is EXACTLY bounded:

        detected <= wd_err_n * (trips + 1) + probes

    (each un-tripped serving period contributes at most `wd_err_n`
    detections before tripping, and every other detection happened on
    a probe) — the invariant `benchmarks.fault_bench` asserts.

`FaultSpec` rides the campaign grid as a new axis, exactly like the
`thermal.ThermalScenario` rows: `sim_engine.SimSpec(faults=...)`
replays every (trace, policy, timing/table, scenario) cell under every
fault scenario in the same ONE dispatch.  `FaultSpec.none()` (or
`faults=None`) is a STATIC branch that compiles the exact unfaulted
code path — bit-identity is pinned by `tests/test_faults.py` the same
way the `C*R==1` channel branch is pinned.

Everything here is pure elementwise jnp over an indexable fault-row
`fp` (``fp[col]`` a scalar in the scans, an [S] lane vector in
`replay_rows`, a [lanes] tile row in the Pallas kernel), so the three
replay layouts share the fault arithmetic the same way they share
`dram_sim.service_math`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------- layout
# fault-row column indices (`FaultScenario.as_row()` packs, every
# consumer indexes by these names — the row is the vmappable unit)
STUCK_C = 0        # stuck-at reading (C); active once t >= STUCK_FROM
STUCK_FROM = 1     # ns; < 0 = stuck-at disabled
DRIFT = 2          # additive sensor drift (C per ns)
NOISE = 3          # bounded additive noise amplitude (C, uniform +-)
QUANT = 4          # quantization step (C); 0 = off
LAG_TAU = 5        # first-order sensing-lag time constant (ns); 0 = off
DROP_P = 6         # per-request dropout probability (repeat last)
ERR_SCALE = 7      # error prob per unit of timing reduction beyond
ERR_FREE = 8       # ... this error-free reduction margin
ERR_BIN_C = 9      # error prob per C of true-temp excess over the bin
DET_FRAC = 10      # fraction of errors the ECC detects (rest silent)
RETRY_NS = 11      # detected-error retry surcharge on top of JEDEC tCL
WD_ERR_N = 12      # detected-error budget per serving period; 0 = off
WD_JUMP_C = 13     # implausible per-request reading jump (C); 0 = off
WD_SENSE_N = 14    # consecutive implausible readings to trip; 0 = off
WD_PROBE = 15      # probe every k-th degraded request; 0 = no probes
WD_RECOVER_N = 16  # consecutive clean probes to recover; 0 = never
SEED = 17          # per-scenario noise/dropout hash seed
F_COLS = 18

ERR_CAP = 0.95     # error-probability ceiling (a retry must terminate)
NO_READING = -1.0e9   # sensor-state sentinel: no previous reading yet
N_COUNTERS = 5     # detected, silent, trips, degraded, probes


@dataclasses.dataclass(frozen=True)
class FaultScenario:
    """One fault-injection scenario — one row of the fault axis.

    All defaults are INERT: `FaultScenario()` senses perfectly, never
    errors, never trips.  Severity is expressed by the magnitudes, so
    a (mode x severity) grid is just a tuple of rows."""

    name: str = "none"
    # sensor faults
    stuck_c: float = 0.0
    stuck_from_ns: float = -1.0
    drift_c_per_ns: float = 0.0
    noise_c: float = 0.0
    quant_c: float = 0.0
    lag_tau_ns: float = 0.0
    dropout_p: float = 0.0
    # transient read errors
    err_scale: float = 0.0
    err_free_red: float = 0.05
    err_bin_c: float = 0.0
    detect_frac: float = 1.0
    retry_ns: float = 50.0
    # watchdog
    wd_err_n: int = 0
    wd_jump_c: float = 0.0
    wd_sense_n: int = 0
    wd_probe: int = 0
    wd_recover_n: int = 0
    seed: int = 0

    def as_row(self) -> np.ndarray:
        """[F_COLS] float32 packed row (the vmappable unit)."""
        r = np.zeros((F_COLS,), np.float32)
        r[STUCK_C] = self.stuck_c
        r[STUCK_FROM] = self.stuck_from_ns
        r[DRIFT] = self.drift_c_per_ns
        r[NOISE] = self.noise_c
        r[QUANT] = self.quant_c
        r[LAG_TAU] = self.lag_tau_ns
        r[DROP_P] = self.dropout_p
        r[ERR_SCALE] = self.err_scale
        r[ERR_FREE] = self.err_free_red
        r[ERR_BIN_C] = self.err_bin_c
        r[DET_FRAC] = self.detect_frac
        r[RETRY_NS] = self.retry_ns
        r[WD_ERR_N] = self.wd_err_n
        r[WD_JUMP_C] = self.wd_jump_c
        r[WD_SENSE_N] = self.wd_sense_n
        r[WD_PROBE] = self.wd_probe
        r[WD_RECOVER_N] = self.wd_recover_n
        r[SEED] = self.seed
        return r

    @property
    def is_inert(self) -> bool:
        """True when this scenario can never perturb the replay."""
        return (self.stuck_from_ns < 0 and self.drift_c_per_ns == 0
                and self.noise_c == 0 and self.quant_c == 0
                and self.lag_tau_ns == 0 and self.dropout_p == 0
                and self.err_scale == 0 and self.err_bin_c == 0
                and self.wd_err_n == 0 and self.wd_sense_n == 0)


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """The fault AXIS of a campaign: a tuple of `FaultScenario` rows
    replayed against every (trace, policy, timing, thermal) cell of a
    `sim_engine.SimSpec` in one dispatch.  `seed` keys the threefry
    error-uniform stream (`fault_uniforms`)."""

    scenarios: tuple[FaultScenario, ...] = (FaultScenario(),)
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "scenarios", tuple(self.scenarios))
        assert self.scenarios, "FaultSpec needs at least one scenario"
        for s in self.scenarios:
            assert isinstance(s, FaultScenario), type(s)

    @classmethod
    def none(cls) -> "FaultSpec":
        """The no-fault spec: one inert row.  `SimSpec(faults=none())`
        compiles the EXACT unfaulted code path (static branch) and is
        bit-identical to `faults=None` up to the trailing F=1 axis."""
        return cls()

    def __len__(self) -> int:
        return len(self.scenarios)

    @property
    def is_none(self) -> bool:
        """True when every row is inert — the engine then takes the
        unfaulted static branch (bit-identity by construction)."""
        return all(s.is_inert for s in self.scenarios)

    def pack(self) -> np.ndarray:
        """[F, F_COLS] float32 scenario rows."""
        return np.stack([s.as_row() for s in self.scenarios])

    def names(self) -> tuple[str, ...]:
        return tuple(s.name for s in self.scenarios)


def fault_uniforms(key, n_traces: int, n: int) -> jnp.ndarray:
    """[T, N] threefry error uniforms, one stream per trace row, folded
    per row exactly like `SynthSpec` — generated INSIDE the campaign
    dispatch (call under jit), positional by ISSUE order and shared
    across timing/fault lanes (common random numbers), so the scan and
    Pallas backends consume the identical bits."""
    def one(i):
        return jax.random.uniform(jax.random.fold_in(key, i), (n,),
                                  jnp.float32)
    return jax.vmap(one)(jnp.arange(n_traces, dtype=jnp.int32))


def hash01(seed, k):
    """Deterministic per-request uniform-ish hash in [0, 1) from pure
    float arithmetic (the classic fract-sin mix) — used for the
    in-scan sensor noise and dropout draws, where a threefry fold per
    request would not replicate inside the Pallas loop body.  `seed`
    broadcasts against the integer request counter `k`."""
    x = jnp.sin(k.astype(jnp.float32) * 12.9898
                + seed * 78.233 + 0.5) * 43758.5453
    return x - jnp.floor(x)


def fault_sensor(fp, t, dt, raw, lag_prev, held_prev, k):
    """One faulted temperature reading.

    fp: indexable fault row (``fp[col]``); t: request arrival (ns);
    dt: inter-arrival gap; raw: the TRUE sensed temperature; lag_prev/
    held_prev: carried sensor state (`NO_READING` before the first
    reading); k: int32 request counter.  Returns (reading, lag_new,
    held_new) — every stage is inert at the `FaultScenario` defaults,
    so an all-default row reproduces `raw` exactly."""
    # first-order sensing lag toward the true temperature
    tau = fp[LAG_TAU]
    alpha = jnp.where(tau > 0.0,
                      1.0 - jnp.exp(-jnp.maximum(dt, 0.0)
                                    / jnp.maximum(tau, 1e-9)), 1.0)
    have_lag = lag_prev > 0.5 * NO_READING
    lagged = jnp.where(have_lag, lag_prev + alpha * (raw - lag_prev),
                       raw)
    r = jnp.where(tau > 0.0, lagged, raw)
    # additive drift + bounded noise
    r = r + fp[DRIFT] * t
    r = r + fp[NOISE] * (2.0 * hash01(fp[SEED], k) - 1.0)
    # stuck-at overrides everything once active
    r = jnp.where((fp[STUCK_FROM] >= 0.0) & (t >= fp[STUCK_FROM]),
                  fp[STUCK_C], r)
    # dropout: the sensor repeats its last reported reading
    drop = hash01(fp[SEED] + 1.0, k) < fp[DROP_P]
    have_held = held_prev > 0.5 * NO_READING
    r = jnp.where(drop & have_held, held_prev, r)
    # quantization last (the register the controller actually reads)
    q = jnp.maximum(fp[QUANT], 1e-9)
    r = jnp.where(fp[QUANT] > 0.0, jnp.round(r / q) * q, r)
    return r, lagged, r


def error_prob(fp, red, excess_c):
    """Margin-conditioned per-request error probability.

    red: fractional timing reduction of the SERVED row vs the JEDEC
    row (sum over tRCD/tRAS/tWR/tRP); excess_c: how far the TRUE
    temperature sits above the served bin's upper edge (C, 0 for the
    JEDEC fallback row — structurally error-free).  Clipped to
    `ERR_CAP` so a detected-error retry always terminates."""
    p = (fp[ERR_SCALE] * jnp.maximum(red - fp[ERR_FREE], 0.0)
         + fp[ERR_BIN_C] * excess_c)
    return jnp.clip(p, 0.0, ERR_CAP)


def error_draw(fp, u, p):
    """(errored, detected, silent) bool from one issue-order uniform."""
    err = u < p
    det = err & (u < p * fp[DET_FRAC])
    return err, det, err & ~det


def wd_state0(shape=()):
    """(wd_err, wd_bad, wd_clean, probe_cnt, tripped) int32 zeros —
    the watchdog carry of one module (or one per lane)."""
    z = jnp.zeros(shape, jnp.int32)
    return (z, z, z, z, z)


def wd_gate(fp, wd):
    """Pre-service watchdog gate for the CURRENT request.

    Returns (is_probe, use_agg): `use_agg` selects the adaptive row,
    else the JEDEC fallback; every `wd_probe`-th degraded request is a
    probe served AT the adaptive row (its outcome drives recovery)."""
    tripped, probe_cnt = wd[4], wd[3]
    probe_n = fp[WD_PROBE].astype(jnp.int32)
    is_probe = (tripped > 0) & (probe_n > 0) & (probe_cnt >= probe_n - 1)
    use_agg = (tripped == 0) | is_probe
    return is_probe, use_agg


def wd_update(fp, wd, det, implaus, is_probe):
    """Post-service watchdog transition.  Returns (wd', new_trip).

    The detected-error budget `wd_err` is CUMULATIVE per serving
    period (reset only on probe-confirmed recovery) — that is what
    makes the detected-error bound in the module docstring exact.  The
    implausibility counter is CONSECUTIVE (a plausible reading
    resets it).  The trip is sticky until `wd_recover_n` consecutive
    clean probes."""
    wd_err, wd_bad, wd_clean, probe_cnt, tripped = wd
    wd_err = wd_err + det.astype(jnp.int32)
    wd_bad = jnp.where(implaus, wd_bad + 1, 0)
    err_n = fp[WD_ERR_N].astype(jnp.int32)
    sense_n = fp[WD_SENSE_N].astype(jnp.int32)
    trip_now = (((err_n > 0) & (wd_err >= err_n))
                | ((sense_n > 0) & (wd_bad >= sense_n)))
    new_trip = (tripped == 0) & trip_now
    tripped = jnp.where(trip_now, 1, tripped)
    wd_clean = jnp.where(is_probe,
                         jnp.where(det, 0, wd_clean + 1), wd_clean)
    rec_n = fp[WD_RECOVER_N].astype(jnp.int32)
    recover = (tripped > 0) & (rec_n > 0) & (wd_clean >= rec_n)
    z = jnp.zeros_like(wd_err)
    wd_err = jnp.where(recover, z, wd_err)
    wd_bad = jnp.where(recover, z, wd_bad)
    wd_clean = jnp.where(recover, z, wd_clean)
    tripped = jnp.where(recover, z, tripped)
    probe_cnt = jnp.where(tripped > 0,
                          jnp.where(is_probe, z, probe_cnt + 1), z)
    return (wd_err, wd_bad, wd_clean, probe_cnt, tripped), new_trip


def counter_update(cnt, v, det, sil, new_trip, degraded, is_probe):
    """Accumulate the five on-device fault counters (order: detected,
    silent, trips, degraded, probes), gated on request validity."""
    vi = v.astype(jnp.int32)
    return (cnt[0] + det.astype(jnp.int32) * vi,
            cnt[1] + sil.astype(jnp.int32) * vi,
            cnt[2] + new_trip.astype(jnp.int32) * vi,
            cnt[3] + degraded.astype(jnp.int32) * vi,
            cnt[4] + is_probe.astype(jnp.int32) * vi)


__all__ = ["FaultScenario", "FaultSpec", "F_COLS", "N_COUNTERS",
           "ERR_CAP", "NO_READING", "fault_uniforms", "hash01",
           "fault_sensor", "error_prob", "error_draw", "wd_state0",
           "wd_gate", "wd_update", "counter_update"]
