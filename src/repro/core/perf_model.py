"""Real-system evaluation model (paper Sec. 6, Fig. 4).

35 workloads spanning the paper's pool (SPEC-like, STREAM, GUPS-like),
each characterised by (MPKI, row-buffer hit rate, write fraction,
memory-level parallelism).  A simple miss-overlap CPU model converts the
DRAM simulator's average access latency into IPC:

    CPI = CPI_exe + (MPKI/1000) * lat_mem * (1 - overlap)

Single-core runs replay each workload's trace alone; multi-core runs
interleave four instances (destroying row locality and adding queueing
pressure, which is why the paper sees larger multi-core gains).
AL-DRAM's speedup comes ONLY from swapping the timing parameters —
the paper-faithful evaluation set (tRCD/tRAS/tWR/tRP reduced by
27/32/33/18 %, Sec. 6) vs DDR3 standard.

The whole evaluation is batched through `repro.core.sim_engine`:
`evaluate_many` synthesizes all 35 workloads x both core modes in ONE
vmapped dispatch and replays them against arbitrarily many stacked
timing rows (and scheduling policies) in ONE more — `evaluate` is the
two-row (standard vs adaptive) instantiation, and kernel launches
never scale with the number of workloads, timing sets or policies.
With the default engine the campaign is fully device-resident
(in-dispatch FR-FCFS prepass and statistics; only the [modes,
workloads, P, S] summaries are transferred — see the sim_engine
module docstring); pass `SimEngine(stats="host", reorder="host")` for
the bit-exact reference pipeline.  `workload_speedup` keeps the old
per-trace reference path (via the `dram_sim.simulate` shim, which IS
that reference configuration) for equivalence tests.

`evaluate_adaptive` is the closed-loop variant: the timing set is no
longer a static row but a profiled per-bin table stack whose rows the
replay selects in-scan from the RC-modelled module temperature
(`repro.core.thermal`), benchmarked against the static-worst-case and
oracle deployments — still O(1) traced dispatches for the whole
(workloads x modes x policies x scenarios) campaign.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram_sim
from repro.core import thermal as TH
from repro.core import timing as T
from repro.core.sim_engine import SimEngine, SimResult, SimSpec
from repro.core.spans import span
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, TimingParams


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    mpki: float
    row_hit: float
    write_frac: float
    overlap: float = 0.50       # memory-level parallelism factor
    cpi_exe: float = 0.7
    intensive: bool = True


# The paper's pool: SPEC CPU2006 + STREAM variants + GUPS (35 workloads).
WORKLOADS: list[Workload] = [
    # memory-intensive (MPKI >= 10 per the paper's classification)
    Workload("mcf", 67.7, 0.45, 0.25),
    Workload("lbm", 31.9, 0.70, 0.40),
    Workload("milc", 25.8, 0.55, 0.25),
    Workload("libquantum", 25.4, 0.90, 0.15),
    Workload("soplex", 26.8, 0.55, 0.25),
    Workload("gems", 24.9, 0.50, 0.30),
    Workload("omnetpp", 21.6, 0.40, 0.30),
    Workload("leslie3d", 20.9, 0.65, 0.30),
    Workload("bwaves", 18.7, 0.70, 0.25),
    Workload("sphinx3", 17.1, 0.60, 0.20),
    Workload("zeusmp", 4.9, 0.60, 0.30),
    Workload("cactusADM", 5.3, 0.55, 0.35),
    Workload("xalancbmk", 23.9, 0.45, 0.25),
    Workload("astar", 10.2, 0.45, 0.30),
    Workload("wrf", 8.1, 0.65, 0.30),
    # STREAM kernels (very memory-bandwidth-intensive)
    Workload("s.copy", 52.0, 0.88, 0.50, overlap=0.45),
    Workload("s.scale", 51.0, 0.88, 0.50, overlap=0.45),
    Workload("s.add", 55.0, 0.90, 0.34, overlap=0.45),
    Workload("s.triad", 56.0, 0.90, 0.34, overlap=0.45),
    # GUPS-like random access
    Workload("gups", 48.0, 0.10, 0.50, overlap=0.50),
    # non-intensive
    Workload("perlbench", 2.0, 0.60, 0.25, intensive=False),
    Workload("bzip2", 3.6, 0.55, 0.30, intensive=False),
    Workload("gcc", 4.2, 0.55, 0.30, intensive=False),
    Workload("gobmk", 1.5, 0.50, 0.25, intensive=False),
    Workload("hmmer", 2.2, 0.75, 0.20, intensive=False),
    Workload("sjeng", 1.2, 0.45, 0.25, intensive=False),
    Workload("h264ref", 2.8, 0.70, 0.20, intensive=False),
    Workload("tonto", 1.3, 0.65, 0.25, intensive=False),
    Workload("namd", 1.0, 0.70, 0.20, intensive=False),
    Workload("dealII", 3.2, 0.65, 0.25, intensive=False),
    Workload("povray", 0.7, 0.60, 0.20, intensive=False),
    Workload("calculix", 2.6, 0.70, 0.25, intensive=False),
    Workload("gromacs", 1.8, 0.65, 0.25, intensive=False),
    Workload("sixtrack", 1.1, 0.70, 0.20, intensive=False),
    Workload("gamess", 0.8, 0.65, 0.20, intensive=False),
]

MODES = (False, True)           # single-core, multi-core


def _knobs(w: Workload, multi_core: bool) -> tuple[float, float, float]:
    """(row_hit, write_frac, inter_arrival_ns) of one workload trace.
    Multi-core: 4 instances share the channel — locality drops and
    arrival pressure quadruples."""
    row_hit = w.row_hit * (0.55 if multi_core else 1.0)
    # arrival rate ~ mpki * issue rate; multi-core stacks four cores
    inter = max(4.0, 400.0 / w.mpki) / (4.0 if multi_core else 1.0)
    return row_hit, w.write_frac, inter


def _trace_for(w: Workload, key, n: int, multi_core: bool):
    row_hit, write_frac, inter = _knobs(w, multi_core)
    return dram_sim.synth_trace(key, n, row_hit=row_hit,
                                write_frac=write_frac,
                                inter_arrival_ns=inter)


def workload_speedup(w: Workload, std: TimingParams, fast: TimingParams,
                     key, n: int = 8192, multi_core: bool = True) -> float:
    """Per-trace reference path (two `simulate` shim calls)."""
    trace = _trace_for(w, key, n, multi_core)
    lat_std = float(dram_sim.simulate(trace, std)["mean_latency_ns"])
    lat_fast = float(dram_sim.simulate(trace, fast)["mean_latency_ns"])
    cpi_std = w.cpi_exe + w.mpki / 1000.0 * lat_std * (1 - w.overlap)
    cpi_fast = w.cpi_exe + w.mpki / 1000.0 * lat_fast * (1 - w.overlap)
    return cpi_std / cpi_fast - 1.0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _synth_batch(key, n, n_banks, offsets, row_hits, write_fracs,
                 inters):
    """ONE traced dispatch: every workload trace of a campaign, vmapped
    (per-row key fold keeps each trace identical to the per-call
    `_trace_for` path)."""
    def one(off, rh, wf, ia):
        k = jax.random.fold_in(key, off)
        return dram_sim.synth_trace(k, n, n_banks=n_banks, row_hit=rh,
                                    write_frac=wf, inter_arrival_ns=ia)
    return jax.vmap(one)(offsets, row_hits, write_fracs, inters)


# counts _synth_batch launches the same way SimEngine.dispatch_count
# counts replay launches, so `evaluate` reports measured dispatches
synth_dispatch_count = 0


class _SynthScope:
    """Handle yielded by `synth_dispatch_scope`: `.count` is the number
    of synthesis launches since the scope opened (frozen at exit)."""

    def __init__(self, start: int):
        self._start = start
        self._end: int | None = None

    @property
    def count(self) -> int:
        cur = synth_dispatch_count if self._end is None else self._end
        return cur - self._start


@contextlib.contextmanager
def synth_dispatch_scope(reset: bool = False):
    """Scoped synthesis-launch accounting over the module-global
    `synth_dispatch_count` — the counterpart of reading a fresh
    `SimEngine().dispatch_count`, without the d0/s0 delta bookkeeping
    every caller otherwise repeats.  Yields a handle whose `.count` is
    the launches inside the scope; `reset=True` additionally restores
    the global to its entry value on exit (so a test can assert
    absolute counts without caring who synthesized before it)."""
    global synth_dispatch_count
    start = synth_dispatch_count
    scope = _SynthScope(start)
    try:
        yield scope
    finally:
        scope._end = synth_dispatch_count
        if reset:
            synth_dispatch_count = start


def _pool_knobs():
    """(offsets, row_hits, write_fracs, inter_arrivals) of the full 70
    trace pool — single-core block then multi-core block, each in
    WORKLOADS order; the fold offsets keep every trace bit-identical
    to the per-call `_trace_for` path."""
    offs, rhs, wfs, ias = [], [], [], []
    for multi in MODES:
        for i, w in enumerate(WORKLOADS):
            rh, wf, ia = _knobs(w, multi)
            offs.append(i + (1000 if multi else 0))
            rhs.append(rh)
            wfs.append(wf)
            ias.append(ia)
    return offs, rhs, wfs, ias


def trace_batch(n: int = 8192, seed: int = 0,
                n_banks: int = 8) -> dram_sim.Trace:
    """All 35 workloads x (single, multi) as one batched `Trace` with a
    [70, n] leading axis — rows ordered single-block then multi-block,
    each in WORKLOADS order."""
    global synth_dispatch_count
    offs, rhs, wfs, ias = _pool_knobs()
    synth_dispatch_count += 1
    with span("sim.dispatch"):
        return _synth_batch(jax.random.PRNGKey(seed), n, n_banks,
                            jnp.asarray(offs, jnp.int32),
                            jnp.asarray(rhs, jnp.float32),
                            jnp.asarray(wfs, jnp.float32),
                            jnp.asarray(ias, jnp.float32))


def synth_spec(n: int = 8192, seed: int = 0,
               n_banks: int = 8) -> dram_sim.SynthSpec:
    """The DECLARATIVE `trace_batch`: the same 70-trace pool as a
    `dram_sim.SynthSpec` (same knobs, same threefry fold offsets, so
    the synthesized streams are bit-identical).  Hand it to a
    `SimSpec` as the trace axis and the engine fuses the synthesis
    INTO the replay dispatch — the whole Fig. 4 campaign becomes ONE
    launch and `synth_dispatch_count` never moves."""
    offs, rhs, wfs, ias = _pool_knobs()
    return dram_sim.SynthSpec(n=n, offsets=tuple(offs),
                              row_hits=tuple(rhs),
                              write_fracs=tuple(wfs),
                              inter_arrivals=tuple(ias),
                              seed=seed, n_banks=n_banks)


def tenant_spec(n: int = 8192, n_streams: int = 8, seed: int = 0,
                n_banks: int = 8,
                kinds=("poisson", "bursty", "diurnal")
                ) -> dram_sim.TenantSpec:
    """MULTI-TENANT traffic over the SAME workload pool: the 70
    (workload x core-mode) pool entries become tenants, each with the
    locality/write/inter-arrival knobs of `_pool_knobs` plus an
    arrival-rate process cycled from `kinds`
    (`thermal.rate_scenario`), and every stream is a Dirichlet tenant
    mix (alpha 0.15 — a few dominant tenants per stream, the rest
    background) drawn deterministically from `seed`.  Hand the spec to
    a `SimSpec` as the trace axis: the per-request tenant draw, knob
    gather, and rate-modulated arrivals all fuse INTO the replay
    dispatch exactly like `synth_spec` — `synth_dispatch_count` never
    moves."""
    offs, rhs, wfs, ias = _pool_knobs()
    k = len(rhs)
    r = np.random.default_rng(seed)
    mixes = r.dirichlet(np.full(k, 0.15), size=n_streams)
    return dram_sim.TenantSpec(
        n=n, mixes=tuple(tuple(m) for m in mixes),
        row_hits=tuple(rhs), write_fracs=tuple(wfs),
        inter_arrivals=tuple(ias),
        arrivals=tuple(kinds[i % len(kinds)] for i in range(k)),
        seed=seed, n_banks=n_banks)


def evaluate_many(timings, n: int = 8192, seed: int = 0,
                  engine: SimEngine | None = None,
                  policies: tuple[dram_sim.Policy, ...] = (dram_sim.OPEN_FCFS,),
                  n_banks: int = 8, region_map=None) -> dict:
    """Replay the full workload pool under arbitrarily many stacked
    timing rows (and policies): ONE synthesis dispatch + ONE batched
    replay dispatch, however many scenario cells the campaign spans.
    `timings` may be [S, 6] rows or a per-bank [S, banks, 6] stack
    (FLY-DRAM spatial tables — see `aldram.evaluate_bank_system`), or
    — with `region_map` (the `SimSpec.region_map` contract) — the
    mask-compressed [S, U, 6] unique-row stack whose requests gather
    their (bank, subarray-region) row through the map in-scan
    (`aldram.evaluate_region_system`).

    Returns mean latencies as [modes(2), workloads(35), P, S] plus the
    raw `SimResult` (trace axis = mode-major flattening).
    """
    engine = engine or SimEngine()
    res = engine.run(SimSpec(traces=trace_batch(n, seed, n_banks),
                             timings=timings, policies=policies,
                             n_banks=n_banks, region_map=region_map))
    nw = len(WORKLOADS)
    grid = res.mean_latency_ns.reshape((len(MODES), nw) +
                                       res.mean_latency_ns.shape[1:])
    return {"result": res, "mean_latency_ns": grid,
            "workloads": [w.name for w in WORKLOADS]}


def evaluate_adaptive(table, bins, scenarios, config=None, n: int = 4096,
                      seed: int = 0, engine: SimEngine | None = None,
                      policies: tuple[dram_sim.Policy, ...] =
                      (dram_sim.OPEN_FCFS,), n_banks: int = 8,
                      fused: bool = False) -> dict:
    """Closed-loop Fig. 4: replay the workload pool with IN-SCAN
    temperature-bin selection under every thermal scenario, and price
    it against the two bracketing deployments:

      * static-worst-case — ONE register set provisioned for the
        scenario's peak sensed temperature (what a non-adaptive
        AL-DRAM deployment must ship),
      * oracle — the zero-hysteresis adaptive controller (the upper
        bound; the gap to it is the cost of thrash protection).

    `table`: [bins+1, 6] stacked rows, JEDEC fallback LAST (e.g.
    `aldram.TimingTable.safe_stack`), or the per-bank stack
    [bins+1, banks, 6] (`safe_stack_banks` — the in-scan selection
    then gathers row (bin, request's bank)); `bins`: ascending bin
    edges; `scenarios`: `thermal.ThermalScenario`s; `config`:
    `thermal.ThermalConfig`.

    O(1) traced dispatches regardless of scenario/policy count: ONE
    trace synthesis + ONE adaptive replay (scenarios and their oracle
    variants share the scenario axis) + ONE static replay (the JEDEC
    baseline and every scenario's worst-case row share the timing
    axis).  `fused=True` collapses all three into ONE dispatch
    (`SimEngine.run_bracket` with a declarative `synth_spec` trace
    axis: synthesis, adaptive replay, on-device worst-bin round-up
    AND the static bracket in a single launch) — numerically the same
    evaluation to device-stats tolerance.  Speedups are CPI-model
    speedups vs the JEDEC baseline, shaped [modes, workloads, P, C].
    """
    engine = engine or SimEngine()
    config = config or TH.ThermalConfig()
    scenarios = tuple(scenarios)
    table = np.asarray(table, np.float32)
    assert table.ndim in (2, 3), \
        "evaluate_adaptive takes ONE table stack ([S+1, 6] or the " \
        "per-bank [S+1, banks, 6])"
    bins = tuple(float(b) for b in bins)
    nc = len(scenarios)

    # adaptive + oracle variants ride one scenario axis -> one dispatch
    # (K axis explicit, so a per-bank stack is unambiguous)
    tspec = TH.ThermalSpec(
        scenarios=scenarios + tuple(s.oracle() for s in scenarios),
        temp_bins=bins, config=config)

    # static-worst-case bracket: provision each scenario for its peak
    # sensed temperature (max over traces AND policies — one register
    # set per deployment); index len(bins) is the JEDEC fallback row.
    # The peak is measured on the ADAPTIVE trajectory, which
    # UNDERSTATES a static deployment's own self-heating (slower rows
    # hold the row active longer and deposit more heat), so
    # provisioning adds the controller's hysteresis margin as a
    # guardband before rounding up — conservative in the safe
    # direction, and it can only raise `worst_bin` above every bin the
    # adaptive replay selected, so the adaptive >= static-worst
    # bracket stays structural
    if fused:
        spec = SimSpec(traces=synth_spec(n, seed, n_banks),
                       timings=table[None], policies=policies,
                       thermal=tspec, n_banks=n_banks)
        br = engine.run_bracket(spec, base_row=DDR3_1600.as_row(),
                                n_real=nc)
        a = br["adaptive"]
        res_a = SimResult(spec=spec, mean_latency_ns=a["mean"],
                          p99_latency_ns=a["p99"], total_ns=a["total"],
                          valid=br["valid"], temp_max=a["temp_max"],
                          temp_mean=a["temp_mean"],
                          bin_switches=a["bin_switches"],
                          bank_heat=a["bank_heat"])
        peak, worst_bin = br["temp_peak"], br["worst_bin"]
        lat_a = a["mean"][:, :, 0, :]                # [T, P, 2C]
        lat_s = br["static"]["mean"]                 # [T, P, 1+C]
    else:
        traces = trace_batch(n, seed, n_banks)
        res_a = engine.run(SimSpec(traces=traces, timings=table[None],
                                   policies=policies, thermal=tspec,
                                   n_banks=n_banks))
        lat_a = res_a.mean_latency_ns[:, :, 0, :]    # [T, P, 2C]
        peak = res_a.temp_max[:, :, 0, :nc].max(axis=(0, 1))    # [C]
        worst_bin = np.searchsorted(np.asarray(bins),
                                    peak + config.hyst_c, side="left")
        base = np.broadcast_to(DDR3_1600.as_row(), table.shape[1:])
        rows = np.concatenate([base[None], table[worst_bin]], axis=0)
        res_s = engine.run(SimSpec(traces=traces, timings=rows,
                                   policies=policies, n_banks=n_banks))
        lat_s = res_s.mean_latency_ns                # [T, P, 1+C]

    # one CPI pass: [base | static-worst | adaptive | oracle] columns
    lat = np.concatenate([lat_s, lat_a], axis=-1)
    nw = len(WORKLOADS)
    grid = lat.reshape((len(MODES), nw) + lat.shape[1:])
    sp = cpi_speedups(grid)                          # [2, W, P, 1+3C]
    out = {
        "scenarios": [s.name for s in scenarios],
        "bins": bins, "table": table, "worst_bin": worst_bin,
        "temp_peak": peak,
        "static_worst": sp[..., 1:1 + nc],
        "adaptive": sp[..., 1 + nc:1 + 2 * nc],
        "oracle": sp[..., 1 + 2 * nc:],
        "mean_latency_ns": grid, "result": res_a,
        "workloads": [w.name for w in WORKLOADS],
    }
    # multi-core gmean summaries for EVERY policy of the campaign;
    # `per_scenario` is the first policy's view (the headline the
    # benchmarks report), `per_policy` carries them all
    switches = res_a.bin_switches[:, :, 0, :nc]
    per_policy = []
    for pi in range(len(policies)):
        per = {}
        for ci, s in enumerate(scenarios):
            per[s.name] = {
                "adaptive_gmean":
                    gmean_speedup(out["adaptive"][1, :, pi, ci]),
                "static_worst_gmean":
                    gmean_speedup(out["static_worst"][1, :, pi, ci]),
                "oracle_gmean":
                    gmean_speedup(out["oracle"][1, :, pi, ci]),
                "worst_bin": (float(bins[worst_bin[ci]])
                              if worst_bin[ci] < len(bins) else None),
                "temp_peak": float(peak[ci]),
                "mean_bin_switches": float(switches[:, pi, ci].mean()),
            }
        per_policy.append(per)
    out["per_scenario"] = per_policy[0]
    out["per_policy"] = per_policy
    return out


def cpi_speedups(mean_lat_ns: np.ndarray) -> np.ndarray:
    """CPI speedup of every timing row vs row 0 (the standard-timing
    baseline): [modes, workloads, P, S] latencies -> same-shape
    speedups (column 0 is identically 0)."""
    mpki = np.array([w.mpki for w in WORKLOADS])[None, :, None, None]
    ov = np.array([w.overlap for w in WORKLOADS])[None, :, None, None]
    ce = np.array([w.cpi_exe for w in WORKLOADS])[None, :, None, None]
    cpi = ce + mpki / 1000.0 * mean_lat_ns.astype(np.float64) * (1 - ov)
    return cpi[..., :1] / cpi - 1.0


def gmean_speedup(vals) -> float:
    return float(np.exp(np.mean(np.log1p(list(vals)))) - 1.0)


def evaluate(std: TimingParams = DDR3_1600,
             fast: TimingParams = ALDRAM_55C_EVAL,
             n: int = 8192, seed: int = 0,
             engine: SimEngine | None = None) -> dict:
    """Reproduces Fig. 4's aggregate numbers — all 35 workloads, both
    core modes and both timing sets in 2 traced dispatches total."""
    engine = engine or SimEngine()
    d0, s0 = engine.dispatch_count, synth_dispatch_count
    em = evaluate_many(T.stack_timing([std, fast]), n=n, seed=seed,
                       engine=engine)
    sp = cpi_speedups(em["mean_latency_ns"])         # [2, 35, 1, 2]
    out: dict = {"single": {}, "multi": {}}
    for mi, multi in enumerate(MODES):
        tag = "multi" if multi else "single"
        for i, w in enumerate(WORKLOADS):
            out[tag][w.name] = float(sp[mi, i, 0, 1])

    mi_ = [out["multi"][w.name] for w in WORKLOADS if w.intensive]
    mn = [out["multi"][w.name] for w in WORKLOADS if not w.intensive]
    out["summary"] = {
        "multi_intensive_gmean": gmean_speedup(mi_),
        "multi_nonintensive_gmean": gmean_speedup(mn),
        "multi_all_gmean": gmean_speedup(mi_ + mn),
        "single_intensive_gmean": gmean_speedup(
            [out["single"][w.name] for w in WORKLOADS if w.intensive]),
        "best_multi": max(out["multi"].items(), key=lambda kv: kv[1]),
    }
    synth = synth_dispatch_count - s0
    out["dispatches"] = {"synth": synth,
                         "replay": engine.dispatch_count - d0,
                         "total": synth + engine.dispatch_count - d0}
    return out
