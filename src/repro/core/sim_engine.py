"""Batched trace-replay campaigns: the real-system evaluation (paper
Sec. 6, Fig. 4) as ONE vmapped/padded `lax.scan` dispatch.

Mirrors the `MarginEngine` design (`repro.core.sweep`) on the system
side: a `SimSpec` declares the campaign axes —

  * traces    — any number of request streams, padded to one length
                with a validity mask,
  * policies  — memory-controller scheduling policies
                (`dram_sim.Policy`: open/closed page, FR-FCFS-lite
                reordering window),
  * timings   — stacked timing-parameter rows
                (`TimingParams.as_row` / `timing.stack_timing`), or a
                PER-BANK [S, banks, 6] stack (FLY-DRAM spatial
                tables: each request replays under its bank's row,
                gathered in-scan — same dispatch count),

and `SimEngine` compiles the whole (T x P x S) grid into a single
jitted replay dispatch, returning a structured `SimResult` of mean/p99
latency, runtime and (opt-in) the raw latency grid.
`dram_sim.simulate` is the [1 x 1 x 1] shim over the reference path,
so scalar and batched replays agree bit-for-bit.

The FAST PATH (engine defaults) keeps the whole campaign
device-resident:

  * reorder="device" — the FR-FCFS-lite issue order is computed by
    `dram_sim.frfcfs_perm` as a prepass INSIDE the dispatch (the jitted
    JAX formulation is parity-tested request-for-request against the
    retained Python loop, so this changes where the permutation is
    computed, never what it is),
  * stats="device" — masked mean/p99 and the thermal diagnostics
    (temp_max / temp_mean / bin_switches) reduce on-device and only
    [grid]-shaped summaries cross the host boundary,
  * `SimSpec.collect` — the O(grid * N) raw per-request outputs
    ("latencies", "temps", "bins") materialize only when asked for.

`stats="host"` + `reorder="host"` is the bit-exact reference path
(exactly the original pack -> replay -> host `_masked_stats` pipeline);
device stats match it within 1e-5 relative (the raw latency grid is
bit-identical either way — only the reduction order differs).
`backend="pallas"` swaps the vmapped `lax.scan` replay for the
`repro.kernels.replay` Pallas kernels compiled for the TPU (static, and
single-channel adaptive); it raises off the TPU, where
`backend="pallas_interpret"` runs the same kernel bodies on the host.

Attaching a `thermal.ThermalSpec` opens the fourth campaign axis —
thermal scenarios — and switches the replay to the closed-loop
`dram_sim.replay_adaptive`: the timing axis is then a stack of TABLES
([K, bins+1, 6], JEDEC fallback row last) whose rows the in-scan
controller selects per request from the RC-modelled temperature, and
the whole (T x P x K x C) grid is STILL one dispatch.

`dispatch_count` increments once per replay launch — evaluation
campaigns are expected to cost O(1) dispatches regardless of the
number of workloads, timing sets or policies (the call-count spy in
tests/test_dram_sim.py pins this down).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import timing as T
from repro.core.autotune import ReplayConfig, ReplayTuner, replay_unit
from repro.core.dram_sim import (OPEN_FCFS, SYNTH_SPECS, BankGroupError,
                                 KVSpec, Policy, SynthSpec, TenantSpec,
                                 Trace, check_prefix_valid, frfcfs_perm,
                                 frfcfs_reorder, replay_adaptive,
                                 replay_rows, replay_rows_frfcfs,
                                 split_chan)
from repro.core.spans import span
from repro.core.thermal import ThermalSpec

COLLECTABLE = ("latencies", "temps", "bins")


def _as_rows(timings) -> np.ndarray:
    """Normalize the timing axis to a [S, 6] stacked-row matrix, or
    a PER-BANK [S, banks, 6] stack (FLY-DRAM spatial tables — each
    request replays under its bank's row)."""
    if isinstance(timings, T.TimingParams):
        return timings.as_row()[None, :]
    if isinstance(timings, (list, tuple)):
        return T.stack_timing(timings)
    arr = np.asarray(timings, np.float32)
    if arr.ndim == 1:
        arr = arr[None, :]
    assert arr.ndim in (2, 3) and arr.shape[-1] == 6, arr.shape
    return arr


def _as_tables(timings, n_bins: int) -> np.ndarray:
    """Normalize the adaptive timing axis to [K, n_bins + 1, 6] table
    stacks (per-bin rows + the JEDEC fallback row last) or the
    per-bank [K, n_bins + 1, banks, 6] form.  A SINGLE per-bank stack
    must be passed 4-dim (`stack[None]`) — a 3-dim input is always
    read as K per-module stacks."""
    arr = np.asarray(timings, np.float32)
    if arr.ndim == 2:
        arr = arr[None, :, :]
    assert arr.ndim in (3, 4) and arr.shape[-1] == 6, arr.shape
    assert arr.shape[1] == n_bins + 1, \
        f"table stack needs {n_bins}+1 rows (JEDEC last), got {arr.shape}"
    return arr


@dataclasses.dataclass(frozen=True)
class SimSpec:
    """A declarative trace-replay campaign: every trace runs under every
    policy and every timing row.  `traces` is a tuple of `Trace`s (of
    any lengths — shorter ones are padded), or a single `Trace` whose
    fields carry a leading batch axis.

    `collect` opts into the raw per-request outputs ("latencies",
    "temps", "bins") on the device-stats fast path — without it only
    [grid]-shaped summaries leave the device, so large campaigns never
    materialize O(grid * N) arrays host-side.  The host-stats reference
    path always materializes them (it needs the raw grid anyway)."""

    # tuple of `Trace`s, or a `dram_sim.SynthSpec` / `TenantSpec` /
    # `KVSpec` — the DECLARATIVE trace batch whose synthesis the engine
    # fuses INTO the replay dispatch (the whole campaign is one launch)
    traces: tuple[Trace, ...] | SynthSpec | TenantSpec | KVSpec
    # [S, 6] rows | per-bank [S, banks, 6] | adaptive [K, S+1, 6] |
    # adaptive per-bank [K, S+1, banks, 6]
    timings: np.ndarray
    policies: tuple[Policy, ...] = (OPEN_FCFS,)
    n_banks: int = 8
    mlp_window: int = 8
    # attaching a thermal axis switches to the closed-loop adaptive
    # replay; `timings` is then a stack of per-bin TABLES, not rows
    thermal: ThermalSpec | None = None
    collect: tuple[str, ...] = ()
    # multi-channel module geometry: C*R independent bank groups, with
    # the per-policy `Policy.interleave` mapping requests to channels
    # in-scan; `t_burst_ns` is the per-channel data-bus occupancy of
    # one burst (the contention price).  1/1 degenerates bit-exactly
    # to the single-channel replay.
    n_channels: int = 1
    n_ranks: int = 1
    t_burst_ns: float = 5.0
    # optional fault AXIS (`faults.FaultSpec`): every campaign cell
    # additionally replays under every fault scenario, all in the SAME
    # dispatch — results then gain a trailing F axis plus the
    # [..., F, faults.N_COUNTERS] counter grid.  None (or an all-inert
    # spec) compiles the EXACT unfaulted code path (static branch,
    # like the C*R == 1 channel degeneracy).
    faults: "faults.FaultSpec | None" = None
    # optional subarray-region spatial hierarchy (mask-compressed
    # finer-than-bank timing maps): an int32 index map
    # [banks*regions] (shared) or [S, banks*regions] / [K,
    # banks*regions] (per-lane / per-stack) into the timing axis's
    # UNIQUE rows — `timings` is then the compressed [S, U, 6]
    # (static) / [K, S+1, U, 6] (adaptive) unique-row store and each
    # request gathers its (bank, region-of-row) slot's row through
    # the map in-scan.  None compiles the EXACT dense per-bank (or
    # per-module) path — a static branch, like `faults=None`.
    region_map: np.ndarray | None = None
    # rank-level and bank-group timings (`timing.RankTimings`): None
    # compiles the exact path without them; set, every rank carries its
    # last ACT / column, a four-ACT window and per-group last ACT /
    # column (`dram_sim.service_math(rank=...)`), with bank group =
    # rank-level bank // (n_banks // bank_groups).  Static FCFS replay
    # only (`backend` scan or pallas); other paths raise
    # `dram_sim.BankGroupError`.
    bank_groups: int = 1
    rank_timings: "T.RankTimings | None" = None

    def __post_init__(self):
        tr = self.traces
        if isinstance(tr, Trace):
            shape = np.shape(tr.arrival)            # [T, N] or [N]
            with span("sim.prep", streams=shape[0] if shape[1:] else 1,
                      requests=math.prod(shape)):
                tr = (tuple(Trace(*(np.asarray(f)[i] for f in tr))
                            for i in range(np.asarray(tr.arrival).shape[0]))
                      if np.asarray(tr.arrival).ndim == 2 else (tr,))
        if not isinstance(tr, SYNTH_SPECS):
            tr = tuple(tr)
        object.__setattr__(self, "traces", tr)
        assert self.n_channels >= 1 and self.n_ranks >= 1, \
            (self.n_channels, self.n_ranks)
        if self.n_banks % self.bank_groups or (
                self.bank_groups > 1 and self.rank_timings is None):
            raise ValueError(
                f"{self.bank_groups} bank groups need rank_timings and "
                f"a bank count they divide ({self.n_banks})")
        object.__setattr__(
            self, "timings",
            _as_rows(self.timings) if self.thermal is None else
            _as_tables(self.timings, len(self.thermal.temp_bins)))
        object.__setattr__(self, "policies", tuple(self.policies))
        object.__setattr__(self, "collect", tuple(self.collect))
        assert self.traces and self.policies, "empty campaign"
        assert all(c in COLLECTABLE for c in self.collect), self.collect
        # per-bank timing axes must match the simulated bank count;
        # with a region map the [.., U, 6] axis is the UNIQUE-row
        # store instead, checked against the map's index range
        tdim = self.timings.ndim - (0 if self.thermal is None else 1)
        if self.region_map is not None:
            rm = np.asarray(self.region_map, np.int32)
            object.__setattr__(self, "region_map", rm)
            assert tdim == 3, \
                "region_map needs a [.., U, 6] unique-row timing axis"
            assert rm.ndim in (1, 2) \
                and rm.shape[-1] % self.n_banks == 0, \
                (rm.shape, self.n_banks)
            if rm.ndim == 2:
                assert rm.shape[0] == self.timings.shape[0], \
                    (rm.shape, self.timings.shape)
            assert int(rm.max()) < self.timings.shape[-2], \
                (int(rm.max()), self.timings.shape)
        elif tdim == 3:
            assert self.timings.shape[-2] == self.n_banks, \
                (self.timings.shape, self.n_banks)
        if self.faults is not None:
            assert isinstance(self.faults, faults.FaultSpec), \
                type(self.faults)
            if self.fault_on and self.thermal is None:
                # the static faulted replay prices retries against ONE
                # [6] JEDEC row (the last timing row, mirroring the
                # adaptive tables' JEDEC-last convention) — the
                # per-bank/per-region static stacks have no such
                # single row (route faulted spatial campaigns through
                # the adaptive path, whose tables carry JEDEC rows)
                assert self.timings.ndim == 2, \
                    "fault axis + spatial (per-bank/per-region) " \
                    "static timings unsupported"

    @property
    def fault_on(self) -> bool:
        """True when the fault axis can actually perturb the replay —
        an all-inert `FaultSpec` short-circuits to the unfaulted
        compiled path (bit-identity by construction)."""
        return self.faults is not None and not self.faults.is_none

    @classmethod
    def single(cls, trace: Trace, tp: T.TimingParams,
               policy: Policy = OPEN_FCFS, **kw) -> "SimSpec":
        return cls(traces=(trace,), timings=tp, policies=(policy,), **kw)

    @property
    def shape(self) -> tuple[int, ...]:
        base = (len(self.traces), len(self.policies), self.timings.shape[0])
        return (base if self.thermal is None else
                base + (len(self.thermal.scenarios),))

    @property
    def synth(self) -> "SynthSpec | TenantSpec | None":
        """The declarative synthesis spec, when the trace axis is one."""
        return (self.traces if isinstance(self.traces, SYNTH_SPECS)
                else None)

    @property
    def grouped(self) -> bool:
        """True when the replay carries the rank and bank-group state."""
        return self.rank_timings is not None

    @property
    def chan(self) -> tuple:
        """The STATIC channel geometry (n_channels, n_ranks,
        t_burst_ns) threaded through the jitted replay bodies, plus
        (bank_groups, trrd_s, trrd_l, tfaw, tccd_s, tccd_l) when the
        spec is `grouped` (`dram_sim.split_chan`)."""
        base = (self.n_channels, self.n_ranks, float(self.t_burst_ns))
        if not self.grouped:
            return base
        return base + ((self.bank_groups,)
                       + self.rank_timings.as_tuple(),)

    @property
    def ileave_codes(self) -> np.ndarray:
        """Per-policy interleave codes [P] (a traced campaign column,
        like `closed_flags`)."""
        return np.array([p.ileave_code for p in self.policies],
                        np.int32)

    def trace_tuple(self) -> tuple[Trace, ...]:
        """The trace axis as materialized `Trace`s (a `SynthSpec` axis
        synthesizes once, cached on the spec — see
        `SynthSpec.materialize`)."""
        return (self.traces.materialize() if self.synth is not None
                else self.traces)

    # ------------------------------------------------------------ packing
    def _pack_streams(self):
        """Pad the traces into dense [T, N] request arrays in FCFS
        order plus the [T, N] validity mask."""
        tr = self.trace_tuple()
        lens = [int(np.asarray(t.arrival).shape[0]) for t in tr]
        n = max(lens)
        arrival = np.zeros((len(tr), n), np.float32)
        bank = np.zeros((len(tr), n), np.int32)
        row = np.zeros((len(tr), n), np.int32)
        is_write = np.zeros((len(tr), n), bool)
        valid = np.zeros((len(tr), n), bool)
        for i, t in enumerate(tr):
            valid[i, :lens[i]] = True
            arrival[i, :lens[i]] = np.asarray(t.arrival)
            bank[i, :lens[i]] = np.asarray(t.bank)
            row[i, :lens[i]] = np.asarray(t.row)
            is_write[i, :lens[i]] = np.asarray(t.is_write)
        check_prefix_valid(valid, "SimSpec.pack")
        return arrival, bank, row, is_write, valid

    def policy_knobs(self):
        """Per-policy (window, slack, cap) columns of the in-dispatch
        FR-FCFS prepass.  Closed-page auto-precharges after every
        access, so the row-hit promotion FR-FCFS-lite optimizes for
        cannot exist — window 0 keeps those policies (and plain FCFS)
        on the identity permutation."""
        windows = np.array([0 if p.closed or p.reorder_window <= 1
                            else p.reorder_window for p in self.policies],
                           np.int32)
        slacks = np.array([p.reorder_slack_ns for p in self.policies],
                          np.float32)
        caps = np.array([4 * max(int(w), 1) for w in windows], np.int32)
        return windows, slacks, caps

    def pack_device(self):
        """Fast-path packing: FCFS-order [T, N] request arrays + the
        validity mask + the per-policy reorder knobs — the FR-FCFS
        issue orders materialize on device, inside the dispatch."""
        return self._pack_streams() + self.policy_knobs()

    def pack(self):
        """Reference packing: dense [T, P, N] request arrays (the
        policy axis materializes FR-FCFS-lite issue orders HOST-side
        via the retained Python loop, cached across calls) plus the
        [T, N] validity mask and the per-policy closed-page flags."""
        tr, pol = self.trace_tuple(), self.policies
        if self.grouped and any(p.reorder_window > 1 and not p.closed
                                for p in pol):
            raise BankGroupError(
                "frfcfs_reorder: the host FR-FCFS reorder keys open rows "
                "on rank-level banks; replay rank-constrained specs FCFS")
        lens = [int(np.asarray(t.arrival).shape[0]) for t in tr]
        n = max(lens)
        tp_ = (len(tr), len(pol))
        arrival = np.zeros(tp_ + (n,), np.float32)
        bank = np.zeros(tp_ + (n,), np.int32)
        row = np.zeros(tp_ + (n,), np.int32)
        is_write = np.zeros(tp_ + (n,), bool)
        valid = np.zeros((len(tr), n), bool)
        for i, t in enumerate(tr):
            valid[i, :lens[i]] = True
            reordered: dict = {}
            for j, p in enumerate(pol):
                # closed-page keeps FCFS order (see policy_knobs); the
                # O(N*window) Python reorder is cached per
                # (window, slack) so policies sharing a reorder pay it
                # once per trace (and `frfcfs_reorder` caches across
                # pack() calls on top)
                key = (None if p.closed or p.reorder_window <= 1 else
                       (p.reorder_window, p.reorder_slack_ns))
                if key not in reordered:
                    reordered[key] = (t if key is None else
                                      frfcfs_reorder(t, *key))
                t2 = reordered[key]
                arrival[i, j, :lens[i]] = np.asarray(t2.arrival)
                bank[i, j, :lens[i]] = np.asarray(t2.bank)
                row[i, j, :lens[i]] = np.asarray(t2.row)
                is_write[i, j, :lens[i]] = np.asarray(t2.is_write)
        check_prefix_valid(valid, "SimSpec.pack")
        closed = np.array([p.closed for p in pol])
        return arrival, bank, row, is_write, valid, closed

    @property
    def closed_flags(self) -> np.ndarray:
        return np.array([p.closed for p in self.policies])


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Result grid of one campaign; all arrays lead with [T, P, S] =
    (traces, policies, timing rows) — or [T, P, K, C] = (traces,
    policies, table stacks, thermal scenarios) for adaptive campaigns.
    `latencies` is padded to the longest trace — mask with `valid`
    before reducing yourself.  The `temp_*`/`bin_*` diagnostics are
    populated only on the adaptive path.  On the device-stats fast
    path the raw `latencies`/`temps`/`bins` grids are None unless the
    spec's `collect` asked for them.

    A `SimSpec.faults` axis appends a trailing F (fault scenario) grid
    axis to every array (before the request/bank axis on the raw
    grids) and populates `fault_counters`: the on-device
    [..., F, faults.N_COUNTERS] int32 accumulators, unpacked by the
    `detected_errors` / `silent_errors` / `wd_trips` /
    `degraded_requests` / `wd_probes` properties."""

    spec: SimSpec
    mean_latency_ns: np.ndarray     # [T, P, S] | [T, P, K, C] (+F)
    p99_latency_ns: np.ndarray      # same leading shape
    total_ns: np.ndarray            # same leading shape
    valid: np.ndarray               # [T, N]
    latencies: np.ndarray | None = None     # [..., N] (0 at padding)
    temps: np.ndarray | None = None         # [T, P, K, C, N] sensed C
    bins: np.ndarray | None = None          # [T, P, K, C, N] (-1 pad)
    temp_max: np.ndarray | None = None      # [T, P, K, C]
    temp_mean: np.ndarray | None = None     # [T, P, K, C]
    bin_switches: np.ndarray | None = None  # [T, P, K, C]
    bank_heat: np.ndarray | None = None     # [T, P, K, C, B] end C
    fault_counters: np.ndarray | None = None  # [..., F, N_COUNTERS]
    # static replays: summed delay of each lane's column commands past
    # what their banks alone allowed (tRRD, tFAW, tCCD); zeros without
    # `SimSpec.rank_timings`
    constraint_stall_ns: np.ndarray | None = None   # [T, P, S]

    def _counter(self, i: int):
        return (None if self.fault_counters is None
                else self.fault_counters[..., i])

    @property
    def detected_errors(self):      # [..., F] int32
        return self._counter(0)

    @property
    def silent_errors(self):        # [..., F] int32
        return self._counter(1)

    @property
    def wd_trips(self):             # [..., F] int32
        return self._counter(2)

    @property
    def degraded_requests(self):    # [..., F] int32
        return self._counter(3)

    @property
    def wd_probes(self):            # [..., F] int32
        return self._counter(4)


def _eff_window(arrival: np.ndarray, valid: np.ndarray, window: int,
                slack_ns: float) -> int:
    """EXACT shrink of the FR-FCFS pending-buffer size: with
    non-decreasing arrivals, a buffer slot j is promotable only while
    its request arrives within `slack` of the head's arrival — slot j
    holds a request at stream distance >= j from the head, so j >=
    cnt_i = |{k >= i : arr[k] <= arr[i] + slack}| can NEVER be
    eligible at head i.  A buffer of max_i cnt_i therefore yields the
    IDENTICAL permutation (later slots only refill earlier, which
    changes nothing the scheduler can observe).  All arithmetic is
    float32, matching `frfcfs_perm`'s horizon compare bit-for-bit.

    Bench traces cut the 64-deep buffer to ~36-39 slots — nearly
    halving the dominant O(N * window) per-step cost of reordered
    campaigns.  Returns `window` untouched (no shrink) if any valid
    prefix has decreasing arrivals (synthetic traces never do)."""
    eff = 1
    slack = np.float32(slack_ns)
    for t in range(arrival.shape[0]):
        c = int(valid[t].sum())
        if c == 0:
            continue
        arr = arrival[t, :c].astype(np.float32)
        if np.any(np.diff(arr) < 0):
            return window
        horizon = (arr + slack).astype(np.float32)
        cnt = np.searchsorted(arr, horizon, side="right") \
            - np.arange(c, dtype=np.int64)
        eff = max(eff, int(cnt.max()))
    return max(1, min(window, eff, arrival.shape[1]))


def _reorder_prepass(arrival, bank, row, is_write, valid, slacks, caps,
                     reorder_plan: tuple, n_banks: int,
                     n_policies: int):
    """In-dispatch FR-FCFS prepass: [T, N] FCFS streams -> [T, P, N]
    per-policy issue orders, all on device.  `reorder_plan` (static)
    groups the policy columns with a window >= 2 by window size as
    `(window, eff, idx)` entries — each group pays an O(N * eff)
    permutation scan sized to its EXACT slack-horizon buffer bound
    (`_eff_window`), not the nominal window; window-0 policies
    broadcast the FCFS stream untouched."""
    t, n = arrival.shape

    def bcast(x):
        return jnp.broadcast_to(x[:, None, :], (t, n_policies, n))

    if not reorder_plan:
        return (bcast(arrival), bcast(bank), bcast(row),
                bcast(is_write))

    perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, None],
                            (t, n_policies, n))
    for window, eff, idx in reorder_plan:
        sel = np.asarray(idx, np.int32)

        def one(a, b, r, v, s_, c_, w=window, e=eff):
            return frfcfs_perm(a, b, r, v, w, s_, c_, min(e, n),
                               n_banks)

        f_p = jax.vmap(one, in_axes=(None, None, None, None, 0, 0))
        f_tp = jax.vmap(f_p, in_axes=(0, 0, 0, 0, None, None))
        perm = perm.at[:, sel, :].set(
            f_tp(arrival, bank, row, valid, slacks[sel], caps[sel]))

    def gather(x):
        return jnp.take_along_axis(bcast(x), perm, axis=2)

    return (gather(arrival), gather(bank), gather(row),
            gather(is_write))


def _merged_replay(arrival, bank, row, is_write, valid, timings, closed,
                   slacks, caps, reorder_plan: tuple, n_banks: int,
                   mlp_window: int, all_valid: bool,
                   chan: tuple = (1, 1, 5.0), ileave=None, fault=None,
                   region_map=None):
    """The `backend="merged"` replay core: [T, N] FCFS streams ->
    (lat [T, P, S, N], total [T, P, S]) with the FR-FCFS schedule
    FUSED into the replay scan itself (`dram_sim.replay_rows_frfcfs`)
    — one pass per (trace, policy-group) instead of permute + gather +
    replay, with the pending buffer shrunk to each group's exact
    `_eff_window` bound.  Non-reordering policies replay via the plain
    lane-major scan.  Latencies land in ISSUE order, exactly like the
    prepass pipeline's permuted streams — the statistics reduce the
    same multiset in the same order, so the two fast paths are
    bit-identical cell for cell.

    `fault` (optional) = (fault_rows [S, faults.F_COLS], jedec_row
    [6], uniforms [T, N]) per-lane fault scenarios: the uniforms are
    consumed positionally by ISSUE step in both cores, so the fused
    and prepass pipelines stay bit-identical; the return gains the
    [T, P, S, faults.N_COUNTERS] int32 counter grid."""
    t, n = arrival.shape
    p = closed.shape[0]
    s = timings.shape[0]
    n_ch, n_rk, t_burst, groups = split_chan(chan)
    if groups is not None:
        raise BankGroupError(
            "backend='merged': replay_rows_frfcfs carries no rank or "
            "bank-group state; replay with backend='scan' or 'pallas'")
    il = (jnp.zeros((p,), jnp.int32) if ileave is None
          else jnp.asarray(ileave, jnp.int32))
    lat = jnp.zeros((t, p, s, n))
    total = jnp.zeros((t, p, s))
    cnt = (None if fault is None
           else jnp.zeros((t, p, s, faults.N_COUNTERS), jnp.int32))
    u_tn = None if fault is None else fault[2]
    grouped: set[int] = set()
    for _, _, idx in reorder_plan:
        grouped.update(idx)
    ident = tuple(j for j in range(p) if j not in grouped)

    if ident:
        sel = np.asarray(ident, np.int32)

        def plain(a, b, r, w, v, c, i_, uu=None):
            fl = None if fault is None else (fault[0], fault[1], uu)
            return replay_rows(a, b, r, w, v, timings, c, n_banks,
                               mlp_window, n_channels=n_ch,
                               n_ranks=n_rk, ileave=i_, t_burst=t_burst,
                               fault=fl, region_map=region_map)

        f_p = jax.vmap(plain, in_axes=(None,) * 5 + (0, 0, None))
        f_tp = jax.vmap(f_p, in_axes=(0, 0, 0, 0, 0, None, None, 0))
        out = f_tp(arrival, bank, row, is_write, valid, closed[sel],
                   il[sel], u_tn)
        lat = lat.at[:, sel].set(out[0])
        total = total.at[:, sel].set(out[1])
        if fault is not None:       # [T, Psel, NC, S] -> [T,Psel,S,NC]
            cnt = cnt.at[:, sel].set(out[2].transpose(0, 1, 3, 2))

    for window, eff, idx in reorder_plan:
        sel = np.asarray(idx, np.int32)

        def fused(a, b, r, w, v, c, s_, cp, i_, uu=None, _w=window,
                  _e=eff):
            fl = None if fault is None else (fault[0], fault[1], uu)
            return replay_rows_frfcfs(a, b, r, w, v, timings, c, _w,
                                      s_, cp, min(_e, n), n_banks,
                                      mlp_window, all_valid=all_valid,
                                      n_channels=n_ch, n_ranks=n_rk,
                                      ileave=i_, t_burst=t_burst,
                                      fault=fl, region_map=region_map)

        f_p = jax.vmap(fused, in_axes=(None,) * 5 + (0, 0, 0, 0, None))
        f_tp = jax.vmap(f_p, in_axes=(0, 0, 0, 0, 0, None, None, None,
                                      None, 0))
        out = f_tp(arrival, bank, row, is_write, valid, closed[sel],
                   slacks[sel], caps[sel], il[sel], u_tn)
        lat = lat.at[:, sel].set(out[0])
        total = total.at[:, sel].set(out[1])
        if fault is not None:
            cnt = cnt.at[:, sel].set(out[2].transpose(0, 1, 3, 2))
    if fault is None:
        return lat, total
    return lat, total, cnt


def _p99_k(valid: np.ndarray) -> int:
    """Static top-k depth covering every trace's p99 order statistics
    (the float32 arithmetic mirrors `_device_stats` exactly, so the
    in-dispatch descending indices are guaranteed < k)."""
    c = valid.sum(-1).astype(np.float32)
    lo = np.floor((np.float32(0.99) * (c - 1.0)).astype(np.float32))
    return int((c - lo).max())


def _valid_count(valid, ndim: int):
    """(valid mask broadcast against an ndim-rank [T, ..., N] grid,
    per-trace valid count as float32 [T, 1, ...]).  The count passes an
    optimization barrier: a fused synthetic campaign's all-True mask is
    a compile-time constant, and XLA would then rewrite `sum / cnt` as
    `sum * (1 / cnt)` — one ulp away from the materialized twin's
    division."""
    mid = (1,) * (ndim - 2)
    v = valid.reshape((valid.shape[0],) + mid + (valid.shape[1],))
    cnt = valid.sum(-1).astype(jnp.float32).reshape(
        (valid.shape[0],) + mid)
    return v, jax.lax.optimization_barrier(cnt)


def _device_stats(lat, valid, k: int):
    """In-dispatch masked mean / interpolated p99 over the last axis.
    Same interpolation arithmetic as the host `_masked_stats`
    reference; only the summation order differs (XLA reduction vs
    numpy pairwise), which keeps the two within ~1e-7 relative — the
    documented contract is 1e-5.  The p99 order statistics come from a
    `top_k` of static depth `k` (`_p99_k`) instead of a full sort —
    the selected VALUES are identical (order statistics don't depend
    on how they're found) and XLA's top-k is ~20x cheaper than its
    sort on a [grid, N] latency tensor."""
    v, cnt = _valid_count(valid, lat.ndim)
    mean = jnp.where(v, lat, 0.0).sum(-1) / cnt
    # descending top-k; -inf padding sorts last, so entry j is the
    # (j+1)-th largest VALID latency and ascending position i maps to
    # descending position cnt-1-i
    top = jax.lax.top_k(jnp.where(v, lat, -jnp.inf), k)[0]
    q = (jnp.float32(0.99) * (cnt - 1.0)).astype(jnp.float32)
    lo = jnp.floor(q)
    hi = jnp.ceil(q)
    frac = q - lo
    di_lo = (cnt - 1.0 - lo).astype(jnp.int32)
    di_hi = (cnt - 1.0 - hi).astype(jnp.int32)
    vlo = jnp.take_along_axis(
        top, jnp.broadcast_to(di_lo[..., None], top.shape[:-1] + (1,)),
        -1)[..., 0]
    vhi = jnp.take_along_axis(
        top, jnp.broadcast_to(di_hi[..., None], top.shape[:-1] + (1,)),
        -1)[..., 0]
    return mean, vlo + (vhi - vlo) * frac


def _device_thermal_diag(temps, bin_sel, valid):
    """In-dispatch thermal diagnostics over each trace's valid prefix:
    (temp_max [grid], temp_mean [grid], bin_switches [grid]).  max and
    switch counts are exact; the mean matches the host loop within
    float-reduction noise."""
    v, cnt = _valid_count(valid, temps.ndim)
    tmax = jnp.where(v, temps, -jnp.inf).max(-1)
    tmean = jnp.where(v, temps, 0.0).sum(-1) / cnt
    pair = v[..., 1:] & v[..., :-1]          # padding is a suffix
    switches = ((bin_sel[..., 1:] != bin_sel[..., :-1]) & pair).sum(-1)
    return tmax, tmean, switches


def _synth_inputs(synth) -> tuple:
    """The device arrays a synthesis spec reads (a `KVSpec`'s Zipf
    table), passed to the dispatch as arguments, not constants."""
    return synth.device_inputs() if isinstance(synth, KVSpec) else ()


def _synth_streams(synth, inputs=()):
    """In-dispatch synthesis prologue: a `SynthSpec` (static) becomes
    the [T, n] FCFS streams + an all-True valid mask, traced INSIDE
    the replay dispatch (threefry is deterministic, so the streams are
    bit-identical to `SynthSpec.materialize`)."""
    tb = synth.synth(*inputs)
    valid = jnp.ones(tb.arrival.shape, bool)
    return tb.arrival, tb.bank, tb.row, tb.is_write, valid


def _static_body(n_banks, mlp_window, reorder_plan, backend, want,
                 p99_k, bs, arrival, bank, row, is_write, valid,
                 timings, closed, slacks, caps, all_valid=False,
                 chan=(1, 1, 5.0), ileave=None, fault=None,
                 region_map=None):
    """Shared static-timing replay body (traced under a jit wrapper):
    replay every (trace, policy, timing row) cell and reduce.

    Fast path: arrival/bank/row/is_write are [T, N] FCFS streams; the
    FR-FCFS prepass (`reorder_plan` non-empty) materializes the
    [T, P, N] per-policy issue orders on device, or — with
    backend="merged" — the scheduler fuses into the replay scan and no
    [T, P, N] streams ever materialize.  Reference path: the arrays
    arrive [T, P, N], already host-reordered, with an empty plan.
    valid: [T, N] (shared across policies — reordering permutes only
    the valid prefix); timings: [S, 6] or per-bank [S, B, 6];
    closed/slacks/caps: [P].  `want` (static) selects the outputs:
    "stats" computes masked mean/p99 in-dispatch, "lat" returns the
    raw [T, P, S, N] latency grid; total runtime [T, P, S] is always
    returned (an exact max reduction, so its in-dispatch order cannot
    perturb bits).  `backend` (static) picks the replay core: "scan"
    is the lane-stacked `dram_sim.replay_rows` lax.scan, "merged" the
    scheduler-fused `dram_sim.replay_rows_frfcfs` scan,
    "pallas"/"pallas_interpret" the `repro.kernels.replay` kernel
    (lane-block size `bs`, None = kernel default).

    `fault` (optional) = (fault_rows [S, faults.F_COLS], jedec_row
    [6], threefry key): per-LANE fault scenarios — the engine expands
    the (timing x fault) product onto the lane axis — whose error
    uniforms are synthesized IN-dispatch (`faults.fault_uniforms`, so
    every backend consumes identical bits); `out["cnt"]` then carries
    the [T, P, S, faults.N_COUNTERS] int32 counter grid.

    `region_map` (optional int32, `dram_sim.replay_rows`'s contract)
    switches `timings` to the mask-compressed [S, U, 6] unique-row
    stacks — a [G] map shared across lanes or an [S, G] per-lane map
    (G = banks * regions); every backend gathers each request's
    (bank, region) row through the map in-scan.

    A `chan` with bank groups (`SimSpec.grouped`) replays FCFS through
    the scan or the kernel, both carrying the rank state, and
    `out["stall"]` carries the [T, P, S] summed constraint stall; the
    FR-FCFS prepass, faults and regions raise `BankGroupError`.
    """
    n_ch, n_rk, t_burst, groups = split_chan(chan)
    if groups is not None and (reorder_plan or fault is not None
                               or region_map is not None):
        raise BankGroupError(
            "the FR-FCFS prepass (frfcfs_perm), the faulted and the "
            "region replays carry no rank or bank-group state")
    il = (jnp.zeros((closed.shape[0],), jnp.int32) if ileave is None
          else jnp.asarray(ileave, jnp.int32))
    cnt = stall = None
    if fault is not None:
        f_rows, j_row, fkey = fault
        u = faults.fault_uniforms(fkey, valid.shape[0], valid.shape[1])
        fault = (f_rows, j_row, u)
    if backend == "merged" and arrival.ndim == 2:
        res = _merged_replay(
            arrival, bank, row, is_write, valid, timings, closed,
            slacks, caps, reorder_plan, n_banks, mlp_window, all_valid,
            chan=chan, ileave=il, fault=fault, region_map=region_map)
        lat, total = res[:2]
        if fault is not None:
            cnt = res[2]
    else:
        if arrival.ndim == 2:
            a3, b3, r3, w3 = _reorder_prepass(
                arrival, bank, row, is_write, valid, slacks, caps,
                reorder_plan, n_banks, closed.shape[0])
        else:
            a3, b3, r3, w3 = arrival, bank, row, is_write

        if backend in ("scan", "merged"):
            def one(a, b, r, w, v, c, i_, uu=None):
                fl = None if fault is None else (f_rows, j_row, uu)
                return replay_rows(a, b, r, w, v, timings, c, n_banks,
                                   mlp_window, n_channels=n_ch,
                                   n_ranks=n_rk, ileave=i_,
                                   t_burst=t_burst, fault=fl,
                                   region_map=region_map, groups=groups)

            f_p = jax.vmap(one, in_axes=(0, 0, 0, 0, None, 0, 0, None))
            f_tp = jax.vmap(f_p, in_axes=(0, 0, 0, 0, 0, None, None, 0))
            res = f_tp(a3, b3, r3, w3, valid, closed, il,
                       None if fault is None else u)
            lat, total = res[:2]
            if fault is not None:   # [T, P, NC, S] -> [T, P, S, NC]
                cnt = res[2].transpose(0, 1, 3, 2)
        else:
            from repro.kernels.replay import ops as replay_ops
            res = replay_ops.replay_grid(
                a3, b3, r3, w3, valid, timings, closed, n_banks,
                mlp_window, impl=backend, bs=bs, chan=chan, ileave=il,
                fault=fault, region_map=region_map)
            lat, total = res[:2]
            if fault is not None:
                cnt = res[2]
        if groups is not None:
            stall = res[2]

    out = {"total": total}
    if stall is not None:
        out["stall"] = stall
    if "stats" in want:
        out["mean"], out["p99"] = _device_stats(lat, valid, p99_k)
    if "lat" in want:
        out["lat"] = lat
    if cnt is not None:
        out["cnt"] = cnt
    return out


def _adaptive_body(n_banks, mlp_window, reorder_plan, backend, want,
                   p99_k, bs, arrival, bank, row, is_write, valid,
                   tables, bins, scns, tcfg, closed, slacks, caps,
                   chan=(1, 1, 5.0), ileave=None, fault=None,
                   region_map=None):
    """Shared closed-loop replay body: every (trace, policy, table
    stack, thermal scenario) cell.

    Stream layout and the FR-FCFS prepass follow `_static_body`;
    tables: [K, S+1, 6] (JEDEC fallback row last) or per-bank
    [K, S+1, B, 6]; bins: [S]; scns: [C, thermal.SCN_COLS]; tcfg: [6]
    `ThermalConfig.as_row`.  `want` (static) selects outputs: "stats"
    adds in-dispatch mean/p99 and the thermal diagnostics
    (temp_max/temp_mean/bin_switches); "lat"/"temps"/"bins" return the
    raw [T, P, K, C, N] grids.  The [T, P, K, C] total runtime and
    [T, P, K, C, B] end-of-trace bank heat are always returned.

    `backend` "pallas"/"pallas_interpret" runs the adaptive Pallas
    kernel (`repro.kernels.replay`), whose OWN accumulator tiles
    produce the thermal diagnostics on-device — the raw O(grid * N)
    temperature/bin traces only materialize when "temps"/"bins" are
    asked for.  "scan"/"merged" run the vmapped
    `dram_sim.replay_adaptive` scan (the scheduler-fused merged core
    is static-timing only, so "merged" degrades to the scan + prepass
    here).

    `fault` (optional) = (fault_rows [F, faults.F_COLS], threefry
    key): the fault axis rides INNERMOST (a trailing F grid axis on
    every output, before N/banks) with the error uniforms synthesized
    in-dispatch; `out["cnt"]` then carries the
    [T, P, K, C, F, faults.N_COUNTERS] int32 counter grid.

    `region_map` (optional int32, `dram_sim.replay_adaptive`'s
    contract) switches `tables` to the mask-compressed [K, S+1, U, 6]
    unique-column stacks — a [G] map shared by every stack or a
    [K, G] per-stack map riding the table axis.
    """
    rm_ax = (0 if region_map is not None and region_map.ndim == 2
             else None)
    n_ch, n_rk, t_burst, groups = split_chan(chan)
    if groups is not None:
        raise BankGroupError(
            f"backend={backend!r}: the adaptive replay (replay_adaptive, "
            f"adaptive_blocks) carries no rank or bank-group state")
    il = (jnp.zeros((closed.shape[0],), jnp.int32) if ileave is None
          else jnp.asarray(ileave, jnp.int32))
    if fault is not None:
        f_rows, fkey = fault
        u = faults.fault_uniforms(fkey, valid.shape[0], valid.shape[1])
        fault = (f_rows, u)
    if arrival.ndim == 2:
        a3, b3, r3, w3 = _reorder_prepass(
            arrival, bank, row, is_write, valid, slacks, caps,
            reorder_plan, n_banks, closed.shape[0])
    else:
        a3, b3, r3, w3 = arrival, bank, row, is_write

    if n_ch * n_rk > 1 and backend in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"backend={backend!r}: the adaptive replay kernel is "
            f"single-channel; replay this {n_ch}-channel, "
            f"{n_rk}-rank adaptive campaign with backend='scan'")
    diag = None
    cnt = None
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels.replay import ops as replay_ops
        emit_raw = ("temps" in want) or ("bins" in want)
        res = replay_ops.replay_grid_adaptive(
            a3, b3, r3, w3, valid, tables, bins, scns, tcfg,
            closed, n_banks, mlp_window, impl=backend, bs=bs,
            emit_raw=emit_raw, fault=fault, region_map=region_map)
        lat, total, temps, bin_sel, bank_heat, diag = res[:6]
        if fault is not None:
            cnt = res[6]
    elif fault is not None:
        def one_f(a, b, r, w, v, tbl, scn, c, i_, fr, uu, rm):
            return replay_adaptive(a, b, r, w, v, tbl, bins, scn,
                                   tcfg, c, n_banks, mlp_window,
                                   n_channels=n_ch, n_ranks=n_rk,
                                   ileave=i_, t_burst=t_burst,
                                   fault=(fr, uu), region_map=rm)

        f_f = jax.vmap(one_f, in_axes=(None,) * 9 + (0, None, None))
        f_c = jax.vmap(f_f, in_axes=(None,) * 6 + (0,) + (None,) * 5)
        f_kc = jax.vmap(f_c, in_axes=(None,) * 5 + (0,) + (None,) * 5
                        + (rm_ax,))
        f_pkc = jax.vmap(f_kc,
                         in_axes=(0, 0, 0, 0, None, None, None, 0, 0,
                                  None, None, None))
        f_tpkc = jax.vmap(f_pkc,
                          in_axes=(0, 0, 0, 0, 0, None, None, None,
                                   None, None, 0, None))
        lat, total, temps, bin_sel, bank_heat, cnt = f_tpkc(
            a3, b3, r3, w3, valid, tables, scns, closed, il, f_rows, u,
            region_map)
        cnt = cnt.astype(jnp.int32)
    else:
        def one(a, b, r, w, v, tbl, scn, c, i_, rm):
            return replay_adaptive(a, b, r, w, v, tbl, bins, scn,
                                   tcfg, c, n_banks, mlp_window,
                                   n_channels=n_ch, n_ranks=n_rk,
                                   ileave=i_, t_burst=t_burst,
                                   region_map=rm)

        f_c = jax.vmap(one,
                       in_axes=(None,) * 5 + (None, 0, None, None,
                                              None))
        f_kc = jax.vmap(f_c,
                        in_axes=(None,) * 5 + (0, None, None, None,
                                               rm_ax))
        f_pkc = jax.vmap(f_kc,
                         in_axes=(0, 0, 0, 0, None, None, None, 0, 0,
                                  None))
        f_tpkc = jax.vmap(f_pkc,
                          in_axes=(0, 0, 0, 0, 0, None, None, None,
                                   None, None))
        lat, total, temps, bin_sel, bank_heat = f_tpkc(
            a3, b3, r3, w3, valid, tables, scns, closed, il,
            region_map)

    out = {"total": total, "bank_heat": bank_heat}
    if "stats" in want:
        out["mean"], out["p99"] = _device_stats(lat, valid, p99_k)
        if diag is not None:
            out["temp_max"], out["temp_mean"], out["bin_switches"] = diag
        else:
            (out["temp_max"], out["temp_mean"],
             out["bin_switches"]) = _device_thermal_diag(temps, bin_sel,
                                                         valid)
    if "lat" in want:
        out["lat"] = lat
    if "temps" in want:
        out["temps"] = temps
    if "bins" in want:
        out["bins"] = bin_sel
    if cnt is not None:
        out["cnt"] = cnt
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
def _replay_grid(synth, n_banks, mlp_window, reorder_plan, backend,
                 want, p99_k, bs, chan, arrival, bank, row, is_write,
                 valid, timings, closed, slacks, caps, ileave,
                 region_map=None, fault=None, synth_inputs=()):
    """ONE dispatch: (optional in-dispatch trace synthesis +) static
    replay grid — see `_static_body`.  `synth` (static) is None for
    materialized streams, or the campaign's `dram_sim.SynthSpec` /
    `TenantSpec`: the stream/valid arguments are then ignored
    placeholders and the FCFS streams are synthesized INSIDE this same
    dispatch (every synthetic trace is full-length, which also unlocks
    the merged core's rolling-ring `all_valid` form).  `chan` (static)
    is the `SimSpec.chan` channel geometry; `ileave` the per-policy
    interleave-code column; `fault` the optional (fault_rows,
    jedec_row, key) lane expansion of `_static_body`; `synth_inputs`
    the device arrays the synthesis reads (`_synth_inputs`)."""
    all_valid = synth is not None
    if all_valid:
        arrival, bank, row, is_write, valid = _synth_streams(
            synth, synth_inputs)
    return _static_body(n_banks, mlp_window, reorder_plan, backend,
                        want, p99_k, bs, arrival, bank, row, is_write,
                        valid, timings, closed, slacks, caps,
                        all_valid=all_valid, chan=chan, ileave=ileave,
                        fault=fault, region_map=region_map)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
def _replay_grid_adaptive(synth, n_banks, mlp_window, reorder_plan,
                          backend, want, p99_k, bs, chan, arrival,
                          bank, row, is_write, valid, tables, bins,
                          scns, tcfg, closed, slacks, caps, ileave,
                          region_map=None, fault=None, synth_inputs=()):
    """ONE dispatch: (optional in-dispatch trace synthesis +)
    closed-loop adaptive replay grid — see `_adaptive_body` and
    `_replay_grid`'s `synth` contract; `fault` the optional
    (fault_rows, key) fault axis of `_adaptive_body`."""
    if synth is not None:
        arrival, bank, row, is_write, valid = _synth_streams(
            synth, synth_inputs)
    return _adaptive_body(n_banks, mlp_window, reorder_plan, backend,
                          want, p99_k, bs, arrival, bank, row,
                          is_write, valid, tables, bins, scns, tcfg,
                          closed, slacks, caps, chan=chan,
                          ileave=ileave, fault=fault,
                          region_map=region_map)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
def _bracket_grid(synth, n_banks, mlp_window, reorder_plan, backend,
                  p99_k, n_real, bs, chan, arrival, bank, row,
                  is_write, valid, tables, bins, scns, tcfg, closed,
                  slacks, caps, base_row, ileave, synth_inputs=()):
    """ONE dispatch for the whole adaptive-vs-bracket evaluation
    (`perf_model.evaluate_adaptive`'s inner loop): in-dispatch
    synthesis (when `synth` is set) + the adaptive campaign + the
    per-scenario worst-bin STATIC provisioning derived from its own
    temperature peaks — the `searchsorted` bin round-up that used to
    run host-side between two launches now runs on device between the
    two replay halves.

    `tables` must be a single stack ([1, S+1, (B,) 6]); `n_real`
    (static) is the number of non-oracle scenarios (the leading
    entries of the scenario axis) whose peaks drive the provisioning;
    `base_row` is the JEDEC baseline timing row prepended to the
    worst-bin rows, exactly like the host-side bracket.  Returns
    {"adaptive": ..., "static": ..., "worst_bin" [n_real],
    "temp_peak" [n_real]} with both halves reduced via "stats".
    """
    if synth is not None:
        arrival, bank, row, is_write, valid = _synth_streams(
            synth, synth_inputs)
    out_a = _adaptive_body(n_banks, mlp_window, reorder_plan, backend,
                           ("stats",), p99_k, bs, arrival, bank, row,
                           is_write, valid, tables, bins, scns, tcfg,
                           closed, slacks, caps, chan=chan,
                           ileave=ileave)
    # static-worst-case provisioning from the adaptive trajectory's
    # peaks, guarded by the controller hysteresis (tcfg[2]) — same
    # arithmetic as the host-side bracket in perf_model
    peak = out_a["temp_max"][:, :, 0, :n_real].max(axis=(0, 1))
    worst = jnp.searchsorted(bins, peak + tcfg[2], side="left")
    tab0 = tables[0]                     # [S+1, (B,) 6], JEDEC last
    base = jnp.broadcast_to(base_row, tab0.shape[1:])
    rows = jnp.concatenate([base[None], jnp.take(tab0, worst, axis=0)],
                           axis=0)
    out_s = _static_body(n_banks, mlp_window, reorder_plan, backend,
                         ("stats",), p99_k, bs, arrival, bank, row,
                         is_write, valid, rows, closed, slacks, caps,
                         all_valid=synth is not None, chan=chan,
                         ileave=ileave)
    return {"adaptive": out_a, "static": out_s, "worst_bin": worst,
            "temp_peak": peak}


def _shard_pad(tree, n_dev: int):
    """Pad every [T, ...]-leading leaf of a per-stream tree to a T
    divisible by the device count by REPEATING the last row (real
    work, so padded shards stay finite; the engine slices the extra
    rows off after the gather).  Returns (padded tree, real T)."""
    t = int(jax.tree_util.tree_leaves(tree)[0].shape[0])
    pad = (-t) % n_dev
    if pad == 0:
        return tree, t

    def p(x):
        x = jnp.asarray(x)
        return jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], 0)

    return jax.tree_util.tree_map(p, tree), t


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _sharded_grid(mesh, kind, statics, per_stream, extras):
    """ONE SHARDED dispatch: the campaign's (trace x tenant-mix)
    leading axis is partitioned across the mesh's "campaign" axis via
    `shard_map`, each device replaying only its shard of streams
    through the SAME `_static_body` / `_adaptive_body` the
    single-device grids run — so a one-device mesh is bit-identical to
    the unsharded path (identical ops on identical values).  Only the
    `want`-selected outputs cross the shard boundary ([t_local, ...]
    masked stats, all-gathered on the campaign axis); per-trace
    mean/p99 are shard-local reductions, so the gathered statistics
    are EXACT, not approximations.

    `kind` (static): "static" | "adaptive" | "bracket".  `statics`:
    (synth, n_banks, mlp_window, reorder_plan, backend, want, p99_k,
    bs, chan, n_real).  `per_stream`: the [T]-leading tree — the
    packed (arrival, bank, row, is_write, valid) streams, or a
    declarative spec's `stream_knobs()` rows when synthesis is fused
    (each device then synthesizes only its shard, threefry-identical
    to its slice of the unsharded batch).  `extras`: the replicated
    inputs in the matching grid-function order.  The "bracket" kind
    `pmax`es the per-scenario temperature peaks across shards between
    the two replay halves, so worst-bin provisioning still sees the
    GLOBAL peak."""
    P_ = jax.sharding.PartitionSpec
    (synth, n_banks, mlp_window, plan, backend, want, p99_k, bs, chan,
     n_real) = statics
    sh, rep = P_("campaign"), P_()

    def body(per_stream, extras):
        if synth is not None:
            tb = synth.synth_traced(per_stream)
            arrival, bank, row, is_write = (tb.arrival, tb.bank,
                                            tb.row, tb.is_write)
            valid = jnp.ones(arrival.shape, bool)
        else:
            arrival, bank, row, is_write, valid = per_stream
        if kind == "static":
            timings, closed, slacks, caps, ileave, region_map = extras
            return _static_body(
                n_banks, mlp_window, plan, backend, want, p99_k, bs,
                arrival, bank, row, is_write, valid, timings, closed,
                slacks, caps, all_valid=synth is not None, chan=chan,
                ileave=ileave, region_map=region_map)
        if kind == "adaptive":
            (tables, bins, scns, tcfg, closed, slacks, caps, ileave,
             region_map) = extras
            return _adaptive_body(
                n_banks, mlp_window, plan, backend, want, p99_k, bs,
                arrival, bank, row, is_write, valid, tables, bins,
                scns, tcfg, closed, slacks, caps, chan=chan,
                ileave=ileave, region_map=region_map)
        (tables, bins, scns, tcfg, closed, slacks, caps, base_row,
         ileave) = extras
        out_a = _adaptive_body(
            n_banks, mlp_window, plan, backend, ("stats",), p99_k, bs,
            arrival, bank, row, is_write, valid, tables, bins, scns,
            tcfg, closed, slacks, caps, chan=chan, ileave=ileave)
        peak = out_a["temp_max"][:, :, 0, :n_real].max(axis=(0, 1))
        peak = jax.lax.pmax(peak, "campaign")    # global, all shards
        worst = jnp.searchsorted(bins, peak + tcfg[2], side="left")
        tab0 = tables[0]
        base = jnp.broadcast_to(base_row, tab0.shape[1:])
        rows = jnp.concatenate(
            [base[None], jnp.take(tab0, worst, axis=0)], axis=0)
        out_s = _static_body(
            n_banks, mlp_window, plan, backend, ("stats",), p99_k, bs,
            arrival, bank, row, is_write, valid, rows, closed, slacks,
            caps, all_valid=synth is not None, chan=chan,
            ileave=ileave)
        return {"adaptive": out_a, "static": out_s, "worst_bin": worst,
                "temp_peak": peak}

    out_specs = (sh if kind != "bracket" else
                 {"adaptive": sh, "static": sh, "worst_bin": rep,
                  "temp_peak": rep})
    return jax.shard_map(body, mesh=mesh, in_specs=(sh, rep),
                         out_specs=out_specs, check_vma=False)(
        per_stream, extras)


def _masked_stats(lat: np.ndarray, valid: np.ndarray):
    """Masked mean / interpolated p99 over the last axis, computed
    host-side in numpy: per-row pairwise summation depends only on the
    row length, so a [T, P, S, N] grid and the [1, 1, 1, N] shim give
    bit-identical statistics (XLA's batched reduces do not).  The mean
    reduces each trace's VALID PREFIX, not the zero-padded row — numpy's
    pairwise partitioning over a padded length differs from the
    unpadded sum, so summing padding (even zeros) would only be
    coincidentally bit-equal.  Works for any number of campaign axes
    between the trace axis and the request axis ([T, P, S, N] static,
    [T, P, K, C, N] adaptive).  This is the `stats="host"` reference;
    `_device_stats` is the in-dispatch fast path (1e-5-relative)."""
    mid = (1,) * (lat.ndim - 2)
    v = valid.reshape((valid.shape[0],) + mid + (valid.shape[1],))
    cnt = valid.sum(-1).astype(np.float32).reshape(
        (valid.shape[0],) + mid)
    mean = np.empty(lat.shape[:-1], np.float32)
    for t in range(lat.shape[0]):                    # padding is a suffix
        c = int(valid[t].sum())
        mean[t] = lat[t, ..., :c].sum(-1, dtype=np.float32) / np.float32(c)
    # sorting pads to +inf, so the first `cnt` slots equal the sorted
    # valid prefix and interpolating below them is structurally exact
    s = np.sort(np.where(v, lat, np.inf), axis=-1)
    q = (np.float32(0.99) * (cnt - 1.0)).astype(np.float32)
    lo = np.floor(q).astype(np.int64)
    hi = np.ceil(q).astype(np.int64)
    frac = q - lo.astype(np.float32)        # keep the whole path float32
    vlo = np.take_along_axis(
        s, np.broadcast_to(lo[..., None], s.shape[:-1] + (1,)), -1)[..., 0]
    vhi = np.take_along_axis(
        s, np.broadcast_to(hi[..., None], s.shape[:-1] + (1,)), -1)[..., 0]
    return mean, vlo + (vhi - vlo) * frac


def _expand_fault_axis(x, nf: int, axis: int):
    """Broadcast an UNFAULTED result grid across an all-inert fault
    axis: every inert scenario replays bit-identically to the
    fault-free path, so the F rows are copies by construction — the
    engine never pays the faulted compile for a `FaultSpec.none()`."""
    return (None if x is None
            else np.repeat(np.expand_dims(x, axis), nf, axis))


def _plan_entries(windows: np.ndarray, policies, arrival, valid,
                  n: int) -> tuple:
    """Static reorder plan: `(window, eff, policy idx)` per window
    group.  With concrete [T, N] arrivals the buffer shrinks to the
    EXACT `_eff_window` bound of the group's largest slack (a larger
    slack can only need a deeper buffer, so one bound covers the
    group); without them (an unmaterialized `SynthSpec`) it stays at
    the nominal window."""
    groups: dict[int, list[int]] = {}
    for i, w in enumerate(windows.tolist()):
        if w > 1:
            groups.setdefault(int(w), []).append(i)
    plan = []
    for w, ix in sorted(groups.items()):
        if arrival is None:
            eff = min(w, n)
        else:
            slack = max(float(policies[i].reorder_slack_ns) for i in ix)
            eff = _eff_window(arrival, valid, w, slack)
        plan.append((w, eff, tuple(ix)))
    return tuple(plan)


@dataclasses.dataclass
class SimEngine:
    """Facade that compiles a `SimSpec` into one replay dispatch —
    static (T x P x S) or, with a thermal axis, adaptive
    (T x P x K x C); either way ONE launch per `run`.

    Knobs (see module docstring):

      backend — "scan" (default: vmapped lax.scan), "merged"
                (FR-FCFS fused into the replay scan — no [T, P, N]
                streams materialize), "pallas" (the
                repro.kernels.replay kernels compiled for the TPU,
                static AND single-channel adaptive; raises off the
                TPU), "pallas_interpret" (the same kernel bodies run
                on the host), "auto" (the attached `tuner`'s profiled
                choice, else pallas on TPU — scan for a multi-channel
                adaptive campaign — and scan elsewhere).
      stats   — "device" (default: in-dispatch reductions, only
                [grid]-shaped summaries transferred, raw grids gated
                by SimSpec.collect) or "host" (bit-exact numpy
                reference, raw grids always materialized).
      reorder — "device" (default: FR-FCFS prepass inside the
                dispatch) or "host" (retained Python loop in pack()).
      tuner   — optional `autotune.ReplayTuner`; `autotune(spec)`
                profiles every candidate (backend, block_rows,
                fuse_synth) config on the campaign and records the
                winner per (campaign kind, size bin), which
                backend="auto" then consults.
      mesh    — optional `jax.sharding.Mesh` with a "campaign" axis
                (see `launch.mesh.make_campaign_mesh`): every run then
                goes through the `shard_map` path, partitioning the
                (trace x tenant-mix) leading axis across the mesh's
                devices with only masked per-shard stats crossing the
                boundary — still ONE dispatch, bit-identical to the
                unsharded path on a one-device mesh.  Requires the
                default device stats + device reorder.

    A `SimSpec` whose trace axis is a declarative `dram_sim.SynthSpec`
    / `TenantSpec` fuses the trace synthesis INTO the dispatch (unless
    the resolved config says otherwise): synthesis + FR-FCFS + replay
    + statistics are then truly one launch — and under a mesh each
    device synthesizes ONLY its shard of streams.
    """

    dispatch_count: int = 0
    backend: str = "scan"
    stats: str = "device"
    reorder: str = "device"
    tuner: "ReplayTuner | None" = None
    mesh: "jax.sharding.Mesh | None" = None

    def __post_init__(self):
        assert self.backend in ("auto", "scan", "merged", "pallas",
                                "pallas_interpret"), self.backend
        assert self.stats in ("device", "host"), self.stats
        assert self.reorder in ("device", "host"), self.reorder
        if self.mesh is not None:
            assert "campaign" in self.mesh.axis_names, \
                "campaign mesh needs a 'campaign' axis"

    def _tuner_key(self, spec: SimSpec):
        """(campaign-kind unit, request count) — the tuner table key.
        Region-compressed campaigns tune under the `replay_unit`
        region offset, with the region count folded into the size
        condition (the in-scan map gather scales with regions the way
        dispatch cost scales with N)."""
        n = (spec.traces.n if spec.synth is not None else
             max(int(np.asarray(t.arrival).shape[0])
                 for t in spec.traces))
        adaptive = spec.thermal is not None
        banked = (spec.timings.ndim - (1 if adaptive else 0)) == 3
        regioned = spec.region_map is not None
        if regioned:
            n *= spec.region_map.shape[-1] // spec.n_banks
        return replay_unit(adaptive, banked,
                           channels=spec.n_channels * spec.n_ranks > 1,
                           regioned=regioned), n

    def _resolve(self, spec: SimSpec,
                 config: "ReplayConfig | None" = None):
        """(backend, fuse_synth, block_rows) for one run: an explicit
        `config` wins; otherwise backend="auto" + an attached tuner
        answers with the profiled candidate for this campaign's
        (kind, size) bin — falling back, AdaptiveTable-style, to
        candidate 0 (the conservative scan default) on unprofiled
        bins.  "auto" without a tuner picks the kernel on the TPU
        (the scan for a multi-channel adaptive campaign, which the
        single-channel adaptive kernel cannot replay) and the scan
        elsewhere; an explicit "pallas" off the TPU raises."""
        cfg = config
        if cfg is None and self.backend == "auto" and \
                self.tuner is not None:
            cfg = self.tuner.lookup(*self._tuner_key(spec))
        if cfg is None:
            backend, fuse, bs = self.backend, True, None
        else:
            backend, fuse, bs = cfg.backend, cfg.fuse_synth, \
                cfg.block_rows
        platform = jax.default_backend()
        if backend == "auto":
            multi_adaptive = (spec.thermal is not None
                              and spec.n_channels * spec.n_ranks > 1)
            backend = ("pallas" if platform == "tpu" and not multi_adaptive
                       else "scan")
        if backend == "pallas" and platform != "tpu":
            raise ValueError(
                f"backend='pallas' compiles the replay kernels for a "
                f"TPU, and JAX's default backend is {platform!r}; ask "
                f"for backend='pallas_interpret' to run the kernel "
                f"bodies on the host")
        return backend, fuse, bs

    def _backend(self) -> str:
        return self._resolve(
            SimSpec(traces=(Trace(np.zeros(1, np.float32),
                                  np.zeros(1, np.int32),
                                  np.zeros(1, np.int32),
                                  np.zeros(1, bool)),),
                    timings=np.zeros((1, 6), np.float32)))[0]

    def _inputs(self, spec: SimSpec):
        """(stream arrays ([T,N] fast / [T,P,N] reference), valid,
        closed, reorder knobs, static reorder plan)."""
        if self.reorder == "device":
            arrival, bank, row, is_write, valid, windows, slacks, caps \
                = spec.pack_device()
            plan = _plan_entries(windows, spec.policies, arrival,
                                 valid, arrival.shape[1])
        else:
            arrival, bank, row, is_write, valid, _ = spec.pack()
            p = len(spec.policies)
            slacks = np.zeros((p,), np.float32)
            caps = np.ones((p,), np.int32)
            plan = ()
        return (jnp.asarray(arrival), jnp.asarray(bank),
                jnp.asarray(row), jnp.asarray(is_write),
                jnp.asarray(valid), valid,
                jnp.asarray(spec.closed_flags), jnp.asarray(slacks),
                jnp.asarray(caps), plan)

    def _streams(self, spec: SimSpec, fuse: bool):
        """Resolve the campaign streams: returns (synth, arrival, bank,
        row, is_write, valid_device, valid_host, closed, slacks, caps,
        plan).  When the trace axis is a `SynthSpec` and fusion is on
        (device reorder only — the host reorder loop needs concrete
        arrays), the stream slots are scalar placeholders and `synth`
        carries the static spec into the dispatch; the reorder plan
        then takes its EXACT buffer caps from the spec's cached
        materialization when one exists (e.g. warmed by `autotune`) —
        threefry determinism makes the in-dispatch streams bit-equal
        to it — and the nominal window otherwise."""
        synth = spec.synth if (fuse and self.reorder == "device") \
            else None
        if synth is None:
            return (None,) + self._inputs(spec)
        valid = np.ones((len(synth), synth.n), bool)
        windows, slacks, caps = spec.policy_knobs()
        cached = synth._cache.get("traces")
        arr = (np.stack([np.asarray(t.arrival) for t in cached])
               if cached is not None else None)
        plan = _plan_entries(windows, spec.policies, arr, valid,
                             synth.n)
        z = jnp.zeros((), jnp.float32)
        return (synth, z, z, z, z, z, valid,
                jnp.asarray(spec.closed_flags), jnp.asarray(slacks),
                jnp.asarray(caps), plan)

    def _dispatch(self, kind, spec, synth, plan, backend, want, p99_k,
                  bs, streams, extras, n_real=0, fault=None):
        """Route one campaign launch: the plain jitted grid, or — when
        a `mesh` is attached — the `shard_map` path (trace axis
        partitioned across the "campaign" devices, per-stream inputs
        padded to a device multiple by repeating the last stream and
        sliced back after the gather).  Either way: ONE dispatch."""
        chan = spec.chan
        if self.mesh is None:
            if kind == "static":
                return _replay_grid(synth, spec.n_banks,
                                    spec.mlp_window, plan, backend,
                                    want, p99_k, bs, chan, *streams,
                                    *extras, fault=fault,
                                    synth_inputs=_synth_inputs(synth))
            if kind == "adaptive":
                return _replay_grid_adaptive(
                    synth, spec.n_banks, spec.mlp_window, plan,
                    backend, want, p99_k, bs, chan, *streams, *extras,
                    fault=fault, synth_inputs=_synth_inputs(synth))
            return _bracket_grid(synth, spec.n_banks, spec.mlp_window,
                                 plan, backend, p99_k, n_real, bs,
                                 chan, *streams, *extras,
                                 synth_inputs=_synth_inputs(synth))
        assert fault is None, \
            "fault campaigns are single-device (no mesh sharding yet)"
        assert not _synth_inputs(synth), \
            "KVSpec campaigns are single-device (no mesh sharding yet)"
        assert self.stats == "device" and self.reorder == "device", \
            "sharded campaigns need device stats + device reorder"
        n_dev = self.mesh.shape["campaign"]
        per_stream = (synth.stream_knobs() if synth is not None
                      else streams)
        per_stream, t = _shard_pad(per_stream, n_dev)
        t_pad = int(jax.tree_util.tree_leaves(per_stream)[0].shape[0])
        n = synth.n if synth is not None else streams[0].shape[-1]
        self.shard_shape = (n_dev, t_pad // n_dev, int(n))
        statics = (synth, spec.n_banks, spec.mlp_window, plan, backend,
                   want, p99_k, bs, chan, n_real)
        out = _sharded_grid(self.mesh, kind, statics, per_stream,
                            extras)
        if kind == "bracket":
            sl = lambda d: {k: v[:t] for k, v in d.items()}
            return {"adaptive": sl(out["adaptive"]),
                    "static": sl(out["static"]),
                    "worst_bin": out["worst_bin"],
                    "temp_peak": out["temp_peak"]}
        return {k: v[:t] for k, v in out.items()}

    def autotune(self, spec: SimSpec, reps: int = 3) -> "ReplayConfig":
        """Profile every candidate replay configuration on THIS
        campaign and record the winner in the tuner's table (persisted
        to disk), which `backend="auto"` consults on later runs of any
        same-kind/size campaign.  Creates a platform-default
        `ReplayTuner` when none is attached.  Materializes a
        `SynthSpec` trace axis once up front, so the reorder plan gets
        its exact buffer caps for BOTH the profiled and the later
        fused runs.  Dispatch accounting stays honest — each profiling
        run increments `dispatch_count` like any other, so call this
        during warmup, not inside a measured section."""
        import time
        if self.tuner is None:
            self.tuner = ReplayTuner(platform=jax.default_backend())
        if spec.synth is not None:
            spec.trace_tuple()    # warm cache -> exact reorder caps
        unit, n = self._tuner_key(spec)

        def measure(cfg: "ReplayConfig") -> float:
            self.run(spec, config=cfg)            # compile + warm
            best = np.inf
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                self.run(spec, config=cfg)
                best = min(best, time.perf_counter() - t0)
            return best

        cfg, _ = self.tuner.tune(unit, n, measure)
        return cfg

    def run(self, spec: SimSpec,
            config: "ReplayConfig | None" = None) -> SimResult:
        backend, fuse, bs = self._resolve(spec, config)
        with span("sim.prep") as s:
            (synth, arrival, bank, row, is_write, valid_d, valid, closed,
             slacks, caps, plan) = self._streams(spec, fuse)
            s.count(streams=valid.shape[0], requests=valid.size)
            if spec.grouped:
                s.count(ranks=spec.n_channels * spec.n_ranks,
                        bank_groups=spec.bank_groups)
            if isinstance(spec.traces, KVSpec):
                s.count(ops=spec.traces.op_count())
        self.dispatch_count += 1
        fa = spec.faults
        f_on = spec.fault_on
        nf = 0 if fa is None else len(fa)

        if spec.thermal is None:
            s_rows = spec.timings.shape[0]
            timings, fault = spec.timings, None
            if f_on:
                # (timing x fault) product expanded onto the lane
                # axis — lane l = s * F + f replays timing row s under
                # scenario f; the LAST timing row doubles as the JEDEC
                # fallback (retry re-issue + watchdog degradation
                # target), mirroring the adaptive tables' JEDEC-last
                # convention
                timings = np.repeat(spec.timings, nf, axis=0)
                fault = (jnp.asarray(np.tile(fa.pack(), (s_rows, 1))),
                         jnp.asarray(spec.timings[-1]),
                         jax.random.PRNGKey(fa.seed))
            want = (("stats",) + (("lat",)
                                  if "latencies" in spec.collect else ())
                    if self.stats == "device" else ("lat",))
            with span("sim.dispatch"):
                rm = (None if spec.region_map is None
                      else jnp.asarray(spec.region_map))
                out = self._dispatch(
                    "static", spec, synth, plan, backend, want,
                    _p99_k(valid), bs,
                    (arrival, bank, row, is_write, valid_d),
                    (jnp.asarray(timings), closed, slacks, caps,
                     jnp.asarray(spec.ileave_codes), rm), fault=fault)
            with span("sim.fetch"):
                if self.stats == "host":
                    lat = np.asarray(out["lat"])
                    mean, p99 = _masked_stats(lat, valid)
                else:
                    mean, p99 = np.asarray(out["mean"]), np.asarray(out["p99"])
                    lat = (np.asarray(out["lat"]) if "lat" in out else None)
                total = np.asarray(out["total"])
                cnt = None
                if f_on:
                    # unflatten the (timing x fault) lane axis: [T, P,
                    # S*F, ...] -> [T, P, S, F, ...]
                    def uf(x):
                        return (None if x is None else
                                x.reshape(x.shape[:2] + (s_rows, nf)
                                          + x.shape[3:]))

                    mean, p99, total, lat = map(uf, (mean, p99, total, lat))
                    cnt = uf(np.asarray(out["cnt"]))
                elif fa is not None:      # inert spec: F copies + zeros
                    mean, p99, total, lat = (
                        _expand_fault_axis(x, nf, 3)
                        for x in (mean, p99, total, lat))
                    cnt = np.zeros(total.shape + (faults.N_COUNTERS,),
                                   np.int32)
                stall = (np.asarray(out["stall"]) if "stall" in out
                         else np.zeros_like(total))
                return SimResult(spec=spec, mean_latency_ns=mean,
                                 p99_latency_ns=p99, total_ns=total,
                                 latencies=lat, valid=valid,
                                 fault_counters=cnt,
                                 constraint_stall_ns=stall)

        scns, bins, tcfg = spec.thermal.pack()
        fault = (None if not f_on else
                 (jnp.asarray(fa.pack()), jax.random.PRNGKey(fa.seed)))
        if self.stats == "device":
            want = ("stats",)
            want += ("lat",) if "latencies" in spec.collect else ()
            want += ("temps",) if "temps" in spec.collect else ()
            want += ("bins",) if "bins" in spec.collect else ()
        else:
            want = ("lat", "temps", "bins")
        with span("sim.dispatch"):
            out = self._dispatch(
                "adaptive", spec, synth, plan, backend, want,
                _p99_k(valid), bs, (arrival, bank, row, is_write, valid_d),
                (jnp.asarray(spec.timings), jnp.asarray(bins),
                 jnp.asarray(scns), jnp.asarray(tcfg), closed, slacks,
                 caps, jnp.asarray(spec.ileave_codes),
                 None if spec.region_map is None
                 else jnp.asarray(spec.region_map)), fault=fault)

        with span("sim.fetch"):
            if self.stats == "host":
                lat, temps, bin_sel = (np.asarray(out["lat"]),
                                       np.asarray(out["temps"]),
                                       np.asarray(out["bins"]))
                mean, p99 = _masked_stats(lat, valid)
                # thermal diagnostics over each trace's valid prefix
                tmax = np.empty(lat.shape[:-1], np.float32)
                tmean = np.empty(lat.shape[:-1], np.float32)
                switches = np.empty(lat.shape[:-1], np.int64)
                for t in range(lat.shape[0]):            # padding is a suffix
                    c = int(valid[t].sum())
                    tmax[t] = temps[t, ..., :c].max(-1)
                    tmean[t] = temps[t, ..., :c].mean(-1)
                    switches[t] = (np.diff(bin_sel[t, ..., :c], axis=-1)
                                   != 0).sum(-1)
            else:
                mean, p99 = np.asarray(out["mean"]), np.asarray(out["p99"])
                tmax, tmean = (np.asarray(out["temp_max"]),
                               np.asarray(out["temp_mean"]))
                switches = np.asarray(out["bin_switches"])
                lat = np.asarray(out["lat"]) if "lat" in out else None
                temps = np.asarray(out["temps"]) if "temps" in out else None
                bin_sel = np.asarray(out["bins"]) if "bins" in out else None
            total = np.asarray(out["total"])
            heat = np.asarray(out["bank_heat"])
            cnt = np.asarray(out["cnt"]) if f_on else None
            if fa is not None and not f_on:
                # inert spec: the unfaulted [T, P, K, C] grid broadcast
                # across the F copies (axis 4, before N/banks) + zeros
                mean, p99, total, tmax, tmean, switches, lat, temps, \
                    bin_sel, heat = (
                        _expand_fault_axis(x, nf, 4)
                        for x in (mean, p99, total, tmax, tmean, switches,
                                  lat, temps, bin_sel, heat))
                cnt = np.zeros(total.shape + (faults.N_COUNTERS,),
                               np.int32)
            return SimResult(spec=spec, mean_latency_ns=mean,
                             p99_latency_ns=p99, total_ns=total,
                             latencies=lat, valid=valid, temps=temps,
                             bins=bin_sel, temp_max=tmax, temp_mean=tmean,
                             bin_switches=switches, bank_heat=heat,
                             fault_counters=cnt)

    def run_bracket(self, spec: SimSpec, base_row,
                    n_real: int | None = None,
                    config: "ReplayConfig | None" = None) -> dict:
        """The adaptive-vs-static-worst-case bracket
        (`perf_model.evaluate_adaptive`'s two replay launches) as ONE
        dispatch: the adaptive campaign runs, its per-scenario
        temperature peaks round up to worst-case provisioning bins ON
        DEVICE, and the static campaign replays under those rows in
        the same launch — with a `SynthSpec` trace axis the synthesis
        fuses in too, making the whole evaluation `dispatches=1`.

        `spec` must be adaptive with a single table stack; `base_row`
        is the JEDEC baseline row prepended to the worst-bin rows;
        `n_real` = number of non-oracle scenarios driving the
        provisioning (default: all).  Returns numpy dicts
        {"adaptive", "static", "worst_bin", "temp_peak", "valid"} —
        "adaptive" carries mean/p99/total + thermal diagnostics +
        bank_heat, "static" mean/p99/total over the [1 + n_real]
        timing rows."""
        assert spec.thermal is not None and spec.timings.shape[0] == 1, \
            "run_bracket needs an adaptive spec with ONE table stack"
        assert not spec.fault_on, \
            "run_bracket carries no fault axis — run() the faulted spec"
        assert spec.region_map is None, \
            "run_bracket carries no region axis — run() the spec"
        backend, fuse, bs = self._resolve(spec, config)
        with span("sim.prep") as s:
            (synth, arrival, bank, row, is_write, valid_d, valid, closed,
             slacks, caps, plan) = self._streams(spec, fuse)
            s.count(streams=valid.shape[0], requests=valid.size)
        scns, bins, tcfg = spec.thermal.pack()
        n_real = len(scns) if n_real is None else int(n_real)
        self.dispatch_count += 1
        with span("sim.dispatch"):
            out = self._dispatch(
                "bracket", spec, synth, plan, backend, ("stats",),
                _p99_k(valid), bs, (arrival, bank, row, is_write, valid_d),
                (jnp.asarray(spec.timings), jnp.asarray(bins),
                 jnp.asarray(scns), jnp.asarray(tcfg), closed, slacks,
                 caps, jnp.asarray(base_row, jnp.float32),
                 jnp.asarray(spec.ileave_codes)),
                n_real=n_real)

        def host(d):
            return {k: np.asarray(v) for k, v in d.items()}

        with span("sim.fetch"):
            return {"adaptive": host(out["adaptive"]),
                    "static": host(out["static"]),
                    "worst_bin": np.asarray(out["worst_bin"]),
                    "temp_peak": np.asarray(out["temp_peak"]),
                    "valid": valid}


_DEFAULT: SimEngine | None = None


def default_engine() -> SimEngine:
    """Shared engine used by the `dram_sim.simulate` shim: the full
    bit-exact reference configuration (host stats, host reorder)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = SimEngine(stats="host", reorder="host")
    return _DEFAULT


__all__ = ["Policy", "OPEN_FCFS", "SimSpec", "SimResult", "SimEngine",
           "SynthSpec", "TenantSpec", "ThermalSpec", "ReplayConfig",
           "ReplayTuner", "default_engine"]
