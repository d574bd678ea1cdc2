"""Trace-driven DRAM bank-timing simulator (JAX lax.scan).

Models an in-order memory controller over `n_banks` banks on one
rank/channel, honoring tRCD / tRAS / tRP / tWR / tCL.  Service latency
per request under the default open-page policy:

  row hit      : tCL
  row empty    : tRCD + tCL
  row conflict : (tRAS remainder) + tRP + tRCD + tCL
  write reuse  : a following conflict additionally waits out tWR

This is the engine behind the Fig. 4 real-system reproduction
(`repro.core.perf_model`): the ONLY thing AL-DRAM changes is the timing
parameters, so speedups fall out of the same trace replayed under
standard vs adaptive timings.

The replay core is written to be batched: it takes stacked timing
rows (`TimingParams.as_row`), a validity mask (so traces of different
lengths can be padded into one grid) and a scheduling `Policy`, and
`repro.core.sim_engine.SimEngine` runs a whole (traces x policies x
timing rows) campaign in ONE dispatch.  `replay_one` is the one-row
reference scan; `replay_rows` is the engine's core — the timing-row
axis rides the minor lane axis of the carried bank state (the same
layout as the `repro.kernels.replay` Pallas kernel), which pays the
per-request bank gather/scatter once per (trace, policy) step instead
of once per timing row (~4x on CPU, bit-identical).
`simulate(trace, tp)` remains as a thin single-item shim over the
batched path.

Every replay layout also accepts PER-BANK timing rows (FLY-DRAM-style
spatial tables: one register row per rank-level bank): `replay_one`
takes [banks, 6], `replay_rows` [S, banks, 6], `replay_adaptive` a
[S+1, banks, 6] table stack, and the Pallas kernel a banked timing
tile — each request is serviced with ITS bank's row, gathered
alongside the bank-state gather the scan already pays.  A per-bank
input whose rows are constant across banks replays bit-identical to
the per-module path.

`replay_adaptive` is the closed-loop variant (paper Sec. 4's online
mechanism): the `lax.scan` state additionally carries an RC thermal
state (`repro.core.thermal`), and each request selects its timing row
*inside the scan* — `searchsorted` over the stacked per-bin table rows
at the currently sensed temperature, with up-immediate/down-hysteretic
bin switching.  Both replays share the per-request service arithmetic
(`_service`), so a constant-temperature scenario with activity heating
disabled reproduces the static replay bit-for-bit.

Scheduling-policy axis:

  * page policy — "open" leaves the row latched after an access
    (hits are cheap, conflicts pay the precharge at the *next* access);
    "closed" auto-precharges after every access (no hits, no
    conflicts: every access is a row-empty ACT once the precharge has
    completed).
  * FR-FCFS-lite — `frfcfs_reorder` reorders a trace host-side within a
    bounded lookahead window, issuing the oldest row-hit first (with a
    starvation cap), approximating a first-ready FCFS scheduler.
    `frfcfs_perm` is the jitted JAX formulation of the same scheduler
    (a `lax.scan` over the pending window) that `sim_engine` runs as a
    prepass INSIDE the campaign dispatch (the kernel backends run its
    lane-parallel Pallas form, `repro.kernels.replay.frfcfs`) —
    parity-tested
    request-for-request against the Python reference, which is retained
    as the host path (and cached across `SimSpec.pack()` calls).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core.timing import TimingParams


class Trace(NamedTuple):
    arrival: jnp.ndarray    # [N] ns, non-decreasing
    bank: jnp.ndarray       # [N] int32
    row: jnp.ndarray        # [N] int32
    is_write: jnp.ndarray   # [N] bool


# address-interleaving policies: how a request's (bank, row) address
# maps onto the channel axis of a multi-channel module.  "row" keeps
# whole rows on one channel (locality-preserving), "cacheline" stripes
# consecutive addresses across channels (bandwidth-spreading), and
# "bank_xor" hashes bank into the channel pick (breaks pathological
# bank<->channel alignment, cf. permutation-based interleaving).
ILEAVE_CODES = {"row": 0, "cacheline": 1, "bank_xor": 2}


def chan_rank(bank, row, ileave, n_channels: int, n_ranks: int,
              n_banks: int = 8):
    """Elementwise (channel, rank) of each request under an
    interleaving policy — pure jnp, so the mapping runs IN-SCAN (and
    inside the Pallas kernel) from the same `ileave` code column the
    policy axis carries.  `ileave` is a traced int32 scalar (one of
    `ILEAVE_CODES`); bank/row are int32 of any matching shape."""
    c = n_channels
    addr = row * jnp.int32(n_banks) + bank    # flat address proxy
    ch = jnp.where(ileave == 0, row % c,
                   jnp.where(ileave == 1, addr % c, (bank ^ row) % c))
    rank = (row // c) % n_ranks
    return ch.astype(jnp.int32), rank.astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class Policy:
    """One memory-controller scheduling policy (a campaign axis).

    page: "open" (default) or "closed" (auto-precharge every access).
    reorder_window: FR-FCFS-lite lookahead; <= 1 keeps FCFS order.
    interleave: address-interleaving policy mapping requests onto the
    channels of a multi-channel `SimSpec` (one of `ILEAVE_CODES`;
    inert when n_channels == 1).
    """

    page: str = "open"
    reorder_window: int = 0
    # promote a row-hit over the head request only when it arrives
    # within this slack (default ~ tRP + tRCD conflict premium):
    # reordering toward a request that is still in flight would stall
    # the channel longer than the conflict it avoids
    reorder_slack_ns: float = 30.0
    interleave: str = "row"

    def __post_init__(self):
        assert self.page in ("open", "closed"), self.page
        assert self.interleave in ILEAVE_CODES, self.interleave

    @property
    def closed(self) -> bool:
        return self.page == "closed"

    @property
    def ileave_code(self) -> int:
        return ILEAVE_CODES[self.interleave]


OPEN_FCFS = Policy()


def _row_pick_scan(bank, new_row, reuse, n_banks: int):
    """Sequential reference of the row-locality recurrence: reuse keeps
    the bank's last fresh row (0 before any), a miss latches `new_row`.
    Retained as the parity oracle for `_row_pick` — integer-exact
    equality is pinned by tests, because trace *identity* (not just
    distribution) anchors every committed evaluation number."""
    def pick(carry, x):
        last_rows = carry
        b, nr, ru = x
        r = jnp.where(ru, last_rows[b], nr)
        return last_rows.at[b].set(r), r

    _, row = jax.lax.scan(pick, jnp.zeros((n_banks,), jnp.int32),
                          (bank, new_row, reuse))
    return row


def _row_pick(bank, new_row, reuse, n_banks: int):
    """Vectorized (scan-free) `_row_pick_scan`, bit-identical: request
    i's row is `new_row[j]` where j is the LATEST non-reuse request
    <= i on the same bank (j = i itself when i is fresh), or 0 when no
    fresh access preceded it — a per-bank `cummax` over marked indices
    plus one gather, O(banks * N) elementwise instead of an N-step
    scan (the synthesis prologue of a fused campaign dispatch must not
    reintroduce a sequential loop)."""
    n = bank.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    fresh = jnp.where(reuse, -1, idx)                       # [N]
    marked = jnp.where(
        bank[None, :] == jnp.arange(n_banks, dtype=jnp.int32)[:, None],
        fresh[None, :], -1)                                 # [B, N]
    latest = jax.lax.cummax(marked, axis=1)
    j = latest[bank, idx]
    return jnp.where(j >= 0, new_row[jnp.maximum(j, 0)], 0)


def synth_trace(key, n: int, n_banks: int = 8, n_rows: int = 4096,
                row_hit: float = 0.6, write_frac: float = 0.3,
                inter_arrival_ns: float = 20.0) -> Trace:
    """Synthetic workload: per-bank row locality with geometric row
    reuse (hit prob `row_hit`), Poisson-ish arrivals.  Fully
    vectorized (no scan), so it fuses cleanly into the prologue of a
    single-dispatch campaign (`sim_engine` + `SynthSpec`)."""
    kb, kr, kw, ka, kh = jax.random.split(key, 5)
    bank = jax.random.randint(kb, (n,), 0, n_banks)
    # row sequence: reuse previous row on that bank w.p. row_hit
    new_row = jax.random.randint(kr, (n,), 0, n_rows)
    reuse = jax.random.uniform(kh, (n,)) < row_hit
    row = _row_pick(bank, new_row, reuse, n_banks)
    gaps = jax.random.exponential(ka, (n,)) * inter_arrival_ns
    arrival = jnp.cumsum(gaps)
    is_write = jax.random.uniform(kw, (n,)) < write_frac
    return Trace(arrival, bank, row, is_write)


def _runtime_knobs(knobs):
    """The per-stream knob rows as values computed on the device, not
    compile-time constants.  Under jit the spec's knobs would be
    constants, and XLA folds what it can compute from them (a tenant
    mix's log-probabilities) on the host, with the host's math: on a
    TPU that synthesized 2 of 16 tenant streams differently from the
    sharded campaign, whose knob rows arrive as device inputs."""
    return jax.lax.optimization_barrier(knobs)


@dataclasses.dataclass(frozen=True)
class SynthSpec:
    """DECLARATIVE trace batch: the `synth_trace` knobs of every
    stream, instead of materialized arrays.  `sim_engine.SimSpec`
    accepts one as its `traces` axis, and the engine then synthesizes
    the whole batch INSIDE the replay dispatch (threefry keys folded
    per row, exactly like `perf_model._synth_batch`) — a fig4-scale
    campaign is synthesis + reorder + replay + stats in ONE launch.

    Trace i is `synth_trace(fold_in(PRNGKey(seed), offsets[i]), n,
    n_banks, row_hits[i], write_fracs[i], inter_arrivals[i])` —
    bit-identical to the materialized `perf_model.trace_batch` rows by
    construction (same fold, same generator ops).

    `materialize()` runs the batched synthesis host-visibly (cached on
    the instance; counted as ONE `perf_model.synth_dispatch_count`
    launch the first time) — the engine uses it to derive the exact
    slack-horizon reorder-buffer caps, and `SimSpec.pack()` uses it so
    the reference pipelines accept a `SynthSpec` transparently."""

    n: int
    offsets: tuple[int, ...]
    row_hits: tuple[float, ...]
    write_fracs: tuple[float, ...]
    inter_arrivals: tuple[float, ...]
    seed: int = 0
    n_banks: int = 8

    def __post_init__(self):
        for f in ("offsets", "row_hits", "write_fracs",
                  "inter_arrivals"):
            object.__setattr__(self, f, tuple(getattr(self, f)))
            assert len(getattr(self, f)) == len(self.offsets), f
        object.__setattr__(self, "_cache", {})

    def __len__(self) -> int:
        return len(self.offsets)

    def knob_arrays(self):
        """(key, offsets, row_hits, write_fracs, inter_arrivals) device
        arrays — the ONLY traced inputs the fused synthesis needs."""
        return (jax.random.PRNGKey(self.seed),
                jnp.asarray(self.offsets, jnp.int32),
                jnp.asarray(self.row_hits, jnp.float32),
                jnp.asarray(self.write_fracs, jnp.float32),
                jnp.asarray(self.inter_arrivals, jnp.float32))

    def stream_knobs(self):
        """The PER-STREAM knob arrays ([T]-leading, one row per
        trace) that `synth_traced` consumes — the tree a sharded
        campaign partitions across devices (`sim_engine`'s shard_map
        path feeds each device only its shard of these rows)."""
        return (jnp.asarray(self.offsets, jnp.int32),
                jnp.asarray(self.row_hits, jnp.float32),
                jnp.asarray(self.write_fracs, jnp.float32),
                jnp.asarray(self.inter_arrivals, jnp.float32))

    def synth_traced(self, knobs):
        """Synthesize the [t, n] `Trace` batch from (possibly sharded)
        traced knob rows — `knobs` is a `stream_knobs()`-shaped tuple;
        the threefry key derives from the static seed, so any shard of
        rows synthesizes bit-identically to its slice of `synth()`."""
        key = jax.random.PRNGKey(self.seed)
        offs, rhs, wfs, ias = knobs

        def one(off, rh, wf, ia):
            k = jax.random.fold_in(key, off)
            return synth_trace(k, self.n, n_banks=self.n_banks,
                               row_hit=rh, write_frac=wf,
                               inter_arrival_ns=ia)

        return jax.vmap(one)(offs, rhs, wfs, ias)

    def synth(self):
        """The in-dispatch synthesis prologue: [T, n] `Trace` batch as
        traced arrays (call under jit) — see `_runtime_knobs`."""
        return self.synth_traced(_runtime_knobs(self.stream_knobs()))

    def materialize(self) -> tuple[Trace, ...]:
        """Host-side tuple-of-`Trace`s view (one synthesis launch,
        cached on the instance — repeated campaigns over the same spec
        pay it once)."""
        cache = self._cache
        if "traces" not in cache:
            from repro.core import perf_model          # lazy: no cycle
            perf_model.synth_dispatch_count += 1
            tb = jax.jit(self.synth)()
            fields = [np.asarray(f) for f in tb]
            cache["traces"] = tuple(
                Trace(*(f[i] for f in fields))
                for i in range(len(self)))
        return cache["traces"]


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """DECLARATIVE MULTI-TENANT trace batch: each stream is a mixture
    of tenants drawn per request from a shared tenant pool, with
    per-tenant arrival PROCESSES (Poisson / bursty / diurnal — the
    `thermal.rate_scenario` closed-form rows, evaluated by the same
    `ambient_at` machinery with base ~1.0 read as a rate multiplier).

    Rides the `SynthSpec` machinery end to end: `sim_engine.SimSpec`
    accepts one as its `traces` axis and fuses the synthesis INTO the
    replay dispatch (the spec is a hashable static jit arg), and the
    shard_map campaign path partitions `stream_knobs()` rows across
    devices exactly like `SynthSpec`.

    Pool axes ([K] tenants): `row_hits` / `write_fracs` /
    `inter_arrivals` are the `synth_trace` knobs of each tenant;
    `arrivals` holds each tenant's rate-scenario row ([K][SCN_COLS],
    or `thermal.ThermalScenario`s / "poisson"/"bursty"/"diurnal" kind
    strings, normalized at construction).  Stream axis ([T]): `mixes`
    is the [T][K] tenant-probability matrix (rows need not be
    normalized — the categorical draw normalizes), `offsets` the
    per-stream threefry fold ids (default: the stream index).

    Per stream, per request: a tenant is drawn from the mix, the
    request's locality/write knobs gather from its tenant, base
    exponential gaps scale by tenant `inter_arrivals`, and the gaps
    are then modulated by the tenant's rate scenario evaluated at the
    unmodulated cumulative time (rate 2x => half the gap), keeping the
    synthesis fully vectorized — no scan, so it fuses into the replay
    prologue."""

    n: int
    mixes: tuple
    row_hits: tuple[float, ...]
    write_fracs: tuple[float, ...]
    inter_arrivals: tuple[float, ...]
    arrivals: tuple = ("poisson",)
    offsets: tuple[int, ...] = ()
    seed: int = 0
    n_banks: int = 8
    n_rows: int = 4096

    def __post_init__(self):
        from repro.core import thermal
        k = len(self.row_hits)
        mixes = tuple(tuple(float(x) for x in m) for m in self.mixes)
        assert mixes and all(len(m) == k for m in mixes), \
            (len(mixes), k)
        rows = []
        for a in (self.arrivals if len(self.arrivals) > 1
                  else tuple(self.arrivals) * k):
            if isinstance(a, str):
                a = thermal.rate_scenario(a)
            if isinstance(a, thermal.ThermalScenario):
                a = a.as_row()
            rows.append(tuple(float(x) for x in np.asarray(a)))
        assert len(rows) == k, (len(rows), k)
        offsets = (tuple(range(len(mixes))) if not self.offsets
                   else tuple(int(o) for o in self.offsets))
        assert len(offsets) == len(mixes), (len(offsets), len(mixes))
        object.__setattr__(self, "mixes", mixes)
        object.__setattr__(self, "arrivals", tuple(rows))
        object.__setattr__(self, "offsets", offsets)
        for f in ("row_hits", "write_fracs", "inter_arrivals"):
            object.__setattr__(
                self, f, tuple(float(x) for x in getattr(self, f)))
            assert len(getattr(self, f)) == k, f
        object.__setattr__(self, "_cache", {})

    def __len__(self) -> int:
        return len(self.mixes)

    def stream_knobs(self):
        """PER-STREAM rows ([T]-leading) consumed by `synth_traced` —
        the tree a sharded campaign partitions across devices."""
        return (jnp.asarray(self.offsets, jnp.int32),
                jnp.asarray(self.mixes, jnp.float32))

    def synth_traced(self, knobs):
        """Synthesize the [t, n] `Trace` batch from (possibly sharded)
        traced `stream_knobs` rows; the tenant pool rides as static
        constants, so any shard synthesizes bit-identically to its
        slice of `synth()`."""
        from repro.core.thermal import ambient_at
        key = jax.random.PRNGKey(self.seed)
        rhs = jnp.asarray(self.row_hits, jnp.float32)
        wfs = jnp.asarray(self.write_fracs, jnp.float32)
        ias = jnp.asarray(self.inter_arrivals, jnp.float32)
        scn = jnp.asarray(self.arrivals, jnp.float32)   # [K, SCN_COLS]
        offs, mixes = knobs

        def one(off, mix):
            k = jax.random.fold_in(key, off)
            kt, kb, kr, kh, kw, ka = jax.random.split(k, 6)
            tenant = jax.random.categorical(
                kt, jnp.log(mix + 1e-9), shape=(self.n,))
            bank = jax.random.randint(kb, (self.n,), 0, self.n_banks)
            new_row = jax.random.randint(kr, (self.n,), 0, self.n_rows)
            reuse = jax.random.uniform(kh, (self.n,)) < rhs[tenant]
            row = _row_pick(bank, new_row, reuse, self.n_banks)
            is_write = jax.random.uniform(kw, (self.n,)) < wfs[tenant]
            gaps = jax.random.exponential(ka, (self.n,)) * ias[tenant]
            # rate modulation at the UNMODULATED cumulative time keeps
            # the generator closed-form (no gap->time recurrence)
            t0 = jnp.cumsum(gaps)
            rate = jax.vmap(ambient_at)(scn[tenant], t0)
            arrival = jnp.cumsum(gaps / jnp.maximum(rate, 0.05))
            return Trace(arrival, bank, row, is_write)

        return jax.vmap(one)(offs, mixes)

    def synth(self):
        """The in-dispatch synthesis prologue: [T, n] `Trace` batch as
        traced arrays (call under jit) — see `_runtime_knobs`."""
        return self.synth_traced(_runtime_knobs(self.stream_knobs()))

    def materialize(self) -> tuple[Trace, ...]:
        """Host-side tuple-of-`Trace`s view (one synthesis launch,
        cached on the instance)."""
        cache = self._cache
        if "traces" not in cache:
            from repro.core import perf_model          # lazy: no cycle
            perf_model.synth_dispatch_count += 1
            tb = jax.jit(self.synth)()
            fields = [np.asarray(f) for f in tb]
            cache["traces"] = tuple(
                Trace(*(f[i] for f in fields))
                for i in range(len(self)))
        return cache["traces"]


def zipf_cdf_u32(n_items: int, theta: float) -> np.ndarray:
    """Inverse-CDF table of Zipf(theta) over ranks 0..n_items-1 (rank i
    drawn with probability proportional to (i + 1)**-theta), in 32-bit
    fixed point: entry i = floor(2**32 * CDF(i)) for i < n_items - 1,
    computed in float64.  A uniform 32-bit draw u selects rank
    `searchsorted(table, u, side="right")` — integer arithmetic, the
    same on every device; each rank's probability is exact to 2**-32."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -float(theta)
    c = np.cumsum(w)
    return np.floor(c[:-1] / c[-1] * 2.0 ** 32).astype(np.uint32)


def fnv1a64_mod(x, n_items: int):
    """YCSB's key scramble of a rank x < 2**32 (uint32 array):
    `Math.abs(FNV-1a-64(x's 8 little-endian bytes)) % n_items`,
    computed exactly in uint32 (hi, lo) pairs, since a TPU has no
    64-bit integers.  `n_items` a power of two at most 2**32: the
    remainder is then the low bits of |h|, and the low 32 bits of -h
    are -lo."""
    u32 = jnp.uint32
    x = jnp.asarray(x).astype(u32)
    hi = jnp.full(x.shape, 0xCBF29CE4, u32)
    lo = jnp.full(x.shape, 0x84222325, u32)
    for i in range(8):
        if i < 4:
            lo = lo ^ ((x >> u32(8 * i)) & u32(0xFF))
        # h * (2**40 + 0x1B3) mod 2**64, with lo * 0x1B3 split in 16-bit
        # halves so that its carry into hi is exact
        a = (lo & u32(0xFFFF)) * u32(0x1B3)
        b = (lo >> u32(16)) * u32(0x1B3)
        s = (b & u32(0xFFFF)) << u32(16)
        low = s + a
        carry = (b >> u32(16)) + (low < s).astype(u32)
        hi = hi * u32(0x1B3) + carry + (lo << u32(8))
        lo = low
    neg = hi >= u32(0x80000000)
    return jnp.where(neg, u32(0) - lo, lo) & u32(n_items - 1)


# The address map of `KVSpec`'s DDR4 channel: bit fields of a line
# address (the byte address >> 6), low to high.  A 1 KB record slot is
# 16 line addresses: 8 in each of 2 bank groups, one row.
KV_ADDRESS_BITS = (("col_low", 3), ("group", 2), ("col_high", 4),
                   ("bank", 2), ("rank", 1), ("row", 16))


def kv_address(line_addr):
    """(rank, group, bank, row, column) of int32 line addresses under
    `KV_ADDRESS_BITS`; the column is col_high * 8 + col_low."""
    f, shift = {}, 0
    for name, bits in KV_ADDRESS_BITS:
        f[name] = (line_addr >> shift) & ((1 << bits) - 1)
        shift += bits
    return (f["rank"], f["group"], f["bank"], f["row"],
            f["col_high"] * 8 + f["col_low"])


@dataclasses.dataclass(frozen=True)
class KVSpec:
    """DECLARATIVE key-value trace batch: YCSB core workload A (Cooper
    et al., SoCC 2010) on one dual-rank DDR4 channel, fused into the
    replay dispatch like `SynthSpec`.  Per stream (threefry key folded
    with the stream index):

      * operations arrive open loop, Poisson with mean `op_interval_ns`;
      * each reads one record, and with probability `update_frac`
        then writes one of its 10 fields of 100 bytes back;
      * the record's rank is Zipf(`theta`) by the inverse CDF
        (`zipf_cdf_u32`, passed in as a device input), scrambled to a
        slot of `n_records` as YCSB's ScrambledZipfianGenerator does
        (`fnv1a64_mod`);
      * slot s holds bytes [1024 s, 1024 s + 1024): a read is its 16
        lines, all arriving at the operation's time; an update adds
        writes of the 2 lines from the one holding the field's first
        byte;
      * line addresses map to (rank, group, bank, row, column) by
        `KV_ADDRESS_BITS`; the `Trace` carries bank = group * 4 + bank
        and row = (row << 1) | rank, so `chan_rank` on one channel of
        2 ranks recovers the rank.

    Operations are drawn n // 16 + 1 per stream (each has at least 16
    lines) and the first `n` lines kept."""

    n: int
    n_streams: int
    seed: int = 0
    update_frac: float = 0.5
    theta: float = 0.99
    n_records: int = 1 << 24
    op_interval_ns: float = 100.0

    # the channel the address map describes
    n_ranks = 2
    bank_groups = 4
    n_banks = 16
    LINES = 16            # 64 B lines of a 1 KB record slot
    WRITE_LINES = 2
    FIELDS = 10
    FIELD_BYTES = 100

    def __post_init__(self):
        rec = int(self.n_records)
        if rec & (rec - 1) or not 2 <= rec <= 1 << 24:
            raise ValueError(f"n_records must be a power of two in "
                             f"[2, 2**24], got {rec}")
        object.__setattr__(self, "_cache", {})

    def __len__(self) -> int:
        return self.n_streams

    @property
    def n_ops(self) -> int:
        """Operations drawn per stream."""
        return self.n // self.LINES + 1

    def stream_knobs(self):
        """Per-stream threefry fold ids [T]."""
        return (jnp.arange(self.n_streams, dtype=jnp.int32),)

    def device_inputs(self) -> tuple:
        """The Zipf table the synthesis reads, on the device (made and
        copied once per spec)."""
        cache = self._cache
        if "cdf" not in cache:
            cache["cdf"] = jnp.asarray(zipf_cdf_u32(self.n_records,
                                                    self.theta))
        return (cache["cdf"],)

    def _draws(self, off):
        """(Zipf uniforms u32, update flags, field ids, operation
        arrival times) [n_ops] of stream `off`."""
        m = self.n_ops
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), off)
        kz, ku, kf, ka = jax.random.split(key, 4)
        u = jax.random.bits(kz, (m,), jnp.uint32)
        update = jax.random.uniform(ku, (m,)) < self.update_frac
        field = jax.random.randint(kf, (m,), 0, self.FIELDS)
        t_op = jnp.cumsum(jax.random.exponential(ka, (m,))
                          * self.op_interval_ns)
        return u, update, field, t_op

    def _op_ends(self, update):
        lens = self.LINES + self.WRITE_LINES * update.astype(jnp.int32)
        return jnp.cumsum(lens), lens

    def synth_traced(self, knobs, cdf):
        (offs,) = knobs
        i32 = jnp.int32

        def one(off):
            u, update, field, t_op = self._draws(off)
            slot = fnv1a64_mod(jnp.searchsorted(cdf, u, side="right"),
                               self.n_records).astype(i32)
            ends, lens = self._op_ends(update)
            starts = ends - lens

            def per_line(v):
                """int32 per-operation values v, each on its
                operation's lines: the steps between operations
                scattered at their first lines, then a running sum
                (exact; for 128 streams of 16384 lines on a TPU v5e a
                gather of one index per line took ~21 ms, a search of
                the operation ends per line ~235 ms)."""
                steps = jnp.diff(v, prepend=jnp.zeros((1,), i32))
                return jnp.cumsum(jnp.zeros((self.n,), i32).at[starts].add(
                    steps, mode="drop"))

            p = jnp.arange(self.n, dtype=i32) - per_line(starts)
            rec = per_line(slot * self.FIELDS + field)
            t = jax.lax.bitcast_convert_type(
                per_line(jax.lax.bitcast_convert_type(t_op, i32)),
                jnp.float32)
            is_write = p >= self.LINES           # p: line of the operation
            first = rec % self.FIELDS * self.FIELD_BYTES // 64
            line = jnp.where(is_write, first + p - self.LINES, p)
            rank, group, bank, row, _ = kv_address(
                rec // self.FIELDS * self.LINES + line)
            return Trace(t, group * 4 + bank, row * 2 + rank, is_write)

        return jax.vmap(one)(offs)

    def synth(self, cdf):
        """The in-dispatch synthesis prologue: [T, n] `Trace` batch
        (call under jit with `device_inputs()`)."""
        return self.synth_traced(_runtime_knobs(self.stream_knobs()), cdf)

    def op_count(self) -> int:
        """YCSB operations with at least one line among the first `n`
        of their stream, summed over the streams (one small launch,
        cached on the spec)."""
        cache = self._cache
        if "ops" not in cache:
            def count(off):
                ends, lens = self._op_ends(self._draws(off)[1])
                return jnp.sum(ends - lens < self.n)

            cache["ops"] = int(jax.jit(jax.vmap(count))(
                *self.stream_knobs()).sum())
        return cache["ops"]

    def materialize(self) -> tuple[Trace, ...]:
        """Host-side tuple-of-`Trace`s view (one synthesis launch,
        cached on the instance)."""
        cache = self._cache
        if "traces" not in cache:
            from repro.core import perf_model          # lazy: no cycle
            perf_model.synth_dispatch_count += 1
            tb = jax.jit(self.synth)(*self.device_inputs())
            fields = [np.asarray(f) for f in tb]
            cache["traces"] = tuple(
                Trace(*(f[i] for f in fields))
                for i in range(len(self)))
        return cache["traces"]


# the declarative trace-axis types `sim_engine.SimSpec` accepts and
# fuses into the replay dispatch
SYNTH_SPECS = (SynthSpec, TenantSpec, KVSpec)


def check_prefix_valid(valid, where: str = "replay"):
    """Enforce the padding-suffix invariant every replay layout's ring
    gate depends on: each trace's `valid` mask must be True on a
    prefix and False on the suffix.  Interior-invalid requests would
    silently desynchronize the bounded-MLP completion gate (the Pallas
    kernel indexes its ring by the loop counter; the scans skip the
    slot but keep counting), so they are rejected loudly here.  Traced
    (jit-abstract) masks skip the check — the engine validates the
    concrete mask before handing it to a jitted dispatch."""
    if isinstance(valid, jax.core.Tracer):
        return
    v = np.asarray(valid, bool).reshape(-1, np.shape(valid)[-1])
    cnt = v.sum(-1)
    idx = np.arange(v.shape[-1])
    bad = (v != (idx[None, :] < cnt[:, None])).any(-1)
    if bad.any():
        t = int(np.argmax(bad))
        first_gap = int(np.argmin(v[t])) if not v[t].all() else -1
        raise ValueError(
            f"{where}: `valid` must be a prefix-true mask (padding "
            f"strictly a suffix) — trace row {t} has {int(cnt[t])} "
            f"valid requests but an invalid slot at index {first_gap} "
            "is followed by valid ones. Compact each trace before "
            "packing (the ring gate of the replay kernels counts "
            "requests positionally).")


def frfcfs_order(trace: Trace, window: int, slack_ns: float = 30.0,
                 max_defer: int | None = None) -> np.ndarray:
    """Issue-order permutation of the FR-FCFS-lite Python reference:
    greedily issue, among the next `window` pending requests, the
    oldest one hitting the currently open row of its bank (else the
    oldest request).  A candidate is promoted only when it arrives
    within `slack_ns` of the head request (a hit that is still in
    flight costs more to wait for than the conflict it avoids), and a
    starvation cap forces the head out after `max_defer` consecutive
    deferrals.

    All horizon arithmetic is float32 so the device formulation
    (`frfcfs_perm`) can match it request-for-request.
    """
    arrival = np.asarray(trace.arrival, np.float32)
    bank = np.asarray(trace.bank)
    row = np.asarray(trace.row)
    n = arrival.shape[0]
    cap = 4 * window if max_defer is None else max_defer
    slack = np.float32(slack_ns)
    order = np.empty(n, np.int64)
    open_row: dict[int, int] = {}
    pend = list(range(n))
    defer = 0
    for k in range(n):
        pick = 0
        if defer < cap:
            horizon = np.float32(arrival[pend[0]] + slack)
            for j in range(min(window, len(pend))):
                idx = pend[j]
                if (arrival[idx] <= horizon and
                        open_row.get(int(bank[idx]), -1) == int(row[idx])):
                    pick = j
                    break
        idx = pend.pop(pick)
        defer = defer + 1 if pick > 0 else 0
        open_row[int(bank[idx])] = int(row[idx])
        order[k] = idx
    return order


# Host-reorder results cached across `SimSpec.pack()` calls: repeated
# campaigns over the same traces (benchmark repeats, profile-then-replay
# pipelines) pay the O(N*window) Python prepass once.  Keyed on a
# CONTENT digest of the trace's request fields plus the policy knobs —
# keying on array identity (id()) would return a stale permutation
# after an in-place mutation (same object, new contents), and a GC'd
# id can even be reused by an unrelated array.
_REORDER_CACHE: "dict[tuple, Trace]" = {}
_REORDER_CACHE_MAX = 128


def _trace_digest(trace: Trace) -> bytes:
    """Content digest of every request field (the issue order depends
    on arrival, bank AND row; is_write rides along for completeness)."""
    h = hashlib.blake2b(digest_size=16)
    for f in trace:
        a = np.ascontiguousarray(np.asarray(f))
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.digest()


def frfcfs_reorder(trace: Trace, window: int, slack_ns: float = 30.0,
                   max_defer: int | None = None) -> Trace:
    """FR-FCFS-lite host-side preprocessing (see `frfcfs_order`):
    requests keep their arrival timestamps, only issue order changes.
    Results are cached across calls keyed on (trace content digest,
    window, slack, cap), so mutating a trace's arrays in place yields
    a fresh reorder instead of a stale cached permutation."""
    if window <= 1:
        return trace
    key = (_trace_digest(trace), window, float(slack_ns), max_defer)
    hit = _REORDER_CACHE.get(key)
    if hit is not None:
        # refresh the LRU position: dicts keep re-assigned keys at
        # their ORIGINAL insertion slot, so pop + re-insert
        _REORDER_CACHE.pop(key)
        _REORDER_CACHE[key] = hit
        return hit
    order = frfcfs_order(trace, window, slack_ns, max_defer)
    fields = []
    for f in trace:
        a = np.asarray(f)[order]
        # the cached entry is shared across hits: freeze it so an
        # in-place mutation of a RETURNED trace raises instead of
        # silently poisoning later equal-content lookups
        a.flags.writeable = False
        fields.append(a)
    out = Trace(*fields)
    while len(_REORDER_CACHE) >= _REORDER_CACHE_MAX:
        _REORDER_CACHE.pop(next(iter(_REORDER_CACHE)))
    _REORDER_CACHE[key] = out
    return out


def frfcfs_perm(arrival, bank, row, valid, window, slack_ns, cap,
                max_window: int, n_banks: int = 8):
    """Device formulation of `frfcfs_order`: the issue-order
    permutation [N] (int32) of one padded request stream, computed by a
    `lax.scan` whose carry holds the first `max_window` PENDING
    requests (the only candidates FR-FCFS-lite ever promotes), the
    per-bank open rows, and the starvation counter.  O(N * max_window)
    vector work instead of the O(N * window) Python loop, and it vmaps
    over the (trace x policy) axes of a campaign so the reorder runs as
    a prepass INSIDE the replay dispatch.

    `window`, `slack_ns` and `cap` are traced scalars (per-policy
    columns of a batched campaign); `max_window` is the static buffer
    size (>= every policy's window, <= N).  `window <= 1` degenerates
    to the identity permutation, which is how closed-page and FCFS
    policies ride the same dispatch.  Padding (`valid` False) must be a
    suffix: padded slots are never promoted, so they drain in order
    after the last real request — exactly the Python reference applied
    to the unpadded prefix.
    """
    n = arrival.shape[0]
    w = max_window
    slots = jnp.arange(w, dtype=jnp.int32)
    slack = jnp.asarray(slack_ns, jnp.float32)
    state0 = (arrival[:w], bank[:w], row[:w], valid[:w],
              jnp.arange(w, dtype=jnp.int32),
              jnp.full((n_banks,), -1, jnp.int32),     # open rows
              jnp.zeros((), jnp.int32),                # defer counter
              jnp.asarray(w, jnp.int32))               # next refill

    def step(st, _):
        a_buf, b_buf, r_buf, v_buf, i_buf, open_row, defer, nxt = st
        hit = open_row[b_buf] == r_buf
        horizon = a_buf[0] + slack
        elig = (hit & (a_buf <= horizon) & v_buf & (slots < window))
        promo = elig.any() & (defer < cap)
        pick = jnp.where(promo, jnp.argmax(elig), 0).astype(jnp.int32)
        out = i_buf[pick]
        open_row = open_row.at[b_buf[pick]].set(r_buf[pick])
        defer = jnp.where(pick > 0, defer + 1, 0)
        # shift the buffer left past the picked slot; the freed last
        # slot refills from the stream (sentinel once it runs dry)
        nxt_c = jnp.minimum(nxt, n - 1)
        src = jnp.where(slots >= pick, slots + 1, slots)

        def shift(buf, fill):
            return jnp.concatenate([buf, fill[None]])[src]

        st2 = (shift(a_buf, arrival[nxt_c]), shift(b_buf, bank[nxt_c]),
               shift(r_buf, row[nxt_c]),
               shift(v_buf, valid[nxt_c] & (nxt < n)),
               shift(i_buf, nxt_c), open_row, defer, nxt + 1)
        return st2, out

    _, perm = jax.lax.scan(step, state0, None, length=n)
    return perm


# Rows per subarray (DDR3 512x512 mats): consecutive row addresses sit
# at consecutive physical positions within a subarray, so the region of
# a row is its position stripe — the SAME contiguous position->region
# mapping `MarginEngine.sweep` reduces tail cells under, which is what
# makes the profiled region rows valid for the replayed address stream.
SUBARRAY_ROWS = 512


def region_of(row, regions: int):
    """Subarray region id of a row address: which of `regions` equal
    position stripes the row's within-subarray offset falls in.  Exact
    contiguous nesting across resolution levels (l | R implies
    `region_of(r, l) == region_of(r, R) // (R // l)`), so one R-region
    table answers every coarser level by integer division.  `regions`
    is static."""
    r_i = row.astype(jnp.int32) if row.dtype != jnp.int32 else row
    return (r_i % SUBARRAY_ROWS) * regions // SUBARRAY_ROWS


class BankState(NamedTuple):
    """Controller state shared by the static and adaptive scans."""

    open_row: jnp.ndarray      # [B] (-1 = precharged)
    act_time: jnp.ndarray      # [B] last ACT issue time
    wr_done: jnp.ndarray       # [B] time last write recovery ends
    ready: jnp.ndarray         # [B] bank ready for next command
    done_ring: jnp.ndarray     # [W] completion times, ring buffer
    idx: jnp.ndarray           # scalar request counter


def _bank_state0(n_banks: int, mlp_window: int) -> BankState:
    return BankState(open_row=jnp.full((n_banks,), -1, jnp.int32),
                     act_time=jnp.zeros((n_banks,)),
                     wr_done=jnp.zeros((n_banks,)),
                     ready=jnp.zeros((n_banks,)),
                     done_ring=jnp.zeros((mlp_window,)),
                     idx=jnp.zeros((), jnp.int32))


class BankGroupError(NotImplementedError):
    """A replay path that does not carry the rank and bank-group state
    (`SimSpec.rank_timings`) was asked to replay a spec that has it.
    Raised instead of replaying without the constraints."""


# The time of a command never issued: so far in the past that no
# rank or bank-group constraint measured from it can bind.
NEVER = -1e30


def split_chan(chan):
    """(n_channels, n_ranks, t_burst, groups) of a static channel
    geometry: `SimSpec.chan`'s (C, R, t_burst) or, with the rank and
    bank-group state on, (C, R, t_burst, groups) where groups =
    (n_groups, trrd_s, trrd_l, tfaw, tccd_s, tccd_l)."""
    n_ch, n_rk, t_burst, *rest = chan
    return n_ch, n_rk, t_burst, (tuple(rest[0]) if rest else None)


def service_math(t, gate, open_b, act_b, wrd_b, rdy_b, rf, w, trcd,
                 tras, twr, trp, tcl, closed, rank=None):
    """The per-request timing arithmetic on ALREADY-GATHERED bank
    state — pure elementwise jnp, shared verbatim by the three replay
    layouts (`_service`'s scalar gathers, `replay_rows`' timing-row
    lane vectors, the Pallas kernel's [banks, lanes] tiles), so the
    timing model lives in exactly one place and their bit-identical
    contract is structural rather than copy-discipline.

    `open_b`/`rf` carry the open-row id in the caller's dtype (int32
    or float32 — exact for row ids below 2**24; -1 = precharged).
    Returns (row_latched, act_new, wr_done_new, ready_new, done,
    latency, is_hit).  Latency is measured from *eligibility* (the
    closed-loop gate), not the nominal trace timestamp — under
    saturation the backlog belongs to the CPU-side stall model, not
    to each DRAM access.

    `rank` (optional) adds the rank and bank-group constraints of a
    DDR4-style device: ((rank_act, group_act, faw_act, rank_col,
    group_col, bus), (trrd_s, trrd_l, tfaw, tccd_s, tccd_l)) for a
    request to rank k, bank group g — rank k's last ACT, group (k, g)'s
    last ACT, rank k's fourth-last ACT, rank k's and group (k, g)'s
    last column command (`NEVER` before the first), and the time the
    channel's data bus frees (`NEVER` on one rank of one channel; the
    caller then leaves it out of `gate`).  Then

      ACT    = max(candidate, rank_act + tRRD_S, group_act + tRRD_L,
                   faw_act + tFAW)
      column = max(ACT + tRCD (a hit: start), bus, rank_col + tCCD_S,
                   group_col + tCCD_L)
      done   = column + tCL

    where the candidate is the ACT the bank alone allows (start for an
    empty bank, conflict start + tRP for a conflict).  So the bus holds
    back the column command, not the next request's ACT, and ACTs are
    issued in request order (FCFS) as the constraints allow.  An open
    bank is ready for its next command at this column, not at `done`:
    column commands to an open row pipeline, tCCD_L apart (a bank is
    in its own group), and a conflict may precharge from the column on
    (no tRTP).  The returns gain (column, stall): stall is the
    column's delay past max(the column the bank alone allows, bus),
    exactly 0 where no constraint binds (the same float operations
    then give the same column).  The caller updates the rank state
    after every request, and counts completions into the runtime
    itself, since an open bank's `ready` no longer covers its last
    data."""
    start = jnp.maximum(jnp.maximum(t, rdy_b), gate)
    is_hit = open_b == rf
    is_empty = open_b == -1
    # conflict: precharge may start only after tRAS from ACT and
    # after write recovery completes
    pre_ok = jnp.maximum(act_b + tras, wrd_b)
    conflict_start = jnp.maximum(start, pre_ok)
    act_new = jnp.where(
        is_hit, act_b,
        jnp.where(is_empty, start + 0.0, conflict_start + trp))
    data_start = jnp.where(
        is_hit, start,
        jnp.where(is_empty, start + trcd, conflict_start + trp + trcd))
    if rank is not None:
        (r_act, g_act, f_act, r_col, g_col, bus), (trrd_s, trrd_l, tfaw,
                                                   tccd_s, tccd_l) = rank
        act_at = jnp.maximum(jnp.maximum(act_new, r_act + trrd_s),
                             jnp.maximum(g_act + trrd_l, f_act + tfaw))
        act_new = jnp.where(is_hit, act_b, act_at)
        bank_col = jnp.maximum(data_start, bus)
        col = jnp.maximum(
            jnp.maximum(jnp.where(is_hit, start, act_new + trcd), bus),
            jnp.maximum(r_col + tccd_s, g_col + tccd_l))
        stall = col - bank_col
        data_start = col
    done = data_start + tcl
    wrd_new = jnp.where(w, done + twr, wrd_b)
    # closed-page: auto-precharge after the burst — the row is never
    # left open and the bank re-opens only after the precharge
    # (which itself waits out tRAS-from-ACT and write recovery)
    pre_start = jnp.maximum(jnp.maximum(done, act_new + tras), wrd_new)
    # with the rank state an open bank takes its next column command
    # once this one has issued (tCCD_L of its group then spaces them);
    # without it, once this one's data is done
    ready_new = jnp.where(closed, pre_start + trp,
                          done if rank is None else data_start)
    row_latched = jnp.where(closed, jnp.full_like(rf, -1), rf)
    out = (row_latched, act_new, wrd_new, ready_new, done,
           done - jnp.maximum(t, gate), is_hit)
    return out if rank is None else out + (data_start, stall)


class RankState(NamedTuple):
    """Rank and bank-group controller state of a geometry with
    `rank_timings` (C*R ranks of G groups each), shared by the scalar
    (`replay_one`) and lane-major (`replay_rows`) scans; a trailing
    lane axis (S,) rides every field in the latter."""

    act: jnp.ndarray       # [CR(, S)] each rank's last ACT
    col: jnp.ndarray       # [CR(, S)] each rank's last column command
    g_act: jnp.ndarray     # [CR*G(, S)] each bank group's last ACT
    g_col: jnp.ndarray     # [CR*G(, S)] each bank group's last column
    faw: jnp.ndarray       # [CR, 4(, S)] each rank's last four ACTs
    ptr: jnp.ndarray       # [CR(, S)] int32 ring slot of the oldest
    stall: jnp.ndarray     # [(S)] summed constraint stall, ns


def _rank_state0(n_ranks: int, n_groups: int, lanes=()) -> RankState:
    never = jnp.full((n_ranks,) + lanes, NEVER)
    return RankState(act=never, col=never,
                     g_act=jnp.full((n_ranks * n_groups,) + lanes, NEVER),
                     g_col=jnp.full((n_ranks * n_groups,) + lanes, NEVER),
                     faw=jnp.full((n_ranks, 4) + lanes, NEVER),
                     ptr=jnp.zeros((n_ranks,) + lanes, jnp.int32),
                     stall=jnp.zeros(lanes))


def rank_index(b, ch, rk, n_ranks: int, n_banks: int, n_groups: int):
    """(global rank, global bank group) of a request to rank-level
    bank `b` (group b // (n_banks // n_groups)) on channel `ch`,
    rank `rk`."""
    k = ch * n_ranks + rk
    return k, k * n_groups + b // (n_banks // n_groups)


def _rank_view(rs: RankState, k, g):
    """((rank_act, group_act, faw_act, rank_col, group_col) of rank k,
    group g — `service_math(rank=...)`'s state but the bus — and the
    one-hot ring slot of rank k's fourth-last ACT)."""
    ring = (jnp.arange(4).reshape((4,) + (1,) * rs.stall.ndim)
            == rs.ptr[k])
    f_act = jnp.sum(jnp.where(ring, rs.faw[k], 0.0), axis=0)
    return (rs.act[k], rs.g_act[g], f_act, rs.col[k], rs.g_col[g]), ring


def _rank_update(rs: RankState, k, g, ring, v, is_hit, act_new, col,
                 stall) -> RankState:
    """The rank state after a request (`v` False: padding, unchanged):
    an ACT (any miss) becomes the rank's and the group's last and
    replaces the rank's fourth-last in the ring; the column becomes
    the rank's and the group's last."""
    acted = v & ~is_hit

    def put(x, i, new, when):
        return x.at[i].set(jnp.where(when, new, x[i]))

    return RankState(
        act=put(rs.act, k, act_new, acted),
        col=put(rs.col, k, col, v),
        g_act=put(rs.g_act, g, act_new, acted),
        g_col=put(rs.g_col, g, col, v),
        faw=put(rs.faw, k, act_new, ring & acted),
        ptr=put(rs.ptr, k, jnp.where(rs.ptr[k] == 3, 0, rs.ptr[k] + 1),
                acted),
        stall=rs.stall + jnp.where(v, stall, 0.0))


def _service(s: BankState, t, b, r, w, trcd, tras, twr, trp, tcl,
             closed, mlp_window: int, extra_gate=None, surcharge=None,
             rank=None):
    """Service ONE request: gathers bank `b`'s state, applies
    `service_math`, scatters the update back.  Shared bit-for-bit
    between `replay_one` (timing scalars fixed for the whole trace)
    and `replay_adaptive` (timing scalars gathered from the in-scan
    bin selection).  `extra_gate` (optional) is max'd into the MLP
    ring gate — the per-channel bus-occupancy gate of multi-channel
    replays; None keeps the single-channel arithmetic untouched.
    `surcharge` (optional) is a traced delay added to the request's
    completion, latency and downstream readiness — the detected-error
    retry price of `repro.core.faults` (the bank stays busy through
    the JEDEC re-issue); None keeps the fault-free arithmetic
    untouched.  `rank` (optional) is the gathered rank state of
    `service_math(rank=...)`; the returns then gain (ACT time, column
    time, constraint stall) for the caller's rank-state update.
    Returns (next state, raw latency, row-hit flag, completion
    time)."""
    gate = s.done_ring[s.idx % mlp_window]     # i-window completion
    if extra_gate is not None:
        gate = jnp.maximum(gate, extra_gate)
    args = (t, gate, s.open_row[b], s.act_time[b], s.wr_done[b],
            s.ready[b], r, w, trcd, tras, twr, trp, tcl, closed)
    res = service_math(*args) if rank is None else service_math(*args,
                                                                rank)
    row_latched, act_new, wrd_new, ready_new, done, lat, is_hit = res[:7]
    if surcharge is not None:
        done = done + surcharge
        lat = lat + surcharge
        wrd_new = jnp.where(w, wrd_new + surcharge, wrd_new)
        ready_new = ready_new + surcharge
    s2 = BankState(open_row=s.open_row.at[b].set(row_latched),
                   act_time=s.act_time.at[b].set(act_new),
                   wr_done=s.wr_done.at[b].set(wrd_new),
                   ready=s.ready.at[b].set(ready_new),
                   done_ring=s.done_ring.at[s.idx % mlp_window].set(done),
                   idx=s.idx + 1)
    if rank is None:
        return s2, lat, is_hit, done
    return s2, lat, is_hit, done, (act_new,) + res[7:]


def replay_one(arrival, bank, row, is_write, valid, tp_row, closed,
               n_banks: int = 8, mlp_window: int = 8,
               n_channels: int = 1, n_ranks: int = 1, ileave=None,
               t_burst: float = 5.0, fault=None, region_map=None,
               groups=None):
    """Replay one trace under one stacked timing row and page policy.

    arrival/bank/row/is_write: [N] request stream; `valid`: [N] mask
    (False entries are padding — they leave the controller state and
    the latency statistics untouched, so differently sized traces can
    share one batched grid).  `tp_row`: [6] `TimingParams.as_row`, or
    [banks, 6] PER-BANK rows (FLY-DRAM-style spatial tables): each
    request is then serviced with ITS bank's row, gathered in-scan.
    A [banks, 6] input whose rows are all equal replays bit-identical
    to the [6] path (same values feed the same `_service` arithmetic).
    `closed`: scalar bool (auto-precharge page policy).  Returns
    (per-request latency [N] with zeros at padding, total runtime).

    `mlp_window` models the CPU's bounded memory-level parallelism as a
    closed loop: request i cannot issue before request i-window
    completed (an out-of-order core stalls once its miss buffers fill),
    which keeps the queue bounded instead of saturating open-loop.

    With `n_channels`/`n_ranks` > 1 the carried controller state holds
    C*R*B independent bank FSMs — each request maps to a (channel,
    rank) via `chan_rank(ileave)` IN-SCAN — plus a per-channel
    bus-free time: a request's issue is additionally gated on its
    channel's data bus (busy for `t_burst` ns from each data-burst
    start), which is how per-channel queue contention is priced at
    zero extra dispatches.  Per-bank timing rows stay keyed on the
    ORIGINAL [0, n_banks) bank id (the spatial table is per rank-level
    bank).  `n_channels == n_ranks == 1` is a static branch that keeps
    the single-channel arithmetic bit-identical.

    `fault` (optional, STATIC branch — None compiles the exact
    fault-free path) is a `(fault_row [faults.F_COLS], jedec_row [6],
    u [N])` triple: each request then draws a margin-conditioned
    transient-error outcome from its issue-order uniform (detected
    errors retry at the JEDEC tCL + `retry_ns`, priced via
    `_service(surcharge=...)`), and a per-module watchdog degrades to
    the JEDEC row on a tripped detected-error budget (see
    `repro.core.faults`).  Returns then gain a third element: the
    [faults.N_COUNTERS] int32 counter vector (detected, silent,
    trips, degraded, probes).

    `region_map` (optional, int32 [banks * regions]) switches `tp_row`
    to the MASK-COMPRESSED finer-than-bank layout
    (`aldram.TimingTable`): tp_row is then the [U, 6] unique-row store
    and each request gathers row `region_map[bank * regions +
    region_of(row, regions)]` in-scan — the request's subarray region
    resolves to a unique store row through the index map.  `regions`
    is derived from the map length; `regions == 1` with the identity
    map and U == banks feeds the exact per-bank gather arithmetic.

    `groups` (optional, STATIC: (n_groups, trrd_s, trrd_l, tfaw,
    tccd_s, tccd_l)) adds the rank and bank-group constraints of
    `service_math(rank=...)`: the carry gains a `RankState` over the
    C*R ranks, a request's group is its rank-level bank // (n_banks
    // n_groups), and the returns gain the summed constraint stall.
    None compiles the exact path without them."""
    banked = tp_row.ndim == 2
    multi = n_channels * n_ranks > 1
    faulted = fault is not None
    regioned = region_map is not None
    grouped = groups is not None
    if grouped and (faulted or regioned):
        raise BankGroupError("replay_one: no fault or region axis with "
                             "the rank and bank-group state")
    if regioned:
        assert banked, "region_map requires a [U, 6] unique-row store"
        n_regions = region_map.shape[0] // n_banks
        assert region_map.shape[0] == n_banks * n_regions
    if not banked:
        trcd, tras, twr, trp, tcl = (tp_row[0], tp_row[1], tp_row[2],
                                     tp_row[3], tp_row[5])
    if multi:
        il = jnp.asarray(0 if ileave is None else ileave, jnp.int32)
    if faulted:
        f_row, j_row, u_arr = fault
        j6 = (j_row[0], j_row[1], j_row[2], j_row[3], j_row[5])
        jsum = j_row[0] + j_row[1] + j_row[2] + j_row[3]

    def step(carry, req):
        if faulted:
            carry, wd, cnt = carry
            t, b, r, w, v, u_k = req
        else:
            t, b, r, w, v = req
        if grouped:
            carry, rs = carry
        s, cf = carry if multi else (carry, None)
        if multi:
            ch, rk = chan_rank(b, r, il, n_channels, n_ranks, n_banks)
            gb = (ch * n_ranks + rk) * n_banks + b
            eg = cf[ch]
        else:
            ch, rk = 0, 0
            gb, eg = b, None
        if grouped:
            k, grp = rank_index(b, ch, rk, n_ranks, n_banks, groups[0])
            rk_in, ring = _rank_view(rs, k, grp)
        if regioned:
            g = b * n_regions + region_of(r, n_regions)
            tb = tp_row[region_map[g]]
            tc6 = (tb[0], tb[1], tb[2], tb[3], tb[5])
        elif banked:
            tb = tp_row[b]
            tc6 = (tb[0], tb[1], tb[2], tb[3], tb[5])
        else:
            tc6 = (trcd, tras, twr, trp, tcl)
        if faulted:
            is_probe, use_agg = faults.wd_gate(f_row, wd)
            tc6 = tuple(jnp.where(use_agg, a, jb)
                        for a, jb in zip(tc6, j6))
            red = jnp.maximum(
                1.0 - (tc6[0] + tc6[1] + tc6[2] + tc6[3]) / jsum, 0.0)
            p = faults.error_prob(f_row, red, 0.0)
            _, det, sil = faults.error_draw(f_row, u_k, p)
            sur = jnp.where(det, j6[4] + f_row[faults.RETRY_NS], 0.0)
        else:
            sur = None
        if grouped:
            # the channel bus gates the column, not the request's start
            rank = (rk_in + (NEVER if eg is None else eg,), groups[1:])
            eg = None
        s2, lat, is_hit, done, *aux = _service(
            s, t, gb, r, w, tc6[0], tc6[1], tc6[2], tc6[3], tc6[4],
            closed, mlp_window, extra_gate=eg, surcharge=sur,
            rank=rank if grouped else None)
        if multi:
            # the channel data bus is busy for t_burst from the burst
            # start (done - tCL): later requests on this channel wait
            c2 = (s2, cf.at[ch].set(done - tc6[4] + t_burst))
            c1 = (s, cf)
        else:
            c2, c1 = s2, s
        if grouped:
            c2 = (c2, _rank_update(rs, k, grp, ring, v, is_hit, *aux[0]))
            c1 = (c1, rs)
        # padding: keep every state component as-is and emit zero latency
        c3 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(v, new, old), c2, c1)
        if faulted:
            degraded = wd[4] > 0
            wd2, new_trip = faults.wd_update(f_row, wd, det, False,
                                            is_probe)
            wd2 = jax.tree_util.tree_map(
                lambda new, old: jnp.where(v, new, old), wd2, wd)
            cnt2 = faults.counter_update(cnt, v, det, sil, new_trip,
                                         degraded, is_probe)
            return (c3, wd2, cnt2), jnp.where(v, lat, 0.0)
        return c3, jnp.where(v, lat, 0.0)

    s0 = _bank_state0(n_channels * n_ranks * n_banks, mlp_window)
    carry0 = (s0, jnp.zeros((n_channels,))) if multi else s0
    if grouped:
        carry0 = (carry0, _rank_state0(n_channels * n_ranks, groups[0]))
    xs = (arrival, bank, row, is_write, valid)
    if faulted:
        carry0 = (carry0, faults.wd_state0(),
                  tuple(jnp.zeros((), jnp.int32)
                        for _ in range(faults.N_COUNTERS)))
        xs = xs + (u_arr,)
    c_end, lat = jax.lax.scan(step, carry0, xs)
    if faulted:
        c_end, _, cnt_end = c_end
    if grouped:
        c_end, rs_end = c_end
    s_end = c_end[0] if multi else c_end
    # runtime includes the trailing write-recovery window: the module is
    # busy until the last write has restored, not just until last data
    total = jnp.maximum(s_end.ready.max(), s_end.wr_done.max())
    if faulted:
        return lat, total, jnp.stack(cnt_end)
    if grouped:
        # the latest completion is among the last mlp_window (each
        # request issues after the one mlp_window before completed)
        return (lat, jnp.maximum(total, s_end.done_ring.max()),
                rs_end.stall)
    return lat, total


def replay_rows(arrival, bank, row, is_write, valid, timings, closed,
                n_banks: int = 8, mlp_window: int = 8,
                n_channels: int = 1, n_ranks: int = 1, ileave=None,
                t_burst: float = 5.0, fault=None, region_map=None,
                groups=None):
    """Replay one trace under a whole [S, 6] STACK of timing rows in
    one `lax.scan` — the timing-row axis rides the minor (lane) axis
    of the carried bank state ([B, 4, S] packed as open-row/act/
    wr-done/ready, done-ring [W, S]) instead of an outer vmap, so the
    per-request bank gather/scatter and the one-hot request masks are
    paid once per (trace, policy) step rather than once per timing
    row.  ~4x faster than `vmap(replay_one)` over rows on CPU and the
    same layout the Pallas replay kernel uses on TPU; bit-identical to
    `replay_one` per row (same `_service` arithmetic, same operation
    order — the open row is carried as float32, exact for row ids
    below 2**24).

    `timings` may also be a PER-BANK stack [S, banks, 6]: each
    request's [S] timing columns are then gathered from its bank
    alongside the bank-state gather.  Constant-across-banks input
    replays bit-identical to the [S, 6] path.

    With `n_channels`/`n_ranks` > 1 the packed bank state grows to
    [C*R*B, 4, S] (the channel/rank axes fold into the bank-FSM axis —
    same one gather/scatter per request) plus a [C, S] per-channel
    bus-free time max'd into the issue gate; requests map to channels
    in-scan via `chan_rank(ileave)`, and per-bank timing rows stay
    keyed on the ORIGINAL bank id.  C == R == 1 is a static branch
    that keeps the single-channel arithmetic bit-identical.

    Returns (per-request latency [S, N] with zeros at padding, total
    runtime [S]).  Padding must be a suffix of `valid` (the ring gate
    is masked, not re-indexed — same contract as the Pallas kernel).

    `fault` (optional, STATIC branch) is `(fault_rows [S,
    faults.F_COLS], jedec_row [6], u [N])`: PER-LANE fault scenarios
    against the common issue-order uniform stream — each lane carries
    its own watchdog and counters, so the (timing x fault) product
    rides the lane axis of one scan.  Returns then gain a third
    element: [faults.N_COUNTERS, S] int32 counters.

    `region_map` (optional int32) switches `timings` to the
    mask-compressed region layout [S, U, 6] (S unique-row stores
    stacked on the lane axis): each request gathers unique row
    `region_map[..., bank * regions + region_of(row, regions)]`
    in-scan.  A [G] map (G = banks * regions) is shared by every lane
    (one module's store under S timing variants); an [S, G] map gives
    every LANE its own index map — the fleet-serve layout where the
    lane axis is the module axis and each module compresses
    differently.  Constant-region input replays bit-identical to the
    per-bank [S, banks, 6] path.

    `groups` (optional, STATIC) matches `replay_one`: the carry gains a
    lane-major `RankState` and the returns the [S] summed constraint
    stall."""
    banked = timings.ndim == 3
    multi = n_channels * n_ranks > 1
    faulted = fault is not None
    regioned = region_map is not None
    grouped = groups is not None
    if grouped and (faulted or regioned):
        raise BankGroupError("replay_rows: no fault or region axis with "
                             "the rank and bank-group state")
    if regioned:
        assert banked, "region_map requires [S, U, 6] unique stores"
        n_regions = region_map.shape[-1] // n_banks
        assert region_map.shape[-1] == n_banks * n_regions
        per_lane_map = region_map.ndim == 2
        if per_lane_map:
            assert region_map.shape[0] == timings.shape[0], \
                (region_map.shape, timings.shape)
            lane_i = jnp.arange(timings.shape[0])
    if not banked:
        trcd, tras, twr, trp, tcl = (timings[:, 0], timings[:, 1],
                                     timings[:, 2], timings[:, 3],
                                     timings[:, 5])
    s_rows = timings.shape[0]
    if multi:
        il = jnp.asarray(0 if ileave is None else ileave, jnp.int32)
    if faulted:
        f_rows, j_row, u_arr = fault
        fpT = f_rows.T                  # [F_COLS, S] lane columns
        j6 = (j_row[0], j_row[1], j_row[2], j_row[3], j_row[5])
        jsum = j_row[0] + j_row[1] + j_row[2] + j_row[3]

    def step(st, req):
        if faulted:
            st, wd, cnt = st
            t, b, r, w, v, u_k = req
        else:
            t, b, r, w, v = req
        if grouped:
            st, rs = st
        if multi:
            bs, ring, cf, idx = st      # [CRB, 4, S], [W, S], [C, S]
        else:
            bs, ring, idx = st          # [B, 4, S], [W, S], scalar
        if multi:
            ch, rk = chan_rank(b, r, il, n_channels, n_ranks, n_banks)
            gb = (ch * n_ranks + rk) * n_banks + b
        else:
            ch, rk = 0, 0
            gb = b
        if grouped:
            k, grp = rank_index(b, ch, rk, n_ranks, n_banks, groups[0])
            rk_in, fring = _rank_view(rs, k, grp)
        rowb = bs[gb]                   # [4, S] one gather per request
        gate0 = ring[idx % mlp_window]  # [S]
        # with the rank state the channel bus gates the column instead
        gate = (jnp.maximum(gate0, cf[ch]) if multi and not grouped
                else gate0)
        rf = r.astype(jnp.float32)
        if regioned:
            g = b * n_regions + region_of(r, n_regions)
            if per_lane_map:
                tb = timings[lane_i, region_map[:, g]]  # [S, 6]
            else:
                tb = timings[:, region_map[g], :]
            tc_ = (tb[:, 0], tb[:, 1], tb[:, 2], tb[:, 3], tb[:, 5])
        elif banked:
            tb = timings[:, b, :]       # [S, 6] this bank's columns
            tc_ = (tb[:, 0], tb[:, 1], tb[:, 2], tb[:, 3], tb[:, 5])
        else:
            tc_ = (trcd, tras, twr, trp, tcl)
        if faulted:
            is_probe, use_agg = faults.wd_gate(fpT, wd)
            tc_ = tuple(jnp.where(use_agg, a, jb)
                        for a, jb in zip(tc_, j6))
            red = jnp.maximum(
                1.0 - (tc_[0] + tc_[1] + tc_[2] + tc_[3]) / jsum, 0.0)
            p = faults.error_prob(fpT, red, 0.0)
            _, det, sil = faults.error_draw(fpT, u_k, p)
            sur = jnp.where(det, j6[4] + fpT[faults.RETRY_NS], 0.0)
        args = (t, gate, rowb[0], rowb[1], rowb[2], rowb[3], rf, w,
                tc_[0], tc_[1], tc_[2], tc_[3], tc_[4], closed)
        res = (service_math(*args, (rk_in + (cf[ch] if multi else NEVER,),
                                    groups[1:]))
               if grouped else service_math(*args))
        latched, act_new, wrd_new, rdy_new, done, lat, is_hit = res[:7]
        if faulted:
            done = done + sur
            lat = lat + sur
            wrd_new = jnp.where(w, wrd_new + sur, wrd_new)
            rdy_new = rdy_new + sur
        new_row = jnp.stack([jnp.broadcast_to(latched, (s_rows,)),
                             act_new, wrd_new, rdy_new])
        bs2 = bs.at[gb].set(jnp.where(v, new_row, rowb))
        ring2 = ring.at[idx % mlp_window].set(jnp.where(v, done, gate0))
        idx2 = idx + v.astype(jnp.int32)
        if multi:
            busy = done - tc_[4] + t_burst     # burst start + t_burst
            cf2 = cf.at[ch].set(jnp.where(v, busy, cf[ch]))
            st2 = (bs2, ring2, cf2, idx2)
        else:
            st2 = (bs2, ring2, idx2)
        if grouped:
            st2 = (st2, _rank_update(rs, k, grp, fring, v, is_hit,
                                     act_new, *res[7:]))
        if faulted:
            degraded = wd[4] > 0
            wd2, new_trip = faults.wd_update(fpT, wd, det, False,
                                            is_probe)
            wd2 = jax.tree_util.tree_map(
                lambda new, old: jnp.where(v, new, old), wd2, wd)
            cnt2 = faults.counter_update(cnt, v, det, sil, new_trip,
                                         degraded, is_probe)
            return (st2, wd2, cnt2), jnp.where(v, lat, 0.0)
        return st2, jnp.where(v, lat, 0.0)

    nb_tot = n_channels * n_ranks * n_banks
    bs0 = jnp.concatenate([jnp.full((nb_tot, 1, s_rows), -1.0),
                           jnp.zeros((nb_tot, 3, s_rows))], axis=1)
    st0 = (bs0, jnp.zeros((mlp_window, s_rows)))
    st0 += ((jnp.zeros((n_channels, s_rows)),) if multi else ())
    st0 += (jnp.zeros((), jnp.int32),)
    if grouped:
        st0 = (st0, _rank_state0(n_channels * n_ranks, groups[0],
                                 (s_rows,)))
    xs = (arrival, bank, row, is_write, valid)
    if faulted:
        st0 = (st0, faults.wd_state0((s_rows,)),
               tuple(jnp.zeros((s_rows,), jnp.int32)
                     for _ in range(faults.N_COUNTERS)))
        xs = xs + (u_arr,)
    st_end, lat = jax.lax.scan(step, st0, xs)
    if faulted:
        st_end, _, cnt_end = st_end
    if grouped:
        st_end, rs_end = st_end
    bse = st_end[0]
    total = jnp.maximum(bse[:, 3].max(0), bse[:, 2].max(0))
    if faulted:
        return lat.T, total, jnp.stack(cnt_end)   # + [NC, S]
    if grouped:                          # + completions (replay_one)
        return (lat.T, jnp.maximum(total, st_end[1].max(0)),
                rs_end.stall)            # + [S]
    return lat.T, total                  # [S, N], [S]


class AdaptiveState(NamedTuple):
    """`replay_adaptive` scan state: controller + thermal loop."""

    bank: BankState
    heat: jnp.ndarray          # [B] per-bank overheat above ambient, C
    cur_bin: jnp.ndarray       # scalar int32, currently selected bin
    t_prev: jnp.ndarray        # scalar, last request arrival (ns)


def replay_adaptive(arrival, bank, row, is_write, valid, table, bins,
                    scn_row, tcfg_row, closed,
                    n_banks: int = 8, mlp_window: int = 8,
                    n_channels: int = 1, n_ranks: int = 1, ileave=None,
                    t_burst: float = 5.0, fault=None, region_map=None):
    """Closed-loop replay: per-request in-scan timing-bin selection.

    `table`: [S+1, 6] stacked timing rows — one per temperature bin
    plus the JEDEC fallback row LAST (selected whenever the sensed
    temperature exceeds the hottest profiled bin, mirroring
    `aldram.TimingTable.lookup_many`) — or a PER-BANK stack
    [S+1, banks, 6] (`aldram.TimingTable.safe_stack_banks`): the scan
    then gathers row (selected bin, request's bank), so a FLY-DRAM
    deployment rides the same dispatch.  An [S+1, 6] stack replays as
    the constant per-bank stack through that same gather, so the two
    forms are bit-identical by construction (the two gathers compiled
    to different fusions whose bank heat differed in the last bit).
    `bins`: [S] ascending bin edges (C).  `scn_row`:
    [thermal.SCN_COLS] ambient-scenario row; `tcfg_row`:
    `thermal.ThermalConfig.as_row()`.

    Per request the scan (1) decays the per-bank heat toward the
    scenario ambient over the inter-arrival gap, (2) senses
    ambient + summed bank overheat, (3) re-selects the timing bin via
    `searchsorted` — UP-switches are immediate (reliability never
    waits), DOWN-switches require the sensed temperature to fall the
    hysteresis margin below the cooler bin's edge (no register
    thrash), (4) services the request with the selected row's timings
    (`_service`, shared with the static replay), and (5) deposits the
    access energy of `repro.core.power` — a miss pays the ACT/PRE pair
    plus the row-active window of the *selected* tRAS — as heat on the
    accessed bank.

    With `n_channels`/`n_ranks` > 1 the controller state and the
    per-bank heat grow to the C*R*B bank-FSM axis (requests map to
    channels in-scan via `chan_rank(ileave)`, per-bank table rows stay
    keyed on the rank-level bank id) and a per-channel bus-free time
    gates issue exactly like `replay_rows` — the returned overheat is
    then [C*R*B].  C == R == 1 is a static branch that keeps the
    single-channel arithmetic bit-identical.

    Returns (latency [N], total runtime, sensed temperature [N],
    selected bin [N] int32 with -1 at padding, end-of-trace per-bank
    overheat [B] in C — the bank-resolved footprint of the access
    stream, so hot banks are attributable even though the module-level
    sensor reads their sum).  With `c_heat = 0` and a steady scenario
    this reduces to `replay_one` of the constant row, bit-for-bit.

    `fault` (optional, STATIC branch — None compiles the exact
    fault-free path) is `(fault_row [faults.F_COLS], u [N])`: the
    sensed temperature then runs through the `faults.fault_sensor`
    pipeline (stuck-at / drift / noise / quantization / lag / dropout)
    BEFORE bin selection, each request draws a margin-conditioned
    transient-error outcome (the TRUE temperature's excess over the
    served bin's upper edge conditions the probability — the JEDEC
    fallback row is structurally error-free), and the watchdog
    (detected-error budget + sensor rate-of-change implausibility)
    degrades stickily to the table's JEDEC row with probe-based
    recovery.  The emitted temperature/bin streams then report the
    CONTROLLER's view: the faulted reading and the bin actually served
    (including watchdog degradation).  Returns gain a sixth element:
    the [faults.N_COUNTERS] int32 counter vector.

    `region_map` (optional int32 [banks * regions] or [banks,
    regions], `aldram.TimingTable.safe_stack_regions`) switches
    `table` to the mask-compressed [S+1, U, 6] unique-column stack:
    the scan then gathers row (selected bin, map[bank * regions +
    region_of(row, regions)]) — the in-scan bin choice and the
    request's subarray region compose in one gather, and the JEDEC
    fallback row rides the last stack position of every unique column
    (structurally identical across columns, so degradation semantics
    match the per-bank stack exactly)."""
    from repro.core.power import access_energy_from_terms
    from repro.core.thermal import ambient_at, heat_decay, overheat_sum
    tau, c_heat, hyst_c = tcfg_row[0], tcfg_row[1], tcfg_row[2]
    e_burst, e_act_pre, p_as = tcfg_row[3], tcfg_row[4], tcfg_row[5]
    hyst = hyst_c * scn_row[8]                   # per-scenario scale
    regioned = region_map is not None
    if table.ndim == 2:
        table = jnp.broadcast_to(jnp.asarray(table)[:, None, :],
                                 (table.shape[0], n_banks, 6))
    if regioned:
        region_map = region_map.reshape(-1)
        n_regions = region_map.shape[0] // n_banks
        assert region_map.shape[0] == n_banks * n_regions
    multi = n_channels * n_ranks > 1
    faulted = fault is not None
    nb_tot = n_channels * n_ranks * n_banks
    n_rows_t = table.shape[0]                    # S + 1 (JEDEC last)
    il = (jnp.asarray(0 if ileave is None else ileave, jnp.int32)
          if multi else None)
    if faulted:
        f_row, u_arr = fault
        # bin s's upper edge; the JEDEC fallback "bin" has none
        bins_ext = jnp.concatenate(
            [jnp.asarray(bins, jnp.float32),
             jnp.full((1,), jnp.inf, jnp.float32)])

    def step(carry, req):
        if faulted:
            carry, fstate = carry
            lag_p, held_p, psen_p, wd, cnt = fstate
            t, b, r, w, v, amb, decay, u_k, k_idx = req
        else:
            t, b, r, w, v, amb, decay = req
        s, cf = carry if multi else (carry, None)
        dt = jnp.maximum(t - s.t_prev, 0.0)
        heat = s.heat * decay
        sensed = amb + overheat_sum(heat)
        if faulted:
            reading, lag2, held2 = faults.fault_sensor(
                f_row, t, dt, sensed, lag_p, held_p, k_idx)
        else:
            reading = sensed
        # conservative rounding UP (smallest bin edge >= sensed); the
        # index len(bins) selects the JEDEC fallback row
        up = jnp.searchsorted(bins, reading, side="left")
        # down-switch only once sensed has fallen `hyst` below the
        # cooler bin's edge; up-switches bypass the hysteresis entirely
        down = jnp.searchsorted(bins, reading + hyst, side="left")
        new_bin = jnp.maximum(up, jnp.minimum(s.cur_bin, down))
        if faulted:
            is_probe, use_agg = faults.wd_gate(f_row, wd)
            use_bin = jnp.where(use_agg, new_bin, n_rows_t - 1)
        else:
            use_bin = new_bin
        if regioned:
            u_col = region_map[b * n_regions + region_of(r, n_regions)]
            tp = table[use_bin, u_col]
        else:
            tp = table[use_bin, b]
        if faulted:
            if regioned:
                jed = table[n_rows_t - 1, u_col]
            else:
                jed = table[n_rows_t - 1, b]
            jsum = jed[0] + jed[1] + jed[2] + jed[3]
            red = jnp.maximum(
                1.0 - (tp[0] + tp[1] + tp[2] + tp[3]) / jsum, 0.0)
            # the TRUE temperature's excess over the served bin's
            # edge — a mis-binned hot module errors even though its
            # (faulted) reading looked fine
            excess = jnp.maximum(sensed - bins_ext[use_bin], 0.0)
            p_e = faults.error_prob(f_row, red, excess)
            _, det, sil = faults.error_draw(f_row, u_k, p_e)
            sur = jnp.where(det, jed[5] + f_row[faults.RETRY_NS], 0.0)
        else:
            sur = None
        if multi:
            ch, rk = chan_rank(b, r, il, n_channels, n_ranks, n_banks)
            gb = (ch * n_ranks + rk) * n_banks + b
            eg = cf[ch]
        else:
            gb, eg = b, None
        s2b, lat, is_hit, done = _service(s.bank, t, gb, r, w, tp[0],
                                          tp[1], tp[2], tp[3], tp[5],
                                          closed, mlp_window,
                                          extra_gate=eg, surcharge=sur)
        # closed loop: the heat deposit depends on the row-active
        # window of the timings we just selected (same formula as the
        # host-side power model, by construction)
        miss = 1.0 - is_hit.astype(jnp.float32)
        energy = access_energy_from_terms(e_burst, e_act_pre, p_as,
                                          miss, tp[1])
        # a one-hot masked add, as the kernel deposits it (a scatter-add
        # here rounded the bank heat differently from the kernel)
        deposit = jnp.where(jnp.arange(nb_tot) == gb, c_heat * energy,
                            0.0)
        s2 = AdaptiveState(bank=s2b, heat=heat + deposit,
                           cur_bin=new_bin.astype(jnp.int32),
                           t_prev=t + 0.0)
        c2 = (s2, cf.at[ch].set(done - tp[5] + t_burst)) if multi \
            else s2
        c1 = (s, cf) if multi else s
        if faulted:
            # implausibility: per-request reading jump beyond the
            # rate-of-change bound (needs a previous reading)
            implaus = ((f_row[faults.WD_JUMP_C] > 0.0)
                       & (psen_p > 0.5 * faults.NO_READING)
                       & (jnp.abs(reading - psen_p)
                          > f_row[faults.WD_JUMP_C]))
            degraded = wd[4] > 0
            wd2, new_trip = faults.wd_update(f_row, wd, det, implaus,
                                             is_probe)
            cnt2 = faults.counter_update(cnt, v, det, sil, new_trip,
                                         degraded, is_probe)
            c2 = (c2, (lag2, held2, reading, wd2, cnt2))
            c1 = (c1, fstate)
        c3 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(v, new, old), c2, c1)
        return c3, (jnp.where(v, lat, 0.0),
                    jnp.where(v, reading, 0.0),
                    jnp.where(v, use_bin.astype(jnp.int32), -1))

    s0 = AdaptiveState(bank=_bank_state0(nb_tot, mlp_window),
                       heat=jnp.zeros((nb_tot,)),
                       cur_bin=jnp.zeros((), jnp.int32),
                       t_prev=jnp.zeros(()))
    carry0 = (s0, jnp.zeros((n_channels,))) if multi else s0
    # the thermal drive (ambient, RC decay over each arrival gap) as
    # precomputed streams — the transcendentals stay out of the scan,
    # computed exactly as the Pallas kernel's launcher computes them
    xs = (arrival, bank, row, is_write, valid,
          ambient_at(scn_row, arrival), heat_decay(arrival, tau))
    if faulted:
        no_r = jnp.asarray(faults.NO_READING, jnp.float32)
        carry0 = (carry0, (no_r, no_r, no_r, faults.wd_state0(),
                           tuple(jnp.zeros((), jnp.int32)
                                 for _ in range(faults.N_COUNTERS))))
        xs = xs + (u_arr,
                   jnp.arange(arrival.shape[0], dtype=jnp.int32))
    c_end, (lat, temp, bin_sel) = jax.lax.scan(step, carry0, xs)
    if faulted:
        c_end, fstate_end = c_end
        cnt_end = fstate_end[4]
    s_end = c_end[0] if multi else c_end
    total = jnp.maximum(s_end.bank.ready.max(), s_end.bank.wr_done.max())
    if faulted:
        return (lat, total, temp, bin_sel, s_end.heat,
                jnp.stack(cnt_end))
    return lat, total, temp, bin_sel, s_end.heat


def simulate(trace: Trace, tp: TimingParams, n_banks: int = 8,
             mlp_window: int = 8,
             policy: Policy = OPEN_FCFS) -> dict[str, jnp.ndarray]:
    """Replay one trace under one set of timing parameters.  Returns
    mean/percentile latency and total runtime.

    Thin single-item shim over the batched `sim_engine.SimEngine` path
    (a [1 trace x 1 policy x 1 timing row] campaign), so the scalar and
    batched replays share one code path bit-for-bit."""
    from repro.core import sim_engine
    res = sim_engine.default_engine().run(sim_engine.SimSpec(
        traces=(trace,), timings=tp, policies=(policy,),
        n_banks=n_banks, mlp_window=mlp_window))
    return {
        "mean_latency_ns": res.mean_latency_ns[0, 0, 0],
        "p99_latency_ns": res.p99_latency_ns[0, 0, 0],
        "total_ns": res.total_ns[0, 0, 0],
        "latencies": res.latencies[0, 0, 0],
    }
