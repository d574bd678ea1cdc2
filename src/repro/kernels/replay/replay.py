"""Pallas TPU kernel: batched trace replay over a (trace x policy x
timing row) campaign grid.

One program per (trace, policy) campaign cell and per block of 128
timing rows: the timing-row axis rides the 128-lane minor dimension
(every lane replays the SAME request stream under a different timing
row — the memory-access pattern AL-DRAM campaigns sweep), and the
whole controller state lives in VMEM scratch as [banks, lanes] /
[mlp_window, lanes] tiles:

  open_row / act_time / wr_done / ready : [n_banks, BLOCK_ROWS]
  done_ring (bounded-MLP completion gate): [mlp_window, BLOCK_ROWS]

A `fori_loop` walks the N requests of the stream; per request the
scalar (arrival, bank, row, is_write, valid) fields — read from the
cell's [1, 1, N] SMEM blocks, since a VMEM block cannot serve a scalar
at a dynamic lane offset — broadcast against the lane axis, the
bank/ring rows are selected with one-hot sublane
masks (no dynamic lane indexing), and the per-request service
arithmetic mirrors `repro.core.dram_sim._service` operation for
operation — the kernel is numerics-parity-tested against the vmapped
`lax.scan` path (`repro.kernels.replay.ref`).

Padding semantics match the scan: invalid requests (a suffix — the
ring gate is indexed by the loop counter, which equals the scan's
valid-step counter only while padding stays a suffix) leave every
state tile untouched and emit zero latency.

Per-bank timing tables (FLY-DRAM spatial variation) ride a
[n_banks, 6, S] timing tile: the request's 6 timing lanes are
selected with the same one-hot bank mask that gathers its bank-state
rows, so the per-bank gather costs one extra masked reduce per
request and nothing else changes.

Multi-channel campaigns (`chan=(n_channels, n_ranks, t_burst)` with
C*R > 1) widen the state tiles to [C*R*n_banks, BLOCK_ROWS] — the
global FSM index is (channel*n_ranks + rank)*n_banks + bank, computed
in-loop by `dram_sim.chan_rank` from the per-policy interleave code
(the `il_ref` per-cell SMEM column) — and add one [n_channels,
BLOCK_ROWS] bus-free scratch tile: the issue gate maxes in the
request's channel-bus row (selected by the same one-hot trick, here
over the channel axis) and the bus stays busy for `t_burst` after
each data transfer.  Per-bank timing tables keep their rank-level
[n_banks, 6, S] tile — spatial tables are per-module, not
per-channel.  C*R == 1 compiles the exact single-channel kernel (the
channel branches are static).

A geometry with bank groups (`chan` = (C, R, t_burst, groups), see
`dram_sim.split_chan`) adds the rank state of `dram_sim.RankState` as
scratch tiles — each rank's last ACT and column [C*R, BLOCK_ROWS],
each group's last ACT and column [C*R*G, BLOCK_ROWS], the four-ACT
ring [C*R*4, BLOCK_ROWS] with its int32 slot pointer [C*R,
BLOCK_ROWS] — gathered and updated with the same one-hot masks, the
ring slot per lane, and a per-lane stall output row; without groups
the kernel is the exact one before them.

Per-cell flags (closed page, interleave code) and the small constant
rows (JEDEC row, thermal constants) sit whole in SMEM; per-lane
outputs are [G, 1, lanes] rows so every block meets the (8, 128)
tiling.  On-chip memory per program: the request fields in SMEM
(double-buffered, 40 B per request, 48 B with faults, of 1 MiB) and the
[N, 128] latency tile in VMEM (double-buffered, 1 KiB per request; the
adaptive kernel adds its [N, lanes] ambient input, and two more for
the raw temperature/bin traces) under a 64 MiB scoped-VMEM limit — `max_requests` turns both into the largest
N a launch accepts, and larger launches raise before lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import faults
from repro.core.dram_sim import (NEVER, BankGroupError, chan_rank,
                                 rank_index, region_of, service_math,
                                 split_chan)
from repro.core.power import access_energy_from_terms
from repro.core.thermal import ambient_at, heat_decay, overheat_sum

# Timing rows per program, on the 128-lane minor axis.
BLOCK_ROWS = 128

# On-chip memory of one replay program.  Each request field of a cell
# ([N] int32/float32) is double-buffered in SMEM; each raw [N, lanes]
# output tile (latency, and the adaptive kernel's raw temperature/bin
# traces) is double-buffered in VMEM, a lane block padding to 128
# lanes.  The reserves cover the flags, tables and state tiles.
VMEM_LIMIT_BYTES = 64 * 2**20
_SMEM_BYTES = 2**20
_SMEM_RESERVE = 64 * 2**10
_VMEM_RESERVE = 4 * 2**20


def max_requests(n_fields: int, n_raw: int) -> int:
    """Largest request count N per stream that a launch with
    `n_fields` per-request fields in SMEM and `n_raw` [N, lanes] tiles
    in VMEM holds on chip.  Static kernel (5 fields, the latency
    tile): 24576, 20480 with faults (6 fields).  Adaptive kernel (6
    fields with the heat decay; latency + ambient tiles): 20480, 17554
    with faults, 15360 with the raw temperature/bin traces (4
    tiles)."""
    smem = (_SMEM_BYTES - _SMEM_RESERVE) // (n_fields * 4 * 2)
    vmem = (VMEM_LIMIT_BYTES - _VMEM_RESERVE) // (n_raw * 128 * 4 * 2)
    return min(smem, vmem)


def _check_requests(n: int, n_fields: int, n_raw: int, name: str):
    cap = max_requests(n_fields, n_raw)
    if n > cap:
        raise ValueError(
            f"{name}: {n} requests per stream exceed the {cap} one "
            f"program holds on chip ({n_fields} request fields in "
            f"SMEM, {n_raw} [N, lanes] tiles in VMEM); split the "
            f"traces or replay them with backend='scan'")


# A whole small array (per-cell page/interleave flags, the JEDEC row,
# the thermal constants) held in SMEM and read as scalars.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _cell_stream(n: int) -> pl.BlockSpec:
    """One cell's [1, 1, N] request field of a [G, 1, N] stream, in
    SMEM: the per-request loop reads it one scalar at a time, which a
    VMEM block cannot serve at a dynamic lane offset."""
    return pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _lane_row(bs: int) -> pl.BlockSpec:
    """One cell's lane block of a [G, 1, L] per-lane output row."""
    return pl.BlockSpec((1, 1, bs), lambda i, j: (i, 0, j))


def _cells3(*streams):
    """[G, N] request streams -> the [G, 1, N] layout of `_cell_stream`."""
    return [x.reshape(x.shape[0], 1, x.shape[1]) for x in streams]


def _kernel(closed_ref, il_ref, arr_ref, bank_ref, row_ref, wr_ref,
            val_ref, tim_ref, *refs, n_banks: int,
            mlp_window: int, n_req: int, banked: bool = False,
            chan=(1, 1, 5.0), faulted: bool = False,
            regioned: bool = False):
    if regioned:
        # mask-compressed spatial tables: tim_ref is the [U, 6, bs]
        # UNIQUE-row tile and map_ref the [G, bs] int32 index-map tile
        # (G = banks * regions; per-lane maps ride the lane axis,
        # shared maps broadcast) — the request's (bank, region) slot
        # resolves to a unique row via two chained one-hot reduces
        map_ref, *refs = refs
    n_ch, n_rk, t_burst, groups = split_chan(chan)
    grouped = groups is not None
    if faulted:
        # extra inputs: lane-tiled fault rows [F_COLS, bs], the JEDEC
        # fallback column [6, 1], per-cell issue-order uniforms [1, N];
        # extra outputs: the five fault counters as on-device
        # accumulator tiles; extra scratch: the per-lane watchdog.
        (flt_ref, jed_ref, u_ref, lat_ref, total_ref, det_ref,
         sil_ref, trp_ref, deg_ref, prb_ref, open_s, act_s, wrd_s,
         rdy_s, ring_s, cf_s, wde_s, wdb_s, wdc_s, wdp_s,
         wdt_s) = refs
    elif grouped:
        (lat_ref, total_ref, stall_ref, open_s, act_s, wrd_s, rdy_s,
         ring_s, cf_s, ract_s, rcol_s, gact_s, gcol_s, faw_s,
         fptr_s) = refs
    else:
        (lat_ref, total_ref, open_s, act_s, wrd_s, rdy_s, ring_s,
         cf_s) = refs
    bs = lat_ref.shape[-1]
    multi = n_ch * n_rk > 1          # static: C*R == 1 keeps the
    nb_tot = n_ch * n_rk * n_banks   # original single-channel kernel
    cell = pl.program_id(0)
    closed = closed_ref[cell] > 0.5
    if not banked:
        trcd, tras, twr, trp, tcl = (tim_ref[0, :], tim_ref[1, :],
                                     tim_ref[2, :], tim_ref[3, :],
                                     tim_ref[5, :])
    bank_iota = jax.lax.broadcasted_iota(jnp.int32, (nb_tot, bs), 0)
    ring_iota = jax.lax.broadcasted_iota(jnp.int32, (mlp_window, bs), 0)
    if regioned:
        n_map = map_ref.shape[0]
        n_regions = n_map // n_banks
        map_iota = jax.lax.broadcasted_iota(jnp.int32, (n_map, bs), 0)
        uniq_iota = jax.lax.broadcasted_iota(
            jnp.int32, (tim_ref.shape[0], bs), 0)
    if multi:
        il = il_ref[cell]
        # the timing tile stays keyed on the rank-level bank id
        bank_iota_b = jax.lax.broadcasted_iota(jnp.int32,
                                               (n_banks, bs), 0)
        chan_iota = jax.lax.broadcasted_iota(jnp.int32, (n_ch, bs), 0)
    if grouped:
        n_g, rank_t = groups[0], groups[1:]
        cr = n_ch * n_rk
        rank_iota = jax.lax.broadcasted_iota(jnp.int32, (cr, bs), 0)
        grp_iota = jax.lax.broadcasted_iota(jnp.int32, (cr * n_g, bs), 0)
        faw_iota = jax.lax.broadcasted_iota(jnp.int32, (cr * 4, bs), 0)
        for s_ in (ract_s, rcol_s, gact_s, gcol_s, faw_s):
            s_[...] = jnp.full(s_.shape, NEVER, jnp.float32)
        fptr_s[...] = jnp.zeros((cr, bs), jnp.int32)
        stall_ref[...] = jnp.zeros((1, 1, bs), jnp.float32)

    # scratch persists across grid steps — re-arm the controller state
    open_s[...] = jnp.full((nb_tot, bs), -1.0, jnp.float32)
    act_s[...] = jnp.zeros((nb_tot, bs), jnp.float32)
    wrd_s[...] = jnp.zeros((nb_tot, bs), jnp.float32)
    rdy_s[...] = jnp.zeros((nb_tot, bs), jnp.float32)
    ring_s[...] = jnp.zeros((mlp_window, bs), jnp.float32)
    cf_s[...] = jnp.zeros((n_ch, bs), jnp.float32)
    if faulted:
        flt = flt_ref[...]                    # [F_COLS, bs] lane rows
        j6 = (jed_ref[0], jed_ref[1], jed_ref[2], jed_ref[3],
              jed_ref[5])
        jsum = jed_ref[0] + jed_ref[1] + jed_ref[2] + jed_ref[3]
        for r_ in (det_ref, sil_ref, trp_ref, deg_ref, prb_ref):
            r_[...] = jnp.zeros((1, 1, bs), jnp.int32)
        for s_ in (wde_s, wdb_s, wdc_s, wdp_s, wdt_s):
            s_[...] = jnp.zeros((1, bs), jnp.int32)

    def body(k, _):
        t = arr_ref[0, 0, k]
        b = bank_ref[0, 0, k]
        r_i = row_ref[0, 0, k]
        rf = r_i.astype(jnp.float32)
        w = wr_ref[0, 0, k] > 0
        v = val_ref[0, 0, k] > 0
        if multi:
            # global FSM index of the request's (channel, rank, bank)
            ch, rank = chan_rank(b, r_i, il, n_ch, n_rk, n_banks)
            gb = (ch * n_rk + rank) * n_banks + b
            cm = chan_iota == ch              # one-hot channel row
        else:
            ch, rank = 0, 0
            gb = b
        bm = bank_iota == gb                  # one-hot bank rows
        rm = ring_iota == (k % mlp_window)    # one-hot ring slot

        open_b = jnp.sum(jnp.where(bm, open_s[...], 0.0), axis=0)
        act_b = jnp.sum(jnp.where(bm, act_s[...], 0.0), axis=0)
        wrd_b = jnp.sum(jnp.where(bm, wrd_s[...], 0.0), axis=0)
        rdy_b = jnp.sum(jnp.where(bm, rdy_s[...], 0.0), axis=0)
        gate = jnp.sum(jnp.where(rm, ring_s[...], 0.0), axis=0)
        if multi:
            # channel bus contention joins the issue gate (with the
            # rank state: the column, in service_math)
            cf_b = jnp.sum(jnp.where(cm, cf_s[...], 0.0), axis=0)
            if not grouped:
                gate = jnp.maximum(gate, cf_b)
        if grouped:
            # the request's rank and bank-group rows, and per lane the
            # ring slot of the rank's fourth-last ACT
            kr, grp = rank_index(b, ch, rank, n_rk, n_banks, n_g)
            km = rank_iota == kr
            gm = grp_iota == grp
            ptr = jnp.sum(jnp.where(km, fptr_s[...], 0), axis=0)
            fm = faw_iota == (kr * 4 + ptr)[None, :]
            rank_in = (jnp.sum(jnp.where(km, ract_s[...], 0.0), axis=0),
                       jnp.sum(jnp.where(gm, gact_s[...], 0.0), axis=0),
                       jnp.sum(jnp.where(fm, faw_s[...], 0.0), axis=0),
                       jnp.sum(jnp.where(km, rcol_s[...], 0.0), axis=0),
                       jnp.sum(jnp.where(gm, gcol_s[...], 0.0), axis=0),
                       cf_b if multi else NEVER)
        if regioned:
            # chained one-hot gather: (bank, region) slot -> unique
            # row index (per lane, via the map tile) -> timing lanes
            g_id = b * n_regions + region_of(r_i, n_regions)
            u_lane = jnp.sum(jnp.where(map_iota == g_id, map_ref[...],
                                       0), axis=0)         # [bs] int32
            umb = uniq_iota == u_lane[None, :]
            tim_b = jnp.sum(jnp.where(umb[:, None, :], tim_ref[...],
                                      0.0), axis=0)         # [6, bs]
            tc = (tim_b[0], tim_b[1], tim_b[2], tim_b[3], tim_b[5])
        elif banked:
            # per-bank timing tile [n_banks, 6, bs]: select the
            # request's bank with the same one-hot sublane mask
            bmb = bank_iota_b == b if multi else bm
            tim_b = jnp.sum(jnp.where(bmb[:, None, :], tim_ref[...],
                                      0.0), axis=0)         # [6, bs]
            tc = (tim_b[0], tim_b[1], tim_b[2], tim_b[3], tim_b[5])
        else:
            tc = (trcd, tras, twr, trp, tcl)
        if faulted:
            # watchdog gate -> serve the JEDEC column when degraded;
            # mirrors dram_sim.replay_rows operation for operation
            wd = (wde_s[0, :], wdb_s[0, :], wdc_s[0, :], wdp_s[0, :],
                  wdt_s[0, :])
            is_probe, use_agg = faults.wd_gate(flt, wd)
            tc = tuple(jnp.where(use_agg, a, jb)
                       for a, jb in zip(tc, j6))
            red = jnp.maximum(
                1.0 - (tc[0] + tc[1] + tc[2] + tc[3]) / jsum, 0.0)
            p_e = faults.error_prob(flt, red, 0.0)
            _e, det, sil = faults.error_draw(flt, u_ref[0, 0, k], p_e)
            sur = jnp.where(det, j6[4] + flt[faults.RETRY_NS], 0.0)

        # the per-request timing model itself is the SHARED elementwise
        # helper (repro.core.dram_sim.service_math) — only the one-hot
        # gather/scatter layout is kernel-specific
        args = (t, gate, open_b, act_b, wrd_b, rdy_b, rf, w, tc[0],
                tc[1], tc[2], tc[3], tc[4], closed)
        res = (service_math(*args, (rank_in, rank_t)) if grouped
               else service_math(*args))
        row_latched, act_new, wrd_new, rdy_new, done, lat, is_hit = res[:7]
        if faulted:
            # detected-error retry: re-issue at the JEDEC row keeps
            # the bank busy through the retry (same arithmetic as
            # dram_sim._service(surcharge=...))
            done = done + sur
            lat = lat + sur
            wrd_new = jnp.where(w, wrd_new + sur, wrd_new)
            rdy_new = rdy_new + sur

        upd = bm & v
        open_s[...] = jnp.where(upd, row_latched, open_s[...])
        act_s[...] = jnp.where(upd, act_new, act_s[...])
        wrd_s[...] = jnp.where(upd, wrd_new, wrd_s[...])
        rdy_s[...] = jnp.where(upd, rdy_new, rdy_s[...])
        ring_s[...] = jnp.where(rm & v, done, ring_s[...])
        if multi:
            # bus busy for t_burst ns from the burst start (done - tCL)
            busy = done - tc[4] + t_burst
            cf_s[...] = jnp.where(cm & v, busy, cf_s[...])
        if grouped:
            # dram_sim._rank_update, tile for tile
            col, stall = res[7:]
            acted = v & ~is_hit
            ract_s[...] = jnp.where(km & acted, act_new, ract_s[...])
            gact_s[...] = jnp.where(gm & acted, act_new, gact_s[...])
            faw_s[...] = jnp.where(fm & acted, act_new, faw_s[...])
            fptr_s[...] = jnp.where(km & acted,
                                    jnp.where(ptr == 3, 0, ptr + 1),
                                    fptr_s[...])
            rcol_s[...] = jnp.where(km & v, col, rcol_s[...])
            gcol_s[...] = jnp.where(gm & v, col, gcol_s[...])
            stall_ref[0, 0, :] = stall_ref[0, 0, :] + jnp.where(
                v, stall, 0.0)
        if faulted:
            degraded = wd[4] > 0
            wd2, new_trip = faults.wd_update(flt, wd, det, False,
                                             is_probe)
            wde_s[0, :] = jnp.where(v, wd2[0], wd[0])
            wdb_s[0, :] = jnp.where(v, wd2[1], wd[1])
            wdc_s[0, :] = jnp.where(v, wd2[2], wd[2])
            wdp_s[0, :] = jnp.where(v, wd2[3], wd[3])
            wdt_s[0, :] = jnp.where(v, wd2[4], wd[4])
            vi = v.astype(jnp.int32)
            for r_, f_ in zip((det_ref, sil_ref, trp_ref, deg_ref,
                               prb_ref),
                              (det, sil, new_trip, degraded, is_probe)):
                r_[0, 0, :] = r_[0, 0, :] + f_.astype(jnp.int32) * vi

        lat_ref[0, k, :] = jnp.where(v, lat, 0.0)
        return 0

    jax.lax.fori_loop(0, n_req, body, 0)
    total_ref[0, 0, :] = jnp.maximum(jnp.max(rdy_s[...], axis=0),
                                     jnp.max(wrd_s[...], axis=0))
    if grouped:
        # completions too: an open bank's ready is its last column
        total_ref[0, 0, :] = jnp.maximum(total_ref[0, 0, :],
                                         jnp.max(ring_s[...], axis=0))


def _adaptive_kernel(closed_ref, arr_ref, bank_ref, row_ref, wr_ref,
                     val_ref, dec_ref, amb_ref, tim_ref, scn_ref,
                     bins_ref, tcfg_ref, *refs, n_banks: int,
                     mlp_window: int, n_req: int,
                     banked: bool, emit_raw: bool,
                     faulted: bool = False, regioned: bool = False):
    """Closed-loop (adaptive) replay cell: the static kernel's layout
    plus the `dram_sim.AdaptiveState` carried in VMEM scratch — per-
    bank RC heat [n_banks, lanes], current bin + last arrival [1,
    lanes] — with the per-request timing row RE-SELECTED in-kernel by
    a one-hot bin(×bank) mask over the [S+1(, banks), 6, lanes] table
    tile.  Each lane replays the same (trace, policy) stream under a
    different (table stack, thermal scenario) pair; bin selection
    mirrors `dram_sim.replay_adaptive` operation for operation:
    up-switch immediate, down-switch hysteretic (`sum(bins < x)` IS
    `searchsorted(bins, x, 'left')`), index len(bins) = the JEDEC
    fallback row last in the stack.  The temp_max / temp_mean /
    bin_switches diagnostics accumulate directly in their output
    tiles, so the O(N * lanes) raw temperature/bin traces never leave
    VMEM unless `emit_raw` asks for them.

    `faulted` (static) adds the `repro.core.faults` loop: a lane-tiled
    fault-row input [F_COLS, bs] + issue-order uniforms [1, N], the
    sensor/watchdog state as extra scratch, and the five fault
    counters as accumulator output tiles next to temp_max /
    bin_switches — mirroring `dram_sim.replay_adaptive(fault=...)`
    operation for operation.

    `regioned` (static) switches `tim_ref` to the mask-compressed
    [U, S+1, 6, bs] UNIQUE-column tile with a [G, bs] int32 index-map
    tile (`map_ref`, G = banks * regions) as an extra input right
    after `tcfg_ref`: the request's (bank, region) slot resolves to a
    unique column via two chained one-hot reduces, and that column
    mask replaces the bank mask ONLY where TIMINGS are gathered (the
    bin-row select and the faulted JEDEC gather) — the bank-state and
    heat tiles stay keyed on the physical bank."""
    refs = list(refs)
    if regioned:
        map_ref = refs[0]
        del refs[0]
    if faulted:
        flt_ref, u_ref = refs[:2]
        del refs[:2]
    (lat_ref, total_ref, tmax_ref, tmean_ref, sw_ref,
     heat_ref) = refs[:6]
    del refs[:6]
    if emit_raw:
        traw_ref, braw_ref = refs[:2]
        del refs[:2]
    if faulted:
        det_ref, sil_ref, trp_ref, deg_ref, prb_ref = refs[:5]
        del refs[:5]
    (open_s, act_s, wrd_s, rdy_s, ring_s, heat_s, bin_s, tprev_s,
     tcomp_s) = refs[:9]
    del refs[:9]
    if faulted:
        (lag_s, held_s, psen_s, pbin_s, wde_s, wdb_s, wdc_s, wdp_s,
         wdt_s) = refs
    bs = lat_ref.shape[-1]
    n_bins = tim_ref.shape[-3]                 # S+1 (JEDEC row last)
    closed = closed_ref[pl.program_id(0)] > 0.5
    scn = scn_ref[...]                         # [SCN_COLS, bs]
    bins_t = bins_ref[...]                     # [S(pad), bs]
    c_heat = tcfg_ref[1]
    e_burst, e_act_pre, p_as = tcfg_ref[3], tcfg_ref[4], tcfg_ref[5]
    hyst = tcfg_ref[2] * scn[8]                # per-scenario scale [bs]
    bank_iota = jax.lax.broadcasted_iota(jnp.int32, (n_banks, bs), 0)
    ring_iota = jax.lax.broadcasted_iota(jnp.int32, (mlp_window, bs), 0)
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (n_bins, bs), 0)
    if regioned:
        n_map = map_ref.shape[0]
        n_regions = n_map // n_banks
        map_iota = jax.lax.broadcasted_iota(jnp.int32, (n_map, bs), 0)
        uniq_iota = jax.lax.broadcasted_iota(
            jnp.int32, (tim_ref.shape[0], bs), 0)

    # scratch persists across grid steps — re-arm controller + thermal
    open_s[...] = jnp.full((n_banks, bs), -1.0, jnp.float32)
    act_s[...] = jnp.zeros((n_banks, bs), jnp.float32)
    wrd_s[...] = jnp.zeros((n_banks, bs), jnp.float32)
    rdy_s[...] = jnp.zeros((n_banks, bs), jnp.float32)
    ring_s[...] = jnp.zeros((mlp_window, bs), jnp.float32)
    heat_s[...] = jnp.zeros((n_banks, bs), jnp.float32)
    bin_s[...] = jnp.zeros((1, bs), jnp.int32)
    tprev_s[...] = jnp.zeros((1, bs), jnp.float32)
    tmax_ref[...] = jnp.full((1, 1, bs), -jnp.inf, jnp.float32)
    tmean_ref[...] = jnp.zeros((1, 1, bs), jnp.float32)  # sum until /cnt
    tcomp_s[...] = jnp.zeros((1, bs), jnp.float32)
    sw_ref[...] = jnp.zeros((1, 1, bs), jnp.int32)
    if faulted:
        flt = flt_ref[...]                  # [F_COLS, bs] lane rows
        # the JEDEC fallback row is a STATIC index (last in the stack)
        jed_full = None if banked else tim_ref[n_bins - 1]  # [6, bs]
        jall = tim_ref[:, n_bins - 1] if banked else None   # [B,6,bs]
        s_pad = bins_t.shape[0]
        edge_iota = jax.lax.broadcasted_iota(jnp.int32, (s_pad, bs), 0)
        no_r = jnp.full((1, bs), faults.NO_READING, jnp.float32)
        lag_s[...] = no_r
        held_s[...] = no_r
        psen_s[...] = no_r
        pbin_s[...] = jnp.zeros((1, bs), jnp.int32)
        for r_ in (det_ref, sil_ref, trp_ref, deg_ref, prb_ref):
            r_[...] = jnp.zeros((1, 1, bs), jnp.int32)
        for s_ in (wde_s, wdb_s, wdc_s, wdp_s, wdt_s):
            s_[...] = jnp.zeros((1, bs), jnp.int32)

    def body(k, n_valid):
        t = arr_ref[0, 0, k]
        b = bank_ref[0, 0, k]
        r_i = row_ref[0, 0, k]
        rf = r_i.astype(jnp.float32)
        w = wr_ref[0, 0, k] > 0
        v = val_ref[0, 0, k] > 0
        bm = bank_iota == b
        rm = ring_iota == (k % mlp_window)

        # thermal loop: decay toward ambient over the arrival gap,
        # sense ambient + summed bank overheat, re-select the bin
        tprev = tprev_s[0, :]
        dt = jnp.maximum(t - tprev, 0.0)
        heat = heat_s[...] * dec_ref[0, 0, k]
        sensed = amb_ref[0, k, :] + overheat_sum(heat)
        if faulted:
            # the controller reads the FAULTED sensor register
            lag_p, held_p, psen_p = (lag_s[0, :], held_s[0, :],
                                     psen_s[0, :])
            reading, lag2, held2 = faults.fault_sensor(
                flt, t, dt, sensed, lag_p, held_p, k)
        else:
            reading = sensed
        cur = bin_s[0, :]
        up = jnp.sum((bins_t < reading[None, :]).astype(jnp.int32),
                     axis=0)
        down = jnp.sum((bins_t < (reading + hyst)[None, :])
                       .astype(jnp.int32), axis=0)
        new_bin = jnp.maximum(up, jnp.minimum(cur, down))
        if faulted:
            # watchdog gate: serve the JEDEC fallback row (index
            # n_bins-1) while tripped, except on probe requests
            wd = (wde_s[0, :], wdb_s[0, :], wdc_s[0, :], wdp_s[0, :],
                  wdt_s[0, :])
            is_probe, use_agg = faults.wd_gate(flt, wd)
            use_bin = jnp.where(use_agg, new_bin, n_bins - 1)
        else:
            use_bin = new_bin

        # timing row select: one-hot bin sublane mask (x bank mask on
        # per-bank tiles, x unique-column mask on region-compressed
        # tiles), same masked-reduce idiom as the bank state
        sel = bin_iota == use_bin[None, :]               # [S+1, bs]
        if regioned:
            # chained one-hot gather: (bank, region) slot -> unique
            # column index (per lane, via the map tile) -> bin row
            g_id = b * n_regions + region_of(r_i, n_regions)
            u_lane = jnp.sum(jnp.where(map_iota == g_id, map_ref[...],
                                       0), axis=0)       # [bs] int32
            tmask = uniq_iota == u_lane[None, :]
        else:
            tmask = bm
        if banked:
            m = tmask[:, None, :] & sel[None, :, :]      # [B, S+1, bs]
            tim_b = jnp.sum(jnp.where(m[:, :, None, :], tim_ref[...],
                                      0.0), axis=(0, 1))   # [6, bs]
        else:
            tim_b = jnp.sum(jnp.where(sel[:, None, :], tim_ref[...],
                                      0.0), axis=0)         # [6, bs]
        tc = (tim_b[0], tim_b[1], tim_b[2], tim_b[3], tim_b[5])
        if faulted:
            # margin-conditioned error draw: reduction of the SERVED
            # row vs JEDEC + the TRUE temperature's excess over the
            # served bin's edge (dram_sim.replay_adaptive's bins_ext)
            jed = (jnp.sum(jnp.where(tmask[:, None, :], jall, 0.0),
                           axis=0) if banked else jed_full)  # [6, bs]
            jsum = jed[0] + jed[1] + jed[2] + jed[3]
            red = jnp.maximum(
                1.0 - (tc[0] + tc[1] + tc[2] + tc[3]) / jsum, 0.0)
            edge = jnp.sum(jnp.where(edge_iota == use_bin[None, :],
                                     bins_t, 0.0), axis=0)
            edge = jnp.where(use_bin >= n_bins - 1, jnp.inf, edge)
            excess = jnp.maximum(sensed - edge, 0.0)
            p_e = faults.error_prob(flt, red, excess)
            _e, det, sil = faults.error_draw(flt, u_ref[0, 0, k], p_e)
            sur = jnp.where(det, jed[5] + flt[faults.RETRY_NS], 0.0)

        open_b = jnp.sum(jnp.where(bm, open_s[...], 0.0), axis=0)
        act_b = jnp.sum(jnp.where(bm, act_s[...], 0.0), axis=0)
        wrd_b = jnp.sum(jnp.where(bm, wrd_s[...], 0.0), axis=0)
        rdy_b = jnp.sum(jnp.where(bm, rdy_s[...], 0.0), axis=0)
        gate = jnp.sum(jnp.where(rm, ring_s[...], 0.0), axis=0)

        (row_latched, act_new, wrd_new, rdy_new, done, lat,
         is_hit) = service_math(t, gate, open_b, act_b, wrd_b, rdy_b,
                                rf, w, tc[0], tc[1], tc[2], tc[3],
                                tc[4], closed)
        if faulted:
            # detected-error retry priced into the request + bank state
            done = done + sur
            lat = lat + sur
            wrd_new = jnp.where(w, wrd_new + sur, wrd_new)
            rdy_new = rdy_new + sur

        # closed loop: deposit the access energy of the timings we
        # just SELECTED as heat on the accessed bank (shared formula)
        miss = 1.0 - is_hit.astype(jnp.float32)
        energy = access_energy_from_terms(e_burst, e_act_pre, p_as,
                                          miss, tc[1])

        upd = bm & v
        open_s[...] = jnp.where(upd, row_latched, open_s[...])
        act_s[...] = jnp.where(upd, act_new, act_s[...])
        wrd_s[...] = jnp.where(upd, wrd_new, wrd_s[...])
        rdy_s[...] = jnp.where(upd, rdy_new, rdy_s[...])
        ring_s[...] = jnp.where(rm & v, done, ring_s[...])
        heat_s[...] = jnp.where(
            v, heat + jnp.where(bm, c_heat * energy, 0.0), heat_s[...])
        bin_s[0, :] = jnp.where(v, new_bin, cur)
        tprev_s[0, :] = jnp.where(v, t, tprev)
        if faulted:
            # implausibility (reading jump beyond the rate-of-change
            # bound), watchdog transition, counters + sensor state
            implaus = ((flt[faults.WD_JUMP_C] > 0.0)
                       & (psen_p > 0.5 * faults.NO_READING)
                       & (jnp.abs(reading - psen_p)
                          > flt[faults.WD_JUMP_C]))
            degraded = wd[4] > 0
            wd2, new_trip = faults.wd_update(flt, wd, det, implaus,
                                             is_probe)
            lag_s[0, :] = jnp.where(v, lag2, lag_p)
            held_s[0, :] = jnp.where(v, held2, held_p)
            psen_s[0, :] = jnp.where(v, reading, psen_p)
            wde_s[0, :] = jnp.where(v, wd2[0], wd[0])
            wdb_s[0, :] = jnp.where(v, wd2[1], wd[1])
            wdc_s[0, :] = jnp.where(v, wd2[2], wd[2])
            wdp_s[0, :] = jnp.where(v, wd2[3], wd[3])
            wdt_s[0, :] = jnp.where(v, wd2[4], wd[4])
            vi = v.astype(jnp.int32)
            for r_, f_ in zip((det_ref, sil_ref, trp_ref, deg_ref,
                               prb_ref),
                              (det, sil, new_trip, degraded, is_probe)):
                r_[0, 0, :] = r_[0, 0, :] + f_.astype(jnp.int32) * vi

        # diagnostics accumulate in their own output tiles; the temp
        # stats and raw traces report the CONTROLLER's view (the
        # faulted reading, the bin actually served) — exactly what the
        # scan path emits
        tmax_ref[0, 0, :] = jnp.maximum(tmax_ref[0, 0, :],
                                        jnp.where(v, reading, -jnp.inf))
        # compensated (Kahan) sum: a plain float32 running sum over N
        # requests drifts ~N ulps (7.9e-5 relative at N = 8192 on the
        # chip), far from the scan's tree reduction
        acc = tmean_ref[0, 0, :]
        y = jnp.where(v, reading, 0.0) - tcomp_s[0, :]
        acc2 = acc + y
        tcomp_s[0, :] = (acc2 - acc) - y
        tmean_ref[0, 0, :] = acc2
        if faulted:
            pb = pbin_s[0, :]
            sw_ref[0, 0, :] = sw_ref[0, 0, :] + (
                (use_bin != pb) & v & (k > 0)).astype(jnp.int32)
            pbin_s[0, :] = jnp.where(v, use_bin, pb)
        else:
            sw_ref[0, 0, :] = sw_ref[0, 0, :] + (
                (new_bin != cur) & v & (k > 0)).astype(jnp.int32)
        lat_ref[0, k, :] = jnp.where(v, lat, 0.0)
        if emit_raw:
            traw_ref[0, k, :] = jnp.where(v, reading, 0.0)
            braw_ref[0, k, :] = jnp.where(v, use_bin, -1)
        return n_valid + v.astype(jnp.int32)

    cnt = jax.lax.fori_loop(0, n_req, body, jnp.int32(0))
    total_ref[0, 0, :] = jnp.maximum(jnp.max(rdy_s[...], axis=0),
                                     jnp.max(wrd_s[...], axis=0))
    tmean_ref[0, 0, :] = tmean_ref[0, 0, :] / cnt.astype(jnp.float32)
    heat_ref[0, :, :] = heat_s[...]


@functools.partial(jax.jit,
                   static_argnames=("n_banks", "mlp_window",
                                    "interpret", "bs", "emit_raw"))
def adaptive_blocks(closed_col, arrival, bank, row, is_write, valid,
                    tables_t, scn_t, bins_t, tcfg_col,
                    n_banks: int = 8, mlp_window: int = 8,
                    interpret: bool = False, bs: int = BLOCK_ROWS,
                    emit_raw: bool = False, fault=None,
                    region_map=None):
    """Adaptive-campaign kernel launch.  closed_col: [G, 1] float32;
    arrival: [G, N] float32; bank/row/is_write/valid: [G, N] int32;
    tables_t: [S+1, 6, L] (or PER-BANK [n_banks, S+1, 6, L]) — lane l
    holds the table stack of its (table, scenario) pair; scn_t:
    [SCN_COLS, L] scenario rows per lane; bins_t: [S(>=1, inf-padded),
    L]; tcfg_col: [6, 1] `ThermalConfig.as_row`.  L % bs == 0.
    Returns (lat [G, N, L], total [G, L], tmax [G, L], tmean [G, L],
    switches [G, L] int32, bank_heat [G, n_banks, L]) plus, when
    `emit_raw`, the raw (temps [G, N, L], bins [G, N, L] int32), plus,
    when `fault` = (fault tile [F_COLS, L], uniforms [G, N]) is given,
    the five [G, L] int32 fault counters (detected, silent, trips,
    degraded, probes).

    `region_map` (optional int32 [banks*regions, L] lane-tiled index
    map) switches `tables_t` to the mask-compressed PER-REGION
    [U, S+1, 6, L] unique-column tile — each lane's requests gather
    their table column through the lane's map column in-kernel."""
    g, n = arrival.shape
    banked = tables_t.ndim == 4
    faulted = fault is not None
    regioned = region_map is not None
    length = tables_t.shape[-1]
    n_bins = tables_t.shape[-3]
    assert tables_t.shape[-2] == 6 and length % bs == 0, \
        (tables_t.shape, bs)
    if banked and not regioned:
        assert tables_t.shape[0] == n_banks, (tables_t.shape, n_banks)
    _check_requests(n, 6 + faulted, 4 if emit_raw else 2,
                    "adaptive_blocks")
    grid = (g, length // bs)
    kernel = functools.partial(_adaptive_kernel, n_banks=n_banks,
                               mlp_window=mlp_window, n_req=n,
                               banked=banked, emit_raw=emit_raw,
                               faulted=faulted, regioned=regioned)
    tab_spec = (pl.BlockSpec((tables_t.shape[0], n_bins, 6, bs),
                             lambda i, j: (0, 0, 0, j))
                if banked else
                pl.BlockSpec((n_bins, 6, bs), lambda i, j: (0, 0, j)))
    s_bins = bins_t.shape[0]
    # closed, the five request streams + the per-request heat decay,
    # the per-lane ambient tile, the table tile, ...
    in_specs = [_SMEM] + [_cell_stream(n)] * 6 + [
        pl.BlockSpec((1, n, bs), lambda i, j: (i, 0, j)),  # ambient
        tab_spec,                                       # table tile
        pl.BlockSpec((scn_t.shape[0], bs), lambda i, j: (0, j)),
        pl.BlockSpec((s_bins, bs), lambda i, j: (0, j)),  # bins
        _SMEM,                                          # tcfg
    ]
    # the thermal drive, precomputed exactly as the scan precomputes it
    decay = heat_decay(arrival, tcfg_col[0, 0])            # [G, N]
    ambient = ambient_at(scn_t, arrival[:, :, None])       # [G, N, L]
    inputs = ([closed_col.reshape(g)]
              + _cells3(arrival, bank, row, is_write, valid, decay)
              + [ambient, tables_t, scn_t, bins_t, tcfg_col.reshape(-1)])
    if regioned:
        in_specs.append(pl.BlockSpec((region_map.shape[0], bs),
                                     lambda i, j: (0, j)))
        inputs.append(region_map)
    out_specs = [
        pl.BlockSpec((1, n, bs), lambda i, j: (i, 0, j)),   # lat
        _lane_row(bs),                                      # total
        _lane_row(bs),                                      # tmax
        _lane_row(bs),                                      # tmean
        _lane_row(bs),                                      # switches
        pl.BlockSpec((1, n_banks, bs), lambda i, j: (i, 0, j)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((g, n, length), jnp.float32),
        jax.ShapeDtypeStruct((g, 1, length), jnp.float32),
        jax.ShapeDtypeStruct((g, 1, length), jnp.float32),
        jax.ShapeDtypeStruct((g, 1, length), jnp.float32),
        jax.ShapeDtypeStruct((g, 1, length), jnp.int32),
        jax.ShapeDtypeStruct((g, n_banks, length), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((n_banks, bs), jnp.float32),   # open_row
        pltpu.VMEM((n_banks, bs), jnp.float32),   # act_time
        pltpu.VMEM((n_banks, bs), jnp.float32),   # wr_done
        pltpu.VMEM((n_banks, bs), jnp.float32),   # ready
        pltpu.VMEM((mlp_window, bs), jnp.float32),  # done_ring
        pltpu.VMEM((n_banks, bs), jnp.float32),   # RC bank heat
        pltpu.VMEM((1, bs), jnp.int32),           # current bin
        pltpu.VMEM((1, bs), jnp.float32),         # last arrival
        pltpu.VMEM((1, bs), jnp.float32),         # temp-sum compensation
    ]
    if emit_raw:
        out_specs += [pl.BlockSpec((1, n, bs), lambda i, j: (i, 0, j)),
                      pl.BlockSpec((1, n, bs), lambda i, j: (i, 0, j))]
        out_shape += [jax.ShapeDtypeStruct((g, n, length), jnp.float32),
                      jax.ShapeDtypeStruct((g, n, length), jnp.int32)]
    if faulted:
        flt_t, u = fault
        in_specs += [
            pl.BlockSpec((flt_t.shape[0], bs), lambda i, j: (0, j)),
            _cell_stream(n),                             # uniforms
        ]
        inputs += [flt_t] + _cells3(u)
        out_specs += [_lane_row(bs)] * 5
        out_shape += [jax.ShapeDtypeStruct((g, 1, length), jnp.int32)] * 5
        scratch += ([pltpu.VMEM((1, bs), jnp.float32)] * 3   # lag/held
                    + [pltpu.VMEM((1, bs), jnp.int32)] * 6)  # pbin+wd
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*inputs)
    # [G, 1, L] lane rows -> [G, L]
    rows = (1, 2, 3, 4) + ((tuple(range(len(out) - 5, len(out))))
                           if faulted else ())
    return tuple(x[:, 0] if i in rows else x for i, x in enumerate(out))


@functools.partial(jax.jit,
                   static_argnames=("n_banks", "mlp_window",
                                    "interpret", "bs", "chan"))
def replay_blocks(closed_col, ileave_col, arrival, bank, row, is_write,
                  valid, timings_t, n_banks: int = 8,
                  mlp_window: int = 8, interpret: bool = False,
                  bs: int = BLOCK_ROWS, chan=(1, 1, 5.0), fault=None,
                  region_map=None):
    """closed_col: [G, 1] float32 (1.0 = closed page); ileave_col:
    [G, 1] int32 per-cell interleave code (`dram_sim.ILEAVE_CODES`,
    inert on a single-channel launch); arrival: [G, N] float32;
    bank/row/is_write/valid: [G, N] int32 (flags as 0/1); timings_t:
    [6, S] float32 with S % bs == 0 (rows = as_row columns), or the
    PER-BANK tile [n_banks, 6, S] — each request's timing lane columns
    are then selected with the same one-hot bank mask that gathers its
    bank state.  `chan` (static) = (n_channels, n_ranks, t_burst_ns):
    C*R > 1 sizes the controller-state scratch [C*R*n_banks, bs] and
    adds the per-channel bus-free scratch [C, bs] (see `_kernel`).
    G = flattened (trace x policy) cells.  Returns (latency [G, N, S],
    total runtime [G, S]); with `fault` = (fault tile [F_COLS, S],
    JEDEC column [6, 1], uniforms [G, N]) also the five [G, S] int32
    fault counters (detected, silent, trips, degraded, probes); with
    bank groups in `chan` also the [G, S] summed constraint stall.

    `region_map` (optional int32 [banks*regions, S] lane-tiled index
    map) switches `timings_t` to the mask-compressed PER-REGION
    [U, 6, S] unique-row tile — each lane's requests gather their
    timing row through the lane's map column in-kernel."""
    g, n = arrival.shape
    banked = timings_t.ndim == 3
    faulted = fault is not None
    regioned = region_map is not None
    groups = split_chan(chan)[3]
    if groups is not None and (faulted or regioned):
        raise BankGroupError("replay_blocks: no fault or region axis "
                             "with the rank and bank-group state")
    s = timings_t.shape[-1]
    nb_tot = chan[0] * chan[1] * n_banks
    assert timings_t.shape[-2] == 6 and s % bs == 0, (timings_t.shape, bs)
    if banked and not regioned:
        assert timings_t.shape[0] == n_banks, (timings_t.shape, n_banks)
    _check_requests(n, 5 + faulted, 1, "replay_blocks")
    grid = (g, s // bs)
    kernel = functools.partial(_kernel, n_banks=n_banks,
                               mlp_window=mlp_window, n_req=n,
                               banked=banked, chan=chan,
                               faulted=faulted, regioned=regioned)
    tim_spec = (pl.BlockSpec((timings_t.shape[0], 6, bs),
                             lambda i, j: (0, 0, j))
                if banked else
                pl.BlockSpec((6, bs), lambda i, j: (0, j)))
    # closed, ileave, the five request streams, the timing tile
    in_specs = [_SMEM, _SMEM] + [_cell_stream(n)] * 5 + [tim_spec]
    inputs = ([closed_col.reshape(g), ileave_col.reshape(g)]
              + _cells3(arrival, bank, row, is_write, valid)
              + [timings_t])
    if regioned:
        in_specs.append(pl.BlockSpec((region_map.shape[0], bs),
                                     lambda i, j: (0, j)))
        inputs.append(region_map)
    out_specs = [
        pl.BlockSpec((1, n, bs), lambda i, j: (i, 0, j)),
        _lane_row(bs),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((g, n, s), jnp.float32),
        jax.ShapeDtypeStruct((g, 1, s), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((nb_tot, bs), jnp.float32),    # open_row
        pltpu.VMEM((nb_tot, bs), jnp.float32),    # act_time
        pltpu.VMEM((nb_tot, bs), jnp.float32),    # wr_done
        pltpu.VMEM((nb_tot, bs), jnp.float32),    # ready
        pltpu.VMEM((mlp_window, bs), jnp.float32),  # done_ring
        pltpu.VMEM((chan[0], bs), jnp.float32),   # chan bus-free
    ]
    if groups is not None:
        cr = chan[0] * chan[1]
        out_specs.append(_lane_row(bs))              # constraint stall
        out_shape.append(jax.ShapeDtypeStruct((g, 1, s), jnp.float32))
        scratch += [pltpu.VMEM((cr, bs), jnp.float32)] * 2 + [
            pltpu.VMEM((cr * groups[0], bs), jnp.float32)] * 2 + [
            pltpu.VMEM((cr * 4, bs), jnp.float32),   # four-ACT ring
            pltpu.VMEM((cr, bs), jnp.int32)]         # its slot pointer
    if faulted:
        flt_t, jed_col, u = fault
        in_specs += [
            pl.BlockSpec((flt_t.shape[0], bs), lambda i, j: (0, j)),
            _SMEM,                                       # JEDEC row
            _cell_stream(n),                             # uniforms
        ]
        inputs += [flt_t, jed_col.reshape(-1)] + _cells3(u)
        out_specs += [_lane_row(bs)] * 5
        out_shape += [jax.ShapeDtypeStruct((g, 1, s), jnp.int32)] * 5
        scratch += [pltpu.VMEM((1, bs), jnp.int32)] * 5   # watchdog
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*inputs)
    # [G, 1, S] lane rows -> [G, S]
    return (out[0],) + tuple(x[:, 0] for x in out[1:])
