"""Jitted public wrappers for the replay kernel.

`replay_grid` is the entry point `repro.core.sim_engine._replay_grid`
dispatches to when `SimEngine(backend="pallas")` is selected: it takes
the same [T, P, N] request grid + [S, 6] timing rows as the vmapped
lax.scan path, flattens the (trace x policy) axes into kernel cells,
pads the timing-row axis to the 128-lane block, casts the
bool/scalar-flag inputs to the kernel's int32/float32 layout, and
unpads/reshapes the outputs back to the scan path's [T, P, S, N] /
[T, P, S] shapes — so the two backends are drop-in interchangeable
inside the one-dispatch campaign.

impl: 'auto' (pallas on TPU, ref elsewhere), 'pallas' (compiled for
the TPU; raises elsewhere), 'pallas_interpret' (kernel body on the
host — the parity-test mode), 'ref' (vmapped lax.scan oracle).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.dram_sim import check_prefix_valid, split_chan
from repro.kernels import resolve_impl
from repro.kernels.replay import ref, replay


def _pad_rows(timings_t: jnp.ndarray, bs: int) -> jnp.ndarray:
    """Pad the trailing timing-row axis of a [..., 6, S] tile to a
    block multiple; padding replicates column 0 (always-valid timings
    whose outputs are sliced off)."""
    s = timings_t.shape[-1]
    rem = (-s) % bs
    if rem == 0:
        return timings_t
    fill = jnp.broadcast_to(timings_t[..., :1],
                            timings_t.shape[:-1] + (rem,))
    return jnp.concatenate([timings_t, fill], axis=-1)


def replay_grid(arrival, bank, row, is_write, valid, timings, closed,
                n_banks: int = 8, mlp_window: int = 8,
                impl: str = "auto", bs: int | None = None,
                chan=(1, 1, 5.0), ileave=None, fault=None,
                region_map=None):
    """arrival/bank/row/is_write: [T, P, N]; valid: [T, N]; timings:
    [S, 6] or per-bank [S, banks, 6]; closed: [P] bool; `chan`
    (static) = (n_channels, n_ranks, t_burst_ns) channel geometry and
    `ileave` the per-policy [P] interleave-code column (both inert at
    the single-channel default) -> (latency [T, P, S, N], total
    [T, P, S]) — same contract as the lax.scan path
    (`ref.replay_grid`).

    `fault` (optional) = (fault_rows [S, faults.F_COLS], jedec_row
    [6], uniforms [T, N]) — per-LANE fault scenarios, same contract as
    `ref.replay_grid`; the returns then gain a [T, P, S,
    faults.N_COUNTERS] int32 counter grid.

    `region_map` (optional int32, `ref.replay_grid`'s contract)
    switches `timings` to the mask-compressed [S, U, 6] unique-row
    stack — a [G] map shared across lanes or an [S, G] per-lane map
    (G = banks * regions); the kernel path tiles it to [G, S_pad] and
    gathers through it in VMEM.

    A `chan` with bank groups (`dram_sim.split_chan`) replays with the
    rank and bank-group state; the returns gain the [T, P, S] summed
    constraint stall.
    """
    check_prefix_valid(valid, "replay_grid")
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.replay_grid(arrival, bank, row, is_write, valid,
                               timings, closed, n_banks, mlp_window,
                               chan=tuple(chan), ileave=ileave,
                               fault=fault, region_map=region_map)

    bs = bs or replay.BLOCK_ROWS
    t, p, n = arrival.shape
    s = timings.shape[0]
    g = t * p

    def cells(x, dtype):
        return x.astype(dtype).reshape(g, n)

    arrival_g = cells(arrival, jnp.float32)
    bank_g = cells(bank, jnp.int32)
    row_g = cells(row, jnp.int32)
    wr_g = cells(is_write, jnp.int32)
    val_g = jnp.broadcast_to(valid.astype(jnp.int32)[:, None, :],
                             (t, p, n)).reshape(g, n)
    closed_col = jnp.broadcast_to(
        closed.astype(jnp.float32)[None, :], (t, p)).reshape(g, 1)
    il = (jnp.zeros((p,), jnp.int32) if ileave is None
          else jnp.asarray(ileave, jnp.int32))
    il_col = jnp.broadcast_to(il[None, :], (t, p)).reshape(g, 1)
    tim = jnp.asarray(timings, jnp.float32)
    # [S, 6] -> [6, S]; per-bank [S, B, 6] -> [B, 6, S]
    tim_t = _pad_rows(tim.T if tim.ndim == 2
                      else tim.transpose(1, 2, 0), bs)
    k_fault = None
    if fault is not None:
        f_rows, j_row, u = fault
        # lane-tiled fault rows [F_COLS, S_pad] (pad lanes replicate
        # lane 0, outputs sliced off) + the JEDEC fallback column +
        # per-cell uniforms (shared across the policy axis)
        flt_t = _pad_rows(jnp.asarray(f_rows, jnp.float32).T, bs)
        jed_col = jnp.asarray(j_row, jnp.float32)[:, None]
        u_g = jnp.broadcast_to(
            jnp.asarray(u, jnp.float32)[:, None, :],
            (t, p, n)).reshape(g, n)
        k_fault = (flt_t, jed_col, u_g)
    k_map = None
    if region_map is not None:
        # [S, G] per-lane map -> [G, S]; [G] shared map broadcasts;
        # lane padding replicates lane 0 (outputs sliced off anyway)
        rm = jnp.asarray(region_map, jnp.int32)
        rm_t = (rm.T if rm.ndim == 2
                else jnp.broadcast_to(rm[:, None], (rm.shape[0], s)))
        k_map = _pad_rows(rm_t, bs)

    out = replay.replay_blocks(
        closed_col, il_col, arrival_g, bank_g, row_g, wr_g, val_g,
        tim_t, n_banks=n_banks, mlp_window=mlp_window,
        interpret=(impl == "pallas_interpret"), bs=bs,
        chan=tuple(chan), fault=k_fault, region_map=k_map)
    lat, total = out[:2]
    # [G, N, S_pad] -> [T, P, S, N]
    lat = lat[:, :, :s].reshape(t, p, n, s).transpose(0, 1, 3, 2)
    total = total[:, :s].reshape(t, p, s)
    if split_chan(chan)[3] is not None:
        return lat, total, out[2][:, :s].reshape(t, p, s)
    if fault is None:
        return lat, total
    cnt = jnp.stack([c[:, :s].reshape(t, p, s) for c in out[2:]],
                    axis=-1)                    # [T, P, S, NC]
    return lat, total, cnt


def _adaptive_bs(length: int, bs: int | None) -> int:
    """Lane-block size for an adaptive launch: thermal campaigns often
    have far fewer than 128 (table, scenario) lanes — padding a K*C=8
    campaign to the full 128-lane block would do 16x the work — so
    sub-128 lane counts round up to a multiple of 8 instead."""
    if bs is not None:
        return bs
    return (replay.BLOCK_ROWS if length >= replay.BLOCK_ROWS
            else -(-length // 8) * 8)


def replay_grid_adaptive(arrival, bank, row, is_write, valid, tables,
                         bins, scns, tcfg, closed, n_banks: int = 8,
                         mlp_window: int = 8, impl: str = "auto",
                         bs: int | None = None, emit_raw: bool = False,
                         fault=None, region_map=None):
    """Adaptive-campaign counterpart of `replay_grid`: arrival/bank/
    row/is_write: [T, P, N]; valid: [T, N]; tables: [K, S+1, 6] or
    per-bank [K, S+1, banks, 6] (JEDEC fallback row last); bins: [S];
    scns: [C, SCN_COLS]; tcfg: [6]; closed: [P].

    The kernel lane axis carries the flattened (table k, scenario c)
    pairs, l = k * C + c: the table tile repeats each stack C times
    and the scenario tile is tiled K times, so every lane replays the
    same (trace, policy) stream under its own closed thermal loop.

    Returns (lat [T, P, K, C, N], total [T, P, K, C], temps, bin_sel,
    bank_heat [T, P, K, C, banks], diag):

      * kernel path — diag = (temp_max, temp_mean, bin_switches), all
        [T, P, K, C], reduced ON-DEVICE in the kernel's own
        accumulator tiles; temps/bin_sel are None unless `emit_raw`
        (the O(grid * N) raw traces never leave VMEM otherwise).
      * ref path — temps/bin_sel always populated (the scan emits
        them anyway), diag = None (the engine reduces downstream).

    `fault` (optional) = (fault_rows [F, faults.F_COLS], uniforms
    [T, N]) rides the lane axis INNERMOST, l = (k*C + c)*F + f: every
    grid output gains a trailing F axis (before N/banks) and the
    return gains a 7th element, the [T, P, K, C, F, faults.N_COUNTERS]
    int32 counter grid.

    `region_map` (optional int32, `ref.replay_grid_adaptive`'s
    contract) switches `tables` to the mask-compressed [K, S+1, U, 6]
    unique-column stacks — a [G] map shared by every stack or a
    [K, G] per-stack map; the kernel path tiles it onto the lane axis
    (the map rides each stack's C*F lanes) and gathers through it in
    VMEM.
    """
    check_prefix_valid(valid, "replay_grid_adaptive")
    impl = resolve_impl(impl)
    if impl == "ref":
        out = ref.replay_grid_adaptive(
            arrival, bank, row, is_write, valid, tables, bins, scns,
            tcfg, closed, n_banks, mlp_window, fault=fault,
            region_map=region_map)
        lat, total, temps, bin_sel, bank_heat = out[:5]
        if fault is None:
            return lat, total, temps, bin_sel, bank_heat, None
        return lat, total, temps, bin_sel, bank_heat, None, out[5]

    t, p, n = arrival.shape
    tab = jnp.asarray(tables, jnp.float32)
    banked = tab.ndim == 4
    k = tab.shape[0]
    c = scns.shape[0]
    nf = 1 if fault is None else fault[0].shape[0]
    length = k * c * nf
    bs = _adaptive_bs(length, bs)
    g = t * p

    def cells(x, dtype):
        return x.astype(dtype).reshape(g, n)

    arrival_g = cells(arrival, jnp.float32)
    bank_g = cells(bank, jnp.int32)
    row_g = cells(row, jnp.int32)
    wr_g = cells(is_write, jnp.int32)
    val_g = jnp.broadcast_to(jnp.asarray(valid).astype(jnp.int32)
                             [:, None, :], (t, p, n)).reshape(g, n)
    closed_col = jnp.broadcast_to(
        jnp.asarray(closed).astype(jnp.float32)[None, :],
        (t, p)).reshape(g, 1)
    # [K, S+1(, B), 6] -> [(B,) S+1, 6, K] -> repeat C*F: lane
    # l = (k*C + c)*F + f
    tab_t = (tab.transpose(2, 1, 3, 0) if banked else
             tab.transpose(1, 2, 0))
    tab_t = _pad_rows(jnp.repeat(tab_t, c * nf, axis=-1), bs)
    # [C, SCN_COLS] -> [SCN_COLS, C] repeat F, tiled K times
    scn_t = _pad_rows(jnp.tile(
        jnp.repeat(jnp.asarray(scns, jnp.float32).T, nf, axis=-1),
        (1, k)), bs)
    k_fault = None
    if fault is not None:
        f_rows, u = fault
        # [F, F_COLS] -> [F_COLS, F] tiled K*C times: lane (k*C+c)*F+f
        flt_t = _pad_rows(jnp.tile(
            jnp.asarray(f_rows, jnp.float32).T, (1, k * c)), bs)
        u_g = jnp.broadcast_to(
            jnp.asarray(u, jnp.float32)[:, None, :],
            (t, p, n)).reshape(g, n)
        k_fault = (flt_t, u_g)
    k_map = None
    if region_map is not None:
        # [K, G] per-stack map -> [G, K] repeated onto each stack's
        # C*F lanes; [G] shared map broadcasts across the lane axis
        rm = jnp.asarray(region_map, jnp.int32)
        rm_t = (jnp.repeat(rm.T, c * nf, axis=-1) if rm.ndim == 2
                else jnp.broadcast_to(rm[:, None],
                                      (rm.shape[0], length)))
        k_map = _pad_rows(rm_t, bs)
    b_arr = jnp.asarray(bins, jnp.float32)
    if b_arr.shape[0] == 0:
        # empty bin-edge set (JEDEC-only table): a +inf row keeps the
        # in-kernel `sum(bins < sensed)` at the scan's searchsorted(0)
        b_arr = jnp.full((1,), jnp.inf, jnp.float32)
    bins_t = jnp.broadcast_to(b_arr[:, None],
                              (b_arr.shape[0], tab_t.shape[-1]))
    tcfg_col = jnp.asarray(tcfg, jnp.float32)[:, None]

    out = replay.adaptive_blocks(
        closed_col, arrival_g, bank_g, row_g, wr_g, val_g, tab_t,
        scn_t, bins_t, tcfg_col, n_banks=n_banks,
        mlp_window=mlp_window, interpret=(impl == "pallas_interpret"),
        bs=bs, emit_raw=emit_raw, fault=k_fault, region_map=k_map)
    lat, total, tmax, tmean, switches, bank_heat = out[:6]

    if fault is None:
        def grid4(x):                   # [G, L_pad] -> [T, P, K, C]
            return x[:, :length].reshape(t, p, k, c)

        def grid5(x):                   # [G, N, L_pad] -> [T,P,K,C,N]
            return (x[:, :, :length].reshape(t, p, n, k, c)
                    .transpose(0, 1, 3, 4, 2))

        heat = (bank_heat[:, :, :length].reshape(t, p, n_banks, k, c)
                .transpose(0, 1, 3, 4, 2))
    else:
        def grid4(x):                   # [G, L_pad] -> [T,P,K,C,F]
            return x[:, :length].reshape(t, p, k, c, nf)

        def grid5(x):                   # [G,N,L_pad] -> [T,P,K,C,F,N]
            return (x[:, :, :length].reshape(t, p, n, k, c, nf)
                    .transpose(0, 1, 3, 4, 5, 2))

        heat = (bank_heat[:, :, :length]
                .reshape(t, p, n_banks, k, c, nf)
                .transpose(0, 1, 3, 4, 5, 2))

    diag = (grid4(tmax), grid4(tmean), grid4(switches))
    temps = grid5(out[6]) if emit_raw else None
    bin_sel = grid5(out[7]) if emit_raw else None
    if fault is None:
        return grid5(lat), grid4(total), temps, bin_sel, heat, diag
    cnt = jnp.stack([grid4(x) for x in out[-5:]], axis=-1)
    return (grid5(lat), grid4(total), temps, bin_sel, heat, diag,
            cnt)


__all__ = ["replay_grid", "replay_grid_adaptive"]
