"""lax.scan oracles for the replay kernels: the vmapped
`repro.core.dram_sim.replay_one` / `replay_adaptive` paths evaluated
over the same flattened-cell layouts the kernels use.  Used for CPU
execution and as the parity references for the Pallas kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dram_sim import replay_adaptive, replay_one, split_chan


@functools.partial(jax.jit,
                   static_argnames=("n_banks", "mlp_window", "chan"))
def replay_grid(arrival, bank, row, is_write, valid, timings, closed,
                n_banks: int = 8, mlp_window: int = 8,
                chan=(1, 1, 5.0), ileave=None, fault=None,
                region_map=None):
    """arrival/bank/row/is_write: [T, P, N]; valid: [T, N]; timings:
    [S, 6] or per-bank [S, banks, 6] (vmapping the timing axis hands
    `replay_one` a [banks, 6] row set per lane); closed: [P] bool;
    `chan` (static) = (n_channels, n_ranks, t_burst_ns) channel
    geometry, `ileave` the per-policy interleave-code column ->
    (latency [T, P, S, N], total [T, P, S]).

    `fault` (optional, STATIC branch) = (fault_rows [S,
    faults.F_COLS], jedec_row [6], uniforms [T, N]): each timing lane
    carries its own fault scenario (the engine expands the (timing x
    fault) product onto the lane axis) and the returns gain a
    [T, P, S, faults.N_COUNTERS] int32 counter grid.

    `region_map` (optional int32, `dram_sim.replay_one`'s contract)
    switches `timings` to the mask-compressed [S, U, 6] unique-store
    stack: a [G] map is shared across timing lanes, an [S, G] map
    rides the lane vmap so every lane gathers through its own index
    map (the fleet-serve per-module layout).

    A `chan` with bank groups (`dram_sim.split_chan`) replays with the
    rank and bank-group state and returns gain the [T, P, S] summed
    constraint stall."""
    n_ch, n_rk, t_burst, groups = split_chan(chan)
    il = (jnp.zeros((arrival.shape[1],), jnp.int32) if ileave is None
          else jnp.asarray(ileave, jnp.int32))
    rm_ax = (0 if region_map is not None and region_map.ndim == 2
             else None)

    if fault is not None:
        f_rows, j_row, u = fault

        def one_f(a, b, r, w, v, tp, c, i_, fr, uu, rm):
            return replay_one(a, b, r, w, v, tp, c, n_banks,
                              mlp_window, n_channels=n_ch,
                              n_ranks=n_rk, ileave=i_,
                              t_burst=t_burst, fault=(fr, j_row, uu),
                              region_map=rm, groups=groups)

        f_s = jax.vmap(one_f, in_axes=(None, None, None, None, None,
                                       0, None, None, 0, None, rm_ax))
        f_ps = jax.vmap(f_s, in_axes=(0, 0, 0, 0, None, None, 0, 0,
                                      None, None, None))
        f_tps = jax.vmap(f_ps, in_axes=(0, 0, 0, 0, 0, None, None,
                                        None, None, 0, None))
        return f_tps(arrival, bank, row, is_write,
                     jnp.asarray(valid, bool), timings, closed, il,
                     f_rows, u, region_map)

    def one(a, b, r, w, v, tp, c, i_, rm):
        return replay_one(a, b, r, w, v, tp, c, n_banks, mlp_window,
                          n_channels=n_ch, n_ranks=n_rk, ileave=i_,
                          t_burst=t_burst, region_map=rm, groups=groups)

    f_s = jax.vmap(one, in_axes=(None, None, None, None, None, 0,
                                 None, None, rm_ax))
    f_ps = jax.vmap(f_s, in_axes=(0, 0, 0, 0, None, None, 0, 0, None))
    f_tps = jax.vmap(f_ps, in_axes=(0, 0, 0, 0, 0, None, None, None,
                                    None))
    return f_tps(arrival, bank, row, is_write,
                 jnp.asarray(valid, bool), timings, closed, il,
                 region_map)


@functools.partial(jax.jit, static_argnames=("n_banks", "mlp_window"))
def replay_grid_adaptive(arrival, bank, row, is_write, valid, tables,
                         bins, scns, tcfg, closed, n_banks: int = 8,
                         mlp_window: int = 8, fault=None,
                         region_map=None):
    """Adaptive oracle: `dram_sim.replay_adaptive` vmapped over the
    (trace, policy, table stack, scenario) axes.  arrival/bank/row/
    is_write: [T, P, N]; valid: [T, N]; tables: [K, S+1, 6] or
    per-bank [K, S+1, banks, 6]; bins: [S]; scns: [C, SCN_COLS];
    tcfg: [6]; closed: [P] -> (latency [T, P, K, C, N], total
    [T, P, K, C], temps [T, P, K, C, N], bins [T, P, K, C, N] int32,
    bank_heat [T, P, K, C, banks]).

    `fault` (optional, STATIC branch) = (fault_rows [F,
    faults.F_COLS], uniforms [T, N]) adds the fault axis INNERMOST
    (outputs gain a trailing F grid axis before N/banks) plus a
    [T, P, K, C, F, faults.N_COUNTERS] int32 counter grid.

    `region_map` (optional int32) switches `tables` to the
    mask-compressed [K, S+1, U, 6] unique-column stacks: a [G] map is
    shared by every stack, a [K, G] map rides the table vmap so each
    stack gathers through its own index map."""
    rm_ax = (0 if region_map is not None and region_map.ndim == 2
             else None)
    if fault is not None:
        f_rows, u = fault

        def one_f(a, b, r, w, v, tbl, scn, c, fr, uu, rm):
            return replay_adaptive(a, b, r, w, v, tbl, bins, scn,
                                   tcfg, c, n_banks, mlp_window,
                                   fault=(fr, uu), region_map=rm)

        f_f = jax.vmap(one_f, in_axes=(None,) * 8 + (0, None, None))
        f_c = jax.vmap(f_f, in_axes=(None,) * 5
                       + (None, 0, None, None, None, None))
        f_kc = jax.vmap(f_c, in_axes=(None,) * 5
                        + (0, None, None, None, None, rm_ax))
        f_pkc = jax.vmap(f_kc, in_axes=(0, 0, 0, 0, None, None, None,
                                        0, None, None, None))
        f_tpkc = jax.vmap(f_pkc, in_axes=(0, 0, 0, 0, 0, None, None,
                                          None, None, 0, None))
        return f_tpkc(arrival, bank, row, is_write,
                      jnp.asarray(valid, bool), tables, scns, closed,
                      f_rows, u, region_map)

    def one(a, b, r, w, v, tbl, scn, c, rm):
        return replay_adaptive(a, b, r, w, v, tbl, bins, scn, tcfg, c,
                               n_banks, mlp_window, region_map=rm)

    f_c = jax.vmap(one, in_axes=(None,) * 5 + (None, 0, None, None))
    f_kc = jax.vmap(f_c, in_axes=(None,) * 5 + (0, None, None, rm_ax))
    f_pkc = jax.vmap(f_kc, in_axes=(0, 0, 0, 0, None, None, None, 0,
                                    None))
    f_tpkc = jax.vmap(f_pkc, in_axes=(0, 0, 0, 0, 0, None, None, None,
                                      None))
    return f_tpkc(arrival, bank, row, is_write,
                  jnp.asarray(valid, bool), tables, scns, closed,
                  region_map)
