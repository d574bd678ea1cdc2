"""Plain reference of the DRAM request replay on a DDR4 channel with
ranks and bank groups, in numpy.

One FCFS memory controller per lane (a request stream under one timing
row and page policy), requests serviced one at a time in issue order.
Each bank keeps its open row, last ACT, write-recovery end and ready
time; each rank its last ACT, last column command and its last four
ACTs; each bank group (rank, bank // (banks / groups)) its last ACT and
column; the channel the time its data bus frees.  A request's rank is
its row mod the rank count (one channel).  For a request at time t:

  gate   = completion of the request mlp_window before it
  start  = max(t, bank ready, gate)
  hit    = the bank's open row is the request's; empty = no open row
  cstart = max(start, bank ACT + tRAS, bank write recovery)
  bank-alone ACT    = start (empty) or cstart + tRP (conflict)
  bank-alone column = start (hit), start + tRCD (empty) or
                      cstart + tRP + tRCD (conflict), and no earlier
                      than the bus
  ACT    = max(bank-alone ACT, rank last ACT + tRRD_S,
               group last ACT + tRRD_L, rank fourth-last ACT + tFAW)
  column = max(start (hit) or ACT + tRCD, bus, rank last column +
               tCCD_S, group last column + tCCD_L)
  done   = column + tCL;  latency = done - max(t, gate)
  stall  = column - bank-alone column (summed per lane)

then the bank: open row (none under closed page), ACT, write recovery
(done + tWR after a write), ready = column (open page) or max(done,
ACT + tRAS, write recovery) + tRP (closed page); a miss's ACT becomes
its rank's and group's last and joins the rank's last four; the column
becomes the rank's and group's last; the bus frees at done - tCL +
t_burst.  Runtime is the latest of every bank's ready, write recovery
and completion.  Commands never issued count as at -1e30.  All
arithmetic is in `dtype`.

Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

from reference.replay import TCL, TRAS, TRCD, TRP, TWR, stats

NEVER = -1e30


def replay(arrival, bank, row, is_write, rows, closed, rank_ns,
           n_ranks: int = 2, n_banks: int = 16, n_groups: int = 4,
           mlp_window: int = 8, t_burst: float = 3.3333333333333335,
           dtype=np.float32):
    """Replay L lanes: arrival/bank/row/is_write [L, N], rows [L, 6],
    closed [L] bool, rank_ns (tRRD_S, tRRD_L, tFAW, tCCD_S, tCCD_L).
    Returns (latency [L, N], runtime [L], stall [L]) in `dtype`."""
    f = np.dtype(dtype).type
    arrival = np.asarray(arrival).astype(dtype)
    bank = np.asarray(bank, np.int64)
    row = np.asarray(row, np.int64)
    is_write = np.asarray(is_write, bool)
    rows = np.asarray(rows).astype(dtype)
    closed = np.asarray(closed, bool)
    n_lanes, n = arrival.shape
    trcd, tras, twr, trp, tcl = (rows[:, c] for c in
                                 (TRCD, TRAS, TWR, TRP, TCL))
    trrd_s, trrd_l, tfaw, tccd_s, tccd_l = (f(x) for x in rank_ns)
    rank = row % n_ranks
    b_all = rank * n_banks + bank
    g_all = rank * n_groups + bank // (n_banks // n_groups)
    lane = np.arange(n_lanes)
    never = f(NEVER)
    open_row = np.full((n_ranks * n_banks, n_lanes), -1, np.int64)
    act = np.zeros((n_ranks * n_banks, n_lanes), dtype)
    wrd = np.zeros((n_ranks * n_banks, n_lanes), dtype)
    rdy = np.zeros((n_ranks * n_banks, n_lanes), dtype)
    ring = np.zeros((mlp_window, n_lanes), dtype)
    bus = np.full(n_lanes, never, dtype) if n_ranks == 1 else \
        np.zeros(n_lanes, dtype)
    r_act = np.full((n_ranks, n_lanes), never, dtype)
    r_col = np.full((n_ranks, n_lanes), never, dtype)
    last4 = np.full((n_ranks, n_lanes, 4), never, dtype)  # oldest first
    g_act = np.full((n_ranks * n_groups, n_lanes), never, dtype)
    g_col = np.full((n_ranks * n_groups, n_lanes), never, dtype)
    stall = np.zeros(n_lanes, dtype)
    t_burst = f(t_burst)
    zero = f(0.0)
    lat = np.empty((n_lanes, n), dtype)
    for i in range(n):
        t, b, k, g = arrival[:, i], b_all[:, i], rank[:, i], g_all[:, i]
        r, w = row[:, i], is_write[:, i]
        gate = ring[i % mlp_window].copy()
        open_b, act_b = open_row[b, lane], act[b, lane]
        wrd_b, rdy_b = wrd[b, lane], rdy[b, lane]
        start = np.maximum(np.maximum(t, rdy_b), gate)
        hit = open_b == r
        empty = open_b == -1
        c_start = np.maximum(start, np.maximum(act_b + tras, wrd_b))
        bank_act = np.where(empty, start + zero, c_start + trp)
        bank_col = np.where(hit, start,
                            np.where(empty, start + trcd,
                                     c_start + trp + trcd))
        bank_col = np.maximum(bank_col, bus)
        act_new = np.where(hit, act_b, np.maximum(
            np.maximum(bank_act, r_act[k, lane] + trrd_s),
            np.maximum(g_act[g, lane] + trrd_l,
                       last4[k, lane, 0] + tfaw)))
        col = np.maximum(
            np.maximum(np.where(hit, start, act_new + trcd), bus),
            np.maximum(r_col[k, lane] + tccd_s, g_col[g, lane] + tccd_l))
        stall = stall + (col - bank_col)
        done = col + tcl
        wrd_new = np.where(w, done + twr, wrd_b)
        pre_start = np.maximum(np.maximum(done, act_new + tras), wrd_new)
        open_row[b, lane] = np.where(closed, -1, r)
        act[b, lane] = act_new
        wrd[b, lane] = wrd_new
        rdy[b, lane] = np.where(closed, pre_start + trp, col)
        miss = ~hit
        r_act[k, lane] = np.where(miss, act_new, r_act[k, lane])
        g_act[g, lane] = np.where(miss, act_new, g_act[g, lane])
        four = last4[k, lane]
        last4[k, lane] = np.where(
            miss[:, None],
            np.concatenate([four[:, 1:], act_new[:, None]], axis=1), four)
        r_col[k, lane] = col
        g_col[g, lane] = col
        ring[i % mlp_window] = done
        lat[:, i] = done - np.maximum(t, gate)
        if n_ranks > 1:
            bus = done - tcl + t_burst
    total = np.maximum(np.maximum(rdy.max(0), wrd.max(0)), ring.max(0))
    return lat, total, stall


def campaign(streams, policies, rows, rank_ns, n_ranks: int = 2,
             n_banks: int = 16, n_groups: int = 4, mlp_window: int = 8,
             t_burst: float = 3.3333333333333335,
             dtype=np.float32) -> dict:
    """Mean, p99, runtime and summed constraint stall of every
    (stream, policy, row) lane, each [streams, policies, rows].
    streams: (arrival, bank, row, is_write) in arrival order; policies:
    FCFS dicts of page ("open"/"closed")."""
    lanes: dict[str, list] = {k: [] for k in (
        "arrival", "bank", "row", "is_write", "rows", "closed")}
    for s in streams:
        for p in policies:
            assert int(p.get("reorder_window", 0)) <= 1, p
            for r in rows:
                for k, a in zip(("arrival", "bank", "row", "is_write"), s):
                    lanes[k].append(np.asarray(a))
                lanes["rows"].append(r)
                lanes["closed"].append(p.get("page", "open") == "closed")
    lat, total, stall = replay(
        *(np.stack(lanes[k]) for k in ("arrival", "bank", "row",
                                       "is_write", "rows")),
        np.asarray(lanes["closed"]), rank_ns, n_ranks=n_ranks,
        n_banks=n_banks, n_groups=n_groups, mlp_window=mlp_window,
        t_burst=t_burst, dtype=dtype)
    mean, p99 = stats(lat)
    shape = (len(streams), len(policies), len(rows))
    return {"mean": mean.reshape(shape), "p99": p99.reshape(shape),
            "total": np.asarray(total, np.float64).reshape(shape),
            "stall": np.asarray(stall, np.float64).reshape(shape)}
