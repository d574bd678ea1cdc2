"""Plain reference of the DRAM request replay, in numpy.

One memory controller per lane: a lane is one request stream replayed
under one timing row.  Requests are serviced one at a time in issue
order (FCFS, or the FR-FCFS-lite order of `frfcfs_order`) against a
per-bank state machine (open row, last ACT, write-recovery end, bank
ready), with a bounded memory-level-parallelism gate (request i issues
no earlier than request i - mlp_window completed) and, with several
channels, a per-channel data-bus gate.  All arithmetic is in the
configuration's precision (`dtype`); float32 is what the deployments
state, and a lower precision is the control that has to fail.

This file is the benchmark's own statement of the replay semantics; it
imports nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

# timing-row columns: (trcd, tras, twr, trp, trefi_ms, tcl)
TRCD, TRAS, TWR, TRP, TCL = 0, 1, 2, 3, 5
ILEAVE = {"row": 0, "cacheline": 1, "bank_xor": 2}


def frfcfs_order(arrival, bank, row, window: int, slack_ns: float,
                 max_defer: int) -> np.ndarray:
    """Issue order of FR-FCFS-lite: among the next `window` pending
    requests issue the oldest that hits its bank's open row and arrives
    within `slack_ns` of the head request, else the head; after
    `max_defer` consecutive deferrals the head goes first.  Horizon
    arithmetic is float32."""
    arrival = np.asarray(arrival, np.float32)
    bank = np.asarray(bank).tolist()
    row = np.asarray(row).tolist()
    n = len(bank)
    slack = np.float32(slack_ns)
    order = np.empty(n, np.int64)
    open_row: dict[int, int] = {}
    pend = list(range(n))
    defer = 0
    for k in range(n):
        pick = 0
        if defer < max_defer:
            horizon = np.float32(arrival[pend[0]] + slack)
            for j in range(min(window, len(pend))):
                i = pend[j]
                if arrival[i] <= horizon and open_row.get(bank[i]) == row[i]:
                    pick = j
                    break
        i = pend.pop(pick)
        defer = defer + 1 if pick > 0 else 0
        open_row[bank[i]] = row[i]
        order[k] = i
    return order


def channel_of(bank, row, ileave: int, n_channels: int, n_ranks: int,
               n_banks: int):
    """(channel, rank) of each request under an interleaving policy."""
    bank = np.asarray(bank, np.int64)
    row = np.asarray(row, np.int64)
    c = n_channels
    if ileave == 0:
        ch = row % c
    elif ileave == 1:
        ch = (row * n_banks + bank) % c
    else:
        ch = (bank ^ row) % c
    return ch, (row // c) % n_ranks


def replay(arrival, bank, row, is_write, rows, closed=None, ileave=None,
           n_banks: int = 8, mlp_window: int = 8, n_channels: int = 1,
           n_ranks: int = 1, t_burst: float = 5.0, dtype=np.float32):
    """Replay L lanes at once.

    arrival/bank/row/is_write: [L, N] request streams in issue order;
    rows: [L, 6] timing row of each lane; closed: [L] bool page policy
    (default open); ileave: [L] interleave codes (multi-channel only).
    Returns (latency [L, N], total runtime [L]) in `dtype`."""
    f = np.dtype(dtype).type
    arrival = np.asarray(arrival).astype(dtype)
    bank = np.asarray(bank, np.int64)
    row = np.asarray(row, np.int64)
    is_write = np.asarray(is_write, bool)
    rows = np.asarray(rows).astype(dtype)
    n_lanes, n = arrival.shape
    closed = (np.zeros(n_lanes, bool) if closed is None
              else np.asarray(closed, bool))
    trcd, tras, twr, trp, tcl = (rows[:, c] for c in
                                 (TRCD, TRAS, TWR, TRP, TCL))
    multi = n_channels * n_ranks > 1
    if multi:
        ch, rk = channel_of(bank, row, 0, n_channels, n_ranks, n_banks)
        for code in (1, 2):
            sel = np.asarray(ileave) == code
            ch[sel], rk[sel] = channel_of(bank[sel], row[sel], code,
                                          n_channels, n_ranks, n_banks)
        g_all = (ch * n_ranks + rk) * n_banks + bank
    else:
        g_all = bank
    groups = n_channels * n_ranks * n_banks
    lane = np.arange(n_lanes)
    open_row = np.full((groups, n_lanes), -1, np.int64)
    act = np.zeros((groups, n_lanes), dtype)
    wrd = np.zeros((groups, n_lanes), dtype)
    rdy = np.zeros((groups, n_lanes), dtype)
    ring = np.zeros((mlp_window, n_lanes), dtype)
    bus = np.zeros((n_channels, n_lanes), dtype)
    t_burst = f(t_burst)
    zero = f(0.0)
    lat = np.empty((n_lanes, n), dtype)
    for i in range(n):
        t = arrival[:, i]
        g = g_all[:, i]
        r = row[:, i]
        w = is_write[:, i]
        gate = ring[i % mlp_window].copy()
        if multi:
            c = ch[:, i]
            gate = np.maximum(gate, bus[c, lane])
        open_b, act_b = open_row[g, lane], act[g, lane]
        wrd_b, rdy_b = wrd[g, lane], rdy[g, lane]
        start = np.maximum(np.maximum(t, rdy_b), gate)
        hit = open_b == r
        empty = open_b == -1
        pre_ok = np.maximum(act_b + tras, wrd_b)
        c_start = np.maximum(start, pre_ok)
        act_new = np.where(hit, act_b,
                           np.where(empty, start + zero, c_start + trp))
        data = np.where(hit, start,
                        np.where(empty, start + trcd,
                                 c_start + trp + trcd))
        done = data + tcl
        wrd_new = np.where(w, done + twr, wrd_b)
        pre_start = np.maximum(np.maximum(done, act_new + tras), wrd_new)
        rdy_new = np.where(closed, pre_start + trp, done)
        open_row[g, lane] = np.where(closed, -1, r)
        act[g, lane] = act_new
        wrd[g, lane] = wrd_new
        rdy[g, lane] = rdy_new
        ring[i % mlp_window] = done
        lat[:, i] = done - np.maximum(t, gate)
        if multi:
            bus[c, lane] = done - tcl + t_burst
    total = np.maximum(rdy.max(0), wrd.max(0))
    return lat, total


def stats(lat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(mean, p99) over the last axis of full-length latency rows.  The
    p99 interpolates between order statistics at q = 0.99 (n - 1), the
    quantile position computed in float32."""
    lat = np.asarray(lat)
    n = lat.shape[-1]
    mean = lat.astype(np.float64).mean(-1)
    s = np.sort(lat.astype(np.float64), axis=-1)
    q = float(np.float32(0.99) * np.float32(n - 1))
    lo, hi = int(np.floor(q)), int(np.ceil(q))
    frac = q - lo
    return mean, s[..., lo] + (s[..., hi] - s[..., lo]) * frac


def campaign(streams, policies, rows, n_banks: int = 8,
             mlp_window: int = 8, n_channels: int = 1, n_ranks: int = 1,
             t_burst: float = 5.0, dtype=np.float32) -> dict:
    """Mean, p99 and runtime of every (stream, policy, row) lane, each
    [streams, policies, rows].  streams: (arrival, bank, row, is_write)
    in arrival order; policies: dicts of page ("open"/"closed"),
    reorder_window, reorder_slack_ns and interleave."""
    lanes: dict[str, list] = {k: [] for k in (
        "arrival", "bank", "row", "is_write", "rows", "closed", "ileave")}
    n = len(streams[0][0])
    for stream in streams:
        for p in policies:
            closed = p.get("page", "open") == "closed"
            w = int(p.get("reorder_window", 0))
            order = (frfcfs_order(stream[0], stream[1], stream[2], w,
                                  p.get("reorder_slack_ns", 30.0), 4 * w)
                     if w > 1 and not closed else np.arange(n))
            for r in rows:
                for k, a in zip(("arrival", "bank", "row", "is_write"),
                                stream):
                    lanes[k].append(np.asarray(a)[order])
                lanes["rows"].append(r)
                lanes["closed"].append(closed)
                lanes["ileave"].append(ILEAVE[p.get("interleave", "row")])
    lat, total = replay(
        *(np.stack(lanes[k]) for k in ("arrival", "bank", "row",
                                       "is_write", "rows")),
        closed=np.asarray(lanes["closed"]),
        ileave=np.asarray(lanes["ileave"]), n_banks=n_banks,
        mlp_window=mlp_window, n_channels=n_channels, n_ranks=n_ranks,
        t_burst=t_burst, dtype=dtype)
    mean, p99 = stats(lat)
    shape = (len(streams), len(policies), len(rows))
    return {"mean": mean.reshape(shape), "p99": p99.reshape(shape),
            "total": np.asarray(total, np.float64).reshape(shape)}
