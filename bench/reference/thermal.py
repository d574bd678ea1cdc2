"""Plain reference of the adaptive (closed thermal loop) replay and of
the static bracket around it, in numpy.

Per request of a lane (one stream under one thermal scenario): the
per-bank heat decays by exp(-gap / tau) over the gap since the
previous request; the controller senses the scenario's ambient plus
the banks' summed overheat (added in bank order); the selected bin
rounds up to the smallest bin edge >= the reading (the JEDEC row above
the hottest bin), and steps down only once the reading has fallen the
hysteresis margin (config hysteresis x the scenario's scale) below the
cooler edge; the request is serviced as in `reference.replay` under the
selected row; and the access deposits c_heat x energy on its bank,
energy = e_burst + miss x (e_act_pre + p_act_standby x tRAS of the
selected row).

The static bracket provisions each scenario for the peak sensed
temperature over every stream and policy of its adaptive replay, plus
the hysteresis margin, rounded up to a bin (index = number of bins
means the JEDEC row), and replays every stream under the JEDEC
baseline and each scenario's worst-case row.

The ambient and the decay factors are evaluated with jax.numpy on the
default device; the loop runs in numpy.  Nothing here imports the
program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.traffic import curve_at

TRCD, TRAS, TWR, TRP, TCL = 0, 1, 2, 3, 5


@jax.jit
def _drive(arrival, scn, tau):
    """Ambient [L, N] and decay factors [L, N] of lanes with their own
    scenario rows [L, 8+] and streams [L, N]."""
    amb = jax.vmap(curve_at)(scn, arrival)
    prev = jnp.concatenate([jnp.zeros_like(arrival[:, :1]),
                            arrival[:, :-1]], axis=-1)
    decay = jnp.exp(-jnp.maximum(arrival - prev, 0.0) / tau)
    return amb, decay


def adaptive(arrival, bank, row, is_write, table, bins, scn, tcfg,
             n_banks: int = 8, mlp_window: int = 8, dtype=np.float32):
    """Replay L lanes under the in-scan bin selection.

    arrival/bank/row/is_write: [L, N] streams (issue order = arrival
    order: FCFS); table: [bins + 1, 6] rows, JEDEC last; bins: [S]
    edges; scn: [L, 9] scenario rows (the last column scales the
    hysteresis); tcfg: (tau_ns, c_heat, hyst_c, e_burst, e_act_pre,
    p_act_standby).  Returns dict of lat [L, N], temps [L, N], bins
    [L, N], total [L], heat [L, banks]."""
    f = np.dtype(dtype).type
    amb, decay = (np.asarray(x).astype(dtype) for x in _drive(
        jnp.asarray(arrival, jnp.float32), jnp.asarray(scn, jnp.float32),
        jnp.float32(tcfg[0])))
    arrival = np.asarray(arrival).astype(dtype)
    bank = np.asarray(bank, np.int64)
    row = np.asarray(row, np.int64)
    is_write = np.asarray(is_write, bool)
    table = np.asarray(table).astype(dtype)
    edges = np.asarray(bins).astype(dtype)
    n_lanes, n = arrival.shape
    c_heat, hyst_c, e_burst, e_act_pre, p_as = (f(x) for x in tcfg[1:6])
    hyst = (hyst_c * np.asarray(scn)[:, 8]).astype(dtype)
    lane = np.arange(n_lanes)
    open_row = np.full((n_banks, n_lanes), -1, np.int64)
    act = np.zeros((n_banks, n_lanes), dtype)
    wrd = np.zeros((n_banks, n_lanes), dtype)
    rdy = np.zeros((n_banks, n_lanes), dtype)
    heat = np.zeros((n_banks, n_lanes), dtype)
    ring = np.zeros((mlp_window, n_lanes), dtype)
    cur = np.zeros(n_lanes, np.int64)
    one = f(1.0)
    zero = f(0.0)
    lat = np.empty((n_lanes, n), dtype)
    temps = np.empty((n_lanes, n), dtype)
    sel = np.empty((n_lanes, n), np.int64)
    for i in range(n):
        t, b, r, w = arrival[:, i], bank[:, i], row[:, i], is_write[:, i]
        heat = heat * decay[:, i]
        total = heat[0]
        for k in range(1, n_banks):
            total = total + heat[k]
        sensed = amb[:, i] + total
        up = np.searchsorted(edges, sensed, side="left")
        down = np.searchsorted(edges, sensed + hyst, side="left")
        cur = np.maximum(up, np.minimum(cur, down))
        tp = table[cur]                                   # [L, 6]
        trcd, tras, twr, trp, tcl = (tp[:, c] for c in
                                     (TRCD, TRAS, TWR, TRP, TCL))
        gate = ring[i % mlp_window].copy()
        open_b, act_b = open_row[b, lane], act[b, lane]
        wrd_b, rdy_b = wrd[b, lane], rdy[b, lane]
        start = np.maximum(np.maximum(t, rdy_b), gate)
        hit = open_b == r
        empty = open_b == -1
        c_start = np.maximum(start, np.maximum(act_b + tras, wrd_b))
        act[b, lane] = np.where(hit, act_b, np.where(empty, start + zero,
                                                     c_start + trp))
        data = np.where(hit, start, np.where(empty, start + trcd,
                                             c_start + trp + trcd))
        done = data + tcl
        wrd[b, lane] = np.where(w, done + twr, wrd_b)
        rdy[b, lane] = done
        open_row[b, lane] = r
        ring[i % mlp_window] = done
        lat[:, i] = done - np.maximum(t, gate)
        miss = one - hit.astype(dtype)
        energy = e_burst + miss * (e_act_pre + p_as * tras)
        heat[b, lane] = heat[b, lane] + c_heat * energy
        temps[:, i] = sensed
        sel[:, i] = cur
    return {"lat": lat, "temps": temps, "bins": sel,
            "total": np.maximum(rdy.max(0), wrd.max(0)),
            "heat": heat.T.copy()}
