"""Plain reference of the AL-DRAM profile: population, margins, table.

A module population is drawn from `--seed` as lognormal cell
parameters over a (module, chip, bank, tail cell) hierarchy.  Each
cell's read and write margins under a timing combo (tRCD, tRAS, tWR,
tRP, tREFI) at a test temperature follow the closed-form RC charge
model of the paper's Sec. 3: sensing from the charge left after
leakage, a partial restore or write drive, a precharge residual, and
the steady state of the refresh loop found by a fixed point.  A margin
>= 0 means error-free.

The profile (paper Sec. 5) is, per module: the longest refresh
interval at which every cell passes at standard timings (85 C), less
an 8 ms guardband; then, at that interval and each temperature bin,
the passing combo of least latency sum (least tRCD on a tie, the
slowest combo when none passes) for the read and the write test, per
module and per rank-level bank; one register row takes the larger
tRCD and tRP of the two tests, tRAS from the read and tWR from the
write test.

Margins are computed with jax.numpy in the precision asked for; the
rest is numpy.  Nothing here imports the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FIELDS = ("tau_r", "xfer", "tau_ret85", "tau_p", "tau_w")
WEAK_SIGNS = (+1.0, -1.0, -1.0, +1.0, +1.0)
FIXED_POINT_ITERS = 8


# ------------------------------------------------------------ population
def _hier_field(key, shape, v, mu, weak_sign, k_field, extra=None):
    km, kc, kb, kx = jax.random.split(key, 4)
    m, c, b, _ = shape
    z = (jax.random.normal(km, (m, 1, 1, 1)) * v["s_module"]
         + jax.random.normal(kc, (m, c, 1, 1)) * v["s_chip"]
         + jax.random.normal(kb, (m, c, b, 1)) * v["s_bank"])
    tail = jnp.abs(jax.random.normal(kx, shape)) * v["s_cell"]
    if extra is not None:
        tail = tail + extra * v["s_cell"]
    return mu * jnp.exp(k_field * (z + weak_sign * tail))


@functools.partial(jax.jit, static_argnums=(1,))
def _population(key, shape, v):
    k_r, k_x, k_t, k_p, k_w, k_c = jax.random.split(key, 6)
    shared = jnp.abs(jax.random.normal(k_c, shape)) * v["rc_ret_corr"]
    cells = jnp.stack([
        _hier_field(k_r, shape, v, v["mu_tau_r"], +1.0, v["k_tau_r"],
                    shared),
        _hier_field(k_x, shape, v, v["mu_xfer"], -1.0, v["k_xfer"]),
        _hier_field(k_t, shape, v, v["mu_tau_ret85"], -1.0,
                    v["k_tau_ret"], shared),
        _hier_field(k_p, shape, v, v["mu_tau_p"], +1.0, v["k_tau_p"]),
        _hier_field(k_w, shape, v, v["mu_tau_w"], +1.0, v["k_tau_w"]),
    ], axis=-1)
    return cells.astype(jnp.float32)


def population(seed: int, cfg: dict) -> jax.Array:
    """[modules, chips, banks, cells, 5] cell parameters on the default
    device, drawn from `seed` (cfg = the config's "population")."""
    shape = (cfg["n_modules"], cfg["n_chips"], cfg["n_banks"],
             cfg["n_cells"])
    v = {k: jnp.float32(x) for k, x in cfg["variation"].items()}
    return _population(jax.random.PRNGKey(seed), shape, v)


# ------------------------------------------------------------ combo grids
def _down(standard: float, lo: float, step: float) -> np.ndarray:
    n = int(np.floor((standard - lo) / step + 1e-9)) + 1
    return standard - step * np.arange(n)


def combo_grid(op: str, std_row, step: float) -> np.ndarray:
    """[C, 5] (trcd, tras, twr, trp, trefi) combos of the read test
    (tRCD, tRAS, tRP swept; tWR standard) or the write test (tRCD, tWR,
    tRP swept; tRAS standard), down from the standard row."""
    trcd_s, tras_s, twr_s, trp_s, trefi_s = (float(x) for x in std_row[:5])
    a = _down(trcd_s, 3.75, step)
    b = (_down(tras_s, 12.5, 2 * step) if op == "read"
         else _down(twr_s, 2.5, step))
    c = _down(trp_s, 3.75, step)
    g = np.stack(np.meshgrid(a, b, c, indexing="ij"), -1).reshape(-1, 3)
    out = np.zeros((g.shape[0], 5), np.float32)
    out[:, 0], out[:, 3], out[:, 4] = g[:, 0], g[:, 2], trefi_s
    if op == "read":
        out[:, 1], out[:, 2] = g[:, 1], twr_s
    else:
        out[:, 1], out[:, 2] = tras_s, g[:, 1]
    return out


def refresh_grid(step_ms: float = 8.0) -> np.ndarray:
    return np.arange(8.0, 512.0 + step_ms / 2, step_ms, dtype=np.float32)


# ------------------------------------------------------------ margins
def _margins(cells, combos, temps, trefi_r, trefi_w, k):
    """(read, write) margins [n, m] of cells [n, 5] under combos
    [m, 5] at per-combo temperatures [m]; trefi_r/trefi_w: per-cell
    refresh intervals [n] (negative = the combo's own)."""
    f = cells.dtype
    tau_r, xfer, tau_ret85, tau_p, tau_w = (cells[:, i:i + 1]
                                            for i in range(5))
    trcd, tras, twr, trp, trefi = (combos[None, :, i] for i in range(5))
    t = temps[None, :]

    def rc(tau):
        return tau * (1.0 + k["k_rc"] * jnp.maximum(t - 55.0, 0.0))

    tau_r_t = rc(tau_r)
    retention = tau_ret85 * jnp.exp(k["k_ret"] * (85.0 - t))
    residual = k["v_precharge"] * jnp.exp(
        -jnp.maximum(trp - k["t_p0"], 0.0) / tau_p)

    def dv(q):
        return (q - 0.5) * xfer

    def sense(q):
        d = jnp.maximum(dv(q) - residual, 1e-6)
        return (k["t_wl"] + k["alpha_share"] * tau_r_t
                + k["tau_s"] * jnp.log(k["dv_full"] / d))

    # read / refresh steady state
    tr = jnp.where(trefi_r[:, None] >= 0, trefi_r[:, None], trefi)
    leak = jnp.exp(-tr / retention)
    tau_w_t = rc(tau_w)

    def body(_, q):
        q_acc = 0.5 + (q - 0.5) * leak
        t_rest = jnp.maximum(tras - sense(q_acc), 0.0)
        q_shared = 0.5 + (q_acc - 0.5) * xfer
        return 1.0 - (1.0 - q_shared) * jnp.exp(-t_rest / tau_w_t)

    q = jax.lax.fori_loop(0, FIXED_POINT_ITERS, body,
                          jnp.full(leak.shape, 0.95, f) + 0.0 * tras)
    q_acc = 0.5 + (q - 0.5) * leak
    read = jnp.minimum((dv(q_acc) - residual - k["dv_min"]) / k["dv_min"],
                       trcd - sense(q_acc))

    # write / refresh steady state
    tw = jnp.where(trefi_w[:, None] >= 0, trefi_w[:, None], trefi)
    leak_w = jnp.exp(-tw / (retention * k["kappa_w"]))
    drive = rc(tau_w) * k["beta_w"]
    q_written = 1.0 - (1.0 - 0.05) * jnp.exp(
        -jnp.maximum(twr + k["t_wr_base"], 0.0) / drive)
    q_sense = 0.5 + (q_written - 0.5) * leak_w
    d = jnp.maximum(dv(q_sense) - residual, 1e-6)
    t_open = (k["t_wl"] + k["alpha_share"] * tau_r_t
              + k["tau_s"] * jnp.log(jnp.maximum(k["dv_full_w"] / d, 1e-6)))
    write = jnp.minimum(
        jnp.minimum((dv(q_sense) - residual - k["dv_min"]) / k["dv_min"],
                    trcd - t_open),
        twr - k["t_wr_floor"] * (tau_r_t / 4.5))
    return read, write


@functools.partial(jax.jit, static_argnums=(5,))
def _margins_jit(cells, combos, temps, trefi_r, trefi_w, dtype, k):
    kd = {n: jnp.asarray(x, dtype) for n, x in k.items()}
    return _margins(cells.astype(dtype), combos.astype(dtype),
                    temps.astype(dtype), trefi_r.astype(dtype),
                    trefi_w.astype(dtype), kd)


def margins(cells, combos, temps, trefi_r, trefi_w, constants: dict,
            dtype=jnp.float32):
    """Host (read, write) margin grids [n, m] as float32 arrays."""
    r, w = _margins_jit(jnp.asarray(cells), jnp.asarray(combos),
                        jnp.asarray(temps, jnp.float32),
                        jnp.asarray(trefi_r, jnp.float32),
                        jnp.asarray(trefi_w, jnp.float32),
                        jnp.dtype(dtype), constants)
    return (np.asarray(r.astype(jnp.float32)),
            np.asarray(w.astype(jnp.float32)))


# ------------------------------------------------------------ profile
def select(combos: np.ndarray, ok: np.ndarray, lat_cols) -> np.ndarray:
    """Per leading index of `ok` [..., C]: the passing combo of least
    latency sum over `lat_cols`, least tRCD on a tie; the slowest combo
    when none passes.  Returns [..., 5]."""
    s = combos[:, list(lat_cols)].sum(-1)
    order = np.lexsort((combos[:, 0], s))
    ok_o = ok[..., order]
    pick = np.where(ok_o.any(-1), order[ok_o.argmax(-1)], int(s.argmax()))
    return combos[pick]


def profile_module(cells_m, std_row, temp_bins, constants: dict,
                   step_ns: float, guardband_ms: float = 8.0,
                   refresh_temp: float = 85.0, dtype=jnp.float32) -> dict:
    """Profile ONE module ([chips, banks, cells, 5]): its safe refresh
    intervals (read, write) and its register rows, module-level
    [bins, 4] and per-bank [bins, banks, 4]."""
    ch, bk, kc = cells_m.shape[:3]
    flat = jnp.asarray(cells_m).reshape(-1, 5)
    n = flat.shape[0]
    none = np.full(n, -1.0, np.float32)
    grid = refresh_grid()
    rc = np.repeat(np.asarray(std_row[:5], np.float32)[None], len(grid), 0)
    rc[:, 4] = grid
    rm, wm = margins(flat, rc, np.full(len(grid), refresh_temp, np.float32),
                     none, none, constants, dtype)
    safe = []
    for mg in (rm, wm):
        fail = ~(mg >= 0.0).all(0)                  # [grid]
        idx = int(fail.argmax()) if fail.any() else len(grid)
        best = grid[max(idx - 1, 0)]
        safe.append(np.float32(max(best - guardband_ms, grid[0])))
    read_c = combo_grid("read", std_row, step_ns)
    write_c = combo_grid("write", std_row, step_ns)
    nt = len(temp_bins)
    cols = np.concatenate([np.tile(read_c, (nt, 1)),
                           np.tile(write_c, (nt, 1))])
    temps = np.concatenate([np.repeat(np.float32(temp_bins), len(read_c)),
                            np.repeat(np.float32(temp_bins), len(write_c))])
    rm, wm = margins(flat, cols, temps, np.full(n, safe[0], np.float32),
                     np.full(n, safe[1], np.float32), constants, dtype)
    nr = nt * len(read_c)
    chosen = {}
    for op, mg, combos, lat_cols in (
            ("read", rm[:, :nr], read_c, (0, 1, 3)),
            ("write", wm[:, nr:], write_c, (0, 2, 3))):
        ok = (mg.reshape(ch, bk, kc, nt, -1) >= 0.0).all(2).all(0)
        chosen[op] = (select(combos, ok.all(0), lat_cols),     # [T, 5]
                      select(combos, ok, lat_cols))            # [B, T, 5]

    def combine(r, w):
        p = np.empty(r.shape[:-1] + (4,), np.float32)
        p[..., 0] = np.maximum(r[..., 0], w[..., 0])
        p[..., 1] = r[..., 1]
        p[..., 2] = w[..., 2]
        p[..., 3] = np.maximum(r[..., 3], w[..., 3])
        return p

    return {"safe_trefi_read": safe[0], "safe_trefi_write": safe[1],
            "params_module": combine(chosen["read"][0], chosen["write"][0]),
            "params_bank": combine(chosen["read"][1],
                                   chosen["write"][1]).transpose(1, 0, 2)}
