"""Plain reference of the traffic the cells replay, from `--seed`.

Request streams are drawn with JAX's threefry generator from a key
folded per stream, so the benchmark and the program under test can
draw the same streams independently.  Per request: a bank uniform over
the banks, a row that reuses the bank's last fresh row with the
stream's row-hit probability (else a fresh uniform row), a write flag,
and an exponential gap scaled by the mean inter-arrival time; arrivals
are the running sum of the gaps.

The random draws run on JAX's default device; the row-reuse
recurrence runs in numpy, one request at a time.  Nothing here
imports the program under test.
"""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel: str) -> dict:
    with open(os.path.join(BENCH, rel)) as fh:
        return json.load(fh)


def pool(rel: str = "data/pool_knobs.json") -> dict:
    """The frozen 70-entry pool: offsets, row_hits, write_fracs,
    inter_arrivals_ns."""
    return load_json(rel)


def row_pick(bank, new_row, reuse, n_banks: int) -> np.ndarray:
    """Row of each request: the bank's last fresh row when `reuse`
    (0 before any fresh access), else `new_row`."""
    last = [0] * n_banks
    out = np.empty(len(bank), np.int32)
    for i, (b, nr, ru) in enumerate(zip(np.asarray(bank).tolist(),
                                        np.asarray(new_row).tolist(),
                                        np.asarray(reuse).tolist())):
        if not ru:
            last[b] = nr
        out[i] = last[b]
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _stream_draws(key, n, n_banks, n_rows, row_hit, write_frac, inter):
    kb, kr, kw, ka, kh = jax.random.split(key, 5)
    bank = jax.random.randint(kb, (n,), 0, n_banks)
    new_row = jax.random.randint(kr, (n,), 0, n_rows)
    reuse = jax.random.uniform(kh, (n,)) < row_hit
    arrival = jnp.cumsum(jax.random.exponential(ka, (n,)) * inter)
    is_write = jax.random.uniform(kw, (n,)) < write_frac
    return arrival, bank, new_row, reuse, is_write


def pool_stream(seed: int, offset: int, n: int, row_hit: float,
                write_frac: float, inter: float, n_banks: int = 8,
                n_rows: int = 4096):
    """One stream of the pool: (arrival f32, bank, row, is_write) [n]."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), offset)
    arrival, bank, new_row, reuse, is_write = (
        np.asarray(x) for x in _stream_draws(
            key, n, n_banks, n_rows, np.float32(row_hit),
            np.float32(write_frac), np.float32(inter)))
    return arrival, bank, row_pick(bank, new_row, reuse, n_banks), is_write


def curve_at(r, t):
    """An ambient-temperature curve row, [base, amp_sin, period_sin_ns,
    amp_step, t_step_ns, amp_burst, period_burst_ns, duty], evaluated
    at times `t` (ns)."""
    return (r[0] + r[1] * jnp.sin(2.0 * math.pi * t / r[2])
            + r[3] * (t >= r[4]).astype(jnp.float32)
            + r[5] * ((t % r[6]) < r[7] * r[6]).astype(jnp.float32))
