"""Plain reference of the YCSB workload A key-value stream on one
dual-rank DDR4 channel, from `--seed`.

Per stream (a threefry key folded with the stream index), n // 16 + 1
operations are drawn, each in one batch of JAX draws split four ways:

  * 32 random bits: the Zipf(theta) rank of the operation's record by
    the inverse CDF, `searchsorted(table, bits, "right")`, where table
    entry i = floor(2**32 * CDF(i)) in float64 (rank i weighs
    (i + 1)**-theta);
  * a uniform below `update_frac`: the operation is an update;
  * an integer in [0, 10): the field an update writes;
  * an exponential gap times the mean operation interval: the
    operations' arrival times are the running sum.

The record slot is YCSB's ScrambledZipfianGenerator scramble of the
rank: |FNV-1a-64 of its 8 little-endian bytes, as a signed 64-bit
integer| modulo the record count, here in numpy uint64.  Slot s holds
bytes [1024 s, 1024 s + 1024); a read is its 16 lines of 64 bytes, an
update those 16 and then 2 written lines from the one holding the
field's first byte (field f starts at byte 100 f).  The requests of
each operation arrive at its time, and the first n of the stream are
kept.

A line address (byte address >> 6) splits, low bits first, into 3
bits of column, 2 of bank group, 4 of column, 2 of bank, 1 of rank and
16 of row.  The stream is returned as (arrival, bank, row, is_write)
with bank = group * 4 + bank and row = row * 2 + rank.

Nothing here imports the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINES = 16
WRITE_LINES = 2
FIELDS = 10
FIELD_BYTES = 100
FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3


@functools.lru_cache(maxsize=2)
def zipf_table(n_items: int, theta: float) -> np.ndarray:
    """uint32 [n_items - 1]: floor(2**32 * CDF(i)), CDF in float64."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -float(theta)
    c = np.cumsum(w)
    return np.floor(c[:-1] / c[-1] * 2.0 ** 32).astype(np.uint32)


def scramble(rank, n_items: int) -> np.ndarray:
    """YCSB's `Math.abs(fnvhash64(rank)) % n_items`, in numpy uint64."""
    v = np.asarray(rank, np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME)
    return np.abs(h.view(np.int64)) % n_items


@functools.partial(jax.jit, static_argnums=(1,))
def _draws(key, m, update_frac, interval):
    kz, ku, kf, ka = jax.random.split(key, 4)
    bits = jax.random.bits(kz, (m,), jnp.uint32)
    update = jax.random.uniform(ku, (m,)) < update_frac
    field = jax.random.randint(kf, (m,), 0, FIELDS)
    t_op = jnp.cumsum(jax.random.exponential(ka, (m,)) * interval)
    return bits, update, field, t_op


def ops(seed: int, stream: int, n: int, y: dict):
    """(slot, update, field, time) [n // 16 + 1] of one stream's
    operations (y: the traffic file's "ycsb" parameters)."""
    m = n // LINES + 1
    key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
    bits, update, field, t_op = (np.asarray(x) for x in _draws(
        key, m, np.float32(y["update_frac"]),
        np.float32(y["op_interval_ns"])))
    rank = np.searchsorted(zipf_table(int(y["n_records"]),
                                      float(y["zipf_theta"])),
                           bits, side="right")
    return scramble(rank, int(y["n_records"])), update, field, t_op


def stream(seed: int, stream: int, n: int, y: dict):
    """One stream's first n requests: (arrival f32, bank, row,
    is_write) [n]."""
    slot, update, field, t_op = ops(seed, stream, n, y)
    arrival, line_addr, is_write = [], [], []
    for s, up, f, t in zip(slot.tolist(), update.tolist(),
                           field.tolist(), t_op.tolist()):
        lines = list(range(LINES))
        if up:
            first = f * FIELD_BYTES // 64
            lines += list(range(first, first + WRITE_LINES))
        for j, line in enumerate(lines):
            arrival.append(t)
            line_addr.append(s * LINES + line)
            is_write.append(j >= LINES)
        if len(arrival) >= n:
            break
    a = np.asarray(line_addr[:n], np.int64)
    group = (a >> 3) & 3
    bank = (a >> 9) & 3
    rank = (a >> 11) & 1
    row = a >> 12
    return (np.asarray(arrival[:n], np.float32),
            (group * 4 + bank).astype(np.int32),
            (row * 2 + rank).astype(np.int32),
            np.asarray(is_write[:n], bool))
