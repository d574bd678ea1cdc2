"""Pallas TPU kernel: the FR-FCFS-lite issue permutation of every
(trace, policy) pair of a campaign's reorder plan in one launch.

`repro.core.dram_sim.frfcfs_perm` computes one pair's permutation as a
`lax.scan` of N steps, each a handful of tiny dependent ops on a
pending buffer of ~40 slots; vmapped over the pairs the loop costs its
per-iteration overhead, not its work.  Here each pair rides one of the
128 lanes, so one step of the loop advances 128 pairs at once:

  pending buffer (arrival, bank, row, stream index, row hit):
                               [W, 128] tiles, a slot per sublane
  open row of each bank:       [n_banks, 128]
  starvation counter:          [1, 128]
  window, slack, cap per lane: [1, 128] rows

W is the plan's largest exact buffer bound (`sim_engine._eff_window`)
rounded up to 8.  A pair whose own bound is smaller keeps its answer in
the larger buffer: `slot < window` masks every slot the policy cannot
see, and slots past the bound are never eligible (`_eff_window`).

All pairs refill from the same stream position (W + step), so each
refill is one sublane row of the [N + W, 128] stream tiles and no
gather, and each step stores one row of the [N, 128] permutation.
Invalid requests (the padding suffix, and the sentinel rows past the
stream) carry bank -1: never a row hit, never promoted, so padding
drains in order exactly as in `frfcfs_perm`.

Row hits are kept per slot instead of gathered each step: issuing a
request changes only its bank's open row, so only the slots of that
bank update, and a refilled slot reads its bank's open row once.

On-chip memory: the three stream fields and the output permutation,
each [rows, 128] int32/float32 (single-buffered: one block of lanes is
resident at a time), under the replay kernels' 64 MiB scoped-VMEM
limit; `max_requests` turns that into the largest N a launch accepts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.replay.replay import VMEM_LIMIT_BYTES

LANES = 128
SUBLANES = 8
_VMEM_RESERVE = 2 * 2**20      # the pending buffer, masks and spills
_TILES = 4                     # arrival, bank, row in; permutation out


def _round8(x: int) -> int:
    return -(-x // SUBLANES) * SUBLANES


def max_requests(max_window: int) -> int:
    """Largest request count N per stream one launch holds in VMEM with
    a `max_window`-slot pending buffer: 31,704 at the Fig. 4
    campaign's 40-slot buffer."""
    rows = (VMEM_LIMIT_BYTES - _VMEM_RESERVE) // (_TILES * LANES * 4)
    return rows - _round8(max_window)


def _kernel(par_ref, arr_ref, bank_ref, row_ref, out_ref, *, w: int,
            n_banks: int):
    n = out_ref.shape[0]
    win = par_ref[0:1, :]
    slack = jax.lax.bitcast_convert_type(par_ref[1:2, :], jnp.float32)
    cap = par_ref[2:3, :]
    slot = jax.lax.broadcasted_iota(jnp.int32, (w, LANES), 0)
    last = slot == w - 1
    bank_iota = jax.lax.broadcasted_iota(jnp.int32, (n_banks, LANES), 0)

    def step(k, st):
        a_buf, b_buf, r_buf, i_buf, hit, open_row, defer = st
        horizon = a_buf[0:1, :] + slack
        elig = (hit > 0) & (a_buf <= horizon) & (slot < win)
        first = jnp.min(jnp.where(elig, slot, w), axis=0, keepdims=True)
        pick = jnp.where((first < w) & (defer < cap), first, 0)
        sel = slot == pick

        def picked(buf):
            return jnp.sum(jnp.where(sel, buf, 0), axis=0, keepdims=True)

        pb, pr = picked(b_buf), picked(r_buf)
        out_ref[pl.ds(k, 1), :] = picked(i_buf)
        # bank -1 (padding) matches no bank row and no valid slot
        open_row = jnp.where(bank_iota == pb, pr, open_row)
        hit = jnp.where((b_buf == pb) & (pb >= 0),
                        (r_buf == pr).astype(jnp.int32), hit)
        defer = jnp.where(pick > 0, defer + 1, 0)
        # the refill: stream row W + k, its hit against the open rows
        fa = arr_ref[pl.ds(k + w, 1), :]
        fb = bank_ref[pl.ds(k + w, 1), :]
        fr = row_ref[pl.ds(k + w, 1), :]
        f_open = jnp.sum(jnp.where(bank_iota == fb, open_row, 0), axis=0,
                         keepdims=True)
        f_hit = ((fb >= 0) & (f_open == fr)).astype(jnp.int32)
        fi = jnp.full((1, LANES), k + w, jnp.int32)
        # shift the slots past the pick up by one; the last slot
        # takes the refill
        keep = slot < pick

        def shift(buf, fill):
            up = jnp.where(last, fill, pltpu.roll(buf, w - 1, 0))
            return jnp.where(keep, buf, up)

        return (shift(a_buf, fa), shift(b_buf, fb), shift(r_buf, fr),
                shift(i_buf, fi), shift(hit, f_hit), open_row, defer)

    b0 = bank_ref[0:w, :]
    r0 = row_ref[0:w, :]
    st0 = (arr_ref[0:w, :], b0, r0, slot,
           ((b0 >= 0) & (r0 == -1)).astype(jnp.int32),   # open rows -1
           jnp.full((n_banks, LANES), -1, jnp.int32),
           jnp.zeros((1, LANES), jnp.int32))
    jax.lax.fori_loop(0, n, step, st0)


@functools.partial(jax.jit, static_argnames=("w", "n_banks", "interpret"))
def frfcfs_blocks(params, arrival, bank, row, *, w: int, n_banks: int,
                  interpret: bool = False):
    """params: [3, L] int32 lane rows (window, float32 slack bits,
    starvation cap); arrival [N + w, L] float32; bank, row [N + w, L]
    int32, bank -1 on every invalid row; L a multiple of 128, w of 8.
    Returns the [N, L] int32 issue permutation of every lane's
    stream.  Its device op is named after this function, which the
    benchmark's replay-kernel metric does not count: the prepass is
    XLA-side time of the replay program."""
    rows, lanes = arrival.shape
    n = rows - w
    assert lanes % LANES == 0 and w % SUBLANES == 0, (arrival.shape, w)

    def tile(r):
        return pl.BlockSpec((r, LANES), lambda j: (0, j),
                            pipeline_mode=pl.Buffered(1))

    return pl.pallas_call(
        functools.partial(_kernel, w=w, n_banks=n_banks),
        grid=(lanes // LANES,),
        in_specs=[tile(3), tile(rows), tile(rows), tile(rows)],
        out_specs=tile(n),
        out_shape=jax.ShapeDtypeStruct((n, lanes), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(params, arrival, bank, row)


def reorder_perm(arrival, bank, row, valid, windows, slacks, caps,
                 max_window: int, n_banks: int = 8,
                 interpret: bool = False):
    """Issue permutations [T, Q, N] int32 of T FCFS streams ([T, N]
    arrival/bank/row, prefix-true `valid`) under Q FR-FCFS-lite
    policies: `windows` (static ints), `slacks` and `caps` ([Q],
    traced).  Equal, element for element, to `dram_sim.frfcfs_perm` of
    each (trace, policy) pair with a buffer of `max_window` slots
    (>= every policy's `_eff_window` bound); banks lie in
    [0, n_banks).  Raises before lowering when N exceeds what one
    launch holds on chip (`max_requests`)."""
    t, n = arrival.shape
    q = len(windows)
    w = _round8(min(max_window, n))
    cap_n = max_requests(w)
    if n > cap_n:
        raise ValueError(
            f"frfcfs_blocks: {n} requests per stream exceed the {cap_n} "
            f"one launch holds on chip with a {w}-slot pending buffer "
            f"(three request fields and the permutation, [N, 128] each, "
            f"in VMEM); split the traces or reorder them with "
            f"backend='scan'")
    rows = n + w
    lanes = -(-t * q // LANES) * LANES

    def field(x, fill):
        # [T, N] -> [rows, lanes]: lane t * Q + q carries stream t;
        # rows past N and lanes past T * Q hold `fill`
        x = jnp.repeat(x.T, q, axis=1)
        return jnp.pad(x, ((0, rows - n), (0, lanes - t * q)),
                       constant_values=fill)

    valid = jnp.asarray(valid, bool)
    a = field(jnp.asarray(arrival, jnp.float32), 0.0)
    b = field(jnp.where(valid, jnp.asarray(bank, jnp.int32), -1), -1)
    r = field(jnp.asarray(row, jnp.int32), 0)

    def lane_row(x):
        return jnp.pad(jnp.tile(x, t), (0, lanes - t * q))

    params = jnp.stack([
        lane_row(jnp.asarray(windows, jnp.int32)),
        lane_row(jax.lax.bitcast_convert_type(
            jnp.asarray(slacks, jnp.float32), jnp.int32)),
        lane_row(jnp.asarray(caps, jnp.int32))])
    perm = frfcfs_blocks(params, a, b, r, w=w, n_banks=n_banks,
                         interpret=interpret)
    return perm[:, :t * q].T.reshape(t, q, n)
