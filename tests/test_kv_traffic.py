"""The YCSB workload A stream (`dram_sim.KVSpec`):

  * the FNV-1a-64 scramble in uint32 pairs equals Python's integer
    FNV on 10,000 ranks;
  * the program's keys and streams equal the benchmark's plain
    reference (`bench/reference/ycsb.py`) for a seed;
  * the most popular ranks are drawn as often as Zipf(0.99) says;
  * the address map round-trips line addresses to (rank, group, bank,
    row, column), and a record is 16 lines of one row in 2 groups.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim as D

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)
from reference import ycsb as Y  # noqa: E402

N_RECORDS = 1 << 24
SEED = 3000000007


def _fnv_abs_mod(x: int, n: int) -> int:
    """YCSB's Math.abs(fnvhash64(x)) % n with Python integers."""
    h = 0xCBF29CE484222325
    for i in range(8):
        h ^= (x >> (8 * i)) & 0xFF
        h = (h * 0x100000001B3) % (1 << 64)
    if h >= 1 << 63:
        h -= 1 << 64
    return abs(h) % n if h != -(1 << 63) else h % n


def test_fnv_scramble_matches_python_integers():
    rng = np.random.default_rng(0)
    x = np.concatenate([np.arange(5000),
                        rng.integers(0, 1 << 32, 5000)]).astype(np.uint32)
    got = np.asarray(D.fnv1a64_mod(jnp.asarray(x), N_RECORDS))
    want = np.array([_fnv_abs_mod(int(v), N_RECORDS) for v in x])
    np.testing.assert_array_equal(got, want)
    # the whole 64-bit remainder, for the largest power of two
    got32 = np.asarray(D.fnv1a64_mod(jnp.asarray(x[:500]), 1 << 32))
    np.testing.assert_array_equal(
        got32, [_fnv_abs_mod(int(v), 1 << 32) for v in x[:500]])


def _program_slots(spec, off):
    u, update, field, t_op = spec._draws(off)
    rank = jnp.searchsorted(spec.device_inputs()[0], u, side="right")
    return (np.asarray(D.fnv1a64_mod(rank, spec.n_records)),
            np.asarray(update), np.asarray(field), np.asarray(t_op))


def test_program_and_reference_draw_the_same_keys():
    spec = D.KVSpec(n=640, n_streams=3, seed=SEED)
    y = {"update_frac": 0.5, "zipf_theta": 0.99, "n_records": N_RECORDS,
         "op_interval_ns": 100.0}
    traces = spec.materialize()
    for i in range(3):
        slot, update, field, t_op = Y.ops(SEED, i, spec.n, y)
        got = _program_slots(spec, i)
        np.testing.assert_array_equal(got[0], slot)
        np.testing.assert_array_equal(got[1], update)
        np.testing.assert_array_equal(got[2], field)
        np.testing.assert_allclose(got[3], t_op, rtol=1e-6)
        ref = Y.stream(SEED, i, spec.n, y)
        for a, b in zip(traces[i][1:], ref[1:]):       # bank, row, write
            np.testing.assert_array_equal(np.asarray(a), b)
        np.testing.assert_allclose(np.asarray(traces[i][0]), ref[0],
                                   rtol=1e-6)


def test_top_ranks_follow_zipf():
    spec = D.KVSpec(n=16 * 4095, n_streams=8, seed=SEED)
    cdf = spec.device_inputs()[0]
    ranks = np.concatenate([
        np.asarray(jnp.searchsorted(cdf, spec._draws(i)[0], side="right"))
        for i in range(spec.n_streams)])
    w = np.arange(1, N_RECORDS + 1, dtype=np.float64) ** -0.99
    p = w / w.sum()
    m = ranks.size
    for r in range(8):
        count = int((ranks == r).sum())
        sigma = np.sqrt(m * p[r] * (1 - p[r]))
        assert abs(count - m * p[r]) < 5 * sigma, (r, count, m * p[r])
    assert ranks.min() >= 0 and ranks.max() < N_RECORDS


def test_address_map_round_trips():
    rng = np.random.default_rng(1)
    la = rng.integers(0, 1 << 28, 4096).astype(np.int32)
    rank, group, bank, row, col = (np.asarray(x) for x in
                                   D.kv_address(jnp.asarray(la)))
    assert rank.max() == 1 and group.max() == 3 and bank.max() == 3
    assert col.max() == 127 and row.max() < 1 << 16
    back = ((col & 7) | group << 3 | (col >> 3) << 5 | bank << 9
            | rank << 11 | row << 12)
    np.testing.assert_array_equal(back, la)
    # one record: 16 lines of one row, 8 in each of 2 groups, one bank
    slot = 123457
    rank, group, bank, row, col = (np.asarray(x) for x in D.kv_address(
        jnp.arange(16, dtype=jnp.int32) + slot * 16))
    assert len(set(rank)) == len(set(bank)) == len(set(row)) == 1
    assert sorted(np.unique(group, return_counts=True)[1]) == [8, 8]
    assert len(set(zip(group.tolist(), col.tolist()))) == 16


def test_n_records_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        D.KVSpec(n=64, n_streams=1, n_records=1000)
    with pytest.raises(ValueError, match="power of two"):
        D.KVSpec(n=64, n_streams=1, n_records=1 << 25)


def test_synthesis_fuses_into_one_dispatch():
    """A KVSpec trace axis synthesizes inside the replay dispatch, the
    Zipf table passed in as an argument, not folded into the program."""
    from repro.core import perf_model
    from repro.core.sim_engine import SimEngine, SimSpec
    from repro.core.timing import DDR4_2400, DDR4_2400_RANK
    spec = SimSpec(traces=D.KVSpec(n=128, n_streams=2, seed=5,
                                   n_records=1 << 12),
                   timings=DDR4_2400, n_banks=16, n_ranks=2,
                   bank_groups=4, rank_timings=DDR4_2400_RANK)
    eng = SimEngine()
    with perf_model.synth_dispatch_scope() as syn:
        res = eng.run(spec)
    assert eng.dispatch_count == 1 and syn.count == 0
    ref = SimEngine(stats="host", reorder="host").run(spec)
    np.testing.assert_array_equal(res.total_ns, ref.total_ns)
    np.testing.assert_array_equal(res.constraint_stall_ns,
                                  ref.constraint_stall_ns)
    jaxpr = jax.make_jaxpr(spec.traces.synth)(
        *spec.traces.device_inputs())
    assert max((v.aval.size for v in jaxpr.jaxpr.constvars),
               default=0) < 1 << 12
