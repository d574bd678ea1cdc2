"""Rank and bank-group constraints of a DDR4 channel in the static
replay (`SimSpec.rank_timings`):

  * hand-built traces isolate each constraint — tRRD_L and tRRD_S
    between two ACTs, tFAW on a fifth ACT, tCCD_L and tCCD_S between
    two column commands, none across ranks — in `replay_one`,
    `replay_rows`, the Pallas kernel (interpret mode) and the
    benchmark's plain reference alike;
  * the four agree on seeded random bank-grouped traces;
  * a geometry without the rank state reports zero stall, and rank
    timings of zero stall nothing;
  * every path that does not carry the state raises `BankGroupError`,
    and backend="auto" sends a grouped campaign to the kernel on a TPU.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim as D
from repro.core import faults, sim_engine
from repro.core.dram_sim import BankGroupError, KVSpec, Policy
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import ThermalSpec, steady
from repro.core.timing import (DDR4_2400, DDR4_2400_RANK,
                               DDR4_2400_TCK_NS, RankTimings,
                               stack_timing)
from repro.kernels.replay import ops as replay_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)
from reference import replay_ddr4 as RD  # noqa: E402

TCK = DDR4_2400_TCK_NS
TRCD = float(np.float32(DDR4_2400.trcd))
ROW = DDR4_2400.as_row()
N_BANKS, N_GROUPS = 16, 4


def _rt(**kw) -> RankTimings:
    """Rank timings with only the named ones set (in nCK)."""
    base = dict(trrd_s=0, trrd_l=0, tfaw=0, tccd_s=0, tccd_l=0)
    base.update(kw)
    return RankTimings(**{k: v * TCK for k, v in base.items()})


def _groups(rt: RankTimings) -> tuple:
    return (N_GROUPS,) + rt.as_tuple()


def _replay(impl, arrival, bank, row, is_write, rows, closed, rt,
            n_ranks, t_burst):
    """(latency [L, N], runtime [L], stall [L]) of one stream under the
    lanes' (row, page policy) pairs, by `impl`."""
    arrival = np.asarray(arrival, np.float32)
    bank, row = np.asarray(bank, np.int32), np.asarray(row, np.int32)
    is_write = np.asarray(is_write, bool)
    rows, closed = np.asarray(rows, np.float32), np.asarray(closed, bool)
    n, lanes = arrival.shape[0], rows.shape[0]
    if impl == "reference":
        rep = lambda x: np.repeat(x[None], lanes, axis=0)  # noqa: E731
        return RD.replay(rep(arrival), rep(bank), rep(row), rep(is_write),
                         rows, closed, rt.as_tuple(), n_ranks=n_ranks,
                         n_banks=N_BANKS, n_groups=N_GROUPS,
                         t_burst=t_burst)
    valid = jnp.ones((n,), bool)
    args = (jnp.asarray(arrival), jnp.asarray(bank), jnp.asarray(row),
            jnp.asarray(is_write))
    kw = dict(n_banks=N_BANKS, mlp_window=8, n_ranks=n_ranks,
              t_burst=t_burst, groups=_groups(rt))
    if impl == "replay_one":
        out = [D.replay_one(*args, valid, jnp.asarray(r), c, **kw)
               for r, c in zip(rows, closed)]
        return tuple(np.stack([np.asarray(o[i]) for o in out])
                     for i in range(3))
    if impl == "replay_rows":
        out = [D.replay_rows(*args, valid, jnp.asarray(r[None]), c, **kw)
               for r, c in zip(rows, closed)]
        return tuple(np.concatenate([np.asarray(o[i]) for o in out])
                     for i in range(3))
    # the kernel: one cell per lane's policy, each under its row
    chan = (1, n_ranks, t_burst, _groups(rt))
    grid = tuple(jnp.broadcast_to(a[None, None], (1, lanes, n))
                 for a in args)
    lat, total, stall = replay_ops.replay_grid(
        *grid, valid[None], jnp.asarray(rows), jnp.asarray(closed),
        N_BANKS, 8, impl="pallas_interpret", bs=8, chan=chan)
    pick = np.arange(lanes)
    return (np.asarray(lat)[0, pick, pick], np.asarray(total)[0, pick, pick],
            np.asarray(stall)[0, pick, pick])


IMPLS = ["replay_one", "replay_rows", "kernel", "reference"]

# (banks, rows, rank timings, ranks, the column of each request): every
# request arrives at 0 to an empty bank, so the bank alone would issue
# every ACT at 0 and every column at tRCD
CASES = {
    "trrd_l": ([0, 1], [0, 0], _rt(trrd_l=6), 1, [0.0, 6 * TCK]),
    "trrd_l_other_group": ([0, 4], [0, 0], _rt(trrd_l=6), 1, [0.0, 0.0]),
    "trrd_s": ([0, 4], [0, 0], _rt(trrd_s=4), 1, [0.0, 4 * TCK]),
    "tfaw": ([0, 4, 8, 12, 1], [0] * 5, _rt(trrd_s=4, tfaw=26), 1,
             [0.0, 4 * TCK, 8 * TCK, 12 * TCK, 26 * TCK]),
    "tfaw_off": ([0, 4, 8, 12, 1], [0] * 5, _rt(trrd_s=4), 1,
                 [0.0, 4 * TCK, 8 * TCK, 12 * TCK, 16 * TCK]),
    "tccd_l": ([0, 1], [0, 0], _rt(tccd_l=6), 1, [0.0, 6 * TCK]),
    "tccd_l_other_group": ([0, 4], [0, 0], _rt(tccd_l=6), 1, [0.0, 0.0]),
    "tccd_s": ([0, 4], [0, 0], _rt(tccd_s=4), 1, [0.0, 4 * TCK]),
    # rank = row % 2 on one channel: the second request is on rank 1
    "across_ranks": ([0, 1], [0, 1], DDR4_2400_RANK, 2, [0.0, 0.0]),
    "across_ranks_group": ([0, 4], [0, 1], DDR4_2400_RANK, 2, [0.0, 0.0]),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", list(CASES))
def test_constraint_isolated(case, impl):
    banks, rows, rt, n_ranks, delay = CASES[case]
    n = len(banks)
    lat, _, stall = _replay(impl, np.zeros(n), banks, rows,
                            np.zeros(n, bool), ROW[None], [False], rt,
                            n_ranks, 0.0)
    # done = column + tCL, and latency counts from 0 (no gate: n <= 8)
    col = lat[0] - np.float32(DDR4_2400.tcl)
    np.testing.assert_allclose(col, TRCD + np.asarray(delay), atol=1e-4)
    np.testing.assert_allclose(stall[0], sum(delay), atol=1e-4)


def _random_trace(seed, n, n_ranks):
    """Bursty requests over 16 banks of `n_ranks` ranks, few rows (hits,
    conflicts and empties), 30% writes."""
    rng = np.random.default_rng(seed)
    arrival = np.cumsum(rng.exponential(4.0, n)).astype(np.float32)
    bank = rng.integers(0, N_BANKS, n)
    row = rng.integers(0, 3, n) * n_ranks + rng.integers(0, n_ranks, n)
    return arrival, bank, row, rng.random(n) < 0.3


@pytest.mark.parametrize("n_ranks", [1, 2])
@pytest.mark.parametrize("seed", [0, 1])
def test_implementations_agree(seed, n_ranks):
    arrival, bank, row, wr = _random_trace(seed, 160, n_ranks)
    rows = stack_timing([DDR4_2400, DDR4_2400.scaled(0.8, 0.8, 0.8, 0.8)])
    rows = np.concatenate([rows, rows])
    closed = np.array([False, False, True, True])
    t_burst = 4 * TCK
    out = {impl: _replay(impl, arrival, bank, row, wr, rows, closed,
                         DDR4_2400_RANK, n_ranks, t_burst)
           for impl in IMPLS}
    for impl in ("replay_rows", "kernel"):
        for a, b in zip(out[impl], out["replay_one"]):
            np.testing.assert_array_equal(a, b)
    for a, b in zip(out["reference"], out["replay_one"]):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert (out["replay_one"][2] > 0).all()


def _kv_spec(**kw):
    base = dict(traces=KVSpec(n=256, n_streams=2, seed=11,
                              n_records=1 << 16),
                timings=stack_timing([DDR4_2400,
                                      DDR4_2400.scaled(0.8, 0.8, 0.8,
                                                       0.8)]),
                policies=(Policy(), Policy(page="closed")), n_banks=16,
                n_ranks=2, t_burst_ns=4 * TCK)
    base.update(kw)
    return SimSpec(**base)


def test_kv_campaign_backends_agree():
    """The YCSB streams through the engine: scan, kernel and the host
    reference path agree, and the open-page lanes (pipelined hits of
    one group) stall on tCCD_L."""
    spec = _kv_spec(bank_groups=4, rank_timings=DDR4_2400_RANK)
    scan = SimEngine(backend="scan").run(spec)
    kern = SimEngine(backend="pallas_interpret").run(spec)
    host = SimEngine(backend="scan", stats="host", reorder="host").run(spec)
    assert (scan.constraint_stall_ns[:, 0] > 0).all()
    assert (scan.constraint_stall_ns >= 0).all()
    for f in ("total_ns", "constraint_stall_ns", "p99_latency_ns"):
        np.testing.assert_array_equal(getattr(kern, f), getattr(scan, f))
        np.testing.assert_array_equal(getattr(host, f), getattr(scan, f))
    np.testing.assert_allclose(kern.mean_latency_ns, scan.mean_latency_ns,
                               rtol=1e-6)


def test_no_rank_state_stalls_nothing():
    """Without rank timings the stall reads zero; with rank timings all
    zero the rank state is there and stalls nothing."""
    plain = SimEngine(backend="scan").run(_kv_spec())
    assert plain.constraint_stall_ns.shape == plain.total_ns.shape
    assert (plain.constraint_stall_ns == 0).all()
    zero = SimEngine(backend="scan").run(_kv_spec(
        bank_groups=4, rank_timings=RankTimings(0, 0, 0, 0, 0)))
    assert (zero.constraint_stall_ns == 0).all()


def test_bank_groups_need_rank_timings():
    with pytest.raises(ValueError, match="rank_timings"):
        _kv_spec(bank_groups=4)
    with pytest.raises(ValueError, match="divide"):
        _kv_spec(bank_groups=3, rank_timings=DDR4_2400_RANK)


REFUSED = {
    "frfcfs_prepass": (dict(policies=(Policy(reorder_window=16),)), {}),
    "frfcfs_host": (dict(policies=(Policy(reorder_window=16),)),
                    dict(reorder="host")),
    "faulted": (dict(faults=faults.FaultSpec(scenarios=(
        faults.FaultScenario(name="errors", err_scale=1.0),))), {}),
    "regioned": (dict(timings=np.repeat(
        stack_timing([DDR4_2400])[:, None], 16, axis=1),
        region_map=np.arange(16, dtype=np.int32)), {}),
    "adaptive_scan": (dict(thermal=ThermalSpec(
        scenarios=(steady(45.0),), temp_bins=(85.0,)),
        timings=np.stack([stack_timing([DDR4_2400] * 2)])), {}),
    "adaptive_kernel": (dict(thermal=ThermalSpec(
        scenarios=(steady(45.0),), temp_bins=(85.0,)),
        timings=np.stack([stack_timing([DDR4_2400] * 2)])),
        dict(backend="pallas_interpret")),
}


@pytest.mark.parametrize("path", list(REFUSED))
def test_paths_without_the_rank_state_refuse(path):
    spec_kw, eng_kw = REFUSED[path]
    spec = _kv_spec(bank_groups=4, rank_timings=DDR4_2400_RANK, **spec_kw)
    with pytest.raises(BankGroupError):
        SimEngine(**eng_kw).run(spec)


def test_run_bracket_refuses():
    spec = _kv_spec(bank_groups=4, rank_timings=DDR4_2400_RANK,
                    thermal=ThermalSpec(scenarios=(steady(45.0),),
                                        temp_bins=(85.0,)),
                    timings=np.stack([stack_timing([DDR4_2400] * 2)]))
    with pytest.raises(BankGroupError):
        SimEngine().run_bracket(spec, DDR4_2400.as_row())


def test_auto_sends_a_grouped_campaign_to_the_kernel(monkeypatch):
    spec = _kv_spec(bank_groups=4, rank_timings=DDR4_2400_RANK)
    monkeypatch.setattr(sim_engine.jax, "default_backend", lambda: "tpu")
    assert SimEngine(backend="auto")._resolve(spec) == "pallas"
