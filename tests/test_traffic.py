"""Multi-channel campaign contracts (PR 8): degenerate parity and
interleave determinism.

  * `n_channels=1` (any interleave) is BIT-identical to the pre-channel
    replay — the channel plumbing is a static no-op at C*R == 1, for
    `replay_one` directly and through the engine (host + device stats,
    static + adaptive);
  * a one-device campaign mesh's `shard_map` path is bit-identical to
    the unsharded dispatch (static + adaptive + bracket);
  * multi-channel replay agrees across the scan and Pallas
    (interpret) backends;
  * interleave policy codes are deterministic across `pack()` /
    `pack_device()` calls (the traced campaign column never drifts);
  * a fused `TenantSpec` campaign equals its materialized twin with
    zero synthesis launches.
"""

import jax
import numpy as np
import pytest

from repro.core import perf_model
from repro.core.dram_sim import (ILEAVE_CODES, OPEN_FCFS, Policy,
                                 chan_rank, replay_one)
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import (ThermalConfig, ThermalSpec, diurnal,
                                steady)
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, stack_timing
from repro.launch.mesh import make_campaign_mesh


def _trace(n=96, seed=0, banks=8):
    rng = np.random.default_rng(seed)
    from repro.core.dram_sim import Trace
    return Trace(arrival=np.sort(rng.exponential(20.0, n)).astype(
                     np.float32),
                 bank=rng.integers(0, banks, n).astype(np.int32),
                 row=rng.integers(0, 512, n).astype(np.int32),
                 is_write=rng.random(n) < 0.3)


def _spec(n_channels=1, n_ranks=1, interleave="row", **kw):
    traces = tuple(_trace(seed=s) for s in range(3))
    rows = stack_timing([DDR3_1600, ALDRAM_55C_EVAL])
    pols = (OPEN_FCFS, Policy(reorder_window=8, interleave=interleave))
    return SimSpec(traces=traces, timings=rows, policies=pols,
                   n_channels=n_channels, n_ranks=n_ranks, **kw)


def _thermal_spec(**chan_kw):
    tab = np.stack([ALDRAM_55C_EVAL.as_row(), DDR3_1600.as_row()])[None]
    tspec = ThermalSpec(
        scenarios=(steady(48.0), diurnal(40.0, 90.0, period_ns=2.0e4)),
        temp_bins=(55.0,),
        config=ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4))
    return SimSpec(traces=tuple(_trace(seed=s) for s in range(2)),
                   timings=tab, thermal=tspec,
                   policies=(Policy(reorder_window=4),), **chan_kw)


STAT_FIELDS = ("mean_latency_ns", "p99_latency_ns", "total_ns")
THERMAL_FIELDS = STAT_FIELDS + ("temp_max", "temp_mean", "bin_switches")


def _assert_results_equal(a, b, fields=STAT_FIELDS):
    for f in fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


class TestDegenerateParity:
    def test_replay_one_c1_bit_identical(self):
        """Explicit n_channels=1 kwargs (any interleave code) replay
        the EXACT pre-channel arithmetic."""
        t = _trace()
        row = DDR3_1600.as_row()
        lat0, tot0 = replay_one(t.arrival, t.bank, t.row, t.is_write,
                                np.ones(len(t.arrival), bool), row,
                                False)
        for code in ILEAVE_CODES.values():
            lat1, tot1 = replay_one(
                t.arrival, t.bank, t.row, t.is_write,
                np.ones(len(t.arrival), bool), row, False,
                n_channels=1, n_ranks=1, ileave=np.int32(code))
            assert np.array_equal(np.asarray(lat0), np.asarray(lat1))
            assert float(tot0) == float(tot1), code

    @pytest.mark.parametrize("stats", ["device", "host"])
    def test_engine_c1_ignores_interleave(self, stats):
        """At C*R == 1 every interleave policy maps to channel 0 —
        the engine output can't depend on the policy's interleave."""
        eng = SimEngine(stats=stats, reorder=stats)
        base = eng.run(_spec())
        for il in ("cacheline", "bank_xor"):
            _assert_results_equal(base, eng.run(_spec(interleave=il)))

    def test_adaptive_c1_bit_identical(self):
        eng = SimEngine()
        base = eng.run(_thermal_spec())
        res = eng.run(_thermal_spec(n_channels=1, n_ranks=1,
                                    t_burst_ns=99.0))
        _assert_results_equal(base, res, THERMAL_FIELDS)


class TestSingleDeviceMeshParity:
    """A one-device campaign mesh runs the same single-device grids
    inside `shard_map` — outputs must be bit-identical, so attaching a
    mesh is always safe."""

    def test_static_bit_identical(self):
        mesh = make_campaign_mesh(1)
        spec = _spec(n_channels=2, interleave="bank_xor")
        _assert_results_equal(SimEngine().run(spec),
                              SimEngine(mesh=mesh).run(spec))

    def test_adaptive_bit_identical(self):
        mesh = make_campaign_mesh(1)
        spec = _thermal_spec(n_channels=2)
        _assert_results_equal(SimEngine().run(spec),
                              SimEngine(mesh=mesh).run(spec),
                              THERMAL_FIELDS)

    def test_bracket_bit_identical(self):
        mesh = make_campaign_mesh(1)
        spec = _thermal_spec()
        base = DDR3_1600.as_row()
        br0 = SimEngine().run_bracket(spec, base_row=base)
        br1 = SimEngine(mesh=mesh).run_bracket(spec, base_row=base)
        for k in ("worst_bin", "temp_peak"):
            assert np.array_equal(np.asarray(br0[k]),
                                  np.asarray(br1[k])), k
        for half in ("adaptive", "static"):
            for k, v in br0[half].items():
                assert np.array_equal(np.asarray(v),
                                      np.asarray(br1[half][k])), \
                    (half, k)

    def test_sharded_requires_device_stats(self):
        eng = SimEngine(mesh=make_campaign_mesh(1), stats="host",
                        reorder="host")
        with pytest.raises(AssertionError):
            eng.run(_spec())

    def test_ragged_trace_axis_pads_and_slices(self):
        """T not divisible by the device count round-trips through
        `_shard_pad` without polluting the stats."""
        mesh = make_campaign_mesh(1)
        rows = stack_timing([DDR3_1600])
        traces = tuple(_trace(seed=s) for s in range(3))
        spec = SimSpec(traces=traces, timings=rows, n_channels=2)
        res = SimEngine(mesh=mesh).run(spec)
        assert res.mean_latency_ns.shape[0] == 3
        _assert_results_equal(SimEngine().run(spec), res)


class TestMultiChannelBackends:
    def test_static_backends_agree(self):
        spec = _spec(n_channels=2, n_ranks=2, interleave="cacheline")
        ref = SimEngine(backend="scan").run(spec)
        res = SimEngine(backend="pallas_interpret").run(spec)
        for f in STAT_FIELDS:
            np.testing.assert_allclose(
                np.asarray(getattr(res, f)),
                np.asarray(getattr(ref, f)), rtol=1e-5, err_msg=f)

    def test_contention_prices_latency(self):
        """More channels must not slow the campaign down: splitting
        one bus across C channels relieves contention."""
        m1 = float(SimEngine().run(
            _spec(interleave="bank_xor")).mean_latency_ns.mean())
        m4 = float(SimEngine().run(
            _spec(n_channels=4,
                  interleave="bank_xor")).mean_latency_ns.mean())
        assert m4 <= m1 + 1e-6, (m1, m4)

    def test_chan_rank_codes(self):
        bank = np.arange(8, dtype=np.int32)
        row = np.arange(8, dtype=np.int32) * 3
        for name, code in ILEAVE_CODES.items():
            ch, rank = jax.jit(chan_rank, static_argnums=(3, 4))(
                bank, row, np.int32(code), 4, 2)
            ch, rank = np.asarray(ch), np.asarray(rank)
            assert ch.min() >= 0 and ch.max() < 4, name
            assert rank.min() >= 0 and rank.max() < 2, name
            if name == "row":
                assert np.array_equal(ch, row % 4)


class TestInterleaveDeterminism:
    def test_codes_stable_across_pack_calls(self):
        spec = _spec(n_channels=2, interleave="bank_xor")
        c0 = spec.ileave_codes.copy()
        p0 = spec.pack()
        d0 = spec.pack_device()
        p1 = spec.pack()
        d1 = spec.pack_device()
        assert np.array_equal(spec.ileave_codes, c0)
        for a, b in zip(p0, p1):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(d0, d1):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_codes_match_policy_order(self):
        pols = tuple(Policy(interleave=il) for il in ILEAVE_CODES)
        spec = SimSpec(traces=(_trace(),),
                       timings=stack_timing([DDR3_1600]),
                       policies=pols, n_channels=2)
        assert np.array_equal(
            spec.ileave_codes,
            np.array([ILEAVE_CODES[il] for il in ILEAVE_CODES],
                     np.int32))


class TestTenantFusion:
    def test_fused_tenants_bit_identical_zero_synth(self):
        tenants = perf_model.tenant_spec(n=48, n_streams=3, seed=1)
        rows = stack_timing([DDR3_1600, ALDRAM_55C_EVAL])
        kw = dict(timings=rows,
                  policies=(Policy(reorder_window=8,
                                   interleave="cacheline"),),
                  n_channels=2)
        eng = SimEngine()
        res_m = eng.run(SimSpec(traces=tenants.materialize(), **kw))
        d0, s0 = eng.dispatch_count, perf_model.synth_dispatch_count
        res_f = eng.run(SimSpec(traces=tenants, **kw))
        assert eng.dispatch_count - d0 == 1
        assert perf_model.synth_dispatch_count == s0
        _assert_results_equal(res_f, res_m)

    def test_tenant_mixes_differ_across_streams(self):
        """Distinct Dirichlet mixes + arrival kinds produce distinct
        streams (the tenant axis is not a broadcast)."""
        mat = perf_model.tenant_spec(n=64, n_streams=3,
                                     seed=2).materialize()
        arr = [np.asarray(t.arrival) for t in mat]
        assert not np.array_equal(arr[0], arr[1])
        assert not np.array_equal(arr[1], arr[2])
