"""Replay Pallas kernel (interpret mode) vs the vmapped lax.scan
oracle: campaign-grid parity across page policies, ragged padding and
timing-row blocking, the adaptive (closed thermal loop) kernel with
its on-device diagnostics, plus the SimEngine backend plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim, sim_engine
from repro.core.dram_sim import OPEN_FCFS, Policy
from repro.core.sim_engine import SimEngine, SimSpec
from repro.core.thermal import (ThermalConfig, ThermalSpec, diurnal,
                                stack_scenarios, steady)
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, stack_timing
from repro.kernels.replay import ops as replay_ops


def _grid_inputs(t=2, p=2, n=96, s=3, seed=0):
    """Padded [T, P, N] request grid + [S, 6] rows + closed flags."""
    lens = [n, n // 2] + [n] * max(0, t - 2)
    arr = np.zeros((t, n), np.float32)
    bank = np.zeros((t, n), np.int32)
    row = np.zeros((t, n), np.int32)
    wr = np.zeros((t, n), bool)
    val = np.zeros((t, n), bool)
    for i in range(t):
        tr = dram_sim.synth_trace(jax.random.PRNGKey(seed + i), lens[i],
                                  row_hit=0.5, write_frac=0.4)
        arr[i, :lens[i]] = tr.arrival
        bank[i, :lens[i]] = tr.bank
        row[i, :lens[i]] = tr.row
        wr[i, :lens[i]] = tr.is_write
        val[i, :lens[i]] = True
    rows = stack_timing(
        [DDR3_1600, ALDRAM_55C_EVAL,
         DDR3_1600.scaled(0.8, 0.8, 0.8, 0.8)][:s] +
        [DDR3_1600.scaled(f, 1.0, 1.0, 1.0)
         for f in np.linspace(0.99, 0.7, max(0, s - 3))])
    closed = np.array([(i % 2) == 1 for i in range(p)])

    def b3(x):
        return jnp.asarray(np.broadcast_to(x[:, None], (t, p, n)).copy())

    return (b3(arr), b3(bank), b3(row), b3(wr), jnp.asarray(val),
            jnp.asarray(rows), jnp.asarray(closed))


class TestReplayKernel:
    @pytest.mark.parametrize("t,p,n,s", [
        (2, 2, 96, 3),          # open + closed page, ragged padding
        (1, 1, 64, 1),          # degenerate single cell
        (3, 2, 128, 5),         # more timing rows than a small block
    ])
    def test_matches_scan_oracle(self, t, p, n, s):
        args = _grid_inputs(t, p, n, s)
        lat_ref, tot_ref = replay_ops.replay_grid(*args, impl="ref")
        lat_pl, tot_pl = replay_ops.replay_grid(
            *args, impl="pallas_interpret", bs=8)
        np.testing.assert_allclose(np.asarray(lat_pl),
                                   np.asarray(lat_ref), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(tot_pl),
                                   np.asarray(tot_ref), rtol=1e-5)

    def test_block_size_invariance(self):
        args = _grid_inputs(2, 1, 64, 4)
        l1, t1 = replay_ops.replay_grid(*args, impl="pallas_interpret",
                                        bs=4)
        l2, t2 = replay_ops.replay_grid(*args, impl="pallas_interpret",
                                        bs=8)
        assert np.array_equal(np.asarray(l1), np.asarray(l2))
        assert np.array_equal(np.asarray(t1), np.asarray(t2))

    def test_padding_emits_zero_latency(self):
        args = _grid_inputs(2, 1, 96, 2)
        lat, _ = replay_ops.replay_grid(*args, impl="pallas_interpret",
                                        bs=8)
        assert (np.asarray(lat)[1, :, :, 48:] == 0.0).all()

    def test_mlp_window_gate(self):
        """A non-default MLP window changes the closed-loop gating the
        same way in both backends."""
        args = _grid_inputs(1, 1, 64, 2)
        for w in (2, 4):
            l_ref, t_ref = replay_ops.replay_grid(*args, impl="ref",
                                                  mlp_window=w)
            l_pl, t_pl = replay_ops.replay_grid(
                *args, impl="pallas_interpret", mlp_window=w, bs=8)
            np.testing.assert_allclose(np.asarray(l_pl),
                                       np.asarray(l_ref), rtol=1e-5)
            np.testing.assert_allclose(np.asarray(t_pl),
                                       np.asarray(t_ref), rtol=1e-5)


def _adaptive_inputs(t=2, p=2, n=96, k=2, s=2, banked=False, seed=0):
    """Adaptive-campaign grid: streams as in `_grid_inputs` (ragged
    valid prefixes) plus table stacks / bin edges / scenario rows /
    thermal-config row."""
    arr, bank, row, wr, val, _, closed = _grid_inputs(t, p, n, s=1,
                                                      seed=seed)
    closed = closed[:p]
    # K stacks of S bin rows + JEDEC fallback, optionally per-bank
    # (FLY-DRAM spatial variation: each bank gets its own scaling)
    stacks = []
    for j in range(k):
        rows = [DDR3_1600.scaled(f, f, f, f).as_row()
                for f in np.linspace(0.7 + 0.05 * j, 0.9, s)]
        rows.append(DDR3_1600.as_row())
        tab = np.stack(rows)                          # [S+1, 6]
        if banked:
            scale = np.linspace(1.0, 1.1, 8)[None, :, None]
            tab = tab[:, None, :] * scale             # [S+1, B, 6]
        stacks.append(tab)
    tables = np.stack(stacks).astype(np.float32)
    bins = np.linspace(55.0, 85.0, s).astype(np.float32)
    scns = stack_scenarios((steady(48.0),
                            diurnal(40.0, 90.0, period_ns=2.0e4)))
    tcfg = ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4).as_row()
    return (arr, bank, row, wr, val, jnp.asarray(tables),
            jnp.asarray(bins), jnp.asarray(scns), jnp.asarray(tcfg),
            closed)


class TestAdaptiveKernel:
    @pytest.mark.parametrize("banked", [False, True],
                             ids=["per-module", "per-bank"])
    def test_matches_scan_oracle_ragged(self, banked):
        """Interpret-mode adaptive kernel vs the lax.scan reference on
        a ragged campaign (trace 1 is half padding), per-module and
        per-bank table stacks alike — raw latencies, temperature and
        bin traces, bank heat, and the ON-DEVICE diagnostics."""
        args = _adaptive_inputs(t=2, p=2, n=96, k=2, s=2, banked=banked)
        l_ref, tot_ref, temps_ref, bins_ref, heat_ref, diag_ref = \
            replay_ops.replay_grid_adaptive(*args, impl="ref")
        assert diag_ref is None
        l_pl, tot_pl, temps_pl, bins_pl, heat_pl, diag = \
            replay_ops.replay_grid_adaptive(*args,
                                            impl="pallas_interpret",
                                            bs=8, emit_raw=True)
        np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(tot_pl),
                                   np.asarray(tot_ref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(temps_pl),
                                   np.asarray(temps_ref), rtol=1e-5,
                                   atol=1e-4)
        assert np.array_equal(np.asarray(bins_pl), np.asarray(bins_ref))
        np.testing.assert_allclose(np.asarray(heat_pl),
                                   np.asarray(heat_ref), rtol=1e-5,
                                   atol=1e-4)
        # the kernel's in-VMEM diagnostics must agree with the host
        # reduction over the ref path's raw traces
        valid = args[4]
        tmax_h, tmean_h, sw_h = sim_engine._device_thermal_diag(
            temps_ref, bins_ref, valid)
        tmax_k, tmean_k, sw_k = diag
        np.testing.assert_allclose(np.asarray(tmax_k),
                                   np.asarray(tmax_h), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(tmean_k),
                                   np.asarray(tmean_h), rtol=1e-4)
        assert np.array_equal(np.asarray(sw_k), np.asarray(sw_h))

    def test_adaptive_block_size_invariance(self):
        args = _adaptive_inputs(t=1, p=1, n=64, k=2, s=2)
        outs = [replay_ops.replay_grid_adaptive(
                    *args, impl="pallas_interpret", bs=bs)
                for bs in (4, 8)]
        for a, b in zip(outs[0][:2], outs[1][:2]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(outs[0][5], outs[1][5]):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestEngineBackend:
    def test_pallas_backend_passes_parity_suite(self):
        """SimEngine(backend='pallas_interpret') — the kernel bodies on
        the host — replays the same campaign as the scan backend, raw
        latencies and summaries alike, with FR-FCFS reorder in the
        mix."""
        traces = (dram_sim.synth_trace(jax.random.PRNGKey(0), 128),
                  dram_sim.synth_trace(jax.random.PRNGKey(1), 96,
                                       row_hit=0.2))
        spec = SimSpec(
            traces=traces,
            timings=stack_timing([DDR3_1600, ALDRAM_55C_EVAL]),
            policies=(OPEN_FCFS, Policy(page="closed"),
                      Policy(reorder_window=4)),
            collect=("latencies",))
        scan = SimEngine().run(spec)
        pallas = SimEngine(backend="pallas_interpret").run(spec)
        np.testing.assert_allclose(pallas.latencies, scan.latencies,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(pallas.mean_latency_ns,
                                   scan.mean_latency_ns, rtol=1e-5)
        np.testing.assert_allclose(pallas.p99_latency_ns,
                                   scan.p99_latency_ns, rtol=1e-5)
        np.testing.assert_allclose(pallas.total_ns, scan.total_ns,
                                   rtol=1e-5)

    def test_pallas_backend_one_dispatch(self, monkeypatch):
        from repro.core import sim_engine
        calls = {"replay": 0}
        real = sim_engine._replay_grid

        def spy(*a, **k):
            calls["replay"] += 1
            return real(*a, **k)

        monkeypatch.setattr(sim_engine, "_replay_grid", spy)
        SimEngine(backend="pallas_interpret").run(
            SimSpec(traces=(dram_sim.synth_trace(
                jax.random.PRNGKey(2), 64),), timings=DDR3_1600))
        assert calls["replay"] == 1

    def test_adaptive_campaign_runs_kernel_with_scan_parity(self,
                                                            monkeypatch):
        """The kernel backend routes the adaptive (thermal) campaign
        through the adaptive kernel — no scan fallback — and its
        stats match the scan backend's, FR-FCFS reorder included."""
        calls = {"adaptive": 0}
        real = replay_ops.replay_grid_adaptive

        def spy(*a, **k):
            calls["adaptive"] += 1
            return real(*a, **k)

        monkeypatch.setattr(replay_ops, "replay_grid_adaptive", spy)
        stack = np.stack([ALDRAM_55C_EVAL.as_row(),
                          DDR3_1600.as_row()])[None]    # [K=1, S+1, 6]
        spec = SimSpec(
            traces=(dram_sim.synth_trace(jax.random.PRNGKey(3), 72),
                    dram_sim.synth_trace(jax.random.PRNGKey(4), 56)),
            timings=stack,
            policies=(OPEN_FCFS, Policy(reorder_window=4)),
            thermal=ThermalSpec(
                scenarios=(steady(48.0),
                           diurnal(40.0, 90.0, period_ns=2.0e4)),
                temp_bins=(55.0,),
                config=ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4)))
        res_pl = SimEngine(backend="pallas_interpret").run(spec)
        assert calls["adaptive"] >= 1, "adaptive kernel never invoked"
        res_sc = SimEngine().run(spec)
        for f in ("mean_latency_ns", "p99_latency_ns", "total_ns",
                  "temp_max", "temp_mean", "bank_heat"):
            np.testing.assert_allclose(getattr(res_pl, f),
                                       getattr(res_sc, f), rtol=1e-5,
                                       atol=1e-4, err_msg=f)
        assert np.array_equal(res_pl.bin_switches, res_sc.bin_switches)


class TestResolve:
    """`backend="auto"` picks the replay core from the platform and
    the campaign alone: the kernel on the TPU, except for a
    multi-channel adaptive campaign (the adaptive kernel is
    single-channel), and the scan elsewhere.  Resolving compiles
    nothing."""

    @pytest.mark.parametrize("platform,adaptive,channels,expected", [
        ("tpu", False, 1, "pallas"),
        ("tpu", False, 2, "pallas"),
        ("tpu", True, 1, "pallas"),
        ("tpu", True, 2, "scan"),
        ("cpu", True, 1, "scan"),
    ], ids=["tpu-static-1ch", "tpu-static-2ch", "tpu-adaptive-1ch",
            "tpu-adaptive-2ch", "cpu"])
    def test_auto(self, monkeypatch, platform, adaptive, channels,
                  expected):
        tr = (dram_sim.synth_trace(jax.random.PRNGKey(7), 32),)
        if adaptive:
            spec = SimSpec(
                traces=tr, timings=np.stack([ALDRAM_55C_EVAL.as_row(),
                                             DDR3_1600.as_row()])[None],
                n_channels=channels,
                thermal=ThermalSpec(scenarios=(steady(48.0),),
                                    temp_bins=(55.0,)))
        else:
            spec = SimSpec(traces=tr, timings=DDR3_1600,
                           n_channels=channels)
        monkeypatch.setattr(sim_engine.jax, "default_backend",
                            lambda: platform)
        assert SimEngine(backend="auto")._resolve(spec) == expected


class TestNoSilentFallback:
    """Off the TPU a request for the compiled kernels raises; nothing
    reroutes it to interpret mode, the scan or the jnp reference."""

    @staticmethod
    def _spec(**kw):
        return SimSpec(traces=(dram_sim.synth_trace(
            jax.random.PRNGKey(5), 32),), timings=DDR3_1600, **kw)

    def test_pallas_backend_raises_off_tpu(self):
        assert jax.default_backend() != "tpu"
        with pytest.raises(ValueError, match="pallas_interpret"):
            SimEngine(backend="pallas").run(self._spec())

    @pytest.mark.parametrize("wrapper", ["static", "adaptive"])
    def test_replay_impl_pallas_raises_off_tpu(self, wrapper):
        if wrapper == "static":
            args = _grid_inputs(1, 1, 32, 1)
            fn = replay_ops.replay_grid
        else:
            args = _adaptive_inputs(t=1, p=1, n=32, k=1, s=2)
            fn = replay_ops.replay_grid_adaptive
        with pytest.raises(ValueError, match="pallas_interpret"):
            fn(*args, impl="pallas")

    def test_margin_impl_pallas_raises_off_tpu(self):
        from repro.kernels.charge_sim import ops as charge_ops
        cells = jnp.ones((4, 5), jnp.float32)
        combos = jnp.asarray(np.stack([DDR3_1600.as_array()] * 2))
        with pytest.raises(ValueError, match="pallas_interpret"):
            charge_ops.combo_margins(cells, combos, 55.0, impl="pallas")

    def test_multichannel_adaptive_kernel_raises(self):
        """The adaptive kernel is single-channel: a multi-channel
        adaptive campaign that asks for it raises instead of riding
        the scan."""
        stack = np.stack([ALDRAM_55C_EVAL.as_row(),
                          DDR3_1600.as_row()])[None]
        spec = SimSpec(
            traces=(dram_sim.synth_trace(jax.random.PRNGKey(6), 32),),
            timings=stack, n_channels=2,
            thermal=ThermalSpec(scenarios=(steady(48.0),),
                                temp_bins=(55.0,)))
        with pytest.raises(ValueError, match="single-channel"):
            SimEngine(backend="pallas_interpret").run(spec)
        # the scan replays it when asked
        res = SimEngine(backend="scan").run(spec)
        assert np.isfinite(res.mean_latency_ns).all()

    def test_request_count_above_chip_limit_raises(self):
        """Above the SMEM/VMEM bound a launch fails with a clear error
        before any compiler sees it."""
        from repro.kernels.replay import replay
        n = replay.max_requests(5, 1) + 1
        z = jnp.zeros((1, n), jnp.float32)
        zi = jnp.zeros((1, n), jnp.int32)
        with pytest.raises(ValueError, match="requests per stream"):
            replay.replay_blocks(
                jnp.zeros((1, 1)), jnp.zeros((1, 1), jnp.int32), z, zi,
                zi, zi, zi, jnp.zeros((6, 8)), bs=8, interpret=True)
