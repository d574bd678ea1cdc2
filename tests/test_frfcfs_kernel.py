"""The FR-FCFS prepass kernel (`repro.kernels.replay.frfcfs`, interpret
mode) against the scan it replaces: the issue permutation of every
(trace, policy) lane equals `dram_sim.frfcfs_perm`, and its valid
prefix `dram_sim.frfcfs_order`, element for element — unequal windows
in one launch, padding, more pairs than a 128-lane block, a binding
starvation cap, tied and non-monotone arrivals — and through
`SimEngine(backend="pallas_interpret")` the replayed latencies equal
the host-reordered reference bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sim
from repro.core.dram_sim import OPEN_FCFS, Policy, Trace
from repro.core.sim_engine import SimEngine, SimSpec, _eff_window
from repro.core.thermal import ThermalConfig, ThermalSpec, diurnal, steady
from repro.core.timing import ALDRAM_55C_EVAL, DDR3_1600, stack_timing
from repro.kernels.replay import frfcfs


def _streams(t, n, seed=0, lens=None, row_hit=0.6, ia=20.0):
    """[T, N] padded FCFS streams of `synth_trace`, valid prefix
    `lens[i]` long."""
    lens = lens or [n] * t
    arr = np.zeros((t, n), np.float32)
    bank = np.zeros((t, n), np.int32)
    row = np.zeros((t, n), np.int32)
    for i in range(t):
        tr = dram_sim.synth_trace(jax.random.PRNGKey(seed + i), lens[i],
                                  row_hit=row_hit, inter_arrival_ns=ia)
        arr[i, :lens[i]] = tr.arrival
        bank[i, :lens[i]] = tr.bank
        row[i, :lens[i]] = tr.row
    valid = np.arange(n)[None] < np.asarray(lens)[:, None]
    return arr, bank, row, valid


def _case(name):
    """(arrival, bank, row, valid, windows, slacks, caps) of one
    parity case."""
    if name == "windows":       # 1, 4, 16, 64 in one launch
        arr, bank, row, valid = _streams(3, 384, seed=1, ia=6.0)
        return (arr, bank, row, valid, (1, 4, 16, 64),
                (30.0, 30.0, 15.0, 60.0), (4, 16, 64, 256))
    if name == "padded":        # padding with request-like contents
        arr, bank, row, valid = _streams(3, 320, seed=2,
                                         lens=[320, 211, 7])
        rng = np.random.default_rng(2)
        pad = ~valid
        arr[pad] = rng.uniform(0.0, 50.0, pad.sum())
        bank[pad] = rng.integers(0, 8, pad.sum())
        row[pad] = rng.integers(0, 3, pad.sum())
        return arr, bank, row, valid, (8, 16), (30.0, 30.0), (32, 64)
    if name == "many_lanes":    # 70 x 2 pairs: two lane blocks
        arr, bank, row, valid = _streams(70, 64, seed=3, ia=8.0)
        return arr, bank, row, valid, (16, 64), (30.0, 30.0), (64, 256)
    if name == "cap_binds":     # all hits on one bank: the cap forces
        n = 128                 # the head out every 3 deferrals
        row = np.where(np.arange(n) % 3, 7, 1).astype(np.int32)[None]
        return (np.zeros((1, n), np.float32), np.zeros((1, n), np.int32),
                row, np.ones((1, n), bool), (4, 8), (1e9, 1e9), (3, 5))
    if name == "tied_arrivals":
        arr, bank, row, valid = _streams(2, 256, seed=4, row_hit=0.8)
        arr = np.floor(arr / 50.0) * 50.0
        return arr, bank, row, valid, (4, 16), (0.0, 30.0), (16, 64)
    assert name == "non_monotone"
    arr, bank, row, valid = _streams(2, 256, seed=5, row_hit=0.7)
    arr[:, 10:40] = arr[:, 10:40][:, ::-1]
    return arr, bank, row, valid, (16, 64), (30.0, 30.0), (64, 256)


@pytest.mark.parametrize("name", ["windows", "padded", "many_lanes",
                                  "cap_binds", "tied_arrivals",
                                  "non_monotone"])
def test_perm_matches_frfcfs_perm_and_order(name):
    arr, bank, row, valid, windows, slacks, caps = _case(name)
    t, n = arr.shape
    effs = [_eff_window(arr, valid, w, s) for w, s in zip(windows, slacks)]
    if name == "windows":
        assert len(set(min(e, n) for e in effs)) > 2     # unequal bounds
    if name == "non_monotone":
        assert effs == list(windows)                     # no shrink
    perm = np.asarray(frfcfs.reorder_perm(
        jnp.asarray(arr), jnp.asarray(bank), jnp.asarray(row),
        jnp.asarray(valid), windows, jnp.asarray(slacks, jnp.float32),
        jnp.asarray(caps, jnp.int32), max(effs), interpret=True))
    assert perm.shape == (t, len(windows), n)
    for q, (w, s, cap, e) in enumerate(zip(windows, slacks, caps, effs)):
        ref = np.asarray(jax.vmap(
            lambda a, b, r, v: dram_sim.frfcfs_perm(
                a, b, r, v, jnp.int32(w), jnp.float32(s), jnp.int32(cap),
                min(e, n)))(jnp.asarray(arr), jnp.asarray(bank),
                            jnp.asarray(row), jnp.asarray(valid)))
        for i in range(t):
            c = int(valid[i].sum())
            tr = Trace(arr[i, :c], bank[i, :c], row[i, :c],
                       np.zeros(c, bool))
            assert np.array_equal(perm[i, q], ref[i]), (i, w)
            assert np.array_equal(perm[i, q, :c], dram_sim.frfcfs_order(
                tr, w, s, max_defer=cap)), (i, w)
            assert np.array_equal(perm[i, q, c:], np.arange(c, n))


def test_requests_above_the_limit_raise():
    w = 40
    n = frfcfs.max_requests(w) + 8
    z = jnp.zeros((1, n), jnp.float32)
    with pytest.raises(ValueError, match="exceed"):
        frfcfs.reorder_perm(z, z.astype(jnp.int32), z.astype(jnp.int32),
                            jnp.ones((1, n), bool), (16,),
                            jnp.ones((1,)), jnp.ones((1,), jnp.int32), w,
                            interpret=True)
    # the Fig. 4 campaign (8192 requests, a 40-slot buffer) fits
    assert frfcfs.max_requests(w) >= 8192


REF = dict(stats="host", reorder="host")


def test_engine_kernel_prepass_equals_host_reorder():
    """End to end: the static replay with the kernel prepass replays
    the host-reordered request orders — raw latencies bit-identical."""
    arr, bank, row, valid = _streams(2, 512, seed=6, lens=[512, 300])
    traces = tuple(
        Trace(arr[i, :c], bank[i, :c], row[i, :c],
              np.zeros(c, bool)) for i, c in enumerate((512, 300)))
    pols = (OPEN_FCFS, Policy(reorder_window=8),
            Policy(reorder_window=4, reorder_slack_ns=60.0),
            Policy(reorder_window=64))
    spec = SimSpec(traces=traces,
                   timings=stack_timing([DDR3_1600, ALDRAM_55C_EVAL]),
                   policies=pols, collect=("latencies",))
    host = SimEngine(**REF).run(spec)
    dev = SimEngine(backend="pallas_interpret").run(spec)
    assert np.array_equal(dev.latencies, host.latencies)
    assert np.array_equal(dev.total_ns, host.total_ns)


def test_adaptive_kernel_prepass_equals_host_reorder():
    """The thermal campaign runs the same prepass: behind the adaptive
    kernel, the kernel prepass and the host-reordered streams replay
    bit-identical latencies, temperatures and bins."""
    arr, bank, row, _ = _streams(2, 256, seed=8)
    traces = tuple(Trace(arr[i], bank[i], row[i], np.zeros(256, bool))
                   for i in range(2))
    stack = np.stack([ALDRAM_55C_EVAL.as_row(),
                      DDR3_1600.as_row()])[None]        # [K=1, S+1, 6]
    spec = SimSpec(
        traces=traces, timings=stack,
        policies=(OPEN_FCFS, Policy(reorder_window=16)),
        thermal=ThermalSpec(
            scenarios=(steady(48.0),
                       diurnal(40.0, 90.0, period_ns=2.0e4)),
            temp_bins=(55.0,),
            config=ThermalConfig(tau_ns=5.0e3, c_heat=2.0e-4)),
        collect=("latencies", "temps", "bins"))
    host = SimEngine(backend="pallas_interpret", reorder="host").run(spec)
    dev = SimEngine(backend="pallas_interpret").run(spec)
    for f in ("latencies", "temps", "bins", "total_ns", "bank_heat"):
        assert np.array_equal(getattr(dev, f), getattr(host, f)), f
