"""Device-resident campaign fast path: end-to-end pipeline benchmark.

Replays the Fig. 4-scale campaign — the paper's 35 multi-core
workload traces (n = 8192 requests) x 2 FR-FCFS scheduling policies
(16- and 64-entry transaction queues, the range real DDR3/4
controllers ship) x 16 stacked timing rows — through three SimEngine
pipelines and reports the end-to-end wall-clock ratios:

  * reference — the pre-fast-path pipeline exactly as PR 2/3 ran it:
    `pack()` materializes FR-FCFS issue orders with the O(N * window)
    pure-Python loop (the cross-call reorder cache is cleared each
    rep, faithful to the per-call-only caching it used to have), ONE
    replay dispatch, raw [T, P, S, N] latency transfer, host numpy
    `_masked_stats`.
  * fast — the PR 4 fast path on materialized traces: the FR-FCFS
    prepass AND the masked mean/p99 reductions ride INSIDE the one
    replay dispatch (`reorder="device"`, `stats="device"`), and only
    [T, P, S]-shaped summaries cross the host boundary.  Its wall
    time (`fast_s`) is the committed-baseline regression gate in CI.
  * fused — the trace axis is a declarative `dram_sim.SynthSpec`, so
    synthesis + FR-FCFS + replay + statistics are truly ONE dispatch
    (`dispatches=1` total, `synth_dispatch_count` never moves); the
    FR-FCFS pending buffer shrinks to its EXACT slack-horizon bound,
    and `backend="auto"` picks the replay core from the platform (the
    Pallas kernel on the TPU, the scan elsewhere).

All pipelines replay the same multiset of requests (threefry makes
the in-dispatch synthesis bit-identical to the materialized batch),
so the ratios isolate what each stage eliminates.  Wall times are
medians over `reps` runs after untimed compile warm-ups.  The bench
asserts the acceptance contract — device stats within 1e-5 relative
of the host reference, one dispatch per fused campaign — and the
``dispatches=1`` CSV field plus the committed ``BENCH_sim_bench.json``
wall-time baseline are checked by CI.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmarks.common import emit


def run(fast: bool = False) -> dict:
    from repro.core import dram_sim, perf_model
    from repro.core.dram_sim import Policy, SynthSpec, Trace
    from repro.core.sim_engine import SimEngine, SimSpec
    from repro.core.timing import DDR3_1600, stack_timing

    n = 1024 if fast else 8192
    n_rows = 8 if fast else 16
    reps = 2 if fast else 3

    # the multi-core half of the Fig. 4 pool (rows 35:70 of the
    # batched synthesis — one traced dispatch), plus the SAME pool as
    # a declarative SynthSpec (identical fold offsets -> bit-identical
    # streams, synthesized inside the fused dispatch)
    tb = perf_model.trace_batch(n=n, seed=0)
    traces = Trace(*(np.asarray(f)[35:70] for f in tb))
    offs, rhs, wfs, ias = perf_model._pool_knobs()
    synth = SynthSpec(n=n, offsets=offs[35:], row_hits=rhs[35:],
                      write_fracs=wfs[35:], inter_arrivals=ias[35:])
    rows = stack_timing([DDR3_1600.scaled(f, f, f, f)
                         for f in np.linspace(1.0, 0.6, n_rows)])
    policies = (Policy(reorder_window=16), Policy(reorder_window=64))
    spec = SimSpec(traces=traces, timings=rows, policies=policies)
    spec_fused = SimSpec(traces=synth, timings=rows, policies=policies)

    fast_eng = SimEngine()                                 # device/device
    ref_eng = SimEngine(stats="host", reorder="host")      # the old path
    fused_eng = SimEngine(backend="auto")

    fast_eng.run(spec)                       # untimed compile warm-ups
    spec_fused.trace_tuple()                 # exact FR-FCFS buffer caps
    fused_eng.run(spec_fused)
    dram_sim._REORDER_CACHE.clear()
    res_ref = ref_eng.run(spec)

    t_fast = []
    for _ in range(reps):
        t0 = time.monotonic()
        res_fast = fast_eng.run(spec)
        t_fast.append(time.monotonic() - t0)
    s0 = perf_model.synth_dispatch_count
    d0 = fused_eng.dispatch_count
    t_fused = []
    for _ in range(reps):
        t0 = time.monotonic()
        res_fused = fused_eng.run(spec_fused)
        t_fused.append(time.monotonic() - t0)
    fused_replays = fused_eng.dispatch_count - d0
    fused_synths = perf_model.synth_dispatch_count - s0
    t_ref = []
    for _ in range(reps):
        # pre-fast-path pack() re-paid the Python reorder every call
        dram_sim._REORDER_CACHE.clear()
        t0 = time.monotonic()
        res_ref = ref_eng.run(spec)
        t_ref.append(time.monotonic() - t0)

    med_fast = statistics.median(t_fast)
    med_fused = statistics.median(t_fused)
    med_ref = statistics.median(t_ref)
    speedup = med_ref / med_fast
    speedup_fused = med_ref / med_fused

    # acceptance: device stats within 1e-5 relative of the host
    # reference — for BOTH fast paths — and the fused campaign is ONE
    # dispatch TOTAL (no separate synthesis launch)
    rel = max(
        float(np.abs(res_fast.mean_latency_ns
                     / res_ref.mean_latency_ns - 1.0).max()),
        float(np.abs(res_fast.p99_latency_ns
                     / res_ref.p99_latency_ns - 1.0).max()))
    rel_fused = max(
        float(np.abs(res_fused.mean_latency_ns
                     / res_ref.mean_latency_ns - 1.0).max()),
        float(np.abs(res_fused.p99_latency_ns
                     / res_ref.p99_latency_ns - 1.0).max()))
    assert rel <= 1e-5, rel
    assert rel_fused <= 1e-5, rel_fused
    assert np.array_equal(res_fast.total_ns, res_ref.total_ns)
    np.testing.assert_allclose(res_fused.total_ns, res_ref.total_ns,
                               rtol=1e-5)
    assert res_fast.latencies is None, "collect-gated output leaked"
    assert fused_replays == reps and fused_synths == 0, \
        (fused_replays, fused_synths)
    dispatches_per_run = 1                  # pinned by the spy tests

    emit("sim_fastpath_campaign", med_fused * 1e6,
         "speedup={:.1f}x|speedup_fused={:.1f}x|vs_fast={:.2f}x|"
         "ref={:.2f}s|fast={:.2f}s|fused={:.2f}s|grid=35x2x{}|n={}|"
         "stats_rel={:.1e}|dispatches={}".format(
             speedup, speedup_fused, med_fast / med_fused, med_ref,
             med_fast, med_fused, n_rows, n, max(rel, rel_fused),
             dispatches_per_run))
    return {
        "speedup": speedup, "ref_s": med_ref, "fast_s": med_fast,
        "fused_s": med_fused, "speedup_fused": speedup_fused,
        "speedup_vs_fast": med_fast / med_fused,
        "ref_s_all": t_ref, "fast_s_all": t_fast,
        "fused_s_all": t_fused,
        "stats_rel_err": rel, "stats_rel_err_fused": rel_fused,
        "n": n, "grid": f"35x2x{n_rows}",
        "windows": [p.reorder_window for p in policies],
        "dispatches": {"replay_per_run": dispatches_per_run,
                       "synth": 1,
                       "fused_total_per_run": fused_replays // reps,
                       "fused_synth": fused_synths},
    }


if __name__ == "__main__":
    import json
    print(json.dumps({k: v for k, v in run().items()
                      if not k.endswith("_all")}, indent=1))
