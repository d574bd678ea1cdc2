"""Bring-up run of the AL-DRAM main path on a TPU, through the public API.

    python chip_smoke.py               # one chip: profile -> verify ->
                                       # static replay -> adaptive replay
    python chip_smoke.py --four-chips  # four chips: the sharded traffic
                                       # campaign against its unsharded run

One chip, paper scale:

  * profile   the 115-module calibrated population, non-fast profiler,
              with the Pallas margin kernel; margins of a module subset
              against the jnp reference on the same chip (max |diff|,
              pass/fail flips — flips must be zero), and the module
              and bank pass envelopes reduced on the device (equal);
  * verify    the zero-error invariant of the profiled table;
  * static    the Fig. 4 campaign (35 workloads x 2 modes, n=8192)
              with the Pallas replay kernel against the scan;
  * adaptive  the fused thermal campaign (adaptive replay + static
              bracket, one dispatch) with the Pallas kernels against
              the scan; the thermal diagnostics come from the kernel's
              own accumulators.

Replay statistics must agree within 1e-5 relative and `total_ns`
exactly; each phase prints where the two backends are not
bit-identical.  Each phase prints its compile-inclusive first call
(`cold_s`) and a second call (`warm_s`) in seconds: results come back
as numpy arrays, so each call has waited for the device.  These are
bring-up times, not a benchmark.

Nothing falls back: the run stops at once without a TPU, and any
failed check raises.  The last line of standard output is
`{"ok": true, "device": {"platform", "kind", "count"}}`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

RTOL = 1e-5          # device-stats contract (README, "Verify")
N_REQ = 8192         # requests per trace: the Fig. 4 scale


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def report(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=float), flush=True)


def timed(fn):
    """(result, seconds) of one call."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def compare(a, b, exact: bool = False) -> dict:
    """Max relative difference of `a` against the reference `b`, the
    count of elements that are not bit-identical, and whether the pair
    meets its bound (exact, or `RTOL`) with finite values."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    check(a.shape == b.shape, f"shapes {a.shape} vs {b.shape}")
    rel = float((np.abs(a - b) / np.maximum(np.abs(b), 1e-30)).max())
    ok = bool(np.isfinite(a).all()) and (rel == 0.0 if exact
                                         else rel <= RTOL)
    return {"max_rel": rel, "not_bit_identical": int((a != b).sum()),
            "of": int(a.size), "bound": 0.0 if exact else RTOL,
            "ok": ok}


def check_parity(phase: str, parity: dict) -> None:
    bad = sorted(k for k, v in parity.items() if not v["ok"])
    check(not bad, f"{phase}: {bad} out of bounds")


def margin_parity(engine, ref_engine, pop, spec) -> dict:
    """`engine`'s dense margins of the campaign `spec` on `pop` against
    `ref_engine`'s (max |diff|, pass/fail flips, margins compared), and
    the module and bank pass envelopes of the two engines' sweeps, which
    reduce the grids on the device (entries differing)."""
    import numpy as np
    kern = engine.campaign_margins(pop, spec)
    ref = ref_engine.campaign_margins(pop, spec)
    a, b = engine.sweep(pop, spec), ref_engine.sweep(pop, spec)
    return {
        "margin_max_abs_diff": max(float(np.abs(k - r).max())
                                   for k, r in zip(kern, ref)),
        "pass_fail_flips": sum(int(((k >= 0.0) != (r >= 0.0)).sum())
                               for k, r in zip(kern, ref)),
        "margin_cells_compared": int(sum(k.size for k in kern)),
        "envelopes_differing": sum(
            int((x != y).sum()) for f in ("ok", "ok_bank")
            for x, y in zip(getattr(a, f), getattr(b, f)))}


# ------------------------------------------------------------- phases
def phase_profile(pop, prof, subset: int = 4):
    """Profile the population with the Pallas margin kernel (cold and
    warm), then hold the kernel's margins of `subset` modules against
    the jnp reference on the same device."""
    import numpy as np
    from repro.core import aldram
    from repro.core.aldram import ALDRAMController
    from repro.core.sweep import MarginEngine
    from repro.core.variation import Population

    ctrl = ALDRAMController(prof)
    table, cold = timed(lambda: ctrl.profile(pop))
    _, warm = timed(lambda: ALDRAMController(prof).profile(pop))
    m = pop.n_modules
    cpm = int(np.prod(pop.cells.shape[1:4]))
    spec = ctrl.sweep_result.spec
    cols = len(spec.temps) * sum(t.combos.shape[0] for t in spec.tests)
    g = max(1, aldram.PROFILE_GRID_ELEMS // (cpm * cols))
    expect = 1 + math.ceil(m / g) if g < m else 2
    # both profiles dispatch through the profiler's one engine
    check(ctrl.engine.dispatch_count == 2 * expect,
          f"profile dispatches {ctrl.engine.dispatch_count // 2} "
          f"!= {expect}")
    check(bool(np.isfinite(table.params).all()), "table not finite")

    sub = Population(pop.cells[:subset])
    rp_r, rp_w = prof.refresh_campaign(sub, 85.0)
    sub_spec = prof.campaign_spec(ctrl.temp_bins, rp_r, rp_w)
    parity = margin_parity(prof.engine, MarginEngine(
        constants=prof.constants, std=prof.std, impl="ref"), sub, sub_spec)
    check(parity["pass_fail_flips"] == 0,
          f"{parity['pass_fail_flips']} pass/fail flips against the "
          f"reference")
    check(parity["envelopes_differing"] == 0,
          f"{parity['envelopes_differing']} envelope entries differ from "
          f"the reference")
    report("profile", cold_s=cold, warm_s=warm, modules=m,
           cells=int(m * cpm), combo_columns=cols,
           dispatches=expect, parity_modules=subset, **parity)
    return ctrl


def phase_verify(ctrl, pop):
    ok, cold = timed(lambda: ctrl.verify(pop))
    check(ok is True, "verify() returned False")
    ok2, warm = timed(lambda: ctrl.verify(pop))
    check(ok2 is True, "verify() returned False (second call)")
    report("verify", cold_s=cold, warm_s=warm, verified=True)


def phase_static(ctrl, pop, backend: str, n: int = N_REQ):
    """Fig. 4 campaign through `evaluate_system`: kernel vs scan."""
    from repro.core import perf_model
    from repro.core.sim_engine import SimEngine

    def run(be):
        eng = SimEngine(backend=be)
        with perf_model.synth_dispatch_scope() as syn:
            out = ctrl.evaluate_system(pop, n=n, engine=eng)
        check(eng.dispatch_count == 1 and syn.count == 1,
              f"{be}: dispatches replay={eng.dispatch_count} "
              f"synth={syn.count}, expected 1 + 1")
        return out["result"]

    res, cold = timed(lambda: run(backend))
    _, warm = timed(lambda: run(backend))
    ref, ref_cold = timed(lambda: run("scan"))
    _, ref_warm = timed(lambda: run("scan"))
    parity = {f: compare(getattr(res, f), getattr(ref, f),
                         exact=(f == "total_ns"))
              for f in ("mean_latency_ns", "p99_latency_ns",
                        "total_ns")}
    report("static", backend=backend, cold_s=cold, warm_s=warm,
           scan_cold_s=ref_cold, scan_warm_s=ref_warm,
           grid=list(res.mean_latency_ns.shape), n=n,
           dispatches={"replay": 1, "synth": 1}, parity=parity)
    check_parity("static", parity)


def phase_adaptive(ctrl, pop, backend: str, n: int = N_REQ):
    """Fused thermal campaign through `evaluate_dynamic(fused=True)`:
    adaptive replay + static bracket in ONE dispatch, kernel vs scan."""
    from repro.core import perf_model
    from repro.core.sim_engine import SimEngine

    def run(be):
        eng = SimEngine(backend=be)
        with perf_model.synth_dispatch_scope() as syn:
            out = ctrl.evaluate_dynamic(pop, n=n, engine=eng, fused=True)
        check(eng.dispatch_count == 1 and syn.count == 0,
              f"{be}: dispatches replay={eng.dispatch_count} "
              f"synth={syn.count}, expected 1 + 0")
        return out

    out, cold = timed(lambda: run(backend))
    _, warm = timed(lambda: run(backend))
    ref, ref_cold = timed(lambda: run("scan"))
    _, ref_warm = timed(lambda: run("scan"))
    a, b = out["result"], ref["result"]
    parity = {f: compare(getattr(a, f), getattr(b, f),
                         exact=(f in ("total_ns", "bin_switches")))
              for f in ("mean_latency_ns", "p99_latency_ns", "total_ns",
                        "temp_max", "temp_mean", "bin_switches",
                        "bank_heat")}
    # the static bracket and baseline columns of the same dispatch
    parity["bracket_mean_latency_ns"] = compare(
        out["mean_latency_ns"], ref["mean_latency_ns"])
    parity["worst_bin"] = compare(out["worst_bin"], ref["worst_bin"],
                                  exact=True)
    report("adaptive", backend=backend, cold_s=cold, warm_s=warm,
           scan_cold_s=ref_cold, scan_warm_s=ref_warm,
           grid=list(a.mean_latency_ns.shape), n=n,
           dispatches={"replay": 1, "synth": 0}, parity=parity)
    check_parity("adaptive", parity)


def phase_four_chips(n: int = N_REQ, n_streams: int = 16):
    """`traffic_bench`'s full-scale campaign sharded over a 4-chip
    campaign mesh against the same campaign unsharded on one chip, in
    this process: the statistics must be identical."""
    import jax
    import numpy as np
    from repro.core import perf_model
    from repro.core.dram_sim import Policy
    from repro.core.sim_engine import SimEngine, SimSpec
    from repro.core.timing import DDR3_1600, stack_timing
    from repro.launch.mesh import make_campaign_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX sees "
          f"{len(devs)}")
    tenants = perf_model.tenant_spec(n=n, n_streams=n_streams, seed=0)
    rows = stack_timing([DDR3_1600.scaled(f, f, f, f)
                         for f in np.linspace(1.0, 0.68, 8)])
    policies = tuple(Policy(reorder_window=16, interleave=il)
                     for il in ("row", "cacheline", "bank_xor"))
    sharded = SimEngine(mesh=make_campaign_mesh())
    single = SimEngine()
    for n_ch in (1, 2, 4):
        spec = SimSpec(traces=tenants, timings=rows, policies=policies,
                       n_channels=n_ch)
        res, cold = timed(lambda: sharded.run(spec))
        _, warm = timed(lambda: sharded.run(spec))
        peak = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
        ref, ref_cold = timed(lambda: single.run(spec))
        _, ref_warm = timed(lambda: single.run(spec))
        parity = {f: compare(getattr(res, f), getattr(ref, f),
                             exact=True)
                  for f in ("mean_latency_ns", "p99_latency_ns",
                            "total_ns")}
        report("four_chips", channels=n_ch, cold_s=cold, warm_s=warm,
               single_chip_cold_s=ref_cold, single_chip_warm_s=ref_warm,
               shard_shape=list(sharded.shard_shape),
               grid=[n_streams, len(policies), len(rows)], n=n,
               peak_bytes_per_device=peak,
               sharded_rel=max(p["max_rel"] for p in parity.values()),
               parity=parity)
        check_parity(f"four_chips channels={n_ch}", parity)
        check(sharded.shard_shape[0] == 4,
              f"shard shape {sharded.shard_shape}")
        # every chip held a shard: none stayed empty
        check(min(peak) > 0, f"peak bytes per device {peak}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded traffic campaign on a "
                         "4-chip mesh, against its unsharded run")
    args = ap.parse_args()

    from repro.runtime import compile_cache
    cache = compile_cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    report("device", **device, compile_cache=cache)

    if args.four_chips:
        phase_four_chips()
    else:
        from benchmarks.common import population, profiler
        pop = population(False)
        prof = dataclasses.replace(profiler(False), impl="pallas",
                                   engine=None)
        ctrl = phase_profile(pop, prof)
        phase_verify(ctrl, pop)
        phase_static(ctrl, pop, "pallas")
        phase_adaptive(ctrl, pop, "pallas")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
