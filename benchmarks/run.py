"""Benchmark harness: one function per paper table/figure plus the
framework/roofline benches.  Prints ``name,us_per_call,derived`` CSV
and writes a machine-readable ``BENCH_<name>.json`` summary per bench
(wall time, dispatch counts, headline stats) to the REPO ROOT by
default, so the perf trajectory is tracked across PRs (committed
baselines; CI also uploads them as workflow artifacts and gates the
sim_bench fast wall time against the committed baseline).

  python -m benchmarks.run [--fast] [--only NAME] [--out-dir DIR]
                           [--repeat N] [--baseline DIR]
                           [--baseline-factor F]

``--repeat N`` runs each bench N times and reports the MEDIAN wall
time (the per-run walls are kept in the summary), so one-off noise on
shared runners doesn't pollute the trajectory.

``--baseline DIR`` compares each bench's median wall time against the
committed ``BENCH_<name>.json`` in DIR (e.g. the repo root) after the
run, prints a regression table, and exits non-zero when any bench
runs slower than ``--baseline-factor`` (default 2.0) times its
baseline — the same contract CI applies to the sim_bench fast path,
available locally for every bench.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

_MAX_DEPTH = 3
_MAX_ITEMS = 24
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _headline(obj, depth: int = 0):
    """Scalar-only projection of a bench's result dict: keeps the
    JSON-serializable headline numbers, drops arrays/traces/objects so
    the summaries stay diff-friendly."""
    if isinstance(obj, (bool, int, float, str)) or obj is None:
        return obj
    try:
        import numpy as np
        # numpy scalars are headline numbers too — convert BEFORE the
        # depth cutoff so np.float32 and float survive identically
        if isinstance(obj, np.generic):
            return obj.item()
    except Exception:  # noqa: BLE001
        pass
    if depth >= _MAX_DEPTH:
        return None
    if isinstance(obj, dict):
        out = {}
        for k, v in list(obj.items())[:_MAX_ITEMS]:
            hv = _headline(v, depth + 1)
            if hv is not None or v is None:
                out[str(k)] = hv
        return out or None
    if isinstance(obj, (list, tuple)):
        vals = [_headline(v, depth + 1) for v in obj[:_MAX_ITEMS]]
        vals = [v for v in vals if v is not None]
        return vals or None
    return None


def _write_summary(out_dir: str, name: str, walls: list[float],
                   fast: bool, result,
                   error: str | None = None) -> None:
    summary = {"name": name,
               "wall_s": round(statistics.median(walls), 6),
               "fast": fast, "error": error}
    if len(walls) > 1:
        summary["repeats"] = len(walls)
        summary["wall_s_all"] = [round(w, 6) for w in walls]
    if isinstance(result, dict):
        if "dispatches" in result:
            summary["dispatches"] = _headline(result["dispatches"])
        summary["headline"] = _headline(result)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced population / fewer samples")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out-dir", default=_REPO_ROOT,
                    help="directory for the BENCH_<name>.json summaries "
                         "(default: the repo root, so baselines are "
                         "committed and tracked across PRs)")
    ap.add_argument("--repeat", type=int, default=1,
                    help="run each bench N times; report the median "
                         "wall time")
    ap.add_argument("--baseline", default=None,
                    help="directory holding committed BENCH_<name>.json "
                         "baselines to regression-compare against")
    ap.add_argument("--baseline-factor", type=float, default=2.0,
                    help="fail when a bench's wall time exceeds "
                         "FACTOR x its baseline (default 2.0)")
    args = ap.parse_args()

    from repro.runtime import compile_cache
    compile_cache.enable()
    from benchmarks import (fault_bench, fig2_refresh, fig2_timing,
                            fig3_population, fig4_system, fig_bank,
                            fig_region, fleet_bench, framework,
                            multi_timing, power_bench, repeatability,
                            roofline, sim_bench, thermal_bench,
                            traffic_bench)

    benches = {
        "fig2_refresh": fig2_refresh.run,
        "fig2_timing": fig2_timing.run,
        "fig3_population": fig3_population.run,
        "fig4_system": fig4_system.run,
        "fig4_profiled": fig4_system.run_profiled,
        "fig_bank": fig_bank.run,
        "fig_region": fig_region.run,
        "sim_bench": sim_bench.run,
        "thermal_bench": thermal_bench.run,
        "power": power_bench.run,
        "repeatability": repeatability.run,
        "multi_timing": multi_timing.run,
        "fleet_bench": fleet_bench.run,
        "fault_bench": fault_bench.run,
        "traffic_bench": traffic_bench.run,
        "framework": framework.run,
        "roofline": roofline.run,
    }
    os.makedirs(args.out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    measured: dict[str, float] = {}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        walls, res, err = [], None, None
        for _ in range(max(1, args.repeat)):
            t0 = time.monotonic()
            try:
                res = fn(fast=args.fast)
            except Exception as e:  # noqa: BLE001
                err = f"{type(e).__name__}: {e}"
                print(f"{name},0,ERROR:{err}", flush=True)
                traceback.print_exc(file=sys.stderr)
            walls.append(time.monotonic() - t0)
            if err:
                break
        if err:
            failed.append(name)
        else:
            measured[name] = statistics.median(walls)
        _write_summary(args.out_dir, name, walls, args.fast, res,
                       error=err)
    if args.baseline:
        regressions = _compare_baseline(measured, args.baseline,
                                        args.baseline_factor,
                                        fast=args.fast)
        if regressions:
            raise SystemExit(f"wall-time regressions: {regressions}")
    if failed:
        raise SystemExit(f"failed: {failed}")


def _compare_baseline(measured: dict[str, float], baseline_dir: str,
                      factor: float, fast: bool = False) -> list[str]:
    """Print a wall-time table vs the committed baselines; return the
    benches slower than `factor` x baseline.  Benches without a
    committed baseline — or with an unreadable/malformed one, or one
    recorded under a different --fast mode — just WARN and skip (the
    run's own summaries are already written by this point; a missing
    or stale baseline must never fail the run).  The converse holds
    too: a baseline for a bench that did NOT run this time (renamed,
    removed, or filtered by --only) warns and is skipped — it must
    never gate either.  Only comparable entries gate."""
    regressions = []
    print(f"\nbaseline compare vs {baseline_dir} "
          f"(fail > {factor:g}x):", file=sys.stderr)
    try:
        stale = sorted(
            f[len("BENCH_"):-len(".json")]
            for f in os.listdir(baseline_dir)
            if f.startswith("BENCH_") and f.endswith(".json"))
    except OSError:
        stale = []
    for name in stale:
        if name not in measured:
            print(f"  {name}: baseline present but bench did not run "
                  f"this time — skipped", file=sys.stderr)
    for name, wall in measured.items():
        path = os.path.join(baseline_dir, f"BENCH_{name}.json")
        try:
            with open(path) as f:
                base = json.load(f)
        except (OSError, ValueError):
            print(f"  {name}: {wall:.3f}s (no baseline)",
                  file=sys.stderr)
            continue
        if not isinstance(base, dict):
            print(f"  {name}: {wall:.3f}s (malformed baseline)",
                  file=sys.stderr)
            continue
        if bool(base.get("fast")) != bool(fast):
            print(f"  {name}: {wall:.3f}s (baseline from different "
                  f"--fast mode)", file=sys.stderr)
            continue
        base_wall = base.get("wall_s")
        if not base_wall:
            print(f"  {name}: {wall:.3f}s (baseline has no wall_s)",
                  file=sys.stderr)
            continue
        ratio = wall / base_wall
        flag = " REGRESSION" if ratio > factor else ""
        print(f"  {name}: {wall:.3f}s vs {base_wall:.3f}s "
              f"({ratio:.2f}x){flag}", file=sys.stderr)
        if ratio > factor:
            regressions.append(name)
    return regressions


if __name__ == "__main__":
    main()
