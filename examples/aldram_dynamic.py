"""Closed-loop AL-DRAM demo: the ONLINE mechanism, end to end.

Profiles the module population, stacks the per-bin all-module-safe
timing rows (JEDEC fallback last), and replays the 35-workload pool
with the controller's temperature-bin switching running INSIDE the
traced scan — per-request RC temperature sensing, conservative
round-up, down-switch hysteresis — under dynamic ambient scenarios
(steady, diurnal ramp, cooling failure, bursty), bracketed by the
static-worst-case and oracle deployments.  Three traced dispatches
for the whole campaign.

    PYTHONPATH=src python examples/aldram_dynamic.py [--fast]
"""

import argparse
import json
import os
import sys

# the benchmark modules live at the repo root, not next to this script
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()

    from benchmarks.common import population, profiler
    from repro.core.aldram import ALDRAMController, default_scenarios
    from repro.core.sim_engine import SimEngine

    pop = population(args.fast)
    ctrl = ALDRAMController(profiler(args.fast))
    print("== profiling the population ==")
    ctrl.profile(pop)
    rows, bins = ctrl.table.safe_stack()
    print("bin edges (C):", list(map(float, bins)))
    print("table stack (trcd, tras, twr, trp | trefi, tcl), JEDEC last:")
    for r in rows:
        print("  ", [round(float(x), 2) for x in r])

    print("== adaptive replay under dynamic thermal scenarios ==")
    engine = SimEngine()
    res = ctrl.evaluate_dynamic(pop, scenarios=default_scenarios(),
                                n=1024 if args.fast else 4096,
                                engine=engine)
    print(json.dumps(res["per_scenario"], indent=1))
    print(f"replay dispatches: {engine.dispatch_count} "
          "(1 adaptive grid + 1 static bracket)")
    for name, d in res["per_scenario"].items():
        gap = d["oracle_gmean"] - d["adaptive_gmean"]
        print(f"{name:>18}: adaptive {d['adaptive_gmean']:+.1%} vs "
              f"static-worst {d['static_worst_gmean']:+.1%} "
              f"(hysteresis costs {gap:+.2%})")


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
