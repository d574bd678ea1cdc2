"""Full AL-DRAM reproduction pipeline on the 115-module population:
refresh envelopes -> safe intervals -> timing sweeps at 55/85C ->
per-parameter reductions vs the paper's measured numbers -> system
speedup (Fig. 4), both from the paper's 55C evaluation constants and —
closing the loop — from the profiler's own TimingTable, resolved per
temperature bin through one batched SimEngine campaign.

    PYTHONPATH=src python examples/aldram_profile.py [--fast]
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

    from benchmarks import fig2_refresh, fig3_population, fig4_system
    print("== refresh envelopes (Fig 2a) ==")
    print(json.dumps(fig2_refresh.run(fast=args.fast), indent=1))
    print("== population analysis (Fig 3 / Sec 5.2) ==")
    print(json.dumps(fig3_population.run(fast=args.fast), indent=1))
    print("== system evaluation (Fig 4, paper 55C constants) ==")
    print(json.dumps(fig4_system.run(fast=args.fast)["summary"],
                     indent=1, default=str))
    print("== system evaluation (Fig 4, profiled TimingTable, "
          "temperature-resolved) ==")
    prof = fig4_system.run_profiled(fast=args.fast)
    print(json.dumps({str(t): s for t, s in prof["per_temp"].items()},
                     indent=1))


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
