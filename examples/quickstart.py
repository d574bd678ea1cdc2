"""Quickstart: the AL-DRAM pipeline in 60 seconds.

Profiles a small simulated DIMM population, builds the per-module /
per-temperature timing tables, verifies the reliability invariant, and
replays a memory trace under standard vs adaptive timings.

    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses

import jax

from repro.core import dram_sim
from repro.core.aldram import ALDRAMController
from repro.core.calibration import (CALIBRATED_CONSTANTS,
                                    CALIBRATED_VARIATION)
from repro.core.profiler import Profiler
from repro.core.timing import DDR3_1600
from repro.core.variation import sample_population


def main():
    # 1. a small population (12 modules) for speed
    vcfg = dataclasses.replace(CALIBRATED_VARIATION, n_modules=12,
                               n_cells=8)
    pop = sample_population(jax.random.PRNGKey(0), vcfg)

    # 2. profile -> tables (45..85C bins).  The whole multi-temperature
    # read+write campaign is compiled by the MarginEngine into two
    # batched kernel dispatches (one refresh sweep, one timing sweep).
    ctrl = ALDRAMController(Profiler(constants=CALIBRATED_CONSTANTS,
                                     grid_step=2.5))
    ctrl.profile(pop)
    print("timing reductions @55C:", ctrl.average_reductions(55.0))
    print("timing reductions @85C:", ctrl.average_reductions(85.0))

    # 3. reliability invariant (the paper's 33-day stress test) — one
    # vectorized dispatch over every (module, temperature bin) pair
    print("zero-error invariant:", ctrl.verify(pop))
    print("kernel dispatches for profile+verify:",
          ctrl.engine.dispatch_count)

    # 4. runtime selection + replay a trace
    module, temp = 3, 55.0
    fast = ctrl.select(module, temp)
    print(f"module {module} @ {temp}C ->", fast)
    trace = dram_sim.synth_trace(jax.random.PRNGKey(1), 4096)
    std = dram_sim.simulate(trace, DDR3_1600)
    adp = dram_sim.simulate(trace, fast)
    print("mean DRAM latency: standard {:.1f}ns -> AL-DRAM {:.1f}ns "
          "({:.1%} faster)".format(
              float(std["mean_latency_ns"]), float(adp["mean_latency_ns"]),
              float(std["mean_latency_ns"] / adp["mean_latency_ns"] - 1)))


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    main()
