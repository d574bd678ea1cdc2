"""Write the frozen profiled table that the replay cells read.

    python3 bench/make_table.py [--config ddr3-1600-1ch] [--seed 0]
                                [--out bench/data/table-ddr3-1600-1ch.json]

Draws the configuration's calibrated population at population seed 0
(`reference.margins.population`), profiles every module with the plain
reference (`reference.margins.profile_module`) and writes the
module-level table: temperature bins, [modules, bins, 4] register rows
(tRCD, tRAS, tWR, tRP in ns) and the per-module safe refresh intervals.
The same population is then profiled by the program under test
(`ALDRAMController.profile`) on the same device, and the count of
table entries where the two differ is printed, and kept in the file.
Run it on the chip: the margin grids of 115 modules are 3e9 elements.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="ddr3-1600-1ch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax
    import numpy as np
    from reference import margins as M

    with open(os.path.join(BENCH, "configs", args.config + ".json")) as fh:
        cfg = json.load(fh)
    pc = cfg["population"]
    bins = tuple(float(b) for b in cfg["temp_bins_c"])
    std = cfg["timing_standard"]["row"]
    cells = M.population(args.seed, pc)
    t0 = time.perf_counter()
    per = [M.profile_module(cells[m], std, bins, pc["charge_constants"],
                            float(cfg["grid_step_ns"]),
                            float(cfg["refresh_guardband_ms"]),
                            float(cfg["refresh_test_c"]))
           for m in range(cells.shape[0])]
    ref = {k: np.stack([p[k] for p in per]) for k in per[0]}
    t_ref = time.perf_counter() - t0

    from repro.core.aldram import ALDRAMController
    from repro.core.charge import ChargeConstants
    from repro.core.profiler import Profiler
    from repro.core.timing import TimingParams
    from repro.core.variation import Population
    prof = Profiler(constants=ChargeConstants(**pc["charge_constants"]),
                    std=TimingParams.from_row(std),
                    refresh_guardband_ms=float(cfg["refresh_guardband_ms"]),
                    grid_step=float(cfg["grid_step_ns"]))
    t0 = time.perf_counter()
    table = ALDRAMController(prof, temp_bins=bins).profile(Population(cells))
    t_prog = time.perf_counter() - t0
    prog = {"params_module": table.params_module,
            "params_bank": table.params,
            "safe_trefi_read": table.safe_trefi_read,
            "safe_trefi_write": table.safe_trefi_write}
    diff = {k: int((np.asarray(prog[k]) != ref[k]).sum()) for k in ref}
    dev = jax.devices()[0]
    out = {
        "about": "Module-level AL-DRAM table of the configuration's "
                 "calibrated population at population seed "
                 f"{args.seed}, profiled by bench/make_table.py with "
                 "the plain reference.",
        "config": args.config, "population_seed": args.seed,
        "device": f"{dev.platform} {dev.device_kind}",
        "entries_differing_from_program": diff,
        "temp_bins": list(bins),
        "params_module": ref["params_module"].tolist(),
        "safe_trefi_read": ref["safe_trefi_read"].tolist(),
        "safe_trefi_write": ref["safe_trefi_write"].tolist(),
    }
    path = args.out or os.path.join(BENCH, "data",
                                    f"table-{args.config}.json")
    with open(path, "w") as fh:
        json.dump(out, fh)
        fh.write("\n")
    print(json.dumps({"reference_s": t_ref, "program_s": t_prog,
                      "entries_differing_from_program": diff,
                      "out": path}))


if __name__ == "__main__":
    main()
