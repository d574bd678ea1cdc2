"""The control of a cell's correctness check, and the program's own
readings beside it.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--program]

For each seed the plain reference runs twice on the cell's sampled
inputs: in the configuration's precision (float32), and in the nearest
lower one (bfloat16) in the program's place.  The number the cell's
check compares is printed for the control, with its limit; the control
has to exceed it.  With --program the timed entry is also called once
per seed and its number printed: the sound reading the limit sits
above.  One JSON line per seed; the last line sums them up.  Runs on
the machine it is started on; the runs of bench/run.py do not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()
    sys.path.append(BENCH)
    sys.path[:0] = [os.path.join(ROOT, "src")]
    import run
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.modules.setdefault("bench_trace", run.load_module(
        os.path.join(BENCH, "trace.py"), "bench_trace"))
    run.enable_compile_cache(jax)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell_def, config, traffic = run.find_cell(bench, args.workload)
    entry = run.entry_module(traffic["entry"])
    rows = []
    for seed in args.seeds:
        cell = entry.Cell(config=config, traffic=traffic, seed=seed,
                          chips=int(cell_def["chips"]))
        line = {"workload": args.workload, "seed": seed}
        (name, limit), = traffic["check"]["limits"].items()
        out = cell.call() if args.program else None
        cell.release()
        ref = cell.reference(np.float32)
        if out is not None:
            line["program"] = {name: cell.number(cell.select(out), ref)}
        value = cell.number(cell.reference(jnp.bfloat16), ref)
        line["control"] = {name: value}
        line["limit"] = limit
        line["control_fails"] = bool(not value <= limit)
        rows.append(line)
        print(json.dumps(line), flush=True)
    dev = jax.devices()[0]
    print(json.dumps({
        "workload": args.workload, "seeds": args.seeds,
        "control_min": min(next(iter(r["control"].values())) for r in rows),
        "program_max": (max(next(iter(r["program"].values()))
                            for r in rows) if args.program else None),
        "all_controls_fail": all(r["control_fails"] for r in rows),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
