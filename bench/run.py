"""Chip benchmark: run one cell of BENCHMARK.json once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of BENCHMARK.json's "workloads": a configuration
(bench/configs/<config>.json) under a traffic mix
(bench/traffic/<traffic>.json).  The traffic file names the entry
driver (bench/entries/<entry>.py) that builds the cell's inputs from
the two files and the seed, calls the system under test, counts the
work of a call and compares what the timed path produced with the
plain reference (bench/reference/).  Each metric is computed by its
own reader, bench/metrics/<metric>.py, found by the metric's name.

Order of a run:

  1. JAX's persistent compilation cache at <checkout>/.jax_cache, or
     at $JAX_COMPILATION_CACHE_DIR where that is set;
  2. the device check: a TPU, and as many chips as the cell asks for,
     or the run stops with exit code 3 and prints no result;
  3. the cell's inputs, from its files and --seed;
  4. warm-up: one call of the entry, which compiles (or loads from the
     cache) every program the window runs; `setup_s` is the time from
     the start of this process to the end of warm-up;
  5. the window: the entry is called again and again until --seconds
     have passed; each call ends in host numpy arrays, so the device
     has finished.  With --trace 1 the window runs under the JAX
     profiler, each call inside a `bench.call` span;
  6. the peak device memory, then the reference comparison;
  7. the result: the numbers compared, each with its limit, as the
     last lines of standard error, and one JSON object as the last
     line of standard output.

With --trace 0 the result carries the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, the device's busy and window seconds,
and a breakdown of device time and idle gaps.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".trace")


class BenchError(Exception):
    """A cell, file or device the run cannot use."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path."""
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, config, traffic) of the workload `name`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = load_json(os.path.join(BENCH, "configs",
                                    cell["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_for(bench: dict, kind: str, cell: str) -> list[dict]:
    """The `kind` ("end_to_end" or "per_layer") metrics a cell reports:
    those that list it under "workloads", or have no such list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def entry_module(kind: str):
    return load_module(os.path.join(BENCH, "entries", kind + ".py"),
                       "bench_entry_" + kind)


def enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of the window comes from the cache after the first
    # run in a checkout, however short its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s); JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts the programs JAX compiled or loaded from its persistent
    cache, and the cache's hits (JAX's monitoring events)."""

    PROGRAM = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.programs = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._program)
        jax.monitoring.register_event_listener(self._hit)

    def _program(self, event, duration, **kw):
        self.programs += event == self.PROGRAM

    def _hit(self, event, **kw):
        self.hits += event == self.HIT

    def __str__(self) -> str:
        return (f"{self.programs} programs, {self.hits} from the "
                f"persistent cache")


def run_window(cell, seconds: float, trace: bool, jax):
    """Call the entry until `seconds` have passed.  Returns (durations,
    window seconds from the first call's start to the last call's end,
    output digests, failures, error texts, last output, trace events or
    None)."""
    import numpy as np
    from compare import digest
    durations, digests, errors = [], [], []
    failed, last = 0, None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # device ops and the runtime's host events; no Python tracer,
        # which would slow the host path it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    w0 = time.perf_counter()
    try:
        while True:
            t0 = time.perf_counter()
            try:
                if trace:
                    with jax.profiler.TraceAnnotation("bench.call"):
                        out = cell.call()
                else:
                    out = cell.call()
                ok = all(bool(np.isfinite(np.asarray(v)).all())
                         for v in out.values())
            except Exception:       # counted, and the window goes on
                out, ok = None, False
                errors.append(traceback.format_exc(limit=3))
            t1 = time.perf_counter()
            durations.append(t1 - t0)
            failed += not ok
            digests.append(None if out is None else digest(out))
            if out is not None:
                last = out
            if t1 - w0 >= seconds:
                break
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
    events = None
    if trace:
        from bench_trace import load, xplane_path
        t0 = time.perf_counter()
        path = xplane_path(TRACE_DIR)
        events = load(path)
        print(f"trace: {os.path.getsize(path)} bytes written in "
              f"{t0 - t_stop:.1f} s; "
              f"{sum(len(v) for v in events['devices'].values())} device "
              f"ops, {len(events['host'])} host events, read in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    window_s = t1 - w0
    return durations, window_s, digests, failed, errors, last, events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell_def, config, traffic = find_cell(bench, args.workload)
        entry = entry_module(traffic["entry"])
        sys.path[:0] = [os.path.join(ROOT, "src")]
        import jax
        cache_dir = enable_compile_cache(jax)
        device = device_info(jax, int(cell_def["chips"]))
    except (BenchError, OSError, KeyError, ValueError, RuntimeError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(f"device {json.dumps(device)} compile_cache {cache_dir}",
          file=sys.stderr, flush=True)

    compiles = CompileCounter(jax)
    t_init = time.perf_counter()
    cell = entry.Cell(config=config, traffic=traffic, seed=args.seed,
                      chips=int(cell_def["chips"]))
    t_inputs = time.perf_counter()
    cell.call()                                         # warm-up
    setup_s = time.perf_counter() - T_START
    print(f"setup_s {setup_s!r}: start and device {t_init - T_START!r}, "
          f"inputs {t_inputs - t_init!r}, warm call "
          f"{T_START + setup_s - t_inputs!r}; {compiles}",
          file=sys.stderr, flush=True)
    n_setup = compiles.programs

    durations, window_s, digests, failed, errors, last, events = run_window(
        cell, args.seconds, bool(args.trace), jax)
    for e in errors[:3]:
        print(e, file=sys.stderr)
    print(f"window: {len(durations)} calls in {window_s!r} s, "
          f"{compiles.programs - n_setup} programs compiled or loaded",
          file=sys.stderr, flush=True)
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices())

    red = None
    if events is not None:
        from bench_trace import mean_over_devices, reduce
        t0 = time.perf_counter()
        red = reduce(events)
        print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        device["busy_s"] = mean_over_devices(red.get("busy_s", {})) or 0.0
        device["window_s"] = red.get("window_s", 0.0)

    ctx = {"setup_s": setup_s, "durations": durations,
           "window_s": window_s, "calls": len(durations),
           "work": cell.work, "trace": red, "chips": device["count"]}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, kind, args.workload):
        v = reader(m["name"]).value(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the comparison runs once the window has closed and the peak
    # memory has been read; the program's state is dropped first
    checks = {"failed_calls": (float(failed), 0.0),
              "calls_differing": (float(sum(
                  d != digests[0] for d in digests)), 0.0)}
    if last is not None:
        cell.release()
        (name, limit), = traffic["check"]["limits"].items()
        checks[name] = (cell.number(cell.select(last), cell.reference()),
                        float(limit))
    correct = last is not None and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())
    for name, (v, lim) in checks.items():
        print(f"check {name} = {v!r} (limit {lim!r}): "
              f"{'ok' if math.isfinite(v) and v <= lim else 'FAIL'}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)

    result = {"correct": bool(correct), "attempted": len(durations),
              "failed": int(failed), "metrics": metrics, "device": device}
    if red:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.append(BENCH)
    sys.modules.setdefault("bench_trace", load_module(
        os.path.join(BENCH, "trace.py"), "bench_trace"))
    sys.exit(main())
