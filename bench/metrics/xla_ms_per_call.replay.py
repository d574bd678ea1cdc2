"""xla_ms_per_call.replay (ms): per entry call, device busy time inside
the call minus the replay kernels' time, averaged over the chips: the
SimEngine's device-side XLA work (synthesis, FR-FCFS prepass,
statistics) (device trace)."""

import bench_trace as T


def value(ctx: dict):
    red = ctx["trace"]
    if not red or not red.get("busy_in_calls_s"):
        return None
    n_dev = len(red["busy_in_calls_s"])
    busy = T.mean_over_devices(red["busy_in_calls_s"])
    kern = T.kernel_s(red, "replay") / n_dev
    return (busy - kern) / red["calls"] * 1e3
