"""host_ms_per_call.replay (ms): per entry call, its wall time minus
the device busy time inside it (averaged over the chips): the time the
ALDRAMController / SimEngine host path holds the call while no device
operation runs (device trace and the harness's call spans)."""

import bench_trace as T


def value(ctx: dict):
    red = ctx["trace"]
    v = None if not red else T.host_s_per_call(red)
    return None if v is None else v * 1e3
