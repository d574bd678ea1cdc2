"""device_idle_share.replay (%): share of the traced window in which
no operation ran on the device, averaged over the chips (device
trace)."""

import bench_trace as T


def value(ctx: dict):
    return None if not ctx["trace"] else T.idle_share(ctx["trace"])
