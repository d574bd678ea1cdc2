"""replay_kernel_ns_per_kreq (ns/kreq): device time of the static and
adaptive replay kernels, summed over the chips, per 1000 request-replays
of the traced window (device trace; the work is counted from the
campaign's shapes)."""

import bench_trace as T


def value(ctx: dict):
    red = ctx["trace"]
    work = ctx["work"].get("request_replays")
    if not red or not work:
        return None
    kern = T.kernel_s(red, "replay")
    if kern <= 0.0:
        return None
    return kern * 1e9 / (work * red["calls"] / 1e3)
