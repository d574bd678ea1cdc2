"""host_s_per_profile (s): per profile, its wall time minus the device
busy time inside it: the MarginEngine host side (device-to-host copy
of the margin grids, envelope reduction, combo selection) while no
device operation runs (device trace and the harness's call spans)."""

import bench_trace as T


def value(ctx: dict):
    return None if not ctx["trace"] else T.host_s_per_call(ctx["trace"])
