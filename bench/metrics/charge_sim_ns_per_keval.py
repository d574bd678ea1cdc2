"""charge_sim_ns_per_keval (ns/keval): device time of the charge_sim
margin kernel per 1000 margin evaluations of the traced window (device
trace; the evaluations are counted from the campaign's spec)."""

import bench_trace as T


def value(ctx: dict):
    red = ctx["trace"]
    work = ctx["work"].get("margin_evals")
    if not red or not work:
        return None
    kern = T.kernel_s(red, "charge_sim")
    if kern <= 0.0:
        return None
    return kern * 1e9 / (work * red["calls"] / 1e3)
