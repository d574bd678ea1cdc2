"""margin_mevals_per_s (Meval/s): margin evaluations of every profile
completed in the window over the window's wall seconds, on the host
clock."""


def value(ctx: dict):
    work = ctx["work"].get("margin_evals")
    if work is None or not ctx["window_s"] > 0:
        return None
    return work * ctx["calls"] / ctx["window_s"] / 1e6
