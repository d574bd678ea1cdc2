"""campaign_p95_ms (ms): 95th percentile of the wall time of every
entry call of the window (linear interpolation), on the host clock."""

import numpy as np


def value(ctx: dict):
    if not ctx["durations"]:
        return None
    return float(np.percentile(np.asarray(ctx["durations"]), 95.0)) * 1e3
