"""margin_fetch_gb_per_s (GB/s): the margin grids' bytes copied to the
host over the self time of the program's `margin.fetch` spans, the
host's wait for the margin kernel included (program spans and their
`bytes` counts, `repro.core.spans`, summed in the run's process over
the traced window)."""


def value(ctx: dict):
    try:
        from repro.core import spans
    except ImportError:                 # a program without spans
        return None
    s = spans.summary()
    if not ctx["trace"] or not s["roots"] or s["roots"] != ctx["calls"]:
        return None
    t = s["spans"].get("margin.fetch")
    if not t or not t.get("bytes") or t["self_s"] <= 0.0:
        return None
    return t["bytes"] / t["self_s"] / 1e9
