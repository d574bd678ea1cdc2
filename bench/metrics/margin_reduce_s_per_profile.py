"""margin_reduce_s_per_profile (s): per profile, the self time of the
program's `margin.reduce` spans: the pass envelopes of the refresh and
timing campaigns and the combo selection (program spans,
`repro.core.spans`, summed in the run's process over the traced
window)."""

NAMES = ("margin.reduce",)
SCALE = 1.0


def value(ctx: dict):
    try:
        from repro.core import spans
    except ImportError:                 # a program without spans
        return None
    s = spans.summary()
    if not ctx["trace"] or not s["roots"] or s["roots"] != ctx["calls"]:
        return None
    got = [s["spans"][n]["self_s"] for n in NAMES if n in s["spans"]]
    return sum(got) * SCALE / s["roots"] if got else None
