"""controller_ms_per_call.replay (ms): per entry call, the self time of
the program's `aldram.evaluate_system` and `aldram.evaluate_dynamic`
spans: the controller's own host work around the replay (table
lookups, timing rows, CPI speedups and summaries), its nested stages
left out (program spans, `repro.core.spans`, summed in the run's
process over the traced window)."""

NAMES = ("aldram.evaluate_system", "aldram.evaluate_dynamic")
SCALE = 1e3


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
