"""prep_ms_per_call.replay (ms): per entry call, the self time of the
program's `sim.prep` spans: the host packing of the request streams
(SimSpec's split of a synthesized batch into streams, SimEngine's
stream packing, FR-FCFS buffer bound and reorder plan), the host's wait
for the synthesis included (program spans, `repro.core.spans`, summed
in the run's process over the traced window)."""

NAMES = ("sim.prep",)
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
