"""controller_s_per_profile (s): per profile, the self time of the
program's `aldram.profile` span: the controller's host work around the
margin campaigns (the cells' transfer and the kernel launches, the
module groups' views joined, the register tables), its nested stages
left out (program spans, `repro.core.spans`, summed in the run's
process over the traced window)."""

NAMES = ("aldram.profile",)
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
