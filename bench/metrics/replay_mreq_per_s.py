"""replay_mreq_per_s (Mreq/s): simulated request-replays completed in
the window over the window's wall seconds (first call's start to last
call's end), on the host clock."""


def value(ctx: dict):
    work = ctx["work"].get("request_replays")
    if work is None or not ctx["window_s"] > 0:
        return None
    return work * ctx["calls"] / ctx["window_s"] / 1e6
