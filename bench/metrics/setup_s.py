"""setup_s (s): process start to the end of warm-up, on the host clock."""


def value(ctx: dict):
    return ctx["setup_s"]
