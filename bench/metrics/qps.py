"""qps: requests answered inside the window over the window's seconds."""


def read(ctx):
    w = ctx.window
    ok = [r is not None for r in w.responses]
    return float((w.finished_in_window() & ok).sum()) / w.seconds
