"""queue_wait_ms: mean time from a request's admission to the start of its batch's
dispatch, over the requests the engine scored on the device in the window, in ms.
From the ``queue_wait_ms_total`` and ``queue_waits`` counters of
``ServeStats.summary()`` read as the window opened and closed; nothing where the
program has no such counters."""


def read(ctx):
    a, b = ctx.stats_before, ctx.stats_after
    if "queue_waits" not in b:
        return None
    n = b["queue_waits"] - a["queue_waits"]
    return (b["queue_wait_ms_total"] - a["queue_wait_ms_total"]) / n if n else None
