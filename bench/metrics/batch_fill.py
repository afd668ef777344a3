"""batch_fill: requests the engine scored on the device in the window over the batch
slots it dispatched (each batch pads to its ladder bucket), in %. From
``ServeStats.summary()`` counters read as the window opened and closed."""


def read(ctx):
    a, b = ctx.stats_before, ctx.stats_after
    slots = 0
    for key, n in b["bucket_batches"].items():
        batch = int(key.split("x")[0])
        slots += batch * (n - a["bucket_batches"].get(key, 0))
    served = (b["requests"] - b["cache_hits"]) - (a["requests"] - a["cache_hits"])
    return 100.0 * served / slots if slots else None
