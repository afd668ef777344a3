"""blocks_scored_per_query: mean ``SearchResponse.n_blocks_scored`` (round-0 and
phase-3 blocks) of the requests scored on the device in the window."""


def read(ctx):
    n = [r.n_blocks_scored for _, r in ctx.served_in_window() if r.n_blocks_scored is not None]
    return sum(n) / len(n) if n else None
