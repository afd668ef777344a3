"""recall_at_k: share of the exact top-k (``bench.reference``) that the served answers
hold, over the sample of answers compared after the window."""


def read(ctx):
    return ctx.recall
