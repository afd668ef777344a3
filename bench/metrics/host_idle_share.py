"""host_idle_share: share of the traced window in which no operation ran on the
device while the engine's worker did host work on a batch (its ``serve.pad``,
``serve.dispatch`` and ``serve.resolve`` spans), in % (``bench.spans``). Nothing
where the program has no such span."""

from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.idle_share(ctx.trace, *ctx.trace_span, spans.HOST)
