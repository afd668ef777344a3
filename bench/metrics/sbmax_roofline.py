"""sbmax_roofline: least time of the phase-1 superblock bounds the window's
device-scored requests needed (``bench.costs.sbmax_work``) over the time of the
``sbmax`` kernel in the trace, in %."""

from bench.costs import sbmax_work
from bench.peaks import least_seconds


def read(ctx):
    t = ctx.kernel_seconds(["sbmax"])
    served = ctx.served_in_window()
    if not t or not served:
        return None
    ops, nbytes = sbmax_work([ctx.n_terms[i] for i, _ in served], ctx.config["query"]["beta"],
                             ctx.index_meta["n_superblocks"], ctx.config["index"]["bound_bits"])
    return 100.0 * least_seconds(ops, nbytes, ctx.device_kind) / t
