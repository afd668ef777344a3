"""doc_score_roofline: least time of the document scoring the window's device-scored
requests needed (``bench.costs.doc_score_work``) over the time of the ``doc_score``
kernels in the trace, in %."""

from bench.costs import doc_score_work
from bench.peaks import least_seconds


def read(ctx):
    t = ctx.kernel_seconds(["doc_score_fwd", "doc_score_flat"])
    served = ctx.served_in_window()
    if not t or not served:
        return None
    idx = ctx.config["index"]
    ops, nbytes = doc_score_work([r.n_blocks_scored for _, r in served], idx["b"],
                                 ctx.index_meta["postings_per_doc"], idx["doc_bits"])
    return 100.0 * least_seconds(ops, nbytes, ctx.device_kind) / t
