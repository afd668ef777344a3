"""Jit'd wrappers for the doc_score kernels over the quantized scoring operands.

Applies the per-block dequant scales (kernels are scale-free) and clamps block ids;
callers mask padded/ineligible blocks downstream (repro.core.scoring.score_blocks).
A kernel call scalar-prefetches the [Q, S] block ids into SMEM (1 MiB on a v5e), so a
batch whose ids exceed ``SMEM_BLOCK_IDS`` runs as consecutive calls over query groups.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.index.layout import FlatDocsQ, FwdDocsQ
from repro.kernels.doc_score.kernel import doc_score_flat_pallas, doc_score_fwd_pallas

SMEM_BLOCK_IDS = 1 << 17  # block ids one kernel call prefetches: 512 KiB of int32


def per_query_groups(kernel, operands, q_tids, q_ws, blk_c, interpret, budget=SMEM_BLOCK_IDS):
    """``kernel(*operands, q_tids, q_ws, blk_c, interpret)`` over groups of queries
    whose block ids fit ``budget`` (one call when the whole batch does). Rows are
    independent, so the result is the one-call result."""
    q, s = blk_c.shape
    rows = max(1, budget // s)
    if rows >= q:
        return kernel(*operands, q_tids, q_ws, blk_c, interpret)
    n = -(-q // rows)

    def group(x):
        x = jnp.pad(x, [(0, n * rows - q)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape(n, rows, *x.shape[1:])

    out = jax.lax.map(
        lambda a: kernel(*operands, *a, interpret), (group(q_tids), group(q_ws), group(blk_c))
    )
    return out.reshape(n * rows, *out.shape[2:])[:q]


@partial(jax.jit, static_argnames=("interpret",))
def _call_fwd(tids3, ws3, scales, q_tids, q_ws, blk_ids, interpret):
    blk_c = jnp.clip(blk_ids, 0, tids3.shape[0] - 1).astype(jnp.int32)
    raw = per_query_groups(
        doc_score_fwd_pallas, (tids3, ws3),
        q_tids.astype(jnp.int32), q_ws.astype(jnp.float32), blk_c, interpret,
    )
    return raw * scales[blk_c][:, :, None]


def doc_score_fwd_op(fwdq: FwdDocsQ, q_tids, q_ws, blk_ids, interpret: bool = False) -> jnp.ndarray:
    """[Q, S] selected blocks of the query (q_tids, q_ws) -> scaled scores float32 [Q, S, b]."""
    return _call_fwd(fwdq.tids, fwdq.ws, fwdq.scales, q_tids, q_ws, blk_ids, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _call_flat(tids, ws, doc_ends, scales, q_tids, q_ws, blk_ids, interpret):
    blk_c = jnp.clip(blk_ids, 0, tids.shape[0] - 1).astype(jnp.int32)
    raw = per_query_groups(
        doc_score_flat_pallas, (tids, ws, doc_ends),
        q_tids.astype(jnp.int32), q_ws.astype(jnp.float32), blk_c, interpret,
    )
    return raw * scales[blk_c][:, :, None]


def doc_score_flat_op(flatq: FlatDocsQ, q_tids, q_ws, blk_ids, interpret: bool = False) -> jnp.ndarray:
    """[Q, S] selected blocks of the query (q_tids, q_ws) -> scaled scores float32 [Q, S, b]."""
    return _call_flat(
        flatq.tids, flatq.ws, flatq.doc_ends, flatq.scales, q_tids, q_ws, blk_ids, interpret
    )
