"""Jit'd wrappers for the doc_score kernels over the quantized scoring operands.

Applies the per-block dequant scales (kernels are scale-free) and clamps block ids;
callers mask padded/ineligible blocks downstream (repro.core.scoring.score_blocks).
Given that mask, the kernels skip its dead slots (``live_ids``): they score 0.
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


def live_ids(blk_c, blk_mask):
    """Clamped block ids [Q, S] with each dead slot (``blk_mask`` false) as ``-1 - id``,
    ``id`` the nearest live block before it in its row (the row's first live block
    before its first live slot; the row's first slot where none is live). Runs of dead
    slots then name one block, which the kernel's pipeline fetches once. ``None``:
    every slot is live."""
    if blk_mask is None:
        return blk_c
    slot = jnp.arange(blk_c.shape[1], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(blk_mask, slot, -1), axis=1)  # last live slot so far
    first = jnp.argmax(blk_mask, axis=1).astype(jnp.int32)[:, None]
    fill = jnp.take_along_axis(blk_c, jnp.where(last < 0, first, last), axis=1)
    return jnp.where(blk_mask, blk_c, -1 - fill)


def _call(kernel, operands, scales, q_tids, q_ws, blk_ids, blk_mask, interpret):
    blk_c = jnp.clip(blk_ids, 0, scales.shape[0] - 1).astype(jnp.int32)
    raw = per_query_groups(
        kernel, operands, q_tids.astype(jnp.int32), q_ws.astype(jnp.float32),
        live_ids(blk_c, blk_mask), interpret,
    )
    return raw * scales[blk_c][:, :, None]


@partial(jax.jit, static_argnames=("interpret",))
def _call_fwd(tids3, ws3, scales, q_tids, q_ws, blk_ids, blk_mask, interpret):
    return _call(doc_score_fwd_pallas, (tids3, ws3), scales, q_tids, q_ws, blk_ids, blk_mask,
                 interpret)


def doc_score_fwd_op(
    fwdq: FwdDocsQ, q_tids, q_ws, blk_ids, interpret: bool = False, blk_mask=None
) -> jnp.ndarray:
    """[Q, S] selected blocks of the query (q_tids, q_ws) -> scaled scores float32 [Q, S, b].
    Slots where the optional bool [Q, S] ``blk_mask`` is false are skipped and score 0."""
    return _call_fwd(fwdq.tids, fwdq.ws, fwdq.scales, q_tids, q_ws, blk_ids, blk_mask, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _call_flat(tids, ws, doc_ends, scales, q_tids, q_ws, blk_ids, blk_mask, interpret):
    return _call(doc_score_flat_pallas, (tids, ws, doc_ends), scales, q_tids, q_ws, blk_ids,
                 blk_mask, interpret)


def doc_score_flat_op(
    flatq: FlatDocsQ, q_tids, q_ws, blk_ids, interpret: bool = False, blk_mask=None
) -> jnp.ndarray:
    """[Q, S] selected blocks of the query (q_tids, q_ws) -> scaled scores float32 [Q, S, b].
    Slots where the optional bool [Q, S] ``blk_mask`` is false are skipped and score 0."""
    return _call_flat(
        flatq.tids, flatq.ws, flatq.doc_ends, flatq.scales, q_tids, q_ws, blk_ids, blk_mask,
        interpret,
    )
