"""Document scoring over candidate blocks/positions (fwd and flat layouts).

Scoring uses the FULL query (dense-scattered) — the paper follows Seismic: pruned
query for candidate generation, entire query for scoring (§4.3 "Fwd").

All block scoring routes through ``score_blocks`` -> ``repro.core.ops.score_gather``
(one dispatch with ref/kernel parity over the quantized block-major operands);
``score_positions_fwd`` remains for position-addressed consumers (the exact oracle,
threshold sampling) and reads the same per-block-quantized weights so every path in
the system scores with identical arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.query import QueryBatch
from repro.index.layout import FwdDocsQ, LSPIndex

NEG = -1e30


def score_positions_fwd(
    index: LSPIndex, qdense: jnp.ndarray, pos: jnp.ndarray
) -> jnp.ndarray:
    """Score docs at block-ordered positions. qdense [Q, V+1]; pos [Q, D] -> [Q, D].

    Invalid/padded positions (remap sentinel) score NEG so they never reach top-k.
    """
    fwdq: FwdDocsQ = index.docs_fwdq
    b = index.b
    n_pad = index.doc_remap.shape[0]
    pos_c = jnp.clip(pos, 0, n_pad - 1)
    blk, did = pos_c // b, pos_c % b
    tids = fwdq.tids[blk, did]  # [Q, D, T] int32
    ws = fwdq.ws[blk, did].astype(jnp.float32)  # [Q, D, T]
    qv = jax.vmap(lambda qd, t: qd[t])(qdense, tids)  # [Q, D, T]
    scores = jnp.sum(qv * ws, axis=-1) * fwdq.scales[blk]
    valid = index.doc_remap[pos_c] < index.n_docs
    return jnp.where(valid, scores, NEG)


def score_blocks(
    index: LSPIndex,
    qb: QueryBatch,
    blk_ids: jnp.ndarray,
    blk_mask: jnp.ndarray,
    layout: str = "fwd",
    impl: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Score all docs of selected blocks. blk_ids/blk_mask [Q, S] -> ([Q, S*b], pos).

    Masked blocks and padded docs (remap sentinel) score NEG so they never reach
    top-k; the kernels do no work for masked blocks. One call serves both layouts
    and both impls (ref / Pallas kernel); ``qb`` is the full (unpruned) query.
    """
    b = index.b
    scores = ops.score_gather(index, qb, blk_ids, layout, impl, blk_mask)  # [Q, S, b]
    pos = blk_ids[:, :, None] * b + jnp.arange(b)[None, None, :]  # [Q, S, b]
    n_pad = index.doc_remap.shape[0]
    valid = index.doc_remap[jnp.clip(pos, 0, n_pad - 1)] < index.n_docs
    scores = jnp.where(valid & blk_mask[:, :, None], scores, NEG)
    return scores.reshape(scores.shape[0], -1), pos.reshape(pos.shape[0], -1)
