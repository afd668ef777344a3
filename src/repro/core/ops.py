"""Dispatch layer: Pallas kernels on TPU, pure-jnp reference elsewhere.

impl:
  "auto"      compiled Pallas kernels on TPU, ref otherwise
  "ref"       always pure jnp
  "kernel"    always Pallas: compiled on TPU, interpret=True elsewhere (how the CPU
              tests check kernel <-> ref parity)

On a TPU, "auto" and "kernel" both run the compiled kernels; nothing falls back to
interpret mode or to ref there. ``kernel_mode`` is the one place that decides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bounds
from repro.core.query import QueryBatch, scatter_dense
from repro.index.layout import PackedBounds


def kernel_mode(impl: str) -> str:
    """How the ops run under ``impl`` on the default backend: "compiled" (Pallas on
    the TPU), "interpret" (Pallas interpreted off the TPU) or "ref" (pure jnp)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu):
        return "ref"
    return "compiled" if on_tpu else "interpret"


def sbmax(pb: PackedBounds, tids: jnp.ndarray, ws: jnp.ndarray, impl: str = "auto") -> jnp.ndarray:
    mode = kernel_mode(impl)
    if mode == "ref":
        return bounds.bound_scores(pb, tids, ws)
    from repro.kernels.sbmax.ops import sbmax_op

    return sbmax_op(pb, tids, ws, interpret=mode == "interpret")


def gathered_block_bounds(
    pb: PackedBounds,
    c: int,
    tids: jnp.ndarray,
    ws: jnp.ndarray,
    sel_sb: jnp.ndarray,
    impl: str = "auto",
) -> jnp.ndarray:
    mode = kernel_mode(impl)
    if mode == "ref":
        return bounds.gathered_block_bounds(pb, c, tids, ws, sel_sb)
    from repro.kernels.boundsum_gather.ops import boundsum_gather_op

    return boundsum_gather_op(pb, c, tids, ws, sel_sb, interpret=mode == "interpret")


def score_gather(
    index,
    qb: QueryBatch,
    blk_ids: jnp.ndarray,
    layout: str = "fwd",
    impl: str = "auto",
    blk_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-document scores of the selected blocks: [Q, S] block ids -> [Q, S, b].

    The single dispatch point for document scoring (round-0 superblock expansion and
    phase-3 block scoring both route here). ``qb`` is the full (unpruned) query.
    Scores carry the per-block dequant scales; padded/ineligible blocks are NOT
    masked here — that is score_blocks' job. The kernels skip the slots where
    ``blk_mask`` [Q, S] is false (they score 0 there); the reference ignores it.
    """
    operand = index.docs_flatq if layout == "flat" else index.docs_fwdq
    assert operand is not None, (
        f"index has no quantized '{layout}' scoring operand (build_flat_inv off?)"
    )
    mode = kernel_mode(impl)
    if mode == "ref":
        from repro.kernels.doc_score import ref as ds_ref

        qdense = scatter_dense(qb)
        blk_c = jnp.clip(blk_ids, 0, index.n_blocks - 1)
        raw = (
            ds_ref.doc_score_flat_ref(operand, qdense, blk_c)
            if layout == "flat"
            else ds_ref.doc_score_fwd_ref(operand, qdense, blk_c)
        )
        return raw * operand.scales[blk_c][:, :, None]
    from repro.kernels.doc_score.ops import doc_score_flat_op, doc_score_fwd_op

    op = doc_score_flat_op if layout == "flat" else doc_score_fwd_op
    return op(operand, qb.tids, qb.ws, blk_ids, interpret=mode == "interpret", blk_mask=blk_mask)
