"""Offline index construction: CSR corpus -> LSPIndex.

Host-side (numpy) by design: index building is an offline batch job; the built index is
a device pytree consumed by the online retrieval pipeline (repro/core/lsp.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.index import clustering
from repro.index.layout import FlatDocsQ, FlatInv, FwdDocs, FwdDocsQ, LSPIndex, PackedBounds
from repro.index.pack import SEG_WORDS, align_up, pack_rows_strided
from repro.index.quantize import (
    quantize_bounds,
    quantize_bounds_per_row,
    quantize_weights,
    quantize_weights_per_block,
)


@dataclass(frozen=True)
class IndexBuildConfig:
    b: int = 8  # docs per block
    c: int = 16  # blocks per superblock
    bound_bits: int = 4  # block/superblock max-weight quantization (paper: 4)
    doc_bits: int = 8  # document weight quantization (paper follows BMP: 8)
    # "row" = per-term scales (beyond-paper: recovers 8-bit ranking quality at 4-bit
    # storage, scales fold into query weights); "global" = paper-literal single scale
    quant_granularity: str = "row"
    build_flat_inv: bool = True
    build_avg: bool = True  # superblock averages (needed by SP and LSP/2 only)
    # lane alignment of the quantized scoring operands (FwdDocsQ.t_pad / FlatDocsQ.m).
    # 8 keeps host gathers compact (CPU ref path); 128 gives the TPU kernels full lane
    # tiles (configs.lsp_msmarco's deployment configs set it).
    lane_pad: int = 8
    d_proj: int = 64
    kmeans_iters: int = 8
    seed: int = 0

    def __post_init__(self):
        assert (self.c * self.bound_bits) % 32 == 0, (
            "superblock gather granule must be word-aligned: c*bound_bits % 32 == 0"
        )


def build_index(
    doc_ptr: np.ndarray,
    tids: np.ndarray,
    ws: np.ndarray,
    vocab: int,
    cfg: IndexBuildConfig,
) -> LSPIndex:
    n_docs = len(doc_ptr) - 1
    b, c = cfg.b, cfg.c

    remap = clustering.block_order(
        doc_ptr, tids, ws, vocab, b, c, cfg.d_proj, cfg.kmeans_iters, cfg.seed
    )  # position -> original doc id (padded entries == n_docs)
    n_pad = len(remap)
    n_blocks = n_pad // b
    n_superblocks = n_blocks // c

    # position of each original doc
    pos_of = np.full(n_docs + 1, -1, np.int64)
    pos_of[remap] = np.arange(n_pad)

    doc_of_posting = np.repeat(np.arange(n_docs), np.diff(doc_ptr))
    post_pos = pos_of[doc_of_posting]  # position of the posting's doc
    post_blk = post_pos // b

    # ---- block max / superblock max & avg term-weight matrices (dense, term-major)
    blk_max = np.zeros((vocab, n_blocks), np.float32)
    np.maximum.at(blk_max, (tids, post_blk), ws)
    sb_max = blk_max.reshape(vocab, n_superblocks, c).max(axis=2)

    # superblock-level matrices pack at the kernel's row-tile granule; the block-level
    # matrix packs at one-superblock granules (cw words) for random-access gathers.
    cw = c * cfg.bound_bits // 32

    def qbounds(w):
        if cfg.quant_granularity == "row":
            q, s = quantize_bounds_per_row(w, cfg.bound_bits)
            return q, jnp.asarray(s)
        q, s = quantize_bounds(w, cfg.bound_bits)
        return q, s

    sb_avg_pb = None
    if cfg.build_avg:
        # SBavg is the avg-of-block-max (mean over the superblock's c block maxima),
        # exactly what the SP / LSP2 rule's SBavg(X) > θ/η branch expects — NOT the
        # mean posting weight per doc slot, which under-counts multi-doc blocks and
        # silently distorts SP eligibility relative to the paper
        sb_avg = blk_max.reshape(vocab, n_superblocks, c).mean(axis=2)
        q, s = qbounds(sb_avg)
        sb_avg_pb = PackedBounds(
            jnp.asarray(pack_rows_strided(q, cfg.bound_bits, SEG_WORDS)),
            cfg.bound_bits, s, n_superblocks, SEG_WORDS,
        )

    qb, sb_scale = qbounds(sb_max)
    sb_pb = PackedBounds(
        jnp.asarray(pack_rows_strided(qb, cfg.bound_bits, SEG_WORDS)),
        cfg.bound_bits, sb_scale, n_superblocks, SEG_WORDS,
    )
    qk, blk_scale = qbounds(blk_max)
    blk_pb = PackedBounds(
        jnp.asarray(pack_rows_strided(qk, cfg.bound_bits, cw)),
        cfg.bound_bits, blk_scale, n_blocks, cw,
    )

    # ---- forward document index (block-ordered, padded term lists)
    lengths = np.diff(doc_ptr)
    t_max = int(lengths.max()) if n_docs else 1
    t_max = max(8, -(-t_max // 8) * 8)  # pad to lane-friendly multiple of 8
    fw_tids = np.full((n_pad, t_max), vocab, np.int32)
    fw_ws = np.zeros((n_pad, t_max), np.uint8)
    qw, doc_scale = quantize_weights(ws, cfg.doc_bits)
    col = (np.arange(len(tids)) - doc_ptr[doc_of_posting]).astype(np.int64)
    fw_tids[post_pos, col] = tids
    fw_ws[post_pos, col] = qw
    docs_fwd = FwdDocs(jnp.asarray(fw_tids), jnp.asarray(fw_ws), doc_scale, t_max)

    # ---- quantized block-major forward index (doc_score operand, per-block scales)
    qw_blk, blk_scales = quantize_weights_per_block(ws, post_blk, n_blocks, cfg.doc_bits)
    w_dtype = np.uint8 if cfg.doc_bits <= 8 else np.uint16
    t_pad = align_up(t_max, cfg.lane_pad)
    fq_tids = np.full((n_pad, t_pad), vocab, np.int32)
    fq_ws = np.zeros((n_pad, t_pad), w_dtype)
    fq_tids[post_pos, col] = tids
    fq_ws[post_pos, col] = qw_blk
    docs_fwdq = FwdDocsQ(
        jnp.asarray(fq_tids.reshape(n_blocks, b, t_pad)),
        jnp.asarray(fq_ws.reshape(n_blocks, b, t_pad)),
        jnp.asarray(blk_scales),
        cfg.doc_bits,
        t_pad,
    )

    # ---- flat compact inverted index (postings sorted by (block, local doc, term))
    docs_flat = None
    docs_flatq = None
    if cfg.build_flat_inv:
        order = np.lexsort((tids, post_pos % b, post_blk))
        s_tid = tids[order].astype(np.int32)
        s_did = (post_pos[order] % b).astype(np.int32)
        s_w = qw[order]
        counts = np.bincount(post_blk, minlength=n_blocks)
        block_ptr = np.zeros(n_blocks + 1, np.int64)
        np.cumsum(counts, out=block_ptr[1:])
        max_nnz = int(counts.max()) if n_blocks else 0
        max_nnz = max(8, -(-max_nnz // 8) * 8)
        # pad postings with sentinels so gathers of max_nnz past the end are safe
        pad = max_nnz
        docs_flat = FlatInv(
            jnp.asarray(np.concatenate([s_tid, np.full(pad, vocab, np.int32)])),
            jnp.asarray(np.concatenate([s_did, np.zeros(pad, np.int32)])),
            jnp.asarray(np.concatenate([s_w, np.zeros(pad, np.uint8)])),
            jnp.asarray(block_ptr.astype(np.int32)),
            max_nnz,
            doc_scale,
        )

        # quantized block-major flat segments (doc_score flat operand). Postings are
        # already sorted by local doc id within each block, so per-doc scores are
        # contiguous runs; doc_ends[k, j] = end of doc j's run in block k's segment.
        m = align_up(max_nnz, cfg.lane_pad)
        fl_tids = np.full((n_blocks, m), vocab, np.int32)
        fl_ws = np.zeros((n_blocks, m), w_dtype)
        s_w_blk = qw_blk[order]
        row = post_blk[order]
        off = (np.arange(len(order)) - block_ptr[row]).astype(np.int64)
        fl_tids[row, off] = s_tid
        fl_ws[row, off] = s_w_blk
        # ends of each local-did run: cumulative count of postings with did <= j
        did_counts = np.zeros((n_blocks, b), np.int64)
        np.add.at(did_counts, (row, s_did), 1)
        doc_ends = np.cumsum(did_counts, axis=1).astype(np.int32)
        docs_flatq = FlatDocsQ(
            jnp.asarray(fl_tids),
            jnp.asarray(fl_ws),
            jnp.asarray(doc_ends),
            jnp.asarray(blk_scales),
            cfg.doc_bits,
            m,
        )

    return LSPIndex(
        b=b,
        c=c,
        n_docs=n_docs,
        vocab=vocab,
        n_blocks=n_blocks,
        n_superblocks=n_superblocks,
        sb_bounds=sb_pb,
        blk_bounds=blk_pb,
        sb_avg=sb_avg_pb,
        docs_fwd=docs_fwd,
        docs_flat=docs_flat,
        doc_remap=jnp.asarray(remap),
        docs_fwdq=docs_fwdq,
        docs_flatq=docs_flatq,
    )
