"""Operations and bytes that each kernel's work needs, from the cell's shapes.

These count what the algorithm has to do for the queries that were served, not what
an implementation loads: padded query rows, padded term slots, masked candidate
slots and the aligned 8-row tiles of the bring-up kernels are not counted. A
kernel's roofline share is then the least time of this work (``peaks.least_seconds``)
over the kernel's time in the trace, and cannot pass 100% unless the counts are too
high or the trace misses part of the kernel's time.

  sbmax      per query: each of its ceil(beta * n_terms) pruned terms streams the
             term's row of superblock bounds (n_superblocks values of bound_bits)
             and multiply-adds it into the query's bound row: 2 ops per value.
  doc_score  per scored block (``n_blocks_scored``, rounds 0 and 3): each posting
             of its b documents is read (4-byte term id, doc_bits weight) and its
             weight multiply-added with the query's weight of that term: 2 ops per
             posting; plus the block's float32 scale. Postings per block are the
             corpus's mean (nnz / n_docs * b).
"""

from __future__ import annotations

import math


def sbmax_work(n_terms, beta: float, n_superblocks: int, bound_bits: int) -> tuple:
    """(ops, bytes) of phase-1 SBMax for queries with ``n_terms`` terms each."""
    kept = sum(math.ceil(beta * n) for n in n_terms)
    values = kept * n_superblocks
    return 2.0 * values, values * bound_bits / 8.0


def doc_score_work(n_blocks, b: int, postings_per_doc: float, doc_bits: int) -> tuple:
    """(ops, bytes) of document scoring for ``n_blocks`` scored blocks per query."""
    blocks = float(sum(n_blocks))
    postings = blocks * b * postings_per_doc
    return 2.0 * postings, postings * (4 + doc_bits / 8.0) + 4.0 * blocks
