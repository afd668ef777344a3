"""Fused document scoring for selected blocks (Pallas TPU) — the phase-3 hot path.

out[q, s, j] = sum_t qdense[q, tids[blk[q,s], j, t]] * ws[blk[q,s], j, t]

The selected block ids and the query's (term id, weight) lists are scalar-prefetched
(PrefetchScalarGridSpec, the same random-access idiom as boundsum_gather): each grid
step DMAs exactly one block's quantized forward rows — a [b, t_pad] tile (fwd) — or the
aligned row group holding its [m] postings segment (flat), dequantizes the uint8/uint16
weights in-register (through int32: the TPU has no unsigned->float cast), and looks
the query up at the block's term ids. The TPU has no vector gather from a
vocabulary-wide row, so the lookup is a match against the query's nq terms:
qv[x] = sum_i qw[i] * [tids[x] == qt[i]], which equals qdense[tids[x]] exactly for a
query with distinct terms. The [Q, S*b, T] gather tensor of the jnp path is never
materialized: per-step VMEM is one block tile.

Grid: (Q, S). Each step writes its b scores as one lane of a [b, 128] output tile
(the output is [Q, b, S] in HBM, transposed back by the wrapper), so consecutive S
steps share a tile and the S axis is "arbitrary". Scales are per-block and applied by
the ops.py wrapper (kernels stay scale-free, like the bound kernels).

Padded term slots carry the sentinel term id (== vocab), which no weighted query term
carries (padded query slots have weight 0), so they contribute nothing without an
explicit mask.

Dead slots: a block id ``-1 - id`` (negative) marks a slot whose scores the caller
discards. Its step names block ``id`` (``live_block``) for the DMA and skips the term
loop and the lane store, so its raw score is exactly 0. The ops.py wrapper sets ``id``
to the nearest live block before the slot: consecutive dead steps then name the block
already in VMEM, and the pipeline copies nothing for them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sbmax.kernel import ROWS, tile_row

LANES = 128  # output tile width: one lane per selected block


def _query_values(tids, qt_ref, qw_ref, q, nq: int):
    """The dense query row at ``tids``: sum of the weights of matching query terms."""

    def body(i, qv):
        return qv + jnp.where(tids == qt_ref[q, i], qw_ref[q, i], 0.0)

    return jax.lax.fori_loop(0, nq, body, jnp.zeros(tids.shape, jnp.float32))


def live_block(blk):
    """The block a (possibly dead, ``-1 - id``) slot's id names: ``id`` either way."""
    return jnp.maximum(blk, -1 - blk)


def _store_lane(out_ref, scores, s, live):
    """Write the (b, 1) column ``scores()`` of step ``s`` into lane s % LANES of the
    output tile; a dead step computes nothing and leaves its lane at 0."""
    lane = s % LANES

    @pl.when(lane == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _store():
        hit = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1) == lane
        out_ref[0] = jnp.where(hit, scores(), out_ref[0])


def _out_shape(q: int, b: int, s: int):
    return jax.ShapeDtypeStruct((q, b, -(-s // LANES) * LANES), jnp.float32)


def _fwd_kernel(blk_ref, qt_ref, qw_ref, tids_ref, ws_ref, out_ref, *, nq: int):
    q, s = pl.program_id(0), pl.program_id(1)

    def scores():
        tids = tids_ref[0]  # [b, T] int32
        w = ws_ref[0].astype(jnp.int32).astype(jnp.float32)  # [b, T] dequant (scale outside)
        qv = _query_values(tids, qt_ref, qw_ref, q, nq)
        return jnp.sum(qv * w, axis=-1, keepdims=True)

    _store_lane(out_ref, scores, s, blk_ref[q, s] >= 0)


def doc_score_fwd_pallas(
    tids3: jnp.ndarray,  # int32 [NB, b, T]
    ws3: jnp.ndarray,  # uint8/uint16 [NB, b, T]
    q_tids: jnp.ndarray,  # int32 [Q, nq] query term ids (sentinel == vocab)
    q_ws: jnp.ndarray,  # float32 [Q, nq] query weights (0 at sentinels)
    blk_ids: jnp.ndarray,  # int32 [Q, S] pre-clamped to [0, NB); dead slots -1 - id
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns float32 [Q, S, b] raw (unscaled) per-document scores (0 at dead slots)."""
    _, b, t = tids3.shape
    q, s = blk_ids.shape
    nq = q_tids.shape[1]
    tile = lambda qi, si, blk, qt, qw: (live_block(blk[qi, si]), 0, 0)  # noqa: E731

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, nq=nq),
        name="doc_score_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(q, s),
            in_specs=[pl.BlockSpec((1, b, t), tile), pl.BlockSpec((1, b, t), tile)],
            out_specs=pl.BlockSpec((1, b, LANES), lambda qi, si, *_: (qi, 0, si // LANES)),
        ),
        out_shape=_out_shape(q, b, s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(blk_ids, q_tids, q_ws, tids3, ws3)
    return out[:, :, :s].transpose(0, 2, 1)


def _flat_kernel(blk_ref, qt_ref, qw_ref, tids_ref, ws_ref, ends_ref, out_ref, *, nq: int):
    q, s = pl.program_id(0), pl.program_id(1)

    def scores():
        r = blk_ref[q, s] % ROWS  # a live slot's id is its block
        tids = tile_row(tids_ref[...], r)  # [1, m] int32
        w = tile_row(ws_ref[...].astype(jnp.int32), r).astype(jnp.float32)  # [1, m]
        ends_row = tile_row(ends_ref[...], r)  # [1, b] run ends (sorted by local doc id)
        contrib = _query_values(tids, qt_ref, qw_ref, q, nq) * w  # [1, m]
        # doc j's run is [ends[j-1], ends[j]): lay the ends out down the sublanes
        b, m = ends_row.shape[1], tids.shape[1]
        sub = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
        ends = jnp.sum(jnp.where(sub == lane, ends_row, 0), axis=1, keepdims=True)  # [b, 1]
        starts = jnp.sum(jnp.where(sub == lane + 1, ends_row, 0), axis=1, keepdims=True)
        pos = jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
        run = (pos >= starts) & (pos < ends)  # [b, m] doc-run masks
        return jnp.sum(jnp.where(run, contrib, 0.0), axis=1, keepdims=True)

    _store_lane(out_ref, scores, s, blk_ref[q, s] >= 0)


def doc_score_flat_pallas(
    tids: jnp.ndarray,  # int32 [NB, m]
    ws: jnp.ndarray,  # uint8/uint16 [NB, m]
    doc_ends: jnp.ndarray,  # int32 [NB, b]
    q_tids: jnp.ndarray,  # int32 [Q, nq]
    q_ws: jnp.ndarray,  # float32 [Q, nq]
    blk_ids: jnp.ndarray,  # int32 [Q, S] pre-clamped; dead slots -1 - id
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns float32 [Q, S, b] raw (unscaled) per-document scores (0 at dead slots)."""
    _, m = tids.shape
    b = doc_ends.shape[1]
    q, s = blk_ids.shape
    nq = q_tids.shape[1]
    group = lambda qi, si, blk, qt, qw: (live_block(blk[qi, si]) // ROWS, 0)  # noqa: E731

    out = pl.pallas_call(
        functools.partial(_flat_kernel, nq=nq),
        name="doc_score_flat",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(q, s),
            in_specs=[
                pl.BlockSpec((ROWS, m), group),
                pl.BlockSpec((ROWS, m), group),
                pl.BlockSpec((ROWS, b), group),
            ],
            out_specs=pl.BlockSpec((1, b, LANES), lambda qi, si, *_: (qi, 0, si // LANES)),
        ),
        out_shape=_out_shape(q, b, s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(blk_ids, q_tids, q_ws, tids, ws, doc_ends)
    return out[:, :, :s].transpose(0, 2, 1)
