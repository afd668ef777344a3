"""Random-access block BoundSum for selected superblocks (Pallas TPU).

out[q, s, b] = sum_i ws[q, i] * unpack(packed3[tids[q, i], sel[q, s], :])[b]

packed3 is the block-level max-weight matrix viewed [V, NS, cw]: superblock granules of
cw = c*bits/32 words, the word-aligned random-access unit that the paper's
selectors-first SIMDBP-256* layout provides on CPU. A TPU block must be
(8, 128)-aligned, so each grid step DMAs the aligned (ROWS, L) word tile that holds the
granule (L = 128 lanes, or the whole row when it is narrower or not lane-aligned),
picks the term's row in-register and accumulates its unpacked (vpw, L) tile. The
wrapper then reads the granule's cw lanes out of that tile. The DMA pipeline hides the
latency across the (Q, S, nq) grid; Q and S are parallel dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sbmax.kernel import ROWS, unpack_tile_row

LANES = 128


def _kernel(tids_ref, ws_ref, sel_ref, packed_ref, out_ref, *, bits: int):
    q = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = ws_ref[q, i]

    @pl.when(w != 0.0)
    def _acc():
        vals = unpack_tile_row(packed_ref[...], tids_ref[q, i] % ROWS, bits)
        out_ref[0, 0] += w * vals.astype(jnp.float32)


def boundsum_gather_pallas(
    packed: jnp.ndarray,  # uint32 [V, NS * cw] block-level matrix, granule cw
    c: int,
    bits: int,
    tids: jnp.ndarray,  # int32 [Q, nq] pre-clamped
    ws: jnp.ndarray,  # float32 [Q, nq]
    sel_sb: jnp.ndarray,  # int32 [Q, S] selected superblock ids
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns float32 [Q, S, c] unscaled block bound sums."""
    cw = c * bits // 32
    vpw = 32 // bits
    w_words = packed.shape[1]
    lanes = LANES if w_words % LANES == 0 else w_words
    assert lanes % cw == 0, f"granule {cw} words straddles a {lanes}-word tile"
    q, nq = tids.shape
    s = sel_sb.shape[1]

    tiles = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        name="boundsum_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(q, s, nq),
            in_specs=[
                pl.BlockSpec(
                    (ROWS, lanes),
                    lambda qi, si, i, tids_ref, ws_ref, sel_ref: (
                        tids_ref[qi, i] // ROWS,
                        sel_ref[qi, si] * cw // lanes,
                    ),
                ),
            ],
            out_specs=pl.BlockSpec((1, 1, vpw, lanes), lambda qi, si, i, *_: (qi, si, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((q, s, vpw, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tids, ws, sel_sb, packed)
    # the granule's cw lanes of each tile, in value order j*cw + w'
    lane = (sel_sb * cw % lanes)[:, :, None, None] + jnp.arange(cw)[None, None, None, :]
    gran = jnp.take_along_axis(tiles, jnp.broadcast_to(lane, (q, s, vpw, cw)), axis=3)
    return gran.reshape(q, s, vpw * cw)
