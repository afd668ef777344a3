"""SBMax / BoundSum Pallas TPU kernel (paper Eq. 1; the SIMD BoundSum hot spot).

out[q, n] = sum_i ws[q, i] * unpack(packed[tids[q, i], :])[n]

The packed matrix uses the lane-strided segment layout (repro.index.pack): a term's
row is cut into TW-word segments, each unpacking into a full (vpw, TW=128) VREG tile
with a vectorized shift — value order matches the output tile with no transpose.
Query term rows are gathered through scalar-prefetched term ids
(PrefetchScalarGridSpec index_map), the TPU analogue of the random-access row fetch
the paper's hoisted selectors enable on CPU. A TPU block must be (8, 128)-aligned, so
each grid step DMAs the aligned (ROWS, TW) tile holding the term's row and picks the
row out in-register with a sublane mask (one tile per step; the other rows are not
used).

Grid: (Q, n_seg, nq) — nq innermost and marked "arbitrary" so consecutive steps
accumulate into the same output window (standard reduction pattern); Q and segments
are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TW = 128  # word-tile width == pack.SEG_WORDS == lane count
ROWS = 8  # sublanes of one 32-bit tile: the aligned row group a step loads


def tile_row(x: jnp.ndarray, r) -> jnp.ndarray:
    """Row ``r`` of an int32 (ROWS, L) tile as (1, L): a sublane mask and an exact
    integer sum, since a block loads whole aligned row groups."""
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(sub == r, x, 0), axis=0, keepdims=True)


def unpack_tile_row(words: jnp.ndarray, r, bits: int) -> jnp.ndarray:
    """Row ``r`` of a uint32 (ROWS, L) word tile, unpacked to int32 (vpw, L).

    The unpack runs in int32 with logical shifts: the TPU has no unsigned->float
    cast.
    """
    row = tile_row(pltpu.bitcast(words, jnp.int32), r)  # (1, L)
    vpw = 32 // bits
    shifts = jax.lax.broadcasted_iota(jnp.int32, (vpw, row.shape[1]), 0) * bits
    return jax.lax.shift_right_logical(row, shifts) & ((1 << bits) - 1)


def _kernel(tids_ref, ws_ref, packed_ref, out_ref, *, bits: int):
    i = pl.program_id(2)  # query-term index (reduction dim)
    q = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = ws_ref[q, i]

    @pl.when(w != 0.0)
    def _acc():
        vals = unpack_tile_row(packed_ref[...], tids_ref[q, i] % ROWS, bits)
        out_ref[0, 0] += w * vals.astype(jnp.float32)


def sbmax_pallas(
    packed: jnp.ndarray,  # uint32 [V, W]  (W % TW == 0)
    tids: jnp.ndarray,  # int32 [Q, nq]  (pre-clamped to < V)
    ws: jnp.ndarray,  # float32 [Q, nq] (0 for padded terms)
    bits: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns float32 [Q, W * vpw] of *unscaled* quantized bound sums."""
    v, w_words = packed.shape
    assert w_words % TW == 0, f"packed width {w_words} not a multiple of {TW}"
    n_seg = w_words // TW
    q, nq = tids.shape
    vpw = 32 // bits

    grid = (q, n_seg, nq)
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        name="sbmax",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (ROWS, TW), lambda qi, s, i, tids_ref, ws_ref: (tids_ref[qi, i] // ROWS, s)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, vpw, TW), lambda qi, s, i, *_: (qi, s, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((q, n_seg, vpw, TW), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tids, ws, packed)
    return out.reshape(q, n_seg * vpw * TW)
