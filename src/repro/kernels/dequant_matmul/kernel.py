"""4-bit dequant GEMM (Pallas TPU, MXU): scores = x @ dequant(packed_w).

Used by the dense-embedding LSP path (recsys `retrieval_cand`): 1M candidate item
embeddings are quantized to 4 bits, blocked/superblocked, and scored against query
embeddings. The weight matrix is packed along N with the lane-strided segment layout
(granule = SEG_WORDS = 128 words -> one segment = vpw x 128 logical columns), so each
grid step unpacks into vpw full (K_tile, 128) MXU operands — one jnp.dot per bit-lane,
no transpose, fp32 accumulation across the K grid dimension.

Tiling: grid (M/TM, n_seg, K/TK), K innermost (reduction). VMEM per step:
x (TM x TK x 4B) + packed (TK x 128 x 4B) + out (TM x vpw x 128 x 4B) — with the
default TM=128, TK=512, 4-bit: 256KB + 256KB + 512KB, well inside 16MB VMEM with
double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TW = 128  # lane width of a packed word tile (== pack.SEG_WORDS)


def _kernel(x_ref, w_ref, out_ref, *, bits: int):
    k = pl.program_id(2)
    vpw = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]  # [TM, TK] f32/bf16
    packed = w_ref[...]  # [TK, TW] u32
    for j in range(vpw):
        wj = ((packed >> jnp.uint32(j * bits)) & mask).astype(x.dtype)  # [TK, TW]
        out_ref[:, 0, j, :] += jnp.dot(x, wj, preferred_element_type=jnp.float32)


def dequant_matmul_pallas(
    x: jnp.ndarray,  # [M, K] float32/bfloat16
    packed_w: jnp.ndarray,  # uint32 [K, W] (columns packed, granule SEG_WORDS)
    bits: int,
    tm: int = 128,
    tk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns float32 [M, W * vpw] of unscaled scores (caller applies scale)."""
    m, k = x.shape
    k2, w_words = packed_w.shape
    assert k == k2
    assert w_words % TW == 0
    vpw = 32 // bits
    tm = min(tm, m)
    tk = min(tk, k)
    assert m % tm == 0 and k % tk == 0, (m, tm, k, tk)
    n_seg = w_words // TW

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(m // tm, n_seg, k // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda mi, s, ki: (mi, ki)),
            pl.BlockSpec((tk, TW), lambda mi, s, ki: (ki, s)),
        ],
        out_specs=pl.BlockSpec((tm, 1, vpw, TW), lambda mi, s, ki: (mi, s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_seg, vpw, TW), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed_w)
    return out.reshape(m, n_seg * vpw * TW)
