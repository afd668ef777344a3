"""Pallas TPU kernels for the paper's compute hot spots.

  sbmax           SIMD BoundSum -> VPU unpack + weighted accumulate over packed
                  superblock (or block) maximum term weights
  boundsum_gather random-access block BoundSum for selected superblocks
                  (the selectors-first random-access decode of SIMDBP-256*)
  dequant_matmul  4-bit dequant GEMM (dense-embedding LSP scoring, MXU)

  doc_score       fused gather + dequant + dot document scoring for selected blocks
                  (phase-3 hot path; quantized forward index, VPU accumulate)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
ref.py (pure-jnp oracle). Off the TPU the tests run them with interpret=True against
the oracles; tests/test_chip_compile.py compiles the retrieval kernels for a described
TPU v5e at real widths, and chip_smoke.py runs them compiled on the chip.
"""

