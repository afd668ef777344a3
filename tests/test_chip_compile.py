"""Compile the retrieval path for a TPU v5e without one (ahead-of-time, at the
widths chip_smoke.py serves): each main-path Pallas kernel, and the whole
jit_search step. Interpret mode cannot see what the TPU lowering refuses
(unaligned blocks, unsupported casts, in-kernel gathers); these compiles do.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.index.pack import SEG_WORDS, align_up

# chip_smoke.py's deployment: BERT wordpiece vocabulary, 524,288 docs, the paper's
# k=10 index geometry (b=16, c=16, 4-bit bounds, 8-bit docs, 128-lane doc rows)
VOCAB, N_DOCS, B, C, BITS, T_PAD, M = 30522, 524_288, 16, 16, 4, 128, 1024
Q, NQ, K = 8, 64, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sds_index(sharding, n_docs: int = N_DOCS):
    """An LSPIndex of shapes: what build_index makes at the smoke's geometry."""
    from repro.index.layout import FlatDocsQ, FwdDocsQ, LSPIndex, PackedBounds

    sd = lambda shape, dt: _sds(sharding, shape, dt)  # noqa: E731
    vpw = 32 // BITS
    nb = align_up(n_docs, B * C) // B
    ns = nb // C
    cw = C * BITS // 32

    def packed(n, granule):
        words = align_up(n, granule * vpw) // vpw
        return PackedBounds(sd((VOCAB, words), jnp.uint32), BITS, sd((VOCAB,), jnp.float32), n, granule)

    return LSPIndex(
        b=B, c=C, n_docs=n_docs, vocab=VOCAB, n_blocks=nb, n_superblocks=ns,
        sb_bounds=packed(ns, SEG_WORDS),
        blk_bounds=packed(nb, cw),
        sb_avg=packed(ns, SEG_WORDS),
        docs_fwd=None,
        docs_flat=None,
        doc_remap=sd((nb * B,), jnp.int32),
        docs_fwdq=FwdDocsQ(
            sd((nb, B, T_PAD), jnp.int32), sd((nb, B, T_PAD), jnp.uint8),
            sd((nb,), jnp.float32), 8, T_PAD,
        ),
        docs_flatq=FlatDocsQ(
            sd((nb, M), jnp.int32), sd((nb, M), jnp.uint8), sd((nb, B), jnp.int32),
            sd((nb,), jnp.float32), 8, M,
        ),
    )


def _kernel_call(name, sharding):
    """(fn, shape args) of one kernel at the smoke's widths and k=10 budgets.
    "doc_score_fwd_64q" is the ops wrapper at 64 queries, whose block ids outgrow
    SMEM and run over query groups; the "_masked" wrappers take phase 3's block
    mask and skip its dead slots."""
    from repro.kernels.boundsum_gather.kernel import boundsum_gather_pallas
    from repro.kernels.doc_score.kernel import doc_score_flat_pallas, doc_score_fwd_pallas
    from repro.kernels.doc_score.ops import doc_score_flat_op, doc_score_fwd_op
    from repro.kernels.sbmax.kernel import sbmax_pallas

    ix = _sds_index(sharding)
    s = lambda shape, dt: _sds(sharding, shape, dt)  # noqa: E731
    q_terms = (s((Q, NQ), jnp.int32), s((Q, NQ), jnp.float32))
    budget, scored = 250, 250 * C  # γ=250 candidate superblocks, their blocks
    fq, flq = ix.docs_fwdq, ix.docs_flatq
    return {
        "sbmax": (lambda p, t, w: sbmax_pallas(p, t, w, BITS), (ix.sb_bounds.packed, *q_terms)),
        "boundsum_gather": (
            lambda p, t, w, sel: boundsum_gather_pallas(p, C, BITS, t, w, sel),
            (ix.blk_bounds.packed, *q_terms, s((Q, budget), jnp.int32)),
        ),
        "doc_score_fwd": (
            doc_score_fwd_pallas, (fq.tids, fq.ws, *q_terms, s((Q, scored), jnp.int32))
        ),
        "doc_score_flat": (
            doc_score_flat_pallas,
            (flq.tids, flq.ws, flq.doc_ends, *q_terms, s((Q, scored), jnp.int32)),
        ),
        "doc_score_fwd_64q": (
            lambda fq, t, w, blk: doc_score_fwd_op(fq, t, w, blk),
            (fq, s((64, NQ), jnp.int32), s((64, NQ), jnp.float32), s((64, scored), jnp.int32)),
        ),
        "doc_score_fwd_masked": (
            lambda fq, t, w, blk, m: doc_score_fwd_op(fq, t, w, blk, blk_mask=m),
            (fq, *q_terms, s((Q, scored), jnp.int32), s((Q, scored), jnp.bool_)),
        ),
        "doc_score_flat_masked": (
            lambda flq, t, w, blk, m: doc_score_flat_op(flq, t, w, blk, blk_mask=m),
            (flq, *q_terms, s((Q, scored), jnp.int32), s((Q, scored), jnp.bool_)),
        ),
    }[name]


@pytest.mark.parametrize(
    "name",
    [
        "sbmax", "boundsum_gather", "doc_score_fwd", "doc_score_flat", "doc_score_fwd_64q",
        "doc_score_fwd_masked", "doc_score_flat_masked",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_call(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _query_shapes(sharding):
    return (
        _sds(sharding, (Q, NQ), jnp.int32),
        _sds(sharding, (Q, NQ), jnp.float32),
        _sds(sharding, (Q,), jnp.int32),
        *(_sds(sharding, (Q,), jnp.float32) for _ in range(3)),
    )


@pytest.mark.parametrize("layout", ["fwd", "flat"])
def test_search_step_compiles_for_v5e(one_chip, monkeypatch, layout):
    """The whole served program (one bucket) at the smoke's size, with the
    compiled kernels the TPU dispatch picks (this process sees a CPU backend, so
    the test steers the dispatch to what a TPU process would take)."""
    from repro.core import ops
    from repro.core.config import StaticConfig
    from repro.core.lsp import jit_search

    monkeypatch.setattr(ops, "kernel_mode", lambda impl: "compiled")
    scfg = StaticConfig(variant="lsp0", gamma=250, gamma0=32, k_max=K, doc_layout=layout)
    run = jit_search(_sds_index(one_chip), scfg)
    lowered = run.lower(*_query_shapes(one_chip))
    text = lowered.as_text()
    scorer = "doc_score_flat" if layout == "flat" else "doc_score_fwd"
    for kernel in ("sbmax", "boundsum_gather", scorer):
        assert kernel in text, f"{kernel} kernel missing from the served program"
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30  # the step's scratch, beside a ~1 GB index


def test_lowered_search_does_not_grow_with_corpus():
    """The index rides as arguments: the served program's text is the same size
    for a corpus 8x larger (a closed-over index would be embedded as constants)."""
    from repro.core.config import StaticConfig
    from repro.core.lsp import jit_search
    from repro.core.query import make_query_batch
    from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
    from repro.index.builder import IndexBuildConfig, build_index

    sizes = {}
    for n_docs in (2048, 16384):
        cfg = CorpusConfig(n_docs=n_docs, vocab=512, n_topics=8, seed=0)
        corpus = make_corpus(cfg)
        index = build_index(
            corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab,
            IndexBuildConfig(b=8, c=8, kmeans_iters=1),
        )
        run = jit_search(index, StaticConfig(variant="lsp0", gamma=8, gamma0=2, k_max=10))
        qb = make_query_batch(make_queries(cfg, corpus, 4), corpus.vocab, nq_max=32)
        d = np.ones(4, np.float32)
        sizes[n_docs] = len(
            run.lower(qb.tids, qb.ws, np.full(4, 10, np.int32), d, d, d).as_text()
        )
    assert sizes[16384] < 1.05 * sizes[2048], sizes
