"""Index-builder integrity: permutation validity + bound matrices vs brute force."""

import numpy as np

from repro.core.bounds import unpack_strided
from repro.index.builder import IndexBuildConfig, build_index
from repro.index.quantize import quantize_bounds_per_row


def test_builder_integrity(tiny_corpus):
    _, corpus, _ = tiny_corpus
    cfg = IndexBuildConfig(b=8, c=8, kmeans_iters=2)
    idx = build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab, cfg)
    n_docs = len(corpus.doc_ptr) - 1

    remap = np.asarray(idx.doc_remap)
    real = remap[remap < n_docs]
    assert len(np.unique(real)) == n_docs, "every doc appears exactly once"
    assert idx.n_blocks * idx.b == len(remap)
    assert idx.n_superblocks * idx.c == idx.n_blocks

    # brute-force block max for a sample of (term, block) pairs
    rng = np.random.default_rng(0)
    blk_unpacked = unpack_strided(
        idx.blk_bounds.packed, idx.blk_bounds.bits, idx.blk_bounds.granule_words
    )
    scale = np.asarray(idx.blk_bounds.scale)
    scale_col = scale[:, None] if scale.ndim else scale  # per-term row scales
    blk = np.asarray(blk_unpacked)[:, : idx.n_blocks].astype(np.float32) * scale_col
    pos_of = np.full(n_docs + 1, -1)
    pos_of[remap] = np.arange(len(remap))
    for _ in range(50):
        t = rng.integers(0, corpus.vocab)
        b = rng.integers(0, idx.n_blocks)
        docs = remap[b * idx.b : (b + 1) * idx.b]
        true_max = 0.0
        for d in docs:
            if d >= n_docs:
                continue
            sl = slice(corpus.doc_ptr[d], corpus.doc_ptr[d + 1])
            w = corpus.ws[sl][corpus.tids[sl] == t]
            if len(w):
                true_max = max(true_max, float(w.max()))
        lvl = float(scale[t]) if scale.ndim else float(scale)
        assert blk[t, b] >= true_max - 1e-4, "quantized block max must upper-bound"
        assert blk[t, b] <= true_max + lvl + 1e-4, "and be tight to one level"


def _true_block_max(corpus, idx):
    """Dense [V, NB] block-max matrix recomputed independently from the corpus and
    the built permutation."""
    n_docs = len(corpus.doc_ptr) - 1
    remap = np.asarray(idx.doc_remap)
    pos_of = np.full(n_docs + 1, -1, np.int64)
    pos_of[remap] = np.arange(len(remap))
    doc_of_posting = np.repeat(np.arange(n_docs), np.diff(corpus.doc_ptr))
    post_blk = pos_of[doc_of_posting] // idx.b
    blk_max = np.zeros((corpus.vocab, idx.n_blocks), np.float32)
    np.maximum.at(blk_max, (corpus.tids, post_blk), corpus.ws)
    return blk_max, post_blk


def test_sb_avg_is_avg_of_block_max(tiny_corpus, tiny_index):
    """SP / LSP2's SBavg must be the mean of the superblock's c block maxima (what
    layout.py documents and the pruning rule requires) — pinned bit-exactly against
    an independent recomputation, and distinct from the old mean-posting-weight bug."""
    _, corpus, _ = tiny_corpus
    idx = tiny_index
    assert idx.sb_avg is not None
    blk_max, post_blk = _true_block_max(corpus, idx)
    expected = blk_max.reshape(corpus.vocab, idx.n_superblocks, idx.c).mean(axis=2)

    # the stored matrix is exactly quantize(avg-of-block-max): same quant pipeline
    q_expected, s_expected = quantize_bounds_per_row(expected, idx.sb_avg.bits)
    stored = np.asarray(
        unpack_strided(idx.sb_avg.packed, idx.sb_avg.bits, idx.sb_avg.granule_words)
    )[:, : idx.n_superblocks]
    np.testing.assert_array_equal(stored, q_expected)
    np.testing.assert_allclose(np.asarray(idx.sb_avg.scale), s_expected, rtol=1e-6)

    # and it is NOT the seed's unfaithful mean-posting-weight-per-doc-slot matrix
    sb_sum = np.zeros((corpus.vocab, idx.n_superblocks), np.float32)
    np.add.at(sb_sum, (corpus.tids, post_blk // idx.c), corpus.ws)
    old_wrong = sb_sum / float(idx.b * idx.c)
    assert np.abs(expected - old_wrong).max() > 0.05, "corpus too degenerate to tell apart"


def test_sp_eligibility_matches_hand_computed_rule(tiny_corpus, tiny_index):
    """The SBavg(X) > θ/η branch, evaluated through the packed/quantized pipeline
    (ops.sbmax on sb_avg), must match the rule computed by hand from the dequantized
    avg-of-block-max matrix on a miniature single-term query."""
    import jax.numpy as jnp

    from repro.core import ops

    _, corpus, _ = tiny_corpus
    idx = tiny_index
    stored = np.asarray(
        unpack_strided(idx.sb_avg.packed, idx.sb_avg.bits, idx.sb_avg.granule_words)
    )[:, : idx.n_superblocks].astype(np.float32)
    scale = np.asarray(idx.sb_avg.scale)
    deq = stored * (scale[:, None] if scale.ndim else scale)

    term = int(np.argmax(deq.max(axis=1)))  # a term with signal
    w = 2.0
    sbavg = np.asarray(
        ops.sbmax(idx.sb_avg, jnp.array([[term]], jnp.int32), jnp.array([[w]], jnp.float32), "ref")
    )[0]
    by_hand = w * deq[term]
    np.testing.assert_allclose(sbavg, by_hand, rtol=1e-5, atol=1e-5)
    theta, eta = float(np.median(by_hand[by_hand > 0])), 2.0
    np.testing.assert_array_equal(sbavg > theta / eta, by_hand > theta / eta)


def test_fwd_index_roundtrip(tiny_corpus, tiny_index):
    """Forward index must contain exactly each document's (term, weight) pairs."""
    _, corpus, _ = tiny_corpus
    idx = tiny_index
    n_docs = len(corpus.doc_ptr) - 1
    remap = np.asarray(idx.doc_remap)
    tids = np.asarray(idx.docs_fwd.tids)
    ws = np.asarray(idx.docs_fwd.ws)
    rng = np.random.default_rng(1)
    for pos in rng.integers(0, len(remap), 20):
        d = remap[pos]
        if d >= n_docs:
            assert (tids[pos] == corpus.vocab).all()
            continue
        sl = slice(corpus.doc_ptr[d], corpus.doc_ptr[d + 1])
        true = dict(zip(corpus.tids[sl].tolist(), corpus.ws[sl].tolist()))
        got = {int(t): float(w) for t, w in zip(tids[pos], ws[pos]) if t < corpus.vocab}
        assert set(got) == set(true)
        for t, w in got.items():
            assert abs(w * idx.docs_fwd.scale - true[t]) <= idx.docs_fwd.scale / 2 + 1e-6


def test_project_docs_sums_in_posting_order(tiny_corpus):
    """The projection equals the one-posting-at-a-time scatter (same arithmetic,
    same order), empty documents included."""
    from repro.index.clustering import project_docs

    _, corpus, _ = tiny_corpus
    lens = np.diff(corpus.doc_ptr)
    lens[3] = 0  # an empty document keeps a zero row
    doc_ptr = np.concatenate([[0], np.cumsum(lens)])
    tids, ws = corpus.tids[: doc_ptr[-1]], corpus.ws[: doc_ptr[-1]]
    d_proj, seed = 16, 5
    rng = np.random.default_rng(seed)
    proj = rng.standard_normal((corpus.vocab, d_proj), dtype=np.float32) / np.sqrt(d_proj)
    want = np.zeros((len(lens), d_proj), np.float32)
    np.add.at(want, np.repeat(np.arange(len(lens)), lens), ws[:, None] * proj[tids])
    want /= np.maximum(np.linalg.norm(want, axis=1, keepdims=True), 1e-9)
    got = project_docs(doc_ptr, tids, ws, corpus.vocab, d_proj, seed)
    np.testing.assert_array_equal(got, want)
    assert not got[3].any()


def test_kmeans_over_document_chunks_matches_one_chunk(monkeypatch):
    """The assignment step over padded document chunks (bounded memory) finds the
    same clusters as over the whole [n, k] matrix; only the float order of the
    centroid sums differs."""
    from repro.index import clustering

    rng = np.random.default_rng(0)
    centers = rng.standard_normal((8, 16)).astype(np.float32) * 4
    x = (centers[rng.integers(0, 8, 400)] + rng.standard_normal((400, 16))).astype(np.float32)
    whole_a, whole_c = clustering.kmeans(x, 8, iters=4, seed=0)
    monkeypatch.setattr(clustering, "CHUNK_ELEMS", 8 * 64)  # 64 rows a chunk: 7, padded
    chunk_a, chunk_c = clustering.kmeans(x, 8, iters=4, seed=0)
    np.testing.assert_array_equal(chunk_a, whole_a)
    np.testing.assert_allclose(chunk_c, whole_c, rtol=1e-5, atol=1e-5)
