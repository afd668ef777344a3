"""doc_score kernel subsystem: ref <-> kernel parity (interpret mode on CPU) plus
end-to-end retrieve() parity for both quantized doc layouts."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RetrievalConfig, make_query_batch, retrieve
from repro.index.layout import FlatDocsQ, FwdDocsQ
from repro.kernels.doc_score.kernel import doc_score_flat_pallas, doc_score_fwd_pallas
from repro.kernels.doc_score.ops import doc_score_flat_op, doc_score_fwd_op
from repro.kernels.doc_score.ref import doc_score_flat_ref, doc_score_fwd_ref


def _rand_fwdq(rng, nb, b, t, vocab, bits=8):
    tids = rng.integers(0, vocab, (nb, b, t)).astype(np.int32)
    ws = rng.integers(0, 1 << bits, (nb, b, t)).astype(np.uint8)
    # padded term slots: sentinel tid (== vocab), zero weight — like the builder
    n_pad = rng.integers(0, t, (nb, b))
    for k in range(nb):
        for j in range(b):
            if n_pad[k, j]:
                tids[k, j, -n_pad[k, j]:] = vocab
                ws[k, j, -n_pad[k, j]:] = 0
    scales = rng.random(nb).astype(np.float32) + 0.1
    return FwdDocsQ(jnp.asarray(tids), jnp.asarray(ws), jnp.asarray(scales), bits, t)


def _qdense(rng, q, vocab):
    qd = rng.standard_normal((q, vocab + 1)).astype(np.float32)
    qd[:, vocab] = 0.0  # sentinel column
    return jnp.asarray(qd)


def _dense_terms(qdense):
    """A dense query row as the kernels' (term id, weight) lists: every id once."""
    q, vp = qdense.shape
    return jnp.broadcast_to(jnp.arange(vp, dtype=jnp.int32), (q, vp)), qdense


@pytest.mark.parametrize("nb,b,t,vocab,q,s", [(32, 8, 16, 64, 2, 5), (17, 4, 24, 300, 3, 9), (8, 16, 8, 33, 1, 3)])
def test_doc_score_fwd_matches_ref(nb, b, t, vocab, q, s):
    rng = np.random.default_rng(nb * 10 + b)
    fwdq = _rand_fwdq(rng, nb, b, t, vocab)
    qdense = _qdense(rng, q, vocab)
    blk = jnp.asarray(rng.integers(0, nb, (q, s)).astype(np.int32))
    q_tids, q_ws = _dense_terms(qdense)
    out_k = doc_score_fwd_pallas(fwdq.tids, fwdq.ws, q_tids, q_ws, blk, interpret=True)
    out_r = doc_score_fwd_ref(fwdq, qdense, blk)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-4)
    # op wrapper applies per-block scales on both paths identically
    scaled = doc_score_fwd_op(fwdq, q_tids, q_ws, blk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(scaled),
        np.asarray(out_r) * np.asarray(fwdq.scales)[np.asarray(blk)][:, :, None],
        rtol=1e-5, atol=1e-4,
    )


@pytest.mark.parametrize("nb,b,m,vocab,q,s", [(24, 8, 40, 64, 2, 6), (9, 4, 16, 120, 3, 4)])
def test_doc_score_flat_matches_ref(nb, b, m, vocab, q, s):
    rng = np.random.default_rng(nb * 7 + m)
    # per-block postings sorted by local doc id: runs delimited by doc_ends
    counts = rng.integers(0, m // b + 1, (nb, b))
    doc_ends = np.cumsum(counts, axis=1).astype(np.int32)
    tids = np.full((nb, m), vocab, np.int32)
    ws = np.zeros((nb, m), np.uint8)
    for k in range(nb):
        n = doc_ends[k, -1]
        tids[k, :n] = rng.integers(0, vocab, n)
        ws[k, :n] = rng.integers(0, 256, n)
    scales = rng.random(nb).astype(np.float32) + 0.1
    flatq = FlatDocsQ(jnp.asarray(tids), jnp.asarray(ws), jnp.asarray(doc_ends), jnp.asarray(scales), 8, m)
    qdense = _qdense(rng, q, vocab)
    blk = jnp.asarray(rng.integers(0, nb, (q, s)).astype(np.int32))
    q_tids, q_ws = _dense_terms(qdense)
    out_k = doc_score_flat_pallas(
        flatq.tids, flatq.ws, flatq.doc_ends, q_tids, q_ws, blk, interpret=True
    )
    out_r = doc_score_flat_ref(flatq, qdense, blk)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-4)
    scaled = doc_score_flat_op(flatq, q_tids, q_ws, blk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(scaled),
        np.asarray(out_r) * scales[np.asarray(blk)][:, :, None],
        rtol=1e-5, atol=1e-4,
    )


def test_doc_score_layouts_agree(tiny_index, tiny_qb):
    """fwd and flat quantized operands hold the same per-block-quantized weights, so
    raw per-doc scores must agree exactly across layouts (ref and kernel)."""
    rng = np.random.default_rng(0)
    q = tiny_qb.tids.shape[0]
    blk = jnp.asarray(rng.integers(0, tiny_index.n_blocks, (q, 12)).astype(np.int32))
    fwd = doc_score_fwd_op(tiny_index.docs_fwdq, tiny_qb.tids, tiny_qb.ws, blk, interpret=True)
    flat = doc_score_flat_op(tiny_index.docs_flatq, tiny_qb.tids, tiny_qb.ws, blk, interpret=True)
    np.testing.assert_allclose(np.asarray(fwd), np.asarray(flat), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("layout", ["fwd", "flat"])
def test_retrieve_kernel_matches_ref(tiny_index, tiny_qb, layout):
    """End-to-end parity incl. padded/masked blocks and sentinel docs: the tiny corpus
    pads the last superblock with sentinel documents, and θ/η pruning masks blocks."""
    cfg = RetrievalConfig(variant="lsp0", k=10, gamma=8, gamma0=2, beta=0.5, doc_layout=layout)
    r_ref = retrieve(tiny_index, tiny_qb, cfg, impl="ref")
    r_ker = retrieve(tiny_index, tiny_qb, cfg, impl="kernel")
    np.testing.assert_array_equal(np.asarray(r_ref.doc_ids), np.asarray(r_ker.doc_ids))
    np.testing.assert_allclose(np.asarray(r_ref.scores), np.asarray(r_ker.scores), rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(r_ref.n_blocks_scored), np.asarray(r_ker.n_blocks_scored)
    )


def test_doc_score_sentinel_blocks_clamped(tiny_index, tiny_qb):
    """Out-of-range block ids (padding) are clamped, never out-of-bounds; the caller's
    mask is what excludes them — scores at clamped ids are finite."""
    q = tiny_qb.tids.shape[0]
    blk = jnp.full((q, 4), tiny_index.n_blocks + 99, jnp.int32)
    out = doc_score_fwd_op(tiny_index.docs_fwdq, tiny_qb.tids, tiny_qb.ws, blk, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("layout", ["fwd", "flat"])
def test_doc_score_query_groups_match_one_call(tiny_index, tiny_qb, layout):
    """A batch whose block ids exceed the SMEM budget runs as calls over query
    groups (here 3 rows each, the last group padded); the result is the one-call one."""
    from repro.kernels.doc_score.ops import per_query_groups

    rng = np.random.default_rng(1)
    q = tiny_qb.tids.shape[0]
    blk = jnp.asarray(rng.integers(0, tiny_index.n_blocks, (q, 12)).astype(np.int32))
    if layout == "flat":
        fq = tiny_index.docs_flatq
        kernel, operands = doc_score_flat_pallas, (fq.tids, fq.ws, fq.doc_ends)
    else:
        fq = tiny_index.docs_fwdq
        kernel, operands = doc_score_fwd_pallas, (fq.tids, fq.ws)
    args = (kernel, operands, tiny_qb.tids, tiny_qb.ws, blk, True)
    grouped = per_query_groups(*args, budget=3 * 12)
    assert q % 3 != 0  # the padded last group is exercised
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(per_query_groups(*args)))


def _mask(kind, q, s, rng):
    """A [q, s] block mask of the named shape (s > 2 * 128: three output tiles)."""
    slot = np.arange(s)[None, :]
    if kind == "random":
        m = rng.random((q, s)) < 0.2
        m[:, 0] = False  # a leading dead run, before the row's first live slot
        return m
    if kind == "prefix":
        return np.broadcast_to(slot < rng.integers(1, s, (q, 1)), (q, s))
    if kind == "all_live":
        return np.ones((q, s), bool)
    if kind == "all_dead":
        return np.zeros((q, s), bool)
    # one live slot in each 128-lane output tile, at a different lane in each
    return np.broadcast_to(slot % 128 == (slot // 128) * 37 % 128, (q, s))


@pytest.mark.parametrize("kind", ["random", "prefix", "all_live", "all_dead", "one_per_tile"])
@pytest.mark.parametrize("layout", ["fwd", "flat"])
def test_doc_score_masked_matches_ref(tiny_index, tiny_qb, layout, kind):
    """Under a block mask the kernels skip the dead slots: a live slot scores what
    the unmasked kernel gives, bit for bit, and matches the reference; a dead slot
    scores exactly 0. ``blk_mask=None`` is the all-live mask."""
    from repro.core.query import scatter_dense

    rng = np.random.default_rng(2)
    q, s = 3, 300
    qt, qw = tiny_qb.tids[:q], tiny_qb.ws[:q]
    blk = jnp.asarray(rng.integers(0, tiny_index.n_blocks, (q, s)).astype(np.int32))
    mask = _mask(kind, q, s, rng)
    if layout == "flat":
        operand, op, ref = tiny_index.docs_flatq, doc_score_flat_op, doc_score_flat_ref
    else:
        operand, op, ref = tiny_index.docs_fwdq, doc_score_fwd_op, doc_score_fwd_ref
    masked = np.asarray(op(operand, qt, qw, blk, interpret=True, blk_mask=jnp.asarray(mask)))
    full = np.asarray(op(operand, qt, qw, blk, interpret=True))
    want = np.asarray(ref(operand, scatter_dense(tiny_qb)[:q], blk))
    want = want * np.asarray(operand.scales)[np.asarray(blk)][:, :, None]
    np.testing.assert_array_equal(masked[mask], full[mask])
    np.testing.assert_allclose(masked[mask], want[mask], rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(masked[~mask], 0.0)
    if kind == "all_live":
        np.testing.assert_array_equal(masked, full)


def test_live_ids_fill_dead_slots_from_the_last_live_block():
    """Dead slots carry -1 - (the nearest live block before them; the row's first
    live block before its first live slot; the row's first slot in an all-dead row)."""
    from repro.kernels.doc_score.ops import live_ids

    blk = jnp.asarray([[5, 6, 7, 8, 9], [5, 6, 7, 8, 9], [5, 6, 7, 8, 9]], jnp.int32)
    mask = jnp.asarray([[False, True, False, False, True], [True] * 5, [False] * 5])
    np.testing.assert_array_equal(
        np.asarray(live_ids(blk, mask)),
        [[-7, 6, -7, -7, 9], [5, 6, 7, 8, 9], [-6, -6, -6, -6, -6]],
    )
    assert live_ids(blk, None) is blk


@pytest.mark.parametrize("layout", ["fwd", "flat"])
def test_doc_score_query_groups_masked_match_one_call(tiny_index, tiny_qb, layout):
    """Dead-slot ids pass through the query-group split unchanged: the grouped
    result under a mask is the one-call result, with 0 at every dead slot."""
    from repro.kernels.doc_score.ops import live_ids, per_query_groups

    rng = np.random.default_rng(3)
    q = tiny_qb.tids.shape[0]
    blk = jnp.asarray(rng.integers(0, tiny_index.n_blocks, (q, 12)).astype(np.int32))
    mask = rng.random((q, 12)) < 0.4
    if layout == "flat":
        fq = tiny_index.docs_flatq
        kernel, operands = doc_score_flat_pallas, (fq.tids, fq.ws, fq.doc_ends)
    else:
        fq = tiny_index.docs_fwdq
        kernel, operands = doc_score_fwd_pallas, (fq.tids, fq.ws)
    args = (kernel, operands, tiny_qb.tids, tiny_qb.ws, live_ids(blk, jnp.asarray(mask)), True)
    grouped = np.asarray(per_query_groups(*args, budget=3 * 12))
    np.testing.assert_array_equal(grouped, np.asarray(per_query_groups(*args)))
    np.testing.assert_array_equal(grouped[~mask], 0.0)


@pytest.mark.parametrize("variant", ["lsp0", "bmp"])
def test_search_kernel_matches_ref_bit_for_bit(tiny_index, tiny_qb, monkeypatch, variant):
    """The whole traversal with the doc_score kernel (interpret mode) returns the
    reference's RetrievalResult bit for bit: lsp0 at k=10 over every superblock,
    where most phase-3 slots are dead (a live prefix), and bmp, whose eligible
    slots are a run after a dead round-0 prefix. The bound kernels are steered to
    the reference on both sides (bmp's block-bound rows are narrower than sbmax's
    tile at this size), so the two sides differ only in document scoring."""
    from repro.core import ops
    from repro.core.config import StaticConfig
    from repro.core.lsp import search_retrieve

    sbmax, gathered = ops.sbmax, ops.gathered_block_bounds
    monkeypatch.setattr(ops, "sbmax", lambda pb, t, w, impl="auto": sbmax(pb, t, w, "ref"))
    monkeypatch.setattr(
        ops, "gathered_block_bounds",
        lambda pb, c, t, w, sel, impl="auto": gathered(pb, c, t, w, sel, "ref"),
    )
    ns, c = tiny_index.n_superblocks, tiny_index.c
    scfg = StaticConfig(variant=variant, gamma=ns if variant == "lsp0" else 8, gamma0=2, k_max=10)
    r_ref = search_retrieve(tiny_index, tiny_qb, scfg, impl="ref")
    r_ker = search_retrieve(tiny_index, tiny_qb, scfg, impl="kernel")
    for field in r_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_ker, field)), np.asarray(getattr(r_ref, field)), err_msg=field
        )
    if variant == "lsp0":
        live = np.asarray(r_ref.n_blocks_scored) - 2 * c  # phase-3 blocks of ns * c slots
        assert live.mean() < 0.5 * ns * c
