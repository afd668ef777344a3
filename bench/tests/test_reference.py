"""The plain reference against brute force over a dense corpus matrix."""

import numpy as np
import pytest

from bench.corpus import make_corpus, make_queries
from bench.reference import dense_queries, exact_topk, pair_scores

CFG = dict(n_docs=3000, vocab=400, n_topics=8, doc_len_mean=32, query_len_mean=16,
           topic_concentration=0.25, seed=2)


@pytest.fixture(scope="module")
def brute():
    corpus = make_corpus(CFG)
    queries = make_queries(CFG, corpus, 6, seed=3)
    dense = np.zeros((CFG["n_docs"], CFG["vocab"]))
    for d in range(CFG["n_docs"]):
        lo, hi = corpus.doc_ptr[d], corpus.doc_ptr[d + 1]
        dense[d, corpus.tids[lo:hi]] = corpus.ws[lo:hi]
    return corpus, queries, dense_queries(queries, CFG["vocab"]).astype(np.float64) @ dense.T


@pytest.mark.parametrize("k", [10, 700])
def test_exact_topk_is_the_brute_force_order(brute, k):
    corpus, queries, scores = brute
    ids, vals = exact_topk(corpus, queries, k, chunk=512)  # several chunks, a ragged last
    ids_order = np.broadcast_to(np.arange(scores.shape[1]), scores.shape)
    want = np.lexsort((ids_order, -scores), axis=1)[:, :k]
    assert np.array_equal(ids, want)
    np.testing.assert_allclose(vals, np.take_along_axis(scores, want, 1), rtol=1e-5)


def test_pair_scores_are_exact_and_nan_outside_the_corpus(brute):
    corpus, queries, scores = brute
    q = np.array([0, 1, 2, 5, 3])
    d = np.array([17, 2999, 0, 1234, 3000])
    got = pair_scores(corpus, queries, q, d, block=2)
    np.testing.assert_allclose(got[:4], scores[q[:4], d[:4]], rtol=1e-12)
    assert np.isnan(got[4])


def test_ties_and_zero_scores_follow_the_doc_id_order():
    # docs 0-3 share one posting of term 1 (a tie), docs 4-5 have no query term
    from bench.corpus import Corpus

    corpus = Corpus(np.array([0, 1, 2, 3, 4, 5, 6]), np.array([1, 1, 1, 1, 2, 3], np.int32),
                    np.ones(6, np.float32), 4, np.zeros(6, np.int32))
    q = [(np.array([1], np.int32), np.array([2.0], np.float32))]
    ids, vals = exact_topk(corpus, q, 5, chunk=4)
    assert ids.tolist() == [[0, 1, 2, 3, 4]] and vals.tolist() == [[2, 2, 2, 2, 0]]
    ids, _ = exact_topk(corpus, q, 2, chunk=4)
    assert ids.tolist() == [[0, 1]]
