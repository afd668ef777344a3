"""The traffic generator is a function of the traffic file and --seed alone; an open
loop's send times of the traffic file alone."""

import numpy as np
import pytest

from bench.corpus import make_corpus
from bench.traffic import make_plan, n_requests, sample_positions

CFG = dict(n_docs=3000, vocab=600, n_topics=8, doc_len_mean=48, query_len_mean=24,
           topic_concentration=0.25, seed=1)
OPEN = {"loop": "open", "rate_qps": 50, "sample": 8, "warmup_queries": 3, "engine": {}}
CLOSED = {"loop": "closed", "clients": 4, "pool_per_second": 30, "sample": 8, "engine": {}}
ZIPF = dict(OPEN, pool={"size": 64, "zipf_s": 1.0})
BIG = 2**33 + 12345  # seeds go past 32 signed bits


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG)


def _same(a, b):
    return all(np.array_equal(x, y) for (x, _), (y, _) in zip(a, b)) and all(
        np.array_equal(x, y) for (_, x), (_, y) in zip(a, b))


@pytest.mark.parametrize("traffic", [OPEN, CLOSED, ZIPF], ids=["open", "closed", "zipf"])
def test_same_seed_same_plan(corpus, traffic):
    a = make_plan(traffic, CFG, corpus, BIG, 2.0)
    b = make_plan(traffic, CFG, corpus, BIG, 2.0)
    assert _same(a.stream, b.stream) and _same(a.warmup, b.warmup)
    if traffic["loop"] == "open":
        assert np.array_equal(a.offsets, b.offsets)
    c = make_plan(traffic, CFG, corpus, BIG + 1, 2.0)
    assert not _same(a.stream, c.stream)


def test_open_loop_schedule_has_a_fixed_count(corpus):
    plans = [make_plan(OPEN, CFG, corpus, s, 2.0) for s in (1, 2, BIG)]
    for p in plans:
        assert len(p.stream) == n_requests(OPEN, 2.0) == 100
        assert np.all(np.diff(p.offsets) >= 0) and 0 <= p.offsets[0] and p.offsets[-1] < 2.0
    # every seed offers the same schedule; the queries on it differ
    assert all(np.array_equal(plans[0].offsets, p.offsets) for p in plans)
    assert not _same(plans[0].stream, plans[1].stream)


def test_zipf_pool_repeats_a_fixed_pool_by_rank(corpus):
    p = make_plan(ZIPF, CFG, corpus, BIG, 4.0)
    keys = [t.tobytes() + w.tobytes() for t, w in p.stream]
    counts = sorted((keys.count(k) for k in set(keys)), reverse=True)
    assert len(set(keys)) <= 64 and counts[0] > 5 * counts[len(counts) // 2]


def test_distinct_queries_have_distinct_terms(corpus):
    p = make_plan(OPEN, CFG, corpus, 7, 2.0)
    for t, w in p.stream:
        assert len(np.unique(t)) == len(t) == len(w) and t.dtype == np.int32


def test_sample_is_drawn_from_the_seed_and_holds_the_longest():
    fin = np.ones(100, bool)
    fin[[5, 50]] = False
    a = sample_positions(BIG, fin, 10, longest=77)
    assert np.array_equal(a, sample_positions(BIG, fin, 10, longest=77))
    assert 77 in a and 5 not in a and 50 not in a and len(a) == 10
    assert not np.array_equal(a, sample_positions(BIG + 1, fin, 10, longest=77))
