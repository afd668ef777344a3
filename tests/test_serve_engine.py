"""Serving-layer semantics: bucket selection, cross-bucket result identity, cache
hit/eviction, failure isolation, shutdown, and stats consistency under load."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.api import SearchRequest
from repro.core import RetrievalConfig, jit_retrieve
from repro.core.query import canonical_query, query_key
from repro.serve import BucketLadder, QueryResultCache, RetrievalEngine


def _echo_retriever(qb):
    """Deterministic pure function of each canonical query row (shape-independent
    for nq >= 4): ids = first 4 term ids, scores = their weights."""
    tids = np.asarray(qb.tids)
    ws = np.asarray(qb.ws)
    return tids[:, :4], ws[:, :4]


def _query(rng, n=6, vocab=512):
    tids = rng.choice(vocab, n, replace=False).astype(np.int32)
    ws = rng.random(n).astype(np.float32) + 0.1
    return tids, ws


# ---- bucket ladder ----------------------------------------------------------------


def test_bucket_ladder_selects_smallest_cover():
    lad = BucketLadder(32, 64)
    sel = lambda n, q: (lad.select(n, q).batch, lad.select(n, q).nq)
    assert lad.batch_sizes == [1, 4, 16, 32] and lad.nq_sizes == [16, 64]
    assert sel(1, 10) == (1, 16)
    assert sel(2, 10) == (4, 16)
    assert sel(17, 17) == (32, 64)
    # beyond-ladder inputs clip to the maxima instead of failing
    assert sel(1000, 1000) == (32, 64)
    assert sel(0, 0) == (1, 16)


def test_bucket_ladder_explicit_sizes_clip_and_sort():
    lad = BucketLadder(8, 32, batch_sizes=[64, 2, 2], nq_sizes=[32])
    assert lad.batch_sizes == [2, 8] and lad.nq_sizes == [32]
    assert len(lad.shapes()) == 2


def test_make_query_batch_is_permutation_invariant():
    """Equal-weight ties must batch identically regardless of input order (the
    canonical term-id tie-break), including at the nq_max truncation boundary —
    otherwise identical queries batch differently outside the serve path."""
    from repro.core.query import make_query_batch

    t = np.array([7, 3, 11, 5], np.int32)
    w = np.array([1.0, 2.0, 1.0, 1.0], np.float32)
    perm = [2, 3, 0, 1]
    for nq in (0, 2):
        qa = make_query_batch([(t, w)], vocab=64, nq_max=nq)
        qb = make_query_batch([(t[perm], w[perm])], vocab=64, nq_max=nq)
        np.testing.assert_array_equal(np.asarray(qa.tids), np.asarray(qb.tids))
        np.testing.assert_array_equal(np.asarray(qa.ws), np.asarray(qb.ws))
    # weight desc, then term id asc among the 1.0 ties; truncation keeps [3, 5]
    trunc = make_query_batch([(t, w)], vocab=64, nq_max=2)
    assert np.asarray(trunc.tids)[0].tolist() == [3, 5]
    # and the batch row now matches the serve path's canonical_query exactly
    ct, cw = canonical_query(t, w)
    full = make_query_batch([(t, w)], vocab=64)
    np.testing.assert_array_equal(np.asarray(full.tids)[0][: len(ct)], ct)
    np.testing.assert_array_equal(np.asarray(full.ws)[0][: len(cw)], cw)


def test_query_key_is_permutation_invariant():
    t = np.array([5, 2, 9], np.int32)
    w = np.array([1.0, 2.0, 3.0], np.float32)
    perm = [2, 0, 1]
    assert query_key(t, w) == query_key(t[perm], w[perm])
    assert query_key(t, w) != query_key(t, 2 * w)
    ct, cw = canonical_query(t, w)
    assert list(ct) == [9, 2, 5] and list(cw) == [3.0, 2.0, 1.0]  # weight desc
    # truncation happens after canonical ordering, so it is permutation-stable
    assert query_key(t, w, nq_max=2) == query_key(t[perm], w[perm], nq_max=2)


# ---- cross-bucket correctness ------------------------------------------------------


def test_bucketed_results_bit_identical_to_padded(tiny_index, tiny_corpus):
    """Same query stream through the batch-1 bucket and through the padded
    max_batch single-shape engine must give bit-identical (ids, scores)."""
    _, corpus, queries = tiny_corpus
    cfg = RetrievalConfig(variant="lsp0", k=10, gamma=16, gamma0=4, beta=0.5)
    retr = jit_retrieve(tiny_index, cfg, impl="ref")
    padded = RetrievalEngine(retr, corpus.vocab, max_batch=4, nq_max=64,
                             batch_buckets=[4], nq_buckets=[64], cache_size=0)
    bucketed = RetrievalEngine(retr, corpus.vocab, max_batch=4, nq_max=64, cache_size=0)
    try:
        for t, w in queries[:8]:
            ia, sa = padded.submit(t, w).result(timeout=120)
            ib, sb = bucketed.submit(t, w).result(timeout=120)
            np.testing.assert_array_equal(ia, ib)
            np.testing.assert_array_equal(sa, sb)
        # sequential submits actually exercised the small bucket
        assert any(k.startswith("1x") for k in bucketed.stats.summary()["bucket_batches"])
    finally:
        padded.shutdown()
        bucketed.shutdown()


def test_warmup_precompiles_every_bucket():
    shapes = []

    def retr(qb):
        return _echo_retriever(qb)

    retr.warmup = lambda s: shapes.extend(s)
    eng = RetrievalEngine(retr, vocab=64, max_batch=16, nq_max=64, warmup=True)
    try:
        assert sorted(shapes) == [(b.batch, b.nq) for b in sorted(eng.ladder.shapes())]
    finally:
        eng.shutdown()

    seen = []
    eng2 = RetrievalEngine(lambda qb: seen.append(np.asarray(qb.tids).shape) or _echo_retriever(qb),
                           vocab=64, max_batch=4, nq_max=32, warmup=True)
    try:
        assert set(seen) >= {(b.batch, b.nq) for b in eng2.ladder.shapes()}
    finally:
        eng2.shutdown()


# ---- failure semantics -------------------------------------------------------------


def test_retriever_exception_fails_batch_and_keeps_serving():
    class Boom(RuntimeError):
        pass

    def flaky(qb):
        if (np.asarray(qb.tids)[:, 0] == 13).any():
            raise Boom("injected")
        return _echo_retriever(qb)

    eng = RetrievalEngine(flaky, vocab=512, max_batch=2, nq_max=16, cache_size=0)
    try:
        bad = eng.submit(np.array([13], np.int32), np.array([9.0], np.float32))
        with pytest.raises(Boom):
            bad.result(timeout=30)
        good = eng.submit(np.array([7, 3], np.int32), np.array([2.0, 1.0], np.float32))
        ids, scores = good.result(timeout=30)
        assert ids[0] == 7 and scores[0] == 2.0
        s = eng.stats.summary()
        assert s["failures"] == 1 and s["requests"] == 1
    finally:
        eng.shutdown()


def test_submit_after_shutdown_raises():
    eng = RetrievalEngine(_echo_retriever, vocab=64, max_batch=2, nq_max=16)
    eng.shutdown()
    eng.shutdown()  # idempotent
    with pytest.raises(RuntimeError):
        eng.submit(np.array([1], np.int32), np.array([1.0], np.float32))
    assert eng.stats.summary()["rejected"] >= 1


def test_shutdown_drains_and_fails_queued_requests():
    release = threading.Event()

    def slow(qb):
        release.wait(timeout=30)
        return _echo_retriever(qb)

    eng = RetrievalEngine(slow, vocab=64, max_batch=2, nq_max=16, max_wait_ms=0.0, cache_size=0)
    try:
        rng = np.random.default_rng(0)
        futs = [eng.submit(*_query(rng, vocab=64)) for _ in range(6)]
        deadline = time.monotonic() + 10  # wait until the worker is inside slow()
        while eng._q.qsize() < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        shut = threading.Thread(target=eng.shutdown)
        shut.start()
        time.sleep(0.1)
        release.set()
        shut.join(timeout=30)
        assert not shut.is_alive()
        done = sum(1 for f in futs if f.exception(timeout=30) is None)
        failed = [f for f in futs if f.exception(timeout=1) is not None]
        assert done >= 1  # the in-flight batch completed
        assert failed, "queued requests must be failed, not left hanging"
        assert all(isinstance(f.exception(timeout=1), RuntimeError) for f in failed)
    finally:
        release.set()
        eng.shutdown()


# ---- cache -------------------------------------------------------------------------


def test_cache_lru_semantics():
    c = QueryResultCache(capacity=2)
    c.put(b"a", 1)
    c.put(b"b", 2)
    assert c.get(b"a") == 1  # refreshes recency: b is now LRU
    c.put(b"c", 3)
    assert c.get(b"b") is None and c.evictions == 1
    assert c.get(b"a") == 1 and c.get(b"c") == 3
    assert len(c) == 2
    c.clear()
    assert len(c) == 0


def test_engine_cache_hit_and_eviction():
    calls = []

    def counting(qb):
        calls.append(np.asarray(qb.tids).shape[0])
        return _echo_retriever(qb)

    eng = RetrievalEngine(counting, vocab=512, max_batch=1, nq_max=16, cache_size=2)
    try:
        rng = np.random.default_rng(1)
        q1, q2, q3 = (_query(rng) for _ in range(3))
        r1 = eng.submit(*q1).result(timeout=30)
        n_after_q1 = len(calls)
        # permuted resubmission of q1 is a hit (canonical key) and skips the retriever
        perm = np.argsort(q1[0])
        r1b = eng.submit(q1[0][perm], q1[1][perm]).result(timeout=30)
        np.testing.assert_array_equal(r1[0], r1b[0])
        np.testing.assert_array_equal(r1[1], r1b[1])
        assert len(calls) == n_after_q1
        eng.submit(*q2).result(timeout=30)
        eng.submit(*q3).result(timeout=30)  # capacity 2: q1 evicted (LRU)
        before = len(calls)
        eng.submit(*q1).result(timeout=30)
        assert len(calls) == before + 1  # miss -> recompute
        s = eng.stats.summary()
        assert s["cache_hits"] == 1 and s["cache_misses"] == 4
        assert 0 < s["cache_hit_rate"] < 1
        assert eng.cache.evictions >= 1
    finally:
        eng.shutdown()


def test_cached_rows_do_not_alias_caller_results():
    """A caller mutating its (ids, scores) in place must not corrupt the cache —
    neither via the miss that filled it nor via a later hit."""
    eng = RetrievalEngine(_echo_retriever, vocab=512, max_batch=1, nq_max=16, cache_size=8)
    try:
        rng = np.random.default_rng(2)
        q = _query(rng)
        ids1, scores1 = eng.submit(*q).result(timeout=30)  # miss fills the cache
        expected = (ids1.copy(), scores1.copy())
        ids1[:] = -1
        scores1[:] = -1.0
        ids2, scores2 = eng.submit(*q).result(timeout=30)  # hit
        np.testing.assert_array_equal(ids2, expected[0])
        np.testing.assert_array_equal(scores2, expected[1])
        ids2[:] = -7  # mutating a hit's result must not poison later hits either
        ids3, _ = eng.submit(*q).result(timeout=30)
        np.testing.assert_array_equal(ids3, expected[0])
        assert eng.stats.summary()["cache_hits"] == 2
    finally:
        eng.shutdown()


# ---- index lifecycle: hot-swap -----------------------------------------------------


def _tagged_retriever(tag: float):
    """Echo retriever whose scores carry ``tag``: distinguishes which 'index
    generation' served a request."""

    def retr(qb):
        tids = np.asarray(qb.tids)
        ws = np.asarray(qb.ws)
        return tids[:, :4], ws[:, :4] + tag

    return retr


def test_hot_swap_flips_results_and_never_serves_stale_cache():
    eng = RetrievalEngine(_tagged_retriever(0.0), vocab=512, max_batch=2, nq_max=16,
                          cache_size=8)
    try:
        rng = np.random.default_rng(3)
        q = _query(rng)
        ids1, scores1 = eng.submit(*q).result(timeout=30)
        # cached: resubmission is a hit served from epoch 0
        eng.submit(*q).result(timeout=30)
        assert eng.stats.summary()["cache_hits"] == 1
        assert eng.epoch == 0

        epoch = eng.swap_retriever(_tagged_retriever(100.0), warm=False)
        assert epoch == eng.epoch == 1
        # same query after the swap: the epoch-keyed probe must MISS (no stale
        # result from the retired index) and score on the new retriever
        ids2, scores2 = eng.submit(*q).result(timeout=30)
        np.testing.assert_array_equal(ids2, ids1)
        np.testing.assert_allclose(scores2, scores1 + 100.0, rtol=1e-6)
        s = eng.stats.summary()
        assert s["cache_hits"] == 1 and s["swaps"] == 1 and s["last_swap_ms"] >= 0.0
        # and the new epoch's fill works: a second resubmission hits the NEW result
        ids3, scores3 = eng.submit(*q).result(timeout=30)
        np.testing.assert_allclose(scores3, scores2, rtol=0)
        assert eng.stats.summary()["cache_hits"] == 2
    finally:
        eng.shutdown()


def test_hot_swap_inflight_batch_completes_on_old_retriever():
    entered, release = threading.Event(), threading.Event()

    def slow_v1(qb):
        entered.set()
        release.wait(timeout=30)
        return _tagged_retriever(0.0)(qb)

    eng = RetrievalEngine(slow_v1, vocab=512, max_batch=2, nq_max=16,
                          max_wait_ms=0.0, cache_size=8)
    try:
        rng = np.random.default_rng(4)
        q = _query(rng)
        fut = eng.submit(*q)
        assert entered.wait(timeout=30)  # the worker is inside the old retriever
        swapped = eng.swap_retriever(_tagged_retriever(100.0), warm=False)
        assert swapped == 1  # swap completed while the old batch is still in flight
        release.set()
        ids, scores = fut.result(timeout=30)  # served by the OLD retriever: tag 0
        assert float(scores[0]) < 50.0
        # the in-flight batch's cache fill was dropped (its epoch retired mid-
        # flight): the same query now misses and is scored by the new retriever
        _, scores2 = eng.submit(*q).result(timeout=30)
        assert float(scores2[0]) > 50.0
        assert eng.stats.summary()["cache_hits"] == 0
    finally:
        release.set()
        eng.shutdown()


def test_swap_index_from_disk_with_factory(tiny_index, tiny_corpus, tmp_path):
    from repro.index.store import save_index

    _, corpus, queries = tiny_corpus
    cfg = RetrievalConfig(variant="lsp0", k=10, gamma=16, gamma0=4, beta=0.5)
    factory = lambda ix: jit_retrieve(ix, cfg, impl="ref")
    eng = RetrievalEngine(factory(tiny_index), corpus.vocab, max_batch=2, nq_max=64,
                          cache_size=8, retriever_factory=factory)
    try:
        t, w = queries[0]
        before = eng.submit(t, w).result(timeout=120)
        path = tmp_path / "index"
        save_index(str(path), tiny_index)
        epoch = eng.swap_index(str(path), warm=False)
        assert epoch == 1
        after = eng.submit(t, w).result(timeout=120)  # cache missed, same index bits
        np.testing.assert_array_equal(before[0], after[0])
        np.testing.assert_array_equal(before[1], after[1])
        assert eng.stats.summary()["cache_hits"] == 0
        assert eng.stats.summary()["swaps"] == 1
    finally:
        eng.shutdown()


def test_swap_without_factory_or_after_shutdown_raises():
    eng = RetrievalEngine(_echo_retriever, vocab=64, max_batch=2, nq_max=16)
    try:
        with pytest.raises(RuntimeError, match="retriever_factory"):
            eng.swap_index("/nonexistent")
    finally:
        eng.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        eng.swap_retriever(_echo_retriever)


def test_hot_swap_under_continuous_traffic_zero_failures():
    """A live engine under concurrent load swaps retrievers repeatedly: every
    future resolves with a result (zero failures), results come from exactly one
    generation each, and post-swap results eventually flow from the new one."""
    eng = RetrievalEngine(_tagged_retriever(0.0), vocab=512, max_batch=4, nq_max=16,
                          max_wait_ms=0.5, cache_size=32)
    stop = threading.Event()
    tags_seen, errors = set(), []

    def client(seed):
        rng = np.random.default_rng(seed)
        pool = [_query(rng) for _ in range(8)]
        i = 0
        while not stop.is_set():
            try:
                _, scores = eng.submit(*pool[i % 8]).result(timeout=60)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return
            tags_seen.add(round(float(scores[0]) // 100) * 100)
            i += 1

    threads = [threading.Thread(target=client, args=(s,)) for s in range(3)]
    for t in threads:
        t.start()
    try:
        for gen in (100.0, 200.0, 300.0):
            time.sleep(0.05)
            eng.swap_retriever(_tagged_retriever(gen), warm=True)
        time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        eng.shutdown()
    assert not errors, errors
    s = eng.stats.summary()
    assert s["failures"] == 0 and s["swaps"] == 3
    assert 300 in tags_seen  # traffic reached the final generation


# ---- stats + concurrency -----------------------------------------------------------


def test_stats_consistent_under_concurrent_load():
    eng = RetrievalEngine(_echo_retriever, vocab=512, max_batch=8, nq_max=16,
                          max_wait_ms=1.0, cache_size=64)
    errors = []

    def client(seed):
        rng = np.random.default_rng(seed)
        pool = [_query(rng) for _ in range(4)]  # repeats -> cache traffic
        try:
            for i in range(16):
                ids, scores = eng.submit(*pool[i % 4]).result(timeout=60)
                assert ids.shape == scores.shape
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        eng.stats.summary()  # concurrent reads must not race the engine's writes
        time.sleep(0.001)
    for t in threads:
        t.join()
    eng.shutdown()
    assert not errors, errors
    s = eng.stats.summary()
    assert s["requests"] == 4 * 16
    assert s["cache_hits"] + s["cache_misses"] == s["requests"]
    assert sum(eng.stats.bucket_batches.values()) == s["batches"] > 0
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]


def test_concurrent_submit_shutdown_stress():
    def slowish(qb):
        time.sleep(0.002)
        return _echo_retriever(qb)

    eng = RetrievalEngine(slowish, vocab=256, max_batch=4, nq_max=16,
                          max_wait_ms=0.5, cache_size=0, queue_depth=8)
    futs: list[Future] = []
    lock = threading.Lock()
    stop_submitting = threading.Event()

    def client(seed):
        rng = np.random.default_rng(seed)
        while not stop_submitting.is_set():
            try:
                f = eng.submit(*_query(rng, vocab=256))
            except RuntimeError:
                return  # engine shut down underneath us: the documented contract
            with lock:
                futs.append(f)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    eng.shutdown()
    stop_submitting.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    # every accepted future resolves: a result, or RuntimeError from the drain
    for f in futs:
        exc = f.exception(timeout=30)
        assert exc is None or isinstance(exc, RuntimeError)
    assert any(f.exception(timeout=1) is None for f in futs)


# ---- tracing: stage spans and the queue-wait counter --------------------------------

_STAGES = ("serve.pad", "serve.dispatch", "serve.fetch", "serve.resolve")


def _host_events(trace_dir):
    """(line index, name, start, end, stats) of every host event in the trace."""
    import jax

    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((i, e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_stage_spans_in_profiler_trace(tmp_path):
    import jax

    entered, release = threading.Event(), threading.Event()

    def gated(qb):
        entered.set()
        release.wait(timeout=30)
        return _echo_retriever(qb)

    eng = RetrievalEngine(gated, vocab=64, max_batch=1, nq_max=16, max_wait_ms=0.0,
                          cache_size=0, queue_depth=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        q = [np.array([i + 1, i + 2], np.int32) for i in range(3)]
        w = np.array([1.0, 0.5], np.float32)
        futs = [eng.search(SearchRequest(q[0], w, request_id="a"))]
        assert entered.wait(timeout=30)  # "a" is in service: the lane is free again
        futs.append(eng.search(SearchRequest(q[1], w, request_id="b")))  # fills the lane
        blocked = threading.Thread(
            target=lambda: futs.append(eng.search(SearchRequest(q[2], w, request_id="c"))))
        blocked.start()
        time.sleep(0.2)  # "c" is held by backpressure
        release.set()
        blocked.join(timeout=30)
        assert not blocked.is_alive()
        assert [f.result(timeout=30).doc_ids[0] for f in futs] == [1, 2, 3]
    finally:
        release.set()
        eng.shutdown()
        jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    names = {e[1] for e in events}
    for name in ("serve.admit", "serve.backpressure", "serve.collect", "serve.batch") + _STAGES:
        assert name in names, name
    batches = [e for e in events if e[1] == "serve.batch"]
    assert sorted(b[4]["request_ids"] for b in batches) == ["a", "b", "c"]
    assert sorted(b[4]["batch_id"] for b in batches) == [0, 1, 2]
    assert all(b[4]["bucket"] == "1x16" and b[4]["n"] == 1 for b in batches)
    for line, _, lo, hi, _ in batches:
        inside = sorted((s, e, n) for i, n, s, e, _ in events
                        if i == line and n in _STAGES and lo <= s and e <= hi)
        assert tuple(n for _, _, n in inside) == _STAGES  # one each, in order
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))
    (held,) = [e for e in events if e[1] == "serve.backpressure"]
    assert held[4]["request_id"] == "c" and held[3] > held[2]
    assert any(i == held[0] and n == "serve.admit" and st["request_id"] == "c"
               and s <= held[2] and held[3] <= e for i, n, s, e, st in events)


def test_queue_wait_counts_admission_to_dispatch():
    service_s = 0.5
    entered = threading.Event()

    def slow(qb):
        entered.set()
        time.sleep(service_s)
        return _echo_retriever(qb)

    eng = RetrievalEngine(slow, vocab=64, max_batch=1, nq_max=16, max_wait_ms=0.0,
                          cache_size=16)
    try:
        rng = np.random.default_rng(0)
        a, b = _query(rng, vocab=64), _query(rng, vocab=64)
        fa = eng.search(SearchRequest(*a))
        assert entered.wait(timeout=30)
        fb = eng.search(SearchRequest(*b))  # waits out the rest of a's service
        fa.result(timeout=30), fb.result(timeout=30)
        assert eng.search(SearchRequest(*a)).result(timeout=30).cache_hit  # never queued
        s = eng.stats.summary()
        lat_ms = sum(eng.stats.latencies_ms)
    finally:
        eng.shutdown()
    assert s["requests"] == 3 and s["cache_hits"] == 1 and s["queue_waits"] == 2
    assert s["queue_wait_ms_total"] >= 0.5 * service_s * 1e3
    # each device-scored latency holds its queue wait and then a whole service
    assert lat_ms - s["queue_wait_ms_total"] >= 2 * 0.99 * service_s * 1e3
