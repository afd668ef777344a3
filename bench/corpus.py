"""The benchmark's data: a synthetic learned-sparse corpus and its queries.

A copy of the generator in ``repro.data.synthetic`` (which the program keeps for its
own tests), held here so that the yardstick does not move when the program does. It
reproduces the statistics that matter to the algorithm: Zipfian term frequencies,
log-normal term weights, topical clusterability (so similarity-based block formation
has signal), and SPLADE-like document and query lengths. ``make_corpus`` draws the
same corpus as the original for the same configuration. ``make_queries`` keeps the
original's recipe (half the terms from a random document of the query's topic, half
from the Zipfian background) but draws them without the per-query scans over the
corpus and the vocabulary, so a pool of thousands of queries takes well under a
second; it is not stream-identical to the original.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

CORPUS_KEYS = (
    "n_docs", "vocab", "n_topics", "doc_len_mean", "query_len_mean",
    "topic_concentration", "seed",
)


class Corpus(NamedTuple):
    doc_ptr: np.ndarray  # int64 [n_docs+1]
    tids: np.ndarray  # int32 [nnz]
    ws: np.ndarray  # float32 [nnz]
    vocab: int
    doc_topic: np.ndarray  # int32 [n_docs]


def _zipf_probs(v: int, a: float = 1.07) -> np.ndarray:
    p = 1.0 / np.arange(1, v + 1) ** a
    return p / p.sum()


def make_corpus(cfg: dict) -> Corpus:
    """The corpus of a configuration's ``corpus`` section (keys ``CORPUS_KEYS``)."""
    n_docs, vocab, n_topics = cfg["n_docs"], cfg["vocab"], cfg["n_topics"]
    rng = np.random.default_rng(cfg["seed"])
    base = _zipf_probs(vocab)[rng.permutation(vocab)]
    # each topic boosts a random subset of terms
    topic_terms = rng.integers(0, vocab, size=(n_topics, max(vocab // 32, 8)))

    doc_topic = rng.integers(0, n_topics, n_docs).astype(np.int32)
    lens = np.clip(rng.poisson(cfg["doc_len_mean"], n_docs), 4, None).astype(np.int64)
    ptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lens, out=ptr[1:])
    nnz = int(ptr[-1])

    n_topical = (lens * cfg["topic_concentration"]).astype(np.int64)
    # background terms for every slot, then the topical slots overwritten
    tids = rng.choice(vocab, size=nnz, p=base).astype(np.int32)
    slot_doc = np.repeat(np.arange(n_docs), lens)
    topical = (np.arange(nnz) - ptr[slot_doc]) < n_topical[slot_doc]
    rows = doc_topic[slot_doc[topical]]  # one topic-term draw per topical slot
    tids[topical] = topic_terms[rows, rng.integers(0, topic_terms.shape[1], rows.shape[0])]

    ws = rng.lognormal(mean=0.0, sigma=0.7, size=nnz).astype(np.float32)
    # one weight per (doc, term): keep the largest
    key = slot_doc.astype(np.int64) * vocab + tids
    order = np.lexsort((-ws, key))
    key_s, ws_s = key[order], ws[order]
    first = np.ones(nnz, bool)
    first[1:] = key_s[1:] != key_s[:-1]
    key_u, ws_u = key_s[first], ws_s[first]
    doc_u = key_u // vocab
    new_ptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(np.bincount(doc_u, minlength=n_docs), out=new_ptr[1:])
    return Corpus(new_ptr, (key_u % vocab).astype(np.int32), ws_u.astype(np.float32),
                  vocab, doc_topic)


def make_queries(cfg: dict, corpus: Corpus, n_queries: int, seed: int) -> list:
    """``n_queries`` queries [(tids int32, weights float32)], each with distinct
    terms, sharing the corpus's topical structure (so pruning sees the same
    bound-tightness regime as Figure 1 of the paper)."""
    rng = np.random.default_rng(seed)
    n_topics, vocab = cfg["n_topics"], cfg["vocab"]
    cdf = np.cumsum(_zipf_probs(vocab))
    by_topic = np.argsort(corpus.doc_topic, kind="stable")
    topic_ptr = np.searchsorted(corpus.doc_topic[by_topic], np.arange(n_topics + 1))
    out = []
    for _ in range(n_queries):
        topic = int(rng.integers(0, n_topics))
        ln = max(4, int(rng.poisson(cfg["query_len_mean"])))
        lo, hi = topic_ptr[topic], topic_ptr[topic + 1]
        d = by_topic[rng.integers(lo, hi)] if hi > lo else rng.integers(0, len(corpus.doc_ptr) - 1)
        dts = corpus.tids[corpus.doc_ptr[d] : corpus.doc_ptr[d + 1]]
        n_top = min(ln // 2, len(dts))
        t_topical = rng.choice(dts, n_top, replace=False) if n_top else np.empty(0, np.int32)
        t_bg = np.minimum(np.searchsorted(cdf, rng.random(ln - n_top)), vocab - 1)
        t = np.unique(np.concatenate([t_topical, t_bg]).astype(np.int32))
        out.append((t, rng.lognormal(0.0, 0.7, len(t)).astype(np.float32)))
    return out
