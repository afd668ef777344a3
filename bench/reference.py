"""The plain reference: exact top-k by brute force over the corpus the benchmark made.

It imports nothing of the program and takes nothing the program made: it reads the
corpus's float32 term weights (``bench.corpus``), not the index's quantized copy.

  exact_topk   for each query, the k documents of largest dot product with it,
               (score desc, doc id asc). Each chunk of documents is laid out as a
               dense [docs, vocab] float32 matrix on the device and multiplied by
               the dense queries at ``Precision.HIGHEST``; a bisection on the
               scores' bits finds each query's k-th score exactly.
  pair_scores  the exact (float64, on the host) dot product of given (query, doc)
               pairs, for checking the score the program returned with each doc.
"""

from __future__ import annotations

import numpy as np

from bench.corpus import Corpus


def dense_queries(queries, vocab: int) -> np.ndarray:
    q = np.zeros((len(queries), vocab), np.float32)
    for i, (t, w) in enumerate(queries):
        q[i, t] = w  # query terms are distinct
    return q


def _chunks(corpus: Corpus, chunk: int):
    """Postings of each chunk of ``chunk`` docs as (doc row in chunk, term, weight)
    arrays padded to one length (padding: row 0, term 0, weight 0)."""
    n_docs = len(corpus.doc_ptr) - 1
    n_chunks = -(-n_docs // chunk)
    starts = corpus.doc_ptr[np.minimum(np.arange(n_chunks + 1) * chunk, n_docs)]
    width = int(np.diff(starts).max())
    rows = np.zeros((n_chunks, width), np.int32)
    tids = np.zeros((n_chunks, width), np.int32)
    ws = np.zeros((n_chunks, width), np.float32)
    doc_of = np.repeat(np.arange(n_docs), np.diff(corpus.doc_ptr))
    for c in range(n_chunks):
        lo, hi = starts[c], starts[c + 1]
        rows[c, : hi - lo] = doc_of[lo:hi] - c * chunk
        tids[c, : hi - lo] = corpus.tids[lo:hi]
        ws[c, : hi - lo] = corpus.ws[lo:hi]
    return rows, tids, ws


def exact_topk(corpus: Corpus, queries, k: int, chunk: int = 8192):
    """(ids int64 [Q, k], scores float32 [Q, k]) of the exact top-k."""
    import jax
    import jax.numpy as jnp

    n_docs, vocab = len(corpus.doc_ptr) - 1, corpus.vocab
    rows, tids, ws = _chunks(corpus, chunk)
    nq = len(queries)

    @jax.jit
    def run(qd, rows, tids, ws):
        def step(carry, xs):
            best_s, best_i = carry
            c, r, t, w = xs
            docs = jnp.zeros((chunk, vocab), jnp.float32).at[r, t].add(w)
            s = jnp.dot(qd, docs.T, precision=jax.lax.Precision.HIGHEST)
            ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
            s = jnp.where(ids[None, :] < n_docs, s, -jnp.inf)
            cand_s = jnp.concatenate([best_s, s], axis=1)
            cand_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (nq, chunk))], axis=1)
            # (score desc, id asc): ids ascend along the candidates (the kept
            # ones come from earlier chunks), so a stable sort keeps that order
            order = jnp.argsort(-cand_s, axis=1, stable=True)[:, :k]
            return (jnp.take_along_axis(cand_s, order, 1),
                    jnp.take_along_axis(cand_i, order, 1)), None

        init = (jnp.full((nq, k), -jnp.inf, jnp.float32), jnp.full((nq, k), -1, jnp.int32))
        xs = (jnp.arange(rows.shape[0], dtype=jnp.int32), rows, tids, ws)
        return jax.lax.scan(step, init, xs)[0]

    s, i = run(jnp.asarray(dense_queries(queries, vocab)), jnp.asarray(rows), jnp.asarray(tids),
               jnp.asarray(ws))
    return np.asarray(i).astype(np.int64), np.asarray(s)


def pair_scores(corpus: Corpus, queries, q_idx: np.ndarray, doc_ids: np.ndarray,
                block: int = 1 << 16) -> np.ndarray:
    """Exact float64 scores of (queries[q_idx[j]], doc_ids[j]); a doc id outside the
    corpus scores nan. Pairs are taken ``block`` at a time."""
    n_docs = len(corpus.doc_ptr) - 1
    q_idx = np.asarray(q_idx, np.int64).ravel()
    d = np.asarray(doc_ids, np.int64).ravel()
    qd = dense_queries(queries, corpus.vocab)
    out = np.full(len(d), np.nan)
    for lo in range(0, len(d), block):
        qb, db = q_idx[lo : lo + block], d[lo : lo + block]
        valid = (db >= 0) & (db < n_docs)
        dv = np.where(valid, db, 0)
        lens = np.where(valid, corpus.doc_ptr[dv + 1] - corpus.doc_ptr[dv], 0)
        pair = np.repeat(np.arange(len(db)), lens)
        off = np.arange(lens.sum()) - np.repeat(np.cumsum(lens) - lens, lens)
        post = corpus.doc_ptr[dv][pair] + off
        contrib = qd[qb[pair], corpus.tids[post]].astype(np.float64) * corpus.ws[post]
        sums = np.bincount(pair, weights=contrib, minlength=len(db))
        out[lo : lo + block] = np.where(valid, sums, np.nan)
    return out
