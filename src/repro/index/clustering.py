"""Similarity-based block formation (paper §3: "blocks are formed based on similarity,
and each block uniformly contains b documents").

Pipeline (the standard BMP/SP recipe, adapted to run fast in JAX):
  1. random-project sparse docs to a small dense space (d_proj) — k-means over raw
     30k-300k-dim sparse vectors is pointless; a JL projection preserves the cosine
     geometry the clustering needs;
  2. Lloyd k-means with K ~= n_docs / (b*c) (one cluster ~ one superblock's worth);
  3. order documents by (cluster, distance-to-centroid) and chunk uniformly into
     blocks of exactly b docs; c consecutive blocks form a superblock.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

DIST_ROWS = 1 << 14  # rows per task of the k-means++ distance updates


def project_docs(
    doc_ptr: np.ndarray, tids: np.ndarray, ws: np.ndarray, vocab: int, d_proj: int, seed: int
) -> np.ndarray:
    """Sparse CSR docs -> L2-normalized dense [n_docs, d_proj] via random projection."""
    rng = np.random.default_rng(seed)
    proj = rng.standard_normal((vocab, d_proj), dtype=np.float32) / np.sqrt(d_proj)
    n_docs = len(doc_ptr) - 1
    out = np.zeros((n_docs, d_proj), np.float32)
    # segment matmul: out[d] = sum_j ws[j] * proj[tids[j]] for j in doc d, summed in
    # posting order: pass r adds the r-th posting of every doc that has one
    lens = np.diff(doc_ptr)
    for r in range(int(lens.max(initial=0))):
        ds = np.flatnonzero(lens > r)
        j = doc_ptr[ds] + r
        out[ds] += ws[j, None] * proj[tids[j]]
    norms = np.linalg.norm(out, axis=1, keepdims=True)
    return out / np.maximum(norms, 1e-9)


def _kmeans_pp_init(x: np.ndarray, k: int, seed: int) -> np.ndarray:
    """k-means++ seeding (D² sampling): spreads initial centroids, which matters far
    more than extra Lloyd iterations for the block-formation quality (SBMax ranking).

    Host-side: the draws follow one numpy Generator. The distance update of each
    draw, k passes over [n, d] in all, is split over row chunks on a thread pool
    (numpy releases the GIL); each row's arithmetic is the unsplit one."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    cent = np.empty((k, x.shape[1]), np.float32)
    starts = range(0, n, DIST_ROWS)
    with ThreadPoolExecutor() as pool:

        def sq_dist(c):
            rows = pool.map(lambda i: ((x[i : i + DIST_ROWS] - c) ** 2).sum(axis=1), starts)
            return np.concatenate(list(rows))

        cent[0] = x[rng.integers(n)]
        d2 = sq_dist(cent[0])
        for i in range(1, k):
            # float64: Generator.choice requires p to sum to 1 within ~1.5e-8, which
            # accumulated float32 rounding can miss on large corpora
            p = d2.astype(np.float64)
            total = p.sum()
            if total <= 1e-12:  # all points already covered
                cent[i:] = x[rng.integers(n, size=k - i)]
                break
            cent[i] = x[rng.choice(n, p=p / total)]
            d2 = np.minimum(d2, sq_dist(cent[i]))
    return cent


# Bound on the [chunk, k] distance and one-hot matrices of one assignment step, in
# elements (256 MiB of float32 each): the step's peak no longer grows with n*k.
CHUNK_ELEMS = 1 << 26


def _assign(x, cent, k: int, valid):
    """Nearest centroid of each row of x, and the per-cluster counts and sums over
    the rows where ``valid`` holds."""
    hi = jax.lax.Precision.HIGHEST  # f32 matmuls on the TPU too, as on the CPU
    # [n, k] squared distances via |x|^2 - 2 x.c + |c|^2 (|x|^2 constant -> drop)
    d = -2.0 * jnp.dot(x, cent.T, precision=hi) + jnp.sum(cent * cent, axis=1)[None, :]
    assign = jnp.argmin(d, axis=1)
    member = jnp.where(valid, assign, k)  # k: no cluster
    one_hot = jax.nn.one_hot(member, k, dtype=jnp.float32)
    return assign, one_hot.sum(0), jnp.dot(one_hot.T, x, precision=hi)


def kmeans(x: np.ndarray, k: int, iters: int = 8, seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Lloyd iterations (jit'd, on the default device) from a k-means++ seeding.
    Returns (assignments [n], centroids [k, d]).

    The assignment step scans over chunks of at most ``CHUNK_ELEMS // k``
    documents (padded rows join no cluster), so its memory is bounded by the
    chunk and not by n*k."""
    n = x.shape[0]
    rows = max(1, min(n, CHUNK_ELEMS // k))
    n_chunks = -(-n // rows)
    xj = jnp.asarray(np.concatenate([x, np.zeros((n_chunks * rows - n, x.shape[1]), x.dtype)]))
    cent = jnp.asarray(_kmeans_pp_init(x, k, seed))
    valid = jnp.asarray(np.arange(n_chunks * rows) < n).reshape(n_chunks, rows)

    @jax.jit
    def step(cent, xj, valid):
        def body(acc, chunk):
            xc, ok = chunk
            a, counts, sums = _assign(xc, cent, k, ok)
            return (acc[0] + counts, acc[1] + sums), a

        zero = (jnp.zeros((k,), jnp.float32), jnp.zeros(cent.shape, jnp.float32))
        (counts, sums), assign = jax.lax.scan(body, zero, (xj.reshape(n_chunks, rows, -1), valid))
        new_cent = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep empty clusters where they were
        new_cent = jnp.where(counts[:, None] > 0, new_cent, cent)
        return new_cent, assign.reshape(-1)[:n]

    assign = None
    for _ in range(iters):
        cent, assign = step(cent, xj, valid)
    return np.asarray(assign), np.asarray(cent)


def chain_order(cent: np.ndarray) -> np.ndarray:
    """Greedy nearest-neighbour chain over centroids -> rank per cluster id.

    Cluster ids out of k-means are arbitrary, but the uniform b-doc chunking makes
    blocks (and superblocks) straddle cluster boundaries — adjacent clusters in the
    doc order should therefore be *similar* clusters, or the straddling blocks get
    envelope bounds over unrelated regions and the SBMax ranking degrades.
    """
    k = len(cent)
    left = np.ones(k, bool)
    chain = np.empty(k, np.int64)
    cur = 0
    for i in range(k):
        chain[i] = cur
        left[cur] = False
        if i + 1 == k:
            break
        d = ((cent - cent[cur]) ** 2).sum(axis=1)
        d[~left] = np.inf
        cur = int(np.argmin(d))
    rank = np.empty(k, np.int64)
    rank[chain] = np.arange(k)
    return rank


def block_order(
    doc_ptr: np.ndarray,
    tids: np.ndarray,
    ws: np.ndarray,
    vocab: int,
    b: int,
    c: int,
    d_proj: int = 64,
    kmeans_iters: int = 8,
    seed: int = 0,
) -> np.ndarray:
    """Return doc_remap: position -> original doc id, similarity-ordered, padded to a
    multiple of b*c with repeats of the last doc masked out downstream by remap >= n."""
    n_docs = len(doc_ptr) - 1
    x = project_docs(doc_ptr, tids, ws, vocab, d_proj, seed)
    k = max(1, int(np.ceil(n_docs / (b * c))))
    if n_docs <= b:  # degenerate tiny corpus
        order = np.arange(n_docs)
    else:
        assign, cent = kmeans(x, k, iters=kmeans_iters, seed=seed)
        dist = np.einsum("nd,nd->n", x - cent[assign], x - cent[assign])
        order = np.lexsort((dist, chain_order(cent)[assign]))
    pad = (-n_docs) % (b * c)
    # pad positions point past n_docs (sentinel empty docs)
    return np.concatenate([order, np.full(pad, n_docs, np.int64)]).astype(np.int32)
