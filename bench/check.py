"""The comparison that decides ``correct``.

After the window has closed, every request that was due in it has been waited for.
Four numbers are compared, each with its own limit from the configuration file
(``checks``):

  unanswered  requests of the window whose future failed or never resolved (limit 0)
  malformed   answers, of all requests served, that are not k distinct document ids
              of the corpus with scores in non-increasing order (limit 0)
  score_err   over a sample of answers drawn from the seed, the widest gap between
              the score the program returned with a document and the exact float64
              dot product of the query with that document (``reference.pair_scores``),
              as a share of the query's exact best score. A wrong document, a
              document returned for another query's scores, a wrong kernel result
              and a lower-precision copy of the documents all widen it.
  recall_short  over the same sample, 1 - recall@k against the reference's exact
              top-k: the share of the exact top-k that the answers miss. Correctly
              scored but wrong documents widen it: a top-k merge that returns the
              wrong ranks, a traversal that skips the superblocks it should score.

The same recall is the end-to-end metric ``recall_at_k``, whose bound holds the
small losses that this limit, set for faults, lets pass.
"""

from __future__ import annotations

import numpy as np


def malformed(ids: np.ndarray, scores: np.ndarray, n_docs: int) -> np.ndarray:
    """Per row of [R, k] answers: True where the row is not a well-formed top-k."""
    ids = np.asarray(ids)
    bad = (ids < 0).any(1) | (ids >= n_docs).any(1)
    srt = np.sort(ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(1)
    bad |= (np.diff(np.asarray(scores, np.float64), axis=1) > 0).any(1)
    return bad


def score_err(served_scores: np.ndarray, exact_scores: np.ndarray, best: np.ndarray) -> float:
    """max |served - exact| / best over [S, k] pairs (best: [S] exact top-1 scores);
    a pair whose document is not in the corpus (exact nan) reads inf."""
    gap = np.abs(np.asarray(served_scores, np.float64) - exact_scores)
    gap = np.where(np.isnan(gap), np.inf, gap) / np.maximum(best, 1e-30)[:, None]
    return float(gap.max()) if gap.size else 0.0


def recall(served_ids: np.ndarray, ref_ids: np.ndarray) -> float:
    """Mean over rows of |served ∩ reference| / k."""
    k = ref_ids.shape[1]
    hits = [len(np.intersect1d(a, b)) for a, b in zip(served_ids, ref_ids)]
    return float(np.mean(hits) / k)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number is at or
    under its limit."""
    out = {n: {"value": numbers[n], "limit": limits[n]} for n in limits}
    return all(v["value"] <= v["limit"] for v in out.values()), out
