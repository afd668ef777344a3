"""Kernel work counts at known shapes, the percentile arithmetic, the peaks table and
the check's numbers."""

import math

import numpy as np
import pytest

from bench import check, costs, peaks
from bench.stats import percentile


def test_sbmax_work_counts_pruned_terms_times_superblocks():
    # ceil(0.33*24)=8 and ceil(0.33*10)=4 terms; 2,048 superblocks of 4 bits
    ops, nbytes = costs.sbmax_work([24, 10], 0.33, 2048, 4)
    assert ops == 2 * 12 * 2048
    assert nbytes == 12 * 2048 / 2


def test_doc_score_work_counts_postings_of_scored_blocks():
    # 100 + 300 blocks of 16 docs with 45 postings each, 8-bit weights
    ops, nbytes = costs.doc_score_work([100, 300], 16, 45.0, 8)
    postings = 400 * 16 * 45
    assert ops == 2 * postings
    assert nbytes == postings * 5 + 4 * 400


def test_least_seconds_takes_the_larger_bound():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["flops_per_s"] == 197e12
    assert peaks.least_seconds(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.least_seconds(197e12, 1, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_percentile_is_over_all_requests_with_failures_last():
    lat = np.arange(1, 101, dtype=float)  # 1..100 ms
    assert percentile(lat, 50) == pytest.approx(np.percentile(lat, 50))
    assert percentile(lat, 99) == pytest.approx(np.percentile(lat, 99))
    # two failures among 100: the p99 lands between two missing requests
    with_fail = np.concatenate([lat[:98], [math.inf, math.inf]])
    assert percentile(with_fail, 99) == math.inf
    assert percentile(with_fail, 50) == pytest.approx(np.percentile(lat[:98].tolist() + [1e9, 1e9], 50))
    one_fail = np.concatenate([lat[:99], [math.inf]])
    assert percentile(one_fail, 99) == math.inf  # interpolation toward a missing one


def test_check_numbers():
    ids = np.array([[3, 1, 2], [1, 1, 2], [0, 5, -1], [4, 3, 2]])
    sc = np.array([[3.0, 2.0, 1.0], [3.0, 2.0, 1.0], [3.0, 2.0, 1.0], [1.0, 2.0, 0.5]])
    assert check.malformed(ids, sc, n_docs=5).tolist() == [False, True, True, True]
    exact = np.array([[3.0, 2.0, 1.1]])
    assert check.score_err(np.array([[3.0, 2.0, 1.0]]), exact, np.array([4.0])) == pytest.approx(0.025)
    assert check.score_err(np.array([[3.0]]), np.array([[np.nan]]), np.array([4.0])) == math.inf
    assert check.recall(np.array([[1, 2, 3]]), np.array([[3, 2, 9]])) == pytest.approx(2 / 3)
    ok, out = check.verdict({"a": 0, "b": 0.5}, {"a": 0, "b": 0.4})
    assert not ok and out == {"a": {"value": 0, "limit": 0}, "b": {"value": 0.5, "limit": 0.4}}
