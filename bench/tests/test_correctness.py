"""The comparison that decides ``correct``, driven through a whole run at a small
size on the CPU (the harness's look for a chip is the only part skipped): the program
as configured passes; its lower-precision control (4-bit document weights, the
configuration's ``control``) and a timed path broken underneath fail.

The faults a served cell can have: half of each batch left out (its rows answered
with the other half's results), an answer altered where it is produced (each row's
first document id moved by one), and a top-k merge that returns the wrong ranks
(ranks k+1..2k of the traversal's order, each with its true score, so only
``recall_short`` can see it). A step that returns its state unchanged and the
exchange between chips do not exist in these one-chip serving cells.
"""

import dataclasses
import time

import jax.numpy as jnp
import pytest

from bench import harness, spec

SECONDS = 1.5


def _tiny(cell):
    bm = spec.load_benchmark()
    wl = spec.workload(bm, cell)
    cfg = spec.load_json(spec.config_path(wl["config"]))
    cfg["corpus"].update(n_docs=4096, vocab=1024, n_topics=8)
    cfg["index"].update(b=8, c=8, lane_pad=8, kmeans_iters=2)
    cfg["query"].update(k=min(cfg["query"]["k"], 20), gamma=24, gamma0=4)
    tr = {"loop": "closed", "clients": 8, "pool_per_second": 4000, "sample": 24,
          "warmup_queries": 2,
          "engine": {"max_batch": 4, "batch_buckets": [4], "nq_max": 128, "nq_buckets": [128],
                     "max_wait_ms": 2.0, "cache_size": 1024}}
    return bm, cfg, tr


def _run(cell, tmp_path, cfg=None):
    bm, base, tr = _tiny(cell)
    return harness.execute(bm, cell, 2**33 + 5, SECONDS, False, time.monotonic(),
                           config=cfg or base, traffic=tr, cache=tmp_path)


CELLS = ["msmarco-k10.open", "msmarco-k1000.closed"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_as_configured_is_correct(cell, tmp_path):
    out = _run(cell, tmp_path)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(cell, tmp_path):
    _, cfg, _ = _tiny(cell)
    cfg["index"].update(cfg["control"]["index"])
    out = _run(cell, tmp_path, cfg)
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > out["checks"]["score_err"]["limit"]


def _half_batch(ids, scores, real):
    # rows [h, real) answered with rows [0, real - h): half the batch left out
    h = (real + 1) // 2
    src = jnp.where(jnp.arange(ids.shape[0]) < h, jnp.arange(ids.shape[0]),
                    jnp.arange(ids.shape[0]) - h)
    return ids[src], scores[src]


def _altered(ids, scores, real):
    return ids.at[:, 0].set(ids[:, 0] + 1), scores


def _wrap(real_search, fault):
    """A ``backends.jit_search`` whose served answers pass through ``fault``."""

    def broken(index, scfg, impl="auto", defaults=None):
        run = real_search(index, scfg, impl=impl, defaults=defaults)

        def served(qb, dyn=None):
            out = run(qb, dyn)
            n_real = int((qb.tids < qb.vocab).any(axis=1).sum())
            ids, scores = fault(out.doc_ids, out.scores, n_real)
            return out._replace(doc_ids=ids, scores=scores)

        for attr in ("warmup", "lower", "n_traces", "supports_dynamic", "static_cfg",
                     "defaults", "vocab"):
            setattr(served, attr, getattr(run, attr))
        return served

    return broken


def _topk_shift(real_search):
    """A ``backends.jit_search`` that runs the traversal for the top 2k and serves its
    ranks k+1..2k as the top k."""
    from repro.core.config import DynamicParams

    def broken(index, scfg, impl="auto", defaults=None):
        k = scfg.k_max
        run = real_search(index, dataclasses.replace(scfg, k_max=2 * k), impl=impl,
                          defaults=DynamicParams(k=2 * k))

        def served(qb, dyn=None):
            out = run(qb, None)
            return out._replace(doc_ids=out.doc_ids[:, k:], scores=out.scores[:, k:])

        for attr in ("warmup", "lower", "n_traces", "supports_dynamic", "vocab"):
            setattr(served, attr, getattr(run, attr))
        served.static_cfg, served.defaults = scfg, defaults
        return served

    return broken


@pytest.mark.parametrize("fault", [_half_batch, _altered], ids=["half_batch", "altered"])
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.api import backends

    monkeypatch.setattr(backends, "jit_search", _wrap(backends.jit_search, fault))
    out = _run("msmarco-k10.open", tmp_path)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_wrong_ranks_from_top_k_are_not_correct(cell, tmp_path, monkeypatch):
    from repro.api import backends

    monkeypatch.setattr(backends, "jit_search", _topk_shift(backends.jit_search))
    out = _run(cell, tmp_path)
    checks = out["checks"]
    assert not out["correct"]
    assert checks["recall_short"]["value"] > checks["recall_short"]["limit"], checks
    assert checks["score_err"]["value"] <= checks["score_err"]["limit"], checks
