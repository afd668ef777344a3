"""The one traffic generator: a traffic file of parameters -> the requests of a run.

A traffic mix is a JSON file ``bench/traffic/<name>.json`` with the keys:

  loop             "open" (send at fixed times) or "closed" (clients wait for answers)
  rate_qps         open loop: requests per second offered
  clients          closed loop: clients, each with one request in flight
  pool_per_second  closed loop: distinct queries drawn per second of window (an upper
                   bound on the rate; a run that uses them all up fails)
  pool             optional {"size": n, "zipf_s": s}: draw requests (and the warm-up
                   queries) from n distinct queries by Zipf(s) rank instead of
                   sending every query once
  sample           requests compared with the reference after the window
  warmup_queries   queries sent through the engine before the window opens
  engine           keyword arguments of ``Retriever.serve`` (batch/nq ladder, wait,
                   result-cache size)

The queries a run sends, and their order, are drawn here from ``--seed``. An open
loop's send times are not: it draws ``round(rate_qps * seconds)`` of them uniformly
over the window (a Poisson process conditioned on its count) from one fixed stream,
so every seed offers the same schedule and the same amount of work, and seeds differ
only in which queries arrive when. A tail read at a fixed load then carries no
spread from one seed's arrivals being burstier than another's; what spread is left
is the system's own from run to run.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from bench.corpus import Corpus, make_queries

# sub-streams of one --seed
_QUERIES, _ARRIVALS, _PICKS, _WARMUP, _SAMPLE = range(5)
_SCHEDULE_SEED = 0  # the open-loop send times' stream, the same for every --seed


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


def _query_seed(seed: int, stream: int) -> int:
    return int(np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])
               .generate_state(1)[0])


@dataclass
class Plan:
    """What one run sends. ``stream[i]`` is the i-th request's query; an open loop
    sends it ``offsets[i]`` seconds after the window opens, a closed loop when a
    client is free (``offsets`` is None)."""

    stream: list
    offsets: Optional[np.ndarray]
    clients: int
    warmup: list
    seconds: float


def n_requests(traffic: dict, seconds: float) -> int:
    if traffic["loop"] == "open":
        return int(round(traffic["rate_qps"] * seconds))
    return int(traffic["pool_per_second"] * seconds)


def make_plan(traffic: dict, corpus_cfg: dict, corpus: Corpus, seed: int, seconds: float) -> Plan:
    n = n_requests(traffic, seconds)
    n_warm = traffic.get("warmup_queries", 0)
    pool_cfg = traffic.get("pool", {})
    size = pool_cfg.get("size", 0)
    if size:  # repeated queries: picks from a fixed pool by Zipf rank
        pool = make_queries(corpus_cfg, corpus, size, _query_seed(seed, _QUERIES))
        ranks = np.arange(1, size + 1, dtype=np.float64)
        p = ranks ** -float(pool_cfg["zipf_s"])
        p /= p.sum()
        stream = [pool[i] for i in rng(seed, _PICKS).choice(size, n, p=p)]
        # warm-up draws from the same pool, so the window finds a warm cache
        warmup = [pool[i] for i in rng(seed, _WARMUP).choice(size, n_warm, p=p)]
    else:  # every request a distinct query
        stream = make_queries(corpus_cfg, corpus, n, _query_seed(seed, _QUERIES))
        warmup = make_queries(corpus_cfg, corpus, n_warm, _query_seed(seed, _WARMUP))
    offsets = None
    if traffic["loop"] == "open":
        offsets = np.sort(rng(_SCHEDULE_SEED, _ARRIVALS).uniform(0.0, seconds, n))
    return Plan(stream, offsets, int(traffic.get("clients", 0)), warmup, float(seconds))


def sample_positions(seed: int, finished: np.ndarray, size: int, longest: int) -> np.ndarray:
    """Stream positions of the requests compared with the reference: ``size`` of
    the finished ones drawn from the seed, always with ``longest`` (the position of
    the request with the most query terms) among them when it finished."""
    fin = np.flatnonzero(finished)
    order = rng(seed, _SAMPLE).permutation(fin)
    picked = list(order[:size])
    if finished[longest] and longest not in picked:
        picked[-1] = longest
    return np.sort(np.asarray(picked, np.int64))
