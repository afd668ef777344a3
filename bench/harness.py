"""One run of one cell, from set-up to the result line (``run.py`` adds the look for
the chip and the printing).

  set-up   corpus and index from ``bench/.cache`` (built there on a checkout's first
           run), the engine from ``Retriever.from_index(...).serve(...)`` with every
           bucket of the cell's ladder compiled, the run's requests drawn from the
           seed, and a few warm-up queries through the engine
  window   ``--seconds`` of the cell's traffic (``drive``); with ``--trace 1`` under
           the profiler, in a ``bench.window`` span
  after    the device's peak memory read, the engine shut down and the index freed,
           then the plain reference over a sample of the answers (``check``)
"""

from __future__ import annotations

import gc
import math
import resource
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import check, drive, reference, spec, store, tracing
from bench.traffic import make_plan, sample_positions


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a metric reader may read (``bench/metrics/<name>.py``)."""

    cell: str
    config: dict
    traffic: dict
    device_kind: str
    setup_s: float
    window: drive.Window
    n_terms: np.ndarray  # query terms of each stream request
    stats_before: dict  # engine ServeStats.summary() as the window opened
    stats_after: dict  # ... and as it closed
    recall: float
    index_meta: dict  # n_superblocks, postings_per_doc
    trace: list = None  # tracing planes of the window, or None
    trace_span: tuple = None  # (start_ns, end_ns) of the window in the trace

    def served_in_window(self) -> list:
        """Responses that finished inside the window and were scored on the device
        (not served from the result cache)."""
        fin = self.window.finished_in_window()
        return [(i, r) for i, r in enumerate(self.window.responses)
                if fin[i] and r is not None and not r.cache_hit]

    def kernel_seconds(self, names):
        if self.trace is None:
            return None
        ns = tracing.kernel_ns(self.trace, names, *self.trace_span)
        return None if ns is None else ns / 1e9


def static_and_params(query: dict):
    from repro.core.config import DynamicParams, StaticConfig

    static = StaticConfig(variant=query["variant"], gamma=query["gamma"],
                          gamma0=query["gamma0"], k_max=query["k"],
                          doc_layout=query["doc_layout"])
    params = DynamicParams(k=query["k"], mu=query["mu"], eta=query["eta"], beta=query["beta"])
    return static, params


class _GcPauses:
    """Pauses of the process's garbage collector, by generation (a diagnostic for
    host stalls in the window; logged, not a metric)."""

    def __init__(self):
        self.started, self.pauses = None, []

    def callback(self, phase, info):
        if phase == "start":
            self.started = time.perf_counter()
        elif self.started is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self.started))

    def summary(self) -> str:
        by = {g: [p for gg, p in self.pauses if gg == g] for g in (0, 1, 2)}
        return ", ".join(f"gen{g} {len(v)}x max {max(v, default=0) * 1e3:.1f} ms"
                         for g, v in by.items())


def _host_counters() -> dict:
    """Counters of host contention, read as the window opens and closes (logged as
    deltas, a diagnostic for host stalls): this process's major page faults and
    involuntary context switches, and the host's CPU steal ticks."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"major_faults": ru.ru_majflt, "invol_switches": ru.ru_nivcsw}
    try:
        with open("/proc/stat") as f:
            out["steal_ticks"] = int(f.readline().split()[8])
    except (OSError, IndexError, ValueError):
        pass
    return out


def _watch_gc() -> _GcPauses:
    w = _GcPauses()
    gc.callbacks.append(w.callback)
    return w


def _free(tree) -> None:
    import jax

    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()


def execute(bm: dict, cell: str, seed: int, seconds: float, trace: bool, t_start: float,
            config: dict = None, traffic: dict = None, cache: Path = store.CACHE) -> dict:
    """Run ``cell`` once and return its result line (a dict, ``checks`` last).
    ``config``/``traffic`` replace the cell's files (tests run at small sizes)."""
    import jax

    from repro.api import Retriever, SearchRequest

    wl = spec.workload(bm, cell)
    cfg = config or spec.load_json(spec.config_path(wl["config"]))
    tr = traffic or spec.load_json(spec.traffic_path(wl["traffic"]))
    dev = jax.devices()[0]

    log(f"{time.monotonic() - t_start:.2f} s: JAX up, loading corpus and index")
    corpus = store.load_corpus(cfg["corpus"], cache, log)
    index = store.load_index(cfg["corpus"], cfg["index"], corpus, cache, log)
    log(f"{time.monotonic() - t_start:.2f} s: index on the device")
    n_docs = len(corpus.doc_ptr) - 1
    index_meta = {"n_superblocks": index.n_superblocks, "n_blocks": index.n_blocks,
                  "postings_per_doc": len(corpus.tids) / n_docs}
    static, params = static_and_params(cfg["query"])
    retr = Retriever.from_index(index, static, params=params, impl=cfg.get("impl", "auto"))
    t0 = time.perf_counter()
    engine = retr.serve(warmup=True, **tr["engine"])
    log(f"engine {engine.ladder}: warm-up compile {time.perf_counter() - t0:.1f} s")
    try:
        plan = make_plan(tr, cfg["corpus"], corpus, seed, seconds)
        requests = [SearchRequest(t, w) for t, w in plan.stream]
        log(f"{time.monotonic() - t_start:.2f} s: {len(requests)} requests drawn")
        for f in [engine.search(SearchRequest(t, w)) for t, w in plan.warmup]:
            f.result(timeout=600)
        traces0 = retr.n_traces()
        trace_dir = cache / "trace" / f"{cell}-{seed}"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        stats0 = engine.stats.summary()
        stall_log = cache / "stalls" / f"{cell}-{seed}.txt"
        gc_pauses = _watch_gc()
        host0 = _host_counters()
        setup_s = time.monotonic() - t_start
        log(f"window opens after {setup_s:.2f} s of set-up")
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            if tr["loop"] == "open":
                win = drive.open_loop(engine, requests, plan.offsets, seconds, stall_log)
            else:
                win = drive.closed_loop(engine, requests, plan.clients, seconds, stall_log)
        stats1 = engine.stats.summary()
        host1 = _host_counters()
        gc.callbacks.remove(gc_pauses.callback)
        if trace:
            jax.profiler.stop_trace()
        recompiles = retr.n_traces() - traces0
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        engine.shutdown()
    log(f"window: {int(win.sent.sum())} sent, {len(win.errors)} failed, "
        f"{int(win.finished_in_window().sum())} finished inside it; "
        f"{recompiles} compiles inside it; peak device memory {mem} bytes")
    log(f"garbage collections inside the window: {gc_pauses.summary()}")
    log("host counters over the window: "
        + ", ".join(f"{n} +{host1[n] - host0[n]}" for n in host0 if n in host1))
    stalls = stall_log.read_text() if stall_log.exists() else ""
    if stalls:
        log(f"stall watch: stacks of every thread when the sender stalled "
            f"({stall_log}):\n{stalls[:6000]}")
    if len(win.late_s):
        log(f"sender lateness: p50 {np.percentile(win.late_s, 50) * 1e3:.3f} ms, "
            f"p99 {np.percentile(win.late_s, 99) * 1e3:.3f} ms, "
            f"max {win.late_s.max() * 1e3:.3f} ms")
    del engine, retr
    _free(index)
    del index
    gc.collect()

    # ---- correctness, after the window: all answers, then a sample vs the reference
    k = cfg["query"]["k"]
    sent = np.flatnonzero(win.sent)
    ok = [i for i in sent if win.responses[i] is not None]
    unanswered = len(sent) - len(ok)
    ids_all = np.stack([win.responses[i].doc_ids for i in ok]) if ok else np.zeros((0, k), int)
    sc_all = np.stack([win.responses[i].scores for i in ok]) if ok else np.zeros((0, k))
    bad = int(check.malformed(ids_all, sc_all, n_docs).sum())
    finished = np.array([r is not None for r in win.responses])
    n_terms = np.array([len(t) for t, _ in plan.stream])
    longest = int(np.argmax(np.where(finished, n_terms, -1)))
    pos = sample_positions(seed, finished, tr["sample"], longest)
    queries = [plan.stream[i] for i in pos]
    t0 = time.perf_counter()
    ref_ids, ref_scores = reference.exact_topk(corpus, queries, k)
    ids = np.stack([win.responses[i].doc_ids for i in pos])
    scores = np.stack([win.responses[i].scores for i in pos])
    exact = reference.pair_scores(corpus, queries, np.repeat(np.arange(len(pos)), k), ids.ravel())
    err = check.score_err(scores, exact.reshape(ids.shape), ref_scores[:, 0].astype(np.float64))
    rec = check.recall(ids, ref_ids)
    log(f"reference over {len(pos)} answers: {time.perf_counter() - t0:.1f} s; "
        f"recall@{k} {rec:.6f}")
    correct, checks = check.verdict(
        {"unanswered": unanswered, "malformed": bad, "score_err": err,
         "recall_short": 1.0 - rec}, cfg["checks"])

    # ---- metrics
    ctx = Context(cell, cfg, tr, dev.device_kind, setup_s, win, n_terms, stats0, stats1, rec,
                  index_meta)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()), "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        planes = tracing.load(trace_dir)
        try:
            span = tracing.window_ns(planes)
        except ValueError:
            span = (-math.inf, math.inf)
        ctx.trace, ctx.trace_span = planes, span
        busy = tracing.busy_ns(planes, *span) / 1e9
        result_device["busy_s"] = busy
        result_device["window_s"] = (span[1] - span[0]) / 1e9 if math.isfinite(span[0]) else seconds
        breakdown = {"device_ops": tracing.top_ops(planes, *span),
                     "idle_gaps": tracing.idle_gaps(planes, *span)}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(bm, cell, kind):
        v = spec.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": correct, "attempted": int(len(sent)), "failed": int(unanswered),
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out
