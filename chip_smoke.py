"""Run the served retrieval path once on a TPU, at a real corpus size.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: shard_map over model=4 vs one device

The deployment: a synthetic learned-sparse corpus, generated from ``--seed``, of
524,288 documents (the size of BEIR's larger sets; Quora has 523k) over the
30,522-term BERT wordpiece vocabulary that SPLADE-family encoders emit, with
``CorpusConfig``'s document and query lengths. It is indexed on the device with the
paper's k=10 geometry (``configs.lsp_msmarco.INDEX_K10``: b=16, c=16, 4-bit bounds,
8-bit docs).

One chip: the index is served through ``Retriever.from_index(...).serve(warmup=True)``
(the 'local' backend, ``impl="auto"``). 64 queries at k=10 go through the engine;
every future must resolve without error, the served program must hold the compiled
Pallas kernels (no interpret mode, no jnp reference), the engine must count no
failure, and recall@10 against the exact oracle (``core.exact.retrieve_exact``
behind the 'exact' backend) on the same queries must reach ``RECALL_FLOOR``.

Four chips: the same corpus served by the shard_map transport over a model=4 mesh,
its stacked shards placed once over the mesh, and by one device. The doc ids and
scores of the two must be bit-identical.

The script needs a TPU: it fails, printing no result, where JAX's default backend is
anything else. It runs in one process. Its last output line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

N_DOCS = 524_288
VOCAB = 30_522
N_QUERIES = 64
K = 10
NQ = 64  # query terms per compiled bucket (the engine's nq rung)
BATCH = 8  # the engine's batch rung
# Recall@10 of the served path against the exact oracle must reach this. The same
# seed and geometry, rehearsed on the CPU with the jnp reference path (which the
# kernels match bit for bit) at 1/8 and 1/4 of the corpus, with γ scaled to the same
# share of superblocks, gave 0.833 and 0.916; the floor leaves room for the spread of
# 64 queries and for k-means run on the chip.
RECALL_FLOOR = 0.75
KERNELS = ("sbmax", "boundsum_gather", "doc_score_fwd")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_summary(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def array_bytes(tree) -> int:
    """Bytes of the device arrays in a pytree."""
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array))


def make_deployment(seed: int, n_docs: int = N_DOCS):
    """(index, queries): the corpus generated from ``seed`` and indexed on the
    default device, and the queries drawn from it."""
    import jax

    from repro.configs.lsp_msmarco import INDEX_K10
    from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
    from repro.index.builder import build_index

    ccfg = CorpusConfig(n_docs=n_docs, vocab=VOCAB, seed=seed)
    t0 = time.perf_counter()
    corpus = make_corpus(ccfg)
    queries = make_queries(ccfg, corpus, N_QUERIES, seed=seed + 1)
    t1 = time.perf_counter()
    log(f"corpus: {n_docs} docs, {len(corpus.tids)} postings, vocab {VOCAB}, "
        f"{N_QUERIES} queries ({t1 - t0:.1f} s on the host)")
    longest = max(len(t) for t, _ in queries)
    assert longest <= NQ, f"a query has {longest} terms, more than the {NQ}-term bucket"
    index = jax.block_until_ready(
        build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab, INDEX_K10)
    )
    log(f"index: {index.n_blocks} blocks, {index.n_superblocks} superblocks, "
        f"{array_bytes(index)} bytes on the device, built in {time.perf_counter() - t1:.1f} s")
    return index, queries


def served_kernels(run, shapes) -> dict:
    """Which Pallas kernels the compiled program of one bucket holds, by name."""
    text = run.lower(*shapes).as_text()
    return {name: name in text for name in KERNELS} | {
        "tpu_custom_calls": text.count("tpu_custom_call")
    }


def bucket_shapes(vocab: int):
    import numpy as np

    d = np.ones(BATCH, np.float32)
    return (
        np.full((BATCH, NQ), vocab, np.int32), np.zeros((BATCH, NQ), np.float32),
        np.full(BATCH, K, np.int32), d, d, d,
    )


def one_chip(jax, seed: int, n_docs: int = N_DOCS) -> None:
    import numpy as np

    from repro.api import Retriever, SearchRequest
    from repro.core import ops
    from repro.eval.metrics import recall_vs_oracle

    index, queries = make_deployment(seed, n_docs)
    retr = Retriever.from_index(index)
    log(f"retriever: {retr}")
    log("impl per op under impl='auto': "
        + ", ".join(f"{op}={ops.kernel_mode('auto')}" for op in KERNELS))

    t0 = time.perf_counter()
    engine = retr.serve(
        warmup=True, max_batch=BATCH, batch_buckets=[BATCH], nq_max=NQ, nq_buckets=[NQ],
        cache_size=0,
    )
    log(f"warmup (compile of {engine.ladder}): {time.perf_counter() - t0:.1f} s, "
        f"{retr.n_traces()} trace(s)")
    try:
        kernels = served_kernels(engine.retriever, bucket_shapes(retr.vocab))
        log(f"served program kernels: {kernels}")
        missing = [name for name in KERNELS if not kernels[name]]
        assert not missing, f"the served program lacks the compiled kernels {missing}"

        requests = [SearchRequest(t, w) for t, w in queries]
        t0 = time.perf_counter()
        futures = [engine.search(r) for r in requests]
        responses, errors = [], []
        for f in futures:
            try:
                responses.append(f.result(timeout=600))
            except Exception as e:  # noqa: BLE001 - every failure is reported
                errors.append(repr(e))
        log(f"served {len(responses)}/{len(requests)} queries in "
            f"{time.perf_counter() - t0:.2f} s, {len(errors)} failed futures")
        assert not errors, f"futures failed: {errors[:3]}"
        stats = engine.stats.summary()
        log(f"engine: failures={stats['failures']} batches={stats.get('batches')} "
            f"traces={retr.n_traces()}")
        assert stats["failures"] == 0, f"engine counted {stats['failures']} failures"
    finally:
        engine.shutdown()

    oracle = Retriever.from_index(index, retr.static_cfg, backend="exact")
    t0 = time.perf_counter()
    exact = oracle.search_batch(requests)
    log(f"exact oracle: {time.perf_counter() - t0:.1f} s (compile included)")
    ids = np.stack([r.doc_ids for r in responses])
    recall = recall_vs_oracle(ids, np.stack([r.doc_ids for r in exact]))
    visited = float(np.mean([r.n_superblocks_visited for r in responses]))
    log(f"recall@{K} vs exact: {recall:.4f} (floor {RECALL_FLOOR}); superblocks visited "
        f"{visited:.1f} / {index.n_superblocks}")
    assert recall >= RECALL_FLOOR, f"recall@{K} {recall:.4f} < floor {RECALL_FLOOR}"
    mem = jax.devices()[0].memory_stats() or {}
    log(f"device memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_in_use={mem.get('bytes_in_use')}")


def search_in_batches(retr, requests) -> list:
    """``requests`` through ``retr.search_batch``, BATCH at a time (the served bucket)."""
    return [r for i in range(0, len(requests), BATCH) for r in retr.search_batch(requests[i : i + BATCH])]


def four_chips(jax, seed: int, n_docs: int = N_DOCS) -> None:
    import numpy as np

    from repro.api import Retriever, SearchRequest
    from repro.distributed.retrieval import shard_index, to_host
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    assert n == 4, f"--chips 4 needs four devices, JAX sees {n}"
    index, queries = make_deployment(seed, n_docs)
    requests = [SearchRequest(t, w) for t, w in queries]
    single = Retriever.from_index(index)
    scfg, ns = single.static_cfg, index.n_superblocks
    t0 = time.perf_counter()
    one = search_in_batches(single, requests)
    log(f"single device: {time.perf_counter() - t0:.1f} s (compile included)")
    # the shards go to the host; the single-device index leaves device 0, so the
    # bytes below are what the shard_map retriever places on each device
    shards = [to_host(s) for s in shard_index(index, n)]
    del single, index
    gc.collect()

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()]

    before = in_use()
    mesh = make_host_mesh(model=n)
    sharded = Retriever.from_index(shards, scfg, mesh=mesh, ns_true=ns)
    assert sharded.backend_name == "shard_map", sharded.backend_name
    after = in_use()
    log(f"bytes_in_use per device before the shard_map retriever: {before}")
    log(f"bytes_in_use per device after: {after} "
        f"(added: {[a - b for a, b in zip(after, before)]})")
    t0 = time.perf_counter()
    many = search_in_batches(sharded, requests)
    log(f"shard_map over model={n}: {time.perf_counter() - t0:.1f} s (compile included)")
    ids1 = np.stack([r.doc_ids for r in one])
    ids4 = np.stack([r.doc_ids for r in many])
    same_ids = np.array_equal(ids1, ids4)
    same_scores = np.array_equal(
        np.stack([r.scores for r in one]), np.stack([r.scores for r in many])
    )
    log(f"shard_map vs single device: doc ids identical={same_ids}, "
        f"scores identical={same_scores} over {len(requests)} queries")
    assert same_ids and same_scores, "shard_map results differ from the single device"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX's default backend is {backend!r}", file=sys.stderr)
        return 1
    device = device_summary(jax)
    log(f"device: {device}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    log(f"compile cache: {cache}")
    (four_chips if args.chips == 4 else one_chip)(jax, args.seed)
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {entries} entries in {cache}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
