"""Retrieval serving launcher: build (or load) an LSP index over a corpus and serve
batched queries through the unified ``repro.api`` surface — one facade, typed
requests/responses, bucketed engine (shape-bucket ladder + result cache +
resilient pipeline, DESIGN.md §6) with latency percentiles.

With ``--index-dir`` the launcher uses the persisted-index lifecycle (DESIGN.md §7):
a committed index under that directory is mmap-loaded (milliseconds) instead of
rebuilt; a fresh build is saved there for the next start. ``--swap-mid-run``
demonstrates zero-downtime hot-swap: halfway through the request stream the engine
flips to a re-built index while traffic keeps flowing.

``--shards N`` serves through the sharded backend (DESIGN.md §8) — bit-identical
results to the single-device engine, index memory 1/N per shard. With a mesh whose
``model`` axis matches N (e.g. 4 host devices for --shards 4) the shards run under
shard_map; otherwise the host-loop transport serves from one process. With
``--index-dir`` the sharded shard set is persisted/loaded as one atomically
committed manifest, and --swap-mid-run swaps ALL shards under one epoch.

``--sweep-k A,B,...`` replays the stream at per-request k overrides — the
static/dynamic split (DESIGN.md §9) serves every point through the one compiled
ladder, zero recompiles.

``--slo-p99-ms`` / ``--deadline-ms`` / ``--tenant-quota`` turn on the SLO
control plane (DESIGN.md §10): the controller walks overloaded traffic down the
degradation ladder to hold the served p99, queued requests past their deadline
fail fast with ``DeadlineExceeded`` instead of being scored, and per-tenant
token buckets reject over-quota traffic at admission.

  PYTHONPATH=src python -m repro.launch.serve --n-docs 16384 --requests 128
  PYTHONPATH=src python -m repro.launch.serve --index-dir /tmp/lsp_index  # save, then mmap
  PYTHONPATH=src python -m repro.launch.serve --swap-mid-run
  PYTHONPATH=src python -m repro.launch.serve --no-buckets --cache-size 0  # old engine
  PYTHONPATH=src python -m repro.launch.serve --shards 4  # host-loop transport
  PYTHONPATH=src python -m repro.launch.serve --sweep-k 1,5,10  # dynamic overrides
  PYTHONPATH=src python -m repro.launch.serve --slo-p99-ms 50 --deadline-ms 25
  PYTHONPATH=src python -m repro.launch.serve --tenant-quota 'default=100/20,teamA=500'
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      PYTHONPATH=src python -m repro.launch.serve --shards 4  # shard_map transport
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.api import DynamicParams, Retriever, SearchRequest, StaticConfig
from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
from repro.index.builder import IndexBuildConfig, build_index
from repro.index.store import (
    IndexStoreError,
    load_index_auto,
    read_manifest,
    save_index,
    save_sharded_index,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import AdmissionConfig, DeadlineExceeded, SLOConfig, TenantQuota


def parse_tenant_quotas(spec: str) -> AdmissionConfig:
    """Parse ``'tenant=rate[/burst],...'``; the tenant name ``default`` sets the
    quota applied to every tenant not listed explicitly."""
    quotas, default_quota = {}, None
    for item in spec.split(","):
        name, sep, rb = item.partition("=")
        if not sep or not name.strip():
            raise ValueError(f"bad --tenant-quota item {item!r}; want 'tenant=rate[/burst]'")
        rate, _, burst = rb.partition("/")
        q = TenantQuota(rate=float(rate), burst=float(burst) if burst else 0.0)
        if name.strip() == "default":
            default_quota = q
        else:
            quotas[name.strip()] = q
    return AdmissionConfig(quotas=quotas, default_quota=default_quota)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--n-docs", type=int, default=16384)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--c", type=int, default=16)
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--gamma", type=int, default=0, help="0 -> NS/8 (zero-shot scaled)")
    p.add_argument("--variant", default="lsp0", choices=["lsp0", "lsp1", "lsp2", "sp", "bmp"])
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--no-buckets", action="store_true",
                   help="single compiled shape: every batch padded to max-batch")
    p.add_argument("--cache-size", type=int, default=1024, help="result-cache entries; 0 disables")
    p.add_argument("--no-warmup", action="store_true", help="skip bucket pre-compilation")
    p.add_argument("--shards", type=int, default=0,
                   help="serve through the sharded backend over N index shards "
                        "(shard_map when the device count allows a model=N mesh, "
                        "else the bit-identical host-loop transport)")
    p.add_argument("--index-dir", default=None,
                   help="persisted-index dir: mmap-load if committed, else build + save")
    p.add_argument("--swap-mid-run", action="store_true",
                   help="hot-swap to a re-built index halfway through the stream")
    p.add_argument("--sweep-k", default=None,
                   help="comma-separated k values (each <= --k) replayed as "
                        "per-request DynamicParams overrides, zero recompiles")
    p.add_argument("--slo-p99-ms", type=float, default=0.0,
                   help="SLO controller target: degrade per-request params under "
                        "queue/latency pressure to hold served p99 under this (0 = off)")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-request deadline: queued requests past it fail fast "
                        "with DeadlineExceeded, never scored (0 = none)")
    p.add_argument("--tenant-quota", default=None,
                   help="admission quotas 'tenant=rate[/burst],...' in requests/s; "
                        "tenant 'default' covers unlisted tenants")
    args = p.parse_args()
    print(f"[serve] compile cache {enable_compile_cache()}")

    ccfg = CorpusConfig(n_docs=args.n_docs, vocab=args.vocab, n_topics=32, seed=0)
    corpus = make_corpus(ccfg)
    bcfg = IndexBuildConfig(b=args.b, c=args.c)
    n_shards = args.shards

    def build():
        return build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab, bcfg)

    idx = None  # LSPIndex, or store.ShardedIndex when --shards is persisted
    if args.index_dir:
        try:
            t0 = time.perf_counter()
            idx = load_index_auto(args.index_dir, mmap=True, device=True)
            stored_shards = len(idx.shards) if hasattr(idx, "shards") else 0
            if stored_shards != n_shards:
                print(f"[serve] stored index has {stored_shards} shards, "
                      f"want {n_shards}; rebuilding")
                idx = None
            else:
                fp = idx.fingerprint if stored_shards else read_manifest(args.index_dir)["fingerprint"]
                print(f"[serve] mmap-loaded index {args.index_dir} ({fp[:12]}…) "
                      f"in {time.perf_counter() - t0:.3f}s")
        except FileNotFoundError:
            pass
        except IndexStoreError as exc:  # version/manifest drift -> rebuild + resave
            print(f"[serve] stored index unusable ({exc}); rebuilding")
    if idx is None:
        t0 = time.perf_counter()
        idx = build()
        print(f"[serve] built index in {time.perf_counter() - t0:.1f}s")
        if args.index_dir:
            if n_shards:
                fp = save_sharded_index(args.index_dir, idx, n_shards, bcfg)
                idx = load_index_auto(args.index_dir, mmap=True, device=True)
                print(f"[serve] saved {n_shards}-shard index -> {args.index_dir} ({fp[:12]}…)")
            else:
                fp = save_index(args.index_dir, idx, bcfg)
                print(f"[serve] saved index -> {args.index_dir} ({fp[:12]}…)")
    gamma = args.gamma or max(16, idx.n_superblocks // 8)
    scfg = StaticConfig(
        variant=args.variant, gamma=gamma, gamma0=min(32, gamma), k_max=args.k
    )
    params = DynamicParams.recommended(args.k)
    print(f"[serve] NS={idx.n_superblocks}, {args.variant} γ={gamma}"
          + (f", {n_shards} shards" if n_shards else ""))

    mesh = None
    if n_shards and len(jax.devices()) >= n_shards:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(model=n_shards, data=1)
        print(f"[serve] shard_map transport over mesh {dict(mesh.shape)}")
    elif n_shards:
        print(f"[serve] {len(jax.devices())} device(s) < {n_shards} shards: host-loop transport")

    retr = Retriever.from_index(
        idx, scfg, params=params, shards=0 if hasattr(idx, "shards") else n_shards,
        mesh=mesh,
    )
    batch_buckets = [args.max_batch] if args.no_buckets else None
    serve_kw = {}
    if args.slo_p99_ms:
        serve_kw["slo"] = SLOConfig(p99_ms=args.slo_p99_ms)
    if args.deadline_ms or args.tenant_quota:
        adm = (parse_tenant_quotas(args.tenant_quota) if args.tenant_quota
               else AdmissionConfig())
        serve_kw["admission"] = AdmissionConfig(
            default_deadline_ms=args.deadline_ms,
            quotas=adm.quotas, default_quota=adm.default_quota,
        )
    eng = retr.serve(
        max_batch=args.max_batch, nq_max=64, batch_buckets=batch_buckets,
        cache_size=args.cache_size, warmup=not args.no_warmup, **serve_kw,
    )
    print(f"[serve] backend {retr.backend_name}, buckets {eng.ladder}, cache={args.cache_size}")
    queries = make_queries(ccfg, corpus, args.requests)
    half = len(queries) // 2 if args.swap_mid_run else len(queries)
    futs = [eng.search(SearchRequest(t, w)) for t, w in queries[:half]]
    if args.swap_mid_run:
        epoch = eng.swap_index(build())  # built + warmed off the worker; atomic flip
        print(f"[serve] hot-swapped to epoch {epoch} "
              f"({eng.stats.summary()['last_swap_ms']:.0f} ms) with traffic in flight")
        futs += [eng.search(SearchRequest(t, w)) for t, w in queries[half:]]
    shed = 0
    for f in futs:
        try:
            f.result(timeout=600)
        except DeadlineExceeded:
            shed += 1
    if shed:
        print(f"[serve] {shed} queued requests shed at their deadline (typed, never scored)")
    if args.sweep_k:
        ks = [int(v) for v in args.sweep_k.split(",")]
        t0 = time.perf_counter()
        # count traces on the engine's LIVE backend: --swap-mid-run replaced the
        # one `retr` was built with
        live = eng.retriever
        before = live.n_traces()
        sweep = [
            eng.search(SearchRequest(t, w, params=DynamicParams(k=kv, beta=params.beta)))
            for kv in ks for t, w in queries
        ]
        for f in sweep:
            try:
                f.result(timeout=600)
            except DeadlineExceeded:
                pass
        print(f"[serve] dynamic sweep k={ks}: {len(sweep)} requests in "
              f"{time.perf_counter() - t0:.1f}s, recompiles={live.n_traces() - before}")
    eng.shutdown()
    s = eng.stats.summary()
    print(f"[serve] {s['requests']} requests / {s['batches']} batches | "
          f"mean {s['mean_ms']:.1f} ms p50 {s['p50_ms']:.1f} p99 {s['p99_ms']:.1f}")
    print(f"[serve] buckets used {s['bucket_batches']} | "
          f"cache hit rate {s['cache_hit_rate']:.2f} ({s['cache_hits']}/{s['cache_hits'] + s['cache_misses']}) | "
          f"swaps {s['swaps']} | failures {s['failures']}")
    if args.slo_p99_ms or args.deadline_ms or args.tenant_quota:
        print(f"[serve] slo: degraded {s['degraded']} | "
              f"deadline_expired {s['deadline_expired']} | "
              f"quota_rejected {s['quota_rejected']} | rejected {s['rejected']}"
              + (f" | level {s.get('slo_level')}" if args.slo_p99_ms else ""))


if __name__ == "__main__":
    main()
