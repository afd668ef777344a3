"""Distributed-equivalence tests: run in a subprocess with 4 host devices (device
count locks at first jax init, so the multi-device cases re-exec python)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], env=env, capture_output=True, text=True, timeout=600
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_shardmap_retrieval_matches_hostloop():
    out = _run(
        """
        import numpy as np
        from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
        from repro.index.builder import IndexBuildConfig, build_index
        from repro.core import RetrievalConfig, make_query_batch, retrieve
        from repro.distributed.retrieval import shard_index, retrieve_distributed, make_mesh_retriever
        from repro.launch.mesh import make_host_mesh
        ccfg = CorpusConfig(n_docs=2048, vocab=512, n_topics=8, seed=0)
        corpus = make_corpus(ccfg)
        idx = build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab,
                          IndexBuildConfig(b=8, c=8, kmeans_iters=2, build_avg=False))
        qb = make_query_batch(make_queries(ccfg, corpus, 8), corpus.vocab)
        cfg = RetrievalConfig(variant="lsp0", k=10, gamma=16, gamma0=8, beta=0.5)
        shards = shard_index(idx, 2)
        ids_h, _ = retrieve_distributed(shards, qb, cfg)
        run, _ = make_mesh_retriever(shards, cfg, make_host_mesh(model=2, data=2), impl="ref")
        ids_m, _ = run(qb)
        assert (np.sort(np.asarray(ids_h),1) == np.sort(np.asarray(ids_m),1)).all()
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_sharded_retriever_mesh_bit_identical_to_hostloop_and_single():
    """The shard_map transport of ShardedRetriever must be bit-identical to both
    the host-loop transport and single-device retrieve — on a RAGGED shard count
    (NS=40 over model=4 divides; over model=3 it pads) and with queries sharded
    over the data axis."""
    out = _run(
        """
        import numpy as np
        from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
        from repro.index.builder import IndexBuildConfig, build_index
        from repro.core import RetrievalConfig, make_query_batch, retrieve
        from repro.distributed.sharded import ShardedRetriever
        from repro.launch.mesh import make_host_mesh
        ccfg = CorpusConfig(n_docs=2500, vocab=512, n_topics=8, seed=0)
        corpus = make_corpus(ccfg)
        idx = build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab,
                          IndexBuildConfig(b=8, c=8, kmeans_iters=2))
        qb = make_query_batch(make_queries(ccfg, corpus, 8), corpus.vocab)
        for variant, kw in [("lsp0", {}), ("lsp2", dict(mu=0.4, eta=0.7)),
                            ("lsp0", dict(block_budget=3)),  # competitive: bounds-merge collective
                            ("sp", dict(mu=0.5, eta=0.8, block_budget=17))]:
            cfg = RetrievalConfig(variant=variant, k=10, gamma=16, gamma0=8, beta=0.5, **kw)
            ref = retrieve(idx, qb, cfg, impl="ref")
            for model, data in ((4, 1), (2, 2)):
                sr = ShardedRetriever(idx, cfg, n_shards=model,
                                      mesh=make_host_mesh(model=model, data=data), impl="ref")
                res = sr(qb)
                for a, b in ((ref.doc_ids, res.doc_ids), (ref.scores, res.scores),
                             (ref.theta, res.theta),
                             (ref.n_superblocks_visited, res.n_superblocks_visited),
                             (ref.n_blocks_scored, res.n_blocks_scored)):
                    assert (np.asarray(a) == np.asarray(b)).all(), (variant, model, data)
            # ragged: 3 shards over NS not divisible by 3 -> padded tail, host vs mesh
            host = ShardedRetriever(idx, cfg, n_shards=3, impl="ref")(qb)
            # (no 3-divisible mesh on 4 devices; host-loop vs single covers ragged)
            assert (np.asarray(host.doc_ids) == np.asarray(ref.doc_ids)).all()
            assert (np.asarray(host.scores) == np.asarray(ref.scores)).all()
        print("OK")
        """
    )
    assert "OK" in out


def test_sharded_doc_score_kernel_bit_identical_to_single_ref():
    """Both transports with the doc_score kernel (interpret mode) return the
    single-device reference's answers bit for bit. Their block masks are not
    prefixes: round 0 keeps the owned superblocks of the global top-γ₀, phase 3
    the η-cut survivors (γ = NS: most slots dead) or the owned members of the
    global block budget. The bound kernels are steered to the reference, so the
    two sides differ only in document scoring."""
    out = _run(
        """
        import numpy as np
        from repro.core import ops
        from repro.data.synthetic import CorpusConfig, make_corpus, make_queries
        from repro.index.builder import IndexBuildConfig, build_index
        from repro.core import RetrievalConfig, make_query_batch, retrieve
        from repro.distributed.sharded import ShardedRetriever
        from repro.launch.mesh import make_host_mesh
        sbmax, gathered = ops.sbmax, ops.gathered_block_bounds
        ops.sbmax = lambda pb, t, w, impl="auto": sbmax(pb, t, w, "ref")
        ops.gathered_block_bounds = (
            lambda pb, c, t, w, sel, impl="auto": gathered(pb, c, t, w, sel, "ref"))
        ccfg = CorpusConfig(n_docs=2500, vocab=512, n_topics=8, seed=0)
        corpus = make_corpus(ccfg)
        idx = build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab,
                          IndexBuildConfig(b=8, c=8, kmeans_iters=2))
        qb = make_query_batch(make_queries(ccfg, corpus, 8), corpus.vocab)
        ns = idx.n_superblocks
        for kw in ({}, dict(block_budget=17)):
            cfg = RetrievalConfig(variant="lsp0", k=10, gamma=ns, gamma0=2, beta=0.5, **kw)
            ref = retrieve(idx, qb, cfg, impl="ref")
            for name, sr in (
                ("host", ShardedRetriever(idx, cfg, n_shards=3, impl="kernel")),
                ("mesh", ShardedRetriever(idx, cfg, n_shards=2,
                                          mesh=make_host_mesh(model=2, data=2), impl="kernel")),
            ):
                res = sr(qb)
                for f in ("doc_ids", "scores", "theta", "n_superblocks_visited",
                          "n_blocks_scored"):
                    a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(res, f))
                    assert (a == b).all(), (kw, name, f)
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_vocab_parallel_embedding_matches_local():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.embedding import vocab_parallel_lookup
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=2, data=2)
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, 64, (16, 3)).astype(np.int32))
        out = vocab_parallel_lookup(table, ids, mesh, ("data",))
        ref = table[ids]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_distributed_topk():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed.topk import distributed_topk
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=4, data=1)
        rng = np.random.default_rng(0)
        scores = jnp.asarray(rng.standard_normal((3, 64)).astype(np.float32))
        def f(s):
            return distributed_topk(s, 5, "model")
        fn = shard_map(f, mesh=mesh, in_specs=(P(None, "model"),),
                       out_specs=(P(None, None), P(None, None)), check_vma=False)
        vals, ids = fn(scores)
        ref_vals, ref_ids = jax.lax.top_k(scores, 5)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals), rtol=1e-6)
        assert (np.sort(np.asarray(ids),1) == np.sort(np.asarray(ref_ids),1)).all()
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_grad_compression_error_feedback():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.grad_compress import compressed_psum, init_error_feedback
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=1, data=4)
        rng = np.random.default_rng(0)
        g_local = jnp.asarray(rng.standard_normal((4, 32)).astype(np.float32))
        def f(g):
            ef = init_error_feedback({"g": g[0]})
            out, ef = compressed_psum({"g": g[0]}, ef, "data")
            return out["g"][None], ef.err["g"][None]
        fn = shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                       out_specs=(P("data", None), P("data", None)), check_vma=False)
        mean_c, err = fn(g_local)
        true_mean = np.asarray(g_local).mean(axis=0)
        got = np.asarray(mean_c)[0]
        # int8-compressed mean close to true mean; residual bounded by one quant level
        assert np.abs(got - true_mean).max() < np.abs(g_local).max()/127 + 1e-5
        assert np.abs(np.asarray(err)).max() <= np.abs(np.asarray(g_local)).max()/127 + 1e-6
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_sharded_dense_retrieval_matches_single():
    out = _run(
        """
        import numpy as np, jax.numpy as jnp
        from repro.core.config import RetrievalConfig
        from repro.core.lsp_dense import (DenseIndexConfig, build_dense_index,
            retrieve_dense, shard_dense_index, make_sharded_dense_retriever)
        from repro.launch.mesh import make_host_mesh
        rng = np.random.default_rng(0)
        centers = rng.standard_normal((8, 16)).astype(np.float32)
        cands = (centers[rng.integers(0, 8, 4096)] + 0.3*rng.standard_normal((4096,16))).astype(np.float32)
        idx = build_dense_index(cands, DenseIndexConfig(b=32, c=8, kmeans_iters=2, ns_align=4))
        q = jnp.asarray(rng.standard_normal((2, 16)).astype(np.float32))
        cfg = RetrievalConfig(variant="lsp0", k=10, gamma=idx.n_superblocks//2, gamma0=2)
        ids_s, vals_s = retrieve_dense(idx, q, cfg)
        mesh = make_host_mesh(model=2, data=2)
        shards = shard_dense_index(idx, 2)
        cfg_l = RetrievalConfig(variant="lsp0", k=10, gamma=shards[0].n_superblocks, gamma0=2)
        run, _ = make_sharded_dense_retriever(shards, cfg_l, mesh)
        ids_m, vals_m = run(q)
        # per-shard full gamma covers at least the single-host visitation
        rec = np.mean([len(np.intersect1d(np.asarray(ids_m)[i], np.asarray(ids_s)[i]))/10 for i in range(2)])
        assert rec >= 0.9, rec
        print("OK")
        """
    )
    assert "OK" in out
