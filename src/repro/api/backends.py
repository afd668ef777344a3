"""Backend registry for the unified search API (DESIGN.md §9).

A *backend* is a serving strategy behind the one ``Retriever`` facade. Every
factory returns a callable with the ``core.lsp.jit_search`` contract:

    retriever(qb: QueryBatch, dyn=None) -> RetrievalResult-compatible
    retriever.supports_dynamic  # True: per-row DynamicParams ride the batch
    retriever.warmup(shapes)    # pre-compile (Q, nq) bucket shapes
    retriever.n_traces()        # trace counter (zero-recompilation tests)
    retriever.static_cfg / .defaults / .vocab

Built-ins:
  local      one-device jitted LSP traversal (``jit_search``)
  sharded    host-loop sharded transport — bit-identical, any device count
  shard_map  mesh transport over the ``model`` axis (needs ``mesh=``)
  exact      rank-safe exhaustive oracle behind the same dynamic contract

``register_backend`` lets downstream code add strategies (e.g. a dense or
remote backend) without touching the facade.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.config import DynamicParams, StaticConfig
from repro.core.exact import retrieve_exact
from repro.core.lsp import (
    RetrievalResult,
    jit_search,
    make_dynamic_runner,
    mask_beyond_k,
    split_arrays,
)
from repro.core.query import QueryBatch
from repro.index.layout import LSPIndex

_REGISTRY: dict[str, Callable] = {}


def register_backend(name: str):
    """Decorator: register ``factory(index, static_cfg, **kw) -> retriever``."""

    def deco(factory: Callable) -> Callable:
        _REGISTRY[name] = factory
        return factory

    return deco


def get_backend(name: str) -> Callable:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: {sorted(_REGISTRY)}"
        ) from None


def list_backends() -> list[str]:
    return sorted(_REGISTRY)


@register_backend("local")
def local_backend(
    index: LSPIndex,
    static_cfg: StaticConfig,
    *,
    impl: str = "auto",
    defaults: Optional[DynamicParams] = None,
    **_,
):
    """Single-device jitted traversal — the default."""
    if not isinstance(index, LSPIndex):
        raise ValueError(
            "backend 'local' serves one LSPIndex; a sharded index set needs "
            "backend 'sharded' or 'shard_map'"
        )
    return jit_search(index, static_cfg, impl=impl, defaults=defaults)


@register_backend("sharded")
def sharded_backend(
    index,
    static_cfg: StaticConfig,
    *,
    shards: int = 0,
    impl: str = "auto",
    defaults: Optional[DynamicParams] = None,
    ns_true: Optional[int] = None,
    **_,
):
    """Host-loop sharded transport (DESIGN.md §8): bit-identical to 'local',
    index memory 1/P per shard, runs on any device count."""
    from repro.distributed.sharded import ShardedRetriever

    return ShardedRetriever(
        index, static_cfg, n_shards=shards or None, impl=impl,
        ns_true=ns_true, defaults=defaults,
    )


@register_backend("shard_map")
def shard_map_backend(
    index,
    static_cfg: StaticConfig,
    *,
    shards: int = 0,
    mesh=None,
    impl: str = "auto",
    defaults: Optional[DynamicParams] = None,
    ns_true: Optional[int] = None,
    **_,
):
    """Mesh transport: shards under shard_map over the ``model`` axis."""
    from repro.distributed.sharded import ShardedRetriever

    if mesh is None:
        raise ValueError("backend 'shard_map' needs mesh= (e.g. launch.mesh.make_host_mesh)")
    return ShardedRetriever(
        index, static_cfg, n_shards=shards or None, mesh=mesh, impl=impl,
        ns_true=ns_true, defaults=defaults,
    )


@register_backend("exact")
def exact_backend(
    index: LSPIndex,
    static_cfg: StaticConfig,
    *,
    defaults: Optional[DynamicParams] = None,
    doc_chunk: int = 8192,
    **_,
):
    """Rank-safe exhaustive oracle behind the same dynamic contract — the
    reference arm for recall audits. Dynamic k masks the top-k_max prefix;
    μ/η/β have no effect (nothing is pruned). θ reports 0 and the visit
    counters 0: exhaustive scoring visits everything and prunes nothing."""
    if not isinstance(index, LSPIndex):
        raise ValueError("backend 'exact' serves one LSPIndex (no sharded oracle)")
    vocab = index.vocab
    scfg = static_cfg
    defaults = (defaults or DynamicParams(k=scfg.k_max)).validate_for(scfg)
    traces = {"n": 0}
    arrays, rebuild = split_arrays(index)

    @jax.jit
    def fn(arrays, tids, ws, k, mu, eta, beta):
        traces["n"] += 1
        ids, vals = retrieve_exact(
            rebuild(arrays), QueryBatch(tids, ws, vocab), scfg.k_max, doc_chunk
        )
        vals, ids = mask_beyond_k(vals, ids.astype(jnp.int32), k, scfg.k_max)
        zeros = jnp.zeros(tids.shape[0], jnp.int32)
        return RetrievalResult(ids, vals, zeros, zeros, theta=zeros.astype(jnp.float32))

    return make_dynamic_runner(fn, arrays, scfg, defaults, vocab, traces)
