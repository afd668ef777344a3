"""Distributed LSP retrieval: index shards over `model`, queries over pod/data.

Each model-shard owns a contiguous range of superblocks (and their blocks/documents)
and runs the full LSP pipeline locally with the SAME γ (safe: the union of per-shard
top-γ covers the global top-γ under any overlap pattern), then a hierarchical
distributed top-k merges the per-shard results.

Collectives per query batch: 2 all_gathers of [Q, P*k] (scores + ids) — O(kP) floats,
independent of index size. This is why index-sharded retrieval is compute/memory-bound
rather than collective-bound (§Roofline).

Shards are produced host-side by `shard_index` (slice + repack — production builds
per-shard indexes directly from corpus shards; this utility reshards a global build,
e.g. after an elastic mesh change).

``block_budget`` note: this path runs the FULL pipeline per shard, so a
competitive budget is applied *per shard* — each shard keeps its own locally
top-bounded blocks (up to P·block_budget scored globally). That is rank-safe
(a superset of the single-device keep-set) but not bit-identical in visit
counters. The bit-identical competitive cut — one global keep-set via the
cross-shard bounds merge — is `distributed/sharded.py`'s contract; use
`ShardedRetriever` when parity with `core.lsp` matters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.config import RetrievalConfig
from repro.core.lsp import search_retrieve
from repro.core.query import QueryBatch
from repro.core.scoring import NEG
from repro.core.topk import canonical_topk
from repro.index.layout import LSPIndex, PackedBounds
from repro.index.pack import pack_rows_strided, unpack_rows_strided


def _pb_slice(pb: PackedBounds, lo_unit: int, n_unit: int) -> PackedBounds:
    """Slice a packed bounds matrix to a unit range (unpack -> slice -> repack).

    Units past ``pb.n`` (the ragged tail of the last shard) are padded with
    zero bounds: a quantized zero bound means SBMax == 0 for any query, so a
    padded superblock can never out-rank a real one under the canonical
    (value desc, id asc) candidate order — pad ids are the largest."""
    rows = unpack_rows_strided(np.asarray(pb.packed), pb.bits, pb.granule_words, pb.n)
    hi = lo_unit + n_unit
    if hi > rows.shape[1]:
        rows = np.pad(rows, ((0, 0), (0, hi - rows.shape[1])))
    sl = rows[:, lo_unit:hi]
    return PackedBounds(
        jnp.asarray(pack_rows_strided(sl, pb.bits, pb.granule_words)),
        pb.bits,
        pb.scale,
        n_unit,
        pb.granule_words,
    )


def _pad_rows(a: np.ndarray, n_rows: int, fill) -> np.ndarray:
    """Pad the leading axis of ``a`` to ``n_rows`` with ``fill``."""
    if a.shape[0] >= n_rows:
        return a
    pad = [(0, n_rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad, constant_values=fill)


def shards_of(n_superblocks: int, n_shards: int) -> int:
    """Per-shard superblock count: ceil(NS / P). The last shard's tail is padded
    with empty superblocks so arbitrary corpus sizes shard evenly."""
    return -(-n_superblocks // n_shards)


def _local_index(index: LSPIndex, shard: int, n_shards: int) -> LSPIndex:
    ns_l = shards_of(index.n_superblocks, n_shards)
    nb_l = ns_l * index.c
    nd_l = nb_l * index.b
    s0, b0, d0 = shard * ns_l, shard * nb_l, shard * nd_l
    fq = index.docs_fwdq
    # ragged tail: padded blocks hold sentinel terms (id == vocab, weight 0) and
    # padded doc positions remap to the n_docs sentinel — they score NEG everywhere
    remap = _pad_rows(np.asarray(index.doc_remap)[d0 : d0 + nd_l], nd_l, index.n_docs)
    fq_tids = _pad_rows(np.asarray(fq.tids)[b0 : b0 + nb_l], nb_l, index.vocab)
    fq_ws = _pad_rows(np.asarray(fq.ws)[b0 : b0 + nb_l], nb_l, 0)
    fq_scales = _pad_rows(np.asarray(fq.scales)[b0 : b0 + nb_l], nb_l, 1.0)
    return LSPIndex(
        b=index.b,
        c=index.c,
        n_docs=index.n_docs,  # global doc count (remap validity is global)
        vocab=index.vocab,
        n_blocks=nb_l,
        n_superblocks=ns_l,
        sb_bounds=_pb_slice(index.sb_bounds, s0, ns_l),
        blk_bounds=_pb_slice(index.blk_bounds, b0, nb_l),
        sb_avg=None if index.sb_avg is None else _pb_slice(index.sb_avg, s0, ns_l),
        docs_fwd=None,  # scoring reads docs_fwdq only; don't duplicate the big layout
        docs_flat=None,  # distributed path uses the Fwd layout
        doc_remap=jnp.asarray(remap),
        docs_fwdq=fq._replace(
            tids=jnp.asarray(fq_tids), ws=jnp.asarray(fq_ws), scales=jnp.asarray(fq_scales)
        ),
        docs_flatq=None,
    )


def shard_index(index: LSPIndex, n_shards: int) -> list[LSPIndex]:
    """Contiguous superblock-range shards; the last shard's ragged tail (when
    NS % n_shards != 0) is padded with empty superblocks that score NEG."""
    return [_local_index(index, s, n_shards) for s in range(n_shards)]


def to_host(shard: LSPIndex) -> LSPIndex:
    """``shard`` with its device arrays copied to host memory (numpy)."""
    return jax.tree.map(lambda x: np.asarray(x) if isinstance(x, jax.Array) else x, shard)


def retrieve_distributed(
    shards: list[LSPIndex], qb: QueryBatch, cfg: RetrievalConfig, impl: str = "ref"
):
    """Host-loop reference for the shard_map version (identical per-shard math)."""
    all_ids, all_scores = [], []
    for sh in shards:
        res = search_retrieve(sh, qb, cfg.static(), cfg.dynamic(), impl=impl)
        all_ids.append(res.doc_ids)
        all_scores.append(jnp.where(res.doc_ids >= 0, res.scores, NEG))
    ids = jnp.concatenate(all_ids, axis=1)
    scores = jnp.concatenate(all_scores, axis=1)
    # canonical (score desc, doc-id asc) merge: equal-score ties at the k boundary
    # must resolve by global doc id, not by shard concatenation order, or the
    # merged result diverges from the single-device canonical selection
    vals, out_ids = canonical_topk(scores, ids, cfg.k, id_bound=shards[0].n_docs + 1)
    return jnp.where(vals > NEG / 2, out_ids, -1), vals


class StackedShards:
    """Per-shard arrays stacked on a leading axis, for a shard_map over ``mesh``.
    Each stack is built on the host and placed once with
    ``NamedSharding(mesh, P('model', ...))``: shard p lives only on the devices of
    model index p, never gathered on one device first."""

    def __init__(self, shards: list[LSPIndex], mesh):
        self.meta = shards[0]
        self.n_shards = len(shards)
        self.shards = shards
        self.mesh = mesh
        st = self.stack
        self.sb_packed = st(lambda s: s.sb_bounds.packed)
        self.blk_packed = st(lambda s: s.blk_bounds.packed)
        self.fwdq_tids = st(lambda s: s.docs_fwdq.tids)
        self.fwdq_ws = st(lambda s: s.docs_fwdq.ws)
        self.fwdq_scales = st(lambda s: s.docs_fwdq.scales)
        self.remap = st(lambda s: s.doc_remap)

    def stack(self, get):
        """``get(shard)`` of every shard, stacked on a new leading (shard) axis."""
        host = np.stack([np.asarray(get(s)) for s in self.shards])
        spec = P("model", *([None] * (host.ndim - 1)))
        return jax.device_put(host, NamedSharding(self.mesh, spec))


def make_mesh_retriever(shards: list[LSPIndex], cfg: RetrievalConfig, mesh, impl: str = "auto"):
    """shard_map retriever: index shards over `model`, queries over pod/data axes."""
    from jax import shard_map

    stacked = StackedShards(shards, mesh)
    meta = stacked.meta
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def local_fn(sb_packed, blk_packed, fwdq_tids, fwdq_ws, fwdq_scales, remap, q_tids, q_ws):
        # leading shard axis has local extent 1 under shard_map
        local = LSPIndex(
            b=meta.b,
            c=meta.c,
            n_docs=meta.n_docs,
            vocab=meta.vocab,
            n_blocks=meta.n_blocks,
            n_superblocks=meta.n_superblocks,
            sb_bounds=meta.sb_bounds._replace(packed=sb_packed[0]),
            blk_bounds=meta.blk_bounds._replace(packed=blk_packed[0]),
            sb_avg=None,
            docs_fwd=None,  # scoring reads the quantized block-major operand only
            docs_flat=None,
            doc_remap=remap[0],
            docs_fwdq=meta.docs_fwdq._replace(
                tids=fwdq_tids[0], ws=fwdq_ws[0], scales=fwdq_scales[0]
            ),
            docs_flatq=None,
        )
        res = search_retrieve(local, QueryBatch(q_tids, q_ws, meta.vocab), cfg.static(), cfg.dynamic(), impl=impl)
        scores = jnp.where(res.doc_ids >= 0, res.scores, NEG)
        av = jax.lax.all_gather(scores, "model", axis=1, tiled=True)  # [Q, P*k]
        ai = jax.lax.all_gather(res.doc_ids, "model", axis=1, tiled=True)
        # canonical final merge (see retrieve_distributed): shard order must not
        # decide equal-score ties
        vals, ids = canonical_topk(av, ai, cfg.k, id_bound=meta.n_docs + 1)
        return jnp.where(vals > NEG / 2, ids, -1), vals

    qspec = P(batch_axes, None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P("model", None, None),
            P("model", None, None),
            P("model", None, None, None),
            P("model", None, None, None),
            P("model", None),
            P("model", None),
            qspec,
            qspec,
        ),
        out_specs=(qspec, qspec),
        check_vma=False,
    )
    jfn = jax.jit(fn)
    arrays = (
        stacked.sb_packed,
        stacked.blk_packed,
        stacked.fwdq_tids,
        stacked.fwdq_ws,
        stacked.fwdq_scales,
        stacked.remap,
    )

    def run(qb: QueryBatch):
        return jfn(*arrays, qb.tids, qb.ws)

    return run, stacked
