"""Dense-embedding LSP: the paper's superblock pruning applied to dot-product
retrieval over dense candidate embeddings (recsys `retrieval_cand`, MIND serving).

Adaptation of Eq. 1 to signed dense vectors: a block B's score bound for query q is

  Bound(q, B) = sum_d [ q_d > 0 ? q_d * max_{x in B} x_d : q_d * min_{x in B} x_d ]
              = q+ . maxW(B) + q- . minW(B)

Per-dimension max/min are quantized OUTWARD (max up, min down) at 4 bits — bounds stay
valid upper bounds — and packed in the lane-strided layout, so bound computation is two
`dequant_matmul` Pallas GEMMs. The retrieval flow mirrors repro/core/lsp.py: SBMax ->
top-γ (+μ) -> block bounds -> exact scoring of surviving blocks' candidates.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import RetrievalConfig
from repro.core.lsp import resolve_block_budget
from repro.core.topk import canonical_topk
from repro.index import clustering
from repro.index.pack import SEG_WORDS, pack_rows_strided
from repro.kernels.dequant_matmul.ref import dequant_matmul_ref

NEG = -1e30


class PackedMinMax(NamedTuple):
    max_packed: jnp.ndarray  # uint32 [D, W]
    min_packed: jnp.ndarray
    scale: object  # float (global) or float32 [D] (per-dimension rows)
    zero: object  # float or float32 [D]
    n: int
    granule_words: int
    bits: int


class DenseLSPIndex(NamedTuple):
    b: int
    c: int
    n_cands: int
    dim: int
    n_blocks: int
    n_superblocks: int
    sb: PackedMinMax  # superblock per-dim max/min
    blk: PackedMinMax  # block per-dim max/min (superblock-contiguous)
    cands: jnp.ndarray  # [n_pad, D] block-ordered candidate embeddings (bf16)
    remap: jnp.ndarray  # int32 [n_pad] position -> original candidate id


@dataclass(frozen=True)
class DenseIndexConfig:
    b: int = 64
    c: int = 16
    bits: int = 4
    kmeans_iters: int = 6
    seed: int = 0
    ns_align: int = 1  # pad n_superblocks to this multiple (mesh-shardability)


def _quant_minmax(mx: np.ndarray, mn: np.ndarray, bits: int, granule: int) -> PackedMinMax:
    """Per-dimension affine quantization of the [D, N] min/max bound rows.

    A single global (scale, zero) wastes the few 4-bit levels on the widest dimension
    and flattens everyone else's bounds to near-constants — the superblock ranking
    degrades badly. Per-dimension scales keep ranking near-8-bit; they fold into the
    query (q_d * scale_d) so the dequant GEMMs stay scale-free, and the zero-point
    correction is a single q . zero dot product.
    """
    levels = (1 << bits) - 1
    lo = mn.min(axis=1, keepdims=True)
    hi = mx.max(axis=1, keepdims=True)
    scale = np.maximum((hi - lo) / levels, 1e-9).astype(np.float32)
    zero = lo.astype(np.float32)
    qmax = np.clip(np.ceil((mx - zero) / scale - 1e-9), 0, levels).astype(np.uint8)  # round up
    qmin = np.clip(np.floor((mn - zero) / scale + 1e-9), 0, levels).astype(np.uint8)  # round down
    return PackedMinMax(
        jnp.asarray(pack_rows_strided(qmax, bits, granule)),
        jnp.asarray(pack_rows_strided(qmin, bits, granule)),
        jnp.asarray(scale[:, 0]),
        jnp.asarray(zero[:, 0]),
        mx.shape[1],
        granule,
        bits,
    )


def build_dense_index(cands: np.ndarray, cfg: DenseIndexConfig) -> DenseLSPIndex:
    n, d = cands.shape
    b, c = cfg.b, cfg.c
    # cluster-order candidates (k-means on the embeddings themselves)
    k = max(1, n // (b * c))
    norm = cands / np.maximum(np.linalg.norm(cands, axis=1, keepdims=True), 1e-9)
    if n > b:
        assign, cent = clustering.kmeans(norm.astype(np.float32), k, cfg.kmeans_iters, cfg.seed)
        dist = np.einsum("nd,nd->n", norm - cent[assign], norm - cent[assign])
        order = np.lexsort((dist, clustering.chain_order(cent)[assign]))
    else:
        order = np.arange(n)
    ns = -(-n // (b * c))
    ns = -(-ns // cfg.ns_align) * cfg.ns_align
    n_pad = ns * b * c
    remap = np.concatenate([order, np.full(n_pad - n, n, np.int64)]).astype(np.int32)
    nb = n_pad // b

    x = np.zeros((n_pad, d), np.float32)
    x[: len(order)] = cands[order]
    xb = x.reshape(nb, b, d)
    # padded rows must not loosen bounds upward: they are zero, exclude via +-inf fill
    valid = (remap < n).reshape(nb, b)
    big = np.float32(1e30)
    blk_max = np.where(valid[..., None], xb, -big).max(axis=1).T.astype(np.float32)  # [D, NB]
    blk_min = np.where(valid[..., None], xb, big).min(axis=1).T.astype(np.float32)
    empty = ~valid.any(axis=1)
    blk_max[:, empty] = 0.0
    blk_min[:, empty] = 0.0
    sb_max = blk_max.reshape(d, ns, c).max(axis=2)
    sb_min = blk_min.reshape(d, ns, c).min(axis=2)

    cw = c * cfg.bits // 32
    return DenseLSPIndex(
        b=b,
        c=c,
        n_cands=n,
        dim=d,
        n_blocks=nb,
        n_superblocks=ns,
        sb=_quant_minmax(sb_max, sb_min, cfg.bits, SEG_WORDS),
        blk=_quant_minmax(blk_max, blk_min, cfg.bits, cw),
        cands=jnp.asarray(x, jnp.bfloat16),
        remap=jnp.asarray(remap),
    )


def _bounds(pm: PackedMinMax, q: jnp.ndarray, interpret_ok: bool = True) -> jnp.ndarray:
    """[B, n] upper bounds: q+ . maxW + q- . minW (affine dequant, zero-point corrected).

    Per-dimension scales fold into the query rows (contraction is over D), keeping the
    dequant GEMMs scale-free; the zero-point term is the dot product q . zero.
    """
    qs = q * pm.scale  # broadcasts for scalar or per-dim [D] scale
    qp = jnp.maximum(qs, 0.0)
    qm = jnp.minimum(qs, 0.0)
    if jax.default_backend() == "tpu":
        from repro.kernels.dequant_matmul.kernel import dequant_matmul_pallas

        raw = dequant_matmul_pallas(qp, pm.max_packed, pm.bits) + dequant_matmul_pallas(
            qm, pm.min_packed, pm.bits
        )
    else:
        raw = dequant_matmul_ref(qp, pm.max_packed, pm.bits) + dequant_matmul_ref(
            qm, pm.min_packed, pm.bits
        )
    corr = (q * pm.zero).sum(axis=1, keepdims=True)
    return raw[:, : pm.n] + corr


def retrieve_dense(index: DenseLSPIndex, q: jnp.ndarray, cfg: RetrievalConfig):
    """q [B, D] -> (cand_ids [B, k], scores [B, k]). LSP/0 or LSP/1 semantics."""
    bq = q.shape[0]
    ns, c, b = index.n_superblocks, index.c, index.b
    gamma = min(cfg.gamma, ns)
    g0 = min(cfg.gamma0, gamma)
    budget = min(cfg.resolved_sb_budget(), ns)

    sb_bound = _bounds(index.sb, q)  # [B, NS]
    top_vals, top_idx = jax.lax.top_k(sb_bound, budget)

    # round 0: exact-score the top-γ0 superblocks
    span = c * b
    pos0 = top_idx[:, :g0, None] * span + jnp.arange(span)[None, None, :]
    pos0 = pos0.reshape(bq, -1)
    s0 = _score_positions(index, q, pos0)
    # min over the top-k == k-th value; keeps XLA's fast TopK lowering (see lsp.py)
    theta_vals, _ = jax.lax.top_k(s0, min(cfg.k, s0.shape[1]))
    theta = theta_vals.min(axis=-1)

    rank = jnp.arange(budget)[None, :]
    eligible = (rank < gamma) & (top_vals >= theta[:, None])
    if cfg.variant == "lsp1":
        eligible = eligible | (top_vals > theta[:, None] / cfg.mu)
    eligible &= rank >= g0

    # block bounds for selected superblocks (jnp gather; granule = cw words)
    cw = c * index.blk.bits // 32
    sel_max = index.blk.max_packed.reshape(index.dim, ns, cw)[:, top_idx]  # [D, B, S, cw]
    sel_min = index.blk.min_packed.reshape(index.dim, ns, cw)[:, top_idx]
    from repro.core.bounds import unpack_strided

    vmax = unpack_strided(sel_max.transpose(1, 2, 0, 3), index.blk.bits, cw)  # [B, S, D, c]
    vmin = unpack_strided(sel_min.transpose(1, 2, 0, 3), index.blk.bits, cw)
    qs = q * index.blk.scale  # per-dim scales fold into the query (see _bounds)
    qp = jnp.maximum(qs, 0.0)
    qm = jnp.minimum(qs, 0.0)
    blk_bound = (
        jnp.einsum("bd,bsdc->bsc", qp, vmax.astype(jnp.float32))
        + jnp.einsum("bd,bsdc->bsc", qm, vmin.astype(jnp.float32))
    ) + ((q * index.blk.zero).sum(1))[:, None, None]
    blk_bound = jnp.where(eligible[:, :, None], blk_bound, NEG)
    keep = blk_bound > theta[:, None, None] / cfg.eta
    flat = jnp.where(keep, blk_bound, NEG).reshape(bq, -1)
    bb = resolve_block_budget(cfg, budget * c)
    bvals, bidx = jax.lax.top_k(flat, bb)
    sel_sb = jnp.take_along_axis(top_idx, bidx // c, axis=1)
    blk_ids = sel_sb * c + bidx % c
    pos1 = (blk_ids[:, :, None] * b + jnp.arange(b)[None, None, :]).reshape(bq, -1)
    s1 = _score_positions(index, q, pos1)
    s1 = jnp.where(jnp.repeat(bvals > NEG / 2, b, axis=1), s1, NEG)

    scores = jnp.concatenate([s0, s1], axis=1)
    pos = jnp.concatenate([pos0, pos1], axis=1)
    # canonical (score desc, candidate-id asc) final merge — equal-score ties must
    # not resolve by traversal position (cluster order differs between shardings)
    ids_all = index.remap[jnp.clip(pos, 0, index.remap.shape[0] - 1)]
    vals, ids = canonical_topk(scores, ids_all, cfg.k, id_bound=index.n_cands + 1)
    return jnp.where(vals > NEG / 2, ids, -1), vals


def _score_positions(index: DenseLSPIndex, q: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    x = index.cands[jnp.clip(pos, 0, index.cands.shape[0] - 1)]  # [B, P, D]
    s = jnp.einsum("bpd,bd->bp", x.astype(jnp.float32), q)
    return jnp.where(index.remap[jnp.clip(pos, 0, index.remap.shape[0] - 1)] < index.n_cands, s, NEG)


def shard_dense_index(index: DenseLSPIndex, n_shards: int) -> list[DenseLSPIndex]:
    """Slice a dense index into contiguous superblock ranges (repacked per shard)."""
    from repro.index.pack import SEG_WORDS, unpack_rows_strided

    assert index.n_superblocks % n_shards == 0
    ns_l = index.n_superblocks // n_shards
    nb_l = ns_l * index.c
    np_l = nb_l * index.b
    cw = index.c * index.blk.bits // 32

    def slice_pm(pm: PackedMinMax, lo_unit: int, n_unit: int, granule: int) -> PackedMinMax:
        mx = unpack_rows_strided(np.asarray(pm.max_packed), pm.bits, pm.granule_words, pm.n)
        mn = unpack_rows_strided(np.asarray(pm.min_packed), pm.bits, pm.granule_words, pm.n)
        return PackedMinMax(
            jnp.asarray(pack_rows_strided(mx[:, lo_unit : lo_unit + n_unit], pm.bits, granule)),
            jnp.asarray(pack_rows_strided(mn[:, lo_unit : lo_unit + n_unit], pm.bits, granule)),
            pm.scale, pm.zero, n_unit, granule, pm.bits,
        )

    out = []
    for s in range(n_shards):
        out.append(
            DenseLSPIndex(
                b=index.b, c=index.c, n_cands=index.n_cands, dim=index.dim,
                n_blocks=nb_l, n_superblocks=ns_l,
                sb=slice_pm(index.sb, s * ns_l, ns_l, SEG_WORDS),
                blk=slice_pm(index.blk, s * nb_l, nb_l, cw),
                cands=index.cands[s * np_l : (s + 1) * np_l],
                remap=index.remap[s * np_l : (s + 1) * np_l],
            )
        )
    return out


def dense_local_fn(meta: DenseLSPIndex, cfg: RetrievalConfig):
    """Per-shard body of the sharded dense retriever (shared with the dry-run cell)."""

    def local_fn(sb_max, sb_min, blk_max, blk_min, cands, remap, q):
        local = DenseLSPIndex(
            b=meta.b, c=meta.c, n_cands=meta.n_cands, dim=meta.dim,
            n_blocks=meta.n_blocks, n_superblocks=meta.n_superblocks,
            sb=meta.sb._replace(max_packed=sb_max[0], min_packed=sb_min[0]),
            blk=meta.blk._replace(max_packed=blk_max[0], min_packed=blk_min[0]),
            cands=cands[0], remap=remap[0],
        )
        ids, vals = retrieve_dense(local, q, cfg)
        vals = jnp.where(ids >= 0, vals, NEG)
        av = jax.lax.all_gather(vals, "model", axis=1, tiled=True)
        ai = jax.lax.all_gather(ids, "model", axis=1, tiled=True)
        # canonical cross-shard merge: shard order must not decide ties
        v, mi = canonical_topk(av, ai, cfg.k, id_bound=meta.n_cands + 1)
        return jnp.where(v > NEG / 2, mi, -1), v

    return local_fn


def make_sharded_dense_retriever(shards: list[DenseLSPIndex], cfg: RetrievalConfig, mesh):
    """shard_map dense LSP: each model-shard prunes + scores its candidate range with
    the full γ, then a hierarchical top-k merges (collectives O(P*k) instead of the
    pjit version's full candidate-array all-gather; see §Perf log)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    meta = shards[0]
    st = lambda get: jnp.stack([get(s) for s in shards])
    arrs = dict(
        sb_max=st(lambda s: s.sb.max_packed), sb_min=st(lambda s: s.sb.min_packed),
        blk_max=st(lambda s: s.blk.max_packed), blk_min=st(lambda s: s.blk.min_packed),
        cands=st(lambda s: s.cands), remap=st(lambda s: s.remap),
    )
    local_fn = dense_local_fn(meta, cfg)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=tuple([P("model", None, None)] * 5 + [P("model", None), P(None, None)]),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )

    def run(q):
        return fn(
            arrs["sb_max"], arrs["sb_min"], arrs["blk_max"], arrs["blk_min"],
            arrs["cands"], arrs["remap"], q,
        )

    return run, arrs


def retrieve_dense_exact(index: DenseLSPIndex, q: jnp.ndarray, k: int):
    s = jnp.einsum("nd,bd->bn", index.cands.astype(jnp.float32), q)
    valid = index.remap < index.n_cands
    s = jnp.where(valid[None, :], s, NEG)
    # canonical selection so the oracle breaks ties the same way the pruned and
    # sharded paths do (score desc, candidate-id asc), not by storage position
    ids_all = jnp.broadcast_to(index.remap[None, :], s.shape)
    vals, ids = canonical_topk(s, ids_all, k, id_bound=index.n_cands + 1)
    return jnp.where(vals > NEG / 2, ids, -1), vals
