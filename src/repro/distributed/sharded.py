"""Sharded LSP serving retriever: global pruning decisions, local scoring.

``retrieve_distributed``/``make_mesh_retriever`` (distributed/retrieval.py) run
the *whole* pipeline per shard at the same γ and merge — safe (the union of
per-shard top-γ covers the global top-γ) but not *identical*: a shard with weak
round-0 documents seeds a lower θ and visits superblocks the global traversal
would not, so results can legitimately differ at equal parameters. Production
serving wants the stronger property — a sharded engine that is **bit-identical**
to the single-device engine — so this module splits the traversal differently:

  every *decision* is global, every *scoring gather* is local.

    stage 1   per-shard SBMax over the local superblock range -> local top-B
              candidates -> canonical merge (value desc, global id asc) into THE
              global candidate list — identical to single-device ``lax.top_k``
              (which breaks ties by position) because ids are positions.
    stage 2   each shard scores its members of the *global* top-γ₀ (round 0),
              per-shard top-k score lists merge into the *global* θ — the same
              k-th value ``_kth_threshold`` computes, because the k largest of a
              union are contained in the union of per-shard k-largest.
    stage 3   the variant eligibility rule runs against the global (rank, value,
              θ) triple masked to owned superblocks; block BoundSums and the
              θ/η block cut read only local index memory. With a *competitive*
              ``block_budget`` (< budget·c) one more collective runs: each
              shard's canonical top-``block_budget`` (bound desc, global
              block-id asc) bound list merges into the global cutoff pair —
              the budget-th (bound, id) of the union — and every shard masks
              its keep-set at that cutoff (``core.topk.canonical_keep_mask``),
              which reconstructs the single-device competitive cut exactly,
              including duplicated-bound blocks straddling shard boundaries.
              Document scoring then reads only local memory; local canonical
              top-k -> all_gather [Q, P·k] -> canonical final top-k.

Per-query collective volume: O(P·B) for the candidate merge + O(P·k) for θ and
the final merge + O(P·block_budget) for the bounds merge when the budget binds
— independent of corpus size (index reads stay local). Compute per shard keeps
the single-device *shapes* (the worst case where one shard owns every global
candidate is real) except phase-3 scoring, which a binding budget caps at
``block_budget`` blocks per shard instead of budget·c: the paper's bounded
phase-3 cost survives sharding. Index memory is 1/P per device: sharding buys
capacity and bandwidth, not FLOP count (DESIGN.md §8).

Static/dynamic split (DESIGN.md §9): all shapes — candidate widths, per-shard
θ-list widths (k_max), merge widths, the block-budget cut — come from
``StaticConfig``; the dynamic (k, μ, η, β) thread through every stage as
traced [Q] arrays exactly as in ``core.lsp.search_retrieve``, so one compiled
sharded program serves any ``DynamicParams`` point (mixed per row)
bit-identically to a re-jitted static config AND to the single-device program
at the same point. The budget itself resolves through
``core.lsp.resolve_block_budget`` — the same clamp the single-device paths
use — so a competitive budget means the same cut on every topology. BMP (no
superblock level to shard on) and the legacy scoring path are rejected.

Two transports share all of the per-shard math above:
  * host-loop (``mesh=None``): shards traversed in one jitted program on any
    device count — the reference semantics, used by the property suites;
  * ``shard_map`` over the mesh ``model`` axis with ``lax.all_gather`` merges
    (queries shard over pod/data when those axes exist, else replicate).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ops
from repro.core.config import (
    DynamicArgs,
    DynamicParams,
    RetrievalConfig,
    StaticConfig,
    dynamic_args,
)
from repro.core.lsp import (
    _expand_superblocks,
    competitive_block_topk,
    make_dynamic_runner,
    mask_beyond_k,
    masked_kth_min,
    resolve_block_budget,
    split_arrays,
)
from repro.core.query import QueryBatch, prune_terms
from repro.core.scoring import NEG, score_blocks
from repro.core.topk import canonical_keep_mask, canonical_topk
from repro.index.layout import LSPIndex
from repro.distributed.retrieval import StackedShards, shard_index, to_host


class ShardedRetrievalResult(NamedTuple):
    """RetrievalResult-compatible prefix + per-shard pruning telemetry.

    The first five fields mirror ``core.lsp.RetrievalResult`` (the serving
    engine unpacks ``out[0]``/``out[1]``); the ``shard_*`` fields expose the
    per-shard view the pruning-safety property tests assert over.
    ``shard_candidates`` is the load-balance counter: each shard's share of the
    global top-γ candidate list per query (they sum to min(γ, budget)); skew
    here is what the ROADMAP's interleaved-assignment question is about."""

    doc_ids: jnp.ndarray  # int32 [Q, k] original doc ids, -1 where no result
    scores: jnp.ndarray  # float32 [Q, k]
    n_superblocks_visited: jnp.ndarray  # int32 [Q] summed over shards (distinct)
    n_blocks_scored: jnp.ndarray  # int32 [Q] summed over shards (distinct)
    theta: jnp.ndarray  # float32 [Q] the global round-0 threshold
    shard_theta: jnp.ndarray  # float32 [Q, P] per-shard local round-0 θ
    shard_superblocks: jnp.ndarray  # int32 [Q, P] distinct superblocks per shard
    shard_blocks: jnp.ndarray  # int32 [Q, P] distinct blocks per shard
    shard_candidates: jnp.ndarray  # int32 [Q, P] share of the global top-γ per shard


class _Plan(NamedTuple):
    """Static shape knobs shared by every shard (mirrors search_retrieve's locals)."""

    gamma: int
    g0: int
    budget: int  # global candidate-list width, clamped at the TRUE superblock count
    budget_l: int  # per-shard candidate contribution
    k_max: int  # widest dynamic k; sizes every k-dependent width
    width0: int  # round-0 score width g0*c*b (θ's clamp width)
    k_l: int  # per-shard θ contribution min(k_max, width0)
    ns_l: int  # per-shard (padded) superblock count
    n_shards: int
    block_budget: int  # phase-3 block cap (resolve_block_budget; == budget*c when unset)
    n_blocks_pad: int  # global PADDED block-id bound ns_l*P*c (bounds-merge id_bound)
    competitive: bool  # block_budget < budget*c: the cross-shard bounds merge runs


def make_plan(scfg: StaticConfig, ns_true: int, ns_l: int, c: int, b: int, n_shards: int) -> _Plan:
    gamma = min(scfg.gamma, ns_true)
    budget = min(scfg.resolved_sb_budget(), ns_true)
    g0 = min(scfg.gamma0, gamma, budget)
    width0 = g0 * c * b
    # the SAME resolution the single-device traversal applies over its
    # [Q, budget*c] flat candidate width — one clamp rule, every topology
    block_budget = resolve_block_budget(scfg, budget * c)
    return _Plan(
        gamma=gamma,
        g0=g0,
        budget=budget,
        budget_l=min(budget, ns_l),
        k_max=scfg.k_max,
        width0=width0,
        k_l=min(scfg.k_max, width0),
        ns_l=ns_l,
        n_shards=n_shards,
        block_budget=block_budget,
        n_blocks_pad=ns_l * n_shards * c,
        competitive=block_budget < budget * c,
    )


# --------------------------------------------------------------- per-shard stages
# Pure functions of (local index, replicated global arrays): the host-loop and
# shard_map transports call exactly this math, so the two paths cannot diverge.


def _phase1_local(local: LSPIndex, qb_pr: QueryBatch, impl: str, plan: _Plan):
    """Local SBMax + local top-budget_l candidates (stable: local id asc on ties)."""
    sbmax_l = ops.sbmax(local.sb_bounds, qb_pr.tids, qb_pr.ws, impl)  # [Q, ns_l]
    return jax.lax.top_k(sbmax_l, plan.budget_l)


def _round0_local(local: LSPIndex, qb_full, g_ids, lo, scfg, impl, plan: _Plan):
    """Score the shard's members of the GLOBAL top-γ₀ superblocks."""
    g0_ids = g_ids[:, : plan.g0]
    owned0 = (g0_ids >= lo) & (g0_ids < lo + plan.ns_l)
    loc0 = jnp.clip(g0_ids - lo, 0, plan.ns_l - 1)
    blk0 = _expand_superblocks(loc0, local.c)  # [Q, g0*c] local block ids
    mask0 = jnp.repeat(owned0, local.c, axis=1)
    scores0, pos0 = score_blocks(local, qb_full, blk0, mask0, scfg.doc_layout, impl)
    return owned0, loc0, scores0, pos0


def _local_theta(scores0: jnp.ndarray, plan: _Plan, k) -> jnp.ndarray:
    """The shard-local round-0 threshold (same clamp rule as _kth_threshold)."""
    vals, _ = jax.lax.top_k(scores0, plan.k_l)
    return masked_kth_min(vals, jnp.minimum(k, plan.width0))


def merge_theta(theta_lists: jnp.ndarray, plan: _Plan, k) -> jnp.ndarray:
    """Global θ from concatenated per-shard top-k_l round-0 score lists [Q, P*k_l].

    Takes the min over the top-min(k, width0) of the union — exactly what
    ``_kth_threshold`` computes over the unsharded round-0 array: if k exceeds
    the round-0 width the single-device θ degrades to the global min (usually
    clamped to 0), and min(k, width0) reproduces that degradation. The list
    width k_l = min(k_max, width0) bounds every dynamic k's selection, so one
    merge width serves the whole dynamic range."""
    vals, _ = jax.lax.top_k(theta_lists, min(plan.k_max, plan.width0))
    return masked_kth_min(vals, jnp.minimum(k, plan.width0))


class _Phase2(NamedTuple):
    """Per-shard phase-2 output: the η-cut block-bound candidates (flattened,
    with their GLOBAL block ids) plus what the accounting needs downstream."""

    loc_idx: jnp.ndarray  # int32 [Q, budget] clipped local candidate superblock ids
    eligible: jnp.ndarray  # bool [Q, budget] ownership-masked eligibility
    owned: jnp.ndarray  # bool [Q, budget] candidate-ownership (load balance)
    flat_bounds: jnp.ndarray  # f32 [Q, budget*c] η-cut bounds, NEG elsewhere
    flat_gids: jnp.ndarray  # int32 [Q, budget*c] GLOBAL block ids of the flat slots


def _phase2_local(
    local: LSPIndex,
    lo,
    qb_pr: QueryBatch,
    g_vals,
    g_ids,
    theta,
    scfg: StaticConfig,
    d: DynamicArgs,
    impl: str,
    plan: _Plan,
) -> _Phase2:
    """Eligibility at the global (rank, value, θ) + block BoundSums + θ/η cut.

    ``flat_gids`` expands the GLOBAL candidate ids (``g_ids`` — bit-identical
    to the single-device ``top_idx`` by stage-1 parity), so the per-shard
    (bound, gid) candidates are exactly the single-device flat candidates
    partitioned by ownership: non-owned and η-cut slots are NEG-bounded and
    inert under every downstream mask."""
    c, ns_l = local.c, plan.ns_l
    rank = jnp.arange(plan.budget)[None, :]
    th = theta[:, None]
    mu = d.mu[:, None]
    eta = d.eta[:, None]
    owned = (g_ids >= lo) & (g_ids < lo + ns_l)
    loc_idx = jnp.clip(g_ids - lo, 0, ns_l - 1)
    in_gamma = (rank < plan.gamma) & (g_vals >= th)
    if scfg.variant == "lsp0":
        eligible = in_gamma
    elif scfg.variant == "lsp1":
        eligible = in_gamma | (g_vals > th / mu)
    elif scfg.variant in ("lsp2", "sp"):
        assert local.sb_avg is not None, f"{scfg.variant} needs superblock averages"
        sbavg_l = ops.sbmax(local.sb_avg, qb_pr.tids, qb_pr.ws, impl)  # [Q, ns_l]
        avg_vals = jnp.take_along_axis(sbavg_l, loc_idx, axis=1)  # garbage if !owned
        sp_rule = (g_vals > th / mu) | (avg_vals > th / eta)
        eligible = (in_gamma | sp_rule) if scfg.variant == "lsp2" else sp_rule
    else:
        raise ValueError(f"unknown variant {scfg.variant!r}")
    if scfg.variant != "sp":
        eligible = eligible & (rank >= plan.g0)  # round 0 already scored these
    eligible = eligible & owned  # each shard prunes/scores only what it owns

    blk_bounds = ops.gathered_block_bounds(
        local.blk_bounds, c, qb_pr.tids, qb_pr.ws, loc_idx, impl
    )  # [Q, budget, c]
    blk_bounds = jnp.where(eligible[:, :, None], blk_bounds, NEG)
    blk_keep = blk_bounds > th[:, :, None] / eta[:, :, None]
    flat_bounds = jnp.where(blk_keep, blk_bounds, NEG).reshape(blk_bounds.shape[0], -1)
    flat_gids = _expand_superblocks(g_ids, c)  # == the single-device flat gids
    return _Phase2(loc_idx, eligible, owned, flat_bounds, flat_gids)


def _local_block_candidates(p2: _Phase2, plan: _Plan):
    """This shard's contribution to the cross-shard bounds merge: its canonical
    top-``block_budget`` (bound desc, global block-id asc) — a block outside
    the local top-budget is outside the global top-budget a fortiori, so the
    list covers everything this shard could contribute to the global cut.
    Same ``competitive_block_topk`` the single-device cut runs."""
    return competitive_block_topk(
        p2.flat_bounds, p2.flat_gids, plan.block_budget, plan.n_blocks_pad + 1
    )


def merge_block_cutoff(cat_vals, cat_gids, plan: _Plan):
    """Global block cutoff from the concatenated per-shard bound lists
    [Q, P·block_budget]: the budget-th (bound, id) pair of their canonical
    top-``block_budget``. By the composition property (core/topk.py) that
    top-k equals the canonical top-k over ALL blocks that survived the η-cut,
    so the cutoff is exactly the single-device cut boundary — block ids are
    globally unique, the order is total, and masking each shard at this pair
    (``canonical_keep_mask``) keeps exactly the single-device selection, ties
    straddling shard boundaries included. O(P·block_budget) per query."""
    gv, gg = canonical_topk(
        cat_vals, cat_gids, plan.block_budget, id_bound=plan.n_blocks_pad + 1
    )
    return gv[:, -1], gg[:, -1]


def _phase3_local(
    local: LSPIndex,
    lo,
    qb_full,
    p2: _Phase2,
    owned0,
    loc0,
    scores0,
    pos0,
    block_cut,
    scfg: StaticConfig,
    d: DynamicArgs,
    impl: str,
    plan: _Plan,
):
    """Block selection (full-width or cutoff-masked competitive), local doc
    scoring, local canonical top-k_max and distinct-visit + load-balance
    accounting. ``block_cut`` is None (non-binding budget: the θ/η cut is the
    only block filter) or this shard's (bounds, gids, mask) candidate list
    plus the global (cut_val, cut_id) pair from ``merge_block_cutoff``."""
    c = local.c
    rank = jnp.arange(plan.budget)[None, :]
    if scfg.variant == "sp":
        # faithful SP: round 0 only seeds θ; its documents are not returned
        scores0 = jnp.full_like(scores0, NEG)
    if block_cut is None:
        width = plan.budget * c  # full width: every η-cut survivor is scored
        bvals, bidx = jax.lax.top_k(p2.flat_bounds, width)
        sel_sb = jnp.take_along_axis(p2.loc_idx, bidx // c, axis=1)
        blk_ids = sel_sb * c + bidx % c
        blk_mask = bvals > NEG / 2
    else:
        lb_vals, lb_gids, lb_mask, cut_v, cut_id = block_cut
        # membership at the global cutoff: exactly the owned members of the
        # global top-block_budget survive — phase-3 width shrinks from
        # budget*c to block_budget per shard (the bounded-cost point)
        blk_mask = lb_mask & canonical_keep_mask(lb_vals, lb_gids, cut_v, cut_id)
        blk_ids = jnp.where(blk_mask, lb_gids - lo * c, 0)  # local block ids

    scores1, pos1 = score_blocks(local, qb_full, blk_ids, blk_mask, scfg.doc_layout, impl)

    all_scores = jnp.concatenate([scores0, scores1], axis=1)
    all_pos = jnp.concatenate([pos0, pos1], axis=1)
    n_pad = local.doc_remap.shape[0]
    all_ids = local.doc_remap[jnp.clip(all_pos, 0, n_pad - 1)]  # ORIGINAL doc ids
    vals_k, ids_k = canonical_topk(
        all_scores, all_ids.astype(jnp.int32), plan.k_max, id_bound=local.n_docs + 1
    )
    ids_k = jnp.where(vals_k > NEG / 2, ids_k, -1)
    vals_k = jnp.where(vals_k > NEG / 2, vals_k, jnp.float32(NEG))

    # distinct-visit accounting, partitioned by ownership: summed over shards it
    # reproduces the single-device counters exactly (each candidate has one
    # owner, and the competitive keep-set partitions the single-device one)
    n_owned0 = owned0.sum(axis=1, dtype=jnp.int32)
    in_round0 = ((blk_ids[:, :, None] // c == loc0[:, None, :]) & owned0[:, None, :]).any(2)
    n_blk = n_owned0 * c + (blk_mask & ~in_round0).sum(axis=1, dtype=jnp.int32)
    n_sb = n_owned0 + (p2.eligible & (rank >= plan.g0)).sum(axis=1, dtype=jnp.int32)
    # load balance: this shard's share of the global top-γ candidate list — the
    # ownership skew contiguous superblock ranges can produce (ROADMAP item)
    n_cand = (p2.owned & (rank < plan.gamma)).sum(axis=1, dtype=jnp.int32)
    return ids_k, vals_k, n_sb, n_blk, n_cand


def _split_cfg(cfg, dyn):
    """Accept the legacy combined RetrievalConfig or the split StaticConfig."""
    if isinstance(cfg, RetrievalConfig):
        return cfg.static(), (dyn if dyn is not None else cfg.dynamic())
    return cfg, dyn


def _validate(scfg: StaticConfig, impl: str) -> None:
    if scfg.variant not in ("lsp0", "lsp1", "lsp2", "sp"):
        raise ValueError(
            f"ShardedRetriever: variant {scfg.variant!r} has no superblock level to shard on"
            if scfg.variant in ("bmp", "exact")
            else f"unknown variant {scfg.variant!r}"
        )
    if scfg.doc_layout != "fwd":
        raise ValueError("ShardedRetriever: shards carry the fwd quantized operand only")
    if impl == "legacy":
        raise ValueError("ShardedRetriever: legacy scoring is a single-device baseline")


# ------------------------------------------------------------------- host loop


def sharded_retrieve(
    shards: Sequence[LSPIndex],
    qb_full: QueryBatch,
    cfg: Union[RetrievalConfig, StaticConfig],
    impl: str = "auto",
    ns_true: Optional[int] = None,
    dyn: Union[DynamicParams, DynamicArgs, None] = None,
) -> ShardedRetrievalResult:
    """Host-loop transport: every shard traversed in-process (one XLA program
    under jit). Bit-identical to ``search_retrieve`` on the unsharded index, and
    to the shard_map transport — the property suites pin both. ``cfg`` is a
    ``StaticConfig`` (with ``dyn`` supplying the traced point) or the legacy
    combined ``RetrievalConfig`` (its dynamic half is the default point)."""
    scfg, dyn = _split_cfg(cfg, dyn)
    meta = shards[0]
    ns_true = ns_true if ns_true is not None else sum(s.n_superblocks for s in shards)
    _validate(scfg, impl)
    plan = make_plan(scfg, ns_true, meta.n_superblocks, meta.c, meta.b, len(shards))
    d = dynamic_args(dyn, qb_full.tids.shape[0], scfg.k_max)
    bounds_impl = impl
    qb_pr = prune_terms(qb_full, d.beta)

    # stage 1: local candidates -> global canonical candidate list (replicated)
    lvs, lis = zip(*(_phase1_local(s, qb_pr, bounds_impl, plan) for s in shards))
    vals_cat = jnp.concatenate(lvs, axis=1)
    ids_cat = jnp.concatenate(
        [li + p * plan.ns_l for p, li in enumerate(lis)], axis=1
    ).astype(jnp.int32)
    g_vals, g_ids = canonical_topk(
        vals_cat, ids_cat, plan.budget, id_bound=plan.ns_l * plan.n_shards
    )

    # stage 2: round-0 scoring of owned global-top-γ₀ members -> global θ
    r0 = [
        _round0_local(s, qb_full, g_ids, p * plan.ns_l, scfg, impl, plan)
        for p, s in enumerate(shards)
    ]
    shard_theta = jnp.stack(
        [_local_theta(scores0, plan, d.k) for _, _, scores0, _ in r0], axis=1
    )
    th_lists = jnp.concatenate([jax.lax.top_k(s0, plan.k_l)[0] for _, _, s0, _ in r0], axis=1)
    theta = merge_theta(th_lists, plan, d.k)

    # stage 3: eligibility + block bounds + θ/η cut per shard
    p2s = [
        _phase2_local(s, p * plan.ns_l, qb_pr, g_vals, g_ids, theta, scfg, d, impl, plan)
        for p, s in enumerate(shards)
    ]
    # cross-shard bounds merge: only when the block budget binds — each shard's
    # canonical top-block_budget bound list concatenates (the host-loop's
    # all_gather) into the global cutoff every shard masks its keep-set at
    cuts = [None] * plan.n_shards
    if plan.competitive:
        lbs = [_local_block_candidates(p2, plan) for p2 in p2s]
        cut_v, cut_id = merge_block_cutoff(
            jnp.concatenate([lb[0] for lb in lbs], axis=1),
            jnp.concatenate([lb[1] for lb in lbs], axis=1),
            plan,
        )
        cuts = [(lb[0], lb[1], lb[2], cut_v, cut_id) for lb in lbs]
    # phase 3: block selection + scoring, local canonical top-k
    parts = [
        _phase3_local(
            s, p * plan.ns_l, qb_full, p2s[p],
            r0[p][0], r0[p][1], r0[p][2], r0[p][3], cuts[p], scfg, d, impl, plan,
        )
        for p, s in enumerate(shards)
    ]
    ids_cat = jnp.concatenate([pr[0] for pr in parts], axis=1)
    vals_cat = jnp.concatenate([pr[1] for pr in parts], axis=1)
    fvals, fids = canonical_topk(vals_cat, ids_cat, plan.k_max, id_bound=meta.n_docs + 1)
    fvals, fids = mask_beyond_k(fvals, fids, d.k, plan.k_max)
    n_sb = jnp.stack([pr[2] for pr in parts], axis=1)  # [Q, P]
    n_blk = jnp.stack([pr[3] for pr in parts], axis=1)
    n_cand = jnp.stack([pr[4] for pr in parts], axis=1)
    return ShardedRetrievalResult(
        doc_ids=fids,
        scores=fvals,
        n_superblocks_visited=n_sb.sum(axis=1),
        n_blocks_scored=n_blk.sum(axis=1),
        theta=theta,
        shard_theta=shard_theta,
        shard_superblocks=n_sb,
        shard_blocks=n_blk,
        shard_candidates=n_cand,
    )


# ------------------------------------------------------------------- shard_map


def _local_index_from(
    meta: LSPIndex, sb_packed, blk_packed, sbavg_packed, tids, ws, scales, remap, bound_scales
) -> LSPIndex:
    sb_scale, blk_scale, *avg_scale = bound_scales
    return LSPIndex(
        b=meta.b,
        c=meta.c,
        n_docs=meta.n_docs,
        vocab=meta.vocab,
        n_blocks=meta.n_blocks,
        n_superblocks=meta.n_superblocks,
        sb_bounds=meta.sb_bounds._replace(packed=sb_packed, scale=sb_scale),
        blk_bounds=meta.blk_bounds._replace(packed=blk_packed, scale=blk_scale),
        sb_avg=None
        if meta.sb_avg is None
        else meta.sb_avg._replace(packed=sbavg_packed, scale=avg_scale[0]),
        docs_fwd=None,
        docs_flat=None,
        doc_remap=remap,
        docs_fwdq=meta.docs_fwdq._replace(tids=tids, ws=ws, scales=scales),
        docs_flatq=None,
    )


class _StackedShardsAvg(StackedShards):
    """StackedShards + the sb_avg operand (needed by lsp2/sp under sharding) + the
    per-term bound scales every shard shares, replicated over the mesh."""

    def __init__(self, shards: Sequence[LSPIndex], mesh):
        super().__init__(list(shards), mesh)
        meta = shards[0]
        self.sbavg_packed = (
            None if meta.sb_avg is None else self.stack(lambda s: s.sb_avg.packed)
        )
        replicated = NamedSharding(mesh, P())
        self.bound_scales = tuple(
            jax.device_put(np.asarray(pb.scale, np.float32), replicated)
            for pb in (meta.sb_bounds, meta.blk_bounds, meta.sb_avg)
            if pb is not None
        )


def make_sharded_mesh_fn(
    shards: Sequence[LSPIndex], scfg: StaticConfig, mesh, impl: str, ns_true: int
):
    """shard_map transport: same stages, lax.all_gather merges over `model`.
    Returns ``(fn, arrays)``: ``arrays`` are the stacked shard operands, placed
    once over the mesh's ``model`` axis, and ``fn(arrays, tids, ws, k, mu, eta,
    beta)`` takes them as arguments — the dynamic point rides the same
    replicated (or data-sharded) spec as the query batch."""
    from jax import shard_map

    stacked = _StackedShardsAvg(shards, mesh)
    meta = stacked.meta
    plan = make_plan(scfg, ns_true, meta.n_superblocks, meta.c, meta.b, len(shards))
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    data_sharded = any(mesh.shape[a] > 1 for a in batch_axes if a in mesh.axis_names)
    qspec = P(batch_axes, None) if data_sharded else P(None, None)
    have_avg = stacked.sbavg_packed is not None

    def local_fn(sb_packed, blk_packed, sbavg_packed, fwdq_tids, fwdq_ws, fwdq_scales, remap, bound_scales, q_tids, q_ws, d_k, d_mu, d_eta, d_beta):
        local = _local_index_from(
            meta, sb_packed[0], blk_packed[0], None if not have_avg else sbavg_packed[0],
            fwdq_tids[0], fwdq_ws[0], fwdq_scales[0], remap[0], bound_scales,
        )
        lo = jax.lax.axis_index("model") * plan.ns_l
        qb = QueryBatch(q_tids, q_ws, meta.vocab)
        d = DynamicArgs(d_k, d_mu, d_eta, d_beta)
        qb_pr = prune_terms(qb, d.beta)

        lv, li = _phase1_local(local, qb_pr, impl, plan)
        vals_cat = jax.lax.all_gather(lv, "model", axis=1, tiled=True)
        ids_cat = jax.lax.all_gather((li + lo).astype(jnp.int32), "model", axis=1, tiled=True)
        g_vals, g_ids = canonical_topk(
            vals_cat, ids_cat, plan.budget, id_bound=plan.ns_l * plan.n_shards
        )

        owned0, loc0, scores0, pos0 = _round0_local(local, qb, g_ids, lo, scfg, impl, plan)
        theta_l = _local_theta(scores0, plan, d.k)
        th_lists = jax.lax.all_gather(
            jax.lax.top_k(scores0, plan.k_l)[0], "model", axis=1, tiled=True
        )
        theta = merge_theta(th_lists, plan, d.k)

        p2 = _phase2_local(local, lo, qb_pr, g_vals, g_ids, theta, scfg, d, impl, plan)
        cut = None
        if plan.competitive:
            # cross-shard bounds merge: local top-block_budget bound lists
            # all_gather over `model` into [Q, P·block_budget]; the canonical
            # cutoff pair replicates, each shard masks its own keep-set at it
            lb_vals, lb_gids, lb_mask = _local_block_candidates(p2, plan)
            cat_v = jax.lax.all_gather(lb_vals, "model", axis=1, tiled=True)
            cat_g = jax.lax.all_gather(lb_gids, "model", axis=1, tiled=True)
            cut_v, cut_id = merge_block_cutoff(cat_v, cat_g, plan)
            cut = (lb_vals, lb_gids, lb_mask, cut_v, cut_id)
        ids_k, vals_k, n_sb, n_blk, n_cand = _phase3_local(
            local, lo, qb, p2, owned0, loc0, scores0, pos0, cut, scfg, d, impl, plan,
        )
        fids = jax.lax.all_gather(ids_k, "model", axis=1, tiled=True)
        fvals = jax.lax.all_gather(vals_k, "model", axis=1, tiled=True)
        mvals, mids = canonical_topk(fvals, fids, plan.k_max, id_bound=meta.n_docs + 1)
        mvals, mids = mask_beyond_k(mvals, mids, d.k, plan.k_max)
        shard_sb = jax.lax.all_gather(n_sb[:, None], "model", axis=1, tiled=True)
        shard_blk = jax.lax.all_gather(n_blk[:, None], "model", axis=1, tiled=True)
        shard_th = jax.lax.all_gather(theta_l[:, None], "model", axis=1, tiled=True)
        shard_cand = jax.lax.all_gather(n_cand[:, None], "model", axis=1, tiled=True)
        return ShardedRetrievalResult(
            doc_ids=mids,
            scores=mvals,
            n_superblocks_visited=shard_sb.sum(axis=1),
            n_blocks_scored=shard_blk.sum(axis=1),
            theta=theta,
            shard_theta=shard_th,
            shard_superblocks=shard_sb,
            shard_blocks=shard_blk,
            shard_candidates=shard_cand,
        )

    shard_spec3 = P("model", None, None)
    vec_spec = P(batch_axes) if data_sharded else P(None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            shard_spec3,
            shard_spec3,
            shard_spec3 if have_avg else P(None),
            P("model", None, None, None),
            P("model", None, None, None),
            P("model", None),
            P("model", None),
            tuple(P() for _ in stacked.bound_scales),
            qspec,
            qspec,
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
        ),
        out_specs=ShardedRetrievalResult(
            doc_ids=qspec,
            scores=qspec,
            n_superblocks_visited=vec_spec,
            n_blocks_scored=vec_spec,
            theta=vec_spec,
            shard_theta=qspec,
            shard_superblocks=qspec,
            shard_blocks=qspec,
            shard_candidates=qspec,
        ),
        check_vma=False,
    )
    arrays = (
        stacked.sb_packed,
        stacked.blk_packed,
        stacked.sbavg_packed if have_avg else jnp.zeros((1,), jnp.uint32),
        stacked.fwdq_tids,
        stacked.fwdq_ws,
        stacked.fwdq_scales,
        stacked.remap,
        stacked.bound_scales,
    )

    def run(arrays, tids, ws, k, mu, eta, beta):
        return fn(*arrays, tids, ws, k, mu, eta, beta)

    return run, arrays


# ------------------------------------------------------------------- retriever


class ShardedRetriever:
    """Engine-pluggable sharded retriever: ``retrieve(QueryBatch[, dyn]) ->
    result`` whose (doc_ids, scores) prefix is bit-identical to the
    single-device program at the same (static, dynamic) point. Accepts an
    unsharded ``LSPIndex`` (sharded here) or a pre-sharded list (e.g.
    ``index.store.load_sharded_index``; pass the global ``ns_true`` from the
    manifest — shard-local padding makes it unrecoverable from the shards
    alone).

    ``mesh=None`` runs the host-loop transport (any device count, one program);
    a mesh with a ``model`` axis of size ``n_shards`` runs under shard_map.
    Exposes the same ``warmup(shapes)`` / ``n_traces()`` / ``supports_dynamic``
    contract as ``core.lsp.jit_search`` so the serving engine's bucket ladder
    pre-compiles every shape and threads per-request ``DynamicParams``."""

    supports_dynamic = True

    def __init__(
        self,
        index_or_shards,
        cfg: Union[RetrievalConfig, StaticConfig],
        n_shards: Optional[int] = None,
        mesh=None,
        impl: str = "auto",
        ns_true: Optional[int] = None,
        defaults: Optional[DynamicParams] = None,
    ):
        scfg, default_dyn = _split_cfg(cfg, defaults)
        if isinstance(index_or_shards, LSPIndex):
            ns_true = index_or_shards.n_superblocks
            assert n_shards, "n_shards required when passing an unsharded index"
            shards = shard_index(index_or_shards, n_shards)
        elif hasattr(index_or_shards, "shards"):  # index.store.ShardedIndex
            shards = list(index_or_shards.shards)
            ns_true = index_or_shards.n_superblocks
        else:
            shards = list(index_or_shards)
            if ns_true is None:
                ns_true = sum(s.n_superblocks for s in shards)  # exact iff unpadded
        self.shards = shards
        self.n_shards = len(shards)
        self.static_cfg = scfg
        self.cfg = cfg  # as passed (legacy callers read .cfg back)
        self.defaults = (default_dyn or DynamicParams(k=scfg.k_max)).validate_for(scfg)
        self.impl = impl
        self.ns_true = ns_true
        self.vocab = shards[0].vocab
        self.mesh = mesh
        _validate(scfg, impl)
        self._traces = {"n": 0}
        traces = self._traces
        if mesh is not None:
            assert mesh.shape["model"] == self.n_shards, (
                f"mesh model axis {mesh.shape['model']} != n_shards {self.n_shards}"
            )
            # the mesh transport places each shard on its own devices
            # (StackedShards); the shards keep no copy on the default device
            shards = self.shards = [to_host(s) for s in shards]
            mesh_run, arrays = make_sharded_mesh_fn(shards, scfg, mesh, impl, ns_true)

            @jax.jit
            def _fn(arrays, tids, ws, k, mu, eta, beta):
                traces["n"] += 1
                return mesh_run(arrays, tids, ws, k, mu, eta, beta)

            self._fn = _fn
        else:
            imp, nst = impl, ns_true
            arrays, rebuild = split_arrays(shards)

            @jax.jit
            def _host(arrays, tids, ws, k, mu, eta, beta):
                traces["n"] += 1
                sh = rebuild(arrays)
                return sharded_retrieve(
                    sh, QueryBatch(tids, ws, sh[0].vocab), scfg, imp, nst,
                    dyn=DynamicArgs(k, mu, eta, beta),
                )

            self._fn = _host
        # the same wrapper jit_search and the 'exact' backend use: validation,
        # [Q] broadcasting, sentinel warmup, trace counter — one contract
        self._run = make_dynamic_runner(
            self._fn, arrays, scfg, self.defaults, self.vocab, traces
        )

    def __call__(self, qb: QueryBatch, dyn=None) -> ShardedRetrievalResult:
        return self._run(qb, dyn)

    def n_traces(self) -> int:
        return self._traces["n"]

    def warmup(self, shapes) -> None:
        """Pre-compile every (Q, nq) bucket shape with sentinel-only queries."""
        self._run.warmup(shapes)

    @classmethod
    def from_dir(cls, directory: str, cfg, mesh=None, impl: str = "auto", defaults=None):
        """Build from a persisted sharded index (``index.store.save_sharded_index``)."""
        from repro.index.store import load_index_auto

        return cls(
            load_index_auto(directory, mmap=True, device=True), cfg,
            mesh=mesh, impl=impl, defaults=defaults,
        )
