"""LSP/0, LSP/1, LSP/2 + SP and BMP baselines — batched, static-shape, jit-able.

Faithful reproduction of the paper's traversal semantics, restructured for TPU
(DESIGN.md §2). The CPU implementation's continuously-updated threshold θ becomes a
two-round scheme:

  round 0  score all documents of the top-γ₀ superblocks; θ = k-th best score.
  round 1  apply the variant's superblock pruning rule with θ, compute block
           BoundSums for surviving superblocks, prune blocks at θ/η, score the rest.

Round-0 superblocks are exactly the first γ₀ entries of the SBMax-descending order, so
round 1 skips them and the union of both rounds equals the paper's visitation set. The
two-round θ is never larger than the CPU's θ at the same traversal point, i.e. we prune
at most as aggressively — recall is preserved or slightly improved at equal parameters.

Variant pruning rules (paper §4.1), applied to the SBMax-sorted candidate list:
  LSP/0  visit top-γ superblocks with SBMax >= θ; nothing else.
  LSP/1  LSP/0 ∪ { X : SBMax(X) > θ/μ }           (both sets are prefixes!)
  LSP/2  LSP/0 ∪ { X : SBMax(X) > θ/μ or SBavg(X) > θ/η }   (SP rule + guarantee)
  SP     { X : SBMax(X) > θ/μ or SBavg(X) > θ/η }  — no guarantee; can fail (Fig. 2)
  BMP    no superblock level: BoundSum over all blocks, prune at θ/η.

Both scoring rounds (round-0 superblock expansion and phase-3 block scoring) route
through ``score_blocks`` -> ``ops.score_gather``: one dispatch, ref/kernel parity,
fwd or flat quantized operands (DESIGN.md §3-4).

Static/dynamic split (DESIGN.md §9): the traversal takes a shape-bearing
``StaticConfig`` plus traced per-row ``DynamicArgs`` (k ≤ k_max, μ, η, β) —
``search_retrieve``/``jit_search`` are the canonical entry points, and ONE
compiled program serves any dynamic point (even mixed within a batch)
bit-identically to a program re-jitted with those values baked in. The legacy
``retrieve``/``jit_retrieve`` (combined ``RetrievalConfig``) remain as thin
deprecation shims over the same code path.

impl: "auto" | "ref" | "kernel" as elsewhere, plus "legacy" — the seed's
position-major jnp scoring, kept addressable so benchmarks can track the fused
path's speedup against the pre-doc_score baseline. ("legacy" assumes the static
point k == k_max; it exists for profiling, not for dynamic serving.)
"""

from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops
from repro.core.config import (
    DynamicArgs,
    DynamicParams,
    RetrievalConfig,
    StaticConfig,
    dynamic_args,
)
from repro.core.query import QueryBatch, prune_terms, scatter_dense
from repro.core.scoring import NEG, score_blocks, score_positions_fwd
from repro.core.topk import canonical_topk
from repro.index.layout import LSPIndex


class RetrievalResult(NamedTuple):
    doc_ids: jnp.ndarray  # int32 [Q, k] original doc ids, -1 where no result
    scores: jnp.ndarray  # float32 [Q, k]
    n_superblocks_visited: jnp.ndarray  # int32 [Q]
    n_blocks_scored: jnp.ndarray  # int32 [Q]
    theta: Optional[jnp.ndarray] = None  # float32 [Q] round-0 pruning threshold


def masked_kth_min(vals: jnp.ndarray, k_sel: jnp.ndarray) -> jnp.ndarray:
    """min over the first k_sel lanes of a descending top-k list [Q, W] == the
    per-row k_sel-th value, clamped at 0. The elementwise +inf mask before a
    full reduce consumes every lane, which keeps XLA on its fast TopK lowering
    (a slice would be rewritten into a full variadic sort — see _kth_threshold).
    Both the single-device θ and the sharded θ merges use THIS function, so the
    two paths' order statistics cannot drift apart."""
    sel = jnp.arange(vals.shape[-1])[None, :] < k_sel[:, None]
    return jnp.maximum(jnp.where(sel, vals, jnp.inf).min(axis=-1), 0.0)


def _kth_threshold(scores: jnp.ndarray, k, k_max: int, legacy: bool = False) -> jnp.ndarray:
    """θ = k-th best score (0 if fewer than k valid docs -> prunes nothing unsafely).

    ``k`` may be a traced int32 [Q] array (per-row dynamic k ≤ k_max): the min is
    then taken over the first k lanes of the top-min(k_max, width) list via an
    elementwise +inf mask. Consuming all lanes keeps XLA on its fast TopK
    lowering — the sliced form gets rewritten to a full variadic sort, ~60x
    slower on CPU for round-0-sized inputs — and for k == k_max the mask is
    all-true, reducing to exactly the static ``vals.min``. ``legacy`` keeps the
    seed's sliced form so impl="legacy" reproduces the pre-doc_score execution
    profile (static point only)."""
    width = scores.shape[-1]
    kk = min(k_max, width)
    vals, _ = jax.lax.top_k(scores, kk)
    if legacy:
        return jnp.maximum(vals[:, -1], 0.0)
    if not isinstance(k, jnp.ndarray):
        if min(int(k), width) == kk:
            return jnp.maximum(vals.min(axis=-1), 0.0)
        k = jnp.full((scores.shape[0],), k, jnp.int32)
    return masked_kth_min(vals, jnp.minimum(k, width))


def mask_beyond_k(vals: jnp.ndarray, ids: jnp.ndarray, k, k_max: int):
    """Finalize a canonical top-k_max selection: invalid slots (no candidate) and
    slots at rank >= the row's dynamic k become (NEG, -1). The first k columns
    of the k_max-wide canonical order ARE the canonical top-k (the order is
    total), which is what makes dynamic k bit-identical to a re-jitted static
    k. Returns (scores, ids)."""
    valid = vals > NEG / 2
    if isinstance(k, jnp.ndarray):
        valid = valid & (jnp.arange(vals.shape[-1])[None, :] < k[:, None])
    elif k < k_max:
        valid = valid & (jnp.arange(vals.shape[-1])[None, :] < k)
    return jnp.where(valid, vals, jnp.float32(NEG)), jnp.where(valid, ids, -1)


def _expand_superblocks(sb_idx: jnp.ndarray, c: int) -> jnp.ndarray:
    """Superblock ids [Q, S] -> their block ids [Q, S*c]."""
    blk = sb_idx[:, :, None] * c + jnp.arange(c)[None, None, :]
    return blk.reshape(blk.shape[0], -1)


def resolve_block_budget(scfg, cand_blocks: int, default: int = 0) -> int:
    """The one clamp rule for the phase-3 block cap, shared by every variant
    and every topology: an explicit ``block_budget`` (or the variant's default
    when unset) can never exceed the candidate width in blocks. The lsp path
    (cand_blocks = budget·c), the bmp path (cand_blocks = n_blocks, default =
    4·γ·c), the dense mirror and the sharded plan (distributed/sharded.py)
    all derive their cut width HERE, so an oversized budget clamps identically
    everywhere and a competitive one means the same thing on every path."""
    bb = scfg.block_budget or (default or cand_blocks)
    return min(bb, cand_blocks)


def competitive_block_topk(
    flat_bounds: jnp.ndarray, flat_gids: jnp.ndarray, block_budget: int, id_bound: int
):
    """THE competitive block cut: top-``block_budget`` of the flattened
    (bound, block-id) candidates under the canonical (bound desc, id asc)
    order. ``lax.top_k`` would tie-break equal bounds by candidate-list rank —
    an artifact of traversal order a sharded pipeline cannot reproduce — so
    a binding budget cuts on the same total order the document merges use.
    Returns (bounds, block_ids, mask); masked slots (fewer survivors than the
    budget) get id 0, inert under the mask for every downstream gather.
    The single-device traversal applies this directly over [Q, budget·c]; each
    shard applies it to its owned slots to produce its contribution to the
    cross-shard bounds merge (distributed/sharded.py) — one implementation,
    so the local and sharded cuts cannot drift apart."""
    bvals, gids = canonical_topk(flat_bounds, flat_gids, block_budget, id_bound=id_bound)
    mask = bvals > NEG / 2
    return bvals, jnp.where(mask, gids, 0), mask


def _score_blocks_dispatch(index, qb_full, blk_ids, blk_mask, scfg, impl):
    """Layout + impl routing for both scoring rounds, including the legacy baseline."""
    if impl == "legacy":
        b = index.b
        pos = blk_ids[:, :, None] * b + jnp.arange(b)[None, None, :]
        pos = pos.reshape(pos.shape[0], -1)
        scores = score_positions_fwd(index, scatter_dense(qb_full), pos)
        mask = jnp.repeat(blk_mask, b, axis=1)
        return jnp.where(mask, scores, NEG), pos
    return score_blocks(index, qb_full, blk_ids, blk_mask, scfg.doc_layout, impl)


_IMPLS = ("auto", "ref", "kernel", "legacy")

Dynamic = Union[DynamicParams, DynamicArgs, None]


def search_retrieve(
    index: LSPIndex,
    qb_full: QueryBatch,
    scfg: StaticConfig,
    dyn: Dynamic = None,
    impl: str = "auto",
) -> RetrievalResult:
    """The unified traversal: static shapes from ``scfg``, per-row dynamic
    (k, μ, η, β) from ``dyn`` (host params are broadcast; ``None`` means the
    static point k = k_max). Result arrays are [Q, k_max]; rows are masked at
    their dynamic k."""
    assert impl in _IMPLS, f"impl must be one of {_IMPLS}, got {impl!r}"
    if isinstance(dyn, DynamicParams):
        dyn.validate_for(scfg)
    d = dynamic_args(dyn, qb_full.tids.shape[0], scfg.k_max)
    variant = scfg.variant
    if variant == "exact":
        raise ValueError(
            "variant 'exact' has no pruned traversal; use the repro.api 'exact' "
            "backend or core.exact.retrieve_exact"
        )
    if variant == "bmp":
        return _retrieve_bmp(index, qb_full, scfg, d, impl)
    bounds_impl = "ref" if impl == "legacy" else impl

    ns, c = index.n_superblocks, index.c
    gamma = min(scfg.gamma, ns)
    budget = min(scfg.resolved_sb_budget(), ns)
    # an explicit sb_budget below γ0 caps round 0 too (the candidate list is only
    # budget wide); clamping here keeps the visited-superblock accounting honest
    g0 = min(scfg.gamma0, gamma, budget)
    qb = prune_terms(qb_full, d.beta)

    # ---- phase 1: superblock bounds (paper Eq. 1), full sorted candidate list
    sbmax = ops.sbmax(index.sb_bounds, qb.tids, qb.ws, bounds_impl)  # [Q, NS]
    top_vals, top_idx = jax.lax.top_k(sbmax, budget)

    # ---- round 0: seed θ from the guaranteed head of the list
    blk0 = _expand_superblocks(top_idx[:, :g0], c)  # [Q, g0*c]
    scores0, pos0 = _score_blocks_dispatch(
        index, qb_full, blk0, jnp.ones_like(blk0, bool), scfg, impl
    )
    theta = _kth_threshold(scores0, d.k, scfg.k_max, legacy=impl == "legacy")  # [Q]

    # ---- variant eligibility over ranks [g0, budget)
    rank = jnp.arange(budget)[None, :]
    th = theta[:, None]
    mu = d.mu[:, None]  # [Q, 1] — per-row dynamic μ/η broadcast over candidates
    eta = d.eta[:, None]
    in_gamma = (rank < gamma) & (top_vals >= th)
    if variant == "lsp0":
        eligible = in_gamma
    elif variant == "lsp1":
        eligible = in_gamma | (top_vals > th / mu)
    elif variant in ("lsp2", "sp"):
        assert index.sb_avg is not None, f"{variant} needs superblock averages in the index"
        sbavg = ops.sbmax(index.sb_avg, qb.tids, qb.ws, bounds_impl)
        avg_vals = jnp.take_along_axis(sbavg, top_idx, axis=1)
        sp_rule = (top_vals > th / mu) | (avg_vals > th / eta)
        eligible = (in_gamma | sp_rule) if variant == "lsp2" else sp_rule
    else:
        raise ValueError(f"unknown variant {variant!r}")
    if variant == "sp":
        # Faithful SP has NO guaranteed visitation: round 0 only seeds θ (the paper's
        # threshold-estimation role) and its documents are NOT returned — this is what
        # lets erroneous pruning produce empty results (paper Fig. 2).
        scores0 = jnp.full_like(scores0, NEG)
    else:
        eligible = eligible & (rank >= g0)  # round 0 already scored these

    # ---- phase 2: block bounds for surviving superblocks, prune at θ/η
    blk_bounds = ops.gathered_block_bounds(
        index.blk_bounds, c, qb.tids, qb.ws, top_idx, bounds_impl
    )  # [Q, budget, c]
    blk_bounds = jnp.where(eligible[:, :, None], blk_bounds, NEG)
    blk_keep = blk_bounds > th[:, :, None] / eta[:, :, None]

    flat_bounds = jnp.where(blk_keep, blk_bounds, NEG).reshape(blk_bounds.shape[0], -1)
    block_budget = resolve_block_budget(scfg, budget * c)
    if block_budget < budget * c:
        # binding budget: canonical cut on (bound desc, global block-id asc) —
        # the order the cross-shard bounds merge reproduces bit-identically
        bvals, blk_ids, blk_mask = competitive_block_topk(
            flat_bounds, _expand_superblocks(top_idx, c), block_budget, index.n_blocks + 1
        )
    else:
        # full width: the θ/η cut is the only block filter, every survivor is
        # selected and the positional tie-break is immaterial (set-identical)
        bvals, bidx = jax.lax.top_k(flat_bounds, block_budget)  # over [Q, budget*c]
        sel_sb = jnp.take_along_axis(top_idx, bidx // c, axis=1)
        blk_ids = sel_sb * c + bidx % c
        blk_mask = bvals > NEG / 2

    # ---- phase 3: document scoring
    scores1, pos1 = _score_blocks_dispatch(index, qb_full, blk_ids, blk_mask, scfg, impl)

    # ---- merge rounds, final top-k. Canonical (score desc, doc-id asc) selection:
    # equal-score ties at the k boundary resolve by global doc id, not by traversal
    # position — the total order a sharded merge can reproduce bit-identically.
    all_scores = jnp.concatenate([scores0, scores1], axis=1)
    all_pos = jnp.concatenate([pos0, pos1], axis=1)
    all_ids = index.doc_remap[jnp.clip(all_pos, 0, index.doc_remap.shape[0] - 1)]
    vals, ids = canonical_topk(
        all_scores, all_ids.astype(jnp.int32), scfg.k_max, id_bound=index.n_docs + 1
    )
    vals, ids = mask_beyond_k(vals, ids, d.k, scfg.k_max)

    # ---- block accounting: phase-3 blocks inside a round-0 superblock (possible for
    # the sp variant, whose eligibility does not exclude ranks < g0) are re-scores of
    # round-0 work, not additional visited blocks — count distinct blocks only.
    in_round0 = (blk_ids[:, :, None] // c == top_idx[:, None, :g0]).any(axis=2)
    n_blocks_scored = g0 * c + (blk_mask & ~in_round0).sum(axis=1, dtype=jnp.int32)

    # ---- superblock accounting mirrors the block accounting: sp's rule ignores
    # ranks < g0, so its eligibility can re-select round-0 superblocks — those are
    # re-visits, not new superblocks; count distinct only (the non-sp variants
    # already fold rank >= g0 into eligible, making the mask a no-op there).
    n_sb_new = (eligible & (rank >= g0)).sum(axis=1, dtype=jnp.int32)

    return RetrievalResult(
        doc_ids=ids,
        scores=vals,
        n_superblocks_visited=g0 + n_sb_new,
        n_blocks_scored=n_blocks_scored,
        theta=theta,
    )


def _retrieve_bmp(
    index: LSPIndex, qb_full: QueryBatch, scfg: StaticConfig, d: DynamicArgs, impl: str
) -> RetrievalResult:
    """BMP baseline: single-level block filtering (Mallia et al. '24) on our layout.

    The round-0 block count b0 is sized by the *static* k_max (it is shape-
    bearing), so bmp's dynamic-k guarantee is weaker than the lsp variants':
    results match a re-jitted static config only at k == k_max."""
    nb, b = index.n_blocks, index.b
    bounds_impl = "ref" if impl == "legacy" else impl
    qb = prune_terms(qb_full, d.beta)

    boundsum = ops.sbmax(index.blk_bounds, qb.tids, qb.ws, bounds_impl)  # [Q, NB]
    b0 = min(max(scfg.gamma0 * index.c, scfg.k_max // b + 1), nb)
    v0, i0 = jax.lax.top_k(boundsum, b0)
    scores0, pos0 = _score_blocks_dispatch(index, qb_full, i0, jnp.ones_like(i0, bool), scfg, impl)
    theta = _kth_threshold(scores0, d.k, scfg.k_max, legacy=impl == "legacy")

    budget = resolve_block_budget(scfg, nb, default=4 * scfg.gamma * index.c)
    vals, idx = jax.lax.top_k(boundsum, budget)
    rank = jnp.arange(budget)[None, :]
    eligible = (vals > theta[:, None] / d.eta[:, None]) & (rank >= b0)
    scores1, pos1 = _score_blocks_dispatch(index, qb_full, idx, eligible, scfg, impl)

    all_scores = jnp.concatenate([scores0, scores1], axis=1)
    all_pos = jnp.concatenate([pos0, pos1], axis=1)
    all_ids = index.doc_remap[jnp.clip(all_pos, 0, index.doc_remap.shape[0] - 1)]
    tvals, ids = canonical_topk(
        all_scores, all_ids.astype(jnp.int32), scfg.k_max, id_bound=index.n_docs + 1
    )
    tvals, ids = mask_beyond_k(tvals, ids, d.k, scfg.k_max)
    return RetrievalResult(
        doc_ids=ids,
        scores=tvals,
        n_superblocks_visited=jnp.zeros(ids.shape[0], jnp.int32),
        n_blocks_scored=b0 + eligible.sum(axis=1, dtype=jnp.int32),
        theta=theta,
    )


def validate_dynamic(dyn: Dynamic, scfg: StaticConfig) -> None:
    """Host-side check of a per-call dynamic point (or per-row list) against the
    compiled program's StaticConfig (k <= k_max); traced DynamicArgs pass through."""
    if isinstance(dyn, DynamicParams):
        dyn.validate_for(scfg)
    elif isinstance(dyn, (list, tuple)):
        for p in dyn:
            p.validate_for(scfg)


def split_arrays(tree):
    """``(arrays, rebuild)``: the array leaves of an index pytree (an ``LSPIndex``,
    a shard list, ...) and the function that puts arrays back around its static
    leaves. The ints that fix shapes (b, c, vocab, n_blocks, bit widths, ...) stay
    Python values; the arrays become arguments of the jitted program instead of
    constants baked into it, so the program's size does not grow with the index
    and one resident copy of the index serves every compiled bucket. Host
    (numpy) arrays are put on the default device here, once, not on every call.
    Anything with a ``shape`` and ``dtype`` counts as an array, so a tree of
    ``jax.ShapeDtypeStruct`` lowers the same program without data."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    is_array = [hasattr(x, "shape") and hasattr(x, "dtype") for x in leaves]
    arrays = [
        jax.device_put(x) if isinstance(x, np.ndarray) else x
        for x, a in zip(leaves, is_array)
        if a
    ]
    statics = [x for x, a in zip(leaves, is_array) if not a]

    def rebuild(arrs):
        it_a, it_s = iter(arrs), iter(statics)
        return treedef.unflatten([next(it_a) if a else next(it_s) for a in is_array])

    return arrays, rebuild


def make_dynamic_runner(
    program, arrays, scfg: StaticConfig, defaults: DynamicParams, vocab: int, traces: dict
):
    """Wrap a jitted ``program(arrays, tids, ws, k, mu, eta, beta)`` into the
    backend contract every serving layer consumes: ``run(qb, dyn=None)`` with
    host-param validation + [Q] broadcasting, ``run.warmup(shapes)`` sentinel
    pre-compilation, ``run.n_traces()`` (the zero-recompilation counter),
    ``run.lower(tids, ws, k, mu, eta, beta)`` (the bucket's program, for
    inspection or an AOT compile), and the
    ``supports_dynamic``/``static_cfg``/``defaults``/``vocab`` attributes.
    ``arrays`` (the index, see ``split_arrays``) ride every call as arguments.
    ``jit_search``, the 'exact' backend and ``ShardedRetriever`` all share THIS
    wrapper, so the contract cannot drift between backends."""

    def run(qb: QueryBatch, dyn: Dynamic = None):
        validate_dynamic(dyn, scfg)
        d = dynamic_args(defaults if dyn is None else dyn, qb.tids.shape[0], scfg.k_max)
        return program(arrays, qb.tids, qb.ws, d.k, d.mu, d.eta, d.beta)

    def warmup(shapes) -> None:
        for q, nq in shapes:
            d = dynamic_args(defaults, q, scfg.k_max)
            out = program(
                arrays, jnp.full((q, nq), vocab, jnp.int32), jnp.zeros((q, nq), jnp.float32), *d
            )
            jax.block_until_ready(out)

    run.warmup = warmup
    run.lower = lambda *query: program.lower(arrays, *query)
    run.n_traces = lambda: traces["n"]
    run.supports_dynamic = True
    run.static_cfg = scfg
    run.defaults = defaults
    run.vocab = vocab
    return run


def jit_search(
    index: LSPIndex,
    scfg: StaticConfig,
    impl: str = "auto",
    defaults: Optional[DynamicParams] = None,
):
    """Compile the dynamic traversal over the index: ONE XLA program per
    (Q, nq) input shape serves ANY ``DynamicParams`` point — including mixed
    per-row points — with zero recompiles across a sweep.

    The jit boundary takes the index arrays (``split_arrays``: arguments, not
    constants), (tids, ws) and the four [Q] dynamic arrays; shapes depend only
    on the batch, so a serving ladder's buckets each resolve to one program
    through the returned callable. ``run.warmup(shapes)`` pre-triggers those
    compilations, and ``run.n_traces()`` exposes the trace counter the
    zero-recompilation property tests assert over.
    """
    vocab = index.vocab
    defaults = (defaults or DynamicParams(k=scfg.k_max)).validate_for(scfg)
    traces = {"n": 0}
    arrays, rebuild = split_arrays(index)

    @jax.jit
    def fn(arrays, tids, ws, k, mu, eta, beta):
        traces["n"] += 1  # python side effect: runs at trace time only
        return search_retrieve(
            rebuild(arrays), QueryBatch(tids, ws, vocab), scfg, DynamicArgs(k, mu, eta, beta),
            impl=impl,
        )

    return make_dynamic_runner(fn, arrays, scfg, defaults, vocab, traces)


# --------------------------------------------------------------- legacy shims
# Retained one release for existing call sites; both route through the same
# unified code path at the static point (k == k_max), so behaviour — including
# bitwise results — is unchanged.


def retrieve(
    index: LSPIndex, qb_full: QueryBatch, cfg: RetrievalConfig, impl: str = "auto"
) -> RetrievalResult:
    warnings.warn(
        "retrieve(index, qb, RetrievalConfig) is deprecated; use "
        "search_retrieve(index, qb, StaticConfig, DynamicParams) or the "
        "repro.api.Retriever facade",
        DeprecationWarning,
        stacklevel=2,
    )
    return search_retrieve(index, qb_full, cfg.static(), cfg.dynamic(), impl=impl)


def jit_retrieve(index: LSPIndex, cfg: RetrievalConfig, impl: str = "auto"):
    """Deprecated: compile a retriever over the index at one fixed
    ``RetrievalConfig`` point (the index arrays ride as arguments, as in
    ``jit_search``). QueryBatch.vocab is static (shapes depend on it),
    so the jit boundary takes only the tids/ws arrays; the dynamic parameters
    are baked into the trace as constants — this is exactly the "re-jitted
    static config" the dynamic path's bit-identity tests compare against.

    jax.jit specializes per (Q, nq_max) input shape, so the serving ladder's shape
    buckets each resolve to their own XLA program through the one returned callable.
    ``run.warmup(shapes)`` pre-triggers those compilations: sentinel-only inputs are
    enough because compilation depends on shapes, not values."""
    warnings.warn(
        "jit_retrieve is deprecated; use jit_search(index, StaticConfig) or the "
        "repro.api.Retriever facade",
        DeprecationWarning,
        stacklevel=2,
    )
    vocab = index.vocab
    scfg, dyn = cfg.split()
    traces = {"n": 0}
    arrays, rebuild = split_arrays(index)

    @jax.jit
    def fn(arrays, tids, ws):
        traces["n"] += 1
        return search_retrieve(rebuild(arrays), QueryBatch(tids, ws, vocab), scfg, dyn, impl=impl)

    def run(qb: QueryBatch):
        return fn(arrays, qb.tids, qb.ws)

    def warmup(shapes) -> None:
        for q, nq in shapes:
            out = fn(arrays, jnp.full((q, nq), vocab, jnp.int32), jnp.zeros((q, nq), jnp.float32))
            jax.block_until_ready(out)

    run.warmup = warmup
    run.n_traces = lambda: traces["n"]
    return run
