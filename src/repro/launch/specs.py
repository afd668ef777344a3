"""Cell builder: (arch, shape, mesh) -> jit-able step fn + ShapeDtypeStruct inputs +
shardings. This is what the multi-pod dry-run lowers and compiles for every cell.

Step kinds:
  LM       train_4k -> train_step (remat + grad-accum + Adafactor)
           prefill_32k -> prefill (last-token logits + KV caches)
           decode_32k / long_500k -> serve_step (1 token, KV cache in/out)
  GNN      full_graph/ogb -> full-batch node-classification train_step
           minibatch_lg -> sampled-subgraph train_step; molecule -> energy train_step
  RecSys   train_batch -> train_step (vocab-parallel embeddings)
           serve_p99 / serve_bulk -> forward scoring
           retrieval_cand -> LSP dense-index retrieval (mind) / exhaustive (others)

No real arrays are allocated: params come from jax.eval_shape over the init fns and
inputs are ShapeDtypeStructs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed import sharding as shr
from repro.optim.adafactor import Adafactor
from repro.common.tree_utils import tree_cast


@dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: tuple  # ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    note: str = ""
    donate: tuple = ()  # argnums aliased into outputs (params/opt for train, KV for decode)


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if isinstance(s, P) else P()),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def _batch_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ===================================================================== LM cells
_LM_ACCUM = {  # grad-accum per arch (activation-memory control at 4k seq)
    "llama4-maverick-400b-a17b": 8,
    "phi3.5-moe-42b-a6.6b": 8,
    "gemma3-27b": 8,
    "granite-3-8b": 8,
    "qwen3-4b": 4,
}


def _lm_train_cell(arch: ArchConfig, shape: ShapeSpec, mesh) -> Cell:
    cfg = arch.lm
    from repro.models.stacked import init_lm_stacked, lm_loss_stacked

    opt = Adafactor(lr=1e-3)
    accum = _LM_ACCUM.get(arch.name, 4)
    bsz, seq = shape.global_batch, shape.seq_len
    micro = bsz // accum

    params_s0 = jax.eval_shape(partial(init_lm_stacked, cfg=cfg), jax.random.PRNGKey(0))
    pspec0 = shr.stacked_lm_param_specs(params_s0, mesh, fsdp=True, kv_shard=False)

    def step(params, opt_state, tokens, labels):
        # bf16 cast happens per group INSIDE the layer scan (cast_dtype) — no
        # resident whole-model bf16 replica; grads come back f32 (cast transpose).
        # cast_specs keeps the cast on the FSDP shards -> bf16 all-gathers.
        def lf(p, tk, lb):
            return lm_loss_stacked(
                p, cfg, tk, lb, remat=True, cast_dtype=jnp.bfloat16, cast_specs=pspec0.groups
            )[0]

        def micro_step(acc, mb):
            tk, lb = mb
            loss, g = jax.value_and_grad(lf)(params, tk, lb)
            return (jax.tree.map(jnp.add, acc[0], g), acc[1] + loss), None

        zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        tks = tokens.reshape(accum, micro, seq)
        lbs = labels.reshape(accum, micro, seq)
        (grads, loss_sum), _ = jax.lax.scan(micro_step, (zeros, 0.0), (tks, lbs))
        grads = jax.tree.map(lambda g: g / accum, grads)
        new_p, new_s, _ = opt.update(grads, opt_state, params)
        return new_p, new_s, loss_sum / accum

    params_s = jax.eval_shape(partial(init_lm_stacked, cfg=cfg), jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(opt.init, params_s)
    tokens_s = _sds((bsz, seq), jnp.int32)

    pspec = shr.stacked_lm_param_specs(params_s, mesh, fsdp=True, kv_shard=False)
    ospec = _adafactor_specs(opt_s, pspec)
    bspec = P(_batch_axes(mesh), None)
    return Cell(
        arch.name,
        shape.name,
        "train_step",
        step,
        (params_s, opt_s, tokens_s, tokens_s),
        (_named(mesh, pspec), _named(mesh, ospec), NamedSharding(mesh, bspec), NamedSharding(mesh, bspec)),
        (_named(mesh, pspec), _named(mesh, ospec), NamedSharding(mesh, P())),
        note=f"grad_accum={accum}, remat per layer, Adafactor, bf16 compute / fp32 master",
        donate=(0, 1),
    )


def _adafactor_specs(opt_s, param_specs):
    from repro.optim.adafactor import AdafactorState

    return AdafactorState(step=P(), moments=shr.adafactor_state_specs(param_specs))


def _lm_prefill_cell(arch: ArchConfig, shape: ShapeSpec, mesh) -> Cell:
    cfg = arch.lm
    from repro.models.stacked import init_lm_stacked, lm_prefill_stacked

    bsz, seq = shape.global_batch, shape.seq_len

    def step(params, tokens):
        logits, state = lm_prefill_stacked(tree_cast(params, jnp.bfloat16), cfg, tokens, max_len=seq)
        return logits[:, -1:, :], state

    params_s = jax.eval_shape(partial(init_lm_stacked, cfg=cfg), jax.random.PRNGKey(0))
    tokens_s = _sds((bsz, seq), jnp.int32)
    state_s = jax.eval_shape(step, params_s, tokens_s)[1]

    pspec = shr.stacked_lm_param_specs(params_s, mesh, fsdp=True, kv_shard=True)
    bspec = P(_batch_axes(mesh), None)
    state_spec = shr.decode_state_specs(state_s, mesh, bsz, cfg.n_kv_heads, stacked=True)
    return Cell(
        arch.name,
        shape.name,
        "prefill_step",
        step,
        (params_s, tokens_s),
        (_named(mesh, pspec), NamedSharding(mesh, bspec)),
        (NamedSharding(mesh, P(_batch_axes(mesh), None, "model")), _named(mesh, state_spec)),
        note="returns last-token logits + populated KV caches",
    )


def _lm_decode_cell(arch: ArchConfig, shape: ShapeSpec, mesh) -> Cell:
    cfg = arch.lm
    from repro.models.stacked import init_decode_state_stacked, init_lm_stacked, lm_decode_step_stacked

    bsz, seq = shape.global_batch, shape.seq_len

    def step(params, token, state):
        return lm_decode_step_stacked(tree_cast(params, jnp.bfloat16), cfg, token, state)

    params_s = jax.eval_shape(partial(init_lm_stacked, cfg=cfg), jax.random.PRNGKey(0))
    token_s = _sds((bsz, 1), jnp.int32)
    state_s = jax.eval_shape(partial(init_decode_state_stacked, cfg, bsz, seq), )

    pspec = shr.stacked_lm_param_specs(params_s, mesh, fsdp=True, kv_shard=True)
    state_spec = shr.decode_state_specs(state_s, mesh, bsz, cfg.n_kv_heads, stacked=True)
    if bsz >= _n_batch_shards(mesh):
        bspec = P(_batch_axes(mesh), None)
        logits_spec = P(_batch_axes(mesh), None, "model")
        seq_note = "batch-sharded KV"
    else:
        bspec = P(None, None)  # batch too small to shard; KV length shards instead
        logits_spec = P(None, None, "model")
        seq_note = "sequence-parallel KV (batch < shards)"
    return Cell(
        arch.name,
        shape.name,
        "serve_step",
        step,
        (params_s, token_s, state_s),
        (_named(mesh, pspec), NamedSharding(mesh, bspec), _named(mesh, state_spec)),
        (NamedSharding(mesh, logits_spec), _named(mesh, state_spec)),
        note=f"1 new token vs {seq}-long KV cache; {seq_note}",
        donate=(2,),
    )


def _n_batch_shards(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


# ===================================================================== GNN cells
def _gnn_cell(arch: ArchConfig, shape: ShapeSpec, mesh) -> Cell:
    cfg = arch.gnn
    from repro.models.schnet import init_schnet, molecule_batch_forward, schnet_forward, schnet_readout

    opt = Adafactor(lr=1e-3)
    all_axes = tuple(mesh.axis_names)
    n_classes = 47 if shape.name == "ogb_products" else 16

    if shape.kind == "batched_graphs":
        b, n, e = shape.batch, shape.n_nodes, shape.n_edges
        in_dim = 16  # atom-type one-hot width

        def loss_fn(params, z, pos, es, ed, em, y):
            pred = molecule_batch_forward(params, cfg, z, pos, es, ed, em)
            return jnp.mean(jnp.square(pred[:, 0] - y))

        def step(params, opt_state, z, pos, es, ed, em, y):
            loss, g = jax.value_and_grad(loss_fn)(params, z, pos, es, ed, em, y)
            new_p, new_s, _ = opt.update(g, opt_state, params)
            return new_p, new_s, loss

        params_s = jax.eval_shape(partial(init_schnet, cfg=cfg, in_dim=in_dim, out_dim=1), jax.random.PRNGKey(0))
        opt_s = jax.eval_shape(opt.init, params_s)
        args = (
            params_s,
            opt_s,
            _sds((b, n, in_dim), jnp.float32),
            _sds((b, n, 3), jnp.float32),
            _sds((b, e), jnp.int32),
            _sds((b, e), jnp.int32),
            _sds((b, e), jnp.bool_),
            _sds((b,), jnp.float32),
        )
        bspec = _batch_axes(mesh)
        pspec = jax.tree.map(lambda _: P(), params_s)
        ospec = jax.tree.map(lambda _: P(), opt_s)
        in_sh = (
            _named(mesh, pspec),
            _named(mesh, ospec),
            NamedSharding(mesh, P(bspec, None, None)),
            NamedSharding(mesh, P(bspec, None, None)),
            NamedSharding(mesh, P(bspec, None)),
            NamedSharding(mesh, P(bspec, None)),
            NamedSharding(mesh, P(bspec, None)),
            NamedSharding(mesh, P(bspec)),
        )
        return Cell(
            arch.name, shape.name, "train_step", step, args, in_sh,
            (_named(mesh, pspec), _named(mesh, ospec), NamedSharding(mesh, P())),
            note="batched molecular graphs, energy MSE",
            donate=(0, 1),
        )

    # full-graph or sampled-minibatch node classification
    if shape.kind == "minibatch":
        from repro.data.graph import SampledSubgraph

        shp = SampledSubgraph.shapes(shape.batch_nodes, shape.fanout, 100)
        n_nodes, d_feat = shp["node_feats"]
        n_edges = shp["edge_src"][0]
        n_out = shape.batch_nodes
        note = f"sampled 2-hop subgraph (fanout {shape.fanout}), {n_nodes} nodes/{n_edges} edges"
    else:
        n_nodes, d_feat = shape.n_nodes, shape.d_feat
        n_edges = shape.n_edges
        n_out = shape.n_nodes
        note = "full-batch; edge-parallel over all mesh axes, node arrays replicated"
    # explicit pjit shardings need divisibility: pad edge arrays to the mesh size
    # (padded edges carry edge_mask=False in the data pipeline)
    n_edges = -(-n_edges // mesh.size) * mesh.size

    def loss_fn(params, x, es, ed, ew, em, labels, label_mask):
        h = schnet_forward(params, cfg, x, es, ed, ew, em)
        logits = schnet_readout(params, h)[: labels.shape[0]]
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32), labels[:, None], axis=-1)[:, 0]
        ce = jnp.where(label_mask, logz - gold, 0.0)
        return ce.sum() / jnp.maximum(label_mask.sum(), 1)

    def step(params, opt_state, x, es, ed, ew, em, labels, label_mask):
        loss, g = jax.value_and_grad(loss_fn)(params, x, es, ed, ew, em, labels, label_mask)
        new_p, new_s, _ = opt.update(g, opt_state, params)
        return new_p, new_s, loss

    params_s = jax.eval_shape(
        partial(init_schnet, cfg=cfg, in_dim=d_feat, out_dim=n_classes), jax.random.PRNGKey(0)
    )
    opt_s = jax.eval_shape(opt.init, params_s)
    args = (
        params_s,
        opt_s,
        _sds((n_nodes, d_feat), jnp.float32),
        _sds((n_edges,), jnp.int32),
        _sds((n_edges,), jnp.int32),
        _sds((n_edges,), jnp.float32),
        _sds((n_edges,), jnp.bool_),
        _sds((n_out,), jnp.int32),
        _sds((n_out,), jnp.bool_),
    )
    pspec = jax.tree.map(lambda _: P(), params_s)
    ospec = jax.tree.map(lambda _: P(), opt_s)
    espec = NamedSharding(mesh, P(all_axes))
    in_sh = (
        _named(mesh, pspec),
        _named(mesh, ospec),
        NamedSharding(mesh, P(None, None)),  # node features replicated
        espec, espec, espec, espec,
        NamedSharding(mesh, P(None)),
        NamedSharding(mesh, P(None)),
    )
    return Cell(
        arch.name, shape.name, "train_step", step, args, in_sh,
        (_named(mesh, pspec), _named(mesh, ospec), NamedSharding(mesh, P())),
        note=note,
        donate=(0, 1),
    )


# ===================================================================== recsys cells
def _recsys_batch_arrays(arch: ArchConfig, batch: int):
    rc = arch.recsys
    if arch.name.startswith("dlrm"):
        return {
            "dense": _sds((batch, rc.n_dense), jnp.float32),
            "sparse_ids": _sds((batch, rc.n_sparse), jnp.int32),
            "labels": _sds((batch,), jnp.float32),
        }
    if arch.name == "din":
        return {
            "target_ids": _sds((batch, rc.n_sparse), jnp.int32),
            "hist_ids": _sds((batch, rc.hist_len, rc.n_sparse), jnp.int32),
            "hist_mask": _sds((batch, rc.hist_len), jnp.bool_),
            "labels": _sds((batch,), jnp.float32),
        }
    return {  # mind
        "target_ids": _sds((batch, rc.n_sparse), jnp.int32),
        "hist_ids": _sds((batch, rc.hist_len, rc.n_sparse), jnp.int32),
        "hist_mask": _sds((batch, rc.hist_len), jnp.bool_),
    }


def _recsys_forward(arch: ArchConfig, mesh, use_vp: bool):
    """Returns (init_fn, fwd(params, batch) -> loss_or_logits builder)."""
    import repro.models.recsys as R

    rc = arch.recsys
    baxes = _batch_axes(mesh)

    def lookup(tables, ids2d):
        if use_vp == "scatter":  # §Perf P18: reduce-scatter + model-axis batch split
            from repro.distributed.embedding import vocab_parallel_lookup_scattered

            offs = jnp.asarray(tables.offsets, jnp.int32)
            return vocab_parallel_lookup_scattered(
                tables.table, ids2d + offs[None, :], mesh, baxes
            )
        if use_vp:
            from repro.distributed.embedding import vocab_parallel_lookup

            offs = jnp.asarray(tables.offsets, jnp.int32)
            return vocab_parallel_lookup(tables.table, ids2d + offs[None, :], mesh, baxes)
        return R.field_lookup(tables, ids2d)

    if arch.name.startswith("dlrm"):
        init = partial(R.init_dlrm, cfg=rc)

        def fwd(params, batch):
            bot = R._mlp(params.bot, batch["dense"], final_act=True)
            embs = lookup(params.tables, batch["sparse_ids"])
            z = jnp.concatenate([bot[:, None, :], embs], axis=1)
            gram = jnp.einsum("bfd,bgd->bfg", z, z)
            iu, ju = jnp.triu_indices(z.shape[1], k=1)
            pairs = gram[:, iu, ju]
            return R._mlp(params.top, jnp.concatenate([bot, pairs], axis=1))[:, 0]

        def loss(params, batch):
            return R.bce_loss(fwd(params, batch), batch["labels"])

        return init, fwd, loss

    if arch.name == "din":
        init = partial(R.init_din, cfg=rc)

        def fwd(params, batch):
            b = batch["target_ids"].shape[0]
            t = lookup(params.tables, batch["target_ids"]).reshape(b, -1)
            hl = batch["hist_ids"].shape[1]
            nf = batch["hist_ids"].shape[2]
            h = lookup(params.tables, batch["hist_ids"].reshape(b * hl, nf)).reshape(b, hl, -1)
            tb = jnp.broadcast_to(t[:, None, :], h.shape)
            a_in = jnp.concatenate([h, tb, h - tb, h * tb], axis=-1)
            scores = R._mlp(params.attn, a_in)[..., 0] * batch["hist_mask"].astype(jnp.float32)
            interest = jnp.einsum("bl,bli->bi", scores, h)
            return R._mlp(params.top, jnp.concatenate([interest, t], axis=-1))[:, 0]

        def loss(params, batch):
            return R.bce_loss(fwd(params, batch), batch["labels"])

        return init, fwd, loss

    init = partial(R.init_mind, cfg=rc)

    def interests_fn(params, batch):
        b = batch["hist_ids"].shape[0]
        hl = batch["hist_ids"].shape[1]
        nf = batch["hist_ids"].shape[2]
        h = lookup(params.tables, batch["hist_ids"].reshape(b * hl, nf)).reshape(b, hl, -1)
        h = h @ params.s_bilinear
        mask = batch["hist_mask"]
        b_mask = (mask.astype(jnp.float32) - 1.0) * 1e9
        blk = jax.random.normal(jax.random.PRNGKey(0), (1, hl, rc.n_interests))
        b_rout = jnp.broadcast_to(blk, (b, hl, rc.n_interests))
        import repro.models.recsys as RR

        interests = None
        for _ in range(rc.capsule_iters):
            w = jax.nn.softmax(b_rout + b_mask[..., None], axis=-1)
            z = jnp.einsum("blk,bld->bkd", w, h)
            interests = RR._squash(z)
            b_rout = b_rout + jnp.einsum("bkd,bld->blk", jax.lax.stop_gradient(interests), h)
        return interests

    def fwd(params, batch):
        return interests_fn(params, batch)

    def loss(params, batch):
        b = batch["target_ids"].shape[0]
        ints = interests_fn(params, batch)
        te = lookup(params.tables, batch["target_ids"]).reshape(b, -1) @ params.s_bilinear
        uv = R.mind_user_vector(params, rc, ints, te)
        return R.sampled_softmax_loss(uv, te)

    return init, fwd, loss


def _recsys_param_specs(params_s):
    from repro.models.recsys import EmbedTables

    def fix(p):
        if isinstance(p, EmbedTables):
            return EmbedTables(table=P("model", None), offsets=P(None))
        return jax.tree.map(lambda _: P(), p)

    # NamedTuple of (tables, *mlps)
    return type(params_s)(*[fix(f) for f in params_s])


def _recsys_cell(arch: ArchConfig, shape: ShapeSpec, mesh) -> Cell:
    rc = arch.recsys
    baxes = _batch_axes(mesh)
    init, fwd, loss = _recsys_forward(
        arch, mesh, use_vp="scatter" if shape.kind == "rank_train" else True
    )
    params_s = jax.eval_shape(init, jax.random.PRNGKey(0))
    pspec = _recsys_param_specs(params_s)
    batch_arrays = _recsys_batch_arrays(arch, shape.batch)
    bshard = {
        k: NamedSharding(mesh, P(baxes, *([None] * (len(v.shape) - 1))))
        for k, v in batch_arrays.items()
    }

    if shape.kind == "rank_train":
        opt = Adafactor(lr=1e-3)
        opt_s = jax.eval_shape(opt.init, params_s)
        ospec = _adafactor_specs(opt_s, pspec)

        def step(params, opt_state, batch):
            l, g = jax.value_and_grad(loss, allow_int=True)(params, batch)
            new_p, new_s, _ = opt.update(g, opt_state, params)
            return new_p, new_s, l

        return Cell(
            arch.name, shape.name, "train_step", step,
            (params_s, opt_s, batch_arrays),
            (_named(mesh, pspec), _named(mesh, ospec), bshard),
            (_named(mesh, pspec), _named(mesh, ospec), NamedSharding(mesh, P())),
            note="vocab-parallel embedding (psum over model), Adafactor",
            donate=(0, 1),
        )

    if shape.kind == "rank_serve":
        arrays = {k: v for k, v in batch_arrays.items() if k != "labels"}
        ashard = {k: bshard[k] for k in arrays}

        def step(params, batch):
            return fwd(params, batch)

        out_spec = (
            NamedSharding(mesh, P(baxes, None, None))
            if arch.name == "mind"
            else NamedSharding(mesh, P(baxes))
        )
        return Cell(
            arch.name, shape.name, "serve_step", step, (params_s, arrays),
            (_named(mesh, pspec), ashard), out_spec,
            note="forward scoring only",
        )

    # retrieval_cand
    return _recsys_retrieval_cell(arch, shape, mesh, params_s, pspec)


def _recsys_retrieval_cell(arch: ArchConfig, shape: ShapeSpec, mesh, params_s, pspec) -> Cell:
    """batch=1 user, 1M candidates.

    mind: the paper's technique — dense LSP (superblock-pruned) candidate scoring.
    din/dlrm: non-dot interactions -> exhaustive scoring, candidates model-sharded.
    """
    rc = arch.recsys
    n_cand = shape.n_candidates
    baxes = _batch_axes(mesh)

    if arch.name == "mind":
        from jax import shard_map

        from repro.core.config import RetrievalConfig
        from repro.core.lsp_dense import DenseLSPIndex, PackedMinMax, dense_local_fn

        d = rc.embed_dim
        b_, c_ = 64, 16
        n_shards = mesh.shape["model"]
        ns = -(-n_cand // (b_ * c_))
        ns = -(-ns // n_shards) * n_shards
        ns_l = ns // n_shards  # per-shard superblocks
        nb_l = ns_l * c_
        np_l = nb_l * b_
        vpw = 8  # 4-bit
        sb_words_l = -(-ns_l // (128 * vpw)) * 128  # per-shard sb row, SEG granule
        cw = c_ * 4 // 32
        gamma_ = max(1, min(32, ns_l))
        cfg = RetrievalConfig(variant="lsp0", k=100, gamma=gamma_, gamma0=min(8, gamma_))

        meta = DenseLSPIndex(
            b=b_, c=c_, n_cands=n_cand, dim=d, n_blocks=nb_l, n_superblocks=ns_l,
            sb=PackedMinMax(None, None, 0.01, -1.0, ns_l, 128, 4),
            blk=PackedMinMax(None, None, 0.01, -1.0, nb_l, cw, 4),
            cands=None, remap=None,
        )
        local = dense_local_fn(meta, cfg)
        step = shard_map(
            local,
            mesh=mesh,
            in_specs=tuple([P("model", None, None)] * 5 + [P("model", None), P(None, None)]),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False,
        )

        args = (
            _sds((n_shards, d, sb_words_l), jnp.uint32),
            _sds((n_shards, d, sb_words_l), jnp.uint32),
            _sds((n_shards, d, ns_l * cw), jnp.uint32),
            _sds((n_shards, d, ns_l * cw), jnp.uint32),
            _sds((n_shards, np_l, d), jnp.bfloat16),
            _sds((n_shards, np_l), jnp.int32),
            _sds((rc.n_interests, d), jnp.float32),  # batch=1 user's K interests
        )
        in_sh = tuple(
            NamedSharding(mesh, P("model", None, None)) for _ in range(5)
        ) + (
            NamedSharding(mesh, P("model", None)),
            NamedSharding(mesh, P(None, None)),
        )
        return Cell(
            arch.name, shape.name, "retrieve_step", step, args, in_sh,
            (NamedSharding(mesh, P(None, None)), NamedSharding(mesh, P(None, None))),
            note="dense LSP (the paper's technique) over 1M candidates, shard_map "
            "hierarchical top-k (per-shard gamma, O(P*k) merge)",
        )

    # din / dlrm: exhaustive candidate scoring, candidates sharded over model
    init, fwd, _ = _recsys_forward(arch, mesh, use_vp=False)

    if arch.name == "din":
        def step(params, cand_ids, hist_ids, hist_mask):
            import repro.models.recsys as R

            n = cand_ids.shape[0]
            hist_b = jnp.broadcast_to(hist_ids[None], (1, *hist_ids.shape)).reshape(1, *hist_ids.shape)
            # score candidates in chunks via vmap over candidate axis
            def score(cid):
                batch = {
                    "target_ids": cid[None, :],
                    "hist_ids": hist_ids[None],
                    "hist_mask": hist_mask[None],
                }
                return fwd(params, batch)[0]

            return jax.lax.map(score, cand_ids, batch_size=4096)

        args = (
            params_s,
            _sds((n_cand, rc.n_sparse), jnp.int32),
            _sds((rc.hist_len, rc.n_sparse), jnp.int32),
            _sds((rc.hist_len,), jnp.bool_),
        )
        in_sh = (
            _named(mesh, pspec),
            NamedSharding(mesh, P("model", None)),
            NamedSharding(mesh, P(None, None)),
            NamedSharding(mesh, P(None)),
        )
        return Cell(
            arch.name, shape.name, "retrieve_step", step, args, in_sh,
            NamedSharding(mesh, P("model")),
            note="1 user x 1M candidates, per-candidate target attention (chunked)",
        )

    def step(params, dense, sparse_ids, cand_ids):
        import repro.models.recsys as R

        # fixed user features; candidate id replaces the item field (field 0)
        def score(cid):
            ids = sparse_ids.at[0, 0].set(cid)
            batch = {"dense": dense, "sparse_ids": ids}
            return fwd(params, batch)[0]

        return jax.lax.map(score, cand_ids, batch_size=8192)

    args = (
        params_s,
        _sds((1, rc.n_dense), jnp.float32),
        _sds((1, rc.n_sparse), jnp.int32),
        _sds((n_cand,), jnp.int32),
    )
    in_sh = (
        _named(mesh, pspec),
        NamedSharding(mesh, P(None, None)),
        NamedSharding(mesh, P(None, None)),
        NamedSharding(mesh, P("model")),
    )
    return Cell(
        arch.name, shape.name, "retrieve_step", step, args, in_sh,
        NamedSharding(mesh, P("model")),
        note="1 user x 1M candidates, item field swept (chunked)",
    )


# ===================================================================== entry point
def build_cell(arch: ArchConfig, shape_name: str, mesh) -> Optional[Cell]:
    if shape_name in arch.skip_shapes:
        return None
    shape = arch.shapes[shape_name]
    if arch.family == "lm":
        if shape.kind == "train":
            return _lm_train_cell(arch, shape, mesh)
        if shape.kind == "prefill":
            return _lm_prefill_cell(arch, shape, mesh)
        return _lm_decode_cell(arch, shape, mesh)
    if arch.family == "gnn":
        return _gnn_cell(arch, shape, mesh)
    return _recsys_cell(arch, shape, mesh)
