"""Vocab-parallel embedding lookup (Megatron-style) under shard_map.

The stacked recsys table (e.g. Criteo-1TB: ~228M rows x 128 = 117GB fp32) is
row-sharded over `model`. A naive pjit gather risks GSPMD materializing an all-gather
of the table; this shard_map formulation pins the distribution strategy:

  each shard gathers the ids it owns (others contribute zeros) -> one psum over
  `model` yields the full [B, F, D] activation, replicated across `model`.

The psum volume (B*F*D floats) is the dominant collective of recsys training — a
deliberate baseline; §Perf iterates on it (reduce-scatter + all-to-all variant).
Differentiable: the psum's transpose is identity, the masked gather's transpose is a
masked scatter-add back into the owning shard.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def vocab_parallel_lookup(table: jnp.ndarray, flat_ids: jnp.ndarray, mesh, batch_axes) -> jnp.ndarray:
    """table [R, D] (R divisible by model axis), flat_ids int32 [B, F] global row ids
    -> [B, F, D] replicated over model, sharded over batch axes."""
    from jax import shard_map

    n_model = mesh.shape["model"]
    r = table.shape[0]
    assert r % n_model == 0, f"table rows {r} must divide model axis {n_model}"
    r_local = r // n_model

    def local(table_l, ids):
        shard = jax.lax.axis_index("model")
        lo = shard * r_local
        rel = ids - lo
        own = (rel >= 0) & (rel < r_local)
        rows = table_l[jnp.clip(rel, 0, r_local - 1)]
        rows = jnp.where(own[..., None], rows, 0.0)
        return jax.lax.psum(rows, "model")

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )
    return fn(table, flat_ids)


def vocab_parallel_lookup_scattered(
    table: jnp.ndarray, flat_ids: jnp.ndarray, mesh, batch_axes
) -> jnp.ndarray:
    """§Perf P18: reduce-scatter variant of vocab_parallel_lookup.

    The psum version replicates the [B, F, D] activation across `model` — every model
    shard then runs the SAME dense MLPs redundantly. Here the partial contributions
    are reduce-scattered along the BATCH dim instead: per-device exchange volume is
    half of an all-reduce, and the output batch is sharded over (data+..., model), so
    the downstream interaction/MLP compute 1/16th each (the model axis becomes extra
    batch parallelism for the dense part; pjit propagates the 2-axis batch sharding).

    Requires B divisible by (batch shards x model). Output: [B/model_local, F, D]
    locally; global sharding P((batch_axes, 'model'), None, None).
    """
    from jax import shard_map

    n_model = mesh.shape["model"]
    r = table.shape[0]
    assert r % n_model == 0
    r_local = r // n_model

    def local(table_l, ids):
        shard = jax.lax.axis_index("model")
        lo = shard * r_local
        rel = ids - lo
        own = (rel >= 0) & (rel < r_local)
        rows = table_l[jnp.clip(rel, 0, r_local - 1)]
        rows = jnp.where(own[..., None], rows, 0.0)
        return jax.lax.psum_scatter(rows, "model", scatter_dimension=0, tiled=True)

    out_batch = tuple(batch_axes) + ("model",)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(batch_axes, None)),
        out_specs=P(out_batch, None, None),
        check_vma=False,
    )
    return fn(table, flat_ids)
