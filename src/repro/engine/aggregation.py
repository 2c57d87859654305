"""Device-side two-stage aggregation and distributed joins (paper App. D),
as shard_map collectives — the explicit (beyond-GSPMD) realizations used by
the optimized paths and by the ML benchmark kernels.

* :func:`two_stage_aggregate` — segment pre-aggregation per shard, then a
  psum_scatter "shuffle" so each shard finalizes its own hash partitions.
* :func:`grad_reduce_two_stage` — the same plan applied to a gradient
  pytree: reduce-scatter over the data axis, sharded update, all-gather —
  PC's producing/consuming stages for gradient maps.
* :func:`broadcast_join` / :func:`hash_partition_join` — the two join
  algorithms over (key, value) arrays inside shard_map regions.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["segment_preaggregate", "two_stage_aggregate",
           "grad_reduce_two_stage", "broadcast_join", "hash_partition_join"]


def segment_preaggregate(keys: jax.Array, values: jax.Array,
                         num_buckets: int) -> jax.Array:
    """Stage 1: local segment-sum into a dense bucket map (combiner page).

    keys: (T,) int32 in [0, num_buckets); values: (T, ...)."""
    return jax.ops.segment_sum(values, keys, num_segments=num_buckets)


def two_stage_aggregate(keys: jax.Array, values: jax.Array,
                        num_buckets: int, axis_name: str) -> jax.Array:
    """Inside shard_map: pre-aggregate locally, then reduce-scatter so each
    shard owns `num_buckets / axis_size` finalized partitions."""
    local = segment_preaggregate(keys, values, num_buckets)
    # shuffle: each shard receives the partitions it is responsible for
    return jax.lax.psum_scatter(local, axis_name, scatter_dimension=0,
                                tiled=True)


def grad_reduce_two_stage(grads: Any, axis_name: str) -> Any:
    """Reduce-scatter each gradient leaf over its first divisible dim; the
    caller updates its shard and all-gathers (see train_step shard_map
    variant). Falls back to psum for tiny/indivisible leaves."""
    n = jax.lax.axis_size(axis_name)

    def red(g):
        if g.ndim >= 1 and g.shape[0] % n == 0 and g.shape[0] >= n:
            return jax.lax.psum_scatter(g, axis_name, scatter_dimension=0,
                                        tiled=True)
        return jax.lax.psum(g, axis_name)

    return jax.tree.map(red, grads)


def broadcast_join(probe_keys: jax.Array, build_keys: jax.Array,
                   build_values: jax.Array, axis_name: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Broadcast join: the (small) build side is all-gathered to every
    shard, probe side stays put. Returns (matched mask, joined values).

    build side must have unique keys (dimension-table semantics)."""
    if axis_name is not None:
        build_keys = jax.lax.all_gather(build_keys, axis_name, tiled=True)
        build_values = jax.lax.all_gather(build_values, axis_name, tiled=True)
    order = jnp.argsort(build_keys)
    sk = build_keys[order]
    idx = jnp.searchsorted(sk, probe_keys)
    idx = jnp.clip(idx, 0, sk.shape[0] - 1)
    matched = sk[idx] == probe_keys
    vals = build_values[order][idx]
    return matched, vals


def hash_partition_join(keys: jax.Array, values: jax.Array,
                        num_partitions: int, axis_name: str
                        ) -> Tuple[jax.Array, jax.Array]:
    """Repartition (key, value) rows by key hash across shards via
    all_to_all — the shuffle stage of PC's hash join. Rows are binned into
    fixed-capacity per-destination buckets (combiner pages); overflow rows
    are dropped exactly like capacity-overflow in the MoE dispatch.

    keys: (T,), values: (T, d). Returns the shard's received (keys, values)
    with -1 key marking empty slots."""
    n = jax.lax.axis_size(axis_name)
    T = keys.shape[0]
    cap = T // n * 2  # per-destination capacity
    dest = (keys % num_partitions) * n // num_partitions
    order = jnp.argsort(dest)
    sd, sk, sv = dest[order], keys[order], values[order]
    start = jnp.searchsorted(sd, jnp.arange(n))
    rank = jnp.arange(T) - start[sd]
    keep = rank < cap
    slot = jnp.where(keep, sd * cap + rank, n * cap)
    out_k = jnp.full((n * cap + 1,), -1, keys.dtype).at[slot].set(sk)
    out_v = jnp.zeros((n * cap + 1, values.shape[-1]),
                      values.dtype).at[slot].set(sv)
    out_k = out_k[:-1].reshape(n, cap)
    out_v = out_v[:-1].reshape(n, cap, -1)
    rk = jax.lax.all_to_all(out_k, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    rv = jax.lax.all_to_all(out_v, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    return rk, rv
