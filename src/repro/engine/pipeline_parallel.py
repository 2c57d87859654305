"""GPipe-style pipeline parallelism over a dedicated mesh axis.

The production dry-run treats the ``pod`` axis as data-parallel by default;
passing ``--pipeline`` re-purposes it as a ``pipe`` axis with this schedule:
each pipeline rank holds ``n_layers / n_stages`` of the stacked layer
params, microbatches stream through with ``ppermute`` transfers, and the
bubble is the standard (n_stages - 1) / (n_micro + n_stages - 1).

Implemented as a shard_map program so the transfers are explicit
collective-permutes — countable in §Roofline's collective term.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "pipeline_loss"]


def pipeline_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stage_params: Any, x: jax.Array, n_micro: int,
                     mesh: Mesh, axis: str = "pipe") -> jax.Array:
    """Run x through all pipeline stages.

    stage_params: pytree whose leaves have leading dim == n_stages (sharded
    over `axis`); x: (B, ...) global batch (sharded over `axis` is wrong —
    it is split into microbatches on rank 0 conceptually; in SPMD all ranks
    step the same loop and mask).
    """
    n_stages = mesh.shape[axis]
    assert x.shape[0] % n_micro == 0

    def spmd(params_local, x_local):
        # params_local leaves: (1, ...) -> squeeze
        p = jax.tree.map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        micro = x_local.reshape(n_micro, -1, *x_local.shape[1:])
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def tick(carry, t):
            buf, outs = carry
            # rank 0 injects microbatch t (if in range)
            inject = jnp.where(t < n_micro, t, 0)
            buf = jnp.where(rank == 0,
                            jnp.where(t < n_micro, micro[inject], buf), buf)
            y = stage_fn(p, buf)
            # last rank emits finished microbatch t - (n_stages - 1)
            emit = t - (n_stages - 1)
            outs = jnp.where(
                (rank == n_stages - 1) & (emit >= 0) & (emit < n_micro),
                outs.at[jnp.clip(emit, 0, n_micro - 1)].set(y), outs)
            # shift activations downstream
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(n_ticks))
        # broadcast results from the last rank to all (for the loss)
        outs = jax.lax.psum(
            jnp.where(rank == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs.reshape(-1, *x_local.shape[1:])

    fn = jax.shard_map(spmd, mesh=mesh,
                       in_specs=(P(axis), P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x)


def pipeline_loss(stage_fn, stage_params, x, y, n_micro, mesh,
                  axis: str = "pipe") -> jax.Array:
    out = pipeline_forward(stage_fn, stage_params, x, n_micro, mesh, axis)
    return jnp.mean((out - y) ** 2)
