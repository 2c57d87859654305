"""MoE dispatch gather — the hash-partition-join build kernel.

After routing sorts token-slots by expert key (the repartition), this
kernel materializes the (E*C, d) per-expert buffers: for each capacity
slot it dereferences the token Handle (row index) and DMAs the row from
the token matrix in HBM into the buffer tile in VMEM. Grid =
(E*C / block_slots); rows are gathered with dynamic loads (token matrix
stays in ANY/HBM). Overflow slots (keep=0) are zero-filled, exactly like
PC's combiner-page overflow.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["moe_gather"]


def _kernel(x_ref, ids_ref, keep_ref, o_ref, *, block_slots: int):
    base = pl.program_id(0) * block_slots

    def body(i, _):
        tid = ids_ref[base + i]
        row = x_ref[jnp.maximum(tid, 0), :]
        keep = keep_ref[base + i]
        o_ref[i, :] = jnp.where(keep > 0, row, jnp.zeros_like(row))
        return 0

    jax.lax.fori_loop(0, block_slots, body, 0)


def moe_gather(x: jax.Array, token_ids: jax.Array, keep: jax.Array,
               block_slots: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """x: (T, d); token_ids: (S,) row per slot; keep: (S,) int32/bool.

    Returns the (S, d) dispatch buffer (caller reshapes to (E, C, d))."""
    S = token_ids.shape[0]
    d = x.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_slots = min(block_slots, S)
    Sp = -(-S // block_slots) * block_slots
    if Sp != S:
        token_ids = jnp.pad(token_ids, (0, Sp - S))
        keep = jnp.pad(keep.astype(jnp.int32), (0, Sp - S))
    kern = functools.partial(_kernel, block_slots=block_slots)
    out = pl.pallas_call(
        kern,
        grid=(Sp // block_slots,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # token matrix in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # handles
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_slots, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, d), x.dtype),
        interpret=interpret,
    )(x, token_ids, keep.astype(jnp.int32))
    return out[:S]
