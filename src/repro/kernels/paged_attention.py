"""Paged decode attention — the object-model kernel (DESIGN.md §2).

One query token attends to a KV cache stored as fixed-size HBM pages with a
block table of offset Handles (the PC object model on device). Grid =
(batch, kv_heads); the kernel walks the sequence's block table, DMA-ing one
page at a time into VMEM (pages and tables live in ANY/HBM memory space and
are loaded with dynamic slices — the Handle dereference), maintaining the
online-softmax state for the G grouped query heads of this kv head.

VMEM working set per step: one (page, hd) K tile + V tile + (G, hd)
accumulator ≈ (2*page+G)*hd*4 B — e.g. 0.20 MiB at page=128, hd=128, G=8.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["paged_attention"]

NEG_INF = -1e30


def _kernel(q_ref, kp_ref, vp_ref, tbl_ref, len_ref, o_ref, *,
            page_size: int, max_pages: int, scale: float):
    b = pl.program_id(0)
    kh = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
    G, hd = q.shape
    seq_len = len_ref[b]

    def body(p, carry):
        m, l, acc = carry
        page_id = tbl_ref[b, p]  # Handle dereference (int32 page id)
        pid = jnp.maximum(page_id, 0)
        k = kp_ref[pid, :, kh, :].astype(jnp.float32)  # (page, hd)
        v = vp_ref[pid, :, kh, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos < seq_len) & (page_id >= 0)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        pw = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pw.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            pw, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((G,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G,), jnp.float32)
    a0 = jnp.zeros((G, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, max_pages, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, hd); k/v_pages: (P, page, K, hd); tables: (B, max_pages)
    global page ids (-1 = hole); lengths: (B,). Returns (B, H, hd)."""
    B, H, hd = q.shape
    P, page_size, K, _ = k_pages.shape
    max_pages = tables.shape[1]
    G = H // K
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qg = q.reshape(B, K, G, hd)
    kern = functools.partial(_kernel, page_size=page_size,
                             max_pages=max_pages, scale=hd ** -0.5)
    out = pl.pallas_call(
        kern,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, k: (b, k, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # page pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),  # block tables
            pl.BlockSpec(memory_space=pl.ANY),  # lengths
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, k: (b, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(qg, k_pages, v_pages, tables, lengths)
    return out.reshape(B, H, hd)
