"""Mixture-of-Experts layer — PC's hash-partition join on TPU.

Token→expert dispatch is literally the paper's n-way hash-partition join
(Appendix D.3): the router assigns each token a key (expert id), tokens are
*sorted by key* (the repartition), grouped into fixed-capacity per-expert
buffers (the paper's ``Vector<Object>`` build per hash bucket), processed,
and scattered back (the probe + combine). Under expert parallelism the
(E, C, d) buffer is sharded over the model axis and XLA materializes the
shuffle as an all-to-all; the planner falls back to TP-within-expert (the
broadcast join) when E does not divide the mesh axis.

Capacity overflow drops tokens (combiner-page overflow in the paper); the
residual connection carries dropped tokens through, and the load-balance
auxiliary loss keeps drop rates low — both standard Switch-style choices.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models.context import Ctx
from repro.models.layers import ffn_apply, ffn_defs
from repro.models.params import ParamDef

__all__ = ["moe_defs", "moe_apply", "expert_capacity"]


def expert_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / max(1, cfg.n_experts)
            * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_defs(cfg: ArchConfig, stacked: Optional[int] = None) -> Dict:
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    lead = (stacked,) if stacked else ()
    la = ("layers",) if stacked else ()
    gated = cfg.activation in ("swiglu", "geglu")
    out = {
        "router": ParamDef((*lead, d, E), (*la, "embed", None), init="small"),
        "w_down": ParamDef((*lead, E, ff, d), (*la, "experts", "ff", "embed")),
    }
    if gated:
        out["w_gate"] = ParamDef((*lead, E, d, ff),
                                 (*la, "experts", "embed", "ff"))
        out["w_up"] = ParamDef((*lead, E, d, ff),
                               (*la, "experts", "embed", "ff"))
    else:
        out["w_up"] = ParamDef((*lead, E, d, ff),
                               (*la, "experts", "embed", "ff"))
    if cfg.n_shared_experts:
        # shared experts fuse into one always-on FFN of width n_shared*ff
        import dataclasses as _dc
        shared_cfg = _dc.replace(cfg, d_ff=cfg.n_shared_experts * ff)
        out["shared"] = ffn_defs(shared_cfg, stacked)
    return out


def _expert_ffn(cfg: ArchConfig, p: Dict, buf: jax.Array) -> jax.Array:
    """buf: (E, C, d) -> (E, C, d), batched over experts."""
    gated = cfg.activation in ("swiglu", "geglu")
    if gated:
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        act = (jax.nn.silu(g) if cfg.activation == "swiglu"
               else jax.nn.gelu(g, approximate=True))
        h = act * u
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        if cfg.activation == "relu2":
            h = jax.nn.relu(h) ** 2
        else:
            h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_apply(cfg: ArchConfig, p: Dict, x: jax.Array, ctx: Ctx
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    if (ctx.ep_shard_map and ctx.mesh is not None and ctx.plan is not None
            and ctx.plan.moe_strategy == "ep"):
        return _moe_apply_ep_shard_map(cfg, p, x, ctx)
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    C = expert_capacity(cfg, T)
    xt = x.reshape(T, d)

    # --- routing (float32)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    weights, ids = jax.lax.top_k(probs, k)  # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # --- load-balance aux loss (Switch): E * sum_e f_e * P_e
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = counts / (T * k)
    P = probs.mean(axis=0)
    aux = E * jnp.sum(f * P)

    # --- hash-partition: sort token-slots by expert key
    flat_e = ids.reshape(-1)  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(E))  # first slot per expert
    rank = jnp.arange(T * k) - starts[se]
    keep = rank < C
    pos = jnp.where(keep, se * C + rank, E * C)  # E*C = overflow bin

    # --- build per-expert buffers (the repartitioned pages)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[pos].set(xt[st])
    buf = buf[: E * C].reshape(E, C, d)
    if ctx.quantize_dispatch:
        # int8 over the wire (the all-to-all crosses the EP axis here):
        # per-row absmax scale, dequantized expert-side. Halves dispatch
        # bytes vs bf16; EXPERIMENTS.md §Perf quantifies the term.
        scale = jnp.max(jnp.abs(buf), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        q = jnp.clip(jnp.round(buf / scale), -127, 127).astype(jnp.int8)
        q = ctx.constrain(q, "experts", None, None)
        scale = ctx.constrain(scale, "experts", None, None)
        buf = (q.astype(x.dtype) * scale).astype(x.dtype)
    else:
        buf = ctx.constrain(buf, "experts", None, None)

    y_e = _expert_ffn(cfg, p, buf)  # (E, C, d)
    y_e = ctx.constrain(y_e, "experts", None, None)

    # --- probe/combine: gather outputs back to token order, weighted
    flat_y = jnp.concatenate(
        [y_e.reshape(E * C, d), jnp.zeros((1, d), y_e.dtype)])[pos]
    contrib = flat_y * (sw * keep).astype(flat_y.dtype)[:, None]
    y = jnp.zeros((T, d), x.dtype).at[st].add(contrib.astype(x.dtype))

    if cfg.n_shared_experts:
        import dataclasses as _dc
        shared_cfg = _dc.replace(cfg, d_ff=cfg.n_shared_experts * cfg.d_ff)
        y = y + ffn_apply(shared_cfg, p["shared"], xt)
    return y.reshape(B, S, d), aux


def _moe_apply_ep_shard_map(cfg: ArchConfig, p: Dict, x: jax.Array, ctx: Ctx
                            ) -> Tuple[jax.Array, jax.Array]:
    """Explicit expert parallelism (beyond-GSPMD, §Perf): each model shard
    owns E/tp experts; activations are replicated over the model axis, so
    each shard gathers ONLY its own experts' tokens (shard-local
    hash-partition build — zero dispatch collective), runs its experts, and
    the combine is a single psum of the partial outputs per layer. This
    replaces GSPMD's scatter-driven resharding storm (measured in
    EXPERIMENTS.md §Perf) with exactly one collective."""
    from jax.sharding import PartitionSpec as P
    from repro.models.model_zoo import _batch_axis

    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    tp = ctx.plan.tp_size
    E_local = E // tp
    C = expert_capacity(cfg, T)
    b_ax = _batch_axis(ctx.plan)
    tp_ax = ctx.plan.tp_axis

    expert_specs = {}
    for key in ("w_gate", "w_up", "w_down"):
        if key in p:
            expert_specs[key] = P(tp_ax, None, None)

    def local_moe(router, experts, xin):
        my = jax.lax.axis_index(tp_ax)
        xt = xin.reshape(-1, d)
        Tl = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
        counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
        aux = E * jnp.sum(counts / (Tl * k) * probs.mean(0))

        flat_e = ids.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Tl), k)
        flat_w = weights.reshape(-1)
        # shard-local build: keep only slots routed to MY experts
        mine = (flat_e // E_local) == my
        local_e = jnp.where(mine, flat_e % E_local, E_local)
        order = jnp.argsort(local_e, stable=True)
        se, st, sw = local_e[order], flat_t[order], flat_w[order]
        starts = jnp.searchsorted(se, jnp.arange(E_local))
        rank = jnp.arange(Tl * k) - starts[se]
        keep = (rank < C) & (se < E_local)
        pos = jnp.where(keep, se * C + rank, E_local * C)
        buf = jnp.zeros((E_local * C + 1, d), xt.dtype).at[pos].set(xt[st])
        buf = buf[: E_local * C].reshape(E_local, C, d)
        y_e = _expert_ffn(cfg, experts, buf)
        flat_y = jnp.concatenate(
            [y_e.reshape(E_local * C, d), jnp.zeros((1, d), y_e.dtype)])[pos]
        contrib = flat_y * (sw * keep).astype(flat_y.dtype)[:, None]
        y_part = jnp.zeros((Tl, d), xt.dtype).at[st].add(
            contrib.astype(xt.dtype))
        # the combine: ONE collective per layer
        y_full = jax.lax.psum(y_part, tp_ax)
        return y_full.reshape(xin.shape), aux

    experts_p = {kk: p[kk] for kk in expert_specs}
    fn = jax.shard_map(
        local_moe, mesh=ctx.mesh,
        in_specs=(P(None, None), expert_specs, P(b_ax, None, None)),
        out_specs=(P(b_ax, None, None), P()),
        check_vma=False)
    y, aux = fn(p["router"], experts_p, x)
    if cfg.n_shared_experts:
        import dataclasses as _dc
        shared_cfg = _dc.replace(cfg, d_ff=cfg.n_shared_experts * cfg.d_ff)
        y = y + ffn_apply(shared_cfg, p["shared"],
                          x.reshape(-1, d)).reshape(x.shape)
    return y, aux
