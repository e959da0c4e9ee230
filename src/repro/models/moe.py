"""Mixture-of-Experts FFN, two routers.

``scoring="softmax"`` (Mixtral, Llama 4): capacity-based gather/scatter
routing. TPU adaptation: instead of a dense one-hot dispatch einsum (O(T^2)
FLOPs at high expert counts) we build an (E, C) token-index buffer with a
cumsum position assignment and use pure gathers/scatters, so expert FLOPs
stay O(capacity_factor x active FLOPs). Experts are sharded over the
``model`` mesh axis ("expert" logical axis); in fsdp mode d_ff
additionally shards over ``data``.

``scoring="sigmoid"`` (DeepSeek-V3): :func:`apply_dropless`. The router
scores every expert, this layer computes the part of the result its held
experts give (``MoEConfig.held`` from ``held_offset``) with nothing
dropped, as a dense masked einsum over the held experts, and the shared
experts are added.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (MOE_EXPERTS, MOE_ROUTE, ParamStore, silu,
                                 swiglu)


def init_moe(store: ParamStore, prefix: str, cfg: ArchConfig, stack: int = 0):
    """stack>0: leading `layers` axis for lax.scan."""
    m, d = cfg.moe, cfg.d_model
    E, H, ff = m.num_experts, m.n_held, m.d_expert or cfg.d_ff
    lead = (stack,) if stack else ()
    lax_ = ("layers",) if stack else ()
    store.param(f"{prefix}/router", lead + (d, E), lax_ + ("embed", "expert"),
                scale=0.02)
    store.param(f"{prefix}/w_gate", lead + (H, d, ff),
                lax_ + ("expert", "embed", "ff"))
    store.param(f"{prefix}/w_up", lead + (H, d, ff),
                lax_ + ("expert", "embed", "ff"))
    store.param(f"{prefix}/w_down", lead + (H, ff, d),
                lax_ + ("expert", "ff", "embed"))
    if m.n_shared:
        fs = m.n_shared * ff
        store.param(f"{prefix}/shared_gate", lead + (d, fs),
                    lax_ + ("embed", "ff"))
        store.param(f"{prefix}/shared_up", lead + (d, fs),
                    lax_ + ("embed", "ff"))
        store.param(f"{prefix}/shared_down", lead + (fs, d),
                    lax_ + ("ff", "embed"))


def apply_moe(p, x: jax.Array, cfg: ArchConfig):
    """x: (B, T, d) -> (out (B, T, d), aux_loss scalar).

    Routing/capacity is computed independently per example (B is the sharded
    axis), keeping dispatch local to the data shard.
    """
    B, T, d = x.shape
    E, k, cf = cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.capacity_factor
    C = max(1, int(T * k * cf / E))

    logits = jnp.einsum("btd,de->bte", x, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (B,T,E) fp32
    top_w, top_e = jax.lax.top_k(probs, k)                   # (B,T,k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # flatten the k routes into the token axis: (B, T*k)
    routes = top_e.reshape(B, T * k)
    route_w = top_w.reshape(B, T * k)
    onehot = jax.nn.one_hot(routes, E, dtype=jnp.int32)      # (B,T*k,E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot            # position in expert
    pos = jnp.sum(pos_all * onehot, axis=-1)                 # (B,T*k)
    keep = pos < C

    # scatter token indices into the (E*C) dispatch buffer (dropped -> clipped,
    # masked out at combine time)
    token_idx = jnp.tile(jnp.arange(T * k) // k, (B, 1))     # source token
    dest = routes * C + jnp.where(keep, pos, C * E)          # OOB when dropped
    buf = jnp.zeros((B, E * C), jnp.int32)
    buf = jax.vmap(lambda b, dst, src: b.at[dst].set(src, mode="drop"))(
        buf, dest, token_idx)

    gathered = jnp.take_along_axis(
        x, buf[..., None].clip(0, T - 1), axis=1)            # (B, E*C, d)
    gx = gathered.reshape(B, E, C, d)

    # expert SwiGLU, experts sharded over the model axis
    g = jnp.einsum("becd,edf->becf", gx, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", gx, p["w_up"])
    y = jnp.einsum("becf,efd->becd", silu(g) * u, p["w_down"])
    y = y.reshape(B, E * C, d)

    # combine: each route gathers its slot back, weighted, drop-masked
    slot = (routes * C + pos).clip(0, E * C - 1)             # (B,T*k)
    back = jnp.take_along_axis(y, slot[..., None], axis=1)   # (B,T*k,d)
    w = (route_w * keep).astype(back.dtype)
    out = jnp.sum(back.reshape(B, T, k, d) * w.reshape(B, T, k, 1), axis=2)

    # Switch-style load-balance aux loss
    frac_routed = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_routed * mean_prob) * cfg.moe.router_aux_loss
    return out.astype(x.dtype), aux


# ------------------------------------------------------------ dropless

def route_sigmoid(x: jax.Array, router: jax.Array, cfg: ArchConfig):
    """DeepSeek-V3 ``noaux_tc`` routing of (N, d) tokens -> (experts (N,
    k) int32, weights (N, k) float32). The gate runs in float32 at HIGHEST;
    the top-k is chosen on score + selection bias, the weights are the
    chosen scores over their sum (``norm_topk_prob``) times
    ``routed_scale``.
    The bias is a buffer that a balancing update moves between steps; that
    update is not modelled, so it stays zero (with ``n_group`` 1 the group
    stage selects every expert)."""
    m = cfg.moe
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((m.num_experts,), jnp.float32)
    _, top_e = jax.lax.top_k(s + bias, m.top_k)
    top_w = jnp.take_along_axis(s, top_e, -1)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_e, top_w * m.routed_scale


def apply_dropless(p, x: jax.Array, cfg: ArchConfig):
    """x (B, T, d) -> (out (B, T, d), rows sent to held experts).

    Each held expert is applied to every token and its output weighted by
    the token's route weight for it (zero where the token routes
    elsewhere), so nothing can drop; the shared experts' SwiGLU is added
    for every token. At the rows an expert gets in a federated client's
    batch (tens to hundreds) this masked form runs faster on a v5e than
    grouped matmuls over the routed rows alone."""
    m = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    with jax.named_scope(MOE_ROUTE):
        top_e, top_w = route_sigmoid(xf, p["router"], cfg)
        hit = top_e[..., None] == m.held_offset + jnp.arange(m.n_held)
        w = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), 1)   # (N, held)
    with jax.named_scope(MOE_EXPERTS):
        g = jnp.einsum("nd,edf->enf", xf, p["w_gate"])
        u = jnp.einsum("nd,edf->enf", xf, p["w_up"])
        h = silu(g) * u * w.T[..., None].astype(x.dtype)
        out = jnp.einsum("enf,efd->nd", h, p["w_down"])
        if m.n_shared:
            out = out + swiglu(xf, p["shared_gate"], p["shared_up"],
                               p["shared_down"])
    return out.reshape(B, T, d), jnp.sum(hit).astype(jnp.float32)
