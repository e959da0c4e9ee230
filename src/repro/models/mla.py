"""Multi-head latent attention for training (DeepSeek-V2/V3, no q LoRA).

As ``DeepseekV3Attention`` with ``q_lora_rank`` None: ``q = x W_q`` split
per head into a no-position part and a rotary part; ``[c_kv, k_pe] =
x W_kv_a`` with ``k_pe`` one rotary head shared by all heads; ``c_kv`` is
RMS-normed and expanded by ``W_kv_b`` into each head's no-position key and
value. Rotary acts on the rotary parts only, interleaved
(``rope_interleave``): the (even, odd) pairs are first gathered into
halves (``deinterleave``) and then rotated as ``rotate_half``. Scores are
scaled by (nope + rope)^-1/2. No decode cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import attention, rope_rotate
from repro.models.common import ParamStore, rms_norm


def init_mla(store: ParamStore, prefix: str, cfg: ArchConfig, stack: int = 0):
    """stack>0: leading ``layers`` axis for lax.scan."""
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    lead = (stack,) if stack else ()
    lx = ("layers",) if stack else ()
    store.param(f"{prefix}/wq", lead + (d, H * (m.qk_nope_dim + m.qk_rope_dim)),
                lx + ("embed", "heads"))
    store.param(f"{prefix}/wkv_a", lead + (d, m.kv_lora_rank + m.qk_rope_dim),
                lx + ("embed", "kv_lora"))
    store.param(f"{prefix}/kv_norm", lead + (m.kv_lora_rank,),
                lx + ("kv_lora",), init="ones")
    store.param(f"{prefix}/wkv_b",
                lead + (m.kv_lora_rank, H * (m.qk_nope_dim + m.v_dim)),
                lx + ("kv_lora", "heads"))
    store.param(f"{prefix}/wo", lead + (H * m.v_dim, d),
                lx + ("heads", "embed"))


def deinterleave(x: jax.Array) -> jax.Array:
    """(..., 2n) with (even, odd) pairs -> (..., n evens, n odds)."""
    *lead, r = x.shape
    return jnp.swapaxes(x.reshape(*lead, r // 2, 2), -1, -2).reshape(
        *lead, r)


def apply_mla(p, x: jax.Array, cfg: ArchConfig, positions) -> jax.Array:
    """x (B, T, d), positions (B, T) -> (B, T, d), causal."""
    m, H = cfg.mla, cfg.n_heads
    B, T, _ = x.shape
    nope, rope, r = m.qk_nope_dim, m.qk_rope_dim, m.kv_lora_rank
    q = jnp.einsum("btd,dh->bth", x, p["wq"]).reshape(B, T, H, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = jnp.einsum("btd,dr->btr", x, p["wkv_a"])
    c_kv, k_pe = kv[..., :r], kv[..., r:].reshape(B, T, 1, rope)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("btr,rh->bth", c_kv, p["wkv_b"]).reshape(
        B, T, H, nope + m.v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rope_rotate(deinterleave(q_pe), positions, cfg.rope_theta)
    k_pe = rope_rotate(deinterleave(k_pe), positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, T, H, rope))], -1)
    o = attention(q, k, v, causal=True, unroll=cfg.unroll)
    return jnp.einsum("bth,hd->btd", o.reshape(B, T, H * m.v_dim), p["wo"])
