"""Attention: GQA/MQA, RoPE, M-RoPE, causal + sliding-window, decode.

Training/prefill attention is *query-chunked* (flash-style outer loop via
``lax.scan``) so the (Tq, Tk) score tensor never materializes at full size —
this is the jnp reference path used for lowering/roofline; the Pallas TPU
kernel lives in ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------- RoPE

def rope_rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, hd); positions: (B, T) int32. Pairs (even, odd) halves."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,T,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_rotate(x: jax.Array, positions3: jax.Array, sections, theta: float):
    """Multimodal RoPE (qwen2-vl): positions3 (3, B, T) for (t, h, w).

    The hd/2 frequency slots are partitioned into ``sections`` groups; slot
    group i uses positions3[i]. Equivalent to standard RoPE when the three
    position streams coincide (text tokens).
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    sel = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                     total_repeat_length=hd // 2)          # (hd/2,) in {0,1,2}
    # gather per-slot positions: (B, T, hd/2)
    pos = jnp.einsum("sbt,cs->btc", positions3.astype(jnp.float32),
                     jax.nn.one_hot(sel, 3, dtype=jnp.float32))
    ang = pos * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- core SDPA

def _sdpa_block(q, k, v, mask):
    """q:(B,cq,Hkv,g,hd) k/v:(B,Tk,Hkv,hd) mask:(cq,Tk) or None -> (B,cq,Hkv,g,hd)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              q_offset: int = 0, q_chunk: int = 1024,
              unroll: bool = False) -> jax.Array:
    """GQA attention. q:(B,Tq,Hq,hd), k:(B,Tk,Hkv,hd), v:(B,Tk,Hkv,hv) ->
    (B,Tq,Hq,hv); scores are scaled by hd^-1/2.

    ``q_offset``: absolute position of q[0] relative to k[0] (for caches).
    ``window``: sliding-window width (keys with qpos-kpos >= window masked).
    """
    B, Tq, Hq, hd = q.shape
    Tk, Hkv, hv = k.shape[1], k.shape[2], v.shape[-1]
    assert Hq % Hkv == 0
    g = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, g, hd)

    def mask_for(qpos):
        kpos = jnp.arange(Tk)
        m = jnp.ones((qpos.shape[0], Tk), bool)
        if causal:
            m &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            m &= (qpos[:, None] - kpos[None, :]) < window
        return m

    if Tq <= q_chunk:
        qpos = jnp.arange(Tq) + q_offset
        need_mask = causal or (window is not None)
        o = _sdpa_block(qg, k, v, mask_for(qpos) if need_mask else None)
        return o.reshape(B, Tq, Hq, hv)

    while Tq % q_chunk:      # largest divisor of Tq not above q_chunk
        q_chunk -= 1
    n = Tq // q_chunk
    qs = qg.reshape(B, n, q_chunk, Hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, args):
        i, qc = args
        qpos = i * q_chunk + jnp.arange(q_chunk) + q_offset
        return None, _sdpa_block(qc, k, v, mask_for(qpos))

    if unroll:   # dry-run cost pass: scan bodies are undercounted by XLA
        os = jnp.stack([body(None, (jnp.asarray(i), qs[i]))[1]
                        for i in range(n)])
    else:
        _, os = jax.lax.scan(body, None, (jnp.arange(n), qs))
    return os.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tq, Hq, hv)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid_len) -> jax.Array:
    """Single-token decode. q:(B,1,Hq,hd); caches:(B,S,Hkv,hd)."""
    B, _, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)
    mask = kpos[None, :] < jnp.asarray(valid_len).reshape(-1, 1)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)
