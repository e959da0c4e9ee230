"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lbgm_projection_ref(g: jax.Array, l: jax.Array):
    g32 = g.astype(jnp.float32)
    l32 = l.astype(jnp.float32)
    return jnp.dot(g32, l32), jnp.dot(g32, g32), jnp.dot(l32, l32)


def lbgm_sparse_decision_ref(blocks: jax.Array, idx: jax.Array):
    """The three dense passes the fused sparse kernel replaces.
    blocks: (nb, block) f32; idx: (nb, kb) int32 block-local positions.
    Returns (gg scalar, gathered (nb, kb), top_idx (nb, kb), top_val
    (nb, kb)) — top-k is by |value| per block row, values kept signed.
    """
    b32 = blocks.astype(jnp.float32)
    gg = jnp.sum(b32 * b32)
    gathered = jnp.take_along_axis(b32, idx, axis=1)
    kb = idx.shape[1]
    _, ti = jax.lax.top_k(jnp.abs(b32), kb)
    tv = jnp.take_along_axis(b32, ti, axis=1)
    return gg, gathered, ti.astype(jnp.int32), tv


def lbgm_dequant_accum_ref(acc: jax.Array, w: jax.Array, gscale: jax.Array,
                           idx: jax.Array, qv: jax.Array, scale: jax.Array):
    """Sequential dequantize + scatter-accumulate: the engine's one fold
    for quantized sparse payloads (``SparseCodecAggregator``).

    acc: (nb, block) f32; w, gscale: (C,); idx: (C, nb, kb) int32; qv:
    (C, nb, kb) wire-dtype values; scale: (C, nb, 1) f32 row scales.
    Gather-modify-scatter with ``coeff = (w * gscale) * scale`` folded
    before the multiply with the widened values — the same
    ``a + where(w > 0, c * v, 0)`` shape as
    ``SparseTopKAggregator`` so full-round aggregates stay bit-equal to
    the unquantized path when the values are on the fp32 grid already.
    """
    def body(a, x):
        w_k, g_k, i_k, q_k, s_k = x
        rows = jnp.arange(a.shape[0])[:, None]
        coeff = (w_k * g_k) * s_k                        # (nb, 1)
        cur = a[rows, i_k]
        new = cur + jnp.where(w_k > 0,
                              coeff * q_k.astype(jnp.float32), 0.0)
        return a.at[rows, i_k].set(new), None

    out, _ = jax.lax.scan(body, acc, (w, gscale, idx, qv, scale))
    return out


def sort_topk_rows(idx: jax.Array, val: jax.Array):
    """Canonicalize a block-row top-k (idx, val) pair by ascending index.

    ``lax.top_k`` emits entries in descending-|value| order, the
    threshold-select decision kernel in index order; consumers treat each
    row as a set, so equivalence tests compare through this canonical
    form.
    """
    order = jnp.argsort(idx, axis=-1)
    return (jnp.take_along_axis(idx, order, axis=-1),
            jnp.take_along_axis(val, order, axis=-1))


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """Naive softmax attention. q:(BH,Tq,hd), k/v:(BH,Tk,hd)."""
    Tq, Tk = q.shape[1], k.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Tq)[:, None]
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def rwkv6_scan_ref(r, k, v, logw, u):
    """Per-timestep recurrence — the ground-truth RWKV6 semantics.
    r,k,v,logw: (BH, T, hd); u: (BH, hd). Returns fp32 (BH, T, hd).

        out_t = r_t (S_{t-1} + u * k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    r, k, v, lw = (a.astype(jnp.float32) for a in (r, k, v, logw))
    u = u.astype(jnp.float32)
    BH, T, hd = r.shape

    def step(S, xs):
        rt, kt, vt, lwt = xs                     # (BH, hd)
        kv = jnp.einsum("bd,be->bde", kt, vt)
        out = jnp.einsum("bd,bde->be", rt, S + u[..., None] * kv)
        S = jnp.exp(lwt)[..., None] * S + kv
        return S, out

    S0 = jnp.zeros((BH, hd, hd), jnp.float32)
    xs = tuple(a.transpose(1, 0, 2) for a in (r, k, v, lw))
    _, outs = jax.lax.scan(step, S0, xs)
    return outs.transpose(1, 0, 2)
