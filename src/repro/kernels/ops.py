"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to ``None`` -> :func:`_default_interpret` backend
auto-detection (interpreter on CPU where the container validates kernel
bodies, compiled Mosaic on real TPUs). The raw ``*_pallas`` entry points in
the kernel modules share the same ``None`` default, so callers that bypass
these wrappers get compiled execution on TPU too.

The two LBGM wrappers (:func:`lbgm_projection`,
:func:`lbgm_sparse_decision`) are the FL engine's fused decision hot path
(``FLConfig.fused_kernels``). Both carry a ``custom_vmap`` rule that maps
``jax.vmap`` — how every client scheduler batches the per-client step —
onto the kernels' leading batch grid dimension, so a vmap'd client block
compiles to ONE batched ``pallas_call`` instead of per-client dispatches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lbgm_projection import (lbgm_projection_batched_pallas,
                                           lbgm_projection_pallas)
from repro.kernels.lbgm_sparse import (lbgm_sparse_decision_batched_pallas,
                                       lbgm_sparse_decision_pallas)
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _bcast(x, batched, axis_size):
    """custom_vmap hands unbatched args through unchanged; lift them."""
    return x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)


@functools.lru_cache(maxsize=None)
def _proj_leaf(interpret: bool):
    """Per-leaf fused projection with vmap routed to the batched kernel."""

    @custom_vmap
    def f(g, l):
        return lbgm_projection_pallas(g, l, interpret=interpret)

    @f.def_vmap
    def _rule(axis_size, in_batched, g, l):
        g = _bcast(g, in_batched[0], axis_size)
        l = _bcast(l, in_batched[1], axis_size)
        out = lbgm_projection_batched_pallas(g, l, interpret=interpret)
        return out, (True, True, True)

    return f


@functools.lru_cache(maxsize=None)
def _sparse_decision(interpret: bool):
    """Fused sparse decision with vmap routed to the batched kernel."""

    @custom_vmap
    def f(blocks, idx):
        return lbgm_sparse_decision_pallas(blocks, idx, interpret=interpret)

    @f.def_vmap
    def _rule(axis_size, in_batched, blocks, idx):
        blocks = _bcast(blocks, in_batched[0], axis_size)
        idx = _bcast(idx, in_batched[1], axis_size)
        out = lbgm_sparse_decision_batched_pallas(blocks, idx,
                                                  interpret=interpret)
        return out, (True, True, True, True)

    return f


def lbgm_projection(g_tree, l_tree, interpret=None):
    """Fused (<g,l>, ||g||^2, ||l||^2) over a pytree pair (one HBM pass per
    leaf). Returns fp32 scalars. vmap-ing this (the schedulers' client axis)
    compiles to the batched kernel, one leading grid dimension per leaf."""
    interpret = _default_interpret() if interpret is None else interpret
    f = _proj_leaf(bool(interpret))
    gl = gg = ll = jnp.zeros((), jnp.float32)
    g_leaves = jax.tree.leaves(g_tree)
    l_leaves = jax.tree.leaves(l_tree)
    for g, l in zip(g_leaves, l_leaves):
        a, b, c = f(g.reshape(-1), l.reshape(-1))
        gl, gg, ll = gl + a, gg + b, ll + c
    return gl, gg, ll


def lbgm_sparse_decision(blocks, idx, interpret=None):
    """One fused pass over a (nb, block) gradient block layout: returns
    ``(gg, gathered, top_idx, top_val)`` — the three dense passes of the
    sparse-LBG client step (gather at LBG positions, ||g||^2, block-wise
    top-k) in a single read of g, each top-k row as an index-ordered set
    (see ``kernels/lbgm_sparse.py``). vmap over the client axis maps onto
    the kernel's leading batch grid dimension."""
    interpret = _default_interpret() if interpret is None else interpret
    return _sparse_decision(bool(interpret))(blocks, idx)


def flash_attention(q, k, v, *, causal=True, window=None, interpret=None):
    """GQA flash attention. q:(B,Tq,Hq,hd), k/v:(B,Tk,Hkv,hd)."""
    interpret = _default_interpret() if interpret is None else interpret
    B, Tq, Hq, hd = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Tq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(
        B * Hq, Tk, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(
        B * Hq, Tk, hd)
    o = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                               interpret=interpret)
    return o.reshape(B, Hq, Tq, hd).transpose(0, 2, 1, 3)


def rwkv6_scan(r, k, v, logw, u, interpret=None):
    """Chunked RWKV6. r/k/v/logw: (B,T,H,hd); u: (H,hd) -> fp32 (B,T,H,hd)."""
    interpret = _default_interpret() if interpret is None else interpret
    B, T, H, hd = r.shape
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    o = rwkv6_scan_pallas(flat(r), flat(k), flat(v), flat(logw), uf,
                          interpret=interpret)
    return o.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
