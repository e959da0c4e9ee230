"""Decoder-LM assembly covering all assigned architecture families.

Supports: dense GQA (llama/yi/deepseek/mistral/qwen3), MoE (mixtral/llama4),
DeepSeek-V3 blocks (MLA + sigmoid-routed dropless MoE with shared experts),
SSM (rwkv6), hybrid RG-LRU+local-attn (recurrentgemma), enc-dec (whisper
backbone) and VLM early-fusion (qwen2-vl backbone, M-RoPE).

Homogeneous stacks (single-entry block_pattern, no enc-dec) are *stacked*
along a leading "layers" axis and run under ``lax.scan`` (+ optional remat);
leading dense layers of an MoE stack (``first_dense``) sit in front of it,
unrolled, as ``dense_<i>``; heterogeneous patterns unroll.

Each block returns its auxiliary outputs as a dict of scalars summed over
layers: ``aux`` (the capacity router's load-balance loss, added to the
loss) and ``moe_routed_rows`` (the dropless router's count of rows sent to
held experts, a counter).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv6_lib
from repro.models.attention import (attention, decode_attention, mrope_rotate,
                                    rope_rotate)
from repro.models.common import (MLA, MOE_ROUTED_ROWS, ParamStore, rms_norm,
                                 subtree, swiglu)


# ------------------------------------------------------------------ init

def _init_attn(store: ParamStore, prefix: str, cfg: ArchConfig, stack: int,
               cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    lead = (stack,) if stack else ()
    lx = ("layers",) if stack else ()
    tag = "x" if cross else "a"
    store.param(f"{prefix}/w{tag}_q", lead + (d, nq * hd),
                lx + ("embed", "heads"))
    store.param(f"{prefix}/w{tag}_k", lead + (d, nkv * hd),
                lx + ("embed", "kv_heads"))
    store.param(f"{prefix}/w{tag}_v", lead + (d, nkv * hd),
                lx + ("embed", "kv_heads"))
    store.param(f"{prefix}/w{tag}_o", lead + (nq * hd, d),
                lx + ("heads", "embed"))
    if cfg.qk_norm and not cross:
        store.param(f"{prefix}/q_norm", lead + (hd,), lx + ("head_dim",),
                    init="ones")
        store.param(f"{prefix}/k_norm", lead + (hd,), lx + ("head_dim",),
                    init="ones")


def _init_ffn(store: ParamStore, prefix: str, cfg: ArchConfig, stack: int,
              dense: bool = False):
    if cfg.moe.num_experts and not dense:
        moe_lib.init_moe(store, prefix + "/moe", cfg, stack)
    else:
        d, ff = cfg.d_model, cfg.d_ff
        lead = (stack,) if stack else ()
        lx = ("layers",) if stack else ()
        store.param(f"{prefix}/w_gate", lead + (d, ff), lx + ("embed", "ff"))
        store.param(f"{prefix}/w_up", lead + (d, ff), lx + ("embed", "ff"))
        store.param(f"{prefix}/w_down", lead + (ff, d), lx + ("ff", "embed"))


def _init_block(store: ParamStore, prefix: str, cfg: ArchConfig, kind: str,
                stack: int = 0, cross: bool = False, dense: bool = False):
    d = cfg.d_model
    lead = (stack,) if stack else ()
    lx = ("layers",) if stack else ()
    store.param(f"{prefix}/norm1", lead + (d,), lx + ("embed",), init="ones")
    if kind in ("attn", "swa") and cfg.mla.kv_lora_rank:
        mla_lib.init_mla(store, prefix + "/mla", cfg, stack)
    elif kind in ("attn", "swa"):
        _init_attn(store, prefix, cfg, stack)
    elif kind == "rwkv6":
        rwkv6_lib.init_rwkv6(store, prefix + "/tmix", cfg, stack)
    elif kind == "rglru":
        rglru_lib.init_rglru(store, prefix + "/rec", cfg, stack)
    else:
        raise ValueError(kind)
    if cross:
        store.param(f"{prefix}/norm_x", lead + (d,), lx + ("embed",),
                    init="ones")
        _init_attn(store, prefix, cfg, stack, cross=True)
    store.param(f"{prefix}/norm2", lead + (d,), lx + ("embed",), init="ones")
    _init_ffn(store, prefix, cfg, stack, dense)


def uses_scan(cfg: ArchConfig) -> bool:
    return (len(cfg.block_pattern) == 1 and not cfg.encdec
            and not cfg.unroll)


def init_lm(key: jax.Array, cfg: ArchConfig):
    """Returns (params flat dict, logical axes flat dict)."""
    import numpy as np
    dtype = jnp.dtype(cfg.dtype)
    store = ParamStore(key, dtype)
    d = cfg.d_model
    store.param("embed", (cfg.vocab_size, d), ("vocab", "embed"), scale=0.02)
    if cfg.encdec:
        for i in range(cfg.n_encoder_layers):
            _init_block(store, f"enc_{i:02d}", cfg, "attn")
        store.param("enc_norm", (d,), ("embed",), init="ones")
        for i in range(cfg.n_layers):
            _init_block(store, f"dec_{i:02d}", cfg, "attn", cross=True)
    elif uses_scan(cfg):
        for i in range(cfg.first_dense):
            _init_block(store, f"dense_{i:02d}", cfg, cfg.block_pattern[0],
                        dense=True)
        _init_block(store, "blocks", cfg, cfg.block_pattern[0],
                    stack=cfg.n_layers - cfg.first_dense)
    else:
        for i in range(cfg.n_layers):
            _init_block(store, f"layer_{i:02d}", cfg, cfg.block_kind(i))
    store.param("final_norm", (d,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        store.param("lm_head", (d, cfg.vocab_size), ("embed", "vocab"),
                    scale=0.02)
    return store.params, store.axes


# ------------------------------------------------------------------ fwd

def _apply_attn_train(p, x, cfg: ArchConfig, kind: str, positions, pos3,
                      window_override=None):
    B, T, d = x.shape
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("btd,dh->bth", x, p["wa_q"]).reshape(B, T, nq, hd)
    k = jnp.einsum("btd,dh->bth", x, p["wa_k"]).reshape(B, T, nkv, hd)
    v = jnp.einsum("btd,dh->bth", x, p["wa_v"]).reshape(B, T, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope and pos3 is not None:
        q = mrope_rotate(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = mrope_rotate(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = rope_rotate(q, positions, cfg.rope_theta)
        k = rope_rotate(k, positions, cfg.rope_theta)
    window = window_override if window_override is not None else (
        cfg.sliding_window if kind == "swa" else None)
    o = attention(q, k, v, causal=True, window=window, unroll=cfg.unroll)
    return jnp.einsum("bth,hd->btd", o.reshape(B, T, nq * hd), p["wa_o"])


def _apply_cross_attn(p, x, enc_out, cfg: ArchConfig):
    B, T, d = x.shape
    Te = enc_out.shape[1]
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("btd,dh->bth", x, p["wx_q"]).reshape(B, T, nq, hd)
    k = jnp.einsum("btd,dh->bth", enc_out, p["wx_k"]).reshape(B, Te, nkv, hd)
    v = jnp.einsum("btd,dh->bth", enc_out, p["wx_v"]).reshape(B, Te, nkv, hd)
    o = attention(q, k, v, causal=False)
    return jnp.einsum("bth,hd->btd", o.reshape(B, T, nq * hd), p["wx_o"])


def _aux_zero(cfg: ArchConfig) -> Dict[str, Any]:
    """A stack's auxiliary outputs (see the module's docstring), zero."""
    if not cfg.moe.num_experts:
        return {}
    if cfg.moe.scoring == "sigmoid":
        return {MOE_ROUTED_ROWS: 0.0}
    return {"aux": 0.0}


def _aux_add(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in {**a, **b}}


def _apply_ffn(p, x, cfg: ArchConfig, dense: bool = False):
    """-> (out, the block's auxiliary outputs)."""
    if cfg.moe.num_experts and not dense:
        if cfg.moe.scoring == "sigmoid":
            out, rows = moe_lib.apply_dropless(subtree(p, "moe"), x, cfg)
            return out, {MOE_ROUTED_ROWS: rows}
        out, aux = moe_lib.apply_moe(subtree(p, "moe"), x, cfg)
        return out, {"aux": aux}
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), {}


def _apply_block_train(p, x, cfg: ArchConfig, kind: str, positions, pos3=None,
                       enc_out=None, causal_attn=True, dense: bool = False):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in ("attn", "swa") and cfg.mla.kv_lora_rank:
        with jax.named_scope(MLA):
            h = mla_lib.apply_mla(subtree(p, "mla"), h, cfg, positions)
    elif kind in ("attn", "swa"):
        if causal_attn:
            h = _apply_attn_train(p, h, cfg, kind, positions, pos3)
        else:  # encoder self-attention
            B, T, d = h.shape
            hd, nq, nkv = (cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads)
            q = jnp.einsum("btd,dh->bth", h, p["wa_q"]).reshape(B, T, nq, hd)
            k = jnp.einsum("btd,dh->bth", h, p["wa_k"]).reshape(B, T, nkv, hd)
            v = jnp.einsum("btd,dh->bth", h, p["wa_v"]).reshape(B, T, nkv, hd)
            o = attention(q, k, v, causal=False)
            h = jnp.einsum("bth,hd->btd", o.reshape(B, T, nq * hd), p["wa_o"])
    elif kind == "rwkv6":
        h, _ = rwkv6_lib.apply_rwkv6(subtree(p, "tmix"), h, cfg)
    elif kind == "rglru":
        h, _ = rglru_lib.apply_rglru(subtree(p, "rec"), h, cfg)
    x = x + h
    if enc_out is not None:
        hx = rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + _apply_cross_attn(p, hx, enc_out, cfg)
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    h2, aux = _apply_ffn(p, h2, cfg, dense)
    return x + h2, aux


def _embed(params, cfg: ArchConfig, tokens):
    emb = jnp.take(params["embed"], tokens, axis=0)
    return emb


def build_mrope_positions(cfg: ArchConfig, B: int, T: int):
    """(3, B, T) positions: a vision grid of `vision_tokens` patches followed
    by sequential text positions (qwen2-vl style)."""
    nv = cfg.vision_tokens
    side = max(1, int(nv ** 0.5))
    idx = jnp.arange(T)
    is_vis = idx < nv
    t_pos = jnp.where(is_vis, 0, idx - nv + side)
    h_pos = jnp.where(is_vis, idx // side, idx - nv + side)
    w_pos = jnp.where(is_vis, idx % side, idx - nv + side)
    pos3 = jnp.stack([t_pos, h_pos, w_pos])                  # (3, T)
    return jnp.broadcast_to(pos3[:, None, :], (3, B, T))


def forward_hidden(params: Dict[str, jax.Array], cfg: ArchConfig,
                   tokens: jax.Array,
                   extra_embeds: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Backbone forward to the final hidden states. tokens (B,T) ->
    (hidden (B,T,d), auxiliary outputs summed over layers).

    ``extra_embeds``: modality-stub embeddings. audio (enc-dec): encoder
    input frames (B, Te, d). vlm: patch embeddings (B, n_vis, d) that
    *overwrite* the first n_vis token embeddings (early fusion).
    """
    B, T = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    pos3 = None
    if cfg.mrope:
        pos3 = build_mrope_positions(cfg, B, T)
        if extra_embeds is not None:
            nv = extra_embeds.shape[1]
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x[:, nv:]],
                                axis=1)
    aux_total = _aux_zero(cfg)

    enc_out = None
    if cfg.encdec:
        assert extra_embeds is not None, "enc-dec needs encoder frames"
        from repro.models.common import sinusoidal_positions
        e = extra_embeds.astype(x.dtype)
        e = e + sinusoidal_positions(e.shape[1], cfg.d_model).astype(x.dtype)
        for i in range(cfg.n_encoder_layers):
            e, aux = _apply_block_train(subtree(params, f"enc_{i:02d}"), e,
                                        cfg, "attn", None, causal_attn=False)
            aux_total = _aux_add(aux_total, aux)
        enc_out = rms_norm(e, params["enc_norm"], cfg.norm_eps)
        for i in range(cfg.n_layers):
            x, aux = _apply_block_train(subtree(params, f"dec_{i:02d}"), x,
                                        cfg, "attn", positions,
                                        enc_out=enc_out)
            aux_total = _aux_add(aux_total, aux)
    elif uses_scan(cfg):
        kind = cfg.block_pattern[0]
        for i in range(cfg.first_dense):
            blk = functools.partial(
                _apply_block_train, subtree(params, f"dense_{i:02d}"),
                cfg=cfg, kind=kind, positions=positions, pos3=pos3,
                dense=True)
            if cfg.remat:
                blk = jax.checkpoint(blk)
            x, _ = blk(x)
        stacked = subtree(params, "blocks")

        def body(carry, layer_p):
            h, aux_acc = carry
            h, aux = _apply_block_train(layer_p, h, cfg, kind, positions,
                                        pos3)
            return (h, _aux_add(aux_acc, aux)), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), stacked)
    else:
        for i in range(cfg.n_layers):
            blk = functools.partial(
                _apply_block_train, subtree(params, f"layer_{i:02d}"), cfg=cfg,
                kind=cfg.block_kind(i), positions=positions, pos3=pos3)
            if cfg.remat:
                blk = jax.checkpoint(blk)
            x, aux = blk(x)
            aux_total = _aux_add(aux_total, aux)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def _head(params, cfg: ArchConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, cfg: ArchConfig, tokens, extra_embeds=None):
    """Full-logit forward (small models / tests). -> (logits (B,T,V),
    auxiliary outputs)."""
    x, aux = forward_hidden(params, cfg, tokens, extra_embeds)
    return jnp.einsum("btd,dv->btv", x, _head(params, cfg)), aux


def prefill_logits(params, cfg: ArchConfig, tokens, extra_embeds=None):
    """Inference prefill: hidden for all positions, head for the last one."""
    x, _ = forward_hidden(params, cfg, tokens, extra_embeds)
    return jnp.einsum("bd,dv->bv", x[:, -1], _head(params, cfg))


def lm_loss(params, cfg: ArchConfig, tokens, labels, extra_embeds=None,
            ce_chunk: int = 512) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE with a *chunked* softmax over T so the (B,T,V) logits
    never materialize (vocab up to 256k x 1M tokens would not fit).
    labels = next tokens (caller-shifted); negative labels are masked.
    The metrics carry the load-balance term ``aux`` and any counters of
    the blocks (``moe_routed_rows``).
    """
    x, stats = forward_hidden(params, cfg, tokens, extra_embeds)
    aux = stats.get("aux", 0.0)
    head = _head(params, cfg)
    B, T, d = x.shape
    c = min(ce_chunk, T)
    assert T % c == 0, (T, c)
    n = T // c
    xs = x.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_ce(carry, args):
        xc, lc = args
        logits = jnp.einsum("bcd,dv->bcv", xc, head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc.clip(0)[..., None],
                                 axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        tot, cnt = carry
        return (tot + jnp.sum((lse - ll) * mask), cnt + jnp.sum(mask)), None

    if cfg.unroll:
        carry = (jnp.zeros(()), jnp.zeros(()))
        for i in range(n):
            carry, _ = chunk_ce(carry, (xs[i], ls[i]))
        tot, cnt = carry
    else:
        (tot, cnt), _ = jax.lax.scan(chunk_ce, (0.0, 0.0), (xs, ls))
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce + aux
    return loss, {**stats, "ce": ce, "aux": aux}
