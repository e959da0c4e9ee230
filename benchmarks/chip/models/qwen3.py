"""Qwen3 dense decoder: the plain reference, the benchmark's weights, and
how a configuration file maps onto the program's ``lm`` component.

The reference follows the published architecture (Qwen3ForCausalLM):
RMSNorm pre-norm blocks, grouped-query attention with per-head RMSNorm on
q and k, rotary position embedding on halves (``rotate_half``), SwiGLU
feed-forward, a final RMSNorm and the output head (the embedding matrix
where ``tie_word_embeddings``). Plain ``jax.numpy`` with no scan, no
kernels and no chunking; matmul precision comes from the caller's
``jax.default_matmul_precision``, dtype from the parameters.

Parameters are a flat dict under the program's names (``embed``,
``blocks/<leaf>`` stacked over layers, ``final_norm``, ``lm_head`` when
untied): the names are the interface between the two sides, nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_model(cfg: dict) -> dict:
    """``ComponentSpec("lm", kw)`` keywords that build this configuration."""
    return {"arch": "qwen3-1.7b", "reduced": False,
            "n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "norm_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "tie_embeddings": cfg["tie_word_embeddings"],
            "dtype": cfg["dtype"]}


def shapes(cfg: dict) -> dict:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hd, nq, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"])
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed": (V, d),
           "blocks/norm1": (L, d),
           "blocks/wa_q": (L, d, nq * hd),
           "blocks/wa_k": (L, d, nkv * hd),
           "blocks/wa_v": (L, d, nkv * hd),
           "blocks/wa_o": (L, nq * hd, d),
           "blocks/q_norm": (L, hd),
           "blocks/k_norm": (L, hd),
           "blocks/norm2": (L, d),
           "blocks/w_gate": (L, d, ff),
           "blocks/w_up": (L, d, ff),
           "blocks/w_down": (L, ff, d),
           "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    return out


def init(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Weights from ``key``: normal(0, initializer_range) matrices, unit
    norm scales (the published initialisation). Trace under ``jax.jit``."""
    std = cfg["initializer_range"]
    names = sorted(shapes(cfg))
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {}
    for name, shape in shapes(cfg).items():
        if "norm" in name.split("/")[-1]:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (std * jax.random.normal(keys[name], shape,
                                                 jnp.float32)).astype(dtype)
    return out


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x (B, T, H, hd): rotate_half rotary embedding at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :hd // 2], x32[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def loss(params: dict, tokens: jax.Array, labels: jax.Array,
         cfg: dict) -> jax.Array:
    """Mean next-token cross-entropy of (B, T) ``tokens`` against
    ``labels`` (every label counts)."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    hd, nq, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"])
    B, T = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for l in range(cfg["num_hidden_layers"]):
        p = {k[len("blocks/"):]: v[l] for k, v in params.items()
             if k.startswith("blocks/")}
        h = _rms(x, p["norm1"], eps)
        q = (h @ p["wa_q"]).reshape(B, T, nq, hd)
        k = (h @ p["wa_k"]).reshape(B, T, nkv, hd)
        v = (h @ p["wa_v"]).reshape(B, T, nkv, hd)
        q = _rope(_rms(q, p["q_norm"], eps), theta)
        k = _rope(_rms(k, p["k_norm"], eps), theta)
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k
                       ).astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, nq * hd)
        x = x + o @ p["wa_o"]
        h = _rms(x, p["norm2"], eps)
        x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    x = _rms(x, params["final_norm"], eps)
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    logits = (x @ head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def batch_loss(params: dict, batch: dict, cfg: dict) -> jax.Array:
    return loss(params, batch["tokens"], batch["labels"], cfg)


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward FLOPs of one sequence: 6 per matmul parameter per
    token (the embedding lookup excluded, the output head included) plus
    attention's 12 * layers * seq * (heads * head_dim) per token."""
    T = traffic["data"]["kw"]["seq_len"]
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hd, nq, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"])
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    matmul = L * (2 * d * nq * hd + 2 * d * nkv * hd + 3 * d * ff) + d * V
    return float(T * (6 * matmul + 12 * L * T * nq * hd))
