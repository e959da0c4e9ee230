"""DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``): the plain
reference, the benchmark's weights, how a configuration file maps onto the
program's ``lm`` component, and the work the round requires.

The reference follows the published architecture (DeepseekV3ForCausalLM
with ``q_lora_rank`` null): RMSNorm pre-norm blocks; multi-head latent
attention (``q = x W_q`` split into a no-position and a rotary part per
head; ``[c_kv, k_pe] = x W_kv_a``, ``c_kv`` RMS-normed and expanded by
``W_kv_b`` into each head's key and value, ``k_pe`` one rotary head for
all; with ``rope_interleave`` the rotary parts are permuted from (pairs)
to (halves) and then rotated with ``rotate_half``; scores over
sqrt(qk_head_dim)); the first ``first_k_dense_replace`` layers a SwiGLU,
the rest a mixture of experts: sigmoid scores of all routed experts in
float32, the top ``num_experts_per_tok`` chosen on score plus the
selection bias (zero: its balancing update is not modelled), weights the
chosen scores over their sum (``norm_topk_prob``) times
``routed_scaling_factor``, plus the shared experts' SwiGLU. Then a final
RMSNorm and the untied output head.

The configuration holds the chip's share of each MoE layer: experts
``[held_expert_offset, + n_routed_experts)`` of the published count
(``published.n_routed_experts``), which the router scores all of. Routes
to the experts that are not held add nothing, here as in the program. Each
held expert is applied to every token and weighted by its route weight
(zero where the token does not route to it): no sort, no gather, no
kernel. Plain ``jax.numpy``; matmul precision from the caller's
``jax.default_matmul_precision``, dtype from the parameters.

Parameters are a flat dict under the program's names (``embed``,
``dense_<i>/<leaf>`` for the leading dense layers, ``blocks/<leaf>``
stacked over the MoE layers, ``final_norm``, ``lm_head``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_model(cfg: dict) -> dict:
    """``ComponentSpec("lm", kw)`` keywords that build this configuration.
    The program's DeepSeek-V3 block always normalises the top-k weights
    and interleaves the rotary pairs (both true in this family's
    configurations); the reference reads both keys."""
    return {"arch": "kanana-2-30b-a3b", "reduced": False,
            "n_layers": cfg["num_hidden_layers"],
            "first_dense": cfg["first_k_dense_replace"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "norm_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "tie_embeddings": cfg["tie_word_embeddings"],
            "dtype": cfg["dtype"],
            "mla": {"kv_lora_rank": cfg["kv_lora_rank"],
                    "qk_nope_dim": cfg["qk_nope_head_dim"],
                    "qk_rope_dim": cfg["qk_rope_head_dim"],
                    "v_dim": cfg["v_head_dim"]},
            "moe": {"num_experts": _router_width(cfg),
                    "top_k": cfg["num_experts_per_tok"],
                    "scoring": cfg["scoring_func"],
                    "d_expert": cfg["moe_intermediate_size"],
                    "n_shared": cfg["n_shared_experts"],
                    "routed_scale": cfg["routed_scaling_factor"],
                    "held": cfg["n_routed_experts"],
                    "held_offset": cfg["held_expert_offset"]}}


def _router_width(cfg: dict) -> int:
    """The router scores every published expert; ``n_routed_experts`` is
    the share held here."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def shapes(cfg: dict) -> dict:
    d, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    n_held, E = cfg["n_routed_experts"], _router_width(cfg)
    n_dense = cfg["first_k_dense_replace"]
    L = cfg["num_hidden_layers"] - n_dense

    def mla(lead):
        return {"norm1": lead + (d,),
                "mla/wq": lead + (d, H * (nope + rope)),
                "mla/wkv_a": lead + (d, r + rope),
                "mla/kv_norm": lead + (r,),
                "mla/wkv_b": lead + (r, H * (nope + vd)),
                "mla/wo": lead + (H * vd, d),
                "norm2": lead + (d,)}

    out = {"embed": (V, d)}
    for i in range(n_dense):
        blk = dict(mla(()), w_gate=(d, ff), w_up=(d, ff), w_down=(ff, d))
        out.update({f"dense_{i:02d}/{k}": v for k, v in blk.items()})
    blk = dict(mla((L,)), **{
        "moe/router": (L, d, E),
        "moe/w_gate": (L, n_held, d, fe), "moe/w_up": (L, n_held, d, fe),
        "moe/w_down": (L, n_held, fe, d),
        "moe/shared_gate": (L, d, fs), "moe/shared_up": (L, d, fs),
        "moe/shared_down": (L, fs, d)})
    out.update({f"blocks/{k}": v for k, v in blk.items()})
    out["final_norm"] = (d,)
    out["lm_head"] = (d, V)
    return out


def init(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Weights from ``key``: normal(0, initializer_range) matrices, unit
    norm scales. Trace under ``jax.jit``."""
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


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], -1)


def _rope(x, theta, interleave):
    """x (B, T, H, r): rotary embedding at positions 0..T-1, as
    ``apply_rotary_pos_emb_interleave`` where ``interleave``."""
    B, T, H, r = x.shape
    if interleave:
        x = x.reshape(B, T, H, r // 2, 2).swapaxes(-1, -2).reshape(
            B, T, H, r)
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    out = x32 * jnp.cos(emb) + _rotate_half(x32) * jnp.sin(emb)
    return out.astype(x.dtype)


def _swiglu(h, g, u, dn):
    return (jax.nn.silu(h @ g) * (h @ u)) @ dn


def _attention(p, h, cfg):
    B, T, _ = h.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    theta, inter = float(cfg["rope_theta"]), cfg["rope_interleave"]
    q = (h @ p["mla/wq"]).reshape(B, T, H, nope + rope)
    ckv = h @ p["mla/wkv_a"]
    c, k_pe = ckv[..., :r], ckv[..., r:].reshape(B, T, 1, rope)
    kv = (_rms(c, p["mla/kv_norm"], cfg["rms_norm_eps"]) @ p["mla/wkv_b"]
          ).reshape(B, T, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta, inter)],
                        -1)
    k_pe = jnp.broadcast_to(_rope(k_pe, theta, inter), (B, T, H, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(nope + rope))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, H * vd)
    return o @ p["mla/wo"]


def route_weights(p, h, cfg):
    """(B, T, held) weight of each held expert for each token: its route
    weight where the token routes to it, else 0."""
    k, E = cfg["num_experts_per_tok"], _router_width(cfg)
    off, n_held = cfg["held_expert_offset"], cfg["n_routed_experts"]
    s = jax.nn.sigmoid(h.astype(jnp.float32)
                       @ p["moe/router"].astype(jnp.float32))
    _, top = jax.lax.top_k(s + jnp.zeros((E,), jnp.float32), k)
    w = jnp.take_along_axis(s, top, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    hit = top[..., None] == (off + jnp.arange(n_held))
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), -2)


def _moe(p, h, cfg):
    w = route_weights(p, h, cfg).astype(h.dtype)
    out = _swiglu(h, p["moe/shared_gate"], p["moe/shared_up"],
                  p["moe/shared_down"])
    for e in range(cfg["n_routed_experts"]):
        y = _swiglu(h, p["moe/w_gate"][e], p["moe/w_up"][e],
                    p["moe/w_down"][e])
        out = out + w[..., e:e + 1] * y
    return out


def loss(params: dict, tokens: jax.Array, labels: jax.Array,
         cfg: dict) -> jax.Array:
    """Mean next-token cross-entropy of (B, T) ``tokens`` against
    ``labels`` (every label counts)."""
    eps = cfg["rms_norm_eps"]
    n_dense = cfg["first_k_dense_replace"]
    x = params["embed"][tokens]
    for l in range(cfg["num_hidden_layers"]):
        if l < n_dense:
            pre = f"dense_{l:02d}/"
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
        else:
            p = {k[len("blocks/"):]: v[l - n_dense]
                 for k, v in params.items() if k.startswith("blocks/")}
        x = x + _attention(p, _rms(x, p["norm1"], eps), cfg)
        h = _rms(x, p["norm2"], eps)
        if l < n_dense:
            x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        else:
            x = x + _moe(p, h, cfg)
    x = _rms(x, params["final_norm"], eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def batch_loss(params: dict, batch: dict, cfg: dict) -> jax.Array:
    return loss(params, batch["tokens"], batch["labels"], cfg)


def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _expert_evals(cfg: dict) -> float:
    """Held-expert evaluations a token needs on average: top_k x held /
    routed (0.375 at 6 x 8 / 128)."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / _router_width(cfg))


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward FLOPs of one sequence: 6 per active matmul
    parameter per token (the embedding lookup excluded, the output head
    and the router included; the held experts counted at
    ``_expert_evals`` evaluations a token) plus attention's
    6 * layers * seq * heads * (qk_head_dim + v_head_dim) per token."""
    T = traffic["data"]["kw"]["seq_len"]
    d, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    fe, L = cfg["moe_intermediate_size"], cfg["num_hidden_layers"]
    mla = (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
           + H * vd * d)
    dense = 3 * d * cfg["intermediate_size"]
    moe = (d * _router_width(cfg) + 3 * d * fe * cfg["n_shared_experts"]
           + 3 * d * fe * _expert_evals(cfg))
    matmul = (L * mla + cfg["first_k_dense_replace"] * dense
              + _moe_layers(cfg) * moe + d * V)
    return float(T * (6 * matmul + 6 * L * T * H * (nope + rope + vd)))

