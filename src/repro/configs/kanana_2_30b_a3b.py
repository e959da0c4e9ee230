"""kanana-2-30b-a3b [moe] — DeepSeek-V3 blocks: MLA, 128 sigmoid-routed
experts top-6 with 2 shared, one leading dense layer.
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601]

48L d_model=2048 32H MLA (no q LoRA, kv_lora_rank 512, qk 128+64, v 128,
interleaved RoPE) dense d_ff=6144, experts 128 x 768 top-6 (noaux_tc,
routed_scaling_factor 2.448), vocab=128256, untied head.
"""
from repro.configs.base import ArchConfig, LBGMConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="kanana-2-30b-a3b",
    arch_type="moe",
    source="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6144,
    vocab_size=128256,
    moe=MoEConfig(num_experts=128, top_k=6, scoring="sigmoid", d_expert=768,
                  n_shared=2, routed_scale=2.448),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_dim=128),
    first_dense=1,
    block_pattern=("attn",),
    rope_theta=1e6,
    norm_eps=1e-6,
    tie_embeddings=False,
    dp_mode="replicated",
    lbgm=LBGMConfig(variant="topk", k_frac=0.01, num_clients=16),
    long_context="skip",
)
