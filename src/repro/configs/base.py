"""Configuration system for the LBGM reproduction framework.

Every assigned architecture gets one module in this package exporting
``CONFIG``; the registry in ``__init__`` maps ``--arch`` ids to them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# canonical FL/LBGM knob container — LBGMConfig below is the arch-side
# *view* of it; shared defaults are read from FLConfig's fields so the two
# cannot drift (repro.fed.flconfig is pure-Python, safe to import here)
from repro.fed.flconfig import FLConfig

_FL_DEFAULTS = {f.name: f.default for f in dataclasses.fields(FLConfig)}


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0            # 0 => dense FFN
    top_k: int = 1
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01   # load-balance loss coefficient
    # "softmax": softmax top-k, capacity dispatch, load-balance loss
    # (Mixtral / Llama 4); "sigmoid": DeepSeek-V3's sigmoid scores, top-k
    # selected on score + bias (noaux_tc), dropless over the held experts
    scoring: str = "softmax"
    d_expert: int = 0               # expert width; 0 => ArchConfig.d_ff
    n_shared: int = 0               # shared experts: one SwiGLU n_shared x wide
    routed_scale: float = 1.0       # sigmoid: routed_scaling_factor
    # the chip's share under expert parallelism: experts
    # [held_offset, held_offset + held) live here (0 => all); the router
    # keeps all num_experts outputs and routes to absent experts add nothing
    held: int = 0
    held_offset: int = 0

    @property
    def n_held(self) -> int:
        return self.held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V3, no q LoRA, interleaved
    rotary); a ``kv_lora_rank`` of 0 turns it off."""
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclass(frozen=True)
class LBGMConfig:
    """Paper Algorithm 1 knobs — arch-side view of ``fed.flconfig.FLConfig``.

    The algorithmic defaults (threshold, sampling, enablement) are FLConfig's
    own; only the pod-scale execution defaults (``num_clients`` per
    ("pod","data") axes, single local step) differ for the big-model
    training path. Convert with :meth:`to_fl` / ``FLConfig.from_lbgm``.
    """
    enabled: bool = _FL_DEFAULTS["use_lbgm"]
    variant: str = "full"           # "full" | "topk" (compressed LBG, paper P3)
    delta_threshold: float = _FL_DEFAULTS["delta_threshold"]
    k_frac: float = 0.01            # for variant="topk": fraction of entries kept
    num_clients: int = 16           # client groups along the ("pod","data") axes
    local_steps: int = 1            # tau; >1 only supported in replicated mode
    sample_frac: float = _FL_DEFAULTS["sample_frac"]

    def to_fl(self, **overrides) -> FLConfig:
        """The canonical engine config carrying these knobs."""
        return FLConfig.from_lbgm(self, **overrides)


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                  # dense | moe | ssm | hybrid | audio | vlm
    source: str                     # citation bracket from the assignment
    n_layers: int = 2
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 2048
    vocab_size: int = 32768
    head_dim: int = 0               # 0 => d_model // n_heads
    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    # leading dense layers of a scanned MoE stack (DeepSeek's
    # first_k_dense_replace)
    first_dense: int = 0
    # block pattern: tuple cycled over layers. entries:
    #   "attn" (global), "swa" (sliding-window attn), "rwkv6", "rglru"
    block_pattern: Tuple[str, ...] = ("attn",)
    sliding_window: int = 8192      # used by "swa" blocks / long-context decode
    qk_norm: bool = False
    mrope: bool = False             # qwen2-vl multimodal rotary
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    encdec: bool = False            # whisper-style encoder-decoder
    n_encoder_layers: int = 0
    encoder_seq: int = 1500         # whisper stub frame count
    vision_tokens: int = 0          # qwen2-vl stub patch count (prepended)
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # distribution
    dp_mode: str = "replicated"     # "replicated" | "fsdp"
    remat: bool = True              # activation checkpointing per block
    lbgm: LBGMConfig = field(default_factory=LBGMConfig)
    # long-context decode policy: "swa" | "recurrent" | "skip" | "full"
    long_context: str = "swa"
    # unroll every lax.scan (layers, attention chunks, CE chunks, rwkv
    # chunk loop) — used by the dry-run cost pass because XLA cost analysis
    # counts while-loop bodies ONCE, not x trip count. Never for real runs.
    unroll: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % len(self.block_pattern)]

    def reduced(self, **overrides) -> "ArchConfig":
        """Reduced variant of the same family for CPU smoke tests."""
        small = dict(
            n_layers=2,
            d_model=min(self.d_model, 128),
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=min(self.d_ff, 256),
            vocab_size=min(self.vocab_size, 512),
            sliding_window=min(self.sliding_window, 32),
            encoder_seq=16 if self.encdec else self.encoder_seq,
            n_encoder_layers=2 if self.encdec else 0,
            vision_tokens=8 if self.vision_tokens else 0,
            dp_mode="replicated",
            remat=False,
            dtype="float32",
            mrope_sections=(4, 6, 6) if self.mrope else self.mrope_sections,
            lbgm=dataclasses.replace(self.lbgm, num_clients=4),
            first_dense=min(self.first_dense, 1),
        )
        if self.moe.num_experts:
            small["moe"] = dataclasses.replace(
                self.moe, num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2))
            if self.moe.d_expert:
                small["moe"] = dataclasses.replace(
                    small["moe"], d_expert=min(self.moe.d_expert, 64))
        if self.mla.kv_lora_rank:
            small["mla"] = dataclasses.replace(
                self.mla, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                v_dim=16)
        small.update(overrides)
        return dataclasses.replace(self, **small)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def _mla_param_count(cfg: ArchConfig) -> int:
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    r = m.kv_lora_rank
    return (d * H * (m.qk_nope_dim + m.qk_rope_dim)      # q
            + d * (r + m.qk_rope_dim) + r                 # kv_a + its norm
            + r * H * (m.qk_nope_dim + m.v_dim)           # kv_b
            + H * m.v_dim * d)                            # o


def is_moe_layer(cfg: ArchConfig, layer: int) -> bool:
    return (bool(cfg.moe.num_experts) and layer >= cfg.first_dense
            and cfg.block_kind(layer) in ("attn", "swa"))


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (embeddings + blocks + head)."""
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd = cfg.resolved_head_dim
    n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
    total = V * d                       # embed
    if not cfg.tie_embeddings:
        total += V * d                  # lm head
    for layer in range(cfg.n_layers):
        kind = cfg.block_kind(layer)
        if kind in ("attn", "swa") and cfg.mla.kv_lora_rank:
            total += _mla_param_count(cfg)
        elif kind in ("attn", "swa"):
            total += d * n_q * hd + 2 * d * n_kv * hd + n_q * hd * d
        elif kind == "rwkv6":
            # r,k,v,g,o projections + decay lora + mixing params
            total += 5 * d * d + 2 * d * 64 + 6 * d
        elif kind == "rglru":
            # conv4 + input/gate projections + recurrent params
            total += 4 * d + 2 * d * d + 3 * d
        if is_moe_layer(cfg, layer):
            fe = cfg.moe.d_expert or ff
            total += ((cfg.moe.n_held + cfg.moe.n_shared) * 3 * d * fe
                      + d * cfg.moe.num_experts)
        else:
            total += 3 * d * ff
        total += 2 * d                  # norms
    if cfg.encdec:
        # encoder layers: self attn + ffn
        total += cfg.n_encoder_layers * (
            d * n_q * hd + 2 * d * n_kv * hd + n_q * hd * d + 3 * d * ff + 2 * d)
        # decoder cross-attention
        total += cfg.n_layers * (d * n_q * hd + 2 * d * n_kv * hd + n_q * hd * d + d)
    return total


def active_param_count(cfg: ArchConfig) -> int:
    """Params active per token (MoE: only top_k experts count)."""
    if not cfg.moe.num_experts:
        return param_count(cfg)
    dense = param_count(cfg)
    d, m = cfg.d_model, cfg.moe
    fe = m.d_expert or cfg.d_ff
    moe_layers = sum(is_moe_layer(cfg, l) for l in range(cfg.n_layers))
    # of the held experts, a token meets top_k * held / num_experts
    idle = m.n_held * (1.0 - m.top_k / m.num_experts)
    return int(dense - moe_layers * idle * 3 * d * fe)
