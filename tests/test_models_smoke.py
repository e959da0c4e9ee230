"""Per-architecture smoke tests (required deliverable f): instantiate a
REDUCED variant of each assigned family (2 layers, d_model<=512, <=4
experts) and run one forward + one train step on CPU, asserting output
shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, param_count
from repro.models.frontends import make_stub_embeds
from repro.models.transformer import forward, init_lm
from repro.train import trainer as tr


# the reduced smokes of these archs each cost 12-19 s on CPU (wide vocab /
# recurrent scan compiles); they run in CI's slow job, keeping the default
# verify loop fast while every arch stays covered
_HEAVY_SMOKE = {"whisper-base", "recurrentgemma-2b", "mixtral-8x22b",
                "llama4-maverick-400b-a17b", "rwkv6-3b"}


@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY_SMOKE else a
    for a in ASSIGNED_ARCHS])
def test_reduced_forward_and_train_step(arch, key):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.moe.num_experts:
        assert cfg.moe.num_experts <= 4
    params, axes = init_lm(key, cfg)
    assert set(axes) == set(params)

    B, T = 2, 64
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    extra = make_stub_embeds(key, cfg, B)
    logits, aux = jax.jit(lambda p, t, e: forward(p, cfg, t, e))(
        params, toks, extra)
    assert logits.shape == (B, T, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    K = 2
    state, _ = tr.init_train_state(key, cfg, K)
    step = jax.jit(tr.make_train_step(cfg, K, lr=0.01))
    ktoks = jax.random.randint(key, (K, 2, T), 0, cfg.vocab_size)
    batch = {"tokens": ktoks, "labels": jnp.roll(ktoks, -1, axis=-1)}
    if extra is not None:
        batch["extra"] = jnp.broadcast_to(
            make_stub_embeds(key, cfg, 2)[None],
            (K, 2) + make_stub_embeds(key, cfg, 2).shape[1:])
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # params actually moved
    moved = any(
        bool(jnp.any(state2["params"][k] != state["params"][k]))
        for k in state["params"])
    assert moved


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "kanana-2-30b-a3b": (48, 2048, 32, 32, 6144, 128256),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    assert param_count(cfg) > 0


def test_moe_expert_counts():
    assert get_config("llama4-maverick-400b-a17b").moe.num_experts == 128
    assert get_config("llama4-maverick-400b-a17b").moe.top_k == 1
    assert get_config("mixtral-8x22b").moe.num_experts == 8
    assert get_config("mixtral-8x22b").moe.top_k == 2


def test_paper_small_models(key):
    from repro.models.smallnets import (apply_cnn, apply_fcn, classifier_loss,
                                        init_cnn, init_fcn)
    x = jax.random.normal(key, (4, 28, 28, 1))
    y = jnp.asarray([0, 1, 2, 3])
    for init, apply, name in ((init_cnn, apply_cnn, "paper-cnn"),
                              (init_fcn, apply_fcn, "paper-fcn")):
        cfg = get_config(name)
        params, _ = init(key, cfg)
        loss, m = classifier_loss(apply, params, cfg, x, y)
        assert bool(jnp.isfinite(loss)) and 0.0 <= float(m["acc"]) <= 1.0
