"""Infrastructure: checkpointing, sharding rules, roofline parser, PCA,
optimizers, data, specs."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis.pca import GradientSpaceTracker, cosine_matrix, n_pca
from repro.analysis.roofline import (RooflineReport, build_report,
                                     collective_bytes)
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import INPUT_SHAPES, get_config
from repro.data.synthetic import linear_regression, markov_lm
from repro.optim import adam_init, adam_update, sgd_init, sgd_update
from repro.optim.schedules import cosine, make_schedule
from repro.train import sharding as shd


# ------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": np.arange(6.0).reshape(2, 3),
                        "nested": {"b": np.ones(4, np.float32)}},
             "step": np.asarray(7)}
    path = os.path.join(tmp_path, "ck.npz")
    save_checkpoint(path, state, {"arch": "test"})
    loaded, meta = load_checkpoint(path)
    assert meta["arch"] == "test"
    np.testing.assert_allclose(loaded["params"]["w"], state["params"]["w"])
    np.testing.assert_allclose(loaded["params"]["nested"]["b"],
                               state["params"]["nested"]["b"])
    assert int(loaded["step"]) == 7


# ---------------------------------------------------------- compile cache

def _cache_updates(monkeypatch):
    """Record the config updates enable_compile_cache makes (and keep them
    out of this process's JAX config)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    return calls


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch):
    from repro.compile_cache import compile_cache_dir, enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/shared/cache")
    calls = _cache_updates(monkeypatch)
    assert compile_cache_dir() is None
    assert enable_compile_cache() is None
    assert calls == []


def test_compile_cache_fixed_dir_under_checkout(monkeypatch):
    from repro.compile_cache import compile_cache_dir, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _cache_updates(monkeypatch)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(os.path.realpath(checkout), ".jax_cache")
    assert compile_cache_dir() == want
    assert enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    # the same path on every call: no pid, time or temp directory in it
    assert compile_cache_dir() == want
    assert os.path.isdir(os.path.join(os.path.dirname(want), "src", "repro"))


# ------------------------------------------------------------- sharding

MESH = AbstractMesh((16, 16), ("data", "model"))


def test_param_pspec_rules():
    assert shd.param_pspec(("embed", "ff"), (512, 2048), "replicated",
                           MESH) == P(None, "model")
    assert shd.param_pspec(("embed", "ff"), (512, 2048), "fsdp",
                           MESH) == P("data", "model")
    # non-divisible dims stay unsharded
    assert shd.param_pspec(("embed", "ff"), (500, 2048), "fsdp",
                           MESH) == P(None, "model")
    assert shd.param_pspec(("vocab", "embed"), (32768, 512), "replicated",
                           MESH) == P("model", None)
    # one mesh axis never used twice
    spec = shd.param_pspec(("ff", "vocab"), (2048, 32768), "replicated", MESH)
    assert list(spec).count("model") == 1


def test_cache_pspec_prefers_kv_heads_then_head_dim():
    # kv=16 divisible => heads take the model axis
    s = shd.cache_pspec(("batch", "cache", "kv_heads", "head_dim"),
                        (128, 4096, 16, 128), MESH)
    assert s == P(("data",), None, "model", None)
    # kv=8 not divisible by 16 => head_dim takes it (distributed decode)
    s = shd.cache_pspec(("batch", "cache", "kv_heads", "head_dim"),
                        (128, 4096, 8, 128), MESH)
    assert s == P(("data",), None, None, "model")
    # batch=1 cannot shard
    s = shd.cache_pspec(("batch", "cache", "kv_heads", "head_dim"),
                        (1, 4096, 8, 128), MESH)
    assert s[0] is None


# ------------------------------------------------------------- roofline

HLO_SNIPPET = """
HloModule test
ENTRY %main {
  %ar = f32[1024,256]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256]
  %ag = bf16[512,128]{1,0} all-gather(%y), replica_groups=[16,16]<=[16,16]T(1,0)
  %rs = f32[64]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}
  %cp = f32[32,32]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
}
"""


def test_collective_bytes_parser():
    out = collective_bytes(HLO_SNIPPET)
    n = 16
    ar = 1024 * 256 * 4
    assert out["all-reduce"] == pytest.approx(2 * ar * (n - 1) / n)
    ag = 512 * 128 * 2
    assert out["all-gather"] == pytest.approx(ag * (n - 1) / n)
    assert out["reduce-scatter"] == pytest.approx(64 * 4 * 3)
    assert out["collective-permute"] == pytest.approx(32 * 32 * 4)
    assert out["count"] == 4


def test_roofline_report_terms():
    rep = build_report("a", "s", "m", 256, {"flops": 197e12,
                                            "bytes accessed": 819e9},
                       HLO_SNIPPET, model_flops_global=197e12 * 256 * 0.5)
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(1.0)
    assert rep.dominant in ("compute", "memory")
    assert rep.useful_flops_ratio == pytest.approx(0.5)


# ------------------------------------------------------------- pca

def test_npca_detects_low_rank():
    rng = np.random.RandomState(0)
    basis = rng.randn(3, 64)
    grads = rng.randn(40, 3) @ basis  # rank 3 exactly
    assert n_pca(grads, 0.99) <= 3
    tr = GradientSpaceTracker()
    for g in grads[:10]:
        tr.add({"w": jnp.asarray(g)})
    s = tr.summary()
    assert s["n99_final"] <= 3 and s["epochs"] == 10
    hm_pgd, hm_self = tr.heatmaps()
    assert hm_self.shape == (10, 10)
    np.testing.assert_allclose(np.diag(hm_self), 1.0, atol=1e-5)


# ------------------------------------------------------------- optim/data

def test_sgd_momentum_and_adam(key):
    params = {"w": jnp.ones(4)}
    grads = {"w": jnp.full(4, 0.5)}
    p1, _ = sgd_update(params, grads, sgd_init(params), lr=0.1)
    np.testing.assert_allclose(p1["w"], 0.95)
    st = sgd_init(params, momentum=0.9)
    p2, st = sgd_update(params, grads, st, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(p2["w"], 0.95)
    ast = adam_init(params)
    p3, ast = adam_update(params, grads, ast, lr=0.1)
    assert float(p3["w"][0]) < 1.0


def test_schedules():
    f = cosine(1.0, 100, warmup=10)
    assert float(f(0)) == 0.0
    assert float(f(10)) == pytest.approx(1.0)
    assert float(f(100)) == pytest.approx(0.0, abs=1e-6)
    g = make_schedule("corollary1", 0.0, 100, tau=4)
    assert float(g(0)) == pytest.approx(1 / (4 * 100) ** 0.5)


def test_markov_lm_learnable_structure():
    x, y = markov_lm(4, 32, vocab=64, seed=0)
    assert x.shape == (4, 32) and np.all(x[:, 1:] == y[:, :-1])


# ------------------------------------------------------------- specs

def test_abstract_specs_no_allocation():
    from repro.launch import specs as sp
    cfg = get_config("qwen3-1.7b")
    sds, axes = sp.abstract_params(cfg)
    assert all(isinstance(v, jax.ShapeDtypeStruct) for v in sds.values())
    assert set(axes) == set(sds)
    st, sa = sp.abstract_decode_state(cfg, 8, 1024)
    assert isinstance(st["pos"], jax.ShapeDtypeStruct)
    b = sp.train_batch_specs(cfg, INPUT_SHAPES["train_4k"], 16)
    assert b["tokens"].shape == (16, 16, 4096)
