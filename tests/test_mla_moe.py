"""DeepSeek-V3 blocks (latent attention, sigmoid-routed dropless experts
with shared experts, leading dense layers) against the benchmark's plain
reference, at a small size on the CPU with seeded random weights.

* the program's ``lm_loss`` and its gradients equal the reference's
  (``benchmarks/chip/models/deepseek_v3.py``);
* interleaved RoPE equals the pairwise rotation it stands for;
* the held-expert shares of one layer, summed over every offset with the
  shared experts counted once, give the uncut layer;
* a batch that routes every token to one held expert loses none of them;
* the held-expert layer under ``vmap`` (the schedulers' client axis)
  equals one call per client, values and gradients;
* through ``FLEngine``: a held expert that no token reaches gets exact
  zeros and comes through the decision, the int8 codec and the fold
  unchanged; ``moe_routed_rows`` counts every route when all experts are
  held; the model's named scopes reach the compiled round.
"""
import dataclasses
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.fed import spans
from repro.fed.experiment import ExperimentSpec, build_experiment
from repro.models import moe as moe_lib
from repro.models.attention import rope_rotate
from repro.models.common import ParamStore
from repro.models.mla import deinterleave
from repro.models.transformer import lm_loss

_REF = Path(__file__).resolve().parents[1] / "benchmarks/chip/models"
_spec = importlib.util.spec_from_file_location(
    "bench_deepseek_v3", _REF / "deepseek_v3.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: a DeepSeek-V3 configuration file at a size the CPU holds: 4 of 8
#: routed experts held (offset 2), top-3, 2 shared, one dense layer
TINY = {"family": "deepseek_v3", "component": "lm",
        "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_interleave": True,
        "moe_intermediate_size": 24, "n_routed_experts": 4,
        "held_expert_offset": 2, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 3, "n_shared_experts": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "first_k_dense_replace": 1,
        "num_hidden_layers": 3, "vocab_size": 96, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "tie_word_embeddings": False,
        "initializer_range": 0.02, "dtype": "float32"}


def program(cfg: dict, **fl):
    """The engine the ``lm`` component builds for ``cfg``, with the
    reference's weights in place of its own."""
    spec = {"name": "mla-moe",
            "model": {"name": "lm", "kw": ref.program_model(cfg)},
            "data": {"name": "markov",
                     "kw": {"n": 16, "n_eval": 0, "seq_len": 16,
                            "vocab": cfg["vocab_size"]}},
            "partition": {"name": "iid", "kw": {}},
            "fl": dict({"num_clients": 2, "tau": 2, "lr": 0.5,
                        "batch_size": 2, "use_lbgm": True,
                        "delta_threshold": 0.5, "lbg_variant": "topk",
                        "lbg_kw": {"k_frac": 0.05}, "scheduler": "chunked",
                        "chunk_size": 2, "codec": "int8", "seed": 0}, **fl),
            "rounds": 1, "eval": {"every": 0, "final": False}}
    engine, _ = build_experiment(ExperimentSpec.from_dict(spec))
    params = ref.init(cfg, jax.random.PRNGKey(7))
    assert {k: v.shape for k, v in engine.params.items()} == {
        k: v.shape for k, v in params.items()}
    engine.params = params
    return engine


def arch(cfg: dict):
    """The program's ArchConfig for ``cfg``."""
    kw = ref.program_model(cfg)
    base = get_config(kw.pop("arch"))
    kw.pop("reduced")
    kw["moe"] = dataclasses.replace(base.moe, **kw["moe"])
    kw["mla"] = dataclasses.replace(base.mla, **kw["mla"])
    return dataclasses.replace(base, **kw)


def test_loss_and_grads_match_reference():
    """Float32 on the CPU, where both sides compute every matmul in full
    float32. The program weights the hidden units before the experts'
    down projection and sums over experts inside one einsum, chunks
    the cross-entropy and sums in other orders than the reference's dense
    per-expert loop, so the two agree to float32 rounding of sums over
    the model's widths: 1e-5 relative on the loss, and on each gradient
    leaf 1e-4 of its largest entry (a few hundred float32 ulps)."""
    cat = arch(TINY)
    params = ref.init(TINY, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              TINY["vocab_size"])
    labels = jnp.roll(toks, -1, -1)
    (l, aux), g = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, cat, toks, labels), has_aux=True))(params)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks, labels, TINY)))(params)
    np.testing.assert_allclose(float(l), float(lr), rtol=1e-5)
    for k in gr:
        scale = float(jnp.max(jnp.abs(gr[k])))
        assert scale > 0, k
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                   rtol=0, atol=1e-4 * scale, err_msg=k)
    # 3 of 8 experts a token, half of them held: about 3/2 routes a token
    rows = float(aux[spans.MOE_ROUTED_ROWS])
    assert 0 < rows <= 2 * 16 * 3 * 2


def test_interleaved_rope_is_pairwise_rotation():
    """Permute the (even, odd) pairs into halves, then ``rotate_half``:
    pair i is rotated by position x theta^(-2i/r), as the interleaved form
    states. Exact up to float32 rounding of the same products."""
    B, T, H, r, theta = 2, 8, 3, 8, 1e4
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, r))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    got = rope_rotate(deinterleave(x), pos, theta)
    inv = 1.0 / theta ** (jnp.arange(0, r, 2) / r)
    ang = jnp.arange(T)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    ev, od = x[..., 0::2], x[..., 1::2]
    want = jnp.concatenate([ev * c - od * s, ev * s + od * c], -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(deinterleave(jnp.arange(8.0))),
        [0, 2, 4, 6, 1, 3, 5, 7])


def _layer(held=0, offset=0, E=8, k=3, seed=0):
    cfg = dataclasses.replace(
        get_config("kanana-2-30b-a3b").reduced(), d_model=32,
        moe=dataclasses.replace(get_config("kanana-2-30b-a3b").moe,
                                num_experts=E, top_k=k, d_expert=16,
                                held=held, held_offset=offset))
    store = ParamStore(jax.random.PRNGKey(seed), jnp.float32)
    moe_lib.init_moe(store, "moe", cfg)
    return cfg, {n[len("moe/"):]: v for n, v in store.params.items()}


def test_held_shares_sum_to_uncut_layer():
    """Expert parallelism over 4 chips of 2 experts: the shares' outputs,
    with the shared experts (which every chip computes) counted once, add
    up to the uncut layer, and their routed rows to every route."""
    cfg, p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    whole, rows = moe_lib.apply_dropless(p, x, cfg)
    shared = moe_lib.swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
    total, total_rows = -3 * shared, 0.0
    for off in range(0, 8, 2):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=2, held_offset=off))
        share = dict(p, **{n: p[n][off:off + 2]
                           for n in ("w_gate", "w_up", "w_down")})
        out, r = moe_lib.apply_dropless(share, x, c)
        total, total_rows = total + out, total_rows + float(r)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert float(rows) == total_rows == 2 * 12 * 3


def test_one_expert_takes_every_token_without_drops():
    """A router that sends every token to held expert 1 first: all B*T of
    its rows are computed (a capacity router would drop most), equal to
    the dense per-expert reference."""
    cfg, p = _layer(held=4, offset=0, k=2)
    bias = jnp.zeros((32, 8)).at[:, 1].set(1.0)
    p = dict(p, router=p["router"] + bias)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))) + 1.0
    top_e, _ = moe_lib.route_sigmoid(x.reshape(-1, 32), p["router"], cfg)
    assert bool(jnp.all(top_e[:, 0] == 1))
    out, rows = moe_lib.apply_dropless(p, x, cfg)
    tiny = {"num_experts_per_tok": 2, "n_routed_experts": 4,
            "held_expert_offset": 0, "published": {"n_routed_experts": 8},
            "norm_topk_prob": True, "routed_scaling_factor": 2.448}
    rp = {"moe/" + n: v for n, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want = ref._moe(rp, x, tiny)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(rows) >= 2 * 16


def test_held_experts_under_vmap_equal_one_call_each():
    """Two clients with their own weights and tokens, as the schedulers
    vmap them: the layer's values and gradients are each client's own."""
    cfg, p = _layer(held=4, offset=2)
    p2 = jax.tree.map(lambda a: jnp.stack([a, 1.01 * a]), p)
    x2 = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 12, 32))

    def loss(p, x):
        out, rows = moe_lib.apply_dropless(p, x, cfg)
        return jnp.sum(out ** 2), rows

    fn = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (vl, vrows), vg = jax.vmap(fn)(p2, x2)
    for i in range(2):
        (l, rows), g = fn(jax.tree.map(lambda a: a[i], p2), x2[i])
        np.testing.assert_allclose(float(vl[i]), float(l), rtol=1e-6)
        assert float(vrows[i]) == float(rows)
        for a, b in zip(jax.tree.leaves(vg), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a[i]), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_unreached_expert_gets_exact_zeros_through_the_round():
    """A zero router scores every expert 1/2, and top-k keeps the lowest
    indices on ties: every token routes to experts 0-2, so of the held
    experts 2-5 only the first is reached. Each client's gradient is
    exactly zero on the other three, and after a round of top-k
    decisions, int8 encoding and the fold their weights are bit for bit
    the ones they started with, while the reached expert moved. One
    local step: a second would see the router the first moved."""
    engine = program(TINY, tau=1)
    engine.params = dict(engine.params, **{
        "blocks/moe/router": jnp.zeros_like(
            engine.params["blocks/moe/router"])})
    before = jax.device_get(engine.params)
    rng = np.random.RandomState(0)
    batch = engine._sample_batches(rng)
    client = jax.jit(engine._make_client_update())
    one = {k: v[0, 0] for k, v in batch.items() if not k.startswith("_")}
    asg, stats = client(engine.params, one)
    assert float(stats[spans.MOE_ROUTED_ROWS]) == 2 * 16 * 2
    for n in ("w_gate", "w_up", "w_down"):
        g = np.asarray(asg[f"blocks/moe/{n}"])
        assert not np.any(g[:, 1:]), n
        assert np.any(g[:, 0]), n
    m = engine.run_round(rng)
    assert np.isfinite(m["loss"]) and m["wire_bytes"] > 0
    after = jax.device_get(engine.params)
    for n in ("w_gate", "w_up", "w_down"):
        k = f"blocks/moe/{n}"
        np.testing.assert_array_equal(after[k][:, 1:], before[k][:, 1:])
        assert np.any(after[k][:, 0] != before[k][:, 0])


def test_routed_rows_counter_counts_every_route_when_all_held():
    """All 8 experts held: every token's top_k routes reach this chip, so
    the round's counter is clients x steps x batch x seq x top_k x MoE
    layers, on each scheduler."""
    cfg = dict(TINY, n_routed_experts=8, held_expert_offset=0)
    want = 2 * 2 * 2 * 16 * 3 * 2
    for sched in ("chunked", "vmap"):
        engine = program(cfg, scheduler=sched)
        m = engine.run_round(np.random.RandomState(0))
        assert m[spans.MOE_ROUTED_ROWS] == want, sched


def test_model_scopes_in_compiled_round():
    engine = program(TINY)
    rng = np.random.RandomState(0)
    batch = engine._sample_batches(rng)
    mask = jnp.asarray(engine._sample_mask(rng), jnp.float32)
    text = engine._round.lower(engine.params, engine.lbg, engine.residual,
                               batch, mask).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    segs = {s for n in names for s in re.findall(r"\bfl\.\w+", n)}
    assert set(spans.MODEL_SCOPES) | {spans.CLIENT_SGD} <= segs


@pytest.mark.parametrize("held,offset", [(4, 2), (8, 0)])
def test_param_count_matches_init(held, offset):
    cfg = dict(TINY, n_routed_experts=held, held_expert_offset=offset)
    from repro.configs import param_count
    from repro.models.transformer import init_lm
    params, axes = init_lm(jax.random.PRNGKey(0), arch(cfg))
    assert set(axes) == set(params)
    # param_count leaves out the final norm
    assert param_count(arch(cfg)) + cfg["hidden_size"] == sum(
        v.size for v in params.values())
