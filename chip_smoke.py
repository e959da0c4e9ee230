#!/usr/bin/env python3
"""Smoke test of the LBGM federated round on a TPU, through the normal
entry points (ExperimentSpec -> run_experiment -> FLEngine).

    python chip_smoke.py             # one chip: phases kernels, fcn, lm
    python chip_smoke.py --chips 4   # four chips: phase lm-mesh only
    python chip_smoke.py --only kernels   # one one-chip phase

Phases (one process; each prints its own lines):

* ``kernels`` — the Pallas kernels of the main path at the LM's leaf
  shapes against their XLA references (``kernels/ref.py``), each run on
  the chip; the quantized fold with both paths' times.
* ``fcn`` — the paper FCN at its published widths on mixture data with a
  label-skew split, default vmap scheduler + dense LBG store (the batched
  projection kernel).
* ``lm`` — qwen3-1.7b at published widths, cut to 4 layers and 1/8 of the
  vocabulary, on markov data: 8 clients, chunked scheduler, top-k store,
  int8 wire codec (the batched sparse-decision kernel).
* ``moe`` — the held-expert layer of ``kanana-2-30b-a3b`` at the
  benchmark cell's widths and batch (8 of 128 experts, top-6, shared
  experts, 4 x 256 tokens): the program's masked einsum over the held
  experts against the plain per-expert reference
  (``benchmarks/chip/models/deepseek_v3.py``), forward and gradients,
  with the program's forward + backward time.
* ``lm-mesh`` (``--chips 4``) — the ``lm`` round on the sharded scheduler
  with the mesh-aware top-k store, on a [4, 1] client mesh and on a
  [2, 2] mesh with tensor-parallel client compute
  (``model_sharding="auto"``), each against the chunked run on one chip.

Every one-chip round phase runs its round with ``fused_kernels=None`` (auto: the
kernels compile on a TPU) and again with ``fused_kernels=False`` (plain
XLA), asserts agreement, and asserts that the compiled round's HLO calls
each kernel of its path (``tpu_custom_call``). Times are printed for
information only. The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``; any failure raises and exits non-zero.
The script refuses to run anywhere but a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ROUNDS = 3
#: loss agreement across execution paths (fp32, different reduction orders)
RTOL = 1e-4

LM_MODEL = {"arch": "qwen3-1.7b", "reduced": False, "n_layers": 4,
            "vocab_size": 18992, "dtype": "float32"}
LM_SEQ = 256
#: clients per scan step on one chip: the benchmark's round at chunk 2
#: (the same model and traffic) compiles to 11.5 GiB for a v5e (1.04 GiB
#: arguments + 1.04 GiB outputs - 0.14 GiB aliased + 9.6 GiB temporaries,
#: XLA memory_analysis), about 4 GiB more per extra client
LM_CHUNK = 2


def fcn_spec(fused):
    from repro.fed import ComponentSpec, EvalPolicy, ExperimentSpec, FLConfig
    return ExperimentSpec(
        name="smoke-fcn", model=ComponentSpec("fcn"),
        data=ComponentSpec("mixture", {"n": 2000, "n_eval": 0}),
        partition=ComponentSpec("label_skew", {"classes_per_client": 3}),
        fl=FLConfig(num_clients=20, tau=2, lr=0.05, batch_size=16,
                    use_lbgm=True, delta_threshold=0.2,
                    fused_kernels=fused),
        rounds=ROUNDS, eval=EvalPolicy(every=0, final=False))


def lm_spec(fused, codec="int8", **fl):
    from repro.fed import ComponentSpec, EvalPolicy, ExperimentSpec, FLConfig
    base = dict(num_clients=8, tau=2, lr=0.02, batch_size=4, use_lbgm=True,
                delta_threshold=0.5, lbg_variant="topk",
                lbg_kw={"k_frac": 0.01}, scheduler="chunked",
                chunk_size=LM_CHUNK, codec=codec, fused_kernels=fused)
    base.update(fl)
    return ExperimentSpec(
        name="smoke-lm", model=ComponentSpec("lm", dict(LM_MODEL)),
        data=ComponentSpec("markov", {"n": 256, "n_eval": 0,
                                      "seq_len": LM_SEQ,
                                      "vocab": LM_MODEL["vocab_size"]}),
        partition=ComponentSpec("iid"),
        fl=FLConfig(**base), rounds=ROUNDS,
        eval=EvalPolicy(every=0, final=False))


def round_kernels(engine):
    """Names of the Pallas kernels the engine's compiled round calls."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    batch = engine._sample_batches(rng)
    mask = jnp.asarray(engine._sample_mask(rng), jnp.float32)
    hlo = engine._round.lower(engine.params, engine.lbg, engine.residual,
                              batch, mask).compile().as_text()
    calls = [l for l in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    return sorted({k for k in ("lbgm_projection", "lbgm_sparse_decision",
                               "lbgm_dequant_fold")
                   if any(k in l for l in calls)})


def run_arm(phase, arm, spec, inspect=None):
    """run_experiment(spec), then the compiled round's kernels (a second
    build of the same program, served from the compile cache)."""
    import numpy as np
    from repro.fed import run_experiment
    from repro.fed.experiment import build_experiment
    t0 = time.time()
    res = run_experiment(spec)
    wall = time.time() - t0
    engine, _ = build_experiment(spec)
    kernels = round_kernels(engine)
    extra = inspect(engine) if inspect else {}
    del engine
    gc.collect()
    rec = res.records
    out = {"phase": phase, "arm": arm,
           "fused_kernels": spec.fl.fused_kernels, "codec": spec.fl.codec,
           "scheduler": spec.fl.scheduler, "mesh": spec.fl.mesh,
           "model_sharding": spec.fl.model_sharding,
           "loss": [r.loss for r in rec],
           "uplink_floats": [r.uplink_floats for r in rec],
           "frac_scalar": [r.frac_scalar for r in rec],
           "wire_bytes": [r.wire_bytes for r in rec],
           "kernels": kernels,
           "ms_per_round_incl_compile": res.us_per_round / 1e3,
           "wall_s": wall, **extra}
    print(json.dumps(out), flush=True)
    assert all(np.isfinite(out["loss"])), out
    return out


def agree(a, b, *, all_rounds):
    """Round 1 enters with identical params: equal decisions and
    accounting, fp32-equal loss. Later rounds are compared while the
    decisions so far agree (``all_rounds``)."""
    import numpy as np
    tag = f"{a['arm']} vs {b['arm']}"
    assert a["uplink_floats"][0] == b["uplink_floats"][0], tag
    assert a["frac_scalar"][0] == b["frac_scalar"][0], tag
    n = 1
    if all_rounds:
        while (n < len(a["loss"])
               and a["uplink_floats"][:n] == b["uplink_floats"][:n]
               and a["frac_scalar"][:n] == b["frac_scalar"][:n]):
            n += 1
    np.testing.assert_allclose(a["loss"][:n], b["loss"][:n], rtol=RTOL,
                               err_msg=tag)
    print(f"  agree[{tag}]: round-1 uplink/frac equal, loss equal at "
          f"rtol={RTOL} over {n} round(s)", flush=True)


# ------------------------------------------------------------------ phases

def phase_kernels():
    """Each kernel of the main path on the chip against its reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.lbgm_projection import lbgm_projection_batched_pallas
    from repro.kernels.lbgm_sparse import lbgm_sparse_decision_batched_pallas
    key = jax.random.PRNGKey(0)
    # decision: a stacked 2048x6144 leaf's row group, a norm leaf, one
    # model rank's rows of a 208-row leaf, the embedding, and one tile
    # with kb close to the block; normal data, then data whose |values|
    # are subnormal (and some -0.0). Values and gathers must match the
    # reference bit for bit; the XLA reference runs on the host CPU, where
    # nothing flushes subnormals.
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    bits = lambda a: np.asarray(a).view(np.int32)
    for B, nb, block, kb in ((2, 192, 65536, 655), (2, 1, 2048, 20),
                             (2, 52, 65536, 655), (2, 608, 65536, 639),
                             (2, 8, 256, 250)):
        for data in ("normal", "subnormal"):
            # made on the host: a TPU may flush subnormal products
            g = rng.standard_normal((B, nb, block), np.float32)
            if data == "subnormal":
                g = g * np.float32(1e-40)
                g = np.where(np.abs(g) < 2e-41, np.float32(-0.0), g)
            idx = rng.integers(0, block, (B, nb, kb), np.int32)
            t0 = time.time()
            gg, gath, ti, tv = jax.block_until_ready(
                lbgm_sparse_decision_batched_pallas(g, idx))
            dt = time.time() - t0
            for b in range(B):
                rgg, rgath, rti, rtv = ref.lbgm_sparse_decision_ref(
                    jax.device_put(g[b], cpu), jax.device_put(idx[b], cpu))
                np.testing.assert_allclose(float(gg[b]), float(rgg),
                                           rtol=1e-5)
                np.testing.assert_array_equal(bits(gath[b]), bits(rgath))
                si, sv = ref.sort_topk_rows(np.asarray(ti[b]),
                                            np.asarray(tv[b]))
                ri, rv = ref.sort_topk_rows(np.asarray(rti), np.asarray(rtv))
                np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
                np.testing.assert_array_equal(bits(sv), bits(rv))
            print(json.dumps({"phase": "kernels",
                              "kernel": "lbgm_sparse_decision",
                              "shape": [B, nb, block, kb], "data": data,
                              "bit_exact": True, "first_call_s": dt}),
                  flush=True)
    # projection at the FCN's leaf sizes (784x200, 200, 200x10, 10)
    for n in (156800, 200, 2000, 10):
        g = jax.random.normal(key, (4, n))
        l = jax.random.normal(jax.random.fold_in(key, 2), (4, n))
        gl, gg, ll = lbgm_projection_batched_pallas(g, l)
        for b in range(4):
            want = ref.lbgm_projection_ref(g[b], l[b])
            np.testing.assert_allclose(
                np.array([gl[b], gg[b], ll[b]]), np.asarray(want),
                rtol=1e-4, atol=1e-3)
        print(json.dumps({"phase": "kernels", "kernel": "lbgm_projection",
                          "shape": [4, n], "rtol": 1e-4}), flush=True)
    phase_fold()


def phase_fold():
    """The quantized fold kernel against the XLA gather-scatter fold it
    replaced (``ref.lbgm_dequant_accum_ref``), both on the chip, at the
    int8 cell's 2048x6144 leaf (chunk 2) and a kanana-2-30b-a3b-5l expert
    leaf (4 x 8 x 2048 x 768, chunk 1): NaN positions equal, values
    within one rounding, and the bits that differ (with the largest ulp
    gap) and each path's time for one chunk are printed. int8: the chunk's clients, all real;
    fp8: a phantom client first (w = 0, NaN gscale and values), and a real
    client sends two NaNs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm.wire import Fp8Codec, Int8Codec
    from repro.kernels import ref
    from repro.kernels.lbgm_sparse import (fold_carry_shape,
                                           lbgm_dequant_fold_pallas)
    rng = np.random.default_rng(1)
    xla = jax.jit(ref.lbgm_dequant_accum_ref)
    for C, nb, block, kb in ((2, 192, 65536, 655), (1, 768, 65536, 655)):
        acc = rng.standard_normal((nb, block), np.float32)
        # the block is a whole number of tiles: the carry is a reshape
        carry = jnp.asarray(acc.reshape(fold_carry_shape(nb, block)))
        for codec in (Int8Codec(stochastic=False),
                      Fp8Codec(stochastic=False)):
            n = C + (codec.name == "fp8")
            idx = np.stack([np.stack([rng.choice(block, kb, replace=False)
                                      for _ in range(nb)])
                            for _ in range(n)]).astype(np.int32)
            qv, scale = jax.vmap(lambda v: codec.quantize(v, None))(
                jnp.asarray(rng.standard_normal((n, nb, kb), np.float32)))
            w = rng.random(n).astype(np.float32) + 0.5
            gs = rng.random(n).astype(np.float32)
            if n > C:
                w[0], gs[0] = 0.0, np.nan
                qv = qv.at[0].set(jnp.nan).at[n - 1, 0, 1].set(jnp.nan)
                qv = qv.at[n - 1, nb - 1, kb - 1].set(jnp.nan)
            rest = (jnp.asarray(w), jnp.asarray(gs), jnp.asarray(idx), qv,
                    scale)
            got = np.asarray(lbgm_dequant_fold_pallas(carry, *rest)
                             ).reshape(nb, block)
            want = np.asarray(xla(jnp.asarray(acc), *rest))
            nan = np.isnan(want)
            np.testing.assert_array_equal(np.isnan(got), nan)
            # bits are reported, not required: a backend that fuses
            # acc + coeff * q into one multiply-add in one path only rounds
            # once there, off by one rounding of a sum below 8 in magnitude
            np.testing.assert_allclose(got[~nan], want[~nan], rtol=1e-6,
                                       atol=1e-6)
            ulp = np.abs(got[~nan].view(np.int32).astype(np.int64)
                         - want[~nan].view(np.int32).astype(np.int64))
            print(json.dumps({
                "phase": "kernels", "kernel": "lbgm_dequant_fold",
                "codec": codec.name, "shape": [n, nb, block, kb],
                "nan": int(nan.sum()), "bit_exact": bool(ulp.max() == 0),
                "max_ulp": int(ulp.max()),
                "positions_off": int((ulp > 0).sum()),
                "kernel_ms": _ms(lbgm_dequant_fold_pallas, carry, *rest),
                "xla_ms": _ms(xla, jnp.asarray(acc), *rest)}), flush=True)


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _ms(fn, *args, n=10):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.time() - t) / n


#: the held experts run at the cell's DEFAULT precision (one bfloat16
#: pass, float32 accumulation) against a float32 HIGHEST reference:
#: relative error of a few 1e-3, where the bfloat16 reference's is ~1e-2
MOE_RTOL = 2e-2


def phase_moe():
    """The held-expert layer at the cell's widths against the reference."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import moe as moe_lib
    bench = os.path.join(ROOT, "benchmarks", "chip")
    spec = importlib.util.spec_from_file_location(
        "bench_deepseek_v3", os.path.join(bench, "models", "deepseek_v3.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(bench, "configs",
                           "kanana-2-30b-a3b-5l.json")) as f:
        cj = json.load(f)
    base = get_config("kanana-2-30b-a3b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, held=cj["n_routed_experts"],
        held_offset=cj["held_expert_offset"]))
    shapes = {k[len("blocks/moe/"):]: v[1:] for k, v in ref.shapes(cj).items()
              if k.startswith("blocks/moe/")}
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes) + 2)
    p = {k: 0.02 * jax.random.normal(kk, shp)
         for kk, (k, shp) in zip(keys, sorted(shapes.items()))}
    x = jax.random.normal(keys[-2], (4, LM_SEQ, cj["hidden_size"]))
    dy = jax.random.normal(keys[-1], x.shape)

    def prog(p, x):
        return jnp.sum(moe_lib.apply_dropless(p, x, cfg)[0] * dy)

    def dense(p, x):
        rp = {"moe/" + k: v for k, v in p.items()}
        return jnp.sum(ref._moe(rp, x, cj) * dy)

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    _, (gp, gx) = grad(prog)(p, x)
    with jax.default_matmul_precision("highest"):
        _, (rgp, rgx) = grad(dense)(p, x)
    bf = lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)
    _, (cgp, cgx) = grad(dense)(bf(p), bf(x))
    errs = {"x": _rel(gx, rgx), **{k: _rel(gp[k], rgp[k]) for k in gp}}
    ctrl = {"x": _rel(cgx, rgx), **{k: _rel(cgp[k], rgp[k]) for k in cgp}}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref._moe(
            {"moe/" + k: v for k, v in p.items()}, x, cj))(p, x)
    got, rows = jax.jit(lambda p, x: moe_lib.apply_dropless(p, x, cfg))(p, x)
    out = _rel(got, want)
    print(json.dumps({"phase": "moe", "forward_rel": out,
                      "grad_rel": errs, "bf16_reference_grad_rel": ctrl,
                      "routed_rows": float(rows)}), flush=True)
    assert out < MOE_RTOL and max(errs.values()) < MOE_RTOL, (out, errs)
    print(json.dumps({"phase": "moe",
                      "fwd_bwd_ms": _ms(grad(prog), p, x)}), flush=True)


def phase_fcn():
    auto = run_arm("fcn", "kernels", fcn_spec(None))
    xla = run_arm("fcn", "xla", fcn_spec(False))
    assert auto["kernels"] == ["lbgm_projection"], auto["kernels"]
    assert xla["kernels"] == [], xla["kernels"]
    agree(auto, xla, all_rounds=True)


def phase_lm():
    auto = run_arm("lm", "kernels+int8", lm_spec(None))
    # fused_kernels=False selects the dense legacy fold, which a lossy
    # codec refuses: this arm ships fp32. Round 1 is codec-independent
    # (every client's first round is a full round from the same params).
    xla = run_arm("lm", "xla+fp32", lm_spec(False, codec="none"))
    assert auto["kernels"] == ["lbgm_dequant_fold",
                               "lbgm_sparse_decision"], auto["kernels"]
    assert xla["kernels"] == [], xla["kernels"]
    agree(auto, xla, all_rounds=False)


def bank_placement(engine):
    """Per-device shard shapes of the biggest leaf's bank and params."""
    name = max(engine.params, key=lambda k: engine.params[k].size)
    val = engine.lbg[name]["val"]
    shards = val.addressable_shards
    p = engine.params[name]
    return {"leaf": name, "bank_shape": list(val.shape),
            "bank_shards": [list(s.data.shape) for s in shards],
            "bank_devices": sorted(s.device.id for s in shards),
            "param_shape": list(p.shape),
            "param_shard": list(p.addressable_shards[0].data.shape)}


def phase_lm_mesh():
    import jax
    import numpy as np
    devs = jax.devices()
    assert len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}"
    mesh = dict(lbg_variant="topk-sharded", scheduler="sharded")
    ref_ = run_arm("lm-mesh", "chunked-1chip", lm_spec(None))
    r41 = run_arm("lm-mesh", "[4,1]-replicate",
                  lm_spec(None, mesh=[4, 1], chunk_size=4, **mesh),
                  inspect=bank_placement)
    r22 = run_arm("lm-mesh", "[2,2]-auto",
                  lm_spec(None, mesh=[2, 2], chunk_size=2,
                          model_sharding="auto", **mesh),
                  inspect=bank_placement)
    for r, (c, m) in ((r41, (4, 1)), (r22, (2, 2))):
        assert r["kernels"] == ["lbgm_dequant_fold",
                                "lbgm_sparse_decision"], r
        n_chunks, chunk, nb, kb = r["bank_shape"]
        # block rows shard over the model axis where they divide
        want = [n_chunks, chunk // c, nb // m if nb % m == 0 else nb, kb]
        assert r["bank_devices"] == sorted(d.id for d in devs), r
        assert all(s == want for s in r["bank_shards"]), (r, want)
        for a, b in zip(ref_["loss"], r["loss"]):
            np.testing.assert_allclose(a, b, rtol=RTOL, err_msg=r["arm"])
        assert ref_["uplink_floats"][0] == r["uplink_floats"][0], r
        assert ref_["frac_scalar"][0] == r["frac_scalar"][0], r
        # int8 delta-coded indices: bytes follow the selected positions,
        # which fp32 reassociation may move by a few near-ties
        np.testing.assert_allclose(r["wire_bytes"][0], ref_["wire_bytes"][0],
                                   rtol=RTOL, err_msg=r["arm"])
    # tensor-parallel params: the biggest leaf holds 1/2 of its rows/cols
    assert r22["param_shard"] != r22["param_shape"], r22
    assert r41["param_shard"] == r41["param_shape"], r41
    print(f"  agree[chunked vs [4,1] and [2,2]-auto]: round-1 uplink/frac "
          f"equal, round-1 wire bytes and loss over {ROUNDS} rounds equal "
          f"at rtol={RTOL}, banks spread over all 4 devices", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the lm-mesh phase (needs 4 chips)")
    ap.add_argument("--only", choices=("kernels", "fcn", "lm", "moe"),
                    help="run this one-chip phase alone")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 1
    t0 = time.time()
    phases = ([phase_kernels, phase_fcn, phase_lm] if args.chips == 1
              else [phase_lm_mesh])
    if args.only:
        phases = [globals()["phase_" + args.only]]
    for phase in phases:
        t = time.time()
        print(f"== {phase.__name__[6:]}", flush=True)
        phase()
        print(f"== {phase.__name__[6:]} passed in {time.time() - t:.1f}s",
              flush=True)
    print(f"== all phases passed in {time.time() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
