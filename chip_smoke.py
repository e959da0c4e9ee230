#!/usr/bin/env python3
"""Smoke test of the LBGM federated round on a TPU, through the normal
entry points (ExperimentSpec -> run_experiment -> FLEngine).

    python chip_smoke.py             # one chip: phases kernels, fcn, lm
    python chip_smoke.py --chips 4   # four chips: phase lm-mesh only

Phases (one process; each prints its own lines):

* ``kernels`` — the Pallas kernels of the main path at the LM's leaf
  shapes against their XLA references (``kernels/ref.py``).
* ``fcn`` — the paper FCN at its published widths on mixture data with a
  label-skew split, default vmap scheduler + dense LBG store (the batched
  projection kernel).
* ``lm`` — qwen3-1.7b at published widths, cut to 4 layers and 1/8 of the
  vocabulary, on markov data: 8 clients, chunked scheduler, top-k store,
  int8 wire codec (the batched sparse-decision kernel).
* ``lm-mesh`` (``--chips 4``) — the ``lm`` round on the sharded scheduler
  with the mesh-aware top-k store, on a [4, 1] client mesh and on a
  [2, 2] mesh with tensor-parallel client compute
  (``model_sharding="auto"``), each against the chunked run on one chip.

Every one-chip phase runs its round with ``fused_kernels=None`` (auto: the
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
#: clients per scan step on one chip: the compiled round needs 14.4 GiB
#: at 2 (1.21 GiB arguments + 1.21 GiB outputs - 0.17 GiB aliased +
#: 12.1 GiB temporaries, XLA memory_analysis for v5e), ~6 GiB more per
#: extra client
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
    return sorted({k for k in ("lbgm_projection", "lbgm_sparse_decision")
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
    # decision: one stacked 2048x6144 block row group and a norm leaf
    for B, nb, block, kb in ((2, 192, 65536, 655), (2, 1, 2048, 20)):
        g = jax.random.normal(key, (B, nb, block))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (B, nb, kb),
                                 0, block, jnp.int32)
        t0 = time.time()
        gg, gath, ti, tv = jax.block_until_ready(
            lbgm_sparse_decision_batched_pallas(g, idx))
        dt = time.time() - t0
        for b in range(B):
            rgg, rgath, rti, rtv = ref.lbgm_sparse_decision_ref(g[b], idx[b])
            np.testing.assert_allclose(float(gg[b]), float(rgg), rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(gath[b]),
                                          np.asarray(rgath))
            si, sv = ref.sort_topk_rows(ti[b], tv[b])
            ri, rv = ref.sort_topk_rows(rti, rtv)
            np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
            np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))
        print(json.dumps({"phase": "kernels", "kernel": "lbgm_sparse_decision",
                          "shape": [B, nb, block, kb], "exact": True,
                          "first_call_s": dt}), flush=True)
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
    assert auto["kernels"] == ["lbgm_sparse_decision"], auto["kernels"]
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
        assert r["kernels"] == ["lbgm_sparse_decision"], r
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
