"""2-D (clients x model) mesh execution (ISSUE 5 tentpole).

Acceptance:
  * ``FLConfig.mesh`` generalizes to a JSON-able spec — ``None`` / int
    (the pre-2-D spelling, still valid) / ``[clients, model]`` — and
    round-trips losslessly;
  * a ``(1, 1)`` mesh reproduces the chunked scheduler bit-for-bit and an
    int spec ``n`` is bit-identical to ``[n, 1]``;
  * on a real multi-device 2-D mesh (forced host devices, subprocess) the
    round history matches chunked within fp32 tolerance with IDENTICAL
    uplink accounting, the sparse bank physically shards along BOTH axes,
    and per-device bank bytes scale as O(K·k_frac·M / (c·m));
  * ``RoundPrefetcher`` x "sharded" interplay: a mid-run host-prep
    exception propagates to the caller, and the prefetch path is
    rng-stream invariant under the 2-D mesh.

ISSUE-8 additions (``FLConfig.model_sharding``):
  * the knob validates and JSON round-trips; ``"auto"`` requires the
    sharded scheduler and a metadata-carrying model component;
  * on 8 forced host devices (subprocess), ``"auto"`` on the ``"lm"``
    component matches ``"replicate"`` within fp32 tolerance with
    IDENTICAL uplink accounting, params physically shard 1/m per model
    rank, and the whole-round per-device memory envelope shrinks;
  * a ``[1, 1]`` mesh under the default ``"replicate"`` still reproduces
    the pre-PR golden history float-exact even with 8 devices visible.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import mixture_classification
from repro.fed import FLConfig, FLEngine, partition_iid
from repro.models.smallnets import apply_fcn, classifier_loss, init_fcn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def fcn_setup():
    cfg = get_config("paper-fcn")
    params, _ = init_fcn(jax.random.PRNGKey(0), cfg)
    x, y = mixture_classification(900, 10, seed=0)
    loss_fn = lambda p, b: classifier_loss(apply_fcn, p, cfg, b["x"], b["y"])
    return params, x, y, loss_fn


def make_engine(fcn_setup, K=10, **flkw):
    params, x, y, loss_fn = fcn_setup
    parts = partition_iid(len(y), K, seed=0)
    data = [{"x": x[p], "y": y[p]} for p in parts]
    return FLEngine(loss_fn, params, data,
                    FLConfig(num_clients=K, tau=2, lr=0.05, batch_size=16,
                             **flkw))


def _assert_identical_run(fl_a, fl_b, rounds=3):
    ha = fl_a.run(rounds)
    hb = fl_b.run(rounds)
    for k in fl_a.params:
        np.testing.assert_array_equal(np.asarray(fl_a.params[k]),
                                      np.asarray(fl_b.params[k]), err_msg=k)
    assert ha == hb


# ------------------------------------------------------- mesh spec knob


def test_mesh_spec_validation():
    # int spelling unchanged (and still rejected when invalid)
    assert FLConfig(scheduler="sharded", mesh=1).mesh == 1
    with pytest.raises(ValueError, match="mesh"):
        FLConfig(mesh=0)
    # 2-D spelling: [clients, model], both >= 1, exactly two entries
    assert FLConfig(scheduler="sharded", mesh=[2, 2]).mesh == [2, 2]
    for bad in ([0, 2], [2, 0], [2], [2, 2, 2], [2.0, 2], True, [True, 2],
                "2x2"):
        with pytest.raises(ValueError, match="mesh"):
            FLConfig(scheduler="sharded", mesh=bad)
    # tuples normalize to lists so a JSON round-trip compares equal
    cfg = FLConfig(scheduler="sharded", mesh=(2, 2))
    assert cfg.mesh == [2, 2]
    assert FLConfig.from_dict(json.loads(json.dumps(cfg.to_dict()))) == cfg
    # model-axis sharding needs the mesh-aware scheduler
    with pytest.raises(ValueError, match="sharded"):
        FLConfig(scheduler="chunked", mesh=[1, 2])
    assert FLConfig(scheduler="chunked").mesh is None  # int-free default ok


def test_mesh_shape_views():
    assert FLConfig().mesh_shape is None
    assert FLConfig().mesh_model_dim == 1
    assert FLConfig(scheduler="sharded", mesh=3).mesh_shape == (3, 1)
    assert FLConfig(scheduler="sharded", mesh=[2, 4]).mesh_shape == (2, 4)
    assert FLConfig(scheduler="sharded", mesh=[2, 4]).mesh_model_dim == 4


def test_make_fl_mesh_shapes_and_errors():
    from repro.launch.mesh import make_fl_mesh
    n = len(jax.devices())
    mesh = make_fl_mesh(None)
    assert mesh.axis_names == ("clients", "model")
    assert dict(mesh.shape) == {"clients": n, "model": 1}
    mesh = make_fl_mesh(1)
    assert dict(mesh.shape) == {"clients": 1, "model": 1}
    mesh = make_fl_mesh([1, 1], client_axis="c", model_axis="m")
    assert mesh.axis_names == ("c", "m")
    # the error names what JAX found, not a workaround
    found = f"found {n} {jax.devices()[0].platform} device"
    with pytest.raises(RuntimeError, match=found):
        make_fl_mesh([n + 1, 1])
    with pytest.raises(RuntimeError, match=found):
        make_fl_mesh([1, n + 1])
    with pytest.raises(ValueError, match="axis"):
        make_fl_mesh([0, 1])


def test_bank_model_partition_rule():
    from repro.core.lbgm_sharded import (bank_model_partition,
                                         model_shard_rows)
    # nb rounds to 16 for multi-block leaves -> power-of-two meshes divide
    assert model_shard_rows(16, 4) == 4
    assert model_shard_rows(16, 1) == 0      # n_model=1: nothing to shard
    assert model_shard_rows(1, 4) == 0       # single-block leaf: replicated
    assert model_shard_rows(16, 3) == 0      # non-divisible: replicated
    params = {"big": jnp.zeros((700, 128)), "small": jnp.zeros(64)}
    part = bank_model_partition(params, 0.1, 4)
    assert part == {"big": True, "small": False}


def test_spec_with_2d_mesh_roundtrips(tmp_path):
    from repro.fed import ExperimentSpec
    spec = ExperimentSpec.from_dict({
        "name": "mesh2d",
        "fl": {"num_clients": 8, "scheduler": "sharded", "chunk_size": 4,
               "mesh": [2, 4], "lbg_variant": "topk-sharded"},
    })
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec and again.fl.mesh == [2, 4]
    assert json.loads(spec.to_json())["fl"]["mesh"] == [2, 4]
    path = tmp_path / "spec.json"
    spec.save(str(path))
    assert ExperimentSpec.load(str(path)) == spec


# ------------------------------------------------- model_sharding knob


def test_model_sharding_knob_validation():
    assert FLConfig().model_sharding == "replicate"
    cfg = FLConfig(scheduler="sharded", mesh=[1, 1],
                   model_sharding="auto")
    assert cfg.model_sharding == "auto"
    # JSON round-trip (from_dict rejects unknown keys, so the knob being
    # round-trippable proves it is a first-class serialized field)
    assert FLConfig.from_dict(json.loads(json.dumps(cfg.to_dict()))) == cfg
    with pytest.raises(ValueError, match="model_sharding"):
        FLConfig(model_sharding="tp")
    # tensor-parallel client compute only exists on the sharded scheduler
    with pytest.raises(ValueError, match="sharded"):
        FLConfig(scheduler="chunked", model_sharding="auto")


def test_model_sharding_auto_needs_axes_metadata(fcn_setup):
    """The FCN component carries no axes tree: engine construction must
    fail actionably, not at trace time."""
    with pytest.raises(ValueError, match="sharding metadata"):
        make_engine(fcn_setup, K=6, scheduler="sharded", mesh=[1, 1],
                    chunk_size=3, use_lbgm=True, delta_threshold=0.2,
                    lbg_variant="topk-sharded", lbg_kw={"k_frac": 0.25},
                    model_sharding="auto")


def test_model_sharding_auto_rejects_compressor(fcn_setup):
    """auto + a compressor pipeline is refused (its top-k would hit
    model-sharded gradients in GSPMD auto-land); axes are checked first,
    so hand a fake tree to reach the compressor check."""
    params, x, y, loss_fn = fcn_setup
    parts = partition_iid(len(y), 6, seed=0)
    data = [{"x": x[p], "y": y[p]} for p in parts]
    axes = {k: ("hidden",) * v.ndim for k, v in params.items()}
    with pytest.raises(ValueError, match="compressor"):
        FLEngine(loss_fn, params, data,
                 FLConfig(num_clients=6, tau=2, lr=0.05, batch_size=16,
                          scheduler="sharded", mesh=[1, 1], chunk_size=3,
                          use_lbgm=True, delta_threshold=0.2,
                          lbg_variant="topk-sharded",
                          lbg_kw={"k_frac": 0.25}, compressor="topk",
                          compressor_kw={"k_frac": 0.1},
                          model_sharding="auto"),
                 model_axes=axes)


# ----------------------------------------- (1,1) / int-vs-list equivalence


def test_1x1_mesh_equals_chunked_bitforbit(fcn_setup):
    """Acceptance: mesh=[1,1] reproduces the chunked scheduler exactly."""
    kw = dict(use_lbgm=True, delta_threshold=0.5, chunk_size=3,
              lbg_variant="topk", lbg_kw={"k_frac": 0.25})
    fl_c = make_engine(fcn_setup, K=6, scheduler="chunked", **kw)
    kw["lbg_variant"] = "topk-sharded"
    fl_s = make_engine(fcn_setup, K=6, scheduler="sharded", mesh=[1, 1],
                       **kw)
    assert (fl_s.sched.n_client_dev, fl_s.sched.n_model) == (1, 1)
    _assert_identical_run(fl_c, fl_s, rounds=3)


def test_model_sharding_auto_11_matches_replicate():
    """The auto chunk function (GSPMD client compute, manual decision and
    fold) on a [1, 1] mesh: the "lm" component gives the same losses,
    uplink floats and wire bytes as "replicate"."""
    import dataclasses

    from repro.fed import ExperimentSpec, run_experiment
    spec_r = ExperimentSpec.from_dict({
        "name": "lm-auto-11",
        "model": {"name": "lm",
                  "kw": {"arch": "yi-34b", "reduced": True,
                         "vocab_size": 1024}},
        "data": {"name": "markov", "kw": {"n": 64, "n_eval": 0,
                                          "seq_len": 32, "vocab": 1024}},
        "partition": {"name": "iid", "kw": {}},
        "fl": {"num_clients": 4, "tau": 2, "lr": 0.02, "batch_size": 4,
               "use_lbgm": True, "delta_threshold": 0.5, "seed": 0,
               "scheduler": "sharded", "chunk_size": 2, "mesh": [1, 1],
               "lbg_variant": "topk-sharded", "lbg_kw": {"k_frac": 0.01}},
        "rounds": 2,
        "eval": {"every": 0, "final": False, "verbose": False},
    })
    spec_a = dataclasses.replace(
        spec_r, fl=dataclasses.replace(spec_r.fl, model_sharding="auto"))
    res_r, res_a = run_experiment(spec_r), run_experiment(spec_a)
    assert len(res_r.records) == len(res_a.records) == 2
    for a, b in zip(res_r.records, res_a.records):
        assert (a.loss, a.uplink_floats, a.wire_bytes) == \
            (b.loss, b.uplink_floats, b.wire_bytes), (a, b)


def test_int_mesh_equals_2d_mesh_bitforbit(fcn_setup):
    """Compatibility rule: mesh=n is exactly mesh=[n, 1]."""
    kw = dict(use_lbgm=True, delta_threshold=0.2, chunk_size=5,
              scheduler="sharded", lbg_variant="topk-sharded",
              lbg_kw={"k_frac": 0.25})
    fl_int = make_engine(fcn_setup, K=10, mesh=1, **kw)
    fl_2d = make_engine(fcn_setup, K=10, mesh=[1, 1], **kw)
    _assert_identical_run(fl_int, fl_2d, rounds=3)


def test_mesh_topk_step_n_model_1_is_local_step():
    """make_mesh_topk_step(n_model=1) must BE the device-local step (the
    bit-for-bit (n, 1) contract rides on sharing that code path)."""
    from repro.core.lbgm_sharded import make_mesh_topk_step
    step = make_mesh_topk_step(0.5, 0.25, n_model=1, sparse_out=True)
    rng = np.random.RandomState(0)
    g = {"w": jnp.asarray(rng.randn(40, 8).astype(np.float32))}
    from repro.core.lbgm import init_topk_lbg, lbgm_topk_client_step
    lbg = init_topk_lbg(g, 0.25)
    (send, gscale), new_lbg, stats = step(g, lbg)
    (send_r, gscale_r), new_r, stats_r = lbgm_topk_client_step(
        g, lbg, 0.5, 0.25, sparse_out=True)
    for a, b in zip(jax.tree.leaves((send, gscale, new_lbg, tuple(stats))),
                    jax.tree.leaves((send_r, gscale_r, new_r,
                                     tuple(stats_r)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # n_model > 1 refuses the dense-scatter contract with the fix named
    with pytest.raises(ValueError, match="sparse_out"):
        make_mesh_topk_step(0.5, 0.25, n_model=2, sparse_out=False)


# ------------------------------- RoundPrefetcher x sharded interplay


def test_prefetch_exception_propagates_midrun_sharded(fcn_setup):
    """A host-prep failure on the prefetch thread must surface as the
    documented RuntimeError at the next round, not hang or vanish."""
    fl = make_engine(fcn_setup, K=6, scheduler="sharded", mesh=[1, 1],
                     chunk_size=3, use_lbgm=True, delta_threshold=0.2)
    calls = {"n": 0}
    orig = fl._sample_batches

    def failing(rng):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("host prep exploded")
        return orig(rng)

    fl._sample_batches = failing
    src = fl.prefetcher(np.random.RandomState(1), depth=1)
    try:
        fl.run_round(src)  # rounds staged before the failure still run
        with pytest.raises(RuntimeError, match="prefetch thread failed"):
            for _ in range(4):
                fl.run_round(src)
        # the cause chain carries the real error
        with pytest.raises(RuntimeError) as ei:
            src.next()
        assert "host prep exploded" in str(ei.value.__cause__)
    finally:
        src.close()


@pytest.mark.slow
def test_prefetch_rng_stream_invariant_under_2d_mesh(fcn_setup):
    """Prefetched and synchronous runs draw the same stream — history and
    params bit-identical — under the 2-D sharded scheduler."""
    kw = dict(scheduler="sharded", mesh=[1, 1], chunk_size=3,
              use_lbgm=True, delta_threshold=0.3, sample_frac=0.7,
              lbg_variant="topk-sharded", lbg_kw={"k_frac": 0.25})
    fl_pre = make_engine(fcn_setup, K=6, **kw)
    fl_sync = make_engine(fcn_setup, K=6, **kw)
    h_pre = fl_pre.run(4, prefetch=True)
    h_sync = fl_sync.run(4, prefetch=False)
    assert h_pre == h_sync
    for k in fl_pre.params:
        np.testing.assert_array_equal(np.asarray(fl_pre.params[k]),
                                      np.asarray(fl_sync.params[k]))


# --------------------------------------------- multi-device 2-D (forced)

MULTI_DEV_2D_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import json
import jax
import numpy as np
from repro.configs import get_config
from repro.data.synthetic import mixture_classification
from repro.fed import FLConfig, FLEngine, partition_iid
from repro.models.smallnets import apply_fcn, classifier_loss, init_fcn

assert len(jax.devices()) == 8
# widen the FCN so fc1/w spans >1 block (nb -> 16): the model axis has
# real rows to shard
cfg = dataclasses.replace(get_config("paper-fcn"), d_model=512)
params, _ = init_fcn(jax.random.PRNGKey(0), cfg)
x, y = mixture_classification(600, 10, seed=0)
loss_fn = lambda p, b: classifier_loss(apply_fcn, p, cfg, b["x"], b["y"])
parts = partition_iid(len(y), 8, seed=0)
data = [{"x": x[p], "y": y[p]} for p in parts]

def eng(**kw):
    base = dict(num_clients=8, tau=2, lr=0.05, batch_size=16,
                use_lbgm=True, delta_threshold=0.6, chunk_size=4,
                sample_frac=0.8, lbg_kw={"k_frac": 0.25})
    base.update(kw)
    return FLEngine(loss_fn, params, data, FLConfig(**base))

fc = eng(scheduler="chunked", lbg_variant="topk")
f81 = eng(scheduler="sharded", mesh=[8, 1], lbg_variant="topk-sharded")
f24 = eng(scheduler="sharded", mesh=[2, 4], lbg_variant="topk-sharded")

# --- bank placement: both axes, exactly where the issue says
ms = f24.sched.model_sharded
assert ms["fc1/w"] is True and ms["fc1/b"] is False, ms
specs = {k: str(l["idx"].sharding.spec) for k, l in f24.lbg.items()}
assert specs["fc1/w"] == "PartitionSpec(None, 'clients', 'model')", specs
assert specs["fc1/b"] == "PartitionSpec(None, 'clients')", specs

# --- per-device bank bytes scale as O(K·k_frac·M / (c·m)) for the
# model-shardable leaf: each of the 8 devices holds exactly 1/(2*4) of
# the global bank rows
g = f24.lbg["fc1/w"]["val"]
n_chunks, chunk, nb, kb = g.shape
local = g.addressable_shards[0].data.shape
assert local == (n_chunks, chunk // 2, nb // 4, kb), (g.shape, local)
assert g.size // 8 == int(np.prod(local)), (g.size, local)
# ...and the (8, 1) client-only mesh holds 1/8 along clients alone
g81 = f81.lbg["fc1/w"]["val"]
local81 = g81.addressable_shards[0].data.shape
assert local81 == (g81.shape[0], g81.shape[1] // 8) + g81.shape[2:]

# --- equivalence: chunked vs (8,1) vs (2,4)
hc = fc.run(3)
h81 = f81.run(3)
h24 = f24.run(3)
# round 1 enters with bit-identical params => uplink accounting is EXACT,
# and the global block layout is mesh-shape independent, so every mesh
# shape reports the same round-1 full-round cost
assert hc[0]["uplink_floats"] == h81[0]["uplink_floats"] \
    == h24[0]["uplink_floats"], (hc[0], h81[0], h24[0])
assert hc[0]["frac_scalar"] == h81[0]["frac_scalar"] \
    == h24[0]["frac_scalar"]
# ...and so are the wire bytes (codec "none": static per full round), the
# model-sharded leaves' rows summed over the model axis
assert hc[0]["wire_bytes"] == h81[0]["wire_bytes"] \
    == h24[0]["wire_bytes"], (hc[0], h81[0], h24[0])
M = sum(int(v.size) for v in params.values())
flip = 1.5 * 0.25 * M  # one client's full-round topk cost
for a, b in zip(hc, h24):
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5, atol=1e-7)
    assert abs(a["uplink_floats"] - b["uplink_floats"]) <= 2 * flip, (a, b)
for a, b in zip(hc, h81):
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5, atol=1e-7)
for k in fc.params:
    np.testing.assert_allclose(np.asarray(fc.params[k]),
                               np.asarray(f24.params[k]),
                               rtol=1e-5, atol=1e-6, err_msg=k)
    np.testing.assert_allclose(np.asarray(fc.params[k]),
                               np.asarray(f81.params[k]),
                               rtol=1e-5, atol=1e-6, err_msg=k)

# --- XLA memory model (as in test_engine.py): at a FIXED client-axis
# width, growing the model axis must not grow the per-device transient
# set — training stays O(chunk·M / c) per device while the decision +
# aggregation rows it used to hold whole now shard m ways. (The compiled
# stats are whole-program across all mesh devices; divide by the device
# count for the per-device view.)
def round_memory(fl):
    import jax.numpy as jnp
    batch = fl._sample_batches(np.random.RandomState(0))
    mask = jnp.ones(fl.cfg.num_clients, jnp.float32)
    # lower on the live arrays (banks keep their mesh shardings; the
    # uncommitted host args place exactly as in run_round)
    lowered = fl._round.lower(fl.params, fl.lbg, fl.residual, batch, mask)
    stats = lowered.compile().memory_analysis()
    if stats is None or not hasattr(stats, "temp_size_in_bytes"):
        return None
    return int(stats.temp_size_in_bytes)

f21 = eng(scheduler="sharded", mesh=[2, 1], lbg_variant="topk-sharded")
assert f21.sched.chunk == f24.sched.chunk  # same client width per device
t21, t24 = round_memory(f21), round_memory(f24)
mem = {"t21_per_dev": t21 and t21 // 2, "t24_per_dev": t24 and t24 // 8}
if t21 is not None and t24 is not None and t21 > 0:
    assert t24 / 8 <= 1.05 * (t21 / 2), mem
print(json.dumps({"ok": True, "mem": mem}))
"""


@pytest.mark.slow
def test_2d_mesh_multi_device_matches_chunked():
    """Acceptance: 2x4 and 8x1 meshes match chunked within fp32 tolerance
    with identical uplink accounting; the bank shards along both axes with
    per-device bytes divided by c*m (subprocess: forced host devices)."""
    _run_forced_8dev(MULTI_DEV_2D_SCRIPT)


def _run_forced_8dev(script):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]


# --------------------------------- model_sharding="auto" (forced 8-dev)

MODEL_SHARDING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.fed import ExperimentSpec, run_experiment
from repro.fed.experiment import build_experiment

assert len(jax.devices()) == 8
C, M = 2, 4
base = {
    "name": "lm-model-sharding",
    "model": {"name": "lm",
              "kw": {"arch": "yi-34b", "reduced": True,
                     "vocab_size": 1024}},
    "data": {"name": "markov", "kw": {"n": 256, "n_eval": 0,
                                      "seq_len": 32, "vocab": 1024}},
    "partition": {"name": "iid", "kw": {}},
    "fl": {"num_clients": 8, "tau": 2, "lr": 0.02, "batch_size": 4,
           "use_lbgm": True, "delta_threshold": 0.5, "seed": 0,
           "scheduler": "sharded", "chunk_size": 4, "mesh": [C, M],
           "lbg_variant": "topk-sharded", "lbg_kw": {"k_frac": 0.01}},
    "rounds": 3,
    "eval": {"every": 0, "final": False, "verbose": False},
}
spec_r = ExperimentSpec.from_dict(base)
spec_a = dataclasses.replace(
    spec_r, fl=dataclasses.replace(spec_r.fl, model_sharding="auto"))

# --- (b) physical placement under auto: every leaf's addressable shard
# is exactly its resolved PartitionSpec's slice — model-parallel leaves
# hold 1/M of their rows, vocab-axis leaves shard along d_model (their
# gathers must stay device-local), norms replicate
eng_a, _ = build_experiment(spec_a)
specs = {k: s.spec
         for k, s in eng_a.sched.param_shardings.items()}
tot = loc = 0
sharded_leaves = 0
for k, v in eng_a.params.items():
    spec = tuple(specs[k]) + (None,) * (v.ndim - len(tuple(specs[k])))
    exp = tuple(d // (M if s == "model" else 1)
                for d, s in zip(v.shape, spec))
    got = v.addressable_shards[0].data.shape
    assert got == exp, (k, spec, v.shape, got, exp)
    tot += v.size
    loc += int(np.prod(got))
    sharded_leaves += "model" in spec
assert sharded_leaves >= 8, specs    # attn QKV/O, MLP, embed, lm_head
assert tuple(specs["embed"]) == (None, "model"), specs["embed"]
assert tuple(specs["lm_head"]) == ("model", None), specs["lm_head"]
# replicated leaves are only the tiny norms: per-device param bytes stay
# within a hair of the 1/M floor
assert loc / tot <= 1 / M + 0.02, (loc, tot)

# --- (a) histories: fp32-tolerance losses, EXACT uplink accounting (the
# global block layout is mesh- and sharding-mode-independent)
res_r = run_experiment(spec_r)
res_a = run_experiment(spec_a)
for a, b in zip(res_r.records, res_a.records):
    np.testing.assert_allclose(a.loss, b.loss, rtol=1e-5, atol=1e-7)
    assert a.uplink_floats == b.uplink_floats, (a, b)
    assert a.frac_scalar == b.frac_scalar, (a, b)
    assert a.wire_bytes == b.wire_bytes, (a, b)
# round 1 is all full rounds: codec "none" ships 4-byte indices and 4-byte
# values for every block-layout slot of every leaf, on every model rank's
# rows together
from repro.core.lbgm import _block_layout
full = sum(8.0 * nb * kb for nb, _, kb in (
    _block_layout(int(v.size), 0.01) for v in eng_a.params.values()))
assert res_a.records[0].wire_bytes == 8 * full, (res_a.records[0], full)

# --- (b2) whole-round memory envelope (XLA memory_analysis; params
# exact from the shards above). The 1/M scaling lands on the
# param-shaped buffers: per-device param bytes hit the 1/M floor in (b),
# and here auto's per-device footprint must fit inside replicate's
# transient pool plus a 1/M share of the param bytes. The transient pool
# itself is NOT asserted to shrink by 1/M at this toy width — it is
# dominated by state that is model-sharded identically in BOTH modes
# (the look-back banks / sparse-aggregation carry) plus mesh-invariant
# batch buffers, so auto only has to not regress it.
def round_memory(fl):
    batch = fl._sample_batches(np.random.RandomState(0))
    mask = jnp.ones(fl.cfg.num_clients, jnp.float32)
    lowered = fl._round.lower(fl.params, fl.lbg, fl.residual, batch, mask)
    stats = lowered.compile().memory_analysis()
    if stats is None or not hasattr(stats, "temp_size_in_bytes"):
        return None
    return int(stats.temp_size_in_bytes)

eng_r, _ = build_experiment(spec_r)
t_r, t_a = round_memory(eng_r), round_memory(eng_a)
mem = {"t_r_per_dev": t_r and t_r // 8, "t_a_per_dev": t_a and t_a // 8,
       "p_r_per_dev": 4 * tot, "p_a_per_dev": 4 * loc}
if t_r is not None and t_a is not None and t_r > 0:
    assert t_a <= 1.05 * t_r, mem                      # transients: no regression
    comb_a = 4 * loc + t_a / 8
    bound = (1 / M + 0.02) * (4 * tot) + t_r / 8       # 1/M param share
    assert comb_a <= bound, mem
print(json.dumps({"ok": True, "mem": mem}))
"""


GOLDEN_11_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np
from repro.configs import get_config
from repro.data.synthetic import mixture_classification
from repro.fed import FLConfig, FLEngine, partition_label_skew
from repro.models.smallnets import apply_fcn, classifier_loss, init_fcn

assert len(jax.devices()) == 8
# exactly the test_wire.py golden fixture config, pinned to mesh=[1, 1]
# and the default model_sharding="replicate": 8 visible devices and the
# new auto machinery must leave this path bit-for-bit untouched
cfg = get_config("paper-fcn")
params, _ = init_fcn(jax.random.PRNGKey(0), cfg)
x, y = mixture_classification(1200, 10, seed=0)
loss_fn = lambda p, b: classifier_loss(apply_fcn, p, cfg, b["x"], b["y"])
parts = partition_label_skew(y, 6, 3, seed=0)
data = [{"x": x[p], "y": y[p]} for p in parts]
fl = FLEngine(loss_fn, params, data,
              FLConfig(num_clients=6, tau=2, lr=0.05, batch_size=16,
                       use_lbgm=True, delta_threshold=0.2,
                       sample_frac=0.7, scheduler="sharded", chunk_size=4,
                       mesh=[1, 1], model_sharding="replicate",
                       lbg_variant="topk-sharded",
                       lbg_kw={"k_frac": 0.25}))
with open(@GOLDEN@) as f:
    golden = json.load(f)["sharded"]
rng = np.random.RandomState(0)
for r, gh in enumerate(golden):
    h = fl.run_round(rng)
    for k, v in gh.items():
        assert float.fromhex(v) == h[k], (r, k, v, h[k])
print(json.dumps({"ok": True}))
"""


@pytest.mark.slow
def test_model_sharding_auto_multi_device_lm():
    """ISSUE-8 acceptance: on a 2x4 forced-host-device mesh the "lm"
    component under model_sharding="auto" shards every model-parallel
    param 1/m per rank, matches "replicate" within fp32 tolerance with
    identical uplink accounting, and shrinks the per-device param +
    transient envelope toward the 1/m floor."""
    _run_forced_8dev(MODEL_SHARDING_SCRIPT)


@pytest.mark.slow
def test_replicate_11_mesh_stays_golden_with_8_devices():
    """The [1, 1] + model_sharding="replicate" path reproduces the
    pre-PR golden history float-exact even with 8 host devices visible
    (the auto machinery is inert unless opted into)."""
    golden = os.path.join(REPO, "tests", "golden",
                          "engine_history_pre_codec.json")
    _run_forced_8dev(GOLDEN_11_SCRIPT.replace("@GOLDEN@", repr(golden)))
