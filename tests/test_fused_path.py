"""Fused LBGM decision hot path + sparse scalar-round aggregation
(ISSUE 4 tentpole).

Four pillars:
  (a) the batched Pallas kernels (leading client-axis grid dimension)
      match the ``kernels/ref.py`` oracles in interpret mode, including
      under ``jax.vmap`` (the custom_vmap routing the schedulers rely on)
      and at non-tile-aligned sizes;
  (b) the bit-identical pad-row trims and the ``sparse_out`` client-step
      contract ((idx, val) payload + gscale, no dense scatter) agree with
      the legacy step;
  (c) engine-level: the sparse aggregation path equals the pre-PR dense
      path bit-for-bit on full rounds and within fp32 tolerance (with
      IDENTICAL uplink accounting) on scalar rounds, across
      vmap/chunked/sharded; ``fused_kernels=False`` restores the legacy
      path; ``fused_kernels=True`` (Pallas interpret off-TPU) agrees too;
  (d) the round prefetcher is numerically invisible and the vectorized
      batch sampler preserves the exact rng stream of the old per-client
      loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import lbgm as lbgm_lib
from repro.data.synthetic import mixture_classification
from repro.fed import FLConfig, FLEngine, partition_iid
from repro.kernels import ops, ref
from repro.models.smallnets import apply_fcn, classifier_loss, init_fcn

# ------------------------------------------------------------- (a) kernels


@pytest.mark.parametrize("n", [257, 10_007, 65536])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_projection_matches_ref(key, n, dtype):
    B = 3
    g = (jax.random.normal(key, (B, n)) * 0.1).astype(dtype)
    l = (jax.random.normal(jax.random.fold_in(key, 1), (B, n)) * 0.1
         ).astype(dtype)
    from repro.kernels.lbgm_projection import lbgm_projection_batched_pallas
    gl, gg, ll = lbgm_projection_batched_pallas(g, l, interpret=True)
    tol = 5e-3 if dtype == jnp.bfloat16 else 1e-4
    for b in range(B):
        want = ref.lbgm_projection_ref(g[b], l[b])
        np.testing.assert_allclose(
            np.array([gl[b], gg[b], ll[b]]), np.asarray(want), rtol=tol)


def test_projection_vmap_routes_to_batched_kernel(key):
    """vmap over the client axis must hit the batched kernel (leading batch
    grid dim) and agree with per-client calls."""
    B, n = 4, 5000
    g = jax.random.normal(key, (B, n))
    l = jax.random.normal(jax.random.fold_in(key, 1), (B, n))
    got = jax.vmap(lambda a, b: ops.lbgm_projection(
        {"x": a}, {"x": b}, interpret=True))(g, l)
    for b in range(B):
        one = ops.lbgm_projection({"x": g[b]}, {"x": l[b]}, interpret=True)
        np.testing.assert_allclose(
            np.array([got[0][b], got[1][b], got[2][b]]),
            np.asarray(one), rtol=1e-5)


@pytest.mark.parametrize("nb,block,kb", [(1, 700, 33), (3, 512, 17),
                                         (16, 1000, 9),
                                         # multi-tile rows at the LM's width
                                         (2, 65536, 655)])
def test_sparse_decision_kernel_matches_ref(key, nb, block, kb):
    blocks = jax.random.normal(key, (nb, block))
    perm = jnp.argsort(
        jax.random.normal(jax.random.fold_in(key, 2), (nb, block)), axis=1)
    idx = perm[:, :kb].astype(jnp.int32)
    gg, gath, ti, tv = ops.lbgm_sparse_decision(blocks, idx, interpret=True)
    rgg, rgath, rti, rtv = ref.lbgm_sparse_decision_ref(blocks, idx)
    np.testing.assert_allclose(float(gg), float(rgg), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(gath), np.asarray(rgath))
    # top-k rows are index-ordered sets: compare in canonical order
    si, sv = ref.sort_topk_rows(ti, tv)
    ri, rv = ref.sort_topk_rows(rti, rtv)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))


def test_sparse_decision_vmap_over_clients(key):
    B, nb, block, kb = 3, 2, 256, 11
    blocks = jax.random.normal(key, (B, nb, block))
    idx = jnp.tile(jnp.arange(kb, dtype=jnp.int32)[None, None],
                   (B, nb, 1))
    got = jax.vmap(lambda x, i: ops.lbgm_sparse_decision(
        x, i, interpret=True))(blocks, idx)
    for b in range(B):
        want = ref.lbgm_sparse_decision_ref(blocks[b], idx[b])
        np.testing.assert_allclose(float(got[0][b]), float(want[0]),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1][b]),
                                      np.asarray(want[1]))
        si, sv = ref.sort_topk_rows(got[2][b], got[3][b])
        ri, rv = ref.sort_topk_rows(want[2], want[3])
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))


# ---------------------------------------------------- (b) step-level logic


def _rand_grad(key, shapes):
    ks = jax.random.split(key, len(shapes))
    return {n: jax.random.normal(k, s)
            for k, (n, s) in zip(ks, shapes.items())}


#: fc1/w-like leaf spans >1 block so nb rounds up to 16 (pad rows live)
SHAPES = {"w": (700, 128), "b": (64,)}


def test_trim_pad_is_bit_identical(key):
    g = _rand_grad(key, SHAPES)["w"]
    assert lbgm_lib._block_layout(g.size, 0.1)[0] == 16  # pad rows exist
    a = lbgm_lib.leaf_topk(g, 0.1)
    b = lbgm_lib.leaf_topk(g, 0.1, trim_pad=True)
    np.testing.assert_array_equal(np.asarray(a["idx"]), np.asarray(b["idx"]))
    np.testing.assert_array_equal(np.asarray(a["val"]), np.asarray(b["val"]))
    ga = lbgm_lib.leaf_sparse_gather(g, a, 0.1)
    gb = lbgm_lib.leaf_sparse_gather(g, a, 0.1, trim_pad=True)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))


@pytest.mark.parametrize("delta", [-1.0, 0.5, 1.0])
def test_sparse_out_contract_matches_dense_step(key, delta):
    """(send, gscale) must reproduce the dense g_tilde: scatter(send) *
    gscale == g_tilde, new_lbg/stats identical."""
    k_frac = 0.1
    g = _rand_grad(key, SHAPES)
    lbg = lbgm_lib.init_topk_lbg(g, k_frac)
    # a refreshed bank (so the recycle branch can fire for delta=1.0)
    _, lbg, _ = lbgm_lib.lbgm_topk_client_step(
        _rand_grad(jax.random.fold_in(key, 7), SHAPES), lbg, -1.0, k_frac)
    gt, nl, st = lbgm_lib.lbgm_topk_client_step(g, lbg, delta, k_frac)
    (send, gscale), nl2, st2 = lbgm_lib.lbgm_topk_client_step(
        g, lbg, delta, k_frac, sparse_out=True)
    for a, b in zip(jax.tree.leaves((nl, tuple(st))),
                    jax.tree.leaves((nl2, tuple(st2)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if bool(st.sent_scalar):
        np.testing.assert_allclose(float(gscale), float(st.rho), rtol=1e-6)
    else:
        assert float(gscale) == 1.0
    for name in g:
        dense = lbgm_lib.leaf_scatter(send[name], g[name].shape,
                                      g[name].size, k_frac)
        np.testing.assert_allclose(np.asarray(dense) * float(gscale),
                                   np.asarray(gt[name]), rtol=1e-5,
                                   atol=1e-7)


def test_topk_step_fused_matches_unfused(key):
    k_frac = 0.1
    g = _rand_grad(key, SHAPES)
    lbg = lbgm_lib.init_topk_lbg(g, k_frac)
    _, lbg, _ = lbgm_lib.lbgm_topk_client_step(
        _rand_grad(jax.random.fold_in(key, 7), SHAPES), lbg, -1.0, k_frac)
    gt_a, nl_a, st_a = lbgm_lib.lbgm_topk_client_step(g, lbg, 0.5, k_frac)
    gt_b, nl_b, st_b = lbgm_lib.lbgm_topk_client_step(g, lbg, 0.5, k_frac,
                                                      fused=True)
    assert bool(st_a.sent_scalar) == bool(st_b.sent_scalar)
    for a, b in zip(jax.tree.leaves(gt_a), jax.tree.leaves(gt_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-7)
    # the kernel emits each refreshed bank row as an index-ordered set
    for name in g:
        ai, av = ref.sort_topk_rows(nl_a[name]["idx"], nl_a[name]["val"])
        bi, bv = ref.sort_topk_rows(nl_b[name]["idx"], nl_b[name]["val"])
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
        np.testing.assert_allclose(np.asarray(av), np.asarray(bv),
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(st_a.sin2), float(st_b.sin2),
                               rtol=1e-4, atol=1e-6)


def test_dense_client_step_fused_matches_unfused(key):
    g = _rand_grad(key, SHAPES)
    lbg = _rand_grad(jax.random.fold_in(key, 3), SHAPES)
    gt_a, nl_a, st_a = lbgm_lib.lbgm_client_step(g, lbg, 0.5)
    gt_b, nl_b, st_b = lbgm_lib.lbgm_client_step(g, lbg, 0.5, fused=True)
    assert bool(st_a.sent_scalar) == bool(st_b.sent_scalar)
    for a, b in zip(jax.tree.leaves((gt_a, nl_a)),
                    jax.tree.leaves((gt_b, nl_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


# ----------------------------------------------- (c) engine round parity


@pytest.fixture(scope="module")
def fcn_setup():
    cfg = get_config("paper-fcn")
    params, _ = init_fcn(jax.random.PRNGKey(0), cfg)
    x, y = mixture_classification(600, 10, seed=0)
    loss_fn = lambda p, b: classifier_loss(apply_fcn, p, cfg, b["x"], b["y"])
    return params, x, y, loss_fn


def make_engine(fcn_setup, K=6, **flkw):
    params, x, y, loss_fn = fcn_setup
    parts = partition_iid(len(y), K, seed=0)
    data = [{"x": x[p], "y": y[p]} for p in parts]
    base = dict(num_clients=K, tau=2, lr=0.05, batch_size=8,
                use_lbgm=True, lbg_variant="topk", lbg_kw={"k_frac": 0.1})
    base.update(flkw)
    return FLEngine(loss_fn, params, data, FLConfig(**base))


SCHED_KW = {
    "vmap": {},
    "chunked": {"chunk_size": 3},
    "sharded": {"chunk_size": 3, "mesh": 1, "lbg_variant": "topk-sharded"},
}


@pytest.mark.parametrize("sched", ["vmap", "chunked", "sharded"])
def test_sparse_agg_equals_dense_full_rounds_bitforbit(fcn_setup, sched):
    """delta=-1 -> every round full: the sparse aggregation path must be
    bit-for-bit identical to the pre-PR dense-scatter path."""
    kw = dict(delta_threshold=-1.0, scheduler=sched, **SCHED_KW[sched])
    fl_d = make_engine(fcn_setup, fused_kernels=False, **kw)
    fl_s = make_engine(fcn_setup, **kw)
    assert not fl_d._sparse_agg and fl_s._sparse_agg
    hd = fl_d.run(3)
    hs = fl_s.run(3)
    assert hd == hs
    for k in fl_d.params:
        np.testing.assert_array_equal(np.asarray(fl_d.params[k]),
                                      np.asarray(fl_s.params[k]), err_msg=k)


@pytest.mark.parametrize("sched", ["vmap", "chunked", "sharded"])
def test_sparse_agg_equals_dense_scalar_rounds_fp32(fcn_setup, sched):
    """delta=1 -> every post-refresh round recycles: fp32 tolerance
    (w*rho folds before the scatter) with IDENTICAL uplink accounting."""
    kw = dict(delta_threshold=1.0, scheduler=sched, **SCHED_KW[sched])
    fl_d = make_engine(fcn_setup, fused_kernels=False, **kw)
    fl_s = make_engine(fcn_setup, **kw)
    hd = fl_d.run(4)
    hs = fl_s.run(4)
    assert hs[-1]["frac_scalar"] == 1.0          # the regime under test
    for a, b in zip(hd, hs):
        assert a["uplink_floats"] == b["uplink_floats"]
        assert a["frac_scalar"] == b["frac_scalar"]
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
    for k in fl_d.params:
        np.testing.assert_allclose(np.asarray(fl_d.params[k]),
                                   np.asarray(fl_s.params[k]),
                                   rtol=1e-5, atol=1e-7, err_msg=k)


@pytest.mark.slow
def test_fused_true_interpret_engine_agrees(fcn_setup):
    """fused_kernels=True off-TPU runs the Pallas kernels in interpret
    mode inside the jitted round (vmap within chunks) — numerics must stay
    within fp32 tolerance of the legacy path, uplink identical."""
    kw = dict(delta_threshold=0.5, scheduler="chunked", chunk_size=3)
    fl_d = make_engine(fcn_setup, fused_kernels=False, **kw)
    fl_f = make_engine(fcn_setup, fused_kernels=True, **kw)
    assert fl_f.store.fused
    hd = fl_d.run(2)
    hf = fl_f.run(2)
    for a, b in zip(hd, hf):
        assert a["uplink_floats"] == b["uplink_floats"]
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
    for k in fl_d.params:
        np.testing.assert_allclose(np.asarray(fl_d.params[k]),
                                   np.asarray(fl_f.params[k]),
                                   rtol=1e-5, atol=1e-7, err_msg=k)


def test_aggregator_selection_and_knob(fcn_setup):
    from repro.fed.engine import (DenseAggregator, SparseTopKAggregator,
                                  resolve_fused_kernels)
    # dense store has no sparse payload -> dense aggregation regardless
    fl = make_engine(fcn_setup, lbg_variant="dense")
    assert isinstance(fl.agg, DenseAggregator) and not fl._sparse_agg
    # topk store defaults to sparse aggregation...
    fl = make_engine(fcn_setup)
    assert isinstance(fl.agg, SparseTopKAggregator) and fl._sparse_agg
    # ...unless the knob pins the legacy path
    fl = make_engine(fcn_setup, fused_kernels=False)
    assert isinstance(fl.agg, DenseAggregator)
    assert not fl.store.fused
    # Pallas auto-resolution follows the backend
    cfg = FLConfig(fused_kernels=None)
    assert resolve_fused_kernels(cfg) == (jax.default_backend() == "tpu")
    assert resolve_fused_kernels(FLConfig(fused_kernels=True)) is True


def test_fused_knob_validation_and_json_roundtrip():
    from repro.fed import ExperimentSpec
    with pytest.raises(ValueError, match="fused_kernels"):
        FLConfig(fused_kernels="yes")
    # int 0/1 compare == to False/True but would slip past the engine's
    # `is not False` aggregator gate — must be rejected, not coerced
    with pytest.raises(ValueError, match="fused_kernels"):
        FLConfig(fused_kernels=0)
    with pytest.raises(ValueError, match="fused_kernels"):
        FLConfig(fused_kernels=1)
    for v in (None, True, False):
        cfg = FLConfig(fused_kernels=v)
        assert FLConfig.from_dict(cfg.to_dict()) == cfg
        spec = ExperimentSpec(fl=cfg)
        again = ExperimentSpec.from_json(spec.to_json())
        assert again == spec and again.fl.fused_kernels is v


# -------------------------------------------------- (d) host-side pipeline


def test_prefetched_run_matches_sync_bitforbit(fcn_setup):
    fl_a = make_engine(fcn_setup, delta_threshold=0.2, scheduler="chunked",
                       chunk_size=3, sample_frac=0.7)
    fl_b = make_engine(fcn_setup, delta_threshold=0.2, scheduler="chunked",
                       chunk_size=3, sample_frac=0.7)
    ha = fl_a.run(4, prefetch=False)
    hb = fl_b.run(4, prefetch=True)
    assert ha == hb
    for k in fl_a.params:
        np.testing.assert_array_equal(np.asarray(fl_a.params[k]),
                                      np.asarray(fl_b.params[k]))


def test_vectorized_sampling_preserves_rng_stream(fcn_setup):
    """The one-gather sampler must consume the rng exactly like the old
    per-client loop (same draws, same order, same values)."""
    fl = make_engine(fcn_setup, K=5)
    rng = np.random.RandomState(42)
    got = fl._sample_batches(rng)
    # reference: the pre-PR per-client loop
    ref_rng = np.random.RandomState(42)
    out = None
    for d in fl.client_data:
        n = len(next(iter(d.values())))
        idx = ref_rng.randint(0, n, size=(fl.cfg.tau, fl.cfg.batch_size))
        picked = {k: v[idx] for k, v in d.items()}
        if out is None:
            out = {k: [] for k in picked}
        for k, v in picked.items():
            out[k].append(v)
    want = {k: np.stack(v) for k, v in out.items()}
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    # and the stream position afterwards is identical
    np.testing.assert_array_equal(rng.rand(5), ref_rng.rand(5))


def test_prefetcher_surfaces_thread_errors(fcn_setup):
    fl = make_engine(fcn_setup, K=4)
    pf = fl.prefetcher(np.random.RandomState(0))
    try:
        pf.next()  # a good round first
        fl._data_cat = None  # poison the sampler -> thread must fail
        with pytest.raises(RuntimeError, match="prefetch"):
            while True:
                pf.next()
        # a dead producer must keep raising, not hang on the empty queue
        with pytest.raises(RuntimeError, match="prefetch"):
            pf.next()
    finally:
        fl._data_cat = {}
        pf.close()


def test_prefetcher_next_after_close_raises(fcn_setup):
    fl = make_engine(fcn_setup, K=4)
    pf = fl.prefetcher(np.random.RandomState(0))
    pf.next()
    pf.close()
    with pytest.raises(RuntimeError, match="close"):
        pf.next()


def test_lbg_kw_reserved_key_actionable_error(fcn_setup):
    with pytest.raises(ValueError, match="fused_kernels"):
        make_engine(fcn_setup, lbg_kw={"k_frac": 0.1, "fused": True})
    # the 2-D mesh knobs are engine-controlled too (FLConfig.mesh)
    with pytest.raises(ValueError, match="FLConfig.mesh"):
        make_engine(fcn_setup, lbg_variant="topk-sharded",
                    lbg_kw={"k_frac": 0.1, "n_model": 2})
    with pytest.raises(ValueError, match="FLConfig.mesh"):
        make_engine(fcn_setup, lbg_variant="topk-sharded",
                    lbg_kw={"k_frac": 0.1, "model_axis": "x"})


# ----------------------------- (e) threshold-select decision kernel edges


def _edge_rows(key, nb, block, kb, data):
    """(blocks, idx) for the decision kernel's edge cases, made in numpy so
    subnormals survive (XLA's CPU arithmetic flushes them)."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**30)))
    g = rng.standard_normal((nb, block)).astype(np.float32)
    idx = np.argsort(rng.standard_normal((nb, block)), axis=1)[:, :kb]
    if data == "packed":
        # every selection in one 256-lane tile (kb == 256): tile 5
        g *= np.float32(1e-3)
        g[:, 5 * 256:6 * 256] = rng.uniform(1, 2, (nb, 256))
    elif data == "ties":
        # 10 definites, then 21 equal |values| straddling lanes 255 | 256,
        # half of which fill the slots in index order
        g *= np.float32(1e-3)
        g[:, 600:610] = 5.0
        g[:, 250:271] = np.where(np.arange(21) % 2, 1.0, -1.0)
    elif data == "zero_row":
        g[0] = 0.0
    elif data == "subnormal":
        g = g * np.float32(1e-40)
        g[np.abs(g) < 2e-41] = np.float32(-0.0)
        g[:, 3] = np.float32(-0.0)
    elif data == "fresh_bank":
        idx = np.zeros((nb, kb), np.int64)     # a fresh bank: all zeros
    return jnp.asarray(g), jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("nb,block,kb,data", [
    pytest.param(*c, "normal", id="-".join(map(str, c)))
    for c in [(1, 700, 33), (3, 512, 17), (16, 1000, 9), (4, 256, 256),
              (12, 300, 7), (2, 100, 10)]] + [
    (3, 4096, 256, "packed"), (2, 1024, 20, "ties"),
    (2, 512, 17, "zero_row"), (2, 2048, 40, "subnormal"),
    (3, 1000, 30, "fresh_bank")])
def test_two_pass_kernel_matches_ref_setwise(key, nb, block, kb, data):
    """The decision kernel (no in-kernel top_k / take_along_axis) must
    select the exact same (idx, val) SET per block row as the sorted
    oracle — slot order is by index, so compare through the canonical
    form — with the gathered values bit for bit and ||g||^2 agreeing too.
    Edge data: a block shorter than one 128-lane tile, all selections
    packed into one tile, ties straddling a tile boundary, an all-zero
    row, subnormal and -0.0 values, and a fresh bank's all-zero (repeated)
    LBG positions."""
    from repro.kernels.lbgm_sparse import lbgm_sparse_decision_pallas
    if data == "normal":
        blocks = jax.random.normal(key, (nb, block))
        perm = jnp.argsort(jax.random.normal(jax.random.fold_in(key, 2),
                                             (nb, block)), axis=1)
        idx = perm[:, :kb].astype(jnp.int32)
    else:
        blocks, idx = _edge_rows(key, nb, block, kb, data)
    gg, gath, ti, tv = lbgm_sparse_decision_pallas(
        blocks, idx, interpret=True)
    rgg, rgath, rti, rtv = ref.lbgm_sparse_decision_ref(blocks, idx)
    bits = lambda a: np.asarray(a).view(np.int32)
    np.testing.assert_allclose(float(gg), float(rgg), rtol=1e-5)
    np.testing.assert_array_equal(bits(gath), bits(rgath))
    si, sv = ref.sort_topk_rows(ti, tv)
    ri, rv = ref.sort_topk_rows(rti, rtv)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    np.testing.assert_array_equal(bits(sv), bits(rv))
    # slot order: the entries above the kb-th |value| by index, then the
    # entries equal to it by index
    mag = bits(tv) & 0x7FFFFFFF
    for i, a in zip(np.asarray(ti), mag):
        above = a > a.min()
        assert not (np.diff(above.astype(int)) > 0).any()
        assert (np.diff(i[above]) > 0).all() and (np.diff(i[~above]) > 0).all()


def test_two_pass_kernel_tiny_magnitudes(key):
    """Regression: the bit-space bisection must resolve rows whose
    |values| are far below any absolute float resolution (a float-interval
    bisection left such rows entirely inside the tie band and selected by
    index instead of magnitude)."""
    from repro.kernels.lbgm_sparse import lbgm_sparse_decision_pallas
    for scale in (1e-20, 1e-35, 1e30):
        blocks = jax.random.normal(key, (2, 256)) * scale
        idx = jnp.tile(jnp.arange(4, dtype=jnp.int32)[None], (2, 1))
        _, gath, ti, tv = lbgm_sparse_decision_pallas(
            blocks, idx, interpret=True)
        _, rgath, rti, rtv = ref.lbgm_sparse_decision_ref(blocks, idx)
        np.testing.assert_array_equal(np.asarray(gath), np.asarray(rgath))
        si, sv = ref.sort_topk_rows(ti, tv)
        ri, rv = ref.sort_topk_rows(rti, rtv)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ri),
                                      err_msg=f"scale={scale}")
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))


def test_two_pass_kernel_degenerate_rows(key):
    """All-zero rows and rows with fewer nonzeros than kb: the threshold
    collapses to 0 and the tie-fill must keep every nonzero plus the
    lowest-index zeros — exactly lax.top_k's tie rule."""
    from repro.kernels.lbgm_sparse import lbgm_sparse_decision_pallas
    z = jnp.zeros((2, 300))
    z = z.at[1, 250].set(3.0).at[1, 7].set(-2.0)
    idx = jnp.tile(jnp.arange(5, dtype=jnp.int32)[None], (2, 1))
    gg, gath, ti, tv = lbgm_sparse_decision_pallas(
        z, idx, interpret=True)
    want = ref.lbgm_sparse_decision_ref(z, idx)
    np.testing.assert_array_equal(np.asarray(gath), np.asarray(want[1]))
    si, sv = ref.sort_topk_rows(ti, tv)
    ri, rv = ref.sort_topk_rows(want[2], want[3])
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))
    # the nonzeros at positions 7 and 250 both survive the index-order fill
    assert {7, 250} <= set(np.asarray(ti[1]).tolist())


def test_two_pass_vmap_and_env_knob(key):
    """vmap routes to the batched decision grid (rows tiled 8 at a time
    here: nb = 16), and the decision has one kernel — no env switch or
    variant argument picks another."""
    import inspect
    assert not hasattr(ops, "TWO_PASS_ENV")
    assert list(inspect.signature(ops.lbgm_sparse_decision).parameters) \
        == ["blocks", "idx", "interpret"]
    B, nb, block, kb = 3, 16, 256, 11
    blocks = jax.random.normal(key, (B, nb, block))
    idx = jnp.tile(jnp.arange(kb, dtype=jnp.int32)[None, None], (B, nb, 1))
    got = jax.vmap(lambda x, i: ops.lbgm_sparse_decision(
        x, i, interpret=True))(blocks, idx)
    for b in range(B):
        want = ref.lbgm_sparse_decision_ref(blocks[b], idx[b])
        np.testing.assert_allclose(float(got[0][b]), float(want[0]),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1][b]),
                                      np.asarray(want[1]))
        si, sv = ref.sort_topk_rows(got[2][b], got[3][b])
        ri, rv = ref.sort_topk_rows(want[2], want[3])
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))


def test_two_pass_step_level_agrees(key):
    """topk_step_core(fused=True) through the decision kernel: same
    accept/recycle decision and fp32-tolerance g_tilde vs the legacy
    step (bank sets match; element order inside a row may differ)."""
    k_frac = 0.1
    g = _rand_grad(key, SHAPES)
    lbg = lbgm_lib.init_topk_lbg(g, k_frac)
    _, lbg, _ = lbgm_lib.lbgm_topk_client_step(
        _rand_grad(jax.random.fold_in(key, 7), SHAPES), lbg, -1.0, k_frac)
    gt_a, _, st_a = lbgm_lib.lbgm_topk_client_step(g, lbg, 0.5, k_frac)
    gt_b, _, st_b = lbgm_lib.lbgm_topk_client_step(g, lbg, 0.5, k_frac,
                                                   fused=True)
    assert bool(st_a.sent_scalar) == bool(st_b.sent_scalar)
    np.testing.assert_allclose(float(st_a.sin2), float(st_b.sin2),
                               rtol=1e-4, atol=1e-6)
    for name in g:
        np.testing.assert_allclose(np.asarray(gt_a[name]),
                                   np.asarray(gt_b[name]),
                                   rtol=1e-5, atol=1e-7)
