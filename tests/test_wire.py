"""Wire codec subsystem tests (repro.comm.wire + engine integration).

Five pillars:
  (a) codec primitives against pure-NumPy oracles: stochastic-rounding
      unbiasedness, int8/fp8 nearest-rounding error bounds, exact
      requantization idempotency (the power-of-two-scale property the
      deployment-faithfulness argument rests on), and the varint-delta
      index byte count (incl. degenerate kb=1 rows and pad rows),
  (b) ``codec="none"`` is the pre-codec engine bit-for-bit: round
      histories on all three schedulers match the golden fixture captured
      at the pre-codec revision (tests/golden/engine_history_pre_codec
      .json), and ``delta_idx`` only changes the byte metric,
  (c) the dequantizing scatter-accumulate the quantized aggregator folds
      with matches a NumPy oracle, and equals the unquantized fold of the
      decoded payloads bit for bit,
  (d) quantized engine runs: scheduler equivalence, wire-byte math vs
      hand-computed oracles, the >= 3x int8-over-fp32-LBGM byte-reduction
      contract at matched accuracy, CommLedger bookkeeping, and the
      actionable config errors (lossy + dense bank, scalar_median without
      the sparse path, unknown codec, bad codec_kw),
  (e) the ``scalar_median`` O(K) robust rule: weighted-median oracle and
      agreement with the geometric median on rank-1 payload stacks.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.accounting import CommLedger
from repro.comm.wire import (E4M3_MAX, WIRE_KEY, Fp8Codec, Int8Codec,
                             codec_rng, delta_idx_bytes, e4m3_nearest,
                             make_codec, pow2_scale, stochastic_round)
from repro.fed import FLConfig, FLEngine
from repro.fed.registry import CODECS
from repro.fed.robust import GeometricMedian, ScalarMedian, \
    ScalarMedianSparseAggregator
from repro.kernels.ref import lbgm_dequant_accum_ref

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "engine_history_pre_codec.json")

# --------------------------------------------------------------- fixtures


@pytest.fixture(scope="module")
def fcn_setup():
    from repro.configs import get_config
    from repro.data.synthetic import mixture_classification
    from repro.models.smallnets import (apply_fcn, classifier_loss,
                                        init_fcn)
    cfg = get_config("paper-fcn")
    params, _ = init_fcn(jax.random.PRNGKey(0), cfg)
    x, y = mixture_classification(1200, 10, seed=0)
    loss_fn = lambda p, b: classifier_loss(apply_fcn, p, cfg,
                                           b["x"], b["y"])
    return params, x, y, loss_fn


def make_engine(fcn_setup, K=6, **flkw):
    from repro.fed import partition_label_skew
    params, x, y, loss_fn = fcn_setup
    parts = partition_label_skew(y, K, 3, seed=0)
    data = [{"x": x[p], "y": y[p]} for p in parts]
    return FLEngine(loss_fn, params, data,
                    FLConfig(num_clients=K, tau=2, lr=0.05, batch_size=16,
                             **flkw))


def run_rounds(fl, n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [fl.run_round(rng) for _ in range(n)]


#: the exact FLConfig kwargs the golden fixture was generated with
GOLDEN_BASE = dict(use_lbgm=True, delta_threshold=0.2, sample_frac=0.7)
GOLDEN_SCHED = {
    "vmap": dict(scheduler="vmap"),
    "chunked": dict(scheduler="chunked", chunk_size=4),
    "sharded": dict(scheduler="sharded", chunk_size=4,
                    lbg_variant="topk-sharded", lbg_kw={"k_frac": 0.25}),
}

#: sparse top-k payload configs (the quantized codecs' home turf)
TOPK_SCHED = {
    "vmap": dict(scheduler="vmap", lbg_variant="topk",
                 lbg_kw={"k_frac": 0.25}),
    "chunked": dict(scheduler="chunked", chunk_size=4, lbg_variant="topk",
                    lbg_kw={"k_frac": 0.25}),
    "sharded": dict(scheduler="sharded", chunk_size=4,
                    lbg_variant="topk-sharded", lbg_kw={"k_frac": 0.25}),
}


# ------------------------------------------- (a) primitive vs NumPy oracle


def test_stochastic_round_unbiased_and_integer_fixed():
    rng = np.random.RandomState(0)
    f = jnp.asarray(rng.randn(64).astype(np.float32) * 7)
    u = jnp.asarray(rng.rand(4000, 64).astype(np.float32))
    q = stochastic_round(f, u)                     # broadcast over draws
    assert np.array_equal(np.asarray(q), np.floor(np.asarray(q)))
    frac = np.asarray(f) - np.floor(np.asarray(f))
    sigma = np.sqrt(np.maximum(frac * (1 - frac), 1e-12) / 4000)
    np.testing.assert_array_less(
        np.abs(np.asarray(q.mean(0)) - np.asarray(f)), 5 * sigma + 1e-6)
    # exact integers are fixed points for EVERY draw
    ints = jnp.asarray(np.arange(-5, 6, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(stochastic_round(ints, u[:, :11])),
        np.broadcast_to(np.asarray(ints), (4000, 11)))


def test_pow2_scale_oracle():
    m = jnp.asarray([0.0, 1e-9, 0.5, 127.0, 128.0, 1e4], jnp.float32)
    s = np.asarray(pow2_scale(m, 127.0))
    for mi, si in zip(np.asarray(m), s):
        if mi == 0:
            assert si == 1.0
        else:
            assert si == 2.0 ** np.ceil(np.log2(mi / 127.0))
            assert mi / si <= 127.0 and mi / (si / 2) > 127.0 * (1 - 1e-6)


@pytest.mark.parametrize("codec_cls,max_rel", [(Int8Codec, 1.0 / 127.0),
                                               (Fp8Codec, 1.0 / 16.0)])
def test_nearest_quantization_error_bound(codec_cls, max_rel):
    """Nearest rounding: per-row error <= half the worst grid step, i.e.
    int8: scale/2 <= rowmax/127; fp8 e4m3: rel error <= 2^-4 in-binade."""
    rng = np.random.RandomState(1)
    val = jnp.asarray(rng.randn(16, 128).astype(np.float32) * 3)
    codec = codec_cls(stochastic=False)
    q, scale = codec.quantize(val, None)
    dq = np.asarray(codec.decode_leaf(
        {"idx": None, "val": q, "scale": scale}))
    rowmax = np.max(np.abs(np.asarray(val)), axis=-1, keepdims=True)
    assert np.all(np.abs(dq - np.asarray(val)) <= rowmax * max_rel + 1e-7)


@pytest.mark.parametrize("codec_cls", [Int8Codec, Fp8Codec])
@pytest.mark.parametrize("stochastic", [True, False])
def test_requantization_idempotent(codec_cls, stochastic):
    """dequant(quant(v)) is a fixed point of quant-dequant — exactly.

    This is the deployment-faithfulness property: the bank holds grid
    values, and re-encoding them every round (as the payload path does)
    must reproduce them bit-for-bit under ANY rounding seed."""
    rng = np.random.RandomState(2)
    val = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    codec = codec_cls(stochastic=stochastic)
    key = jax.random.PRNGKey(0) if stochastic else None
    q, scale = codec.quantize(val, key)
    v1 = codec.decode_leaf({"idx": None, "val": q, "scale": scale})
    for seed in (1, 2, 3):
        key2 = jax.random.PRNGKey(seed) if stochastic else None
        q2, scale2 = codec.quantize(v1, key2)
        v2 = codec.decode_leaf({"idx": None, "val": q2, "scale": scale2})
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_e4m3_nearest_saturates_and_hits_grid():
    x = jnp.asarray([0.0, 1.0, 447.0, 449.0, 1e6, -1e6, 0.3], jnp.float32)
    out = np.asarray(e4m3_nearest(x))
    assert out[3] == E4M3_MAX and out[4] == E4M3_MAX
    assert out[5] == -E4M3_MAX
    # grid values survive a second pass exactly
    np.testing.assert_array_equal(out, np.asarray(e4m3_nearest(out)))


def np_varint_bytes(idx):
    """Hand-computed varint-delta byte count (the wire-format oracle)."""
    total = 0
    for row in np.asarray(idx).reshape(-1, idx.shape[-1]):
        prev = 0
        for v in np.sort(row):
            d = int(v) - prev
            total += 1 if d < (1 << 7) else (2 if d < (1 << 14) else 3)
            prev = int(v)
    return float(total)


@pytest.mark.parametrize("shape,high", [((6, 17), 1 << 15), ((4, 1), 9000),
                                        ((1, 64), 200), ((3, 5), 1 << 16)])
def test_delta_idx_bytes_matches_numpy_oracle(shape, high):
    rng = np.random.RandomState(3)
    idx = rng.randint(0, high, size=shape).astype(np.int32)
    got = float(delta_idx_bytes(jnp.asarray(idx)))
    assert got == np_varint_bytes(idx)


def test_delta_idx_bytes_degenerate_and_pad_rows():
    # kb = 1: exactly one varint per row (the first index, delta from 0)
    one = jnp.asarray([[5], [200], [40000]], jnp.int32)
    assert float(delta_idx_bytes(one)) == 1 + 2 + 3
    # pad rows (iota indices, the phantom-client payload): all deltas are
    # 1 -> 1 byte each, same as the NumPy oracle prices them
    pad = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (4, 32))
    assert float(delta_idx_bytes(pad)) == np_varint_bytes(np.asarray(pad))
    assert float(delta_idx_bytes(pad)) == 4 * 32


def test_codec_registry_and_kw_errors():
    assert set(CODECS.names()) >= {"none", "delta_idx", "int8", "fp8"}
    cfg = FLConfig(num_clients=2, codec="int8",
                   codec_kw={"stochastic": False})
    codec = make_codec(cfg)
    assert codec.lossy and not codec.stochastic
    with pytest.raises(ValueError, match="zstd"):
        FLConfig(num_clients=2, codec="zstd")
    with pytest.raises(ValueError, match="codec_kw"):
        make_codec(FLConfig(num_clients=2, codec="int8",
                            codec_kw={"bogus": 1}))
    # JSON round-trip carries the codec knobs
    cfg2 = FLConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert cfg2.codec == "int8" and cfg2.codec_kw == {"stochastic": False}


def test_codec_rng_dedicated_stream():
    a, b = codec_rng(0), codec_rng(0)
    assert np.array_equal(a.randint(0, 2 ** 31 - 1, 8),
                          b.randint(0, 2 ** 31 - 1, 8))
    assert not np.array_equal(codec_rng(0).randint(0, 2 ** 31 - 1, 8),
                              codec_rng(1).randint(0, 2 ** 31 - 1, 8))


def test_commledger_byte_math_oracle():
    led = CommLedger()
    led.record(10.0, 100.0, wire=40.0, vanilla_wire=400.0)
    led.record(1.0, 100.0, wire=1.0, vanilla_wire=400.0)
    assert led.rounds == 2
    assert led.uplink_floats == 11.0 and led.vanilla_floats == 200.0
    assert led.wire_bytes == 41.0 and led.vanilla_wire_bytes == 800.0
    assert led.savings == 1.0 - 11.0 / 200.0
    assert led.wire_savings == 1.0 - 41.0 / 800.0
    assert led.per_round[1] == {"uplink": 1.0, "vanilla": 100.0,
                                "wire": 1.0, "vanilla_wire": 400.0}
    s = led.summary()
    assert s["wire_bytes"] == 41.0 and s["wire_savings"] == led.wire_savings
    assert CommLedger().wire_savings == 0.0


# ------------------------- (b) codec="none" bit-for-bit vs golden fixture


@pytest.mark.parametrize("sched", sorted(GOLDEN_SCHED))
def test_codec_none_bit_for_bit_with_pre_codec_history(fcn_setup, sched):
    """The default codec reproduces the round histories captured at the
    revision BEFORE the codec subsystem existed, float-exact, on all
    three schedulers (the fixture stores float.hex strings)."""
    with open(GOLDEN) as f:
        golden = json.load(f)[sched]
    fl = make_engine(fcn_setup, **GOLDEN_BASE, **GOLDEN_SCHED[sched])
    hist = run_rounds(fl, n=len(golden))
    for r, (h, gh) in enumerate(zip(hist, golden)):
        for k, v in gh.items():
            assert float.fromhex(v) == h[k], (sched, r, k)


def test_delta_idx_only_changes_byte_metric(fcn_setup):
    """Lossless index compression: every pre-existing history number is
    bit-equal to codec='none'; only wire_bytes shrinks (equal on a pure
    scalar round, where neither codec ships indices)."""
    kw = TOPK_SCHED["chunked"]
    h0 = run_rounds(make_engine(fcn_setup, **GOLDEN_BASE, **kw))
    h1 = run_rounds(make_engine(fcn_setup, codec="delta_idx",
                                **GOLDEN_BASE, **kw))
    for a, b in zip(h0, h1):
        for k in ("loss", "uplink_floats", "frac_scalar", "total_uplink",
                  "vanilla_uplink", "savings"):
            assert a[k] == b[k], k
        assert b["wire_bytes"] <= a["wire_bytes"]
    assert h1[-1]["total_wire_bytes"] < h0[-1]["total_wire_bytes"]


# ------------------------------------ (c) dequant-accumulate vs oracles


def _quantized_payload(wire_dtype, seed, C=5, nb=4, kb=8, block=32):
    """Random quantized sparse payload rows with one phantom client (w=0,
    NaN gscale/values — the w > 0 gate must keep them out)."""
    rng = np.random.RandomState(seed)
    acc = jnp.asarray(rng.randn(nb, block).astype(np.float32))
    w = jnp.asarray(rng.rand(C).astype(np.float32))
    w = w.at[seed % C].set(0.0)                 # a phantom client
    gscale = jnp.asarray(rng.rand(C).astype(np.float32))
    gscale = gscale.at[seed % C].set(np.nan)
    idx = jnp.asarray(
        np.stack([np.stack([rng.choice(block, kb, replace=False)
                            for _ in range(nb)]) for _ in range(C)])
        .astype(np.int32))
    val = rng.randn(C, nb, kb).astype(np.float32)
    val[seed % C] = np.nan
    codec = (Int8Codec if wire_dtype == "int8" else Fp8Codec)(
        stochastic=False)
    qv, scale = jax.vmap(lambda v: codec.quantize(v, None))(
        jnp.asarray(np.nan_to_num(val)))
    if wire_dtype == "fp8":
        qv = qv.at[seed % C].set(jnp.nan)       # NaN survives e4m3
    return codec, acc, w, gscale, idx, qv, scale


@pytest.mark.parametrize("wire_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("seed", range(3))
def test_dequant_accum_kernel_matches_ref(wire_dtype, seed):
    """The quantized aggregator's fold (``lbgm_dequant_accum_ref``, its one
    path on every backend) against a NumPy client-by-client oracle."""
    _, acc, w, gscale, idx, qv, scale = _quantized_payload(wire_dtype, seed)
    out = lbgm_dequant_accum_ref(acc, w, gscale, idx, qv, scale)
    want = np.array(acc)
    rows = np.arange(want.shape[0])[:, None]
    q32 = np.asarray(qv.astype(jnp.float32))
    for c in range(len(w)):
        if float(w[c]) > 0:
            coeff = (np.float32(w[c]) * np.float32(gscale[c])) * \
                np.asarray(scale[c])
            want[rows, np.asarray(idx[c])] += coeff * q32[c]
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("wire_dtype", ["int8", "fp8"])
def test_engine_fused_dequant_accum_bit_equals_xla(fcn_setup, wire_dtype):
    """The quantized streaming fold (widen + power-of-two scale inside the
    fold kernel) equals the unquantized fold of the decoded payloads — the
    products are identical, only XLA's FMA contraction of the running sum
    may differ by an ulp where clients share a position — and an engine
    with that codec folds through it."""
    from repro.core.lbgm import _block_layout
    from repro.fed.engine import SparseCodecAggregator, SparseTopKAggregator
    params = fcn_setup[0]
    k_frac = 0.25
    q_agg = SparseCodecAggregator(params, k_frac)
    f_agg = SparseTopKAggregator(params, k_frac)
    send_q, send_f = {}, {}
    for name, leaf in params.items():
        nb, block, kb = _block_layout(int(leaf.size), k_frac)
        # one seed for every leaf: the same client is the phantom in all
        codec, _, w, gscale, idx, qv, scale = _quantized_payload(
            wire_dtype, 0, C=5, nb=nb, kb=kb, block=block)
        send_q[name] = {"idx": idx, "val": qv, "scale": scale}
        send_f[name] = {"idx": idx, "val": jax.vmap(codec.decode_leaf)(
            send_q[name])}
    # each fold on its own carry layout, compared in the params layout
    got = q_agg.finalize(q_agg.accumulate(q_agg.init(params), w,
                                          (send_q, gscale)))
    want = f_agg.finalize(f_agg.accumulate(f_agg.init(params), w,
                                           (send_f, gscale)))
    for name in params:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=1e-6,
                                   atol=1e-7, err_msg=name)
    fl = make_engine(fcn_setup, codec=wire_dtype, **GOLDEN_BASE,
                     **TOPK_SCHED["vmap"])
    assert type(fl.agg) is SparseCodecAggregator
    assert all(np.isfinite(e["loss"]) for e in run_rounds(fl, n=2))


# ----------------------------------------- (d) quantized engine contracts


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_quantized_schedulers_agree(fcn_setup, codec):
    """vmap and chunked (same topk store, pure layout change) produce
    bit-identical quantized histories — the codec seam composes with the
    execution layout; the sharded/topk-sharded path (a different bank
    layout, so a different but valid trajectory) converges too."""
    h_v = run_rounds(make_engine(fcn_setup, codec=codec, **GOLDEN_BASE,
                                 **TOPK_SCHED["vmap"]))
    h_c = run_rounds(make_engine(fcn_setup, codec=codec, **GOLDEN_BASE,
                                 **TOPK_SCHED["chunked"]))
    assert h_v == h_c
    h_s = run_rounds(make_engine(fcn_setup, codec=codec, **GOLDEN_BASE,
                                 **TOPK_SCHED["sharded"]))
    assert all(np.isfinite(e["loss"]) for e in h_v + h_s)
    assert h_s[-1]["total_wire_bytes"] > 0


def test_vanilla_dense_int8_wire_byte_oracle(fcn_setup):
    """use_lbgm=False + int8: every participant ships M 1-byte values +
    one 4-byte scale per leaf; hand-computed bytes match exactly."""
    fl = make_engine(fcn_setup, codec="int8", use_lbgm=False,
                     sample_frac=1.0)
    h = run_rounds(fl, n=2)
    M = sum(int(p.size) for p in fl.params.values())
    L = len(fl.params)
    K = fl.cfg.num_clients
    for e in h:
        assert e["wire_bytes"] == K * (M + 4 * L)
    assert h[-1]["total_wire_bytes"] == 2 * K * (M + 4 * L)
    expect_savings = 1.0 - (M + 4 * L) / (4.0 * M)
    assert abs(h[-1]["wire_savings"] - expect_savings) < 1e-9


def test_sparse_none_full_round_wire_byte_oracle(fcn_setup):
    """codec='none' full rounds on the top-k store price the (fp32 value,
    raw int32 index) pair: 8 bytes per kept entry, padded block layout."""
    from repro.core.lbgm import _block_layout
    fl = make_engine(fcn_setup, **dict(GOLDEN_BASE, sample_frac=1.0),
                     **TOPK_SCHED["vmap"])
    h = run_rounds(fl, n=1)          # round 1 is a full round everywhere
    expect = 0.0
    for p in fl.params.values():
        nb, _, kb = _block_layout(int(p.size), 0.25)
        expect += 8.0 * kb * nb       # 4B fp32 value + 4B raw int32 index
    assert h[0]["frac_scalar"] == 0.0
    assert h[0]["wire_bytes"] == fl.cfg.num_clients * expect


def test_int8_beats_fp32_lbgm_by_3x(fcn_setup):
    """The PR's acceptance contract at test scale: int8 wire bytes are
    >= 3x smaller than fp32 LBGM wire bytes on the same run."""
    kw = dict(sample_frac=1.0, **{k: v for k, v in GOLDEN_BASE.items()
                                  if k != "sample_frac"})
    base = run_rounds(make_engine(fcn_setup, **kw, **TOPK_SCHED["chunked"]))
    q = run_rounds(make_engine(fcn_setup, codec="int8", **kw,
                               **TOPK_SCHED["chunked"]))
    ratio = base[-1]["total_wire_bytes"] / q[-1]["total_wire_bytes"]
    assert ratio >= 3.0, ratio
    assert abs(base[-1]["loss"] - q[-1]["loss"]) < 0.05


def test_scalar_round_wire_is_one_byte_quantized(fcn_setup):
    """Force recycle rounds (huge delta threshold after warmup): each
    participant's wire cost collapses to scalar_bytes (1 for int8)."""
    fl = make_engine(fcn_setup, codec="int8", use_lbgm=True,
                     delta_threshold=50.0, sample_frac=1.0,
                     **TOPK_SCHED["vmap"])
    h = run_rounds(fl, n=3)
    assert h[-1]["frac_scalar"] == 1.0
    assert h[-1]["wire_bytes"] == fl.cfg.num_clients * 1.0


def test_lossy_codec_requires_sparse_or_vanilla(fcn_setup):
    with pytest.raises(ValueError, match="lossy"):
        make_engine(fcn_setup, codec="int8", **GOLDEN_BASE)  # dense bank
    # lossless codec on the dense bank is fine
    make_engine(fcn_setup, codec="delta_idx", **GOLDEN_BASE)


def test_deterministic_codec_draws_no_seeds(fcn_setup):
    """codec_kw={'stochastic': False} must not put WIRE_KEY in the batch
    (rng-stream contract: deterministic codecs leave every stream
    untouched)."""
    fl = make_engine(fcn_setup, codec="int8",
                     codec_kw={"stochastic": False}, **GOLDEN_BASE,
                     **TOPK_SCHED["vmap"])
    batch = fl._sample_batches(np.random.RandomState(0))
    assert WIRE_KEY not in batch
    fl2 = make_engine(fcn_setup, codec="int8", **GOLDEN_BASE,
                      **TOPK_SCHED["vmap"])
    batch2 = fl2._sample_batches(np.random.RandomState(0))
    assert WIRE_KEY in batch2
    run_rounds(fl, n=2)              # and the deterministic path runs


def test_collect_sparse_decodes_quantized_payloads(fcn_setup):
    """Robust collect rules compose with a lossy codec (decode seam)."""
    h = run_rounds(make_engine(fcn_setup, codec="int8",
                               aggregator="geometric_median",
                               **GOLDEN_BASE, **TOPK_SCHED["chunked"]))
    assert all(np.isfinite(e["loss"]) for e in h)


# ----------------------------------------------------- (e) scalar_median


def np_weighted_median(w, gs):
    gs = np.where(w > 0, gs, 0.0).astype(np.float64)
    order = np.argsort(gs, kind="stable")
    v, ws = gs[order], w.astype(np.float64)[order]
    cum = np.cumsum(ws)
    return v[int(np.argmax(cum >= 0.5 * w.sum()))]


@pytest.mark.parametrize("seed", range(5))
def test_scalar_median_matches_numpy_oracle(seed):
    rng = np.random.RandomState(seed)
    K = 9
    w = rng.rand(K).astype(np.float32)
    w[seed % K] = 0.0
    w /= w.sum()
    gs = rng.randn(K).astype(np.float32) * 3
    gs[seed % K] = np.nan            # phantom client: masked by w > 0
    med = float(ScalarMedian().median(jnp.asarray(w), jnp.asarray(gs)))
    assert med == np.float32(np_weighted_median(w, gs))


def test_scalar_median_equals_geometric_median_on_rank1():
    """On rank-1 payload stacks (all clients share one bank direction,
    scaled by their rho), the geometric median IS the weighted-median
    scalar times the direction — the two rules agree to Weiszfeld
    tolerance, at O(K) vs O(K*M) cost."""
    from repro.core.lbgm import _block_layout
    rng = np.random.RandomState(7)
    K = 9
    params = {"w": jnp.asarray(rng.randn(11, 13).astype(np.float32))}
    k_frac = 0.3
    nb, block, kb = _block_layout(11 * 13, k_frac)
    idx = np.stack([np.sort(rng.choice(block, kb, replace=False))
                    for _ in range(nb)]).astype(np.int32)
    val = rng.randn(nb, kb).astype(np.float32)
    w = rng.rand(K).astype(np.float32)
    w /= w.sum()
    rho = (rng.rand(K) * 4 - 1).astype(np.float32)
    send = {"w": {"idx": jnp.broadcast_to(jnp.asarray(idx), (K, nb, kb)),
                  "val": jnp.broadcast_to(jnp.asarray(val), (K, nb, kb))}}
    gscale = jnp.asarray(rho)
    sm = ScalarMedianSparseAggregator(ScalarMedian(), params, k_frac)
    out_sm = sm.reduce(jnp.asarray(w), (send, gscale))
    from repro.fed.robust import CollectSparseAggregator
    # plenty of iterations: this seed's weight masses nearly balance at
    # the median (cum hits 0.4988 just below it), which is Weiszfeld's
    # slowest regime
    gm = CollectSparseAggregator(GeometricMedian(iters=1000, eps=1e-9),
                                 params, k_frac)
    out_gm = gm.reduce(jnp.asarray(w), (send, gscale))
    np.testing.assert_allclose(np.asarray(out_sm["w"]),
                               np.asarray(out_gm["w"]),
                               rtol=2e-3, atol=2e-3)


def test_scalar_median_engine_runs_and_needs_sparse_path(fcn_setup):
    for kw in (TOPK_SCHED["vmap"], TOPK_SCHED["sharded"]):
        h = run_rounds(make_engine(fcn_setup, codec="int8",
                                   aggregator="scalar_median",
                                   **GOLDEN_BASE, **kw))
        assert all(np.isfinite(e["loss"]) for e in h)
    with pytest.raises(ValueError, match="scalar"):
        make_engine(fcn_setup, aggregator="scalar_median", **GOLDEN_BASE)


# -------------------------------------------------- experiment/bench glue


def test_experiment_history_carries_wire_keys(fcn_setup):
    from benchmarks.common import build_spec, spec_metadata
    spec = build_spec(num_clients=4, n_data=320, n_eval=80, codec="int8",
                      use_lbgm=True, delta_threshold=0.2,
                      lbg_variant="topk", lbg_kw={"k_frac": 0.25})
    from repro.fed import run_experiment
    res = run_experiment(spec, rounds=2)
    for rec in res.records:
        assert rec.wire_bytes > 0 and rec.total_wire_bytes > 0
    assert res.history[-1]["wire_savings"] == res.records[-1].wire_savings
    meta = spec_metadata(spec)
    assert meta["codec"] == "int8" and "kernel_variant" not in meta
