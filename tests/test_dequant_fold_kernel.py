"""The quantized fold kernel (``lbgm_dequant_fold_pallas``, interpret mode
on CPU) against its oracle, ``kernels.ref.lbgm_dequant_accum_ref``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.wire import Fp8Codec, Int8Codec
from repro.kernels.lbgm_sparse import (fold_carry_shape,
                                       lbgm_dequant_fold_pallas)
from repro.kernels.ref import lbgm_dequant_accum_ref

#: the oracle test's tolerance (tests/test_wire.py): the kernel's sum may
#: be contracted into a fused multiply-add where the oracle's is not
RTOL, ATOL = 1e-6, 1e-7

LAYOUTS = {                      # (C, nb, block, kb)
    "wide-52-rows": (2, 52, 65536, 655),    # nb not a multiple of 8
    "one-row-2048": (5, 1, 2048, 20),
    "under-128-lanes": (1, 3, 96, 30),
    "kb-near-block": (2, 8, 256, 250),
}


def _payload(codec, C, nb, block, kb, seed=0):
    """A random chunk: carry, weights (client 0 a phantom: w = 0 with a
    NaN gscale), unique positions per row, quantized values."""
    rng = np.random.default_rng(seed)
    acc = rng.standard_normal((nb, block)).astype(np.float32)
    w = rng.random(C).astype(np.float32)
    gscale = rng.random(C).astype(np.float32)
    if C > 1:
        w[0], gscale[0] = 0.0, np.nan
    idx = np.stack([np.stack([rng.choice(block, kb, replace=False)
                              for _ in range(nb)]) for _ in range(C)])
    val = rng.standard_normal((C, nb, kb)).astype(np.float32)
    val[:, :, :2] = 0.0                          # selected zeros
    cod = (Int8Codec if codec == "int8" else Fp8Codec)(stochastic=False)
    qv, scale = jax.vmap(lambda v: cod.quantize(v, None))(jnp.asarray(val))
    return (jnp.asarray(acc), jnp.asarray(w), jnp.asarray(gscale),
            jnp.asarray(idx.astype(np.int32)), qv, scale)


def _fold(acc, *rest):
    """The kernel on the (nb, block) view the oracle takes."""
    nb, block = acc.shape
    _, nt, tile = fold_carry_shape(nb, block)
    carry = jnp.pad(acc, ((0, 0), (0, nt * tile - block))).reshape(
        nb, nt, tile)
    out = lbgm_dequant_fold_pallas(carry, *rest)
    return np.asarray(out).reshape(nb, -1)[:, :block]


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_fold_matches_ref(codec, layout):
    args = _payload(codec, *LAYOUTS[layout])
    got = _fold(*args)
    want = np.asarray(lbgm_dequant_accum_ref(*args))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    # positions no client with w > 0 selected keep their bits
    acc, w, _, idx = (np.asarray(a) for a in args[:4])
    hit = np.zeros(acc.shape, bool)
    rows = np.arange(acc.shape[0])[:, None]
    for c in np.nonzero(w > 0)[0]:
        hit[rows, idx[c]] = True
    np.testing.assert_array_equal(got[~hit].view(np.int32),
                                  acc[~hit].view(np.int32))


@pytest.mark.parametrize("layout", ["wide-52-rows", "one-row-2048"])
def test_fold_phantom_nan_leaves_no_trace(layout):
    """A phantom client's (w = 0) fp8 NaN values never reach the carry."""
    acc, w, gscale, idx, qv, scale = _payload("fp8", *LAYOUTS[layout])
    qv = qv.at[0].set(jnp.nan)
    got = _fold(acc, w, gscale, idx, qv, scale)
    assert np.all(np.isfinite(got))
    want = np.asarray(lbgm_dequant_accum_ref(acc, w, gscale, idx, qv,
                                             scale))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("layout", ["wide-52-rows", "one-row-2048",
                                    "under-128-lanes"])
def test_fold_real_nan_lands_only_at_its_positions(layout):
    """A real client's NaN lands at its own positions, as the oracle's
    does, and nowhere else: not across a tile's column."""
    acc, w, gscale, idx, qv, scale = _payload("fp8", *LAYOUTS[layout])
    c = int(jnp.argmax(w))
    qv = qv.at[c, 0, 3].set(jnp.nan).at[c, -1, -1].set(jnp.nan)
    got = _fold(acc, w, gscale, idx, qv, scale)
    want = np.asarray(lbgm_dequant_accum_ref(acc, w, gscale, idx, qv,
                                             scale))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    assert np.isnan(want).sum() == 2
    ok = ~np.isnan(want)
    np.testing.assert_allclose(got[ok], want[ok], rtol=RTOL, atol=ATOL)


def test_fold_never_filled_bank_rows_read_as_zero():
    """A bank never filled sends kb copies of position 0 with value 0 (and
    a scalar round may send it): the oracle adds exact zeros there, and so
    must the kernel, though its expansion sums the slots' codes."""
    acc, w, gscale, idx, qv, scale = _payload("int8", *LAYOUTS["kb-near-"
                                                               "block"])
    idx = idx.at[1].set(0)
    qv = qv.at[1].set(0)
    got = _fold(acc, w, gscale, idx, qv, scale)
    want = np.asarray(lbgm_dequant_accum_ref(acc, w, gscale, idx, qv,
                                             scale))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_fold_refuses_other_wire_dtypes():
    acc, w, gscale, idx, qv, scale = _payload("int8", *LAYOUTS["one-row-"
                                                               "2048"])
    with pytest.raises(TypeError, match="int8 or e4m3"):
        _fold(acc, w, gscale, idx, qv.astype(jnp.bfloat16), scale)
