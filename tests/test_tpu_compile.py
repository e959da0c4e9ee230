"""Compile rehearsals for a TPU v5e that is described, not attached.

The Pallas kernels of the federated round's main path (and the quantized
fold's XLA program, fp8 included — v5e has no native fp8) at the widths the
chip smoke test runs, compiled by the TPU compiler installed with JAX. A
block spec or body the chip's compiler refuses fails here, at no chip time.
Interpret-mode tests cannot see those faults.

The topology is described inside a module-scoped fixture (never at import
time), so every pytest-xdist worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.wire import Fp8Codec, Int8Codec
from repro.kernels.lbgm_projection import lbgm_projection_batched_pallas
from repro.kernels.lbgm_sparse import lbgm_sparse_decision_batched_pallas
from repro.kernels.ref import lbgm_dequant_accum_ref

#: clients per scan step of the chip smoke test's LM round
CHUNK = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep it out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _calls(hlo, kernel):
    return [l for l in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in l and kernel in l]


@pytest.mark.parametrize("nb,block,kb", [
    (192, 65536, 655),     # one 2048x6144 leaf, k_frac = 0.01
    (1, 2048, 20),         # one d_model norm leaf
    (52, 65536, 655),      # one model rank's rows of a 208-row leaf, m = 4
])
def test_decision_kernel_compiles_for_v5e(one_chip, nb, block, kb):
    hlo = _compile(
        lambda g, i: lbgm_sparse_decision_batched_pallas(g, i,
                                                         interpret=False),
        one_chip, ((CHUNK, nb, block), jnp.float32),
        ((CHUNK, nb, kb), jnp.int32))
    assert len(_calls(hlo, "lbgm_sparse_decision")) == 1


@pytest.mark.parametrize("n", [784 * 200, 200, 200 * 10, 10])
def test_projection_kernel_compiles_for_v5e(one_chip, n):
    """The paper FCN's leaves, batched over its 20 vmap'd clients."""
    hlo = _compile(
        lambda g, l: lbgm_projection_batched_pallas(g, l, interpret=False),
        one_chip, ((20, n), jnp.float32), ((20, n), jnp.float32))
    assert len(_calls(hlo, "lbgm_projection")) == 1


@pytest.mark.parametrize("codec_cls", [Int8Codec, Fp8Codec])
def test_quantized_fold_compiles_for_v5e(one_chip, codec_cls):
    """Quantize one chunk's payload rows to the wire dtype and fold them
    with the dequantizing scatter-accumulate, at the int8 layout of a
    2048x6144 leaf."""
    codec = codec_cls(stochastic=True)
    nb, block, kb = 192, 65536, 655

    def fold(acc, w, gs, idx, val, key):
        qv, scale = jax.vmap(codec.quantize)(
            val, jax.random.split(key, CHUNK))
        return lbgm_dequant_accum_ref(acc, w, gs, idx, qv, scale)

    hlo = _compile(fold, one_chip, ((nb, block), jnp.float32),
                   ((CHUNK,), jnp.float32), ((CHUNK,), jnp.float32),
                   ((CHUNK, nb, kb), jnp.int32),
                   ((CHUNK, nb, kb), jnp.float32), ((2,), jnp.uint32))
    assert "tpu_custom_call" not in hlo
