"""Compile rehearsals for a TPU v5e that is described, not attached.

The Pallas kernels of the federated round's main path (the quantized fold
fp8 included — v5e has no native fp8) at the widths the chip smoke test
runs, compiled by the TPU compiler installed with JAX. A
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
from repro.kernels.lbgm_sparse import (lbgm_dequant_fold_pallas,
                                       lbgm_sparse_decision_batched_pallas)

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
    (608, 65536, 639),     # the 18992x2048 embedding (594 rows hold data)
    (8, 256, 250),         # one tile, kb close to the block
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
    with the dequantizing fold kernel, at the int8 layout of a 2048x6144
    leaf."""
    codec = codec_cls(stochastic=True)
    nb, block, kb = 192, 65536, 655

    def fold(acc, w, gs, idx, val, key):
        qv, scale = jax.vmap(codec.quantize)(
            val, jax.random.split(key, CHUNK))
        return lbgm_dequant_fold_pallas(acc, w, gs, idx, qv, scale,
                                        interpret=False)

    hlo = _compile(fold, one_chip, ((nb, 256, 256), jnp.float32),
                   ((CHUNK,), jnp.float32), ((CHUNK,), jnp.float32),
                   ((CHUNK, nb, kb), jnp.int32),
                   ((CHUNK, nb, kb), jnp.float32), ((2,), jnp.uint32))
    assert len(_calls(hlo, "lbgm_dequant_fold")) == 1


@pytest.mark.parametrize("shape,chunk", [
    ((2048, 6144), CHUNK),      # qwen3-1.7b: nb 192, kb 655, the int8 cell
    ((4, 8, 2048, 768), 1),     # kanana-2-30b-a3b-5l's stacked held experts:
], ids=["qwen3-2048x6144", "kanana-experts"])  # nb 768, kb 655, chunk 1
def test_codec_fold_compiles_in_place_for_v5e(one_chip, monkeypatch, shape,
                                              chunk):
    """``SparseCodecAggregator.accumulate`` on one leaf compiles to one fold
    kernel call that updates the donated carry in place: no second
    carry-sized buffer."""
    from repro.core.lbgm import _block_layout
    from repro.fed.engine import SparseCodecAggregator
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    params = {"w": jax.ShapeDtypeStruct(shape, jnp.float32)}
    agg = SparseCodecAggregator(params, 0.01)
    nb, block, kb = _block_layout(params["w"].size, 0.01)
    carry = jax.eval_shape(agg.init, params)["w"]

    def fold(acc, w, gs, idx, qv, scale):
        send = {"w": {"idx": idx, "val": qv, "scale": scale}}
        return agg.accumulate({"w": acc}, w, (send, gs))["w"]

    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    compiled = jax.jit(fold, donate_argnums=0).lower(
        sds(carry.shape, jnp.float32), sds((chunk,), jnp.float32),
        sds((chunk,), jnp.float32), sds((chunk, nb, kb), jnp.int32),
        sds((chunk, nb, kb), jnp.int8),
        sds((chunk, nb, 1), jnp.float32)).compile()
    calls = _calls(compiled.as_text(), "lbgm_dequant_fold")
    assert len(calls) == 1 and "output_to_operand_aliasing" in calls[0]
    ma = compiled.memory_analysis()
    carry_bytes = 4 * nb * block
    assert carry.shape == (nb, 256, 256)
    assert ma.alias_size_in_bytes == carry_bytes
    assert ma.temp_size_in_bytes < carry_bytes // 4, ma


@pytest.mark.parametrize("clients", [1, 2])
def test_held_experts_compile_for_v5e(one_chip, clients):
    """The held-expert layer of kanana-2-30b-a3b at the benchmark cell's
    widths and batch (8 of 128 experts, top-6, 4 x 256 tokens), forward
    and backward, vmapped over the scheduler's clients, compiles for a
    v5e as plain XLA (no custom call)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe as moe_lib
    base = get_config("kanana-2-30b-a3b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe,
                                                            held=8))
    d, f, H, E = cfg.d_model, cfg.moe.d_expert, 8, cfg.moe.num_experts
    fs = cfg.moe.n_shared * f
    shapes = {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
              "w_down": (H, f, d), "shared_gate": (d, fs),
              "shared_up": (d, fs), "shared_down": (fs, d)}
    names = sorted(shapes)

    def loss(*args):
        p = dict(zip(names, args[:-1]))
        return jnp.sum(moe_lib.apply_dropless(p, args[-1], cfg)[0] ** 2)

    step = jax.vmap(jax.grad(loss, argnums=tuple(range(len(names) + 1))))
    hlo = _compile(step, one_chip,
                   *[((clients,) + shapes[n], jnp.float32) for n in names],
                   ((clients, 4, 256, d), jnp.float32))
    assert "tpu_custom_call" not in hlo
