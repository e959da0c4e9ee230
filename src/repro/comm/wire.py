"""Uplink wire codecs — quantized payload encoding + real-byte accounting.

The compressor pipeline (``repro.compression``) and the LBGM store decide
*what* a client uploads (dense update, sparse top-k ``(idx, val)`` payload,
or a single scalar rho); this module decides *how those numbers sit on the
wire* and prices the bytes a NIC would actually move. Codecs resolve
through ``repro.fed.registry.CODECS`` (``FLConfig.codec`` /
``FLConfig.codec_kw``, validated at construction, JSON/CLI round-trip like
every other knob):

``none``
    fp32 legacy wire format — payload values and round history are
    bit-for-bit the pre-codec engine; only the new ``wire_bytes`` metric
    is added (computed from static sizes + the existing ``sent_scalar``
    flag, so it reads no payload data).
``delta_idx``
    lossless index compression for the sparse payloads: values stay
    fp32, the index stream is delta-coded (below). Bit-for-bit values.
``int8`` / ``fp8``
    stochastically rounded value quantization (int8 grid, or fp8 e4m3)
    with one fp32 scale per block row (sparse payloads) or per leaf
    (dense payloads), plus delta-coded indices and a 1-byte e4m3 rho on
    scalar rounds. ``codec_kw={"stochastic": false}`` switches to
    deterministic round-to-nearest.

Wire format (one full-round sparse payload, per leaf; block layout from
``repro.core.lbgm._block_layout`` — ``nb`` rows of ``kb`` entries)::

    [values]   nb*kb * value_bytes      (4 = fp32 | 1 = int8/fp8 e4m3)
    [scales]   nb * 4                   (quantized codecs only; fp32,
                                         power-of-two, one per block row)
    [indices]  raw: nb*kb * 4 (int32)
               delta-coded: per row, indices sorted ascending, first
               index then successive deltas, each as a varint:
               1 byte (< 2^7) / 2 bytes (< 2^14) / 3 bytes otherwise
    scalar (recycle) round: scalar_bytes total (4 = fp32 rho | 1 = e4m3)
    dense full round: M * value_bytes + 4 per leaf scale (quantized only)

Quantization uses power-of-two scales (``2^ceil(log2(max|v|/Q))``) so that
dequantize(quantize(v)) is EXACT on already-on-grid values: ``q * 2^e`` is
exact in fp32 and dividing it back by any power-of-two scale yields an
integer, which both stochastic and nearest rounding map to itself. That
idempotency is what keeps the simulation deployment-faithful — the LBG
bank holds the dequantized grid values a real server would have stored at
the last full round, and a scalar round's ``rho_q * bank`` reconstruction
matches the server's bit-for-bit no matter how often the payload path
re-encodes it.

Stochastic rounding (``E[q] = f``) consumes one uint32 seed per client per
round, drawn host-side from the dedicated :func:`codec_rng` stream and
riding the batch dict under the reserved ``WIRE_KEY`` — the same seam the
attack extras use — so the batch/mask rng stream is untouched and a
``codec="none"`` run draws nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed.registry import CODECS, register_codec

#: reserved batch-dict key for the per-client stochastic-rounding seed
WIRE_KEY = "_wire_seed"

#: e4m3 largest finite magnitude (S.1111.110 = 1.75 * 2^8)
E4M3_MAX = 448.0

#: fp8 storage dtype for the wire representation
_F8 = jnp.float8_e4m3fn


def codec_rng(seed: int) -> np.random.RandomState:
    """Dedicated host rng stream for stochastic-rounding seeds.

    Like :func:`repro.fed.attacks.fault_rng`, a deterministic transform of
    the experiment seed that is de-correlated from both the batch/mask
    stream and the fault stream, so toggling the codec never shifts any
    other draw."""
    return np.random.RandomState((seed + 0xC0DEC) * 16807 % (2 ** 31))


# ------------------------------------------------------------ primitives

def stochastic_round(f, u):
    """Unbiased rounding of ``f`` to the integer grid: ``E[out] = f``.

    ``u`` is uniform on [0, 1). Exact integers round to themselves for
    every ``u`` (frac = 0 never exceeds u) — the idempotency workhorse."""
    lo = jnp.floor(f)
    return lo + (u < (f - lo))


def pow2_scale(m, qmax):
    """Smallest power-of-two ``s`` with ``m / s <= qmax`` (elementwise).

    Power-of-two, not ``m / qmax``: multiplying the integer/e4m3 grid back
    by ``s`` is then exact in fp32, giving the exact-requantization
    property the module docstring relies on. The power is materialized
    with ``ldexp`` on the integer exponent — ``exp2`` lowers to
    ``exp(x*ln2)`` on some backends and lands 1 ulp off a true power of
    two, which would silently void that exactness. All-zero rows get
    s = 1."""
    e = jnp.ceil(jnp.log2(jnp.maximum(m, 1e-38) / qmax)).astype(jnp.int32)
    s = jnp.ldexp(jnp.ones_like(m, jnp.float32), e)
    return jnp.where(m > 0, s, 1.0)


def e4m3_nearest(x):
    """Round-to-nearest e4m3 value of ``x`` (saturating), as fp32.

    Used for the scalar-round rho stream: one byte on the wire, and the
    aggregate applies exactly the value the server would decode."""
    return (jnp.clip(x, -E4M3_MAX, E4M3_MAX)
            .astype(_F8).astype(jnp.float32))


def _e4m3_step(a):
    """Grid spacing of e4m3 at magnitude ``a`` (a >= 0, fp32).

    Exponent comes from the IEEE bit pattern (exact — no log rounding),
    clipped to e4m3's normal range [-6, 8]; below 2^-6 the grid is the
    denormal ladder with constant step 2^-9."""
    e = ((jax.lax.bitcast_convert_type(a, jnp.int32) >> 23) & 0xFF) - 127
    return jnp.exp2((jnp.clip(e, -6, 8) - 3).astype(jnp.float32))


def delta_idx_bytes(idx):
    """Wire bytes of the varint-delta index stream for one sparse leaf.

    ``idx``: (..., kb) int32, block-local (< 2^16). Per row the indices
    are sorted ascending and sent as first-index-then-deltas, each delta
    as a varint (1/2/3 bytes). Lossless by construction — sorting loses
    nothing because ``(idx, val)`` pairs travel together and scatter-add
    is order-free within a row. Degenerate kb = 1 rows cost exactly one
    varint (the first index, delta from 0); pad rows (iota indices) are
    all-ones deltas, 1 byte each — counted like any other row, matching
    the fp32-scalar accounting which also prices pad rows."""
    s = jnp.sort(idx, axis=-1)
    prev = jnp.concatenate(
        [jnp.zeros_like(s[..., :1]), s[..., :-1]], axis=-1)
    d = s - prev
    return jnp.sum(1.0 + (d >= (1 << 7)) + (d >= (1 << 14)))


# ----------------------------------------------------------- codec base

class WireCodec:
    """Base codec: the fp32 legacy wire format.

    Subclasses override the class attributes (byte model) and, for lossy
    codecs, :meth:`quantize`. The engine calls :meth:`encode_sparse` /
    :meth:`encode_dense` at the tail of ``client_fn`` — after the uplink
    pipeline and the LBGM store step, i.e. on exactly what would be
    serialized — and the aggregator seam dequantizes via
    :meth:`decode_leaf` (or inside the dequantizing scatter-accumulate,
    ``kernels.ref.lbgm_dequant_accum_ref``).
    """

    name = "none"
    lossy = False          # value quantization active
    stochastic = False     # consumes a per-client rounding seed
    delta_idx = False      # varint-delta index stream vs raw int32
    value_bytes = 4.0      # per transmitted payload value
    scalar_bytes = 4.0     # per scalar-round rho
    scale_bytes = 0.0      # per block row (sparse) / per leaf (dense)
    #: sparse payload leaf keys the aggregator seam sees
    payload_keys = ("idx", "val")

    # ------------------------------------------------------- byte model
    def sparse_full_bytes(self, send):
        """Full-round wire bytes of a sparse ``{name: {idx, val, ...}}``
        payload (one client). For non-delta codecs this is a static
        constant — no payload data is read."""
        total = jnp.zeros((), jnp.float32)
        for sk in send.values():
            idx = sk["idx"]
            nk, nb = float(idx.size), float(idx.shape[0])
            ib = delta_idx_bytes(idx) if self.delta_idx else 4.0 * nk
            total = total + ib + self.value_bytes * nk \
                + self.scale_bytes * nb
        return total

    def sparse_layout_bytes(self, layouts):
        """Static full-round wire bytes for a ``[(nb, kb), ...]`` block
        layout. The legacy dense-aggregation oracle path
        (``fused_kernels=False`` over a top-k store) ships the same
        conceptual (idx, val) payload as the sparse path but never
        materializes the indices, so data-dependent delta coding cannot
        apply there: indices price at the raw 4 bytes. For non-delta
        codecs this equals :meth:`sparse_full_bytes` exactly — the two
        aggregation paths report identical histories."""
        return float(sum((self.value_bytes + 4.0) * nb * kb
                         + self.scale_bytes * nb for nb, kb in layouts))

    # --------------------------------------------------------- encoding
    def encode_sparse(self, out, new_lbg, stats, seed):
        """Encode one client's sparse ``((send, gscale))`` payload.

        Returns ``(out, new_lbg, wire_bytes)``. The base (lossless)
        codecs leave payload and bank untouched — bit-for-bit."""
        del seed
        wire = jnp.where(stats.sent_scalar, self.scalar_bytes,
                         self.sparse_full_bytes(out[0]))
        return out, new_lbg, wire

    def encode_dense(self, gt, cost, seed):
        """Encode one client's dense update tree; ``cost`` is the uplink
        pipeline's fp32-scalar count. Returns ``(gt, wire_bytes)``."""
        del seed
        return gt, 4.0 * cost

    # --------------------------------------------------------- decoding
    def decode_leaf(self, sk):
        """fp32 values of one sparse payload leaf (the seam's 'decode')."""
        return sk["val"]


@register_codec("none")
class NoneCodec(WireCodec):
    pass


@register_codec("delta_idx")
class DeltaIdxCodec(WireCodec):
    name = "delta_idx"
    delta_idx = True


class _QuantizedCodec(WireCodec):
    """Shared machinery for the lossy value codecs."""

    lossy = True
    delta_idx = True
    value_bytes = 1.0
    scalar_bytes = 1.0     # rho as e4m3
    scale_bytes = 4.0
    payload_keys = ("idx", "val", "scale")
    wire_dtype = jnp.int8
    qmax = 127.0

    def __init__(self, stochastic: bool = True):
        self.stochastic = bool(stochastic)

    def _key(self, seed):
        """Per-client PRNG key, or None when rounding deterministically
        (no seed rides the batch dict then)."""
        return jax.random.PRNGKey(seed) if self.stochastic else None

    @staticmethod
    def _fold(key, i):
        return None if key is None else jax.random.fold_in(key, i)

    def _round(self, f, key):
        if self.stochastic:
            return stochastic_round(f, jax.random.uniform(key, f.shape))
        return jnp.round(f)

    def quantize(self, val, key):
        """(rows, cols) fp32 -> (wire-dtype grid, (rows, 1) fp32 scale)."""
        raise NotImplementedError

    def decode_leaf(self, sk):
        return sk["val"].astype(jnp.float32) * sk["scale"]

    def encode_sparse(self, out, new_lbg, stats, seed):
        send, gscale = out
        key = self._key(seed)
        send2, lbg2 = {}, {}
        for i, name in enumerate(sorted(send)):
            sk = send[name]
            q, scale = self.quantize(sk["val"], self._fold(key, i))
            send2[name] = {"idx": sk["idx"], "val": q, "scale": scale}
            # the bank keeps the DEQUANTIZED grid values: on a full round
            # send.val and new_lbg.val are the same keep_val array, so
            # applying the identical transform keeps client bank == what
            # the server decoded; on a recycle round the bank values are
            # already on the grid and the transform is exactly identity
            lbg2[name] = {"idx": new_lbg[name]["idx"],
                          "val": q.astype(jnp.float32) * scale}
        gscale_q = jnp.where(stats.sent_scalar,
                             e4m3_nearest(gscale), gscale)
        wire = jnp.where(stats.sent_scalar, self.scalar_bytes,
                         self.sparse_full_bytes(send2))
        return (send2, gscale_q), lbg2, wire

    def encode_dense(self, gt, cost, seed):
        del cost  # the codec ships the dense tree itself: M values + scales
        key = self._key(seed)
        out, total = {}, 0.0
        for i, name in enumerate(sorted(gt)):
            leaf = gt[name]
            q, scale = self.quantize(
                leaf.astype(jnp.float32).reshape(1, -1),
                self._fold(key, i))
            # dense aggregation consumes fp32 trees — dequantize here
            # (fusion into the aggregator is the sparse path's job)
            out[name] = (q.astype(jnp.float32) * scale).reshape(leaf.shape)
            total += self.value_bytes * leaf.size + self.scale_bytes
        return out, jnp.full((), total, jnp.float32)


@register_codec("int8")
class Int8Codec(_QuantizedCodec):
    name = "int8"

    def quantize(self, val, key):
        m = jnp.max(jnp.abs(val), axis=-1, keepdims=True)
        scale = pow2_scale(m, self.qmax)
        q = self._round(val / scale, key)
        # pow2_scale guarantees |val/scale| <= qmax up to log2 rounding
        # fuzz; clamp so that fuzz can never wrap the int8 cast
        q = jnp.clip(q, -self.qmax, self.qmax)
        return q.astype(self.wire_dtype), scale


@register_codec("fp8")
class Fp8Codec(_QuantizedCodec):
    name = "fp8"
    wire_dtype = _F8
    qmax = E4M3_MAX

    def quantize(self, val, key):
        m = jnp.max(jnp.abs(val), axis=-1, keepdims=True)
        scale = pow2_scale(m, self.qmax)
        x = val / scale
        a = jnp.abs(x)
        step = _e4m3_step(a)
        # round the mantissa-scaled magnitude on its local grid; crossing
        # up into the next binade lands on that binade's grid (f = 16
        # -> 8 * 2*step), so every outcome is e4m3-representable
        r = self._round(a / step, key)
        xq = jnp.clip(jnp.sign(x) * r * step, -self.qmax, self.qmax)
        return xq.astype(self.wire_dtype), scale


# ------------------------------------------------------------- resolver

def make_codec(cfg) -> WireCodec:
    """Resolve ``cfg.codec`` / ``cfg.codec_kw`` through the registry."""
    try:
        return CODECS.get(cfg.codec)(**(cfg.codec_kw or {}))
    except TypeError as e:
        raise ValueError(
            f"codec {cfg.codec!r} rejected codec_kw={cfg.codec_kw!r}: {e}"
        ) from e
