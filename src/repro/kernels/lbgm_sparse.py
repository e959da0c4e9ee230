"""Fused sparse-LBG decision kernel (TPU Pallas).

The sparse (top-k) Algorithm-1 client step in ``core/lbgm.py`` makes THREE
separate passes over each dense gradient leaf per client:

1. ``leaf_sparse_gather`` — g's values at the stored LBG positions,
2. ``jnp.vdot(g, g)``     — the squared norm for the sin^2 test,
3. ``leaf_topk``          — |g| + block-wise top-k for the refresh branch.

This kernel fuses all three into ONE read of the (nb, block) block layout:
each grid step loads ``ROWS`` block rows of g once and emits their partial
||g||^2, the gathered values at the LBG's block-local indices, and each
row's top-k entries (signed values + indices). The engine-facing entry has
a LEADING BATCH GRID DIMENSION ``grid=(B, nb / ROWS)`` so the client axis
of a vmap'd scheduler block maps straight onto grid dim 0
(``kernels.ops.lbgm_sparse_decision`` routes ``jax.vmap`` here via a
``custom_vmap`` rule); the row axis is innermost so the per-client ||g||^2
accumulator init at ``j == 0`` is correct under the sequential TPU grid.

The body needs no sort and no dynamic gather, neither of which Mosaic
lowers:

* pass 1 bisects each row's kb-th largest |value| exactly, in int32 IEEE
  bit space (monotone in value for non-negative floats), with nothing but
  bitcasts, compares and sums — all rows of the step at once;
* pass 2 sweeps the row in lane tiles loaded straight from the ref and
  compacts the selected entries (and gathers the LBG positions) with
  one-hot matmuls at HIGHEST precision, so the selected values and
  positions come out exact.

The per-row (idx, val) SET equals ``lax.top_k``'s, ties included (ties
fill in index order — ``lax.top_k``'s lowest-index rule); slot order is by
index, not by descending value, and every consumer treats the pairs as a
set. Block dims are squeezed over the batch axis and tiled over rows, and
the lane axes are padded to 128, so every block satisfies Mosaic's (8, 128)
rule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
#: block rows per grid step (one f32 sublane tile); a leaf with fewer rows
#: is taken whole (a block dim equal to the array's is legal), one with more
#: is padded to a multiple with zero rows, so a step never holds more
ROWS = 8
#: pass-2 compaction tile (lanes per one-hot matmul)
TILE = 256
#: bisection steps for the per-row top-k threshold. 32 integer halvings of
#: [-1, bits(max)] always terminate with lo/hi ADJACENT — hi is exactly the
#: kb-th largest |value|'s bit pattern and the tie band holds only exact
#: ties, at every magnitude (a float-interval bisection has absolute
#: resolution ~max/2^iters and silently mis-selects rows whose |values| all
#: sit below it)
BISECT_ITERS = 32

_NT = (((1,), (1,)), ((), ()))      # contract the lane axes: a @ b.T
_HI = jax.lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _decision_kernel(g_ref, idx_ref, gg_ref, gath_ref, ti_ref, tv_ref, *,
                     kb: int, tile: int, iters: int):
    """One (client, row-group) grid step.

    Refs: g (R, Bp) f32 rows; idx (R, kbp) int32 LBG positions (-1 pads);
    gg (1, LANES) per-client accumulator; gath/ti/tv (R, kbp) f32 outputs.
    """
    R, Bp = g_ref.shape
    kbp = idx_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        gg_ref[...] = jnp.zeros_like(gg_ref)

    g = g_ref[...]
    gg_ref[...] += jnp.sum(g * g)

    # ---- pass 1: bisect each row's kb-th largest |value| t* into (lo, hi]
    # in bit space. Invariant: count(ai > lo) >= kb > count(ai > hi)
    # (ai >= 0, so the initial lo = -1 counts Bp >= kb; nothing exceeds
    # the max). lo + (hi - lo) // 2, NOT (lo + hi) // 2: bit patterns of
    # values >= 2.0 exceed 2^30, so the naive midpoint overflows int32.
    ai = jax.lax.bitcast_convert_type(jnp.abs(g), jnp.int32)
    top = jax.lax.bitcast_convert_type(
        jnp.max(jnp.abs(g), axis=1, keepdims=True), jnp.int32)

    def bis(_, lh):
        lo, hi = lh
        mid = lo + ((hi - lo) >> 1)
        big = jnp.sum((ai > mid).astype(jnp.float32), axis=1,
                      keepdims=True) >= kb
        return jnp.where(big, mid, lo), jnp.where(big, hi, mid)

    lo, hi = jax.lax.fori_loop(
        0, iters, bis, (jnp.full((R, 1), -1, jnp.int32), top))
    # "definite" entries sit strictly above the band; ties (== t*) fill
    # the remaining slots in index order. For rows with fewer than kb
    # nonzeros the band is the zeros, so every nonzero is still kept.
    m = jnp.sum((ai > hi).astype(jnp.float32), axis=1, keepdims=True)

    # ---- pass 2: tiled compaction + gather (one-hot matmuls)
    gath_ref[...] = jnp.zeros_like(gath_ref)
    ti_ref[...] = jnp.zeros_like(ti_ref)
    tv_ref[...] = jnp.zeros_like(tv_ref)
    # inclusive-cumsum operator tri[i, j] = (i <= j); 0/1 is exact in bf16
    tri = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
           ).astype(jnp.bfloat16)
    # 1-indexed output slot of each one-hot row; pad slots (>= kb) never
    # match, which is also the cap for selections past kb
    srow = jax.lax.broadcasted_iota(jnp.int32, (kbp, tile), 0)
    slot_id = jnp.where(srow < kb, srow + 1, -1).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (tile, kbp), 0)
    idx = idx_ref[...]

    def tl(t, carry):
        cdef, ctie = carry
        off = pl.multiple_of(t * tile, tile)
        g_t = g_ref[:, pl.ds(off, tile)]                  # (R, T)
        # classify in the same bit space the threshold lives in (lo may be
        # -1, which is not a valid float to compare against)
        ai_t = jax.lax.bitcast_convert_type(jnp.abs(g_t), jnp.int32)
        dm = ai_t > hi
        sm = (ai_t > lo) & (ai_t <= hi)
        cum_d = jnp.dot(dm.astype(jnp.bfloat16), tri,
                        preferred_element_type=jnp.float32) + cdef
        cum_t = jnp.dot(sm.astype(jnp.bfloat16), tri,
                        preferred_element_type=jnp.float32) + ctie
        # definites take slots 1..m (m < kb), ties m+1..; 0 = unselected
        slot = jnp.where(dm, cum_d, jnp.where(sm, m + cum_t, 0.0))
        pos = (lane + off).astype(jnp.float32)            # (1, T)
        hit = (sub + off)                                 # (T, kbp)
        for r in range(R):
            g_r = g_t[r:r + 1, :]
            oh = (slot[r:r + 1, :] == slot_id).astype(jnp.float32)
            tv_ref[r:r + 1, :] += jax.lax.dot_general(
                g_r, oh, _NT, precision=_HI,
                preferred_element_type=jnp.float32)
            ti_ref[r:r + 1, :] += jax.lax.dot_general(
                pos, oh, _NT, precision=_HI,
                preferred_element_type=jnp.float32)
            # gather at the LBG positions (positions < 2^24, exact in f32)
            oh2 = (hit == idx[r:r + 1, :]).astype(jnp.float32)
            gath_ref[r:r + 1, :] += jnp.dot(
                g_r, oh2, precision=_HI,
                preferred_element_type=jnp.float32)
        return (cdef + jnp.sum(dm.astype(jnp.float32), axis=1, keepdims=True),
                ctie + jnp.sum(sm.astype(jnp.float32), axis=1, keepdims=True))

    zero = jnp.zeros((R, 1), jnp.float32)
    jax.lax.fori_loop(0, Bp // tile, tl, (zero, zero))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lbgm_sparse_decision_batched_pallas(blocks: jax.Array, idx: jax.Array,
                                        interpret: Optional[bool] = None):
    """blocks: (B, nb, block) block-layout gradients; idx: (B, nb, kb)
    int32 block-local LBG positions. Returns
    ``(gg (B,), gathered (B, nb, kb), top_idx (B, nb, kb) int32,
    top_val (B, nb, kb) f32)`` — each client's rows of g read once, each
    top-k row emitted in index order.

    The lane axes are padded before the call: g with zeros up to a tile
    multiple (pass 1's strict compares never select a pad zero ahead of
    real data — pads sit at the highest positions and a row holds at
    least ``block >= kb`` real entries — and pads add exact zeros to
    ||g||^2), idx with -1 up to a multiple of 128 (matches no position).
    Pad rows (up to a multiple of ``ROWS``) add zeros to ||g||^2 and are
    sliced off the outputs.
    """
    if interpret is None:
        from repro.kernels.ops import _default_interpret
        interpret = _default_interpret()
    assert blocks.ndim == 3 and idx.ndim == 3
    assert blocks.shape[:2] == idx.shape[:2]
    B, nb, block = blocks.shape
    kb = idx.shape[2]
    tile = min(TILE, _round_up(block, LANES))
    Bp = _round_up(block, tile)
    kbp = _round_up(kb, LANES)
    rows = min(ROWS, nb)
    nbp = _round_up(nb, rows)
    blocks = jnp.pad(blocks.astype(jnp.float32),
                     ((0, 0), (0, nbp - nb), (0, Bp - block)))
    idx = jnp.pad(idx.astype(jnp.int32),
                  ((0, 0), (0, nbp - nb), (0, kbp - kb)), constant_values=-1)
    kernel = functools.partial(_decision_kernel, kb=kb, tile=tile,
                               iters=BISECT_ITERS)
    row_spec = lambda w: pl.BlockSpec((pl.squeezed, rows, w),
                                      lambda b, j: (b, j, 0))
    out_rows = jax.ShapeDtypeStruct((B, nbp, kbp), jnp.float32)
    gg, gath, ti, tv = pl.pallas_call(
        kernel,
        grid=(B, nbp // rows),
        in_specs=[row_spec(Bp), row_spec(kbp)],
        out_specs=[pl.BlockSpec((pl.squeezed, 1, LANES),
                                lambda b, j: (b, 0, 0)),
                   row_spec(kbp), row_spec(kbp), row_spec(kbp)],
        out_shape=[jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32),
                   out_rows, out_rows, out_rows],
        interpret=interpret,
        name="lbgm_sparse_decision",
    )(blocks, idx)
    return (gg[:, 0, 0], gath[:, :nb, :kb],
            ti[:, :nb, :kb].astype(jnp.int32), tv[:, :nb, :kb])


def lbgm_sparse_decision_pallas(blocks: jax.Array, idx: jax.Array,
                                interpret: Optional[bool] = None):
    """Unbatched view of the fused decision: blocks (nb, block),
    idx (nb, kb) -> (gg scalar, gathered, top_idx, top_val)."""
    gg, gath, ti, tv = lbgm_sparse_decision_batched_pallas(
        blocks[None], idx[None], interpret=interpret)
    return gg[0], gath[0], ti[0], tv[0]
