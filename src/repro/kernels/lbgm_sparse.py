"""Sparse-LBG kernels (TPU Pallas): the client's fused decision and the
server's quantized fold. The decision is described first; the fold
(:func:`lbgm_dequant_fold_pallas`) reuses its (tile, lane) view of a block
row and is described at its kernel.

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
lowers. Each block row arrives as an (n_tiles, T) matrix of contiguous
T-lane tiles, and is handled in two passes. (On a TPU that reshape is a
relayout, which XLA folds into the copy that lays a leaf out in blocks,
``core.lbgm._to_blocks``: the program's path makes one copy of a leaf.)

* pass 1 bisects each row's kb-th largest |value| exactly, in int32 IEEE
  bit space (monotone in value for non-negative floats), with nothing but
  bitcasts, compares and sums, all rows of the step at once. It splits a
  row into *definite* entries (above the threshold band, m of them) and
  *ties* (in it);
* pass 2 selects in two levels, (tile, lane), row by row, with the output
  slots on the lane axis of full-height MXU matmuls:

  1. the row is transposed once to (T, n_tiles); one (T x T) triangle
     matmul per class gives each entry's inclusive rank inside its tile.
     The ranks' last row is the tiles' counts; one small matmul turns them
     into the inclusive prefix counts C_d, C_t over tiles;
  2. slot s <= m is the s-th definite, slot m < s <= kb the (s - m)-th tie
     (ties fill in index order, ``lax.top_k``'s lowest-index rule), slots
     past kb stay empty. With s' the slot's ordinal in its class, its tile
     is a_s = #{a : C[a] < s'} and its rank in the tile s' - C[a_s - 1]:
     O(n_tiles) compares per slot on the VPU;
  3. per group of ``SLOTS`` slots, the one-hot of a_s (n_tiles x SLOTS)
     multiplies the row's transposed ranks (T x n_tiles, bf16) and the four
     bytes of its f32 bit patterns (4T x n_tiles, int8): every slot fetches
     its whole tile. The fetched rank equal to the slot's picks the lane
     (position a_s * T + lane), and the lane's four bytes reassemble the
     value's exact bits. The gather at the LBG positions is the same step
     with (a, lane) = divmod(idx, T), any order, repeats allowed.

Why pass 2 is exact: every matmul operand is 0/1, a rank or count of at
most 256 in magnitude (exact in bf16) or a signed byte (int8); each fetched
output has exactly ONE non-zero term, and every sum stays below 2^24, so a
single-pass bf16 matmul with f32 accumulation, and an int8 one with int32
accumulation, return them exactly. Values never pass through the MXU or
the VPU as floats, so subnormals (which both may flush) and -0.0 survive
bit for bit. Work per row of `block` entries: O(block) for the ranks, and
kbp x block single-pass MACs per fetched piece, on matmuls whose
streamed side is the full (T or 4T)-row piece matrix.

The per-row (idx, val) SET equals ``lax.top_k``'s, ties included. Slots
hold the definites in index order, then the ties in index order, not
descending values; every consumer treats the pairs as a set. The g block
is (ROWS, n_tiles, T) over a squeezed batch axis; the idx and output
blocks are (ROWS, kbp) with kbp padded to 128, so every block satisfies
Mosaic's (8, 128) rule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
#: block rows per grid step (one f32 sublane tile); a leaf's rows are
#: padded to a multiple with zero rows (Mosaic cannot broadcast the
#: per-row thresholds of a lone row)
ROWS = 8
#: pass-2 tile: lanes per tile of a block row (a leaf shorter than TILE
#: takes its 128-padded block as one tile; n_tiles is padded to a multiple
#: of 8). Per row, picking tile and lane costs O(kbp * (n_tiles + TILE))
#: VPU work: TILE = 256 = sqrt(65536) balances the two on the 65536-entry
#: rows. TILE may not exceed 256: a rank within a tile must be exact in
#: bf16. VMEM per grid step at block 65536: the double-buffered g block
#: (2 x ROWS x 256 KiB = 4 MiB) and pass 1's bit patterns (2 MiB); per row
#: its transposed bits, two rank matrices (256 KiB each), the bf16 ranks
#: (128 KiB) and the int8 bytes (256 KiB); per slot group the fetched
#: bytes (4 x TILE x SLOTS int32 = 512 KiB) and ranks (128 KiB): about
#: 8 MiB, inside the 16 MiB scoped default.
TILE = 256
#: output slots per fetch matmul (the one-hot's lane width)
SLOTS = 128
#: bisection steps for the per-row top-k threshold. 32 integer halvings of
#: [-1, bits(max)] always terminate with lo/hi ADJACENT — hi is exactly the
#: kb-th largest |value|'s bit pattern and the tie band holds only exact
#: ties, at every magnitude (a float-interval bisection has absolute
#: resolution ~max/2^iters and silently mis-selects rows whose |values| all
#: sit below it)
BISECT_ITERS = 32
#: a slot's rank target that no entry carries (ranks lie in [-256, 256])
_NO_RANK = 1024.0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tiling(block: int):
    """(T, n_tiles) of a block row: T lanes per tile, n_tiles padded to a
    sublane multiple so every transpose and matmul operand is aligned."""
    tile = min(TILE, _round_up(block, LANES))
    return tile, _round_up(-(-block // tile), 8)


def _tri(n: int, lower: bool):
    """(n, n) bf16 0/1 triangle, diagonal included: lower[i, j] = j <= i."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return ((j <= i) if lower else (i <= j)).astype(jnp.bfloat16)


def _bf16(mask):
    return mask.astype(jnp.float32).astype(jnp.bfloat16)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _decision_kernel(g_ref, idx_ref, gg_ref, gath_ref, ti_ref, tv_ref, *,
                     kb: int, iters: int):
    """One (client, row-group) grid step.

    Refs: g (R, n_tiles, T) f32 rows; idx (R, kbp) int32 LBG positions
    (-1 pads); gg (1, LANES) per-client accumulator; gath/tv (R, kbp) f32
    and ti (R, kbp) int32 outputs.
    """
    R, nt, T = g_ref.shape
    kbp = idx_ref.shape[1]
    shift = T.bit_length() - 1                    # T is 128 or 256

    @pl.when(pl.program_id(1) == 0)
    def _init():
        gg_ref[...] = jnp.zeros_like(gg_ref)

    g = g_ref[...]
    gg_ref[...] += jnp.sum(g * g)

    # ---- pass 1: bisect each row's kb-th largest |value| t* into (lo, hi]
    # in bit space, all rows of the step at once. |value| by clearing the
    # sign bit, never by float arithmetic, which may flush subnormals.
    # Invariant: count(ai > lo) >= kb > count(ai > hi) (ai >= 0, so the
    # initial lo = -1 counts the whole row; nothing exceeds the max).
    # lo + (hi - lo) // 2, NOT (lo + hi) // 2: bit patterns of values
    # >= 2.0 exceed 2^30, so the naive midpoint overflows int32.
    ai = jax.lax.bitcast_convert_type(g, jnp.int32) & 0x7FFFFFFF
    # sum over tiles (whole vregs) before lanes (a cross-lane reduce)
    count = lambda mask: jnp.sum(jnp.sum(mask.astype(jnp.float32), axis=1,
                                         keepdims=True), axis=2,
                                 keepdims=True)           # (R, 1, 1)

    def bis(_, lh):
        lo, hi = lh
        mid = lo + ((hi - lo) >> 1)
        big = count(ai > mid) >= kb
        return jnp.where(big, mid, lo), jnp.where(big, hi, mid)

    top = jnp.max(jnp.max(ai, axis=1, keepdims=True), axis=2, keepdims=True)
    lo_r, hi_r = jax.lax.fori_loop(
        0, iters, bis, (jnp.full((R, 1, 1), -1, jnp.int32), top))
    # "definite" entries sit strictly above the band; ties (== t*) fill the
    # remaining slots in index order. For rows with fewer than kb nonzeros
    # the band is the zeros, so every nonzero is still kept (classify in
    # bit space: lo may be -1, not a float to compare against).
    m_r = count(ai > hi_r)
    which = jax.lax.broadcasted_iota(jnp.int32, (R, 1, 1), 0)

    lower_t = _tri(T, lower=True)                 # in-tile prefix (T, T)
    upper_n = _tri(nt, lower=False)               # over tiles (nt, nt)
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, nt), 0)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (T, SLOTS), 0)
    tile_s = jax.lax.broadcasted_iota(jnp.int32, (nt, SLOTS), 0)
    slot0 = jax.lax.broadcasted_iota(jnp.int32, (1, SLOTS), 1) + 1
    row_r = jax.lax.broadcasted_iota(jnp.int32, (R, SLOTS), 0)

    def row(r, carry):
        own = lambda v: jnp.sum(jnp.where(which == r, v, 0), axis=0)
        lo, hi, m = own(lo_r), own(hi_r), own(m_r)            # (1, 1)
        # ---- pass 2, level 2 first: the row transposed (lanes of a tile
        # on sublanes; an int transpose, since a small f32 one may pass
        # through the MXU, which flushes subnormals) and each entry's
        # inclusive rank inside its tile, definites +, ties -, others 0
        bits = jax.lax.bitcast_convert_type(g_ref[r], jnp.int32).T  # (T, nt)
        ait = bits & 0x7FFFFFFF
        dm = ait > hi
        sm = (ait > lo) & (ait <= hi)
        r_def = _dot(lower_t, _bf16(dm))
        r_tie = _dot(lower_t, _bf16(sm))
        rank = jnp.where(dm, r_def, jnp.where(sm, -r_tie, 0.0)
                         ).astype(jnp.bfloat16)
        # ---- level 1: the tiles' counts are the ranks' last row; their
        # inclusive prefix over tiles (counts <= T are bf16-exact, sums
        # < 2^24), turned into columns (nt, 1) by an int transpose
        cnt = jnp.where(sub8 == 0, r_def[T - 1:], jnp.where(
            sub8 == 1, r_tie[T - 1:], 0.0)).astype(jnp.bfloat16)
        pre = _dot(cnt, upper_n).astype(jnp.int32).T          # (nt, 8)
        c_def = pre[:, :1].astype(jnp.float32)
        c_tie = pre[:, 1:2].astype(jnp.float32)
        # the fetch operand: the four bytes of each bit pattern, signed
        # into int8 (an int8 matmul with int32 sums is exact)
        pieces = jnp.concatenate(
            [(bits << (24 - 8 * p)) >> 24 for p in range(4)],
            axis=0).astype(jnp.int8)                          # (4T, nt)

        def pick(tile, hit):
            """The bit pattern at each slot's `hit` lane of its one-hot
            column `tile`: one int8 fetch, bytes selected one at a time."""
            fetch = jnp.dot(pieces, tile.astype(jnp.int8),
                            preferred_element_type=jnp.int32)  # (4T, SLOTS)
            out = jnp.zeros((1, SLOTS), jnp.int32)
            for p in range(4):
                byte = jnp.sum(jnp.where(hit, fetch[p * T:(p + 1) * T], 0),
                               axis=0, keepdims=True)
                out = out | ((byte & 0xFF) << (8 * p))
            return out

        def group(q, c):
            off = pl.multiple_of(q * SLOTS, SLOTS)
            s = (slot0 + off).astype(jnp.float32)             # (1, SLOTS)
            is_def = s <= m
            want = jnp.where(is_def, s, s - m)                # class ordinal
            cum = jnp.where(is_def, c_def, c_tie)             # (nt, SLOTS)
            before = cum < want
            a = jnp.sum(before.astype(jnp.float32), axis=0, keepdims=True)
            rho = want - jnp.max(jnp.where(before, cum, 0.0), axis=0,
                                 keepdims=True)
            target = jnp.where(is_def, rho,
                               jnp.where(s <= kb, -rho, _NO_RANK))
            a = a.astype(jnp.int32)
            tile = tile_s == a                                # (nt, SLOTS)
            hit = _dot(rank, _bf16(tile)) == target           # (T, SLOTS)
            lane = jnp.sum(jnp.where(hit, lane_t, 0), axis=0, keepdims=True)
            cols = pl.ds(off, SLOTS)

            def put(ref, val):
                # row r of an (R, SLOTS) window: Mosaic stores whole
                # sublane tiles at a dynamic lane offset
                ref[:, cols] = jnp.where(row_r == r, val, ref[:, cols])

            put(tv_ref, jax.lax.bitcast_convert_type(pick(tile, hit),
                                                     jnp.float32))
            put(ti_ref, (a << shift) + lane)
            # the gather: tile idx // T, lane idx % T (-1 pads fetch zeros)
            i = jnp.sum(jnp.where(row_r == r, idx_ref[:, cols], 0), axis=0,
                        keepdims=True)
            put(gath_ref, jax.lax.bitcast_convert_type(
                pick(tile_s == (i >> shift), lane_t == (i & (T - 1))),
                jnp.float32))
            return c

        jax.lax.fori_loop(0, kbp // SLOTS, group, 0)
        return carry

    jax.lax.fori_loop(0, R, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lbgm_sparse_decision_batched_pallas(blocks: jax.Array, idx: jax.Array,
                                        interpret: Optional[bool] = None):
    """blocks: (B, nb, block) block-layout gradients; idx: (B, nb, kb)
    int32 block-local LBG positions. Returns
    ``(gg (B,), gathered (B, nb, kb), top_idx (B, nb, kb) int32,
    top_val (B, nb, kb) f32)`` — each client's rows of g read once, each
    top-k row emitted in index order.

    Each row is padded with zeros to n_tiles * T entries and viewed as
    (n_tiles, T) (pass 1's strict compares never select a pad zero ahead
    of real data — pads sit at the highest positions and a row holds at
    least ``block >= kb`` real entries — and pads add exact zeros to
    ||g||^2);
    idx is padded with -1 up to a multiple of 128 (matches no position).
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
    tile, nt = _tiling(block)
    kbp = _round_up(kb, LANES)
    nbp = _round_up(nb, ROWS)
    blocks = jnp.pad(blocks.astype(jnp.float32),
                     ((0, 0), (0, nbp - nb), (0, nt * tile - block))
                     ).reshape(B, nbp, nt, tile)
    idx = jnp.pad(idx.astype(jnp.int32),
                  ((0, 0), (0, nbp - nb), (0, kbp - kb)), constant_values=-1)
    kernel = functools.partial(_decision_kernel, kb=kb, iters=BISECT_ITERS)
    row_spec = pl.BlockSpec((pl.squeezed, ROWS, kbp), lambda b, j: (b, j, 0))
    out_rows = lambda dt: jax.ShapeDtypeStruct((B, nbp, kbp), dt)
    gg, gath, ti, tv = pl.pallas_call(
        kernel,
        grid=(B, nbp // ROWS),
        in_specs=[pl.BlockSpec((pl.squeezed, ROWS, nt, tile),
                               lambda b, j: (b, j, 0, 0)), row_spec],
        out_specs=[pl.BlockSpec((pl.squeezed, 1, LANES),
                                lambda b, j: (b, 0, 0)),
                   row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32),
                   out_rows(jnp.float32), out_rows(jnp.int32),
                   out_rows(jnp.float32)],
        interpret=interpret,
        name="lbgm_sparse_decision",
    )(blocks, idx)
    return gg[:, 0, 0], gath[:, :nb, :kb], ti[:, :nb, :kb], tv[:, :nb, :kb]


def lbgm_sparse_decision_pallas(blocks: jax.Array, idx: jax.Array,
                                interpret: Optional[bool] = None):
    """Unbatched view of the fused decision: blocks (nb, block),
    idx (nb, kb) -> (gg scalar, gathered, top_idx, top_val)."""
    gg, gath, ti, tv = lbgm_sparse_decision_batched_pallas(
        blocks[None], idx[None], interpret=interpret)
    return gg[0], gath[0], ti[0], tv[0]


# ------------------------------------------------------------------ fold

#: wire-value codes of the fold's expansion. An expanded 0 means "no
#: client slot here", so a selected zero travels as +-_ZERO_CODE (its sign
#: kept), and a NaN as _NAN_CODE, so that no NaN reaches the MXU (0 x NaN
#: would spread it over its column). Both lie beyond every value of the
#: 1-byte wire dtypes (|int8| <= 128, |e4m3| <= 448) and are exact in
#: bf16; _NAN_CODE is no multiple of _ZERO_CODE, so zeros sent twice at
#: one position (the all-zero rows of a bank that was never filled) still
#: read as a zero.
_ZERO_CODE = 4096.0
_NAN_CODE = 6144.0
_FOLD_WIRE_DTYPES = (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn))


def fold_carry_shape(nb: int, block: int):
    """Shape of the fold's fp32 carry for one (nb, block) leaf layout: each
    block row as its (n_tiles, T) tile matrix, zero-padded past ``block``
    (:func:`_tiling`, the decision kernel's view of a row)."""
    tile, nt = _tiling(block)
    return nb, nt, tile


def _fold_kernel(idx_ref, code_ref, coeff_ref, acc_ref, out_ref):
    """One grid step: R block rows, every client of the chunk.

    Refs: idx (C, R, kbp) int32 block-local positions (-1 pads); code
    (C, R, kbp) f32 wire-value codes (0 for pads and for clients with
    w <= 0); coeff (C, R, 1) f32 per-row multipliers; acc/out (R, nt, T)
    f32, the carry's rows (aliased).
    """
    C, R, kbp = idx_ref.shape
    _, nt, T = acc_ref.shape
    shift = T.bit_length() - 1                    # T is 128 or 256
    which = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    tile_a = jax.lax.broadcasted_iota(jnp.int32, (nt, kbp), 0)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (T, kbp), 0)
    nt_dims = (((1,), (1,)), ((), ()))            # a @ b.T

    def row(r, carry):
        # row r of an (R, n) block: one sublane select and sum, exact
        own = lambda v: jnp.sum(jnp.where(which == r, v, 0), axis=0,
                                keepdims=True)

        def client(c, acc):
            i = own(idx_ref[c])                                # (1, kbp)
            v = own(code_ref[c])
            # lanes first: Mosaic broadcasts one axis at a time
            k = own(jnp.broadcast_to(coeff_ref[c], (R, T)))    # (1, T)
            # the row's payload as a dense (nt, T) matrix: slot s lands at
            # (idx // T, idx % T). Positions are unique within a row, so
            # each output holds at most one non-zero product: exact.
            a = _bf16(tile_a == (i >> shift))                  # (nt, kbp)
            b = jnp.where(lane_t == (i & (T - 1)), v, 0.0
                          ).astype(jnp.bfloat16)               # (T, kbp)
            d = jax.lax.dot_general(a, b, nt_dims,
                                    preferred_element_type=jnp.float32)
            mag = jnp.abs(d)
            q = jnp.where(mag < _ZERO_CODE, d,
                          jnp.where(mag == _NAN_CODE, jnp.nan, d * 0.0))
            # acc + (coeff * q), the reference's association (XLA's CPU
            # backend, interpreting, may fuse it into one multiply-add);
            # adding -0.0 leaves every other position's bits as they were
            return acc + jnp.where(d != 0.0, k * q, -0.0)

        out_ref[r] = jax.lax.fori_loop(0, C, client, acc_ref[r])
        return carry

    jax.lax.fori_loop(0, R, row, 0)


def lbgm_dequant_fold_pallas(acc: jax.Array, w: jax.Array,
                             gscale: jax.Array, idx: jax.Array,
                             qv: jax.Array, scale: jax.Array,
                             interpret: Optional[bool] = None):
    """Dequantize one chunk's sparse payloads and fold them into the carry.

    acc: (nb, n_tiles, T) f32 carry (:func:`fold_carry_shape`); w, gscale:
    (C,) f32; idx: (C, nb, kb) int32 block-local positions, unique within
    a row; qv: (C, nb, kb) int8 or float8_e4m3fn wire values; scale:
    (C, nb, 1) f32 row scales. Returns the carry with, client by client in
    order, ``acc + ((w * gscale) * scale) * q`` at each client's positions
    where ``w > 0`` (``kernels.ref.lbgm_dequant_accum_ref`` on the
    (nb, block) view). Positions no such client selected keep their bits.

    Each grid step reads ``ROWS`` rows of the carry once, expands each
    client's payload row to its dense (n_tiles, T) matrix by one one-hot
    bf16 MXU matmul (the wire values and the codes are exact in bf16), and
    writes the rows back once, in place (``input_output_aliases``). A
    carry whose rows are not a multiple of ``ROWS`` ends in a partial grid
    step; the payload is padded to it. Work per row and client:
    n_tiles x kbp x T MACs, with kbp the slots padded to 128.
    """
    if interpret is None:
        from repro.kernels.ops import _default_interpret
        interpret = _default_interpret()
    return _dequant_fold(acc, w, gscale, idx, qv, scale,
                         interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dequant_fold(acc, w, gscale, idx, qv, scale, interpret: bool):
    nb, nt, tile = acc.shape
    C, _, kb = idx.shape
    if jnp.dtype(qv.dtype) not in _FOLD_WIRE_DTYPES:
        raise TypeError(f"the fold takes int8 or e4m3 wire values, got "
                        f"{qv.dtype}")
    rows = min(ROWS, nb)
    nbp, kbp = _round_up(nb, rows), _round_up(kb, LANES)
    q = qv.astype(jnp.float32)
    code = jnp.where(q == 0.0, jnp.copysign(_ZERO_CODE, q),
                     jnp.where(jnp.isnan(q), _NAN_CODE, q))
    code = jnp.where((w > 0)[:, None, None], code, 0.0)
    coeff = (w * gscale)[:, None, None] * scale
    pad = lambda x, v, cols=kbp - kb: jnp.pad(
        x, ((0, 0), (0, nbp - nb), (0, cols)), constant_values=v)
    slot_spec = pl.BlockSpec((C, rows, kbp), lambda j: (0, j, 0))
    acc_spec = pl.BlockSpec((rows, nt, tile), lambda j: (j, 0, 0))
    return pl.pallas_call(
        _fold_kernel,
        grid=(nbp // rows,),
        in_specs=[slot_spec, slot_spec,
                  pl.BlockSpec((C, rows, 1), lambda j: (0, j, 0)),
                  acc_spec],
        out_specs=acc_spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="lbgm_dequant_fold",
    )(pad(idx.astype(jnp.int32), -1), pad(code, 0.0),
      pad(coeff.astype(jnp.float32), 0.0, 0), acc)
