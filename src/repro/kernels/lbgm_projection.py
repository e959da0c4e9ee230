"""Fused LBGM projection kernel (TPU Pallas).

The paper's per-round hot spot is three O(M) reductions over the flattened
gradient g and look-back gradient l: <g,l>, ||g||^2, ||l||^2 (Algorithm 1
steps 6 & 8). Done naively that is 3 separate HBM passes over 2 vectors; this
kernel fuses them into ONE pass (each operand read exactly once), with
(BLOCK_R, 128)-tiled VMEM blocks and a running fp32 accumulator in the output
block (TPU grid is sequential, so across-step accumulation into the same
output block is well-defined).

:func:`lbgm_projection_batched_pallas` takes a stack of B pairs with a
LEADING BATCH GRID DIMENSION ``grid=(B, tiles)``: the client axis of the FL
engine's schedulers maps straight onto grid dim 0, so one ``pallas_call``
covers a whole vmap'd client block (``kernels.ops.lbgm_projection`` routes
``jax.vmap`` here through a ``custom_vmap`` rule). The batch dim of every
block is squeezed, so each block's last two dims are (BLOCK_R, 128) tiles or
the whole (1, 128) accumulator row — the shapes Mosaic accepts. The tile
loop (dim 1) is innermost, so the per-row accumulator init at ``tile == 0``
stays correct under the sequential TPU grid.
:func:`lbgm_projection_pallas` is the B = 1 view.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 64      # sublane-tiled rows per grid step
LANES = 128       # TPU lane width


def _proj_kernel(g_ref, l_ref, out_ref):
    # grid = (B, tiles); refs are the squeezed (BLOCK_R, LANES) tiles and
    # the batch row's (1, LANES) accumulator
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)
    l = l_ref[...].astype(jnp.float32)
    gl = jnp.sum(g * l)
    gg = jnp.sum(g * g)
    ll = jnp.sum(l * l)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    vec = (jnp.where(lane == 0, gl, 0.0) + jnp.where(lane == 1, gg, 0.0)
           + jnp.where(lane == 2, ll, 0.0))
    out_ref[...] += vec


@functools.partial(jax.jit, static_argnames=("interpret",))
def lbgm_projection_batched_pallas(g: jax.Array, l: jax.Array,
                                   interpret: Optional[bool] = None):
    """g, l: (B, n) stacks of flat vectors (any float dtype).
    Returns (gl, gg, ll) fp32 arrays of shape (B,) — one fused pass per row.

    ``interpret=None`` auto-detects the backend (compiled Mosaic on TPU,
    the interpreter elsewhere).
    """
    if interpret is None:
        from repro.kernels.ops import _default_interpret
        interpret = _default_interpret()
    assert g.ndim == 2 and g.shape == l.shape
    B, n = g.shape
    tile = BLOCK_R * LANES
    pad = (-n) % tile
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
        l = jnp.pad(l, ((0, 0), (0, pad)))
    rows = (n + pad) // LANES
    g3 = g.reshape(B, rows, LANES)
    l3 = l.reshape(B, rows, LANES)
    out = pl.pallas_call(
        _proj_kernel,
        grid=(B, rows // BLOCK_R),
        in_specs=[
            pl.BlockSpec((pl.squeezed, BLOCK_R, LANES),
                         lambda b, i: (b, i, 0)),
            pl.BlockSpec((pl.squeezed, BLOCK_R, LANES),
                         lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, 1, LANES),
                               lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32),
        interpret=interpret,
        name="lbgm_projection",
    )(g3, l3)
    return out[:, 0, 0], out[:, 0, 1], out[:, 0, 2]


def lbgm_projection_pallas(g: jax.Array, l: jax.Array,
                           interpret: Optional[bool] = None):
    """g, l: flat 1-D arrays (any float dtype), same length.
    Returns (gl, gg, ll) fp32 scalars."""
    assert g.ndim == 1 and g.shape == l.shape
    gl, gg, ll = lbgm_projection_batched_pallas(g[None], l[None],
                                                interpret=interpret)
    return gl[0], gg[0], ll[0]
