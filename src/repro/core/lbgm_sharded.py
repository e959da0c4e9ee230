"""Shard-local LBGM (beyond-paper §Perf optimization).

The pjit formulation of topk-LBGM reconstructs a dense fp32 gradient from a
flat block layout; GSPMD has to reshard that M-sized tensor back to the
parameter layout, which costs ~4x params of all-gather per client on the
FSDP archs (measured: 6.2 TiB/client for llama4 — EXPERIMENTS.md §Perf).

Fix: run Algorithm 1's top-k variant under ``shard_map`` — every device
performs the block-wise top-k, sparse gather and scatter on its OWN shard of
the gradient; the only cross-device traffic is the psum of three partial
scalars (<g,l>, ||g||^2, ||l||^2) per client. The LBG is stored in the same
block layout, sharded exactly like the gradient. Semantics are identical to
``lbgm_topk_client_step`` up to the block boundaries (blocks now align with
shards, which is the better layout anyway).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.lbgm import (LBGMStats, _block_layout, _to_blocks,
                             decision_from_scalars, topk_step_core,
                             topk_uplink_stats)


def _spec_axes(spec: P) -> Tuple[str, ...]:
    out = []
    for e in spec:
        if e is None:
            continue
        for a in (e if isinstance(e, tuple) else (e,)):
            out.append(a)
    return tuple(out)


def _nshards(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def local_leaf_size(leaf_shape, spec: P, mesh: Mesh) -> int:
    n = 1
    for i, d in enumerate(leaf_shape):
        e = spec[i] if i < len(spec) else None
        div = 1
        if e is not None:
            for a in (e if isinstance(e, tuple) else (e,)):
                div *= mesh.shape[a]
        n *= d // div
    return n


def sharded_lbg_layout(params_like, gspecs: Dict[str, P], mesh: Mesh,
                       k_frac: float):
    """Returns (lbg SDS pytree, lbg NamedSharding pytree)."""
    sds, sh = {}, {}
    for name, leaf in params_like.items():
        axes = _spec_axes(gspecs[name])
        ns = _nshards(mesh, axes)
        nb, _, kb = _block_layout(local_leaf_size(leaf.shape, gspecs[name],
                                                  mesh), k_frac)
        shape = (nb * ns, kb)
        sds[name] = {"idx": jax.ShapeDtypeStruct(shape, jnp.int32),
                     "val": jax.ShapeDtypeStruct(shape, jnp.float32)}
        spec = P(axes if axes else None, None)
        sh[name] = {"idx": NamedSharding(mesh, spec),
                    "val": NamedSharding(mesh, spec)}
    return sds, sh


def init_sharded_lbg(params_like, gspecs, mesh, k_frac: float):
    sds, _ = sharded_lbg_layout(params_like, gspecs, mesh, k_frac)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_local_topk_step(delta: float, k_frac: float, *, corr=None,
                         psum_axes=None, out_dtypes=False, sparse_out=False,
                         fused=False):
    """Device-local Algorithm-1 top-k step: ``fn(grads, lbg)``.

    This is the single decision body both sharded execution modes share:

    * model-axis sharding (``make_sharded_topk_step``) calls it on gradient
      *shards* with ``corr``/``psum_axes`` so the three partial scalars are
      reduced across devices;
    * client-axis sharding (``repro.fed.engine.ShardedTopKLBGStore``) calls
      it with no psum at all — each device holds its local clients' full
      dense gradients and their (idx, val) bank rows, so the accept/recycle
      decision is entirely device-local and the only cross-device traffic
      of the round is the server aggregate's psum.

    ``sparse_out`` / ``fused`` pass through to :func:`topk_step_core`
    (sparse scalar-round aggregation payload / fused Pallas decision).
    """
    def step(grads, lbg):
        return topk_step_core(grads, lbg, delta, k_frac, corr=corr,
                              psum_axes=psum_axes, out_dtypes=out_dtypes,
                              sparse_out=sparse_out, fused=fused)
    return step


def model_shard_rows(nb: int, n_model: int) -> int:
    """Block rows of an ``(nb, kb)`` block-layout leaf each model rank owns
    under ``n_model``-way model-axis sharding, or 0 when the leaf cannot
    shard (``nb`` not divisible — e.g. single-block leaves like biases,
    which stay replicated and are counted once via a rank-0 gate).

    ``_block_layout`` rounds multi-block ``nb`` up to a multiple of 16
    precisely so the usual power-of-two model meshes divide it.
    """
    if n_model > 1 and nb % n_model == 0:
        return nb // n_model
    return 0


def bank_model_partition(params_like, k_frac: float,
                         n_model: int) -> Dict[str, bool]:
    """name -> whether that leaf's sparse-bank block rows shard over the
    model axis. The single place the divisibility rule lives: the engine's
    bank placement (``ShardedScheduler.layout_banks``) and the decision
    body (:func:`make_mesh_topk_step`) both derive from it, so the bank a
    device holds is always exactly the rows its decision reads."""
    return {name: model_shard_rows(_block_layout(leaf.size, k_frac)[0],
                                   n_model) > 0
            for name, leaf in params_like.items()}


def make_mesh_topk_step(delta: float, k_frac: float, *, n_model: int,
                        model_axis: str = "model", sparse_out: bool = True,
                        fused: bool = False, pre_blocked: bool = False,
                        layouts: Dict[str, Tuple[int, int, int]] = None):
    """Per-client Algorithm-1 decision body for the engine's 2-D
    ``(clients, model)`` mesh: ``fn(grads, lbg) -> ((send, gscale),
    new_lbg, stats)``.

    This is :func:`make_sharded_topk_step`'s decomposition run along the
    *model* axis of a mesh the caller is already shard-mapped over (the
    "sharded" client scheduler), rather than a standalone shard_map:

    * ``n_model == 1`` — exactly :func:`make_local_topk_step`, the fully
      device-local body (bit-for-bit the 1-D client-mesh path).
    * ``n_model > 1`` — each model rank processes only its
      ``nb / n_model`` rows of every leaf's *global* block layout
      (``jax.lax.axis_index(model_axis)`` picks the slice, matching the
      rows of the bank shard it holds); the three partial scalars
      (<g,l>, ||g||^2, ||l||^2) are ``psum``-reduced over ``model_axis``.
      Leaves whose ``nb`` does not divide (see
      :func:`bank_model_partition`) are processed whole on every rank and
      gated to rank 0 before the psum — counted exactly once, with no
      replication-correction division to round.

    The *global* block layout (and therefore ``stats.uplink_floats``) is
    mesh-shape independent: every mesh shape reports identical uplink
    accounting. Only ``sparse_out=True`` is supported for ``n_model > 1``
    (the dense g_tilde scatter would need a cross-rank leaf assembly; the
    engine's sparse aggregation contract never materializes it).

    ``pre_blocked=True`` is the ``model_sharding="auto"`` entry point: the
    caller (the scheduler's inner manual-over-``model`` region) hands each
    leaf ALREADY in block-row layout, pre-sliced to this rank's rows for
    sharded leaves (full rows for replicated ones) — tensor-parallel
    gradients re-lay out once at the nested shard_map boundary instead of
    replicate-then-slice. ``layouts`` must then carry the GLOBAL
    ``name -> (nb, block, kb)`` layout (the local row count no longer
    determines it), and the step runs even at ``n_model == 1`` (the psums
    collapse to identities) so the auto path has one body on every mesh.
    """
    if pre_blocked:
        if layouts is None:
            raise ValueError(
                "make_mesh_topk_step: pre_blocked=True needs the global "
                "`layouts` {name: (nb, block, kb)} — local rows cannot "
                "reconstruct the mesh-independent block layout")
        if not sparse_out:
            raise ValueError(
                "make_mesh_topk_step: pre_blocked=True requires "
                "sparse_out=True (block-row inputs have no dense g_tilde "
                "layout to scatter back into)")
    elif n_model == 1:
        return make_local_topk_step(delta, k_frac, sparse_out=sparse_out,
                                    fused=fused)
    if not sparse_out:
        raise ValueError(
            "make_mesh_topk_step: model-axis sharding (n_model > 1) "
            "requires the sparse aggregation contract (sparse_out=True); "
            "the dense per-client g_tilde cannot be assembled device-local")

    def step(grads, lbg):
        if fused:
            from repro.kernels.ops import lbgm_sparse_decision
        rank = jax.lax.axis_index(model_axis)
        gl = jnp.zeros((), jnp.float32)
        ll = jnp.zeros((), jnp.float32)
        gg = jnp.zeros((), jnp.float32)
        local = {}     # per-leaf local block rows (or fused (ti, tv))
        total_k = 0    # GLOBAL kept-entry count: mesh-independent uplink
        for name, g in grads.items():
            sl = lbg[name]
            if pre_blocked:
                nb, block, kb = layouts[name]
            else:
                nb, block, kb = _block_layout(g.size, k_frac)
            total_k += nb * kb
            nb_l = sl["idx"].shape[0]
            sharded = nb_l != nb
            assert nb_l == (nb // n_model if sharded else nb), (
                name, nb_l, nb, n_model)
            if pre_blocked:
                # block rows arrive from the nested shard_map boundary —
                # the caller's in_specs already handed this rank its slice
                assert g.shape == (nb_l, block), (name, g.shape, nb_l, block)
                bl = g
            else:
                bl = _to_blocks(g, nb, block)
                if sharded:
                    bl = jax.lax.dynamic_slice_in_dim(bl, rank * nb_l, nb_l,
                                                      axis=0)
            if fused:
                gg_leaf, gv, ti, tv = lbgm_sparse_decision(bl, sl["idx"])
                local[name] = (ti, tv)
            else:
                gv = jnp.take_along_axis(bl, sl["idx"], axis=1)
                gg_leaf = jnp.vdot(bl, bl)
                local[name] = bl
            pgl = jnp.vdot(gv, sl["val"])
            pll = jnp.vdot(sl["val"], sl["val"])
            pgg = gg_leaf
            if not sharded:
                # replicated leaf: every rank computed the same full-leaf
                # partials — count them once, exactly (a rank-0 gate, not
                # a 1/n division the psum would have to un-round)
                own = (rank == 0).astype(jnp.float32)
                pgl, pll, pgg = pgl * own, pll * own, pgg * own
            gl, ll, gg = gl + pgl, ll + pll, gg + pgg
        gl = jax.lax.psum(gl, model_axis)
        ll = jax.lax.psum(ll, model_axis)
        gg = jax.lax.psum(gg, model_axis)
        # the decision rule itself lives in ONE place (core.lbgm) — this
        # decomposition only changed how the three scalars were reduced
        sin2, rho, scalar = decision_from_scalars(gl, gg, ll, delta)

        send, new_lbg = {}, {}
        for name, g in grads.items():
            sl = lbg[name]
            kb = sl["idx"].shape[1]
            if fused:
                ti, tv = local[name]
            else:
                bl = local[name]
                _, ti = jax.lax.top_k(jnp.abs(bl), kb)
                tv = jnp.take_along_axis(bl, ti, axis=1)
                ti = ti.astype(jnp.int32)
            keep = {"idx": jnp.where(scalar, sl["idx"], ti),
                    "val": jnp.where(scalar, sl["val"], tv)}
            send[name] = keep
            new_lbg[name] = keep
        stats = topk_uplink_stats(sin2, rho, scalar, gg, total_k)
        gscale = jnp.where(scalar, rho, 1.0)
        return (send, gscale), new_lbg, stats

    return step


def make_sharded_topk_step(cfg, mesh: Mesh, gspecs: Dict[str, P],
                           delta: float):
    """Returns fn(grads, lbg) -> (g_tilde, new_lbg, LBGMStats), where grads
    follow gspecs and lbg follows sharded_lbg_layout."""
    k_frac = cfg.lbgm.k_frac
    all_axes = tuple(mesh.axis_names)
    total_dev = math.prod(mesh.shape[a] for a in all_axes)
    lbg_specs = {name: {"idx": P(_spec_axes(gspecs[name]) or None, None),
                        "val": P(_spec_axes(gspecs[name]) or None, None)}
                 for name in gspecs}
    # replication correction: leaves not sharded over some axes are summed
    # that many extra times by the global psum
    corr = {name: total_dev / _nshards(mesh, _spec_axes(gspecs[name]))
            for name in gspecs}

    local_fn = make_local_topk_step(delta, k_frac, corr=corr,
                                    psum_axes=all_axes, out_dtypes=True)

    stat_spec = LBGMStats(*([P()] * 5))
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(gspecs, lbg_specs),
        out_specs=(gspecs, lbg_specs, stat_spec),
        check_vma=False)
