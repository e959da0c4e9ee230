"""Logical-axis -> mesh-axis sharding rules (DESIGN.md §5)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical axes that shard over the `model` mesh axis in every mode
_MODEL_AXES = {"vocab", "heads", "kv_heads", "ff", "expert", "embed2",
               "hidden", "classes", "cout"}
# logical axes that additionally shard over `data` in fsdp mode
_FSDP_AXES = {"embed", "feat"}


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _mesh_axis_for(logical: str, mode: str, mesh: Mesh,
                   dim_size: int) -> Optional[str]:
    if logical in _MODEL_AXES and "model" in mesh.axis_names:
        if dim_size % mesh.shape["model"] == 0:
            return "model"
    if mode == "fsdp" and logical in _FSDP_AXES and "data" in mesh.axis_names:
        if dim_size % mesh.shape["data"] == 0:
            return "data"
    return None


def param_pspec(axes: Tuple[str, ...], shape: Tuple[int, ...], mode: str,
                mesh: Mesh, embed_shard: str = "vocab") -> P:
    used = set()
    out = []
    for logical, dim in zip(axes, shape):
        if embed_shard == "embed" and axes == ("vocab", "embed"):
            # hillclimb variant: shard the embedding table along d_model so
            # token gathers stay local (no per-client table all-gather);
            # the lm_head stays vocab-sharded for chunked-CE memory.
            ax = ("model" if logical == "embed"
                  and dim % mesh.shape.get("model", 1) == 0 else None)
            ax = ax if logical == "embed" else (
                "data" if mode == "fsdp" and logical == "vocab"
                and dim % mesh.shape.get("data", 1) == 0 else None)
        else:
            ax = _mesh_axis_for(logical, mode, mesh, dim)
        if ax in used:
            ax = None
        if ax is not None:
            used.add(ax)
        out.append(ax)
    return P(*out)


def params_shardings(axes_tree: Dict[str, Tuple[str, ...]],
                     params, mode: str, mesh: Mesh,
                     embed_shard: str = "vocab"):
    return {k: NamedSharding(mesh,
                             param_pspec(axes_tree[k], params[k].shape,
                                         mode, mesh,
                                         embed_shard if k == "embed"
                                         else "vocab"))
            for k in params}


def stacked_pspec(base: P, lead_axes: Tuple[str, ...]) -> P:
    """Prepend mesh axes (e.g. the client axis) to a param spec."""
    return P(lead_axes, *base)


def batch_pspec(mesh: Mesh, extra_dims: int = 2) -> P:
    """Client/batch leading axis over ("pod","data"); rest unsharded."""
    return P(dp_axes(mesh), *([None] * extra_dims))


def cache_pspec(axes: Tuple[str, ...], shape: Tuple[int, ...],
                mesh: Mesh) -> P:
    """Decode-state sharding: batch over data axes; kv_heads over model if
    divisible, else head_dim over model (distributed flash-decode)."""
    out = []
    model = mesh.shape.get("model", 1)
    # decide which dim takes the model axis (first divisible preference)
    model_target = None
    for cand in ("kv_heads", "heads", "head_dim", "head_dim2", "embed",
                 "vocab"):
        for logical, dim in zip(axes, shape):
            if logical == cand and dim % model == 0 and model > 1:
                model_target = logical
                break
        if model_target:
            break
    used_model = False
    for logical, dim in zip(axes, shape):
        if logical == "batch":
            dp = dp_axes(mesh)
            total = 1
            for a in dp:
                total *= mesh.shape[a]
            out.append(dp if dim % total == 0 and dim >= total else None)
        elif logical == model_target and not used_model:
            out.append("model")
            used_model = True
        else:
            out.append(None)
    return P(*out)


def state_shardings(axes_tree, state, mesh: Mesh):
    def one(axes, leaf):
        if not isinstance(axes, tuple):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, cache_pspec(axes, leaf.shape, mesh))
    return jax.tree.map(one, axes_tree, state,
                        is_leaf=lambda t: isinstance(t, tuple))
