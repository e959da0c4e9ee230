"""Production mesh construction (function, never module-level — importing
this module must not touch jax device state)."""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

#: JSON-able FL mesh spec (see ``repro.fed.flconfig.FLConfig.mesh``):
#: None = all local devices on the client axis, int n = (n, 1),
#: (c, m) = c-way client mesh x m-way model mesh.
MeshSpec = Union[None, int, Sequence[int]]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(_too_few(n, f"mesh {shape}", devices))
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def _too_few(n: int, what: str, devices) -> str:
    return (f"need {n} devices for the {what}, but JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    devices = jax.devices()[: data * model]
    return Mesh(np.asarray(devices).reshape(data, model), ("data", "model"))


def make_fl_mesh(spec: MeshSpec = None, *, client_axis: str = "clients",
                 model_axis: str = "model") -> Mesh:
    """Named 2-D ``(clients, model)`` mesh for FL rounds
    (``scheduler="sharded"``) — the resolver behind ``FLConfig.mesh``.

    The config stores a plain JSON value; this turns it into a live Mesh:

    * ``None``   — every local device on the client axis: ``(n_local, 1)``;
    * ``int n``  — ``(n, 1)``: pure client-data-parallelism, the pre-2-D
      spelling (bit-for-bit identical rounds);
    * ``(c, m)`` — ``c``-way client mesh x ``m``-way model-axis sharding of
      the LBG decision/banks.

    The mesh is always physically 2-D (the model axis has extent 1 in the
    first two cases) so every consumer — shard_map specs, NamedSharding
    bank placement, psum axes — speaks one mesh vocabulary.
    """
    devices = jax.devices()
    if spec is None:
        shape = (len(devices), 1)
    elif isinstance(spec, int):
        shape = (spec, 1)
    else:
        spec = tuple(int(d) for d in spec)
        if len(spec) != 2:
            raise ValueError(
                f"FL mesh spec must be None, an int, or a (clients, model) "
                f"pair, got {spec!r}")
        shape = spec
    if min(shape) < 1:
        raise ValueError(f"FL mesh needs >= 1 device per axis, got {shape}")
    n = shape[0] * shape[1]
    if n > len(devices):
        raise RuntimeError(
            _too_few(n, f"{shape} (clients, model) FL mesh", devices))
    # all of the host's devices: lay the mesh out on the physical topology
    # (TPU ICI rings); a subset keeps device order
    grid = (mesh_utils.create_device_mesh(shape, devices)
            if n == len(devices)
            else np.asarray(devices[:n]).reshape(shape))
    return Mesh(grid, (client_axis, model_axis))


def make_client_mesh(num_devices: Optional[int] = None,
                     axis: str = "clients") -> Mesh:
    """1-D client mesh — pre-2-D spelling, kept for external callers.

    New code (and the engine) goes through :func:`make_fl_mesh`, which
    returns the same devices as a ``(n, 1)`` named 2-D mesh.
    """
    devices = jax.devices()
    n = len(devices) if num_devices is None else num_devices
    if n < 1:
        raise ValueError(f"client mesh needs >= 1 device, got {n}")
    if n > len(devices):
        raise RuntimeError(_too_few(n, "client mesh", devices))
    return Mesh(np.asarray(devices[:n]), (axis,))
