"""Minimal pure-function module system.

Parameters live in a *flat dict* keyed by slash-separated paths; a parallel
flat dict maps each key to a tuple of *logical axis names* used by the
sharding rules in ``repro.train.sharding``. Homogeneous transformer stacks are
*stacked* along a leading ``layers`` axis and executed with ``lax.scan``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]
Axes = Dict[str, Tuple[str, ...]]

#: stage names the model gives its ops (``jax.named_scope``); inside the
#: federated round they sit within ``fl.client_sgd`` (``repro.fed.spans``),
#: and a trace puts an op down to its innermost ``fl.*`` scope, so these
#: split the client's time
MLA = "fl.mla"                  # latent attention
MOE_ROUTE = "fl.moe_route"      # router and route weights
MOE_EXPERTS = "fl.moe_experts"  # held experts, shared experts
MODEL_SCOPES = (MLA, MOE_ROUTE, MOE_EXPERTS)

#: program counters a loss may report in its aux dict; ``run_round``'s
#: metrics carry each, summed over the round's clients and local steps
MOE_ROUTED_ROWS = "moe_routed_rows"  # rows sent to held experts, all layers
COUNTERS = (MOE_ROUTED_ROWS,)


class ParamStore:
    """Collects params + logical axes during model init."""

    def __init__(self, key: jax.Array, dtype=jnp.float32):
        self._key = key
        self.dtype = dtype
        self.params: Params = {}
        self.axes: Axes = {}

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, name: str, shape, axes, init: str = "normal",
              scale: float | None = None, dtype=None) -> jax.Array:
        assert name not in self.params, f"duplicate param {name}"
        assert len(shape) == len(axes), (name, shape, axes)
        dtype = dtype or self.dtype
        if init == "normal":
            # fan-in scaled normal; last contraction dim heuristic
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            s = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
            arr = jax.random.normal(self._next_key(), shape, jnp.float32) * s
        elif init == "zeros":
            arr = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            arr = jnp.ones(shape, jnp.float32)
        elif init == "uniform":
            s = scale if scale is not None else 1.0
            arr = jax.random.uniform(self._next_key(), shape, jnp.float32,
                                     -s, s)
        else:
            raise ValueError(init)
        arr = arr.astype(dtype)
        self.params[name] = arr
        self.axes[name] = tuple(axes)
        return arr


def subtree(params: Params, prefix: str) -> Params:
    """Slice a flat dict to keys under ``prefix/`` (prefix stripped)."""
    pre = prefix + "/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(dt)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU FFN: down( silu(x@gate) * (x@up) )."""
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", silu(g) * u, w_down)


def group_norm_heads(x: jax.Array, gamma: jax.Array, eps: float = 64e-5):
    """Per-head group norm used by RWKV6 output; x: (..., H, hd)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)).astype(dt)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    pos = np.arange(length)[:, None]
    inv = np.exp(-np.log(10000.0) * (np.arange(0, dim, 2) / dim))[None, :]
    tab = np.zeros((length, dim), np.float32)
    tab[:, 0::2] = np.sin(pos * inv)
    tab[:, 1::2] = np.cos(pos * inv)
    return jnp.asarray(tab)
