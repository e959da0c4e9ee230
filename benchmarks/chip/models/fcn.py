"""The paper's fully connected classifier (setting S2): the plain
reference, the benchmark's weights, and the program's ``fcn`` component.

784 inputs -> ReLU hidden layer -> class logits, mean softmax
cross-entropy. Parameters are a flat dict under the program's names
(``fc1/w``, ``fc1/b``, ``fc2/w``, ``fc2/b``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_model(cfg: dict) -> dict:
    return {"arch": "paper-fcn", "d_model": cfg["hidden_size"],
            "vocab_size": cfg["num_classes"]}


def shapes(cfg: dict) -> dict:
    n_in, h, c = cfg["input_size"], cfg["hidden_size"], cfg["num_classes"]
    return {"fc1/w": (n_in, h), "fc1/b": (h,), "fc2/w": (h, c),
            "fc2/b": (c,)}


def init(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """normal(0, 1/sqrt(fan_in)) weights, zero biases."""
    k1, k2 = jax.random.split(key)
    out = {}
    for name, shape in shapes(cfg).items():
        if name.endswith("/b"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            k = k1 if name.startswith("fc1") else k2
            w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                jnp.float32(shape[0]))
            out[name] = w.astype(dtype)
    return out


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """6 FLOPs per parameter per sample (forward and backward)."""
    n_in, h, c = cfg["input_size"], cfg["hidden_size"], cfg["num_classes"]
    return float(6 * (n_in * h + h + h * c + c))


def batch_loss(params: dict, batch: dict, cfg: dict) -> jax.Array:
    x = batch["x"].reshape(batch["x"].shape[0], -1).astype(
        params["fc1/w"].dtype)
    h = jax.nn.relu(x @ params["fc1/w"] + params["fc1/b"])
    logits = (h @ params["fc2/w"] + params["fc2/b"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], -1))
