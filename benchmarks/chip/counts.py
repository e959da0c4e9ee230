"""Work the round requires, computed from shapes: the model FLOPs of the
clients' local SGD, and the bytes the LBGM decision kernels must move.

These are the yardstick's numerators. They count what the algorithm needs,
not what an implementation happens to do: no padding rows, no recompute.
"""
from __future__ import annotations

from typing import Dict, Tuple

from reference import block_layout

Shapes = Dict[str, Tuple[int, ...]]


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def round_samples(fl: dict) -> int:
    """Samples (sequences or images) the sampled clients train on per round."""
    k = round(fl["num_clients"] * fl.get("sample_frac", 1.0))
    return k * fl["tau"] * fl["batch_size"]


def round_model_flops(model, cfg: dict, fl: dict, traffic: dict) -> float:
    """Forward and backward FLOPs of one round's local SGD."""
    return float(round_samples(fl)) * model.train_flops_per_sample(
        cfg, traffic)


def decision_bytes(shapes: Shapes, k_frac: float, clients: int) -> float:
    """Least HBM traffic of the sparse decision per round: read each
    client's gradient once (4 bytes an entry), read its LBG indices, and
    write the gathered values, top indices and top values, over the rows
    that hold data (4 bytes each)."""
    per_client = 0
    for shape in shapes.values():
        size = _size(shape)
        _, block, kb = block_layout(size, k_frac)
        live = -(-size // block)
        per_client += 4 * size + 4 * 4 * live * kb
    return float(per_client * clients)


def projection_bytes(shapes: Shapes, clients: int) -> float:
    """Least HBM traffic of the dense projection per round: read the
    gradient and the LBG once per client (4 bytes an entry each)."""
    return float(2 * 4 * sum(_size(s) for s in shapes.values()) * clients)
