"""The benchmark's own data generators and partitioners.

Copies of the program's markov-LM and Gaussian-mixture generators and its
iid and label-skew partitioners, kept here so that a change to the
program's data code cannot move the yardstick. :func:`register` adds them
to the program's component registries under ``bench-*`` names; the engine
then receives only the arrays made here, from the run's seed.

A traffic file (``traffic/<mix>.json``) names one generator and one
partitioner with their parameters:

    {"data": {"name": "markov", "kw": {"n": 256, "seq_len": 256}},
     "partition": {"name": "iid", "kw": {}},
     "fl": {... FLConfig fields that define the round ...}}
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

IMG = 28


def markov(seed: int, n: int, seq_len: int, vocab: int,
           branching: int = 4) -> Dict[str, np.ndarray]:
    """Token streams from a random sparse Markov chain: each token has
    ``branching`` likely successors. Returns int32 ``tokens`` and the
    next-token ``labels``, each (n, seq_len)."""
    rng = np.random.RandomState(seed)
    nxt = rng.randint(0, vocab, size=(vocab, branching))
    toks = np.empty((n, seq_len + 1), np.int32)
    toks[:, 0] = rng.randint(0, vocab, size=n)
    choices = rng.randint(0, branching, size=(n, seq_len))
    for t in range(seq_len):
        toks[:, t + 1] = nxt[toks[:, t], choices[:, t]]
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def mixture(seed: int, n: int, num_classes: int = 10,
            noise: float = 0.35) -> Dict[str, np.ndarray]:
    """28x28 classification from per-class Gaussian prototypes (the
    MNIST-sized stand-in): float32 ``x`` (n, 28, 28, 1), int32 ``y``."""
    rng = np.random.default_rng(seed)
    protos = rng.standard_normal((num_classes, IMG, IMG, 1), np.float32)
    protos /= np.linalg.norm(protos.reshape(num_classes, -1),
                             axis=1).reshape(-1, 1, 1, 1)
    protos *= IMG
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = rng.standard_normal((n, IMG, IMG, 1), np.float32)
    x *= noise
    x += protos[y]
    return {"x": x, "y": y}


def iid(train: Dict[str, np.ndarray], num_clients: int,
        seed: int) -> List[np.ndarray]:
    n = len(next(iter(train.values())))
    idx = np.random.RandomState(seed).permutation(n)
    return [np.sort(s) for s in np.array_split(idx, num_clients)]


def label_skew(train: Dict[str, np.ndarray], num_clients: int, seed: int,
               classes_per_client: int = 3) -> List[np.ndarray]:
    """Each client holds ``classes_per_client`` labels; every drawn class's
    pool is dealt out contiguously over the clients that drew it."""
    labels = train["y"]
    rng = np.random.RandomState(seed)
    num_classes = int(labels.max()) + 1
    by_class = [np.where(labels == c)[0] for c in range(num_classes)]
    for c in by_class:
        rng.shuffle(c)
    choices = [rng.choice(num_classes, classes_per_client, replace=False)
               for _ in range(num_clients)]
    take: List[List[np.ndarray]] = [[] for _ in range(num_clients)]
    for c in range(num_classes):
        takers = [k for k in range(num_clients) if c in choices[k]]
        if not takers:
            continue
        for k, shard in zip(takers, np.array_split(by_class[c], len(takers))):
            take[k].append(shard)
    empty = np.array([], dtype=np.int64)
    return [np.sort(np.concatenate(t)) if t else empty for t in take]


DATA = {"markov": markov, "mixture": mixture}
PARTITIONS = {"iid": iid, "label_skew": label_skew}


def make(traffic: dict, seed: int, num_clients: int
         ) -> Tuple[Dict[str, np.ndarray], List[np.ndarray]]:
    """The mix's training data and its client partition, from ``seed``."""
    data = DATA[traffic["data"]["name"]](seed, **traffic["data"]["kw"])
    part = traffic["partition"]
    parts = PARTITIONS[part["name"]](data, num_clients, seed + 1,
                                     **part.get("kw", {}))
    return data, parts


def register(data: Dict[str, np.ndarray], parts: List[np.ndarray]
             ) -> Tuple[str, str]:
    """Make ``data`` and ``parts`` a dataset and a partitioner of the
    program's registries; returns their names (new ones on every call)."""
    from repro.fed.registry import DATASETS, PARTITIONERS
    n = sum(name.startswith("bench-data-") for name in DATASETS.names())
    names = f"bench-data-{n}", f"bench-partition-{n}"
    DATASETS.register(names[0], lambda: (data, {}))
    PARTITIONERS.register(names[1], lambda train, num_clients: parts)
    return names
