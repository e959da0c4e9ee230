"""Declarative experiment API: ``ExperimentSpec`` -> ``run_experiment``.

The paper positions LBGM as plug-and-play across models, datasets and
sparsifiers (P3/P4); this module makes an FL experiment a first-class,
serializable object instead of hand-wired glue. A frozen
:class:`ExperimentSpec` names every component by registry key (model, data,
partition), embeds the canonical :class:`~repro.fed.flconfig.FLConfig`
knobs, and round-trips losslessly through plain dicts / JSON — so a spec
file *is* the experiment, and a sweep is just a list of specs.

Entry points:

* ``build_experiment(spec) -> (FLEngine, eval_fn)`` — resolve components
  and wire the engine (the only place outside tests that should construct
  ``FLEngine`` directly).
* ``run_experiment(spec, rounds=None) -> ExperimentResult`` — build, run,
  evaluate per the spec's :class:`EvalPolicy`, and return typed round
  records plus uplink accounting. The engine's ``history`` is reproduced
  bit-for-bit by an equivalent hand-wired ``FLEngine`` run on the same
  seed (tested in ``tests/test_experiment.py``).
* ``sweep(base_spec, overrides) -> [(point, ExperimentResult)]`` — grid or
  explicit list of dotted-key overrides
  (e.g. ``{"fl.delta_threshold": [.01, .2]}``), the driver behind the
  Fig. 6 threshold sweep.
* ``python -m repro.fed.run --spec spec.json --set key=value`` — CLI over
  the same objects (see ``repro.fed.run``).

Extension points: ``@register_model`` / ``@register_dataset`` /
``@register_partitioner`` (this module registers the paper-native
built-ins), plus ``@register_compressor`` / ``@register_scheduler`` /
``@register_lbg_store`` consumed by the engine layer.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Mapping, Optional, \
    Tuple, Union

import numpy as np

from repro.fed.flconfig import FLConfig
from repro.fed.registry import (DATASETS, MODELS, PARTITIONERS,
                                register_dataset, register_model,
                                register_partitioner)

# --------------------------------------------------------------- spec types


@dataclass(frozen=True)
class ComponentSpec:
    """A registry key plus its keyword arguments: ``("mixture", {"n": 2000})``."""
    name: str
    kw: Dict[str, Any] = field(default_factory=dict)


@dataclass(frozen=True)
class EvalPolicy:
    """When to run held-out evaluation during/after an experiment."""
    every: int = 0          # eval every N rounds (0 = never during the run)
    final: bool = True      # eval once after the last round
    verbose: bool = False   # print per-eval progress lines

    def __post_init__(self):
        if self.every < 0:
            raise ValueError(
                f"EvalPolicy: every must be >= 0, got {self.every}")


@dataclass(frozen=True)
class ExperimentSpec:
    """The complete, serializable description of one FL experiment."""
    model: ComponentSpec = field(
        default_factory=lambda: ComponentSpec("fcn"))
    data: ComponentSpec = field(
        default_factory=lambda: ComponentSpec("mixture"))
    partition: ComponentSpec = field(
        default_factory=lambda: ComponentSpec("label_skew"))
    fl: FLConfig = field(default_factory=FLConfig)
    rounds: int = 40
    eval: EvalPolicy = field(default_factory=EvalPolicy)
    name: str = "experiment"

    # ---------------------------------------------------------- validation
    def validate(self) -> "ExperimentSpec":
        """Check registry keys and ranges; error messages name the fix.

        ``fl`` already validated itself at construction; this covers the
        spec-level fields.
        """
        if self.rounds < 1:
            raise ValueError(
                f"ExperimentSpec: rounds must be >= 1, got {self.rounds}")
        for reg, comp in ((MODELS, self.model), (DATASETS, self.data),
                          (PARTITIONERS, self.partition)):
            if comp.name not in reg:
                raise ValueError(
                    f"ExperimentSpec: unknown {reg.kind} {comp.name!r}; "
                    f"registered {reg.kind}s: {reg.names()}")
        return self

    # ------------------------------------------------------- serialization
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ExperimentSpec":
        d = dict(d)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"ExperimentSpec: unknown fields {sorted(unknown)}; "
                f"known fields: {sorted(known)}")
        for key in ("model", "data", "partition"):
            if key in d and isinstance(d[key], Mapping):
                d[key] = ComponentSpec(**d[key])
        if isinstance(d.get("fl"), Mapping):
            d["fl"] = FLConfig.from_dict(d["fl"])
        if isinstance(d.get("eval"), Mapping):
            d["eval"] = EvalPolicy(**d["eval"])
        return cls(**d)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(s))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str) -> "ExperimentSpec":
        with open(path) as f:
            return cls.from_json(f.read())

    # ----------------------------------------------------------- overrides
    def with_overrides(self, overrides: Mapping[str, Any]) -> "ExperimentSpec":
        """New spec with dotted-key overrides applied, e.g.
        ``{"fl.delta_threshold": 0.4, "model.kw.arch": "paper-cnn"}``.

        Works through the dict round-trip so any serializable field is
        addressable; re-validation happens on reconstruction.
        """
        def is_open(key):  # kw dicts take arbitrary component kwargs
            return key == "kw" or key.endswith("_kw")

        d = self.to_dict()
        for dotted, value in overrides.items():
            parts = dotted.split(".")
            node = d
            for p in parts[:-1]:
                if not isinstance(node, dict) or p not in node:
                    raise ValueError(
                        f"ExperimentSpec: unknown override key {dotted!r} "
                        f"(no field {p!r}; known: "
                        f"{sorted(node) if isinstance(node, dict) else []})")
                if node[p] is None and is_open(p):
                    node[p] = {}
                node = node[p]
            leaf = parts[-1]
            if not isinstance(node, dict):
                raise ValueError(
                    f"ExperimentSpec: unknown override key {dotted!r}")
            if leaf not in node and not (len(parts) > 1
                                         and is_open(parts[-2])):
                raise ValueError(
                    f"ExperimentSpec: unknown override key {dotted!r}; "
                    f"known keys here: {sorted(node)}")
            node[leaf] = value
        return type(self).from_dict(d)


# ------------------------------------------------------------ result types


#: history keys copied verbatim from ``FLEngine.run_round`` metrics into
#: each :class:`RoundRecord` — must track the engine's history keys in
#: lockstep (``result.history`` is asserted float-exact against
#: ``FLEngine.history`` in tests/test_experiment.py)
_HISTORY_KEYS = ("loss", "uplink_floats", "frac_scalar", "wire_bytes",
                 "total_uplink", "vanilla_uplink", "savings",
                 "total_wire_bytes", "wire_savings")


@dataclass
class RoundRecord:
    """One FL round's server-side metrics (mirrors ``FLEngine.history``)."""
    round: int
    loss: float
    uplink_floats: float
    frac_scalar: float
    total_uplink: float
    vanilla_uplink: float
    savings: float
    # real-byte wire accounting (repro.comm.wire / FLConfig.codec)
    wire_bytes: float = 0.0
    total_wire_bytes: float = 0.0
    wire_savings: float = 0.0
    eval: Dict[str, float] = field(default_factory=dict)

    def as_history_entry(self) -> Dict[str, float]:
        return {"loss": self.loss, "uplink_floats": self.uplink_floats,
                "frac_scalar": self.frac_scalar,
                "wire_bytes": self.wire_bytes,
                "total_uplink": self.total_uplink,
                "vanilla_uplink": self.vanilla_uplink,
                "savings": self.savings,
                "total_wire_bytes": self.total_wire_bytes,
                "wire_savings": self.wire_savings}


@dataclass
class ExperimentResult:
    """Typed outcome of ``run_experiment``: round records + accounting."""
    spec: ExperimentSpec
    rounds: int
    records: List[RoundRecord]
    final_eval: Dict[str, float]
    total_uplink: float
    vanilla_uplink: float
    savings: float
    duration_s: float

    @property
    def history(self) -> List[Dict[str, float]]:
        """Engine-compatible history (bit-equal to ``FLEngine.history``)."""
        return [r.as_history_entry() for r in self.records]

    @property
    def us_per_round(self) -> float:
        return self.duration_s / max(self.rounds, 1) * 1e6

    def to_dict(self) -> Dict[str, Any]:
        return {
            "spec": self.spec.to_dict(),
            "rounds": self.rounds,
            "records": [dataclasses.asdict(r) for r in self.records],
            "final_eval": self.final_eval,
            "total_uplink": self.total_uplink,
            "vanilla_uplink": self.vanilla_uplink,
            "savings": self.savings,
            "duration_s": self.duration_s,
        }


# ------------------------------------------------------------ entry points


def build_experiment(spec: ExperimentSpec):
    """Resolve the spec's components and wire the engine.

    Returns ``(engine, eval_fn)`` where ``eval_fn(params)`` evaluates on
    the dataset's held-out split (``{"test_loss": ..., "test_acc": ...}``).
    """
    from repro.fed.engine import FLEngine
    import jax.numpy as jnp

    spec.validate()
    # model init seed defaults to the experiment seed; an explicit
    # model.kw["seed"] wins (kw dicts are open-ended override surface)
    built = MODELS.get(spec.model.name)(
        **{"seed": spec.fl.seed, **spec.model.kw})
    # components may return (params, loss_fn) or, for architectures that
    # know their tensor-parallel layout, (params, loss_fn, axes_tree) —
    # the per-leaf named-axis metadata fl.model_sharding="auto" needs
    params, loss_fn, model_axes = (
        built if len(built) == 3 else (*built, None))
    train, held_out = DATASETS.get(spec.data.name)(**spec.data.kw)
    n_held = len(next(iter(held_out.values()))) if held_out else 0
    if n_held == 0 and (spec.eval.final or spec.eval.every):
        raise ValueError(
            "ExperimentSpec: the eval policy requests evaluation but the "
            "dataset's held-out split is empty (a mean over zero samples "
            "is NaN); grow it (e.g. data.kw n_eval > 0) or disable eval "
            "with EvalPolicy(every=0, final=False)")
    parts = PARTITIONERS.get(spec.partition.name)(
        train, spec.fl.num_clients, **spec.partition.kw)
    client_data = [{k: v[p] for k, v in train.items()} for p in parts]
    engine = FLEngine(loss_fn, params, client_data, spec.fl,
                      model_axes=model_axes)

    eval_batch = {k: jnp.asarray(v) for k, v in held_out.items()}

    def eval_fn(params) -> Dict[str, float]:
        loss, metrics = loss_fn(params, eval_batch)
        out = {"test_loss": float(loss)}
        if "acc" in metrics:
            out["test_acc"] = float(metrics["acc"])
        return out

    return engine, eval_fn


def run_experiment(spec: ExperimentSpec,
                   rounds: Optional[int] = None,
                   resume: bool = False) -> ExperimentResult:
    """Build the spec's experiment, run it, and return the typed result.

    The round loop is identical to ``FLEngine.run`` (same RNG stream, same
    per-round calls), so ``result.history`` matches a hand-wired engine's
    ``history`` bit-for-bit on the same seed; evaluation per
    ``spec.eval`` is layered on top without touching the engine history.
    Host batch prep rides the engine's :class:`~repro.fed.engine.
    RoundPrefetcher` (round t+1 prepared while t executes) — numerically
    invisible, same rng stream. The spec's ``fl.fused_kernels`` knob (and
    every other FLConfig field) JSON round-trips through the spec, so a
    saved spec pins the execution path too.

    ``resume=True`` restores the checkpoint at ``spec.fl.ckpt_path``
    (written per ``spec.fl.ckpt_every``) before the loop and continues
    from the saved round; the completed history is bit-for-bit the
    uninterrupted run's (rng streams, banks, buffered in-flight slots and
    ledger all travel in the checkpoint). Records replayed from the
    checkpointed engine history carry no per-round eval (eval is a pure
    read of params, re-runnable offline); ``final_eval`` is unaffected.
    """
    rounds = spec.rounds if rounds is None else rounds
    engine, eval_fn = build_experiment(spec)
    policy = spec.eval
    records: List[RoundRecord] = []
    rng = np.random.RandomState(spec.fl.seed + 1)
    start = 0
    if resume:
        if not spec.fl.ckpt_path:
            raise ValueError("run_experiment(resume=True) needs "
                             "fl.ckpt_path set in the spec")
        start = engine.restore_checkpoint(spec.fl.ckpt_path, rng)
        records = [RoundRecord(round=i + 1, eval={},
                               **{k: h[k] for k in _HISTORY_KEYS})
                   for i, h in enumerate(engine.history)]
    # accumulate round time only — held-out eval must not contaminate the
    # us_per_round metric the benchmarks report. Host batch prep is
    # double-buffered on the engine's prefetch thread (same rng stream,
    # bit-identical history), so us_per_round measures the device round
    # with round t+1's prep overlapped — the steady-state serving shape.
    duration = 0.0
    src = engine.prefetcher(rng)
    try:
        for r in range(start, rounds):
            t0 = time.time()
            m = engine.run_round(src)
            duration += time.time() - t0
            ev: Dict[str, float] = {}
            if policy.every and (r + 1) % policy.every == 0:
                ev = eval_fn(engine.params)
                if policy.verbose:
                    shown = {**m, **ev}
                    print(f"[{spec.name}] round {r+1:4d} " +
                          " ".join(f"{k}={v:.4g}"
                                   for k, v in shown.items()))
            records.append(RoundRecord(round=r + 1, eval=ev,
                                       **{k: m[k] for k in _HISTORY_KEYS}))
            if spec.fl.ckpt_every and (r + 1) % spec.fl.ckpt_every == 0:
                engine.save_checkpoint(spec.fl.ckpt_path)
    finally:
        src.close()
    final_eval = eval_fn(engine.params) if policy.final else {}
    return ExperimentResult(
        spec=spec, rounds=rounds, records=records, final_eval=final_eval,
        total_uplink=engine.total_uplink,
        vanilla_uplink=engine.vanilla_uplink,
        savings=records[-1].savings if records else 0.0,
        duration_s=duration)


OverridesLike = Union[Mapping[str, Iterable[Any]],
                      Iterable[Mapping[str, Any]]]


def expand_overrides(overrides: OverridesLike) -> List[Dict[str, Any]]:
    """Normalize sweep input to a list of dotted-key override dicts.

    A mapping of ``key -> list of values`` expands to the cartesian grid;
    an iterable of dicts passes through as explicit sweep points.
    """
    if isinstance(overrides, Mapping):
        keys = list(overrides)
        grids = [list(overrides[k]) for k in keys]
        return [dict(zip(keys, combo)) for combo in itertools.product(*grids)]
    return [dict(o) for o in overrides]


def sweep(base_spec: ExperimentSpec, overrides: OverridesLike,
          rounds: Optional[int] = None,
          ) -> List[Tuple[Dict[str, Any], ExperimentResult]]:
    """Run ``base_spec`` under each override set; returns
    ``[(overrides_dict, result), ...]`` in grid order. Each result's
    ``spec`` carries the fully resolved configuration."""
    out = []
    for point in expand_overrides(overrides):
        spec = base_spec.with_overrides(point)
        out.append((point, run_experiment(spec, rounds)))
    return out


# --------------------------------------------------------------- built-ins
#
# Paper-native components. Model builders return ``(params, loss_fn)`` or
# ``(params, loss_fn, axes_tree)`` where ``axes_tree`` names each leaf's
# dimensions for tensor-parallel layout (consumed when
# ``fl.model_sharding="auto"``); dataset builders return
# ``(train, held_out)`` dicts of numpy arrays; partitioners map
# ``(train, num_clients, **kw)`` to per-client index lists.


def _classifier_model(arch: str, seed: int, init_fn, apply_fn,
                      **arch_overrides):
    import jax
    from repro.configs import get_config
    from repro.models.smallnets import classifier_loss

    cfg = get_config(arch)
    if arch_overrides:
        cfg = dataclasses.replace(cfg, **arch_overrides)
    params, _ = init_fn(jax.random.PRNGKey(seed), cfg)
    loss_fn = lambda p, b: classifier_loss(apply_fn, p, cfg, b["x"], b["y"])
    return params, loss_fn


@register_model("fcn")
def _fcn_model(seed: int = 0, arch: str = "paper-fcn", **arch_overrides):
    """Paper S2: 1-hidden-layer FCN classifier on 28x28 inputs."""
    from repro.models.smallnets import apply_fcn, init_fcn
    return _classifier_model(arch, seed, init_fcn, apply_fcn,
                             **arch_overrides)


@register_model("cnn")
def _cnn_model(seed: int = 0, arch: str = "paper-cnn", **arch_overrides):
    """Paper S1: small conv classifier on 28x28 inputs."""
    from repro.models.smallnets import apply_cnn, init_cnn
    return _classifier_model(arch, seed, init_cnn, apply_cnn,
                             **arch_overrides)


@register_model("lm")
def _lm_model(seed: int = 0, arch: str = "qwen3-1.7b", reduced: bool = True,
              **arch_overrides):
    """Next-token LM on one of the assigned large archs (``repro.configs``
    names: yi-34b, deepseek-67b, ...), default ``reduced()`` so the spec
    runs on a CPU container; drop ``reduced`` on real accelerators. This
    is the large-arch entry into the declarative API — the 2-D
    ``(clients, model)`` mesh example in ``examples/`` runs a reduced
    yi-34b through it."""
    import jax
    from repro.configs import get_config
    from repro.models.transformer import init_lm, lm_loss

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    # a dict overrides fields of a nested config (``moe``, ``mla``)
    arch_overrides = {
        k: dataclasses.replace(getattr(cfg, k), **v) if isinstance(v, dict)
        else v for k, v in arch_overrides.items()}
    if arch_overrides:
        cfg = dataclasses.replace(cfg, **arch_overrides)
    params, axes = init_lm(jax.random.PRNGKey(seed), cfg)
    loss_fn = lambda p, b: lm_loss(p, cfg, b["tokens"], b["labels"])
    # third element: the arch's named-axis tree, so fl.model_sharding=
    # "auto" can lay the transformer out over the mesh's model axis
    return params, loss_fn, axes


@register_dataset("mixture")
def _mixture_dataset(n: int = 2000, n_eval: int = 500, num_classes: int = 10,
                     seed: int = 0, noise: float = 0.35):
    """Gaussian-prototype 28x28 classification (MNIST/FMNIST stand-in)."""
    from repro.data.synthetic import mixture_classification
    x, y = mixture_classification(n + n_eval, num_classes, seed=seed,
                                  noise=noise)
    return ({"x": x[:n], "y": y[:n]}, {"x": x[n:], "y": y[n:]})


@register_dataset("markov")
def _markov_dataset(n: int = 256, n_eval: int = 64, seq_len: int = 32,
                    vocab: int = 512, seed: int = 0, branching: int = 4):
    """Markov-chain LM stream (the large-arch training driver's data):
    each token has ``branching`` likely successors — learnable structure
    for the ``"lm"`` model component. ``vocab`` must match the arch's
    (reduced archs clamp to 512)."""
    from repro.data.synthetic import markov_lm
    toks, labels = markov_lm(n + n_eval, seq_len, vocab, seed=seed,
                             branching=branching)
    return ({"tokens": toks[:n], "labels": labels[:n]},
            {"tokens": toks[n:], "labels": labels[n:]})


@register_partitioner("label_skew")
def _label_skew_partitioner(train, num_clients: int,
                            classes_per_client: int = 3, seed: int = 0):
    """Non-iid S1 split: each client sees only a few labels."""
    from repro.fed.partition import partition_label_skew
    return partition_label_skew(train["y"], num_clients,
                                classes_per_client, seed=seed)


@register_partitioner("iid")
def _iid_partitioner(train, num_clients: int, seed: int = 0):
    from repro.fed.partition import partition_iid
    n = len(next(iter(train.values())))
    return partition_iid(n, num_clients, seed=seed)
