"""Shared benchmark harness utilities.

Every figure benchmark describes its run as an
:class:`~repro.fed.experiment.ExperimentSpec` (via :func:`build_spec`) and
executes it with ``repro.fed.run_experiment`` / ``sweep`` — no hand-wired
``FLEngine`` construction.

:func:`emit` prints the human-readable ``name,us,derived`` CSV row AND
appends a structured entry to the ``BENCH_engine.json`` trajectory file
(name, us_per_round, metadata, git rev, timestamp), so perf numbers from
different revisions are diffable instead of living only in CI logs. Set
``BENCH_ENGINE_PATH`` to redirect the file (CI uploads it as an artifact).
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import tempfile
import time
import warnings
from typing import Any, Dict, Optional

#: trajectory file every benchmark appends to (one JSON array)
BENCH_PATH_ENV = "BENCH_ENGINE_PATH"
BENCH_PATH_DEFAULT = "BENCH_engine.json"


def build_spec(num_clients=20, tau=2, lr=0.05, batch_size=16, seed=0,
               noniid=True, n_data=2000, n_eval=500, name="benchmark",
               classes_per_client=3,
               model_kw: Optional[Dict[str, Any]] = None, **flkw):
    """Paper-style FL experiment spec: FCN classifier on synthetic mixture
    data, non-iid label-skew split by default.

    Extra **flkw go straight into FLConfig — e.g. scheduler="chunked",
    chunk_size=32 for the memory-bounded large-cohort path;
    fused_kernels=False pins the legacy dense aggregation path.
    ``model_kw`` passes arch overrides to the model component (e.g.
    {"d_model": 512} to scale the FCN width). ``classes_per_client``
    tunes the label-skew severity (1 = each client holds a single
    class's shard — the regime where losing a client cohort can lose
    whole classes, used by the async straggler benchmark).
    """
    from repro.fed import ComponentSpec, EvalPolicy, ExperimentSpec, FLConfig

    partition = (ComponentSpec("label_skew",
                               {"classes_per_client": classes_per_client,
                                "seed": seed})
                 if noniid else ComponentSpec("iid", {"seed": seed}))
    return ExperimentSpec(
        name=name,
        model=ComponentSpec("fcn", dict(model_kw or {})),
        data=ComponentSpec("mixture",
                           {"n": n_data, "n_eval": n_eval, "seed": seed}),
        partition=partition,
        fl=FLConfig(num_clients=num_clients, tau=tau, lr=lr,
                    batch_size=batch_size, seed=seed, **flkw),
        eval=EvalPolicy(every=0, final=True),
    )


def spec_metadata(spec) -> Dict[str, Any]:
    """The attribution keys every BENCH_engine.json row should carry:
    which execution path produced the number. ``mesh`` is the raw
    JSON-able FLConfig knob (None / int / [clients, model]);
    ``mesh_shape`` the resolved (clients, model) pair (None-spec resolves
    to every local device on the client axis, matching ``make_fl_mesh``);
    ``fused_kernels`` the raw tri-state knob; ``codec``/``codec_kw`` the
    wire codec. Rows
    from different PRs stay diffable because the path is in the row, not
    in the CI log."""
    import jax

    fl = spec.fl
    shape = fl.mesh_shape
    if shape is None and fl.scheduler == "sharded":
        shape = (len(jax.devices()), 1)
    return {
        "mesh": fl.mesh,
        "mesh_shape": list(shape) if shape is not None else None,
        "fused_kernels": fl.fused_kernels,
        "scheduler": fl.scheduler,
        "model_sharding": fl.model_sharding,
        "codec": fl.codec,
        "codec_kw": fl.codec_kw,
        "latency": fl.latency,
        "latency_kw": fl.latency_kw,
        "lbg_store": fl.resolved_lbg_variant if fl.use_lbgm else None,
        "tiers": fl.tiers,
    }


@functools.lru_cache(maxsize=1)
def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def bench_path() -> str:
    return os.environ.get(BENCH_PATH_ENV, BENCH_PATH_DEFAULT)


def record_bench(name: str, us_per_round: float,
                 metadata: Optional[Dict[str, Any]] = None) -> None:
    """Append one entry to the BENCH_engine.json trajectory array.

    The read-modify-write is crash-safe: the new array is written to a
    sibling temp file and moved into place with ``os.replace`` (atomic on
    POSIX), so a benchmark killed mid-write — or two processes racing —
    can no longer leave a truncated file that a later run would silently
    reset. An existing file that fails to parse is backed up next to the
    trajectory (``<path>.corrupt-<n>``) instead of being discarded: a
    perf trajectory spanning many revisions is exactly the artifact you
    don't want a one-off glitch to zero out.
    """
    path = bench_path()
    entries = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                entries = json.load(f)
            if not isinstance(entries, list):
                raise ValueError(
                    f"expected a JSON array, got {type(entries).__name__}")
        except (OSError, ValueError) as e:
            entries = []
            backup = _backup_corrupt(path)
            warnings.warn(
                f"unreadable bench trajectory {path!r} ({e}); "
                + (f"backed up to {backup!r} and " if backup else "")
                + "starting a fresh trajectory",
                RuntimeWarning, stacklevel=2)
    entries.append({
        "name": name,
        "us_per_round": float(us_per_round),
        "metadata": dict(metadata or {}),
        "git_rev": _git_rev(),
        "timestamp": time.time(),
    })
    directory = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".bench-", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(entries, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _backup_corrupt(path: str) -> Optional[str]:
    """Move an unparsable trajectory aside; returns the backup path
    (numbered so repeated failures don't clobber each other), or None if
    even the rename failed."""
    for n in range(1000):
        backup = f"{path}.corrupt-{n}"
        if not os.path.exists(backup):
            try:
                os.replace(path, backup)
                return backup
            except OSError:
                return None
    return None


def emit(name: str, us_per_call: float, derived: str,
         **metadata: Any) -> None:
    """CSV row to stdout + structured entry to BENCH_engine.json."""
    print(f"{name},{us_per_call:.0f},{derived}")
    record_bench(name, us_per_call, {"derived": derived, **metadata})
